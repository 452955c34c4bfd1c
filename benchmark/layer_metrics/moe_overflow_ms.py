"""Device time a step spends on the expert layer's overflow loop, which runs
each held expert whose rows pass its capacity over every row (``ops/moe.py``,
scope ``moe/overflow``), chip 0, per step of the window. 0.0 in a healthy
run: the compacted blocks held every expert's rows in every step."""

UNIT = "ms"
LAYER = "routed experts"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/moe/overflow/" in "/" + scope_reduce.user_scope(r) + "/")
