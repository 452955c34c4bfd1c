"""Device time a step spends scoring and choosing experts (``ops/moe.py``,
scope ``moe/route``: the router's float32 matmul, the scores over every
expert, the top-k and the weights), forward, recomputed forward and
backward, chip 0, per step of the window. 0.0 where the model has no expert
layer."""

UNIT = "ms"
LAYER = "routed experts"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/moe/route/" in "/" + scope_reduce.user_scope(r) + "/")
