"""Device time a step spends moving rows into and out of the held experts'
blocks (``ops/moe.py``, scopes ``moe/dispatch`` and ``moe/combine``: the
slots, the gather of each expert's rows into its padded block, the weighted
scatter-add back, and their backward), chip 0, per step of the window: what
a ragged matmul removes. 0.0 where the model has no expert layer."""

UNIT = "ms"
LAYER = "routed experts"
MOVES = "samples_per_s_per_chip"
PARTS = ("/moe/dispatch/", "/moe/combine/")


def read(trace, run_info):
    import scope_reduce

    def moved(r):
        scope = "/" + scope_reduce.user_scope(r) + "/"
        return any(part in scope for part in PARTS)
    return scope_reduce.ms_per_step(trace, moved)
