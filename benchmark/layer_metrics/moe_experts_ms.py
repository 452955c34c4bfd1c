"""Device time a step spends on the held experts themselves (``ops/moe.py``,
scopes ``moe/dispatch``, ``moe/experts``, ``moe/combine`` and
``moe/overflow``: the rows gathered into blocks of each expert's capacity,
the batched matmuls over the padded blocks, the weighted scatter back, and
the dense turn of each expert that overflowed), without the router and the
shared expert, chip 0, per step of the window. 0.0 where the model has no
expert layer."""

UNIT = "ms"
LAYER = "routed experts"
MOVES = "samples_per_s_per_chip"
PARTS = ("/moe/dispatch/", "/moe/experts/", "/moe/combine/", "/moe/overflow/")


def read(trace, run_info):
    import scope_reduce

    def held(r):
        scope = "/" + scope_reduce.user_scope(r) + "/"
        return any(part in scope for part in PARTS)
    return scope_reduce.ms_per_step(trace, held)
