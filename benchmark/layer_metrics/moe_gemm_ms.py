"""Device time a step spends in the held experts' matmuls (``ops/moe.py``,
scope ``moe/experts`` alone: the three batched matmuls over the blocks of
each expert's padded capacity), forward, recomputed forward and backward,
chip 0, per step of the window: what a ragged matmul runs on the routed rows
only. 0.0 where the model has no expert layer."""

UNIT = "ms"
LAYER = "routed experts"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace,
        lambda r: "/moe/experts/" in "/" + scope_reduce.user_scope(r) + "/")
