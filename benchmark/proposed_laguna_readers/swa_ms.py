"""Device time a step spends in sliding-window attention layers: the ops
traced under a scope ``swa/...`` (``models/laguna.py``: the q/k/v
projections, the rotation, the attention kernels with the repeat of the k/v
heads, the head gates and the output projection), forward, recomputed
forward and backward, chip 0, per step of the window. 0.0 where the model
has no such layer."""

UNIT = "ms"
LAYER = "sliding-window attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/swa/" in "/" + scope_reduce.user_scope(r) + "/")
