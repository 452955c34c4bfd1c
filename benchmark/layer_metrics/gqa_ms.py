"""Device time a step spends in grouped-query attention layers: the ops
traced under a scope ``gqa/...`` (``models/lfm2.py``: the q/k/v projections
with the per-head q/k norms, the rotation, the attention kernels, the output
projection), forward, recomputed forward and backward, chip 0, per step of
the window. 0.0 where the model has no such layer."""

UNIT = "ms"
LAYER = "grouped-query attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/gqa/" in "/" + scope_reduce.user_scope(r) + "/")
