"""Device time a step spends in gated grouped-query attention layers: the ops
traced under a scope ``gattn/...`` (``models/qwen3_next.py``: projections and
q/k norms, the partial rotary, the three attention kernels with the repeat of
the k/v heads, the output gate and projection), forward, recomputed forward
and backward, chip 0, per step of the window. 0.0 where the model has no
such layer."""

UNIT = "ms"
LAYER = "gated grouped-query attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/gattn/" in "/" + scope_reduce.user_scope(r) + "/")
