"""Device time a step spends in gated-DeltaNet layers: the ops traced under a
scope ``gdn/...`` (``models/qwen3_next.py``: the fused projections, the short
convolution, decay and gates, the chunked scan, output norm, gate and
projection), forward, recomputed forward and backward, chip 0, per step of
the window. 0.0 where the model has no such layer."""

UNIT = "ms"
LAYER = "delta-rule attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/gdn/" in "/" + scope_reduce.user_scope(r) + "/")
