"""Device time a step spends in delta-rule attention layers: the ops traced
under a scope ``kda/...`` (``models/kimi_linear.py``: projections, short
convolution, decay and gates, the chunked scan, output norm and projection),
forward, recomputed forward and backward, chip 0, per step of the window.
0.0 where the model has no such layer."""

UNIT = "ms"
LAYER = "delta-rule attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/kda/" in "/" + scope_reduce.user_scope(r) + "/")
