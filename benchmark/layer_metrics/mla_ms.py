"""Device time a step spends in latent-attention layers: the ops traced
under a scope ``mla/...`` (``models/kimi_linear.py``: projections, the causal
attention kernels at a key size of 192, output projection), chip 0, per step
of the window. 0.0 where the model has no such layer."""

UNIT = "ms"
LAYER = "latent attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/mla/" in "/" + scope_reduce.user_scope(r) + "/")
