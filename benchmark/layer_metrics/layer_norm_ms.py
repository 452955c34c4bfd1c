"""Device time a step spends in the LayerNorm kernels, forward and backward:
the ops traced under a scope ``apex_layer_norm_*``, chip 0, per step of the
window."""

UNIT = "ms"
LAYER = "fused kernels"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: scope_reduce.kernel(r).startswith("apex_layer_norm_"))
