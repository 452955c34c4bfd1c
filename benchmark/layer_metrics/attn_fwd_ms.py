"""Device time a step spends in the attention forward kernel: the ops traced
under a scope ``apex_attn_fwd*`` (``ops/_dispatch.py`` names every kernel),
chip 0, per step of the window. 0.0 where the model has no attention."""

UNIT = "ms"
LAYER = "fused kernels"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: scope_reduce.kernel(r).startswith("apex_attn_fwd"))
