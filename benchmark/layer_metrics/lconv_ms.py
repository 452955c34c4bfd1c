"""Device time a step spends in gated short-convolution mixers: the ops
traced under a scope ``lconv/...`` (``models/lfm2.py``: the in-projection's
GEMM, the gated convolution, the out-projection), forward, recomputed
forward and backward, chip 0, per step of the window. 0.0 where the model
has no such layer."""

UNIT = "ms"
LAYER = "gated short convolution"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/lconv/" in "/" + scope_reduce.user_scope(r) + "/")
