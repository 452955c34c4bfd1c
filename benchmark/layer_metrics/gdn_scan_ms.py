"""Device time a step spends in the gated-delta-rule scan of the DeltaNet
layers alone (scope ``gdn/scan`` of ``models/qwen3_next.py`` round
``ops.gated_delta_rule``: the two kernels, the broadcast of the scalar decay,
the repeat of the shared key heads and the reshapes round them), forward and
backward, chip 0, per step of the window. 0.0 where the model has no such
layer."""

UNIT = "ms"
LAYER = "delta-rule attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/gdn/scan/" in "/" + scope_reduce.user_scope(r) + "/")
