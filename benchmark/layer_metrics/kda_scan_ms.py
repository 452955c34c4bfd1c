"""Device time a step spends in the chunked gated-delta-rule scan alone
(``ops/delta_rule.py``, scope ``kda/scan``: the part a fused kernel would
replace), forward, recomputed forward and backward, chip 0, per step of the
window. 0.0 where the model has no such layer."""

UNIT = "ms"
LAYER = "delta-rule attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/kda/scan/" in "/" + scope_reduce.user_scope(r) + "/")
