"""Device time a step spends in expert feed-forward layers: the ops traced
under a scope ``moe/...`` (``ops/moe.py`` and ``models/kimi_linear.py``:
router, dispatch, the held experts, combine, the shared expert, and the
dense turn of each expert that overflowed), chip 0, per step of the window. 0.0
where the model has no such layer."""

UNIT = "ms"
LAYER = "routed experts"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: "/moe/" in "/" + scope_reduce.user_scope(r) + "/")
