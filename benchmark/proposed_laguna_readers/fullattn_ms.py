"""Device time a step spends in the global attention layers of a model that
mixes them with window layers: the ops traced under a scope ``fullattn/...``
(``models/laguna.py``: projections, the YaRN rotation of half of each head,
the causal attention kernels, the head gates and the output projection),
forward, recomputed forward and backward, chip 0, per step of the window.
0.0 where the model has no such layer."""

UNIT = "ms"
LAYER = "global attention"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace,
        lambda r: "/fullattn/" in "/" + scope_reduce.user_scope(r) + "/")
