"""Device time a step spends in the forward pass: the ops whose scope sits
under a ``jvp(`` and no ``transpose(`` (``jit(step)/jvp(amp/fwd)/...``),
chip 0, per step of the window."""

UNIT = "ms"
LAYER = "model step"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(trace, lambda r: r.phase == "fwd")
