"""Device time a step spends in the backward pass: the ops whose scope sits
under a ``transpose(`` (``jit(step)/transpose(jvp(amp/fwd))/...``), chip 0,
per step of the window. A fusion carries one op's scope, so an optimizer
update that XLA fuses into a weight-gradient fusion counts here."""

UNIT = "ms"
LAYER = "model step"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(trace, lambda r: r.phase == "bwd")
