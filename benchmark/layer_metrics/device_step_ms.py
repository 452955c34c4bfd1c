"""Median device time of one run of the compiled step program
(``XLA Modules`` line of chip 0)."""

UNIT = "ms"
LAYER = "model step"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import trace_reduce
    return None if trace is None else trace_reduce.device_step_ms(trace)
