"""Share of the traced window in which no op ran on chip 0."""

UNIT = "%"
LAYER = "compiler + device"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import trace_reduce
    window = trace_reduce.window(trace) if trace else None
    if window is None:
        return None
    lo, hi, _ = window
    return 100.0 * (1.0 - trace_reduce.busy_ns(trace) / (hi - lo))
