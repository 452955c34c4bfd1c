"""The longest stretch of the traced window in which no op ran on chip 0:
what the host loop (dispatch, wait, drain) costs the device at worst."""

UNIT = "ms"
LAYER = "entry point"
MOVES = "step_ms_p90"


def read(trace, run_info):
    import trace_reduce
    if trace is None:
        return None
    found = trace_reduce.idle_gaps(trace)
    if not found:
        # no gap at all in a traced window is a reading; no window is none
        return 0.0 if trace_reduce.window(trace) else None
    return found[0][1] * 1e3
