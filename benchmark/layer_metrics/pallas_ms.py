"""Device time a step spends in Pallas kernels: the Mosaic custom calls on
the ``XLA Ops`` line of chip 0, summed over the window, per step."""

UNIT = "ms"
LAYER = "fused kernels"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import trace_reduce
    return None if trace is None else trace_reduce.mosaic_ms_per_step(trace)
