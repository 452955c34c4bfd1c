"""Device time a step spends in the fusions the runtime calls ``convolution
fusion`` (``hlo_category``): a matrix multiply or convolution with its
epilogue, chip 0, per step of the window."""

UNIT = "ms"
LAYER = "compiler + device"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(
        trace, lambda r: r.hlo_category == "convolution fusion")
