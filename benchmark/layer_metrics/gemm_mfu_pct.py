"""FLOP/s of the ``convolution fusion`` ops while they run, as a share of the
chip's published bf16 peak: the compiler's own ``flops`` of each op times its
runs in the window, over their device time. It is the MXU's rate inside the
GEMMs, not the step's (``device_mfu_pct``)."""

UNIT = "%"
LAYER = "compiler + device"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    found = scope_reduce.windowed(trace)
    if found is None or not run_info["peak_flops"]:
        return None
    gemms = [r for r in found.profile.ops
             if r.hlo_category == "convolution fusion"]
    seconds = sum(r.total_us for r in gemms) / 1e6
    if not seconds:
        return 0.0
    flops = sum((r.flops or 0) * r.occurrences for r in gemms)
    return 100.0 * flops / seconds / run_info["peak_flops"]
