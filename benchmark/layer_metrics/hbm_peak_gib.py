"""Peak device memory on the fullest chip: ``peak_bytes_in_use`` (arrays
and program code) plus ``peak_bytes_reserved`` (the step program's
temporary space, which this runtime keeps apart from the arrays)."""

UNIT = "GiB"
LAYER = "compiler + device"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    return run_info["memory_peak_bytes"] / 2**30 or None
