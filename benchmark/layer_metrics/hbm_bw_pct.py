"""HBM bytes a second over the window, as a share of the chip's published
bandwidth (``peaks.json``). The bytes are the compiler's count: of each
op's ``memory_access_breakdown`` the entries in memory space 1 (HBM; an
operand the layout marks ``S(1)`` sits on the chip and counts under space
3), times the op's runs in the window. XLA ops only: a Mosaic call has no
breakdown, so a cell that spends time in kernels reads low by their
traffic."""

UNIT = "%"
LAYER = "compiler + device"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    found = scope_reduce.windowed(trace)
    peak = scope_reduce.published_peak("hbm_bytes_per_s")
    if found is None or not peak:
        return None
    moved = sum((r.hbm_bytes or 0) * r.occurrences
                for r in found.profile.ops)
    return 100.0 * moved / found.seconds / peak
