"""Share of the busy device time of the window that no scope of the program
names: ops without a ``tf_op`` (``copy-done``, ``slice-done``: the
compiler's own) or with only ``jit(..)`` wrappers in it. What the by-scope
tables of ``python -m apex_tpu.prof`` cannot attribute."""

UNIT = "%"
LAYER = "observability, safety"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    found = scope_reduce.windowed(trace)
    if found is None or not found.profile.total_us:
        return None
    profile = found.profile
    bare = sum(r.total_us for r in profile.ops
               if not scope_reduce.user_scope(r))
    return 100.0 * bare / profile.total_us
