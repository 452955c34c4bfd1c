"""CLI: parse a profiler logdir into device-time tables.

The command-line mirror of the reference's offline analyzers
(`python -m apex.pyprof.parse` over nvprof SQLite →
`apex/pyprof/parse/parse.py:1-30`, and the analyzed table of
`python -m apex.pyprof.prof` → `apex/pyprof/prof/prof.py:1-256`). Here
the artifact is a ``jax.profiler`` trace directory (written by
``apex_tpu.prof.trace`` or any jax trace capture, e.g.
``benchmark/.out/<cell>/trace`` after a ``--trace 1`` run) and the
analysis is per-HLO-op device timing, rolled up by the runtime's op
category, by the program's named scopes (``amp/fwd`` forward and backward,
``amp/update``, ``ddp/sync_gradients``), by kernel name and optimizer
phase (``apex_attn_fwd``, ``optim/lamb/norms``) and, for a masked-LM step,
by the head it took (``mlm/head_gathered``, ``mlm/head_full``).

Where the trace holds three or more runs of the step program, the tables
cover its whole steps — from the start of the second run to the end of the
last but one, the first and the last may be clipped by the profiler — and
read in ms per step; otherwise they cover the whole trace.

Where the logdir holds a ``timeline.json`` (``json.dump`` of
``prof.compile_watch.timeline()``, written by the traced process), the
last table names each import, trace, lower, compile or cache load that
lies inside the trace, with the device's idle time under it.

Usage::

    python -m apex_tpu.prof /tmp/trace            # top-30 op table + rollups
    python -m apex_tpu.prof /tmp/trace --top 100 --depth 3
    python -m apex_tpu.prof /tmp/trace --csv      # machine-readable
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _rollup(title, by, steps, total):
    unit = "ms/step" if steps else "ms"
    print(f"\n{title:<52} {unit:>10} {'%':>6}")
    for key, us in by.items():
        print(f"{key[:52]:<52} {us / 1e3 / (steps or 1):>10.3f} "
              f"{100 * us / total:>5.1f}%")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.prof",
        description="Per-op device-time analysis of a jax.profiler trace")
    p.add_argument("logdir", help="trace directory (contains *.xplane.pb)")
    p.add_argument("--top", type=int, default=30,
                   help="rows in the op table (default 30)")
    p.add_argument("--depth", type=int, default=2,
                   help="scope path components in the by-scope table "
                        "(default 2)")
    p.add_argument("--csv", action="store_true",
                   help="emit name,category,count,total_us,scope rows")
    args = p.parse_args(argv)

    from apex_tpu.prof.xplane import parse_trace

    tp = parse_trace(args.logdir)
    if not tp.ops:
        print("no device ops found in trace (CPU-only run, or no "
              "*.xplane.pb under the logdir)", file=sys.stderr)
        return 1
    runs, steps = tp.step_runs, 0
    if len(runs) >= 3:
        tp, steps = tp.window(runs[1][0], runs[-2][1]), len(runs) - 2
    if args.csv:
        print("name,category,occurrences,total_us,scope")
        for r in tp.ops:
            print(f"{r.name},{r.category},{r.occurrences},"
                  f"{r.total_us:.1f},{r.scope}")
        return 0
    total = tp.total_us or 1.0
    if steps:
        print(f"{tp.device}: {steps} whole steps of {len(runs)} traced "
              f"runs, {tp.module_total_us / steps / 1e3:.3f} ms a step, "
              f"{total / steps / 1e3:.3f} ms of it busy")
    print(tp.table(top=args.top))
    _rollup("category", tp.by_category(), steps, total)
    _rollup(f"scope (depth {args.depth})",
            tp.by_scope(depth=args.depth, phases=True), steps, total)
    own = tp.by_own_scope()
    if own:
        _rollup("kernel / optimizer phase", own, steps, total)
    head = tp.by_head()
    if head:
        _rollup("MLM head, by the branch the steps took", head, steps, total)
    timeline = os.path.join(args.logdir, "timeline.json")
    if os.path.isfile(timeline):
        with open(timeline) as f:
            found = tp.spans_over_idle(json.load(f))
        print(f"\n{'set-up span inside the trace':<52} {'ms':>10} "
              f"{'idle ms':>8}")
        for span, a, b, idle_us in found:
            what = f"{span['name']} {span['program'] or ''}"
            if span.get("cache"):
                what += f" (cache {span['cache']})"
            print(f"{what[:52]:<52} {(b - a) / 1e6:>10.3f} "
                  f"{idle_us / 1e3:>8.3f}")
        if not found:
            print("none: nothing was imported, traced, lowered, compiled "
                  "or loaded from the cache inside it")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
