"""Per-op profile of the DCGAN multi-loss bench step (VERDICT r4
item 8; PERF.md round-5 DCGAN section).

Traces the bench's own run — which includes compile, cost analysis,
init, and warmup dispatches — so ABSOLUTE totals span more dispatches
than the timed loop. Everything printed is therefore normalized per
scanned step: the per-op ``avg_us`` column is per occurrence (one per
scanned step for loop-body ops), and category totals divide by the
max op-occurrence count (the number of scanned steps actually traced,
derived from the trace itself).

Usage: python scripts/prof_dcgan.py [--batch N] [--top N]
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    batch, top = 128, 20
    argv = sys.argv
    if "--batch" in argv:
        batch = int(argv[argv.index("--batch") + 1])
    if "--top" in argv:
        top = int(argv[argv.index("--top") + 1])

    import bench as B
    from apex_tpu import prof
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()
    peak = prof.device_peak_flops()     # unknown device: raises
    logdir = tempfile.mkdtemp(prefix="apex_tpu_prof_dcgan_")
    with prof.trace(logdir):
        img_s, dt, flops_s = B._bench_dcgan(batch, iters=3)
    print(f"batch={batch} img/s={img_s:.0f} ms/step={dt * 1e3:.3f} "
          f"MFU={flops_s / peak:.3f}")

    from apex_tpu.prof import xplane
    p = xplane.parse_trace(logdir)
    cats = p.by_category()
    tot = sum(cats.values())
    # steps executed = the max op occurrence count: a loop-body op runs
    # once per scanned step, so this needs no knowledge of the bench's
    # scan length and is immune to init/warmup dispatches in the trace
    steps = max((o.occurrences for o in p.ops), default=1)
    print(f"~{steps} scanned steps traced; per-step category times "
          f"(init-dispatch time included in totals/percentages):")
    for k, v in list(cats.items())[:8]:
        print(f"  {k:20s} {v / steps:9.1f} us/step  "
              f"{100 * v / tot:5.1f}%")
    print(p.table(top=top))


if __name__ == "__main__":
    main()
