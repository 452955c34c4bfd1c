#!/usr/bin/env python
"""perf_sentinel — the noise-aware perf-regression gate over bench JSON
trajectories (apex_tpu.prof.sentinel as a CLI; pure stdlib — CI and
log-shipping hosts run it without jax).

    python scripts/perf_sentinel.py --check row01.json ... row05.json
    python scripts/perf_sentinel.py --check rows/*.json --replay
    python scripts/perf_sentinel.py --check ... --write-baseline "reason"

Judges the NEWEST metric-bearing row against robust median/MAD
baselines built from the earlier rows, direction-aware (only the
degradation direction fires; see apex_tpu/prof/sentinel.py for the
metric table and thresholds). ``--replay`` backtests every row against
its prefix. Rows without metrics (failed bench runs commit
``"parsed": null``) are skipped with a note.

Waivers: ``--baseline scripts/perf_baseline.json`` (committed; starts
empty) suppresses fingerprinted, explicitly-accepted regressions;
``--write-baseline REASON`` records the current regressions there with
``allow_to`` floors so further degradation re-fires. ``--jsonl`` streams
one ``kind="regress"`` event per verdict
(``check_metrics_schema.py --kind roofline`` validates).

Exit status: 0 clean (or waived), 1 unwaived regression, 2 usage/IO.
The repository commits no trajectory (PR 21); ``tests/test_roofline.py``
pins the gate — seeded-regression positive, no-change negative twin — on
trajectories it builds in a temp directory.
"""

import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_sentinel():
    """Load apex_tpu.prof.sentinel WITHOUT importing the package (the
    package __init__ pulls jax; the sentinel itself is pure stdlib, so
    CI/log hosts can run this gate without an ML stack)."""
    path = os.path.join(_REPO, "apex_tpu", "prof", "sentinel.py")
    spec = importlib.util.spec_from_file_location(
        "apex_tpu_prof_sentinel", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses resolve through here
    spec.loader.exec_module(mod)
    return mod


sentinel = _load_sentinel()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    files, baseline_path, jsonl, json_out = [], None, None, None
    replay = False
    write_reason = None
    it = iter(argv)
    for a in it:
        if a in ("-h", "--help"):
            print(__doc__)
            return 2
        elif a == "--check":
            pass                        # files follow positionally
        elif a == "--baseline":
            baseline_path = next(it, None)
        elif a == "--write-baseline":
            write_reason = next(it, "accepted regression")
        elif a == "--jsonl":
            jsonl = next(it, None)
        elif a == "--json":
            json_out = next(it, None)
        elif a == "--replay":
            replay = True
        elif a.startswith("-"):
            print(f"unknown flag {a!r}\n{__doc__}", file=sys.stderr)
            return 2
        else:
            files.append(a)
    if not files:
        print(__doc__)
        return 2

    if write_reason is not None and not baseline_path:
        print("--write-baseline needs --baseline PATH (the committed "
              "waiver file, e.g. scripts/perf_baseline.json)",
              file=sys.stderr)
        return 2

    try:
        waivers = sentinel.load_baseline(baseline_path) if baseline_path \
            else {}
        # the baseline may also DECLARE extra judged metrics (the
        # "metrics" section — e.g. ddp_wire_bytes over the hierarchical
        # sync row), direction-aware and waiverable like the built-ins
        extra = (sentinel.metric_specs_from_baseline(baseline_path)
                 if baseline_path else [])
    except ValueError as e:
        # a corrupt committed waiver file is a config error (exit 2),
        # not an "unwaived regression" (exit 1)
        print(f"perf_sentinel: {baseline_path}: {e}", file=sys.stderr)
        return 2
    specs = tuple(sentinel.METRICS) + tuple(extra)
    rows = sentinel.load_rows(files, specs=specs)

    # a gate that judged NOTHING must not report clean: unreadable
    # inputs (a moved trajectory, an unexpanded glob passed literally)
    # or a trajectory with zero metric-bearing rows is an IO/usage
    # error, not a pass. Failed-bench rows ("parsed": null) are still
    # tolerated — they are readable and skipped with a note.
    unreadable = [r for r in rows if r["note"]
                  and r["note"].startswith("unreadable")]
    if unreadable:
        for r in unreadable:
            print(f"perf_sentinel: {r['path']}: {r['note']}",
                  file=sys.stderr)
        return 2
    if not any(r["metrics"] for r in rows):
        print("perf_sentinel: no metric-bearing rows in "
              f"{len(rows)} input file(s) — nothing judged",
              file=sys.stderr)
        return 2

    if replay:
        reports = sentinel.replay_trajectory(rows, waivers=waivers,
                                             specs=specs)
        bad = [r for r in reports if not r.ok]
        for rep in reports:
            tag = "ok" if rep.ok else "REGRESSED"
            print(f"-- {rep.subject}: {tag}")
            if not rep.ok:
                print(rep.table())
        if not reports:
            reports = [sentinel.SentinelReport(
                verdicts=[], subject=None, notes=["nothing judgeable"])]
        report = reports[-1]
        # the emitted streams carry EVERY prefix-report's verdicts — a
        # mid-trajectory regression must appear in the JSONL that the
        # exit code judges, not only in the final row's verdicts
        events = [ev for rep in reports for ev in rep.to_events()]
    else:
        report = sentinel.check_trajectory(rows, waivers=waivers,
                                           specs=specs)
        bad = [] if report.ok else [report]
        print(f"-- judging {report.subject} against "
              f"{sum(1 for r in rows if r['metrics']) - 1} prior rows")
        print(report.table())
        events = report.to_events()

    if jsonl:
        with open(jsonl, "w") as f:
            for ev in events:
                f.write(json.dumps(ev) + "\n")
    if json_out:
        with open(json_out, "w") as f:
            json.dump({"ok": not bad,
                       "n_regressions": sum(len(r.regressions)
                                            for r in bad),
                       "verdicts": events}, f, indent=1)
    if write_reason is not None and baseline_path:
        sentinel.save_baseline(baseline_path, report,
                               reason=write_reason)
        print(f"wrote waivers to {baseline_path}")
        return 0

    if bad:
        n = sum(len(r.regressions) for r in bad)
        print(f"perf_sentinel: {n} unwaived regression(s)",
              file=sys.stderr)
        return 1
    print("perf_sentinel: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
