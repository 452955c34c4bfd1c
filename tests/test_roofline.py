"""Roofline observatory + perf sentinel tests.

The CI-shaped halves of `scripts/roofline_audit.py --cpu8`: the per-op
roofline join over the committed BERT-layer fixture (attribution
closure, bound classes, the known fused-backward gap), the AOT-only
analytic path, the noise-aware sentinel's direction/threshold/waiver
semantics over synthetic trajectories, schema negative twins for
``--kind roofline``, and the autotune-origin compile split in
`prof.compile_watch`.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import monitor, prof
from apex_tpu.prof import roofline, sentinel
from apex_tpu.prof.compile_watch import autotune_scope, in_autotune

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCHEMA_SCRIPT = os.path.join(_REPO_ROOT, "scripts",
                              "check_metrics_schema.py")
BERT_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                            "bert_layer.xplane.pb")


def _load_schema_mod():
    from importlib import util as _util
    spec = _util.spec_from_file_location("check_metrics_schema",
                                        _SCHEMA_SCRIPT)
    mod = _util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- the roofline join over the committed fixture ----------------------------

class TestBertFixtureJoin:
    """The committed BERT-layer fixture reproduces PERF.md's round-5
    ledger through the tool (regenerate with
    scripts/make_xplane_fixture.py --bert)."""

    @pytest.fixture()
    def report(self):
        tp = prof.parse_trace(BERT_FIXTURE)
        return roofline.roofline_report(profile=tp,
                                        device_kind="TPU v5 lite")

    def test_closure_over_module_device_time(self, report):
        ok, err = report.check_closure(tolerance=0.05)
        assert ok, f"attribution hole: {err:.4f} > 0.05"
        assert report.measured and len(report.rows) == 7

    def test_bound_classes_and_mxu_cap(self, report):
        by_name = {r.name: r for r in report.rows}
        for name in ("custom-call.201", "custom-call.202"):
            r = by_name[name]
            assert (r.family, r.bound, r.mxu_cap) == \
                ("attention", "compute", 0.5), r
        for name in ("fusion.210", "fusion.211", "fusion.230"):
            assert by_name[name].bound == "memory", by_name[name]
        for name in ("dot.220", "dot.221"):
            assert (by_name[name].family, by_name[name].bound) == \
                ("mlp", "compute"), by_name[name]
        for r in report.rows:
            assert r.efficiency is not None and 0.0 <= r.efficiency <= 1.0

    def test_worst_gaps_names_the_fused_backward_gap(self, report):
        """The PERF.md round-5 line — backward attention ~550 us vs its
        ~440 us d=64 MXU floor — reproduced by the tool."""
        gaps = report.worst_gaps(3)
        bwd = [g for g in gaps if g["op"] == "custom-call.202"]
        assert bwd, [g["op"] for g in gaps]
        top = bwd[0]
        assert 540.0 <= top["measured_us"] <= 560.0
        assert 420.0 <= top["attainable_us"] <= 450.0
        assert top["fingerprint"].startswith("attention|custom-call|")

    def test_fingerprints_stable_across_reruns(self, report):
        tp = prof.parse_trace(BERT_FIXTURE)
        rep2 = roofline.roofline_report(profile=tp,
                                        device_kind="TPU v5 lite")
        assert [r.fingerprint for r in report.rows] == \
            [r.fingerprint for r in rep2.rows]

    def test_events_pass_schema(self, report, tmp_path):
        path = tmp_path / "roofline.jsonl"
        logger = monitor.MetricsLogger(
            sinks=[], roofline_sink=monitor.JSONLSink(str(path)))
        logger.attach_roofline_report(report, step=5)
        logger.close()
        mod = _load_schema_mod()
        lines = path.read_text().splitlines()
        assert mod.check_roofline_lines(lines) == []
        assert all(json.loads(l)["kind"] == "roofline" for l in lines)


def test_aot_only_report_has_no_measurements():
    def step(x, w):
        return jnp.tanh(x @ w).sum()

    compiled = jax.jit(step).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 64), jnp.float32)).compile()
    rep = roofline.roofline_report(compiled=compiled,
                                   device_kind="TPU v5 lite")
    assert rep.rows and not rep.measured
    assert all(r.measured_us is None and r.efficiency is None
               and r.gap_us is None for r in rep.rows)
    assert rep.worst_gaps(5) == []
    # dot FLOPs land (in the dot row or folded into a calling fusion)
    assert sum(r.flops for r in rep.rows) == \
        pytest.approx(2 * 128 * 256 * 64, rel=0.01)


def test_unknown_device_classifies_unknown():
    """CPU/unknown chips have no peak table entry: bounds degrade to
    'unknown' rather than inventing an efficiency."""
    tp = None
    rep = roofline.roofline_report(
        compiled="ENTRY main {\n  %dot.1 = f32[8,8]{1,0} "
                 "dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b), "
                 "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n}",
        profile=tp, device_kind="weird-chip")
    assert rep.peak_flops == 0.0 and rep.hbm_bw == 0.0
    assert all(r.bound == "unknown" for r in rep.rows)


def test_classify_family_scope_then_structure():
    assert roofline.classify_family("bert/attn/flash_attention_fwd") \
        == "attention"
    assert roofline.classify_family("encoder/layer_norm/ln_bwd") \
        == "layer_norm"
    assert roofline.classify_family("", "all-reduce") == "collective"
    assert roofline.classify_family("", "dot") == "gemm"
    assert roofline.classify_family("", "convolution") == "conv"
    assert roofline.classify_family("nothing/here", "fusion") == "other"
    for fam in roofline.FAMILIES:
        assert isinstance(fam, str)


# --- the sentinel ------------------------------------------------------------

def _spec(**kw):
    defaults = dict(name="mfu", path=("extra", "mfu"),
                    direction="higher")
    defaults.update(kw)
    return sentinel.MetricSpec(**defaults)


class TestSentinelCheckRow:
    def test_direction_aware_gain_never_flags(self):
        hist = [0.30, 0.31, 0.30, 0.32]
        v = sentinel.check_row(hist, 0.45, _spec())
        assert not v.regressed and v.degradation < 0

    def test_drop_beyond_threshold_flags(self):
        hist = [0.30, 0.31, 0.30, 0.32]
        v = sentinel.check_row(hist, 0.20, _spec())
        assert v.regressed and v.baseline == pytest.approx(0.305)

    def test_noise_widens_the_threshold(self):
        """The same absolute drop passes on a noisy trajectory and
        fails on a quiet one — the MAD term at work."""
        quiet = [100.0, 100.5, 99.8, 100.2]
        noisy = [100.0, 80.0, 120.0, 95.0, 108.0]
        drop = 90.0
        assert sentinel.check_row(quiet, drop, _spec()).regressed
        assert not sentinel.check_row(noisy, drop, _spec()).regressed

    def test_lower_is_better_direction(self):
        spec = _spec(name="ms_per_step", direction="lower")
        hist = [46.0, 46.5, 45.8]
        assert sentinel.check_row(hist, 60.0, spec).regressed
        assert not sentinel.check_row(hist, 40.0, spec).regressed

    def test_counter_any_increase_fires(self):
        spec = _spec(name="lint_errors", direction="lower", counter=True)
        assert sentinel.check_row([0.0, 0.0], 1.0, spec).regressed
        assert not sentinel.check_row([0.0, 0.0], 0.0, spec).regressed

    def test_min_history_guard(self):
        v = sentinel.check_row([0.30], 0.01, _spec())
        assert not v.regressed and "insufficient history" in v.note


class TestSentinelTrajectory:
    def _rows(self, mfus):
        return [{"path": f"r{i}", "metrics": {"mfu": m}}
                for i, m in enumerate(mfus)]

    def test_clean_trajectory_quiet(self):
        rep = sentinel.check_trajectory(self._rows([0.30, 0.31, 0.32]))
        assert rep.ok and rep.subject == "r2"

    def test_regression_fires_and_waiver_suppresses(self):
        rows = self._rows([0.30, 0.31, 0.30, 0.18])
        rep = sentinel.check_trajectory(rows)
        assert [v.metric for v in rep.regressions] == ["mfu"]
        waived = sentinel.check_trajectory(
            rows, waivers={"regress|mfu": {"reason": "accepted",
                                           "allow_to": 0.18}})
        assert waived.ok and waived.verdicts[0].waived

    def test_waiver_allow_to_refires_past_the_floor(self):
        rows = self._rows([0.30, 0.31, 0.30, 0.10])
        rep = sentinel.check_trajectory(
            rows, waivers={"regress|mfu": {"reason": "accepted",
                                           "allow_to": 0.18}})
        assert not rep.ok, "degrading past allow_to must re-fire"

    def test_metricless_rows_noted_not_flagged(self):
        rows = self._rows([0.30, 0.31, 0.32])
        rows.insert(2, {"path": "failed", "metrics": {},
                        "note": "no parsed bench row (rc=1) — skipped"})
        rep = sentinel.check_trajectory(rows)
        assert rep.ok and any("skipped" in n for n in rep.notes)

    def test_replay_judges_every_prefix(self):
        reports = sentinel.replay_trajectory(
            self._rows([0.30, 0.31, 0.30, 0.32, 0.31]))
        assert len(reports) == 3 and all(r.ok for r in reports)

    def test_regress_events_pass_schema(self):
        rep = sentinel.check_trajectory(
            self._rows([0.30, 0.31, 0.30, 0.18]))
        mod = _load_schema_mod()
        lines = [json.dumps(e) for e in rep.to_events()]
        assert mod.check_roofline_lines(lines) == []


def test_extract_metrics_from_bench_row():
    row = {"value": 2755.0, "extra": {"batch": 128, "mfu": 0.343,
                                      "lint_errors": 0}}
    m = sentinel.extract_metrics(row)
    assert m["device_img_s"] == 2755.0
    assert m["ms_per_step"] == pytest.approx(128 / 2755.0 * 1e3)
    assert m["mfu"] == 0.343 and m["lint_errors"] == 0.0
    assert sentinel.extract_metrics(None) == {}


def test_save_and_load_baseline_roundtrip(tmp_path):
    path = str(tmp_path / "perf_baseline.json")
    rep = sentinel.check_trajectory(
        [{"path": f"r{i}", "metrics": {"mfu": m}}
         for i, m in enumerate([0.30, 0.31, 0.30, 0.18])])
    assert not rep.ok
    sentinel.save_baseline(path, rep, reason="tile sweep tradeoff")
    waivers = sentinel.load_baseline(path)
    assert waivers["regress|mfu"]["allow_to"] == 0.18
    # the written waiver suppresses the same regression
    rep2 = sentinel.check_trajectory(
        [{"path": f"r{i}", "metrics": {"mfu": m}}
         for i, m in enumerate([0.30, 0.31, 0.30, 0.18])],
        waivers=waivers)
    assert rep2.ok


def test_baseline_declared_metric_judged_and_preserved(tmp_path):
    """The committed perf-baseline's "metrics" section declares extra
    judged columns (the ddp_wire_bytes gate): parsed into MetricSpecs,
    extracted from rows, direction-aware flagged on regression, quiet
    on no-change — and --write-baseline refreshes must not drop the
    section."""
    path = str(tmp_path / "perf_baseline.json")
    with open(path, "w") as f:
        json.dump({"version": 1, "waivers": {}, "metrics": [
            {"name": "ddp_wire_bytes",
             "path": ["extra", "ddp_comm_modes", "modes", "hier_int8",
                      "wire_bytes"],
             "direction": "lower", "rel_floor": 0.02}]}, f)
    extra = sentinel.metric_specs_from_baseline(path)
    assert [s.name for s in extra] == ["ddp_wire_bytes"]
    specs = tuple(sentinel.METRICS) + tuple(extra)

    def row(w):
        return {"metrics": sentinel.extract_metrics(
            {"value": 100.0, "extra": {"batch": 8, "ddp_comm_modes": {
                "modes": {"hier_int8": {"wire_bytes": w}}}}}, specs)}

    base = [row(25_000_000), row(25_100_000), row(24_900_000)]
    rep = sentinel.check_trajectory(base + [row(99_000_000)],
                                    specs=specs)
    bad = [v for v in rep.verdicts if v.metric == "ddp_wire_bytes"]
    assert bad and bad[0].regressed
    rep_ok = sentinel.check_trajectory(base + [row(25_000_000)],
                                       specs=specs)
    ok = [v for v in rep_ok.verdicts if v.metric == "ddp_wire_bytes"]
    assert ok and not ok[0].regressed
    # write-baseline keeps the metrics section alongside new waivers
    sentinel.save_baseline(path, rep, reason="accepted")
    assert sentinel.metric_specs_from_baseline(path) == extra
    assert "regress|ddp_wire_bytes" in sentinel.load_baseline(path)
    # malformed entries are loud, not silently dropped
    with open(path, "w") as f:
        json.dump({"metrics": [{"name": "x", "direction": "lower"}]}, f)
    with pytest.raises(ValueError):
        sentinel.metric_specs_from_baseline(path)
    with pytest.raises(ValueError):
        sentinel.metric_specs_from_baseline(
            {"metrics": [{"name": "x", "path": ["v"],
                          "direction": "sideways"}]})


# --- schema negative twins ---------------------------------------------------

def test_roofline_schema_rejects_bad_streams():
    mod = _load_schema_mod()
    ok_roofline = {"kind": "roofline", "rank": 0, "step": None,
                   "op": "dot.1", "opcode": "dot", "family": "mlp",
                   "scope": "bert/mlp/fc1", "bound": "compute",
                   "flops": 1e9, "bytes": 1e6, "attainable_us": 100.0,
                   "measured_us": None, "efficiency": None,
                   "gap_us": None, "occurrences": 0, "dtype": "bf16",
                   "fingerprint": "mlp|dot|bert/mlp/fc1|bf16[8,8]"}
    ok_regress = {"kind": "regress", "rank": 0, "metric": "mfu",
                  "direction": "higher", "latest": 0.3,
                  "baseline": 0.31, "mad": 0.005, "threshold": 0.02,
                  "degradation": 0.01, "n_history": 3,
                  "regressed": False, "waived": False,
                  "fingerprint": "regress|mfu"}
    ok = [json.dumps(ok_roofline), json.dumps(ok_regress)]
    assert mod.check_roofline_lines(ok) == []
    # bad bound enum
    bad = dict(ok_roofline, bound="io")
    assert mod.check_roofline_lines([json.dumps(bad)])
    # efficiency out of [0, 1]
    bad = dict(ok_roofline, measured_us=50.0, efficiency=1.7)
    assert mod.check_roofline_lines([json.dumps(bad)])
    # null on a non-nullable key
    bad = dict(ok_roofline, attainable_us=None)
    assert mod.check_roofline_lines([json.dumps(bad)])
    # missing required key
    bad = dict(ok_roofline); bad.pop("fingerprint")
    assert mod.check_roofline_lines([json.dumps(bad)])
    # negative device time
    bad = dict(ok_roofline, measured_us=-3.0)
    assert mod.check_roofline_lines([json.dumps(bad)])
    # bad regress direction / non-bool regressed
    bad = dict(ok_regress, direction="sideways")
    assert mod.check_roofline_lines([json.dumps(bad)])
    bad = dict(ok_regress, regressed=1)
    assert mod.check_roofline_lines([json.dumps(bad)])
    # unknown kind / empty stream
    assert mod.check_roofline_lines([json.dumps({"kind": "metrics"})])
    assert mod.check_roofline_lines([])


def test_roofline_schema_cli_on_real_stream(tmp_path):
    """Subprocess leg: the exact CLI a deployment runs, over a stream
    the logger actually wrote (AOT report rows are the nullable-
    measured case)."""
    import subprocess

    def step(x):
        return (x @ x).sum()

    compiled = jax.jit(step).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    rep = roofline.roofline_report(compiled=compiled,
                                   device_kind="TPU v5 lite")
    path = tmp_path / "events.jsonl"
    logger = monitor.MetricsLogger(
        sinks=[], roofline_sink=monitor.JSONLSink(str(path)))
    logger.attach_roofline_report(rep)
    logger.close()
    r = subprocess.run([sys.executable, _SCHEMA_SCRIPT, "--kind",
                        "roofline", str(path)],
                       capture_output=True, text=True, cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr


def test_sentinel_cli_never_reports_clean_without_judging(tmp_path):
    """A gate that judged nothing must exit 2, not 'clean': unreadable
    inputs (a moved trajectory, a literally-passed glob) and
    metric-less trajectories are IO/usage errors."""
    import subprocess

    cli = os.path.join(_REPO_ROOT, "scripts", "perf_sentinel.py")

    def run(*args):
        return subprocess.run([sys.executable, cli, "--check", *args],
                              capture_output=True, text=True,
                              cwd=_REPO_ROOT)

    r = run(str(tmp_path / "nope_r01.json"))
    assert r.returncode == 2 and "unreadable" in r.stderr
    failed = tmp_path / "failed.json"
    failed.write_text(json.dumps({"n": 5, "rc": 1, "parsed": None}))
    r = run(str(failed))
    assert r.returncode == 2 and "no metric-bearing rows" in r.stderr
    # --write-baseline without --baseline is a usage error, not a
    # silently-dropped waiver
    good = tmp_path / "good.json"
    good.write_text(json.dumps(
        {"value": 100.0, "extra": {"batch": 8, "mfu": 0.3}}))
    r = run(str(good), "--write-baseline", "reason")
    assert r.returncode == 2 and "--baseline" in r.stderr
    # a corrupt committed waiver file is a config error (2), never an
    # "unwaived regression" (1)
    bad_baseline = tmp_path / "baseline.json"
    bad_baseline.write_text('{"waivers": {,}}')
    r = run(str(good), str(good), str(good),
            "--baseline", str(bad_baseline))
    assert r.returncode == 2 and str(bad_baseline) in r.stderr


def test_sentinel_cli_replay_jsonl_carries_every_prefix(tmp_path):
    """--replay exit 1 on a MID-trajectory regression must be backed by
    the emitted JSONL: the regressed verdicts of every prefix-report
    appear in the stream, not only the final row's."""
    import subprocess

    cli = os.path.join(_REPO_ROOT, "scripts", "perf_sentinel.py")
    files = []
    for i, m in enumerate([0.30, 0.31, 0.30, 0.18, 0.31, 0.30]):
        p = tmp_path / f"r{i:02d}.json"
        p.write_text(json.dumps({"extra": {"mfu": m}}))
        files.append(str(p))
    out = tmp_path / "out.jsonl"
    r = subprocess.run([sys.executable, cli, "--check", *files,
                        "--replay", "--jsonl", str(out)],
                       capture_output=True, text=True, cwd=_REPO_ROOT)
    assert r.returncode == 1, r.stdout + r.stderr
    events = [json.loads(l) for l in out.read_text().splitlines()]
    assert any(e["regressed"] for e in events), \
        "the r03 regression (recovered later) is missing from the JSONL"


# --- autotune-origin compile split -------------------------------------------

def test_autotune_scope_splits_compile_counters():
    from apex_tpu.prof import compile_watch

    compile_watch.install()
    base = prof.global_counters()

    def candidate(x):
        return jnp.sin(x).sum()

    assert not in_autotune()
    with autotune_scope():
        assert in_autotune()
        with autotune_scope():              # re-entrant
            assert in_autotune()
        jax.jit(candidate).lower(
            jax.ShapeDtypeStruct((17, 3), jnp.float32)).compile()
    assert not in_autotune()
    jax.jit(candidate).lower(
        jax.ShapeDtypeStruct((19, 5), jnp.float32)).compile()

    g = prof.global_counters()
    d_compiles = g["compiles"] - base["compiles"]
    d_autotune = g["autotune_compiles"] - base["autotune_compiles"]
    if not compile_watch.installed():
        pytest.skip("jax.monitoring hooks unavailable")
    assert d_compiles == 2, (base, g)
    assert d_autotune == 1, "exactly the in-scope compile tags autotune"
    assert g["autotune_secs"] >= base["autotune_secs"]


def test_function_watch_counts_autotune_subset():
    from apex_tpu.prof import compile_watch
    if not compile_watch.installed():
        pytest.skip("jax.monitoring hooks unavailable")
    watcher = prof.CompileWatcher()
    f = watcher.watch(lambda x: x * 2.0, name="f")
    with autotune_scope():
        f(jnp.ones((4,)))                   # first compile: autotune
    f(jnp.ones((8,)))                       # retrace, plain compile
    w = watcher.watches["f"]
    assert w.n_compiles == 2 and w.n_autotune_compiles == 1
    assert watcher.counters()["f"]["n_autotune_compiles"] == 1
    assert "autotune" in watcher.report()
