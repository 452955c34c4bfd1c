"""Kernel autotuner — tuning-DB semantics + dispatch-seam contracts.

The ISSUE-18 claims, CPU/interpret-testable:

- **fingerprint stability**: the ``family|dims|dtype|chip`` key is
  derived from the dtype *object*'s canonical name and python ints —
  every spelling of the same logical shape (np dtype, jnp dtype,
  string, weak type) produces the identical key across jax versions;
- **exact-key only**: a nearest miss (one row off, other dtype) never
  matches — consultation is a dict lookup, not a similarity search;
- **stale refusal**: an entry whose recorded identity no longer
  re-fingerprints to its key raises ``StaleTuningEntry`` at load;
- **off-mode bitwise**: ``APEX_TPU_AUTOTUNE=off`` produces outputs
  bitwise-identical to the DB-miss path (the pre-tuner trajectory);
- **tuned-vs-default bitwise per family** (interpret mode): row-block
  and block_q retilings change the schedule, never the math — the
  block-invariant representative of each family matches bitwise;
- **satellite-2 refusal**: a tuned/explicit optimizer block that does
  not divide the BUFFER_MULTIPLE-padded arena buffer warns naming the
  offending fingerprint + the fallback taken, and still computes the
  default-block result;
- **APX104 negative twin**: a DB-satisfied shape signature stays at
  info severity (no escalation), with the fix-it naming the DB.
"""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import lint, ops, prof
from apex_tpu.ops import autotune
from apex_tpu.ops import _dispatch


@pytest.fixture(autouse=True)
def _reset_autotune_state():
    autotune.reset_counters()
    yield
    autotune.set_db(None)
    autotune.reset_counters()


def _entry(family, dims, block, dtype="float32", **kw):
    return autotune.TuningEntry(family=family, dims=tuple(dims),
                                dtype=dtype, chip=autotune.chip_kind(),
                                block=dict(block), **kw)


def _db(*entries):
    return autotune.TuningDB({e.fingerprint: e for e in entries})


# --- fingerprint semantics ---------------------------------------------------

class TestFingerprint:
    def test_stable_across_dtype_spellings(self):
        want = autotune.fingerprint("layer_norm", (48, 96),
                                    np.float32, chip="cpu")
        for spelling in (jnp.float32, np.dtype("float32"), "float32",
                         np.float32, jnp.zeros((1,), jnp.float32).dtype):
            assert autotune.fingerprint(
                "layer_norm", (48, 96), spelling, chip="cpu") == want
        assert want == "layer_norm|48x96|float32|cpu"

    def test_bfloat16_and_int_dims(self):
        fp = autotune.fingerprint("xentropy", (np.int64(8), 30522),
                                  jnp.bfloat16, chip="cpu")
        assert fp == "xentropy|8x30522|bfloat16|cpu"

    def test_unknown_family_refused(self):
        with pytest.raises(ValueError, match="unknown kernel family"):
            autotune.fingerprint("conv", (8, 8), jnp.float32)

    def test_chip_key_is_cpu_off_tpu(self):
        assert autotune.chip_kind() == "cpu"


# --- DB load/save/lookup -----------------------------------------------------

class TestTuningDB:
    def test_roundtrip_and_exact_key_hit(self, tmp_path):
        e = _entry("layer_norm", (256, 192), {"block_rows": 64})
        db = _db(e)
        path = str(tmp_path / "db.json")
        db.save(path)
        db2 = autotune.TuningDB.load(path)
        assert db2.lookup(e.fingerprint).block == {"block_rows": 64}
        with autotune.use_db(db2):
            assert autotune.lookup_blocks(
                "layer_norm", (256, 192), jnp.float32) == \
                {"block_rows": 64}
            assert autotune.counters()["hits"] == 1

    def test_nearest_miss_does_not_match(self):
        e = _entry("layer_norm", (256, 192), {"block_rows": 64})
        with autotune.use_db(_db(e)):
            for dims, dtype in (((257, 192), jnp.float32),
                                ((256, 191), jnp.float32),
                                ((256, 192), jnp.bfloat16)):
                assert autotune.lookup_blocks(
                    "layer_norm", dims, dtype) is None
            assert autotune.lookup_blocks(
                "xentropy", (256, 192), jnp.float32) is None
        assert autotune.counters()["hits"] == 0

    def test_stale_entry_refused_loudly(self, tmp_path):
        e = _entry("mlp", (128, 96, 64), {"block_rows": 32})
        path = str(tmp_path / "db.json")
        _db(e).save(path)
        raw = json.load(open(path))
        raw["entries"][e.fingerprint]["dims"] = [128, 96, 65]
        json.dump(raw, open(path, "w"))
        with pytest.raises(autotune.StaleTuningEntry) as exc:
            autotune.TuningDB.load(path)
        assert e.fingerprint in str(exc.value)
        assert "kernel_tune" in str(exc.value)

    def test_malformed_entry_refused(self, tmp_path):
        path = str(tmp_path / "db.json")
        json.dump({"version": 1, "entries": {"k": {"family": "mlp"}}},
                  open(path, "w"))
        with pytest.raises(autotune.StaleTuningEntry):
            autotune.TuningDB.load(path)

    def test_missing_file_is_empty_db(self, tmp_path):
        db = autotune.TuningDB.load(str(tmp_path / "absent.json"))
        assert len(db) == 0

    def test_committed_db_loads_with_all_families(self):
        db = autotune.TuningDB.load(autotune.default_db_path())
        assert set(autotune.FAMILIES) <= set(db.families())
        for e in db.entries.values():
            assert e.sweep.get("n_candidates", 0) >= 2
            assert e.sweep.get("best_us", 0) > 0

    def test_off_mode_skips_consult(self, monkeypatch):
        e = _entry("layer_norm", (256, 192), {"block_rows": 64})
        monkeypatch.setenv("APEX_TPU_AUTOTUNE", "off")
        with autotune.use_db(_db(e)):
            assert autotune.lookup_blocks(
                "layer_norm", (256, 192), jnp.float32) is None
        assert autotune.counters() == {"hits": 0, "misses": 0}

    def test_bad_mode_refused(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_AUTOTUNE", "fast")
        with pytest.raises(ValueError, match="refusing to guess"):
            autotune.mode()

    def test_illegal_tuned_value_warns_and_falls_back(self):
        e = _entry("layer_norm", (256, 192), {"block_rows": 40})
        with autotune.use_db(_db(e)):
            with pytest.warns(RuntimeWarning,
                              match="layer_norm|256x192"):
                got = autotune.tuned_rows("layer_norm", (256, 192),
                                          jnp.float32)
        assert got is None


# --- off-mode bitwise + tuned-vs-default bitwise per family ------------------

class TestBitwiseNumerics:
    def test_off_trajectory_bitwise_identical_to_miss(self, monkeypatch):
        x = jnp.asarray(np.random.RandomState(0).randn(48, 96),
                        jnp.float32)
        w = jnp.ones((96,), jnp.float32)
        b = jnp.zeros((96,), jnp.float32)
        monkeypatch.setenv("APEX_TPU_AUTOTUNE", "off")
        y_off = np.asarray(ops.fused_layer_norm_affine(x, w, b))
        monkeypatch.setenv("APEX_TPU_AUTOTUNE", "db")
        y_db = np.asarray(ops.fused_layer_norm_affine(x, w, b))
        np.testing.assert_array_equal(y_off, y_db)

    def test_layer_norm_tuned_vs_default_bitwise(self):
        from apex_tpu.ops import layer_norm as ln
        x = jnp.asarray(np.random.RandomState(1).randn(96, 80),
                        jnp.float32)
        w = jnp.asarray(np.random.RandomState(2).rand(80), jnp.float32)
        b = jnp.asarray(np.random.RandomState(3).rand(80), jnp.float32)
        default = np.asarray(ln._ln_forward(x, w, b, 1e-5))
        for r in (16, 32, 96):
            tuned = np.asarray(ln._ln_forward(x, w, b, 1e-5,
                                              block_rows=r))
            np.testing.assert_array_equal(default, tuned)

    def test_xentropy_tuned_vs_default_bitwise(self):
        from apex_tpu.ops import xentropy as xe
        x = jnp.asarray(np.random.RandomState(4).randn(64, 300),
                        jnp.float32)
        lab = jnp.asarray(np.random.RandomState(5).randint(0, 300, 64),
                          jnp.int32)
        loss_d, lse_d = xe._fwd_call(x, lab, 0.1)
        for r in (16, 32, 64):
            loss_t, lse_t = xe._fwd_call(x, lab, 0.1, block_rows=r)
            np.testing.assert_array_equal(np.asarray(loss_d),
                                          np.asarray(loss_t))
            np.testing.assert_array_equal(np.asarray(lse_d),
                                          np.asarray(lse_t))

    def test_mlp_tuned_vs_default_bitwise(self):
        from apex_tpu.ops import mlp as mlp_mod
        rng = np.random.RandomState(6)
        x = jnp.asarray(rng.randn(64, 48), jnp.float32)
        ws = (jnp.asarray(rng.randn(48, 64) * 0.1, jnp.float32),
              jnp.asarray(rng.randn(64, 32) * 0.1, jnp.float32))
        bs = (jnp.zeros((64,), jnp.float32),
              jnp.zeros((32,), jnp.float32))
        default = np.asarray(mlp_mod._fused_mlp_fwd_impl(
            x, ws, bs, "relu"))
        for r in (16, 32, 64):
            tuned = np.asarray(mlp_mod._fused_mlp_fwd_impl(
                x, ws, bs, "relu", block_rows=r))
            np.testing.assert_array_equal(default, tuned)

    def test_optimizer_tuned_vs_default_bitwise(self):
        from apex_tpu.ops import multi_tensor as mt
        buf = jnp.asarray(np.random.RandomState(7).randn(512 * 128),
                          jnp.float32)

        def scale(block_rows):
            out, flag = _dispatch.launch(
                mt._scale_kernel, [buf],
                outs=[("block", jnp.float32), ("scalar", jnp.float32)],
                scalars=[1.7], block_rows=block_rows,
                name="apex_rows_scale")
            return np.asarray(out), bool(flag[0, 0] == 0.0)

        out_d, ok_d = scale(None)
        for r in (64, 128, 256):
            out_t, ok_t = scale(r)
            np.testing.assert_array_equal(out_d, out_t)
            assert ok_d == ok_t

    def test_attention_tuned_vs_default_bitwise(self):
        # the committed-DB pattern: the tuned entry's blocks realize to
        # the same blocks the default dispatch clamps to at this shape
        # (1024 -> 256), so a DB hit is the identical program — tuned
        # dispatch adds nothing numerically
        rng = np.random.RandomState(8)
        q = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
        k = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
        v = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
        base = np.asarray(ops.flash_attention(q, k, v, block_q=256,
                                              block_k=256))
        e = _entry("attention", (1, 256, 256, 2, 64),
                   {"block_q": 256, "block_k": 256})
        with autotune.use_db(_db(e)):
            tuned = np.asarray(ops.flash_attention(q, k, v))
            assert autotune.counters()["hits"] >= 1
        np.testing.assert_array_equal(base, tuned)
        # a genuine block_q retile changes XLA:CPU's gemm row
        # partitioning (reassociated fp32 sums on the 8-device test
        # backend, ~1e-7) — equal to fp32 resolution, not bitwise there
        for bq in (64, 128):
            o = np.asarray(ops.flash_attention(q, k, v, block_q=bq,
                                               block_k=256))
            np.testing.assert_allclose(base, o, rtol=0, atol=1e-6)

    def test_attention_tuned_via_db_matches_explicit(self):
        rng = np.random.RandomState(9)
        q = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
        e = _entry("attention", (1, 128, 128, 2, 64),
                   {"block_q": 64, "block_k": 128})
        explicit = np.asarray(ops.flash_attention(q, q, q, block_q=64,
                                                  block_k=128))
        with autotune.use_db(_db(e)):
            tuned = np.asarray(ops.flash_attention(q, q, q))
            assert autotune.counters()["hits"] >= 1
        np.testing.assert_array_equal(explicit, tuned)


# --- satellite 2: the launch-time refusal ------------------------------------

class TestBlockRefusal:
    def test_nondividing_tuned_block_warns_with_fingerprint(self):
        from apex_tpu.ops import multi_tensor as mt
        n = 512 * 128          # BUFFER_MULTIPLE-padded, 512 rows
        buf = jnp.ones((n,), jnp.float32)
        # 96 is on the sublane grid (passes tuned_rows validation) but
        # does not divide the 512-row buffer — the satellite-2 shape
        e = _entry("optimizer", (n,), {"block_rows": 96})
        fp = e.fingerprint
        with autotune.use_db(_db(e)):
            with pytest.warns(RuntimeWarning) as rec:
                out, flag = _dispatch.launch(
                    mt._scale_kernel, [buf],
                    outs=[("block", jnp.float32),
                          ("scalar", jnp.float32)],
                    scalars=[2.0], name="apex_rows_scale")
        msgs = [str(w.message) for w in rec]
        assert any(fp in m and "falling back" in m
                   and f"BLOCK_ROWS={_dispatch.BLOCK_ROWS}" in m
                   for m in msgs), msgs
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((n,), 2.0, np.float32))

    def test_explicit_nondividing_block_warns_and_falls_back(self):
        from apex_tpu.ops import multi_tensor as mt
        buf = jnp.ones((512 * 128,), jnp.float32)
        with pytest.warns(RuntimeWarning, match="explicit block_rows"):
            out, _ = _dispatch.launch(
                mt._scale_kernel, [buf],
                outs=[("block", jnp.float32), ("scalar", jnp.float32)],
                scalars=[3.0], block_rows=384,
                name="apex_rows_scale")
        assert float(out[0]) == 3.0

    def test_as_rows_refusal_names_the_contract(self):
        with pytest.raises(AssertionError) as exc:
            _dispatch.as_rows(jnp.ones((1000,), jnp.float32))
        msg = str(exc.value)
        assert "apex_tpu.arena.flatten" in msg
        assert "BUFFER_MULTIPLE" in msg
        assert "_resolve_block_rows" in msg


# --- APX104 negative twin ----------------------------------------------------

class TestApx104TuningDB:
    def _warning_sig(self):
        """An off-grid dot big enough to escalate: >=25% waste, >=1MiB."""
        def mm(a, b):
            return a @ b

        text = prof.hlo.compiled_hlo(
            mm, jnp.zeros((9, 2048), jnp.float32),
            jnp.zeros((2048, 129), jnp.float32))
        hits = [f for f in lint.hlo_pass.tile_findings(text)
                if f.rule == "tile-padding"]
        assert hits and any(f.severity == "warning" for f in hits), hits
        warn = [f for f in hits if f.severity == "warning"][0]
        return text, warn.scope

    def test_db_satisfied_shape_does_not_escalate(self):
        text, sig = self._warning_sig()
        covered = [f for f in lint.hlo_pass.tile_findings(
                       text, tuned_shapes=[sig])
                   if f.scope == sig]
        assert covered and covered[0].severity == "info"
        assert "kernel_tuning_db" in covered[0].message

    def test_other_shapes_still_escalate(self):
        text, sig = self._warning_sig()
        still = [f for f in lint.hlo_pass.tile_findings(
                     text, tuned_shapes=["some-other-sig"])
                 if f.scope == sig]
        assert still and still[0].severity == "warning"

    def test_lint_hlo_text_passthrough(self):
        text, sig = self._warning_sig()
        findings = lint.lint_hlo_text(text, tuned_shapes=[sig])
        tp = [f for f in findings if f.rule == "tile-padding"
              and f.scope == sig]
        assert tp and tp[0].severity == "info"

    def test_apx104_fix_names_the_workflow(self):
        from apex_tpu.lint import findings as F
        rule = F.RULES["tile-padding"]
        assert rule.id == "APX104"
        assert "kernel_tune.py" in rule.fix
        assert "kernel_tuning_db.json" in rule.fix

    def test_tuned_lint_shapes_from_entries(self):
        e = _entry("mlp", (64, 48, 32), {"block_rows": 32},
                   lint_sigs=("f32[9,2048] x f32[2048,129]",))
        assert autotune.tuned_lint_shapes(_db(e)) == \
            ["f32[9,2048] x f32[2048,129]"]


# --- tune_report join --------------------------------------------------------

class TestTuneReport:
    def test_family_join_and_coverage(self):
        e = _entry("attention", (1, 256, 256, 2, 64),
                   {"block_q": 256, "block_k": 256},
                   sweep={"best_us": 400.0, "default_us": 520.0})
        gaps = [{"fingerprint": "attention|custom-call|bwd|f32[...]",
                 "family": "attention", "op": "custom-call.202",
                 "measured_us": 549.0, "attainable_us": 436.0,
                 "gap_us": 113.0},
                {"fingerprint": "mlp|fusion|x|f32[...]",
                 "family": "mlp", "op": "fusion.3",
                 "measured_us": 100.0, "attainable_us": 90.0,
                 "gap_us": 10.0}]
        rep = autotune.tune_report(db=_db(e), worst_gaps=gaps)
        assert rep["n_candidates"] == 2 and rep["n_covered"] == 1
        attn = next(c for c in rep["candidates"]
                    if c["op"] == "custom-call.202")
        assert attn["covered"] and attn["db_entries"] == [e.fingerprint]
        assert attn["predicted_closure_us"] == 120.0
        assert rep["uncovered_families"] == ["mlp"]

    def test_events_round_trip_monitor_channel(self, tmp_path):
        from apex_tpu import monitor
        path = str(tmp_path / "tune.jsonl")
        logger = monitor.MetricsLogger(
            sinks=[], roofline_sink=monitor.JSONLSink(path))
        logger.record_roofline(autotune.tune_event(
            "sweep", "layer_norm|256x192|float32|cpu", "layer_norm",
            best_us=70.0, default_us=90.0, n_candidates=5))
        logger.record_roofline(autotune.tune_event(
            "refused", "optimizer|65536|float32|cpu", "optimizer"))
        logger.close()
        recs = [json.loads(l) for l in open(path)]
        assert [r["kind"] for r in recs] == ["tune", "tune"]
        assert recs[0]["action"] == "sweep"
        from apex_tpu.monitor.logger import CHANNELS
        roof = next(c for c in CHANNELS if c.name == "roofline")
        assert "tune" in roof.kinds
