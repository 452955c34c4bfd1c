#!/usr/bin/env python
"""kernel_tune — the Pallas block-shape sweep + the asserting CI audit
of the autotuner (run by ``run_tier1.sh --smoke``; exit status is the
verdict).

Two modes:

``--update-db [--interpret]``
    Sweep every kernel family's candidate grid over the tuning shapes
    (best-of-N per candidate, compiles accounted under
    ``compile_watch.autotune_scope()``) and commit the winners to
    ``scripts/kernel_tuning_db.json`` keyed by
    ``family|dims|dtype|chip`` fingerprints. On CPU the sweep runs in
    Pallas interpret mode and the chip key is ``cpu`` — interpret wall
    clock is structural evidence (grid-step count), not a TPU claim;
    re-run on a TPU host to add on-chip entries under their own chip
    key.

``--cpu8 --interpret``
    The asserted structural audit, CPU-only:

    (a) **sweep accounting**: every family sweeps its grid in interpret
        mode and ``autotune_scope()`` reports *exactly* the sweep's
        compile count — then a steady-state consult of the freshly
        written DB re-traces with ``n_autotune_compiles`` unchanged
        (tuned dispatch is a trace-time table lookup, not a compile).
    (b) **DB round-trip**: write → reload → exact-key hit; a nearest
        miss (one row off) does NOT match.
    (c) **stale refusal**: a seeded entry whose recorded dims no longer
        re-fingerprint to its key raises ``StaleTuningEntry`` naming
        the key — refused loudly, never silently applied.
    (d) **measurable win**: at least one family's sweep shows a real
        candidate spread on CPU (the optimizer launcher's 512-row vs
        64-row block is an 8x grid-step difference in interpret mode —
        the claim is sweep→DB→dispatch plumbing, not CPU microseconds).
    (e) **committed DB**: ``scripts/kernel_tuning_db.json`` loads
        stale-free with ≥1 entry per kernel family and serves an
        exact-key hit at trace time.
    (f) **tune_report join**: DB entries join ``worst_gaps()`` off the
        committed BERT-layer fixture and name the ~549-vs-436 us
        fused-backward attention candidate as covered.
    (g) every emitted ``kind="tune"`` stream validates under
        ``check_metrics_schema.py --kind roofline``.

Usage:
  JAX_PLATFORMS=cpu python scripts/kernel_tune.py --cpu8 --interpret
  JAX_PLATFORMS=cpu python scripts/kernel_tune.py --update-db --interpret
"""

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(_REPO, "tests", "fixtures")
_DB_PATH = os.path.join(_REPO, "scripts", "kernel_tuning_db.json")


def _run_schema(path: str, kind: str = "roofline") -> None:
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts",
                                      "check_metrics_schema.py"),
         "--kind", kind, path],
        capture_output=True, text=True)
    assert r.returncode == 0, (
        f"schema validation failed for {path}:\n{r.stdout}{r.stderr}")


# --- the sweep shapes --------------------------------------------------------
# One representative problem shape per family. Small enough that the
# interpret-mode CI sweep stays in seconds; the same table drives
# --update-db, so the committed DB always covers what the audit expects.

def sweep_specs():
    """family -> (dims, dtype, build) where ``build(block) -> (fn, args)``
    calls the family's dispatch seam with the candidate block made
    explicit (explicit always wins over the DB, so sweeping is
    independent of whatever DB is installed)."""
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops import attention as attn
    from apex_tpu.ops import layer_norm as ln
    from apex_tpu.ops import mlp as mlp_mod
    from apex_tpu.ops import xentropy as xe
    from apex_tpu.ops import multi_tensor as mt
    from apex_tpu.ops import _dispatch

    rng = np.random.RandomState(0)
    f32 = jnp.float32

    specs = {}

    b, sq, sk, h, d = 1, 256, 256, 2, 64
    q = jnp.asarray(rng.randn(b, sq, h, d), f32)
    k = jnp.asarray(rng.randn(b, sk, h, d), f32)
    v = jnp.asarray(rng.randn(b, sk, h, d), f32)

    def build_attn(block):
        def fn(q_, k_, v_):
            return attn.flash_attention(
                q_, k_, v_, block_q=block["block_q"],
                block_k=block["block_k"])
        return fn, (q, k, v)

    specs["attention"] = ((b, sq, sk, h, d), f32, build_attn)

    n, hdim = 256, 192
    x_ln = jnp.asarray(rng.randn(n, hdim), f32)
    w_ln = jnp.ones((hdim,), f32)
    b_ln = jnp.zeros((hdim,), f32)

    def build_ln(block):
        def fn(x_, w_, b_):
            return ln._ln_forward(x_, w_, b_, 1e-5,
                                  block_rows=block["block_rows"])
        return fn, (x_ln, w_ln, b_ln)

    specs["layer_norm"] = ((n, hdim), f32, build_ln)

    nm, d0, d1, d2 = 256, 96, 128, 96
    x_mlp = jnp.asarray(rng.randn(nm, d0), f32)
    ws = (jnp.asarray(rng.randn(d0, d1) * 0.05, f32),
          jnp.asarray(rng.randn(d1, d2) * 0.05, f32))
    bs = (jnp.zeros((d1,), f32), jnp.zeros((d2,), f32))

    def build_mlp(block):
        def fn(x_, w0, w1, b0, b1):
            return mlp_mod._fused_mlp_fwd_impl(
                x_, (w0, w1), (b0, b1), "relu",
                block_rows=block["block_rows"])
        return fn, (x_mlp, *ws, *bs)

    specs["mlp"] = ((nm, d0, d1, d2), f32, build_mlp)

    nx, vocab = 128, 384
    x_xe = jnp.asarray(rng.randn(nx, vocab), f32)
    lab = jnp.asarray(rng.randint(0, vocab, nx), jnp.int32)

    def build_xe(block):
        def fn(x_, l_):
            loss, _ = xe._fwd_call(x_, l_, 0.0,
                                   block_rows=block["block_rows"])
            return loss
        return fn, (x_xe, lab)

    specs["xentropy"] = ((nx, vocab), f32, build_xe)

    nopt = 512 * 128          # one BUFFER_MULTIPLE arena buffer
    buf = jnp.asarray(rng.randn(nopt), f32)

    def build_opt(block):
        def fn(b_):
            import jax.numpy as jnp_
            out, flag = _dispatch.launch(
                mt._scale_kernel, [b_],
                outs=[("block", jnp_.float32),
                      ("scalar", jnp_.float32)],
                scalars=[2.0], block_rows=block["block_rows"],
                name="apex_rows_scale")
            return out, flag
        return fn, (buf,)

    specs["optimizer"] = ((nopt,), f32, build_opt)
    return specs


def run_sweep(on_event=None):
    """Sweep every family; returns (TuningDB, per-family timed grids,
    total candidate count)."""
    from apex_tpu.ops import autotune

    db = autotune.TuningDB()
    grids = {}
    total = 0
    for family, (dims, dtype, build) in sweep_specs().items():
        timed = []
        entry = autotune.sweep_entry(
            family, dims, dtype, build,
            on_candidate=lambda blk, us: timed.append((blk, us)))
        db.add(entry)
        grids[family] = timed
        total += len(timed)
        best = min(us for _, us in timed)
        worst = max(us for _, us in timed)
        print(f"  {family:10s} {len(timed)} candidates  "
              f"best {best:9.1f} us {entry.block}  "
              f"spread x{worst / best:.2f}")
        if on_event is not None:
            on_event(autotune.tune_event(
                "sweep", entry.fingerprint, family,
                n_candidates=len(timed),
                best_us=entry.sweep["best_us"],
                default_us=entry.sweep["default_us"],
                chip=entry.chip, dtype=entry.dtype))
    return db, grids, total


# --- audit legs --------------------------------------------------------------

def audit_sweep_accounting(tmp):
    import jax
    import jax.numpy as jnp

    from apex_tpu import monitor
    from apex_tpu.ops import autotune
    from apex_tpu.prof import compile_watch

    print("== sweep: interpret-mode grid per family, compiles accounted")
    compile_watch.install()
    events = []
    before = compile_watch.global_counters()["autotune_compiles"]
    db, grids, total = run_sweep(on_event=events.append)
    after = compile_watch.global_counters()["autotune_compiles"]
    assert after - before == total, (
        f"autotune_scope accounted {after - before} compiles for a "
        f"{total}-candidate sweep — sweep compiles must be accounted "
        f"exactly, never mistaken for steady-state retraces")
    print(f"  autotune_scope: exactly {total} sweep compiles accounted")

    assert set(db.families()) == set(autotune.FAMILIES), db.families()

    # (d) measurable spread on at least one family — the optimizer
    # grid's 512-vs-64 block is an 8x interpret grid-step difference
    spreads = {fam: max(us for _, us in t) / min(us for _, us in t)
               for fam, t in grids.items()}
    best_fam = max(spreads, key=spreads.get)
    assert spreads[best_fam] >= 1.05, (
        f"no family shows a measurable candidate spread: {spreads}")
    print(f"  measurable win: {best_fam} spread x{spreads[best_fam]:.2f}"
          f" across its grid")

    # steady state: consulting the fresh DB at trace time is a table
    # lookup — n_autotune_compiles must NOT move
    n, hdim = 256, 192
    x = jnp.ones((n, hdim), jnp.float32)
    w = jnp.ones((hdim,), jnp.float32)
    b = jnp.zeros((hdim,), jnp.float32)
    with autotune.use_db(db):
        autotune.reset_counters()
        before = compile_watch.global_counters()["autotune_compiles"]

        @jax.jit
        def step(x_, w_, b_):
            from apex_tpu import ops
            return ops.fused_layer_norm_affine(x_, w_, b_).sum()

        jax.block_until_ready(step(x, w, b))
        after = compile_watch.global_counters()["autotune_compiles"]
        hits = autotune.counters()["hits"]
    assert after == before, (
        f"steady-state consult cost {after - before} autotune compiles; "
        f"expected 0")
    assert hits >= 1, "tuned dispatch did not register a DB hit"
    fp = autotune.fingerprint("layer_norm", (n, hdim), jnp.float32)
    assert any(f == fp and hit for f, hit in autotune.recent_consults()), \
        autotune.recent_consults()
    print(f"  steady-state: n_autotune_compiles +0, exact-key hit {fp}")

    # (g) the tune-event stream validates on the roofline channel
    events.append(autotune.tune_event("hit", fp, "layer_norm",
                                      block_rows=db.lookup(fp).block
                                      .get("block_rows")))
    events_path = os.path.join(tmp, "tune.jsonl")
    logger = monitor.MetricsLogger(
        sinks=[], roofline_sink=monitor.JSONLSink(events_path))
    for ev in events:
        logger.record_roofline(ev)
    logger.close()
    _run_schema(events_path)
    print(f"  tune events validate (--kind roofline): {events_path}")
    return db


def audit_db_roundtrip(tmp, db):
    import jax.numpy as jnp

    from apex_tpu.ops import autotune

    print("== DB round-trip, exact-key-only matching, stale refusal")
    path = os.path.join(tmp, "tuning_db.json")
    db.save(path)
    db2 = autotune.TuningDB.load(path)
    assert set(db2.entries) == set(db.entries)

    dims = (256, 192)
    fp = autotune.fingerprint("layer_norm", dims, jnp.float32)
    assert db2.lookup(fp) is not None, f"exact key {fp} missed after reload"
    with autotune.use_db(db2):
        hit = autotune.lookup_blocks("layer_norm", dims, jnp.float32)
        assert hit == db2.lookup(fp).block, hit
        near = autotune.lookup_blocks("layer_norm", (dims[0] + 1, dims[1]),
                                      jnp.float32)
        assert near is None, (
            f"nearest-miss (257, 192) matched {near} — consultation "
            f"must be exact-key only")
    print(f"  write -> reload -> exact-key hit {fp}; (257,192) miss")

    # seeded stale entry: same key, mutated recorded dims
    raw = json.load(open(path))
    key = fp
    raw["entries"][key]["dims"] = [dims[0], dims[1] + 1]
    stale_path = os.path.join(tmp, "tuning_db_stale.json")
    json.dump(raw, open(stale_path, "w"))
    try:
        autotune.TuningDB.load(stale_path)
    except autotune.StaleTuningEntry as e:
        assert key in str(e) and "stale" in str(e).lower(), e
        print(f"  seeded stale entry refused loudly: "
              f"{str(e).split(':')[2][:60].strip()}...")
    else:
        raise AssertionError(
            "stale tuning entry (mismatched shape fingerprint) was "
            "silently accepted")


def audit_committed_db():
    import jax.numpy as jnp

    from apex_tpu.ops import autotune

    print("== committed DB serves trace-time hits for every family")
    db = autotune.TuningDB.load(_DB_PATH)   # raises StaleTuningEntry if bad
    assert len(db) >= len(autotune.FAMILIES), db.stats()
    missing = set(autotune.FAMILIES) - set(db.families())
    assert not missing, f"committed DB lacks families: {missing}"

    specs = sweep_specs()
    with autotune.use_db(db):
        autotune.reset_counters()
        for family, (dims, dtype, _) in specs.items():
            blocks = autotune.lookup_blocks(family, dims, dtype)
            assert blocks, (
                f"committed DB misses its own sweep shape: "
                f"{autotune.fingerprint(family, dims, dtype)}")
        hits = autotune.counters()["hits"]
    assert hits == len(specs), autotune.counters()
    print(f"  {len(db)} entries, families {db.families()}, "
          f"{hits}/{len(specs)} exact-key hits on the sweep shapes")
    return db


def audit_tune_report(tmp, db):
    from apex_tpu import monitor
    from apex_tpu.prof import roofline, xplane
    from apex_tpu.ops import autotune

    print("== tune_report joins worst_gaps off the BERT-layer fixture")
    tp = xplane.parse_trace(os.path.join(_FIXTURES,
                                         "bert_layer.xplane.pb"))
    rep = roofline.roofline_report(profile=tp, device_kind="TPU v5 lite")
    gaps = rep.worst_gaps(5)
    report = autotune.tune_report(db=db, worst_gaps=gaps)
    assert report["n_candidates"] == len(gaps)

    bwd = [c for c in report["candidates"] if c["op"] == "custom-call.202"]
    assert bwd, [c["op"] for c in report["candidates"]]
    c = bwd[0]
    assert c["family"] == "attention", c
    assert 540.0 <= c["measured_us"] <= 560.0, c
    assert 420.0 <= c["attainable_us"] <= 450.0, c
    assert c["covered"], (
        "the ~549-vs-436 us fused-backward attention candidate is NOT "
        f"covered by a committed tuning entry: {c}")
    assert c["db_entries"], c
    print(f"  fused-backward candidate covered: "
          f"{c['measured_us']:.0f} us measured vs "
          f"{c['attainable_us']:.0f} us floor -> entries "
          f"{c['db_entries']}")
    assert "attention" in report["tuned_families"]

    # the joined report rides the roofline channel as tune events
    events_path = os.path.join(tmp, "tune_report.jsonl")
    logger = monitor.MetricsLogger(
        sinks=[], roofline_sink=monitor.JSONLSink(events_path))
    for cand in report["candidates"]:
        logger.record_roofline(autotune.tune_event(
            "hit" if cand["covered"] else "miss",
            cand["fingerprint"] or "", cand["family"] or "unknown",
            gap_us=cand["gap_us"]))
    logger.close()
    _run_schema(events_path)
    print(f"  joined report events validate: {events_path}")


def main_cpu8():
    import jax
    jax.config.update("jax_platforms", "cpu")
    from apex_tpu import _compat
    _compat.request_cpu_devices(8)

    with tempfile.TemporaryDirectory() as tmp:
        db = audit_sweep_accounting(tmp)
        audit_db_roundtrip(tmp, db)
        committed = audit_committed_db()
        audit_tune_report(tmp, committed)
    print("\nkernel_tune audit ok")


def main_update_db():
    from apex_tpu.ops import autotune
    from apex_tpu.prof import compile_watch
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()
    compile_watch.install()
    print(f"== sweeping {len(autotune.FAMILIES)} families "
          f"(chip={autotune.chip_kind()})")
    db, _, total = run_sweep()
    # merge over any existing entries for OTHER keys (e.g. another
    # chip's artifacts) — a sweep only overwrites what it re-measured
    try:
        existing = autotune.TuningDB.load(_DB_PATH)
    except autotune.StaleTuningEntry as e:
        print(f"  discarding stale DB: {e}")
        existing = autotune.TuningDB()
    for key, entry in db.entries.items():
        existing.entries[key] = entry
    existing.save(_DB_PATH)
    n_auto = compile_watch.global_counters()["autotune_compiles"]
    print(f"  {total} candidates timed ({n_auto} accounted compiles) -> "
          f"{len(existing)} entries in {_DB_PATH}")


if __name__ == "__main__":
    if "--interpret" in sys.argv:
        os.environ["APEX_TPU_FORCE_INTERPRET"] = "1"
    if "--update-db" in sys.argv:
        main_update_db()
    elif "--cpu8" in sys.argv:
        main_cpu8()
    else:
        print(__doc__)
        sys.exit(2)
