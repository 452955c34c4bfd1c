"""The readers that take the program's scopes out of the device trace, on
a small trace with known answers.

The trace is a real serialized XSpace in the v5e's layout: ``XLA Modules``
and ``XLA Ops`` on ``/device:TPU:0``, and per op the ``tf_op``,
``hlo_category``, ``flops`` and ``memory_access_breakdown`` stats in the
event metadata. It is written where a traced run leaves its own
(``.out/<cell>/trace/plugins/profile/<time>/``), read back through
``apex_tpu.prof.xplane`` by ``benchmark/scope_reduce.py``, and through
``jax.profiler.ProfileData`` by ``trace_reduce``: the two must cut the
same window."""

import importlib.util
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmark"
PROPOSED = json.loads((BENCH / "proposed_per_layer.json").read_text())[
    "per_layer"]
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod      # the readers import it by this name
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return load("reader_" + name, BENCH / "layer_metrics" / (name + ".py"))


# ---- a serialized XSpace with stats -----------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


STATS = {"tf_op": 1, "hlo_category": 2, "flops": 3,
         "memory_access_breakdown": 4}


def breakdown(*entries):
    """``(1 read | 2 write, memory space, bytes)`` entries."""
    return b"".join(field(1, field(1, op) + field(2, space) + field(3, n))
                    for op, space, n in entries)


def stat(name, value):
    kind = {str: 5, bytes: 6, int: 4}[type(value)]   # str / bytes / int64
    return field(5, field(1, STATS[name]) + (
        field(kind, value) if kind != 6
        else varint(6 << 3 | 2) + varint(len(value)) + value))


def device_plane(name, ops, runs):
    """``ops``: per step ``(hlo text, us, stats)``; ``runs``: how many runs
    of the step, 1000 us apart, each running every op back to back."""
    meta = {"jit_step": 1, **{op[0]: i + 2 for i, op in enumerate(ops)}}
    modules, events = b"", b""
    for k in range(runs):
        t = k * 1000_000_000        # ps
        start = t
        for text, us, _stats in ops:
            events += field(4, field(1, meta[text]) + field(2, t)
                            + field(3, int(us * 1e6)))
            t += int(us * 1e6)
        modules += field(4, field(1, 1) + field(2, start)
                         + field(3, t - start))
    plane = field(2, name)
    plane += field(3, field(1, 1) + field(2, "XLA Modules") + modules)
    plane += field(3, field(1, 2) + field(2, "XLA Ops") + events)
    plane += field(4, field(1, 1) + field(2, field(1, 1)
                                          + field(2, "jit_step")))
    for text, _us, stats in ops:
        body = field(1, meta[text]) + field(2, text)
        for k, v in stats.items():
            body += stat(k, v)
        plane += field(4, field(1, meta[text]) + field(2, body))
    for stat_name, sid in STATS.items():
        plane += field(5, field(1, sid) + field(
            2, field(1, sid) + field(2, stat_name)))
    return field(1, plane)


FWD = "jit(step)/jvp(amp/fwd)/Enc/Layer_0/"
BWD = "jit(step)/transpose(jvp(amp/fwd))/Enc/Layer_0/"


def op(n, text, us, tf_op=None, category=None, flops=None, moved=None):
    stats = {}
    if tf_op:
        stats["tf_op"] = tf_op + ":"
    if category:
        stats["hlo_category"] = category
    if flops is not None:
        stats["flops"] = flops
    if moved is not None:
        stats["memory_access_breakdown"] = breakdown(*moved)
    return (f"%{text}.{n} = f32[8]{{0}} {text.split('.')[0]}(f32[8] %p)",
            us, stats)


def mosaic(n, us, scope):
    return (f'%k.{n} = f32[8]{{0}} custom-call(f32[8] %p), '
            f'custom_call_target="tpu_custom_call"', us,
            {"tf_op": scope + "/pallas_call:", "hlo_category": "custom-call",
             "flops": 0})


#: one step: 59 us of ops
STEP = [
    op(1, "fusion", 10.0, FWD + "Dense_0/dot_general", "convolution fusion",
       flops=2_000_000_000, moved=[(1, 1, 1000), (2, 3, 500)]),
    mosaic(2, 4.0, FWD + "Attn_0/apex_attn_fwd"),
    mosaic(3, 1.0, FWD + "LN_0/apex_layer_norm_fwd"),
    mosaic(4, 1.0, "jit(step)/jvp(amp/fwd)/apex_xentropy_fwd"),
    mosaic(5, 1.5, "jit(step)/transpose(jvp(amp/fwd))/apex_xentropy_bwd"),
    mosaic(6, 2.0, BWD + "LN_0/apex_layer_norm_bwd"),
    mosaic(7, 5.0, BWD + "Attn_0/apex_attn_bwd_dq"),
    mosaic(8, 3.0, BWD + "Attn_0/apex_attn_bwd_dkv"),
    op(9, "fusion", 20.0, BWD + "Dense_0/dot_general", "convolution fusion",
       flops=4_000_000_000, moved=[(1, 1, 3000), (2, 1, 2000)]),
    op(10, "all-reduce", 1.0, "jit(step)/ddp/sync_gradients/bucket00/psum",
       "all-reduce"),
    op(11, "fusion", 5.0, "jit(step)/amp/update/optim/lamb/norms/reduce_sum",
       "loop fusion", moved=[(1, 1, 4000)]),
    op(12, "fusion", 3.0, "jit(step)/amp/update/optim/lamb/update/sub",
       "loop fusion", moved=[(1, 1, 2000), (2, 1, 2000)]),
    op(13, "add", 0.5, "jit(step)/amp/update/add", "non-fusion elementwise"),
    op(14, "copy-done", 2.0, None, "copy-done"),
]
NO_KERNELS = [o for o in STEP if "custom-call" not in o[0]]
HOST = field(1, field(2, "/host:CPU"))

RUN_INFO = {"peak_flops": 197e12}
EXPECTED = {                        # five runs: three whole steps
    "fwd_ms": 0.016, "bwd_ms": 0.0315, "optimizer_ms": 0.0085,
    "gemm_ms": 0.030, "gemm_mfu_pct": 100 * 6e9 / 30e-6 / 197e12,
    "hbm_bw_pct": 100 * 3 * 14000 / 2059e-6 / 819e9,
    "attn_fwd_ms": 0.004, "attn_bwd_ms": 0.008, "layer_norm_ms": 0.003,
    "xentropy_ms": 0.0025, "unscoped_pct": 100 * 2.0 / 59.0,
}


@pytest.fixture()
def sr(tmp_path, monkeypatch):
    """``scope_reduce`` looking under a ``.out`` of the test's own, with
    the v5e's bandwidth for a chip (the test holds none)."""
    mod = load("scope_reduce", BENCH / "scope_reduce.py")
    monkeypatch.setattr(mod, "OUT", str(tmp_path))
    monkeypatch.setattr(mod, "published_peak", lambda key: 819e9)

    def plant(data, cell="cell"):
        d = tmp_path / cell / "trace" / "plugins" / "profile" / "t0"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(data)
        mod._parsed.clear()
        return str(d / "host.xplane.pb")
    mod.plant = plant
    return mod


TRACE = object()        # what the runner hands a reader when it has a trace


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_planted_records(sr, name):
    sr.plant(HOST + device_plane("/device:TPU:0", STEP, runs=5))
    assert reader(name).read(TRACE, RUN_INFO) == pytest.approx(
        EXPECTED[name], rel=1e-9)


def test_the_numbers_close(sr):
    """Kernels sum to the Mosaic time, and forward + backward + optimizer +
    unscoped + what else is scoped (here the gradient sync) to the busy
    time of a step."""
    sr.plant(HOST + device_plane("/device:TPU:0", STEP, runs=5))
    get = lambda n: reader(n).read(TRACE, RUN_INFO)
    kernels = sum(get(n) for n in ("attn_fwd_ms", "attn_bwd_ms",
                                   "layer_norm_ms", "xentropy_ms"))
    assert kernels == pytest.approx(0.0175)
    busy = 0.059
    rest = busy - get("fwd_ms") - get("bwd_ms") - get("optimizer_ms") \
        - get("unscoped_pct") / 100 * busy
    assert rest == pytest.approx(0.001)         # ddp/sync_gradients
    assert sr.windowed(TRACE).parse_s > 0


def test_same_window_as_trace_reduce(sr):
    """Second run of the step to the end of the last but one, on chip 0,
    and the same number of steps to divide by."""
    path = sr.plant(HOST + device_plane("/device:TPU:0", STEP, runs=5))
    tr = load("trace_reduce", BENCH / "trace_reduce.py")
    lo, hi, steps = tr.window(tr.load(path))
    mine = sr.windowed(TRACE)
    assert (mine.profile.window_ns, mine.steps) == ((lo, hi), steps) == (
        (1_000_000.0, 3_059_000.0), 3)
    assert mine.seconds == pytest.approx(2059e-6)
    busy, window_s = tr.busy_and_window_s(tr.load(path))
    assert mine.profile.total_us / 1e6 == pytest.approx(busy)
    assert tr.mosaic_ms_per_step(tr.load(path)) == pytest.approx(0.0175)


def test_nothing_to_sum_reads_zero(sr):
    """A model without the kernel: 0.0 in a traced window, not None."""
    sr.plant(HOST + device_plane("/device:TPU:0", NO_KERNELS, runs=5))
    for name in ("attn_fwd_ms", "attn_bwd_ms", "layer_norm_ms",
                 "xentropy_ms"):
        assert reader(name).read(TRACE, RUN_INFO) == 0.0
    assert reader("gemm_ms").read(TRACE, RUN_INFO) == pytest.approx(0.030)


@pytest.mark.parametrize("case", ["no_trace", "no_file", "no_tpu_plane",
                                  "two_runs", "parent_program",
                                  "unknown_chip"])
def test_none_where_there_is_nothing_to_read(sr, monkeypatch, case):
    trace, names = TRACE, sorted(EXPECTED)
    if case != "no_file":
        sr.plant(HOST + device_plane(
            "/device:GPU:0" if case == "no_tpu_plane" else "/device:TPU:0",
            STEP, runs=2 if case == "two_runs" else 5))
    if case == "no_trace":          # the newest file is another run's
        trace = None
    if case == "parent_program":    # the reader as it was before PR 25
        from apex_tpu.prof import xplane
        monkeypatch.delattr(xplane, "own_scope")
    if case == "unknown_chip":      # no published bandwidth to divide by
        monkeypatch.setattr(sr, "published_peak", lambda key: None)
        names = ["hbm_bw_pct"]
    for name in names:
        assert reader(name).read(trace, RUN_INFO) is None, name
    assert reader("gemm_mfu_pct").read(trace, {"peak_flops": None}) is None


def test_parsed_once_for_a_process(sr, monkeypatch):
    sr.plant(HOST + device_plane("/device:TPU:0", STEP, runs=5))
    from apex_tpu.prof import xplane
    calls = []
    real = xplane.parse_trace
    monkeypatch.setattr(xplane, "parse_trace",
                        lambda *a, **k: calls.append(a) or real(*a, **k))
    for name in sorted(EXPECTED):
        reader(name).read(TRACE, RUN_INFO)
    assert len(calls) == 1


def test_published_peak_is_the_chips_own(monkeypatch):
    mod = load("scope_reduce", BENCH / "scope_reduce.py")
    import jax

    class Chip:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert mod.published_peak("hbm_bytes_per_s") == 819e9
    Chip.device_kind = "cpu"
    assert mod.published_peak("hbm_bytes_per_s") is None


@pytest.mark.parametrize("entry", PROPOSED, ids=[m["name"] for m in PROPOSED])
def test_proposed_entry(entry):
    """Each reader says of itself what its entry will say in
    ``BENCHMARK.json``, under the rules ``test_manifest`` holds entries to."""
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves"}
    mod = reader(entry["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["source"] == "device_trace"
    assert entry["better"] in ("lower", "higher")
    # a layer BENCHMARK.json names already, or one of PERF.md section 3's
    assert entry["layer"] in {m["layer"] for m in MANIFEST["per_layer"]} | {
        "optimizers", "observability, safety"}
    assert entry["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    assert entry["name"] not in {m["name"] for m in MANIFEST["per_layer"]}, \
        "wired: drop it from proposed_per_layer.json"
    if "mfu" in entry["name"]:
        assert entry["unit"] == "%"


def test_proposed_entries_are_the_new_readers():
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    files = {p.stem for p in (BENCH / "layer_metrics").glob("*.py")}
    assert sorted(files - listed) == sorted(m["name"] for m in PROPOSED)
