"""The reduction from a profiler trace to the per-layer numbers, on a
small trace with known answers.

The trace is synthesised as a real serialized XSpace (the wire format of
``.xplane.pb``, written by the few lines of protobuf encoding below) and
read back through ``jax.profiler.ProfileData``, so the loader runs too.
Its shape is the v5e's: ``/device:TPU:0`` with ``XLA Modules`` and
``XLA Ops``, host annotations on ``/host:CPU``."""

import importlib.util
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmark"


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod      # the readers import it by this name
    spec.loader.exec_module(mod)
    return mod


tr = load("trace_reduce", BENCH / "trace_reduce.py")


def reader(name):
    return load("reader_" + name, BENCH / "layer_metrics" / (name + ".py"))


# ---- a serialized XSpace, field numbers of tsl/profiler xplane.proto --------

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


def xspace(planes):
    """``{plane: {line: [(name, start_ns, dur_ns), ...]}}`` -> bytes."""
    out = b""
    for plane_name, lines in planes.items():
        names = sorted({e[0] for events in lines.values() for e in events})
        meta = {n: i + 1 for i, n in enumerate(names)}
        plane = field(2, plane_name)
        for i, (line_name, events) in enumerate(lines.items()):
            line = field(1, i + 1) + field(2, line_name)
            for name, start_ns, dur_ns in events:
                line += field(4, field(1, meta[name])
                              + field(2, int(start_ns * 1000))
                              + field(3, int(dur_ns * 1000)))
            plane += field(3, line)
        for name, mid in meta.items():
            plane += field(4, field(1, mid)
                           + field(2, field(1, mid) + field(2, name)))
        out += field(1, plane)
    return out


MOSAIC = ('%fwd_.1 = f32[256,128]{1,0} custom-call(bf16[256,1000]{1,0} %pad), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.{} = bf16[16,512,1024]{{2,1,0}} fusion(bf16[16,512,1024] %p)"


@pytest.fixture(scope="module")
def trace():
    """Five runs of ``jit_step`` of 1000 ns each, 10 ns apart, so three in
    the window [1010, 4030]. Each step: a 600 ns fusion, a 300 ns fusion
    that starts 100 ns before the first ends (overlap), then a 100 ns
    Mosaic call; the last 100 ns of each run are idle. The middle step
    lacks its second fusion and its Mosaic call, which plants a 410 ns
    hole under the first ``bench/wait_loss``. A short other program runs
    once and must not be taken for the step."""
    modules, ops = [], []
    for k in range(5):
        t = 1010 * k
        modules.append(("jit_step(123)", t, 1000))
        ops.append((FUSION.format(2 * k), t, 600))
        if k != 2:
            ops.append((FUSION.format(2 * k + 1), t + 500, 300))
            ops.append((MOSAIC, t + 800, 100))
    modules.append(("jit_other(9)", 5100, 50))
    ops.append(("%copy.1 = f32[8]{0} copy(f32[8] %x)", 5100, 50))
    host = [("bench/dispatch", 900, 200),
            ("bench/wait_loss", 1100, 1600),      # covers step 2's hole
            ("bench/dispatch", 2700, 100),
            ("bench/wait_loss", 2800, 2000),
            ("not/ours", 0, 9000)]
    return tr.load(xspace({
        "/device:TPU:0": {"Steps": [("0", 0, 1000)], "XLA Modules": modules,
                          "XLA Ops": ops, "Async XLA Ops": []},
        "/host:CPU": {"main/1": host[:3], "worker/2": host[3:]},
        "Task Environment": {}}))


def test_loader_keeps_the_lines_it_reads(trace):
    assert sorted(trace.devices) == [0]
    assert sorted(trace.devices[0]) == ["XLA Modules", "XLA Ops"]
    assert [s.name for s in trace.host_spans] == [
        "bench/dispatch", "bench/wait_loss", "bench/dispatch",
        "bench/wait_loss"]
    first = trace.devices[0]["XLA Ops"][0]
    assert (first.start_ns, first.end_ns, first.ns) == (0, 600, 600)


@pytest.mark.parametrize("intervals, total, holes", [
    ([(0, 10), (5, 20)], 20, []),                       # overlapping
    ([(0, 10), (10, 20)], 20, []),                      # touching
    ([(12, 18), (0, 5), (2, 3)], 11, [(5, 12), (18, 20)]),   # nested, unsorted
    ([], 0, [(0, 20)]),
])
def test_union_and_gaps(intervals, total, holes):
    assert tr.union_ns(intervals) == total
    assert tr.gaps(intervals, 0, 20) == holes


def test_window_drops_the_first_and_last_run_of_the_step(trace):
    assert [e.name for e in tr.step_runs(trace)] == ["jit_step(123)"] * 5
    assert tr.window(trace) == (1010, 4030, 3)
    assert tr.device_step_ms(trace) == pytest.approx(1000 / 1e6)


def test_busy_is_the_union_of_overlapping_ops(trace):
    # steps 1 and 3: [0,600) u [500,800) u [800,900) = 900; step 2: 600
    assert tr.busy_ns(trace) == 900 + 600 + 900
    busy_s, window_s = tr.busy_and_window_s(trace)
    assert busy_s == pytest.approx(2400e-9)
    assert window_s == pytest.approx(3020e-9)
    idle = reader("device_idle_pct").read(trace, {})
    assert idle == pytest.approx(100 * (1 - 2400 / 3020))


def test_the_planted_gap_is_found_under_its_host_span(trace):
    found = tr.idle_gaps(trace)
    # step 2's ops end at 2620; step 3 starts at 3030
    assert found[0] == ("bench/wait_loss", pytest.approx(410e-9))
    # then step 1's idle tail up to step 2 (1910 -> 2020), and step 3's
    # up to the end of its run, where the window closes (3930 -> 4030)
    assert [round(g[1] * 1e9) for g in found[1:]] == [110, 100]
    assert reader("host_gap_ms_max").read(trace, {}) == pytest.approx(410e-6)


def test_mosaic_calls_are_summed_per_step(trace):
    # two of the window's three steps hold one 100 ns call
    assert tr.mosaic_ms_per_step(trace) == pytest.approx(200 / 3 / 1e6)
    assert reader("pallas_ms").read(trace, {}) == pytest.approx(200 / 3 / 1e6)


def test_top_ops_group_an_op_across_layers(trace):
    top = dict(tr.top_ops(trace))
    assert top["fusion bf16[16,512,1024]"] == pytest.approx(
        (600 * 3 + 300 * 2) * 1e-9)
    assert top["fwd_ f32[256,128] [mosaic]"] == pytest.approx(200e-9)
    assert not any(k.startswith("copy") for k in top)


def test_mfu_from_the_device_step(trace):
    info = {"flops_per_sample": 1e3, "global_batch": 4, "chips": 2,
            "peak_flops": 1e10}
    # 4000 FLOPs in 1000 ns = 4e9 FLOP/s of 2e10
    assert reader("device_mfu_pct").read(trace, info) == pytest.approx(20.0)
    assert reader("device_mfu_pct").read(trace, {**info, "peak_flops": None}) \
        is None


def test_counters_need_no_trace():
    info = {"finite": [True, False, True, False],
            "memory_peak_bytes": 3 * 2**30}
    assert reader("skipped_steps").read(None, info) == 2
    assert reader("hbm_peak_gib").read(None, info) == 3.0
    # a backend that reports no memory statistics gives no reading
    assert reader("hbm_peak_gib").read(None, {"memory_peak_bytes": 0}) is None


@pytest.mark.parametrize("name", ["host_gap_ms_max", "device_step_ms",
                                  "device_mfu_pct", "pallas_ms",
                                  "device_idle_pct"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    empty = tr.Trace(devices={}, host_spans=[])
    info = {"flops_per_sample": 1.0, "global_batch": 1, "chips": 1,
            "peak_flops": 1.0}
    assert reader(name).read(None, info) is None
    assert reader(name).read(empty, info) is None
