"""The five readers PR 34 adds, on planted records with known answers.

Four sum the device time of scopes ``models/lfm2.py`` and ``ops/moe.py``
name (``lconv/``, ``gqa/``, ``moe/experts``, ``moe/dispatch|combine``)
out of the trace's ``tf_op`` stats, through ``scope_reduce`` as the readers
of PRs 25, 28 and 32 do; the fifth divides the bytes the gated convolution
requires, by the sizes in the cell's own files and the run's global batch,
by the time under ``lconv/conv`` and the chip's bandwidth. The serialized XSpace and its helpers are
``test_scope_reduce.py``'s."""

import importlib.util
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmark"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = "lfm2_moe.lm_s8192_b2_v8k"


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


planted = load("planted_xspace", HERE / "test_scope_reduce.py")
op, mosaic, reader = planted.op, planted.mosaic, planted.reader

FWD = "jit(step)/jvp(amp/fwd)/Lfm2Moe/"
AGAIN = FWD + "checkpoint/rematted_computation/"
BWD = "jit(step)/transpose(jvp(amp/fwd))/Lfm2Moe/checkpoint/"
CONV = "layers_0/lconv/lconv/conv/jit(_gated_{})/apex_short_conv_{}"


def conv_kernel(n, us, scope, result):
    """A convolution kernel's event, its HLO text with the result's shape."""
    return (f'%apex_short_conv.{n} = {result} custom-call(bf16[2,64,768] '
            f'%x), custom_call_target="tpu_custom_call"', us,
            {"tf_op": scope + "/pallas_call:", "hlo_category": "custom-call",
             "flops": 0})


#: the planted convolution's forward + rerun + backward take 4 us, and
#: ``PEAK`` is the bandwidth at which the 22 bytes a token-channel of the
#: cell's four conv layers (2 sequences of 8192 tokens, 2048 channels, from
#: its files) take just that: a trace at the floor
RUN = {"global_batch": 2}
FLOOR_US = 4.0
PEAK = 4 * 2 * 8192 * 2048 * 22 / (FLOOR_US * 1e-6)
#: one step of a two-layer decoder, scopes as the model nests them: 38.5 us
#: and the convolution's three calls
STEP = [
    op(1, "fusion", 4.0, FWD + "layers_0/lconv/lconv/proj/in_proj/"
       "dot_general"),
    conv_kernel(2, FLOOR_US / 4, FWD + CONV.format("forward", "fwd"),
                "bf16[2,64,256]{2,1,0}"),
    op(3, "fusion", 2.0, FWD + "layers_0/lconv/lconv/out/out_proj/"
       "dot_general"),
    op(4, "fusion", 2.0, FWD + "layers_1/gqa/gqa/proj/q_proj/dot_general"),
    op(5, "fusion", 0.5, FWD + "layers_1/gqa/gqa/rope/concatenate"),
    mosaic(6, 3.0, FWD + "layers_1/gqa/gqa/attn/apex_attn_fwd"),
    op(7, "fusion", 1.5, FWD + "layers_1/moe/moe/route/dot_general"),
    op(8, "fusion", 0.5, FWD + "layers_1/moe/moe/dispatch/gather"),
    op(9, "fusion", 2.0, FWD + "layers_1/moe/moe/experts/"
       "ecd,edf->ecf/dot_general"),
    op(10, "fusion", 0.5, FWD + "layers_1/moe/moe/combine/scatter-add"),
    op(11, "fusion", 1.5, FWD + "layers_1/moe/jvp(moe/overflow)/while/body/"
       "dot_general"),
    op(12, "fusion", 2.5, FWD + "lm/head/dot_general"),
    conv_kernel(13, FLOOR_US / 4, AGAIN + CONV.format("forward", "fwd"),
                "bf16[2,64,256]{2,1,0}"),
    conv_kernel(14, FLOOR_US / 2, BWD + CONV.format("backward", "bwd"),
                "(bf16[2,64,768]{2,1,0}, f32[3,256]{1,0})"),
    op(15, "fusion", 7.0, BWD + "layers_0/lconv/lconv/proj/in_proj/"
       "dot_general"),
    mosaic(16, 4.0, BWD + "layers_1/gqa/gqa/attn/apex_attn_bwd_dkv"),
    op(17, "fusion", 3.0, BWD + "layers_1/moe/moe/experts/"
       "ecd,edf->ecf/dot_general"),
    op(18, "fusion", 1.5, BWD + "layers_1/moe/moe/dispatch/scatter-add"),
    op(19, "fusion", 3.0, "jit(step)/amp/update/optim/adam/update/sub"),
]
EXPECTED = {"lconv_ms": (13.0 + FLOOR_US) / 1e3, "gqa_ms": 0.0095,
            "moe_gemm_ms": 0.005, "moe_move_ms": 0.0025,
            "lconv_conv_hbm_pct": 100.0}
NEW = sorted(EXPECTED)


@pytest.fixture()
def sr(tmp_path, monkeypatch):
    mod = load("scope_reduce", BENCH / "scope_reduce.py")
    monkeypatch.setattr(mod, "OUT", str(tmp_path))
    monkeypatch.setattr(mod, "published_peak", lambda key: PEAK)
    mod._parsed.clear()

    def plant(data):
        d = tmp_path / "cell" / "trace" / "plugins" / "profile" / "t0"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(data)
        mod._parsed.clear()
    mod.plant = plant
    return mod


@pytest.mark.parametrize("name", NEW)
def test_scope_reader_on_planted_records(sr, name):
    """The roofline reader on a trace at the floor (the three calls take the
    time 22 bytes a token-channel take at the chip's bandwidth) reads 100
    and no more: the rerun's bytes are in the time, not in the numerator."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    value = reader(name).read(planted.TRACE, RUN)
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)
    if name == "lconv_conv_hbm_pct":
        assert value <= 100.0 + 1e-6


def test_the_required_bytes_are_the_issues():
    """8 B a token-channel forward and 14 B backward in bfloat16, a layer."""
    mod = reader("lconv_conv_hbm_pct")
    assert mod.required_bytes(16384, 2048, 2) == 16384 * 2048 * (8 + 14)
    assert mod.required_bytes(16384, 2048, 2, layers=4) == 4 * 738197504
    assert mod.required_bytes(1, 1, 4) == 44
    assert mod.cell_shape() == (8192, 2048, 4) and mod.ITEM_BYTES == 2


def test_a_slower_convolution_reads_under_100(sr):
    twice = [(text, us * 2 if "apex_short_conv" in text else us, stats)
             for text, us, stats in STEP]
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", twice,
                                                 runs=5))
    assert reader("lconv_conv_hbm_pct").read(planted.TRACE, RUN) == \
        pytest.approx(50.0, rel=1e-6)
    # whatever implements the scope requires the same bytes: no kernel's
    # name or shape is read
    other = [(text.replace("apex_short_conv", "fusion"), us,
              {**stats, "tf_op": stats["tf_op"].replace(
                  "apex_short_conv_bwd/pallas_call", "mul").replace(
                  "apex_short_conv_fwd/pallas_call", "mul")})
             for text, us, stats in STEP]
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", other,
                                                 runs=5))
    assert reader("lconv_conv_hbm_pct").read(planted.TRACE, RUN) == \
        pytest.approx(100.0, rel=1e-6)
    assert reader("lconv_ms").read(planted.TRACE, RUN) == pytest.approx(
        EXPECTED["lconv_ms"], rel=1e-6)


def test_the_accepted_readers_see_this_step_as_they_should(sr):
    """The four expert metrics the other decoder cells report have something
    to read on this step (``moe_ms`` is the whole expert layer,
    ``moe_experts_ms`` the held experts with their overflow turn: the two new
    readers split it, gemm + move + overflow), but the cell does not list
    them: the accepted tests pin their ``workloads`` to one cell each, and
    those files are a ``benchmark`` PR's to edit (PERF.md section 7).
    ``gattn_ms`` does not read ``gqa/``."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    assert reader("moe_ms").read(planted.TRACE, {}) == pytest.approx(0.0105)
    assert reader("moe_route_ms").read(planted.TRACE, {}) == pytest.approx(
        0.0015)
    assert reader("moe_overflow_ms").read(planted.TRACE, {}) == pytest.approx(
        0.0015)
    assert reader("moe_experts_ms").read(planted.TRACE, {}) == pytest.approx(
        EXPECTED["moe_gemm_ms"] + EXPECTED["moe_move_ms"] + 0.0015)
    assert reader("gattn_ms").read(planted.TRACE, {}) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_scope_reader_elsewhere(sr, name):
    """0.0 on a traced step of another model (BERT's: the scope is absent),
    None where there is nothing to read: no trace, too few runs of the step,
    or (the roofline share) a chip with no published bandwidth."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0",
                                                 planted.STEP, runs=5))
    assert reader(name).read(planted.TRACE, RUN) == 0.0
    assert reader(name).read(None, RUN) is None
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=2))
    assert reader(name).read(planted.TRACE, RUN) is None


def test_no_bandwidth_no_share(sr, monkeypatch):
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    monkeypatch.setattr(sr, "published_peak", lambda key: None)
    assert reader("lconv_conv_hbm_pct").read(planted.TRACE, RUN) is None


@pytest.mark.parametrize("name", NEW)
def test_entry_and_reader_agree(name):
    """The manifest's entry says of the reader what the reader says of
    itself, names the cell that can report it, and reads the device trace."""
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    mod = reader(name)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["source"] == "device_trace"
    assert entry["better"] == ("higher" if name.endswith("_pct") else "lower")
    assert entry["workloads"] == [CELL]


def test_the_new_cell_lists_the_common_metrics_and_its_five():
    """The seven every cell reports and its own five, appended at the end of
    the manifest; no accepted metric's list of cells was touched."""
    common = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    assert len(common) == 7
    for m in MANIFEST["per_layer"]:
        if m["name"] not in common | set(NEW):
            assert CELL not in m["workloads"]
    own = json.loads((BENCH / "workloads" / (CELL + ".json")).read_text())
    assert set(own["per_layer"]) == common | set(NEW)
    assert MANIFEST["per_layer"][-5:] == [
        m for m in MANIFEST["per_layer"] if m["name"] in NEW]
