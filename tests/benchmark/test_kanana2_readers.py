"""The reader of the latent-attention cells, on planted records with known
answers, its proposed ``BENCHMARK.json`` entry, and the seven accepted
metrics the kanana-2 cell is to be appended to.

``mla_attn_roofline`` divides the operations causal attention requires, by
the sizes in the cells' own files, the layers the trace holds and the
run's global batch, by the time of the Mosaic calls under ``mla/attn`` and
the chip's bf16 peak. It reads a kanana-2 step (MLA in every layer) and a
Kimi-Linear step (MLA one layer in four, beside KDA) alike: the two cells
share the kernels and the per-layer shape (32 heads, 192 / 128, 8192
tokens). The reader waits in ``benchmark/proposed_kanana2_readers/``
(``benchmark/proposed_kanana2_per_layer.json`` says why and what wires it);
every test here holds in both states, waiting and wired, so wiring it edits
nothing in this file. The serialized XSpace and its helpers are
``test_scope_reduce.py``'s.
"""

import importlib.util
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmark"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = "kanana2.lm_s8192_b1_v16k"
KIMI = "kimi_linear.lm_s8192_b1"
NEW = ["mla_attn_roofline"]
#: the entries: ``BENCHMARK.json``'s once they are wired, else the proposal's
WIRED = [m for m in MANIFEST["per_layer"] if m["name"] in NEW]
PROPOSAL = BENCH / "proposed_kanana2_per_layer.json"
WAITING = json.loads(PROPOSAL.read_text()) if PROPOSAL.exists() else None
ENTRIES = WIRED or WAITING["per_layer"]
#: the accepted metrics that are to report the cell too: its latent
#: attention and every expert metric, ``moe_overflow_ms`` among them (0.0
#: here, as on Kimi's cell: ``ops/moe.py`` has no overflow turn)
APPENDED = ["mla_ms", "moe_ms", "moe_overflow_ms", "moe_route_ms",
            "moe_experts_ms", "moe_gemm_ms", "moe_move_ms"]


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


planted = load("planted_xspace", HERE / "test_scope_reduce.py")
op, mosaic = planted.op, planted.mosaic


def reader(name):
    """The reader where it lies; while it waits, the cells it reads are the
    proposal's entry's, which is what ``BENCHMARK.json`` will hold."""
    path = BENCH / "layer_metrics" / (name + ".py")
    if not WIRED:
        path = BENCH / "proposed_kanana2_readers" / (name + ".py")
    mod = load("kanana2_reader_" + name, path)
    if not WIRED:
        listed = {m["name"]: m["workloads"] for m in ENTRIES}[name]
        mod.listed_cells = lambda: listed
    return mod


#: both cells' attention: 32 heads of 192 / 128 over 8192 tokens, causal
PAIRS = 8192 * 8193 // 2
RUN = {"global_batch": 1}
#: the planted attention kernels take 6 us a step in each MLA layer, and
#: ``PEAK`` is the rate at which a layer's required operations take just
#: that
FLOOR_US = 6.0
PEAK = 6 * PAIRS * (192 + 128) * 32 / (FLOOR_US * 1e-6)


def _mla_layer(model, i):
    """One MLA layer's ops, scopes as ``models/mla.py`` nests them, its
    three attention kernels taking ``FLOOR_US`` together."""
    fwd = f"jit(step)/jvp(amp/fwd)/{model}/"
    again = fwd + "checkpoint/rematted_computation/"
    bwd = f"jit(step)/transpose(jvp(amp/fwd))/{model}/checkpoint/"
    mla = f"layers_{i}/mla/mla/"
    return [
        op(10 * i + 1, "fusion", 2.0, fwd + mla + "proj/q_proj/dot_general"),
        op(10 * i + 2, "fusion", 0.5, fwd + mla + "rope/concatenate"),
        mosaic(10 * i + 3, FLOOR_US / 4, fwd + mla + "attn/apex_attn_fwd"),
        # the padding of v: under the scope, not a Mosaic call
        op(10 * i + 4, "fusion", 0.25, fwd + mla + "proj/pad"),
        op(10 * i + 5, "fusion", 1.0, fwd + mla + "out/o_proj/dot_general"),
        op(10 * i + 6, "fusion", 2.5, again + mla + "proj/kv_b/dot_general"),
        mosaic(10 * i + 7, FLOOR_US / 4, bwd + mla + "attn/apex_attn_bwd_dq"),
        mosaic(10 * i + 8, FLOOR_US / 2, bwd + mla + "attn/apex_attn_bwd_dkv"),
        op(10 * i + 9, "fusion", 0.25, bwd + mla + "attn/slice"),
    ]


def _moe(model, i):
    fwd = f"jit(step)/jvp(amp/fwd)/{model}/layers_{i}/moe/moe/"
    return [op(100 + i, "fusion", 1.5, fwd + "route/dot_general"),
            op(110 + i, "fusion", 0.5, fwd + "dispatch/gather"),
            op(120 + i, "fusion", 3.0, fwd + "experts/apex_gmm"),
            op(130 + i, "fusion", 0.5, fwd + "combine/gather"),
            op(140 + i, "fusion", 1.0, fwd + "shared/up_proj/dot_general")]


TAIL = [op(200, "fusion", 2.5, "jit(step)/jvp(amp/fwd)/lm/head/dot_general"),
        op(201, "fusion", 3.0, "jit(step)/amp/update/optim/adam/update/sub")]
#: kanana-2: MLA in both planted layers, the dense FFN in the first
KANANA_STEP = (_mla_layer("DeepseekV3", 0)
               + [op(150, "fusion", 4.0, "jit(step)/jvp(amp/fwd)/DeepseekV3/"
                     "layers_0/mlp/up_proj/dot_general")]
               + _mla_layer("DeepseekV3", 1) + _moe("DeepseekV3", 1) + TAIL)
#: Kimi-Linear: KDA in layers 0-2, MLA in layer 3
KIMI_STEP = ([op(160 + i, "fusion", 5.0, "jit(step)/jvp(amp/fwd)/KimiLinear/"
                 f"layers_{i}/kda/kda/scan/apex_kda_fwd") for i in range(3)]
             + _mla_layer("KimiLinear", 3) + _moe("KimiLinear", 3) + TAIL)
CASES = {"kanana2": (KANANA_STEP, 2), "kimi": (KIMI_STEP, 1)}
#: an MLA layer's ops outside the three kernels: 2.0 + 0.5 + 0.25 + 1.0 +
#: 2.5 + 0.25 us
MLA_US = FLOOR_US + 6.5


@pytest.fixture()
def sr(tmp_path, monkeypatch):
    mod = load("scope_reduce", BENCH / "scope_reduce.py")
    monkeypatch.setattr(mod, "OUT", str(tmp_path))
    mod._parsed.clear()

    def plant(data, rate):
        monkeypatch.setattr(mod, "published_peak", lambda key: rate)
        d = tmp_path / "cell" / "trace" / "plugins" / "profile" / "t0"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(data)
        mod._parsed.clear()
    mod.plant = plant
    return mod


def _plant(sr, step, runs=5, rate=PEAK):
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", step,
                                                 runs=runs), rate)


@pytest.mark.parametrize("case", sorted(CASES))
def test_roofline_on_planted_records(sr, case):
    """On each model's step at the floor (every MLA layer's three kernels
    take the time its causal pairs' operations take at the peak) the reader
    reads 100 and no more: the padding of ``v`` and the rest of the scope
    are not counted, and the layers are the trace's."""
    step, _ = CASES[case]
    _plant(sr, step)
    value = reader("mla_attn_roofline").read(planted.TRACE, RUN)
    assert value == pytest.approx(100.0, rel=1e-6)
    assert value <= 100.0 + 1e-6


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_accepted_readers_read_the_cell(sr, case):
    """``mla_ms`` takes every op of the scope, the rotation's among them;
    the expert metrics read the layer ``ops/moe.py`` scopes: what they will
    report on this cell once its name is in their lists."""
    step, layers = CASES[case]
    _plant(sr, step)
    got = {name: planted.reader(name).read(planted.TRACE, RUN)
           for name in APPENDED}
    assert got["mla_ms"] == pytest.approx(layers * MLA_US / 1e3, rel=1e-6)
    assert got["moe_route_ms"] == pytest.approx(1.5 / 1e3, rel=1e-6)
    assert got["moe_experts_ms"] == pytest.approx(4.0 / 1e3, rel=1e-6)
    assert got["moe_ms"] == pytest.approx(6.5 / 1e3, rel=1e-6)
    assert got["moe_gemm_ms"] == pytest.approx(3.0 / 1e3, rel=1e-6)
    assert got["moe_move_ms"] == pytest.approx(1.0 / 1e3, rel=1e-6)
    assert got["moe_overflow_ms"] == 0.0


def test_the_required_operations_are_causal_attention_s():
    """``6 (192 + 128)`` operations a kept pair and a head; the pairs and
    the sizes from both cells' files, which agree."""
    mod = reader("mla_attn_roofline")
    assert mod.causal_pairs(8192) == PAIRS == 33_558_528
    assert mod.required_flops(PAIRS, 32, 192, 128, 6, 1) == \
        6 * PAIRS * 320 * 32 * 6
    # against the configuration's count, which takes half the square
    build = load("configs_kanana2_for_readers",
                 BENCH / "configs" / "kanana2.py")
    sizes = json.loads((BENCH / "configs" / "kanana2.json").read_text())
    matmuls = build.flops_per_sequence({**sizes, "num_hidden_layers": 0},
                                       8192)
    attention = build.flops_per_sequence(sizes, 8192) - matmuls - 6 * 8192 * (
        6 * 26_345_472 + 3 * 2048 * 6144 + 5 * (2048 * 128 + 3 * 2048 * 1536
                                                + 0.75 * 3 * 2048 * 768))
    assert mod.required_flops(PAIRS, 32, 192, 128, 6) == pytest.approx(
        attention, rel=2e-4)
    assert mod.listed_cells() == [KIMI, CELL]
    assert mod.cell_shape() == (PAIRS, 32, 192, 128)
    if not WIRED:       # the reader itself reads BENCHMARK.json alone
        unwired = load("kanana2_reader_unwired", BENCH /
                       "proposed_kanana2_readers" / "mla_attn_roofline.py")
        assert unwired.listed_cells() == [] and unwired.cell_shape() is None


def test_slower_kernels_read_under_100(sr):
    """Twice the time reads 50; and only the Mosaic calls count, so the
    slice and the padding under the same scope take no part."""
    step, _ = CASES["kanana2"]
    twice = [(text, us * 2 if "tpu_custom_call" in text
              and "/mla/attn/" in stats["tf_op"] else us, stats)
             for text, us, stats in step]
    _plant(sr, twice)
    mod = reader("mla_attn_roofline")
    assert mod.read(planted.TRACE, RUN) == pytest.approx(50.0, rel=1e-6)
    assert mod.read(planted.TRACE, {"global_batch": 2}) == pytest.approx(
        100.0, rel=1e-6)


def test_elsewhere_and_without_a_trace(sr):
    """0.0 on a traced step of another model (BERT's: no ``mla/attn``),
    None where there is nothing to read: no trace, too few runs of the
    step, or no published peak."""
    mod = reader("mla_attn_roofline")
    _plant(sr, planted.STEP)
    assert mod.read(planted.TRACE, RUN) == 0.0
    assert mod.read(None, RUN) is None
    _plant(sr, KANANA_STEP, runs=2)
    assert mod.read(planted.TRACE, RUN) is None
    _plant(sr, KANANA_STEP, rate=0)
    assert mod.read(planted.TRACE, RUN) is None


@pytest.mark.parametrize("entry", ENTRIES, ids=[m["name"] for m in ENTRIES])
def test_proposed_entry(entry):
    """The reader says of itself what its entry says, under the rules
    ``test_manifest`` holds entries to; and the metric is wired all the way
    or not at all: its entry in ``BENCHMARK.json``, its reader in
    ``layer_metrics/`` and its name in both cells' lists, or none of
    them."""
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    mod = reader(entry["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert (entry["source"], entry["better"], entry["unit"]) == (
        "device_trace", "higher", "%")
    assert entry["workloads"] == [KIMI, CELL]
    # the layer's name is the accepted metric's of the same layer
    accepted = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert entry["layer"] == accepted["mla_ms"]["layer"]
    assert entry["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    for cell in entry["workloads"]:
        own = json.loads((BENCH / "workloads" / (cell + ".json")).read_text())
        assert {entry["name"] in accepted,
                (BENCH / "layer_metrics" / (entry["name"] + ".py")).exists(),
                entry["name"] in own["per_layer"]} == {bool(WIRED)}


def test_the_cell_joins_the_four_accepted_lists_with_the_reader():
    """Waiting: the proposal names the seven accepted metrics, none of which
    lists the cell yet, and the cell reports the seven common metrics alone;
    wired: the seven list it, the cell reports them and the reader, and the
    proposal and its directory are gone."""
    accepted = {m["name"]: m for m in MANIFEST["per_layer"]}
    common = {name for name, m in accepted.items() if "workloads" not in m}
    own = json.loads((BENCH / "workloads" / (CELL + ".json")).read_text())
    waiting = sorted(p.stem for p in (BENCH / "proposed_kanana2_readers")
                     .glob("*.py"))
    if WIRED:
        assert all(CELL in accepted[name]["workloads"] for name in APPENDED)
        assert set(own["per_layer"]) == common | set(APPENDED) | set(NEW)
        assert waiting == [] and not PROPOSAL.exists()
    else:
        assert WAITING["appended_to"] == {name: CELL for name in APPENDED}
        assert not any(CELL in accepted[name]["workloads"]
                       for name in APPENDED)
        assert set(own["per_layer"]) == common and len(common) == 7
        assert waiting == NEW
