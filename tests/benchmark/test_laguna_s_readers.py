"""The three readers of the window cell, on planted records with known
answers, and their proposed ``BENCHMARK.json`` entries.

Two sum the device time of the scopes ``models/laguna.py`` names (``swa/``,
``fullattn/``) out of the trace's ``tf_op`` stats, through ``scope_reduce``
as the accepted scope readers do; the third divides the operations the
window's band requires, by the sizes in the cell's own files and the run's
global batch, by the time of the Mosaic calls under ``swa/attn`` and the
chip's bf16 peak. The readers wait in ``benchmark/proposed_laguna_readers/``
(``benchmark/proposed_laguna_per_layer.json`` says why and what wires
them); every test here holds in both states, waiting and wired, so wiring
them edits nothing in this file. The serialized XSpace and its helpers are
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
CELL = "laguna_s.lm_s4096_b1_v12k"
NEW = ["swa_ms", "fullattn_ms", "swa_attn_roofline"]
#: the entries: ``BENCHMARK.json``'s once they are wired, else the proposal's
WIRED = [m for m in MANIFEST["per_layer"] if m["name"] in NEW]
PROPOSAL = BENCH / "proposed_laguna_per_layer.json"
ENTRIES = WIRED or json.loads(PROPOSAL.read_text())["per_layer"]


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
        path = BENCH / "proposed_laguna_readers" / (name + ".py")
    mod = load("laguna_reader_" + name, path)
    if not WIRED and hasattr(mod, "listed_cells"):
        listed = {m["name"]: m["workloads"] for m in ENTRIES}[name]
        mod.listed_cells = lambda: listed
    return mod


FWD = "jit(step)/jvp(amp/fwd)/Laguna/"
AGAIN = FWD + "checkpoint/rematted_computation/"
BWD = "jit(step)/transpose(jvp(amp/fwd))/Laguna/checkpoint/"
#: the cell's band: 72 q heads of 128 in 3 window layers, 4096 tokens a
#: sequence, a window of 512 (from its files), one sequence a step
PAIRS = 512 * 513 // 2 + (4096 - 512) * 512
RUN = {"global_batch": 1}
#: the planted window kernels take 6 us a step, and ``PEAK`` is the rate
#: at which the band's required operations take just that
FLOOR_US = 6.0
PEAK = 12 * PAIRS * 128 * 72 * 3 / (FLOOR_US * 1e-6)
SWA = "layers_1/swa/swa/"
FULL = "layers_0/fullattn/fullattn/"
STEP = [
    op(1, "fusion", 2.0, FWD + FULL + "proj/q_proj/dot_general"),
    op(2, "fusion", 0.5, FWD + FULL + "rope/concatenate"),
    mosaic(3, 3.0, FWD + FULL + "attn/apex_attn_fwd"),
    op(4, "fusion", 1.0, FWD + FULL + "out/o_proj/dot_general"),
    op(5, "fusion", 4.0, FWD + "layers_0/mlp/up_proj/dot_general"),
    op(6, "fusion", 2.5, FWD + SWA + "proj/q_proj/dot_general"),
    op(7, "fusion", 0.5, FWD + SWA + "rope/concatenate"),
    # the repeat of the k/v heads: under the scope, not a Mosaic call
    op(8, "fusion", 0.25, FWD + SWA + "attn/broadcast_in_dim"),
    mosaic(9, FLOOR_US / 4, FWD + SWA + "attn/apex_attn_fwd"),
    op(10, "fusion", 1.5, FWD + SWA + "out/g_proj/dot_general"),
    op(11, "fusion", 1.5, FWD + "layers_1/moe/moe/route/dot_general"),
    op(12, "fusion", 2.5, FWD + "lm/head/dot_general"),
    op(13, "fusion", 2.5, AGAIN + SWA + "proj/q_proj/dot_general"),
    mosaic(14, FLOOR_US / 4, BWD + SWA + "attn/apex_attn_bwd_dq"),
    mosaic(15, FLOOR_US / 2, BWD + SWA + "attn/apex_attn_bwd_dkv"),
    op(16, "fusion", 0.25, BWD + SWA + "attn/reduce"),
    op(17, "fusion", 5.0, BWD + SWA + "proj/q_proj/dot_general"),
    mosaic(18, 4.0, BWD + FULL + "attn/apex_attn_bwd_dkv"),
    op(19, "fusion", 3.0, "jit(step)/amp/update/optim/adam/update/sub"),
]
EXPECTED = {"swa_ms": (2.5 + 0.5 + 0.25 + 1.5 + 2.5 + 0.25 + 5.0 + FLOOR_US)
            / 1e3,
            "fullattn_ms": (2.0 + 0.5 + 3.0 + 1.0 + 4.0) / 1e3,
            "swa_attn_roofline": 100.0}


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
    """The roofline reader on a trace at the floor (the three window
    kernels take the time the band's operations take at the peak) reads 100
    and no more: what the kernels do beyond the band is in the time, not in
    the numerator."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    value = reader(name).read(planted.TRACE, RUN)
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)
    if name == "swa_attn_roofline":
        assert value <= 100.0 + 1e-6


def test_the_required_operations_are_the_bands():
    """12 operations a kept pair, a channel and a q head; the band's pairs
    from the cell's files: 1 966 336 at 4096 tokens and a window of 512."""
    mod = reader("swa_attn_roofline")
    assert mod.band_pairs(4096, 512) == PAIRS == 1_966_336
    assert mod.band_pairs(300, 512) == 300 * 301 // 2
    assert mod.required_flops(PAIRS, 72, 128, 3, 1) == \
        12 * 1_966_336 * 128 * 72 * 3
    assert mod.listed_cells() == [CELL]
    assert mod.cell_shape() == (PAIRS, 72, 128, 3)
    if not WIRED:       # the reader itself reads BENCHMARK.json alone
        unwired = load("laguna_reader_unwired", BENCH /
                       "proposed_laguna_readers" / "swa_attn_roofline.py")
        assert unwired.listed_cells() == [] and unwired.cell_shape() is None


def test_slower_kernels_read_under_100(sr):
    """Twice the time reads 50; and only the Mosaic calls count, so the
    repeat of the k/v heads under the same scope takes no part."""
    twice = [(text, us * 2 if "tpu_custom_call" in text
              and "/swa/" in stats["tf_op"] else us, stats)
             for text, us, stats in STEP]
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", twice,
                                                 runs=5))
    assert reader("swa_attn_roofline").read(planted.TRACE, RUN) == \
        pytest.approx(50.0, rel=1e-6)
    assert reader("swa_attn_roofline").read(planted.TRACE, {
        "global_batch": 2}) == pytest.approx(100.0, rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_scope_reader_elsewhere(sr, name):
    """0.0 on a traced step of another model (BERT's: the scopes are
    absent), None where there is nothing to read: no trace, or too few runs
    of the step."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0",
                                                 planted.STEP, runs=5))
    assert reader(name).read(planted.TRACE, RUN) == 0.0
    assert reader(name).read(None, RUN) is None
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=2))
    assert reader(name).read(planted.TRACE, RUN) is None


def test_no_peak_no_share(sr, monkeypatch):
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    monkeypatch.setattr(sr, "published_peak", lambda key: None)
    assert reader("swa_attn_roofline").read(planted.TRACE, RUN) is None


def test_the_accepted_readers_do_not_read_these_scopes(sr):
    """``gqa_ms`` and ``gattn_ms`` read their own models' scopes: 0.0 here."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    for name in ("gqa_ms", "gattn_ms"):
        assert planted.reader(name).read(planted.TRACE, RUN) == 0.0


@pytest.mark.parametrize("entry", ENTRIES, ids=[m["name"] for m in ENTRIES])
def test_proposed_entry(entry):
    """Each reader says of itself what its entry says, under the rules
    ``test_manifest`` holds entries to; and the metric is wired all the way
    or not at all: its entry in ``BENCHMARK.json``, its reader in
    ``layer_metrics/`` and its name in the cell's list, or none of them."""
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    mod = reader(entry["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["source"] == "device_trace"
    assert entry["better"] == ("higher" if "roofline" in entry["name"]
                               else "lower")
    assert entry["workloads"] == [CELL]
    assert entry["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    own = json.loads((BENCH / "workloads" / (CELL + ".json")).read_text())
    assert {entry["name"] in {m["name"] for m in MANIFEST["per_layer"]},
            (BENCH / "layer_metrics" / (entry["name"] + ".py")).exists(),
            entry["name"] in own["per_layer"]} == {bool(WIRED)}
    if entry["name"].endswith("_roofline"):
        assert entry["unit"] == "%"


def test_the_proposal_names_the_three_readers():
    """Waiting: the proposal and its directory hold the three; wired, both
    are gone."""
    assert [m["name"] for m in ENTRIES] == NEW
    waiting = sorted(p.stem for p in (BENCH / "proposed_laguna_readers")
                     .glob("*.py"))
    assert waiting == ([] if WIRED else sorted(NEW))
    assert PROPOSAL.exists() != bool(WIRED)
