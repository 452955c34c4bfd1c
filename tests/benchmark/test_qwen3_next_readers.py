"""The five readers PR 32 adds, on planted records with known answers.

They read the scopes ``models/qwen3_next.py`` and ``ops/moe.py`` name
(``gdn/``, ``gdn/scan``, ``gattn/``, ``moe/route``, and the held experts'
``moe/dispatch|experts|combine|overflow``) out of the trace's ``tf_op``
stats, through ``scope_reduce`` as PR 25's and PR 28's readers do. The
serialized XSpace and its helpers are ``test_scope_reduce.py``'s."""

import importlib.util
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmark"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = "qwen3_next.lm_s8192_b1_v19k"


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


planted = load("planted_xspace", HERE / "test_scope_reduce.py")
op, mosaic, reader = planted.op, planted.mosaic, planted.reader

FWD = "jit(step)/jvp(amp/fwd)/Qwen3Next/"
AGAIN = FWD + "checkpoint/rematted_computation/"
BWD = "jit(step)/transpose(jvp(amp/fwd))/Qwen3Next/checkpoint/"

#: one step of a two-layer decoder, scopes as the model nests them (the op's
#: own ``kda/scan`` inside the model's ``gdn/scan``): 62 us
STEP = [
    op(1, "fusion", 6.0, FWD + "layers_0/gdn/gdn/proj/qkvz_proj/dot_general"),
    op(2, "fusion", 1.0, FWD + "layers_0/gdn/gdn/conv/mul"),
    op(3, "fusion", 0.5, FWD + "layers_0/gdn/gdn/scan/broadcast_in_dim"),
    mosaic(4, 4.0, FWD + "layers_0/gdn/gdn/scan/kda/scan/apex_kda_fwd"),
    op(5, "fusion", 2.0, FWD + "layers_0/gdn/gdn/out/o_proj/dot_general"),
    op(6, "fusion", 2.0, FWD + "layers_3/gattn/gattn/proj/q_proj/dot_general"),
    op(7, "fusion", 0.5, FWD + "layers_3/gattn/gattn/rope/concatenate"),
    mosaic(8, 3.0, FWD + "layers_3/gattn/gattn/attn/apex_attn_fwd"),
    op(9, "fusion", 1.5, FWD + "layers_3/moe/moe/route/dot_general"),
    op(10, "fusion", 0.5, FWD + "layers_3/moe/moe/dispatch/gather"),
    op(11, "fusion", 2.0, FWD + "layers_3/moe/moe/experts/"
       "ecd,edf->ecf/dot_general"),
    op(12, "fusion", 0.5, FWD + "layers_3/moe/moe/combine/scatter-add"),
    op(13, "fusion", 1.0, FWD + "layers_3/moe/moe/shared/shared/up_proj/"
       "dot_general"),
    op(14, "fusion", 1.5, FWD + "layers_2/moe/jvp(moe/overflow)/while/body/"
       "dot_general"),
    op(15, "fusion", 2.5, FWD + "lm/head/dot_general"),
    op(16, "fusion", 1.0, AGAIN + "layers_3/moe/moe/route/dot_general"),
    mosaic(17, 9.0, BWD + "layers_0/gdn/gdn/scan/kda/scan/apex_kda_bwd"),
    op(18, "fusion", 7.0, BWD + "layers_0/gdn/gdn/proj/qkvz_proj/"
       "dot_general"),
    mosaic(19, 4.0, BWD + "layers_3/gattn/gattn/attn/apex_attn_bwd_dkv"),
    op(20, "fusion", 3.0, BWD + "layers_3/moe/moe/experts/"
       "ecd,edf->ecf/dot_general"),
    op(21, "fusion", 3.0, "jit(step)/amp/update/optim/adam/update/sub"),
]
EXPECTED = {"gdn_ms": 0.0295, "gdn_scan_ms": 0.0135, "gattn_ms": 0.0095,
            "moe_route_ms": 0.0025, "moe_experts_ms": 0.0075}
NEW = sorted(EXPECTED)


@pytest.fixture()
def sr(tmp_path, monkeypatch):
    mod = load("scope_reduce", BENCH / "scope_reduce.py")
    monkeypatch.setattr(mod, "OUT", str(tmp_path))
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
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    assert reader(name).read(planted.TRACE, {}) == pytest.approx(
        EXPECTED[name], rel=1e-9)


def test_the_accepted_readers_see_this_step_as_they_should(sr):
    """``moe_ms`` is the whole expert layer: router, held experts and the
    shared one; ``kda_scan_ms`` sees the op's own scope inside ``gdn/scan``
    (neither is listed for this cell)."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    assert reader("moe_ms").read(planted.TRACE, {}) == pytest.approx(
        EXPECTED["moe_route_ms"] + EXPECTED["moe_experts_ms"] + 0.001)
    assert reader("kda_scan_ms").read(planted.TRACE, {}) == pytest.approx(
        0.013)
    assert reader("mla_ms").read(planted.TRACE, {}) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_scope_reader_elsewhere(sr, name):
    """0.0 on a traced step of another model (BERT's), None where there is
    nothing to read: no trace, or a program whose reader has no stats."""
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0",
                                                 planted.STEP, runs=5))
    assert reader(name).read(planted.TRACE, {}) == 0.0
    assert reader(name).read(None, {}) is None
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=2))
    assert reader(name).read(planted.TRACE, {}) is None


@pytest.mark.parametrize("name", NEW)
def test_entry_and_reader_agree(name):
    """The manifest's entry says of the reader what the reader says of
    itself, names the cell that can report it, and reads the device trace."""
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    mod = reader(name)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    assert entry["workloads"] == [CELL]
    lines = (BENCH / "layer_metrics" / (name + ".py")).read_text().count("\n")
    assert lines <= 22


def test_the_new_cells_list_the_seven_common_metrics():
    common = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    assert len(common) == 7
    for cell in (CELL, "bert_large.mlm_s128_b64"):
        own = json.loads((BENCH / "workloads" / (cell + ".json")).read_text())
        assert common <= set(own["per_layer"])
    assert set(json.loads((BENCH / "workloads" / (CELL + ".json"))
                          .read_text())["per_layer"]) == common | set(NEW)
