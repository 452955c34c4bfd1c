"""The five readers PR 28 adds, on planted records with known answers.

They read the scopes ``models/kimi_linear.py`` names (``kda/``, ``kda/scan``,
``mla/``, ``moe/``, ``moe/overflow``) out of the trace's ``tf_op`` stats,
through ``scope_reduce`` as PR 25's readers do. The serialized XSpace and its
helpers are ``test_scope_reduce.py``'s."""

import importlib.util
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmark"
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


planted = load("planted_xspace", HERE / "test_scope_reduce.py")
op, mosaic, reader = planted.op, planted.mosaic, planted.reader

FWD = "jit(step)/jvp(amp/fwd)/KimiLinear/"
AGAIN = FWD + "checkpoint/rematted_computation/"
BWD = "jit(step)/transpose(jvp(amp/fwd))/KimiLinear/checkpoint/"
OVER = "layers_2/moe/jvp(moe/overflow)/while/body/dot_general"

#: one step of a two-layer decoder, with scopes as the v5e's compiler wrote
#: them for the real one: 60 us
STEP = [
    op(1, "fusion", 6.0, FWD + "layers_0/kda/kda/proj/q_proj/dot_general"),
    op(2, "fusion", 1.0, FWD + "layers_0/kda/kda/conv/mul"),
    op(3, "while", 4.0, FWD + "layers_0/kda/kda/scan/while"),
    op(4, "fusion", 2.0, FWD + "layers_0/kda/kda/out/o_proj/dot_general"),
    op(5, "fusion", 3.0, FWD + "layers_0/mlp/gate_proj/dot_general"),
    op(6, "fusion", 2.0, FWD + "layers_3/mla/mla/proj/kv_b/dot_general"),
    mosaic(7, 3.0, FWD + "layers_3/mla/mla/attn/apex_attn_fwd"),
    op(8, "fusion", 0.5, FWD + "layers_3/moe/moe/route/dot_general"),
    op(9, "fusion", 0.5, FWD + "layers_3/moe/moe/dispatch/"
       "gather"),
    op(10, "fusion", 2.0, FWD + "layers_3/moe/moe/experts/"
       "ecd,edf->ecf/dot_general"),
    op(11, "fusion", 1.0, FWD + "layers_3/moe/moe/shared/shared/up_proj/"
       "dot_general"),
    op(12, "fusion", 1.5, FWD + OVER),
    op(13, "fusion", 2.5, FWD + "lm/head/dot_general"),
    op(14, "while", 5.0, AGAIN + "layers_0/kda/kda/scan/while"),
    op(15, "while", 9.0, BWD + "layers_0/kda/kda/scan/while"),
    op(16, "fusion", 7.0, BWD + "layers_0/kda/kda/proj/q_proj/dot_general"),
    mosaic(17, 4.0, BWD + "layers_3/mla/mla/attn/apex_attn_bwd_dkv"),
    op(18, "fusion", 3.0, BWD + "layers_3/moe/moe/experts/"
       "ecd,edf->ecf/dot_general"),
    op(19, "fusion", 3.0, "jit(step)/amp/update/optim/adam/update/sub"),
]
EXPECTED = {"kda_ms": 0.034, "kda_scan_ms": 0.018, "mla_ms": 0.009,
            "moe_ms": 0.0085, "moe_overflow_ms": 0.0015}
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


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_scope_reader_on_planted_records(sr, name):
    sr.plant(planted.HOST + planted.device_plane("/device:TPU:0", STEP,
                                                 runs=5))
    assert reader(name).read(planted.TRACE, {}) == pytest.approx(
        EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
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
    assert entry["source"] == "device_trace"
    assert entry["workloads"] == ["kimi_linear.lm_s8192_b1"]
    lines = (BENCH / "layer_metrics" / (name + ".py")).read_text().count("\n")
    assert lines <= 22
