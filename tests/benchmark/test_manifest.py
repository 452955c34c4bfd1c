"""BENCHMARK.json against the benchmark's contract and against the files
the harness finds by name. Nothing here touches JAX."""

import importlib.util
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

CELLS = MANIFEST["workloads"]
CONFIGS = MANIFEST["configs"]
END_TO_END = MANIFEST["end_to_end"]
PER_LAYER = MANIFEST["per_layer"]


def ids(entries):
    return [e["name"] for e in entries]


def line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, BENCH / "layer_metrics" / (name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reported_by(metric):
    return metric.get("workloads", ids(CELLS))


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(CONFIGS) <= 24 and 1 <= len(CELLS) <= 24
    assert 1 <= len(END_TO_END) <= 16 and 1 <= len(PER_LAYER) <= 128
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in MANIFEST["paths"])
    command = MANIFEST["command"]
    assert len(command) <= 32 and all(line(w) for w in command)
    assert command[1].startswith(MANIFEST["paths"][0] + "/")
    assert (ROOT / command[1]).is_file()


def test_run_seconds_fits_a_full_check_of_24_cells():
    seconds = MANIFEST["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_are_unique():
    for entries in (CONFIGS, CELLS, END_TO_END + PER_LAYER):
        assert len(set(ids(entries))) == len(entries)
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(set(pairs)) == len(pairs)
    files = [c["file"] for c in CONFIGS]
    assert len(set(files)) == len(files)


def test_files_under_paths_have_plain_names():
    for top in MANIFEST["paths"]:
        for f in (ROOT / top).rglob("*"):
            rel = f.relative_to(ROOT).as_posix()
            if any(part in ("__pycache__", ".out") for part in f.parts):
                continue
            assert PATH.match(rel), rel


@pytest.mark.parametrize("config", CONFIGS, ids=ids(CONFIGS))
def test_config(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert line(config["source"]) and line(config["why"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert config["name"] in {c["config"] for c in CELLS}, "used by no cell"
    sizes = json.loads((ROOT / config["file"]).read_text())
    assert sizes["source"] == config["source"]
    # the file says why each reduced key differs, and names no width
    assert sorted(sizes["reduced"]) == sorted(config["reduced"])
    width = re.compile(r"(hidden_size|intermediate|latent|state|head_dim|"
                       r"_dim$|_rank$|expansion|experts_per_tok)")
    assert not [k for k in config["reduced"] if width.search(k)]
    assert "toy" in sizes and "assumed" in sizes
    assert (BENCH / "configs" / (config["name"] + ".py")).is_file()


@pytest.mark.parametrize("cell", CELLS, ids=ids(CELLS))
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), key
    assert line(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["config"] in ids(CONFIGS)
    traffic = json.loads(
        (BENCH / "traffic" / (cell["traffic"] + ".json")).read_text())
    assert {"per_chip_batch", "pool", "arrays", "loss_band", "toy"} <= set(
        traffic)
    # the harness reads the cell from its own file: the two must agree
    own = json.loads(
        (BENCH / "workloads" / (cell["name"] + ".json")).read_text())
    assert {k: own[k] for k in ("config", "traffic", "chips", "why")} == {
        k: cell[k] for k in ("config", "traffic", "chips", "why")}
    assert sorted(own["per_layer"]) == sorted(
        m["name"] for m in PER_LAYER if cell["name"] in reported_by(m))
    assert own["per_layer"], "a cell reports at least one per-layer metric"


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(c["chips"] == 4 for c in CELLS)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", END_TO_END, ids=ids(END_TO_END))
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(reported_by(metric)) <= set(ids(CELLS))


def test_every_cell_reports_setup_and_another_end_to_end_metric():
    assert "setup_s" in ids(END_TO_END)
    for cell in ids(CELLS):
        mine = [m["name"] for m in END_TO_END if cell in reported_by(m)]
        assert "setup_s" in mine and len(mine) >= 2, cell


@pytest.mark.parametrize("metric", PER_LAYER, ids=ids(PER_LAYER))
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES and line(metric["layer"])
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    # the metric it moves is reported in every cell that reports this one
    moved = {m["name"]: m for m in END_TO_END}[metric["moves"]]
    assert set(reported_by(metric)) <= set(reported_by(moved))
    # its reader says the same of itself
    mod = reader(metric["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        metric["unit"], metric["layer"], metric["moves"])
    assert callable(mod.read)


def test_the_chip_has_published_peaks():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["source"]
    v5e = peaks["chips"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
