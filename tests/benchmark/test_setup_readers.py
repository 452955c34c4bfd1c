"""The four readers of the set-up timeline, on planted timelines with known
answers, and their proposed ``BENCHMARK.json`` entries held to what
``test_manifest.py::test_per_layer_metric`` holds an accepted entry to."""

import importlib.util
import json
import pathlib

import pytest

from apex_tpu.prof import compile_watch

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
PROPOSED = json.loads(
    (BENCH / "proposed_setup_per_layer.json").read_text())["per_layer"]
NAMES = ["setup_import_s", "setup_trace_lower_s", "setup_compile_s",
         "setup_backend_compiles"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, BENCH / "proposed_setup_readers" / (name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plant(monkeypatch, spans, installed=True):
    """A timeline of the test's own behind ``compile_watch``: ``spans`` as
    ``(name, start, seconds, program, fields)``."""
    tl = compile_watch.Timeline()
    for name, start, seconds, program, fields in spans:
        tl.add(name, seconds, end=start + seconds, program=program, **fields)
    monkeypatch.setattr(compile_watch, "_timeline", tl)
    monkeypatch.setattr(compile_watch, "_installed", installed)


def run(cache, step_compile_s, **more):
    """One run's set-up: the package's import, three programs traced and
    lowered (the step's trace holds a nested one), their compile requests."""
    spans = [("trace", 20.0, 6.0, "step", {"nested": 1,
                                           "nested_seconds": 0.5}),
             ("lower", 26.0, 2.0, "jit(step)", {}),
             ("compile", 28.0, step_compile_s, "jit(step)",
              {"cache": cache, **more}),
             ("trace", 10.0, 1.0, "init", {}),
             ("lower", 11.0, 0.5, "jit(init)", {}),
             ("compile", 11.5, 0.25, "jit(init)", {"cache": cache, **more}),
             # an eager op's compile inside the step's trace
             ("compile", 21.0, 0.125, "jit(add)", {"cache": "off"})]
    return spans


WARM = run("hit", 3.0, retrieval_s=2.5, saved_s=50.0)
COLD = run("miss", 55.0, stored=True)
#: name -> (warm, cold, nothing recorded but the import)
EXPECTED = {
    "setup_import_s": (2.5, 2.5, 2.5),
    "setup_trace_lower_s": (9.5, 9.5, 0.0),
    "setup_compile_s": (3.375, 55.375, 0.0),
    "setup_backend_compiles": (1, 3, 0),     # the eager op's is never a hit
}


@pytest.fixture()
def planted(monkeypatch):
    def with_import(spans, **kw):
        plant(monkeypatch, spans, **kw)
        compile_watch.record_import("apex_tpu", 5.0, [("amp", 6.0),
                                                      ("ops", 7.5)])
    return with_import


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_planted_timelines(name, planted):
    mod = reader(name)
    for spans, want in zip((WARM, COLD, []), EXPECTED[name]):
        planted(spans)
        got = mod.read(None, {})
        assert got == pytest.approx(want) and type(got) is type(want), name


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_where_nothing_was_installed(name, planted,
                                                          monkeypatch):
    planted(WARM, installed=False)
    assert reader(name).read(None, {}) is None
    # nor from a program of before this timeline: no such function
    planted(WARM)
    monkeypatch.delattr(compile_watch, "setup_report")
    assert reader(name).read(None, {}) is None


def test_an_import_that_wrapped_away_reads_nothing(monkeypatch):
    plant(monkeypatch, WARM)
    assert reader("setup_import_s").read(None, {}) is None
    assert reader("setup_compile_s").read(None, {}) == pytest.approx(3.375)


def test_the_proposal_names_the_four_readers_in_order():
    assert [m["name"] for m in PROPOSED] == NAMES
    accepted = {m["name"] for m in MANIFEST["per_layer"]
                + MANIFEST["end_to_end"]}
    assert not accepted & set(NAMES), "wired: delete the proposal file"


@pytest.mark.parametrize("metric", PROPOSED, ids=NAMES)
def test_proposed_entry_is_a_valid_per_layer_entry(metric):
    import test_manifest as tm
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves"}
    assert tm.NAME.match(metric["name"]) and tm.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in tm.SOURCES and tm.line(metric["layer"])
    assert metric["source"] in ("program_span", "program_counter")
    assert metric["moves"] == "setup_s"
    assert metric["moves"] in tm.ids(tm.END_TO_END)
    # set-up is reported by every cell, so the entries need no `workloads`
    moved = {m["name"]: m for m in tm.END_TO_END}[metric["moves"]]
    assert set(tm.reported_by(metric)) <= set(tm.reported_by(moved))
    mod = reader(metric["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        metric["unit"], metric["layer"], metric["moves"])
    assert callable(mod.read)
