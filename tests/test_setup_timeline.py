"""prof.compile_watch's set-up timeline: every monitoring event a span on
the ``time.perf_counter()`` clock, the old sums a fold over them, the
cache's answer on each compile, the package's import as spans, and the
anchor that lays them over a profile."""

import contextlib
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import prof
from apex_tpu.prof import compile_watch as cw
from apex_tpu.prof import xplane

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
WROTE = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh(monkeypatch):
    """A timeline of the test's own behind the module's listeners, and a
    clock the test moves."""
    tl = cw.Timeline(capacity=64)
    monkeypatch.setattr(cw, "_timeline", tl)
    now = [1000.0]
    monkeypatch.setattr(cw.time, "perf_counter", lambda: now[0])

    def fire(event, secs=None, gap=1.0, inside=(), **kw):
        """One event as JAX fires it: a duration begins ``gap`` seconds
        after whatever came before it, with a scalar event, and ends
        ``secs`` later, once those ``inside`` it have begun and ended."""
        if secs is None:
            cw._on_event(event, **kw)
        elif event not in (TRACE, LOWER, COMPILE):
            cw._on_duration(event, secs, **kw)
        else:
            now[0] += gap
            ends = now[0] + secs
            cw._on_begin(event, 0.0, **kw)
            for inner in inside:
                fire(inner[0], inner[1], gap=0.0, inside=inner[3:4] and inner[3],
                     **inner[2])
            assert now[0] <= ends, "the inner events outlast the outer"
            now[0] = ends
            cw._on_duration(event, secs, **kw)
    tl.fire = fire
    return tl


class OldSums:
    """``compile_watch``'s counters as the parent commit kept them: four
    additions an event."""

    KEYS = {TRACE: ("traces", "trace_secs"), LOWER: ("lowerings",
            "lower_secs"), COMPILE: ("compiles", "compile_secs")}

    def __init__(self):
        self.sums = {"traces": 0, "lowerings": 0, "compiles": 0,
                     "trace_secs": 0.0, "lower_secs": 0.0,
                     "compile_secs": 0.0, "autotune_compiles": 0,
                     "autotune_secs": 0.0, "cache_hits": 0}

    def fire(self, event, secs=None):
        if event == HIT:
            self.sums["cache_hits"] += 1
        if event in self.KEYS:
            n, s = self.KEYS[event]
            self.sums[n] += 1
            self.sums[s] += secs
            if event == COMPILE and cw.in_autotune():
                self.sums["autotune_compiles"] += 1
                self.sums["autotune_secs"] += secs


#: a cold program (trace, lower, a compile the cache misses and stores),
#: one the cache answers, one under the runtime's thresholds, an
#: autotuner's candidate; seconds that add without rounding
SCRIPT = [
    (TRACE, 0.5, "step"), (LOWER, 0.25, "jit(step)"), (ASKED, None, None),
    (WROTE, None, None), (COMPILE, 8.0, "jit(step)"),
    (TRACE, 0.125, "loss"), (LOWER, 0.0625, "jit(loss)"),
    (ASKED, None, None), (HIT, None, None), (SAVED, 3.5, None),
    (RETRIEVAL, 0.5, None), (COMPILE, 0.75, "jit(loss)"),
    (TRACE, 0.03125, "add"), (LOWER, 0.015625, "jit(add)"),
    (ASKED, None, None), (COMPILE, 0.25, "jit(add)"),
    (TRACE, 1.0, "candidate"), (LOWER, 0.5, "jit(candidate)"),
    (COMPILE, 2.0, "jit(candidate)"),
]


def play(tl, old=None):
    for event, secs, program in SCRIPT:
        kw = {"fun_name": program} if program else {}
        scope = (cw.autotune_scope() if program and "candidate" in program
                 else contextlib.nullcontext())
        with scope:
            tl.fire(event, secs, **kw)
            if old is not None:
                old.fire(event, secs)


def test_every_event_is_one_span_of_the_reported_length(fresh):
    jax.config.update("jax_compilation_cache_dir", "/nonexistent/cache")
    try:
        play(fresh)
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
    spans = fresh.snapshot()["spans"]
    durations = [(e, s, p) for e, s, p in SCRIPT
                 if e in (TRACE, LOWER, COMPILE)]
    assert len(spans) == len(durations) == 12
    names = {TRACE: "trace", LOWER: "lower", COMPILE: "compile"}
    ids = set()
    for span, (event, secs, program) in zip(spans, durations):
        assert span["name"] == names[event] and span["program"] == program
        assert span["end"] - span["start"] == pytest.approx(secs, abs=1e-9)
        assert span["seconds"] == secs and span["cause"] is None
        ids.add(span["id"])
    assert len(ids) == 12
    # the clock is time.perf_counter's: a span ends when its event fired
    assert spans[0]["end"] == 1001.5 and spans[-1]["end"] > spans[0]["end"]
    cache = [s for s in spans if s["name"] == "compile"]
    assert [s["cache"] for s in cache] == ["miss", "hit", "miss", "off"]
    assert cache[0]["stored"] is True and cache[2]["stored"] is False
    assert (cache[1]["retrieval_s"], cache[1]["saved_s"]) == (0.5, 3.5)
    assert cache[3]["autotune"] is True
    assert all("cache" not in s for s in spans if s["name"] != "compile")


def test_without_a_cache_directory_every_compile_reads_off(fresh):
    assert jax.config.jax_compilation_cache_dir is None
    play(fresh)
    assert {s["cache"] for s in fresh.snapshot()["spans"]
            if s["name"] == "compile"} == {"off", "hit"}


@pytest.mark.parametrize("capacity", [64, 8, 3])
def test_the_counters_are_the_old_sums(fresh, monkeypatch, capacity):
    """The fold over the list and what wrapped away gives what four
    additions an event gave, key for key and digit for digit, whether or
    not the list wrapped."""
    tl = cw.Timeline(capacity=capacity)
    tl.fire = fresh.fire
    monkeypatch.setattr(cw, "_timeline", tl)
    old = OldSums()
    play(tl, old)
    play(tl, old)
    assert cw.global_counters() == old.sums
    assert list(cw.global_counters()) == list(old.sums)
    # a hit whose compile has not ended yet counts already, as it did
    tl.fire(ASKED)
    tl.fire(HIT)
    old.fire(HIT)
    assert cw.global_counters() == old.sums
    assert prof.global_counters is cw.global_counters


def test_the_list_is_bounded_and_counts_what_it_dropped(fresh, monkeypatch):
    small = cw.Timeline(capacity=8)
    monkeypatch.setattr(cw, "_timeline", small)
    play(fresh)                 # twelve spans into eight places
    tl = small.snapshot()
    assert len(tl["spans"]) == 8 and tl["dropped"] == 4
    assert [s["program"] for s in tl["spans"]][:2] == ["jit(loss)"] * 2
    assert cw.global_counters()["traces"] == 4       # none lost to the sums
    cw.reset_global_counters()
    assert small.snapshot()["spans"] == [] and small.dropped == 0
    assert not any(cw.global_counters().values())


def test_a_trace_inside_another_is_folded_into_it(fresh):
    """A jitted function called inside another is traced while its
    caller's trace is in flight: it is no span of its own, the caller's
    span counts it, and the sums still count both."""
    def named(name):
        return {"fun_name": name}
    fresh.fire(TRACE, 0.25, **named("other"))
    fresh.fire(TRACE, 2.0, **named("step"), inside=[
        (TRACE, 0.125, named("where")),
        (TRACE, 0.5, named("attention"), [(TRACE, 0.25, named("softmax"))]),
        # an eager op while tracing compiles a program of its own
        (TRACE, 0.0625, named("table")), (LOWER, 0.0625, named("jit(table)")),
        (COMPILE, 0.125, named("jit(table)"))])
    fresh.fire(LOWER, 1.0, **named("jit(step)"),
               inside=[(TRACE, 0.5, named("rule"))])
    spans = fresh.snapshot()["spans"]
    assert [(s["name"], s["program"], s.get("nested"),
             s.get("nested_seconds")) for s in spans] == [
        ("trace", "other", None, None),
        ("lower", "jit(table)", None, None),
        ("compile", "jit(table)", None, None),
        ("trace", "step", 4, 0.9375), ("lower", "jit(step)", 1, 0.5)]
    c = cw.global_counters()
    assert (c["traces"], c["trace_secs"]) == (7, 3.6875)
    assert (c["lowerings"], c["compiles"]) == (2, 1)
    totals = prof.setup_report().totals
    # each second once: 0.25 + 2.0 + 1.0, the eager op's inside the 2.0
    assert totals["trace_lower_s"] == pytest.approx(3.25)
    assert totals["compile_s"] == pytest.approx(0.125)
    # an event that began before the listener was there is a span too
    cw._on_duration(TRACE, 0.5, fun_name="early")
    assert fresh.snapshot()["spans"][-1]["program"] == "early"


def test_a_watched_call_is_the_cause_of_what_it_compiles():
    assert cw.install()
    watcher = prof.CompileWatcher()
    f = watcher.watch(lambda x: jnp.tanh(x) * 3.0, name="watched_f")
    before = {s["id"] for s in cw.timeline()["spans"]}
    f(jnp.ones((5, 3)))
    f(jnp.ones((5, 3)))                 # steady state: no span at all
    jax.jit(lambda x: jnp.cosh(x) - 2.0)(jnp.ones((7, 2)))
    new = [s for s in cw.timeline()["spans"] if s["id"] not in before]
    calls = [s for s in new if s["name"] == "call"]
    assert [c["program"] for c in calls] == ["watched_f"]
    call = calls[0]
    assert call["cause"] is None
    inside = [s for s in new if s["cause"] == call["id"]]
    assert {s["name"] for s in inside} == {"trace", "lower", "compile"}
    assert all(call["start"] <= s["start"] and s["end"] <= call["end"]
               for s in inside)
    # the unwatched function's three, and the eager ops of both
    outside = [s for s in new if s["cause"] is None and s is not call]
    assert {"trace", "lower", "compile"} <= {s["name"] for s in outside}
    assert all(s["program"] for s in new), "jax names every program"
    # one record, not two: the watch's counters came from the same spans
    watch = watcher["watched_f"]
    compiles = [s for s in inside if s["name"] == "compile"]
    assert watch.n_compiles == len(compiles) == 1
    assert watch.compile_secs == compiles[0]["seconds"]
    assert watch.n_lowerings == 1 and watch.trace_secs > 0
    rows = prof.setup_report().rows
    assert any(r["inside"] == "call watched_f" and r["span"] == "compile"
               for r in rows)


def test_install_reads_both_clocks_once():
    assert cw.install()
    tl = cw.timeline()
    perf_ns, epoch_ns = tl["anchor"]
    assert cw.install() and cw.timeline()["anchor"] == [perf_ns, epoch_ns]
    import time
    # the pair is one instant on two clocks: it still is, read again now
    drift = (time.time_ns() - epoch_ns) - (time.perf_counter_ns() - perf_ns)
    assert abs(drift) < 50e6
    json.dumps(tl)


def test_a_compile_caused_by_an_import_points_at_it(fresh):
    fresh.fire(TRACE, 0.25, fun_name="table")        # ends at 1001.25
    fresh.fire(COMPILE, 0.5, fun_name="jit(table)")  # 1002.25 .. 1002.75
    fresh.fire(COMPILE, 0.5, gap=20.0, fun_name="jit(later)")
    cw.record_import("pkg", 1000.5, [("a", 1002.0), ("b", 1003.0)])
    spans = {s["program"]: s for s in fresh.snapshot()["spans"]}
    assert spans["pkg"]["name"] == "import"
    assert (spans["pkg"]["start"], spans["pkg"]["end"]) == (1000.5, 1003.0)
    assert spans["pkg.a"]["cause"] == spans["pkg"]["id"]
    assert spans["table"]["cause"] == spans["pkg.a"]["id"]
    assert spans["jit(table)"]["cause"] == spans["pkg.b"]["id"]
    assert spans["jit(later)"]["cause"] is None
    report = prof.setup_report()
    assert report.totals["import_s"] == 2.5
    assert {"span": "compile", "program": "jit(table)", "seconds": 0.5,
            "cache": "off", "inside": "import/b"} in report.rows
    assert "import/b" in report.table()


SCRIPT_OF_A_PROCESS = """
    import json, sys
    import jax, jax.numpy as jnp
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import apex_tpu
    from apex_tpu import prof
    from apex_tpu.prof import compile_watch
    compile_watch.install()
    def cached_program(x):
        return jnp.sin(x) @ jnp.cos(x).T
    jax.jit(cached_program)(jnp.ones((16, 16))).block_until_ready()
    json.dump({"timeline": compile_watch.timeline(),
               "totals": prof.setup_report().totals}, sys.stdout)
"""


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """The same program in two processes, one after the other, over one
    cache directory that starts empty."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "JAX_COMPILATION_CACHE_DIR":
               str(tmp_path_factory.mktemp("compile_cache"))}
    found = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(SCRIPT_OF_A_PROCESS)],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        found.append(json.loads(r.stdout))
    return found


def test_the_second_process_reads_hit_where_the_first_read_miss(
        two_processes):
    def the_compile(run):
        return [s for s in run["timeline"]["spans"] if s["name"] == "compile"
                and s["program"] == "jit(cached_program)"]
    (cold,), (warm,) = map(the_compile, two_processes)
    assert cold["cache"] == "miss" and cold["stored"] is True
    assert warm["cache"] == "hit" and warm["retrieval_s"] > 0
    assert "saved_s" in warm
    first, second = (run["totals"] for run in two_processes)
    assert first["cache_hits"] == 0 and first["backend_compiles"] >= 1
    assert second["cache_hits"] >= 1
    assert second["backend_compiles"] < first["backend_compiles"]
    assert second["cache_retrieval_s"] >= warm["retrieval_s"]


def test_the_package_import_is_a_span_with_fourteen_children(
        two_processes):
    tl = two_processes[0]["timeline"]
    (package,) = [s for s in tl["spans"] if s["name"] == "import"]
    children = [s for s in tl["spans"] if s["name"].startswith("import/")]
    assert package["program"] == "apex_tpu" and package["cause"] is None
    import apex_tpu
    assert [c["name"] for c in children] == [
        "import/" + name for name in apex_tpu.__all__[:-1]]
    assert len(children) == 14
    assert all(c["cause"] == package["id"] for c in children)
    assert all(package["start"] <= c["start"] <= c["end"] <= package["end"]
               for c in children)
    assert sum(c["seconds"] for c in children) <= package["seconds"] + 1e-9
    # the process was there before the package: interpreter, import jax
    age = tl["process_age_at_import_s"]
    assert age is not None and 0.0 < age < 300.0
    assert two_processes[0]["totals"]["import_s"] == package["seconds"]
    # and this process, however old its list is, was told the same way
    assert cw.timeline()["process_age_at_import_s"] is not None


# ---- the anchor lays a span over a profile ----------------------------------

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


def planted_xspace(start_epoch_ns, ops_us):
    """A device plane whose ops line holds ``(start_us, us)`` events of one
    op on the file's own clock, and the ``Task Environment`` plane that
    says which epoch nanosecond that clock counts from."""
    events = b"".join(field(4, field(1, 1) + field(2, int(a * 1e6))
                            + field(3, int(us * 1e6))) for a, us in ops_us)
    device = field(2, "/device:TPU:0")
    device += field(3, field(1, 1) + field(2, "XLA Ops") + events)
    device += field(4, field(1, 1) + field(2, field(1, 1) + field(
        2, "%fusion.1 = f32[8]{0} fusion(f32[8] %p)")))
    env = field(2, "Task Environment")
    env += field(5, field(1, 1) + field(2, field(1, 1)
                                        + field(2, "profile_start_time")))
    env += field(6, field(1, 1) + field(3, start_epoch_ns))
    return field(1, device) + field(1, env)


def test_the_anchor_places_a_span_on_a_planted_profile(tmp_path):
    epoch0 = 1_791_000_000_000_000_000      # where the profile's clock starts
    perf0 = 168_000_000_000_000             # perf_counter_ns at install
    anchor = [perf0, epoch0 - 5_000_000]    # installed 5 ms before the trace
    # ops 0..100 us and 400..500 us; a compile from 150 to 350 us of the
    # profile's clock, and one long before it
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(planted_xspace(epoch0, [(0, 100), (400, 100)]))
    profile = xplane.parse_trace(str(path))
    assert profile.start_epoch_ns == epoch0

    def span(name, start_us, end_us, **kw):
        at = (perf0 + 5_000_000) / 1e9      # the profile's zero, in seconds
        return {"id": 1, "name": name, "start": at + start_us / 1e6,
                "end": at + end_us / 1e6, "program": "jit(step)",
                "cause": None, **kw}
    inside = span("compile", 150, 350, cache="miss")
    a, b = xplane.place(inside, anchor, epoch0)
    assert (a, b) == pytest.approx((150_000, 350_000), abs=100)
    timeline = {"anchor": anchor, "spans": [
        span("compile", -9e6, -8e6, cache="hit"), inside,
        span("trace", 50, 450)]}
    found = profile.spans_over_idle(timeline)
    assert [f[0]["name"] for f in found] == ["compile", "trace"]
    (_, a, b, idle_us), (_, ta, tb, trace_idle_us) = found
    assert (a, b, idle_us) == pytest.approx((150_000, 350_000, 200.0),
                                            abs=100)
    # 50..450 us: the device ran 50 us of the first op and 50 of the second
    assert (ta, tb) == pytest.approx((50_000, 450_000), abs=100)
    assert trace_idle_us == pytest.approx(300.0, abs=0.1)
    # no anchor (never installed), or a file that states no start: nothing
    assert profile.spans_over_idle({"anchor": None, "spans": [inside]}) == []


def test_the_cli_names_a_compile_inside_the_trace(tmp_path, capsys):
    from apex_tpu.prof.__main__ import main
    epoch0, perf0 = 1_791_000_000_000_000_000, 168_000_000_000_000
    d = tmp_path / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        planted_xspace(epoch0, [(0, 100), (400, 100)]))
    at = perf0 / 1e9
    (tmp_path / "timeline.json").write_text(json.dumps({
        "anchor": [perf0, epoch0], "spans": [{
            "id": 7, "name": "compile", "start": at + 150e-6,
            "end": at + 350e-6, "seconds": 200e-6, "program": "jit(late)",
            "cause": None, "cache": "miss", "stored": True}]}))
    assert main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "compile jit(late) (cache miss)" in out
    (tmp_path / "timeline.json").write_text(json.dumps(
        {"anchor": [perf0, epoch0], "spans": []}))
    assert main([str(tmp_path)]) == 0
    assert "none: nothing was imported" in capsys.readouterr().out
