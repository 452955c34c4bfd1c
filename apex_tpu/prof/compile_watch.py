"""Compilation observability: the set-up timeline, trace/lower/compile
counters and the retrace detector.

A silent shape-induced retrace can eat minutes per step with no signal
in any existing sink — the step "just got slow" — and a job's set-up is
minutes of host work nothing attributes. This module watches the
compile pipeline from two directions:

- **the process-wide timeline** via ``jax.monitoring`` events
  (``/jax/core/compile/*_duration``, ``/jax/compilation_cache/*``):
  every trace, MLIR lowering and compile request in the process is one
  span on the ``time.perf_counter()`` clock, with the program's name
  and what the persistent cache answered, whether or not the function
  is wrapped; ``import apex_tpu`` hands over its own spans
  (:func:`install`, :func:`timeline`, :func:`setup_report`). The
  counters (:func:`global_counters` — ``bench.py`` reports
  ``n_compiles`` from this) are a fold over the spans. Builds without
  the monitoring API degrade to the wrapper fallback below.
- **per-function watch** via :meth:`CompileWatcher.watch`: wraps a
  (jitted) function and, per call, detects a new trace from the jit
  cache size (exact; signature diffing is the fallback for callables
  without a cache), records the compile wall time as a
  ``kind="compile"`` span in the active :class:`apex_tpu.trace.Tracer`,
  diffs the argument shape/dtype signature against the previous trace to
  name **which argument changed**, and — after ``warn_after`` retraces
  of the same function — warns through ``warnings`` and the registered
  monitor callbacks (``MetricsLogger.record_memory`` takes the emitted
  ``kind="retrace"`` events; ``check_metrics_schema.py --kind memory``
  validates them). A dispatch that traced is a ``call`` span of the
  timeline, and the ``cause`` of what was compiled inside it.

The watch wrapper never changes the compiled program — the jitted
callable, its trace cache, and its donation/sharding behavior are the
wrapped function's own (the ``memory/no-extra-dispatch`` compile-check
case pins bit-identical HLO).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import os
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

__all__ = ["CompileWatcher", "FunctionWatch", "SetupReport", "Timeline",
           "install", "installed", "global_counters",
           "reset_global_counters", "watch", "autotune_scope",
           "in_autotune", "record_import", "timeline", "setup_report"]

# --- the set-up timeline -----------------------------------------------------

# jax fires the backend-compile duration event around
# compile_or_get_cached, so a "compile" span is a compile REQUEST: a
# backend compile or a load from the persistent cache, whichever the
# request got; its ``cache`` field says which
_SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
# span name -> its count and its seconds in global_counters()
_SUMS_OF_SPAN = {"trace": ("traces", "trace_secs"),
                 "lower": ("lowerings", "lower_secs"),
                 "compile": ("compiles", "compile_secs")}
_ZERO_SUMS = {"traces": 0, "lowerings": 0, "compiles": 0,
              "trace_secs": 0.0, "lower_secs": 0.0, "compile_secs": 0.0,
              "autotune_compiles": 0, "autotune_secs": 0.0,
              "cache_hits": 0}
# what the persistent cache says of the compile request in flight on a
# thread, between the request's first event and its duration event
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
    # fired where the runtime goes on to write the entry: a miss without
    # it is an entry the runtime would not store (under its thresholds)
    "/jax/compilation_cache/cache_misses": "stored",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
SPAN_CAPACITY = 4096


def _add_to_sums(sums: Dict[str, float], span: Dict[str, Any]) -> None:
    keys = _SUMS_OF_SPAN.get(span["name"])
    if keys is None:
        return
    sums[keys[0]] += 1
    sums[keys[1]] += span["seconds"]
    sums["traces"] += span.get("nested", 0)
    sums["trace_secs"] += span.get("nested_seconds", 0.0)
    if span.get("autotune"):
        sums["autotune_compiles"] += 1
        sums["autotune_secs"] += span["seconds"]
    if span.get("cache") == "hit":
        sums["cache_hits"] += 1


class Timeline:
    """Spans on the ``time.perf_counter()`` clock, in one bounded list.

    A span is a dict: ``id``, ``name`` (``import``, ``import/<sub>``,
    ``trace``, ``lower``, ``compile``, ``call``), ``start`` and ``end``
    in ``time.perf_counter()`` seconds, ``seconds`` as reported (``start``
    is ``end - seconds``), ``program`` (the function's name, or None) and
    ``cause`` (the id of the span it happened inside, or None). A jitted
    function called inside another is traced inside its caller's
    ``trace`` span, thousands of times in one step of a model, and a
    lowering rule may trace too: a ``trace`` that ends while another
    ``trace``, ``lower`` or ``compile`` is in flight on its thread is no
    span of its own; the span round it holds their number as ``nested``
    and their sum as ``nested_seconds``. A
    ``compile`` span also says what the persistent cache answered:
    ``cache`` is ``hit``, ``miss`` or ``off``, with ``retrieval_s`` and
    ``saved_s`` on a hit and ``stored`` on a miss, and ``autotune`` where
    it was fired under :func:`autotune_scope`.

    The oldest spans wrap away once ``capacity`` is reached; ``dropped``
    counts them, and their part of :meth:`counters` is kept.
    """

    def __init__(self, capacity: int = SPAN_CAPACITY):
        self._lock = threading.Lock()
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._wrapped = dict(_ZERO_SUMS)
        self._ids = itertools.count(1)
        self._cache: Dict[int, Dict[str, Any]] = {}   # by thread id
        self.dropped = 0
        #: ``(time.perf_counter_ns(), time.time_ns())`` read together at
        #: :func:`install`: what places a span on a profile's clock
        self.anchor: Optional[Tuple[int, int]] = None
        #: seconds from the start of the process to the package's
        #: ``import`` span: interpreter, ``import jax``, the TPU client
        self.process_age_at_import_s: Optional[float] = None

    def new_id(self) -> int:
        return next(self._ids)

    def add(self, name: str, seconds: float, *, end: Optional[float] = None,
            **fields) -> Dict[str, Any]:
        """Append one span that ended at ``end`` (now) and took
        ``seconds``; ``fields`` are ``program``, ``cause``, an ``id``
        handed out earlier, and what a compile span adds."""
        end = time.perf_counter() if end is None else end
        span = {"id": fields.pop("id", None) or self.new_id(), "name": name,
                "start": end - seconds, "end": end, "seconds": seconds,
                "program": None, "cause": None, **fields}
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                _add_to_sums(self._wrapped, self._spans[0])
                self.dropped += 1
            self._spans.append(span)
        return span

    def cache_said(self, key: str, value: Any = True) -> None:
        with self._lock:
            self._cache.setdefault(threading.get_ident(), {})[key] = value

    def cache_answer(self) -> Dict[str, Any]:
        """The ``cache`` fields of the compile span that ends now on this
        thread."""
        with self._lock:
            said = self._cache.pop(threading.get_ident(), {})
        if said.get("hit"):
            return {"cache": "hit", **{k: said[k] for k in
                                       _CACHE_SECONDS.values() if k in said}}
        # a request consults the cache wherever caching is enabled, with
        # or without a directory to keep entries in
        if said.get("asked") and jax.config.jax_compilation_cache_dir:
            return {"cache": "miss", "stored": said.get("stored", False)}
        return {"cache": "off"}

    def counters(self) -> Dict[str, float]:
        with self._lock:
            sums = dict(self._wrapped)
            for span in self._spans:
                _add_to_sums(sums, span)
            # a hit whose compile span has not ended yet
            sums["cache_hits"] += sum(
                1 for said in self._cache.values() if said.get("hit"))
        return sums

    def forget_compiles(self) -> None:
        """Drop every span but the imports, and the sums with them."""
        with self._lock:
            kept = [s for s in self._spans if s["name"].startswith("import")]
            self._spans.clear()
            self._spans.extend(kept)
            self._wrapped = dict(_ZERO_SUMS)
            self._cache.clear()
            self.dropped = 0

    def record_import(self, package: str, start: float,
                      children: List[Tuple[str, float]]) -> None:
        """The package's ``import`` span from ``start`` to the last
        child's end, and one ``import/<sub>`` child for each ``(sub,
        end)``, each starting where the one before it ended. What was
        traced, lowered or compiled inside a child, and has no cause
        yet, is caused by it."""
        age = _process_age_s()
        if age is not None:
            self.process_age_at_import_s = max(
                age - (time.perf_counter() - start), 0.0)
        end = children[-1][1] if children else start
        parent = self.add("import", end - start, end=end, program=package)
        inside = []
        for sub, sub_end in children:
            inside.append(self.add(f"import/{sub}", sub_end - start,
                                   end=sub_end, program=f"{package}.{sub}",
                                   cause=parent["id"]))
            start = sub_end
        with self._lock:
            for span in self._spans:
                if span["cause"] is None and span["name"] in (
                        "trace", "lower", "compile", "call"):
                    for child in inside:
                        if child["start"] <= span["start"] \
                                and span["end"] <= child["end"]:
                            span["cause"] = child["id"]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"anchor": self.anchor and list(self.anchor),
                    "process_age_at_import_s": self.process_age_at_import_s,
                    "dropped": self.dropped,
                    "spans": [dict(s) for s in self._spans]}


def _process_age_s() -> Optional[float]:
    """Seconds since this process started: its start time in
    ``/proc/self/stat`` (clock ticks since boot) against
    ``CLOCK_BOOTTIME``. None where either cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the command's name may hold spaces: count from its ")"
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, AttributeError, ValueError, IndexError):
        return None
    return age if age >= 0 else None


_lock = threading.Lock()
_installed = False
_timeline = Timeline()

# innermost-last stack of the watched dispatches in flight on this
# thread — monitoring events fired during a dispatch are attributed to
# the top of the stack
_tls = threading.local()


class _Call:
    """A watched function's dispatch in flight. It becomes a ``call``
    span, and takes an id, only if something was traced, lowered or
    compiled inside it: a steady-state step adds nothing to the list."""

    __slots__ = ("rec", "start", "id")

    def __init__(self, rec: "FunctionWatch"):
        self.rec, self.start, self.id = rec, time.perf_counter(), None

    def span_id(self) -> int:
        if self.id is None:
            self.id = _timeline.new_id()
        return self.id


def _stack() -> List[_Call]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _in_flight() -> List[List[float]]:
    """Innermost-last, one ``[nested, nested_seconds]`` for each trace,
    lowering or compile request that has begun on this thread and not
    ended: jax fires a scalar event where one begins and the duration
    event where it ends."""
    frames = getattr(_tls, "in_flight", None)
    if frames is None:
        frames = _tls.in_flight = []
    return frames


# autotune-origin marker: compiles fired while a sweep holds this flag
# are counted separately from (and in addition to) the plain compile
# counters — so a kernel autotuner's grid sweep never reads as a
# retrace storm in n_compiles (ROADMAP item 4's compile-attribution
# note; the bench JSON splits the column)
_autotune_tls = threading.local()


def in_autotune() -> bool:
    """True while an :func:`autotune_scope` is open on this thread."""
    return getattr(_autotune_tls, "depth", 0) > 0


@contextlib.contextmanager
def autotune_scope():
    """Tag every backend compile issued inside this context as
    autotune-origin (re-entrant, per-thread). The kernel autotuner's
    sweep loop wraps each candidate compile with it::

        with compile_watch.autotune_scope():
            timed = jax.jit(candidate).lower(*avals).compile()

    ``global_counters()["autotune_compiles"]`` (a subset of
    ``"compiles"``) and ``FunctionWatch.n_autotune_compiles`` count
    them; ``bench.py`` reports the split as ``n_autotune_compiles``
    next to ``n_compiles``."""
    _autotune_tls.depth = getattr(_autotune_tls, "depth", 0) + 1
    try:
        yield
    finally:
        _autotune_tls.depth -= 1


def _on_begin(name: str, _value, **_kw) -> None:
    if name in _SPAN_OF_EVENT:
        _in_flight().append([0, 0.0])


def _on_duration(name: str, secs: float, fun_name: Optional[str] = None,
                 **_kw) -> None:
    said = _CACHE_SECONDS.get(name)
    if said is not None:
        _timeline.cache_said(said, secs)
        return
    span_name = _SPAN_OF_EVENT.get(name)
    if span_name is None:
        return
    frames = _in_flight()
    # nothing in flight: the listener came after this one had begun
    nested, nested_seconds = frames.pop() if frames else (0, 0.0)
    st = _stack()
    if span_name == "trace" and frames:
        frames[-1][0] += 1 + nested
        frames[-1][1] += secs + nested_seconds
        return
    fields = {"program": fun_name or (st[-1].rec.name if st else None),
              "cause": st[-1].span_id() if st else None}
    if nested:
        fields.update(nested=nested, nested_seconds=nested_seconds)
    if span_name == "compile":
        fields.update(_timeline.cache_answer())
        if in_autotune():
            fields["autotune"] = True
    span = _timeline.add(span_name, secs, **fields)
    if st:
        st[-1].rec._count_event(span)


def _on_event(name: str, **_kw) -> None:
    said = _CACHE_EVENTS.get(name)
    if said is not None:
        _timeline.cache_said(said)


def install() -> bool:
    """Register the process-wide ``jax.monitoring`` listener (idempotent;
    listeners cannot be unregistered, so a module flag guards against
    doubles) and read the two clocks together once, for
    :func:`timeline`'s anchor. Returns False when the build has no
    monitoring API — the cache-size wrapper fallback still works."""
    global _installed
    with _lock:
        if _installed:
            return True
        try:
            jax.monitoring.register_scalar_listener(_on_begin)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
        except Exception:
            return False
        _timeline.anchor = (time.perf_counter_ns(), time.time_ns())
        _installed = True
        return True


def installed() -> bool:
    return _installed


def global_counters() -> Dict[str, float]:
    """Process-wide compile-pipeline counters since :func:`install` /
    the last reset: {"traces", "lowerings", "compiles", "*_secs",
    "cache_hits"} — ``compiles`` counts compile requests, of which
    ``cache_hits`` were answered by the persistent compilation cache.
    A fold over :func:`timeline`'s spans and what wrapped away."""
    return _timeline.counters()


def reset_global_counters() -> None:
    """Forget every trace, lower, compile and call span; the import
    spans stay."""
    _timeline.forget_compiles()


def record_import(package: str, start: float,
                  children: List[Tuple[str, float]]) -> None:
    """What ``apex_tpu/__init__.py`` hands over once its subpackages are
    imported (:meth:`Timeline.record_import`)."""
    _timeline.record_import(package, start, children)


def timeline() -> Dict[str, Any]:
    """The process's set-up so far, JSON-able: ``spans`` (oldest first,
    :class:`Timeline` says what one holds), ``dropped``, ``anchor`` (the
    pair ``[time.perf_counter_ns(), time.time_ns()]`` read at
    :func:`install`, None before it; :func:`apex_tpu.prof.xplane.place`
    puts a span on a profile's clock with it) and
    ``process_age_at_import_s``."""
    return _timeline.snapshot()


def _union_s(spans) -> float:
    """Seconds the spans cover, each second once: a jitted function
    called inside another is traced inside its caller's ``trace`` span,
    so the spans' sum counts those seconds twice."""
    total, covered_to = 0.0, float("-inf")
    for start, end in sorted((s["start"], s["end"]) for s in spans):
        if end > covered_to:
            total += end - max(start, covered_to)
            covered_to = end
    return total


@dataclasses.dataclass
class SetupReport:
    """:func:`setup_report`'s answer."""

    #: one dict a span, longest first: ``span``, ``program``,
    #: ``seconds``, ``cache`` (a compile span's, else None) and
    #: ``inside`` (the span that caused it: ``call <fn>``,
    #: ``import/<sub>``, or None)
    rows: List[Dict[str, Any]]
    #: ``process_age_at_import_s``; ``import_s`` (the package's span);
    #: ``trace_lower_s`` and ``compile_s`` (seconds covered, each once);
    #: ``compiles`` (requests), ``backend_compiles`` (those the cache did
    #: not answer), ``cache_hits`` with ``cache_retrieval_s`` and
    #: ``compile_saved_s``, ``unstored`` (misses the runtime did not go
    #: on to write), ``autotune_compiles``; ``dropped``
    totals: Dict[str, Any]

    def table(self, top: int = 20) -> str:
        t = self.totals
        lines = [f"{'span':<16} {'program':<40} {'seconds':>9} "
                 f"{'cache':<5} inside"]
        for r in self.rows[:top]:
            lines.append(
                f"{r['span'][:16]:<16} {str(r['program'] or '-')[:40]:<40} "
                f"{r['seconds']:>9.3f} {r['cache'] or '':<5} "
                f"{r['inside'] or ''}")

        def s(key):
            return "n/a" if t[key] is None else f"{t[key]:.3f}"
        lines.append(
            f"process start to import {s('process_age_at_import_s')} s, "
            f"import {s('import_s')}, trace + lower "
            f"{t['trace_lower_s']:.3f}, compile or cache load "
            f"{t['compile_s']:.3f}: {t['compiles']} requests, "
            f"{t['backend_compiles']} backend compiles "
            f"({t['unstored']} not stored, {t['autotune_compiles']} "
            f"autotune), {t['cache_hits']} cache hits loaded in "
            f"{t['cache_retrieval_s']:.3f} s for {t['compile_saved_s']:.3f}"
            f" s of compiling; {t['dropped']} spans dropped")
        return "\n".join(lines)


def setup_report() -> SetupReport:
    """What the process spent before its first step, by span: what an
    operator prints once the job's first step has run (after
    :func:`install` at its start)::

        print(prof.setup_report().table())
    """
    tl = _timeline.snapshot()
    spans = tl["spans"]
    by_id = {s["id"]: s for s in spans}

    def inside(span):
        cause = by_id.get(span["cause"])
        if cause is None:
            return None
        return (f"call {cause['program']}" if cause["name"] == "call"
                else cause["name"])

    rows = sorted(({"span": s["name"], "program": s["program"],
                    "seconds": s["seconds"], "cache": s.get("cache"),
                    "inside": inside(s)} for s in spans),
                  key=lambda r: -r["seconds"])
    compiles = [s for s in spans if s["name"] == "compile"]
    hits = [s for s in compiles if s["cache"] == "hit"]
    imports = [s["seconds"] for s in spans if s["name"] == "import"]
    totals = {
        "process_age_at_import_s": tl["process_age_at_import_s"],
        "import_s": sum(imports) if imports else None,
        "trace_lower_s": _union_s(
            s for s in spans if s["name"] in ("trace", "lower")),
        "compile_s": _union_s(compiles),
        "compiles": len(compiles),
        "backend_compiles": len(compiles) - len(hits),
        "cache_hits": len(hits),
        "cache_retrieval_s": sum(s.get("retrieval_s", 0.0) for s in hits),
        "compile_saved_s": sum(s.get("saved_s", 0.0) for s in hits),
        "unstored": sum(1 for s in compiles
                        if s["cache"] == "miss" and not s["stored"]),
        "autotune_compiles": sum(1 for s in compiles if s.get("autotune")),
        "dropped": tl["dropped"],
    }
    return SetupReport(rows=rows, totals=totals)


# --- argument signatures -----------------------------------------------------

def _aval_of(x) -> Tuple:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), str(x.dtype))
    # static leaves retrace on VALUE change, so the value is the signature
    return ("static", repr(x)[:80])


def signature(args, kwargs) -> Tuple[Tuple[str, Tuple], ...]:
    """Hashable (path, shape/dtype) signature of a call's arguments —
    the thing a retrace means *changed*."""
    flat = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    return tuple((jax.tree_util.keystr(path), _aval_of(leaf))
                 for path, leaf in flat)


def diff_signatures(old, new) -> str:
    """Human-readable description of what changed between two call
    signatures — names the argument(s) that forced the retrace."""
    if old is None:
        return "first call"
    old_d, new_d = dict(old), dict(new)
    changes = []
    for path, aval in new_d.items():
        prev = old_d.get(path)
        if prev is None:
            changes.append(f"{path or '<args>'}: new argument {aval}")
        elif prev != aval:
            changes.append(f"{path or '<args>'}: {prev} -> {aval}")
    for path in old_d:
        if path not in new_d:
            changes.append(f"{path or '<args>'}: removed")
    if not changes and len(old) != len(new):
        changes.append(f"argument count {len(old)} -> {len(new)}")
    return "; ".join(changes[:6]) or "unknown (same avals — static or " \
        "tracing-context change)"


# --- per-function watch ------------------------------------------------------

@dataclasses.dataclass
class FunctionWatch:
    """Counters for one watched function."""

    name: str
    n_calls: int = 0
    n_traces: int = 0            # distinct traces (jit cache growth)
    n_retraces: int = 0          # traces beyond the first
    n_lowerings: int = 0         # attributed jax.monitoring events
    n_compiles: int = 0
    n_autotune_compiles: int = 0  # subset fired under autotune_scope()
    compile_secs: float = 0.0    # attributed backend-compile seconds
    trace_secs: float = 0.0
    last_signature: Optional[Tuple] = None
    last_change: Optional[str] = None
    retraces: List[Dict] = dataclasses.field(default_factory=list)
    warned: bool = False
    # signatures already traced — the no-cache-introspection fallback's
    # dedupe, so alternating between already-compiled shapes is not
    # miscounted as retracing
    _seen: set = dataclasses.field(default_factory=set)

    def _count_event(self, span: Dict[str, Any]) -> None:
        """One span of the timeline that ended inside this function's
        dispatch."""
        self.trace_secs += span.get("nested_seconds", 0.0)
        if span["name"] == "compile":
            self.n_compiles += 1
            self.compile_secs += span["seconds"]
            if span.get("autotune"):
                self.n_autotune_compiles += 1
        elif span["name"] == "lower":
            self.n_lowerings += 1
        elif span["name"] == "trace":
            self.trace_secs += span["seconds"]

    def to_events(self, rank: int = 0) -> List[Dict]:
        """``kind="retrace"`` events for the memory/compile channel."""
        return [dict(ev, kind="retrace", rank=rank, fn=self.name)
                for ev in self.retraces]


class CompileWatcher:
    """Watches jitted functions for traces/retraces/compiles.

    ::

        watcher = prof.CompileWatcher(warn_after=3)
        step = watcher.watch(jax.jit(step_fn), name="train_step")
        ...
        print(watcher.report())
        # steady state: watcher["train_step"].n_traces == 1

    ``warn_after``: a warning fires once when one function accumulates
    that many retraces (the classic unstable-shape bug). ``on_event``
    callbacks receive each ``kind="retrace"``/``kind="compile"`` event
    dict — wire ``MetricsLogger.record_memory`` here to stream them.
    """

    def __init__(self, *, warn_after: int = 3,
                 on_event: Optional[Callable[[Dict], None]] = None):
        self.warn_after = max(int(warn_after), 1)
        self._on_event: List[Callable[[Dict], None]] = (
            [on_event] if on_event else [])
        self.watches: Dict[str, FunctionWatch] = {}
        install()                      # best effort; fallback works without

    def subscribe(self, fn: Callable[[Dict], None]) -> None:
        self._on_event.append(fn)

    def __getitem__(self, name: str) -> FunctionWatch:
        return self.watches[name]

    def _emit(self, event: Dict) -> None:
        for fn in list(self._on_event):
            try:
                fn(dict(event))
            except Exception:
                pass               # observers never break the train loop

    # -- the wrapper ---------------------------------------------------------

    def watch(self, fn: Callable, name: Optional[str] = None) -> Callable:
        """Wrap ``fn`` (jitted if not already) so every call updates its
        :class:`FunctionWatch`. The returned wrapper carries it as
        ``.watch`` and the underlying jitted callable as ``.jitted``."""
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        name = name or getattr(fn, "__name__", None) or repr(fn)[:40]
        rec = self.watches.setdefault(name, FunctionWatch(name=name))

        def cache_size() -> Optional[int]:
            try:
                return jitted._cache_size()
            except Exception:
                return None

        @functools.wraps(getattr(fn, "__wrapped__", fn))
        def wrapped(*args, **kwargs):
            sig = signature(args, kwargs)
            before = cache_size()
            st = _stack()
            call = _Call(rec)
            st.append(call)
            try:
                out = jitted(*args, **kwargs)
            finally:
                now = time.perf_counter()
                dt_ms = (now - call.start) * 1e3
                st.pop()
                if call.id is not None:
                    _timeline.add(
                        "call", now - call.start, end=now, id=call.id,
                        program=rec.name,
                        cause=st[-1].span_id() if st else None)
            after = cache_size()
            rec.n_calls += 1
            if after is not None and before is not None:
                traced = after > before
            else:                      # no cache introspection: fall back
                traced = sig not in rec._seen
            if traced:
                self._on_trace(rec, sig, dt_ms)
            rec._seen.add(sig)
            rec.last_signature = sig
            return out

        wrapped.watch = rec
        wrapped.jitted = jitted
        return wrapped

    def _on_trace(self, rec: FunctionWatch, sig, dt_ms: float) -> None:
        rec.n_traces += 1
        retrace = rec.n_traces > 1
        change = diff_signatures(rec.last_signature, sig)
        rec.last_change = change
        # compile wall time as a kind="compile" span on the host
        # timeline (back-dated: the duration was only known after the
        # dispatch returned). The dispatch that compiles includes the
        # compile, so dt_ms bounds it from above; the attributed
        # backend_compile seconds (rec.compile_secs) are the exact
        # compiler time when jax.monitoring is available.
        from apex_tpu.trace.spans import current_tracer
        tracer = current_tracer()
        if tracer is not None:
            tracer.add_span_event(f"compile/{rec.name}", "compile", dt_ms)
        self._emit({"kind": "compile", "fn": rec.name, "dur_ms": dt_ms,
                    "n_traces": rec.n_traces, "changed": change,
                    "retrace": retrace})
        if not retrace:
            return
        rec.n_retraces += 1
        ev = {"call": rec.n_calls, "dur_ms": round(dt_ms, 3),
              "changed": change}
        rec.retraces.append(ev)
        self._emit(dict(ev, kind="retrace", fn=rec.name,
                        n_traces=rec.n_traces))
        if rec.n_retraces >= self.warn_after and not rec.warned:
            rec.warned = True
            warnings.warn(
                f"apex_tpu.prof.compile_watch: {rec.name!r} retraced "
                f"{rec.n_retraces} times (last change: {change}). Each "
                f"retrace recompiles the program — pin the changing "
                f"argument's shape/dtype or mark it static.",
                RuntimeWarning, stacklevel=3)

    # -- renderings ----------------------------------------------------------

    def counters(self) -> Dict[str, Dict]:
        """Per-function counter dicts (JSON-able) + process totals."""
        out = {name: {
            "n_calls": r.n_calls, "n_traces": r.n_traces,
            "n_retraces": r.n_retraces, "n_compiles": r.n_compiles,
            "n_autotune_compiles": r.n_autotune_compiles,
            "compile_secs": round(r.compile_secs, 4),
            "last_change": r.last_change,
        } for name, r in self.watches.items()}
        out["_process"] = global_counters()
        return out

    def report(self) -> str:
        lines = [f"{'function':<28} {'calls':>6} {'traces':>7} "
                 f"{'retraces':>9} {'compiles':>9} {'compile_s':>10}"]
        for name, r in sorted(self.watches.items()):
            lines.append(
                f"{name[:28]:<28} {r.n_calls:>6} {r.n_traces:>7} "
                f"{r.n_retraces:>9} {r.n_compiles:>9} "
                f"{r.compile_secs:>10.3f}")
            for ev in r.retraces[-3:]:
                lines.append(f"    retrace @call {ev['call']}: "
                             f"{ev['changed'][:90]}")
        g = global_counters()
        lines.append(f"process totals: {g['traces']} traces, "
                     f"{g['lowerings']} lowerings, {g['compiles']} "
                     f"backend compiles ({g['compile_secs']:.2f}s, "
                     f"of which {g['autotune_compiles']} autotune)"
                     + ("" if _installed else
                        " [jax.monitoring unavailable — per-function "
                        "cache counts only]"))
        return "\n".join(lines)


def watch(fn: Callable, name: Optional[str] = None, *,
          warn_after: int = 3) -> Callable:
    """One-off convenience: wrap ``fn`` under a fresh
    :class:`CompileWatcher` (reachable as ``wrapped.watcher``)."""
    w = CompileWatcher(warn_after=warn_after)
    wrapped = w.watch(fn, name)
    wrapped.watcher = w
    return wrapped
