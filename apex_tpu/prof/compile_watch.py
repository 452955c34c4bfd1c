"""Compilation observability: trace/lower/compile counters + retrace
detector.

A silent shape-induced retrace can eat minutes per step with no signal
in any existing sink — the step "just got slow". This module watches the
compile pipeline from two directions:

- **process-wide counters** via ``jax.monitoring`` events
  (``/jax/core/compile/*_duration``): every trace, MLIR lowering, and
  backend compile in the process is counted and its wall time summed,
  whether or not the function is wrapped (:func:`install`,
  :func:`global_counters` — ``bench.py`` reports ``n_compiles`` from
  this). Builds without the monitoring API degrade to the wrapper
  fallback below.
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
  validates them).

The watch wrapper never changes the compiled program — the jitted
callable, its trace cache, and its donation/sharding behavior are the
wrapped function's own (the ``memory/no-extra-dispatch`` compile-check
case pins bit-identical HLO).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

__all__ = ["CompileWatcher", "FunctionWatch", "install", "installed",
           "global_counters", "reset_global_counters", "watch",
           "autotune_scope", "in_autotune"]

# --- process-wide jax.monitoring counters ------------------------------------

_EVENT_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "compiles",
}

_lock = threading.Lock()
_installed = False
_globals = {"traces": 0, "lowerings": 0, "compiles": 0,
            "trace_secs": 0.0, "lower_secs": 0.0, "compile_secs": 0.0,
            "autotune_compiles": 0, "autotune_secs": 0.0,
            "cache_hits": 0}
# jax fires the backend-compile duration event around
# compile_or_get_cached, so "compiles" counts compile REQUESTS; the ones
# the persistent cache answered are counted here, and
# compiles - cache_hits is what the backend actually compiled
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_SECS_KEY = {"traces": "trace_secs", "lowerings": "lower_secs",
             "compiles": "compile_secs"}

# innermost-last stack of FunctionWatch records whose dispatch is in
# flight on this thread — monitoring events fired during the dispatch
# are attributed to the top of the stack
_tls = threading.local()


def _stack() -> List["FunctionWatch"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


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


def _on_duration(name: str, secs: float, **_kw) -> None:
    key = _EVENT_KEYS.get(name)
    if key is None:
        return
    autotune = key == "compiles" and in_autotune()
    with _lock:
        _globals[key] += 1
        _globals[_SECS_KEY[key]] += secs
        if autotune:
            _globals["autotune_compiles"] += 1
            _globals["autotune_secs"] += secs
    st = _stack()
    if st:
        st[-1]._count_event(key, secs, autotune=autotune)


def _on_event(name: str, **_kw) -> None:
    if name == _CACHE_HIT_EVENT:
        with _lock:
            _globals["cache_hits"] += 1


def install() -> bool:
    """Register the process-wide ``jax.monitoring`` listener (idempotent;
    listeners cannot be unregistered, so a module flag guards against
    doubles). Returns False when the build has no monitoring API — the
    cache-size wrapper fallback still works."""
    global _installed
    with _lock:
        if _installed:
            return True
        try:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
        except Exception:
            return False
        _installed = True
        return True


def installed() -> bool:
    return _installed


def global_counters() -> Dict[str, float]:
    """Process-wide compile-pipeline counters since :func:`install` /
    the last reset: {"traces", "lowerings", "compiles", "*_secs",
    "cache_hits"} — ``compiles`` counts compile requests, of which
    ``cache_hits`` were answered by the persistent compilation cache."""
    with _lock:
        return dict(_globals)


def reset_global_counters() -> None:
    with _lock:
        for k in _globals:
            _globals[k] = 0 if isinstance(_globals[k], int) else 0.0


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

    def _count_event(self, key: str, secs: float,
                     autotune: bool = False) -> None:
        if key == "compiles":
            self.n_compiles += 1
            self.compile_secs += secs
            if autotune:
                self.n_autotune_compiles += 1
        elif key == "lowerings":
            self.n_lowerings += 1
        elif key == "traces":
            self.trace_secs += secs

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
            st.append(rec)
            t0 = time.perf_counter()
            try:
                out = jitted(*args, **kwargs)
            finally:
                dt_ms = (time.perf_counter() - t0) * 1e3
                st.pop()
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
