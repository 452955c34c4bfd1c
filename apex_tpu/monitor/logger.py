"""The host half of the telemetry subsystem: buffered fetch + sinks.

``MetricsLogger`` receives the on-device :class:`~apex_tpu.monitor.Metrics`
snapshot returned by each step and *buffers the device arrays* — nothing
is fetched until ``flush()`` (every ``flush_every`` records, or at
``close()``), so the device→host transfer amortizes over N steps and the
steady-state step loop never blocks on telemetry. With jax's async
dispatch the ``record()`` call itself costs a list append and a clock
read.

On top of the in-graph counters the logger derives host-side health:

- rolling **step time** (wall clock between ``record()`` calls) and
  **throughput** over a sliding window;
- **MFU**, when the per-step model FLOPs are known — call ``attach()``
  with the jitted step and example args and they are taken from XLA's
  cost analysis (reusing :mod:`apex_tpu.prof.hlo`), the peak from
  :data:`apex_tpu.prof.PEAK_FLOPS` (unknown chips report
  ``mfu=None``, never a misleading 0 — same contract as
  ``StepReport.table``);
- **collective bytes per step** from the compiled HLO (see
  :mod:`apex_tpu.monitor.collectives`).

Typical wiring::

    logger = monitor.MetricsLogger(
        sinks=[monitor.StdoutSink(), monitor.JSONLSink("metrics.jsonl")],
        flush_every=10)
    logger.attach(train_step, state, batch)     # statics: flops, coll bytes
    for batch in data:
        state, loss = train_step(state, batch)  # state carries .metrics
        logger.record(state.metrics)
    logger.close()
"""

from __future__ import annotations

import atexit
import collections
import math
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax

from apex_tpu.monitor.metrics import Metrics, metrics_to_dict
from apex_tpu.monitor.sinks import Sink, StdoutSink

__all__ = ["MetricsLogger", "ChannelSpec", "CHANNELS"]


class ChannelSpec(NamedTuple):
    """One declarative row of the event-channel registry: adding a
    channel is adding a row here (ctor kwarg ``{name}_sink=``, the
    ``record_*`` method, close handling and non-finite nulling all
    derive from it) — not another 30-line clone of the previous
    channel's plumbing."""

    name: str                 #: channel name; ctor kwarg = f"{name}_sink"
    kinds: Tuple[str, ...]    #: event kinds on this channel (the
                              #: ``check_metrics_schema.py --kind`` enum)
    method: str               #: the logger's record-method name
    null_nonfinite: bool      #: null Infinity/NaN before emit (the
                              #: strict-JSON contract); channels whose
                              #: emitters never produce non-finite
                              #: numbers skip the walk
    nested_null: bool = False  #: also null one level of nested dicts
                               #: (goodput's buckets_ms)
    why_unbuffered: str = ""  #: one line: why this channel must never
                              #: buffer (every record_* channel is
                              #: unbuffered; the buffered path is the
                              #: Metrics pytree via record()/flush())


#: the event-channel registry. Every channel is UNBUFFERED (events are
#: rare and forensic — a record that only landed at flush time could be
#: lost to the very crash/escalation it documents); the per-channel
#: ``why_unbuffered`` line carries the channel-specific version of that
#: argument. Validate a channel's stream with
#: ``check_metrics_schema.py --kind <name>`` (``trace`` events use
#: ``--kind trace``; the registry rows and the validator's tables are
#: kept in lockstep — scripts/check_metrics_schema.py names each
#: emitter module).
CHANNELS: Tuple[ChannelSpec, ...] = (
    ChannelSpec("trace", ("span", "step", "crash", "watchdog"),
                "record_event", False,
                why_unbuffered="host-side span/step/crash events from "
                "apex_tpu.trace; losing them to a crash would defeat "
                "the point"),
    ChannelSpec("memory", ("memory", "memory_report", "retrace",
                           "compile"), "record_memory", True,
                why_unbuffered="retrace warnings and allocator samples "
                "are rare; an OOM dump must not wait on a flush"),
    ChannelSpec("lint", ("lint_report", "lint_finding"),
                "record_lint", False,
                why_unbuffered="lint runs are rare AOT audits"),
    ChannelSpec("ckpt", ("ckpt_save", "ckpt_restore",
                         "ckpt_escalation"), "record_ckpt", True,
                why_unbuffered="an escalation record buffered to flush "
                "time would be lost to the very crash it documents"),
    ChannelSpec("guard", ("guard_anomaly", "guard_action",
                          "guard_rewind"), "record_guard", True,
                why_unbuffered="a rewind record could be lost to the "
                "escalation it precedes; a NaN-loss anomaly's z is "
                "non-finite by construction"),
    ChannelSpec("goodput", ("goodput", "straggler", "linkfit"),
                "record_goodput", True, nested_null=True,
                why_unbuffered="per-step attribution and straggler "
                "warnings are forensic; a zero-wall warmup step has "
                "no finite goodput fraction (nested buckets nulled)"),
    ChannelSpec("roofline", ("roofline", "regress", "tune"),
                "record_roofline", True,
                why_unbuffered="roofline joins, sentinel verdicts and "
                "autotune sweep/consult records are rare AOT/offline "
                "audits"),
    ChannelSpec("cluster", ("cluster_lease", "cluster_generation",
                            "cluster_fence", "cluster_coord"),
                "record_cluster", True,
                why_unbuffered="a fence refusal usually precedes the "
                "zombie exit it documents"),
    ChannelSpec("integrity", ("integrity_check", "integrity_vote",
                              "integrity_repair"), "record_integrity",
                True,
                why_unbuffered="a divergence vote could be lost to "
                "the rewind/escalation it precedes"),
    ChannelSpec("numerics", ("numerics_check", "scale_update",
                             "precision_verdict"), "record_numerics",
                True,
                why_unbuffered="scale backoffs and precision verdicts "
                "are rare and may immediately precede the overflow "
                "skip they explain"),
    ChannelSpec("podview", ("pod_align", "pod_skew", "pod_drift"),
                "record_podview", True,
                why_unbuffered="pod merges and drift reports are rare "
                "offline/audit joins, and a skew-blame record may "
                "immediately precede the straggler escalation it "
                "explains (an unaligned rank's residual is null)"),
    ChannelSpec("sharding", ("sharding_mesh", "sharding"),
                "record_sharding", True,
                why_unbuffered="per-axis attribution rows are rare AOT "
                "audits (shard_report / mesh_explain pre-flights), and "
                "an unmeasured link's predicted_s is null by contract"),
    ChannelSpec("dynamics", ("dynamics_check", "gns",
                             "convergence_verdict"), "record_dynamics",
                True,
                why_unbuffered="dynamics checks ride the amortized "
                "host-poll cadence already, a convergence flag may "
                "immediately precede the abort it argues for, and an "
                "undefined GNS estimate is null by contract"),
)

def _null_nonfinite(rec: Dict, nested: bool) -> None:
    """Null non-finite numbers in place (Infinity/NaN are not valid
    strict JSON; the schema contract is finite-or-null — the *event*
    behind a non-finite gauge is already counted elsewhere)."""
    for k, v in rec.items():
        if isinstance(v, float) and not math.isfinite(v):
            rec[k] = None
        elif nested and isinstance(v, dict):
            rec[k] = {kk: (None if isinstance(vv, float)
                           and not math.isfinite(vv) else vv)
                      for kk, vv in v.items()}


def _channel_method(spec: ChannelSpec):
    def _record(self, event: Dict) -> None:
        sink = getattr(self, f"{spec.name}_sink")
        if sink is None or self._closed:
            return
        rec = dict(event)
        if spec.null_nonfinite:
            _null_nonfinite(rec, spec.nested_null)
        sink.emit(rec)

    _record.__name__ = spec.method
    _record.__doc__ = (
        f"Emit one {spec.name}-channel event (``kind`` in "
        f"{spec.kinds}) — a plain-dict pass-through, no device "
        f"access, NOTHING buffered: {spec.why_unbuffered}. "
        + ("Non-finite numbers are nulled to keep the strict-JSON "
           "contract. " if spec.null_nonfinite else "")
        + f"Validate the stream with ``check_metrics_schema.py "
        f"--kind {spec.name}``.")
    return _record


class MetricsLogger:
    """See the module docstring. The logger is a context manager and
    registers itself with ``atexit``, so a crashed run never loses its
    buffered tail: ``__exit__`` flushes on exceptions too, and an
    un-``close()``d logger (hard ``sys.exit``, unhandled error above the
    ``with``) is flushed at interpreter exit.

    Beyond the buffered metrics stream, the logger carries one
    **unbuffered event channel per** :data:`CHANNELS` **row** — pass
    ``{name}_sink=`` (``trace_sink=``, ``guard_sink=``, …,
    ``podview_sink=``) and feed events through the matching
    ``record_*`` method; each channel's stream validates under
    ``check_metrics_schema.py --kind {name}``. Events never mix with
    the metrics wire format. Adding a channel is one registry row, not
    another clone of this plumbing.
    """

    def __init__(self, sinks: Optional[Sequence[Sink]] = None, *,
                 flush_every: int = 10, window: int = 50,
                 peak_flops: Optional[float] = None,
                 flops_per_step: Optional[float] = None,
                 collective_bytes_per_step: Optional[int] = None,
                 logical_collective_bytes: Optional[int] = None,
                 donation_safe: bool = False,
                 **channel_sinks: Optional[Sink]):
        self.sinks: List[Sink] = (list(sinks) if sinks is not None
                                  else [StdoutSink()])
        self.flush_every = max(int(flush_every), 1)
        self.flops_per_step = flops_per_step
        self.collective_bytes_per_step = collective_bytes_per_step
        # the event channels: one ``{name}_sink`` attribute + one
        # ``record_*`` method per CHANNELS row (the registry is the
        # single source of truth — docstrings, nulling policy and
        # close() all derive from it)
        valid = {f"{c.name}_sink" for c in CHANNELS}
        unknown = set(channel_sinks) - valid
        if unknown:
            raise TypeError(
                f"MetricsLogger got unknown channel sink(s) "
                f"{sorted(unknown)}; known channels: {sorted(valid)}")
        for spec in CHANNELS:
            setattr(self, f"{spec.name}_sink",
                    channel_sinks.get(f"{spec.name}_sink"))
        self.memory_report = None      # last attached prof.MemoryReport
        self.lint_report = None        # last attached lint.Report
        self.roofline_report = None    # last attached RooflineReport
        self.shard_report = None       # last attached prof.ShardReport
        #: the uncompressed payload one step SEMANTICALLY moves (e.g.
        #: ``4 * n_params`` for an fp32 grad sync) — enables the
        #: per-record ``wire_to_logical`` ratio, same contract as
        #: :func:`apex_tpu.monitor.wire_report`
        self.logical_collective_bytes = logical_collective_bytes
        #: per-dtype wire breakdown from the compiled step (set by
        #: :meth:`attach`): ``{dtype: bytes}`` — the stdout table's
        #: logical-vs-wire columns read it
        self.collective_bytes_by_dtype: Optional[Dict[str, int]] = None
        #: snapshot each recorded metrics pytree into fresh device
        #: buffers (async scalar copies). REQUIRED when the step is
        #: jitted with donate_argnums over the state carrying the
        #: metrics: donation invalidates the input buffers on the next
        #: dispatch, and an un-snapshotted buffered record would be
        #: "Array has been deleted" by flush time.
        self.donation_safe = donation_safe
        if peak_flops is None:
            from apex_tpu.prof.report import PEAK_FLOPS, lookup_peak
            peak_flops = lookup_peak(
                PEAK_FLOPS, jax.devices()[0].device_kind) or None
        self.peak_flops = peak_flops
        # buffered device snapshots + their host receipt times
        self._buf: List[Metrics] = []
        self._times: List[float] = []
        self._last_time: Optional[float] = None
        # sliding (time) window for throughput; bounded deque
        self._window = collections.deque(maxlen=max(int(window), 2))
        self._closed = False
        # crash-safe tail: flush whatever is buffered at interpreter
        # exit if the run never reached close()
        atexit.register(self._atexit_close)

    # -- compile-time statics ------------------------------------------------

    def attach(self, step_fn, *args, **kwargs) -> "MetricsLogger":
        """Derive per-step statics from the compiled step: model FLOPs
        (XLA cost analysis) and collective traffic (optimized HLO), from
        ONE AOT compile of ``step_fn`` — an upfront cost paid once at
        setup, never per step. Statics the caller already set explicitly
        (constructor kwargs) are kept, and nothing compiles when both
        are preset."""
        from apex_tpu.monitor.collectives import (
            collective_bytes_by_dtype, collective_bytes_from_text)
        from apex_tpu.prof import hlo as _hlo
        if (self.flops_per_step is not None
                and self.collective_bytes_per_step is not None):
            # the preset path stays compile-free (its whole point); the
            # per-dtype wire split then simply stays unset (the stdout
            # table shows n/a) unless the caller sets
            # collective_bytes_by_dtype directly
            return self
        compiled = _hlo._compile(step_fn, *args, **kwargs)
        hlo_text = compiled.as_text()
        if self.flops_per_step is None:
            flops = float(_hlo.cost_analysis_of(compiled).get("flops", 0.0))
            self.flops_per_step = flops if flops > 0 else None
        if self.collective_bytes_by_dtype is None:
            # one {dtype: bytes} rollup over the opcodes — the
            # wire_report breakdown that makes compressed sync auditable
            # from the live table (a bf16 DDP step shows bf16 wire
            # bytes at half its fp32 logical payload)
            per: Dict[str, int] = {}
            for per_op in collective_bytes_by_dtype(hlo_text).values():
                for dt, nbytes in per_op.items():
                    per[dt] = per.get(dt, 0) + nbytes
            self.collective_bytes_by_dtype = per
        if self.collective_bytes_per_step is None:
            self.collective_bytes_per_step = collective_bytes_from_text(
                hlo_text).get("total", 0)
        return self

    # -- per-step path (cheap, never syncs) ----------------------------------

    def record(self, metrics: Metrics, **extra) -> None:
        """Buffer one device snapshot. ``extra`` keys (host scalars only)
        are merged into the emitted record at flush."""
        if self.donation_safe:
            from apex_tpu.monitor.metrics import metrics_snapshot
            metrics = metrics_snapshot(metrics)
        now = time.perf_counter()
        self._buf.append((metrics, dict(extra)) if extra else (metrics, None))
        self._times.append(now)
        self._window.append(now)
        if len(self._buf) >= self.flush_every:
            self.flush()

    # -- amortized fetch + emit ----------------------------------------------

    def _throughput(self) -> Optional[float]:
        if len(self._window) < 2:
            return None
        dt = self._window[-1] - self._window[0]
        if dt <= 0:
            return None
        return (len(self._window) - 1) / dt

    def flush(self) -> None:
        """One device→host fetch for every buffered snapshot, then emit."""
        if not self._buf:
            return
        buf, times = self._buf, self._times
        self._buf, self._times = [], []
        try:
            host = jax.device_get([m for m, _ in buf])
        except RuntimeError:
            # a donated step invalidated buffered snapshots (the caller
            # should pass donation_safe=True) — salvage what survives
            # record-by-record instead of losing the whole window
            host = []
            for m, _ in buf:
                try:
                    host.append(jax.device_get(m))
                except RuntimeError:
                    host.append(None)
            buf = [b for b, h in zip(buf, host) if h is not None]
            times = [t for t, h in zip(times, host) if h is not None]
            host = [h for h in host if h is not None]
        thru = self._throughput()
        for (_, extra), m, t in zip(buf, host, times):
            rec: Dict = metrics_to_dict(m)
            if self._last_time is None:
                rec["step_time_ms"] = None
            else:
                rec["step_time_ms"] = (t - self._last_time) * 1e3
            self._last_time = t
            rec["throughput_steps_per_s"] = thru
            if thru and self.flops_per_step and self.peak_flops:
                rec["mfu"] = self.flops_per_step * thru / self.peak_flops
            else:
                rec["mfu"] = None
            rec["collective_bytes"] = self.collective_bytes_per_step
            # the per-dtype logical-vs-wire split (wire_report's
            # accounting, attached per record so compressed-sync runs
            # show their ratio without a separate script)
            rec["wire_by_dtype"] = self.collective_bytes_by_dtype
            if (self.logical_collective_bytes
                    and self.collective_bytes_per_step is not None):
                rec["logical_bytes"] = self.logical_collective_bytes
                rec["wire_to_logical"] = (self.collective_bytes_per_step
                                          / self.logical_collective_bytes)
            else:
                rec["logical_bytes"] = self.logical_collective_bytes
                rec["wire_to_logical"] = None
            rec["wall_time"] = time.time()
            if extra:
                rec.update(extra)
            # non-finite gauges (diverged loss, ...) become null on the
            # wire: Infinity/NaN are not valid strict JSON, and the
            # schema contract is finite-or-null (the *event* is already
            # counted in overflow_count)
            for k, v in rec.items():
                if isinstance(v, float) and not math.isfinite(v):
                    rec[k] = None
            for sink in self.sinks:
                sink.emit(rec)

    # -- event channels ------------------------------------------------------
    # record_event / record_memory / record_lint / record_ckpt /
    # record_guard / record_goodput / record_roofline / record_cluster /
    # record_integrity / record_numerics / record_podview /
    # record_sharding are generated
    # from the CHANNELS
    # registry after the class body — one declarative row per channel,
    # not one 30-line clone. Typical wirings (see each subsystem's
    # docs): ``tracer.subscribe(lambda st: logger.record_event(
    # st.to_event(rank)))``, ``CompileWatcher.subscribe(
    # logger.record_memory)``, ``CheckpointManager(event_sink=
    # logger.record_ckpt)``, ``GuardPolicy(event_sink=
    # logger.record_guard, integrity_sink=logger.record_integrity)``,
    # ``GoodputLedger.subscribe(logger.record_goodput)``,
    # ``ClusterMembership(event_sink=logger.record_cluster)``, and the
    # numerics observatory's host poll feeding ``record_numerics``.

    def sample_memory(self, step: Optional[int] = None, *,
                      device=None, **extra) -> Optional[Dict]:
        """Sample the device allocator (``device.memory_stats()`` — a
        host-side runtime call, zero device dispatches) and emit one
        ``kind="memory"`` event. Off-TPU backends report no stats; the
        event still lands (values null) so the stream shape is uniform.
        Returns the emitted record (or None when there is no sink)."""
        from apex_tpu.prof.memory import device_memory_sample
        if self.memory_sink is None or self._closed:
            return None
        rec: Dict = {"kind": "memory", "step": step, "rank": 0,
                     "wall_time": time.time()}
        try:
            import jax as _jax
            rec["rank"] = _jax.process_index()
        except Exception:
            pass
        rec.update(device_memory_sample(device))
        if extra:
            rec.update(extra)
        self.record_memory(rec)
        return rec

    def attach_memory_report(self, report) -> "MetricsLogger":
        """Attach a :class:`apex_tpu.prof.MemoryReport` (the compiled
        step's footprint): emits one ``kind="memory_report"`` event and
        keeps the report for consumers (``bench.py`` reads
        ``peak_live_bytes``; hand it to
        ``FlightRecorder.attach_memory_report`` too so crash dumps name
        the biggest buffers)."""
        self.memory_report = report
        if report is not None:
            try:
                rank = jax.process_index()
            except Exception:
                rank = 0
            self.record_memory(report.to_event(rank=rank))
        return self

    def attach_shard_report(self, report,
                            step: Optional[int] = None,
                            **to_events_kwargs) -> "MetricsLogger":
        """Attach an :class:`apex_tpu.prof.ShardReport` (the compiled
        step's per-axis HBM disposition): emits its ``sharding_mesh``
        header + one ``kind="sharding"`` row per axis on the sharding
        channel and keeps the report for consumers (``bench.py`` reads
        the per-axis bytes into its ``axis_hbm`` column). Extra kwargs
        (``wire_by_axis=``, ``predicted_s=``, ``candidate=``) pass
        through to :meth:`~apex_tpu.prof.ShardReport.to_events`."""
        self.shard_report = report
        if report is not None:
            try:
                rank = jax.process_index()
            except Exception:
                rank = 0
            for ev in report.to_events(rank=rank, step=step,
                                       **to_events_kwargs):
                self.record_sharding(ev)
        return self

    def attach_lint_report(self, report,
                           step: Optional[int] = None) -> "MetricsLogger":
        """Attach an :class:`apex_tpu.lint.Report`: emits its
        ``lint_report`` header + one ``lint_finding`` event per finding
        and keeps the report for consumers (``bench.py`` reads the
        finding count into its default JSON)."""
        self.lint_report = report
        if report is not None:
            for ev in report.to_events(step=step):
                self.record_lint(ev)
        return self

    def attach_roofline_report(self, report,
                               step: Optional[int] = None,
                               top: Optional[int] = None
                               ) -> "MetricsLogger":
        """Attach an :class:`apex_tpu.prof.RooflineReport`: emits one
        ``kind="roofline"`` event per row (``top`` bounds it) and keeps
        the report for consumers (``bench.py`` reads ``worst_gaps``
        into its default JSON)."""
        self.roofline_report = report
        if report is not None:
            try:
                rank = jax.process_index()
            except Exception:
                rank = 0
            for ev in report.to_events(rank=rank, step=step, top=top):
                self.record_roofline(ev)
        return self

    def close(self) -> None:
        if self._closed:
            return
        self.flush()
        for sink in self.sinks:
            sink.close()
        for spec in CHANNELS:
            sink = getattr(self, f"{spec.name}_sink")
            if sink is not None:
                sink.close()
        self._closed = True
        atexit.unregister(self._atexit_close)

    def _atexit_close(self) -> None:
        try:
            self.close()
        except Exception:
            pass          # a dead backend at exit must not mask the exit

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        # flushes buffered rows on the exception path too — the tail of
        # a crashed run's metrics reaches the sinks before unwind
        self.close()


# materialize one record method per registry row (record_event,
# record_memory, ..., record_numerics) — the registry is the single
# source of truth for channel names, nulling policy and docstrings
for _spec in CHANNELS:
    setattr(MetricsLogger, _spec.method, _channel_method(_spec))
del _spec
