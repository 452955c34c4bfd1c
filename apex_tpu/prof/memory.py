"""HBM footprint reports + buffer attribution + OOM forensics.

The reference's pyprof pipeline stops at time/FLOPs (`apex/pyprof/prof/`);
on TPU the other failure axis is HBM, and today an OOM is diagnosed by
bisecting batch sizes. The compiler already knows everything needed: XLA's
buffer assignment totals are exposed as ``Compiled.memory_analysis()``
and the optimized (scheduled) HLO text carries every buffer with its
shape, layout, and the named-scope path it was traced under. This module
turns both into a :class:`MemoryReport`:

- **totals** — argument / output / temp / generated-code bytes from
  ``memory_analysis()`` (normalized across jax versions);
- **per-buffer attribution** — every entry argument is attributed by its
  *argument path* (jax records ``state.opt_state.slots['m']['float32']``
  as parameter metadata) and every temp by the *named scope* of its
  defining instruction, then bucketed into classes: **params**,
  **optimizer_state**, **activations**, **comm** (``ddp/sync_gradients``
  buckets, collective buffers), **inputs**, **outputs** — so ZeRO shard
  savings and ``bucket_plan`` buffer overhead become a printed
  ``report.table()``, not folklore;
- **peak-live estimate** — the optimized module is scheduled
  (``is_scheduled=true``), so a liveness walk over the instruction order
  (buffers live from definition to last use, arguments for the whole
  program) yields a peak-live-bytes estimate and the class mix at the
  peak;
- **what-if batch scaler** — buffers whose leading dimension is the
  (per-device) batch are scaled linearly to forecast the peak at other
  batch sizes against the device's HBM capacity
  (``device.memory_stats()``), answering "what batch OOMs?" before the
  chip does.

Wiring: :meth:`apex_tpu.monitor.MetricsLogger.sample_memory` streams
runtime ``memory_stats()`` samples into the ``memory`` event channel, and
:meth:`apex_tpu.trace.FlightRecorder.attach_memory_report` embeds the
last report in crash dumps so an OOM dump names the biggest buffers
instead of just dying. See docs/memory.md.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from apex_tpu.prof.hlo import _DTYPE_BYTES, _compile, cost_analysis_of
# ONE scope-stripping rule for device-time attribution (xplane) and
# byte attribution (here)
from apex_tpu.prof.xplane import HLO_TEXT_SCOPE_RE
from apex_tpu.prof.xplane import strip_scope as _strip_scope

__all__ = [
    "MemoryReport", "BufferRecord", "memory_report", "memory_stats_of",
    "hbm_capacity", "device_memory_sample", "BUFFER_CLASSES",
    "parse_entry",
]

#: attribution classes, in table order. The first four are the
#: training-semantics split the ZeRO/Megatron accounting discipline
#: names; inputs/outputs make the attribution total (arguments + outputs
#: + temps + generated code) closed — scripts/memory_budget.py asserts
#: the class sum matches ``memory_analysis()`` within 1%.
BUFFER_CLASSES = ("params", "optimizer_state", "activations", "comm",
                  "inputs", "outputs")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

# entry-computation instruction: "%name = SHAPE opcode(args...)", where
# SHAPE may be a tuple whose layout annotations contain parens
_INSTR_RE = re.compile(
    r"^(?P<root>ROOT )?%?(?P<n>[^ ]+) = "
    r"(?P<shape>\((?:[^()]|\([^()]*\))*\)|[^ ]+) "
    r"(?P<op>[\w-]+)\(")

# opcodes whose "result" is a view / control artifact, not a fresh
# HBM allocation — excluded from the liveness walk
_NO_ALLOC_OPS = ("parameter", "get-tuple-element", "bitcast", "tuple",
                 "after-all", "partition-id", "replica-id",
                 "opt-barrier")

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute",
                   "collective-broadcast", "ragged-all-to-all")


def shape_bytes(shape_text: str) -> int:
    """Total bytes of every typed shape in an HLO type string (tuples
    sum their elements; layout annotations are ignored — estimates are
    unpadded logical bytes)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        elems = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        total += elems * _DTYPE_BYTES[dt]
    return total


def _leading_dim(shape_text: str) -> Optional[int]:
    m = _SHAPE_RE.search(shape_text)
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",") if d]
    return dims[0] if dims else None




def classify_arg_path(path: str) -> str:
    """Attribution class of an entry argument from its argument path
    (jax records the path — e.g. ``state.opt_state.slots['m']['f32']``
    — as the parameter's metadata op_name)."""
    p = path.lower()
    if "opt_state" in p or "optimizer" in p:
        return "optimizer_state"
    if "residual" in p:                    # error-feedback comm residuals
        return "comm"
    if "scaler" in p or "metrics" in p:
        return "optimizer_state"           # training-state bookkeeping
    if "params" in p or "master" in p or "batch_stats" in p:
        return "params"
    return "inputs"


def classify_scope(scope: str, opcode: str) -> str:
    """Attribution class of a temp buffer from its defining
    instruction's named scope + opcode."""
    if opcode.startswith(_COLLECTIVE_OPS):
        return "comm"
    if "ddp/sync_gradients" in scope or re.search(r"(^|/)bucket\d", scope):
        return "comm"
    return "activations"


@dataclasses.dataclass
class BufferRecord:
    """One attributed buffer of the compiled module."""

    name: str          # instruction / parameter name
    kind: str          # "argument" | "temp" | "output"
    bytes: int         # logical (unpadded) bytes of the result shape
    shape: str         # HLO type string
    cls: str           # one of BUFFER_CLASSES
    scope: str         # arg path (arguments) or named-scope path (temps)
    batch_scaled: bool = False   # leading dim == the given batch size


def memory_stats_of(compiled) -> Dict[str, int]:
    """Normalized ``memory_analysis()`` totals of a compiled executable:
    {"argument", "output", "temp", "alias", "generated_code", "total"}
    bytes (zeros when the backend reports nothing). ``total`` counts
    each byte once: arguments + outputs + temps + generated code."""
    out = {"argument": 0, "output": 0, "temp": 0, "alias": 0,
           "generated_code": 0}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        if isinstance(ma, (list, tuple)):          # older per-device lists
            ma = ma[0] if ma else None
    if ma is not None:
        out["argument"] = int(getattr(ma, "argument_size_in_bytes", 0))
        out["output"] = int(getattr(ma, "output_size_in_bytes", 0))
        out["temp"] = int(getattr(ma, "temp_size_in_bytes", 0))
        out["alias"] = int(getattr(ma, "alias_size_in_bytes", 0))
        out["generated_code"] = int(
            getattr(ma, "generated_code_size_in_bytes", 0))
    out["total"] = (out["argument"] + out["output"] + out["temp"]
                    + out["generated_code"])
    return out


def hbm_capacity(device=None) -> Optional[int]:
    """Device memory capacity in bytes from ``memory_stats()`` —
    None when the backend doesn't report (CPU)."""
    device = device or jax.devices()[0]
    try:
        stats = device.memory_stats()
    except Exception:
        stats = None
    if not stats:
        return None
    v = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    return int(v) if v else None


def device_memory_sample(device=None) -> Dict[str, Optional[int]]:
    """One runtime HBM sample (host-side call, no device dispatch):
    {"bytes_in_use", "peak_bytes_in_use", "bytes_limit"} — values None
    when the backend doesn't report them (CPU). Feed to
    ``MetricsLogger.sample_memory`` for the ``memory`` event channel."""
    device = device or jax.devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:
        stats = {}
    pick = lambda k: int(stats[k]) if k in stats else None
    return {"bytes_in_use": pick("bytes_in_use"),
            "peak_bytes_in_use": pick("peak_bytes_in_use"),
            "bytes_limit": pick("bytes_limit")}


# --- entry-computation parse -------------------------------------------------

def _entry_lines(hlo_text: str) -> List[str]:
    lines = hlo_text.splitlines()
    for i, line in enumerate(lines):
        if line.startswith("ENTRY"):
            body = []
            for l in lines[i + 1:]:
                if l.startswith("}"):
                    break
                body.append(l.strip())
            return body
    return []


_PARAM_NUM_RE = re.compile(r"parameter\((\d+)\)")


def parse_entry(hlo_text: str):
    """(args, instrs, root_operands) of the entry computation.

    args: [(name, shape, arg_path, param_number)];
    instrs: [(idx, name, shape, opcode, operands, scope, is_root)].

    Shared scheduled-HLO parser: buffer attribution here and the
    apexlint HLO pass (``apex_tpu.lint``) read the same records.
    """
    args, instrs = [], []
    root_ops: List[str] = []
    for idx, line in enumerate(_entry_lines(hlo_text)):
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name = m.group("n").lstrip("%")
        shape, op = m.group("shape"), m.group("op")
        sm = HLO_TEXT_SCOPE_RE.search(line)
        op_name = sm.group(1) if sm else ""
        if op == "parameter":
            pm = _PARAM_NUM_RE.search(line)
            pnum = int(pm.group(1)) if pm else len(args)
            # the arg-path metadata has escaped quotes: state.params[\'w\']
            args.append((name, shape, op_name.replace("\\'", "'"), pnum))
        # operand names: %-prefixed tokens inside the call parens
        tail = line.split(f" {op}(", 1)[-1]
        operands = re.findall(r"%([\w.\-]+)", tail)
        is_root = bool(m.group("root"))
        if is_root:
            root_ops = operands
        instrs.append((idx, name, shape, op, operands,
                       _strip_scope(op_name), is_root))
    return args, instrs, root_ops


def _liveness(instrs, batch_size: Optional[int]):
    """Scheduled liveness walk over the entry computation.

    Returns (peak_temp_bytes, peak_idx, live_at_peak records,
    batch_scaled_peak_bytes, comm_peak_bytes). Buffers are live from
    their defining instruction to their last top-level use; arguments
    are excluded here (they are live program-wide and counted from
    ``memory_analysis`` argument bytes instead)."""
    defs: Dict[str, Tuple[int, int, str, str, str]] = {}
    last_use: Dict[str, int] = {}
    for idx, name, shape, op, operands, scope, _root in instrs:
        for o in operands:
            if o in defs:
                last_use[o] = idx
        if op in _NO_ALLOC_OPS:
            continue
        nbytes = shape_bytes(shape)
        if nbytes <= 0:
            continue
        defs[name] = (idx, nbytes, shape, op, scope)

    events: Dict[int, int] = {}
    for name, (didx, nbytes, _s, _o, _sc) in defs.items():
        events[didx] = events.get(didx, 0) + nbytes
        end = last_use.get(name, didx)
        events[end + 1] = events.get(end + 1, 0) - nbytes
    live, peak, peak_idx = 0, 0, 0
    for idx in sorted(events):
        live += events[idx]
        if live > peak:
            peak, peak_idx = live, idx

    at_peak: List[BufferRecord] = []
    batch_peak = comm_peak = 0
    for name, (didx, nbytes, shape, op, scope) in defs.items():
        if didx <= peak_idx <= last_use.get(name, didx):
            cls = classify_scope(scope, op)
            scaled = bool(batch_size and batch_size > 1
                          and _leading_dim(shape) == batch_size)
            at_peak.append(BufferRecord(
                name=name, kind="temp", bytes=nbytes, shape=shape,
                cls=cls, scope=scope, batch_scaled=scaled))
            if scaled:
                batch_peak += nbytes
            if cls == "comm":
                comm_peak += nbytes
    at_peak.sort(key=lambda r: -r.bytes)
    return peak, peak_idx, at_peak, batch_peak, comm_peak


# --- the report --------------------------------------------------------------

def _fmt_bytes(n: Optional[float]) -> str:
    from apex_tpu.utils.format import fmt_bytes
    return fmt_bytes(n)


@dataclasses.dataclass
class MemoryReport:
    """Per-class, per-buffer HBM footprint of one compiled step."""

    stats: Dict[str, int]             # memory_analysis totals
    classes: Dict[str, int]           # BUFFER_CLASSES -> bytes
    buffers: List[BufferRecord]       # arguments + temps live at peak
    peak_live_bytes: int              # args + peak live temps (estimate)
    batch_size: Optional[int]         # per-device batch the step compiled at
    batch_bytes: int                  # peak bytes scaling with that batch
    hbm_limit: Optional[int]          # device capacity, None off-TPU
    device_kind: str
    flops: float = 0.0                # XLA cost analysis, for context

    @property
    def total_bytes(self) -> int:
        return self.stats["total"]

    def attributed_total(self) -> int:
        """Sum over classes — scripts/memory_budget.py asserts this
        matches ``memory_analysis()`` within 1%."""
        return sum(self.classes.values())

    # -- what-if batch scaler ------------------------------------------------

    def forecast(self, batch: int) -> Dict[str, Any]:
        """Forecast peak-live bytes at another (per-device) batch size:
        batch-scaled buffers grow linearly, the rest is fixed. ``fits``
        is None when the device doesn't report HBM capacity."""
        if not self.batch_size or self.batch_size < 1:
            raise ValueError("report was built without batch_size=")
        scale = batch / self.batch_size
        peak = int(self.peak_live_bytes - self.batch_bytes
                   + self.batch_bytes * scale)
        fits = None if self.hbm_limit is None else peak <= self.hbm_limit
        return {"batch": batch, "peak_bytes": peak, "fits": fits,
                "headroom_bytes": (None if self.hbm_limit is None
                                   else self.hbm_limit - peak)}

    def max_batch(self) -> Optional[int]:
        """Largest forecast (per-device) batch fitting HBM capacity —
        None when capacity or batch scaling is unknown."""
        if (self.hbm_limit is None or not self.batch_size
                or self.batch_bytes <= 0):
            return None
        fixed = self.peak_live_bytes - self.batch_bytes
        if fixed >= self.hbm_limit:
            return 0
        per_item = self.batch_bytes / self.batch_size
        return int((self.hbm_limit - fixed) // per_item)

    # -- renderings ----------------------------------------------------------

    def top_buffers(self, n: int = 8) -> List[BufferRecord]:
        return sorted(self.buffers, key=lambda r: -r.bytes)[:n]

    def table(self, top: int = 10) -> str:
        s = self.stats
        lines = [
            f"memory report — device={self.device_kind} "
            f"total={_fmt_bytes(s['total'])} "
            f"(args {_fmt_bytes(s['argument'])} + out "
            f"{_fmt_bytes(s['output'])} + temp {_fmt_bytes(s['temp'])} + "
            f"code {_fmt_bytes(s['generated_code'])}; aliased "
            f"{_fmt_bytes(s['alias'])})",
            f"peak live estimate: {_fmt_bytes(self.peak_live_bytes)}"
            + (f" of {_fmt_bytes(self.hbm_limit)} HBM"
               if self.hbm_limit else "")
            + (f"; batch-scaled {_fmt_bytes(self.batch_bytes)} "
               f"@ b={self.batch_size}" if self.batch_size else ""),
            f"{'class':<16} {'bytes':>12} {'share':>7}",
        ]
        total = max(self.attributed_total(), 1)
        for cls in BUFFER_CLASSES:
            b = self.classes.get(cls, 0)
            lines.append(f"{cls:<16} {_fmt_bytes(b):>12} "
                         f"{100.0 * b / total:>6.1f}%")
        lines.append(f"top buffers ({min(top, len(self.buffers))} of "
                     f"{len(self.buffers)}):")
        for r in self.top_buffers(top):
            where = r.scope or r.name
            lines.append(f"  {_fmt_bytes(r.bytes):>12} {r.cls:<16} "
                         f"{r.kind:<8} {where[:60]}")
        mb = self.max_batch()
        if mb is not None:
            lines.append(f"forecast: max per-device batch ~{mb} before "
                         f"HBM capacity")
        return "\n".join(lines)

    def summary(self, top: int = 5) -> Dict[str, Any]:
        """JSON-able digest for crash dumps / the memory event channel
        — the piece :class:`apex_tpu.trace.FlightRecorder` embeds so an
        OOM dump names the biggest buffers."""
        return {
            "total_bytes": self.total_bytes,
            "attributed_bytes": self.attributed_total(),
            "peak_live_bytes": self.peak_live_bytes,
            "hbm_limit": self.hbm_limit,
            "batch_size": self.batch_size,
            "batch_bytes": self.batch_bytes,
            "classes": dict(self.classes),
            "top_buffers": [
                {"name": r.name, "bytes": r.bytes, "class": r.cls,
                 "scope": r.scope[:120]} for r in self.top_buffers(top)],
        }

    def to_event(self, rank: int = 0, step: Optional[int] = None) -> Dict:
        """``kind="memory_report"`` event for the memory channel
        (``check_metrics_schema.py --kind memory`` validates)."""
        ev: Dict[str, Any] = {"kind": "memory_report", "rank": rank,
                              "step": step}
        ev.update(self.summary())
        return ev


def memory_report(fn, *args, batch_size: Optional[int] = None,
                  **kwargs) -> MemoryReport:
    """Build a :class:`MemoryReport` for a step function.

    ``fn`` may be a plain callable (jitted here), a jitted function, or
    an **already-compiled** executable (``.lower(...).compile()`` —
    then pass no args and nothing compiles here). ``batch_size`` is the
    per-device batch dimension of the compiled step; buffers whose
    leading dim equals it are marked batch-scaled and drive
    :meth:`MemoryReport.forecast`. AOT-only: no dispatch ever runs.
    """
    if hasattr(fn, "memory_analysis") and hasattr(fn, "as_text"):
        compiled = fn
    else:
        compiled = _compile(fn, *args, **kwargs)
    stats = memory_stats_of(compiled)
    text = compiled.as_text()
    try:
        flops = float(cost_analysis_of(compiled).get("flops", 0.0))
    except Exception:
        flops = 0.0

    arg_records: List[BufferRecord] = []
    classes = {cls: 0 for cls in BUFFER_CLASSES}
    args_meta, instrs, _root = parse_entry(text)
    parsed_arg_bytes = 0
    for name, shape, path, _pnum in args_meta:
        nbytes = shape_bytes(shape)
        parsed_arg_bytes += nbytes
        cls = classify_arg_path(path or name)
        scaled = bool(batch_size and batch_size > 1
                      and _leading_dim(shape) == batch_size)
        arg_records.append(BufferRecord(
            name=name, kind="argument", bytes=nbytes, shape=shape,
            cls=cls, scope=path, batch_scaled=scaled))
        classes[cls] += nbytes
    # XLA may pad/align argument allocations: scale the parsed per-path
    # attribution to the memory_analysis argument total so the class sum
    # stays closed over the real allocation
    if parsed_arg_bytes and stats["argument"]:
        ratio = stats["argument"] / parsed_arg_bytes
        if abs(ratio - 1.0) > 1e-6:
            for r in arg_records:
                r.bytes = int(r.bytes * ratio)
            for cls in ("params", "optimizer_state", "inputs", "comm"):
                classes[cls] = int(classes[cls] * ratio)

    peak_temp, _peak_idx, at_peak, batch_peak, comm_peak = _liveness(
        instrs, batch_size)
    # temps: the memory_analysis temp+code total is authoritative; the
    # comm share is carved out from the scope-attributed liveness peak
    temp_total = stats["temp"] + stats["generated_code"]
    comm_bytes = min(comm_peak, temp_total)
    classes["comm"] += comm_bytes
    classes["activations"] += temp_total - comm_bytes
    classes["outputs"] += stats["output"]

    arg_bytes_scaled = sum(r.bytes for r in arg_records
                           if r.batch_scaled)
    peak_live = stats["argument"] + min(peak_temp, temp_total or peak_temp)

    dev = jax.devices()[0]
    return MemoryReport(
        stats=stats, classes=classes,
        buffers=arg_records + at_peak,
        peak_live_bytes=peak_live,
        batch_size=batch_size,
        batch_bytes=batch_peak + arg_bytes_scaled,
        hbm_limit=hbm_capacity(dev),
        device_kind=getattr(dev, "device_kind", "?"),
        flops=flops)
