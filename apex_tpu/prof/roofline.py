"""Per-op roofline attribution: measured time vs attainable time.

PERF.md has been closing this loop by hand for five rounds: join each
hot op's *measured* device time (xplane trace) with its *analytic* cost
(FLOPs + bytes from the optimized HLO), price it against the chip's
peaks (MXU FLOP/s, HBM bytes/s), and the ops whose measured time sits
above their attainable bound are the remaining MFU points. This module
is that ledger as a tool:

    report = prof.roofline_report(compiled, profile)
    print(report.table())
    for gap in report.worst_gaps(5): ...   # the autotuner's candidates

- **analytic side** — per top-level instruction of the optimized HLO:
  dot/conv FLOPs (including FLOPs of the fused computation a ``fusion``
  calls, attributed to the calling instruction — the unit the device
  actually times), HBM bytes = operand + result bytes of the top-level
  op (fused temps live in registers/VMEM), attention-kernel FLOPs for
  ``tpu_custom_call`` ops recognized by scope (4·B·H·S²·D forward,
  10·B·H·S²·D backward, with the d<128 lane-cap on the attainable MXU
  rate — the d=64 cap PERF.md's BERT ledger prices by hand);
- **measured side** — a :class:`~apex_tpu.prof.xplane.TraceProfile`
  (live capture on TPU, committed ``tests/fixtures/*.xplane.pb`` in
  CPU CI). Rows without a measurement (AOT-only audits) carry
  ``measured_us=None`` — classification still works, gaps don't;
- **peak table** — :data:`~apex_tpu.prof.report.PEAK_FLOPS` +
  :data:`~apex_tpu.prof.report.PEAK_HBM_BW` (spec sheets; provenance in
  docs/profiling.md#roofline). Each op classifies **compute-bound** or
  **memory-bound** by which bound is larger; ``efficiency`` =
  attainable/measured, clamped to [0, 1] (co-scheduled overlap can beat
  an isolated-op bound — see PERF.md's ResNet mega-fusions);
- **kernel families** — rows aggregate by the named-scope conventions
  the tracer already enforces (attention / layer_norm / mlp / bn_act /
  xentropy / …), and :meth:`RooflineReport.worst_gaps` emits the
  fingerprinted (family, shape, dtype) candidate list ROADMAP item 4's
  autotuner consumes — the *measured* complement of apexlint APX104's
  static tile-padding findings.

Events: ``kind="roofline"`` through ``MetricsLogger(roofline_sink=…)``;
``check_metrics_schema.py --kind roofline`` validates. The asserted CI
audit is ``scripts/roofline_audit.py --cpu8`` (attribution closure over
the committed fixtures + the sentinel replay); the perf-regression gate
over bench trajectories is :mod:`apex_tpu.prof.sentinel`.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from apex_tpu.prof.hlo import _DTYPE_BYTES, _conv_flops, _dot_flops
from apex_tpu.prof.report import PEAK_FLOPS, PEAK_HBM_BW, lookup_peak
from apex_tpu.prof.xplane import HLO_TEXT_SCOPE_RE, strip_scope

__all__ = ["RooflineRow", "RooflineReport", "roofline_report",
           "classify_family", "FAMILIES", "BOUND_CLASSES"]

#: kernel families the aggregation and the autotuner key on — the five
#: fused-op families apex_tpu ships kernels for, plus the structural
#: fallbacks for everything else
FAMILIES = ("attention", "layer_norm", "mlp", "bn_act", "xentropy",
            "optimizer", "gemm", "conv", "collective", "copy", "other")

#: roofline bound classes (the schema enum)
BOUND_CLASSES = ("compute", "memory", "unknown")

# scope-substring → family, first match wins (checked against the
# lowercased stripped scope path; the named-scope conventions the
# tracer/kernels already emit — bench/prof_bert flax module paths land
# here too via their module names)
_FAMILY_PATTERNS: Tuple[Tuple[str, str], ...] = (
    ("flash_attention", "attention"),
    ("attention", "attention"),
    ("attn", "attention"),
    ("layer_norm", "layer_norm"),
    ("layernorm", "layer_norm"),
    ("fused_layer_norm", "layer_norm"),
    ("bn_relu", "bn_act"),
    ("bn_act", "bn_act"),
    ("bn_bwd", "bn_act"),
    ("batchnorm", "bn_act"),
    ("xentropy", "xentropy"),
    ("cross_entropy", "xentropy"),
    ("softmax_xent", "xentropy"),
    ("mlp", "mlp"),
    ("dense", "mlp"),
    ("lamb", "optimizer"),
    ("adam", "optimizer"),
    ("fused_sgd", "optimizer"),
    ("apply_gradients", "optimizer"),
    ("optim", "optimizer"),
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_INSTR_RE = re.compile(
    r"^(?:ROOT )?%?(?P<n>[^ ]+) = "
    r"(?P<shape>\((?:[^()]|\([^()]*\))*\)|[^ ]+) "
    r"(?P<op>[\w-]+)\((?P<args>[^)]*)\)")
# a computation header: "%fused_computation.3 (p0: bf16[..]) -> .. {"
# or "ENTRY %main.42 (..) -> .. {"
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(|\{)")

# result-only opcodes that never own device time / HBM traffic
_SKIP_OPS = ("parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "partition-id", "replica-id",
             "opt-barrier")

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute",
                   "collective-broadcast", "ragged-all-to-all")


def classify_family(scope: str, opcode: str = "",
                    category: str = "") -> str:
    """Kernel family of an op from its stripped named-scope path, with
    the opcode/category as structural fallback."""
    s = (scope or "").lower()
    for pat, fam in _FAMILY_PATTERNS:
        if pat in s:
            return fam
    if opcode.startswith(_COLLECTIVE_OPS) or category == "collective":
        return "collective"
    if opcode == "dot" or category == "gemm":
        return "gemm"
    if opcode == "convolution" or category == "conv":
        return "conv"
    if opcode == "copy" or category == "copy":
        return "copy"
    return "other"


def _shape_elems_bytes(shape_text: str) -> Tuple[int, int]:
    total_e = total_b = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        elems = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        total_e += elems
        total_b += elems * _DTYPE_BYTES[dt]
    return total_e, total_b


def _result_dtype(shape_text: str) -> str:
    m = _SHAPE_RE.search(shape_text)
    return m.group(1) if m else "?"


def _operand_names(args_text: str) -> List[str]:
    if "%" in args_text:
        return re.findall(r"%([^\s,)]+)", args_text)
    return [a.strip().split()[-1] for a in args_text.split(",")
            if a.strip()]


def _attention_call(qshape: str, scope_raw: str) -> Optional[Tuple[float,
                                                                   float]]:
    """(flops, mxu_cap) for a flash-attention ``tpu_custom_call`` given
    its q operand's HLO shape text, or None when the shape doesn't
    parse as an attention operand.

    The FLOPs of a fused attention kernel are invisible to HLO (a
    custom-call has no dot): they are reconstructed from the q operand's
    shape — (B, S, H, D) native layout or (B·H, S, D) transposed —
    as 4·B·H·S²·D forward (QKᵀ + PV) and 10·B·H·S²·D backward
    (dQ/dK/dV re-walk s and p; the 2.5× rule PERF.md's ledger uses).
    ``mxu_cap`` is min(1, D/128): a D<128 contraction fills D of the
    128 lanes, capping the attainable MXU rate — the d=64 cap that
    makes the BERT backward's ~440 µs floor, not ~220.
    """
    m = _SHAPE_RE.search(qshape or "")
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",") if d]
    if len(dims) == 4:            # (B, S, H, D) native layout
        b, s, h, d = dims
        bh = b * h
    elif len(dims) == 3:          # (B·H, S, D) transposed wrappers
        bh, s, d = dims
    else:
        return None
    raw = scope_raw or ""
    bwd = "transpose(" in raw or "_bwd" in raw or "/bwd" in raw
    factor = 10.0 if bwd else 4.0
    flops = factor * bh * float(s) * float(s) * d
    return flops, min(1.0, d / 128.0)


def _module_costs(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """Per-entry-instruction analytic costs from optimized HLO text.

    Returns {name: {flops, bytes, opcode, shape, scope, scope_raw,
    mxu_cap, hlo}}. Walks every computation once building a module-wide
    name→shape table and per-computation dot/conv FLOP sums, then folds
    each fused computation's FLOPs into the calling entry instruction —
    the unit the profiler times.
    """
    shapes: Dict[str, str] = {}
    # (comp, name, shape, opcode, args_text, line, is_entry)
    parsed: List[Tuple[str, str, str, str, str, str, bool]] = []
    comp, in_entry = "", False
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if raw and not raw.startswith(" ") and line.endswith("{"):
            m = _COMP_RE.match(line)
            if m:
                comp, in_entry = m.group(2), bool(m.group(1))
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name = m.group("n").lstrip("%")
        shapes[name] = m.group("shape")
        # older printers (and xplane op metadata) inline operand types:
        # "fusion(bf16[64,256]{1,0} %p0, ...)" — harvest them so
        # operands resolve even without module-level definitions (the
        # committed-fixture path); real definitions win
        for sh, onm in re.findall(
                r"(\w+\[[\d,]*\][^\s]*)\s+%([^\s,)]+)", line):
            shapes.setdefault(onm, sh)
        parsed.append((comp, name, m.group("shape"), m.group("op"),
                       m.group("args"), line, in_entry))

    # per-computation dot/conv FLOPs (the fused bodies)
    comp_flops: Dict[str, float] = {}
    instr_flops: Dict[str, float] = {}
    for comp, name, shape, op, args_text, line, _entry in parsed:
        if op not in ("dot", "convolution"):
            continue
        operands = _operand_names(args_text)
        out_elems, _ = _shape_elems_bytes(shape)
        if op == "dot":
            f = _dot_flops(line, out_elems, operands, shapes)
        else:
            f = _conv_flops(line, out_elems, operands, shapes)
        instr_flops[name] = f
        comp_flops[comp] = comp_flops.get(comp, 0.0) + f

    out: Dict[str, Dict[str, Any]] = {}
    for comp, name, shape, op, args_text, line, entry in parsed:
        if not entry or op in _SKIP_OPS:
            continue
        operands = _operand_names(args_text)
        _, out_bytes = _shape_elems_bytes(shape)
        _, in_bytes = _shape_elems_bytes(
            " ".join(shapes.get(o, "") for o in operands))
        flops = instr_flops.get(name, 0.0)
        called = _CALLS_RE.search(line)
        if called:
            flops += comp_flops.get(called.group(1), 0.0)
        sm = HLO_TEXT_SCOPE_RE.search(line)
        scope_raw = sm.group(1) if sm else ""
        mxu_cap = 1.0
        if (op == "custom-call"
                and classify_family(strip_scope(scope_raw)) == "attention"):
            # q = the first operand; its shape comes from the module
            # symbol table, or inline from the call itself (the xplane
            # metadata path, where operand types are printed in place)
            qshape = shapes.get(operands[0], "") if operands else ""
            if not _SHAPE_RE.search(qshape):
                tail = line.split(f" {op}(", 1)[-1].split(")", 1)[0]
                qshape = tail
            attn = _attention_call(qshape, scope_raw)
            if attn is not None:
                flops, mxu_cap = attn
        out[name] = {"flops": flops, "bytes": float(out_bytes + in_bytes),
                     "opcode": op, "shape": shape,
                     "scope": strip_scope(scope_raw),
                     "scope_raw": scope_raw, "mxu_cap": mxu_cap,
                     "hlo": line[:400]}
    return out


@dataclasses.dataclass
class RooflineRow:
    """One op's measured-vs-attainable verdict."""

    name: str                     # HLO instruction name
    opcode: str
    family: str                   # one of FAMILIES
    scope: str                    # stripped named-scope path
    flops: float                  # per execution
    bytes: float                  # HBM traffic per execution (bound)
    occurrences: int              # executions in the trace (0 AOT-only)
    measured_us: Optional[float]  # avg device us per execution, or None
    compute_us: float             # flops / (peak_flops * mxu_cap)
    memory_us: float              # bytes / hbm_bw
    bound: str                    # one of BOUND_CLASSES
    dtype: str                    # result dtype
    shape: str                    # result shape text
    mxu_cap: float = 1.0          # attainable-rate cap (d<128 attention)
    hlo: str = ""

    @property
    def attainable_us(self) -> float:
        """The roofline bound: max of the compute and memory floors."""
        return max(self.compute_us, self.memory_us)

    @property
    def efficiency(self) -> Optional[float]:
        """attainable/measured ∈ [0, 1]; None without a measurement or
        a bound (the schema's nullable-efficiency contract)."""
        if self.measured_us is None or self.measured_us <= 0:
            return None
        att = self.attainable_us
        if att <= 0:
            return None
        return min(1.0, att / self.measured_us)

    @property
    def gap_us(self) -> Optional[float]:
        """Total measured time above the bound across all occurrences
        (the prize for closing this op), None on AOT-only rows."""
        if self.measured_us is None or self.attainable_us <= 0:
            return None
        return max(0.0, (self.measured_us - self.attainable_us)
                   * max(self.occurrences, 1))

    @property
    def fingerprint(self) -> str:
        """Stable (family, scope, dtype, shape) key — the tuning-DB /
        waiver identity, apexlint-fingerprint style (never includes
        measured numbers, so reruns agree)."""
        dims = _SHAPE_RE.search(self.shape)
        shape = f"{dims.group(1)}[{dims.group(2)}]" if dims else self.shape
        return f"{self.family}|{self.opcode}|{self.scope}|{shape}"

    def to_event(self, rank: int = 0, step: Optional[int] = None) -> Dict:
        """``kind="roofline"`` event (``check_metrics_schema.py --kind
        roofline`` validates)."""
        return {"kind": "roofline", "rank": rank, "step": step,
                "op": self.name, "opcode": self.opcode,
                "family": self.family, "scope": self.scope,
                "bound": self.bound, "flops": self.flops,
                "bytes": self.bytes,
                "attainable_us": round(self.attainable_us, 3),
                "measured_us": (None if self.measured_us is None
                                else round(self.measured_us, 3)),
                "efficiency": (None if self.efficiency is None
                               else round(self.efficiency, 4)),
                "gap_us": (None if self.gap_us is None
                           else round(self.gap_us, 3)),
                "occurrences": self.occurrences, "dtype": self.dtype,
                "fingerprint": self.fingerprint}


def _fmt_us(v: Optional[float]) -> str:
    return "n/a" if v is None else f"{v:.1f}"


@dataclasses.dataclass
class RooflineReport:
    """Per-op roofline ledger of one profiled (or AOT-audited) step."""

    rows: List[RooflineRow]           # sorted by gap desc, then bytes
    device_kind: str
    peak_flops: float                 # 0.0 when the chip is unknown
    hbm_bw: float
    profile_total_us: float           # sum of per-op trace time
    module_total_us: float            # device time inside XLA modules
    module_runs: int

    @property
    def measured(self) -> bool:
        return any(r.measured_us is not None for r in self.rows)

    def check_closure(self, tolerance: float = 0.05
                      ) -> Tuple[bool, float]:
        """Attribution closure: the per-op times the report attributed
        must cover the trace's total device time inside XLA modules
        within ``tolerance`` (an op the join dropped = a hole in the
        ledger). (ok, relative_error); trivially ok on AOT-only
        reports."""
        attributed = sum((r.measured_us or 0.0) * max(r.occurrences, 1)
                         for r in self.rows)
        total = self.module_total_us
        if total <= 0:
            return True, 0.0
        err = abs(attributed - total) / total
        return err <= tolerance, err

    def by_family(self) -> Dict[str, Dict[str, float]]:
        """Per-family aggregate: measured/attainable us (summed over
        occurrences), flops, bytes, efficiency."""
        out: Dict[str, Dict[str, float]] = {}
        for r in self.rows:
            occ = max(r.occurrences, 1)
            agg = out.setdefault(r.family, {
                "measured_us": 0.0, "attainable_us": 0.0,
                "flops": 0.0, "bytes": 0.0, "n_ops": 0})
            agg["n_ops"] += 1
            agg["flops"] += r.flops * occ
            agg["bytes"] += r.bytes * occ
            agg["attainable_us"] += r.attainable_us * occ
            if r.measured_us is not None:
                agg["measured_us"] += r.measured_us * occ
        for agg in out.values():
            m, a = agg["measured_us"], agg["attainable_us"]
            agg["efficiency"] = (round(min(1.0, a / m), 4)
                                 if m > 0 and a > 0 else None)
        return dict(sorted(out.items(),
                           key=lambda kv: -kv[1]["measured_us"]))

    def by_scope(self, depth: int = 2) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for r in self.rows:
            occ = max(r.occurrences, 1)
            key = "/".join([p for p in r.scope.split("/") if p][:depth]) \
                or "(unscoped)"
            agg = out.setdefault(key, {"measured_us": 0.0,
                                       "attainable_us": 0.0})
            agg["attainable_us"] += r.attainable_us * occ
            if r.measured_us is not None:
                agg["measured_us"] += r.measured_us * occ
        return dict(sorted(out.items(),
                           key=lambda kv: -kv[1]["measured_us"]))

    def what_if(self, plan: Dict[str, str]) -> List[Dict[str, Any]]:
        """The what-if dtype column: attainable time per op if a
        precision-placement verdict were applied.

        ``plan`` maps a *site* (a case-insensitive substring of the
        stripped scope — the :func:`apex_tpu.monitor.numerics.site_names`
        convention) to a target format (``FORMAT_TABLE`` key like
        ``"fp8_e4m3"`` or an HLO dtype like ``"bf16"``). For every
        matching row with a priced dtype, the HBM-traffic bound scales
        by the byte ratio and the MXU bound by the spec-sheet dtype
        ladder (each halving of element width doubles the attainable
        FLOP rate — the fp8-doubles-bf16 MXU model
        docs/profiling.md#whatif states; the roofline observatory then
        *verifies* a landed kernel actually collects, ROADMAP item 5).
        Returns JSON-able rows with ``whatif_attainable_us`` and the
        per-occurrence-summed ``whatif_gain_us`` —
        :func:`apex_tpu.monitor.numerics.placement_advisor` ranks them
        by gain × numeric safety."""
        fmt_bytes = {"fp8_e4m3": 1, "fp8_e5m2": 1, "fp16": 2,
                     "bf16": 2, "fp32": 4}
        out: List[Dict[str, Any]] = []
        for site, target in plan.items():
            b_new = fmt_bytes.get(target, _DTYPE_BYTES.get(target))
            if b_new is None:
                raise ValueError(f"what_if target {target!r} is not a "
                                 f"known format or HLO dtype")
            needle = site.lower()
            for r in self.rows:
                if needle not in r.scope.lower():
                    continue
                b_cur = _DTYPE_BYTES.get(r.dtype)
                if b_cur is None or b_new >= b_cur:
                    continue      # target not narrower — no what-if
                ratio = b_new / b_cur
                new_compute = r.compute_us * ratio
                new_memory = r.memory_us * ratio
                whatif = max(new_compute, new_memory)
                gain = max(0.0, (r.attainable_us - whatif)
                           * max(r.occurrences, 1))
                out.append({
                    "site": site, "op": r.name, "scope": r.scope,
                    "family": r.family, "fingerprint": r.fingerprint,
                    "dtype_from": r.dtype, "dtype_to": target,
                    "bound": r.bound,
                    "attainable_us": round(r.attainable_us, 3),
                    "whatif_attainable_us": round(whatif, 3),
                    "whatif_gain_us": round(gain, 3),
                    "measured_us": (None if r.measured_us is None
                                    else round(r.measured_us, 3)),
                    "occurrences": r.occurrences})
        out.sort(key=lambda e: -e["whatif_gain_us"])
        return out

    def worst_gaps(self, k: int = 5) -> List[Dict[str, Any]]:
        """The top-k ops by total time above their roofline — the
        committed, fingerprinted candidate list ROADMAP item 4's
        autotuner consumes (each entry a JSON-able dict; APX104's
        static tile-padding findings are the AOT complement)."""
        gaps = [r for r in self.rows
                if r.gap_us is not None and r.gap_us > 0]
        gaps.sort(key=lambda r: -r.gap_us)
        return [{"fingerprint": r.fingerprint, "op": r.name,
                 "family": r.family, "scope": r.scope,
                 "dtype": r.dtype, "shape": r.shape,
                 "bound": r.bound,
                 "measured_us": round(r.measured_us, 3),
                 "attainable_us": round(r.attainable_us, 3),
                 "gap_us": round(r.gap_us, 3),
                 "efficiency": round(r.efficiency, 4),
                 "occurrences": r.occurrences}
                for r in gaps[:k]]

    def table(self, top: int = 12) -> str:
        head = (f"roofline — device={self.device_kind or '?'} "
                f"peak={self.peak_flops / 1e12:.0f} TFLOP/s "
                f"hbm={self.hbm_bw / 1e9:.0f} GB/s "
                f"ops={len(self.rows)}")
        lines = [head,
                 f"{'op':<26} {'family':<11} {'bound':<8} "
                 f"{'meas_us':>8} {'attain':>8} {'eff':>6} {'gap_us':>8}"]
        rows = sorted(self.rows, key=lambda r: -(r.gap_us or 0.0))
        for r in rows[:top]:
            eff = f"{r.efficiency:.0%}" if r.efficiency is not None \
                else "n/a"
            lines.append(
                f"{r.name[:26]:<26} {r.family:<11} {r.bound:<8} "
                f"{_fmt_us(r.measured_us):>8} "
                f"{_fmt_us(r.attainable_us):>8} {eff:>6} "
                f"{_fmt_us(r.gap_us):>8}")
        fams = self.by_family()
        if fams:
            lines.append("by family: " + "  ".join(
                f"{k}={v['measured_us']:.0f}us"
                + (f"@{v['efficiency']:.0%}"
                   if v.get("efficiency") is not None else "")
                for k, v in list(fams.items())[:6]))
        return "\n".join(lines)

    def summary(self, k: int = 3) -> Dict[str, Any]:
        """JSON-able digest (the bench `roofline_worst_gap` column)."""
        ok, err = self.check_closure()
        gaps = self.worst_gaps(k)
        return {"n_ops": len(self.rows), "measured": self.measured,
                "device": self.device_kind,
                "closure_ok": bool(ok),
                "closure_err": round(err, 6),
                "worst_gaps": gaps,
                "worst_gap_us": gaps[0]["gap_us"] if gaps else None}

    def to_events(self, rank: int = 0, step: Optional[int] = None,
                  top: Optional[int] = None) -> List[Dict]:
        rows = self.rows if top is None else self.rows[:top]
        return [r.to_event(rank=rank, step=step) for r in rows]


def _classify_bound(flops: float, nbytes: float, compute_us: float,
                    memory_us: float) -> str:
    if compute_us <= 0 and memory_us <= 0:
        return "unknown"
    if flops > 0 and compute_us >= memory_us:
        return "compute"
    return "memory" if nbytes > 0 else "unknown"


def roofline_report(compiled=None, profile=None, *,
                    peak_flops: Optional[float] = None,
                    hbm_bw: Optional[float] = None,
                    device_kind: Optional[str] = None) -> RooflineReport:
    """Join analytic per-op cost with measured per-op device time
    against the chip's peak table.

    ``compiled`` — a compiled executable (``.lower(...).compile()``),
    or its optimized-HLO text, or None. ``profile`` — a
    :class:`~apex_tpu.prof.TraceProfile` (``prof.parse_trace``), or
    None for an AOT-only report (rows carry ``measured_us=None``).
    At least one of the two must be given. Measured ops absent from
    the compiled module (or when ``compiled`` is None) fall back to
    analytic costs parsed from their own xplane HLO metadata — which
    carries inline operand types — so the committed fixtures audit
    tf-free in CPU CI with no module at hand.

    ``peak_flops``/``hbm_bw`` default to the attached device's spec
    table (:data:`PEAK_FLOPS` / :data:`PEAK_HBM_BW`); on unknown chips
    (CPU) they are 0 and every row classifies ``unknown`` unless peaks
    are passed explicitly. AOT-only and never dispatches.
    """
    if compiled is None and profile is None:
        raise ValueError("roofline_report needs a compiled module, a "
                         "TraceProfile, or both")
    if device_kind is None:
        try:
            import jax
            device_kind = getattr(jax.devices()[0], "device_kind", "?")
        except Exception:
            device_kind = "?"
    if peak_flops is None:
        peak_flops = lookup_peak(PEAK_FLOPS, device_kind)
    if hbm_bw is None:
        hbm_bw = lookup_peak(PEAK_HBM_BW, device_kind)

    costs: Dict[str, Dict[str, Any]] = {}
    if compiled is not None:
        text = compiled if isinstance(compiled, str) else \
            compiled.as_text()
        costs = _module_costs(text)

    def _mk(name, cost, occurrences, measured_us, category=""):
        flops, nbytes = cost["flops"], cost["bytes"]
        cap = cost.get("mxu_cap", 1.0)
        compute_us = (flops / (peak_flops * cap) * 1e6
                      if peak_flops > 0 and flops > 0 else 0.0)
        memory_us = (nbytes / hbm_bw * 1e6
                     if hbm_bw > 0 and nbytes > 0 else 0.0)
        return RooflineRow(
            name=name, opcode=cost["opcode"],
            family=classify_family(cost["scope"], cost["opcode"],
                                   category),
            scope=cost["scope"], flops=flops, bytes=nbytes,
            occurrences=occurrences, measured_us=measured_us,
            compute_us=compute_us, memory_us=memory_us,
            bound=_classify_bound(flops, nbytes, compute_us, memory_us),
            dtype=_result_dtype(cost["shape"]), shape=cost["shape"],
            mxu_cap=cap, hlo=cost["hlo"])

    rows: List[RooflineRow] = []
    seen = set()
    profile_total = module_total = 0.0
    module_runs = 0
    if profile is not None:
        module_total = profile.module_total_us
        module_runs = profile.module_runs
        for rec in profile.ops:
            profile_total += rec.total_us
            cost = costs.get(rec.name)
            if cost is None:
                # analytic from the op's own instruction text (inline
                # operand types — the committed-fixture path), with the
                # trace's scope put where a module's text carries it
                cost = _module_costs(
                    "ENTRY fallback {\n  " + rec.hlo
                    + f', metadata={{op_name="{rec.scope}"}}\n}}'
                ).get(rec.name)
            if cost is None:
                cost = {"flops": 0.0, "bytes": 0.0, "opcode": rec.opcode,
                        "shape": "", "scope": strip_scope(rec.scope),
                        "scope_raw": rec.scope,
                        "mxu_cap": 1.0, "hlo": rec.hlo[:400]}
            seen.add(rec.name)
            rows.append(_mk(rec.name, cost, rec.occurrences,
                            rec.avg_us, rec.category))
    for name, cost in costs.items():
        if name not in seen:
            rows.append(_mk(name, cost, 0, None))
    rows.sort(key=lambda r: (-(r.gap_us or 0.0),
                             -(r.measured_us or 0.0) * max(r.occurrences,
                                                           1),
                             -r.bytes))
    return RooflineReport(rows=rows, device_kind=device_kind,
                          peak_flops=peak_flops, hbm_bw=hbm_bw,
                          profile_total_us=profile_total,
                          module_total_us=module_total,
                          module_runs=module_runs)
