"""Static per-step collective-traffic accounting from compiled HLO.

The reference could only *infer* allreduce volume from its own bucketing
bookkeeping (`apex/parallel/distributed.py:425-475`); on TPU the compiled
program itself is the ground truth: every collective the step performs is
an instruction in the optimized HLO with a typed result shape. This
module walks that text and sums result bytes per collective opcode —
a compile-time constant per executable, fetched once and attached to
every logged record (the accounting DynamiQ-style compressed collectives
need as their uncompressed baseline).

Async pairs (``all-reduce-start``/``all-reduce-done``) are counted once,
at the ``-done`` (whose result is the actual output shape); the
``-start`` result tuples carry both operand and result buffers and would
double-count.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from apex_tpu.prof import hlo as _hlo
from apex_tpu.prof import xplane as _xplane

__all__ = ["COLLECTIVE_OPCODES", "collective_bytes",
           "collective_bytes_from_text", "collective_bytes_by_dtype",
           "collective_bytes_by_hop", "collective_bytes_by_axis",
           "scope_hop", "scope_axis_row", "wire_report"]

# The canonical prefix list lives next to the trace categorizer so live
# accounting and post-hoc attribution bucket opcodes identically.
COLLECTIVE_OPCODES = _xplane.COLLECTIVE_PREFIXES


def collective_bytes_from_text(hlo_text: str) -> Dict[str, int]:
    """Sum collective result bytes per opcode over an optimized-HLO dump.

    Returns ``{opcode: bytes, ..., "total": bytes}`` (opcodes with zero
    traffic are omitted; ``total`` is always present). A thin rollup of
    :func:`collective_bytes_by_dtype` — one scan, two views.

    Known limit: each instruction is counted ONCE — a collective inside
    a ``while``/``scan`` body (e.g. a per-microbatch psum) executes
    trip-count times per step but is summed once, so loop-wrapped steps
    are under-reported by the trip count. Hoist collectives out of the
    loop (the usual accumulate-then-sync pattern) or scale the estimate
    by the trip count yourself.
    """
    totals = {op: sum(per.values())
              for op, per in collective_bytes_by_dtype(hlo_text).items()}
    totals["total"] = sum(totals.values())
    return totals


def _iter_collective_rows(hlo_text: str):
    """Yield ``(opcode_prefix, dtype, bytes, stripped_scope)`` per
    collective result buffer of an optimized module. Async ``-start``
    halves are skipped (counted at the matching ``-done``) — the one
    scan behind both the per-dtype and the per-hop views."""
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _hlo._INSTR_RE.match(line)
        if not m:
            continue
        op = m.group("op")
        for prefix in COLLECTIVE_OPCODES:
            if op.startswith(prefix):
                if op.endswith("-start"):
                    break  # counted at the matching -done
                sm = _xplane.HLO_TEXT_SCOPE_RE.search(line)
                scope = _xplane.strip_scope(sm.group(1)) if sm else ""
                for dt, dims in _hlo._SHAPE_RE.findall(m.group("shape")):
                    if dt not in _hlo._DTYPE_BYTES:
                        continue
                    elems = 1
                    for d in dims.split(","):
                        if d:
                            elems *= int(d)
                    yield (prefix, dt,
                           elems * _hlo._DTYPE_BYTES[dt], scope)
                break


#: hop classification of a collective's stripped scope: the
#: hierarchical sync nests each hop under a ``bucketNN/ici`` or
#: ``bucketNN/dcn`` sub-span (apex_tpu.parallel.hierarchy), so the
#: link class each byte rides is readable from the compiled program.
#: Everything else — the flat sync's whole traffic included — lands in
#: ``"unattributed"``.
_HOP_RES = (("dcn", re.compile(r"(^|/)dcn(/|$)")),
            ("ici", re.compile(r"(^|/)ici(/|$)")))


def scope_hop(scope: str) -> str:
    """Link-hop class of a stripped collective scope — the ONE
    classifier for the ``bucketNN/ici|dcn`` sub-span convention
    (``pod_comm_budget``'s hierarchical structure audit keys off the
    same function, so the audit and ``by_hop`` cannot drift apart)."""
    for hop, rx in _HOP_RES:
        if rx.search(scope):
            return hop
    return "unattributed"


def scope_axis_row(scope: str) -> str:
    """Mesh-axis attribution row of a stripped collective scope: the
    :func:`apex_tpu.parallel.registry.scope_axis` answer, or the
    explicit ``"unknown"`` row for a scope the registry doesn't know.
    This is the ONE scope→axis join every per-axis consumer shares
    (``wire_report``'s ``by_axis``, the goodput ledger's
    ``comm_axes_ms`` split, ``mesh_explain``'s wire pricing) — the
    registry stays the single source (APX102's allowlist), and
    unattributable traffic lands in a visible row, never silently
    dropped. tests/test_goodput.py pins that no second private copy of
    the table exists."""
    from apex_tpu.parallel import registry
    axis = registry.scope_axis(scope)
    return axis if axis else "unknown"


def collective_bytes_by_dtype(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Collective result bytes per opcode, split per wire dtype:
    ``{opcode: {dtype: bytes}}``. The breakdown is what makes compressed
    collectives auditable — a ``compress="bf16"`` DDP step shows its
    grad traffic under ``{"all-reduce": {"bf16": ...}}`` while the
    logical gradient is fp32. Async ``-start`` halves are skipped
    (counted at the matching ``-done``)."""
    out: Dict[str, Dict[str, int]] = {}
    for prefix, dt, nbytes, _scope in _iter_collective_rows(hlo_text):
        slot = out.setdefault(prefix, {})
        slot[dt] = slot.get(dt, 0) + nbytes
    return out


def collective_bytes_by_hop(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Collective result bytes per **hop**, split per wire dtype:
    ``{"ici" | "dcn" | "unattributed": {dtype: bytes}}``.

    The hop comes from the collective's named scope (the hierarchical
    sync's ``bucketNN/ici`` / ``bucketNN/dcn`` sub-spans survive into
    the compiled program), so a hierarchical step shows its per-hop
    dtype split — int8 inside the slice, bf16-or-int8 across — while a
    flat sync reports everything ``unattributed``. This is the static
    complement of the goodput ledger's exposed-collective buckets
    (``comm_skew`` + ``comm_wire``): the ledger measures how much
    collective time a step exposed, this says which link class and
    wire dtype the bytes behind it rode."""
    out: Dict[str, Dict[str, int]] = {}
    for _prefix, dt, nbytes, scope in _iter_collective_rows(hlo_text):
        slot = out.setdefault(scope_hop(scope), {})
        slot[dt] = slot.get(dt, 0) + nbytes
    return out


def collective_bytes_by_axis(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Collective result bytes per **mesh axis**, split per wire dtype:
    ``{axis | "unknown": {dtype: bytes}}``.

    The axis comes from joining the collective's stripped scope through
    the ONE planned-collective registry
    (:func:`apex_tpu.parallel.registry.scope_axis` via
    :func:`scope_axis_row`), so a hierarchical DDP + ZeRO step splits
    its traffic into ``data_intra`` / ``data_inter`` / ``data`` rows
    while anything outside the registry — the same population APX102
    flags — lands in an explicit ``"unknown"`` row. The static
    complement of the goodput ledger's per-axis ``comm_axes_ms``
    split: the ledger says which axis's exposed time, this says which
    axis's bytes."""
    out: Dict[str, Dict[str, int]] = {}
    for _prefix, dt, nbytes, scope in _iter_collective_rows(hlo_text):
        slot = out.setdefault(scope_axis_row(scope), {})
        slot[dt] = slot.get(dt, 0) + nbytes
    return out


def wire_report(fn=None, *args, hlo_text: Optional[str] = None,
                logical_bytes: Optional[int] = None, **kwargs) -> Dict:
    """Logical-vs-wire collective accounting for one compiled step.

    ``logical_bytes`` is the uncompressed payload the step *semantically*
    moves (e.g. ``4 * n_params`` for an fp32 grad sync); the wire bytes
    come from the optimized HLO's collective result shapes. Returns::

        {"wire_bytes": int, "by_opcode": {op: {dtype: bytes}},
         "by_hop": {hop: {dtype: bytes}},
         "by_axis": {axis: {dtype: bytes}},
         "logical_bytes": int | None, "wire_to_logical": float | None}

    A bucketed+``compress="bf16"`` DDP step reports
    ``wire_to_logical ≈ 0.5`` — the number the acceptance audit pins
    (tests/test_pod_hlo.py) and the uncompressed baseline DynamiQ-style
    collectives are judged against. ``by_hop`` is the per-hop per-dtype
    split of the hierarchical schedule (``"ici"``/``"dcn"`` from the
    hop sub-span scopes; flat traffic is ``"unattributed"``) — see
    :func:`collective_bytes_by_hop`. ``by_axis`` joins each scope
    through the planned-collective registry
    (:func:`collective_bytes_by_axis`; unregistered scopes land in the
    explicit ``"unknown"`` row).
    """
    if hlo_text is None:
        if fn is None:
            raise ValueError("pass a step function or hlo_text=")
        hlo_text = _hlo.compiled_hlo(fn, *args, **kwargs)
    by_op: Dict[str, Dict[str, int]] = {}
    by_hop: Dict[str, Dict[str, int]] = {}
    by_axis: Dict[str, Dict[str, int]] = {}
    for prefix, dt, nbytes, scope in _iter_collective_rows(hlo_text):
        slot = by_op.setdefault(prefix, {})
        slot[dt] = slot.get(dt, 0) + nbytes
        slot = by_hop.setdefault(scope_hop(scope), {})
        slot[dt] = slot.get(dt, 0) + nbytes
        slot = by_axis.setdefault(scope_axis_row(scope), {})
        slot[dt] = slot.get(dt, 0) + nbytes
    wire = sum(b for per in by_op.values() for b in per.values())
    ratio = (wire / logical_bytes) if logical_bytes else None
    return {"wire_bytes": wire, "by_opcode": by_op, "by_hop": by_hop,
            "by_axis": by_axis,
            "logical_bytes": logical_bytes, "wire_to_logical": ratio}


def collective_bytes(fn=None, *args, hlo_text: Optional[str] = None,
                     **kwargs) -> Dict[str, int]:
    """Per-step collective bytes of a jittable step function.

    Either pass the step function + example args (compiled here via
    :func:`apex_tpu.prof.hlo.compiled_hlo`) or a pre-dumped optimized-HLO
    text via ``hlo_text=``.
    """
    if hlo_text is None:
        if fn is None:
            raise ValueError("pass a step function or hlo_text=")
        hlo_text = _hlo.compiled_hlo(fn, *args, **kwargs)
    return collective_bytes_from_text(hlo_text)
