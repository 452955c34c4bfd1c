"""The HLO half of apexlint: rules over what XLA actually compiled.

Operates on optimized (scheduled) HLO text — from a compiled
executable's ``as_text()`` or a ``scripts/dump_hlo.py`` dump — reusing
:func:`apex_tpu.prof.memory.parse_entry` (the scheduled-HLO buffer
parser) and the collective opcode list shared with
:mod:`apex_tpu.monitor.collectives`:

- **donation-miss** (APX101): an entry argument whose path classifies
  as params/optimizer_state (carried training state) that is not in
  the module's ``input_output_alias`` map *and* has a matching
  un-aliased output to donate into — XLA double-allocates it every
  step; the wasted-bytes estimate is the buffer size.
- **implicit-resharding** (APX102): a compiled collective whose
  named-scope path matches none of the known collective scopes
  (``ddp/sync_gradients``, per-bucket spans, SyncBN, ZeRO
  scatter/gather, ...) — the reshard XLA inserted that nobody planned,
  with its wire-byte cost.
- **host-transfer** (APX103): infeed/outfeed/send/recv/python-callback
  custom calls in the steady-state step (same markers
  :mod:`apex_tpu.monitor.check` pins for the telemetry contract).
- **tile-padding** (APX104): ``dot`` operand/result dims off the TPU
  (sublane, 128) tile grid, with a padding-waste byte estimate
  (sublane 8 for 4-byte dtypes, 16 for 2-byte, 32 for 1-byte).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from apex_tpu.lint.findings import Finding
from apex_tpu.prof import hlo as _hlo
from apex_tpu.prof import memory as _mem
from apex_tpu.prof.xplane import (COLLECTIVE_PREFIXES, HLO_TEXT_SCOPE_RE,
                                  strip_scope)

__all__ = ["lint_hlo_text", "parse_input_output_alias",
           "parse_entry_output_shapes", "donation_findings",
           "resharding_findings", "host_transfer_findings",
           "tile_findings"]

#: carried-state classes the donation rule expects to be aliased
_CARRIED_CLASSES = ("params", "optimizer_state")

#: minimal fallback when apex_tpu.parallel cannot be imported — the ONE
#: canonical allowlist is the declarative per-axis registry
#: :mod:`apex_tpu.parallel.registry` (kept next to the code that emits
#: the collectives, so a new planned collective scope is registered in
#: exactly one place; the SPMD pass and the mesh model consume the
#: same rows)
_FALLBACK_KNOWN_SCOPES = (r"ddp/sync_gradients",)

_COMMENT_RE = re.compile(r"/\*.*?\*/")
_LAYOUT_RE = re.compile(r"\{[^{}]*\}")


def _known_scope_patterns(extra: Sequence[str] = ()) -> List[re.Pattern]:
    try:
        from apex_tpu.parallel.registry import known_patterns
        pats = list(known_patterns())
    except Exception:
        pats = list(_FALLBACK_KNOWN_SCOPES)
    pats += list(extra)
    return [re.compile(p) for p in dict.fromkeys(pats)]


def _normalize_shape(shape_text: str) -> str:
    """Layout/comment-free canonical shape for alias matching:
    ``f32[64,64]{1,0:T(8,128)}`` -> ``f32[64,64]``."""
    s = _COMMENT_RE.sub("", shape_text)
    while _LAYOUT_RE.search(s):
        s = _LAYOUT_RE.sub("", s)
    return s.replace(" ", "")


# -- module-header parsing ----------------------------------------------------

_ALIAS_BLOCK_RE = re.compile(
    r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_ENTRY_RE = re.compile(r"\{([\d, ]*)\}:\s*\((\d+)")


def parse_input_output_alias(hlo_text: str) -> Tuple[Set[int], Set[int]]:
    """(aliased parameter numbers, aliased output top-level indices)
    from the module header's ``input_output_alias`` map. Both empty
    when the module declares no aliasing (nothing donated)."""
    head = hlo_text[:hlo_text.find("ENTRY")] if "ENTRY" in hlo_text \
        else hlo_text
    m = _ALIAS_BLOCK_RE.search(head)
    if not m:
        return set(), set()
    params: Set[int] = set()
    outs: Set[int] = set()
    for out_idx, pnum in _ALIAS_ENTRY_RE.findall(m.group(1)):
        params.add(int(pnum))
        first = out_idx.replace(" ", "").split(",")[0]
        outs.add(int(first) if first else 0)
    return params, outs


def _split_top_level(s: str) -> List[str]:
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def parse_entry_output_shapes(hlo_text: str) -> List[str]:
    """Normalized result shapes of the entry computation, in output
    order, from ``entry_computation_layout={(...)->RESULT}``."""
    marker = "entry_computation_layout={"
    i = hlo_text.find(marker)
    if i < 0:
        return []
    j, depth = i + len(marker) - 1, 0
    for j in range(i + len(marker) - 1, len(hlo_text)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    layout = hlo_text[i + len(marker):j]
    if "->" not in layout:
        return []
    result = layout.split("->", 1)[1].strip()
    if result.startswith("("):
        # find the matching close paren of the result tuple
        depth = 0
        for k, ch in enumerate(result):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    result = result[1:k]
                    break
        return [_normalize_shape(p) for p in _split_top_level(result)]
    return [_normalize_shape(result)]


# -- rules --------------------------------------------------------------------

def donation_findings(hlo_text: str, *,
                      min_bytes: int = 4096) -> List[Finding]:
    """Carried-state inputs (params / optimizer-state argument paths)
    not aliased to any output, where an un-aliased output of the same
    shape exists (so donation WOULD have worked — inference-style
    programs whose params never come back out are not flagged)."""
    aliased_params, aliased_outs = parse_input_output_alias(hlo_text)
    out_shapes = parse_entry_output_shapes(hlo_text)
    avail: Dict[str, int] = {}
    for idx, s in enumerate(out_shapes):
        if idx not in aliased_outs:
            avail[s] = avail.get(s, 0) + 1
    args_meta, _instrs, _root = _mem.parse_entry(hlo_text)
    findings: List[Finding] = []
    for name, shape, path, pnum in args_meta:
        cls = _mem.classify_arg_path(path or name)
        if cls not in _CARRIED_CLASSES:
            continue
        nbytes = _mem.shape_bytes(shape)
        if nbytes < min_bytes or pnum in aliased_params:
            continue
        norm = _normalize_shape(shape)
        if avail.get(norm, 0) <= 0:
            continue          # no matching output — not carried state
        avail[norm] -= 1
        findings.append(Finding(
            rule="donation-miss",
            message=f"{cls} input #{pnum} ({norm}) is not donated — "
                    f"{nbytes} bytes double-allocated every step",
            op=name, scope=path or None, bytes=nbytes))
    return findings


def resharding_findings(hlo_text: str,
                        known_scopes: Sequence[str] = ()) -> List[Finding]:
    """Collectives whose named-scope path matches no known pattern."""
    pats = _known_scope_patterns(known_scopes)
    agg: Dict[Tuple[str, str], Tuple[int, int]] = {}
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _mem._INSTR_RE.match(line)
        if not m:
            continue
        op = m.group("op")
        for prefix in COLLECTIVE_PREFIXES:
            if not op.startswith(prefix):
                continue
            if op.endswith("-start"):
                break          # counted at the matching -done
            sm = HLO_TEXT_SCOPE_RE.search(line)
            scope = strip_scope(sm.group(1)) if sm else ""
            if any(p.search(scope) for p in pats):
                break
            nbytes = _mem.shape_bytes(m.group("shape"))
            n, b = agg.get((prefix, scope), (0, 0))
            agg[(prefix, scope)] = (n + 1, b + nbytes)
            break
    return [Finding(
        rule="implicit-resharding",
        message=f"{n} {prefix} op(s) outside any known collective "
                f"scope ({b} wire bytes/step)",
        op=prefix, scope=scope or "<unscoped>", bytes=b, count=n)
        for (prefix, scope), (n, b) in sorted(agg.items())]


def host_transfer_findings(hlo_text: str) -> List[Finding]:
    """Device↔host traffic compiled into the step (the same markers the
    monitor/trace zero-dispatch compile checks pin)."""
    from apex_tpu.monitor.check import HOST_TRAFFIC_MARKERS
    agg: Dict[str, int] = {}
    for raw in hlo_text.splitlines():
        for marker in HOST_TRAFFIC_MARKERS:
            if marker in raw:
                key = marker.strip().rstrip("(")
                agg[key] = agg.get(key, 0) + 1
                break
    return [Finding(
        rule="host-transfer",
        message=f"{n} {kind} instruction(s) in the compiled step",
        op=kind, count=n) for kind, n in sorted(agg.items())]


def _sublane(itemsize: int) -> int:
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def _pad_waste(shape_text: str) -> Tuple[int, int]:
    """(logical_bytes, padded_bytes) of one typed shape under the TPU
    (sublane, 128) tile grid."""
    logical = padded = 0
    for dt, dims_s in _mem._SHAPE_RE.findall(shape_text):
        if dt not in _hlo._DTYPE_BYTES:
            continue
        isize = _hlo._DTYPE_BYTES[dt]
        dims = [int(d) for d in dims_s.split(",") if d]
        if not dims:
            continue
        elems = 1
        for d in dims:
            elems *= d
        pdims = list(dims)
        pdims[-1] = -(-pdims[-1] // 128) * 128
        if len(pdims) >= 2:
            sl = _sublane(isize)
            pdims[-2] = -(-pdims[-2] // sl) * sl
        pelems = 1
        for d in pdims:
            pelems *= d
        logical += elems * isize
        padded += pelems * isize
    return logical, padded


def tile_findings(hlo_text: str, *, min_waste_frac: float = 0.01,
                  min_waste_bytes: int = 1 << 16,
                  tuned_shapes: Sequence[str] = ()) -> List[Finding]:
    """``dot`` instructions whose operand/result dims are off the
    (sublane, 128) tile grid, with the padding-waste estimate. Sub-1%
    AND sub-64KiB waste is rounding residue, not a finding — the floor
    keeps ``bench.py``'s ``lint_findings`` count meaningful.

    ``tuned_shapes``: normalized shape signatures a committed tuning-DB
    entry covers (``apex_tpu.ops.autotune.tuned_lint_shapes()``). A
    matching signature stays at info severity with the fix-it naming
    the DB entry — the shape is model-fixed and the kernel block was
    tuned around it, so escalation would only nag."""
    shapes: Dict[str, str] = {}
    dots: List[Tuple[str, str, List[str]]] = []
    for name, shape, op, operands, _line in _hlo.iter_instructions(
            hlo_text):
        shapes[name] = shape
        if op == "dot":
            dots.append((name, shape, operands))
    agg: Dict[str, Tuple[int, int, int]] = {}
    for name, out_shape, operands in dots:
        sig_parts, logical, padded = [], 0, 0
        for s in [shapes.get(o, "") for o in operands[:2]] + [out_shape]:
            lg, pd = _pad_waste(s)
            logical += lg
            padded += pd
            sig_parts.append(_normalize_shape(s))
        waste = padded - logical
        if logical == 0 or waste <= 0:
            continue
        if waste / logical < min_waste_frac and waste < min_waste_bytes:
            continue
        sig = " x ".join(p for p in sig_parts if p)
        n, w, lg = agg.get(sig, (0, 0, 0))
        agg[sig] = (n + 1, w + waste, lg + logical)
    findings = []
    tuned = set(tuned_shapes)
    for sig, (n, waste, logical) in sorted(agg.items()):
        frac = waste / max(logical, 1)
        db_satisfied = sig in tuned
        severity = ("warning" if (frac >= 0.25 and waste >= 1 << 20
                                  and not db_satisfied) else "info")
        msg = (f"{n} dot(s) {sig} pad {frac:.1%} off the "
               f"(sublane,128) grid")
        if db_satisfied:
            msg += (" [covered by a scripts/kernel_tuning_db.json "
                    "entry — block shapes tuned around this padding]")
        findings.append(Finding(
            rule="tile-padding", severity=severity, message=msg,
            op="dot", scope=sig, bytes=waste, count=n))
    return findings


# -- entry point --------------------------------------------------------------

def lint_hlo_text(hlo_text: str, *, known_scopes: Sequence[str] = (),
                  min_donation_bytes: int = 4096,
                  rules: Optional[Sequence[str]] = None,
                  tuned_shapes: Sequence[str] = ()) -> List[Finding]:
    """Run the HLO rules over optimized-HLO text. ``rules`` restricts
    to a subset of slugs (default: all four); ``tuned_shapes`` feeds
    the APX104 tuning-DB exemption (see :func:`tile_findings`)."""
    run = set(rules) if rules is not None else None

    def on(slug: str) -> bool:
        return run is None or slug in run

    out: List[Finding] = []
    if on("donation-miss"):
        out += donation_findings(hlo_text, min_bytes=min_donation_bytes)
    if on("implicit-resharding"):
        out += resharding_findings(hlo_text, known_scopes)
    if on("host-transfer"):
        out += host_transfer_findings(hlo_text)
    if on("tile-padding"):
        out += tile_findings(hlo_text, tuned_shapes=tuned_shapes)
    return out
