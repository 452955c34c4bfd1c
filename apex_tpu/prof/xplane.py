"""XSpace (xplane.pb) trace parser — the pyprof.parse equivalent.

The reference parses nvprof's SQLite database and correlates kernels with
NVTX ranges (`apex/pyprof/parse/parse.py`, `db.py`, `kernel.py`). The TPU
analogue: ``jax.profiler.trace`` writes an XSpace protobuf per host
(``*.xplane.pb``) containing one plane per device with an "XLA Ops" line —
one timed event per executed HLO instruction, whose metadata carries the
full HLO text (op name, shapes, fusion kind). This module decodes that
file into per-op records and aggregates them.

Decoding prefers the xplane proto bundled with tensorflow
(``tensorflow.tsl.profiler.protobuf.xplane_pb2``) — imported lazily so
apex_tpu itself never depends on tensorflow — and falls back to a
**minimal pure-python wire-format decoder** (:func:`decode_xspace`)
covering exactly the fields this parser reads (plane/line/event
hierarchy + event metadata), so CI parses committed ``*.xplane.pb``
fixtures without tensorflow (``tests/fixtures/``; set
``APEX_TPU_XPLANE_PURE=1`` to force the fallback).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional

__all__ = ["OpRecord", "TraceProfile", "parse_trace", "latest_xplane",
           "COLLECTIVE_PREFIXES", "decode_xspace"]

# HLO instruction text → opcode: "%fusion.3 = f32[8]{0} fusion(...)" → the
# word after the result shape. Shapes may be tuples "(f32[...], u32[])"
# whose layout annotations themselves contain parens ("T(8,128)S(1)"), so
# the tuple alternative must match balanced parens one level deep.
_OPCODE_RE = re.compile(
    r"^%?(?P<name>[^ ]+) = (?:\((?:[^()]|\([^()]*\))*\)|[^ ]+) "
    r"(?P<opcode>[\w-]+)\(")

# named-scope path in HLO op metadata: metadata={op_name="jit(f)/amp/fwd/..."}
_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')

# transform-wrapper path components jax interleaves with user scopes —
# dropped by by_scope() so "jit(step)/transpose(jvp(amp))/fwd" and
# "jit(step)/amp/fwd" aggregate under the same user-named key
_TRANSFORM_WRAPPERS = ("jit(", "transpose(", "jvp(", "vmap(", "pmap(",
                      "shard_map(", "scan(", "while(", "remat(")


def strip_scope(op_name: str) -> str:
    """User-named components of a metadata scope path:
    ``jit(step)/transpose(jvp(amp/fwd))/tanh`` → ``amp/fwd/tanh``.

    A user scope containing ``/`` splits the wrapper parens across path
    components, so besides dropping self-contained wrapper components
    each kept fragment is scrubbed of wrapper prefixes and dangling
    parens. Shared by :meth:`TraceProfile.by_scope` and the
    buffer-attribution in :mod:`apex_tpu.prof.memory`."""
    parts = []
    for p in op_name.split("/"):
        if (p.startswith(_TRANSFORM_WRAPPERS)
                and p.count("(") == p.count(")")):
            continue          # self-contained wrapper, e.g. "jit(step)"
        while p.startswith(_TRANSFORM_WRAPPERS):
            p = p.split("(", 1)[1]      # fragment: keep the user content
        p = p.strip(")")
        if p:
            parts.append(p)
    return "/".join(parts)

# The one canonical list of collective opcode prefixes — longest-prefix
# entries first so e.g. ragged-all-to-all is not folded into all-to-all.
# apex_tpu.monitor.collectives buckets traffic by the same tuple; keep
# trace categorization and live accounting in lockstep here.
COLLECTIVE_PREFIXES = (
    "ragged-all-to-all",
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)

_CATEGORIES = (
    ("convolution", "conv"),
    ("dot", "gemm"),
) + tuple((p, "collective") for p in COLLECTIVE_PREFIXES) + (
    ("copy", "copy"),
    ("fusion", "fusion"),
    ("custom-call", "custom-call"),
    ("scatter", "scatter"),
    ("reduce", "reduction"),
    ("sort", "sort"),
    ("dynamic-update-slice", "slice"),     # before dynamic-slice would
    ("dynamic-slice", "slice"),            # NOT prefix-match it, but keep
    ("while", "control-flow"),             # the specific one first anyway
)


def _categorize(opcode: str, hlo_text: str) -> str:
    for prefix, cat in _CATEGORIES:
        if opcode.startswith(prefix):
            if cat == "fusion":
                m = re.search(r"kind=(\w+)", hlo_text)
                return f"fusion.{m.group(1)[1:].lower()}" if m else "fusion"
            return cat
    return "other"


@dataclasses.dataclass
class OpRecord:
    """Aggregated timing for one HLO instruction across a trace."""

    name: str           # instruction name, e.g. "fusion.31"
    opcode: str         # HLO opcode, e.g. "fusion", "convolution"
    category: str       # coarse category (gemm/conv/fusion.*/collective/...)
    occurrences: int
    total_us: float
    hlo: str            # full HLO instruction text

    @property
    def avg_us(self) -> float:
        return self.total_us / max(self.occurrences, 1)


@dataclasses.dataclass
class TraceProfile:
    """Parsed device activity of one xplane.pb."""

    path: str
    device: str                       # plane name, e.g. "/device:TPU:0"
    ops: List[OpRecord]               # sorted by total_us desc
    module_runs: int                  # XLA Modules line event count
    module_total_us: float            # wall device time inside XLA modules

    def module_us_per_run(self) -> float:
        """Device µs per XLA module run. A trace with no module runs is
        a broken (or CPU) trace, not a device time: it raises rather than
        let a caller substitute host wall."""
        if not self.module_runs:
            raise ValueError(
                f"the trace at {self.path} holds no device module runs "
                f"(device plane {self.device or 'absent'!r})")
        return self.module_total_us / self.module_runs

    def by_category(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for r in self.ops:
            out[r.category] = out.get(r.category, 0.0) + r.total_us
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def by_scope(self, depth: int = 2) -> Dict[str, float]:
        """Device time per named-scope prefix (``trace.span`` names).

        HLO op metadata carries the full scope path each op was traced
        under (``op_name="jit(step)/amp/fwd/conv"``); this aggregates
        ``total_us`` by the first ``depth`` path components after the
        ``jit(...)`` / transform wrappers — so ``trace.span("amp/fwd")``
        spans show up here with their *measured device* time, the
        counterpart of the tracer's host wall-clock timeline. Ops with
        no scope metadata land under ``"(unscoped)"``.
        """
        out: Dict[str, float] = {}
        for r in self.ops:
            m = _OP_NAME_RE.search(r.hlo)
            parts = strip_scope(m.group(1)).split("/") if m else []
            parts = [p for p in parts if p]
            key = "/".join(parts[:depth]) if parts else "(unscoped)"
            out[key] = out.get(key, 0.0) + r.total_us
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def table(self, top: int = 20) -> str:
        total = sum(r.total_us for r in self.ops) or 1.0
        lines = [f"{'op':<40} {'category':<16} {'count':>6} "
                 f"{'total_us':>12} {'avg_us':>10} {'%':>6}"]
        for r in self.ops[:top]:
            lines.append(
                f"{r.name[:40]:<40} {r.category:<16} {r.occurrences:>6} "
                f"{r.total_us:>12.1f} {r.avg_us:>10.2f} "
                f"{100 * r.total_us / total:>5.1f}%")
        return "\n".join(lines)


def latest_xplane(logdir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under a profiler logdir, or None."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


# --- minimal pure-python XSpace decoder --------------------------------------
#
# Protobuf wire format is stable and tiny to read: every field is a
# (tag = field_no << 3 | wire_type, payload) pair; messages are
# length-delimited. This decoder covers exactly the XSpace subset
# parse_trace consumes (field numbers pinned against the tsl proto:
# XSpace.planes=1; XPlane.name=2/lines=3/event_metadata=4 with map
# entries key=1/value=2; XLine.name=2/events=4; XEvent.metadata_id=1/
# duration_ps=3; XEventMetadata.id=1/name=2/display_name=4), so a
# committed fixture parses in CI without tensorflow.

class _Msg:
    """Attribute bag for decoded messages."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _uvarint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) — value is an int for
    varints/fixed, bytes for length-delimited."""
    i = 0
    while i < len(buf):
        tag, i = _uvarint(buf, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _uvarint(buf, i)
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 2:
            n, i = _uvarint(buf, i)
            if i + n > len(buf):      # slicing would silently truncate
                raise ValueError(f"truncated field {fno} "
                                 f"({n} bytes past end)")
            v = buf[i:i + n]
            i += n
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, v


def _decode_event(buf: bytes) -> _Msg:
    ev = _Msg(metadata_id=0, duration_ps=0)
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            ev.metadata_id = v
        elif fno == 3:
            ev.duration_ps = v
    return ev


def _decode_line(buf: bytes) -> _Msg:
    line = _Msg(name="", events=[])
    for fno, _wt, v in _fields(buf):
        if fno == 2:
            line.name = v.decode("utf-8", "replace")
        elif fno == 4:
            line.events.append(_decode_event(v))
    return line


def _decode_event_metadata(buf: bytes) -> _Msg:
    md = _Msg(id=0, name="", display_name="")
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            md.id = v
        elif fno == 2:
            md.name = v.decode("utf-8", "replace")
        elif fno == 4:
            md.display_name = v.decode("utf-8", "replace")
    return md


def _decode_plane(buf: bytes) -> _Msg:
    plane = _Msg(name="", lines=[], event_metadata={})
    for fno, _wt, v in _fields(buf):
        if fno == 2:
            plane.name = v.decode("utf-8", "replace")
        elif fno == 3:
            plane.lines.append(_decode_line(v))
        elif fno == 4:
            key, md = 0, None
            for efno, _ewt, ev in _fields(v):     # map entry
                if efno == 1:
                    key = ev
                elif efno == 2:
                    md = _decode_event_metadata(ev)
            if md is not None:
                plane.event_metadata[key or md.id] = md
    return plane


def decode_xspace(data: bytes) -> _Msg:
    """Decode a serialized XSpace with the pure-python reader — the
    tensorflow-free fallback behind :func:`parse_trace`."""
    xs = _Msg(planes=[])
    for fno, _wt, v in _fields(data):
        if fno == 1:
            xs.planes.append(_decode_plane(v))
    return xs


def _load_xspace(path: str):
    if os.environ.get("APEX_TPU_XPLANE_PURE") != "1":
        try:
            from tensorflow.tsl.profiler.protobuf import xplane_pb2
            xs = xplane_pb2.XSpace()
            with open(path, "rb") as f:
                xs.ParseFromString(f.read())
            return xs
        except OSError:
            raise                     # file problems are not decode paths
        except Exception:
            # no/broken tensorflow (a partial install can raise far
            # more than ImportError): the minimal decoder below
            pass
    try:
        with open(path, "rb") as f:
            return decode_xspace(f.read())
    except (ValueError, IndexError) as e:
        raise ValueError(
            f"could not decode {path!r} as an XSpace proto (pure-python "
            f"fallback): {e!r}. If tensorflow is available its bundled "
            "proto (tensorflow.tsl.profiler.protobuf.xplane_pb2) handles "
            "schema extensions; without trace files at all, use the "
            "XLA-cost-analysis path instead — apex_tpu.prof.hlo."
            "op_estimates / cost_analysis on the jitted step (the "
            "reference degrades its scaler the same way, "
            "apex/amp/scaler.py:39-52)") from e


def parse_trace(logdir_or_file: str, device_index: int = 0) -> TraceProfile:
    """Parse a profiler logdir (or a specific xplane.pb) into per-op records.

    Aggregates every "XLA Ops" event on the selected device plane by HLO
    instruction. On non-TPU backends the device plane may be absent; the
    result then has empty ``ops`` (and ``module_runs == 0``) rather than
    raising, so callers can degrade gracefully.
    """
    path = logdir_or_file
    if os.path.isdir(path):
        found = latest_xplane(path)
        if found is None:
            raise FileNotFoundError(
                f"no *.xplane.pb under {logdir_or_file!r}; did the "
                "jax.profiler trace finish?")
        path = found
    xs = _load_xspace(path)

    device_planes = [p for p in xs.planes if "/device:" in p.name
                     and "CUSTOM" not in p.name and p.lines]
    if not device_planes:
        return TraceProfile(path=path, device="", ops=[], module_runs=0,
                            module_total_us=0.0)
    plane = device_planes[min(device_index, len(device_planes) - 1)]

    agg: Dict[int, OpRecord] = {}
    module_runs, module_total_ps = 0, 0
    for line in plane.lines:
        if line.name == "XLA Modules":
            module_runs = len(line.events)
            module_total_ps = sum(e.duration_ps for e in line.events)
            continue
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            rec = agg.get(ev.metadata_id)
            if rec is None:
                md = plane.event_metadata[ev.metadata_id]
                text = md.name or md.display_name
                m = _OPCODE_RE.match(text)
                name = m.group("name") if m else text[:40]
                opcode = m.group("opcode") if m else "unknown"
                rec = agg[ev.metadata_id] = OpRecord(
                    name=name, opcode=opcode,
                    category=_categorize(opcode, text),
                    occurrences=0, total_us=0.0, hlo=text)
            rec.occurrences += 1
            rec.total_us += ev.duration_ps / 1e6
    ops = sorted(agg.values(), key=lambda r: -r.total_us)
    return TraceProfile(path=path, device=plane.name, ops=ops,
                        module_runs=module_runs,
                        module_total_us=module_total_ps / 1e6)
