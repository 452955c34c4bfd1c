"""XSpace (xplane.pb) trace reader — the pyprof.parse equivalent.

The reference parses nvprof's SQLite database and correlates kernels with
NVTX ranges (`apex/pyprof/parse/parse.py`, `db.py`, `kernel.py`). The TPU
analogue: ``jax.profiler.trace`` writes an XSpace protobuf per host
(``*.xplane.pb``) with one plane per chip (``/device:TPU:<i>``). Its
``XLA Modules`` line has one event per run of a compiled program, its
``XLA Ops`` line one per executed HLO instruction. What an op *is* sits in
the event's metadata, and on a v5e (libtpu 0.0.34) that is:

- ``name``: the instruction's HLO text, with no ``metadata={...}`` in it;
- stats, named through the plane's ``stat_metadata``: ``tf_op`` — the
  ``jax.named_scope`` path the op was traced under, e.g.
  ``jit(step)/transpose(jvp(amp/fwd))/BertEncoder/.../dot_general:`` —
  ``hlo_category`` (``convolution fusion``, ``loop fusion``,
  ``custom-call``, ...), and the compiler's own ``flops``,
  ``bytes_accessed`` and ``memory_access_breakdown`` (bytes by read/write
  and memory space; space 1 is HBM, operands the layout marks ``S(1)``
  sit on the chip and count under space 3).

So the scopes the program opens (``trace.span``: ``amp/fwd``,
``amp/update``, ``ddp/sync_gradients``; ``optim/<name>/<phase>``; a
kernel's ``apex_<kernel>``) name device time on every step. A fusion
carries the scope of one of its ops, and ``tf_op`` is what the executable
was *compiled* with: a step loaded from a compile cache shows the scopes
of the tree that filled it.

This module decodes the file into per-op records and aggregates them, by
op, category and scope, over the whole trace or a window of it. The
decoder (:func:`decode_xspace`) is a minimal pure-python reader of the
protobuf wire format covering exactly the fields read here, so neither a
process that holds the chip nor CI imports tensorflow for it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import struct
from typing import Dict, List, Optional, Tuple

__all__ = ["OpRecord", "TraceProfile", "parse_trace", "latest_xplane",
           "COLLECTIVE_PREFIXES", "decode_xspace", "strip_scope",
           "own_scope", "HLO_TEXT_SCOPE_RE", "place"]

# HLO instruction text → opcode: "%fusion.3 = f32[8]{0} fusion(...)" → the
# first word that opens a paren after a space. The result shape before it
# may be a tuple of tuples with layouts ("((f32[8]{0:T(8,128)S(1)}), u32[])"
# on an async start), but holds no space before a word: its parens follow
# ":", ")" or a letter, its elements start "f32[" or "(".
_OPCODE_RE = re.compile(
    r"^%?(?P<name>[^ ]+) = .*? (?P<opcode>[a-z][\w-]*)\(")

# transform-wrapper path components jax interleaves with user scopes —
# dropped by strip_scope() so "jit(step)/transpose(jvp(amp))/fwd" and
# "jit(step)/amp/fwd" aggregate under the same user-named key
_TRANSFORM_WRAPPERS = ("jit(", "transpose(", "jvp(", "vmap(", "pmap(",
                      "shard_map(", "scan(", "while(", "remat(")

#: the named-scope path as XLA prints it into *compiled HLO text*
#: (``compiled.as_text()``: ``metadata={op_name="jit(f)/amp/fwd/..."}``),
#: for the readers of that text (prof.memory, prof.roofline, lint,
#: monitor.collectives). A device trace's instruction text carries no
#: metadata: there the path is the ``tf_op`` stat, ``OpRecord.scope``.
HLO_TEXT_SCOPE_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')

# scopes the program itself opens deep inside a user's module tree: a
# kernel's (``apex_<kernel>``, ops/_dispatch.py KERNEL_NAMES) and an
# optimizer phase's (``optim/<name>/<phase>``, optim/fused.py). A kernel
# called under a differentiation with no scope round it is all a wrapper
# holds (``jvp(apex_xentropy_fwd)``), which strip_scope would drop like a
# ``jit(step)``: so this reads the raw path, parens and all.
_OWN_SCOPE_RE = re.compile(
    r"(?:^|/|(?<!jit)\()(apex_\w+|optim/\w+/\w+)(?=[/)]|$)")

# the two heads of ``models.mlm_loss`` (models/transformer.py): the scope is
# round everything the branch runs, the xentropy kernels included, so the
# device time under each says which branch the traced steps took
_HEAD_SCOPE_RE = re.compile(r"(?:^|/|\()(mlm/head_\w+)(?=[/)]|$)")

#: the memory space of ``memory_access_breakdown`` that is HBM
HBM_MEMORY_SPACE = 1


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


def own_scope(scope: str) -> str:
    """The kernel name or optimizer phase in a scope path (wrappers and
    all), or ``""``: ``.../SelfMultiheadAttn_0/apex_attn_fwd/pallas_call``
    → ``apex_attn_fwd``; ``jit(f)/transpose(jvp(apex_attn_bwd))/pallas_call``
    → ``apex_attn_bwd``; ``amp/update/optim/lamb/norms/reduce_sum`` →
    ``optim/lamb/norms``."""
    m = _OWN_SCOPE_RE.search(scope)
    return m.group(1) if m else ""


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


def _categorize(opcode: str, hlo_text: str, hlo_category: str = "") -> str:
    """A collective by its opcode (or the runtime's word for it), then
    the runtime's own ``hlo_category`` where the trace carries one, then
    what the opcode and the fusion kind say."""
    if (opcode.startswith(COLLECTIVE_PREFIXES)
            or hlo_category.startswith(COLLECTIVE_PREFIXES)):
        return "collective"
    if hlo_category:
        return hlo_category
    for prefix, cat in _CATEGORIES:
        if opcode.startswith(prefix):
            if cat == "fusion":
                m = re.search(r"kind=(\w+)", hlo_text)
                return f"fusion.{m.group(1)[1:].lower()}" if m else "fusion"
            return cat
    return "other"


@dataclasses.dataclass
class OpRecord:
    """Aggregated timing for one HLO instruction across a trace (or the
    window of it that was asked for)."""

    name: str           # instruction name, e.g. "fusion.31"
    opcode: str         # HLO opcode, e.g. "fusion", "convolution"
    category: str       # hlo_category, else opcode-derived; "collective"
    occurrences: int
    total_us: float
    hlo: str            # full HLO instruction text
    #: ``tf_op`` without its trailing ":" — the named-scope path with the
    #: transform wrappers kept (``jvp(``/``transpose(`` tell forward from
    #: backward); :func:`strip_scope` gives the user-named part
    scope: str = ""
    hlo_category: str = ""      # the runtime's own, "" where absent
    #: the compiler's counts for ONE execution; None where it counted
    #: nothing (every Mosaic custom call: XLA cannot see into a kernel)
    flops: Optional[int] = None
    bytes_accessed: Optional[int] = None    # every memory space
    hbm_bytes: Optional[int] = None         # memory space 1 only

    @property
    def avg_us(self) -> float:
        return self.total_us / max(self.occurrences, 1)

    @property
    def phase(self) -> str:
        """``"bwd"`` under a ``transpose(``, ``"fwd"`` under a ``jvp(``
        alone, ``""`` for what was never differentiated (the update)."""
        if "transpose(" in self.scope:
            return "bwd"
        return "fwd" if "jvp(" in self.scope else ""


@dataclasses.dataclass
class TraceProfile:
    """Parsed device activity of one xplane.pb."""

    path: str
    device: str                       # plane name, e.g. "/device:TPU:0"
    ops: List[OpRecord]               # sorted by total_us desc
    module_runs: int                  # XLA Modules line event count
    module_total_us: float            # wall device time inside XLA modules
    #: ``(start_ns, end_ns)`` of every run of the module that took most
    #: device time (the training step), in order, whatever the window
    step_runs: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)
    #: the ``(lo_ns, hi_ns)`` this profile was cut to, None for all of it
    window_ns: Optional[Tuple[float, float]] = None
    _device: Optional["_DeviceEvents"] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def start_epoch_ns(self) -> Optional[int]:
        """The epoch nanosecond the file's clock counts from
        (``profile_start_time`` of its ``Task Environment`` plane), None
        where it states none."""
        return self._device and self._device.start_epoch_ns

    def spans_over_idle(self, timeline: Dict
                        ) -> List[Tuple[Dict, float, float, float]]:
        """``(span, start_ns, end_ns, idle_us)`` for each span of a
        ``prof.compile_watch.timeline()`` that lies, in part, inside
        this profile (its window, else first op to last): the part
        inside on the profile's clock, and how much of it no op ran on
        the device. A compile or a cache load found here is what the
        device waited for."""
        dev = self._device
        if (dev is None or not dev.ops or self.start_epoch_ns is None
                or not timeline.get("anchor")):
            return []
        lo, hi = self.window_ns or (min(a for _, a, _b in dev.ops) / 1e3,
                                    max(b for _, _a, b in dev.ops) / 1e3)
        found = []
        for span in timeline["spans"]:
            a, b = place(span, timeline["anchor"], self.start_epoch_ns)
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            busy, covered_to = 0.0, a * 1e3
            for op_a, op_b in sorted((oa, ob) for _, oa, ob in dev.ops
                                     if ob > a * 1e3 and oa < b * 1e3):
                op_a, op_b = max(op_a, covered_to), min(op_b, b * 1e3)
                if op_b > op_a:
                    busy += op_b - op_a
                    covered_to = op_b
            found.append((span, a, b, (b - a) / 1e3 - busy / 1e6))
        return found

    def window(self, lo_ns: float, hi_ns: float) -> "TraceProfile":
        """The same trace cut to ``[lo_ns, hi_ns]`` on the device's
        clock: an op counts with the part of it that lies inside. A
        caller that wants whole steps takes the bounds from
        :attr:`step_runs`."""
        if self._device is None:
            raise ValueError("only a profile made by parse_trace keeps "
                             "the events a window is cut from")
        return _aggregate(self.path, self._device, (lo_ns, hi_ns))

    @property
    def total_us(self) -> float:
        """Device time of all ops: the busy time, ops on the ``XLA Ops``
        line of one chip do not overlap."""
        return sum(r.total_us for r in self.ops)

    def module_us_per_run(self) -> float:
        """Device µs per XLA module run. A trace with no module runs is
        a broken (or CPU) trace, not a device time: it raises rather than
        let a caller substitute host wall."""
        if not self.module_runs:
            raise ValueError(
                f"the trace at {self.path} holds no device module runs "
                f"(device plane {self.device or 'absent'!r})")
        return self.module_total_us / self.module_runs

    def _sum_by(self, key) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for r in self.ops:
            k = key(r)
            if k is not None:
                out[k] = out.get(k, 0.0) + r.total_us
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def by_category(self) -> Dict[str, float]:
        return self._sum_by(lambda r: r.category)

    def by_scope(self, depth: int = 2, phases: bool = False
                 ) -> Dict[str, float]:
        """Device time per named-scope prefix (``trace.span`` names).

        Every op's ``scope`` is the path it was traced under
        (``jit(step)/jvp(amp/fwd)/BertEncoder/...``); this aggregates
        ``total_us`` by the first ``depth`` path components after the
        ``jit(...)`` / transform wrappers — so ``trace.span("amp/fwd")``
        spans show up here with their *measured device* time, the
        counterpart of the tracer's host wall-clock timeline. With
        ``phases`` a key says which side of the differentiation it is
        (``amp/fwd [fwd]``, ``amp/fwd [bwd]``). Ops with no scope
        (``copy-done``, ``slice-done``: the compiler's own) land under
        ``"(unscoped)"``.
        """
        def key(r):
            parts = strip_scope(r.scope).split("/") if r.scope else []
            parts = [p for p in parts if p]
            if not parts:
                return "(unscoped)"
            k = "/".join(parts[:depth])
            return f"{k} [{r.phase}]" if phases and r.phase else k
        return self._sum_by(key)

    def by_own_scope(self) -> Dict[str, float]:
        """Device time per kernel name and optimizer phase, wherever in
        a user's module tree it sits (:func:`own_scope`)."""
        return self._sum_by(lambda r: own_scope(r.scope) or None)

    def by_head(self) -> Dict[str, float]:
        """Device time under each head of ``models.mlm_loss``:
        ``mlm/head_gathered`` (the labelled rows, compacted) and
        ``mlm/head_full`` (every row). A head no step took is absent."""
        def key(r):
            m = _HEAD_SCOPE_RE.search(r.scope)
            return m.group(1) if m else None
        return self._sum_by(key)

    def table(self, top: int = 20) -> str:
        total = self.total_us or 1.0
        lines = [f"{'op':<40} {'category':<22} {'count':>6} "
                 f"{'total_us':>12} {'avg_us':>10} {'%':>6}"]
        for r in self.ops[:top]:
            lines.append(
                f"{r.name[:40]:<40} {r.category[:22]:<22} "
                f"{r.occurrences:>6} "
                f"{r.total_us:>12.1f} {r.avg_us:>10.2f} "
                f"{100 * r.total_us / total:>5.1f}%")
        return "\n".join(lines)


def place(span: Dict, anchor, start_epoch_ns: int = 0
          ) -> Tuple[float, float]:
    """``(start_ns, end_ns)`` of a ``prof.compile_watch`` span on a
    profile's clock. The span's times are ``time.perf_counter()``
    seconds; ``anchor`` is the timeline's ``(time.perf_counter_ns(),
    time.time_ns())`` pair and ``start_epoch_ns`` the epoch nanosecond
    the profile counts from (:attr:`TraceProfile.start_epoch_ns`)."""
    perf_ns, epoch_ns = anchor
    shift = epoch_ns - perf_ns - start_epoch_ns     # whole numbers: exact
    return span["start"] * 1e9 + shift, span["end"] * 1e9 + shift


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
# XSpace.planes=1; XPlane.name=2/lines=3/event_metadata=4/stat_metadata=5/
# stats=6,
# both maps with entries key=1/value=2; XLine.name=2/timestamp_ns=3/
# events=4; XEvent.metadata_id=1/offset_ps=2/duration_ps=3;
# XEventMetadata.id=1/name=2/display_name=4/stats=5; XStatMetadata.id=1/
# name=2; XStat.metadata_id=1 and one of double_value=2/uint64_value=3/
# int64_value=4/str_value=5/bytes_value=6/ref_value=7, the last the id of
# a stat_metadata entry whose name is the value). An event's own stats
# (XEvent.stats=4: device_offset_ps, device_duration_ps) repeat its
# offset and duration and are skipped.

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


def _text(v: bytes) -> str:
    return v.decode("utf-8", "replace")


def _decode_event(buf: bytes) -> _Msg:
    ev = _Msg(metadata_id=0, offset_ps=0, duration_ps=0)
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            ev.metadata_id = v
        elif fno == 2:
            ev.offset_ps = v
        elif fno == 3:
            ev.duration_ps = v
    return ev


def _decode_line(buf: bytes) -> _Msg:
    line = _Msg(name="", timestamp_ns=0, events=[])
    for fno, _wt, v in _fields(buf):
        if fno == 2:
            line.name = _text(v)
        elif fno == 3:
            line.timestamp_ns = v
        elif fno == 4:
            line.events.append(_decode_event(v))
    return line


def _decode_stat(buf: bytes):
    """``(stat_metadata id, value, is_ref)`` of one XStat."""
    sid, value, is_ref = 0, None, False
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            sid = v
        elif fno == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif fno == 3:
            value = v
        elif fno == 4:      # int64: two's complement in 64 bits
            value = v - (1 << 64) if v >> 63 else v
        elif fno == 5:
            value = _text(v)
        elif fno == 6:
            value = v
        elif fno == 7:
            value, is_ref = v, True
    return sid, value, is_ref


def _decode_event_metadata(buf: bytes) -> _Msg:
    md = _Msg(id=0, name="", display_name="", raw_stats=[])
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            md.id = v
        elif fno == 2:
            md.name = _text(v)
        elif fno == 4:
            md.display_name = _text(v)
        elif fno == 5:
            md.raw_stats.append(_decode_stat(v))
    return md


def _map_entry(buf: bytes, decode_value):
    key, value = 0, None
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            key = v
        elif fno == 2:
            value = decode_value(v)
    return key, value


def _decode_stat_metadata(buf: bytes) -> _Msg:
    sm = _Msg(id=0, name="")
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            sm.id = v
        elif fno == 2:
            sm.name = _text(v)
    return sm


def _decode_plane(buf: bytes) -> _Msg:
    plane = _Msg(name="", lines=[], event_metadata={}, stat_metadata={})
    raw_stats = []
    for fno, _wt, v in _fields(buf):
        if fno == 6:
            raw_stats.append(_decode_stat(v))
        elif fno == 2:
            plane.name = _text(v)
        elif fno == 3:
            plane.lines.append(_decode_line(v))
        elif fno == 4:
            key, md = _map_entry(v, _decode_event_metadata)
            if md is not None:
                plane.event_metadata[key or md.id] = md
        elif fno == 5:
            key, sm = _map_entry(v, _decode_stat_metadata)
            if sm is not None:
                plane.stat_metadata[key or sm.id] = sm.name
    # the stats by name, a reference resolved to the name it points at
    names = plane.stat_metadata
    plane.stats = {names.get(sid, str(sid)): value
                   for sid, value, _is_ref in raw_stats}
    for md in plane.event_metadata.values():
        md.stats = {names.get(sid, str(sid)):
                    (names.get(value, "") if is_ref else value)
                    for sid, value, is_ref in md.raw_stats}
        del md.raw_stats
    return plane


def decode_xspace(data: bytes) -> _Msg:
    """Decode a serialized XSpace: ``planes`` with ``name``, ``lines``
    (``name``, ``timestamp_ns``, ``events`` of ``metadata_id`` /
    ``offset_ps`` / ``duration_ps``), ``event_metadata`` by id (``name``,
    ``display_name``, ``stats`` by stat name) and ``stat_metadata``."""
    xs = _Msg(planes=[])
    for fno, _wt, v in _fields(data):
        if fno == 1:
            xs.planes.append(_decode_plane(v))
    return xs


def _load_xspace(path: str) -> _Msg:
    try:
        with open(path, "rb") as f:
            return decode_xspace(f.read())
    except (ValueError, IndexError, struct.error) as e:
        raise ValueError(
            f"could not decode {path!r} as an XSpace proto: {e!r}. "
            "Without a readable trace, use the XLA-cost-analysis path "
            "instead — apex_tpu.prof.hlo.op_estimates / cost_analysis on "
            "the jitted step (the reference degrades its scaler the same "
            "way, apex/amp/scaler.py:39-52)") from e


def _hbm_bytes(breakdown) -> Optional[int]:
    """Bytes of one execution that move to or from HBM: the memory-space
    1 entries of a ``memory_access_breakdown`` stat (repeated field 1 of
    ``{1: read=1/write=2, 2: memory space, 3: bytes}``)."""
    if not breakdown:
        return None
    total = 0
    for fno, _wt, entry in _fields(breakdown):
        if fno != 1:
            continue
        access = {f: v for f, _w, v in _fields(entry)}
        if access.get(2, 0) == HBM_MEMORY_SPACE:
            total += access.get(3, 0)
    return total


@dataclasses.dataclass
class _DeviceEvents:
    """One chip's plane, flattened: what a window is cut from."""
    name: str
    metadata: Dict[int, _Msg]
    ops: List[Tuple[int, int, int]]       # (metadata id, start_ps, end_ps)
    modules: List[Tuple[int, int, int]]
    start_epoch_ns: Optional[int] = None


def _events(line: Optional[_Msg]) -> List[Tuple[int, int, int]]:
    if line is None:
        return []
    t0 = line.timestamp_ns * 1000
    return [(e.metadata_id, t0 + e.offset_ps,
             t0 + e.offset_ps + e.duration_ps) for e in line.events]


def _record(md: _Msg) -> OpRecord:
    text = md.name or md.display_name
    m = _OPCODE_RE.match(text)
    opcode = m.group("opcode") if m else "unknown"
    stats = md.stats
    hlo_category = stats.get("hlo_category") or ""
    return OpRecord(
        name=m.group("name") if m else text[:40], opcode=opcode,
        category=_categorize(opcode, text, hlo_category),
        occurrences=0, total_us=0.0, hlo=text,
        scope=(stats.get("tf_op") or "").rstrip(":"),
        hlo_category=hlo_category,
        flops=stats.get("flops") or None,
        bytes_accessed=stats.get("bytes_accessed") or None,
        hbm_bytes=_hbm_bytes(stats.get("memory_access_breakdown")))


def _own_time(events) -> List[Tuple[int, int]]:
    """``(metadata id, picoseconds)`` of each event, less what the events
    inside it take. A ``conditional`` (or a ``while``) has an event of its
    own on the ops line, round those of the branch it ran: counted whole,
    the branch's time would be there twice, once with no scope at all."""
    out, open_ = [], []                 # open_: [metadata id, end, own ps]
    for mid, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and open_[-1][1] <= a:
            done = open_.pop()
            out.append((done[0], done[2]))
        if open_:
            open_[-1][2] -= min(b, open_[-1][1]) - a
        open_.append([mid, b, b - a])
    out.extend((mid, ps) for mid, _end, ps in open_)
    return out


def _aggregate(path: str, dev: _DeviceEvents,
               window_ns: Optional[Tuple[float, float]]) -> TraceProfile:
    if window_ns is None:
        lo = hi = None
    else:
        lo, hi = window_ns[0] * 1000, window_ns[1] * 1000

    def inside(events):
        for mid, a, b in events:
            if lo is not None:
                a, b = max(a, lo), min(b, hi)
                if b <= a:
                    continue
            yield mid, a, b

    agg: Dict[int, OpRecord] = {}
    for mid, ps in _own_time(inside(dev.ops)):
        rec = agg.get(mid)
        if rec is None:
            rec = agg[mid] = _record(dev.metadata[mid])
        rec.occurrences += 1
        rec.total_us += ps / 1e6
    modules = list(inside(dev.modules))

    by_module: Dict[int, float] = {}
    for mid, a, b in dev.modules:
        by_module[mid] = by_module.get(mid, 0.0) + (b - a)
    step = max(by_module, key=by_module.get) if by_module else None
    return TraceProfile(
        path=path, device=dev.name,
        ops=sorted(agg.values(), key=lambda r: -r.total_us),
        module_runs=len(modules),
        module_total_us=sum(b - a for _, a, b in modules) / 1e6,
        step_runs=sorted((a / 1e3, b / 1e3) for mid, a, b in dev.modules
                         if mid == step),
        window_ns=window_ns, _device=dev)


def parse_trace(logdir_or_file: str, device_index: int = 0) -> TraceProfile:
    """Parse a profiler logdir (or a specific xplane.pb) into per-op records.

    Aggregates every "XLA Ops" event on the selected device plane by HLO
    instruction, over the whole trace (:meth:`TraceProfile.window` cuts it
    to a stretch of the device's clock without decoding again).
    On non-TPU backends the device plane may be absent; the result then
    has empty ``ops`` (and ``module_runs == 0``) rather than raising, so
    callers can degrade gracefully.
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
    lines = {line.name: line for line in plane.lines}
    dev = _DeviceEvents(
        name=plane.name, metadata=plane.event_metadata,
        ops=_events(lines.get("XLA Ops")),
        modules=_events(lines.get("XLA Modules")),
        start_epoch_ns=next(
            (p.stats["profile_start_time"] for p in xs.planes
             if "profile_start_time" in p.stats), None))
    return _aggregate(path, dev, None)
