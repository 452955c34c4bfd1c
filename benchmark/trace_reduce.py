"""From a profiler trace to the numbers the per-layer readers take.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with nothing but JAX. On the v5e a chip is the plane
``/device:TPU:<i>`` with the lines ``XLA Modules`` (one event for each run
of a compiled program) and ``XLA Ops`` (one for each HLO instruction, its
name the instruction's text). Host annotations
(``jax.profiler.TraceAnnotation``) are events on the lines of
``/host:CPU``, on the same clock. Named scopes do not reach the device
lines, so nothing here depends on one.

The traced window is cut on the device's own clock: from the start of the
second run of the step program to the end of the last but one. The first
and the last traced step may be clipped by the profiler's start and stop,
and the device waits while the host starts or stops the profiler; both
stay outside the window, so an idle gap inside it is the host loop's own.

Every function takes intervals as ``(start_ns, end_ns)`` pairs, so a test
plants what it likes.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: how a Pallas kernel compiled by Mosaic shows in an op's HLO text
MOSAIC_MARK = "tpu_custom_call"
#: host spans the runner writes round the three things its loop does
HOST_SPAN_PREFIX = "bench/"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """``devices[i][line]`` and ``host_spans``: lists of events by start."""
    devices: Dict[int, Dict[str, List[Event]]]
    host_spans: List[Event]


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(source) -> Trace:
    """``source``: the path of an ``.xplane.pb`` or its bytes."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(source)
            if isinstance(source, bytes) else ProfileData.from_file(source))
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        chip = DEVICE_PLANE.match(plane.name)
        if chip:
            lines = devices.setdefault(int(chip.group(1)), {})
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    lines[line.name] = sorted(
                        (Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events), key=lambda e: e.start_ns)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    host.sort(key=lambda e: e.start_ns)
    return Trace(devices, host)


# ---- interval arithmetic ---------------------------------------------------

def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """The union, as disjoint intervals in order."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """Where nothing of ``intervals`` runs inside ``[lo, hi]``."""
    out, at = [], lo
    for a, b in merge(clip(intervals, lo, hi)):
        if a > at:
            out.append((at, a))
        at = b
    if hi > at:
        out.append((at, hi))
    return out


# ---- the step program and the window ---------------------------------------

def step_runs(trace: Trace, chip: int = 0) -> List[Event]:
    """The runs of the program that took most device time: the step."""
    runs = trace.devices.get(chip, {}).get(MODULES_LINE, [])
    total: Dict[str, float] = {}
    for e in runs:
        total[e.name] = total.get(e.name, 0.0) + e.ns
    if not total:
        return []
    step = max(total, key=total.get)
    return [e for e in runs if e.name == step]


def window(trace: Trace, chip: int = 0) -> Optional[Tuple[float, float, int]]:
    """``(start_ns, end_ns, steps)`` of the traced window, or None where
    fewer than three runs of the step were traced."""
    runs = step_runs(trace, chip)
    if len(runs) < 3:
        return None
    inner = runs[1:-1]
    return inner[0].start_ns, inner[-1].end_ns, len(inner)


def ops_in_window(trace: Trace, chip: int = 0) -> List[Event]:
    w = window(trace, chip)
    if w is None:
        return []
    lo, hi, _ = w
    return [e for e in trace.devices[chip].get(OPS_LINE, [])
            if e.end_ns > lo and e.start_ns < hi]


def device_step_ms(trace: Trace, chip: int = 0) -> Optional[float]:
    """Median device time of one run of the step program."""
    w = window(trace, chip)
    if w is None:
        return None
    return statistics.median(e.ns for e in step_runs(trace, chip)[1:-1]) / 1e6


def busy_ns(trace: Trace, chip: int = 0) -> Optional[float]:
    """The union of the ops' intervals inside the window."""
    w = window(trace, chip)
    if w is None:
        return None
    return union_ns(clip([(e.start_ns, e.end_ns)
                          for e in ops_in_window(trace, chip)], w[0], w[1]))


def busy_and_window_s(trace: Trace) -> Optional[Tuple[float, float]]:
    """Busy seconds averaged over the traced chips, and chip 0's window."""
    chips = [c for c in sorted(trace.devices) if window(trace, c)]
    if not chips or chips[0] != 0:
        return None
    busy = statistics.fmean(busy_ns(trace, c) for c in chips)
    lo, hi, _ = window(trace, 0)
    return busy / 1e9, (hi - lo) / 1e9


def idle_gaps(trace: Trace, chip: int = 0) -> List[Tuple[str, float]]:
    """``(what the host was doing, seconds)`` for every idle gap of the
    window, longest first. A gap is named after the host span that covers
    most of it, and ``host/other`` where none does."""
    w = window(trace, chip)
    if w is None:
        return []
    ops = [(e.start_ns, e.end_ns) for e in ops_in_window(trace, chip)]
    out = []
    for a, b in gaps(ops, w[0], w[1]):
        best, cover = "host/other", 0.0
        for s in trace.host_spans:
            if s.start_ns >= b:
                break
            c = min(b, s.end_ns) - max(a, s.start_ns)
            if c > cover:
                best, cover = s.name, c
        out.append((best, (b - a) / 1e9))
    out.sort(key=lambda g: -g[1])
    return out


def mosaic_ms_per_step(trace: Trace, chip: int = 0) -> Optional[float]:
    """Device time of the Mosaic custom calls, for one step."""
    w = window(trace, chip)
    if w is None:
        return None
    ns = sum(e.ns for e in ops_in_window(trace, chip)
             if MOSAIC_MARK in e.name and e.start_ns >= w[0])
    return ns / w[2] / 1e6


_OP_TEXT = re.compile(r"^%?([^ ]+?)(?:\.\d+)? = \(?([a-z0-9]+\[[0-9,]*\])?")


def op_group(hlo_text: str) -> str:
    """``fusion bf16[16,512,4096]`` from ``%fusion.12 = bf16[16,512,4096]{..}
    fusion(...)``: the instruction's name without its number, and the
    shape of its (first) result — which is how the same op of 24 layers
    comes to one row. A Mosaic kernel is marked as one."""
    m = _OP_TEXT.match(hlo_text)
    group = hlo_text[:60] if m is None else " ".join(g for g in m.groups() if g)
    return group + " [mosaic]" if MOSAIC_MARK in hlo_text else group


def top_ops(trace: Trace, n: int = 10, chip: int = 0
            ) -> List[Tuple[str, float]]:
    """The ``n`` groups of ops with most device time in the window, as
    ``(group, seconds over the window)``."""
    total: Dict[str, float] = {}
    for e in ops_in_window(trace, chip):
        group = op_group(e.name)
        total[group] = total.get(group, 0.0) + e.ns
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in top]
