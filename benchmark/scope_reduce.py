"""From the device trace's own stats to the per-layer numbers that read the
program's scopes.

``trace_reduce`` times the step from outside, by the text of each HLO
instruction. The v5e trace also says, for every op, where the program
traced it and what the compiler counted for it: the event metadata's
``tf_op`` (the ``jax.named_scope`` path), ``hlo_category``, ``flops`` and
``memory_access_breakdown`` stats, which ``jax.profiler.ProfileData`` does
not expose. The program's own reader, ``apex_tpu.prof.xplane``, decodes
them; this file cuts its profile to the window ``trace_reduce.window``
cuts (chip 0, from the start of the second run of the step program to the
end of the last but one) and divides by the same number of steps. A reader
under ``layer_metrics/`` is then one predicate over the op records.

The runner hands a reader its ``ProfileData`` reduction, not the file, so
the file is found here: ``Tracer`` empties the cell's directory before a
traced run, and the newest ``*.xplane.pb`` under ``.out/`` is this run's.
It is parsed once for a process (``Window.parse_s`` says what that cost).
Where there is no such file, no TPU plane in it (a rehearsal), fewer than
three runs of the step, or a program whose reader has no stats yet (the
commit before PR 25), every reader returns None and the line leaves the
metric out. In a traced window, a metric with nothing to sum reads 0.0:
ResNet-50 has no attention kernel.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, NamedTuple, Optional

from apex_tpu.prof import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, ".out")


class Window(NamedTuple):
    profile: object         # apex_tpu.prof.TraceProfile cut to the window
    steps: int              # whole steps in it
    seconds: float          # its length on the device's clock
    parse_s: float          # what decoding and cutting cost this process


# the runner loads each reader as a module of its own and hands none of
# them the file: what they share is here. Path of the xplane -> its Window
_parsed: dict = {}


def _cut(path: str) -> Optional[Window]:
    if not hasattr(xplane, "own_scope"):
        return None         # the program's reader before PR 25: no stats
    t0 = time.perf_counter()
    whole = xplane.parse_trace(path)
    runs = whole.step_runs
    if "/device:TPU:" not in whole.device or len(runs) < 3:
        return None
    lo, hi = runs[1][0], runs[-2][1]
    cut = whole.window(lo, hi)
    return Window(cut, len(runs) - 2, (hi - lo) / 1e9,
                  time.perf_counter() - t0)


def windowed(trace) -> Optional[Window]:
    """The traced window of this run, or None. ``trace`` is what the runner
    hands a reader: None where this run wrote no trace, and then the newest
    file is not this run's."""
    path = None if trace is None else xplane.latest_xplane(OUT)
    if path is None:
        return None
    if path not in _parsed:
        _parsed[path] = _cut(path)
    return _parsed[path]


def user_scope(record) -> str:
    """The op's scope path without ``jit(..)``/``jvp(..)``/``transpose(..)``:
    ``amp/fwd/BertEncoder/...``; ``""`` for an op the compiler made."""
    return xplane.strip_scope(record.scope)


def kernel(record) -> str:
    """``apex_attn_fwd``, ``optim/lamb/norms``: the kernel name or optimizer
    phase the op was traced under, or ``""``."""
    return xplane.own_scope(record.scope)


def in_optimizer(record) -> bool:
    """Traced under amp's ``amp/update`` span or any ``optim/...`` scope."""
    scope = "/" + user_scope(record) + "/"
    return scope.startswith("/amp/update/") or "/optim/" in scope


def ms_per_step(trace, wanted: Callable[[object], bool]) -> Optional[float]:
    """Device ms a step spends in the ops ``wanted`` picks."""
    found = windowed(trace)
    if found is None:
        return None
    return sum(r.total_us for r in found.profile.ops
               if wanted(r)) / found.steps / 1e3


def published_peak(key: str) -> Optional[float]:
    """``peaks.json``'s number for the chip this process holds."""
    import jax
    with open(os.path.join(HERE, "peaks.json")) as f:
        chips = json.load(f)["chips"]
    return chips.get(jax.devices()[0].device_kind, {}).get(key)
