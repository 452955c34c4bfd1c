"""The cross-rank half of apexlint: SPMD congruence + topology rules.

The jaxpr and HLO passes audit one program; the failures that cost a
*pod* are cross-rank: replica groups that disagree between two ranks'
programs deadlock every chip in the group, sharding propagation
silently materializes a replicated operand with a full all-gather, a
flat all-reduce spans a DCN boundary that wanted a hierarchical
schedule, and a nondeterministic draw breaks guard's bitwise-rewind
oracle only after the rewind. All four are statically visible — this
pass is strictly AOT like the other two (trace + compile, never a
dispatch).

- **spmd-divergence** (APX201): extract each rank's collective
  *schedule* (ordered collectives with channel ids, replica groups,
  dtypes, wire bytes) and walk all ranks in lockstep — every
  collective must appear in identical order with matching channel id,
  replica groups and dtype across all participants. The first
  diverging op is reported with the rank pair; a rank whose schedule
  runs dry while a peer still waits is the deadlock shape. One SPMD
  module is congruent by construction, but its groups are still
  checked for well-formedness (disjoint, covering); per-rank compiled
  modules (MPMD, elastic restarts on mixed binaries) get the full
  cross-program check.
- **implicit-full-gather** (APX202): an ``all-gather`` whose stripped
  scope matches no row of the collective-scope registry
  (:mod:`apex_tpu.parallel.registry`) — sharding propagation
  materializing a replicated operand the user never asked for, with
  the wire bytes and the materialized HBM bytes as evidence.
- **dcn-flat-collective** (APX203): a reduction collective whose
  replica group crosses a slice (DCN) boundary *and* keeps more than
  one member inside some slice — the flat one-hop shape. A
  hierarchical schedule reduces within-slice over ICI first, so its
  DCN hop carries 1/local_size of the bytes. Fires on planned scopes
  too: topology, not attribution. Wire-byte evidence uses the same
  result-shape accounting as ``monitor.wire_report``.
- **nondeterminism** (APX204): the static complement to guard's
  bitwise-rewind guarantee — ``rng_bit_generator`` whose key is a
  baked-in constant or whose updated state is dropped (not threaded
  through carried state: a rewind cannot replay the stream),
  ``pure_callback``/``io_callback`` results feeding the committed
  outputs (host values on the commit path re-run differently), and
  float scatter-adds with ``unique_indices=False`` (order-sensitive
  accumulation under SPMD repartitioning; warning severity).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from apex_tpu.lint.findings import Finding
from apex_tpu.lint.mesh_model import MeshModel
from apex_tpu.prof import memory as _mem
from apex_tpu.prof.xplane import (COLLECTIVE_PREFIXES, HLO_TEXT_SCOPE_RE,
                                  strip_scope)

__all__ = ["CollectiveInstr", "extract_collective_schedule",
           "parse_replica_groups", "rank_schedule",
           "congruence_findings", "full_gather_findings",
           "dcn_flat_findings", "nondeterminism_jaxpr_findings",
           "lint_spmd_text"]


# -- collective-schedule extraction -------------------------------------------

@dataclasses.dataclass(frozen=True)
class CollectiveInstr:
    """One collective in a module's schedule, with its match identity."""

    index: int                       # position among the module's collectives
    name: str                        # HLO instruction name
    opcode: str                      # normalized ("all-reduce", ...)
    channel_id: Optional[int]
    replica_groups: Tuple[Tuple[int, ...], ...]  # () = one implicit
                                                 # all-devices group
    dtypes: Tuple[str, ...]          # result dtypes
    bytes: int                       # wire bytes — result-shape
                                     # accounting, = monitor.wire_report
    scope: str                       # stripped named-scope path
    use_global_ids: bool = True

    def identity(self) -> Tuple:
        """The congruence-match key: what every participant must agree
        on for the collective to complete (wire bytes included — a
        dtype-matched but size-mismatched pair still hangs)."""
        return (self.opcode, self.channel_id, self.replica_groups,
                self.dtypes, self.bytes)

    def describe(self) -> str:
        groups = ("all-devices" if not self.replica_groups else
                  "{" + ",".join(
                      "{" + ",".join(map(str, g)) + "}"
                      for g in self.replica_groups[:4])
                  + (",..." if len(self.replica_groups) > 4 else "")
                  + "}")
        return (f"{self.opcode}(channel={self.channel_id}, "
                f"groups={groups}, {'+'.join(self.dtypes)}, "
                f"{self.bytes}B)")


_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{(?:[^{}]|\{[^{}]*\})*\}"
    r"|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")
_IOTA_RE = re.compile(
    r"^\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?$")


def parse_replica_groups(text: str) -> Tuple[Tuple[int, ...], ...]:
    """Parse either replica-group syntax XLA prints:

    explicit ``{{0,1},{2,3}}`` (also ``{}``), or iota(-v2)
    ``[G,S]<=[d0,d1,...]`` with optional transpose ``T(p...)`` —
    ``arange(prod(d)).reshape(d).transpose(p).reshape(G, S)``.
    """
    text = text.strip()
    m = _IOTA_RE.match(text)
    if m:
        gshape = [int(x) for x in m.group(1).split(",") if x]
        dims = [int(x) for x in m.group(2).split(",") if x]
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(3):
            perm = [int(x) for x in m.group(3).split(",") if x]
            arr = arr.transpose(perm)
        arr = arr.reshape(gshape)
        return tuple(tuple(int(v) for v in row) for row in arr)
    if not (text.startswith("{") and text.endswith("}")):
        raise ValueError(f"unrecognized replica_groups {text!r}")
    groups = []
    for gm in re.finditer(r"\{([\d, ]*)\}", text[1:-1]):
        ids = [int(x) for x in gm.group(1).replace(" ", "").split(",")
               if x]
        if ids:
            groups.append(tuple(ids))
    return tuple(groups)


def _split_top_level(s: str) -> List[str]:
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 1 and s[0] == "(":
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def _result_shape_of_start(shape: str) -> str:
    """The result half of an async ``-start`` tuple shape: the tuple is
    ``(operands..., results...)``, so the trailing half is the payload
    the matching ``-done`` returns."""
    if not shape.startswith("("):
        return shape
    parts = _split_top_level(shape)
    if parts and parts[0].startswith("("):
        parts[0] = parts[0][1:]
    if len(parts) >= 2 and len(parts) % 2 == 0:
        return " ".join(parts[len(parts) // 2:])
    return shape  # odd arity — caller falls back to halved bytes


def _dtypes_of(shape: str) -> Tuple[str, ...]:
    return tuple(sorted({dt for dt, _ in _mem._SHAPE_RE.findall(shape)
                         if dt in _mem._DTYPE_BYTES}))


def extract_collective_schedule(hlo_text: str) -> List[CollectiveInstr]:
    """Ordered collectives of an optimized module (entry + nested
    computations, textual schedule order). Async pairs are recorded at
    the ``-start`` (the issue point a deadlock hangs at, and the line
    carrying channel id + replica groups); their ``-done`` halves are
    skipped. Wire bytes use the result shape — identical accounting to
    ``monitor.collective_bytes_by_dtype``, so topology findings agree
    with ``monitor.wire_report`` by construction."""
    out: List[CollectiveInstr] = []
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _mem._INSTR_RE.match(line)
        if not m:
            continue
        op = m.group("op")
        prefix = next((p for p in COLLECTIVE_PREFIXES
                       if op.startswith(p)), None)
        if prefix is None:
            continue
        if op == prefix + "-done":
            continue                       # recorded at the -start
        shape = m.group("shape")
        if op == prefix + "-start":
            result_shape = _result_shape_of_start(shape)
            nbytes = (_mem.shape_bytes(result_shape)
                      if result_shape != shape
                      else _mem.shape_bytes(shape) // 2)
        else:
            result_shape = shape
            nbytes = _mem.shape_bytes(shape)
        cm = _CHANNEL_RE.search(line)
        gm = _GROUPS_RE.search(line)
        sm = HLO_TEXT_SCOPE_RE.search(line)
        out.append(CollectiveInstr(
            index=len(out),
            name=m.group("n").lstrip("%"),
            opcode=prefix,
            channel_id=int(cm.group(1)) if cm else None,
            replica_groups=(parse_replica_groups(gm.group(1))
                            if gm else ()),
            dtypes=_dtypes_of(result_shape),
            bytes=nbytes,
            scope=strip_scope(sm.group(1)) if sm else "",
            use_global_ids="use_global_device_ids=true" in line))
    return out


# -- APX201: cross-rank congruence --------------------------------------------

def _participants(instr: CollectiveInstr, all_ranks: Sequence[int]
                  ) -> List[int]:
    if not instr.replica_groups:
        return list(all_ranks)
    members = {m for g in instr.replica_groups for m in g}
    return [r for r in all_ranks if r in members]


def rank_schedule(schedule: Sequence[CollectiveInstr],
                  rank: int) -> List[CollectiveInstr]:
    """The subsequence of a module's collectives ``rank`` participates
    in (member of some replica group, or every collective when groups
    are implicit)."""
    out = []
    for instr in schedule:
        if not instr.replica_groups or any(
                rank in g for g in instr.replica_groups):
            out.append(instr)
    return out


def _group_shape_findings(rank: int, schedule: Sequence[CollectiveInstr],
                          n_ranks: int) -> List[Finding]:
    """Per-module well-formedness: groups must be disjoint and (with
    global device ids) cover every rank — a rank left out of all groups
    of an instruction it executes never joins the rendezvous.

    Channel ids are not audited within a module: XLA (jaxlib 0.9.0)
    numbers every collective of an SPMD-partitioned module
    ``channel_id=1`` whatever its replica groups, so the id says
    nothing about one module — it is compared only between ranks'
    matched collectives (:func:`_first_mismatch`)."""
    out: List[Finding] = []
    for instr in schedule:
        seen: Set[int] = set()
        dup = [m for g in instr.replica_groups for m in g
               if m in seen or seen.add(m)]
        if dup:
            d = sorted(set(dup))
            out.append(Finding(
                rule="spmd-divergence",
                message=f"{instr.describe()} lists rank(s) {d} in "
                        f"more than one replica group — the groups "
                        f"must partition the mesh",
                op=instr.opcode, scope=instr.scope or instr.name,
                # ranks is a PAIR in the event schema; a single
                # double-listed rank carries its evidence in the message
                ranks=d[:2] if len(d) >= 2 else None))
        elif (instr.replica_groups and instr.use_global_ids
                and len(seen) not in (0, n_ranks)):
            missing = sorted(set(range(n_ranks)) - seen)
            out.append(Finding(
                rule="spmd-divergence",
                message=f"rank {rank}: {instr.describe()} covers only "
                        f"{len(seen)}/{n_ranks} ranks — rank(s) "
                        f"{missing[:4]} execute the op but belong to "
                        f"no group",
                op=instr.opcode, scope=instr.scope or instr.name,
                ranks=[rank, missing[0]] if missing else None))
    return out


def congruence_findings(modules, n_ranks: Optional[int] = None,
                        mesh_model: Optional[MeshModel] = None
                        ) -> List[Finding]:
    """APX201 over one SPMD module or per-rank modules.

    ``modules``: optimized-HLO text or a pre-extracted schedule (one
    SPMD program — every rank runs the same schedule; group
    well-formedness is still audited), or ``{rank: hlo_text}`` for
    per-rank-compiled programs (the MPMD / mixed-binary case the
    lockstep walk exists for). ``n_ranks`` defaults to the mesh
    model's device count, the dict's size, or the highest rank any
    replica group mentions + 1.
    """
    if isinstance(modules, (str, list)):
        schedule = (modules if isinstance(modules, list)
                    else extract_collective_schedule(modules))
        if n_ranks is None:
            n_ranks = _infer_n_ranks(schedule, mesh_model)
        per_rank = {r: schedule for r in range(n_ranks)}
    else:
        texts = dict(modules)
        # one schedule object per distinct module text: parse once, and
        # the identity-based well-formedness dedupe below sees through
        # N ranks sharing one binary
        by_text: Dict[str, List[CollectiveInstr]] = {}
        schedules = {}
        for r, t in texts.items():
            if t not in by_text:
                by_text[t] = extract_collective_schedule(t)
            schedules[r] = by_text[t]
        if n_ranks is None:
            n_ranks = (mesh_model.n_devices if mesh_model is not None
                       else max(max(texts, default=0) + 1,
                                max((m for s in schedules.values()
                                     for i in s
                                     for g in i.replica_groups
                                     for m in g), default=-1) + 1))
        per_rank = {r: schedules[r] if r in schedules else None
                    for r in range(n_ranks)}
        # ranks without a module of their own run rank 0's (the common
        # "one binary, is it safe?" case degenerates to SPMD)
        base = schedules.get(min(schedules, default=0), [])
        per_rank = {r: (s if s is not None else base)
                    for r, s in per_rank.items()}

    out: List[Finding] = []
    seen_mods: Set[int] = set()
    for r in sorted(per_rank):
        if id(per_rank[r]) in seen_mods:
            continue
        seen_mods.add(id(per_rank[r]))
        out += _group_shape_findings(r, per_rank[r], n_ranks)
    if out:
        return out     # malformed groups make the lockstep walk moot

    ranks = sorted(per_rank)
    queues = {r: list(rank_schedule(per_rank[r], r)) for r in ranks}
    heads = {r: 0 for r in ranks}

    def head(r):
        q = queues[r]
        return q[heads[r]] if heads[r] < len(q) else None

    while True:
        live = [r for r in ranks if head(r) is not None]
        if not live:
            break
        r0 = live[0]
        ref = head(r0)
        participants = _participants(ref, ranks)
        diverged = False
        for p in participants:
            if p == r0:
                continue
            other = head(p)
            if other is None:
                out.append(Finding(
                    rule="spmd-divergence",
                    message=f"deadlock: rank {r0} waits in "
                            f"{ref.describe()} at schedule position "
                            f"{heads[r0]} but rank {p}'s collective "
                            f"schedule is exhausted — rank {p} never "
                            f"joins",
                    op=ref.opcode, scope=ref.scope or ref.name,
                    bytes=ref.bytes, ranks=[r0, p],
                    axes=(mesh_model.group_axes(participants)
                          if mesh_model is not None else None)))
                diverged = True
                break
            if other.identity() != ref.identity():
                field = _first_mismatch(ref, other)
                out.append(Finding(
                    rule="spmd-divergence",
                    message=f"first diverging op at schedule position "
                            f"{heads[r0]}: rank {r0} issues "
                            f"{ref.describe()} but rank {p} issues "
                            f"{other.describe()} — {field} mismatch "
                            f"deadlocks every rank in the group",
                    op=ref.opcode, scope=ref.scope or ref.name,
                    bytes=ref.bytes, ranks=[r0, p],
                    axes=(mesh_model.group_axes(participants)
                          if mesh_model is not None else None)))
                diverged = True
                break
        if diverged:
            break      # everything after the first divergence is noise
        for p in participants:
            heads[p] += 1
    return out


def _first_mismatch(a: CollectiveInstr, b: CollectiveInstr) -> str:
    if a.opcode != b.opcode:
        return f"opcode ({a.opcode} vs {b.opcode})"
    if a.channel_id != b.channel_id:
        return f"channel id ({a.channel_id} vs {b.channel_id})"
    if a.replica_groups != b.replica_groups:
        return "replica groups"
    if a.dtypes != b.dtypes:
        return f"dtype ({'+'.join(a.dtypes)} vs {'+'.join(b.dtypes)})"
    return f"payload bytes ({a.bytes} vs {b.bytes})"


def _infer_n_ranks(schedule: Sequence[CollectiveInstr],
                   mesh_model: Optional[MeshModel]) -> int:
    if mesh_model is not None:
        return mesh_model.n_devices
    return max((m for i in schedule for g in i.replica_groups
                for m in g), default=0) + 1


# -- APX202: implicit full gather ---------------------------------------------

def _scope_known(scope: str, extra: Sequence[str]):
    from apex_tpu.parallel import registry
    return registry.scope_entry(scope, extra=extra)


def full_gather_findings(hlo_text_or_schedule, *,
                         mesh_model: Optional[MeshModel] = None,
                         known_scopes: Sequence[str] = ()
                         ) -> List[Finding]:
    """APX202: ``all-gather`` ops outside every registered collective
    scope — the gather sharding propagation inserted to materialize a
    replicated operand. Evidence bytes are the gathered result (wire
    accounting = ``monitor.wire_report``); the message carries the HBM
    bytes the replication costs every participant."""
    schedule = (hlo_text_or_schedule
                if isinstance(hlo_text_or_schedule, list)
                else extract_collective_schedule(hlo_text_or_schedule))
    agg: Dict[Tuple[str, str], List[CollectiveInstr]] = {}
    for instr in schedule:
        if instr.opcode != "all-gather":
            continue
        if _scope_known(instr.scope, known_scopes) is not None:
            continue
        agg.setdefault((instr.opcode, instr.scope), []).append(instr)
    out: List[Finding] = []
    for (op, scope), instrs in sorted(agg.items()):
        nbytes = sum(i.bytes for i in instrs)
        first = instrs[0]
        axes = hop = None
        where = ""
        if mesh_model is not None and first.replica_groups:
            g = first.replica_groups[0]
            axes = mesh_model.group_axes(g) or None
            hop = mesh_model.group_hop(
                {m for gg in first.replica_groups for m in gg})
            full = (len(first.replica_groups) == 1
                    and len(g) == mesh_model.n_devices)
            where = (" across the whole mesh" if full else
                     f" over axes {axes}" if axes else "")
        out.append(Finding(
            rule="implicit-full-gather",
            message=f"{len(instrs)} unplanned all-gather(s){where} "
                    f"materialize a replicated operand the program "
                    f"never names — {nbytes} wire bytes/step and "
                    f"{nbytes} bytes of HBM per participant",
            op=op, scope=scope or "<unscoped>", bytes=nbytes,
            count=len(instrs), axes=axes, hop=hop))
    return out


# -- APX203: DCN-crossing flat collective -------------------------------------

_REDUCE_OPS = ("all-reduce", "reduce-scatter")


def dcn_flat_findings(hlo_text_or_schedule, mesh_model: MeshModel,
                      ) -> List[Finding]:
    """APX203: reduction collectives whose replica groups cross a DCN
    (slice) boundary while keeping >1 member inside some slice — the
    flat one-hop reduce that wanted a hierarchical schedule. Fires on
    planned (scoped) collectives too: this is a topology property, not
    an attribution one."""
    schedule = (hlo_text_or_schedule
                if isinstance(hlo_text_or_schedule, list)
                else extract_collective_schedule(hlo_text_or_schedule))
    agg: Dict[Tuple[str, str], List[CollectiveInstr]] = {}
    for instr in schedule:
        if instr.opcode not in _REDUCE_OPS:
            continue
        groups = instr.replica_groups or (
            tuple(range(mesh_model.n_devices)),)
        if any(mesh_model.is_flat_dcn_group(g) for g in groups):
            agg.setdefault((instr.opcode, instr.scope),
                           []).append(instr)
    out: List[Finding] = []
    local = 1
    for a in mesh_model.axes:
        if a.link == "ici":
            local *= a.size
    for (op, scope), instrs in sorted(agg.items()):
        nbytes = sum(i.bytes for i in instrs)
        g0 = (instrs[0].replica_groups or
              (tuple(range(mesh_model.n_devices)),))[0]
        axes = mesh_model.group_axes(g0) or None
        flat_ms = mesh_model.hop_seconds(nbytes, "dcn") * 1e3
        hier_ms = mesh_model.hop_seconds(
            max(nbytes // max(local, 1), 1), "dcn") * 1e3
        out.append(Finding(
            rule="dcn-flat-collective",
            message=f"{len(instrs)} flat {op}(s) cross a DCN boundary "
                    f"with whole-slice groups — {nbytes} wire bytes "
                    f"ride DCN (~{flat_ms:.2f} ms); a hierarchical "
                    f"schedule (ICI reduce within-slice first) sends "
                    f"~1/{local} of that (~{hier_ms:.2f} ms)",
            op=op, scope=scope or "<unscoped>", bytes=nbytes,
            count=len(instrs), axes=axes, hop="dcn"))
    return out


# -- APX204: nondeterminism ----------------------------------------------------

_COMMIT_CALLBACK_PRIMS = ("pure_callback", "io_callback")


def _is_literal(v) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


def _is_float_dtype(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    try:
        return np.issubdtype(np.dtype(dt), np.floating)
    except TypeError:
        return False


def nondeterminism_jaxpr_findings(jaxpr) -> List[Finding]:
    """APX204 over a (Closed)Jaxpr — see the module docstring for the
    three detector classes. Recurses into every sub-jaxpr; each level's
    commit path is that level's outvars (conservative for nested
    calls, whose results flow outward opaquely)."""
    from apex_tpu.lint.jaxpr_pass import _closed_to_jaxpr, _sub_jaxprs
    out: List[Finding] = []
    _nondet_walk(_closed_to_jaxpr(jaxpr), (), out,
                 _closed_to_jaxpr, _sub_jaxprs)
    return out


def _nondet_walk(jaxpr, path, out, _closed, _subs) -> None:
    used: Set = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not _is_literal(v):
                used.add(v)
    for v in jaxpr.outvars:
        if not _is_literal(v):
            used.add(v)

    # commit-path reachability: vars that (transitively, treating each
    # eqn as opaque) feed this jaxpr's outputs
    needed: Set = {v for v in jaxpr.outvars if not _is_literal(v)}
    for eqn in reversed(jaxpr.eqns):
        if any(v in needed for v in eqn.outvars):
            for v in eqn.invars:
                if not _is_literal(v):
                    needed.add(v)

    where = "/".join(path) or None
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "rng_bit_generator":
            key_literal = eqn.invars and _is_literal(eqn.invars[0])
            state_out = eqn.outvars[0] if eqn.outvars else None
            state_dropped = (state_out is None or state_out not in used)
            # a threaded, argument-derived key replays bitwise — clean
            if key_literal or state_dropped:
                why = ("a baked-in constant key" if key_literal else
                       "a dropped output state (the updated key never "
                       "threads back into carried state)")
                out.append(Finding(
                    rule="nondeterminism",
                    message=f"rng_bit_generator with {why} — the "
                            "stream cannot be replayed after a "
                            "guard rewind",
                    op=name, scope=where))
        elif name in _COMMIT_CALLBACK_PRIMS:
            if any(v in needed for v in eqn.outvars):
                out.append(Finding(
                    rule="nondeterminism",
                    message=f"{name} result feeds the committed step "
                            "outputs — host values on the commit path "
                            "re-run differently on rewind/replay",
                    op=name, scope=where))
        elif name == "scatter-add":
            if (not eqn.params.get("unique_indices", False)
                    and eqn.invars
                    and _is_float_dtype(getattr(eqn.invars[0], "aval",
                                                None))):
                out.append(Finding(
                    rule="nondeterminism", severity="warning",
                    message="float scatter-add with unique_indices="
                            "False — duplicate-index accumulation "
                            "order is not stable under SPMD "
                            "repartitioning",
                    op=name, scope=where))
        sub_path = path + ((str(eqn.params.get("name")),)
                           if eqn.params.get("name") else ())
        for sub in _subs(eqn):
            _nondet_walk(_closed(sub), sub_path, out, _closed, _subs)


# -- entry point --------------------------------------------------------------

def lint_spmd_text(modules, *, mesh_model: Optional[MeshModel] = None,
                   known_scopes: Sequence[str] = (),
                   n_ranks: Optional[int] = None,
                   rules: Optional[Sequence[str]] = None
                   ) -> List[Finding]:
    """Run the cross-rank HLO rules over one SPMD module (text) or
    per-rank modules (``{rank: text}``). APX203 needs a mesh model
    with a DCN axis; APX202 uses it for axis/hop evidence when given.
    The jaxpr-side APX204 detectors live in
    :func:`nondeterminism_jaxpr_findings` (``lint_step`` runs them off
    its one trace)."""
    run = set(rules) if rules is not None else None

    def on(slug: str) -> bool:
        return run is None or slug in run

    out: List[Finding] = []
    if on("spmd-divergence"):
        out += congruence_findings(modules, n_ranks=n_ranks,
                                   mesh_model=mesh_model)
    # APX202/203 audit every DISTINCT module (a rank-local gather or
    # flat reduce in an MPMD peer's program is just as real); identical
    # texts are parsed and reported once
    texts = ([modules] if isinstance(modules, str)
             else list(dict.fromkeys(modules.values())))
    for text in texts:
        schedule = extract_collective_schedule(text)
        if on("implicit-full-gather"):
            out += full_gather_findings(schedule, mesh_model=mesh_model,
                                        known_scopes=known_scopes)
        if on("dcn-flat-collective") and mesh_model is not None:
            out += dcn_flat_findings(schedule, mesh_model)
    return out
