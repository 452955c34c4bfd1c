#!/usr/bin/env python
"""Regenerate tests/fixtures/resnet_step.xplane.pb (+ the BERT-layer
fixture bert_layer.xplane.pb).

Miniature, hand-made XSpace traces in the layout of a v5e capture
(device plane "/device:TPU:0" with "XLA Modules" + "XLA Ops" lines; per
op the HLO instruction text as the event metadata's name and, as a real
trace has them, the named-scope path and the runtime's category as the
``tf_op`` and ``hlo_category`` stats, named through the plane's
``stat_metadata``; plus a host plane the parser must skip). They are
synthetic: the cut-down *real* capture is ``v5e_bert_steps.xplane.pb``
(``tests/fixtures/README.md``). Written with a pure-stdlib protobuf
encoder, and ``tests/test_prof.py::TestXplaneFixture`` /
``tests/test_roofline.py`` pin the decoded tables against the values
below, so a parser or roofline-join regression surfaces in CI instead
of only on-chip.

The ResNet op set has hand-chosen ops and durations (fusions dominating,
one conv, one all-reduce, a copy) — rich enough to exercise opcode
extraction, the category rule (the runtime's ``hlo_category`` where the
op has one, the opcode's otherwise, a collective always by its opcode),
scope attribution, and occurrence aggregation.

The BERT op set is one BERT-Large layer's fwd+bwd hot ops at the bench
geometry (b=16 s=512 h=16 d=64, hidden 1024/4096), with durations taken
from the PERF.md round-5 ledger — notably the fused backward attention
kernel at 549 us against its ~436 us d=64 MXU floor, the one >10% gap
ROADMAP item 4 is chasing — and op durations summing to within 5% of
the module time, so ``apex_tpu.prof.roofline``'s attribution-closure
and worst-gap assertions (``scripts/roofline_audit.py --cpu8``) are
regression-tested tf-free.

``--cut`` makes the third fixture, ``v5e_bert_steps.xplane.pb``, from a
real capture: a ``--trace 1`` run of the benchmark's BERT cell on a v5e
(``benchmark/.out/bert_large.mlm_s512_b16/trace/.../*.xplane.pb``). It
keeps chip 0's plane with its ``stat_metadata``, three runs of the step
program on ``XLA Modules``, and on ``XLA Ops`` the events of those runs for
every op of the last encoder layer, of the embeddings, the head and the
loss, and one in twenty-three of the optimizer's ops and of the compiler's
own copies — so shares stay near those of the whole trace's 24 layers. Of an op's metadata it
keeps the instruction text and the stats the reader takes (``tf_op``,
``hlo_category``, ``flops``, ``bytes_accessed``,
``memory_access_breakdown``), byte for byte as the runtime wrote them.

Usage: python scripts/make_xplane_fixture.py            # both synthetic
       python scripts/make_xplane_fixture.py OUT.pb     # resnet only
       python scripts/make_xplane_fixture.py --bert OUT.pb
       python scripts/make_xplane_fixture.py --cut CAPTURE.xplane.pb [OUT.pb]
"""

import os
import sys


# --- minimal protobuf encoder (wire format) ----------------------------------

def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def field_varint(fno: int, v: int) -> bytes:
    return _uvarint(fno << 3 | 0) + _uvarint(v)


def field_bytes(fno: int, v: bytes) -> bytes:
    return _uvarint(fno << 3 | 2) + _uvarint(len(v)) + v


def field_str(fno: int, s: str) -> bytes:
    return field_bytes(fno, s.encode())


# --- XSpace schema subset (field numbers per the tsl xplane proto) -----------

def event(metadata_id: int, duration_ps: int, offset_ps: int = 0) -> bytes:
    return (field_varint(1, metadata_id) + field_varint(2, offset_ps)
            + field_varint(3, duration_ps))


def line(name: str, events) -> bytes:
    body = field_str(2, name)
    for ev in events:
        body += field_bytes(4, ev)
    return body


#: XPlane.stat_metadata: id -> name, as the v5e runtime numbers them
STAT_IDS = {"hlo_category": 24, "tf_op": 33}


def event_metadata(mid: int, name: str, stats=None) -> bytes:
    """``stats``: name -> str, written as XStat{metadata_id, str_value}."""
    body = field_varint(1, mid) + field_str(2, name)
    for stat, value in (stats or {}).items():
        body += field_bytes(5, field_varint(1, STAT_IDS[stat])
                            + field_str(5, value))
    return body


def plane(name: str, lines=(), metadata=(), stat_ids=None) -> bytes:
    body = field_str(2, name)
    for l in lines:
        body += field_bytes(3, l)
    for mid, md in metadata:
        body += field_bytes(4, field_varint(1, mid) + field_bytes(2, md))
    for stat, sid in (stat_ids or {}).items():
        body += field_bytes(5, field_varint(1, sid) + field_bytes(
            2, field_varint(1, sid) + field_str(2, stat)))
    return body


def xspace(planes) -> bytes:
    return b"".join(field_bytes(1, p) for p in planes)


# --- the fixture content -----------------------------------------------------

#: (metadata_id, HLO text, stats or None, [duration_us per occurrence]) —
#: the pinned per-op table lives in tests/test_prof.py; keep the two in
#: lockstep. An op without ``hlo_category`` takes the opcode's category.
OPS = [
    (10, '%fusion.31 = bf16[64,14,14,256]{3,2,1,0:T(8,128)(2,1)} '
         'fusion(bf16[64,14,14,256]{3,2,1,0} %p0, bf16[256]{0} '
         '%p1), kind=kOutput, calls=%fused_computation.31',
     {'hlo_category': 'output fusion',
      'tf_op': 'jit(step)/jvp(amp/fwd)/stage3/bn_relu:'},
     [93.0, 91.5]),
    (11, '%convolution.7 = '
         'bf16[64,14,14,256]{3,2,1,0:T(8,128)(2,1)} '
         'convolution(bf16[64,14,14,256]{3,2,1,0} %x, '
         'bf16[3,3,256,256]{3,2,1,0} %w), window={size=3x3 '
         'pad=1_1x1_1}, dim_labels=b01f_01io->b01f',
     {'tf_op': 'jit(step)/jvp(amp/fwd)/stage3/conv:'},
     [74.2, 73.8]),
    (12, '%all-reduce.3 = f32[524288]{0:T(1024)} '
         'all-reduce(f32[524288]{0} %grads), '
         'replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%sum',
     {'hlo_category': 'all-reduce',
      'tf_op': 'jit(step)/ddp/sync_gradients/bucket00/'
               'psum:'},
     [41.0]),
    (13, '%fusion.88 = (f32[1024]{0}, f32[1024]{0}) '
         'fusion(bf16[64,14,14,1024]{3,2,1,0} %dz), kind=kInput, '
         'calls=%fused_computation.88',
     {'hlo_category': 'input fusion',
      'tf_op': 'jit(step)/transpose(jvp(amp/fwd))/stage3/'
               'bn_bwd_sums:'},
     [49.7, 50.3]),
    (14, '%copy.5 = bf16[64,56,56,64]{3,2,1,0:T(8,128)(2,1)} '
         'copy(bf16[64,56,56,64]{1,3,2,0} %p4)',
     None,
     [12.5]),
    (15, '%custom-call.9 = bf16[64,512,8,64]{3,2,1,0} '
         'custom-call(bf16[64,512,8,64]{3,2,1,0} %q), '
         'custom_call_target="tpu_custom_call"',
     {'hlo_category': 'custom-call',
      'tf_op': 'jit(step)/jvp(amp/fwd)/attn/'
               'flash_attention:'},
     [31.0]),
]

MODULE_RUNS = [990.0, 1010.0]     # us — two steps captured


# --- the BERT-layer fixture (roofline regression target) ---------------------
#
# One BERT-Large encoder layer's fwd+bwd hot ops at the bench geometry
# (b=16, s=512, h=16, d=64 -> 8192 tokens, hidden 1024, ffn 4096), ONE
# captured step, durations from the PERF.md round-5 per-component
# ledger. The roofline math this pins (v5e: 197 TFLOP/s, 819 GB/s,
# d=64 -> 0.5 MXU cap):
#   attn fwd  354.0 us vs 4*B*H*S^2*D / 98.5e12 = 174.4 us  (eff 0.49)
#   attn bwd  549.0 us vs 10*B*H*S^2*D / 98.5e12 = 436.1 us (eff 0.79)
#     ^ THE known fused-backward gap (PERF round-5: "~550 vs ~440")
#   LN fwd     55.0 us vs 33.6 MB / 819 GB/s = 41.0 us      (memory)
#   LN bwd     71.0 us vs 50.4 MB / 819 GB/s = 61.5 us      (memory)
#   MLP fc1   370.0 us vs 2*8192*4096*1024 / 197e12 = 348.8 (eff 0.94)
#   MLP fc2   365.0 us vs same                              (eff 0.96)
#   bias grad  90.0 us vs 67.1 MB / 819 GB/s = 82.0 us      (memory)
# Op sum 1854.0 us vs the 1900.0 us module run = 2.4% closure error,
# inside roofline_audit's 5% gate.
BERT_OPS = [
    (20, '%custom-call.201 = bf16[16,512,16,64]{3,2,1,0} '
         'custom-call(bf16[16,512,16,64]{3,2,1,0} %q, '
         'bf16[16,512,16,64]{3,2,1,0} %k, '
         'bf16[16,512,16,64]{3,2,1,0} %v), '
         'custom_call_target="tpu_custom_call"',
     {'hlo_category': 'custom-call',
      'tf_op': 'jit(step)/jvp(bert/encoder_5/attn)/'
               'flash_attention_fwd:'},
     [354.0]),
    (21, '%custom-call.202 = (bf16[16,512,16,64]{3,2,1,0}, '
         'bf16[16,512,16,64]{3,2,1,0}, '
         'bf16[16,512,16,64]{3,2,1,0}) '
         'custom-call(bf16[16,512,16,64]{3,2,1,0} %q, '
         'bf16[16,512,16,64]{3,2,1,0} %k, '
         'bf16[16,512,16,64]{3,2,1,0} %v, '
         'bf16[16,512,16,64]{3,2,1,0} %do), '
         'custom_call_target="tpu_custom_call"',
     {'hlo_category': 'custom-call',
      'tf_op': 'jit(step)/transpose(jvp(bert/encoder_5/'
               'attn))/flash_attention_bwd:'},
     [549.0]),
    (22, '%fusion.210 = bf16[8192,1024]{1,0} '
         'fusion(bf16[8192,1024]{1,0} %x, f32[1024]{0} %gamma, '
         'f32[1024]{0} %beta), kind=kOutput, calls=%fused_ln_fwd',
     {'hlo_category': 'output fusion',
      'tf_op': 'jit(step)/jvp(bert/encoder_5/layer_norm)/'
               'ln_fwd:'},
     [55.0]),
    (23, '%fusion.211 = (bf16[8192,1024]{1,0}, f32[1024]{0}, '
         'f32[1024]{0}) fusion(bf16[8192,1024]{1,0} %dz, '
         'bf16[8192,1024]{1,0} %x, f32[1024]{0} %gamma), '
         'kind=kInput, calls=%fused_ln_bwd',
     {'hlo_category': 'input fusion',
      'tf_op': 'jit(step)/transpose(jvp(bert/encoder_5/'
               'layer_norm))/ln_bwd:'},
     [71.0]),
    (24, '%dot.220 = bf16[8192,4096]{1,0} dot(bf16[8192,1024]{1,0}'
         ' %h, bf16[1024,4096]{1,0} %w1), '
         'lhs_contracting_dims={1}, rhs_contracting_dims={0}',
     {'tf_op': 'jit(step)/jvp(bert/encoder_5/mlp)/fc1:'},
     [370.0]),
    (25, '%dot.221 = bf16[8192,1024]{1,0} dot(bf16[8192,4096]{1,0}'
         ' %act, bf16[4096,1024]{1,0} %w2), '
         'lhs_contracting_dims={1}, rhs_contracting_dims={0}',
     {'tf_op': 'jit(step)/jvp(bert/encoder_5/mlp)/fc2:'},
     [365.0]),
    (26, '%fusion.230 = f32[4096]{0} fusion(bf16[8192,4096]{1,0} '
         '%dact), kind=kInput, calls=%fused_bias_grad',
     {'hlo_category': 'input fusion',
      'tf_op': 'jit(step)/transpose(jvp(bert/encoder_5/'
               'mlp))/bias_grad:'},
     [90.0]),
]

BERT_MODULE_RUNS = [1900.0]       # us — one step captured


def build(ops=OPS, module_runs=MODULE_RUNS) -> bytes:
    md = [(1, event_metadata(1, "jit_step(1234)"))]
    op_events = []
    t = 0
    for mid, hlo, stats, durs in ops:
        md.append((mid, event_metadata(mid, hlo, stats)))
        for d in durs:
            op_events.append(event(mid, int(d * 1e6), offset_ps=t))
            t += int(d * 1e6)
    mod_events = [event(1, int(d * 1e6), offset_ps=i * 10 ** 9)
                  for i, d in enumerate(module_runs)]
    device = plane("/device:TPU:0",
                   lines=[line("XLA Modules", mod_events),
                          line("XLA Ops", op_events)],
                   metadata=md, stat_ids=STAT_IDS)
    host = plane("/host:CPU",
                 lines=[line("python", [event(1, 5_000_000)])],
                 metadata=[(1, event_metadata(1, "hostloop"))])
    return xspace([host, device])


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(_REPO, "tests", "fixtures")


# --- the cut of a real capture -------------------------------------------------

CUT_STATS = ("tf_op", "hlo_category", "flops", "bytes_accessed",
             "memory_access_breakdown")
CUT_LAYERS = ("TransformerLayer_23/",)
CUT_RUNS = 3
CUT_ONE_IN = 23


def cut(capture: bytes) -> bytes:
    """Chip 0's plane of a real capture, cut down as the docstring says.
    Reads with the program's own wire-format reader, re-emits the kept
    submessages from their original bytes."""
    sys.path.insert(0, _REPO)
    from apex_tpu.prof.xplane import _fields

    def sub(buf):
        return [(f, w, v) for f, w, v in _fields(buf)]

    def emit(fno, wt, v):
        return field_varint(fno, v) if wt == 0 else field_bytes(fno, v)

    for fno, _wt, raw in _fields(capture):
        if fno == 1 and any(f == 2 and v == b"/device:TPU:0"
                            for f, _w, v in _fields(raw)):
            break
    else:
        raise SystemExit("no /device:TPU:0 plane in the capture")
    parts = sub(raw)
    stat_names = {}
    for f, _w, v in parts:
        if f == 5:
            entry = dict((a, c) for a, _b, c in _fields(v))
            inner = dict((a, c) for a, _b, c in _fields(entry[2]))
            stat_names[entry[1]] = inner.get(2, b"").decode()
    keep_stat = {i for i, n in stat_names.items() if n in CUT_STATS}
    tf_op = next(i for i, n in stat_names.items() if n == "tf_op")

    metadata = {}               # id -> (trimmed bytes, scope)
    for f, _w, v in parts:
        if f != 4:
            continue
        entry = dict((a, c) for a, _b, c in _fields(v))
        body, scope = b"", ""
        for a, b, c in _fields(entry[2]):
            if a == 5:
                st = dict((x, z) for x, _y, z in _fields(c))
                if st.get(1) not in keep_stat:
                    continue
                if st[1] == tf_op:
                    scope = st.get(5, b"").decode()
            elif a not in (1, 2, 4):
                continue
            body += emit(a, b, c)
        metadata[entry[1]] = (body, scope)

    lines = {}
    for f, _w, v in parts:
        if f == 3:
            fields = sub(v)
            name = next(c for a, _b, c in fields if a == 2).decode()
            lines[name] = fields

    def events(fields):
        out = []
        for a, _b, c in fields:
            if a == 4:
                e = dict((x, z) for x, _y, z in _fields(c))
                out.append((e.get(1, 0), e.get(2, 0), e.get(3, 0)))
        return out

    modules = events(lines["XLA Modules"])
    total = {}
    for mid, _o, d in modules:
        total[mid] = total.get(mid, 0) + d
    step = max(total, key=total.get)
    runs = [e for e in modules if e[0] == step][2:2 + CUT_RUNS]
    lo, hi = runs[0][1], runs[-1][1] + runs[-1][2]

    # every op of the kept layers, of the embeddings, the head and the
    # loss; of the optimizer's and of the unscoped, one in CUT_ONE_IN each
    ops = [e for e in events(lines["XLA Ops"]) if lo <= e[1] < hi]
    kept, seen = set(), {"optimizer": 0, "unscoped": 0}
    for mid in sorted({e[0] for e in ops}):
        scope = metadata[mid][1]
        if "TransformerLayer_" in scope:
            keep = any(layer in scope for layer in CUT_LAYERS)
        elif scope and "/amp/update/" not in scope:
            keep = True
        else:
            kind = "optimizer" if scope else "unscoped"
            keep = seen[kind] % CUT_ONE_IN == 0
            seen[kind] += 1
        if keep:
            kept.add(mid)
    ops = [e for e in ops if e[0] in kept]

    def line_bytes(name, evs):
        body = b"".join(emit(a, b, c) for a, b, c in lines[name]
                        if a in (1, 2, 3))          # id, name, timestamp_ns
        return body + b"".join(field_bytes(4, event(m, d, offset_ps=o))
                               for m, o, d in evs)

    out = field_str(2, "/device:TPU:0")
    out += field_bytes(3, line_bytes("XLA Modules", runs))
    out += field_bytes(3, line_bytes("XLA Ops", ops))
    for mid in sorted({e[0] for e in ops} | {step}):
        out += field_bytes(4, field_varint(1, mid)
                           + field_bytes(2, metadata[mid][0]))
    for f, w, v in parts:
        if f == 5:
            out += emit(f, w, v)
    return field_bytes(1, out)


def _write(out: str, ops, module_runs) -> None:
    data = build(ops, module_runs)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "wb") as f:
        f.write(data)
    print(f"wrote {out} ({len(data)} bytes, {len(ops)} ops, "
          f"{len(module_runs)} module runs)")


def main() -> int:
    args = sys.argv[1:]
    if args and args[0] == "--cut":
        out = args[2] if len(args) > 2 else os.path.join(
            _FIXTURES, "v5e_bert_steps.xplane.pb")
        with open(args[1], "rb") as f:
            data = cut(f.read())
        with open(out, "wb") as f:
            f.write(data)
        print(f"wrote {out} ({len(data)} bytes)")
        return 0
    if args and args[0] == "--bert":
        out = args[1] if len(args) > 1 else os.path.join(
            _FIXTURES, "bert_layer.xplane.pb")
        _write(out, BERT_OPS, BERT_MODULE_RUNS)
        return 0
    if args:                           # explicit path: resnet only
        _write(args[0], OPS, MODULE_RUNS)
        return 0
    _write(os.path.join(_FIXTURES, "resnet_step.xplane.pb"),
           OPS, MODULE_RUNS)
    _write(os.path.join(_FIXTURES, "bert_layer.xplane.pb"),
           BERT_OPS, BERT_MODULE_RUNS)
    return 0


if __name__ == "__main__":
    sys.exit(main())
