"""Byte-ledger attribution of an optimized-HLO dump.

Parses the entry computation of a dumped module (scripts/dump_hlo.py),
estimates per-instruction HBM traffic from operand/output shapes, and
groups it by block/layer (from op_name metadata) and by op class. This
is the accounting tool behind PERF.md's "where do the bytes go" tables —
the reference reads nvprof SQLite for the same question
(`apex/pyprof/prof/`); XLA's serialized HLO carries the shapes already.

Usage: python scripts/hlo_bytes.py HLO.txt [--by block|class] [--top N]

Caveats: traffic is estimated as sum(unique operand bytes) + output
bytes per entry instruction — intra-fusion temporaries are free,
parameters/constants counted once per use, and S(1)/S(2) (scoped/SMEM)
annotations are ignored; numbers track XLA's cost analysis within a few
percent on the bench step.
"""

import re
import sys
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e5m2": 1, "f8e4m3fn": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(text):
    """Total bytes of every shape literal in `text` (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# the opcode is the first lowercase word followed by "(": layout
# annotations only contain T(...), S(...) and (2,1) groups, none of
# which a [a-z][\w-]*\( pattern matches
_OPCODE_RE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def parse_entry(path):
    """Yield (name, opcode, out_bytes, args, op_name) per entry op."""
    with open(path) as f:
        text = f.read()
    entry = text[text.rindex("ENTRY "):]
    for line in entry.splitlines():
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.strip().lstrip("%")
        m = _OPCODE_RE.search(rhs)
        if not m:
            continue
        opcode = m.group(1)
        out_b = shape_bytes(rhs[:m.start()])
        args = rhs[m.end():]
        args = args.split("metadata=")[0].split("backend_config=")[0]
        args = args.split("calls=")[0].split("kind=")[0]
        mo = _OPNAME_RE.search(line)
        yield name, opcode, out_b, args, (mo.group(1) if mo else "")


def main():
    path = sys.argv[1]
    by = "block"
    top = 40
    if "--by" in sys.argv:
        by = sys.argv[sys.argv.index("--by") + 1]
    if "--top" in sys.argv:
        top = int(sys.argv[sys.argv.index("--top") + 1])

    # first pass: output bytes per instruction name (definition map)
    defs = {}
    rows = []
    for name, opcode, out_b, args, op_name in parse_entry(path):
        defs[name] = out_b
        rows.append((name, opcode, out_b, args, op_name))

    groups = defaultdict(float)
    cls_groups = defaultdict(float)
    total = 0.0
    for name, opcode, out_b, args, op_name in rows:
        if opcode in ("parameter", "constant", "get-tuple-element",
                      "tuple", "bitcast"):
            continue
        in_b = 0
        seen = set()
        for ref in re.findall(r"%([\w.-]+)", args):
            if ref in defs and ref not in seen:
                seen.add(ref)
                in_b += defs[ref]
        traffic = out_b + in_b
        total += traffic
        # group key: the model block from op_name, else the opcode
        key = opcode
        m = re.search(r"(BottleneckBlock_\d+|stem\w*|Dense_\d+|_BN_\d+"
                      r"|FusedSGD)", op_name)
        blk = m.group(1) if m else (op_name.split("/")[1]
                                    if op_name.count("/") > 1 else opcode)
        fwd = "jvp" in op_name and "transpose" not in op_name
        groups[f"{blk}{'  [fwd]' if fwd else ' [bwd]' if 'transpose' in op_name else ''}"] += traffic
        cls_groups[opcode] += traffic

    sel = groups if by == "block" else cls_groups
    print(f"total est. traffic: {total/1e9:.1f} GB "
          f"({len(rows)} entry instructions)")
    for k, v in sorted(sel.items(), key=lambda kv: -kv[1])[:top]:
        print(f"  {v/1e9:8.2f} GB  {k}")


if __name__ == "__main__":
    main()
