"""The jaxpr half of apexlint: trace-time semantic rules.

Walks ``jax.make_jaxpr`` output (recursing into every sub-jaxpr — pjit
bodies, scan/while bodies, cond branches, custom_vjp calls) and checks
properties that are visible *before* XLA ever runs:

- **rng-key-reuse** (APX001): the same key variable consumed by more
  than one random primitive (directly, via ``random_wrap`` of a raw
  uint32 key, or as the key operand of a call whose body draws
  randomness) — correlated draws, the classic silent-statistics bug.
- **f64-creep** (APX002): any float64 value in the step — a numpy
  scalar or ``.astype`` that promoted the graph.
- **fp32-matmul-in-amp** (APX003): an all-fp32 ``dot_general``/
  ``conv_general_dilated`` while the supplied amp policy computes in
  bf16/fp16 (a bf16-in/f32-out accumulating dot is fine and not
  flagged).
- **host-callback-in-step** (APX004): ``jax.debug.print``/
  ``pure_callback``/``io_callback`` traced into the step.

Everything here is AOT: ``make_jaxpr`` traces but never compiles or
dispatches (the ``lint/no-extra-dispatch`` compile-check case pins
that linting leaves the step's compiled HLO bit-identical).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from apex_tpu.lint.findings import Finding

__all__ = ["lint_jaxpr", "iter_eqns"]

#: primitives that CONSUME a key to draw bits / derive keys
RANDOM_PRIMS = frozenset({
    "random_bits", "random_split", "random_fold_in", "random_gamma",
    "threefry2x32", "rng_bit_generator",
})
#: primitives that wrap a raw uint32 buffer into a typed key — their
#: operand IS the key material, so two wraps of one buffer is reuse
KEY_WRAP_PRIMS = frozenset({"random_wrap"})

# jax 0.9.0 traces ``jax.debug.print`` to its own ``debug_print``
# primitive (``jax.debug.callback`` is still ``debug_callback``)
CALLBACK_PRIMS = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback",
})

MATMUL_PRIMS = frozenset({"dot_general", "conv_general_dilated"})

_F64 = np.dtype(np.float64)
_F32 = np.dtype(np.float32)


def _np_dtype(dt) -> Optional[np.dtype]:
    """np.dtype of an aval dtype, or None for extended dtypes (typed
    PRNG keys) that numpy cannot interpret."""
    if dt is None:
        return None
    try:
        return np.dtype(dt)
    except TypeError:
        return None


def _closed_to_jaxpr(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _sub_jaxprs(eqn):
    """Every Jaxpr nested in an eqn's params (call/control-flow bodies)."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "eqns"):            # Jaxpr
                yield x
            elif hasattr(x, "jaxpr"):          # ClosedJaxpr
                yield x.jaxpr


def iter_eqns(jaxpr, path: Tuple[str, ...] = ()):
    """Yield ``(eqn, path)`` over a jaxpr and all nested jaxprs; path
    accumulates call names (``pjit[name=...]``, scan, while, ...)."""
    jaxpr = _closed_to_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn, path
        name = eqn.params.get("name")
        sub_path = path + ((str(name),) if name
                           else (eqn.primitive.name,))
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, sub_path)


def _contains_random(jaxpr, memo: Dict[int, bool]) -> bool:
    jaxpr = _closed_to_jaxpr(jaxpr)
    key = id(jaxpr)
    if key in memo:
        return memo[key]
    memo[key] = False        # cycle guard (jaxprs are acyclic anyway)
    found = False
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name in RANDOM_PRIMS
                or eqn.primitive.name in KEY_WRAP_PRIMS):
            found = True
            break
        if any(_contains_random(s, memo) for s in _sub_jaxprs(eqn)):
            found = True
            break
    memo[key] = found
    return found


def _is_key_aval(aval) -> bool:
    """True for typed PRNG keys and raw uint32 key buffers."""
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    try:
        if jax.dtypes.issubdtype(dt, jax.dtypes.prng_key):
            return True
    except Exception:
        pass
    shape = getattr(aval, "shape", ())
    nd = _np_dtype(dt)
    return (nd is not None and nd == np.dtype(np.uint32)
            and len(shape) >= 1 and shape[-1] in (2, 4))


def _is_literal(v) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


# -- rules --------------------------------------------------------------------

def _rng_reuse(jaxpr, memo, out: List[Finding],
               path: Tuple[str, ...] = ()) -> None:
    """Per jaxpr level: key vars consumed by >= 2 random consumers."""
    jaxpr = _closed_to_jaxpr(jaxpr)
    consumers: Dict[Any, List[str]] = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        is_random = (name in RANDOM_PRIMS or name in KEY_WRAP_PRIMS
                     or any(_contains_random(s, memo)
                            for s in _sub_jaxprs(eqn)))
        if is_random:
            label = str(eqn.params.get("name") or name)
            for v in eqn.invars:
                if _is_literal(v):
                    continue
                if name in KEY_WRAP_PRIMS or _is_key_aval(v.aval):
                    consumers.setdefault(v, []).append(label)
        # recurse: reuse inside a call body is a violation at that level
        sub_path = path + ((str(eqn.params.get("name")),)
                           if eqn.params.get("name") else ())
        for sub in _sub_jaxprs(eqn):
            _rng_reuse(sub, memo, out, sub_path)
    for var, who in consumers.items():
        if len(who) >= 2:
            aval = getattr(var, "aval", None)
            out.append(Finding(
                rule="rng-key-reuse",
                message=f"key {aval} feeds {len(who)} random consumers: "
                        f"{', '.join(who[:4])}",
                op="/".join(who[:4]), scope="/".join(path),
                count=len(who)))


def _f64_creep(jaxpr, out: List[Finding]) -> None:
    hits: Dict[str, int] = {}
    top = _closed_to_jaxpr(jaxpr)
    for v in top.invars:
        dt = _np_dtype(getattr(getattr(v, "aval", None), "dtype", None))
        # NB: "dt == _F64" without the None guard would be True — numpy
        # coerces None to the default dtype, which IS float64
        if dt is not None and dt == _F64:
            hits["<argument>"] = hits.get("<argument>", 0) + 1
    for eqn, _path in iter_eqns(jaxpr):
        for v in eqn.outvars:
            dt = _np_dtype(getattr(getattr(v, "aval", None), "dtype",
                                   None))
            if dt is not None and dt == _F64:
                hits[eqn.primitive.name] = \
                    hits.get(eqn.primitive.name, 0) + 1
                break
    if hits:
        n = sum(hits.values())
        prims = ", ".join(sorted(hits)[:5])
        out.append(Finding(
            rule="f64-creep",
            message=f"{n} f64-producing equation(s) in the step "
                    f"(primitives: {prims})",
            op=prims, count=n))


def _fp32_matmul(jaxpr, policy, out: List[Finding]) -> None:
    if policy is None or not getattr(policy, "enabled", False):
        return
    half = (np.dtype(np.float16), np.dtype(np.dtype("bfloat16")))
    try:
        compute = np.dtype(policy.compute_dtype)
    except Exception:
        return
    if compute not in half:
        return
    hits: Dict[str, int] = {}
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name not in MATMUL_PRIMS:
            continue
        in_all = [_np_dtype(getattr(getattr(v, "aval", None), "dtype",
                                    None)) for v in eqn.invars]
        in_dts = [d for d in in_all
                  if d is not None and np.issubdtype(d, np.floating)]
        out_dts = [d for d in (
            _np_dtype(getattr(getattr(v, "aval", None), "dtype", None))
            for v in eqn.outvars) if d is not None]
        # bf16-in/f32-out accumulation is the *wanted* shape; only an
        # all-fp32 matmul is creep
        if in_dts and all(d == _F32 for d in in_dts) \
                and all(d == _F32 for d in out_dts):
            key = "/".join(path + (eqn.primitive.name,)) or \
                eqn.primitive.name
            hits[key] = hits.get(key, 0) + 1
    for where, n in sorted(hits.items()):
        out.append(Finding(
            rule="fp32-matmul-in-amp",
            message=f"{n} all-fp32 matmul(s) under an active "
                    f"{compute} policy at {where}",
            op=where.rsplit("/", 1)[-1], scope=where, count=n))


def _callbacks(jaxpr, out: List[Finding]) -> None:
    hits: Dict[str, Tuple[int, str]] = {}
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name in CALLBACK_PRIMS:
            n, p = hits.get(eqn.primitive.name, (0, "/".join(path)))
            hits[eqn.primitive.name] = (n + 1, p)
    for prim, (n, p) in sorted(hits.items()):
        out.append(Finding(
            rule="host-callback-in-step",
            message=f"{n} {prim} call(s) traced into the step",
            op=prim, scope=p or None, count=n))


# -- entry point --------------------------------------------------------------

def lint_jaxpr(fn_or_jaxpr, *args, policy=None, **kwargs) -> List[Finding]:
    """Run the jaxpr rules.

    ``fn_or_jaxpr`` is either a (possibly jitted) callable — traced here
    via ``jax.make_jaxpr(fn)(*args, **kwargs)``, no compile, no dispatch
    — or an already-made (Closed)Jaxpr (then pass no args). ``policy``
    is the :class:`apex_tpu.amp.Policy` the step runs under; the
    fp32-matmul rule only activates for a half-precision policy.
    """
    if hasattr(fn_or_jaxpr, "eqns") or hasattr(fn_or_jaxpr, "jaxpr"):
        jaxpr = fn_or_jaxpr
    else:
        jaxpr = jax.make_jaxpr(fn_or_jaxpr)(*args, **kwargs)
    out: List[Finding] = []
    _rng_reuse(jaxpr, {}, out)
    _f64_creep(jaxpr, out)
    _fp32_matmul(jaxpr, policy, out)
    _callbacks(jaxpr, out)
    return out
