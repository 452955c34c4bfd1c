"""The gated delta rule alone, timed on the chip: the op forward and forward +
backward and each of its two Pallas kernels, at the size one KDA layer of
`kimi_linear.lm_s8192_b1` runs it (B 1, T 8192, H 32, d_k = d_v 128).
`--xla` also times the `jax.numpy` chunked form the kernels replaced (the op
with `_tiled` off), which is what PR 28's `chiprun_out/B/scan_bench.out`
held.

Usage: python scripts/perf_delta_rule.py [--heads 32] [--tokens 8192]
           [--dim 128] [--iters 10] [--xla] [--check]

`--check` first compares the op's output and gradients at the timed size
against the `jax.numpy` form with every matmul at `highest` (compiled
kernels, not interpreted; the kernels' state products run at the default
precision, so expect bfloat16-sized differences).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_tpu.ops import delta_rule as dr


def make_inputs(b, t, h, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -jax.random.uniform(ks[3], (b, t, h, d), minval=0.01, maxval=0.3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def measure(fn, args, iters):
    """Milliseconds a call, after one call that compiles."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def op_cases(tag):
    # fresh functions a call: `jax.jit` caches by the function it is given
    loss = lambda *a: jnp.sum(dr.gated_delta_rule(*a))
    return [(f"{tag} forward", lambda *a: dr.gated_delta_rule(*a)),
            (f"{tag} forward + backward", jax.grad(loss, argnums=range(5)))]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--check", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; B 1 T {a.tokens} "
          f"H {a.heads} d {a.dim}", flush=True)
    args = make_inputs(1, a.tokens, a.heads, a.dim)
    tiled = dr._tiled

    if a.check:
        loss = lambda *x: jnp.sum(dr.gated_delta_rule(*x) * jnp.cos(
            jnp.arange(a.dim)))
        both = jax.jit(lambda *x: (dr.gated_delta_rule(*x),
                                   jax.grad(loss, argnums=range(5))(*x)))
        out, grads = both(*args)
        dr._tiled = lambda dk, dv: False
        with jax.default_matmul_precision("highest"):
            ref_out, ref_grads = jax.jit(lambda *x: (
                dr.gated_delta_rule(*x),
                jax.grad(loss, argnums=range(5))(*x)))(*args)
        dr._tiled = tiled
        rel = lambda x, y: float(jnp.max(jnp.abs(x - y))
                                 / jnp.max(jnp.abs(y)))
        print(f"check out {rel(out, ref_out):.3e} " + " ".join(
            f"d{n} {rel(x, y):.3e}"
            for n, x, y in zip("q k v g beta".split(), grads, ref_grads)),
            flush=True)

    cases = [(n, f, args) for n, f in op_cases("op")]
    if tiled(a.dim, a.dim):
        # the kernels' own layout: the heads side by side, (B, T, H d)
        flat = (*(x.reshape(1, a.tokens, -1) for x in args[:4]), args[4])
        out, states, inverse = jax.jit(dr._forward_kernel)(*flat)
        cases += [
            ("apex_kda_fwd", dr._forward_kernel, flat),
            ("apex_kda_bwd", dr._backward_kernel,
             (*flat, states, inverse, jnp.cos(out))),
        ]
    for name, fn, xs in cases:
        print(f"{name}: {measure(fn, xs, a.iters):.3f} ms", flush=True)
    if a.xla:
        dr._tiled = lambda dk, dv: False
        for name, fn in op_cases("jax.numpy form"):
            print(f"{name}: {measure(fn, args, a.iters):.3f} ms", flush=True)
        dr._tiled = tiled


if __name__ == "__main__":
    main()
