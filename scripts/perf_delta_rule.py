"""The gated delta rule alone, timed on the chip: the op forward and forward +
backward and each of its two Pallas kernels, at the size one KDA layer of
`kimi_linear.lm_s8192_b1` runs it (B 1, T 8192, H 32, d_k = d_v 128).
`--xla` also times the `jax.numpy` chunked form the kernels replaced (the op
with `_tiled` off), which is what PR 28's `chiprun_out/B/scan_bench.out`
held.

`--decay head` takes one decay a head and half as many key heads as
`--heads`, one a grid step's two value heads, as one gated-DeltaNet layer of
`qwen3_next.lm_s8192_b1_v19k` runs it (16 : 32): the op, `apex_gdn_fwd` and
`apex_gdn_bwd` against the broadcast form, the per-channel op and kernels fed
the decay broadcast over the key channels and the key heads repeated. Other
groupings go through `gated_delta_rule`, which repeats their key heads.

Usage: python scripts/perf_delta_rule.py [--heads 32] [--tokens 8192]
           [--dim 128] [--iters 10] [--xla] [--check]
           [--decay channel|head]

`--check` first compares the op's output and gradients at the timed size
against the `jax.numpy` form with every matmul at `highest` (compiled
kernels, not interpreted; the kernels' state products run at the default
precision, so expect bfloat16-sized differences); with `--decay head` also
against the broadcast form's kernels.
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


def make_inputs(b, t, h, d, seed=0, key_heads=None, one_a_head=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    hk = key_heads or h
    q = unit(jax.random.normal(ks[0], (b, t, hk, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, hk, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -jax.random.uniform(ks[3], (b, t, h) if one_a_head else (b, t, h, d),
                            minval=0.01, maxval=0.3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def broadcast(q, k, v, g, beta):
    """One decay a head and shared key heads as the per-channel form takes
    them: ``g`` over the key channels, ``q`` and ``k`` repeated a group."""
    group = v.shape[2] // q.shape[2]
    return (jnp.repeat(q, group, 2), jnp.repeat(k, group, 2), v,
            jnp.broadcast_to(g[..., None], v.shape[:3] + q.shape[-1:]), beta)


def measure(fn, args, iters):
    """Milliseconds a call, after one call that compiles."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def op_cases(tag, prepare=lambda *a: a):
    # fresh functions a call: `jax.jit` caches by the function it is given
    op = lambda *a: dr.gated_delta_rule(*prepare(*a))
    loss = lambda *a: jnp.sum(op(*a))
    return [(f"{tag} forward", op),
            (f"{tag} forward + backward", jax.grad(loss, argnums=range(5)))]


def kernel_cases(fwd_name, bwd_name, forward, backward, flat):
    """The two kernels alone, the backward fed the forward's outputs."""
    out, states, inverse = jax.jit(forward)(*flat)
    return [(fwd_name, forward, flat),
            (bwd_name, backward, (*flat, states, inverse, jnp.cos(out)))]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--xla", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--decay", choices=("channel", "head"), default="channel")
    a = ap.parse_args()
    head = a.decay == "head"
    if head and a.heads % dr.HEADS_A_STEP:
        ap.error("--decay head shares a key head between a grid step's "
                 f"{dr.HEADS_A_STEP} value heads: --heads must be even")
    hk = a.heads // dr.HEADS_A_STEP if head else a.heads
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; B 1 T {a.tokens} "
          f"H {a.heads} H_k {hk} d {a.dim} decay a {a.decay}", flush=True)
    args = make_inputs(1, a.tokens, a.heads, a.dim, key_heads=hk,
                       one_a_head=head)
    tiled = dr._tiled
    rel = lambda x, y: float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))

    if a.check:
        def both(prepare=lambda *x: x):
            op = lambda *x: dr.gated_delta_rule(*prepare(*x))
            loss = lambda *x: jnp.sum(op(*x) * jnp.cos(jnp.arange(a.dim)))
            return jax.jit(lambda *x: (op(*x), jax.grad(
                loss, argnums=range(5))(*x)))
        out, grads = both()(*args)
        dr._tiled = lambda dk, dv: False
        with jax.default_matmul_precision("highest"):
            ref_out, ref_grads = both()(*args)
        dr._tiled = tiled
        print(f"check out {rel(out, ref_out):.3e} " + " ".join(
            f"d{n} {rel(x, y):.3e}"
            for n, x, y in zip("q k v g beta".split(), grads, ref_grads)),
            flush=True)
        if head:
            b_out, b_grads = both(broadcast)(*args)
            print(f"against the broadcast form out {rel(out, b_out):.3e} "
                  + " ".join(f"d{n} {rel(x, y):.3e}" for n, x, y in zip(
                      "q k v g beta".split(), grads, b_grads)), flush=True)

    flat = lambda xs: (*(x.reshape(1, a.tokens, -1) for x in xs[:4]), xs[4])
    cases = [(n, f, args) for n, f in op_cases("op")]
    if head:
        cases += [(n, f, args) for n, f in op_cases("broadcast form",
                                                     broadcast)]
    if tiled(a.dim, a.dim):
        # the kernels' own layout: the heads side by side, (B, T, H d)
        if head:
            group = dr.HEADS_A_STEP
            cases += kernel_cases(
                "apex_gdn_fwd", "apex_gdn_bwd",
                lambda *x: dr._gdn_forward_kernel(*x, group),
                lambda *x: dr._gdn_backward_kernel(*x, group), flat(args))
            wide = flat(jax.jit(broadcast)(*args))
        else:
            wide = flat(args)
        cases += kernel_cases("apex_kda_fwd", "apex_kda_bwd",
                              dr._forward_kernel, dr._backward_kernel, wide)
    for name, fn, xs in cases:
        print(f"{name}: {measure(fn, xs, a.iters):.3f} ms", flush=True)
    if a.xla:
        dr._tiled = lambda dk, dv: False
        for name, fn in op_cases("jax.numpy form"):
            print(f"{name}: {measure(fn, args, a.iters):.3f} ms", flush=True)
        dr._tiled = tiled


if __name__ == "__main__":
    main()
