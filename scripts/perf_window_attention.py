"""Sliding-window attention alone, timed on the chip, at the window cell's
shape: one sequence of 4096 tokens, 72 q heads on 8 k/v heads of 128,
bfloat16, a window of 512 keys, over several tiles; and the cell's global
attention (48 q heads, causal, the op's own tiles) beside it.

Usage: python scripts/perf_window_attention.py [--tokens 4096] [--iters 20]
           [--tiles 256,512,1024]

Each line gives the time of the forward, and of the forward with the three
gradients, the tiles run of the grid a head group, and TFLOP/s on the
operations the mask keeps (4 a kept (query, key) pair, a channel and a head
forward, 12 with the backward).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_tpu.ops import attention as A


def measure(fn, args, iters):
    """Milliseconds a call, after one call that compiles."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tiles", default="256,512,1024")
    a = ap.parse_args()
    t, d, window = a.tokens, 128, 512
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; T {t}", flush=True)
    key = jax.random.PRNGKey(0)
    runs = [(f"window {window}, 72 heads", 72, window, (tile, tile))
            for tile in map(int, a.tiles.split(","))]
    runs.append(("global, 48 heads", 48, None, ()))
    for name, heads, w, tiles in runs:
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (1, t, heads, d), jnp.bfloat16) * 0.5
        k, v = (jax.random.normal(x, (1, t, 8, d), jnp.bfloat16) * 0.5
                for x in ks[1:3])
        weight = jax.random.normal(ks[3], (1, t, heads, d), jnp.float32)
        pairs = sum(min(i + 1, w or t) for i in range(t))
        op = (lambda tiles, w: lambda q, k, v: A.flash_attention(
            q, k, v, None, d ** -0.5, True, *tiles, window=w))(tiles, w)
        loss = (lambda op: lambda q, k, v: jnp.sum(
            op(q, k, v).astype(jnp.float32) * weight))(op)
        bq, bk = tiles or (A.DEFAULT_BLOCK_Q, A.DEFAULT_BLOCK_K)
        ran, grid = A._causal_tiles(bq, bk, t, t, True, w)
        for what, per_pair, fn in (
                ("forward", 4, op),
                ("forward + backward", 12, jax.grad(loss, argnums=(0, 1, 2)))):
            ms = measure(fn, (q, k, v), a.iters)
            flops = per_pair * pairs * d * heads
            print(f"{name}, tiles {bq}x{bk}, {what}: {ms:.3f} ms, {ran} of "
                  f"{grid} tiles, {flops / ms / 1e9:.1f} TFLOP/s kept",
                  flush=True)


if __name__ == "__main__":
    main()
