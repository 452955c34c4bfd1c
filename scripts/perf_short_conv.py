"""The delta-rule layers' short convolution alone, timed on the chip: the op
(`apex_short_conv_fwd`, `apex_short_conv_bwd`) forward and backward (the
gradients alone: nothing reads the forward's result, so it is not run) at
the shapes the two decoder cells run it, against the `jax.numpy`
form it replaced there (`short_conv_reference`): Kimi's q (4096 channels,
every head normalised), its v (4096, none) and Qwen's one call (8192 of a
12288-wide projection, q and k normalised), bfloat16 in, 8192 tokens.

Usage: python scripts/perf_short_conv.py [--tokens 8192] [--iters 20]
           [--blocks 512x512,256x512,...] [--xla]

`--blocks` times the op again at each `BLOCK_T x BLOCK_C`; `--xla` also
times the `jax.numpy` form. Each line gives the time and the share of the
HBM roofline of the bytes the call has to move (819 GB/s).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_tpu.ops import short_conv as sc

HEAD = 128
#: name, channels of x, channels convolved, normalised ranges
SHAPES = [
    ("kimi q", 4096, 4096, ((0, 4096, HEAD ** -0.5),)),
    ("kimi v", 4096, 4096, ()),
    ("qwen qkv", 12288, 8192, ((0, 2048, HEAD ** -0.5), (2048, 4096, 1.0))),
]


def measure(fn, args, iters):
    """Milliseconds a call, after one call that compiles."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def cases(op, norm, weight):
    # fresh functions a call: `jax.jit` caches by the function it is given
    loss = lambda x, taps: jnp.sum(op(x, taps, norm, HEAD) * weight)
    return [("forward", lambda x, taps: op(x, taps, norm, HEAD)),
            ("backward", jax.grad(loss, argnums=(0, 1)))]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--blocks", default="")
    ap.add_argument("--xla", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; B 1 T {a.tokens}",
          flush=True)
    blocks = [(sc.BLOCK_T, sc.BLOCK_C)] + [
        tuple(map(int, b.split("x"))) for b in a.blocks.split(",") if b]
    for name, wide, c, norm in SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(keys[0], (1, a.tokens, wide), jnp.bfloat16)
        taps = jax.random.uniform(keys[1], (4, c), minval=-0.5, maxval=0.5)
        weight = jax.random.normal(keys[2], (1, a.tokens, c))
        # x read and y written; x and d y read, d x written
        moved = {"forward": a.tokens * c * (2 + 4),
                 "backward": a.tokens * c * (2 + 4 + 2)}
        def report(tag, op):
            for what, fn in cases(op, norm, weight):
                try:
                    ms = measure(fn, (x, taps), a.iters)
                except Exception as e:      # e.g. blocks past the VMEM
                    print(f"{name}, {tag}, {what}: {str(e)[-300:]!r}",
                          flush=True)
                    continue
                print(f"{name}, {tag}, {what}: {ms:.3f} ms, "
                      f"{moved[what] / 819e9 / ms * 1e5:.1f}% of HBM",
                      flush=True)
        for sc.BLOCK_T, sc.BLOCK_C in blocks:
            report(f"kernels {sc.BLOCK_T}x{sc.BLOCK_C}", sc.short_conv)
        sc.BLOCK_T, sc.BLOCK_C = blocks[0]
        if a.xla:
            report("jax.numpy form", sc.short_conv_reference)


if __name__ == "__main__":
    main()
