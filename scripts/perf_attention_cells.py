"""The decoder cells' causal attention alone, timed on the chip: the op
(`apex_attn_fwd`, `apex_attn_bwd_dq`, `apex_attn_bwd_dkv`) forward, and
forward with the three gradients, at the shapes the decoder cells run it:
the two MLA cells' latent attention (32 heads of 192 with v at 128, 1024 x 256
tiles; "kimi mla d192" with v zero-padded to 192 and o sliced back around the
op, as the model passed it before the kernels took v at its own head size,
"mla d192 dv128" as it passes it now), LFM2's grouped-query attention (two
sequences, 32 heads on 8 of 64) and Qwen3-Next's (16 heads on 2 of 256),
bfloat16, 8192 tokens, causal.

Usage: python scripts/perf_attention_cells.py [--tokens 8192] [--iters 20]
           [--whole-grid] [--fetch-all]

`--whole-grid` times the kernels again as they were before they skipped the
tiles above the causal frontier (every grid step runs and fetches);
`--fetch-all` again with the arithmetic skipped and the index maps left
unclamped, which is what a traced `causal_offset` gets. Each line gives the
time, the tiles run of the grid, and TFLOP/s on the causal count (half of
the full square's matmuls: 2 in the forward, 7 with the backward's, each at
the head size of q and k or of v, whichever it contracts or makes; the padded
row is counted at v's 128 too).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_tpu.models.mla import _ATTN_TILES
from apex_tpu.ops import attention as A

#: name, batch, q heads, k/v heads, q/k head size, v head size, v padded to
#: the q/k head size around the op, tiles (empty: the op's own)
SHAPES = [
    ("kimi mla d192", 1, 32, 32, 192, 128, True, _ATTN_TILES),
    ("mla d192 dv128", 1, 32, 32, 192, 128, False, _ATTN_TILES),
    ("lfm2 gqa d64", 2, 32, 8, 64, 64, False, ()),
    ("qwen gqa d256", 1, 16, 2, 256, 256, False, ()),
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


def cases(d, d_v, padded, tiles, weight):
    """(what, matmuls at the q/k head size and at v's, function)."""
    # fresh functions a call: `jax.jit` caches by the function it is given,
    # and the frontier is consulted while the kernels are traced
    def op(q, k, v):
        if padded:
            v = jnp.pad(v, [(0, 0)] * 3 + [(0, d - d_v)])
        o = A.flash_attention(q, k, v, None, d ** -0.5, True, *tiles)
        return o[..., :d_v]
    loss = lambda q, k, v: jnp.sum(op(q, k, v).astype(jnp.float32) * weight)
    return [("forward", (1, 1), op),
            ("forward + backward", (5, 4),
             jax.grad(loss, argnums=(0, 1, 2)))]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--whole-grid", action="store_true")
    ap.add_argument("--fetch-all", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; T {a.tokens}",
          flush=True)
    frontier, k_block, q_block = (A._frontier, A._Frontier.k_block,
                                  A._Frontier.q_block)
    forms = [("skipped", {})]
    if a.fetch_all:
        forms.append(("skipped, every block fetched", {
            "k_block": lambda self, iq, ik: ik,
            "q_block": lambda self, iq, ik: iq}))
    if a.whole_grid:
        forms.append(("whole grid", {"frontier": lambda *args: None}))
    t = a.tokens
    for name, batch, heads, kv_heads, d, d_v, padded, tiles in SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(keys[0], (batch, t, heads, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (batch, t, kv_heads, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (batch, t, kv_heads, d_v),
                              jnp.bfloat16)
        weight = jax.random.normal(keys[3], (batch, t, heads, d_v))
        bq, bk = tiles or (A.DEFAULT_BLOCK_Q, A.DEFAULT_BLOCK_K)
        run, grid = A._causal_tiles(A._choose_block(bq, t),
                                    A._choose_block(bk, t, lane=True),
                                    t, t, True)
        for tag, patch in forms:
            A._frontier = patch.get("frontier", frontier)
            A._Frontier.k_block = patch.get("k_block", k_block)
            A._Frontier.q_block = patch.get("q_block", q_block)
            ran = grid if "frontier" in patch else run
            for what, (at_d, at_v), fn in cases(d, d_v, padded, tiles,
                                                weight):
                ms = measure(fn, (q, k, v), a.iters)
                # causal: half the square
                flops = (at_d * d + at_v * d_v) * batch * heads * t * t
                print(f"{name}, {tag}, {what}: {ms:.3f} ms, {ran} of {grid} "
                      f"tiles, {flops / ms / 1e9:.1f} TFLOP/s causal",
                      flush=True)
        A._frontier, A._Frontier.k_block, A._Frontier.q_block = (
            frontier, k_block, q_block)


if __name__ == "__main__":
    main()
