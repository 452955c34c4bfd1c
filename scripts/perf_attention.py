"""Flash-attention perf sweep — the `perf_test_multihead_attn.py` mirror.

The reference's headline artifact is its fused-MHA fwd/bwd timing chart
(`apex/contrib/multihead_attn/README.md`,
`perf_test_multihead_attn.py:9-16`: TitanV, 18 layers, hidden 1024,
16 heads). This sweeps the TPU kernels across sequence lengths at
constant token count and prints achieved TFLOP/s for forward and
forward+backward. K scanned steps per dispatch amortize the host
dispatch overhead (device wall ≈ K·step).

Usage: python scripts/perf_attention.py [--tokens 16384] [--causal]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import time

import jax
import jax.numpy as jnp
import numpy as np


def attn_flops(b, s, h, d):
    # QK^T + PV, fwd; bwd ≈ 2.5x fwd (dq,dk,dv recompute scores once)
    return 2.0 * b * h * s * s * d * 2


def measure(fn, args, iters=3, K=20):
    """Mean step time over K scanned steps per dispatch.

    The carry feeds each step's first input (scaled to ~0 so numerics
    are unchanged) and the output collapses to a scalar — a genuine
    loop dependence, so XLA can neither hoist the body out of the loop
    nor stack K full-size outputs (bench.py's scan threads state for
    the same reason)."""
    q0, rest = args[0], args[1:]

    def scanned(q0, rest):
        def body(c, _):
            out = fn(q0 + c, *rest)
            s = sum(jnp.sum(l.astype(jnp.float32))
                    for l in jax.tree_util.tree_leaves(out))
            return (s * 1e-30).astype(q0.dtype), None
        c, _ = jax.lax.scan(body, jnp.zeros((), q0.dtype), None, length=K)
        return c

    jf = jax.jit(scanned)
    out = jf(q0, rest)
    np.asarray(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jf(q0, rest)
    np.asarray(out)
    return (time.perf_counter() - t0) / (iters * K)


def main():
    from apex_tpu.ops import flash_attention
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()

    tokens = 16384
    if "--tokens" in sys.argv:
        tokens = int(sys.argv[sys.argv.index("--tokens") + 1])
    causal = "--causal" in sys.argv
    h, d = 16, 64                       # BERT-Large head geometry
    dtype = jnp.bfloat16

    print(f"| Seq | Batch | fwd ms | fwd TFLOP/s | fwd+bwd ms | "
          f"eff. TFLOP/s |")
    print("|---|---|---|---|---|---|")
    for s in (128, 512, 2048, 8192):
        b = max(1, tokens // s)
        rng = np.random.RandomState(0)
        mk = lambda i: jnp.asarray(
            rng.randn(b, s, h, d).astype(np.float32) * 0.3, dtype)
        q, k, v = mk(0), mk(1), mk(2)

        fwd = lambda q, k, v: flash_attention(q, k, v, causal=causal)
        t_f = measure(fwd, (q, k, v))

        def fwdbwd(q, k, v):
            def loss(q, k, v):
                o = flash_attention(q, k, v, causal=causal)
                return jnp.sum(o.astype(jnp.float32) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        t_fb = measure(fwdbwd, (q, k, v))

        fl = attn_flops(b, s, h, d) * (0.5 if causal else 1.0)
        print(f"| {s} | {b} | {t_f*1e3:.2f} | {fl/t_f/1e12:.1f} | "
              f"{t_fb*1e3:.2f} | {fl*3.5/t_fb/1e12:.1f} |")


if __name__ == "__main__":
    main()
