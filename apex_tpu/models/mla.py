"""Multi-head latent attention (MLA), the mixer of ``models/kimi_linear.py``
and ``models/deepseek_v3.py``.

The keys and values come out of one compressed vector a token: ``c = x
W_kva``, whose first ``kv_rank`` channels are normalised and expanded by
``W_kvb`` into each head's ``k_nope`` and ``v``, and whose last ``rope_dim``
channels are ``k_pe``, one for all heads. The query is ``x W_q`` (no
compression). ``rope_theta`` None: no positions, ``k_pe`` and the query's
last ``rope_dim`` channels go in as they are (Kimi-Linear's NoPE form).
Otherwise both are turned by position in interleaved pairs
(:func:`~apex_tpu.models.decoder.interleaved_rotary`), DeepSeek-V3's form.

Runs under ``jax.named_scope`` ``mla/{proj,rope,attn,out}`` (``rope`` only
where there are positions).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import ops
from apex_tpu.models.decoder import RMSNorm, _dense, interleaved_rotary

#: (block_q, block_k) of MLA's attention. The kernels' VMEM ledger was
#: fitted at a head size of 64: at q/k's 192 (v then padded to 192) the
#: v5e's compiler refused their default 1024 x 1024 (17.7 MiB of the 16 MiB
#: scoped VMEM in the forward) and 512 x 512 (19.6 MiB in the dk/dv
#: backward), and took this, which v at its own 128 keeps
_ATTN_TILES = (1024, 256)


class LatentAttention(nn.Module):
    """Causal multi-head latent attention, softmax scale ``(nope_dim +
    rope_dim)^-1/2``; ``v`` goes to the attention op at its own head size
    ``v_dim``, below q's and k's, and ``o`` comes back at it."""
    hidden: int
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    eps: float = 1e-5
    rope_theta: Optional[float] = None

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, dq = self.heads, self.nope_dim + self.rope_dim
        with jax.named_scope("mla/proj"):
            q = _dense(h * dq, "q_proj")(x).reshape(b, t, h, dq)
            c = _dense(self.kv_rank + self.rope_dim, "kv_a")(x)
            c_kv = RMSNorm(self.eps, name="kv_norm")(c[..., :self.kv_rank])
            kv = _dense(h * (self.nope_dim + self.v_dim), "kv_b")(c_kv)
            kv = kv.reshape(b, t, h, self.nope_dim + self.v_dim)
            k_pe = c[:, :, None, self.kv_rank:]
        if self.rope_theta is not None:
            with jax.named_scope("mla/rope"):
                # one k_pe head, turned before it is shared by the heads
                q = jnp.concatenate([q[..., :self.nope_dim], interleaved_rotary(
                    q[..., self.nope_dim:], self.rope_theta).astype(q.dtype)],
                    -1)
                k_pe = interleaved_rotary(k_pe, self.rope_theta)
        with jax.named_scope("mla/proj"):
            k_pe = jnp.broadcast_to(k_pe.astype(kv.dtype),
                                    (b, t, h, self.rope_dim))
            k = jnp.concatenate([kv[..., :self.nope_dim], k_pe], -1)
            v = kv[..., self.nope_dim:]
        with jax.named_scope("mla/attn"):
            o = ops.flash_attention(q, k, v, None, dq ** -0.5, True,
                                    *_ATTN_TILES)
            o = o.reshape(b, t, h * self.v_dim)
        with jax.named_scope("mla/out"):
            return _dense(self.hidden, "o_proj")(o)
