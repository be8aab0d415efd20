"""jit'd wrapper: full tiered decode attention over a TieredKV cache.

Runs one Pallas partial per tier (+ a jnp partial over the bf16 write
buffer), then combines flash-decoding style. Also renormalizes the
per-page attention masses that feed the RARO controller.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import modes
from repro.kernels.tiered_attention.tiered_attention import NEG_INF, tiered_decode_partial
from repro.kvcache import paged


def _buffer_partial(q, buf_k, buf_v, n_valid):
    """Partial over the open-page write buffer. q: (B,H,D); buf: (B,P,Hk,D);
    n_valid: (B,) tokens currently in the buffer."""
    b, h, d = q.shape
    _, p, hk, _ = buf_k.shape
    g = h // hk
    qh = (q.astype(jnp.float32) * d**-0.5).reshape(b, hk, g, d)
    s = jnp.einsum("bhgd,bphd->bhgp", qh, buf_k.astype(jnp.float32))
    mask = jnp.arange(p)[None, :] < n_valid[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    pr = jnp.exp(s - m[..., None])
    l = pr.sum(axis=-1)
    acc = jnp.einsum("bhgp,bphd->bhgd", pr, buf_v.astype(jnp.float32))
    return acc.reshape(b, h, d), m.reshape(b, h), l.reshape(b, h)


def combine_partials(parts):
    """parts: list of (acc (B,H,D), m (B,H), l (B,H)) -> (out, M, L)."""
    ms = jnp.stack([m for _, m, _ in parts])  # (T, B, H)
    M = ms.max(0)
    L = jnp.zeros_like(M)
    out = jnp.zeros_like(parts[0][0])
    for acc, m, l in parts:
        w = jnp.exp(m - M)
        L = L + l * w
        out = out + acc * w[..., None]
    return out / jnp.maximum(L, 1e-30)[..., None], M, L


@partial(jax.jit, static_argnames=("cfg", "interpret"))
def tiered_decode_attention(q, cache: paged.TieredKV, cfg: paged.CacheConfig,
                            *, interpret: bool | None = None):
    """q: (B, H, D) -> (out (B,H,D), page_mass (B, MaxP)).

    page_mass[b, j] = attention probability mass on logical page j (mean
    over heads) — the RARO hotness signal.
    """
    b, h, d = q.shape
    parts = []
    page_stats = []

    pools = {
        modes.TIER_BF16: (cache.k16, cache.v16,
                          jnp.ones(cache.sk8.shape[1:][:0] + (cache.k16.shape[0], cfg.n_kv_heads), jnp.float32),
                          jnp.ones((cache.k16.shape[0], cfg.n_kv_heads), jnp.float32)),
        modes.TIER_INT8: (cache.k8, cache.v8, cache.sk8, cache.sv8),
        modes.TIER_INT4: (cache.k4, cache.v4, cache.sk4, cache.sv4),
    }
    for tier, (kp, vp, sk, sv) in pools.items():
        slot_t = jnp.where(cache.tier == tier, cache.slot, -1)
        o, m, l, pp, pm = tiered_decode_partial(q, kp, vp, sk, sv, slot_t,
                                                tier=tier, interpret=interpret)
        parts.append((o, m, l))
        page_stats.append((pp, pm))

    n_buf = cache.seq_len % cfg.page_size
    parts.append(_buffer_partial(q, cache.buf_k, cache.buf_v, n_buf))

    out, M, L = combine_partials(parts)

    # exact per-page mass: pp * exp(pm - M) / L, mean over heads
    mass = jnp.zeros((b, cfg.max_pages), jnp.float32)
    for pp, pm in page_stats:
        w = pp * jnp.exp(pm - M[:, None, :])
        mass = mass + (w / jnp.maximum(L, 1e-30)[:, None, :]).mean(-1) * (pm > NEG_INF / 2).any(-1)
    return out.astype(q.dtype), mass
