"""Pallas TPU tiered paged-decode attention kernel.

One kernel instance per RARO tier (the dtype/dequant path is static per
pool — the flash analogue of "all pages in a block share a mode"). For one
decode token per sequence:

  grid = (B, MaxPages); page slots come from the page table via SCALAR
  PREFETCH (pltpu.PrefetchScalarGridSpec) so the DMA of the right page is
  issued ahead of compute — the canonical TPU paged-attention pattern.

Outputs are flash-decoding partials (m, l, acc) per sequence — combined
across tiers + the bf16 write buffer by ops.combine_partials — plus the
per-page attention mass (sum of unnormalized exp scores, normalized by the
combiner), which is EXACTLY the hotness signal the RARO controller
consumes. The hotness statistics therefore cost zero extra passes.

Every value in the kernel is 2-D, as the TPU lowering wants: a page is
viewed as (P, Hk*D') with the heads side by side on the lanes, and each
query head is widened to (Hk*D) lanes, zero outside its own KV head's
span, so one matmul scores (and one accumulates) every head of the page.

VMEM per program: one page (P, Hk*D') + q (H, Hk*D) + acc (H, Hk*D) f32 —
P=64, Hk<=16, D=128 int4-packed = 64*16*64 B = 64 KiB for the page; tiny.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import modes
from repro.kernels import pair_select, resolve_interpret

NEG_INF = -1e30
# full-f32 MXU passes for the attention matmuls (the oracle is f32)
_F32 = lax.Precision.HIGHEST


def _dequant_page(kp, scale, tier: int):
    """kp: (P, Hk*D') page as stored; scale: (1, Hk*D) f32 per-lane scales
    -> (P, Hk*D) f32."""
    if tier == modes.TIER_BF16:
        return kp.astype(jnp.float32)
    if tier == modes.TIER_INT8:
        return kp.astype(jnp.float32) * scale
    # packed int4: byte j holds the codes of lanes 2j (low nibble, sign-
    # extended) and 2j+1 (high nibble; the arithmetic shift sign-extends)
    kp = kp.astype(jnp.int32)
    lo = (((kp & 0x0F) ^ 0x08) - 0x08).astype(jnp.float32)
    hi = (kp >> 4).astype(jnp.float32)
    n = kp.shape[-1]
    q = (jnp.dot(lo, pair_select(n, 0), preferred_element_type=jnp.float32)
         + jnp.dot(hi, pair_select(n, 1), preferred_element_type=jnp.float32))
    return q * scale


def _decode_kernel(tbl_ref, q_ref, kp_ref, vp_ref, sk_ref, sv_ref,
                   o_ref, m_ref, l_ref, pp_ref, pm_ref, acc_ref, mscr_ref, lscr_ref,
                   *, tier: int, n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mscr_ref[...] = jnp.full_like(mscr_ref, NEG_INF)
        lscr_ref[...] = jnp.zeros_like(lscr_ref)

    valid = tbl_ref[b, j] >= 0

    @pl.when(valid)
    def _page():
        q = q_ref[0]  # (H, Hk*D), pre-scaled and widened
        k = _dequant_page(kp_ref[0], sk_ref[0], tier)  # (P, Hk*D)
        v = _dequant_page(vp_ref[0], sv_ref[0], tier)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=_F32,
                            preferred_element_type=jnp.float32)  # (H, P)
        m_prev = mscr_ref[...]  # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        lscr_ref[...] = lscr_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        # (H, Hk*D): lanes outside a head's own KV span hold other heads'
        # values; the wrapper keeps only the own span
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, precision=_F32, preferred_element_type=jnp.float32)
        mscr_ref[...] = m_new
        # per-(page, head) exp-sum + the max it was computed against; the
        # combiner renormalizes exactly with the final (m, l).
        pp_ref[0, 0] = p.sum(axis=-1, keepdims=True)
        pm_ref[0, 0] = m_new

    @pl.when(~valid)
    def _skip():
        pp_ref[0, 0] = jnp.zeros_like(pp_ref[0, 0])
        pm_ref[0, 0] = jnp.full_like(pm_ref[0, 0], NEG_INF)

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0] = acc_ref[...]
        m_ref[0] = mscr_ref[...]
        l_ref[0] = lscr_ref[...]


def tiered_decode_partial(q, k_pool, v_pool, sk, sv, slot_table, *, tier: int,
                          interpret: bool | None = None):
    """Per-tier flash-decoding partials.

    q: (B, H, D) one token per sequence.
    k_pool/v_pool: (N, P, Hk, D') pages (D' = D, or D//2 when tier=int4).
    sk/sv: (N, Hk) f32 scales (ignored for bf16; pass ones).
    slot_table: (B, MaxP) int32 pool slots for THIS tier, -1 = not-this-tier.

    Returns (o (B,H,D) f32 unnormalized acc, m (B,H), l (B,H),
             page_p (B,MaxP,H) per-page exp-sums, page_m (B,MaxP,H) the max
             each was computed against) — combine with ops.combine_partials.
    """
    b, h, d = q.shape
    n, page, hk, dp = k_pool.shape
    g = h // hk
    mp = slot_table.shape[1]
    w = hk * d

    # query head i reads KV head i // g: zero it outside that head's lanes
    own = (jnp.arange(h) // g)[:, None] == jnp.arange(hk)[None, :]  # (H, Hk)
    q_wide = ((q.astype(jnp.float32) * d**-0.5)[:, :, None, :]
              * own[None, :, :, None]).reshape(b, h, w)
    # per-lane scales: a (1, Hk*D) row per page, so every block's last two
    # dims equal the array's, as the TPU lowering requires
    sk_wide = jnp.repeat(sk, d, axis=1)[:, None, :]
    sv_wide = jnp.repeat(sv, d, axis=1)[:, None, :]

    def page_map(b, j, t):
        return (jnp.maximum(t[b, j], 0), 0, 0)

    kernel = functools.partial(_decode_kernel, tier=tier, n_pages=mp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda b, j, t: (b, 0, 0)),
            pl.BlockSpec((1, page, hk * dp), page_map),
            pl.BlockSpec((1, page, hk * dp), page_map),
            pl.BlockSpec((1, 1, w), page_map),
            pl.BlockSpec((1, 1, w), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, h, w), lambda b, j, t: (b, 0, 0)),
            pl.BlockSpec((1, h, 1), lambda b, j, t: (b, 0, 0)),
            pl.BlockSpec((1, h, 1), lambda b, j, t: (b, 0, 0)),
            pl.BlockSpec((1, 1, h, 1), lambda b, j, t: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, h, 1), lambda b, j, t: (b, j, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, w), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((b, h, w), jnp.float32),
        jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, mp, h, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, mp, h, 1), jnp.float32),
    ]
    acc, m, l, pp, pm = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(slot_table, q_wide, k_pool.reshape(n, page, hk * dp),
      v_pool.reshape(n, page, hk * dp), sk_wide, sv_wide)
    o = (acc.reshape(b, h, hk, d) * own[None, :, :, None]).sum(axis=2)
    return o, m[..., 0], l[..., 0], pp[..., 0], pm[..., 0]
