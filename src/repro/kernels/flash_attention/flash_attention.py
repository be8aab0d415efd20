"""Pallas TPU flash-attention forward kernel (training/prefill hot spot).

Grid: (B*H, Sq/BQ, Sk/BK) with the KV axis innermost (sequential on TPU),
online-softmax state carried in VMEM scratch across KV blocks. Block shapes
default to (128, 128) — MXU-aligned. GQA is handled in the KV index_map
(query head h reads KV head h // group).

VMEM working set per program:
  q (BQ, D) + k (BK, D) + v (BK, D) + acc (BQ, D) f32 + p (BQ, BK) f32
  = 128*128*(2+2+2+4) + 128*128*4 B ~ 0.26 MiB at D=128 — comfortably
  within the ~16 MiB/core budget, leaving headroom for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, bq: int, bk: int, n_k: int, sk_valid: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    k = k_ref[0].astype(jnp.float32)  # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (BQ, BK)

    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < sk_valid  # tail padding
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask &= q_pos >= k_pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    # the MXU accumulates in f32 whatever the operand dtype
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, sk_valid: int | None = None,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool | None = None):
    """q: (BH, Sq, D); k, v: (BH_kv, Sk, D) with BH = B*H, BH_kv = B*Hk and
    the GQA group g = BH // BH_kv applied per batch entry. Sq, Sk must be
    pre-padded to block multiples by the ops wrapper; ``sk_valid`` masking
    is folded into the kernel via the true sk passed in.
    """
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    g = bh // bh_kv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    n_q = sq // bq
    n_k = sk // bk

    kernel = functools.partial(
        _fwd_kernel, scale=d**-0.5, causal=causal, bq=bq, bk=bk, n_k=n_k,
        sk_valid=sk_valid if sk_valid is not None else sk,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, qi, kj: (h, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda h, qi, kj, g_=g: (h // g_, kj, 0)),
            pl.BlockSpec((1, bk, d), lambda h, qi, kj, g_=g: (h // g_, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, qi, kj: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
