"""Pallas page-(re)quantization kernel — the RARO migration hot path.

Grid over pages; each program loads one bf16 page from the source view,
computes per-head symmetric scales, emits the quantized page (int8, or int4
packed 2-per-byte) + scales + the relative RMS error of the page (the
controller's RBER-analogue measurement, so migration cost and error
tracking come from the same pass).

The kernel sees a page as the 2-D (P, Hk*D) view, heads side by side on
the lanes, as the TPU lowering wants; per-head reductions are lane masks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import modes
from repro.kernels import pair_select, resolve_interpret

_QMAX = {modes.TIER_INT8: 127.0, modes.TIER_INT4: 7.0}


def _total(x):
    """Sum of a 2-D value, kept (1, 1) so it stays a vector."""
    return x.sum(axis=1, keepdims=True).sum(axis=0, keepdims=True)


def _quant_kernel(x_ref, q_ref, s_ref, e_ref, *, tier: int, hk: int, d: int):
    x = x_ref[0].astype(jnp.float32)  # (P, Hk*D)
    qmax = _QMAX[tier]
    lane_head = lax.broadcasted_iota(jnp.int32, (1, hk * d), 1) // d
    head_id = lax.broadcasted_iota(jnp.int32, (1, hk), 1)
    col_amax = jnp.max(jnp.abs(x), axis=0, keepdims=True)  # (1, Hk*D)
    lane_scale = jnp.zeros((1, hk * d), jnp.float32)
    scale = jnp.zeros((1, hk), jnp.float32)
    for i in range(hk):
        amax = jnp.max(jnp.where(lane_head == i, col_amax, 0.0), axis=1,
                       keepdims=True)  # (1, 1)
        s_i = jnp.maximum(amax, 1e-8) / qmax
        lane_scale = jnp.where(lane_head == i, s_i, lane_scale)
        scale = jnp.where(head_id == i, s_i, scale)
    q = jnp.clip(jnp.round(x / lane_scale), -qmax, qmax)
    n_el = x.shape[0] * x.shape[1]
    err_num = jnp.sqrt(_total((x - q * lane_scale) ** 2) / n_el)
    err_den = jnp.sqrt(_total(x * x) / n_el) + 1e-8
    if tier == modes.TIER_INT8:
        q_ref[0] = q.astype(jnp.int32).astype(jnp.int8)
    else:
        n = hk * d // 2
        nt = (((1,), (1,)), ((), ()))
        lo = lax.dot_general(q, pair_select(n, 0), nt,
                             preferred_element_type=jnp.float32)
        hi = lax.dot_general(q, pair_select(n, 1), nt,
                             preferred_element_type=jnp.float32)
        byte = ((lo.astype(jnp.int32) & 0x0F)
                | ((hi.astype(jnp.int32) & 0x0F) << 4))
        q_ref[0] = jnp.where(byte >= 128, byte - 256, byte).astype(jnp.int8)
    s_ref[0] = scale
    e_ref[0] = err_num / err_den


def quantize_pages(x, *, tier: int, interpret: bool | None = None):
    """x: (N, P, Hk, D) bf16/f32 pages -> (q, scales (N, Hk), err (N,)).

    q is (N, P, Hk, D) int8 for tier=int8 or (N, P, Hk, D//2) packed for
    tier=int4. Scales and errors are written with a unit middle axis
    ((N, 1, Hk), (N, 1, 1)) so each block's last two dims equal the
    array's, as the TPU lowering requires; the result drops it again.
    """
    n, p, hk, d = x.shape
    dq = d if tier == modes.TIER_INT8 else d // 2
    kernel = functools.partial(_quant_kernel, tier=tier, hk=hk, d=d)
    q, s, e = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, p, hk * d), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, p, hk * dq), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, hk), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, p, hk * dq), jnp.int8),
            jax.ShapeDtypeStruct((n, 1, hk), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x.reshape(n, p, hk * d))
    return q.reshape(n, p, hk, dq), s[:, 0], e[:, 0, 0]
