"""Pallas kernels of the KV-cache tiering layer (each: kernel + ops + ref)."""

import jax
import jax.numpy as jnp
from jax import lax


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas ``interpret`` flag: an explicit value wins; ``None`` compiles
    the kernel on a TPU backend and interprets it everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def pair_select(n: int, odd: int):
    """(n, 2n) 0/1 f32 matrix whose row j selects lane ``2j + odd`` — the
    int4 pair layout of ``repro.kvcache.quant.pack_int4``. Kernels
    (de)interleave nibbles with it on the MXU instead of strided lane
    slices, which the TPU lowering lacks; exact, since every output sums
    one small integer."""
    row = lax.broadcasted_iota(jnp.int32, (n, 2 * n), 0)
    col = lax.broadcasted_iota(jnp.int32, (n, 2 * n), 1)
    return (col == 2 * row + odd).astype(jnp.float32)
