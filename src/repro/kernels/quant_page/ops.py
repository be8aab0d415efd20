"""jit'd wrapper for the page-quantization migration kernel."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.quant_page.quant_page import quantize_pages


@partial(jax.jit, static_argnames=("tier", "interpret"))
def quant_pages(x, *, tier: int, interpret: bool | None = None):
    return quantize_pages(x, tier=tier, interpret=interpret)
