"""Batched serving driver with the RARO-tiered KV cache.

Serves a small dense LM: prefill a batch of prompts, decode with the
tiered paged cache (Pallas tiered_attention, compiled on a TPU and
interpreted elsewhere),
running the RARO controller between steps. Reports throughput, tier
occupancy / HBM bytes, and output-quality drift vs an all-bf16 cache —
the serving analogue of the paper's IOPS-vs-capacity trade.

  PYTHONPATH=src python -m repro.launch.serve --steps 64
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import modes
from repro.kernels.tiered_attention.ops import tiered_decode_attention
from repro.kvcache import paged, tiers
from repro.models import base, layers as L, registry, transformer as T


def serve_cfg(vocab=512, d_model=128, n_layers=4, n_heads=4, n_kv=2):
    return ModelConfig(arch="serve-demo", family="dense", n_layers=n_layers,
                       d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv,
                       d_ff=256, vocab=vocab, dtype=jnp.float32, remat=False)


def serve_cache_cfg(cfg, steps, batch):
    """The tiered KV cache a ``steps``-token decode of ``batch`` sequences
    runs on: 8-token pages in small bf16 / int8 pools over a large int4 one."""
    return paged.CacheConfig(n_seqs=batch, max_pages=max(steps // 8 + 2, 4),
                             page_size=8, n_kv_heads=cfg.n_kv_heads,
                             head_dim=cfg.head_dim, pool_pages=(8, 16, 256),
                             migrate_per_step=4)


def tiered_decode_step(params, caches, cache_cfg, rcfg, tokens, pos, cfg):
    """decode_step variant whose attention reads the tiered paged cache.
    ``caches`` is a list of (TieredKV) per layer."""
    b = tokens.shape[0]
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    new_caches = []
    layer_params = [jax.tree_util.tree_map(lambda a: a[i], params["layers"])
                    for i in range(cfg.n_layers)]
    for lp, c in zip(layer_params, caches):
        xn = T.norm(cfg, lp["ln1"], x)
        q, k, v = T.qkv(lp["attn"], xn, cfg, pos[:, None])
        ct = tiers.commit_tier(c, cache_cfg, rcfg)
        c = paged.append(c, cache_cfg, k[:, 0], v[:, 0], ct)
        o, mass = tiered_decode_attention(q[:, 0], c, cache_cfg)
        c, _ = tiers.raro_step(c, cache_cfg, rcfg, mass)
        h = x + o[:, None].reshape(b, 1, -1).astype(cfg.dtype) @ lp["attn"]["wo"]
        x = h + L.mlp(lp["mlp"], T.norm(cfg, lp["ln2"], h), cfg.act)
        new_caches.append(c)
    x = T.norm(cfg, params["ln_f"], x)
    return L.lm_logits(params["embed"], x, cfg.vocab), new_caches


def run(steps=64, batch=4, raro_enabled=True, seed=0, cfg=None, params=None,
        quiet=False):
    cfg = cfg or serve_cfg()
    api = registry.get_api(cfg)
    if params is None:
        params = base.materialize(api.specs(), jax.random.PRNGKey(seed), jnp.float32)

    hk, dh = cfg.n_kv_heads, cfg.head_dim
    ccfg = serve_cache_cfg(cfg, steps, batch)
    rcfg = tiers.RAROConfig(enabled=raro_enabled)
    caches = [paged.init(ccfg, jnp.float32) for _ in range(cfg.n_layers)]

    # reference: exact bf16 cache decode for quality comparison
    ref_cache = {k: jnp.zeros((cfg.n_layers, batch, steps + 1, hk, dh), jnp.float32)
                 for k in ("k", "v")}

    tok = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, 1), 0, cfg.vocab)
    ref_tok = tok
    drift = []
    t0 = time.time()
    for t in range(steps):
        pos = jnp.full((batch,), t, jnp.int32)
        logits, caches = tiered_decode_step(params, caches, ccfg, rcfg, tok, pos, cfg)
        ref_logits, ref_cache = T.decode_step(params, ref_cache, ref_tok, pos, cfg)
        d = jnp.mean(jnp.abs(jax.nn.softmax(logits[:, -1].astype(jnp.float32))
                             - jax.nn.softmax(ref_logits[:, -1].astype(jnp.float32))))
        drift.append(float(d))
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        ref_tok = jnp.argmax(ref_logits[:, -1], -1).astype(jnp.int32)[:, None]
    dt = time.time() - t0

    occ = [paged.pool_occupancy(c) for c in caches]
    mem = sum(paged.memory_bytes(c, ccfg) for c in caches)
    mem_bf16 = sum(
        int((~f).sum()) * 2 * ccfg.page_size * hk * dh * 2
        for c in caches for f in [c.free[0] | ~c.free[0]]  # all pages at bf16
    ) or 1
    committed = sum(int((np.asarray(c.tier) >= 0).sum()) for c in caches)
    bf16_equiv = committed * 2 * ccfg.page_size * hk * dh * 2
    tier_hist = np.zeros(3, int)
    for c in caches:
        tt = np.asarray(c.tier)
        for i in range(3):
            tier_hist[i] += (tt == i).sum()
    out = {
        "tok_per_s": batch * steps / dt,
        "mean_prob_drift": float(np.mean(drift)),
        "final_prob_drift": float(drift[-1]),
        "kv_bytes": mem,
        "kv_bytes_bf16_equiv": bf16_equiv,
        "capacity_saving": 1.0 - mem / max(bf16_equiv, 1),
        "tier_pages": tier_hist.tolist(),
    }
    if not quiet:
        for k, v in out.items():
            print(f"  {k}: {v}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    a = ap.parse_args()
    print("== RARO tiered KV serving ==")
    run(steps=a.steps, batch=a.batch, raro_enabled=True)
    print("== static int4-only baseline (QLC analogue) ==")
    run(steps=a.steps, batch=a.batch, raro_enabled=False)


if __name__ == "__main__":
    main()
