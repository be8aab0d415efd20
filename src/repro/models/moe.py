"""Mixture-of-Experts transformers: granite-moe (top-8 of 40, GQA) and
deepseek-v3 (MLA + 1 shared + 256 routed top-8 + MTP).

Dispatch is sort-based with capacity (MegaBlocks-style dense buffers):
tokens are argsorted by expert, placed into an (E, C, D) buffer (capacity
drop), run through vmapped expert FFNs as grouped GEMMs, and combined by
router weight. With experts sharded over "model" this lowers to the
canonical all-to-all dispatch pattern.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mla as mla_mod
from repro.models import transformer as T
from repro.models.base import ParamSpec


def moe_specs(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    s = {
        "router": ParamSpec((d, e), ("embed", None), "scaled", jnp.float32),
        "w_in": ParamSpec((e, d, f), ("experts", "embed", "moe_ff"), "scaled"),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "moe_ff"), "scaled"),
        "w_out": ParamSpec((e, f, d), ("experts", "moe_ff", "embed"), "scaled"),
    }
    if cfg.n_shared_experts:
        s["shared"] = L.mlp_specs(d, cfg.moe_d_ff * cfg.n_shared_experts)
    return s


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor) + 1
    return -(-c // 8) * 8  # pad for tiling


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map + all_to_all (§Perf iteration 3b).
#
# The jit-level scatter dispatch below lowers through GSPMD to a replicated
# (T*K, D) scatter + all-reduce — measured at 240 GB/chip/layer on
# deepseek-v3 train_4k. The shard_map version routes each token exactly
# once: tokens are split over the model axis, every chip quantizes its own
# routing, packs a fixed-capacity (tp, cap_send, D) send buffer, and a
# single all_to_all over "model" delivers tokens to their expert shard
# (wire = cap_send * D * 2B per chip instead of the full token matrix).
# ---------------------------------------------------------------------------
def moe_apply_ep(p, x, cfg: ModelConfig, mesh):
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["model"]
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    e_loc = cfg.n_experts // tp

    def local(p_loc, x_loc):
        # x_loc: (b_loc, s_loc, D); p_loc experts: (e_loc, D, F)
        b, s, d = x_loc.shape
        n = b * s
        k = cfg.top_k
        xf = x_loc.reshape(n, d)
        logits = (xf.astype(jnp.float32) @ p_loc["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(probs, k)  # (n, k) global expert ids
        w = w / w.sum(-1, keepdims=True)

        me = jax.lax.pmean(probs.mean(0), mesh.axis_names)
        ce = jnp.zeros((cfg.n_experts,), jnp.float32).at[idx.reshape(-1)].add(
            w.reshape(-1)) / n
        ce = jax.lax.pmean(ce, mesh.axis_names)
        aux = cfg.aux_loss_coef * cfg.n_experts * jnp.sum(me * ce)

        # pack send buffer: one row group per destination expert shard
        cap_send = -(-int(n * k * cfg.capacity_factor) // tp)
        cap_send = -(-cap_send // 8) * 8
        dest = idx.reshape(-1) // e_loc  # (n*k,) destination shard
        order = jnp.argsort(dest)
        sorted_dest = dest[order]
        seg = jnp.searchsorted(sorted_dest, jnp.arange(tp))
        pos = jnp.arange(n * k) - seg[sorted_dest]
        keep = pos < cap_send
        slot = jnp.where(keep, sorted_dest * cap_send + pos, tp * cap_send)
        tok = order // k
        send = jnp.zeros((tp * cap_send, d), x_loc.dtype).at[slot].set(
            xf[tok], mode="drop")
        send_eid = jnp.full((tp * cap_send,), -1, jnp.int32).at[slot].set(
            idx.reshape(-1)[order] % e_loc, mode="drop")

        recv = lax.all_to_all(send.reshape(tp, cap_send, d), "model", 0, 0,
                              tiled=False)
        recv_eid = lax.all_to_all(send_eid.reshape(tp, cap_send), "model", 0, 0,
                                  tiled=False)
        rx = recv.reshape(tp * cap_send, d)
        re = recv_eid.reshape(tp * cap_send)

        # local grouped GEMMs over my e_loc experts, capacity per expert
        cap_e = -(-tp * cap_send // e_loc)
        cap_e = -(-cap_e // 8) * 8
        order2 = jnp.argsort(jnp.where(re >= 0, re, e_loc))
        se = jnp.where(re[order2] >= 0, re[order2], e_loc)
        seg2 = jnp.searchsorted(se, jnp.arange(e_loc))
        pos2 = jnp.arange(tp * cap_send) - seg2[jnp.minimum(se, e_loc - 1)]
        keep2 = (se < e_loc) & (pos2 < cap_e)
        slot2 = jnp.where(keep2, se * cap_e + pos2, e_loc * cap_e)
        buf = jnp.zeros((e_loc * cap_e, d), x_loc.dtype).at[slot2].set(
            rx[order2], mode="drop")
        hb = buf.reshape(e_loc, cap_e, d)
        h = jnp.einsum("ecd,edf->ecf", hb, p_loc["w_in"])
        g = jnp.einsum("ecd,edf->ecf", hb, p_loc["w_gate"])
        h = (h * jax.nn.silu(g)).astype(x_loc.dtype)
        yb = jnp.einsum("ecf,efd->ecd", h, p_loc["w_out"]).reshape(e_loc * cap_e, d)

        # un-sort back to recv order, return through all_to_all
        out_rx = jnp.zeros((tp * cap_send, d), jnp.float32)
        out_rx = out_rx.at[jnp.where(keep2, order2, tp * cap_send)].set(
            yb[jnp.minimum(slot2, e_loc * cap_e - 1)].astype(jnp.float32),
            mode="drop")
        back = lax.all_to_all(out_rx.reshape(tp, cap_send, d), "model", 0, 0,
                              tiled=False).reshape(tp * cap_send, d)

        # combine: weight each assignment and scatter-add to its token
        per_assign = back[jnp.minimum(slot, tp * cap_send - 1)]
        per_assign = jnp.where(keep[:, None], per_assign, 0)
        w_sorted = w.reshape(-1)[order]
        y = jnp.zeros((n, d), jnp.float32).at[
            jnp.where(keep, tok, n)].add(per_assign * w_sorted[:, None], mode="drop")

        if cfg.n_shared_experts:
            y = y + L.mlp(p_loc["shared"], xf, cfg.act).astype(jnp.float32)
        return y.reshape(b, s, d).astype(x_loc.dtype), aux

    pspec_params = {
        "router": P(None, None),
        "w_in": P("model", None, None),
        "w_gate": P("model", None, None),
        "w_out": P("model", None, None),
    }
    if cfg.n_shared_experts:
        pspec_params["shared"] = jax.tree_util.tree_map(
            lambda _: P(None, None), p["shared"])
    x_spec = P(dp_axes, "model", None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(pspec_params, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)
    return fn(p, x)


def moe_apply(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (out, aux_loss)."""
    b, s, d = x.shape
    n = b * s
    k = cfg.top_k
    e = cfg.n_experts
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
    w, idx = lax.top_k(probs, k)  # (N, K)
    w = w / w.sum(-1, keepdims=True)

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(w.reshape(-1)) / n
    aux = cfg.aux_loss_coef * e * jnp.sum(me * ce)

    # ---- sort-based dispatch with capacity ----
    cap = capacity(cfg, n)
    flat_e = idx.reshape(-1)  # (N*K,)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos = jnp.arange(n * k) - seg_start[sorted_e]
    keep = pos < cap
    slot = jnp.where(keep, sorted_e * cap + pos, e * cap)  # OOB -> dropped
    tok = order // k

    buf = jnp.zeros((e * cap, d), x.dtype).at[slot].set(xf[tok], mode="drop")
    hb = buf.reshape(e, cap, d)
    h = jnp.einsum("ecd,edf->ecf", hb, p["w_in"])
    g = jnp.einsum("ecd,edf->ecf", hb, p["w_gate"])
    h = (h * jax.nn.silu(g)).astype(x.dtype)
    yb = jnp.einsum("ecf,efd->ecd", h, p["w_out"]).reshape(e * cap, d)

    # ---- combine ----
    per_assign = jnp.where(keep[:, None], yb[jnp.minimum(slot, e * cap - 1)], 0)
    w_sorted = w.reshape(-1)[order]
    y = jnp.zeros((n, d), jnp.float32).at[tok].add(
        per_assign.astype(jnp.float32) * w_sorted[:, None]
    )

    if cfg.n_shared_experts:
        y = y + L.mlp(p["shared"], xf, cfg.act).astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Full MoE decoder model (granite / deepseek-v3)
# ---------------------------------------------------------------------------
def _ambient_mesh():
    """Mesh for shard_map EP dispatch, if we are under jax.set_mesh with a
    real model axis; None -> fall back to the jit-level dispatch."""
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is not None and "model" in m.axis_names and m.shape["model"] > 1:
            return m
    except Exception:
        pass
    return None


def _moe_ffn(cfg: ModelConfig, p, xn):
    """Dispatch selector: shard_map EP when enabled + applicable."""
    if cfg.moe_hints:
        mesh = _ambient_mesh()
        if (mesh is not None and cfg.n_experts % mesh.shape["model"] == 0
                and xn.shape[1] % mesh.shape["model"] == 0):
            return moe_apply_ep(p, xn, cfg, mesh)
    return moe_apply(p, xn, cfg)


def _attn_specs(cfg: ModelConfig):
    return mla_mod.mla_specs(cfg) if cfg.mla else T.attn_specs(cfg)


def _attn_apply(cfg, p, xn, positions):
    if cfg.mla:
        return mla_mod.mla_attention(p, xn, cfg, positions)
    b, s, _ = xn.shape
    q, k, v = T.qkv(p, xn, cfg, positions)
    o = attn.blockwise_attention(q, k, v, causal=True, window=cfg.window)
    return o.reshape(b, s, -1) @ p["wo"]


def moe_layer_specs(cfg: ModelConfig) -> dict:
    return {
        "ln1": T.norm_specs(cfg),
        "attn": _attn_specs(cfg),
        "ln2": T.norm_specs(cfg),
        "moe": moe_specs(cfg),
    }


def dense_layer_specs(cfg: ModelConfig) -> dict:
    return {
        "ln1": T.norm_specs(cfg),
        "attn": _attn_specs(cfg),
        "ln2": T.norm_specs(cfg),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, gated=True),
    }


def specs(cfg: ModelConfig) -> dict:
    s = {
        "embed": L.embedding_specs(cfg.vocab, cfg.d_model),
        "moe_layers": T.stack_specs(cfg.n_layers - cfg.first_k_dense, moe_layer_specs(cfg)),
        "ln_f": T.norm_specs(cfg),
    }
    if cfg.first_k_dense:
        s["dense_layers"] = T.stack_specs(cfg.first_k_dense, dense_layer_specs(cfg))
    if cfg.mtp_depth:
        s["mtp"] = {
            "proj": ParamSpec((2 * cfg.d_model, cfg.d_model), ("embed", "embed"), "scaled"),
            "block": dense_layer_specs(cfg),
            "ln": T.norm_specs(cfg),
        }
    return s


def _dense_layer(cfg, lp, x, positions):
    h = x + _attn_apply(cfg, lp["attn"], T.norm(cfg, lp["ln1"], x), positions)
    return h + L.mlp(lp["mlp"], T.norm(cfg, lp["ln2"], h), cfg.act)


def forward(params, batch, cfg: ModelConfig):
    """Returns (hidden (B,S,D), aux_loss)."""
    x = L.embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
    positions = jnp.arange(x.shape[1])

    if cfg.first_k_dense:
        def dense_body(x, lp):
            return _dense_layer(cfg, lp, x, positions), None
        dbody = jax.checkpoint(dense_body) if cfg.remat else dense_body
        x, _ = lax.scan(dbody, x, params["dense_layers"])

    def moe_body(carry, lp):
        x, aux = carry
        h = x + _attn_apply(cfg, lp["attn"], T.norm(cfg, lp["ln1"], x), positions)
        y, a = _moe_ffn(cfg, lp["moe"], T.norm(cfg, lp["ln2"], h))
        return (h + y, aux + a), None

    mbody = jax.checkpoint(moe_body) if cfg.remat else moe_body
    (x, aux), _ = lax.scan(mbody, (x, jnp.float32(0.0)), params["moe_layers"])
    return T.norm(cfg, params["ln_f"], x), aux


def loss_fn(params, batch, cfg: ModelConfig):
    x, aux = forward(params, batch, cfg)
    logits = L.lm_logits(params["embed"], x, cfg.vocab)
    loss = L.softmax_xent(logits, batch["labels"])
    if cfg.mtp_depth:
        # DeepSeek-V3 MTP (depth 1): predict token t+2 from [h_t ; emb(t+1)].
        nxt = batch["labels"]  # token at t+1
        emb_next = L.embed(params["embed"], jnp.maximum(nxt, 0)).astype(cfg.dtype)
        h2 = jnp.concatenate([x, emb_next], axis=-1) @ params["mtp"]["proj"]
        h2 = _dense_layer(cfg, params["mtp"]["block"], h2, jnp.arange(x.shape[1]))
        h2 = T.norm(cfg, params["mtp"]["ln"], h2)
        logits2 = L.lm_logits(params["embed"], h2[:, :-1], cfg.vocab)
        mtp_labels = batch["labels"][:, 1:]  # token at t+2
        loss = loss + cfg.mtp_loss_coef * L.softmax_xent(logits2, mtp_labels)
    return loss + aux


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def init_cache_specs(cfg: ModelConfig, batch: int, seq_len: int):
    s = T.cache_len(cfg, seq_len)
    if cfg.mla:
        n_moe = cfg.n_layers - cfg.first_k_dense
        out = {
            "moe_ckv": ParamSpec((n_moe, batch, s, cfg.kv_lora_rank),
                                 ("layers", None, None, None), "zeros", cfg.dtype),
            "moe_krope": ParamSpec((n_moe, batch, s, cfg.rope_head_dim),
                                   ("layers", None, None, None), "zeros", cfg.dtype),
        }
        if cfg.first_k_dense:
            out["dense_ckv"] = ParamSpec((cfg.first_k_dense, batch, s, cfg.kv_lora_rank),
                                         ("layers", None, None, None), "zeros", cfg.dtype)
            out["dense_krope"] = ParamSpec((cfg.first_k_dense, batch, s, cfg.rope_head_dim),
                                           ("layers", None, None, None), "zeros", cfg.dtype)
        return out
    hk, dh = cfg.n_kv_heads, cfg.head_dim
    n_moe = cfg.n_layers - cfg.first_k_dense
    kv = ParamSpec((n_moe, batch, s, hk, dh), ("layers", None, None, "kv_heads", None),
                   "zeros", cfg.dtype)
    out = {"moe_k": kv, "moe_v": kv}
    if cfg.first_k_dense:
        kvd = ParamSpec((cfg.first_k_dense, batch, s, hk, dh),
                        ("layers", None, None, "kv_heads", None), "zeros", cfg.dtype)
        out.update({"dense_k": kvd, "dense_v": kvd})
    return out


def prefill(params, batch, cfg: ModelConfig):
    x = L.embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
    positions = jnp.arange(x.shape[1])
    cache = {}

    if cfg.first_k_dense:
        def dbody(x, lp):
            if cfg.mla:
                xn = T.norm(cfg, lp["ln1"], x)
                o, (ckv, krope) = mla_mod.mla_attention(lp["attn"], xn, cfg, positions,
                                                        return_cache=True)
                h = x + o
                kv = (ckv, krope)
            else:
                xn = T.norm(cfg, lp["ln1"], x)
                q, k, v = T.qkv(lp["attn"], xn, cfg, positions)
                o = attn.blockwise_attention(q, k, v, causal=True, window=cfg.window)
                h = x + o.reshape(x.shape[0], x.shape[1], -1) @ lp["attn"]["wo"]
                kv = (k, v)
            h = h + L.mlp(lp["mlp"], T.norm(cfg, lp["ln2"], h), cfg.act)
            return h, kv

        x, (c1, c2) = lax.scan(dbody, x, params["dense_layers"])
        cache.update({"dense_ckv" if cfg.mla else "dense_k": c1,
                      "dense_krope" if cfg.mla else "dense_v": c2})

    def mbody(carry, lp):
        x, aux = carry
        xn = T.norm(cfg, lp["ln1"], x)
        if cfg.mla:
            o, (c1, c2) = mla_mod.mla_attention(lp["attn"], xn, cfg, positions,
                                                return_cache=True)
            h = x + o
        else:
            q, k, v = T.qkv(lp["attn"], xn, cfg, positions)
            o = attn.blockwise_attention(q, k, v, causal=True, window=cfg.window)
            h = x + o.reshape(x.shape[0], x.shape[1], -1) @ lp["attn"]["wo"]
            c1, c2 = k, v
        y, a = _moe_ffn(cfg, lp["moe"], T.norm(cfg, lp["ln2"], h))
        return (h + y, aux + a), (c1, c2)

    (x, _), (c1, c2) = lax.scan(mbody, (x, jnp.float32(0.0)), params["moe_layers"])
    cache.update({"moe_ckv" if cfg.mla else "moe_k": c1,
                  "moe_krope" if cfg.mla else "moe_v": c2})
    x = T.norm(cfg, params["ln_f"], x)
    logits = L.lm_logits(params["embed"], x[:, -1:], cfg.vocab)
    w = T.cache_len(cfg, batch["tokens"].shape[1])
    cache = {k: v[:, :, -w:] for k, v in cache.items()}
    return logits, cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    b = tokens.shape[0]
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    bidx = jnp.arange(b)

    def attn_decode(lp, x, c1, c2):
        s_cache = c1.shape[1]
        widx = pos % s_cache
        xn = T.norm(cfg, lp["ln1"], x)
        if cfg.mla:
            o, c1, c2 = mla_mod.mla_decode(lp["attn"], xn, cfg, pos, c1, c2)
        else:
            q, k, v = T.qkv(lp["attn"], xn, cfg, pos[:, None])
            c1 = c1.at[bidx, widx].set(k[:, 0])
            c2 = c2.at[bidx, widx].set(v[:, 0])
            o = attn.decode_attention(q, c1, c2, jnp.minimum(pos + 1, s_cache))
            o = o.reshape(b, 1, -1) @ lp["attn"]["wo"]
        return x + o, c1, c2

    new_cache = dict(cache)
    if cfg.first_k_dense:
        k1 = "dense_ckv" if cfg.mla else "dense_k"
        k2 = "dense_krope" if cfg.mla else "dense_v"

        def dbody(x, xs):
            lp, c1, c2 = xs
            h, c1, c2 = attn_decode(lp, x, c1, c2)
            h = h + L.mlp(lp["mlp"], T.norm(cfg, lp["ln2"], h), cfg.act)
            return h, (c1, c2)

        x, (nc1, nc2) = lax.scan(dbody, x, (params["dense_layers"], cache[k1], cache[k2]))
        new_cache[k1], new_cache[k2] = nc1, nc2

    k1 = "moe_ckv" if cfg.mla else "moe_k"
    k2 = "moe_krope" if cfg.mla else "moe_v"

    def mbody(x, xs):
        lp, c1, c2 = xs
        h, c1, c2 = attn_decode(lp, x, c1, c2)
        y, _ = moe_apply(lp["moe"], T.norm(cfg, lp["ln2"], h), cfg)
        return h + y, (c1, c2)

    x, (nc1, nc2) = lax.scan(mbody, x, (params["moe_layers"], cache[k1], cache[k2]))
    new_cache[k1], new_cache[k2] = nc1, nc2
    x = T.norm(cfg, params["ln_f"], x)
    return L.lm_logits(params["embed"], x, cfg.vocab), new_cache
