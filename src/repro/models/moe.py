"""Mixture-of-Experts decoder family.

Covers: deepseek-moe-16b [arXiv:2401.06066] — fine-grained experts
(64 routed, top-6, 2 shared, d_expert=1408), GQA attention;
deepseek-v2-236b [arXiv:2405.04434] — MLA (kv_lora=512) + 160 routed/top-6/2
shared experts.

Routing uses the sort-based capacity dispatch (the standard TPU-friendly
grouped-matmul formulation): top-k -> stable sort by expert -> position
within expert -> scatter into an (E, C, D) buffer -> batched expert SwiGLU
-> weighted combine.  Active FLOPs scale with E*C ~= T*top_k*capacity_factor,
not with the full expert count.

MLA decode uses the matrix-absorption trick: the compressed c_kv cache is the
only thing attended over; W_uk is absorbed into the query and W_uv applied to
the context, so per-token decode cost scales with kv_lora, not heads*head_dim.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig


# ---------------------------------------------------------------------------
# routed experts
# ---------------------------------------------------------------------------
def init_moe_ffn(key, cfg: ModelConfig):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_expert
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(k1, (D, E), jnp.float32, scale=0.02),
        "wg": L.dense_init(k2, (E, D, F), cfg.pdtype),
        "wu": L.dense_init(k3, (E, D, F), cfg.pdtype),
        "wd": L.dense_init(k4, (E, F, D), cfg.pdtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_swiglu(k5, D, cfg.n_shared_experts * F, cfg.pdtype)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


def moe_ffn(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (y, aux_loss).

    Long sequences are scanned through the router in `moe_seq_chunk` chunks:
    routing is per-token so this is algorithm-equivalent (capacity is applied
    per chunk), and it bounds the dispatch buffer at (E, C_chunk, D) instead
    of (E, C_seq, D) — the difference between 80 GB and 2.5 GB of live
    buffer at 32k-token prefill on deepseek-v2.
    """
    B, S, D = x.shape
    ch = cfg.moe_seq_chunk
    if ch and S > ch and S % ch == 0:
        n = S // ch
        xs = jnp.moveaxis(x.reshape(B, n, ch, D), 1, 0)      # (n, B, ch, D)

        def body(aux, xc):
            y, a = _moe_ffn_dispatch(p, xc, cfg)
            return aux + a, y

        aux, ys = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
        return jnp.moveaxis(ys, 0, 1).reshape(B, S, D), aux / n
    return _moe_ffn_dispatch(p, x, cfg)


def _constrain_dispatch(buf, cfg: ModelConfig):
    """Pin the (E, C, D) dispatch buffer to (expert_axis, token_axis, -):
    without it GSPMD replicates the scatter output per data shard and
    all-reduces ~10 GB per MoE layer (§Perf P2 iteration 3).  No-op when no
    mesh is set (jax.set_mesh) or it lacks the axes (FL sim, vmapped client
    stacks); any other failure of the constraint raises."""
    if not cfg.moe_dispatch_axes:
        return buf
    from jax.sharding import PartitionSpec as P
    ea, ta = cfg.moe_dispatch_axes
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not {a for a in (ea, ta) if a} <= set(mesh.axis_names):
        return buf
    return jax.lax.with_sharding_constraint(buf, P(ea or None, ta or None,
                                                   None))


def _moe_ffn_dispatch(p, x, cfg: ModelConfig):
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    xt = x.reshape(T, D)

    gates = (xt.astype(jnp.float32) @ p["router"])                  # (T, E)
    probs = jax.nn.softmax(gates, axis=-1)
    topv, topi = jax.lax.top_k(probs, K)                            # (T, K)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)             # deepseek renorm

    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                    # (E,)
    one_hot_top1 = jax.nn.one_hot(topi[:, 0], E)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    flat_e = topi.reshape(-1)                                       # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = topv.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(se, length=E)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K) - offsets[se]
    C = _capacity(T, cfg)
    keep = (pos < C).astype(xt.dtype)
    slot = jnp.minimum(pos, C - 1)

    buf = jnp.zeros((E, C, D), xt.dtype)
    buf = buf.at[se, slot].add(xt[st] * keep[:, None])
    buf = _constrain_dispatch(buf, cfg)
    # batched expert SwiGLU: (E, C, D) x (E, D, F)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(xt.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(xt.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(xt.dtype))

    vals = out_buf[se, slot] * (sw.astype(xt.dtype) * keep)[:, None]
    y = jnp.zeros((T, D), xt.dtype).at[st].add(vals)

    if "shared" in p:
        y = y + L.swiglu(p["shared"], xt)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v2)
# ---------------------------------------------------------------------------
def init_mla(key, cfg: ModelConfig):
    D, H = cfg.d_model, cfg.n_heads
    nh, rh, vh, kl, ql = (cfg.nope_head_dim, cfg.rope_head_dim,
                          cfg.v_head_dim, cfg.kv_lora, cfg.q_lora)
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": L.dense_init(ks[0], (D, kl + rh), cfg.pdtype),
        "kv_norm": jnp.ones((kl,), cfg.pdtype),
        "wkv_b": L.dense_init(ks[1], (kl, H, nh + vh), cfg.pdtype),
        "wo": L.dense_init(ks[2], (H * vh, D), cfg.pdtype),
    }
    if ql:
        p["wq_a"] = L.dense_init(ks[3], (D, ql), cfg.pdtype)
        p["q_norm"] = jnp.ones((ql,), cfg.pdtype)
        p["wq_b"] = L.dense_init(ks[4], (ql, H, nh + rh), cfg.pdtype)
    else:
        p["wq"] = L.dense_init(ks[5], (D, H, nh + rh), cfg.pdtype)
    return p


def _mla_q(p, x, cfg: ModelConfig):
    if "wq_a" in p:
        cq = L.rms_norm(x @ p["wq_a"].astype(x.dtype), p["q_norm"].astype(x.dtype))
        q = jnp.einsum("bsl,lhd->bshd", cq, p["wq_b"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
    return jnp.split(q, [cfg.nope_head_dim], axis=-1)   # q_nope, q_rope


def mla_train(p, x, positions, cfg: ModelConfig):
    B, S, D = x.shape
    H = cfg.n_heads
    nh, rh, vh = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = x @ p["wkv_a"].astype(x.dtype)                            # (B,S,kl+rh)
    c_kv, k_rope = jnp.split(ckv, [cfg.kv_lora], axis=-1)
    c_kv = L.rms_norm(c_kv, p["kv_norm"].astype(x.dtype))
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions,
                          cfg.rope_theta)          # (B,S,1,rh)

    kv = jnp.einsum("bsl,lhd->bshd", c_kv, p["wkv_b"].astype(x.dtype))
    k_nope, v = jnp.split(kv, [nh], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rh))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    out = L.attend_auto(q, k, v, scale=1.0 / math.sqrt(nh + rh))
    return out.reshape(B, S, H * vh) @ p["wo"].astype(x.dtype)


def mla_decode(p, x, pos, c_cache, r_cache, cfg: ModelConfig):
    """Absorbed-matrix MLA decode over the compressed cache.

    c_cache: (B, C, kv_lora); r_cache: (B, C, rope_hd).
    """
    B = x.shape[0]
    H = cfg.n_heads
    nh, rh, vh = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    posv = jnp.full((B, 1), pos, dtype=jnp.int32)

    q_nope, q_rope = _mla_q(p, x, cfg)                              # (B,1,H,·)
    q_rope = L.apply_rope(q_rope, posv, cfg.rope_theta)

    ckv = x @ p["wkv_a"].astype(x.dtype)
    c_kv, k_rope = jnp.split(ckv, [cfg.kv_lora], axis=-1)
    c_kv = L.rms_norm(c_kv, p["kv_norm"].astype(x.dtype))
    k_rope = L.apply_rope(k_rope[:, :, None, :], posv, cfg.rope_theta)[:, :, 0, :]

    C = c_cache.shape[1]
    slot = jnp.minimum(pos, C - 1)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        c_cache, c_kv.astype(c_cache.dtype), slot, axis=1)
    r_cache = jax.lax.dynamic_update_slice_in_dim(
        r_cache, k_rope.astype(r_cache.dtype), slot, axis=1)

    w_uk, w_uv = jnp.split(p["wkv_b"].astype(x.dtype), [nh],
                           axis=-1)              # (kl,H,nh),(kl,H,vh)
    qc = jnp.einsum("bqhn,khn->bqhk", q_nope, w_uk)                 # (B,1,H,kl)
    scores = (jnp.einsum("bqhk,bck->bhqc", qc, c_cache.astype(x.dtype))
              + jnp.einsum("bqhr,bcr->bhqc", q_rope, r_cache.astype(x.dtype)))
    scores = scores * (1.0 / math.sqrt(nh + rh))
    valid = (jnp.arange(C) <= slot)[None, None, None, :]
    scores = jnp.where(valid, scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqc,bck->bqhk", probs, c_cache.astype(x.dtype))
    out = jnp.einsum("bqhk,khv->bqhv", ctx, w_uv)                   # (B,1,H,vh)
    y = out.reshape(B, 1, H * vh) @ p["wo"].astype(x.dtype)
    return y, c_cache, r_cache


# ---------------------------------------------------------------------------
# blocks / model
# ---------------------------------------------------------------------------
def _init_attn(key, cfg: ModelConfig):
    return init_mla(key, cfg) if cfg.kv_lora else L.init_attention(key, cfg)


def init_moe_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdtype),
        "attn": _init_attn(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdtype),
        "moe": init_moe_ffn(k2, cfg),
    }


def init_dense_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdtype),
        "attn": _init_attn(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdtype),
        "mlp": L.init_swiglu(k2, cfg.d_model, cfg.dense_ff or 4 * cfg.d_model,
                             cfg.pdtype),
    }


def init_params(key, cfg: ModelConfig):
    ke, kd, km, kh = jax.random.split(key, 4)
    n_moe = cfg.n_layers - cfg.first_dense_layers
    dense_keys = jax.random.split(kd, max(cfg.first_dense_layers, 1))
    moe_keys = jax.random.split(km, n_moe)
    return {
        "embed": L.embed_init(ke, (cfg.vocab, cfg.d_model), cfg.pdtype),
        "dense_layers": jax.vmap(lambda k: init_dense_layer(k, cfg))(dense_keys),
        "moe_layers": jax.vmap(lambda k: init_moe_layer(k, cfg))(moe_keys),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
        "lm_head": L.dense_init(kh, (cfg.d_model, cfg.vocab), cfg.pdtype),
    }


def _attn_train(lp, h, positions, cfg):
    if cfg.kv_lora:
        return mla_train(lp["attn"], h, positions, cfg)
    return L.attention_train(lp["attn"], h, positions, cfg)


def _dense_block(lp, x, positions, cfg: ModelConfig):
    h = L.rms_norm(x, lp["ln1"].astype(x.dtype), cfg.norm_eps)
    x = x + _attn_train(lp, h, positions, cfg)
    h = L.rms_norm(x, lp["ln2"].astype(x.dtype), cfg.norm_eps)
    return x + L.swiglu(lp["mlp"], h)


def _moe_block(lp, x, positions, cfg: ModelConfig):
    h = L.rms_norm(x, lp["ln1"].astype(x.dtype), cfg.norm_eps)
    x = x + _attn_train(lp, h, positions, cfg)
    h = L.rms_norm(x, lp["ln2"].astype(x.dtype), cfg.norm_eps)
    y, aux = moe_ffn(lp["moe"], h, cfg)
    return x + y, aux


def forward_train(params, tokens, cfg: ModelConfig, positions=None,
                  last_only: bool = False):
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]

    dense_blk = _dense_block
    moe_blk = _moe_block
    if cfg.remat:
        dense_blk = jax.checkpoint(_dense_block, static_argnums=(3,))
        moe_blk = jax.checkpoint(_moe_block, static_argnums=(3,))

    if cfg.first_dense_layers:
        def dbody(h, lp):
            return dense_blk(lp, h, positions, cfg), None
        x, _ = jax.lax.scan(dbody, x, params["dense_layers"],
                            unroll=cfg.scan_unroll)

    def mbody(carry, lp):
        h, aux = carry
        h, a = moe_blk(lp, h, positions, cfg)
        return (h, aux + a), None

    (x, aux), _ = jax.lax.scan(mbody, (x, jnp.zeros((), jnp.float32)),
                               params["moe_layers"], unroll=cfg.scan_unroll)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    return x @ params["lm_head"].astype(x.dtype), aux


def loss_fn(params, batch, cfg: ModelConfig):
    logits, aux = forward_train(params, batch["tokens"], cfg)
    return L.softmax_xent(logits, batch["labels"]) + aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    n_moe = cfg.n_layers - cfg.first_dense_layers
    nd = max(cfg.first_dense_layers, 1)
    if cfg.kv_lora:
        return {
            "dense": {
                "c": jnp.zeros((nd, batch, cache_len, cfg.kv_lora), cfg.cdtype),
                "r": jnp.zeros((nd, batch, cache_len, cfg.rope_head_dim), cfg.cdtype),
            },
            "moe": {
                "c": jnp.zeros((n_moe, batch, cache_len, cfg.kv_lora), cfg.cdtype),
                "r": jnp.zeros((n_moe, batch, cache_len,
                                cfg.rope_head_dim), cfg.cdtype),
            },
        }
    shape_d = (nd, batch, cache_len, cfg.n_kv_heads, cfg.hd)
    shape_m = (n_moe, batch, cache_len, cfg.n_kv_heads, cfg.hd)
    return {
        "dense": {"k": jnp.zeros(shape_d, cfg.cdtype),
                  "v": jnp.zeros(shape_d, cfg.cdtype)},
        "moe": {"k": jnp.zeros(shape_m, cfg.cdtype),
                "v": jnp.zeros(shape_m, cfg.cdtype)},
    }


def _attn_decode(lp, h, pos, cc, cfg):
    if cfg.kv_lora:
        a, c, r = mla_decode(lp["attn"], h, pos, cc[0], cc[1], cfg)
        return a, (c, r)
    a, k, v = L.attention_decode(lp["attn"], h, pos, cc[0], cc[1], cfg)
    return a, (k, v)


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    keys = ("c", "r") if cfg.kv_lora else ("k", "v")

    def dense_body(h, lc):
        lp, c0, c1 = lc
        hn = L.rms_norm(h, lp["ln1"].astype(h.dtype), cfg.norm_eps)
        a, (c0, c1) = _attn_decode(lp, hn, pos, (c0, c1), cfg)
        h = h + a
        hn = L.rms_norm(h, lp["ln2"].astype(h.dtype), cfg.norm_eps)
        return h + L.swiglu(lp["mlp"], hn), (c0, c1)

    dc = cache["dense"]
    x, (d0, d1) = jax.lax.scan(dense_body, x,
                               (params["dense_layers"], dc[keys[0]], dc[keys[1]]))

    def moe_body(h, lc):
        lp, c0, c1 = lc
        hn = L.rms_norm(h, lp["ln1"].astype(h.dtype), cfg.norm_eps)
        a, (c0, c1) = _attn_decode(lp, hn, pos, (c0, c1), cfg)
        h = h + a
        hn = L.rms_norm(h, lp["ln2"].astype(h.dtype), cfg.norm_eps)
        y, _ = moe_ffn(lp["moe"], hn, cfg)
        return h + y, (c0, c1)

    mc = cache["moe"]
    x, (m0, m1) = jax.lax.scan(moe_body, x,
                               (params["moe_layers"], mc[keys[0]], mc[keys[1]]))
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    new_cache = {"dense": {keys[0]: d0, keys[1]: d1},
                 "moe": {keys[0]: m0, keys[1]: m1}}
    return logits, new_cache
