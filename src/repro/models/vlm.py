"""VLM family — Qwen2-VL backbone [arXiv:2409.12191].

The ViT vision encoder + projector is a STUB per the task carve-out:
`input_specs()` supplies precomputed patch embeddings (B, n_vis, d_model).
The language backbone is real: GQA + QKV-bias attention with **M-RoPE** —
3D rotary positions (temporal, height, width) split across head_dim sections.
Vision tokens get grid (t=0, h, w) positions; text tokens get equal (t,h,w)
positions starting after the vision grid extent, following the paper.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import dense
from . import layers as L
from .config import ModelConfig


init_params = dense.init_params  # same parameter structure (dense + qkv bias)


def build_positions(n_vis: int, n_text: int, start_text_only: int = 0):
    """Returns (3, S) M-RoPE positions for [vision grid | text] sequences."""
    if n_vis:
        g = max(int(math.sqrt(n_vis)), 1)
        idx = jnp.arange(n_vis)
        vis = jnp.stack([jnp.zeros((n_vis,), jnp.int32),
                         (idx // g).astype(jnp.int32),
                         (idx % g).astype(jnp.int32)])
        t0 = g  # text starts after max spatial extent
    else:
        vis = jnp.zeros((3, 0), jnp.int32)
        t0 = start_text_only
    txt = jnp.broadcast_to(jnp.arange(n_text, dtype=jnp.int32) + t0, (3, n_text))
    return jnp.concatenate([vis, txt], axis=1)                  # (3, S)


def _mrope_attention(p, x, positions3, cfg: ModelConfig):
    q, k, v = L._qkv(p, x, cfg)
    q = L.apply_mrope(q, positions3, cfg.mrope_sections, cfg.rope_theta)
    k = L.apply_mrope(k, positions3, cfg.mrope_sections, cfg.rope_theta)
    out = L.attend_auto(q, k, v)
    return out.reshape(*x.shape[:2], -1) @ p["wo"].astype(x.dtype)


def _block(lp, x, positions3, cfg: ModelConfig):
    h = L.rms_norm(x, lp["ln1"].astype(x.dtype), cfg.norm_eps)
    x = x + _mrope_attention(lp["attn"], h, positions3, cfg)
    h = L.rms_norm(x, lp["ln2"].astype(x.dtype), cfg.norm_eps)
    return x + L.swiglu(lp["mlp"], h)


def forward_train(params, batch, cfg: ModelConfig, last_only: bool = False):
    """batch: {tokens (B,S_text), vision (B,n_vis,D), labels (B,S_text)}."""
    tok_emb = params["embed"].astype(cfg.cdtype)[batch["tokens"]]
    vis = batch["vision"].astype(cfg.cdtype)
    x = jnp.concatenate([vis, tok_emb], axis=1)
    n_vis, n_text = vis.shape[1], tok_emb.shape[1]
    positions3 = build_positions(n_vis, n_text)[:, None, :]      # (3, 1, S)

    blk = _block
    if cfg.remat:
        blk = jax.checkpoint(_block, static_argnums=(3,))

    def body(h, lp):
        return blk(lp, h, positions3, cfg), None

    x, _ = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    x = x[:, -1:] if last_only else x[:, n_vis:]   # text positions only
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits


def loss_fn(params, batch, cfg: ModelConfig):
    logits = forward_train(params, batch, cfg)
    return L.softmax_xent(logits, batch["labels"])


# ---------------------------------------------------------------------------
# decode — text-only continuation (all three position streams equal)
# ---------------------------------------------------------------------------
init_cache = dense.init_cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    B = tokens.shape[0]
    posv3 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (3, B, 1))

    def body(h, lc):
        lp, ck, cv = lc
        hn = L.rms_norm(h, lp["ln1"].astype(h.dtype), cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], hn, cfg)
        q = L.apply_mrope(q, posv3, cfg.mrope_sections, cfg.rope_theta)
        k = L.apply_mrope(k, posv3, cfg.mrope_sections, cfg.rope_theta)
        C = ck.shape[1]
        slot = jnp.minimum(pos, C - 1)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), slot, axis=1)
        valid = (jnp.arange(C) <= slot)[None, :]
        a = L.gqa_attend(q, ck.astype(q.dtype), cv.astype(q.dtype), valid)
        h = h + a.reshape(B, 1, -1) @ lp["attn"]["wo"].astype(h.dtype)
        hn = L.rms_norm(h, lp["ln2"].astype(h.dtype), cfg.norm_eps)
        return h + L.swiglu(lp["mlp"], hn), (ck, cv)

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]),
                               unroll=cfg.scan_unroll)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return x @ params["lm_head"].astype(x.dtype), {"k": nk, "v": nv}
