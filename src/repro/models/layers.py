"""Shared neural-net building blocks (pure JAX, functional).

Parameters are plain nested dicts of jnp arrays.  All blocks take the
ModelConfig for dtype handling and are written to be `vmap`-able over a
leading client axis and `scan`-able over a stacked layer axis.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """LeCun-normal style init on the penultimate dim."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * s).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


@jax.custom_vjp
def _lookup(table, tokens):
    return table[tokens]


def _lookup_fwd(table, tokens):
    return table[tokens], (table, tokens)


def _lookup_bwd(res, g):
    table, tokens = res
    grad = jnp.zeros_like(table).at[tokens].add(g)
    # the table gradient is summed over the batch's tokens from zero, and
    # only then meets whatever the optimizer adds (weight decay): without
    # the barrier XLA may fold that add into the scatter's base in one
    # program and not in another, which reorders the f32 sum for repeated
    # tokens, so the same step would round differently by program form
    return jax.lax.optimization_barrier(grad), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def embed_tokens(table, tokens, dtype):
    """Rows of the (vocab, d) table for integer `tokens`, cast to `dtype`."""
    return _lookup(table.astype(dtype), tokens)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight).astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight + bias).astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections, theta: float):
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191).

    x: (..., S, H, hd); positions3: (3, ..., S) int32 for (t, h, w) streams.
    `sections` splits hd/2 frequency slots across the three streams.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    # build a per-frequency position by picking the stream each slot belongs to
    sec = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=hd // 2)
    pos = jnp.moveaxis(positions3, 0, -1)              # (..., S, 3)
    pos = jnp.take(pos.astype(jnp.float32), sec, axis=-1)  # (..., S, hd/2)
    ang = pos * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoid_positions(n_pos: int, dim: int):
    pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim))
    pe = jnp.zeros((n_pos, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, d_in: int = 0):
    D = d_in or cfg.d_model
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, cfg.n_heads * hd), cfg.pdtype),
        "wk": dense_init(ks[1], (D, cfg.n_kv_heads * hd), cfg.pdtype),
        "wv": dense_init(ks[2], (D, cfg.n_kv_heads * hd), cfg.pdtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, D), cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.pdtype)
    return p


def _qkv(p, x, cfg: ModelConfig):
    B, S, _ = x.shape
    hd = cfg.hd
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v


def gqa_attend(q, k, v, mask, scale: Optional[float] = None):
    """Grouped-query attention without materialising repeated KV.

    q: (B, Sq, H, hd), k/v: (B, Sk, Hkv, hd), mask broadcastable to
    (B, Hkv, g, Sq, Sk) or (Sq, Sk).  Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qf = q.reshape(B, Sq, Hkv, g, hd)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k) * scale
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])  # v head dim may differ (MLA)


def causal_mask(sq: int, sk: int, window: int = 0, offset: int = 0):
    """(sq, sk) boolean mask. offset = absolute position of query 0 minus key 0."""
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


def block_attention(q, k, v, *, window: int = 0, scale: Optional[float] = None,
                    q_block: int = 1024):
    """Memory-bounded causal (optionally sliding-window) GQA attention.

    Python-unrolled loop over query blocks; each block attends only to the
    static K slice it can see ([0, q_hi) for causal; the trailing
    `window + block` band for windowed), so peak scores memory is
    O(q_block * S) per block and compiled FLOPs match the true causal /
    banded cost — no (S, S) mask or score tensor is ever materialised.
    Also the jnp oracle for the Pallas flash-attention kernel.

    q: (B, S, H, hd); k/v: (B, S, Hkv, hd) -> (B, S, H, vh).
    """
    S = q.shape[1]
    qb = min(q_block, S)
    n_blocks = -(-S // qb)
    outs = []
    for i in range(n_blocks):
        q0, q1 = i * qb, min((i + 1) * qb, S)
        k0 = max(0, q1 - window - (q1 - q0)) if window else 0
        mask = causal_mask(q1 - q0, q1 - k0, window=window, offset=q0 - k0)
        outs.append(gqa_attend(q[:, q0:q1], k[:, k0:q1], v[:, k0:q1],
                               mask, scale=scale))
    return jnp.concatenate(outs, axis=1)


# sequences at or above this length take the blocked path in training
BLOCK_ATTN_MIN_SEQ = 2048


def attend_auto(q, k, v, *, window: int = 0, scale: Optional[float] = None):
    """Dispatch: small seqs use the simple masked path (cheap, easily
    inspected), long seqs the memory-bounded blocked path."""
    if q.shape[1] >= BLOCK_ATTN_MIN_SEQ:
        return block_attention(q, k, v, window=window, scale=scale)
    mask = causal_mask(q.shape[1], k.shape[1], window=window)
    return gqa_attend(q, k, v, mask, scale=scale)


def attention_train(p, x, positions, cfg: ModelConfig, window: int = 0,
                    theta: Optional[float] = None):
    q, k, v = _qkv(p, x, cfg)
    th = theta if theta is not None else cfg.rope_theta
    if th > 0:
        q = apply_rope(q, positions, th)
        k = apply_rope(k, positions, th)
    out = attend_auto(q, k, v, window=window)
    return out.reshape(*x.shape[:2], -1) @ p["wo"].astype(x.dtype)


def attention_decode(p, x, pos, cache_k, cache_v, cfg: ModelConfig,
                     window: int = 0, theta: Optional[float] = None):
    """One-token decode. x: (B,1,D); pos: scalar int; ring-buffer if window>0.

    cache_k/v: (B, C, Hkv, hd) where C = cache capacity (seq_len or window).
    """
    q, k, v = _qkv(p, x, cfg)
    th = theta if theta is not None else cfg.rope_theta
    posv = jnp.full((x.shape[0], 1), pos, dtype=jnp.int32)
    if th > 0:
        q = apply_rope(q, posv, th)
        k = apply_rope(k, posv, th)
    C = cache_k.shape[1]
    slot = jnp.mod(pos, C) if window else jnp.minimum(pos, C - 1)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), slot, axis=1)
    # key absolute positions for masking
    idx = jnp.arange(C)
    if window:
        n_wraps = pos // C
        kpos = jnp.where(idx <= jnp.mod(pos, C), idx + n_wraps * C,
                         idx + (n_wraps - 1) * C)
        valid = (kpos >= 0) & (kpos <= pos) & (kpos > pos - window)
    else:
        valid = idx <= jnp.minimum(pos, C - 1)
    mask = valid[None, :]                                   # (1, C) -> broadcast
    out = gqa_attend(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask)
    y = out.reshape(x.shape[0], 1, -1) @ p["wo"].astype(x.dtype)
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def init_swiglu(key, d: int, f: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": dense_init(k1, (d, f), dtype),
        "wu": dense_init(k2, (d, f), dtype),
        "wd": dense_init(k3, (f, d), dtype),
    }


def swiglu(p, x):
    h = jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (x @ p["wu"].astype(x.dtype))
    return h @ p["wd"].astype(x.dtype)


def init_gelu_mlp(key, d: int, f: int, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w1": dense_init(k1, (d, f), dtype),
        "b1": jnp.zeros((f,), dtype),
        "w2": dense_init(k2, (f, d), dtype),
        "b2": jnp.zeros((d,), dtype),
    }


def gelu_mlp(p, x):
    h = jax.nn.gelu(x @ p["w1"].astype(x.dtype) + p["b1"].astype(x.dtype))
    return h @ p["w2"].astype(x.dtype) + p["b2"].astype(x.dtype)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def softmax_xent(logits, labels, ignore: int = -100):
    """Mean token cross-entropy; labels==ignore are masked."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - ll
    w = (labels != ignore).astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
