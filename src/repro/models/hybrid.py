"""Hybrid recurrent/attention family — RecurrentGemma / Griffin.

recurrentgemma-9b [arXiv:2402.19427]: 38 layers, pattern (RG-LRU, RG-LRU,
local-attn) repeating; RG-LRU is a gated linear recurrence computed with
`jax.lax.associative_scan` (TPU-native parallel scan — the hardware adaptation
of the paper's CUDA fused scan); local attention is MQA with a sliding window.

Layers are grouped into *periods* of (2 recurrent + 1 attention) and scanned
over stacked period-parameters; the non-multiple tail is a second small scan.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig

_C_RGLRU = 8.0


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------
def init_rglru_block(key, cfg: ModelConfig):
    D, W = cfg.d_model, cfg.lru_width or cfg.d_model
    ks = jax.random.split(key, 7)
    # Λ init so that a = exp(-8*softplus(Λ)*r) lands in [0.9, 0.999] at r=0.5
    lam = jax.random.uniform(ks[0], (W,), minval=0.0001, maxval=0.1)
    return {
        "w_in_x": L.dense_init(ks[1], (D, W), cfg.pdtype),
        "w_in_y": L.dense_init(ks[2], (D, W), cfg.pdtype),
        "conv_w": L.dense_init(ks[3], (cfg.conv1d_width, W), cfg.pdtype, scale=0.5),
        "w_a": L.dense_init(ks[4], (W, W), cfg.pdtype, scale=0.01),
        "b_a": jnp.zeros((W,), cfg.pdtype),
        "w_i": L.dense_init(ks[5], (W, W), cfg.pdtype, scale=0.01),
        "b_i": jnp.zeros((W,), cfg.pdtype),
        "lam": lam.astype(jnp.float32),
        "w_out": L.dense_init(ks[6], (W, D), cfg.pdtype),
    }


def _causal_conv1d(x, w, state=None):
    """Depthwise causal conv. x: (B,S,W); w: (cw,W); state: (B,cw-1,W)|None."""
    cw = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype) for i in range(cw))
    new_state = xp[:, -(cw - 1):, :] if cw > 1 else None
    return out, new_state


def _rglru_gates(p, xi):
    r = jax.nn.sigmoid(xi @ p["w_a"].astype(xi.dtype) + p["b_a"].astype(xi.dtype))
    i = jax.nn.sigmoid(xi @ p["w_i"].astype(xi.dtype) + p["b_i"].astype(xi.dtype))
    log_a = (-_C_RGLRU * jax.nn.softplus(p["lam"]) * r.astype(jnp.float32))
    a = jnp.exp(log_a)
    gated_x = (i * xi).astype(jnp.float32) * jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    return a, gated_x


def rglru_scan(p, xi, h0=None):
    """xi: (B,S,W). Linear recurrence h_t = a_t h_{t-1} + b_t via associative scan."""
    a, b = _rglru_gates(p, xi)                       # (B,S,W) f32 each
    if h0 is not None:
        b = b.at[:, 0, :].add(a[:, 0, :] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(xi.dtype)


def rglru_step(p, xi, h):
    """One decode step. xi: (B,1,W); h: (B,W) -> (y (B,1,W), h')."""
    a, b = _rglru_gates(p, xi)
    hn = a[:, 0] * h.astype(jnp.float32) + b[:, 0]
    return hn.astype(xi.dtype)[:, None, :], hn.astype(h.dtype)


def recurrent_block(p, x, cfg: ModelConfig, state=None):
    """Griffin recurrent temporal block. state: None | (h, conv_state)."""
    y = jax.nn.gelu(x @ p["w_in_y"].astype(x.dtype))
    xi = x @ p["w_in_x"].astype(x.dtype)
    if state is None:
        xi, _ = _causal_conv1d(xi, p["conv_w"])
        h = rglru_scan(p, xi)
        out = (h * y) @ p["w_out"].astype(x.dtype)
        return out, None
    h0, conv_state = state
    xi, conv_state = _causal_conv1d(xi, p["conv_w"], conv_state)
    hseq, hn = rglru_step(p, xi, h0)
    out = (hseq * y) @ p["w_out"].astype(x.dtype)
    return out, (hn, conv_state)


# ---------------------------------------------------------------------------
# layer inits
# ---------------------------------------------------------------------------
def init_lru_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdtype),
        "rec": init_rglru_block(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdtype),
        "mlp": L.init_swiglu(k2, cfg.d_model, cfg.d_ff, cfg.pdtype),
    }


def init_attn_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdtype),
        "attn": L.init_attention(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdtype),
        "mlp": L.init_swiglu(k2, cfg.d_model, cfg.d_ff, cfg.pdtype),
    }


def _layout(cfg: ModelConfig):
    """(n_periods, n_tail_lru). Pattern fixed: (rglru, rglru, attn)."""
    P = cfg.n_layers // 3
    tail = cfg.n_layers - 3 * P
    return P, tail


def init_params(key, cfg: ModelConfig):
    P, tail = _layout(cfg)
    ke, k1, k2, k3, kh = jax.random.split(key, 5)
    params = {
        "embed": L.embed_init(ke, (cfg.vocab, cfg.d_model), cfg.pdtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
        "lm_head": L.dense_init(kh, (cfg.d_model, cfg.vocab), cfg.pdtype),
    }
    if P:
        lru_keys = jax.random.split(k1, P * 2).reshape(P, 2, -1)
        params["period_lru"] = jax.vmap(jax.vmap(
            lambda k: init_lru_layer(k, cfg)))(lru_keys)
        params["period_attn"] = jax.vmap(
            lambda k: init_attn_layer(k, cfg))(jax.random.split(k2, P))
    if tail:
        params["tail_lru"] = jax.vmap(
            lambda k: init_lru_layer(k, cfg))(jax.random.split(k3, tail))
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _lru_layer_fwd(lp, x, cfg, state=None):
    h = L.rms_norm(x, lp["ln1"].astype(x.dtype), cfg.norm_eps)
    r, state = recurrent_block(lp["rec"], h, cfg, state)
    x = x + r
    h = L.rms_norm(x, lp["ln2"].astype(x.dtype), cfg.norm_eps)
    return x + L.swiglu(lp["mlp"], h), state


def _attn_layer_fwd(lp, x, positions, cfg):
    h = L.rms_norm(x, lp["ln1"].astype(x.dtype), cfg.norm_eps)
    x = x + L.attention_train(lp["attn"], h, positions, cfg,
                              window=cfg.local_window)
    h = L.rms_norm(x, lp["ln2"].astype(x.dtype), cfg.norm_eps)
    return x + L.swiglu(lp["mlp"], h)


def forward_train(params, tokens, cfg: ModelConfig, positions=None,
                  last_only: bool = False):
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    P, tail = _layout(cfg)

    def period(h, lp):
        lru2, attn = lp
        for j in range(2):
            lj = jax.tree.map(lambda a: a[j], lru2)
            h, _ = _lru_layer_fwd(lj, h, cfg)
        h = _attn_layer_fwd(attn, h, positions, cfg)
        return h, None

    if cfg.remat:
        period = jax.checkpoint(period)
    if P:
        x, _ = jax.lax.scan(period, x, (params["period_lru"], params["period_attn"]),
                            unroll=cfg.scan_unroll)
    if tail:
        def tbody(h, lp):
            h, _ = _lru_layer_fwd(lp, h, cfg)
            return h, None
        x, _ = jax.lax.scan(tbody, x, params["tail_lru"], unroll=cfg.scan_unroll)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    return x @ params["lm_head"].astype(x.dtype)


def loss_fn(params, batch, cfg: ModelConfig):
    logits = forward_train(params, batch["tokens"], cfg)
    return L.softmax_xent(logits, batch["labels"])


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    P, tail = _layout(cfg)
    W = cfg.lru_width or cfg.d_model
    C = min(cache_len, cfg.local_window)
    cw = cfg.conv1d_width
    cache = {}
    if P:
        cache["p_h"] = jnp.zeros((P, 2, batch, W), jnp.float32)
        cache["p_conv"] = jnp.zeros((P, 2, batch, cw - 1, W), cfg.cdtype)
        cache["p_k"] = jnp.zeros((P, batch, C, cfg.n_kv_heads, cfg.hd), cfg.cdtype)
        cache["p_v"] = jnp.zeros((P, batch, C, cfg.n_kv_heads, cfg.hd), cfg.cdtype)
    if tail:
        cache["t_h"] = jnp.zeros((tail, batch, W), jnp.float32)
        cache["t_conv"] = jnp.zeros((tail, batch, cw - 1, W), cfg.cdtype)
    return cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    P, tail = _layout(cfg)
    new_cache = dict(cache)

    if P:
        def period(h, lc):
            lru2, attn, ph, pconv, pk, pv = lc
            hs, cs = [], []
            for j in range(2):
                lj = jax.tree.map(lambda a: a[j], lru2)
                h, (hj, cj) = _lru_layer_fwd(lj, h, cfg, (ph[j], pconv[j]))
                hs.append(hj)
                cs.append(cj)
            hn = L.rms_norm(h, attn["ln1"].astype(h.dtype), cfg.norm_eps)
            a, pk, pv = L.attention_decode(attn["attn"], hn, pos, pk, pv, cfg,
                                           window=cfg.local_window)
            h = h + a
            hn = L.rms_norm(h, attn["ln2"].astype(h.dtype), cfg.norm_eps)
            h = h + L.swiglu(attn["mlp"], hn)
            return h, (jnp.stack(hs), jnp.stack(cs), pk, pv)

        x, (ph, pconv, pk, pv) = jax.lax.scan(
            period, x,
            (params["period_lru"], params["period_attn"],
             cache["p_h"], cache["p_conv"], cache["p_k"], cache["p_v"]))
        new_cache.update(p_h=ph, p_conv=pconv, p_k=pk, p_v=pv)

    if tail:
        def tbody(h, lc):
            lp, th, tconv = lc
            h, (hn, cn) = _lru_layer_fwd(lp, h, cfg, (th, tconv))
            return h, (hn, cn)
        x, (th, tconv) = jax.lax.scan(
            tbody, x, (params["tail_lru"], cache["t_h"], cache["t_conv"]))
        new_cache.update(t_h=th, t_conv=tconv)

    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits, new_cache
