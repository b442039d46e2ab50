"""xLSTM family [arXiv:2405.04517] — sLSTM + mLSTM blocks.

xlstm-125m: 12 layers, d_model=768, 4 heads, vocab 50304.  Layers listed in
`cfg.slstm_layers` use the sLSTM (scalar memory, true recurrence, lax.scan);
all others use the mLSTM (matrix memory) computed in the *chunkwise-parallel*
form: intra-chunk quadratic attention with the gated decay matrix D, and an
inter-chunk recurrent state (C, n, m) carried by lax.scan — O(S * chunk)
compute and O(1) decode state, which is what qualifies this family for the
long_500k shape.

All gating uses the paper's exponential-gate stabilizer m.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig

NEG = -1e30


# ---------------------------------------------------------------------------
# mLSTM — chunkwise parallel
# ---------------------------------------------------------------------------
def init_mlstm_block(key, cfg: ModelConfig):
    D = cfg.d_model
    up = int(D * cfg.mlstm_proj_factor)
    ks = jax.random.split(key, 8)
    return {
        "ln": jnp.ones((D,), cfg.pdtype),
        "w_up": L.dense_init(ks[0], (D, 2 * up), cfg.pdtype),
        "conv_w": L.dense_init(ks[1], (4, up), cfg.pdtype, scale=0.5),
        "wq": L.dense_init(ks[2], (up, up), cfg.pdtype),
        "wk": L.dense_init(ks[3], (up, up), cfg.pdtype),
        "wv": L.dense_init(ks[4], (up, up), cfg.pdtype),
        "w_if": L.dense_init(ks[5], (up, 2 * cfg.n_heads), cfg.pdtype, scale=0.01),
        "b_if": jnp.concatenate([jnp.zeros((cfg.n_heads,)),
                                 jnp.linspace(3.0, 6.0, cfg.n_heads)]
                                ).astype(cfg.pdtype),
        "gn": jnp.ones((up,), cfg.pdtype),
        "w_down": L.dense_init(ks[6], (up, D), cfg.pdtype),
    }


def _causal_conv(x, w, state=None):
    cw = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype) for i in range(cw))
    return out, xp[:, -(cw - 1):, :]


def mlstm_chunkwise(q, k, v, log_i, log_f, chunk: int):
    """Chunkwise-parallel mLSTM. Shapes: q/k/v (B,S,H,hd), gates (B,S,H)."""
    B, S, H, hd = q.shape
    T = min(chunk, S)
    assert S % T == 0, f"seq {S} not divisible by chunk {T}"
    nc = S // T

    def r(x):  # (B,S,...) -> (nc, B, T, ...)
        return jnp.moveaxis(x.reshape(B, nc, T, *x.shape[2:]), 1, 0)

    qs, ks, vs = r(q), r(k), r(v)
    lis, lfs = r(log_i), r(log_f)

    C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    n0 = jnp.zeros((B, H, hd), jnp.float32)
    m0 = jnp.full((B, H), -1e30, jnp.float32)

    def chunk_body(carry, xs):
        C, n, m = carry
        qc, kc, vc, li, lf = xs                                  # (B,T,H,·)
        b = jnp.cumsum(lf, axis=1)                               # (B,T,H) inclusive
        total = b[:, -1]                                         # (B,H)
        # intra-chunk decay matrix exponents: g[t,s] = b_t - b_s + li_s (s<=t)
        gexp = (b[:, :, None, :] - b[:, None, :, :] + li[:, None, :, :])  # (B,T,T,H)
        mask = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])[None, :, :, None]
        gexp = jnp.where(mask, gexp, NEG)
        # per-step stabilizer
        m_intra = jnp.max(gexp, axis=2)                          # (B,T,H)
        m_t = jnp.maximum(b + m[:, None, :], m_intra)            # (B,T,H)
        # inter contribution
        w_inter = jnp.exp(b + m[:, None, :] - m_t)               # (B,T,H)
        qf = qc.astype(jnp.float32)
        inter_h = jnp.einsum("bthd,bhde->bthe", qf, C) * w_inter[..., None]
        inter_n = jnp.einsum("bthd,bhd->bth", qf, n) * w_inter
        # intra contribution
        d = jnp.exp(gexp - m_t[:, :, None, :])                   # (B,T,T,H)
        kf = kc.astype(jnp.float32)
        scores = jnp.einsum("bthd,bshd->btsh", qf, kf) * d
        intra_h = jnp.einsum("btsh,bshd->bthd", scores, vc.astype(jnp.float32))
        intra_n = jnp.sum(scores, axis=2)                        # (B,T,H)
        denom = jnp.maximum(jnp.abs(inter_n + intra_n), jnp.exp(-m_t))
        h = (inter_h + intra_h) / denom[..., None]               # (B,T,H,hd)
        # state update
        m_new = jnp.maximum(total + m,
                            jnp.max(total[:, None, :] - b + li, axis=1))
        w_c = jnp.exp(total + m - m_new)                         # (B,H)
        w_s = jnp.exp(total[:, None, :] - b + li - m_new[:, None, :])  # (B,T,H)
        C = C * w_c[..., None, None] + jnp.einsum(
            "bshd,bshe,bsh->bhde", kf, vc.astype(jnp.float32), w_s)
        n = n * w_c[..., None] + jnp.einsum("bshd,bsh->bhd", kf, w_s)
        return (C, n, m_new), h

    (_, _, _), hs = jax.lax.scan(chunk_body, (C0, n0, m0), (qs, ks, vs, lis, lfs))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, hd)              # back to (B,S,H,hd)
    return h


def mlstm_step(q, k, v, log_i, log_f, state):
    """One decode step. q/k/v: (B,H,hd); gates (B,H); state (C,n,m)."""
    C, n, m = state
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    m_new = jnp.maximum(log_f + m, log_i)
    wf = jnp.exp(log_f + m - m_new)
    wi = jnp.exp(log_i - m_new)
    C = C * wf[..., None, None] + \
        jnp.einsum("bhd,bhe->bhde", kf, vf) * wi[..., None, None]
    n = n * wf[..., None] + kf * wi[..., None]
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)), jnp.exp(-m_new))
    h = num / den[..., None]
    return h, (C, n, m_new)


def group_norm(x, weight, n_groups: int, eps: float = 1e-6):
    """Per-head group norm over the channel dim. x: (..., up)."""
    dt = x.dtype
    shp = x.shape
    xg = x.astype(jnp.float32).reshape(*shp[:-1], n_groups, shp[-1] // n_groups)
    mu = jnp.mean(xg, axis=-1, keepdims=True)
    var = jnp.var(xg, axis=-1, keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return (xg.reshape(shp) * weight).astype(dt)


def mlstm_block(p, x, cfg: ModelConfig, state=None):
    """x: (B,S,D). state: None | (C, n, m, conv_state)."""
    B, S, D = x.shape
    H = cfg.n_heads
    xin = L.rms_norm(x, p["ln"].astype(x.dtype), cfg.norm_eps)
    h2 = xin @ p["w_up"].astype(x.dtype)
    xm, z = jnp.split(h2, 2, axis=-1)
    up = xm.shape[-1]
    hd = up // H

    if state is None:
        xc, _ = _causal_conv(xm, p["conv_w"])
    else:
        C, n, m, conv_state = state
        xc, conv_state = _causal_conv(xm, p["conv_w"], conv_state)
    xc = jax.nn.silu(xc)
    q = (xc @ p["wq"].astype(x.dtype)).reshape(B, S, H, hd)
    k = (xc @ p["wk"].astype(x.dtype)).reshape(B, S, H, hd) / math.sqrt(hd)
    v = (xm @ p["wv"].astype(x.dtype)).reshape(B, S, H, hd)
    gates = (xc @ p["w_if"].astype(x.dtype) +
             p["b_if"].astype(x.dtype)).astype(jnp.float32)
    log_i, f_raw = jnp.split(gates, 2, axis=-1)
    log_f = jax.nn.log_sigmoid(f_raw)

    if state is None:
        h = mlstm_chunkwise(q, k, v, log_i, log_f, cfg.mlstm_chunk)
        new_state = None
    else:
        h, (C, n, m) = mlstm_step(q[:, 0], k[:, 0], v[:, 0],
                                  log_i[:, 0], log_f[:, 0], (C, n, m))
        h = h[:, None]
        new_state = (C, n, m, conv_state)

    h = h.astype(x.dtype).reshape(B, S, up)
    h = group_norm(h, p["gn"].astype(x.dtype), H)
    out = (h * jax.nn.silu(z)) @ p["w_down"].astype(x.dtype)
    return x + out, new_state


# ---------------------------------------------------------------------------
# sLSTM — true recurrence
# ---------------------------------------------------------------------------
def init_slstm_block(key, cfg: ModelConfig):
    D = cfg.d_model
    H = cfg.n_heads
    hd = D // H
    f = int(D * 4 * cfg.slstm_proj_factor / 2)  # GeGLU hidden
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.ones((D,), cfg.pdtype),
        "w_gates": L.dense_init(ks[0], (D, 4 * D), cfg.pdtype),
        "b_gates": jnp.zeros((4 * D,), cfg.pdtype),
        "r_gates": L.dense_init(ks[1], (H, hd, 4 * hd), cfg.pdtype, scale=0.01),
        "gn": jnp.ones((D,), cfg.pdtype),
        "mlp": L.init_swiglu(ks[2], D, f, cfg.pdtype),
        "ln2": jnp.ones((D,), cfg.pdtype),
    }


def _slstm_cell(p, gx, state, H: int, hd: int):
    """gx: (B, 4D) pre-activations from input; state: (c,n,m,h) each (B,H,hd)."""
    c, n, m, h = state
    rec = jnp.einsum("bhd,hde->bhe", h, p["r_gates"].astype(h.dtype))  # (B,H,4hd)
    g = gx.reshape(*gx.shape[:-1], H, 4 * hd) + rec
    zt, it, ft, ot = jnp.split(g.astype(jnp.float32), 4, axis=-1)
    zt = jnp.tanh(zt)
    ot = jax.nn.sigmoid(ot)
    m_new = jnp.maximum(jax.nn.log_sigmoid(ft) + m, it)
    i_p = jnp.exp(it - m_new)
    f_p = jnp.exp(jax.nn.log_sigmoid(ft) + m - m_new)
    c = f_p * c + i_p * zt
    n = f_p * n + i_p
    h_new = ot * c / jnp.maximum(n, 1e-6)
    return (c, n, m_new, h_new.astype(h.dtype))


def slstm_block(p, x, cfg: ModelConfig, state=None):
    B, S, D = x.shape
    H = cfg.n_heads
    hd = D // H
    xin = L.rms_norm(x, p["ln"].astype(x.dtype), cfg.norm_eps)
    gx = xin @ p["w_gates"].astype(x.dtype) + p["b_gates"].astype(x.dtype)

    if state is None:
        zeros = jnp.zeros((B, H, hd), jnp.float32)
        st = (zeros, zeros, jnp.full((B, H, hd), -1e30, jnp.float32),
              jnp.zeros((B, H, hd), x.dtype))
    else:
        st = state

    def step(carry, g_t):
        new = _slstm_cell(p, g_t, carry, H, hd)
        return new, new[3]

    st, hs = jax.lax.scan(step, st, jnp.moveaxis(gx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, D)
    h = group_norm(h, p["gn"].astype(x.dtype), H)
    x = x + h
    hn = L.rms_norm(x, p["ln2"].astype(x.dtype), cfg.norm_eps)
    return x + L.swiglu(p["mlp"], hn), st


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def _kind(i: int, cfg: ModelConfig) -> str:
    return "slstm" if i in cfg.slstm_layers else "mlstm"


def init_params(key, cfg: ModelConfig):
    ke, kh, kl = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = []
    for i in range(cfg.n_layers):
        if _kind(i, cfg) == "slstm":
            layers.append(init_slstm_block(layer_keys[i], cfg))
        else:
            layers.append(init_mlstm_block(layer_keys[i], cfg))
    return {
        "embed": L.embed_init(ke, (cfg.vocab, cfg.d_model), cfg.pdtype),
        "layers": layers,  # python list — layer kind derived from cfg.slstm_layers
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
        "lm_head": L.dense_init(kh, (cfg.d_model, cfg.vocab), cfg.pdtype),
    }


def forward_train(params, tokens, cfg: ModelConfig, positions=None,
                  last_only: bool = False):
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    for i, lp in enumerate(params["layers"]):
        fn = mlstm_block if _kind(i, cfg) == "mlstm" else slstm_block
        if cfg.remat:
            fn = jax.checkpoint(fn, static_argnums=(2,))
        x, _ = fn(lp, x, cfg)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    return x @ params["lm_head"].astype(x.dtype)


def loss_fn(params, batch, cfg: ModelConfig):
    logits = forward_train(params, batch["tokens"], cfg)
    return L.softmax_xent(logits, batch["labels"])


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    del cache_len  # O(1) state
    D, H = cfg.d_model, cfg.n_heads
    up = int(D * cfg.mlstm_proj_factor)
    hd_m = up // H
    hd_s = D // H
    cache = []
    for i in range(cfg.n_layers):
        if _kind(i, cfg) == "slstm":
            cache.append((jnp.zeros((batch, H, hd_s), jnp.float32),
                          jnp.zeros((batch, H, hd_s), jnp.float32),
                          jnp.full((batch, H, hd_s), -1e30, jnp.float32),
                          jnp.zeros((batch, H, hd_s), cfg.cdtype)))
        else:
            cache.append((jnp.zeros((batch, H, hd_m, hd_m), jnp.float32),
                          jnp.zeros((batch, H, hd_m), jnp.float32),
                          jnp.full((batch, H), -1e30, jnp.float32),
                          jnp.zeros((batch, 3, up), cfg.cdtype)))
    return cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    del pos
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    new_cache = []
    for i, (lp, st) in enumerate(zip(params["layers"], cache)):
        if _kind(i, cfg) == "mlstm":
            x, st = mlstm_block(lp, x, cfg, state=st)
        else:
            x, st = slstm_block(lp, x, cfg, state=st)
        new_cache.append(st)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return x @ params["lm_head"].astype(x.dtype), new_cache
