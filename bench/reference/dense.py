"""Plain reference of a dense decoder-only transformer (Qwen2 layout).

Pre-RMSNorm blocks; grouped-query causal attention with biases on the q, k
and v projections and rotary positions (rotate-half form, base
`rope_theta`); a SwiGLU MLP; a final RMSNorm and an output head of its own
(the head is each client's personal part, so it is not tied to the
embedding).  The configuration's keys are those of the model's published
`config.json`.  The parameter tree has the program's key names, so the
weights the benchmark makes here can be handed to the program as they are.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import einsum, matmul, softmax_xent


def _sizes(cfg: dict):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (D, H, cfg["num_key_value_heads"], D // H,
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def init(key, cfg: dict):
    D, H, Hkv, hd, F, V, L = _sizes(cfg)
    ke, kl, kh = jax.random.split(key, 3)

    def dense(k, shape):
        return jax.random.normal(k, shape) / math.sqrt(shape[-2])

    def layer(k):
        ka, km = jax.random.split(k)
        qa = jax.random.split(ka, 4)
        qm = jax.random.split(km, 3)
        return {
            "ln1": jnp.ones((D,)),
            "attn": {"wq": dense(qa[0], (D, H * hd)),
                     "wk": dense(qa[1], (D, Hkv * hd)),
                     "wv": dense(qa[2], (D, Hkv * hd)),
                     "wo": dense(qa[3], (H * hd, D)),
                     "bq": jnp.zeros((H * hd,)),
                     "bk": jnp.zeros((Hkv * hd,)),
                     "bv": jnp.zeros((Hkv * hd,))},
            "ln2": jnp.ones((D,)),
            "mlp": {"wg": dense(qm[0], (D, F)), "wu": dense(qm[1], (D, F)),
                    "wd": dense(qm[2], (F, D))},
        }

    return {
        "embed": jax.random.normal(ke, (V, D)) * 0.02,
        "layers": jax.vmap(layer)(jax.random.split(kl, L)),
        "final_norm": jnp.ones((D,)),
        "lm_head": dense(kh, (D, V)),
    }


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, heads, hd); rotate-half rotary positions."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(x, lp, cfg, q):
    D, H, Hkv, hd, _, _, _ = _sizes(cfg)
    B, S, _ = x.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = lp["attn"]
    h = _rms_norm(x, lp["ln1"], eps)
    qh = (matmul(h, a["wq"], q) + a["bq"]).reshape(B, S, H, hd)
    kh = (matmul(h, a["wk"], q) + a["bk"]).reshape(B, S, Hkv, hd)
    vh = (matmul(h, a["wv"], q) + a["bv"]).reshape(B, S, Hkv, hd)
    qh, kh = _rope(qh, theta), _rope(kh, theta)
    qg = qh.reshape(B, S, Hkv, H // Hkv, hd)
    s = einsum("bqhgd,bkhd->bhgqk", qg, kh, q) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = einsum("bhgqk,bkhd->bqhgd", p, vh, q).reshape(B, S, H * hd)
    x = x + matmul(o, a["wo"], q)
    h = _rms_norm(x, lp["ln2"], eps)
    m = lp["mlp"]
    up = jax.nn.silu(matmul(h, m["wg"], q)) * matmul(h, m["wu"], q)
    return x + matmul(up, m["wd"], q)


def loss(p, batch, cfg: dict, q):
    """Mean next-token loss.  Each block's activations are recomputed in
    the backward pass (the same arithmetic, so that the reference of a
    whole client fits one chip beside the other clients' state)."""
    x = p["embed"][batch["tokens"]]

    @jax.checkpoint
    def body(x, lp):
        return _block(x, lp, cfg, q), None

    x, _ = jax.lax.scan(body, x, p["layers"])
    x = _rms_norm(x, p["final_norm"], cfg["rms_norm_eps"])
    return softmax_xent(matmul(x, p["lm_head"], q), batch["labels"])
