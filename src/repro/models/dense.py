"""Dense decoder-only transformer family.

Covers: qwen2-0.5b [arXiv:2407.10671] (GQA + QKV bias),
granite-3-2b [hf:ibm-granite/granite-3.0-2b-base] (GQA),
codeqwen1.5-7b [hf:Qwen/CodeQwen1.5-7B] (qwen1.5 arch),
h2o-danube-1.8b [arXiv:2401.16818] (llama/mistral mix with sliding-window attn).

Layout: pre-RMSNorm blocks, SwiGLU MLP, RoPE, scan-over-layers with stacked
weights so that a 60-layer model compiles as one loop.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig


def init_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.pdtype),
        "attn": L.init_attention(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.pdtype),
        "mlp": L.init_swiglu(k2, cfg.d_model, cfg.d_ff, cfg.pdtype),
    }


def init_params(key, cfg: ModelConfig):
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params = {
        "embed": L.embed_init(ke, (cfg.vocab, cfg.d_model), cfg.pdtype),
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
        "lm_head": L.dense_init(kh, (cfg.d_model, cfg.vocab), cfg.pdtype),
    }
    return params


def _block(lp, x, positions, cfg: ModelConfig):
    h = L.rms_norm(x, lp["ln1"].astype(x.dtype), cfg.norm_eps)
    x = x + L.attention_train(lp["attn"], h, positions, cfg, window=cfg.window)
    h = L.rms_norm(x, lp["ln2"].astype(x.dtype), cfg.norm_eps)
    return x + L.swiglu(lp["mlp"], h)


def backbone(params, x, positions, cfg: ModelConfig):
    """x: (B, S, D) embeddings -> (B, S, D) features."""
    blk = _block
    if cfg.remat:
        blk = jax.checkpoint(_block, static_argnums=(3,))

    def body(h, lp):
        return blk(lp, h, positions, cfg), None

    x, _ = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    return L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)


def forward_train(params, tokens, cfg: ModelConfig, positions=None,
                  last_only: bool = False):
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    x = backbone(params, x, positions, cfg)
    if last_only:          # prefill: sample only the next token
        x = x[:, -1:]
    return x @ params["lm_head"].astype(x.dtype)


def loss_fn(params, batch, cfg: ModelConfig):
    logits = forward_train(params, batch["tokens"], cfg)
    return L.softmax_xent(logits, batch["labels"])


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    C = min(cache_len, cfg.window) if cfg.window else cache_len
    shape = (cfg.n_layers, batch, C, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_quant:
        # int8 cache + per-(token, head) f32 scales: 2.06 bytes/elem-pair
        # instead of bf16's 4 — the §Perf H3 memory-term optimization.
        sshape = shape[:-1]
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.float32),
                "v_s": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, cfg.cdtype), "v": jnp.zeros(shape, cfg.cdtype)}


def _quantize(x):
    """x: (B, 1, H, hd) -> (int8 values, f32 scales (B, 1, H))."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


def _decode_step_quant(params, cache, tokens, pos, cfg: ModelConfig):
    """int8-KV decode: dequantization fuses into the attention matmul, so
    HBM traffic per step is the int8 cache + scales, not a bf16 cache."""
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)
    B = tokens.shape[0]

    def body(h, lc):
        lp, ck, cv, ks, vs = lc
        hn = L.rms_norm(h, lp["ln1"].astype(h.dtype), cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], hn, cfg)
        posv = jnp.full((B, 1), pos, dtype=jnp.int32)
        if cfg.rope_theta > 0:
            q = L.apply_rope(q, posv, cfg.rope_theta)
            k = L.apply_rope(k, posv, cfg.rope_theta)
        C = ck.shape[1]
        slot = jnp.mod(pos, C) if cfg.window else jnp.minimum(pos, C - 1)
        kq, ksc = _quantize(k)
        vq, vsc = _quantize(v)
        upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
            buf, val.astype(buf.dtype), slot, axis=1)
        ck, cv, ks, vs = upd(ck, kq), upd(cv, vq), upd(ks, ksc), upd(vs, vsc)
        kf = ck.astype(q.dtype) * ks[..., None].astype(q.dtype)
        vf = cv.astype(q.dtype) * vs[..., None].astype(q.dtype)
        idx = jnp.arange(C)
        if cfg.window:
            n_wraps = pos // C
            kpos = jnp.where(idx <= jnp.mod(pos, C), idx + n_wraps * C,
                             idx + (n_wraps - 1) * C)
            valid = (kpos >= 0) & (kpos <= pos) & (kpos > pos - cfg.window)
        else:
            valid = idx <= jnp.minimum(pos, C - 1)
        a = L.gqa_attend(q, kf, vf, valid[None, :])
        h = h + a.reshape(B, 1, -1) @ lp["attn"]["wo"].astype(h.dtype)
        hn = L.rms_norm(h, lp["ln2"].astype(h.dtype), cfg.norm_eps)
        return h + L.swiglu(lp["mlp"], hn), (ck, cv, ks, vs)

    x, (nk, nv, nks, nvs) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"],
                  cache["k_s"], cache["v_s"]), unroll=cfg.scan_unroll)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits, {"k": nk, "v": nv, "k_s": nks, "v_s": nvs}


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    """tokens: (B, 1); pos: scalar int32 (current absolute position)."""
    if cfg.kv_quant:
        return _decode_step_quant(params, cache, tokens, pos, cfg)
    x = L.embed_tokens(params["embed"], tokens, cfg.cdtype)

    def body(h, lp_and_cache):
        lp, ck, cv = lp_and_cache
        hn = L.rms_norm(h, lp["ln1"].astype(h.dtype), cfg.norm_eps)
        a, ck, cv = L.attention_decode(lp["attn"], hn, pos, ck, cv, cfg,
                                       window=cfg.window)
        h = h + a
        hn = L.rms_norm(h, lp["ln2"].astype(h.dtype), cfg.norm_eps)
        h = h + L.swiglu(lp["mlp"], hn)
        return h, (ck, cv)

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]),
                               unroll=cfg.scan_unroll)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits, {"k": nk, "v": nv}
