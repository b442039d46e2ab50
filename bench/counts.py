"""Operations of a round, worked out from the shapes alone.

Only matrix products are counted (2 operations per multiply-add).  A step
counts what its gradient requires and nothing that an implementation
recomputes:

- a u-step (shared part) runs the whole forward pass, the input gradient
  of every layer that has a trained layer below it, and the weight
  gradient of every shared layer;
- a v-step (personal head, shared part pinned) runs the whole forward pass
  and the head's weight gradient.

Attention counts both of its products over the full sequence, forward 4 S
H hd and backward 8 S H hd a token, as PaLM's utilization does
(arXiv:2204.02311, appendix B).
"""
from __future__ import annotations


# ---------------------------------------------------------------------------
# dense transformers (Qwen2 layout)
# ---------------------------------------------------------------------------
def _lm_sizes(cfg: dict):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (D, H, cfg["num_key_value_heads"], D // H,
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def lm_layer_matmul_params(cfg: dict) -> int:
    D, H, Hkv, hd, F, _, _ = _lm_sizes(cfg)
    return 2 * D * H * hd + 2 * D * Hkv * hd + 3 * D * F


def lm_layer_params(cfg: dict) -> int:
    """Every parameter of one block: projections, q/k/v biases, norms."""
    D, H, Hkv, hd, _, _, _ = _lm_sizes(cfg)
    return lm_layer_matmul_params(cfg) + H * hd + 2 * Hkv * hd + 2 * D


def lm_d_flat(cfg: dict) -> int:
    """Parameters of the shared part: the blocks and the embedding."""
    D, _, _, _, _, V, L = _lm_sizes(cfg)
    return L * lm_layer_params(cfg) + V * D


def lm_step_ops(cfg: dict, seq: int) -> dict:
    """Operations per token of one u-step and one v-step."""
    D, H, _, hd, _, V, L = _lm_sizes(cfg)
    p = lm_layer_matmul_params(cfg)
    attn = 4 * seq * H * hd
    head = 2 * D * V
    return {"u": L * (3 * 2 * p + 3 * attn) + 2 * head,
            "v": L * (2 * p + attn) + 2 * head}


# ---------------------------------------------------------------------------
# a round
# ---------------------------------------------------------------------------
def round_ops(cfg: dict, traffic: dict) -> float:
    """Operations one round of the cell requires, over all its clients."""
    ops = lm_step_ops(cfg, traffic["seq"])
    per = traffic["clients"] * traffic["batch"] * traffic["seq"]
    return per * (traffic["k_u"] * ops["u"] + traffic["k_v"] * ops["v"])


def round_tokens(traffic: dict) -> int:
    """Tokens of every local step of a round (u and v), all clients."""
    return (traffic["clients"] * traffic["batch"] * traffic["seq"]
            * (traffic["k_u"] + traffic["k_v"]))
