"""Operations from shapes, against values worked by hand."""
import jax
import numpy as np
import pytest

from bench import counts
from bench.drivers import _common
from bench.run import BENCH, load_json

QWEN = load_json(BENCH / "configs" / "qwen2-0.5b.json")
TINY_LM = dict(QWEN, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=128, vocab_size=256,
               num_hidden_layers=2)


def test_qwen2_layer_params():
    # q and o 896x896, k and v 896x128, their biases, SwiGLU 3x896x4864, norms
    assert counts.lm_layer_params(QWEN) == (
        2 * 896 * 896 + 2 * 896 * 128 + (896 + 2 * 128)
        + 3 * 896 * 4864 + 2 * 896) == 14_912_384
    assert counts.lm_d_flat(QWEN) == 24 * 14_912_384 + 151_936 * 896


@pytest.mark.parametrize("cfg", [QWEN, TINY_LM], ids=["qwen2", "tiny-lm"])
def test_d_flat_is_the_reference_shared_part(cfg):
    layout, d = _common.shared_layout(cfg)
    assert d == counts.lm_d_flat(cfg)
    assert [p for p, _, _ in layout] == sorted(p for p, _, _ in layout)


def test_lm_step_ops_by_hand():
    p = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128      # 36 864
    attn = 4 * 16 * 4 * 16                           # 4 S H hd, S = 16
    head = 2 * 64 * 256
    ops = counts.lm_step_ops(TINY_LM, 16)
    assert ops["u"] == 2 * (6 * p + 3 * attn) + 2 * head == 532480
    assert ops["v"] == 2 * (2 * p + attn) + 2 * head == 221184


def test_round_ops_scale_with_clients_steps_and_batch():
    lm = load_json(BENCH / "traffic" / "ici4.json")
    o = counts.lm_step_ops(QWEN, 512)
    assert counts.round_ops(QWEN, lm) == 4 * 2 * 512 * (o["u"] + o["v"])
    assert counts.round_tokens(lm) == 8192
    more = dict(lm, clients=8, batch=4, k_u=2)
    assert counts.round_ops(QWEN, more) == 8 * 4 * 512 * (2 * o["u"] + o["v"])
    assert counts.round_tokens(more) == 8 * 4 * 512 * 3


def test_shared_layout_matches_the_programs_flat_buffer():
    from repro.core import gossip, partition
    init = _common.stacked_init(TINY_LM, 3)
    stacked = jax.eval_shape(init, jax.random.PRNGKey(0))
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:],
                                                           x.dtype), stacked)
    mask = partition.build_mask(template, partition.classifier_personal)
    layout = gossip.FlatLayout.build(stacked, mask)
    ours, d = _common.shared_layout(TINY_LM)
    assert layout.d_flat == d
    assert [tuple(s) for s in layout.shapes] == [
        tuple(s) for _, _, s in ours]
    assert np.array_equal(np.cumsum([0] + [int(np.prod(s)) for _, _, s in
                                           ours])[:-1],
                          [o for _, o, _ in ours])
