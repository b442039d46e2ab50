"""Serving path acceptance (PR 7, docs/serve.md).

The contracts ISSUE.md pins:

1. all three converter forms — resident FlatDFedPGPState, tree-form
   DFedPGPState, Regime B checkpoint directory — yield BIT-FOR-BIT
   identical ServingStates;
2. served logits are bit-for-bit `eval_params_flat`'s per-user evaluation
   (anchor consensus on an exactly-consensused run);
3. the fused pallas kernel matches the jnp oracle in interpret mode at
   awkward (non-multiple-of-8/128) shapes, f32 and bf16 features;
4. a mixed-user batch is permutation-invariant.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import serve
from repro.checkpoint import save_train_state
from repro.core import dfedpgp, partition
from repro.kernels import ops, ref
from repro.kernels.head_gather import head_gather_matmul_pallas
from repro.models import cnn
from repro.optim import SGD

M, B = 5, 12
CFG = cnn.CNNConfig(image_size=8, n_classes=10)


def _algo():
    def loss_fn(p, batch):
        return cnn.loss_fn(p, batch, CFG)

    template = cnn.init_params(jax.random.PRNGKey(0), CFG)
    mask = partition.build_mask(template, partition.classifier_personal)
    return dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=SGD(lr=0.1),
                           opt_v=SGD(lr=0.1)), mask


def _trained_like_state(key=0):
    """A FlatDFedPGPState with non-trivial buffer/mu/personal values (as
    if mid-training) + its layout and the algo that owns it."""
    algo, mask = _algo()
    stacked = jax.vmap(lambda k: cnn.init_params(k, CFG))(
        jax.random.split(jax.random.PRNGKey(key), M))
    state, layout = algo.init_flat(stacked)
    kf, km = jax.random.split(jax.random.PRNGKey(key + 100))
    state = state._replace(
        flat=state.flat + 0.1 * jax.random.normal(kf, state.flat.shape),
        mu=jnp.abs(1.0 + 0.3 * jax.random.normal(km, state.mu.shape)))
    return algo, mask, state, layout


def _consensused_state(key=0):
    """Every row identical, mu uniform: an exactly-consensused run — the
    regime where anchor serving is bit-for-bit ANY client's eval."""
    algo, mask, state, layout = _trained_like_state(key)
    state = state._replace(
        flat=jnp.tile(state.flat[0:1], (M, 1)),
        mu=jnp.full_like(state.mu, 1.37))
    return algo, mask, state, layout


def _assert_trees_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# converters
# ---------------------------------------------------------------------------
def test_converter_forms_bitwise_identical(tmp_path):
    """ACCEPTANCE: flat state, tree state and checkpoint restore all
    produce the same ServingState bits."""
    algo, mask, fstate, layout = _trained_like_state()

    ss_flat = serve.from_train_state(fstate, layout=layout,
                                     consensus="mass")
    tree = algo.state_from_flat(fstate, layout)
    ss_tree = serve.from_train_state(tree, mask=mask, consensus="mass")

    save_train_state(str(tmp_path), 7, fstate)
    ss_ckpt, step = serve.from_checkpoint(str(tmp_path), fstate,
                                          layout=layout, consensus="mass")
    assert step == 7

    _assert_trees_bitwise(ss_flat, ss_tree)
    _assert_trees_bitwise(ss_flat, ss_ckpt)
    assert ss_flat.n_users() == M


def test_converter_guards(tmp_path):
    algo, mask, fstate, layout = _trained_like_state()
    with pytest.raises(ValueError, match="FlatLayout"):
        serve.from_train_state(fstate)
    tree = algo.state_from_flat(fstate, layout)
    with pytest.raises(ValueError, match="mask"):
        serve.from_train_state(tree)
    with pytest.raises(TypeError):
        serve.from_train_state({"params": 1})
    with pytest.raises(ValueError, match="consensus"):
        serve.from_train_state(fstate, layout=layout, consensus="median")
    with pytest.raises(FileNotFoundError):
        serve.from_checkpoint(str(tmp_path), fstate, layout=layout)


def test_consensus_modes_agree_when_consensused():
    """On an exactly-consensused buffer the anchor, mass and mean trunks
    are the same model (mass/mean go through f32, so allclose)."""
    _, _, state, layout = _consensused_state()
    anchor = serve.from_train_state(state, layout=layout, consensus=0)
    for mode in serve.CONSENSUS_MODES:
        other = serve.from_train_state(state, layout=layout,
                                       consensus=mode)
        for a, b in zip(jax.tree.leaves(anchor.trunk),
                        jax.tree.leaves(other.trunk)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# served logits == eval_params_flat logits (to a few f32 ulps)
# ---------------------------------------------------------------------------
def test_served_logits_equal_eval_bitwise():
    """ACCEPTANCE: for every request, serve_logits returns the logits row
    that user's eval_params_flat model computes on the same batch, to a few
    f32 ulps.  Fused serve and the vmapped eval are different XLA
    programs, so the compiler may fuse, and round, their sums differently;
    the semantics guarantee equal math, not equal bits."""
    algo, mask, state, layout = _consensused_state()
    sstate = serve.from_train_state(state, layout=layout, consensus=0)

    kx, ku = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (B, CFG.image_size, CFG.image_size, 3))
    uid = jax.random.randint(ku, (B,), 0, M, jnp.int32)

    got = serve.serve_logits(sstate, uid, x, CFG, force="ref")

    params_m = algo.eval_params_flat(state, layout)
    # every user's personalized model evaluated on the SAME full batch
    # (CNN features are bitwise batch-composition-dependent, so the
    # comparison keeps the batch identical and selects rows after)
    all_logits = jax.vmap(lambda p: cnn.logits_fn(p, x, CFG))(params_m)
    want = np.asarray(all_logits[uid, jnp.arange(B)])
    assert got.dtype == jnp.float32
    ulp = np.finfo(np.float32).eps * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=4 * ulp)


def test_serve_matches_user_model_and_naive():
    """serve_naive (the seed-era m-replica path) and the per-user model
    agree bitwise with the fused path on the consensused state."""
    algo, mask, state, layout = _consensused_state(key=2)
    sstate = serve.from_train_state(state, layout=layout, consensus=0)
    models = algo.eval_params_flat(state, layout)

    kx, ku = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (B, CFG.image_size, CFG.image_size, 3))
    uid = jax.random.randint(ku, (B,), 0, M, jnp.int32)

    fused = serve.serve_logits(sstate, uid, x, CFG, force="ref")
    naive = serve.serve_naive(models, uid, x, CFG)
    # the naive path runs one row per forward; conv features are bitwise
    # batch-size dependent, so fused-vs-naive is allclose, not bitwise
    np.testing.assert_allclose(np.asarray(fused), np.asarray(naive),
                               rtol=1e-5, atol=1e-5)


def test_mixed_user_batch_permutation_invariant():
    """Request order must not change any request's logits (bitwise)."""
    _, _, state, layout = _consensused_state(key=1)
    sstate = serve.from_train_state(state, layout=layout, consensus=0)
    kx, ku = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.normal(kx, (B, CFG.image_size, CFG.image_size, 3))
    uid = jax.random.randint(ku, (B,), 0, M, jnp.int32)
    perm = jax.random.permutation(jax.random.PRNGKey(12), B)

    base = serve.serve_logits(sstate, uid, x, CFG, force="ref")
    shuf = serve.serve_logits(sstate, uid[perm], x[perm], CFG, force="ref")
    np.testing.assert_array_equal(np.asarray(base[perm]),
                                  np.asarray(shuf))


# ---------------------------------------------------------------------------
# fused kernel parity (interpret mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [
    (3, 17, 5, 1),        # B < block, tiny d/n, one user
    (8, 64, 10, 7),       # aligned batch, awkward n
    (5, 33, 130, 64),     # n crosses one 128 lane tile
    (16, 8, 257, 9),      # d below one sublane tile, n crosses two tiles
])
@pytest.mark.parametrize("h_dtype", [jnp.float32, jnp.bfloat16])
def test_head_gather_kernel_parity(shape, h_dtype):
    """Pallas (interpret) vs the jnp oracle at awkward shapes — incl. the
    bf16-trunk/f32-head mix the LM serve path uses."""
    Bb, d, n, m = shape
    kh, kw, kb, ku = jax.random.split(jax.random.PRNGKey(hash(shape) % 997),
                                      4)
    H = jax.random.normal(kh, (Bb, d)).astype(h_dtype)
    W = jax.random.normal(kw, (m, d, n), jnp.float32)
    bias = jax.random.normal(kb, (m, n), jnp.float32)
    uid = jax.random.randint(ku, (Bb,), 0, m, jnp.int32)

    want = ref.head_gather_matmul_ref(uid, H, W, bias)
    got = head_gather_matmul_pallas(uid, H, W, bias, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    tol = 2e-2 if h_dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_head_gather_dispatch_and_loud_knob():
    uid = jnp.zeros((4,), jnp.int32)
    H = jnp.ones((4, 8))
    W = jnp.ones((2, 8, 3))
    b = jnp.zeros((2, 3))
    out = ops.head_gather_matmul(uid, H, W, b)      # auto -> ref off-TPU
    np.testing.assert_allclose(np.asarray(out), 8.0)
    with pytest.raises(ValueError, match="block_b"):
        ops.head_gather_matmul(uid, H, W, b, force="ref", block_b=8)
