"""Resident flat-buffer path: FlatLayout/FlatClientState semantics and the
bit-for-bit regression of round_fn_flat / run_experiment(resident=True)
against the pre-refactor per-round-flatten path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dfedpgp, gossip, topology
from repro.fl.simulator import SimConfig, run_experiment
from repro.optim import SGD


def _tree(key, m):
    ks = jax.random.split(key, 3)
    params = {"body": jax.random.normal(ks[0], (m, 4, 3)),
              "gn": jax.random.normal(ks[1], (m, 5)),
              "head": jax.random.normal(ks[2], (m, 2))}
    mask = {"body": True, "gn": True, "head": False}
    return params, mask


# ---------------------------------------------------------------------------
# FlatLayout / FlatClientState
# ---------------------------------------------------------------------------
def test_flat_layout_roundtrip():
    params, mask = _tree(jax.random.PRNGKey(0), 6)
    layout = gossip.FlatLayout.build(params, mask)
    assert layout.d_flat == 17
    flat = layout.pack(params, mask)
    np.testing.assert_array_equal(
        np.asarray(flat), np.asarray(gossip.flatten_shared(params, mask)))
    back = layout.unravel(flat)
    for k in ("body", "gn"):
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))
    assert back["head"] is None
    row = layout.unravel_row(flat[2])
    np.testing.assert_array_equal(np.asarray(row["body"]),
                                  np.asarray(params["body"][2]))


def test_flat_client_state_to_tree():
    params, mask = _tree(jax.random.PRNGKey(1), 5)
    st, layout = gossip.FlatClientState.create(params, mask)
    back = st.to_tree(layout)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))


_ODD_SHAPES = [(), (3,), (5, 7), (1,), (2, 3, 5), (11,), (13, 1), (4, 9),
               (7,)]


def _odd_layout(m):
    """Nine shared leaves of odd sizes (a 0-d one, a bfloat16 one) around a
    personal leaf."""
    ks = jax.random.split(jax.random.PRNGKey(5), len(_ODD_SHAPES) + 1)
    params = {f"s{i}": jax.random.normal(k, (m,) + s)
              for i, (k, s) in enumerate(zip(ks, _ODD_SHAPES))}
    params["s4"] = params["s4"].astype(jnp.bfloat16)
    params["head"] = jax.random.normal(ks[-1], (m, 2))
    mask = {k: k != "head" for k in params}
    return params, mask, gossip.FlatLayout.build(params, mask)


def _leaf_loss(shared):
    return sum(jnp.sum(jnp.sin(x.astype(jnp.float32)) * (i + 1.5))
               for i, x in enumerate(jax.tree.leaves(shared)))


def _concat_leaf_grads(layout, rows):
    """The reference row gradient: the tree-form gradient of each leaf at
    the row's views, concatenated."""
    g = jax.vmap(jax.grad(_leaf_loss))(layout.unravel(rows))
    return jnp.concatenate([x.reshape(rows.shape[0], -1).astype(rows.dtype)
                            for x in jax.tree.leaves(g)], axis=1)


def test_unravel_row_forward_is_the_slices():
    params, mask, layout = _odd_layout(3)
    flat = layout.pack(params, mask)
    assert len(layout.sizes) == 9 and layout.d_flat == flat.shape[1]
    fwd_row = jax.jit(jax.vmap(layout.unravel_row))(flat)
    fwd = jax.jit(layout.unravel)(flat)
    off = 0
    for k, shape, n in zip(sorted(k for k in params if k != "head"),
                           layout.shapes, layout.sizes):
        want = flat[:, off:off + n].reshape((3,) + shape).astype(
            params[k].dtype)
        off += n
        assert fwd_row[k].dtype == fwd[k].dtype == params[k].dtype
        np.testing.assert_array_equal(np.asarray(fwd_row[k]),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(fwd[k]), np.asarray(want))


def test_unravel_row_grad_is_concat_of_leaf_grads():
    """The row gradient under vmap over clients, and along a scan of SGD
    steps, equals the per-leaf gradients concatenated, bit for bit."""
    params, mask, layout = _odd_layout(4)
    flat = layout.pack(params, mask)
    row_grad = jax.vmap(jax.grad(lambda r: _leaf_loss(layout.unravel_row(r))))

    got = jax.jit(row_grad)(flat)
    want = jax.jit(lambda f: _concat_leaf_grads(layout, f))(flat)
    assert got.shape == flat.shape and got.dtype == flat.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def steps(grad_fn):
        def step(rows, _):
            g = grad_fn(rows)
            return rows - 0.1 * g, g
        return jax.jit(lambda f: jax.lax.scan(step, f, None, length=3))

    rows_got, gs_got = steps(row_grad)(flat)
    rows_want, gs_want = steps(lambda f: _concat_leaf_grads(layout, f))(flat)
    np.testing.assert_array_equal(np.asarray(gs_got), np.asarray(gs_want))
    np.testing.assert_array_equal(np.asarray(rows_got),
                                  np.asarray(rows_want))


@pytest.mark.parametrize("mode", ["dense", "sparse", "pallas"])
def test_mix_flat_matches_tree_gossip(mode):
    params, mask = _tree(jax.random.PRNGKey(2), 9)
    topo = topology.directed_random(jax.random.PRNGKey(3), 9, 3)
    mu = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (9,))) + 0.5
    layout = gossip.FlatLayout.build(params, mask)
    flat = layout.pack(params, mask)
    f2, mu2 = gossip.mix_flat(topo, flat, mu, mode=mode)
    pt, mut = gossip.gossip_mix(params, mu, topo, mask,
                                mode=mode if mode != "dense" else "sparse")
    want = gossip.flatten_shared(pt, mask)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mu2), np.asarray(mut), atol=1e-6)


def test_mix_flat_wire_dtype_keeps_resident_dtype():
    params, mask = _tree(jax.random.PRNGKey(5), 8)
    layout = gossip.FlatLayout.build(params, mask)
    flat = layout.pack(params, mask)
    topo = topology.directed_random(jax.random.PRNGKey(6), 8, 3)
    f2, _ = gossip.mix_flat(topo, flat, jnp.ones((8,)), mode="sparse",
                            wire_dtype="bfloat16")
    assert f2.dtype == flat.dtype
    f32, _ = gossip.mix_flat(topo, flat, jnp.ones((8,)), mode="sparse")
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f32), rtol=2e-2,
                               atol=2e-2)


def test_mix_flat_rejects_unknown_mode():
    with pytest.raises(ValueError):
        gossip.mix_flat(topology.ring(4), jnp.ones((4, 3)), jnp.ones((4,)),
                        mode="carrier-pigeon")


# ---------------------------------------------------------------------------
# DFedPGP resident rounds == tree rounds, bit for bit
# ---------------------------------------------------------------------------
def _quad(m=8, d=6, dp=3):
    key = jax.random.PRNGKey(0)
    cu = jax.random.normal(key, (m, d))
    cv = jax.random.normal(jax.random.fold_in(key, 1), (m, dp))

    def loss_fn(p, b):
        return jnp.sum((p["body"] - b["tu"][0]) ** 2) + \
            jnp.sum((p["head"] - b["tv"][0]) ** 2)

    return loss_fn, {"body": True, "head": False}, cu, cv


def _batches(cu, cv, k):
    rep = lambda x: jnp.repeat(x[:, None], k, 1)[..., None, :]
    return {"v": {"tu": rep(cu), "tv": rep(cv)},
            "u": {"tu": rep(cu), "tv": rep(cv)}}


def test_round_fn_flat_bitwise_equals_round_fn():
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
                           k_v=1, k_u=2, lr_decay=0.99)
    s_tree = algo.init({"body": cu, "head": cv})
    s_flat, layout = algo.init_flat({"body": cu, "head": cv})
    sched = topology.TopologySchedule.random(m, 3, seed=13)
    for t in range(3):
        topo = sched.at(t)
        b = _batches(cu, cv, 2)
        s_tree, mt = algo.round_fn(s_tree, topo, b)
        s_flat, mf = jax.jit(
            lambda s, p, bb: algo.round_fn_flat(s, p, bb, layout))(
                s_flat, topo, b)
        for k in mt:
            np.testing.assert_allclose(float(mt[k]), float(mf[k]), atol=1e-6)
    back = algo.state_from_flat(s_flat, layout)
    for k in ("body", "head"):
        np.testing.assert_array_equal(np.asarray(back.params[k]),
                                      np.asarray(s_tree.params[k]))
    np.testing.assert_array_equal(np.asarray(back.mu),
                                  np.asarray(s_tree.mu))
    np.testing.assert_array_equal(
        np.asarray(s_flat.opt_u.momentum),
        np.asarray(s_tree.opt_u.momentum["body"]).reshape(m, -1))


def test_round_fn_flat_matches_tree_when_mu_drifts():
    """Column-stochastic (push) mixing drifts mu away from 1 — the regime
    where the de-bias actually matters.  The flat path's u-gradient must be
    EVALUATED AT z = u/mu and applied to the biased row (Algorithm 1),
    exactly like the tree path — not differentiated through the de-bias
    (which would scale it by 1/mu and silently diverge)."""
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
                           k_v=1, k_u=2, lr_decay=0.99)
    s_tree = algo.init({"body": cu, "head": cv})
    s_flat, layout = algo.init_flat({"body": cu, "head": cv})
    for t in range(3):
        P_push = topology.to_column_stochastic(
            topology.directed_random(jax.random.PRNGKey(70 + t), m, 3))
        b = _batches(cu, cv, 2)
        s_tree, _ = algo.round_fn(s_tree, P_push, b)
        s_flat, _ = algo.round_fn_flat(s_flat, P_push, b, layout)
    # mu must actually have drifted, or this test proves nothing
    assert np.abs(np.asarray(s_tree.mu) - 1.0).max() > 1e-3
    np.testing.assert_allclose(np.asarray(s_flat.mu),
                               np.asarray(s_tree.mu), atol=1e-6)
    back = algo.state_from_flat(s_flat, layout)
    np.testing.assert_allclose(np.asarray(back.params["body"]),
                               np.asarray(s_tree.params["body"]), atol=1e-6)


def test_full_graph_mix_flat_densifies_not_unrolls():
    """k == m sparse topologies (fully_connected) take the dense einsum
    inside mix_flat/mix_any — same numerics, no k-term unrolled trace."""
    fc = topology.fully_connected(8)
    flat = jax.random.normal(jax.random.PRNGKey(0), (8, 5))
    mu = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (8,))) + 0.5
    f2, mu2 = gossip.mix_flat(fc, flat, mu, mode="sparse")
    np.testing.assert_allclose(np.asarray(f2),
                               np.asarray(fc.dense() @ flat), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mu2),
                               np.asarray(fc.dense() @ mu), atol=1e-6)


def test_state_converters_roundtrip():
    loss_fn, mask, cu, cv = _quad()
    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt)
    state = algo.init({"body": cu, "head": cv})
    # put some structure into the momentum before converting
    state, _ = algo.round_fn(state, topology.ring(cu.shape[0]),
                             _batches(cu, cv, 5))
    fstate, layout = algo.state_to_flat(state)
    back = algo.state_from_flat(fstate, layout)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_fn_flat_rejects_mix_fn():
    loss_fn, mask, cu, cv = _quad()
    opt = SGD(lr=0.1)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
                           mix_fn=lambda p, mu, r, P: (p, mu))
    s, layout = algo.init_flat({"body": cu, "head": cv})
    with pytest.raises(ValueError):
        algo.round_fn_flat(s, topology.ring(cu.shape[0]),
                           _batches(cu, cv, 1), layout)


def test_init_flat_rejects_mixed_shared_dtypes():
    """The buffer carries ONE dtype while the tree path accumulates per
    leaf — mixed shared dtypes would silently break bit-compatibility, so
    init_flat refuses them (mixed-dtype models use the tree path)."""
    algo = dfedpgp.DFedPGP(loss_fn=lambda p, b: 0.0,
                           mask={"a": True, "b": True, "c": False},
                           opt_u=SGD(), opt_v=SGD())
    with pytest.raises(ValueError, match="uniform shared-leaf dtype"):
        algo.init_flat({"a": jnp.zeros((4, 3), jnp.bfloat16),
                        "b": jnp.zeros((4, 2), jnp.float32),
                        "c": jnp.zeros((4, 1))})


def test_all_personal_mask_degenerate():
    """d_flat == 0: the resident buffer is empty, rounds still run and only
    mu mixes."""
    loss_fn, _, cu, cv = _quad()
    mask = {"body": False, "head": False}
    opt = SGD(lr=0.1, momentum=0.0, weight_decay=0.0)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
                           k_v=1, k_u=1, lr_decay=1.0)
    s, layout = algo.init_flat({"body": cu, "head": cv})
    assert layout.d_flat == 0 and s.flat.shape == (cu.shape[0], 0)
    topo = topology.directed_random(jax.random.PRNGKey(0), cu.shape[0], 2)
    s2, _ = algo.round_fn_flat(s, topo, _batches(cu, cv, 1), layout)
    np.testing.assert_allclose(np.asarray(s2.mu), np.asarray(topo @ s.mu),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# acceptance: run_experiment resident == pre-refactor path, bit for bit
# ---------------------------------------------------------------------------
def test_run_experiment_resident_bitwise_regression():
    """3 rounds of dfedpgp through the full simulator: the resident buffer
    and the pre-refactor per-round-flatten path produce identical
    personalized models, bit for bit."""
    sim = SimConfig(m=6, rounds=3, n_neighbors=2, n_train=16, n_test=8,
                    batch=8, k_local=2, k_personal=1)
    h_res = run_experiment("dfedpgp", sim, eval_every=1, return_params=True)
    h_leg = run_experiment("dfedpgp", dataclasses.replace(sim,
                                                          resident=False),
                           eval_every=1, return_params=True)
    assert h_res["acc"] == h_leg["acc"]
    for a, b in zip(jax.tree.leaves(h_res["params"]),
                    jax.tree.leaves(h_leg["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
