"""Checkpoint round-trips for the resident and async engines
(repro.checkpoint): restore mid-experiment and continue BIT-FOR-BIT —
FlatDFedPGPState (incl. wire-codec ef/ref memory) and the full async
runtime state (profiles + virtual clock + mailbox ring)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compress
from repro.checkpoint import load_pytree, save_pytree
from repro.core import dfedpgp, topology
from repro.hetero import profiles
from repro.hetero.runtime import AsyncRuntime
from repro.launch.mesh import make_mesh
from repro.optim import SGD


def _quad(m=8, d=6, dp=3):
    key = jax.random.PRNGKey(0)
    cu = jax.random.normal(key, (m, d))
    cv = jax.random.normal(jax.random.fold_in(key, 1), (m, dp))

    def loss_fn(p, b):
        return jnp.sum((p["body"] - b["tu"][0]) ** 2) + \
            jnp.sum((p["head"] - b["tv"][0]) ** 2)

    return loss_fn, {"body": True, "head": False}, cu, cv


def _batches(cu, cv, kv, ku):
    rep = lambda x, k: jnp.repeat(x[:, None], k, 1)[..., None, :]
    return {"v": {"tu": rep(cu, kv), "tv": rep(cv, kv)},
            "u": {"tu": rep(cu, ku), "tv": rep(cv, ku)}}


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _algo(loss_fn, mask, codec=None):
    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    return dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt,
                           opt_v=opt, k_v=1, k_u=2, lr_decay=0.99,
                           codec=codec,
                           codec_gamma=0.5 if codec is not None else 1.0)


@pytest.mark.parametrize("codec", [None, "topk"])
def test_flat_state_checkpoint_roundtrip(tmp_path, codec):
    """Save FlatDFedPGPState mid-run, restore into a ZEROED template,
    continue both copies 2 more rounds: bit-identical everything —
    including the codec's ef/ref memory when present."""
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    c = compress.make_codec(codec, ratio=0.25) if codec else None
    algo = _algo(loss_fn, mask, c)
    state, layout = algo.init_flat({"body": cu, "head": cv})
    sched = topology.TopologySchedule.random(m, 3, seed=7)
    b = _batches(cu, cv, 1, 2)
    for r in range(2):
        state, _ = algo.round_fn_flat(state, sched.at(r), b, layout)

    path = str(tmp_path / "flat_state")
    save_pytree(path, state, metadata={"round": 2})
    # restore into a zeroed template: every value must come from disk
    template = jax.tree.map(jnp.zeros_like, state)
    restored = load_pytree(path, template)
    _assert_trees_equal(state, restored)

    for r in range(2, 4):
        state, _ = algo.round_fn_flat(state, sched.at(r), b, layout)
        restored, _ = algo.round_fn_flat(restored, sched.at(r), b, layout)
    _assert_trees_equal(state, restored)


def test_sharded_flat_state_checkpoint_roundtrip(tmp_path):
    """Regime B resident form: a FlatDFedPGPState laid out by
    steps.flat_state_shardings (buffer rows over the client mesh axes)
    saves through the host npz path and restores onto the SAME shardings,
    then continues bit-for-bit — the checkpoint boundary of the resident
    datacenter round (docs/gossip.md §Regime B resident lifecycle)."""
    from repro.launch import steps

    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    algo = _algo(loss_fn, mask)
    state, layout = algo.init_flat({"body": cu, "head": cv})
    sched = topology.TopologySchedule.random(m, 3, seed=21)
    b = _batches(cu, cv, 1, 2)
    state, _ = algo.round_fn_flat(state, sched.at(0), b, layout)

    mesh = make_mesh((1, 1), ("data", "model"))
    lay = steps.Layout(("data",), (), ("model",), (), m, 1)
    shardings = steps.flat_state_shardings(state, mesh, lay)
    sharded = jax.device_put(state, shardings)
    assert sharded.flat.sharding.spec == \
        steps.sharding.flat_buffer_spec(mesh, lay.client_axes,
                                        layout.d_flat, lay.tp_axes)
    # the (m, d_flat) momentum and the buffer share one layout
    assert sharded.opt_u.momentum.sharding == sharded.flat.sharding

    path = str(tmp_path / "flat_sharded")
    save_pytree(path, sharded, metadata={"round": 1})
    template = jax.tree.map(jnp.zeros_like, state)
    restored = jax.device_put(load_pytree(path, template), shardings)
    _assert_trees_equal(sharded, restored)

    for r in range(1, 3):
        state, _ = algo.round_fn_flat(state, sched.at(r), b, layout)
        restored, _ = algo.round_fn_flat(restored, sched.at(r), b, layout)
    _assert_trees_equal(state, restored)


def test_async_runtime_checkpoint_roundtrip(tmp_path):
    """The async trio — profile + clock + mailbox ring (+ codec memory) —
    round-trips through one npz and resumes bit-for-bit under delays,
    speed tiers and a duty-cycled availability trace."""
    loss_fn, mask, cu, cv = _quad(m=10)
    m = cu.shape[0]
    algo = _algo(loss_fn, mask, compress.make_codec("qsgd", bits=4))
    prof = profiles.tiered(m, spread=4.0, push_delay_max=2,
                           availability=0.7, seed=3)
    rt, state = AsyncRuntime.build(algo, {"body": cu, "head": cv}, prof,
                                   depth=3)
    sched = topology.TopologySchedule.random(m, 3, seed=9)
    tick = jax.jit(lambda s, p, x: rt.tick(s, p, x))
    b = _batches(cu, cv, 1, 2)
    bt = {k: v[:, 0] for k, v in b["u"].items()}
    for t in range(7):
        state, _ = tick(state, topology.to_push_sparse(sched.at(t)), bt)

    path = str(tmp_path / "async_state")
    save_pytree(path, {"state": state, "profile": prof},
                metadata={"tick": 7})
    template = jax.tree.map(jnp.zeros_like,
                            {"state": state, "profile": prof})
    blob = load_pytree(path, template)
    restored, prof2 = blob["state"], blob["profile"]
    _assert_trees_equal(state, restored)
    _assert_trees_equal(prof, prof2)

    # rebuild a runtime from the RESTORED profile and keep ticking: the
    # trajectories (mailbox ring, clock, codec memory included) agree
    # bit-for-bit with the uninterrupted run
    rt2 = dataclasses.replace(rt, profile=profiles.ClientProfile(*prof2))
    tick2 = jax.jit(lambda s, p, x: rt2.tick(s, p, x))
    for t in range(7, 12):
        topo = topology.to_push_sparse(sched.at(t))
        state, _ = tick(state, topo, bt)
        restored, _ = tick2(restored, topo, bt)
    _assert_trees_equal(state, restored)
    assert int(restored.clock.t) == 12