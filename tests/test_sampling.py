"""Partial participation (docs/scale.md): ParticipationSampler determinism,
the sampled resident round's sample-all == all-rows BIT-FOR-BIT identity
(sync and async), dormant-row freezing + push-sum mass conservation under
25% participation, gossip_scatter kernel parity at awkward shapes, and the
launch-layer sampled step builder.

The 8-forced-device variants (acceptance: sample-all parity and the
dormant-mass ledger hold on a real client mesh) run in a subprocess, same
pattern as tests/test_regime_parity.py — forced host devices are
process-global jax state.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_reduced
from repro.core import dfedpgp, pushsum, sampling, topology
from repro.hetero import profiles
from repro.hetero.runtime import AsyncRuntime
from repro.kernels import ops, ref
from repro.launch import steps
from repro.launch.mesh import make_mesh
from repro.optim import SGD

ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# ParticipationSampler: the ONE object deciding who acts this round
# ---------------------------------------------------------------------------
def test_sampler_deterministic_in_seed_and_round():
    s = sampling.ParticipationSampler("uniform", m=32, frac=0.25, seed=7)
    a0 = s.active_at(3)
    assert a0.dtype == np.int32
    assert s.n_active == 8 and a0.shape == (8,)
    np.testing.assert_array_equal(a0, np.sort(np.unique(a0)))
    # pure in (seed, t): replay agrees, a fresh sampler agrees, call order
    # is irrelevant
    np.testing.assert_array_equal(a0, s.active_at(3))
    s2 = sampling.ParticipationSampler("uniform", m=32, frac=0.25, seed=7)
    _ = s2.active_at(11)
    np.testing.assert_array_equal(a0, s2.active_at(3))
    # different round / different seed actually move the draw
    assert not np.array_equal(a0, s.active_at(4))
    s3 = sampling.ParticipationSampler("uniform", m=32, frac=0.25, seed=8)
    assert not np.array_equal(a0, s3.active_at(3))


def test_sampler_mask_agrees_with_ids():
    s = sampling.ParticipationSampler("uniform", m=20, frac=0.3, seed=1)
    for t in range(5):
        mask = s.active_mask(t)
        assert mask.shape == (20,) and mask.dtype == bool
        np.testing.assert_array_equal(np.nonzero(mask)[0], s.active_at(t))
        assert int(mask.sum()) == s.n_active


def test_sampler_full_kind_is_arange():
    s = sampling.ParticipationSampler("full", m=9)
    assert s.n_active == 9
    for t in (0, 5):
        np.testing.assert_array_equal(s.active_at(t), np.arange(9))


def test_sampler_trace_prefers_available_clients():
    m = 16
    prof = profiles.tiered(m, spread=2.0, availability=0.5, seed=3)
    s = sampling.ParticipationSampler("trace", m=m, frac=0.25, seed=0,
                                      profile=prof)
    for t in range(8):
        sel = s.active_at(t)
        wait = np.asarray(profiles.time_to_available(prof, t))
        unsel = np.setdiff1d(np.arange(m), sel)
        # the chosen waits are a prefix of the sorted waits: nobody picked
        # waits longer than anybody skipped (ties at the cut are fine)
        assert wait[sel].max() <= wait[unsel].min()


def test_sampler_validation():
    with pytest.raises(ValueError, match="kind"):
        sampling.ParticipationSampler("lottery", m=4)
    with pytest.raises(ValueError, match="frac"):
        sampling.ParticipationSampler("uniform", m=4, frac=0.0)
    with pytest.raises(ValueError, match="frac"):
        sampling.ParticipationSampler("uniform", m=4, frac=1.5)
    with pytest.raises(ValueError, match="profile"):
        sampling.ParticipationSampler("trace", m=4, frac=0.5)


# ---------------------------------------------------------------------------
# quadratic-core fixtures (the repo's closed-form DFedPGP harness)
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


def _algo(loss_fn, mask):
    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    return dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
                           k_v=1, k_u=2, lr_decay=0.99)


def _assert_states_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.flat), np.asarray(b.flat))
    np.testing.assert_array_equal(np.asarray(a.mu), np.asarray(b.mu))
    np.testing.assert_array_equal(np.asarray(a.opt_u.momentum),
                                  np.asarray(b.opt_u.momentum))
    np.testing.assert_array_equal(np.asarray(a.personal["head"]),
                                  np.asarray(b.personal["head"]))
    np.testing.assert_array_equal(np.asarray(a.opt_v.momentum["head"]),
                                  np.asarray(b.opt_v.momentum["head"]))


# ---------------------------------------------------------------------------
# ACCEPTANCE: sample-all == all-rows, bit for bit (sync)
# ---------------------------------------------------------------------------
def test_round_fn_sampled_sample_all_bitwise():
    """active = all m clients: the gather/induced-renorm/scatter round IS
    round_fn_flat — params, mu and BOTH momenta bit-identical over 3 rounds
    (the sum-preserving induced re-normalization's factor is exactly 1.0
    when every row survives)."""
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    algo = _algo(loss_fn, mask)
    s_full, layout = algo.init_flat({"body": cu, "head": cv})
    s_samp, _ = algo.init_flat({"body": cu, "head": cv})
    sched = topology.TopologySchedule.random(m, 3, seed=13)
    sampler = sampling.ParticipationSampler("full", m=m)
    round_full = jax.jit(lambda s, p, b: algo.round_fn_flat(s, p, b, layout))
    round_samp = jax.jit(
        lambda s, p, a, b: algo.round_fn_sampled(s, p, a, b, layout))
    for t in range(3):
        topo = sched.at(t)
        b = _batches(cu, cv, 2)
        active = jnp.asarray(sampler.active_at(t))
        P_act = topology.induced_subgraph(topo, active, "row")
        s_full, mt_full = round_full(s_full, topo, b)
        s_samp, mt_samp = round_samp(s_samp, P_act, active, b)
        np.testing.assert_array_equal(np.asarray(mt_full["loss_u"]),
                                      np.asarray(mt_samp["loss_u"]))
        assert int(mt_samp["n_active"]) == m
    _assert_states_equal(s_samp, s_full)
    assert int(s_samp.round) == 3


def test_round_fn_sampled_freezes_dormant_rows():
    """25% participation: every dormant row — params, mu, both momenta,
    personal leaves — is BIT-FROZEN, active rows move, and the full-buffer
    mu ledger stays conserved (sync pull mixing is row-stochastic)."""
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    algo = _algo(loss_fn, mask)
    state, layout = algo.init_flat({"body": cu, "head": cv})
    init = state
    sched = topology.TopologySchedule.random(m, 3, seed=5)
    sampler = sampling.ParticipationSampler("uniform", m=m, frac=0.25,
                                            seed=2)
    round_samp = jax.jit(
        lambda s, p, a, b: algo.round_fn_sampled(s, p, a, b, layout))
    ever = np.zeros(m, bool)
    for t in range(3):
        active = sampler.active_at(t)
        ever[active] = True
        b = jax.tree.map(lambda x: x[active], _batches(cu, cv, 2))
        P_act = topology.induced_subgraph(sched.at(t), jnp.asarray(active),
                                          "row")
        state, mt = round_samp(state, P_act, jnp.asarray(active), b)
        assert int(mt["n_active"]) == sampler.n_active
    dormant = ~ever
    assert dormant.any() and ever.any()
    np.testing.assert_array_equal(np.asarray(state.flat)[dormant],
                                  np.asarray(init.flat)[dormant])
    np.testing.assert_array_equal(np.asarray(state.mu)[dormant],
                                  np.asarray(init.mu)[dormant])
    np.testing.assert_array_equal(
        np.asarray(state.opt_u.momentum)[dormant],
        np.asarray(init.opt_u.momentum)[dormant])
    np.testing.assert_array_equal(
        np.asarray(state.personal["head"])[dormant],
        np.asarray(init.personal["head"])[dormant])
    # active rows actually moved
    assert (np.asarray(state.flat)[ever] !=
            np.asarray(init.flat)[ever]).any()
    # mu mass over the whole buffer: conserved (f32)
    np.testing.assert_allclose(float(state.mu.sum()), m, rtol=1e-6)


# ---------------------------------------------------------------------------
# async regime: the participation gate
# ---------------------------------------------------------------------------
def test_async_tick_all_ones_participation_is_identity():
    """participation = all-True must be a no-op gate: the tick trajectory
    is bit-identical to passing no participation at all."""
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    algo = _algo(loss_fn, mask)
    prof = profiles.tiered(m, spread=2.0, push_delay_max=2, seed=4)
    rt, s_a = AsyncRuntime.build(algo, {"body": cu, "head": cv}, prof,
                                 depth=4)
    _, s_b = AsyncRuntime.build(algo, {"body": cu, "head": cv}, prof,
                                depth=4)
    tick_plain = jax.jit(lambda s, p, b: rt.tick(s, p, b))
    tick_gated = jax.jit(
        lambda s, p, b, g: rt.tick(s, p, b, participation=g))
    ones = jnp.ones((m,), bool)
    b = _batches(cu, cv, 1)
    bt = {k: v[:, 0] for k, v in b["u"].items()}
    for t in range(12):
        P_row = topology.directed_random(jax.random.PRNGKey(50 + t), m, 3)
        P = topology.from_dense(topology.to_column_stochastic(P_row), k=m)
        s_a, _ = tick_plain(s_a, P, bt)
        s_b, _ = tick_gated(s_b, P, bt, ones)
    _assert_states_equal(s_a, s_b)
    np.testing.assert_array_equal(np.asarray(s_a.local_round),
                                  np.asarray(s_b.local_round))


def test_dormant_mass_conserved():
    """ACCEPTANCE: random 25% participation per tick on top of a 4x-spread
    availability trace, column-stochastic push mixing — Σmu + mailbox mass
    stays m to f32 at EVERY tick, and the pushsum.mass_split ledger
    (active + dormant + in-flight) accounts for all of it."""
    loss_fn, mask, cu, cv = _quad(m=12)
    m = cu.shape[0]
    algo = _algo(loss_fn, mask)
    prof = profiles.tiered(m, spread=4.0, push_delay_max=3,
                           availability=0.7, seed=1)
    rt, s = AsyncRuntime.build(algo, {"body": cu, "head": cv}, prof,
                               depth=4)
    sampler = sampling.ParticipationSampler("uniform", m=m, frac=0.25,
                                            seed=9)
    tick = jax.jit(
        lambda s, p, b, e, g: rt.tick(s, p, b, e, participation=g))
    rng = np.random.default_rng(0)
    b = _batches(cu, cv, 1)
    bt = {k: v[:, 0] for k, v in b["u"].items()}
    for t in range(50):
        P_row = topology.directed_random(jax.random.PRNGKey(200 + t), m, 3)
        P = topology.from_dense(topology.to_column_stochastic(P_row), k=m)
        delay = jnp.asarray(rng.integers(0, 4, (m, P.k)), jnp.int32)
        part = jnp.asarray(sampler.active_mask(t))
        s, mt = tick(s, P, bt, delay, part)
        np.testing.assert_allclose(float(mt["mass_total"]), m, rtol=1e-5)
        # only gated-on clients ever fire
        assert int(mt["n_fired"]) <= int(part.sum())
        act, dor, flight = pushsum.mass_split(
            s.mu, part, s.mail.slots_mu, s.mail.inbox_mu)
        np.testing.assert_allclose(float(act + dor + flight), m, rtol=1e-5)
    # mail addressed to gated-off clients survived in the inbox rather than
    # vanishing: the run ends with mass genuinely in flight or banked
    assert float(s.mail.inbox_mu.sum() + s.mail.slots_mu.sum()) >= 0.0
    ev = rt.eval_params(s)
    assert bool(jnp.isfinite(ev["body"]).all())


def test_mass_split_components():
    mu = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    mask = jnp.asarray([True, False, True, False])
    inflight = jnp.asarray([0.5, 0.25])
    act, dor, flight = pushsum.mass_split(mu, mask, inflight)
    assert float(act) == 4.0 and float(dor) == 6.0 and float(flight) == 0.75


# ---------------------------------------------------------------------------
# gossip_scatter kernel: interpret parity at awkward shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,d", [(5, 3), (13, 130), (7, 257), (32, 64),
                                 (40, 300), (21, 384)])
@pytest.mark.parametrize("accumulate", [False, True])
def test_gossip_scatter_interpret_parity(m, d, accumulate):
    """The pallas write-back (interpret mode on CPU) is bit-identical to
    the XLA scatter oracle at non-multiple-of-block shapes, both modes."""
    key = jax.random.PRNGKey(m * 100 + d)
    U = jax.random.normal(key, (m, d))
    n = max(1, m // 3)
    rows = jnp.asarray(np.sort(np.random.default_rng(m).choice(
        m, size=n, replace=False)), jnp.int32)
    X = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    got = ops.gossip_scatter(rows, X, U, accumulate=accumulate,
                             force="pallas")
    want = ref.gossip_scatter_ref(rows, X, U, accumulate=accumulate)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gossip_scatter_bf16_buffer_parity():
    # (9, 70) lies wholly in partial tiles; (35, 300) has whole 16-row bf16
    # tiles for the kernel and partial edge tiles in both dims
    for (m, d), rows in (((9, 70), [0, 4, 8]),
                         ((35, 300), [0, 5, 17, 31, 32, 34])):
        U = jax.random.normal(jax.random.PRNGKey(0), (m, d)).astype(
            jnp.bfloat16)
        rows = jnp.asarray(rows, jnp.int32)
        X = jax.random.normal(jax.random.PRNGKey(1), (rows.shape[0], d))
        for acc in (False, True):
            got = ops.gossip_scatter(rows, X, U, accumulate=acc,
                                     force="pallas")
            want = ref.gossip_scatter_ref(rows, X, U, accumulate=acc)
            assert got.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_gossip_scatter_ref_rejects_block_tuning():
    U, X = jnp.zeros((4, 3)), jnp.ones((2, 3))
    rows = jnp.asarray([0, 2], jnp.int32)
    with pytest.raises(ValueError, match="block_m"):
        ops.gossip_scatter(rows, X, U, force="ref", block_m=2)


# ---------------------------------------------------------------------------
# launch layer: the sampled step builder
# ---------------------------------------------------------------------------
MESH = make_mesh((1, 1), ("data", "model"))


def _shape(name, **kw):
    return dataclasses.replace(SHAPES[name], **kw)


def test_sampled_train_step_lowers():
    """build_train_step(resident=True, sample_frac<1): the round takes
    (state, P_act, active, batches) with COMPACT leading dims, donates the
    resident state, and lowers."""
    cfg = get_reduced("qwen2-0.5b")
    shape = _shape("train_4k", seq_len=32, global_batch=2)
    layout = steps.decide_layout(MESH, "qwen2-0.5b", shape)
    sched = topology.TopologySchedule.random(layout.n_clients, 0, seed=3)
    fn, ins, outs, args, donate = steps.build_step(
        cfg, MESH, layout, shape, resident=True, schedule=sched,
        sample_frac=0.5)
    assert donate == (0,)
    n_act = max(1, int(round(0.5 * layout.n_clients)))
    assert args[2].shape == (n_act,)                       # active ids
    assert isinstance(args[1], topology.SparseTopology)    # induced topo
    assert args[1].idx.shape[0] == n_act
    for leaf in jax.tree.leaves(args[3]):                  # compact batches
        assert leaf.shape[0] == n_act
    with MESH:
        compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                           donate_argnums=donate).lower(*args).compile()
    assert compiled is not None


def test_sampled_train_step_guards():
    cfg = get_reduced("qwen2-0.5b")
    shape = _shape("train_4k", seq_len=32, global_batch=2)
    layout = steps.decide_layout(MESH, "qwen2-0.5b", shape)
    sched = topology.TopologySchedule.random(layout.n_clients, 0, seed=3)
    with pytest.raises(ValueError, match="sample_frac"):
        steps.build_train_step(cfg, MESH, layout, shape, schedule=sched,
                               resident=True, sample_frac=0.0)
    with pytest.raises(ValueError, match="resident"):
        steps.build_train_step(cfg, MESH, layout, shape, schedule=sched,
                               sample_frac=0.5)
    # ppermute needs a periodic schedule to even reach the sampled guard
    psched = topology.TopologySchedule.exponential(layout.n_clients)
    with pytest.raises(ValueError, match="ppermute"):
        steps.build_train_step(cfg, MESH, layout, shape, schedule=psched,
                               resident=True, gossip="ppermute",
                               sample_frac=0.5)


# ---------------------------------------------------------------------------
# 8 forced host devices: the acceptance runs on a real client mesh
# ---------------------------------------------------------------------------
_SUBPROCESS_SAMPLED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import dfedpgp, sampling, topology
    from repro.launch.mesh import make_mesh
    from repro.optim import SGD

    m = 8
    mesh = make_mesh((m, 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    cu = jax.random.normal(key, (m, 6))
    cv = jax.random.normal(jax.random.fold_in(key, 1), (m, 3))

    def loss_fn(p, b):
        return jnp.sum((p["body"] - b["tu"][0]) ** 2) + \\
            jnp.sum((p["head"] - b["tv"][0]) ** 2)

    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn,
                           mask={"body": True, "head": False},
                           opt_u=opt, opt_v=opt, k_v=1, k_u=2,
                           lr_decay=0.99)

    def shard_rows(state):
        # every per-client leaf rides the 8-way data axis; scalars replicate
        def put(x):
            if getattr(x, "ndim", None) is None:
                return x
            spec = P("data", *([None] * (x.ndim - 1))) if x.ndim else P()
            return jax.device_put(x, NamedSharding(mesh, spec))
        return jax.tree.map(put, state)

    rep = lambda x: jnp.repeat(x[:, None], 2, 1)[..., None, :]
    b = {"v": {"tu": rep(cu), "tv": rep(cv)},
         "u": {"tu": rep(cu), "tv": rep(cv)}}
    sched = topology.TopologySchedule.random(m, 3, seed=13)

    s_full, layout = algo.init_flat({"body": cu, "head": cv})
    s_samp, _ = algo.init_flat({"body": cu, "head": cv})
    s_full, s_samp = shard_rows(s_full), shard_rows(s_samp)
    round_full = jax.jit(lambda s, p, bb: algo.round_fn_flat(s, p, bb,
                                                             layout))
    round_samp = jax.jit(lambda s, p, a, bb: algo.round_fn_sampled(
        s, p, a, bb, layout))

    # --- sample-all parity on the sharded buffer ---
    for t in range(3):
        topo = sched.at(t)
        active = jnp.arange(m, dtype=jnp.int32)
        P_act = topology.induced_subgraph(topo, active, "row")
        s_full, _ = round_full(s_full, topo, b)
        s_samp, _ = round_samp(s_samp, P_act, active, b)
    for name in ("flat", "mu"):
        a, bb = getattr(s_samp, name), getattr(s_full, name)
        assert (np.asarray(a) == np.asarray(bb)).all(), name
    assert (np.asarray(s_samp.opt_u.momentum) ==
            np.asarray(s_full.opt_u.momentum)).all()
    assert (np.asarray(s_samp.personal["head"]) ==
            np.asarray(s_full.personal["head"])).all()
    print("SAMPLED_PARITY_OK")

    # --- dormant rows frozen + mu ledger at 25% participation ---
    state, _ = algo.init_flat({"body": cu, "head": cv})
    state = shard_rows(state)
    init_flat_buf = np.asarray(state.flat)
    init_mu = np.asarray(state.mu)
    sampler = sampling.ParticipationSampler("uniform", m=m, frac=0.25,
                                            seed=2)
    ever = np.zeros(m, bool)
    for t in range(3):
        active = sampler.active_at(t)
        ever[active] = True
        ba = jax.tree.map(lambda x: x[active], b)
        P_act = topology.induced_subgraph(sched.at(t), jnp.asarray(active),
                                          "row")
        state, mt = round_samp(state, P_act, jnp.asarray(active), ba)
    dormant = ~ever
    assert dormant.any()
    assert (np.asarray(state.flat)[dormant] ==
            init_flat_buf[dormant]).all(), "dormant rows moved"
    assert (np.asarray(state.mu)[dormant] == init_mu[dormant]).all()
    np.testing.assert_allclose(float(state.mu.sum()), m, rtol=1e-6)
    print("DORMANT_MASS_OK")
""")


def _run_forced_8dev(src: str, markers):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\n" \
                                 f"stderr:\n{proc.stderr}"
    for marker in markers:
        assert marker in proc.stdout


def test_sampled_round_acceptance_8_devices():
    """Acceptance: on 8 forced host devices with the state row-sharded over
    the client axis, the sampled round at sample-all is bit-identical to
    the all-rows round, and at 25% participation dormant rows are frozen
    with the mu ledger conserved."""
    _run_forced_8dev(_SUBPROCESS_SAMPLED,
                     ("SAMPLED_PARITY_OK", "DORMANT_MASS_OK"))
