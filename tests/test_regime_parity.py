"""Regime A / Regime B mixing parity: ONE TopologySchedule drives both the
simulator's sparse flat-buffer mix and the datacenter shard_map ppermute
mix, and the two agree leaf-for-leaf.

The real 8-device ppermute run needs forced host devices, which is
process-global jax state — it runs in a subprocess (same pattern as
launch/dryrun.py).  A cheap in-process check of the same schedule
arithmetic (ppermute == roll) keeps signal when subprocesses are
unavailable.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gossip, topology

ROOT = Path(__file__).resolve().parent.parent


def test_schedule_mix_equals_roll_emulation():
    """mix_flat over schedule.at(t) == the roll-based emulation of the
    ppermute permutation, 4 rounds, m=8 exponential."""
    m = 8
    sched = topology.TopologySchedule.exponential(m)
    offsets = sched.permutation_offsets()
    u = jax.random.normal(jax.random.PRNGKey(0), (m, 33))
    mu = jnp.ones((m,))
    u_roll = u
    for t in range(4):
        u, mu = gossip.mix_flat(sched.at(t), u, mu, mode="sparse")
        off = offsets[t % len(offsets)]
        u_roll = 0.5 * (u_roll + jnp.roll(u_roll, shift=off, axis=0))
    np.testing.assert_allclose(np.asarray(u), np.asarray(u_roll),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mu), 1.0, atol=1e-6)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import gossip, partition, topology
    from repro.launch import steps
    from repro.launch.mesh import make_mesh

    m = 8
    mesh = make_mesh((m, 1), ("data", "model"))
    layout = steps.Layout(("data",), (), ("model",), (), m, 1)
    key = jax.random.PRNGKey(0)
    params = {"body": jax.random.normal(key, (m, 6, 4)),
              "head": jax.random.normal(jax.random.fold_in(key, 1), (m, 3))}
    mask = {"body": True, "head": False}
    sched = topology.TopologySchedule.exponential(m)

    # Regime B: shard_map ppermute mix driven by the schedule
    mix_fn = steps.make_ppermute_mix(mesh, layout, mask, params,
                                     schedule=sched)
    pB, muB = params, jnp.ones((m,))
    with mesh:
        for t in range(4):
            pB, muB = mix_fn(pB, muB, jnp.asarray(t, jnp.int32))

    # Regime A: resident flat buffer mixed with the SAME schedule
    lay = gossip.FlatLayout.build(params, mask)
    flat, muA = lay.pack(params, mask), jnp.ones((m,))
    for t in range(4):
        flat, muA = gossip.mix_flat(sched.at(t), flat, muA, mode="sparse")
    pA = partition.merge(lay.unravel(flat), partition.split(params, mask)[1])

    # Regime B resident: ONE ppermute of the (m_local, d_flat) block per
    # round (make_ppermute_mix_flat), same schedule object
    mix_flat_fn = steps.make_ppermute_mix_flat(mesh, layout, lay.d_flat,
                                               schedule=sched)
    flatB, muBf = lay.pack(params, mask), jnp.ones((m,))
    with mesh:
        for t in range(4):
            flatB, muBf = mix_flat_fn(flatB, muBf, jnp.asarray(t, jnp.int32))

    err = max(float(jnp.abs(pA[k] - pB[k]).max()) for k in pA)
    err_mu = float(jnp.abs(muA - muB).max())
    assert err <= 1e-5, f"shared-param mismatch: {err}"
    assert err_mu <= 1e-6, f"mu mismatch: {err_mu}"
    err_f = float(jnp.abs(flatB - flat).max())
    err_fmu = float(jnp.abs(muBf - muA).max())
    assert err_f <= 1e-5, f"flat ppermute mismatch: {err_f}"
    assert err_fmu <= 1e-6, f"flat ppermute mu mismatch: {err_fmu}"
    # personal part untouched by both
    assert float(jnp.abs(pB["head"] - params["head"]).max()) == 0.0
    print("PARITY_OK", err, err_mu, err_f)
""")


def _run_forced_8dev(src: str, marker: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\n" \
                                 f"stderr:\n{proc.stderr}"
    assert marker in proc.stdout


def test_ppermute_mix_matches_schedule_mix_8_devices():
    """Acceptance: m=8 exponential clients, 4 rounds — the simulator's
    schedule-driven sparse mix and the ppermute datacenter mix produce
    identical shared parameters (f32 tolerance)."""
    _run_forced_8dev(_SUBPROCESS, "PARITY_OK")


_SUBPROCESS_RESIDENT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import SHAPES, get_reduced
    from repro.core import topology
    from repro.launch import steps
    from repro.launch.mesh import make_mesh

    m = 8
    mesh = make_mesh((m, 1), ("data", "model"))
    cfg = get_reduced("qwen2-0.5b")
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=16,
                                global_batch=m)
    layout = steps.decide_layout(mesh, "qwen2-0.5b", shape)
    assert layout.n_clients == m and layout.per_client_batch == 1
    sched = topology.TopologySchedule.exponential(m)

    # ONE algo drives all three paths (gossip="matrix": no mix override,
    # so the identical object serves round_fn AND round_fn_flat)
    algo, mask, _, flay = steps.build_train_algo(
        cfg, mesh, layout, k_u=1, k_v=1, gossip="matrix",
        schedule=sched, resident=True)
    from repro.models import get_model
    api = get_model(cfg)
    stacked = jax.vmap(lambda k: api.init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), m))
    s_tree = algo.init(stacked)
    s_flat, flay = algo.init_flat(stacked, flay)
    s_a = jax.tree.map(jnp.copy, s_flat)      # regime A's own (undonated) copy

    fn_t, ins_t, outs_t, _, don_t = steps.build_step(
        cfg, mesh, layout, shape, gossip="matrix", schedule=sched)
    fn_f, ins_f, outs_f, struct_f, don_f = steps.build_step(
        cfg, mesh, layout, shape, gossip="matrix", schedule=sched,
        resident=True)
    # the donated jit carry is the FLAT state — its arg 0 is a
    # FlatDFedPGPState whose (m, d_flat) buffer replaces the params tree
    # (the CPU backend implements no buffer aliasing, so donation is
    # asserted structurally rather than via is_deleted)
    from repro.core.dfedpgp import FlatDFedPGPState
    assert don_f == (0,)
    assert isinstance(struct_f[0], FlatDFedPGPState)
    assert struct_f[0].flat.shape == (m, flay.d_flat)
    jit_t = jax.jit(fn_t, in_shardings=ins_t, out_shardings=outs_t,
                    donate_argnums=don_t)
    jit_f = jax.jit(fn_f, in_shardings=ins_f, out_shardings=outs_f,
                    donate_argnums=don_f)
    # Regime A: the SAME round_fn_flat, plain single-host jit, same schedule
    jit_a = jax.jit(lambda s, P, b: algo.round_fn_flat(s, P, b, flay))

    def batches(t):
        k = jax.random.fold_in(jax.random.PRNGKey(42), t)

        def one(lead, kk):
            toks = jax.random.randint(kk, lead + (shape.seq_len,), 0,
                                      cfg.vocab, jnp.int32)
            return {"tokens": toks, "labels": jnp.roll(toks, -1, -1)}

        kv, ku = jax.random.split(k)
        return {"v": one((m, 1, 1), kv), "u": one((m, 1, 1), ku)}

    with mesh:
        for t in range(3):
            b = batches(t)
            P = sched.at(t)
            s_tree, _ = jit_t(s_tree, P, b)
            s_flat, _ = jit_f(s_flat, P, b)
    for t in range(3):
        s_a, _ = jit_a(s_a, sched.at(t), batches(t))

    def assert_state_equal(x, y, what):
        for i, (a, b) in enumerate(zip(jax.tree.leaves(x),
                                       jax.tree.leaves(y))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{what} leaf {i}")

    # Regime B resident == Regime A resident, bit for bit (params + mu +
    # both momenta + round)
    assert_state_equal(s_flat, s_a, "B-flat vs A-flat")
    # Regime B resident == Regime B tree-form, bit for bit, via the
    # converter (momenta placeholders restored exactly)
    back = algo.state_from_flat(s_flat, flay)
    assert_state_equal(back, s_tree, "B-flat vs B-tree")
    print("RESIDENT_PARITY_OK")
""")


def test_resident_train_step_parity_8_devices():
    """Acceptance (ISSUE 5): 3 full Regime B rounds of
    build_train_step(resident=True) on 8 forced devices are BIT-FOR-BIT
    the tree-form Regime B round and Regime A's round_fn_flat under one
    shared TopologySchedule — params, mu, both momenta — with the flat
    buffer (not the tree) as the donated jit carry."""
    _run_forced_8dev(_SUBPROCESS_RESIDENT, "RESIDENT_PARITY_OK")
