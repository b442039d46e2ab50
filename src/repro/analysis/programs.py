"""The registered jitted programs the invariant analyzer lints.

One `ProgramInstance` per distinct compiled artifact the repo ships
(docs/analysis.md): the resident Regime A round, the sampled round, the
Regime B train step in resident and sampled forms, the async tick, and
the fused serve path.  Each instance packages everything the detectors
need — the pure function, real committed arguments for `N_ROUNDS`
rounds, the donation contract, the client-axis size, and the mesh
context — so a detector never has to know HOW a program is built, only
that `inst.args(t, carry)` yields a runnable call.

The simulation-scale programs use a PRIME client count (`SIM_M = 13`)
on purpose: 13 appears nowhere else in any registered program's shapes,
so the densification detector can identify an (m, m)-scale intermediate
purely from its shape.  The Regime B programs take m from the device
mesh — `python -m repro.analysis` forces 13 host devices for the same
reason (tests on 1 device degrade them to m = 1, where the shape scan
is vacuous but the donation/retrace/host-sync checks still bite).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dfedpgp, topology
from repro.optim import SGD

N_ROUNDS = 3     # rounds every dynamic detector drives (steady state by 2)
SIM_M = 13       # prime client count for the simulation-scale programs


@dataclasses.dataclass(frozen=True)
class ProgramInstance:
    """One registered jitted program, packaged for the detectors.

    fn:          the pure function handed to jit / make_jaxpr.
    round_args:  per-round non-state argument tuples, PRE-BUILT as
                 committed device arrays — the host-sync detector runs
                 rounds under jax.transfer_guard("disallow"), and args
                 materialized at build time keep host-side schedule
                 construction (a host concern by design) out of the
                 guarded window.
    fresh_state: () -> a fresh donated arg-0, or None for stateless
                 programs (serve).  Fresh per call: donation consumes
                 the buffer, so detectors can never share one.
    donate:      donate_argnums of the production jit (() = no donation
                 contract, donation check reports n/a).
    m:           the client-axis size the densify scan keys on.
    jit_kwargs:  extra jax.jit kwargs (Regime B shardings; fixture
                 static_argnums).
    ctx:         () -> context manager the calls run under (the mesh for
                 Regime B, nullcontext otherwise).
    allow_dense: named_scope substrings whose (m, m) intermediates are
                 allowlisted (docs/analysis.md §Allowlisting).
    """
    name: str
    fn: Callable[..., Any]
    round_args: Tuple[Tuple[Any, ...], ...]
    fresh_state: Optional[Callable[[], Any]]
    donate: Tuple[int, ...]
    m: int
    jit_kwargs: dict = dataclasses.field(default_factory=dict)
    ctx: Callable[[], Any] = contextlib.nullcontext
    allow_dense: Tuple[str, ...] = ()

    def args(self, t: int, carry: Any) -> Tuple[Any, ...]:
        """The full argument tuple for round t (carry threads arg-0)."""
        rest = self.round_args[t % len(self.round_args)]
        if self.fresh_state is None:
            return rest
        state = carry if carry is not None else self.fresh_state()
        return (state,) + rest

    def carry_of(self, out: Any) -> Any:
        """The next round's arg-0 from this round's output."""
        return out[0] if self.fresh_state is not None else None


# ---------------------------------------------------------------------------
# simulation-scale core (the quad problem the unit suites train)
# ---------------------------------------------------------------------------
def _quad_setup(m: int = SIM_M, d: int = 6, dp: int = 3):
    key = jax.random.PRNGKey(0)
    cu = jax.random.normal(key, (m, d))
    cv = jax.random.normal(jax.random.fold_in(key, 1), (m, dp))

    def loss_fn(p, b):
        return jnp.sum((p["body"] - b["tu"][0]) ** 2) + \
            jnp.sum((p["head"] - b["tv"][0]) ** 2)

    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn,
                           mask={"body": True, "head": False},
                           opt_u=opt, opt_v=opt, k_v=1, k_u=2,
                           lr_decay=0.99)
    return algo, cu, cv


def _quad_batches(cu, cv, k_v: int, k_u: int, rows=None):
    tu = cu if rows is None else cu[rows]
    tv = cv if rows is None else cv[rows]
    rep = lambda x, k: jnp.repeat(x[:, None], k, 1)[..., None, :]
    return {"v": {"tu": rep(tu, k_v), "tv": rep(tv, k_v)},
            "u": {"tu": rep(tu, k_u), "tv": rep(tv, k_u)}}


def _copy_state(state):
    return jax.tree.map(jnp.copy, state)


def build_sim_resident() -> ProgramInstance:
    """Regime A resident round: round_fn_flat on the donated flat buffer
    (the program train.py --resident jits)."""
    algo, cu, cv = _quad_setup()
    state0, layout = algo.init_flat({"body": cu, "head": cv})
    sched = topology.TopologySchedule.random(SIM_M, 3, seed=13)
    b = _quad_batches(cu, cv, algo.k_v, algo.k_u)
    return ProgramInstance(
        name="simA.resident",
        fn=lambda s, P, bb: algo.round_fn_flat(s, P, bb, layout),
        round_args=tuple((sched.at(t), b) for t in range(N_ROUNDS)),
        fresh_state=lambda: _copy_state(state0),
        donate=(0,), m=SIM_M)


def build_sim_sampled() -> ProgramInstance:
    """Regime A sampled round: gather/round/scatter over the induced
    subgraph (docs/scale.md), donated resident carry."""
    algo, cu, cv = _quad_setup()
    state0, layout = algo.init_flat({"body": cu, "head": cv})
    sched = topology.TopologySchedule.random(SIM_M, 3, seed=13)
    n_act = 7

    def round_rest(t):
        key = jax.random.fold_in(jax.random.PRNGKey(101), t)
        act = jnp.sort(jax.random.permutation(key, SIM_M)[:n_act])
        act = act.astype(jnp.int32)
        P_act = topology.induced_subgraph(sched.at(t), act, "row")
        return (P_act, act, _quad_batches(cu, cv, algo.k_v, algo.k_u,
                                          rows=act))

    return ProgramInstance(
        name="simA.sampled",
        fn=lambda s, P, a, bb: algo.round_fn_sampled(s, P, a, bb, layout),
        round_args=tuple(round_rest(t) for t in range(N_ROUNDS)),
        fresh_state=lambda: _copy_state(state0),
        donate=(0,), m=SIM_M)


def build_async_tick() -> ProgramInstance:
    """The async runtime's tick (docs/hetero.md): local step + mailbox
    fire/drain.  The simulator jits it without donation (the AsyncState
    is python-held across ticks), so the donation check reports n/a."""
    from repro.hetero import profiles
    from repro.hetero.runtime import AsyncRuntime

    algo, cu, cv = _quad_setup()
    rt, state0 = AsyncRuntime.build(algo, {"body": cu, "head": cv},
                                    profiles.uniform(SIM_M), depth=2)
    sched = topology.TopologySchedule.random(SIM_M, 3, seed=13)
    b = _quad_batches(cu, cv, algo.k_v, algo.k_u)

    def tick_batch(t):
        src = b["v"] if t % (algo.k_v + algo.k_u) < algo.k_v else b["u"]
        off = t % (algo.k_v + algo.k_u)
        off = off if off < algo.k_v else off - algo.k_v
        return {k: v[:, off] for k, v in src.items()}

    return ProgramInstance(
        name="async.tick",
        fn=lambda s, P, bb: rt.tick(s, P, bb),
        round_args=tuple((sched.at(t), tick_batch(t))
                         for t in range(N_ROUNDS)),
        fresh_state=lambda: _copy_state(state0),
        donate=(), m=SIM_M)


def build_serve_cnn() -> ProgramInstance:
    """The fused serve path (docs/serve.md): consensus trunk once +
    head_gather per mixed-user batch.  Stateless — no donation contract."""
    from repro import serve
    from repro.core import partition
    from repro.models import cnn
    from repro.serve.engine import serve_logits

    cfg = cnn.CNNConfig(image_size=8, n_classes=10)

    def loss_fn(p, batch):
        return cnn.loss_fn(p, batch, cfg)

    template = cnn.init_params(jax.random.PRNGKey(0), cfg)
    mask = partition.build_mask(template, partition.classifier_personal)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=SGD(lr=0.1),
                           opt_v=SGD(lr=0.1))
    stacked = jax.vmap(lambda k: cnn.init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(1), SIM_M))
    state, layout = algo.init_flat(stacked)
    sstate = serve.from_train_state(state, layout=layout, consensus="mass")

    B = 6

    def request(t):
        ku, kx = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(2), t))
        uid = jax.random.randint(ku, (B,), 0, SIM_M, jnp.int32)
        x = jax.random.normal(
            kx, (B, cfg.image_size, cfg.image_size, cfg.channels))
        return (uid, x)

    return ProgramInstance(
        name="serve.cnn",
        fn=lambda uid, x: serve_logits(sstate, uid, x, cfg),
        round_args=tuple(request(t) for t in range(N_ROUNDS)),
        fresh_state=None, donate=(), m=SIM_M)


# ---------------------------------------------------------------------------
# Regime B (launch/steps.py builders over the device mesh)
# ---------------------------------------------------------------------------
def _build_regime_b(sampled: bool) -> ProgramInstance:
    import dataclasses as dc

    from repro.configs import SHAPES, get_reduced
    from repro.launch import steps
    from repro.launch.mesh import make_mesh

    n_dev = jax.device_count()
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    arch = "qwen2-0.5b"
    cfg = get_reduced(arch)
    shape = dc.replace(SHAPES["train_4k"], seq_len=16, global_batch=n_dev)
    layout = steps.decide_layout(mesh, arch, shape)
    m = layout.n_clients
    sched = topology.TopologySchedule.random(m, min(2, max(m - 1, 0)),
                                             seed=7)
    kw: dict = dict(resident=True, schedule=sched)
    if sampled:
        kw["sample_frac"] = 0.5
    fn, ins, outs, structs, donate = steps.build_step(cfg, mesh, layout,
                                                      shape, **kw)

    def zeros(s):
        return jnp.zeros(s.shape, s.dtype)

    state0 = jax.tree.map(zeros, structs[0])
    # a zero push-sum weight would de-bias to inf; the analyzer runs on
    # values only to drive the program, so any valid mu does
    state0 = state0._replace(mu=jnp.ones_like(state0.mu))
    state0 = jax.device_put(state0, ins[0])

    if sampled:
        n_act = structs[2].shape[0]

        def rest(t):
            key = jax.random.fold_in(jax.random.PRNGKey(11), t)
            act = jnp.sort(jax.random.permutation(key, m)[:n_act])
            act = act.astype(jnp.int32)
            P_act = topology.induced_subgraph(sched.at(t), act, "row")
            b = jax.tree.map(zeros, structs[3])
            return jax.device_put((P_act, act, b), tuple(ins[1:]))
    else:
        def rest(t):
            b = jax.tree.map(zeros, structs[2])
            return jax.device_put((sched.at(t), b), tuple(ins[1:]))

    with jax.set_mesh(mesh):
        round_args = tuple(rest(t) for t in range(N_ROUNDS))
    return ProgramInstance(
        name="regimeB.sampled" if sampled else "regimeB.resident",
        fn=fn,
        round_args=round_args,
        fresh_state=lambda: _copy_state(state0),
        donate=donate, m=m,
        jit_kwargs=dict(in_shardings=ins, out_shardings=outs),
        ctx=lambda: jax.set_mesh(mesh))


def build_regime_b_resident() -> ProgramInstance:
    return _build_regime_b(sampled=False)


def build_regime_b_sampled() -> ProgramInstance:
    return _build_regime_b(sampled=True)


# name -> builder; building is deferred so `--program X` only pays for X
PROGRAMS = {
    "simA.resident": build_sim_resident,
    "simA.sampled": build_sim_sampled,
    "regimeB.resident": build_regime_b_resident,
    "regimeB.sampled": build_regime_b_sampled,
    "async.tick": build_async_tick,
    "serve.cnn": build_serve_cnn,
}
