"""FL simulation engine (Regime A): m vmapped clients on one host.

Reproduces the paper's experimental protocol at simulation scale:
100 clients, 500 rounds, Dirichlet/Pathological non-IID partitions, 10
neighbors per round for DFL methods / 0.1 sampling for CFL methods,
SGD(0.1, momentum 0.9, wd 5e-4) with 0.99x exponential decay, and
personalized test accuracy (each client on its own test split).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro import spec as spec_mod
from repro.core import baselines, dfedpgp, gossip, partition, topology
from repro.obs import gauges as obs_gauges
from repro.data import ClientData, make_dataset, sample_batches
from repro.hetero import profiles as hetero_profiles
from repro.hetero.runtime import AsyncRuntime
from repro.models import cnn
from repro.optim import SGD
from . import compat


@dataclasses.dataclass(frozen=True)
class SimConfig:
    m: int = 100                    # clients
    n_neighbors: int = 10           # DFL gossip degree / CFL ratio*m
    sample_ratio: float = 0.1
    rounds: int = 100
    batch: int = 32
    k_local: int = 5                # shared-part local steps (paper: 5 epochs)
    k_personal: int = 1             # personal-part steps (paper: 1 epoch)
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 5e-4
    lr_decay: float = 0.99
    n_classes: int = 10
    dist: str = "dirichlet"         # dirichlet | pathological
    alpha: float = 0.3
    c: int = 2
    n_train: int = 64
    n_test: int = 32
    image_size: int = 8
    noise: float = 0.7              # synthetic-data noise (task difficulty)
    seed: int = 0
    topology: str = "random"        # random | exponential | ring | full
    # dense | sparse | pallas (docs/gossip.md).  dense/sparse apply to every
    # DFL method; "pallas" selects the fused kernel for DFedPGP's flat-buffer
    # engine — the baselines have no flat buffer and gossip sparse.
    gossip: str = "sparse"
    # resident flat buffer (DFedPGP only): keep the shared part in the
    # (m, d_flat) buffer ACROSS rounds (pack once at init, mix in place)
    # instead of re-flattening every round.  Bit-compatible with the
    # per-round path (tests/test_resident_buffer.py); False restores the
    # pre-refactor flatten-per-round behaviour for A/B regression runs.
    resident: bool = True
    # ---- execution regime (docs/hetero.md) ----
    # "sync"  — lockstep rounds (the paper's protocol; every client blocks
    #           on the slowest peer each round);
    # "async" — virtual-clock gossip with delayed push-sum mailboxes: each
    #           tick only the clients whose next-event time has arrived
    #           act.  DFL push-sum methods only (dfedpgp / osgp /
    #           dfedavgm); history gains a "vtime" axis (virtual-time-to-
    #           accuracy — the real async win).
    runtime: str = "sync"
    hetero: str = "uniform"        # async profile: uniform|tiered|lognormal
    speed_spread: float = 5.0      # slowest/fastest step-cost ratio
    push_delay_max: int = 0        # max sender push-delay class, in ticks
    availability: float = 1.0      # duty fraction of availability traces
    mailbox_depth: int = 4         # delivery ring depth (>= delays + 1)
    # ---- wire codec (repro.compress, docs/compress.md) ----
    # None = today's uncompressed path; "identity" is its bit-for-bit
    # codec-form twin; "topk"/"randk" sparsify to codec_ratio, "qsgd"
    # quantizes to codec_bits — all with error feedback.  Applies to the
    # push-sum flat engines (dfedpgp/osgp/dfedavgm) in BOTH runtimes;
    # history gains cumulative "wire_bytes".
    codec: Optional[str] = None
    codec_ratio: float = 1.0 / 16.0   # kept fraction for topk/randk
    codec_bits: int = 4               # qsgd word size (4 or 8)
    # consensus step size for lossy codecs (CHOCO; docs/compress.md §Step
    # size): sparse pipes need g < 1 or the error-feedback memory grows
    # faster than it drains.  "auto" anneals per round from the
    # residual-to-signal ratio ||u||/(||u||+||ef||) instead of a static
    # guess (sync resident rounds only)
    codec_gamma: object = 1.0      # float in (0, 1], or "auto"
    # ---- partial participation (docs/scale.md) ----
    # "full"    — every client every round (the seed behavior);
    # "uniform" — a seeded uniform-random subset of participation_frac*m
    #             clients per round;
    # "trace"   — availability-trace-driven via the hetero profile (rank
    #             by ticks-until-reachable, subset size stays fixed).
    # Sync: rides the resident flat engine (dfedpgp / flat-core codec
    # runs) — only the active rows are gathered, stepped, mixed over the
    # induced subgraph and scattered back.  Async: gates the virtual
    # clock; dormant clients' mass waits in the persistent inbox.
    participation: str = "full"
    participation_frac: float = 1.0
    # stale-mass discounting (ROADMAP async follow-up (a)): scale each
    # sender's lazy self share by its push-delay class
    # (topology.staleness_self_weight) so receivers' push-sum weights
    # stop plateauing on mass stuck in slow links.  Async runtime only.
    stale_discount: bool = False
    # ---- the new knob surface (repro.spec, PR 7) ----
    # One AlgoSpec replaces the duplicated per-entrypoint knobs above
    # (topology/gossip/resident/codec*/participation*).  When set, those
    # legacy fields must stay at their defaults — resolve_spec raises on
    # a conflict instead of letting two copies silently disagree.
    spec: Optional[spec_mod.AlgoSpec] = None


# algo name -> (constructor kind, context kind)
ALGOS = ("local", "fedavg", "fedper", "fedrep", "fedbabu", "ditto",
         "dfedavgm", "dfedavgm-p", "osgp", "dispfl", "dfedpgp")
CFL = ("fedavg", "fedper", "fedrep", "fedbabu", "ditto")
UNDIRECTED = spec_mod.UNDIRECTED_ALGOS
# push-sum methods the async runtime can drive (docs/hetero.md): osgp and
# dfedavgm are expressed on the same engine as DFedPGP with an all-shared
# partition (full-model gossip) and no personal phase — for dfedavgm the
# undirected doubly-stochastic schedule keeps mu at 1 in steady state, so
# the push-sum de-bias reduces to plain averaging (and under delays it
# supplies exactly the correction plain DFedAvgM lacks).
ASYNC_ALGOS = ("dfedpgp", "osgp", "dfedavgm")


# legacy SimConfig fields the spec now owns (resolve_spec conflict check)
_SPEC_KNOBS = ("topology", "n_neighbors", "gossip", "resident", "codec",
               "codec_ratio", "codec_bits", "codec_gamma",
               "participation", "participation_frac")


def resolve_spec(algo_name: str, sim: SimConfig) -> spec_mod.AlgoSpec:
    """The run's ONE AlgoSpec.  `SimConfig(spec=...)` wins, but only when
    the legacy duplicated knobs sit at their defaults — a non-default
    legacy knob next to an explicit spec is exactly the two-copies-
    disagree bug the spec exists to kill, so it raises instead of
    guessing.  Without a spec, the legacy fields funnel through the one
    factory (compat.spec_from_sim), so they get the same validation."""
    if sim.spec is not None:
        defaults = {f.name: f.default for f in dataclasses.fields(SimConfig)}
        clash = [k for k in _SPEC_KNOBS if getattr(sim, k) != defaults[k]]
        if clash:
            raise ValueError(
                f"SimConfig(spec=...) conflicts with legacy knob(s) "
                f"{clash}: the spec owns them now — drop the duplicated "
                f"SimConfig fields (or drop spec= to keep the deprecated "
                f"surface)")
        if sim.spec.algo != algo_name:
            raise ValueError(
                f"spec.algo={sim.spec.algo!r} but the experiment runs "
                f"{algo_name!r}; one spec describes one algorithm")
        return sim.spec
    return compat.spec_from_sim(sim, algo_name)


def build_algorithm(name: str, loss_fn, mask, sim: SimConfig,
                    spec: Optional[spec_mod.AlgoSpec] = None):
    sp = spec if spec is not None else resolve_spec(name, sim)
    opt = SGD(lr=sim.lr, momentum=sim.momentum, weight_decay=sim.weight_decay)
    kw = dict(loss_fn=loss_fn, opt=opt, lr_decay=sim.lr_decay)
    if name == "local":
        return baselines.LocalOnly(**kw)
    if name == "fedavg":
        return baselines.FedAvg(sample_ratio=sim.sample_ratio, **kw)
    if name == "fedper":
        return baselines.FedPartial(mask=mask, mode="per",
                                    sample_ratio=sim.sample_ratio, **kw)
    if name == "fedrep":
        return baselines.FedPartial(mask=mask, mode="rep", k_head=sim.k_personal,
                                    sample_ratio=sim.sample_ratio, **kw)
    if name == "fedbabu":
        return baselines.FedPartial(mask=mask, mode="babu",
                                    sample_ratio=sim.sample_ratio, **kw)
    if name == "ditto":
        return baselines.Ditto(sample_ratio=sim.sample_ratio, **kw)
    if name == "dfedavgm":
        return baselines.DFedAvgM(**kw)
    if name == "dfedavgm-p":
        return baselines.DFedAvgM(partial_mask=mask, **kw)
    if name == "osgp":
        return baselines.OSGP(**kw)
    if name == "dispfl":
        return baselines.DisPFL(**kw)
    if name == "dfedpgp":
        return dfedpgp.DFedPGP(
            loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
            k_v=sim.k_personal, k_u=sim.k_local, lr_decay=sim.lr_decay,
            gossip=sp.gossip, codec=sp.make_codec(),
            codec_gamma=sp.codec_gamma, telemetry=sp.telemetry)
    raise ValueError(f"unknown algorithm {name!r}; known: {ALGOS}")


def build_flat_core(name: str, loss_fn, mask, sim: SimConfig,
                    spec: Optional[spec_mod.AlgoSpec] = None
                    ) -> dfedpgp.DFedPGP:
    """The flat-engine push-sum core behind a DFL algorithm name.  dfedpgp
    keeps its partial partition and alternating phases; osgp/dfedavgm
    gossip the FULL model (all-shared mask, k_v = 0) — their sync
    round_fns are the k_v = 0 specialization of Algorithm 1, so one
    engine drives all three.  Used by the async runtime for every tick
    schedule, and by the sync regime when a wire codec is requested
    (codecs live on the resident flat buffer: docs/compress.md)."""
    if name not in ASYNC_ALGOS:
        raise ValueError(
            f"the flat push-sum engine drives {ASYNC_ALGOS}; {name!r} "
            f"has no flat-buffer core")
    sp = spec if spec is not None else resolve_spec(name, sim)
    opt = SGD(lr=sim.lr, momentum=sim.momentum,
              weight_decay=sim.weight_decay)
    codec = sp.make_codec()
    if name == "dfedpgp":
        return dfedpgp.DFedPGP(
            loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
            k_v=sim.k_personal, k_u=sim.k_local, lr_decay=sim.lr_decay,
            gossip="pallas" if sp.gossip == "pallas" else "sparse",
            codec=codec, codec_gamma=sp.codec_gamma,
            telemetry=sp.telemetry)
    all_shared = jax.tree.map(lambda _: True, mask)
    return dfedpgp.DFedPGP(
        loss_fn=loss_fn, mask=all_shared, opt_u=opt, opt_v=opt,
        k_v=0, k_u=sim.k_local + sim.k_personal, lr_decay=sim.lr_decay,
        gossip="pallas" if sp.gossip == "pallas" else "sparse",
        codec=codec, codec_gamma=sp.codec_gamma,
        telemetry=sp.telemetry)


# the async runtime's historical name for the same constructor
build_async_core = build_flat_core

# the deprecated knob-surface helpers (make_sim_codec / make_schedule /
# make_sampler) moved to fl/compat.py; PEP 562 keeps the old
# `simulator.make_schedule(...)` call sites importable for one release
_DEPRECATED = ("make_sim_codec", "make_schedule", "make_sampler")


def __getattr__(name):
    if name in _DEPRECATED:
        return getattr(compat, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _trace_profile(sp: spec_mod.AlgoSpec, sim: SimConfig):
    """The availability profile a trace-driven sampler ranks by — built
    from the hetero knobs (those stay SimConfig fields: they describe the
    simulated fleet, not the algorithm)."""
    if sp.participation != "trace":
        return None
    return hetero_profiles.make_profile(
        sim.hetero, sim.m, spread=sim.speed_spread,
        push_delay_max=sim.push_delay_max,
        availability=sim.availability, seed=sim.seed)


@functools.lru_cache(maxsize=None)
def _accuracy_fn(model_cfg: cnn.CNNConfig):
    """One jitted, vmapped accuracy closure per model config — built once
    per experiment so eval rounds stop paying per-call retrace overhead."""
    return jax.jit(jax.vmap(
        lambda p, x, y: cnn.accuracy(p, x, y, model_cfg)))


def evaluate(eval_params, data: ClientData, model_cfg: cnn.CNNConfig):
    acc = _accuracy_fn(model_cfg)(eval_params, data.x_test, data.y_test)
    return float(jnp.mean(acc)), np.asarray(acc)


def run_experiment(algo_name: str, sim: SimConfig,
                   model_cfg: Optional[cnn.CNNConfig] = None,
                   step_gates: Optional[np.ndarray] = None,
                   eval_every: int = 10, verbose: bool = False,
                   return_params: bool = False, sink=None):
    """Returns history dict with per-eval round accuracies.  With
    return_params, history["params"] carries the final stacked
    personalized models (regression tests compare them across engine
    knobs), history["state"] the final train state, history["layout"]
    its FlatLayout (None for tree-form engines) and, on the sync runtime,
    history["round_lowered"] the round program as jax.stages.Lowered.
    sink: optional obs.MetricsSink — every round then emits one schema-v1
    "round" record (docs/observability.md) carrying the round metrics, the
    wire meter, and (spec.telemetry) the in-graph gauges;
    fetching gauges to the host costs one device sync per round, which is
    why emission is opt-in while `history` stays the cheap default."""
    model_cfg = model_cfg or cnn.CNNConfig(image_size=sim.image_size,
                                           n_classes=sim.n_classes)
    key = jax.random.PRNGKey(sim.seed)
    k_data, k_init, k_run = jax.random.split(key, 3)

    data = make_dataset(k_data, sim.m, n_classes=sim.n_classes, dist=sim.dist,
                        alpha=sim.alpha, c=sim.c, n_train=sim.n_train,
                        n_test=sim.n_test, size=sim.image_size,
                        noise=sim.noise)

    def loss_fn(p, batch):
        return cnn.loss_fn(p, batch, model_cfg)

    template = cnn.init_params(jax.random.PRNGKey(0), model_cfg)
    mask = partition.build_mask(template, partition.classifier_personal)
    stacked = jax.vmap(lambda k: cnn.init_params(k, model_cfg))(
        jax.random.split(k_init, sim.m))

    sp = resolve_spec(algo_name, sim)
    if sp.gossip not in gossip.MODES:
        raise ValueError(
            f"gossip mode {sp.gossip!r}: Regime A mixes via the matrix "
            f"engines {gossip.MODES}; 'ppermute' is the sharded trainer's "
            f"mix (launch.build_train_algo)")
    if sim.runtime not in ("sync", "async"):
        raise ValueError(f"runtime {sim.runtime!r}; known: sync | async")
    k_total = sim.k_local + sim.k_personal
    if step_gates is not None:
        # loud (m, K) validation instead of sgd_steps' silent broadcast
        need_k = sim.k_local if algo_name == "dfedpgp" else k_total
        step_gates = hetero_profiles.validate_step_gates(
            step_gates, sim.m, need_k)
    if sim.runtime == "async":
        if step_gates is not None:
            raise ValueError(
                "step_gates are the sync regime's faked heterogeneity; "
                "the async runtime models speed via SimConfig.hetero")
        return async_experiment(algo_name, sim, model_cfg, data, loss_fn,
                                mask, stacked, k_run,
                                eval_every=eval_every, verbose=verbose,
                                return_params=return_params, spec=sp,
                                sink=sink)
    codec = sp.make_codec()
    if codec is None and sp.codec_gamma != 1.0:
        raise ValueError(
            f"codec_gamma={sp.codec_gamma} only applies to lossy "
            f"codecs; set the spec's codec or drop the knob")
    if codec is not None and algo_name not in ASYNC_ALGOS:
        raise ValueError(
            f"codec={sp.codec!r} rides the push-sum flat engines "
            f"{ASYNC_ALGOS}; {algo_name!r} has no wire-payload "
            f"boundary to compress")
    # resident flat buffer: pack the shared part once, here; rounds then
    # mix the buffer in place (no per-round flatten — docs/gossip.md).
    # A wire codec routes osgp/dfedavgm through their flat-engine cores
    # too (the k_v = 0 specialization of Algorithm 1 — the same cores the
    # async runtime drives), because payloads are rows of the flat buffer.
    use_flat = (algo_name == "dfedpgp" and sp.resident) or \
        (codec is not None and algo_name in ("osgp", "dfedavgm"))
    if codec is not None and algo_name != "dfedpgp":
        algo = build_flat_core(algo_name, loss_fn, mask, sim, spec=sp)
    else:
        algo = build_algorithm(algo_name, loss_fn, mask, sim, spec=sp)
    is_pgp_engine = isinstance(algo, dfedpgp.DFedPGP)
    if sp.gossip == "pallas" and not is_pgp_engine:
        print(f"[simulator] note: gossip='pallas' applies to the "
              f"flat-buffer engine; {algo_name} gossips via the sparse "
              f"path")
    schedule = None if (algo_name in CFL or algo_name == "local") else \
        sp.schedule(sim.m)
    sampler = sp.sampler(sim.m, _trace_profile(sp, sim))
    if sampler is not None and not use_flat:
        raise ValueError(
            f"partial participation gathers/scatters the resident flat "
            f"buffer (docs/scale.md); {algo_name!r} with "
            f"resident={sp.resident} has no flat engine — use dfedpgp "
            f"with resident=True (or a flat-core codec run)")
    if use_flat:
        state, layout = algo.init_flat(stacked)
        eval_params = lambda s: algo.eval_params_flat(s, layout)
    else:
        state, layout = algo.init(stacked), None
        eval_params = algo.eval_params

    @jax.jit
    def round_sampled_jit(state, P_act, active, batches, gate):
        # gather the active clients' batches/gates INSIDE the jit (active
        # has a static per-config length, so the trace is reused across
        # rounds); the round itself runs on the compact working set
        kv = algo.k_v
        ba = jax.tree.map(lambda a: jnp.take(a, active, axis=0), batches)
        b = {"v": jax.tree.map(lambda a: a[:, :kv], ba),
             "u": jax.tree.map(lambda a: a[:, kv:], ba)}
        g = None if gate is None else jnp.take(gate, active, axis=0)
        return algo.round_fn_sampled(state, P_act, active, b, layout,
                                     step_gate_u=g)

    @jax.jit
    def round_jit(state, ctx, batches, gate):
        if is_pgp_engine:
            kv = algo.k_v
            b = {"v": jax.tree.map(lambda a: a[:, :kv], batches),
                 "u": jax.tree.map(lambda a: a[:, kv:], batches)}
            if use_flat:
                return algo.round_fn_flat(state, ctx, b, layout,
                                          step_gate_u=gate)
            return algo.round_fn(state, ctx, b, step_gate_u=gate)
        return algo.round_fn(state, ctx, batches, step_gate=gate)

    if sp.telemetry and not use_flat:
        raise ValueError(
            f"spec.telemetry gauges read the resident flat buffer; "
            f"{algo_name!r} with resident={sp.resident} has no buffer to "
            f"gauge (use dfedpgp with resident=True or a flat-core codec "
            f"run)")
    # wire-bytes accounting (docs/compress.md): every directed non-self
    # edge of the round's topology carries one client payload; the
    # per-payload byte cost is static, so the meter is pure host-side
    # bookkeeping through the ONE obs formula both runtimes read
    # (obs.gauges.payload_row_bytes — codec=None meters the uncompressed
    # f32 wire)
    wire_rb = None
    wire_total = 0
    if schedule is not None:
        full_mask = jax.tree.map(lambda _: True, mask)
        wire_mask = mask if algo_name in ("dfedpgp", "dfedavgm-p") \
            else full_mask
        d_wire = gossip.flat_width(stacked, wire_mask)
        wire_rb = obs_gauges.payload_row_bytes(codec, d_wire)
        # lossy codecs track against bootstrapped reference copies
        # (compress.init_ref): first contact ships one full-fidelity row
        # per client — metered here, so the reduction claims stay honest
        wire_total = obs_gauges.bootstrap_bytes(codec, sim.m, d_wire)

    history = {"round": [], "acc": [], "loss": [], "vtime": [],
               "wire_bytes": [], "algo": algo_name, "runtime": "sync"}
    run_id = f"{algo_name}-sync-seed{sim.seed}"
    timer = obs.PhaseTimer()
    t0 = time.perf_counter()
    for r in range(sim.rounds):
        compiles0 = obs.compile_count()
        k_r = jax.random.fold_in(k_run, r)
        # 3-way split kept so the k_batch/k_cfl streams match the
        # pre-schedule RNG layout; the topology key is unused now — the
        # schedule seeds itself from (sim.seed, round)
        _, k_batch, k_cfl = jax.random.split(k_r, 3)
        batches = sample_batches(k_batch, data, k_total, sim.batch)
        active = P_act = None
        if algo_name in CFL:
            ctx = k_cfl
        elif algo_name == "local":
            ctx = jnp.zeros(())  # unused
        else:
            topo = schedule.at(r)
            ctx = topo.dense() if sp.gossip == "dense" else topo
            P_meter = topo
            if sampler is not None:
                active = jnp.asarray(sampler.active_at(r))
                P_act = topology.induced_subgraph(topo, active, "row")
                P_meter = P_act   # only active<->active edges carry bytes
            wire_total += obs_gauges.edge_count(P_meter) * wire_rb
        if step_gates is not None:
            gate = jnp.asarray(step_gates, jnp.float32)
            gate_u = gate[:, :sim.k_local] if algo_name == "dfedpgp" else \
                gate[:, :k_total]
        else:
            gate_u = None
        with timer.phase("round", block=sink is not None) as ph:
            if active is not None:
                state, metrics = round_sampled_jit(state, P_act, active,
                                                   batches, gate_u)
            else:
                state, metrics = round_jit(state, ctx, batches, gate_u)
            ph.out = metrics

        acc = None
        if (r + 1) % eval_every == 0 or r == sim.rounds - 1:
            with timer.phase("eval"):
                acc, _ = evaluate(eval_params(state), data, model_cfg)
            history["round"].append(r + 1)
            history["acc"].append(acc)
            # lockstep rounds: every round costs k_total ticks of the
            # SLOWEST participant; homogeneous cost 1 here — heterogeneous
            # sync cost is charged by the caller (benchmarks/bench_async)
            history["vtime"].append(float((r + 1) * k_total))
            history["wire_bytes"].append(wire_total)
            history["loss"].append(float(metrics["loss"]
                                         if "loss" in metrics
                                         else metrics["loss_u"]))
            if verbose:
                print(f"[{algo_name}] round {r+1:4d} acc={acc:.4f} "
                      f"({time.perf_counter()-t0:.1f}s)")
        if sink is not None:
            sink.emit(obs.round_record(
                run=run_id, algo=algo_name, step=r + 1, m=sim.m, acc=acc,
                vtime=float((r + 1) * k_total), wire_bytes=wire_total,
                compiles=obs.compile_count() - compiles0, **timer.gauges(),
                **{k: v for k, v in metrics.items()
                   if jnp.ndim(v) == 0}))
            timer.reset()
            if sp.graph_every and (r + 1) % sp.graph_every == 0 \
                    and schedule is not None and use_flat:
                from repro.obs import graph as obs_graph
                obs_graph.emit_graph_record(
                    sink, run_id=run_id, algo=algo_name, m=sim.m,
                    seed=sim.seed, schedule=schedule, step=r + 1, t0=r,
                    flat=state.flat, mu=state.mu,
                    personal=state.personal, active=active)
    history["final_acc"] = history["acc"][-1] if history["acc"] else float("nan")
    if return_params:
        history["params"] = eval_params(state)
        history["state"], history["layout"] = state, layout
        if sim.rounds:
            # the round program that ran, lowered at the last round's
            # arguments (the final state has the input state's types)
            history["round_lowered"] = (
                round_sampled_jit.lower(state, P_act, active, batches, gate_u)
                if active is not None
                else round_jit.lower(state, ctx, batches, gate_u))
    return history


# ---------------------------------------------------------------------------
# async regime: virtual-clock gossip (docs/hetero.md)
# ---------------------------------------------------------------------------
def async_round(runtime: AsyncRuntime, tick_fn, state, schedule, data,
                sim: SimConfig, k_run, tick0: int,
                wire_edges=jnp.zeros((), jnp.int32), sampler=None):
    """Advance one sync-equivalent WINDOW of k_v + k_u ticks.

    Each tick: sample one minibatch per client (only active clients
    consume theirs), draw the tick's directed topology from the schedule,
    and run `runtime.tick` (tick_fn: the experiment's ONE jitted closure
    over it — the topology rides in as a pytree, so the trace is reused
    across ticks and windows).  A full-rate client completes exactly one
    local round per window, so `rounds` windows give the async run the
    same fast-client step budget as a sync run of `rounds` rounds — but
    slow clients simply complete fewer rounds instead of stalling the
    population (the barrier the sync regime pays every round is gone).
    Returns (state, last_metrics, next_tick, wire_edges') — wire_edges
    accumulates the payload-carrying directed edges (bytes accounting,
    docs/compress.md) lazily on device."""
    metrics = {}
    # the async regime fires over the LAZY PUSH form of the tick's
    # graph (to_push_sparse: sender keeps 1/2, splits 1/2 over its
    # out-edges).  Column-stochastic => total mass is conserved under
    # any delay trace, and the 1/2 self share keeps a fast client
    # from being yanked onto a stale heavy-mass arrival — the classic
    # stability condition of delayed push-sum (one-peer SGP keeps
    # exactly 1/2).  The pull form stays the sync regime's mix.
    # stale_discount raises the slow-link senders' kept share
    # (topology.staleness_self_weight) so their receivers' push-sum
    # weights stop plateauing on mass stuck in flight.
    self_weight = topology.staleness_self_weight(
        runtime.profile.push_delay) if sim.stale_discount else 0.5
    for t in range(tick0, tick0 + runtime.k_total):
        k_t = jax.random.fold_in(k_run, t)
        b = sample_batches(k_t, data, 1, sim.batch)
        batch = jax.tree.map(lambda a: a[:, 0], b)
        topo = topology.to_push_sparse(schedule.at(t),
                                       self_weight=self_weight)
        # participation gate (docs/scale.md): sampled-out clients neither
        # step nor fire this tick; mass fired at them waits in their
        # persistent inbox, so the mass ledger is untouched
        part = None if sampler is None \
            else jnp.asarray(sampler.active_mask(t))
        state, metrics = tick_fn(state, topo, batch, part)
        wire_edges = wire_edges + metrics["wire_edges"]
    return state, metrics, tick0 + runtime.k_total, wire_edges


def async_experiment(algo_name: str, sim: SimConfig, model_cfg, data,
                     loss_fn, mask, stacked, k_run, eval_every: int = 10,
                     verbose: bool = False, return_params: bool = False,
                     spec: Optional[spec_mod.AlgoSpec] = None, sink=None):
    """The `runtime="async"` leg of run_experiment: same data, model and
    protocol constants, but rounds become windows of ticks on the virtual
    clock and history carries virtual-time-to-accuracy.  sink: optional
    obs.MetricsSink — each tick WINDOW then emits one schema-v1 "tick"
    record (the last tick's gauges + the cumulative wire meter)."""
    sp = spec if spec is not None else resolve_spec(algo_name, sim)
    profile = hetero_profiles.make_profile(
        sim.hetero, sim.m, spread=sim.speed_spread,
        push_delay_max=sim.push_delay_max, availability=sim.availability,
        seed=sim.seed)
    core = build_flat_core(algo_name, loss_fn, mask, sim, spec=sp)
    depth = max(sim.mailbox_depth, sim.push_delay_max + 1)
    runtime, state = AsyncRuntime.build(core, stacked, profile, depth=depth)
    schedule = sp.schedule(sim.m)
    sampler = sp.sampler(sim.m, profile)
    tick_fn = jax.jit(lambda s, topo, b, part: runtime.tick(
        s, topo, b, participation=part))
    # the SAME obs wire formulas the sync meter reads (the historical
    # inline duplicate here is the asymmetry tests/test_compress.py pins)
    wire_rb = obs_gauges.payload_row_bytes(core.codec,
                                           runtime.layout.d_flat)
    wire_boot = obs_gauges.bootstrap_bytes(core.codec, sim.m,
                                           runtime.layout.d_flat)

    history = {"round": [], "acc": [], "loss": [], "vtime": [],
               "wire_bytes": [], "mean_local_rounds": [],
               "algo": algo_name, "runtime": "async"}
    run_id = f"{algo_name}-async-seed{sim.seed}"
    timer = obs.PhaseTimer()
    t0 = time.perf_counter()
    tick = 0
    wire_edges = jnp.zeros((), jnp.int32)
    for r in range(sim.rounds):
        compiles0 = obs.compile_count()
        with timer.phase("window", block=sink is not None) as ph:
            state, metrics, tick, wire_edges = async_round(
                runtime, tick_fn, state, schedule, data, sim, k_run, tick,
                wire_edges, sampler=sampler)
            ph.out = metrics
        acc = None
        if (r + 1) % eval_every == 0 or r == sim.rounds - 1:
            with timer.phase("eval"):
                acc, _ = evaluate(runtime.eval_params(state), data,
                                  model_cfg)
            history["round"].append(r + 1)
            history["acc"].append(acc)
            history["vtime"].append(float(metrics["vtime"]))
            history["wire_bytes"].append(int(wire_edges) * wire_rb
                                         + wire_boot)
            history["loss"].append(float(metrics["loss"]))
            history["mean_local_rounds"].append(
                float(jnp.mean(state.local_round.astype(jnp.float32))))
            if verbose:
                print(f"[{algo_name}/async] window {r+1:4d} "
                      f"vtime={float(metrics['vtime']):.0f} acc={acc:.4f} "
                      f"mass={float(metrics['mass_total']):.3f} "
                      f"({time.perf_counter()-t0:.1f}s)")
        if sink is not None:
            sink.emit(obs.tick_record(
                run=run_id, algo=algo_name, step=r + 1, m=sim.m, acc=acc,
                wire_bytes=int(wire_edges) * wire_rb + wire_boot,
                compiles=obs.compile_count() - compiles0, **timer.gauges(),
                **{k: v for k, v in metrics.items()
                   if jnp.ndim(v) == 0}))
            timer.reset()
            if sp.graph_every and (r + 1) % sp.graph_every == 0:
                # snapshot the IN-FLIGHT-AWARE ledger (flat + mail,
                # mu + mail) — the same accounting eval_params uses, so
                # a client whose mass is mid-wire still reads correctly.
                # mass_total over mu_eff is the conserved local+in-flight
                # total; the age histogram keys off the last executed
                # tick, so every ring slot (delta 1..D) is covered.
                from repro.hetero import mailbox as a_mbox
                from repro.obs import graph as obs_graph
                mail_f, mail_mu = a_mbox.in_flight(state.mail)
                extra = dict(obs_gauges.staleness_gauges(
                    state.local_round))
                extra.update(obs_graph.mailbox_age_hist(
                    state.mail.slots_mu, tick - 1))
                obs_graph.emit_graph_record(
                    sink, run_id=run_id, algo=algo_name, m=sim.m,
                    seed=sim.seed, schedule=schedule, step=r + 1,
                    t0=tick, flat=state.flat + mail_f.astype(
                        state.flat.dtype),
                    mu=state.mu + mail_mu, personal=state.personal,
                    extra=extra)
    history["final_acc"] = history["acc"][-1] if history["acc"] else float("nan")
    if return_params:
        history["params"] = runtime.eval_params(state)
    return history
