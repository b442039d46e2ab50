"""Decentralized directed training driver (Regime B, runnable).

Runs REAL DFedPGP rounds of a transformer-LM config on whatever devices are
available (CPU host devices here; the same code lowers to the production
meshes via dryrun.py).  Each data rank is a personalized client; the shared
body gossips over a time-varying directed graph; the lm_head stays local.

ONE `topology.TopologySchedule` (--topology/--seed) decides who talks to
whom: the matrix gossip pulls `schedule.at(r)` each round and the ppermute
mix derives its shard_map offsets from the same object — the invariant
both regimes share (docs/gossip.md §One topology object).  --resident
trains on the (m, d_flat) flat buffer (`FlatDFedPGPState`, donated jit
carry) instead of the tree-form state.

Usage (small smoke config, a few rounds, synthetic LM data):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --rounds 4 --clients 4 --batch 2 --seq 128 --reduced \
      [--gossip matrix|ppermute] [--topology random|exponential|ring|full] \
      [--resident]
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs
from repro.configs import get_config, get_reduced
from repro.core import partition, topology
from repro.launch import steps
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.obs import gauges as obs_gauges
from repro.spec import make_algo_spec


def synth_lm_batch(key, cfg, lead, seq):
    """Synthetic next-token data with learnable structure (shifted cycle)."""
    kt, = jax.random.split(key, 1)
    toks = jax.random.randint(kt, lead + (seq,), 0, cfg.vocab, jnp.int32)
    labels = jnp.roll(toks, -1, axis=-1)
    batch = {"tokens": toks, "labels": labels}
    if cfg.family == "vlm":
        batch["vision"] = jax.random.normal(
            key, lead + (cfg.n_vision_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, lead + (cfg.n_frames, cfg.d_model), jnp.float32)
    return batch


def make_cli_spec(args, gossip: str):
    """The run's ONE AlgoSpec from the CLI flags (repro.spec).  Topology
    default: the one-peer exponential graph for ppermute (the only kind
    that IS a permutation mix), the paper's n-random-in-neighbors graph
    for the matrix contraction."""
    kind = args.topology or \
        ("exponential" if gossip == "ppermute" else "random")
    return make_algo_spec(
        "dfedpgp", topology=kind, n_neighbors=args.neighbors,
        seed=args.seed, gossip=gossip, resident=args.resident,
        participation="uniform" if args.sample < 1.0 else "full",
        participation_frac=args.sample, telemetry=args.telemetry,
        graph_every=args.graph_every)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--k_u", type=int, default=1)
    ap.add_argument("--k_v", type=int, default=1)
    ap.add_argument("--neighbors", type=int, default=2)
    ap.add_argument("--gossip", default="matrix",
                    choices=["matrix", "ppermute"])
    ap.add_argument("--topology", default="",
                    choices=["", "random", "exponential", "ring", "full"],
                    help="mixing schedule kind (default: exponential for "
                         "ppermute, random otherwise)")
    ap.add_argument("--seed", type=int, default=0,
                    help="schedule seed (random kinds)")
    ap.add_argument("--resident", action="store_true",
                    help="train on the resident (m, d_flat) flat buffer "
                         "(FlatDFedPGPState; docs/gossip.md §Regime B "
                         "resident lifecycle)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (smoke) variant of the arch")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers (depth only; "
                         "every width stays the config's); 0 keeps it")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sample", type=float, default=1.0,
                    help="participation fraction per round (docs/scale.md): "
                         "< 1 draws a seeded uniform subset each round and "
                         "runs the compact sampled step (needs --resident)")
    ap.add_argument("--telemetry", action="store_true",
                    help="in-graph round gauges (repro.obs; needs "
                         "--resident): consensus gap, mass ledger, "
                         "grad/update norms ride the round metrics")
    ap.add_argument("--graph-every", type=int, default=0,
                    help="emit one schema-v2 collaboration-graph record "
                         "every N rounds (repro.obs.graph; needs "
                         "--telemetry): contraction estimate, top-k edge "
                         "attribution, similarity gauges — render with "
                         "`report <metrics> --graph`")
    ap.add_argument("--metrics", default="",
                    help="JSONL path: emit one schema-v1 round record per "
                         "round through obs.JsonlSink (render with "
                         "`python -m repro.obs.report <path>`)")
    ap.add_argument("--profile", default="",
                    help="trace directory: wrap the round loop in "
                         "jax.profiler.trace (view phase-labelled device "
                         "timelines in xprof/tensorboard)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.layers < 0:
        ap.error(f"--layers {args.layers}: want >= 0")
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.layers:
        cfg = cfg.replace(n_layers=args.layers)
    m = args.clients
    n_dev = jax.device_count()
    gossip = args.gossip
    if m * args.tp == n_dev:
        mesh = make_host_mesh(m, args.tp)
    elif n_dev == 1:
        if gossip == "ppermute":
            ap.error("--gossip ppermute permutes client shards across "
                     "devices; one device holds them all — use --gossip "
                     "matrix")
        mesh = None
        print(f"[train] unsharded: {m} clients x tp {args.tp} on the one "
              f"{jax.devices()[0].platform} device")
    else:
        ap.error(f"--clients {m} x --tp {args.tp} = {m * args.tp} mesh "
                 f"ranks; {n_dev} devices present (the mesh takes them all)")
    if not 0.0 < args.sample <= 1.0:
        ap.error(f"--sample {args.sample}: want a fraction in (0, 1]")
    sampled = args.sample < 1.0
    if sampled and not args.resident:
        ap.error("--sample < 1 gathers/scatters the resident flat buffer; "
                 "add --resident")
    if sampled and gossip == "ppermute":
        ap.error("--sample < 1 mixes the compact working set; ppermute "
                 "offsets address all m shards — use --gossip matrix")
    if args.telemetry and not args.resident:
        ap.error("--telemetry gauges read the resident flat buffer; "
                 "add --resident")
    if args.graph_every and not args.telemetry:
        ap.error("--graph-every emits through the telemetry spine; "
                 "add --telemetry")
    spec = make_cli_spec(args, gossip)
    # the spec is the run's one knob object: the schedule the round loop
    # mixes over and the sampler it draws from resolve from the SAME spec
    # the builder consumes (deterministic in its fields, so the builder's
    # internal schedule and this one are equal objects)
    schedule = spec.schedule(m)
    sampler = spec.sampler(m)

    api = get_model(cfg)
    layout = steps.Layout(("data",), (), ("model",), (), m, args.batch)
    algo, mask, params_struct, flat_layout = steps.build_train_algo(
        cfg, mesh, layout, k_u=args.k_u, k_v=args.k_v, spec=spec, lr=0.02)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params_struct)

    def init_state(keys):
        stacked = jax.vmap(lambda k: api.init_params(k, cfg))(keys)
        if flat_layout is not None:
            return algo.init_flat(stacked, flat_layout)[0]
        return algo.init(stacked)

    key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, m)
    jit_kw: dict = dict(donate_argnums=(0,))
    if mesh is None:
        state = jax.jit(init_state)(keys)
    else:
        # each client's state is built on, and stays on, its own mesh rank
        struct = jax.eval_shape(init_state, keys)
        st_sh = (steps.flat_state_shardings if flat_layout is not None
                 else steps.state_shardings)(struct, mesh, layout)
        state = jax.jit(init_state, out_shardings=st_sh)(keys)
        jit_kw["out_shardings"] = (st_sh,
                                   NamedSharding(mesh, PartitionSpec()))

    if sampled:
        @functools.partial(jax.jit, **jit_kw)
        def round_fn(state, P_act, active, batches):
            # compact working set: gather active rows, round, scatter back
            return algo.round_fn_sampled(state, P_act, active, batches,
                                         flat_layout)
    elif args.resident:
        @functools.partial(jax.jit, **jit_kw)
        def round_fn(state, P, batches):
            # the FLAT BUFFER is the donated carry — the round updates the
            # (m, d_flat) buffer in place, no tree materializes
            return algo.round_fn_flat(state, P, batches, flat_layout)
    else:
        @functools.partial(jax.jit, **jit_kw)
        def round_fn(state, P, batches):
            return algo.round_fn(state, P, batches)

    n_lead = sampler.n_active if sampler is not None else m
    print(f"[train] {cfg.arch_id} family={cfg.family} clients={m} "
          f"params/client={partition.count_params(template):,} "
          f"shared={partition.count_params(template, mask, True):,} "
          f"topology={schedule.kind} resident={args.resident}"
          + (f" sample={args.sample} ({n_lead}/{m})" if sampled else ""))

    # one record per round through the telemetry spine (repro.obs): the
    # printed line IS the record's rendered form, so the JSONL artifact
    # and the console never disagree
    sink = obs.JsonlSink(args.metrics) if args.metrics else obs.NULL_SINK
    run_id = f"trainB-{cfg.arch_id}-seed{args.seed}"
    d_wire = partition.count_params(template, mask, True)
    wire_rb = obs_gauges.payload_row_bytes(None, d_wire)
    wire_total = 0
    timer = obs.PhaseTimer()

    import contextlib
    ctx = (jax.set_mesh(mesh) if mesh is not None
           else contextlib.nullcontext())
    with ctx, obs.maybe_trace(args.profile or None):
        for r in range(args.rounds):
            compiles0 = obs.compile_count()
            with timer.phase("data"):
                kr = jax.random.fold_in(key, r + 1)
                kb, _ = jax.random.split(kr)
                batches = {
                    "v": synth_lm_batch(kb, cfg,
                                        (n_lead, args.k_v, args.batch),
                                        args.seq),
                    "u": synth_lm_batch(jax.random.fold_in(kb, 7), cfg,
                                        (n_lead, args.k_u, args.batch),
                                        args.seq),
                }
                if mesh is not None and sampler is None:
                    batches = jax.device_put(batches, steps.batch_specs(
                        batches, mesh, layout, n_lead=2))
            active = None
            # dispatch only: the fetch below is the round's one sync
            with timer.phase("round"):
                if sampler is not None:
                    active = jnp.asarray(sampler.active_at(r))
                    P_r = topology.induced_subgraph(schedule.at(r), active,
                                                    "row")
                    state, metrics = round_fn(state, P_r, active, batches)
                else:
                    P_r = schedule.at(r)
                    state, metrics = round_fn(state, P_r, batches)
            with timer.phase("fetch"):
                metrics = jax.device_get(metrics)
            # the record cannot time its own emission: `emit` is a span
            # on the trace and its bucket is dropped with the reset
            with timer.phase("emit"):
                wire_total += obs_gauges.edge_count(P_r) * wire_rb
                rec = obs.round_record(
                    run=run_id, algo="dfedpgp", step=r, m=m,
                    loss=metrics["loss_u"], wire_bytes=wire_total,
                    round_s=timer.seconds("round") + timer.seconds("fetch"),
                    compiles=obs.compile_count() - compiles0,
                    **timer.gauges(),
                    **{k: v for k, v in metrics.items() if jnp.ndim(v) == 0})
                sink.emit(rec)
                if args.graph_every and (r + 1) % args.graph_every == 0:
                    from repro.obs import graph as obs_graph
                    obs_graph.emit_graph_record(
                        sink, run_id=run_id, algo="dfedpgp", m=m,
                        seed=args.seed, schedule=schedule, step=r, t0=r,
                        flat=state.flat, mu=state.mu,
                        personal=state.personal, active=active)
                print(f"[train] {obs.record.render(rec)} "
                      f"loss_v={rec['loss_v']:.4f} "
                      f"mu=[{rec['mu_min']:.3f},{rec['mu_max']:.3f}]")
            timer.reset()
    sink.close()
    if args.metrics:
        print(f"[train] metrics -> {args.metrics} "
              f"(render: python -m repro.obs.report {args.metrics})")
    return state


if __name__ == "__main__":
    main()
