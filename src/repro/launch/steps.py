"""Step builders + ShapeDtypeStruct input specs for the production meshes.

Regime B (DESIGN.md §2): each "client" of the paper's decentralized directed
gossip is a data-parallel rank of the mesh holding its OWN personalized
parameter values.  The stacked client axis is a real array axis sharded over
the mesh's data (and pod) axes; the model dims are tensor-parallel over the
`model` axis.  The paper's push-sum gossip of the shared part `u` becomes a
mixing-matrix contraction (baseline, paper-faithful) or a shard_map
ppermute schedule over a one-peer exponential graph (optimized, §Perf).

Layouts
-------
- ``data_clients`` (default): clients over ('pod','data'); TP='model'.
- ``fsdp``: a single client whose weights are FSDP-sharded over 'data' and
  TP-sharded over 'model' — used for deepseek-v2-236b (a 236B-param client
  does not fit a 16-chip TP row) and for long_500k decode (global_batch=1
  cannot feed 16 clients).  On the multi-pod mesh deepseek-v2 keeps one
  client per pod ('pod' = client axis): sparse directed gossip across the
  slow inter-pod links, which is exactly the deployment story the paper
  tells for heterogeneous communication resources.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import InputShape
from repro.core import dfedpgp, partition, topology
from repro.core.gossip import FlatLayout
from repro.models import get_model, prefill_logits
from repro.models.config import ModelConfig
from repro.optim import SGD, SGDState
from . import sharding


class Layout(NamedTuple):
    client_axes: Tuple[str, ...]   # stacked-client dim of every leaf
    batch_axes: Tuple[str, ...]    # within-client batch dim (fsdp layout)
    tp_axes: Tuple[str, ...]
    fsdp_axes: Tuple[str, ...]
    n_clients: int
    per_client_batch: int


# archs whose per-client parameters exceed one 16-chip TP row
FSDP_ARCHS = ("deepseek-v2-236b",)


def decide_layout(mesh: Mesh, arch_id: str, shape: InputShape) -> Layout:
    axes = mesh.axis_names
    multi_pod = "pod" in axes

    def nsize(axs):
        n = 1
        for a in axs:
            n *= mesh.shape[a]
        return n

    if arch_id in FSDP_ARCHS:
        ca = ("pod",) if multi_pod else ()
        m = nsize(ca) if ca else 1
        return Layout(ca, ("data",), ("model",), ("data",), m,
                      shape.global_batch // m)

    client_axes = ("pod", "data") if multi_pod else ("data",)
    m = nsize(client_axes)
    if shape.global_batch < m:
        # long_500k (B=1): one model, weights FSDP over the idle data axis
        fa = ("pod", "data") if multi_pod else ("data",)
        return Layout((), (), ("model",), fa, 1, shape.global_batch)
    return Layout(client_axes, (), ("model",), (), m, shape.global_batch // m)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — never allocated)
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_struct(cfg: ModelConfig, shape: InputShape, lead: Tuple[int, ...]):
    """One model-input batch with leading dims `lead` (e.g. (m, K, B)).

    seq_len is the TOTAL context: for the VLM family the assigned vision
    tokens occupy the first n_vision_tokens positions; for the audio family
    the (stub) conv frontend supplies n_frames frame embeddings and seq_len
    is the decoder length.
    """
    S = shape.seq_len
    if cfg.family == "vlm":
        nv = cfg.n_vision_tokens
        st = S - nv
        return {"tokens": _sds(lead + (st,), jnp.int32),
                "vision": _sds(lead + (nv, cfg.d_model), jnp.float32),
                "labels": _sds(lead + (st,), jnp.int32)}
    if cfg.family == "encdec":
        return {"frames": _sds(lead + (cfg.n_frames, cfg.d_model),
                               jnp.float32),
                "tokens": _sds(lead + (S,), jnp.int32),
                "labels": _sds(lead + (S,), jnp.int32)}
    return {"tokens": _sds(lead + (S,), jnp.int32),
            "labels": _sds(lead + (S,), jnp.int32)}


def stacked_param_struct(cfg: ModelConfig, m: int):
    api = get_model(cfg)

    def init_m():
        keys = jax.random.split(jax.random.PRNGKey(0), m)
        return jax.vmap(lambda k: api.init_params(k, cfg))(keys)

    return jax.eval_shape(init_m)


def input_specs(cfg: ModelConfig, shape: InputShape, layout: Layout,
                k_u: int = 1, k_v: int = 1):
    """ShapeDtypeStructs for the step function's data arguments."""
    m, B = layout.n_clients, layout.per_client_batch
    if shape.kind == "train":
        return {
            "batches": {"v": batch_struct(cfg, shape, (m, k_v, B)),
                        "u": batch_struct(cfg, shape, (m, k_u, B))},
            "P": _sds((m, m), jnp.float32),
        }
    if shape.kind == "prefill":
        b = batch_struct(cfg, shape, (m, B))
        b.pop("labels")
        return {"batch": b}
    # decode: one new token against a seq_len-deep cache / recurrent state
    api = get_model(cfg)
    cache = jax.eval_shape(lambda: api.init_cache(cfg, B, shape.seq_len))
    cache = jax.tree.map(lambda x: _sds((m,) + x.shape, x.dtype), cache)
    return {"cache": cache, "tokens": _sds((m, B, 1), jnp.int32),
            "pos": _sds((), jnp.int32)}


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------
def _axes_or_none(axs):
    if not axs:
        return None
    return tuple(axs) if len(axs) > 1 else axs[0]


def batch_specs(batch_tree, mesh: Mesh, layout: Layout, n_lead: int):
    """Client dim (0) over client_axes; per-client batch dim (n_lead) over
    batch_axes; everything else replicated."""
    ca = _axes_or_none(layout.client_axes)
    ba = _axes_or_none(layout.batch_axes)

    def spec(leaf):
        dims = [None] * leaf.ndim
        if ca is not None and leaf.ndim:
            dims[0] = ca
        if ba is not None and leaf.ndim > n_lead:
            dims[n_lead] = ba
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(spec, batch_tree)


def params_shardings(params_struct, mesh: Mesh, layout: Layout):
    return sharding.params_sharding(
        params_struct, mesh, layout.tp_axes,
        client_axes=layout.client_axes or None,
        fsdp_axes=layout.fsdp_axes)


def state_shardings(state_struct, mesh: Mesh, layout: Layout):
    """Shardings for a DFedPGPState with client-stacked params/opt trees."""
    ps = params_shardings(state_struct.params, mesh, layout)
    ca = _axes_or_none(layout.client_axes)

    def opt_shardings(mom_struct):
        # full-momentum leaves share the param sharding; per-client scalar
        # placeholders (shape (m,)) live on the client axis only.
        def one(param_sh, leaf):
            if leaf.ndim <= 1:
                return NamedSharding(mesh, P(ca) if (ca is not None
                                              and leaf.ndim == 1) else P())
            return param_sh

        return type(mom_struct)(jax.tree.map(one, ps, mom_struct.momentum))

    return dfedpgp.DFedPGPState(
        params=ps,
        mu=NamedSharding(mesh, P(ca) if ca is not None else P()),
        opt_u=opt_shardings(state_struct.opt_u),
        opt_v=opt_shardings(state_struct.opt_v),
        round=NamedSharding(mesh, P()),
    )


def flat_state_shardings(state_struct, mesh: Mesh, layout: Layout):
    """Shardings for a FlatDFedPGPState (the resident Regime B round,
    docs/gossip.md §Regime B resident lifecycle).

    The u-view of the params is gone: the (m, d_flat) buffer IS the shared
    part, sharded rows-over-client-axes / flat-dim-over-TP
    (sharding.flat_buffer_spec), and the (m, d_flat) shared momentum and
    the codec ef/ref memory share its layout exactly.  Personal leaves
    (and their momentum tree) keep the per-leaf param rules; mu rides the
    client axes; round is replicated."""
    ca = _axes_or_none(layout.client_axes)
    d_flat = state_struct.flat.shape[1]
    buf = NamedSharding(mesh, sharding.flat_buffer_spec(
        mesh, layout.client_axes, d_flat, layout.tp_axes))
    personal = params_shardings(state_struct.personal, mesh, layout)
    return dfedpgp.FlatDFedPGPState(
        flat=buf,
        personal=personal,
        mu=NamedSharding(mesh, P(ca) if ca is not None else P()),
        opt_u=SGDState(buf),
        opt_v=SGDState(personal),
        round=NamedSharding(mesh, P()),
        ef=jax.tree.map(lambda _: buf, state_struct.ef),
        ref=jax.tree.map(lambda _: buf, state_struct.ref),
    )


def cache_shardings(cache_struct, mesh: Mesh, layout: Layout):
    """KV caches / recurrent state: (client, [layer-stack,] batch, ...)."""
    ca = _axes_or_none(layout.client_axes)
    ba = _axes_or_none(layout.batch_axes)
    tp = _axes_or_none(layout.tp_axes)
    tp_size = int(np.prod([mesh.shape[a] for a in layout.tp_axes],
                          dtype=np.int64)) if layout.tp_axes else 1
    ba_size = int(np.prod([mesh.shape[a] for a in layout.batch_axes],
                          dtype=np.int64)) if layout.batch_axes else 1

    def spec(leaf):
        dims = [None] * leaf.ndim
        if ca is not None:
            dims[0] = ca
        if ba is not None:
            for i in range(1, min(leaf.ndim, 3)):
                if leaf.shape[i] % ba_size == 0 and leaf.shape[i] >= ba_size:
                    dims[i] = ba
                    break
        for i in range(leaf.ndim - 1, 1, -1):
            if dims[i] is None and leaf.shape[i] % tp_size == 0 \
                    and leaf.shape[i] >= tp_size and leaf.shape[i] > 1:
                dims[i] = tp
                break
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(spec, cache_struct)


# ---------------------------------------------------------------------------
# gossip variants
# ---------------------------------------------------------------------------
def _ppermute_pull(a, rnd_s, axis, m: int, offsets):
    """Inside shard_map: pull `a`'s client-axis shard from the peer at the
    round's schedule offset (offsets[rnd_s mod period])."""
    def branch(off):
        perm = [(i, (i + off) % m) for i in range(m)]
        return jax.lax.ppermute(a, axis, perm)

    return jax.lax.switch(jnp.mod(rnd_s, len(offsets)),
                          [(lambda o=off: branch(o)) for off in offsets])


def _schedule_offsets(schedule, m: int):
    """Resolve the mix's schedule (default: one-peer exponential) and its
    validated per-round permutation offsets."""
    schedule = schedule or topology.TopologySchedule.exponential(m)
    assert schedule.m == m, (schedule.m, m)
    return schedule, schedule.permutation_offsets()


def make_ppermute_mix(mesh: Mesh, layout: Layout, mask, params_struct,
                      wire_dtype=None,
                      schedule: "topology.TopologySchedule | None" = None):
    """Beyond-paper gossip (§Perf): one-peer directed graph via shard_map +
    lax.ppermute along the client axis.

    The per-round permutation offsets are DERIVED from a
    `topology.TopologySchedule` (default: the one-peer exponential graph,
    SGP's B-strongly-connected schedule, B=log2 m) — the same object
    Regime A's simulator mixes with, so one schedule decides who talks to
    whom in both regimes and the two mixes agree leaf-for-leaf
    (tests/test_regime_parity.py).  Round t pulls from the peer at
    offsets[t mod period] with weights (1/2, 1/2) — a doubly-stochastic
    permutation mix, so the push-sum weight stays exactly 1.  Wire bytes:
    |u| per client per round instead of the mixing-matrix contraction's
    m-way reduce.

    Returns mix_fn(params, mu, rnd) -> (params, mu).
    """
    ca = layout.client_axes
    axis = ca if len(ca) > 1 else ca[0]
    m = layout.n_clients
    schedule, offsets = _schedule_offsets(schedule, m)

    ps = params_shardings(params_struct, mesh, layout)
    u_specs = jax.tree.map(lambda s, msk: s.spec if msk else None,
                           ps, mask)

    def mix(params, mu, rnd, P_unused=None):
        u, v = partition.split(params, mask)

        def body(rnd_s, u_shard, mu_shard):
            def mix_leaf(a):
                # quantized push-sum payload: ONLY the permuted copy is
                # narrowed (the wire), the resident copy stays full —
                # wire bytes halve, locally-held precision is unchanged.
                recv = _ppermute_pull(
                    a.astype(wire_dtype) if wire_dtype else a,
                    rnd_s, axis, m, offsets)
                return (a + recv.astype(a.dtype)) * 0.5

            u2 = jax.tree.map(mix_leaf, u_shard)
            mu2 = (mu_shard + _ppermute_pull(mu_shard, rnd_s, axis, m,
                                             offsets)) * 0.5
            return u2, mu2

        u2, mu2 = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), u_specs, P(axis)),
            out_specs=(u_specs, P(axis)))(rnd, u, mu)
        return partition.merge(u2, v), mu2

    return mix


def make_ppermute_mix_flat(mesh: Mesh, layout: Layout, d_flat: int,
                           wire_dtype=None,
                           schedule: "topology.TopologySchedule | None"
                           = None):
    """The resident form of `make_ppermute_mix` (tentpole of docs/gossip.md
    §Regime B resident lifecycle): ONE ppermute of each rank's
    (m_local, d_flat) buffer block plus the mu row, instead of a per-leaf
    tree_map of permutes — for `DFedPGP(mix_fn_flat=...)` /
    `round_fn_flat`.  The permutation offsets come from the SAME
    `TopologySchedule` object Regime A mixes with, so the two regimes
    provably agree (tests/test_regime_parity.py).

    Returns mix_fn(flat, mu, rnd, P_unused) -> (flat, mu)."""
    ca = layout.client_axes
    axis = ca if len(ca) > 1 else ca[0]
    m = layout.n_clients
    schedule, offsets = _schedule_offsets(schedule, m)
    buf_spec = sharding.flat_buffer_spec(mesh, ca, d_flat, layout.tp_axes)

    def mix(flat, mu, rnd, P_unused=None):
        def body(rnd_s, flat_blk, mu_blk):
            recv = _ppermute_pull(
                flat_blk.astype(wire_dtype) if wire_dtype else flat_blk,
                rnd_s, axis, m, offsets)
            flat2 = (flat_blk + recv.astype(flat_blk.dtype)) * 0.5
            mu2 = (mu_blk + _ppermute_pull(mu_blk, rnd_s, axis, m,
                                           offsets)) * 0.5
            return flat2, mu2

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), buf_spec, P(axis)),
            out_specs=(buf_spec, P(axis)))(rnd, flat, mu)

    return mix


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------
def _resolve_regime_b(layout: Layout, spec, gossip, schedule, resident,
                      caller: str):
    """One (gossip, schedule, resident, sample_frac) tuple for the Regime B
    builders.  `spec` (a repro.spec.AlgoSpec) is the new surface: the
    schedule comes from `spec.schedule(layout.n_clients)` and the gossip /
    resident / participation knobs from its fields — one object, no
    duplicated kwargs.  The legacy kwargs keep working for one release;
    passing BOTH a spec and a non-default legacy duplicate raises (the
    silent-disagreement bug the spec kills), and legacy non-default uses
    emit a DeprecationWarning pointing at the factory."""
    if spec is None:
        if gossip != "matrix" or resident or schedule is not None:
            warnings.warn(
                f"{caller}(gossip=/schedule=/resident=) kwargs are "
                f"deprecated: build an AlgoSpec "
                f"(repro.spec.make_algo_spec) and pass spec=",
                DeprecationWarning, stacklevel=3)
        return gossip, schedule, resident, 1.0
    clash = [k for k, v, dflt in (("gossip", gossip, "matrix"),
                                  ("schedule", schedule, None),
                                  ("resident", resident, False))
             if v != dflt]
    if clash:
        raise ValueError(
            f"{caller}(spec=...) conflicts with legacy kwarg(s) {clash}: "
            f"the spec owns them now — drop the duplicates")
    # the spec's engine names map onto Regime B's two mixes: "ppermute"
    # is the shard_map permutation mix; every matrix engine (dense /
    # sparse / pallas) is the mixing-matrix contraction ("matrix")
    b_gossip = "ppermute" if spec.gossip == "ppermute" else "matrix"
    return (b_gossip, spec.schedule(layout.n_clients), spec.resident,
            spec.participation_frac)


def build_train_algo(cfg: ModelConfig, mesh: "Mesh | None", layout: Layout,
                     k_u: int = 1, k_v: int = 1, gossip: str = "matrix",
                     bf16_grads: bool = False, gossip_dtype: str = "",
                     schedule: "topology.TopologySchedule | None" = None,
                     resident: bool = False, lr: float = 0.1, spec=None):
    """-> (algo, mask, params_struct, flat_layout).

    The DFedPGP instance behind a Regime B train round, shared by
    `build_train_step` (which jits it against ShapeDtypeStructs) and
    `launch/train.py` (which initializes REAL state from it) — so every
    driver threads the SAME `TopologySchedule` object into the mix, the
    one-topology invariant of docs/gossip.md.  `schedule` must match the
    layout's client count; `resident=True` builds the flat-buffer form
    (mix_fn_flat / grad_hook_flat; flat_layout is the buffer's static
    wire layout, None otherwise).

    `spec` (repro.spec.AlgoSpec) is the new knob surface: it supplies
    gossip / schedule / resident (and, via build_train_step, sample_frac)
    from the ONE validated object both regimes consume; the individual
    kwargs are the deprecated legacy surface (one release)."""
    gossip, schedule, resident, _ = _resolve_regime_b(
        layout, spec, gossip, schedule, resident, "build_train_algo")
    # in-graph round gauges (repro.obs): spec-only — the legacy kwarg
    # surface predates telemetry and never grows new knobs
    telemetry = spec.telemetry if spec is not None else False
    api = get_model(cfg)

    def loss_fn(p, batch):
        return api.loss_fn(p, batch, cfg)

    params_struct = stacked_param_struct(cfg, layout.n_clients)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params_struct)
    mask = partition.build_mask(template, partition.classifier_personal)
    if schedule is not None:
        # a configured topology that does not match the mesh's client
        # count would silently mix a DIFFERENT graph than the experiment
        # requested (the pre-PR-5 build_train_step ignored `schedule`
        # entirely and always fell back to the default exponential graph)
        assert schedule.m == layout.n_clients, \
            (f"schedule.m={schedule.m} != layout.n_clients="
             f"{layout.n_clients}")
    flat_layout = FlatLayout.build(params_struct, mask) if resident else None
    opt = SGD(lr=lr, momentum=0.9, weight_decay=5e-4)
    mix_fn = mix_fn_flat = None
    if gossip == "ppermute":
        wd = jnp.dtype(gossip_dtype) if gossip_dtype else None
        if resident:
            mix_fn_flat = make_ppermute_mix_flat(
                mesh, layout, flat_layout.d_flat, wire_dtype=wd,
                schedule=schedule)
        else:
            mix_fn = make_ppermute_mix(mesh, layout, mask, params_struct,
                                       wire_dtype=wd, schedule=schedule)
    grad_hook = grad_hook_flat = None
    if bf16_grads:
        # §Perf H2: cast SHARED-part grads to bf16 before the optimizer so
        # the cross-data-shard gradient reduction moves half the bytes.
        # Scoped to the shared mask: the personal (classifier) part never
        # crosses a data shard, so narrowing it would cost precision for
        # zero wire savings.  On the resident path the whole (d_flat,) row
        # IS the shared part, so the flat hook casts it outright.
        grad_hook = lambda g: jax.tree.map(
            lambda x, mk: x.astype(jnp.bfloat16) if (mk and x.ndim) else x,
            g, mask)
        grad_hook_flat = lambda g: g.astype(jnp.bfloat16)
    algo = dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
                           k_v=k_v, k_u=k_u, mix_fn=mix_fn,
                           mix_fn_flat=mix_fn_flat,
                           grad_hook=grad_hook,
                           grad_hook_flat=grad_hook_flat,
                           gossip_dtype=gossip_dtype or None,
                           telemetry=telemetry)
    return algo, mask, params_struct, flat_layout


def _topology_specs(mesh: Mesh, layout: Layout, schedule, dense_struct):
    """(P_struct, P_sharding) for the round's mixing-pattern argument: a
    schedule-driven round passes the schedule's own SparseTopology
    (neighbor tables row-sharded over the client axes); schedule-less
    rounds keep the legacy replicated dense (m, m) matrix."""
    if schedule is None:
        return dense_struct, NamedSharding(mesh, P())
    topo0 = schedule.at(0)
    struct = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), topo0)
    ca = _axes_or_none(layout.client_axes)
    sh = jax.tree.map(
        lambda _: NamedSharding(mesh, P(ca, None)), struct)
    return struct, sh


def build_train_step(cfg: ModelConfig, mesh: Mesh, layout: Layout,
                     shape: InputShape, k_u: int = 1, k_v: int = 1,
                     gossip: str = "matrix", bf16_grads: bool = False,
                     gossip_dtype: str = "",
                     schedule: "topology.TopologySchedule | None" = None,
                     resident: bool = False, sample_frac: float = 1.0,
                     spec=None):
    """-> (train_step, in_shardings, out_shardings, arg_structs).

    train_step(state, P, batches) -> (state, metrics): one DFedPGP round —
    K_v personal steps, K_u shared steps at the de-biased parameters, then
    the directed push-sum mixing of the shared part.

    resident=True is the flat-buffer form (docs/gossip.md §Regime B
    resident lifecycle): the state is a FlatDFedPGPState whose (m, d_flat)
    buffer — not the params tree — is the donated jit carry, local SGD
    runs on unraveled row views, and the mix operates on the buffer
    directly (ppermute block mix / gossip.mix_flat).  `schedule` threads
    the experiment's TopologySchedule into the mix AND switches the P
    argument to the schedule's own SparseTopology form, so one object
    decides who talks to whom in both regimes.

    sample_frac < 1 (docs/scale.md) switches to the partial-participation
    step: train_step(state, P_act, active, batches) gathers the active
    rows, runs the round on the compact (n_active, d_flat) working set and
    scatters back (algo.round_fn_sampled).  The caller draws `active` per
    round from a core.sampling.ParticipationSampler and restricts the
    schedule's round topology with TopologySchedule.induced(t, active).
    Requires resident=True and a schedule; the ppermute mix addresses all
    m shards so gossip="ppermute" cannot sample.

    `spec` (repro.spec.AlgoSpec) supplies gossip / schedule / resident /
    sample_frac from the one validated object (see build_train_algo)."""
    algo, mask, params_struct, flat_layout = build_train_algo(
        cfg, mesh, layout, k_u=k_u, k_v=k_v, gossip=gossip,
        bf16_grads=bf16_grads, gossip_dtype=gossip_dtype,
        schedule=schedule, resident=resident, spec=spec)
    if spec is not None:
        if sample_frac != 1.0:
            raise ValueError(
                "build_train_step(spec=...) conflicts with legacy kwarg "
                "['sample_frac']: the spec owns participation now — drop "
                "the duplicate")
        gossip = "ppermute" if spec.gossip == "ppermute" else "matrix"
        schedule = spec.schedule(layout.n_clients)
        resident = spec.resident
        sample_frac = spec.participation_frac

    specs = input_specs(cfg, shape, layout, k_u=k_u, k_v=k_v)
    b_sh = batch_specs(specs["batches"], mesh, layout, n_lead=2)
    metrics_sh = {k: NamedSharding(mesh, P())
                  for k in ("loss_v", "loss_u", "mu_min", "mu_max")}
    P_struct, P_sh = _topology_specs(mesh, layout, schedule, specs["P"])

    if not 0.0 < sample_frac <= 1.0:
        raise ValueError(f"sample_frac={sample_frac}; want (0, 1]")
    if sample_frac < 1.0:
        if not resident:
            raise ValueError("partial participation gathers/scatters the "
                             "resident flat buffer; pass resident=True")
        if schedule is None:
            raise ValueError("partial participation restricts a "
                             "TopologySchedule per round; pass schedule=")
        if gossip == "ppermute":
            raise ValueError("ppermute offsets address all m shards; the "
                             "sampled round mixes the compact working set "
                             "— use gossip='matrix'")
        m = layout.n_clients
        n_act = max(1, int(round(sample_frac * m)))
        B = layout.per_client_batch
        row_spec = sharding.sampled_buffer_spec(
            mesh, layout.client_axes, n_act, flat_layout.d_flat,
            layout.tp_axes)
        ca_act = row_spec[0] if len(row_spec) else None

        b_struct = {"v": batch_struct(cfg, shape, (n_act, k_v, B)),
                    "u": batch_struct(cfg, shape, (n_act, k_u, B))}
        b_sh = jax.tree.map(
            lambda leaf: NamedSharding(
                mesh, P(ca_act, *([None] * (leaf.ndim - 1)))), b_struct)
        k_nb = schedule.at(0).idx.shape[1]
        P_struct = topology.SparseTopology(
            jax.ShapeDtypeStruct((n_act, k_nb), jnp.int32),
            jax.ShapeDtypeStruct((n_act, k_nb), jnp.float32))
        P_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, P(ca_act, None)), P_struct)
        act_struct = jax.ShapeDtypeStruct((n_act,), jnp.int32)
        act_sh = NamedSharding(mesh, P())   # gathers/scatter prefetch it
        metrics_sh["n_active"] = NamedSharding(mesh, P())

        state_struct = jax.eval_shape(
            lambda p: algo.init_flat(p, flat_layout)[0], params_struct)
        st_sh = flat_state_shardings(state_struct, mesh, layout)

        def train_step(state, P_act, active, batches):
            return algo.round_fn_sampled(state, P_act, active, batches,
                                         flat_layout)

        return (train_step,
                (st_sh, P_sh, act_sh, b_sh),
                (st_sh, metrics_sh),
                (state_struct, P_struct, act_struct, b_struct))

    if resident:
        state_struct = jax.eval_shape(
            lambda p: algo.init_flat(p, flat_layout)[0], params_struct)
        st_sh = flat_state_shardings(state_struct, mesh, layout)

        def train_step(state, Pm, batches):
            return algo.round_fn_flat(state, Pm, batches, flat_layout)
    else:
        state_struct = jax.eval_shape(algo.init, params_struct)
        st_sh = state_shardings(state_struct, mesh, layout)

        def train_step(state, Pm, batches):
            return algo.round_fn(state, Pm, batches)

    return (train_step,
            (st_sh, P_sh, b_sh),
            (st_sh, metrics_sh),
            (state_struct, P_struct, specs["batches"]))


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, layout: Layout,
                       shape: InputShape):
    icfg = cfg.replace(remat=False)

    def prefill_step(params, batch):
        return jax.vmap(lambda p, b: prefill_logits(p, b, icfg))(params,
                                                                 batch)

    params_struct = stacked_param_struct(icfg, layout.n_clients)
    specs = input_specs(icfg, shape, layout)
    ps = params_shardings(params_struct, mesh, layout)
    b_sh = batch_specs(specs["batch"], mesh, layout, n_lead=1)
    out_sh = NamedSharding(mesh, P(_axes_or_none(layout.client_axes),
                                   _axes_or_none(layout.batch_axes)))
    return prefill_step, (ps, b_sh), out_sh, (params_struct, specs["batch"])


def build_decode_step(cfg: ModelConfig, mesh: Mesh, layout: Layout,
                      shape: InputShape):
    icfg = cfg.replace(remat=False)
    api = get_model(icfg)

    def serve_step(params, cache, tokens, pos):
        def one(p, c, t):
            return api.decode_step(p, c, t, pos, icfg)

        return jax.vmap(one)(params, cache, tokens)

    params_struct = stacked_param_struct(icfg, layout.n_clients)
    specs = input_specs(icfg, shape, layout)
    ps = params_shardings(params_struct, mesh, layout)
    c_sh = cache_shardings(specs["cache"], mesh, layout)
    t_sh = batch_specs(specs["tokens"], mesh, layout, n_lead=1)
    logits_sh = NamedSharding(mesh, P(_axes_or_none(layout.client_axes)))
    return (serve_step,
            (ps, c_sh, t_sh, NamedSharding(mesh, P())),
            (logits_sh, c_sh),
            (params_struct, specs["cache"], specs["tokens"], specs["pos"]))


def build_step(cfg: ModelConfig, mesh: Mesh, layout: Layout,
               shape: InputShape, **kw):
    """-> (fn, in_shardings, out_shardings, arg_structs, donate_argnums)."""
    if shape.kind == "train":
        fn, ins, outs, args = build_train_step(cfg, mesh, layout, shape, **kw)
        donate = (0,)          # state
    elif shape.kind == "prefill":
        fn, ins, outs, args = build_prefill_step(cfg, mesh, layout, shape)
        donate = ()
    else:
        fn, ins, outs, args = build_decode_step(cfg, mesh, layout, shape)
        donate = (1,)          # cache
    return fn, ins, outs, args, donate
