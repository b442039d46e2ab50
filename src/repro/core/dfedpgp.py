"""DFedPGP — Algorithm 1, faithful implementation.

Per round t, per client i (vmapped over the stacked client axis):
  1. z_i^{t,0} = u_i^t / mu_i^t                       (de-bias, line 18 prev round)
  2. K_v SGD steps on the personal part v_i at fixed z_i^{t,0}   (lines 5-8)
  3. K_u SGD steps on the shared part u_i, gradient evaluated at
     z_i^{t,k} = u_i^{t,k} / mu_i^t                             (lines 9-12)
  4. push/pull (p_{j,i} u, p_{j,i} mu) over the directed graph  (lines 14-17)
     -> u_i^{t+1} = sum_j p_ij u_j^{t+1/2},  mu_i^{t+1} = sum_j p_ij mu_j

The mixing matrix P_t is row-stochastic (pull form, paper Appendix B) and
time-varying.  Gradients are taken on the full model once per step and
masked to the active part — same compute as the paper's alternating scheme.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs import gauges as obs_gauges
from repro.optim import SGD, SGDState
from . import gossip, local, partition

# the device scope of the u-step update (SGD on the shared part and the
# step-gate blend), nested in `dfedpgp.local`
U_UPDATE_SCOPE = "dfedpgp.u_update"


def _check_uniform_dtype(layout) -> None:
    """The resident buffer (params AND momentum) carries one dtype while
    the tree path accumulates per leaf — mixed shared dtypes would silently
    break the bit-compatibility contract, so both flat-state constructors
    (init_flat, state_to_flat) refuse them."""
    if len(set(layout.dtypes)) > 1:
        raise ValueError(
            f"resident flat buffer needs a uniform shared-leaf dtype "
            f"(got {sorted({str(d) for d in layout.dtypes})}); mixed-"
            f"dtype shared parts must use the tree-form round_fn")


def _unit_gates(batches_u):
    """(m, K_u) all-ones step gates, derived from the batch so that they
    carry its client-axis sharding (vmap refuses mapped inputs sharded
    differently)."""
    leaf = jax.tree.leaves(batches_u)[0]
    lead = leaf[(slice(None), slice(None)) + (0,) * (leaf.ndim - 2)]
    return jnp.ones_like(lead, jnp.float32)


class DFedPGPState(NamedTuple):
    params: Any            # stacked (m, ...) — biased u leaves + personal v leaves
    mu: jnp.ndarray        # (m,)
    opt_u: SGDState
    opt_v: SGDState
    round: jnp.ndarray     # scalar int32


class FlatDFedPGPState(NamedTuple):
    """Resident-buffer round state (docs/gossip.md "resident buffer
    lifecycle"): the shared part lives in the (m, d_flat) buffer ACROSS
    rounds — packed once at init, mixed in place every round, unraveled
    into leaf views only at the loss_fn / eval boundary.  Numerically
    bit-compatible with DFedPGPState (tests/test_resident_buffer.py);
    `DFedPGP.state_to_flat` / `state_from_flat` convert."""
    flat: jnp.ndarray      # (m, d_flat) biased shared buffer u
    personal: Any          # personal leaves (m, ...); None at shared slots
    mu: jnp.ndarray        # (m,)
    opt_u: SGDState        # momentum: ONE (m, d_flat) buffer
    opt_v: SGDState        # momentum: personal-leaf tree
    round: jnp.ndarray     # scalar int32
    # wire-codec memory (docs/compress.md): the error-feedback residual
    # and the public reference (tracking) copies — (m, d_flat) f32 for
    # lossy codecs, None otherwise (empty pytree slots — codec-free
    # states are unchanged)
    ef: Any = None
    ref: Any = None


@dataclasses.dataclass(frozen=True)
class DFedPGP:
    loss_fn: Callable              # (params, batch) -> scalar
    mask: Any                      # shared(=True)/personal partition
    opt_u: SGD = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    opt_v: SGD = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    k_v: int = 1                   # personal local steps per round
    k_u: int = 5                   # shared local steps per round
    lr_decay: float = 0.99
    # optional gossip override (params, mu, round, P) -> (params, mu); the
    # tree-form datacenter mix (Regime B's legacy ppermute path, §Perf)
    mix_fn: Optional[Callable] = None
    # optional gossip override on the RESIDENT buffer:
    # (flat, mu, round, P) -> (flat, mu).  This is how Regime B's
    # shard_map mixes (steps.make_ppermute_mix_flat, kernel_mix's flat
    # entry) ride round_fn_flat — the override sees the (m, d_flat)
    # buffer directly, never a tree (docs/gossip.md §Regime B resident)
    mix_fn_flat: Optional[Callable] = None
    # optional hook applied to the shared-part gradients before the
    # optimizer (e.g. bf16 cast so the FSDP reduction runs at half the wire
    # bytes, or a sharding constraint steering GSPMD to reduce-scatter)
    grad_hook: Optional[Callable] = None
    # the resident-path twin: applied to the one (d_flat,) gradient row.
    # Tree hooks expect per-leaf pytrees and would silently misapply to
    # the row, so the flat round only accepts this form (round_fn_flat
    # still raises when only the tree hook is set).
    grad_hook_flat: Optional[Callable] = None
    # gossip payload dtype ("bfloat16" halves the wire bytes of the
    # push-pull transmission — the quantized push-sum of Taheri et al.
    # [ICML'20], which the paper cites for communication efficiency).
    # Push-sum tolerates the quantization: mu stays f32, z = u/mu de-biases.
    gossip_dtype: Optional[str] = None
    # gossip engine for the push-pull transmission (docs/gossip.md):
    #   "sparse" (default) — O(m*k*d) neighbor-indexed gather over the flat
    #            shared buffer; needs a SparseTopology P (falls back to the
    #            dense path when handed a dense matrix);
    #   "dense"  — legacy per-leaf einsum against the (m, m) matrix;
    #   "pallas" — the fused gossip_gather kernel (TPU; interpret on CPU).
    gossip: str = "sparse"
    # optional wire codec for the push-pull payload (repro.compress,
    # docs/compress.md): what each client's row looks like ON THE WIRE.
    # Lossy codecs carry error-feedback memory in FlatDFedPGPState.ef;
    # the identity codec is bit-for-bit the codec-free path.  Resident
    # path only (round_fn_flat / the async runtime) — the tree-form
    # round_fn raises.  Mutually exclusive with gossip_dtype (the codec
    # IS the wire format).
    codec: Optional[Any] = None
    # consensus step size for lossy codecs (CHOCO-Gossip): the codec mix
    # runs on P_g = (1-g) I + g P.  Sparse codecs (topk/randk) can only
    # publish K coordinates per crossing, so g < 1 slows consensus to the
    # pipe's delivery rate — without it the error-feedback memory grows
    # instead of draining (docs/compress.md §Step size).  "auto" anneals
    # the step per round from the residual-to-signal ratio instead of a
    # static guess: g = ||u|| / (||u|| + ||ef||), clipped to [0.05, 1] —
    # a draining residual pushes g back toward the plain tracked mix, a
    # growing one backs consensus off until the pipe catches up
    # (docs/compress.md §Step size; resident sync rounds only).
    codec_gamma: Any = 1.0         # float in (0, 1], or "auto"
    # in-graph round gauges (repro.obs, docs/observability.md): when True
    # the resident rounds return extra f32 reductions in `metrics`
    # (consensus gap, mass ledger, EF ratio, grad/update norms, wire
    # edges).  STATIC — the gauges are pure reads next to the donated
    # carry, and with telemetry=False the traced round is the exact
    # uninstrumented program (tests/test_obs.py pins bit-for-bit).
    telemetry: bool = False

    # ------------------------------------------------------------------
    def init(self, stacked_params) -> DFedPGPState:
        m = jax.tree.leaves(stacked_params)[0].shape[0]

        def part_momentum(keep_shared: bool):
            # full momentum only for the part this phase trains; the other
            # part gets a per-client scalar placeholder (vmap-compatible).
            return SGDState(jax.tree.map(
                lambda p, msk: jnp.zeros_like(p) if msk == keep_shared
                else jnp.zeros(p.shape[:1], p.dtype),
                stacked_params, self.mask))

        return DFedPGPState(
            params=stacked_params,
            mu=jnp.ones((m,), jnp.float32),
            opt_u=part_momentum(True),
            opt_v=part_momentum(False),
            round=jnp.zeros((), jnp.int32),
        )

    # ------------------------------------------------------------------
    def local_update(self, params, mu_i, opt_u, opt_v, batches_v, batches_u,
                     lr_scale, step_gate_u=None):
        """One client's alternating update. params: unstacked pytree."""
        mask = self.mask

        def debias_leaf(p, m):
            # cast back: mu is f32; without the cast the de-biased view of
            # EVERY shared weight (and hence the FSDP gathers and the
            # backward reductions) silently promotes to f32 — 2x the wire
            # and HBM bytes when params are bf16 (§Perf P2).
            return (p / mu_i).astype(p.dtype) if m else p

        def rebias_leaf(p, m):
            return p * mu_i if m else p

        # ---- v-steps at fixed z^{t,0} (personal gradient only) ----
        z = jax.tree.map(debias_leaf, params, mask)

        def v_loss(p, batch):
            # gradient flows to v leaves only; u leaves pinned at z^{t,0}
            pz = partition.where(mask, jax.tree.map(jax.lax.stop_gradient, z), p)
            return self.loss_fn(pz, batch)

        params_v, opt_v, loss_v = local.sgd_steps(
            v_loss, self.opt_v, params, opt_v, batches_v, lr_scale,
            grad_filter=lambda g, p: local.masked_grads(g, mask, keep_shared=False))
        params = partition.where(mask, params, params_v)   # take new v only

        # ---- u-steps: gradient evaluated at z^{t,k} = u^{t,k}/mu, applied to
        # the *biased* u with lr eta_u (Algorithm 1 lines 10-11, exactly) ----
        K_u = jax.tree.leaves(batches_u)[0].shape[0]

        def u_step(carry, xs):
            p, s = carry
            batch, k = xs
            z_k = jax.tree.map(debias_leaf, p, mask)
            loss, g = jax.value_and_grad(self.loss_fn)(z_k, batch)
            g = local.masked_grads(g, mask, keep_shared=True)
            if self.grad_hook is not None:
                g = self.grad_hook(g)
            with jax.named_scope(U_UPDATE_SCOPE):
                p2, s2 = self.opt_u.update(g, s, p, lr_scale)
                if step_gate_u is not None:
                    gate = step_gate_u[k]
                    blend = lambda new, old: jax.tree.map(
                        lambda a, b: (gate * a + (1.0 - gate) * b
                                      ).astype(a.dtype), new, old)
                    p2 = blend(p2, p)
                    s2 = SGDState(blend(s2.momentum, s.momentum))
            # personal leaves must not move in the u-phase
            p2 = partition.where(mask, p2, p)
            return (p2, s2), loss

        (params, opt_u), losses_u = jax.lax.scan(
            u_step, (params, opt_u), (batches_u, jnp.arange(K_u)))
        loss_u = jnp.mean(losses_u)
        return params, opt_u, opt_v, (loss_v, loss_u)

    # ------------------------------------------------------------------
    def round_fn(self, state: DFedPGPState, P, batches, step_gate_u=None):
        """batches: {'v': leaves (m, K_v, B, ...), 'u': leaves (m, K_u, B, ...)}.
        P: the round's mixing pattern — a topology.SparseTopology (preferred;
        enables the O(m*k*d) gossip engines) or a dense (m, m) matrix.
        step_gate_u: optional (m, K_u) gates for computation heterogeneity."""
        if self.codec is not None:
            raise ValueError("wire codecs ride the resident flat buffer "
                             "(round_fn_flat / the async runtime); the "
                             "tree-form round_fn has no payload boundary")
        if self.telemetry:
            raise ValueError("telemetry gauges read the resident "
                             "(m, d_flat) buffer (round_fn_flat / "
                             "round_fn_sampled); the tree-form round_fn "
                             "has no buffer to gauge")
        lr_scale = self.lr_decay ** state.round.astype(jnp.float32)
        if step_gate_u is None:
            step_gate_u = _unit_gates(batches["u"])

        params, opt_u, opt_v, (loss_v, loss_u) = jax.vmap(
            self.local_update, in_axes=(0, 0, 0, 0, 0, 0, None, 0))(
                state.params, state.mu, state.opt_u, state.opt_v,
                batches["v"], batches["u"], lr_scale, step_gate_u)

        # ---- push/pull transmission on the shared part ----
        if self.mix_fn is not None:
            params, mu = self.mix_fn(params, state.mu, state.round, P)
        else:
            params, mu = gossip.gossip_mix(
                params, state.mu, P, self.mask, mode=self.gossip,
                wire_dtype=self.gossip_dtype)

        new_state = DFedPGPState(params, mu, opt_u, opt_v, state.round + 1)
        metrics = {"loss_v": jnp.mean(loss_v), "loss_u": jnp.mean(loss_u),
                   "mu_min": jnp.min(mu), "mu_max": jnp.max(mu)}
        return new_state, metrics

    # ------------------------------------------------------------------
    # resident flat-buffer path (tentpole of docs/gossip.md §resident):
    # the shared part stays in the (m, d_flat) buffer between rounds, so
    # the per-round flatten/unflatten of round_fn is gone entirely.
    # ------------------------------------------------------------------
    def init_flat(self, stacked_params,
                  layout: Optional[gossip.FlatLayout] = None):
        """-> (FlatDFedPGPState, FlatLayout).  Packs the shared part ONCE
        (gossip.FlatClientState); every subsequent round operates on the
        resident buffer.

        Requires a UNIFORM shared-leaf dtype: the buffer (and hence the
        optimizer update and momentum) carries one dtype, while the tree
        path accumulates per leaf — with mixed shared dtypes (e.g. bf16
        body + f32 norms) the two paths would silently diverge, breaking
        the bit-compatibility contract.  Mixed-dtype models use round_fn.
        """
        fcs, layout = gossip.FlatClientState.create(stacked_params,
                                                    self.mask, layout)
        _check_uniform_dtype(layout)
        self._check_codec()
        m = jax.tree.leaves(stacked_params)[0].shape[0]
        from repro.compress import init_ef, init_ref
        return FlatDFedPGPState(
            flat=fcs.flat,
            personal=fcs.personal,
            mu=jnp.ones((m,), jnp.float32),
            opt_u=SGDState(jnp.zeros_like(fcs.flat)),
            opt_v=SGDState(jax.tree.map(jnp.zeros_like, fcs.personal)),
            round=jnp.zeros((), jnp.int32),
            ef=init_ef(self.codec, fcs.flat),
            ref=init_ref(self.codec, fcs.flat),
        ), layout

    def _apply_flat_grad_hook(self, g):
        """The (d_flat,) gradient-row hook of the resident path.  Falls back
        to the tree hook for callers driving local_update_flat directly
        with a row-shaped hook (round_fn_flat itself refuses that case —
        see its guard)."""
        if self.grad_hook_flat is not None:
            return self.grad_hook_flat(g)
        if self.grad_hook is not None:
            return self.grad_hook(g)
        return g

    def _check_codec(self) -> None:
        if self.codec is not None and self.mix_fn_flat is not None:
            raise ValueError("codec and mix_fn_flat are mutually "
                             "exclusive: the codec path owns the wire "
                             "crossing (gossip.mix_flat) — a mix override "
                             "would bypass the error-feedback ledger")
        if isinstance(self.codec_gamma, str):
            if self.codec_gamma != "auto":
                raise ValueError(
                    f"codec_gamma must be a float in (0, 1] or 'auto'; "
                    f"got {self.codec_gamma!r}")
            if self.codec is None or self.codec.exact:
                raise ValueError(
                    "codec_gamma='auto' anneals the lossy-codec consensus "
                    "step; the exact/uncompressed mix never blends (drop "
                    "the knob or use a lossy codec)")
            if self.gossip_dtype is not None:
                raise ValueError("codec and gossip_dtype are mutually "
                                 "exclusive: the codec IS the wire format")
            return
        g = float(self.codec_gamma)
        if self.codec is None or self.codec.exact:
            # same loud-knob rule as block_m: a consensus step only
            # exists on the LOSSY codec path — the exact/uncompressed
            # mixes never blend, so a stray gamma raises instead of
            # silently running a different experiment than requested
            if g != 1.0:
                raise ValueError(
                    f"codec_gamma={g} only applies to lossy codecs; the "
                    f"exact/uncompressed mix never blends (drop the knob "
                    f"or use a lossy codec)")
            if self.codec is None:
                return
        if self.gossip_dtype is not None:
            raise ValueError("codec and gossip_dtype are mutually "
                             "exclusive: the codec IS the wire format")
        # validated here so BOTH regimes reject a bad consensus step at
        # build time (the async tick would otherwise blend an
        # extrapolated or degenerate mixing matrix without ever reaching
        # mix_flat's own check)
        if not 0.0 < g <= 1.0:
            raise ValueError(f"codec_gamma must be in (0, 1], got "
                             f"{self.codec_gamma}")

    def _gamma_value(self, flat, ef):
        """The round's consensus step size: the static knob as-is, or the
        adaptive anneal (codec_gamma="auto") — a traced f32 scalar
        g = ||u|| / (||u|| + ||ef||) over the round's working set, clipped
        to [0.05, 1].  With a zero residual the ratio is exactly 1.0 (the
        plain tracked mix); as the error-feedback memory grows relative to
        the signal, g backs off so the sparse pipe drains instead of
        accumulating (docs/compress.md §Step size).

        The ratio itself is `obs.gauges.ef_signal_ratio` — ONE definition
        shared by the anneal and the telemetry stream, so the gauge a run
        records is exactly the step size the mix used."""
        if not isinstance(self.codec_gamma, str):
            return self.codec_gamma
        return jnp.clip(obs_gauges.ef_signal_ratio(flat, ef), 0.05, 1.0)

    def _round_gauges(self, *, flat, mu, mu_pre, upd_before, upd_after,
                      ef_pre, grad_norm, P, active_mask=None):
        """The telemetry=True aux pack of the resident rounds (repro.obs,
        docs/observability.md §Gauges): pure f32 reductions over the
        post-round buffer — consensus gap, mass ledger, grad/update norms,
        wire edges, moved mass, and (lossy codecs) the EF signal ratio
        the "auto" anneal reads.  Never touches the state that flows on.
        mu_pre: the PRE-mix push-sum weights — the mass that was in
        motion this round (obs.graph.moved_mass)."""
        from repro.obs import graph as obs_graph
        g = dict(obs_gauges.consensus_gap(flat, mu))
        g.update(obs_gauges.mass_ledger(mu, active_mask))
        g["update_norm"] = obs_gauges.buffer_update_norm(upd_before,
                                                         upd_after)
        g["grad_norm"] = grad_norm
        g["wire_edges"] = obs_gauges.wire_edges(P)
        g["moved_mass"] = obs_graph.moved_mass(P, mu_pre)
        if ef_pre is not None:
            # same working set as _gamma_value: post-local signal vs the
            # residual the mix is about to drain
            g["ef_ratio"] = obs_gauges.ef_signal_ratio(upd_after, ef_pre)
        return g

    # ------------------------------------------------------------------
    def local_update_flat(self, flat_row, personal, mu_i, opt_u, opt_v,
                          batches_v, batches_u, lr_scale, step_gate_u,
                          layout: gossip.FlatLayout):
        """One client's alternating update on the resident view.
        flat_row: (d_flat,) biased shared row; personal: unstacked personal
        leaves.  The tree form exists only inside loss_fn (unravel at the
        leaf boundary via local.flat_view_loss)."""
        # ---- v-steps at fixed z^{t,0} (personal gradient only).  K_v = 0
        # (the all-shared OSGP/DFedAvgM cores on this engine) skips the
        # phase statically: there is no personal part to step and an empty
        # scan's mean-loss would be NaN ----
        if jax.tree.leaves(batches_v)[0].shape[0] == 0:
            loss_v = jnp.zeros((), jnp.float32)
        else:
            z_shared = layout.unravel_row(
                (flat_row / mu_i).astype(flat_row.dtype))
            z_pinned = jax.tree.map(jax.lax.stop_gradient, z_shared)

            def v_loss(pv, batch):
                return self.loss_fn(partition.merge(z_pinned, pv), batch)

            personal, opt_v, loss_v = local.sgd_steps(
                v_loss, self.opt_v, personal, opt_v, batches_v, lr_scale)

        # ---- u-steps: gradient at z^{t,k} = u^{t,k}/mu, applied to the
        # biased flat row (Algorithm 1 lines 10-11 on the buffer) ----
        K_u = jax.tree.leaves(batches_u)[0].shape[0]
        flat_loss = local.flat_view_loss(self.loss_fn, layout, personal)

        def u_step(carry, xs):
            row, s = carry
            batch, k = xs
            # gradient EVALUATED AT z^{t,k} = u^{t,k}/mu and applied to the
            # biased row — NOT differentiated through the de-bias (that
            # would scale the gradient by 1/mu; Algorithm 1 lines 10-11,
            # same as the tree path's value_and_grad(loss_fn)(z_k))
            z_row = (row / mu_i).astype(row.dtype)
            loss, g = jax.value_and_grad(flat_loss)(z_row, batch)
            g = self._apply_flat_grad_hook(g)
            with jax.named_scope(U_UPDATE_SCOPE):
                row2, s2 = self.opt_u.update(g, s, row, lr_scale)
                if step_gate_u is not None:
                    gate = step_gate_u[k]
                    blend = lambda new, old: (gate * new + (1.0 - gate) * old
                                              ).astype(new.dtype)
                    row2 = blend(row2, row)
                    s2 = SGDState(blend(s2.momentum, s.momentum))
            if self.telemetry:
                # gauge the POST-HOOK shared gradient (what the optimizer
                # consumed); static gate, so the off-path scan carries the
                # exact uninstrumented output structure
                return (row2, s2), (loss,
                                    jnp.linalg.norm(g.astype(jnp.float32)))
            return (row2, s2), loss

        (flat_row, opt_u), aux_u = jax.lax.scan(
            u_step, (flat_row, opt_u), (batches_u, jnp.arange(K_u)))
        if self.telemetry:
            losses_u, gnorms_u = aux_u
            return flat_row, personal, opt_u, opt_v, (
                loss_v, jnp.mean(losses_u), jnp.mean(gnorms_u))
        return flat_row, personal, opt_u, opt_v, (loss_v, jnp.mean(aux_u))

    # ------------------------------------------------------------------
    def tick_update_flat(self, flat_row, personal, mu_i, opt_u, opt_v,
                         batch, in_v_phase, lr_scale,
                         layout: gossip.FlatLayout,
                         has_v_phase: bool = True):
        """ONE tick of the alternating update on the resident view — the
        async heterogeneity runtime's step primitive (repro.hetero.runtime
        vmaps this per client; docs/hetero.md).

        Computes a single v-step (personal part; the u gradient never
        flows, and u does not move during the v-phase, so de-biasing the
        CURRENT row reproduces the z^{t,0} pin of local_update_flat) and a
        single u-step (gradient at z^{t,k} = u^{t,k}/mu applied to the
        biased row — Algorithm 1 lines 10-11), then selects by the traced
        per-client `in_v_phase`.  The two branches touch disjoint state
        (personal/opt_v vs flat/opt_u), so selection is exact: running
        k_v v-ticks then k_u u-ticks is bit-identical to one
        local_update_flat call on the same batches.

        has_v_phase is STATIC: the k_v == 0 configurations (full-model
        push-sum — async OSGP/DFedAvgM) skip the v branch entirely rather
        than paying a dead gradient per tick.
        """
        z_row = (flat_row / mu_i).astype(flat_row.dtype)
        if has_v_phase:
            z_pinned = jax.tree.map(jax.lax.stop_gradient,
                                    layout.unravel_row(z_row))

            def v_loss(pv, b):
                return self.loss_fn(partition.merge(z_pinned, pv), b)

            loss_v, g_v = jax.value_and_grad(v_loss)(personal, batch)
            pv2, sv2 = self.opt_v.update(g_v, opt_v, personal, lr_scale)

        flat_loss = local.flat_view_loss(self.loss_fn, layout, personal)
        loss_u, g_u = jax.value_and_grad(flat_loss)(z_row, batch)
        g_u = self._apply_flat_grad_hook(g_u)
        with jax.named_scope(U_UPDATE_SCOPE):
            row2, su2 = self.opt_u.update(g_u, opt_u, flat_row, lr_scale)

        if not has_v_phase:
            return row2, personal, su2, opt_v, loss_u

        sel_v = lambda a, b: jnp.where(in_v_phase, a, b)
        flat_out = sel_v(flat_row, row2)
        opt_u_out = SGDState(sel_v(opt_u.momentum, su2.momentum))
        personal_out = jax.tree.map(sel_v, pv2, personal)
        opt_v_out = SGDState(jax.tree.map(sel_v, sv2.momentum,
                                          opt_v.momentum))
        return (flat_out, personal_out, opt_u_out, opt_v_out,
                sel_v(loss_v, loss_u))

    # ------------------------------------------------------------------
    def round_fn_flat(self, state: FlatDFedPGPState, P, batches,
                      layout: gossip.FlatLayout, step_gate_u=None):
        """Resident-buffer round: local steps on unraveled views, then the
        push-pull mixes the buffer in place (gossip.mix_flat, or a
        mix_fn_flat override operating directly on the (m, d_flat) buffer
        — Regime B's shard_map ppermute / fused-kernel mixes).  Tree-form
        mix_fn overrides need round_fn."""
        if self.mix_fn is not None and self.mix_fn_flat is None:
            raise ValueError("mix_fn overrides operate on tree-form "
                             "leaves; the resident path mixes the flat "
                             "buffer directly — provide mix_fn_flat "
                             "(steps.make_ppermute_mix_flat, "
                             "kernel_mix.make_kernel_mix_flat) or use the "
                             "tree-form round_fn")
        if self.grad_hook is not None and self.grad_hook_flat is None:
            # tree-path hooks see per-leaf gradients (e.g. sharding
            # constraints with a leaf-spec pytree); here the gradient is
            # one (d_flat,) row — refuse rather than silently hand a hook
            # the wrong structure.  (local_update_flat does apply the hook
            # to the flat row for callers driving it directly.)
            raise ValueError("grad_hook expects tree-form shared-part "
                             "gradients; provide grad_hook_flat (the "
                             "(d_flat,) row form) or use the tree-form "
                             "round_fn")
        lr_scale = self.lr_decay ** state.round.astype(jnp.float32)
        if step_gate_u is None:
            step_gate_u = _unit_gates(batches["u"])

        def client(flat_row, personal, mu_i, opt_u, opt_v, bv, bu, gate):
            return self.local_update_flat(
                flat_row, personal, mu_i, opt_u, opt_v, bv, bu,
                lr_scale, gate, layout)

        with jax.named_scope("dfedpgp.local"):
            flat, personal, opt_u, opt_v, aux = jax.vmap(client)(
                state.flat, state.personal, state.mu, state.opt_u,
                state.opt_v, batches["v"], batches["u"], step_gate_u)
        loss_v, loss_u = aux[0], aux[1]
        flat_local = flat     # post-local / pre-mix view (update gauge)

        with jax.named_scope("dfedpgp.mix"):
            if self.mix_fn_flat is not None:
                # resident mix override (Regime B): the shard_map ppermute
                # / fused-kernel mixes consume the buffer as-is
                flat, mu = self.mix_fn_flat(flat, state.mu, state.round, P)
                ef, ref = state.ef, state.ref
            elif self.codec is not None:
                # one wire crossing per round: the codec key folds the
                # round index in, so randomized codecs (randk, qsgd)
                # redraw per round deterministically in (codec.seed, round)
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self.codec.seed), state.round)
                flat, mu, ef, ref = gossip.mix_flat(
                    P, flat, state.mu, mode=self.gossip, codec=self.codec,
                    ef=state.ef, ref=state.ref, key=key,
                    codec_gamma=self._gamma_value(flat, state.ef))
            else:
                flat, mu = gossip.mix_flat(P, flat, state.mu,
                                           mode=self.gossip,
                                           wire_dtype=self.gossip_dtype)
                ef, ref = state.ef, state.ref
        new_state = FlatDFedPGPState(flat, personal, mu, opt_u, opt_v,
                                     state.round + 1, ef, ref)
        metrics = {"loss_v": jnp.mean(loss_v), "loss_u": jnp.mean(loss_u),
                   "mu_min": jnp.min(mu), "mu_max": jnp.max(mu)}
        if self.telemetry:
            metrics.update(self._round_gauges(
                flat=flat, mu=mu, mu_pre=state.mu, upd_before=state.flat,
                upd_after=flat_local, ef_pre=state.ef,
                grad_norm=jnp.mean(aux[2]), P=P))
        return new_state, metrics

    # ------------------------------------------------------------------
    def round_fn_sampled(self, state: FlatDFedPGPState, P_act, active,
                         batches, layout: gossip.FlatLayout,
                         step_gate_u=None):
        """Partial-participation resident round (docs/scale.md): only the
        `active` clients act.  Their rows (params, mu, momentum, ef/ref)
        are gathered from the resident buffer, the usual local steps +
        directed mixing run on the compact (n_active, d_flat) working set,
        and the results scatter back — under gossip="pallas" through the
        kernels/gossip_scatter.py kernel, which aliases the big buffer and
        never touches a dormant row.

        P_act: the round's topology RESTRICTED to the active subset in
        compact ids (topology.induced_subgraph / TopologySchedule.induced
        with renorm="row" — the sum-preserving re-normalization is what
        makes active=arange(m) bit-identical to round_fn_flat,
        tests/test_sampling.py).  active: (n_active,) unique global ids,
        sorted (the sampler's output); batches and step_gate_u are COMPACT
        — leaves lead with (n_active, K, B, ...).

        Dormant rows are exactly frozen: params, momentum, codec memory
        and mu never move (the sync pull mix is row-stochastic, so no
        active client's weight references a dormant row after the induced
        re-normalization, and Σmu over dormant rows is conserved
        trivially).  Metrics are means over the ACTIVE clients; mu stats
        span the full buffer."""
        if self.mix_fn is not None or self.mix_fn_flat is not None:
            raise ValueError(
                "mix overrides operate on the full resident buffer "
                "(ppermute offsets address all m shards); the sampled "
                "round mixes the compact working set — drop the override "
                "or use round_fn_flat")
        if self.grad_hook is not None and self.grad_hook_flat is None:
            raise ValueError("grad_hook expects tree-form shared-part "
                             "gradients; provide grad_hook_flat (the "
                             "(d_flat,) row form) or use the tree-form "
                             "round_fn")
        lr_scale = self.lr_decay ** state.round.astype(jnp.float32)
        active = jnp.asarray(active, jnp.int32)
        if step_gate_u is None:
            step_gate_u = _unit_gates(batches["u"])

        take = lambda a: jnp.take(a, active, axis=0)
        with jax.named_scope("dfedpgp.gather"):
            flat_a = take(state.flat)
            mu_a = take(state.mu)
            opt_u_a = SGDState(take(state.opt_u.momentum))
            personal_a = jax.tree.map(take, state.personal)
            opt_v_a = SGDState(jax.tree.map(take, state.opt_v.momentum))
        flat_pre = flat_a     # gathered pre-local rows (update gauge)

        def client(flat_row, personal, mu_i, opt_u, opt_v, bv, bu, gate):
            return self.local_update_flat(
                flat_row, personal, mu_i, opt_u, opt_v, bv, bu,
                lr_scale, gate, layout)

        with jax.named_scope("dfedpgp.local"):
            flat_a, personal_a, opt_u_a, opt_v_a, aux = jax.vmap(
                client)(flat_a, personal_a, mu_a, opt_u_a, opt_v_a,
                        batches["v"], batches["u"], step_gate_u)
        loss_v, loss_u = aux[0], aux[1]
        flat_local = flat_a   # post-local / pre-mix compact rows
        mu_pre = mu_a         # pre-mix compact mu (moved-mass gauge)
        ef_pre = take(state.ef) if self.codec is not None else None

        with jax.named_scope("dfedpgp.mix"):
            if self.codec is not None:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self.codec.seed), state.round)
                ef_a = ef_pre
                ref_a = take(state.ref)
                flat_a, mu_a, ef_a, ref_a = gossip.mix_flat(
                    P_act, flat_a, mu_a, mode=self.gossip, codec=self.codec,
                    ef=ef_a, ref=ref_a, key=key,
                    codec_gamma=self._gamma_value(flat_a, ef_a))
            else:
                ef_a = ref_a = None
                flat_a, mu_a = gossip.mix_flat(
                    P_act, flat_a, mu_a, mode=self.gossip,
                    wire_dtype=self.gossip_dtype)

        # ---- scatter the compact working set back; dormant rows never
        # materialize (the pallas path aliases the buffer in place) ----
        if self.gossip == "pallas":
            from repro.kernels import ops
            put = lambda buf, new: ops.gossip_scatter(active, new, buf,
                                                      force="pallas")
        else:
            put = lambda buf, new: buf.at[active].set(new.astype(buf.dtype))
        with jax.named_scope("dfedpgp.scatter"):
            flat = put(state.flat, flat_a)
            mu = state.mu.at[active].set(mu_a)
            opt_u = SGDState(put(state.opt_u.momentum, opt_u_a.momentum))
            personal = jax.tree.map(
                lambda full, new: full.at[active].set(new),
                state.personal, personal_a)
            opt_v = SGDState(jax.tree.map(
                lambda full, new: full.at[active].set(new),
                state.opt_v.momentum, opt_v_a.momentum))
            ef = state.ef if ef_a is None else put(state.ef, ef_a)
            ref = state.ref if ref_a is None else put(state.ref, ref_a)

        new_state = FlatDFedPGPState(flat, personal, mu, opt_u, opt_v,
                                     state.round + 1, ef, ref)
        metrics = {"loss_v": jnp.mean(loss_v), "loss_u": jnp.mean(loss_u),
                   "mu_min": jnp.min(mu), "mu_max": jnp.max(mu),
                   "n_active": jnp.asarray(active.shape[0], jnp.int32)}
        if self.telemetry:
            # ledger over the FULL buffer with the dormant split visible;
            # consensus gap likewise spans all m rows (dormant rows count
            # — they are what the sampled round leaves behind)
            active_mask = jnp.zeros(state.mu.shape, bool).at[active].set(
                True)
            metrics.update(self._round_gauges(
                flat=flat, mu=mu, mu_pre=mu_pre, upd_before=flat_pre,
                upd_after=flat_local, ef_pre=ef_pre,
                grad_norm=jnp.mean(aux[2]), P=P_act,
                active_mask=active_mask))
        return new_state, metrics

    # ------------------------------------------------------------------
    def eval_params_flat(self, state: FlatDFedPGPState,
                         layout: gossip.FlatLayout):
        """Personalized models from the resident buffer: de-bias the
        buffer, unravel once (the eval boundary), merge personal."""
        z = state.flat / state.mu[:, None].astype(state.flat.dtype)
        return gossip.FlatClientState(z, state.personal).to_tree(layout)

    # ------------------------------------------------------------------
    def state_to_flat(self, state: DFedPGPState,
                      layout: Optional[gossip.FlatLayout] = None):
        """Tree-form -> resident state (checkpoint/migration boundary).
        Enforces the same uniform-dtype precondition as init_flat."""
        fcs, layout = gossip.FlatClientState.create(state.params, self.mask,
                                                    layout)
        _check_uniform_dtype(layout)
        self._check_codec()
        mom, _ = gossip.FlatClientState.create(state.opt_u.momentum,
                                               self.mask, layout)
        mom_v = partition.split(state.opt_v.momentum, self.mask)[1]
        from repro.compress import init_ef, init_ref
        # tree-form states carry no codec memory: a lossy codec starts
        # from FRESH (zero) error-feedback and reference buffers after
        # migration
        return FlatDFedPGPState(fcs.flat, fcs.personal, state.mu,
                                SGDState(mom.flat), SGDState(mom_v),
                                state.round, init_ef(self.codec, fcs.flat),
                                init_ref(self.codec, fcs.flat)), layout

    def state_from_flat(self, fstate: FlatDFedPGPState,
                        layout: gossip.FlatLayout) -> DFedPGPState:
        """Resident -> tree-form state.  Inactive-part momentum slots are
        restored as the per-client scalar placeholders init() creates
        (they are invariantly zero under the masked updates)."""
        params = gossip.FlatClientState(fstate.flat,
                                        fstate.personal).to_tree(layout)
        m = fstate.mu.shape[0]

        def placeholders(keep_shared):
            return jax.tree.map(
                lambda p, msk: jnp.zeros((m,), p.dtype)
                if msk != keep_shared else None, params, self.mask)

        mom_u = partition.merge(layout.unravel(fstate.opt_u.momentum),
                                placeholders(True))
        mom_v = partition.merge(fstate.opt_v.momentum,
                                placeholders(False))
        return DFedPGPState(params, fstate.mu, SGDState(mom_u),
                            SGDState(mom_v), fstate.round)

    # ------------------------------------------------------------------
    def eval_params(self, state: DFedPGPState):
        """Personalized models: de-biased shared part + personal part."""
        mu = state.mu

        def debias(a, m):
            if not m:
                return a
            return a / mu.reshape((-1,) + (1,) * (a.ndim - 1)).astype(a.dtype)

        return jax.tree.map(debias, state.params, self.mask)
