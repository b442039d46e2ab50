"""Sparse gossip engine: neighbor-indexed push-pull over a flat buffer.

The round's transmission  U'[i] = sum_j P[i,j] * U[j]  only touches each
client's k = n+1 in-neighbors, so contracting a dense (m, m) matrix against
every parameter leaf is O(m^2 * d) work for an O(m*k*d) operation.  This
module provides the O(m*k*d) path (docs/gossip.md):

1. `mix_rows(idx, w, x)` — the gather-weighted-sum primitive, unrolled over
   the (small, static) neighbor axis: k row-gathers + fused axpys.  On CPU
   at m=1024, k=8 this is ~15x faster than the dense matmul (measured:
   BENCH_gossip.json); on TPU the same contraction is the Pallas kernel
   `kernels/gossip_gather.py`.
2. `flatten_shared` / `unflatten_shared` — ravel all shared-part leaves of
   the stacked client pytree into ONE (m, d_flat) buffer so a round's
   push-pull is a single gather-mix (one kernel launch) plus the (m,) mu
   update, instead of one contraction per leaf.  The tree form is rebuilt
   lazily, only for local SGD / eval; under jit the reshape/concat pair
   fuses and the asymptotic win is the gather.
3. `gossip_mix` — the round-level entry point used by `DFedPGP.round_fn`,
   `pushsum.mix` and the DFL baselines, behind the `gossip=` knob:
     "dense"  — legacy per-leaf einsum against the (m, m) matrix;
     "sparse" — flat-buffer gather-mix (default; numerics-identical to
                dense within f32 tolerance);
     "pallas" — flat-buffer mix through the fused gossip_gather kernel
                (compiled on TPU, interpret mode elsewhere — validation
                path, not a CPU fast path).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import partition
from .topology import SparseTopology

MODES = ("dense", "sparse", "pallas")


def mix_rows(idx: jnp.ndarray, w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """out[i] = sum_j w[i,j] * x[idx[i,j]] for stacked x: (m,) or (m, ...).

    Unrolled over the static neighbor axis k: the XLA CPU/TPU backends turn
    each term into a row-gather + fused multiply-add, with no (m, k, d)
    intermediate and no O(m^2) matrix."""
    k = idx.shape[1]
    bshape = (-1,) + (1,) * (x.ndim - 1)

    def term(j):
        return w[:, j].reshape(bshape).astype(x.dtype) * \
            jnp.take(x, idx[:, j], axis=0)

    out = term(0)
    for j in range(1, k):
        out = out + term(j)
    return out


def no_sparsity(P) -> bool:
    """True when a SparseTopology has no sparsity to exploit (k >= m, e.g.
    the sparse fully_connected form).  mix_rows unrolls the neighbor axis
    into k gather+axpy terms at trace time, so at k = m the dense matmul
    is both the faster contraction and the smaller program — EVERY engine
    entry point (mix_any, mix_flat, gossip_mix) consults this one rule and
    densifies instead."""
    return isinstance(P, SparseTopology) and P.k >= P.m


def mix_any(P, x: jnp.ndarray) -> jnp.ndarray:
    """One gossip contraction of stacked per-client values x with either
    topology representation: neighbor-indexed O(m*k*numel) for a
    SparseTopology (densified when no_sparsity), dense einsum otherwise.
    The single dispatch point for pushsum.mix, the DFL baselines and
    SparseTopology.__matmul__."""
    if isinstance(P, SparseTopology) and not no_sparsity(P):
        return mix_rows(P.idx, P.w, x)
    Pd = P.dense() if isinstance(P, SparseTopology) else P
    return jnp.einsum("mn,n...->m...", Pd.astype(x.dtype), x)


def mix_tree(P, tree):
    """mix_any over every leaf of a stacked pytree — the per-leaf gossip
    used by pushsum.mix and the OSGP/DFedAvgM/Dis-PFL baselines (they keep
    tree form; DFedPGP's resident path mixes the flat buffer instead)."""
    return jax.tree.map(lambda a: mix_any(P, a), tree)


# ---------------------------------------------------------------------------
# flat-buffer layout
# ---------------------------------------------------------------------------
def flat_width(params, mask) -> int:
    """d_flat: total shared parameters per client."""
    return partition.count_params(jax.tree.map(lambda a: a[0], params),
                                  mask, shared=True)


def flatten_shared(params, mask, dtype=None) -> jnp.ndarray:
    """Ravel the shared leaves of a stacked (m, ...) pytree into one
    (m, d_flat) buffer (leaf order = treedef order, the wire layout in
    docs/gossip.md).  `dtype` is the wire dtype (e.g. bfloat16 halves the
    gossip bytes); defaults to the leaves' common dtype."""
    u, _ = partition.split(params, mask)
    leaves = jax.tree.leaves(u)
    m = leaves[0].shape[0]
    dt = jnp.dtype(dtype) if dtype is not None else jnp.result_type(*leaves)
    return jnp.concatenate([x.reshape(m, -1).astype(dt) for x in leaves],
                           axis=1)


def unflatten_shared(flat: jnp.ndarray, params, mask):
    """Inverse of flatten_shared: slice the (m, d_flat) buffer back into the
    shared leaves (cast to each leaf's dtype); personal leaves pass through
    from `params` untouched."""
    u, v = partition.split(params, mask)
    leaves, treedef = jax.tree.flatten(u)
    out, off = [], 0
    for leaf in leaves:
        n = leaf[0].size
        out.append(flat[:, off:off + n].reshape(leaf.shape)
                   .astype(leaf.dtype))
        off += n
    return partition.merge(jax.tree.unflatten(treedef, out), v)


# ---------------------------------------------------------------------------
# resident flat buffer: the (m, d_flat) buffer as the PRIMARY representation
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static descriptor of the shared part's wire layout.

    Built once from a stacked params template + mask (`FlatLayout.build`);
    afterwards the (m, d_flat) buffer can live across rounds as the resident
    representation of the shared part, and the tree form is reconstructed
    only at the leaf boundary (the model's loss_fn, eval) via `unravel_row`
    / `unravel`.  Leaf order = treedef order of the shared subtree — the
    same wire layout as `flatten_shared`, so `pack` is bit-compatible with
    the per-round path it replaces.

    Hashable and cheap: shapes/dtypes tuples plus the shared-subtree
    treedef, no arrays.
    """
    treedef: Any                        # treedef of the shared subtree
    shapes: tuple                       # per shared leaf, UNSTACKED shape
    dtypes: tuple
    sizes: tuple
    d_flat: int

    @classmethod
    def build(cls, params, mask) -> "FlatLayout":
        """`params` is a stacked (m, ...) pytree (or ShapeDtypeStructs)."""
        u, _ = partition.split(params, mask)
        leaves, treedef = jax.tree.flatten(u)
        shapes = tuple(tuple(leaf.shape[1:]) for leaf in leaves)
        dtypes = tuple(jnp.dtype(leaf.dtype) for leaf in leaves)
        sizes = tuple(int(np.prod(s, dtype=np.int64)) if s else 1
                      for s in shapes)
        return cls(treedef, shapes, dtypes, sizes, sum(sizes))

    # -- tree <-> buffer ---------------------------------------------------
    def pack(self, params, mask, dtype=None) -> jnp.ndarray:
        """Stacked shared leaves -> (m, d_flat) buffer (== flatten_shared,
        same wire order)."""
        return flatten_shared(params, mask, dtype=dtype)

    def unravel_row(self, row: jnp.ndarray):
        """One client's (d_flat,) view -> shared subtree (unstacked leaves,
        cast to each leaf's dtype).  Under jit the slices/reshapes are
        views — this is the only point where the tree form materializes,
        at the loss_fn leaf boundary.  Its gradient is the leaf gradients
        concatenated into one row (`_unravel_row`)."""
        leaves = _unravel_row(self, jnp.dtype(row.dtype), row)
        return jax.tree.unflatten(self.treedef, list(leaves))

    def unravel(self, flat: jnp.ndarray):
        """(m, d_flat) buffer -> stacked shared subtree."""
        m = flat.shape[0]
        out, off = [], 0
        for shape, dt, n in zip(self.shapes, self.dtypes, self.sizes):
            out.append(flat[:, off:off + n].reshape((m,) + shape)
                       .astype(dt))
            off += n
        return jax.tree.unflatten(self.treedef, out)


def _slice_row(layout: FlatLayout, row: jnp.ndarray) -> tuple:
    out, off = [], 0
    for shape, dt, n in zip(layout.shapes, layout.dtypes, layout.sizes):
        out.append(row[off:off + n].reshape(shape).astype(dt))
        off += n
    return tuple(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _unravel_row(layout: FlatLayout, row_dtype, row: jnp.ndarray) -> tuple:
    """The row's leaf views, differentiated by hand: autodiff transposes
    each slice into a pad of the full row width and sums the pads, so every
    element of the row gradient passes one bounds test per leaf (a
    VPU-bound pass at LM width).  The backward writes each leaf's
    cotangent into its own range once, with one concatenate; the values
    are the same, each element being one leaf's cotangent plus exact
    zeros."""
    return _slice_row(layout, row)


def _unravel_row_fwd(layout, row_dtype, row):
    return _slice_row(layout, row), None


def _unravel_row_bwd(layout, row_dtype, _, cts):
    pieces = [g.reshape(-1).astype(row_dtype) for g in cts]
    if not pieces:      # an all-personal mask: no shared leaf, d_flat == 0
        return (jnp.zeros((0,), row_dtype),)
    return (jnp.concatenate(pieces),)


_unravel_row.defvjp(_unravel_row_fwd, _unravel_row_bwd)


class FlatClientState(NamedTuple):
    """Resident representation of the stacked client parameters: the shared
    part lives in ONE (m, d_flat) buffer across rounds (packed once, at
    init); the personal leaves stay in tree form (None at shared
    positions, as produced by partition.split).  Gossip mixes the buffer
    in place — the per-round flatten/unflatten pair of the tree path is
    gone (ROADMAP item (d))."""
    flat: jnp.ndarray          # (m, d_flat) shared buffer
    personal: Any              # personal leaves (m, ...); None at shared

    @classmethod
    def create(cls, params, mask, layout: FlatLayout | None = None):
        """-> (state, layout).  Packs the shared part once.  A degenerate
        all-personal mask yields an empty (m, 0) buffer (rounds still run;
        only mu mixes)."""
        layout = layout or FlatLayout.build(params, mask)
        _, v = partition.split(params, mask)
        if layout.d_flat == 0:
            m = jax.tree.leaves(params)[0].shape[0]
            return cls(jnp.zeros((m, 0), jnp.float32), v), layout
        return cls(flatten_shared(params, mask), v), layout

    def to_tree(self, layout: FlatLayout):
        """Reconstruct the stacked params pytree (eval / checkpoint
        boundary)."""
        return partition.merge(layout.unravel(self.flat), self.personal)


def _transmit(P, x: jnp.ndarray, mu: jnp.ndarray, mode: str,
              block_m=None):
    """The bare push-pull contraction of (x, mu) — the one code path every
    mix_flat variant (plain, wire-dtype, codec-decoded) funnels through,
    so they stay numerics-identical by construction."""
    sparse = isinstance(P, SparseTopology)
    if no_sparsity(P):
        mode = "dense"
    if mode == "dense" or not sparse:
        Pd = P.dense() if sparse else P
        return (jnp.einsum("mn,nd->md", Pd.astype(x.dtype), x),
                jnp.einsum("mn,n->m", Pd, mu))
    if mode == "pallas":
        from repro.kernels import ops
        return (ops.gossip_gather(P.idx, P.w, x, force="pallas",
                                  block_m=block_m),
                mix_rows(P.idx, P.w, mu))
    return mix_rows(P.idx, P.w, x), mix_rows(P.idx, P.w, mu)


def _check_block_m(mode: str, block_m) -> None:
    """block_m tunes the Pallas kernels' DMA panel height; every other
    mode has no kernel to tune, so a stray knob raises instead of being
    silently ignored."""
    if block_m is not None and mode != "pallas":
        raise ValueError(
            f"block_m={block_m} tunes the pallas gossip kernels; mode="
            f"{mode!r} never launches one (use mode='pallas' or drop the "
            f"knob)")


def mix_flat(P, flat: jnp.ndarray, mu: jnp.ndarray, *,
             mode: str = "sparse", wire_dtype=None, edge_gate=None,
             codec=None, ef=None, ref=None, key=None, codec_gamma=1.0,
             block_m=None):
    """One push-pull transmission directly on the resident buffer:
    flat' = P flat, mu' = P mu — no per-round pack/unpack.  The pallas mode
    hands the buffer to the fused gossip_gather kernel as-is.  mu always
    mixes in f32; a wire_dtype narrows only the payload of the mix (the
    buffer returns in its resident dtype).

    edge_gate: optional (m, k) {0,1} mask multiplied into P's pull weights
    WITHOUT renormalization — the mailbox form of the mix
    (repro.hetero.mailbox): gating an edge off means that neighbor's mass
    simply has not arrived, it is NOT redistributed to the live edges.
    Needs the neighbor-indexed representation, so it requires a
    SparseTopology (the dense matrix has no (m, k) edge identity).

    codec: optional wire codec (repro.compress, docs/compress.md).  When
    given, the NON-SELF edges ship compressed DELTAS against each
    sender's public reference copy (`ref`, error-feedback + tracking:
    feedback.publish) and receivers mix the dense updated references; the
    self edge never crosses the wire, so it carries the FULL-fidelity
    row:

        mixed[i] = P[i,i] * flat[i] + sum_{j != i} P[i,j] * ref'[j]

    The call takes `ef`/`ref` memory and returns TWO extra elements —
    (mixed, mu', ef', ref').  An `exact` codec (identity) bypasses all of
    this and runs the plain body on `flat`, bit-for-bit the codec-free
    path.  Sparse payloads under mode="pallas" mix through the fused
    kernels/topk_gather.py kernel — the deltas' dense decodes never
    materialize.  mu is NEVER compressed: push-sum mass conservation is
    codec-agnostic.

    block_m: optional DMA-panel-height override for the pallas kernels;
    raises for modes that launch no kernel."""
    if mode not in MODES:
        raise ValueError(f"gossip mode {mode!r}; known: {MODES}")
    _check_block_m(mode, block_m)
    # a traced gamma is the adaptive anneal (DFedPGP codec_gamma="auto"):
    # its value only exists inside jit, so the static checks move to the
    # caller (DFedPGP._check_codec validates the configuration)
    traced_gamma = isinstance(codec_gamma, jax.core.Tracer)
    if (codec is None or codec.exact) and \
            (traced_gamma or float(codec_gamma) != 1.0):
        # same loud-knob rule as block_m: the consensus step only exists
        # on the lossy codec path
        raise ValueError(
            f"codec_gamma={codec_gamma} only applies to lossy codecs; "
            f"the exact/uncompressed mix never blends")
    sparse = isinstance(P, SparseTopology)
    if edge_gate is not None:
        if not sparse:
            raise ValueError("edge_gate needs a SparseTopology — a dense "
                             "matrix has no per-edge (m, k) identity")
        P = SparseTopology(P.idx, P.w * edge_gate.astype(P.w.dtype))
    if codec is not None:
        if wire_dtype is not None:
            raise ValueError("codec defines the wire format; wire_dtype "
                             "applies to the uncompressed path only")
        from repro.compress import feedback
        if codec.exact:
            mixed, mu2 = _transmit(P, flat, mu, mode, block_m)
            return mixed.astype(flat.dtype), mu2, ef, ref
        # consensus step size gamma (CHOCO-Gossip): the effective mixing
        # matrix is P_g = (1-g) I + g P — still row-stochastic (and
        # column-stochastic if P is), so the push-sum de-bias and the
        # mass ledger are untouched.  g < 1 slows consensus to the rate a
        # SPARSE pipe can actually deliver; g = 1 is the plain tracked mix
        if traced_gamma:
            g = codec_gamma.astype(jnp.float32)
        else:
            g = float(codec_gamma)
            if not 0.0 < g <= 1.0:
                raise ValueError(f"codec_gamma must be in (0, 1], got {g}")
        sw = self_weight_of(P)                                # (m,)
        sw_g = (1.0 - g) + g * sw
        payload, ef2, ref2 = feedback.publish(
            codec, ef, ref, flat, key, wire_frac=1.0 - sw_g)
        wire = _mix_wire(P, ref, ref2, payload, mode, block_m)
        # the ACCUMULATED residual re-enters through the self share (full
        # fidelity — it never rides the wire), so the crossing conserves
        # value exactly: mixed + ef' = u + ef under column-stochastic
        # weights, and tracking ships the re-absorbed residual later
        mixed = sw_g[:, None] * flat.astype(jnp.float32) + ef + g * wire
        mu2 = (1.0 - g) * mu + g * mix_any(P, mu)
        return mixed.astype(flat.dtype), mu2, ef2, ref2
    x = flat.astype(wire_dtype) if wire_dtype is not None else flat
    mixed, mu2 = _transmit(P, x, mu, mode, block_m)
    return mixed.astype(flat.dtype), mu2


def self_weight_of(P) -> jnp.ndarray:
    """(m,) total weight each row places on itself — the share of a mix
    that never crosses the wire (the codec path keeps it full-fidelity)."""
    if isinstance(P, SparseTopology):
        rows = jnp.arange(P.m, dtype=P.idx.dtype)[:, None]
        return (P.w * (P.idx == rows)).sum(1).astype(jnp.float32)
    return jnp.diagonal(P).astype(jnp.float32)


def wire_only(P):
    """P with its self edges zeroed — the edges that actually carry
    payloads.  Same representation in, same out."""
    if isinstance(P, SparseTopology):
        rows = jnp.arange(P.m, dtype=P.idx.dtype)[:, None]
        return SparseTopology(P.idx, jnp.where(P.idx == rows, 0.0, P.w))
    m = P.shape[0]
    return P * (1.0 - jnp.eye(m, dtype=P.dtype))


def _mix_wire(P, ref_prev, ref_new, payload, mode: str, block_m=None):
    """sum_{j != i} P[i,j] * ref'[j] — the tracked half of the codec mix,
    in f32.  On the pallas path the sum splits linearly,
    P_wire @ ref' = P_wire @ ref + P_wire @ decode(p), so sparse payloads
    scatter through kernels/topk_gather.py while the reference rides the
    regular gossip_gather kernel — a dense decode never materializes."""
    Pw = wire_only(P)
    sparse = isinstance(Pw, SparseTopology)
    if sparse and mode == "pallas" and payload.indices is not None \
            and not no_sparsity(Pw):
        from repro.kernels import ops
        d = ref_prev.shape[1]
        return ops.gossip_gather(Pw.idx, Pw.w, ref_prev, force="pallas",
                                 block_m=block_m) \
            + ops.topk_gather(Pw.idx, Pw.w,
                              payload.values.astype(jnp.float32),
                              payload.indices, d, force="pallas",
                              block_m=block_m)
    if sparse and not no_sparsity(Pw) and mode != "dense":
        return mix_rows(Pw.idx, Pw.w, ref_new)
    Pd = Pw.dense() if sparse else Pw
    return jnp.einsum("mn,nd->md", Pd.astype(jnp.float32), ref_new)


# ---------------------------------------------------------------------------
# round-level entry point
# ---------------------------------------------------------------------------
def gossip_mix(params, mu, P, mask, *, mode: str = "sparse",
               wire_dtype=None, block_m=None):
    """One push-pull transmission of the shared part + the mu update.

    P is a SparseTopology (preferred) or a dense (m, m) row-stochastic
    matrix.  A sparse/pallas mode with a dense P falls back to the dense
    path — the neighbor indices are not recoverable inside jit.  Returns
    (params', mu'); mu always mixes in f32 (push-sum de-bias correctness).
    block_m tunes the pallas kernel's DMA panels; the tree-mode dense and
    sparse paths launch no kernel, so they raise on a stray knob instead
    of silently ignoring it.
    """
    if mode not in MODES:
        raise ValueError(f"gossip mode {mode!r}; known: {MODES}")
    _check_block_m(mode, block_m)
    sparse = isinstance(P, SparseTopology)
    if sparse and not any(jax.tree.leaves(mask)):
        # degenerate all-personal mask: nothing to flatten — only mu moves
        return params, mix_any(P, mu)
    if no_sparsity(P):
        mode = "dense"
    if mode == "dense" or not sparse:
        Pd = P.dense() if sparse else P
        gdt = jnp.dtype(wire_dtype) if wire_dtype is not None else None

        def mix_leaf(a, mk):
            if not mk:
                return a
            x = a.astype(gdt) if gdt is not None else a
            return jnp.einsum("mn,n...->m...", Pd.astype(x.dtype), x
                              ).astype(a.dtype)

        return (jax.tree.map(mix_leaf, params, mask),
                jnp.einsum("mn,n->m", Pd, mu))

    flat = flatten_shared(params, mask, dtype=wire_dtype)
    if mode == "pallas":
        from repro.kernels import ops
        mixed = ops.gossip_gather(P.idx, P.w, flat, force="pallas",
                                  block_m=block_m)
    else:
        mixed = mix_rows(P.idx, P.w, flat)
    return (unflatten_shared(mixed, params, mask),
            mix_rows(P.idx, P.w, mu))
