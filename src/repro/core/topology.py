"""Communication topologies: time-varying directed / undirected graphs.

Mixing-matrix conventions (paper Appendix B):
- **Row-stochastic ("pull")**: each row sums to 1.  Client i *pulls* models
  from its in-neighbors and averages with its own weights — the paper's
  experimental setup (Formula 6): n random in-neighbors + self, all 1/(n+1).
- **Column-stochastic ("push")**: each column sums to 1 — the classic
  push-sum setting (Kempe et al. 2003): client i splits its mass over its
  out-neighbors.  Total mass sum_i u_i is conserved.

Either way the push-sum weight mu de-biases the non-doubly-stochastic mixing:
z_i = u_i / mu_i converges to a common consensus point.

Sparse-first representation (docs/gossip.md): every constructor returns a
`SparseTopology` — per-client in-neighbor indices (m, k) and pull weights
(m, k) — because the paper's graphs have k = n+1 << m in-edges per client.
The gossip engines contract against the indices in O(m*k*d) instead of
materializing the O(m^2) matrix; `.dense()` recovers the (m, m) matrix for
baselines, diagnostics, and parity tests.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Dense-degree ceiling (docs/scale.md): constructors whose neighbor table
# is O(m^2)-shaped — fully_connected's (m, m) SparseTopology, the
# undirected builder's dense host matrices — refuse above this m instead
# of silently allocating gigabytes.  4096 is the largest m where the
# (m, m) f32 table is still a "small" 64 MiB.
MAX_DENSE_M = 4096

# the host span around each schedule draw (repro.obs.profiler.span)
TOPOLOGY_SPAN = "dfedpgp.host.topology"


def _check_dense_degree(m: int, what: str) -> None:
    if m > MAX_DENSE_M:
        raise ValueError(
            f"{what} builds an O(m^2)-shaped table; m={m} > "
            f"MAX_DENSE_M={MAX_DENSE_M} would allocate "
            f"{m * m * 4 / 2**30:.1f} GiB of neighbor weights.  At scale "
            f"use a sparse-degree kind (random/exponential/ring) — "
            f"docs/scale.md")


class SparseTopology(NamedTuple):
    """Neighbor-indexed row-stochastic mixing pattern.

    idx: (m, k) int32 — in-neighbor ids of each client (self included).
         Rows with fewer than k in-edges are padded with the row's own id.
    w:   (m, k) float32 — pull weights; padding entries carry weight 0, so
         each row sums to 1 over its real edges.

    A NamedTuple, hence a pytree: it passes through jit/vmap boundaries and
    its (idx, w) leaves are donated/sharded like any other array pair.
    """
    idx: jnp.ndarray
    w: jnp.ndarray

    @property
    def m(self) -> int:
        return self.idx.shape[0]

    @property
    def k(self) -> int:
        return self.idx.shape[1]

    def dense(self) -> jnp.ndarray:
        """Materialize the (m, m) row-stochastic matrix (diagnostics only —
        the gossip hot path never calls this).  Refuses above MAX_DENSE_M:
        the output IS the O(m^2) table every other guard exists to keep
        off the allocator."""
        _check_dense_degree(self.idx.shape[0], "SparseTopology.dense()")
        m = self.idx.shape[0]
        rows = jnp.arange(m)[:, None]
        return jnp.zeros((m, m), self.w.dtype).at[rows, self.idx].add(self.w)

    def __matmul__(self, x):
        """P @ x: out[i] = sum_j w[i,j] * x[idx[i,j]] for x (m,) or
        (m, ...).  Dispatches through gossip.mix_any, which densifies the
        no-sparsity k == m case (fully_connected) instead of unrolling m
        gather terms at trace time."""
        from . import gossip  # local import: gossip imports this module
        return gossip.mix_any(self, jnp.asarray(x))


def from_dense(P, k: int | None = None) -> SparseTopology:
    """Host-side conversion of a dense row-stochastic matrix.  k defaults to
    the maximum number of nonzeros in any row; rows with fewer edges are
    padded with (self, 0).  Guarded: the argsort below works on the full
    (m, m) matrix, so above MAX_DENSE_M this path would allocate the very
    table the sparse representation exists to avoid."""
    Pn = np.asarray(P, np.float32)
    m = Pn.shape[0]
    _check_dense_degree(m, "from_dense (dense host-side conversion)")
    nnz = int((Pn > 0).sum(1).max()) if m else 0
    k = max(nnz, 1) if k is None else k
    if nnz > k:
        raise ValueError(f"k={k} < max row nnz {nnz}")
    order = np.argsort(-Pn, axis=1, kind="stable")[:, :k]
    w = np.take_along_axis(Pn, order, axis=1)
    idx = np.where(w > 0, order, np.arange(m)[:, None])
    return SparseTopology(jnp.asarray(idx, jnp.int32),
                          jnp.asarray(w, jnp.float32))


def densify(P) -> jnp.ndarray:
    """Accept either representation; return the dense (m, m) matrix."""
    return P.dense() if isinstance(P, SparseTopology) else jnp.asarray(P)


# ---------------------------------------------------------------------------
# directed graphs
# ---------------------------------------------------------------------------
def directed_random(key, m: int, n_neighbors: int) -> SparseTopology:
    """Paper's topology: every client pulls from `n` uniform random
    in-neighbors plus itself; uniform weights 1/(n+1).  Row-stochastic;
    k = n+1.

    Above MAX_DENSE_M clients the per-row permutation draw (an O(m^2)
    vmapped intermediate) switches to an O(m*n) randint draw: neighbors
    are sampled uniformly WITH replacement among the m-1 peers (the
    skip-self shift keeps self out).  A duplicate in-edge just doubles
    that neighbor's pull weight; at n << m collisions have probability
    ~n^2/2m per row, negligible at the scales the fast path serves
    (docs/scale.md §Topologies at scale).  Both paths are deterministic
    in `key`; the small-m tables are unchanged."""
    n = min(n_neighbors, m - 1)
    if m > MAX_DENSE_M:
        draws = jax.random.randint(key, (m, n), 0, m - 1)
        rows = jnp.arange(m)[:, None]
        nb = jnp.where(draws >= rows, draws + 1, draws)    # skip self
        idx = jnp.concatenate([rows, nb], axis=1)
        w = jnp.full((m, n + 1), 1.0 / (n + 1), jnp.float32)
        return SparseTopology(idx.astype(jnp.int32), w)
    keys = jax.random.split(key, m)

    def row(i, k):
        perm = jax.random.permutation(k, m - 1)[:n]
        nb = jnp.where(perm >= i, perm + 1, perm)          # skip self
        return jnp.concatenate([i[None], nb])              # self first

    idx = jax.vmap(row)(jnp.arange(m), keys)
    w = jnp.full((m, n + 1), 1.0 / (n + 1), jnp.float32)
    return SparseTopology(idx.astype(jnp.int32), w)


def directed_exponential(m: int, round_idx) -> SparseTopology:
    """One-peer exponential graph (SGP, arXiv:1811.10792): at round t each
    client pulls from the single peer at offset 2^(t mod log2 m).
    Row-stochastic with weights (1/2, 1/2), k = 2.  B-strongly-connected
    with B = log2(m)."""
    assert m & (m - 1) == 0, "exponential graph wants power-of-two m"
    log_m = max(int(np.log2(m)), 1)
    offset = 2 ** jnp.mod(jnp.asarray(round_idx), log_m)
    rows = jnp.arange(m)
    src = jnp.mod(rows - offset, m)
    idx = jnp.stack([rows, src], axis=1).astype(jnp.int32)
    return SparseTopology(idx, jnp.full((m, 2), 0.5, jnp.float32))


def ring(m: int) -> SparseTopology:
    rows = jnp.arange(m)
    idx = jnp.stack([rows, jnp.mod(rows - 1, m)], axis=1).astype(jnp.int32)
    return SparseTopology(idx, jnp.full((m, 2), 0.5, jnp.float32))


def fully_connected(m: int) -> SparseTopology:
    """Complete graph, uniform 1/m weights.  k = m (self first, then the
    m-1 peers in id order): nothing to gain asymptotically, but returning a
    SparseTopology keeps `mix_any` dispatch uniform — the simulator's
    gossip knob no longer silently densifies for this graph.  `.dense()`
    recovers the classic (m, m) averaging matrix.  Raises above
    MAX_DENSE_M — the table itself is O(m^2)."""
    _check_dense_degree(m, "fully_connected (k = m)")
    rows = jnp.arange(m)[:, None]
    others = jnp.arange(m)[None, :] + rows + 1          # (m, m): i+1 .. i+m
    idx = jnp.concatenate([rows, jnp.mod(others, m)[:, : m - 1]], axis=1)
    return SparseTopology(idx.astype(jnp.int32),
                          jnp.full((m, m), 1.0 / m, jnp.float32))


def to_push_sparse(P: SparseTopology,
                   self_weight=0.5) -> SparseTopology:
    """Lazy column-stochastic (push) form of a pull pattern, sparse-native.

    Reuses P's edge set but re-weights it so each SENDER j keeps
    `self_weight[j]` of its mass and splits the rest uniformly over its
    non-self out-edges (the transposed pull edges):

        w[i, p] = (1 - self_weight[j]) / outdeg(j),  j = idx[i, p] != i
        w[i, p] = self_weight[i] (+ the remainder if outdeg == 0)  at the
                  self edge

    Every column sums to 1, so the total push-sum mass is conserved — the
    invariant the async mailbox regime needs (docs/hetero.md).  The lazy
    self share matters there too: a sender that keeps half its mass is
    never yanked onto a stale heavy-mass arrival, which is what makes
    delayed asynchronous push-sum stable (one-peer SGP keeps exactly 1/2).

    self_weight: scalar in [0, 1) or a per-SENDER (m,) array — the
    staleness-discounted form (ROADMAP async follow-up (a)): a sender
    whose pushes ride a slow link keeps proportionally more mass at home
    (`staleness_self_weight`), so its receivers' push-sum weights stop
    plateauing on mass stuck in flight (tests/test_hetero_async.py).

    Jittable: O(m*k), no densify.  Precondition: every row carries a self
    entry (all the constructors in this module do) — the kept share has
    no slot otherwise, which would silently destroy mass; checked loudly
    when the topology is concrete (the host-side schedule path)."""
    m, _ = P.idx.shape
    sw = jnp.broadcast_to(jnp.asarray(self_weight, jnp.float32), (m,))
    if not isinstance(P.idx, jax.core.Tracer):
        has_self = (np.asarray(P.idx) == np.arange(m)[:, None]).any(1)
        if not bool(has_self.all()):
            raise ValueError(
                f"to_push_sparse needs a self entry in every row (rows "
                f"{np.where(~has_self)[0][:5].tolist()} have none): the "
                f"sender's kept share would have no slot and its mass "
                f"would be destroyed")
        if not isinstance(sw, jax.core.Tracer):
            swn = np.asarray(sw)
            if float(swn.min()) < 0.0 or float(swn.max()) >= 1.0:
                raise ValueError(
                    f"self_weight must lie in [0, 1) (a sender keeping "
                    f">= 1 of its mass pushes none); got range "
                    f"[{float(swn.min())}, {float(swn.max())}]")
    rows = jnp.arange(m, dtype=P.idx.dtype)[:, None]
    self_edge = P.idx == rows
    real = (P.w > 0) & ~self_edge
    outdeg = jnp.zeros((m,), jnp.float32).at[P.idx.reshape(-1)].add(
        real.astype(jnp.float32).reshape(-1))
    share = (1.0 - sw) / jnp.maximum(outdeg, 1.0)
    w = jnp.where(real, jnp.take(share, P.idx), 0.0)
    w_self = sw + (1.0 - sw) * (outdeg <= 0)
    # place the kept share on the REAL self edge; rows whose self edge
    # exists only as (self, 0) padding reuse those slots instead (split
    # evenly — the total stays exactly w_self, so columns still sum to 1)
    real_self = self_edge & (P.w > 0)
    self_slot = jnp.where(real_self.any(1, keepdims=True), real_self,
                          self_edge)
    cnt = jnp.maximum(self_slot.sum(1, keepdims=True), 1)
    w = jnp.where(self_slot, w_self[:, None] / cnt, w)
    return SparseTopology(P.idx, w.astype(jnp.float32))


def staleness_self_weight(push_delay, base: float = 0.5) -> jnp.ndarray:
    """Stale-mass discounting (ROADMAP async follow-up (a)): the per-sender
    lazy self share as a function of the sender's push-delay class.

        self_weight[j] = 1 - (1 - base) / (1 + delay[j])

    A delay-0 sender keeps `base` (the classic 1/2); a delay-d sender
    keeps more — its pushed share spends ~(1 + d) ticks on the wire, so
    scaling the PUSHED fraction by 1/(1 + d) keeps the steady-state mass
    in flight roughly constant per sender instead of growing linearly
    with delay.  Without the discount, receivers' push-sum weights mu
    plateau at the mass the slow links hold back
    (tests/test_hetero_async.py::test_staleness_discount_lifts_plateau).
    """
    d = jnp.asarray(push_delay, jnp.float32)
    return 1.0 - (1.0 - float(base)) / (1.0 + d)


def to_column_stochastic(P_row) -> jnp.ndarray:
    """Turn a pull (row-stochastic) pattern into the equivalent push
    (column-stochastic) matrix over the transposed edge set.

    Nodes with no out-edges under the transposed pattern (zero columns —
    possible for asymmetric patterns without self-loops) keep their mass on
    a self-loop instead of producing a 0/0 NaN column."""
    P_row = densify(P_row)
    m = P_row.shape[0]
    A = (P_row > 0).astype(jnp.float32).T                  # out-edges of each col
    col = jnp.sum(A, axis=0, keepdims=True)
    A = A + jnp.eye(m, dtype=A.dtype) * (col == 0)
    return A / jnp.sum(A, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# undirected graphs (for DFedAvgM / Dis-PFL baselines)
# ---------------------------------------------------------------------------
def undirected_random(key, m: int, n_neighbors: int) -> SparseTopology:
    """Symmetric doubly-stochastic matrix via Metropolis-Hastings weights on a
    random undirected n-regular-ish graph (paper's undirected baseline).

    Fully vectorized host-side construction (no Python loop over m), so
    m=1024 topologies build in milliseconds.  The in-degree is capped at
    dmax = min(3n, m-1) — symmetric truncation of the (rare) tail where a
    node is picked by many peers — so the sparse width k = dmax+1 is a
    deterministic function of (m, n) and jitted round functions never
    retrace across rounds."""
    _check_dense_degree(m, "undirected_random (dense host-side builder)")
    n = min(n_neighbors, m - 1)
    picks = np.asarray(directed_random(key, m, n).idx)     # (m, n+1), col 0=self
    A = np.zeros((m, m), bool)
    np.put_along_axis(A, picks, True, axis=1)
    A |= A.T
    np.fill_diagonal(A, False)

    dmax = max(min(3 * n, m - 1), 1)
    pos = A.cumsum(1) - 1                 # rank of each edge within its row
    keep = A & (pos < dmax) & (pos.T < dmax)   # symmetric cap
    deg = keep.sum(1)
    W = np.where(keep,
                 1.0 / (np.maximum(deg[:, None], deg[None, :]) + 1.0), 0.0)
    np.fill_diagonal(W, 1.0 - W.sum(1))

    k = min(dmax + 1, m)
    order = np.argpartition(-W, kth=k - 1, axis=1)[:, :k]
    w = np.take_along_axis(W, order, axis=1)
    idx = np.where(w > 0, order, np.arange(m)[:, None])
    return SparseTopology(jnp.asarray(idx, jnp.int32),
                          jnp.asarray(w, jnp.float32))


# ---------------------------------------------------------------------------
# partial participation: induced subgraphs (docs/scale.md)
# ---------------------------------------------------------------------------
def induced_subgraph(P: SparseTopology, active,
                     renorm: str = "row") -> SparseTopology:
    """The subgraph induced by the `active` client subset, re-indexed to the
    compact [0, n_active) id space.

    active: (n_active,) unique global client ids (the sampler emits them
    sorted; any order works — compact id p is the position of active[p]).
    Edges whose endpoint is dormant are dropped (padded to (self, 0), the
    SparseTopology convention), and the surviving weights are re-scaled so
    each row ("row", the pull form) or each sender column ("col", the push
    form) sums to what it summed to in the FULL graph.

    The scale factor is orig_sum / alive_sum — NOT a renormalization to
    1.0 — deliberately: when every edge survives (sample-all), the two
    sums are the same floating-point value, the factor is exactly 1.0 in
    IEEE arithmetic, and the induced weights are bit-identical to the
    originals.  That is what makes the sample-all ≡ full-participation
    parity contract (tests/test_sampling.py) hold bit-for-bit; a
    renormalize-to-1 would perturb the last ulp (three f32 thirds do not
    sum to 1.0) and break it.

    "col" conserves push-sum mass within the active set: an active
    sender's mass that would have ridden a dropped active→dormant edge is
    re-split over its surviving active out-edges, so Σmu over active rows
    is unchanged by the mix and dormant mu stays frozen — the dormant-row
    mass ledger of docs/scale.md.  Jittable in `active` (shapes depend
    only on n_active); O(n*k + m) work."""
    if renorm not in ("row", "col"):
        raise ValueError(f"renorm must be 'row' or 'col'; got {renorm!r}")
    m, k = P.idx.shape
    # a dense-width input (k ~ m, e.g. a giant from_dense table that
    # slipped past its own guard via monkeypatching) would make the
    # induced table O(n*m) — same guard, keyed on the inherited width
    _check_dense_degree(k, "induced_subgraph of a dense-width (k = m) table")
    active = jnp.asarray(active, jnp.int32)
    n = active.shape[0]
    pos = jnp.full((m,), -1, jnp.int32).at[active].set(
        jnp.arange(n, dtype=jnp.int32))
    gidx = P.idx[active]                       # (n, k) global neighbor ids
    gw = P.w[active]
    cpos = pos[gidx]                           # compact ids, -1 if dormant
    alive = (cpos >= 0) & (gw > 0)
    rows_c = jnp.arange(n, dtype=jnp.int32)[:, None]
    cidx = jnp.where(alive, cpos, rows_c)      # dead edges -> (self, 0) pad
    wz = jnp.where(alive, gw, 0.0)
    if renorm == "row":
        orig = gw.sum(1, keepdims=True)
        live = wz.sum(1, keepdims=True)
        w = wz * jnp.where(live > 0, orig / live, 0.0)
        # a row whose every positive edge went dormant (possible only if
        # the constructor gave self weight 0) freezes on itself instead of
        # zeroing out
        first = jnp.zeros((1, k), bool).at[0, 0].set(True)
        w = jnp.where((live <= 0) & first, orig, w)
    else:
        # per-SENDER column sums: full graph vs induced (both scatter-add
        # the same values in the same order at sample-all -> exact 1.0)
        orig_col = jnp.zeros((m,), jnp.float32).at[P.idx.reshape(-1)].add(
            P.w.reshape(-1))
        alive_col = jnp.zeros((m,), jnp.float32).at[gidx.reshape(-1)].add(
            wz.reshape(-1))
        scale = jnp.where(alive_col > 0, orig_col / alive_col, 0.0)
        w = wz * scale[gidx]
    return SparseTopology(cidx, w.astype(jnp.float32))


# ---------------------------------------------------------------------------
# round schedules: one object decides who talks to whom, in both regimes
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TopologySchedule:
    """The time-varying mixing schedule  t -> SparseTopology.

    The paper's convergence argument rests on the directed mixing schedule
    (tighter connectivity -> faster convergence), so it gets one canonical
    representation consumed by every regime:

    - Regime A (`fl/simulator.py`): `schedule.at(t)` yields the round's
      SparseTopology for the vmapped gossip engines.
    - Regime B (`launch/steps.py`): `schedule.permutation_offsets()` yields
      the per-round ppermute offsets for the shard_map datacenter mix —
      derived from the same neighbor tables, so the two mixes agree
      leaf-for-leaf (tests/test_regime_parity.py).

    Determinism: `at(t)` is a pure function of (kind, m, n, seed, t) —
    two instances built with the same arguments produce identical neighbor
    tables for every round.  Random kinds fold the round index into a
    PRNGKey(seed); static kinds ignore t entirely.
    """
    kind: str                      # random | exponential | ring | full | undirected
    m: int
    n: int = 0                     # in-degree for the random kinds
    seed: int = 0

    KINDS = ("random", "exponential", "ring", "full", "undirected")

    def __post_init__(self):
        if self.kind not in self.KINDS:
            raise ValueError(
                f"schedule kind {self.kind!r}; known: {self.KINDS}")
        # fail at schedule construction, not on the first .at(t) call deep
        # inside a round loop
        if self.kind in ("full", "undirected"):
            _check_dense_degree(self.m, f"topology={self.kind!r}")

    # -- constructors ------------------------------------------------------
    @classmethod
    def random(cls, m: int, n: int, seed: int = 0) -> "TopologySchedule":
        return cls("random", m, n, seed)

    @classmethod
    def exponential(cls, m: int) -> "TopologySchedule":
        assert m & (m - 1) == 0, "exponential graph wants power-of-two m"
        return cls("exponential", m)

    @classmethod
    def ring(cls, m: int) -> "TopologySchedule":
        return cls("ring", m)

    @classmethod
    def full(cls, m: int) -> "TopologySchedule":
        return cls("full", m)

    @classmethod
    def undirected(cls, m: int, n: int, seed: int = 0) -> "TopologySchedule":
        return cls("undirected", m, n, seed)

    # -- the schedule ------------------------------------------------------
    @property
    def period(self) -> int:
        """Rounds until the schedule repeats (B of the B-strongly-connected
        window for the exponential graph; 1 for static graphs; 0 marks the
        aperiodic random kinds)."""
        if self.kind == "exponential":
            return max(int(np.log2(self.m)), 1)
        if self.kind in ("ring", "full"):
            return 1
        return 0

    def key(self, t) -> jnp.ndarray:
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), t)

    def at(self, t) -> SparseTopology:
        """The round-t mixing pattern.  The draw runs eagerly on the host,
        inside the host span `dfedpgp.host.topology`."""
        from repro.obs.profiler import span  # obs imports this module
        with span(TOPOLOGY_SPAN):
            if self.kind == "random":
                return directed_random(self.key(t), self.m, self.n)
            if self.kind == "undirected":
                return undirected_random(self.key(t), self.m, self.n)
            if self.kind == "exponential":
                return directed_exponential(self.m, t)
            if self.kind == "ring":
                return ring(self.m)
            return fully_connected(self.m)

    __call__ = at

    def induced(self, t, active, renorm: str = "row") -> SparseTopology:
        """The round-t pattern restricted to the `active` subset — the ONE
        topology object stays the single source of who-talks-to-whom under
        partial participation (docs/scale.md).  Also under
        `dfedpgp.host.topology`."""
        from repro.obs.profiler import span
        with span(TOPOLOGY_SPAN):
            return induced_subgraph(self.at(t), active, renorm)

    def permutation_offsets(self) -> tuple:
        """For one-peer schedules: the per-round pull offsets, derived from
        the neighbor tables themselves (NOT re-derived arithmetic).  Round t
        uses offsets[t % len(offsets)]: every client pulls from the peer at
        (i - offset) mod m with weights (1/2, 1/2) — the doubly-stochastic
        permutation mix Regime B implements with lax.ppermute.

        Raises ValueError for schedules that are not permutation mixes.
        """
        if self.period == 0:
            raise ValueError(f"{self.kind!r} schedule is not periodic")
        offs = []
        for t in range(self.period):
            topo = self.at(t)
            idx, w = np.asarray(topo.idx), np.asarray(topo.w)
            if idx.shape[1] != 2 or not np.allclose(w, 0.5):
                raise ValueError(
                    f"{self.kind!r} round {t} is not a one-peer "
                    f"(1/2, 1/2) permutation mix")
            rows = np.arange(self.m)
            off = int(np.mod(rows[0] - idx[0, 1], self.m))
            if not np.array_equal(idx[:, 1], np.mod(rows - off, self.m)) \
                    or not np.array_equal(idx[:, 0], rows):
                raise ValueError(
                    f"{self.kind!r} round {t} is not a uniform-offset "
                    f"permutation")
            offs.append(off)
        return tuple(offs)


def get_schedule(kind: str, m: int, n: int = 0,
                 seed: int = 0) -> TopologySchedule:
    """The schedule registry (repro.spec): kind string -> the run's ONE
    TopologySchedule.  The degree/seed knobs only parameterize the random
    kinds; for the static kinds they are zeroed so two resolvers handed
    the same (kind, m) always produce EQUAL schedule objects — the
    one-topology invariant is an equality check away."""
    if kind not in TopologySchedule.KINDS:
        raise ValueError(
            f"schedule kind {kind!r}; known: {TopologySchedule.KINDS}")
    if kind in ("random", "undirected"):
        return TopologySchedule(kind, m, n, seed)
    return TopologySchedule(kind, m, 0, 0)


# ---------------------------------------------------------------------------
# diagnostics (numpy; used by tests and EXPERIMENTS.md)
# ---------------------------------------------------------------------------
def is_strongly_connected(P) -> bool:
    A = np.asarray(densify(P)) > 0
    m = A.shape[0]
    reach = np.eye(m, dtype=bool) | A
    for _ in range(int(np.ceil(np.log2(max(m, 2))))):
        reach = reach | (reach @ reach)
    return bool(reach.all())


def union_strongly_connected(Ps) -> bool:
    """Assumption 1 (B-bounded connectivity): is the union graph of a window
    of mixing matrices strongly connected?"""
    U = np.zeros_like(np.asarray(densify(Ps[0])))
    for P in Ps:
        U = U + np.asarray(densify(P))
    return is_strongly_connected(U)
