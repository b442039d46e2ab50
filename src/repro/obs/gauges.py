"""In-graph round gauges (docs/observability.md §Gauges).

Every gauge in this module is jit-safe and PURE: it reads the resident
(m, d_flat) buffer / the (m,) push-sum weights and returns f32 scalars as
aux outputs of the round, without ever touching the state that flows on.
The instrumented round is therefore BIT-FOR-BIT the uninstrumented round
(tests/test_obs.py) — telemetry only adds reductions next to the donated
carry, no host syncs and no extra unravels.

The paper connection (PAPER.md): the convergence rate of Algorithm 1 is
O(1/sqrt(T)) with a constant driven by the directed graph's connectivity
Gamma(W) — the quantity `consensus_gap` tracks at runtime — while the
push-sum de-bias z = u/mu is only correct while total mass is conserved,
which is what `mass_ledger` (pushsum.mass_split promoted from a test-only
diagnostic to a runtime gauge) pins every round/tick.

Host-side meters (wire-byte arithmetic, device-memory accounting) live at
the bottom: they are the ONE source both runtimes' accounting reads
(fl/simulator.py sync and async meters — the single-source fix for the
historical sync/async asymmetry) and the benchmarks re-export
(`benchmarks/common.py`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.compress import MU_BYTES
from repro.core import pushsum
from repro.core.topology import SparseTopology


# ---------------------------------------------------------------------------
# in-graph gauges (jit-safe, pure reads)
# ---------------------------------------------------------------------------
def consensus_gap(flat: jnp.ndarray, mu: jnp.ndarray) -> dict:
    """De-biased row distance to the mass-weighted mean of the resident
    buffer — the runtime face of the Gamma(W) connectivity term.

    z_i = u_i / mu_i is client i's de-biased model; the mass-weighted mean
    z_bar = sum_i u_i / sum_i mu_i is the point push-sum contracts toward
    (exactly the consensus="mass" trunk of serve.ServingState).  Returns
    {"consensus_gap_mean", "consensus_gap_max"}: mean/max over clients of
    ||z_i - z_bar||_2, in f32.  Under repeated mixing with a connected
    column- or row-stochastic graph this contracts geometrically
    (tests/test_obs.py pins monotone decrease on a full graph)."""
    u = flat.astype(jnp.float32)
    z = u / mu[:, None].astype(jnp.float32)
    z_bar = jnp.sum(u, axis=0) / jnp.sum(mu).astype(jnp.float32)
    d = jnp.sqrt(jnp.sum(jnp.square(z - z_bar[None, :]), axis=1))
    return {"consensus_gap_mean": jnp.mean(d), "consensus_gap_max": jnp.max(d)}


def mass_ledger(mu: jnp.ndarray, active_mask=None, *in_flight_mus) -> dict:
    """The push-sum mass ledger as a runtime gauge: (active, dormant,
    in-flight, total) components of the conserved sum(mu).

    Wraps `pushsum.mass_split` — promoted from a test-only invariant
    (tests/test_sampling.py) to a gauge every instrumented round emits.
    active_mask=None means full participation (everything active);
    in_flight_mus are the mailbox components of the async runtime.  The
    CI telemetry smoke hard-fails when total drifts from m beyond f32
    tolerance (repro.obs.report --check)."""
    if active_mask is None:
        active_mask = jnp.ones(mu.shape, bool)
    active, dormant, flight = pushsum.mass_split(mu, active_mask,
                                                 *in_flight_mus)
    return {"mass_active": active, "mass_dormant": dormant,
            "mass_in_flight": flight,
            "mass_total": active + dormant + flight}


def ef_signal_ratio(flat: jnp.ndarray, ef: jnp.ndarray) -> jnp.ndarray:
    """Residual-to-signal ratio of the error-feedback memory:
    ||u|| / (||u|| + ||ef||) in f32, in (0, 1].

    1.0 means the codec pipe is keeping up (zero residual); a falling
    ratio means the wire is dropping value faster than it drains.  This is
    the SAME expression the adaptive consensus step reads
    (`DFedPGP.codec_gamma="auto"` clips it to [0.05, 1]) — previously
    computed ad-hoc inside `_gamma_value`, now one definition both the
    anneal and the telemetry stream share."""
    un = jnp.linalg.norm(flat.astype(jnp.float32))
    en = jnp.linalg.norm(ef.astype(jnp.float32))
    eps = jnp.float32(1e-12)
    return (un + eps) / (un + en + eps)


def buffer_update_norm(flat_before: jnp.ndarray,
                       flat_after: jnp.ndarray) -> jnp.ndarray:
    """Frobenius norm of the local-phase displacement of the resident
    buffer (pre-mix) — the per-round "how far did local SGD move the
    shared part" gauge, in f32."""
    d = flat_after.astype(jnp.float32) - flat_before.astype(jnp.float32)
    return jnp.linalg.norm(d)


def wire_edges(P, fired: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """In-graph count of directed non-self edges carrying a payload —
    int32 scalar.  `fired` optionally restricts to edges whose SENDER
    fired this tick (the async runtime's form; `None` counts every
    positive-weight non-self edge, the sync round's form).  Bytes are
    host arithmetic: edges * `payload_row_bytes` — one formula for both
    runtimes (docs/observability.md §Wire accounting)."""
    if isinstance(P, SparseTopology):
        rows = jnp.arange(P.idx.shape[0], dtype=P.idx.dtype)[:, None]
        mask = (P.idx != rows) & (P.w > 0)
        if fired is not None:
            mask = jnp.take(fired, P.idx, axis=0) & mask
        return jnp.sum(mask).astype(jnp.int32)
    m = P.shape[0]
    mask = (P > 0) & ~jnp.eye(m, dtype=bool)
    if fired is not None:
        mask = mask & fired[None, :]
    return jnp.sum(mask).astype(jnp.int32)


def staleness_gauges(local_round: jnp.ndarray) -> dict:
    """Distribution of per-client progress lag behind the fleet's head
    (async runtime): lag_i = max_j local_round_j - local_round_i.  The
    mean/max pair is the per-tick shape of the staleness distribution the
    delayed push-sum analysis bounds (docs/hetero.md)."""
    lr = local_round.astype(jnp.float32)
    lag = jnp.max(lr) - lr
    return {"staleness_mean": jnp.mean(lag), "staleness_max": jnp.max(lag)}


def mailbox_gauges(slots_mu: jnp.ndarray, inbox_mu: jnp.ndarray) -> dict:
    """Mailbox occupancy (async runtime): the fraction of (slot, receiver)
    cells / inbox rows holding undelivered or undrained mass, plus the mu
    mass sitting in each.  Rising slot occupancy means wire delays are
    outpacing drains; rising inbox mass means receivers are asleep
    (availability gating) while mail piles up."""
    return {
        "mailbox_slot_occupancy": jnp.mean((slots_mu > 0.0)
                                           .astype(jnp.float32)),
        "mailbox_inbox_occupancy": jnp.mean((inbox_mu > 0.0)
                                            .astype(jnp.float32)),
        "mailbox_slot_mass": jnp.sum(slots_mu),
        "mailbox_inbox_mass": jnp.sum(inbox_mu),
    }


# ---------------------------------------------------------------------------
# wire-byte arithmetic (host-side; the ONE source both runtimes read)
# ---------------------------------------------------------------------------
def payload_row_bytes(codec, d_wire: int) -> int:
    """Bytes one client payload costs on the wire: the codec's metered
    row size, or the uncompressed f32 row + the mu scalar.  Both the sync
    round meter and the async tick meter multiply THIS number by their
    edge counts — the single-source fix for the historical asymmetry
    (fl/simulator.py used to inline the formula twice)."""
    if codec is not None:
        return int(codec.row_bytes(d_wire))
    return 4 * d_wire + MU_BYTES


def bootstrap_bytes(codec, m: int, d_wire: int) -> int:
    """Reference-bootstrap cost of a LOSSY codec: first contact ships one
    full-fidelity f32 row per client (compress.init_ref), metered so the
    compression claims stay honest.  Exact/absent codecs cost zero."""
    if codec is None or codec.exact:
        return 0
    return m * 4 * d_wire


def edge_count(P) -> int:
    """Host-side twin of `wire_edges(P)`: the number of payload-carrying
    directed non-self edges of a concrete round topology (sync meter)."""
    import numpy as np
    if isinstance(P, SparseTopology):
        idx, w = np.asarray(P.idx), np.asarray(P.w)
        rows = np.arange(idx.shape[0])[:, None]
        return int(((w > 0) & (idx != rows)).sum())
    Pd = np.asarray(P)
    return int(((Pd > 0) & ~np.eye(Pd.shape[0], dtype=bool)).sum())


# ---------------------------------------------------------------------------
# device-memory meters (moved here from benchmarks/common.py — obs owns
# resource gauges now; benchmarks re-export for compat)
# ---------------------------------------------------------------------------
def peak_device_memory(device=None):
    """Peak bytes in use on `device` (default: device 0), from the
    backend's allocator stats (jax Device.memory_stats — populated on
    TPU/GPU).  The CPU backend reports no allocator stats, so callers pair
    this with the deterministic `accounted_bytes` meter and record None
    here — the committed artifact then documents which meter produced the
    number.  A backend that fails to report raises."""
    stats = (device or jax.devices()[0]).memory_stats()
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak else None


def accounted_bytes(*arrays) -> int:
    """Deterministic memory meter: total bytes of the given live arrays
    (buffers, working sets, neighbor tables).  Unlike allocator peaks this
    is identical across runners, so check_regression.py can pin it as a
    hard ceiling — any growth is a real change in what the path
    materializes, not noise."""
    total = 0
    for a in arrays:
        leaves = a if isinstance(a, (list, tuple)) else [a]
        for x in leaves:
            total += int(x.size) * int(x.dtype.itemsize)
    return total
