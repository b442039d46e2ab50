"""TopologySchedule: one object decides who talks to whom in both regimes.

Covers round-schedule determinism (same seed -> identical neighbor tables
across instances), the kind -> constructor mapping, permutation-offset
derivation for the ppermute path, and the sparse fully_connected form.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gossip, topology
from repro.core.topology import SparseTopology, TopologySchedule
from repro.fl import simulator


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,kw", [
    ("random", dict(n=3, seed=11)),
    ("undirected", dict(n=3, seed=11)),
])
def test_schedule_determinism_across_instances(kind, kw):
    a = TopologySchedule(kind, 12, **kw)
    b = TopologySchedule(kind, 12, **kw)
    for t in range(5):
        ta, tb = a.at(t), b.at(t)
        np.testing.assert_array_equal(np.asarray(ta.idx), np.asarray(tb.idx))
        np.testing.assert_array_equal(np.asarray(ta.w), np.asarray(tb.w))


def test_schedule_seed_changes_tables():
    a = TopologySchedule.random(12, 3, seed=0)
    b = TopologySchedule.random(12, 3, seed=1)
    assert not np.array_equal(np.asarray(a.at(0).idx),
                              np.asarray(b.at(0).idx))


def test_schedule_rounds_differ_for_random():
    a = TopologySchedule.random(12, 3, seed=0)
    assert not np.array_equal(np.asarray(a.at(0).idx),
                              np.asarray(a.at(1).idx))


# ---------------------------------------------------------------------------
# kind -> constructor mapping
# ---------------------------------------------------------------------------
def test_exponential_schedule_matches_constructor():
    s = TopologySchedule.exponential(16)
    for t in range(6):
        want = topology.directed_exponential(16, t)
        got = s.at(t)
        np.testing.assert_array_equal(np.asarray(got.idx),
                                      np.asarray(want.idx))


def test_static_kinds_ignore_round():
    for s in (TopologySchedule.ring(7), TopologySchedule.full(7)):
        np.testing.assert_array_equal(np.asarray(s.at(0).idx),
                                      np.asarray(s.at(9).idx))
        assert s.period == 1


def test_every_kind_returns_sparse():
    for s in (TopologySchedule.random(8, 3), TopologySchedule.exponential(8),
              TopologySchedule.ring(8), TopologySchedule.full(8),
              TopologySchedule.undirected(8, 3)):
        topo = s.at(2)
        assert isinstance(topo, SparseTopology)
        np.testing.assert_allclose(np.asarray(topo.w).sum(1), 1.0, atol=1e-5)


def test_unknown_kind_rejected():
    with pytest.raises(ValueError):
        TopologySchedule("smallworld", 8)


# ---------------------------------------------------------------------------
# permutation offsets (the Regime B ppermute derivation)
# ---------------------------------------------------------------------------
def test_exponential_offsets_derived_from_tables():
    assert TopologySchedule.exponential(8).permutation_offsets() == (1, 2, 4)
    assert TopologySchedule.exponential(16).permutation_offsets() == \
        (1, 2, 4, 8)
    assert TopologySchedule.ring(6).permutation_offsets() == (1,)


def test_non_permutation_schedules_rejected():
    with pytest.raises(ValueError):
        TopologySchedule.random(8, 3).permutation_offsets()
    with pytest.raises(ValueError):
        TopologySchedule.full(8).permutation_offsets()


# ---------------------------------------------------------------------------
# sparse fully_connected (satellite fix)
# ---------------------------------------------------------------------------
def test_fully_connected_is_sparse_topology():
    fc = topology.fully_connected(6)
    assert isinstance(fc, SparseTopology)
    assert fc.k == 6
    # self first, every client exactly once per row
    np.testing.assert_array_equal(np.asarray(fc.idx[:, 0]), np.arange(6))
    assert all(sorted(np.asarray(fc.idx[i])) == list(range(6))
               for i in range(6))
    np.testing.assert_allclose(np.asarray(fc.dense()),
                               np.full((6, 6), 1.0 / 6), atol=1e-6)


def test_fully_connected_mix_any_is_mean():
    fc = topology.fully_connected(5)
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 4))
    got = gossip.mix_any(fc, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.broadcast_to(np.asarray(x).mean(0), (5, 4)),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# simulator integration
# ---------------------------------------------------------------------------
def test_resolved_schedule_kinds():
    sim = simulator.SimConfig(m=8, n_neighbors=3, seed=4)
    sched = simulator.resolve_spec("dfedpgp", sim).schedule(sim.m)
    assert sched.kind == "random"
    assert simulator.resolve_spec("dfedavgm", sim).schedule(sim.m).kind \
        == "undirected"
    for topo_name in ("exponential", "ring", "full"):
        s = simulator.resolve_spec(
            "dfedpgp",
            dataclasses.replace(sim, topology=topo_name)).schedule(sim.m)
        assert s.kind == topo_name
    with pytest.raises(ValueError):
        simulator.resolve_spec(
            "dfedpgp", dataclasses.replace(sim, topology="torus"))


def test_resolved_schedule_deterministic_in_seed():
    sim = simulator.SimConfig(m=10, n_neighbors=3, seed=7)
    s1 = simulator.resolve_spec("dfedpgp", sim).schedule(sim.m)
    s2 = simulator.resolve_spec("dfedpgp", sim).schedule(sim.m)
    for t in (0, 3):
        np.testing.assert_array_equal(np.asarray(s1.at(t).idx),
                                      np.asarray(s2.at(t).idx))


def test_full_topology_runs_sparse_in_simulator():
    """The gossip knob must not silently densify for the complete graph."""
    sim = simulator.SimConfig(m=6, rounds=1, n_neighbors=2, n_train=8,
                              n_test=4, batch=4, k_local=1, k_personal=1,
                              topology="full")
    h = simulator.run_experiment("dfedpgp", sim, eval_every=1)
    assert np.isfinite(h["final_acc"])


def test_schedule_window_strongly_connected():
    """Assumption 1 (B-bounded connectivity) holds for a period window of
    the exponential schedule."""
    s = TopologySchedule.exponential(16)
    window = [s.at(t) for t in range(s.period)]
    assert topology.union_strongly_connected(window)


# ---------------------------------------------------------------------------
# dense-degree ceiling on every O(m^2) path (docs/scale.md)
# ---------------------------------------------------------------------------
def _tiny_ceiling(monkeypatch, cap=4):
    monkeypatch.setattr(topology, "MAX_DENSE_M", cap)


def test_dense_degree_guard_from_dense(monkeypatch):
    _tiny_ceiling(monkeypatch)
    with pytest.raises(ValueError, match="MAX_DENSE_M"):
        topology.from_dense(np.eye(6, dtype=np.float32))


def test_dense_degree_guard_dense_method(monkeypatch):
    P = topology.ring(6)        # sparse table builds fine above the cap...
    _tiny_ceiling(monkeypatch)
    with pytest.raises(ValueError, match="MAX_DENSE_M"):
        P.dense()               # ...materializing (m, m) does not


def test_dense_degree_guard_densify_helper(monkeypatch):
    _tiny_ceiling(monkeypatch)
    with pytest.raises(ValueError, match="MAX_DENSE_M"):
        topology.densify(topology.ring(6))


def test_dense_degree_guard_fully_connected(monkeypatch):
    _tiny_ceiling(monkeypatch)
    with pytest.raises(ValueError, match="MAX_DENSE_M"):
        topology.fully_connected(6)


def test_dense_degree_guard_undirected(monkeypatch):
    _tiny_ceiling(monkeypatch)
    with pytest.raises(ValueError, match="MAX_DENSE_M"):
        topology.undirected_random(jax.random.PRNGKey(0), 6, 2)


def test_dense_degree_guard_induced_subgraph(monkeypatch):
    # a dense-width (k = m) neighbor table: inducing over it walks the
    # full O(m^2) table, so the same ceiling applies
    P = topology.fully_connected(6)
    _tiny_ceiling(monkeypatch)
    act = jnp.asarray([0, 2, 4], jnp.int32)
    with pytest.raises(ValueError, match="MAX_DENSE_M"):
        topology.induced_subgraph(P, act, "row")


def test_sparse_paths_unaffected_by_ceiling(monkeypatch):
    _tiny_ceiling(monkeypatch)
    # sparse-degree construction and induction stay open above the cap
    P = topology.directed_random(jax.random.PRNGKey(0), 8, 2)
    act = jnp.asarray([0, 3, 5], jnp.int32)
    sub = topology.induced_subgraph(P, act, "row")
    assert sub.idx.shape == (3, P.k)
    np.testing.assert_allclose(np.asarray(sub.w.sum(1)), 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# the draw's host span changes no table
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,direct", [
    ("random", lambda s, t: topology.directed_random(s.key(t), s.m, s.n)),
    ("undirected",
     lambda s, t: topology.undirected_random(s.key(t), s.m, s.n)),
    ("exponential", lambda s, t: topology.directed_exponential(s.m, t)),
    ("ring", lambda s, t: topology.ring(s.m)),
    ("full", lambda s, t: topology.fully_connected(s.m)),
])
def test_at_inside_its_span_returns_the_constructors_tables(kind, direct):
    s = topology.get_schedule(kind, 8, n=3, seed=5)
    for t in range(3):
        got, want = s.at(t), direct(s, t)
        np.testing.assert_array_equal(np.asarray(got.idx),
                                      np.asarray(want.idx))
        np.testing.assert_array_equal(np.asarray(got.w), np.asarray(want.w))
    active = jnp.asarray([0, 2, 5])
    got = s.induced(1, active)
    want = topology.induced_subgraph(direct(s, 1), active, "row")
    np.testing.assert_array_equal(np.asarray(got.idx), np.asarray(want.idx))
    np.testing.assert_array_equal(np.asarray(got.w), np.asarray(want.w))
