"""Seeds above 32 bits give runs of their own, and one seed one run."""
import jax
import numpy as np
import pytest

from bench import generators, seeds


def test_large_seeds_do_not_collide():
    keys = {tuple(np.asarray(jax.random.key_data(seeds.root_key(s)))
                  if hasattr(jax.random, "key_data") else
                  np.asarray(seeds.root_key(s)))
            for s in (5, 2 ** 32 + 5, 2 ** 33 + 5, 2 ** 31 + 5)}
    assert len(keys) == 4


def test_out_of_range_seed_raises():
    with pytest.raises(ValueError):
        seeds.root_key(-1)


def test_same_seed_same_inputs():
    a = seeds.topology_seed(2 ** 31 + 9)
    assert a == seeds.topology_seed(2 ** 31 + 9)
    assert 0 <= a < 2 ** 31 - 1
    k = seeds.stream(2 ** 31 + 9, "rounds")
    b1 = generators.lm_round_batches(k, 3, 100, 2, 1, 1, 2, 8)
    b2 = generators.lm_round_batches(k, 3, 100, 2, 1, 1, 2, 8)
    assert np.array_equal(b1["u"]["tokens"], b2["u"]["tokens"])
    assert not np.array_equal(b1["u"]["tokens"], b1["v"]["tokens"])


def test_topology_tables_are_the_programs():
    from repro.core.topology import TopologySchedule
    for t in range(3):
        idx, w = generators.topology("random", 12, 3, 77, t)
        P = TopologySchedule.random(12, 3, 77).at(t)
        assert np.array_equal(idx, np.asarray(P.idx))
        assert np.array_equal(w, np.asarray(P.w))
        idx, w = generators.topology("exponential", 8, 0, 0, t)
        P = TopologySchedule.exponential(8).at(t)
        assert np.array_equal(idx, np.asarray(P.idx))
    D = generators.dense(*generators.topology("random", 12, 3, 77, 0))
    assert np.allclose(D.sum(axis=1), 1.0)
