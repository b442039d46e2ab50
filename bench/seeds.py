"""Keys drawn from a run's `--seed`.

`jax.random.PRNGKey` keeps only the low 32 bits of a large seed, so two
seeds that differ above bit 32 would give the same run.  The seed is
folded in as two 32-bit words instead.  Each consumer takes its own
stream, so the data, the weights and the per-round draws stay the same
whatever else a run does.
"""
from __future__ import annotations

import jax

STREAMS = {"data": 1, "weights": 2, "rounds": 3, "topology": 4}


def root_key(seed: int):
    if not 0 <= seed < 2 ** 63:
        raise ValueError(f"--seed {seed}: want a whole number in [0, 2**63)")
    hi, lo = divmod(seed, 2 ** 32)
    key = jax.random.fold_in(jax.random.PRNGKey(0), hi)
    return jax.random.fold_in(key, lo)


def stream(seed: int, name: str):
    return jax.random.fold_in(root_key(seed), STREAMS[name])


def topology_seed(seed: int) -> int:
    """The integer seed of the run's topology schedule (31 bits, so that it
    is a valid `PRNGKey` seed for any implementation of the schedule)."""
    return int(jax.random.randint(stream(seed, "topology"), (), 0,
                                  2 ** 31 - 1))
