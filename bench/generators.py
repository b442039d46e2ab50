"""The benchmark's one traffic generator.

A traffic mix (`traffic/<name>.json`) is data: sizes, rates and steps.  This
module turns it into device arrays from a run's seed.  Its functions are
copies of the program's own seeded generators, kept here so that no later
change to the program can change what the benchmark feeds it:

- `lm_batch`: `repro.launch.train.synth_lm_batch` (dense family);
- `topology`: `repro.core.topology.directed_random` (below its dense-size
  switch) and `directed_exponential`, as `TopologySchedule.at(t)` draws
  them.  The reference mixes over these tables; the program draws its own
  from the same seed, so a program whose graph differs fails the check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# tokens
# ---------------------------------------------------------------------------
def lm_batch(key, vocab: int, lead: tuple, seq: int):
    """Uniform random tokens; labels are the tokens shifted by one."""
    kt, = jax.random.split(key, 1)
    toks = jax.random.randint(kt, lead + (seq,), 0, vocab, jnp.int32)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}


def lm_round_batches(run_key, r: int, vocab: int, m: int, k_v: int, k_u: int,
                     batch: int, seq: int):
    """One round's {"v", "u"} token batches, keyed as launch.train keys
    them (round r uses fold_in(key, r + 1))."""
    kb, _ = jax.random.split(jax.random.fold_in(run_key, r + 1))
    return {"v": lm_batch(kb, vocab, (m, k_v, batch), seq),
            "u": lm_batch(jax.random.fold_in(kb, 7), vocab,
                          (m, k_u, batch), seq)}


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------
DENSE_DRAW_MAX_M = 4096       # the program's switch to a with-replacement draw


def _directed_random(key, m: int, n_neighbors: int):
    n = min(n_neighbors, m - 1)
    if m > DENSE_DRAW_MAX_M:
        raise ValueError(f"m={m}: the benchmark copies only the "
                         f"permutation draw (m <= {DENSE_DRAW_MAX_M})")
    keys = jax.random.split(key, m)

    def row(i, k):
        perm = jax.random.permutation(k, m - 1)[:n]
        nb = jnp.where(perm >= i, perm + 1, perm)
        return jnp.concatenate([i[None], nb])

    idx = jax.vmap(row)(jnp.arange(m), keys)
    return idx, np.full((m, n + 1), 1.0 / (n + 1), np.float32)


def topology(kind: str, m: int, n_neighbors: int, seed: int, t: int):
    """Round t's pull table: (idx (m, k) int, w (m, k) float32)."""
    if kind == "random":
        key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
        idx, w = _directed_random(key, m, n_neighbors)
        return np.asarray(idx, np.int64), w
    if kind == "exponential":
        log_m = max(int(np.log2(m)), 1)
        if 2 ** log_m != m:
            raise ValueError(f"exponential graph wants power-of-two m={m}")
        rows = np.arange(m)
        src = np.mod(rows - 2 ** (t % log_m), m)
        return (np.stack([rows, src], axis=1),
                np.full((m, 2), 0.5, np.float32))
    raise ValueError(f"topology kind {kind!r}; known: random, exponential")


def dense(idx, w) -> np.ndarray:
    """The (m, m) row-stochastic matrix of a pull table."""
    m = idx.shape[0]
    P = np.zeros((m, m), np.float64)
    np.add.at(P, (np.arange(m)[:, None], idx), w)
    return P
