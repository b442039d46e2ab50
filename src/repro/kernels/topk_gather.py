"""Pallas TPU kernel: neighbor top-k payload gather + scatter-accumulate.

The compressed gossip transmission (docs/compress.md): each neighbor j
publishes a sparse payload — K (column, value) pairs per row — and the mix

    out[i, c] = sum_{j < k} w[i, j] * sum_{p < K}
                vals[idx[i, j], p] * [cols[idx[i, j], p] == c]

scatter-accumulates the payloads straight into the f32 output accumulator
WITHOUT ever materializing the dense decoded rows (the jnp fallback in
`ref.topk_gather_ref` decodes densely first — O(m*d) extra HBM traffic and
memory the kernel never pays).

Structure mirrors `gossip_gather.py` (same grid, same manual-DMA gather):

- grid (m/block_m, d_panels, k) with k innermost so the f32 accumulator
  lives in VMEM across the neighbor axis;
- the neighbor table rides in SMEM via scalar prefetch; the payload
  arrays stay whole in HBM (`pl.ANY`, as (m, 1, K) row views, see
  gossip_gather) and each grid step DMAs the `block_m` neighbors' (K,)
  value and column rows, all copies in flight before the first wait.
  Values travel as f32 (the accumulate dtype);
- the scatter is TPU-vectorized as K masked FMAs: column ids compare
  against the panel's broadcasted iota — one (block_m, block_d) vector op
  per payload slot, no per-element stores.

K is padded to the 128-lane quantum with (column = d_pad, value = 0)
entries — out-of-panel columns, zero contribution.  `interpret=True` runs
the same body on CPU (the validation path without a TPU, like every
kernel here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gossip_gather import BD, BM, by_row_chunks, row_view

KP = 128            # payload-slot padding quantum (lanes)


def _scatter_kernel(idx_ref, w_ref, v_ref, c_ref, out_ref, vals_ref,
                    cols_ref, acc_ref, sems, *, k: int):
    # idx_ref, w_ref: (mp*k,) scalar-prefetch (SMEM), row-major (i, j).
    # v_ref/c_ref: the WHOLE (m, 1, Kp) payload arrays in HBM/ANY (row
    # views, gossip_gather.row_view); the kernel gathers the panel's
    # block_m neighbor payloads itself.
    i = pl.program_id(0)
    dt = pl.program_id(1)
    j = pl.program_id(2)
    bm, _, Kp = vals_ref.shape
    bd = acc_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def copy(src_ref, dst_ref, r, s):
        return pltpu.make_async_copy(
            src_ref.at[idx_ref[(i * bm + r) * k + j]], dst_ref.at[r],
            sems.at[r, s])

    for r in range(bm):
        copy(v_ref, vals_ref, r, 0).start()
        copy(c_ref, cols_ref, r, 1).start()
    for r in range(bm):
        copy(v_ref, vals_ref, r, 0).wait()
        copy(c_ref, cols_ref, r, 1).wait()

    wcol = jnp.stack([w_ref[(i * bm + r) * k + j] for r in range(bm)])
    wv = wcol[:, None] * vals_ref[...].reshape(bm, Kp)             # (bm, Kp)
    cols = cols_ref[...].reshape(bm, Kp)
    panel_cols = dt * bd + jax.lax.broadcasted_iota(jnp.int32, (1, bd), 1)
    acc = acc_ref[...]
    for p in range(Kp):
        hit = cols[:, p:p + 1] == panel_cols                        # (bm, bd)
        acc = acc + jnp.where(hit, wv[:, p:p + 1], 0.0)
    acc_ref[...] = acc

    @pl.when(j == k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def topk_gather_pallas(idx: jnp.ndarray, w: jnp.ndarray,
                       values: jnp.ndarray, cols: jnp.ndarray, d: int,
                       block_d: int = BD, block_m: int | None = None,
                       interpret: bool = False):
    """out[i] = sum_j w[i,j] * scatter(values[idx[i,j]], cols[idx[i,j]]).

    idx: (m, k) int32 in-neighbor ids; w: (m, k) weights (cast to f32);
    values: (m, K) payload values (any float dtype); cols: (m, K) column
    ids (any int dtype; uint16 wire format welcome); d: dense row width.
    Returns (m, d) in the values dtype, accumulated in f32.
    """
    m, k = idx.shape
    mv, K = values.shape
    assert mv == m and cols.shape == (m, K), (idx.shape, values.shape,
                                              cols.shape)
    block_m = BM if block_m is None else block_m
    mp = -(-m // block_m) * block_m
    dp = max(-(-d // block_d) * block_d, block_d)
    Kp = max(-(-K // KP) * KP, KP)
    if mp != m:
        idx = jnp.concatenate(
            [idx, jnp.zeros((mp - m, k), idx.dtype)], axis=0)
        w = jnp.concatenate([w, jnp.zeros((mp - m, k), w.dtype)], axis=0)
    vals = values.astype(jnp.float32)
    cols = cols.astype(jnp.int32)
    if Kp != K:
        vals = jnp.concatenate(
            [vals, jnp.zeros((m, Kp - K), jnp.float32)], axis=1)
        cols = jnp.concatenate(
            [cols, jnp.full((m, Kp - K), dp, jnp.int32)], axis=1)
    vals, cols = row_view(vals), row_view(cols)

    def call(idx, w):
        rows = idx.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                   # idx, w ride in SMEM
            grid=(rows // block_m, dp // block_d, k),  # k innermost
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # values, DMA-gathered
                pl.BlockSpec(memory_space=pl.ANY),   # cols, DMA-gathered
            ],
            out_specs=pl.BlockSpec((block_m, block_d),
                                   lambda i, dt, j, idx_ref, w_ref: (i, dt)),
            scratch_shapes=[pltpu.VMEM((block_m, 1, Kp), jnp.float32),
                            pltpu.VMEM((block_m, 1, Kp), jnp.int32),
                            pltpu.VMEM((block_m, block_d), jnp.float32),
                            pltpu.SemaphoreType.DMA((block_m, 2))],
        )
        return pl.pallas_call(
            functools.partial(_scatter_kernel, k=k),
            grid_spec=grid_spec,
            name="topk_gather",
            out_shape=jax.ShapeDtypeStruct((rows, dp), values.dtype),
            interpret=interpret,
        )(idx.reshape(-1).astype(jnp.int32),
          w.reshape(-1).astype(jnp.float32), vals, cols)

    return by_row_chunks(call, idx, w, block_m)[:m, :d]
