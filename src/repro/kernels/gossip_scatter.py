"""Pallas TPU kernel: compact-working-set scatter into the resident buffer.

The write-side twin of `gossip_gather`: partial participation
(docs/scale.md) runs the round on the compact (n_active, d_flat) working
set and this kernel lands the results back in the big (m, d_flat) resident
buffer

    U[rows[p], :] = X[p, :]                       (set mode)
    U[rows[p], :] = U[rows[p], :] + X[p, :]       (accumulate mode, f32 sum)

without ever materializing the dormant rows: U stays whole in HBM
(`pl.ANY`) and is ALIASED to the output (`input_output_aliases`), so the
dormant rows are never copied — the kernel's HBM traffic is O(n_active*d),
not O(m*d).  Structure:

- the (n,) destination-row table rides in SMEM via scalar prefetch (plus a
  scalar count so block_m padding rows never fire a DMA);
- the grid is (n/block_m, d_panels).  Mosaic moves HBM data only in whole
  sublane tiles (8 rows for 32-bit, 16 for bf16), so each step reads the
  tile that holds each of its block_m rows, merges into every such tile
  ALL of the panel's rows that fall in it, and writes the tiles back — all
  reads in flight before the first wait, then all writes.  Two rows of one
  panel that share a tile thus write identical bytes, and panels run in
  grid order, so no update is lost;
- the kernel writes only whole tiles of U: when m is not a multiple of the
  tile height or d not a multiple of 128 lanes, the rows of the partial
  last tile and the columns of the partial last lane tile (fewer than 16
  rows, fewer than 128 columns) are written by an XLA scatter, also in
  place.  Only X pads to the panel quantum, and the last d-panel runs a
  statically-narrowed copy instead of writing past the whole lane tiles.

Destination rows must be UNIQUE (the sampler emits a set).  `interpret=True`
runs the same body on CPU — the validation path without a TPU
(tests/test_sampling.py), not a fast path (the jnp oracle
`ref.gossip_scatter_ref` is that).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gossip_gather import BD, BM

LANE = 128
SMEM_ROWS = 1 << 16      # destination rows per call (256 KiB of SMEM)


def _scatter_kernel(rows_ref, nreal_ref, x_ref, u_ref, out_ref, tiles_ref,
                    sems, *, accumulate: bool, rem: int, m_tiles: int):
    # rows_ref, nreal_ref: scalar-prefetch (SMEM).  u_ref: the big buffer,
    # aliased to out_ref — all reads and writes go through out_ref so the
    # alias is the single memory.  x_ref: this panel's (block_m, block_d)
    # f32 VMEM block of the compact working set.
    del u_ref
    i = pl.program_id(0)
    dt = pl.program_id(1)
    nd = pl.num_programs(1)
    bm, bd = x_ref.shape
    th = tiles_ref.shape[1]
    row = [rows_ref[i * bm + r] for r in range(bm)]
    live = [(i * bm + r < nreal_ref[0]) & (row[r] < m_tiles)
            for r in range(bm)]
    tile = [rw // th for rw in row]
    sub = jax.lax.broadcasted_iota(jnp.int32, (th, bd), 0)

    def body(w):
        # w: the STATIC width of this d-panel (bd, or the tail remainder)
        def copy(r, to_hbm):
            hbm = out_ref.at[pl.ds(pl.multiple_of(tile[r] * th, th), th),
                             pl.ds(dt * bd, w)]
            vmem = tiles_ref.at[r, :, pl.ds(0, w)]
            if to_hbm:
                return pltpu.make_async_copy(vmem, hbm, sems.at[r, 1])
            return pltpu.make_async_copy(hbm, vmem, sems.at[r, 0])

        def each_live(fn):
            for r in range(bm):
                pl.when(live[r])(functools.partial(fn, r))

        each_live(lambda r: copy(r, False).start())
        each_live(lambda r: copy(r, False).wait())
        for r in range(bm):
            # every live row of the panel that falls in row r's tile
            cur = tiles_ref[r].astype(jnp.float32)              # (th, bd)
            for r2 in range(bm):
                at = jnp.where(live[r2] & (tile[r2] == tile[r]),
                               row[r2] % th, -1)
                xr = x_ref[pl.ds(r2, 1), :]                     # (1, bd)
                cur = jnp.where(sub == at, cur + xr if accumulate else xr,
                                cur)
            tiles_ref[r] = cur.astype(tiles_ref.dtype)
        each_live(lambda r: copy(r, True).start())
        each_live(lambda r: copy(r, True).wait())

    if rem and nd > 1:
        @pl.when(dt < nd - 1)
        def _full():
            body(bd)

        @pl.when(dt == nd - 1)
        def _tail():
            body(rem)
    elif rem:
        body(rem)       # single panel narrower than block_d: tail only
    else:
        body(bd)


def _scatter_call(rows, X, U, accumulate, block_m, block_d, interpret):
    """One pallas_call: rows (n,), X (np, dx) f32 panel-padded, U (m, d).
    It writes only rows below the last whole tile and columns below the
    last whole lane tile (`_edges` writes the rest)."""
    n = rows.shape[0]
    np_, dx = X.shape
    m, d = U.shape
    th = 8 * 4 // jnp.dtype(U.dtype).itemsize
    d_tiles = d // LANE * LANE
    nd = dx // block_d
    rem = d_tiles - (nd - 1) * block_d      # width of the last panel
    rem = 0 if rem == block_d else rem      # aligned: no tail branch
    if np_ != n:
        rows = jnp.concatenate(
            [rows, jnp.zeros((np_ - n,), rows.dtype)], axis=0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # rows, nreal ride in SMEM
        grid=(np_ // block_m, nd),
        in_specs=[
            pl.BlockSpec((block_m, block_d),
                         lambda i, dt, rows_ref, nreal_ref: (i, dt)),
            pl.BlockSpec(memory_space=pl.ANY),   # U whole, aliased output
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((block_m, th, block_d), U.dtype),
                        pltpu.SemaphoreType.DMA((block_m, 2))],
    )
    return pl.pallas_call(
        functools.partial(_scatter_kernel, accumulate=accumulate, rem=rem,
                          m_tiles=m // th * th),
        grid_spec=grid_spec,
        name="gossip_scatter",
        out_shape=jax.ShapeDtypeStruct(U.shape, U.dtype),
        input_output_aliases={3: 0},        # U IS the output buffer
        interpret=interpret,
    )(rows.astype(jnp.int32), jnp.asarray([n], jnp.int32), X, U)


def _put(U, rows, cols, X, accumulate):
    """U[rows, cols] = X (or += X in f32) as an XLA scatter, in place when
    U is dead after it; rows past the end of U are dropped."""
    if accumulate:
        X = U.at[rows, cols].get(mode="fill", fill_value=0).astype(
            jnp.float32) + X
    return U.at[rows, cols].set(X.astype(U.dtype), mode="drop")


def _edges(rows, X, U, accumulate):
    """The part of the write the kernel leaves: rows of the last, partial
    sublane tile and columns of the last, partial lane tile — at most
    th - 1 rows and 127 columns, written by XLA into U in place."""
    m, d = U.shape
    th = 8 * 4 // jnp.dtype(U.dtype).itemsize
    m_tiles, d_tiles = m // th * th, d // LANE * LANE
    if d_tiles < d:
        U = _put(U, rows, slice(d_tiles, d), X[:, d_tiles:], accumulate)
    if m_tiles < m and d_tiles:
        tail = jnp.where(rows >= m_tiles, rows, m)
        U = _put(U, tail, slice(0, d_tiles), X[:, :d_tiles], accumulate)
    return U


def gossip_scatter_pallas(rows: jnp.ndarray, X: jnp.ndarray, U: jnp.ndarray,
                          accumulate: bool = False, block_d: int = BD,
                          block_m: int | None = None,
                          interpret: bool = False):
    """U.at[rows].set(X)  (or += X in f32 when accumulate) — U aliased.

    rows: (n,) int32 UNIQUE destination rows; X: (n, d) compact values
    (cast to U.dtype on the way in); U: (m, d) float32 or bfloat16
    resident buffer, returned with only the addressed rows changed.  X pads
    to the (block_m, block_d) quantum with zero rows that the scalar count
    keeps from firing any DMA.  U is never padded or copied: the kernel
    writes its whole (th, 128) tiles, XLA the partial edge tiles.
    """
    n, d = X.shape
    m, du = U.shape
    assert du == d and rows.shape == (n,), (rows.shape, X.shape, U.shape)
    if n == 0 or d == 0:
        return U
    if U.dtype not in (jnp.float32, jnp.bfloat16):
        raise ValueError(f"gossip_scatter_pallas: buffer dtype {U.dtype} "
                         f"(float32 or bfloat16)")
    X = X.astype(U.dtype).astype(jnp.float32)
    block_m = BM if block_m is None else block_m
    th = 8 * 4 // jnp.dtype(U.dtype).itemsize
    d_tiles = d // LANE * LANE
    if m >= th and d_tiles:
        dx = -(-d_tiles // block_d) * block_d
        for s in range(0, n, SMEM_ROWS):
            Xs = X[s:s + SMEM_ROWS, :d_tiles]
            ns = Xs.shape[0]
            np_ = -(-ns // block_m) * block_m
            if (np_, dx) != Xs.shape:
                Xs = jnp.zeros((np_, dx), jnp.float32).at[:ns, :d_tiles].set(
                    Xs)
            U = _scatter_call(rows[s:s + SMEM_ROWS], Xs, U, accumulate,
                              block_m, block_d, interpret)
    return _edges(rows, X, U, accumulate)
