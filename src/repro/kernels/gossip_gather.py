"""Pallas TPU kernel: fused neighbor-indexed gossip gather-mix.

Computes the sparse push-pull transmission over the flat client buffer

    out[i, :] = sum_{j < k} w[i, j] * U[idx[i, j], :]        U: (m, d_flat)

in O(m*k*d) HBM traffic.  The neighbor table rides in as flat (m*k,)
scalar-prefetch operands (SMEM); U stays whole in HBM (`pl.ANY`) and the
kernel gathers it with MANUAL row DMAs batched into multi-row panels:

- the grid is (m/block_m, d_panels, k) with k innermost, so the f32 VMEM
  accumulator lives across the neighbor axis;
- each grid step issues `block_m` single-row HBM->VMEM copies — one per
  client in the output panel, rows resolved from the prefetched neighbor
  table — and keeps ALL of them in flight before waiting;
- the weighted accumulation and the output write then run on full
  (block_m, block_d) panels.

TPU layout rules shape the operands (checked by tests/test_tpu_compile.py):

- Mosaic slices an HBM ref only at whole (8, 128) tiles of its last two
  dims, so a single-row DMA out of an (m, d) buffer is refused.  The
  payload is handed to the kernel as an (m, 1, d) view instead: the row is
  then a leading-axis index and XLA lays the view out with (1, 128) tiles.
  Where U's producer cannot write that layout directly, XLA inserts one
  relayout pass over U ahead of the k gathers.
- bf16 packs two rows per sublane, so no single bf16 row is addressable
  either.  A bf16 payload travels as 32-bit words, two adjacent columns per
  word; the kernel splits each word into its two bf16 halves (exact in
  f32), accumulates them separately and re-packs on the way out.
- SMEM holds about 1 MiB, so one call takes at most `SMEM_ENTRIES`
  neighbor-table entries; larger tables are split into row chunks.

bf16 payloads are supported (the quantized push-sum of Taheri et al.) —
the accumulator is f32 regardless of the wire dtype.  This replaces the
dense pushsum_mix matmul (O(m^2*d) MXU work) for the paper's regime
k = n+1 << m.  `interpret=True` runs the same kernel body (including the
DMAs) on CPU — how the kernel is validated without a TPU; interpret mode
executes grid steps sequentially in Python, so it is a correctness path,
not a CPU fast path (use core.gossip.mix_rows for that).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM = 8              # output panel rows (32-bit sublanes); DMAs in flight
BD = 512            # row-panel width in payload elements (4 x 128 lanes)
SMEM_ENTRIES = 1 << 15   # (row, weight) entries per call: 2 x 128 KiB SMEM


def row_view(x: jnp.ndarray) -> jnp.ndarray:
    """(m, w) -> (m, 1, w): a view whose rows are leading-axis slabs, so a
    kernel may DMA one row of it (module docstring)."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def _pack_words(U: jnp.ndarray) -> jnp.ndarray:
    """(m, d) bf16 -> (m, d/2) uint32: columns 2c and 2c+1 share word c
    (low and high half)."""
    m, d = U.shape
    return jax.lax.bitcast_convert_type(U.reshape(m, d // 2, 2), jnp.uint32)


def _unpack_words(Wd: jnp.ndarray) -> jnp.ndarray:
    m, dw = Wd.shape
    return jax.lax.bitcast_convert_type(Wd, jnp.bfloat16).reshape(m, 2 * dw)


def _halves(words):
    """uint32 words -> (low, high) bf16 halves, each exact in f32."""
    lo = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return lo, hi


def _bf16_bits(x):
    """f32 -> its round-to-nearest bf16 value's 16 bits, in the high half
    of a uint32."""
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def by_row_chunks(call, idx, w, block_m: int):
    """call(idx_chunk, w_chunk) over row chunks of the (mp, k) neighbor
    table, each within one call's SMEM budget (`SMEM_ENTRIES`); the
    outputs are stacked along rows."""
    k = idx.shape[1]
    chunk = max(block_m, SMEM_ENTRIES // k // block_m * block_m)
    outs = [call(idx[s:s + chunk], w[s:s + chunk])
            for s in range(0, idx.shape[0], chunk)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def _gather_kernel(idx_ref, w_ref, u_ref, out_ref, rows_ref, acc_ref,
                   sems, *, k: int, packed: bool):
    # idx_ref, w_ref: (mp*k,) scalar-prefetch (SMEM), row-major (i, j).
    # u_ref: the WHOLE (m, 1, dw) word buffer in HBM/ANY — the kernel
    # gathers the panel's block_m neighbor rows itself, all copies in
    # flight before the first wait.
    i = pl.program_id(0)
    dt = pl.program_id(1)
    j = pl.program_id(2)
    bm, _, bw = rows_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def copy(r):
        return pltpu.make_async_copy(
            u_ref.at[idx_ref[(i * bm + r) * k + j], :, pl.ds(dt * bw, bw)],
            rows_ref.at[r], sems.at[r])

    for r in range(bm):
        copy(r).start()
    for r in range(bm):
        copy(r).wait()

    wcol = jnp.stack([w_ref[(i * bm + r) * k + j] for r in range(bm)])
    rows = rows_ref[...].reshape(bm, bw)
    if packed:
        lo, hi = _halves(rows)
        acc_ref[0] += wcol[:, None] * lo
        acc_ref[1] += wcol[:, None] * hi
    else:
        acc_ref[0] += wcol[:, None] * rows.astype(jnp.float32)

    @pl.when(j == k - 1)
    def _flush():
        if packed:
            out_ref[...] = ((_bf16_bits(acc_ref[0]) >> 16)
                            | (_bf16_bits(acc_ref[1])
                               & jnp.uint32(0xFFFF0000)))
        else:
            out_ref[...] = acc_ref[0].astype(out_ref.dtype)


def _gather_call(idx, w, Uw, block_m, block_w, packed, interpret):
    """One pallas_call over mp = len(idx) rows (a multiple of block_m)."""
    mp, k = idx.shape
    dw = Uw.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # idx, w ride in SMEM
        grid=(mp // block_m, dw // block_w, k),  # k innermost: accumulate
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # U whole, gathered by DMA
        ],
        out_specs=pl.BlockSpec((block_m, block_w),
                               lambda i, dt, j, idx_ref, w_ref: (i, dt)),
        scratch_shapes=[pltpu.VMEM((block_m, 1, block_w), Uw.dtype),
                        pltpu.VMEM((2 if packed else 1, block_m, block_w),
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA((block_m,))],
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, k=k, packed=packed),
        grid_spec=grid_spec,
        name="gossip_gather",
        out_shape=jax.ShapeDtypeStruct((mp, dw), Uw.dtype),
        interpret=interpret,
    )(idx.reshape(-1).astype(jnp.int32), w.reshape(-1).astype(jnp.float32),
      Uw)


def gossip_gather_pallas(idx: jnp.ndarray, w: jnp.ndarray, U: jnp.ndarray,
                         block_d: int = BD, block_m: int | None = None,
                         interpret: bool = False):
    """out[i] = sum_j w[i,j] * U[idx[i,j]].

    idx: (m, k) int32 in-neighbor ids; w: (m, k) weights (cast to f32);
    U: (m, d) payload, float32 or bfloat16 (returned unchanged).  U is
    gathered row by row from HBM (d is zero-padded to the block_d panel
    only when misaligned).  The small (m, k) neighbor table is padded —
    with (row 0, weight 0) entries — when m is not a multiple of block_m.
    """
    m, k = idx.shape
    mu, d = U.shape
    assert mu == m, (idx.shape, U.shape)
    if U.dtype not in (jnp.float32, jnp.bfloat16):
        raise ValueError(f"gossip_gather_pallas: payload dtype {U.dtype} "
                         f"(float32 or bfloat16)")
    block_m = BM if block_m is None else block_m
    mp = -(-m // block_m) * block_m
    dp = max(-(-d // block_d) * block_d, block_d)
    if mp != m:
        idx = jnp.concatenate(
            [idx, jnp.zeros((mp - m, k), idx.dtype)], axis=0)
        w = jnp.concatenate([w, jnp.zeros((mp - m, k), w.dtype)], axis=0)
    packed = U.dtype == jnp.bfloat16
    Up = U if dp == d else jnp.zeros((m, dp), U.dtype).at[:, :d].set(U)
    Uw = row_view(_pack_words(Up) if packed else Up)
    block_w = block_d // 2 if packed else block_d

    out = by_row_chunks(
        lambda i, w: _gather_call(i, w, Uw, block_m, block_w, packed,
                                  interpret), idx, w, block_m)
    if packed:
        out = _unpack_words(out)
    return out[:m, :d]
