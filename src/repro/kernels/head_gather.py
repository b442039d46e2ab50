"""Pallas TPU kernel: fused per-user classifier gather + head matmul.

The serving-side sibling of `gossip_gather`/`gossip_scatter` (docs/serve.md):
a batch of requests mixes many users, the shared trunk has already produced
features H once, and each request needs ITS user's personal classifier

    out[r, :] = H[r, :] @ W[uid[r], :, :] + b[uid[r], :]     W: (m, d, n)

without materializing the (B, d, n) gathered weight tensor the naive
`jnp.take` path allocates.  Layout mirrors gossip_gather:

- the (B,) request->user table rides in as a scalar-prefetch operand
  (SMEM); the stacked classifier block W and bias block b stay whole in
  HBM (`pl.ANY`);
- the grid is (B/block_b, n/block_n); each step issues `block_b` slab
  DMAs — one (d, block_n) weight panel plus one (block_n,) bias row per
  request in the output panel — and keeps ALL of them in flight before
  the first wait;
- the per-request vector-matmul accumulates in f32 regardless of the
  trunk dtype (bf16 features with an f32 head is the production mix), so
  the output is always f32 — the same contract as the jnp oracle.

`interpret=True` runs the same kernel body (including the DMAs) on CPU —
how the kernel is validated without a TPU; interpret mode executes
grid steps sequentially in Python, so it is a correctness path, not a CPU
fast path (the serve engine's auto dispatch uses the oracle off-TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gossip_gather import row_view

BB = 8              # requests per output panel; slab DMAs in flight
BN = 128            # output-class panel width (one lane tile)


def _head_kernel(uid_ref, w_ref, b_ref, h_ref, out_ref, wscr, bscr,
                 wsems, bsems):
    # uid_ref: (Bp,) scalar-prefetch (SMEM).  w_ref: the WHOLE (m, d, n)
    # classifier block in HBM/ANY; b_ref: the WHOLE (m, 1, n) bias row view
    # — the kernel gathers each request's slab itself, every copy started
    # before the first wait.
    i = pl.program_id(0)
    nt = pl.program_id(1)
    bb, bn = out_ref.shape

    def wcopy(r):
        return pltpu.make_async_copy(
            w_ref.at[uid_ref[i * bb + r], :, pl.ds(nt * bn, bn)],
            wscr.at[r], wsems.at[r])

    def bcopy(r):
        return pltpu.make_async_copy(
            b_ref.at[uid_ref[i * bb + r], :, pl.ds(nt * bn, bn)],
            bscr.at[r], bsems.at[r])

    for r in range(bb):
        wcopy(r).start()
        bcopy(r).start()
    for r in range(bb):
        wcopy(r).wait()
        bcopy(r).wait()

    h = h_ref[...].astype(jnp.float32)                       # (bb, d)
    acc = jnp.concatenate([
        jnp.dot(h[r:r + 1], wscr[r].astype(jnp.float32),
                preferred_element_type=jnp.float32)
        for r in range(bb)], axis=0)                         # (bb, bn)
    out_ref[...] = acc + bscr[...].reshape(bb, bn).astype(jnp.float32)


def head_gather_matmul_pallas(uid: jnp.ndarray, H: jnp.ndarray,
                              W: jnp.ndarray, b: jnp.ndarray,
                              block_b: int | None = None,
                              block_n: int = BN,
                              interpret: bool = False) -> jnp.ndarray:
    """out[r] = H[r] @ W[uid[r]] + b[uid[r]], f32.

    uid: (B,) int32 request->user ids; H: (B, d) trunk features (any float
    dtype); W: (m, d, n) stacked personal classifiers; b: (m, n) stacked
    biases.  W and b stay in HBM and each request's (d, block_n) slab is
    gathered by DMA.  Host-side padding: uid/H to the block_b panel (user 0,
    zero rows — sliced off); n to the block_n lane panel (zero classes:
    Mosaic slices lanes only in whole 128-lane tiles, so a head narrower
    than that is padded, and copied, on every call); d to the f32
    sublane tile when misaligned (zero features contribute nothing to the
    dot).  b reaches the kernel as an (m, 1, n) row view (see
    gossip_gather: a single-row DMA needs the row on a leading axis).
    """
    B, d = H.shape
    m, dw, n = W.shape
    assert dw == d, (H.shape, W.shape)
    assert b.shape == (m, n), (b.shape, W.shape)
    block_b = BB if block_b is None else block_b
    Bp = -(-B // block_b) * block_b
    np_ = max(-(-n // block_n) * block_n, block_n)
    dp = -(-d // 8) * 8
    if Bp != B:
        uid = jnp.concatenate(
            [uid, jnp.zeros((Bp - B,), uid.dtype)])
        H = jnp.concatenate([H, jnp.zeros((Bp - B, d), H.dtype)], axis=0)
    if dp != d:
        H = jnp.concatenate([H, jnp.zeros((Bp, dp - d), H.dtype)], axis=1)
        W = jnp.concatenate([W, jnp.zeros((m, dp - d, n), W.dtype)],
                            axis=1)
    if np_ != n:
        W = jnp.concatenate([W, jnp.zeros((m, dp, np_ - n), W.dtype)],
                            axis=2)
        b = jnp.concatenate([b, jnp.zeros((m, np_ - n), b.dtype)], axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                    # uid rides in SMEM
        grid=(Bp // block_b, np_ // block_n),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),    # W whole, slab DMAs
            pl.BlockSpec(memory_space=pl.ANY),    # b whole, row DMAs
            pl.BlockSpec((block_b, dp),
                         lambda i, nt, uid_ref: (i, 0)),      # H panel
        ],
        out_specs=pl.BlockSpec((block_b, block_n),
                               lambda i, nt, uid_ref: (i, nt)),
        scratch_shapes=[pltpu.VMEM((block_b, dp, block_n), W.dtype),
                        pltpu.VMEM((block_b, 1, block_n), b.dtype),
                        pltpu.SemaphoreType.DMA((block_b,)),
                        pltpu.SemaphoreType.DMA((block_b,))],
    )
    out = pl.pallas_call(
        _head_kernel,
        grid_spec=grid_spec,
        name="head_gather",
        out_shape=jax.ShapeDtypeStruct((Bp, np_), jnp.float32),
        interpret=interpret,
    )(uid.astype(jnp.int32), W, row_view(b), H)
    return out[:B, :n]
