"""Dispatching wrappers for the Pallas kernels.

Each op picks the execution path:
  - TPU: the Pallas kernel (compiled);
  - CPU/tests: either the pure-jnp oracle (fast) or the kernel in
    interpret mode (`interpret=True` runs the kernel body in Python —
    how the kernels are validated without a TPU).

Loud-knob rule (docs/ci.md, tests/test_kernels.py): every knob that only
parameterizes the Pallas kernel — DMA panel heights, block widths, the
attention/recurrence tile sizes — raises when the call dispatches to the
jnp oracle instead of being silently ignored.  A benchmark sweeping
block sizes on a CPU box would otherwise time the SAME oracle program at
every setting and report the sweep as meaningful.
"""
from __future__ import annotations

import functools

import jax

from . import ref
from .flash_attention import flash_attention_pallas
from .gossip_gather import gossip_gather_pallas
from .gossip_scatter import gossip_scatter_pallas
from .head_gather import head_gather_matmul_pallas
from .pushsum_mix import pushsum_mix_pallas
from .rglru import rglru_pallas
from .topk_gather import topk_gather_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _reject_ref_knobs(**knobs):
    """Raise if any pallas-only knob is set on a jnp-oracle dispatch."""
    stray = [k for k, v in knobs.items() if v is not None]
    if stray:
        raise ValueError(
            f"{', '.join(stray)} tune(s) the pallas kernel; this call "
            f"dispatched to the jnp oracle (force='pallas' to run the "
            f"kernel)")


def _set(**knobs):
    """kwargs dict of only the explicitly-set knobs (None = kernel
    default)."""
    return {k: v for k, v in knobs.items() if v is not None}


@functools.partial(jax.jit, static_argnames=("force", "block_d"))
def pushsum_mix(P, U, force: str = "auto", block_d: int | None = None):
    """U' = P @ U over the stacked client axis. force: auto|pallas|ref.
    block_d tunes the kernel's U-panel width (pallas only)."""
    if force == "pallas" or (force == "auto" and _on_tpu()):
        return pushsum_mix_pallas(P, U, interpret=not _on_tpu(),
                                  **_set(block_d=block_d))
    _reject_ref_knobs(block_d=block_d)
    return ref.pushsum_mix_ref(P, U)


@functools.partial(jax.jit, static_argnames=("force", "block_m", "block_d"))
def gossip_gather(idx, w, U, force: str = "auto",
                  block_m: int | None = None, block_d: int | None = None):
    """out[i] = sum_j w[i,j] * U[idx[i,j]] — the sparse gossip transmission
    over the flat client buffer. force: auto|pallas|ref.  On CPU, `auto`
    uses the jnp oracle; `pallas` runs the kernel in interpret mode (slow,
    validation only).  block_m/block_d tune the kernel's DMA panel height/
    width and are only meaningful on the pallas path — a ref dispatch with
    either set raises instead of silently ignoring the knob."""
    if force == "pallas" or (force == "auto" and _on_tpu()):
        return gossip_gather_pallas(idx, w, U, interpret=not _on_tpu(),
                                    block_m=block_m,
                                    **_set(block_d=block_d))
    _reject_ref_knobs(block_m=block_m, block_d=block_d)
    return ref.gossip_gather_ref(idx, w, U)


@functools.partial(jax.jit, static_argnames=("accumulate", "force",
                                             "block_m", "block_d"))
def gossip_scatter(rows, X, U, accumulate: bool = False,
                   force: str = "auto", block_m: int | None = None,
                   block_d: int | None = None):
    """Write the compact (n_active, d) working set back into the resident
    (m, d) buffer: U.at[rows].set(X), or += X accumulated in f32.  The
    pallas path aliases U in place — dormant rows are never touched or
    copied (docs/scale.md). force: auto|pallas|ref."""
    if force == "pallas" or (force == "auto" and _on_tpu()):
        return gossip_scatter_pallas(rows, X, U, accumulate=accumulate,
                                     interpret=not _on_tpu(),
                                     block_m=block_m,
                                     **_set(block_d=block_d))
    _reject_ref_knobs(block_m=block_m, block_d=block_d)
    return ref.gossip_scatter_ref(rows, X, U, accumulate)


@functools.partial(jax.jit, static_argnames=("d", "force", "block_m",
                                             "block_d"))
def topk_gather(idx, w, values, cols, d: int, force: str = "auto",
                block_m: int | None = None, block_d: int | None = None):
    """Compressed gossip mix: out[i] = sum_j w[i,j] * decode(payload[
    idx[i,j]]) for sparse (column, value) payloads, WITHOUT materializing
    dense decoded rows on the pallas path. force: auto|pallas|ref."""
    if force == "pallas" or (force == "auto" and _on_tpu()):
        return topk_gather_pallas(idx, w, values, cols, d,
                                  interpret=not _on_tpu(), block_m=block_m,
                                  **_set(block_d=block_d))
    _reject_ref_knobs(block_m=block_m, block_d=block_d)
    return ref.topk_gather_ref(idx, w, values, cols, d)


@functools.partial(jax.jit, static_argnames=("force", "block_b", "block_n"))
def head_gather_matmul(uid, H, W, b, force: str = "auto",
                       block_b: int | None = None,
                       block_n: int | None = None):
    """out[r] = H[r] @ W[uid[r]] + b[uid[r]] — the fused per-user
    classifier head of the serve path (docs/serve.md): trunk features H
    computed once for a mixed-user batch, per-request (d, n) classifier
    slabs gathered from the stacked personal block.  Always returns f32
    (the accumulate dtype).  force: auto|pallas|ref.  block_b/block_n tune
    the kernel's request-panel height / class-tile width and are only
    meaningful on the pallas path — a ref dispatch with either set raises
    instead of silently ignoring the knob."""
    if force == "pallas" or (force == "auto" and _on_tpu()):
        return head_gather_matmul_pallas(uid, H, W, b,
                                         interpret=not _on_tpu(),
                                         block_b=block_b,
                                         **_set(block_n=block_n))
    _reject_ref_knobs(block_b=block_b, block_n=block_n)
    return ref.head_gather_matmul_ref(uid, H, W, b)


def flash_attention(q, k, v, *, window: int = 0, scale=None,
                    force: str = "auto", bq: int | None = None,
                    bk: int | None = None):
    """Blocked causal attention. force: auto|pallas|ref.  bq/bk tune the
    kernel's query/key tile sizes (pallas only)."""
    if force == "pallas" or (force == "auto" and _on_tpu()):
        return flash_attention_pallas(q, k, v, window=window, scale=scale,
                                      interpret=not _on_tpu(),
                                      **_set(bq=bq, bk=bk))
    _reject_ref_knobs(bq=bq, bk=bk)
    return ref.flash_attention_ref(q, k, v, window=window, scale=scale)


def rglru(a, b, force: str = "auto", bs: int | None = None,
          bw: int | None = None):
    """Linear recurrence h_t = a_t h_{t-1} + b_t. force: auto|pallas|ref.
    bs/bw tune the kernel's sequence/width tile sizes (pallas only)."""
    if force == "pallas" or (force == "auto" and _on_tpu()):
        return rglru_pallas(a, b, interpret=not _on_tpu(),
                            **_set(bs=bs, bw=bw))
    _reject_ref_knobs(bs=bs, bw=bw)
    return ref.rglru_ref(a, b)
