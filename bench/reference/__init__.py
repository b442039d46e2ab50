"""Plain references of the benchmark's configurations.

Straightforward `jax.numpy` in float32 with every matrix product at
`Precision.HIGHEST`, no kernels and no batching tricks.  They import
nothing of the program: the correctness check runs them on weights and
inputs that the benchmark makes itself from the run's seed.

Each model module has `init(key, cfg)` and `loss(params, batch, cfg, q)`.
`q` rounds the operands of every matrix product: the identity for the
reference, `fp8` for the control (the precision one step below the
bfloat16 products the configurations run).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0      # largest finite float8_e4m3fn


def exact(x):
    return x


def fp8(x):
    """Per-tensor scaled float8_e4m3fn rounding of a product operand in the
    forward pass; the gradient passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(y - x)


PRECISIONS = {"reference": exact, "fp8": fp8}


def matmul(a, b, q):
    return jnp.matmul(q(a), q(b), precision=HIGHEST)


def einsum(spec, a, b, q):
    return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)


def softmax_xent(logits, labels):
    """Mean token cross-entropy in float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def is_personal(path: str) -> bool:
    """DFedPGP's split: the classifier head (and the norm before it) stays
    with its client; everything below it is shared."""
    return any(s in path for s in ("classifier", "lm_head", "final_norm"))


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def leaf_paths(tree) -> list:
    return [path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        tree)[0]]
