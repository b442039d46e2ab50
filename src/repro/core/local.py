"""Shared local-SGD machinery used by DFedPGP and every baseline.

All updates run per client and are vmapped by the round engine; local steps
are a lax.scan over the leading step axis of the batch pytree
(leaves: (K, B, ...)).  `step_gate` (K,) in {0,1} implements computation
heterogeneity (paper Table 3): gated-off steps apply a zero update.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.optim import SGD, SGDState
from . import partition


def masked_grads(grads, mask, keep_shared: bool):
    """Zero the gradient leaves of the other part.

    Inactive leaves become SCALAR zeros (not zeros_like): SGD broadcasts
    them, the parameter is unchanged, and the momentum entry for the
    inactive part stays a scalar — so each phase's optimizer state only
    materialises momentum for the part it actually trains.  At Regime-B
    scale (16 personalized 16B-param clients) this saves a full parameter
    copy per phase."""
    return jax.tree.map(
        lambda g, m: g if (m == keep_shared) else jnp.zeros((), g.dtype),
        grads, mask)


def flat_view_loss(loss_fn: Callable, layout, personal_i):
    """Wrap a tree-form loss into one over a client's flat shared row.

    The resident-buffer path (core/dfedpgp.py round_fn_flat) keeps the
    shared part in the (m, d_flat) buffer across rounds; local SGD differs
    through this wrapper, which unravels the row into leaf views ONLY at
    the loss_fn boundary — under jit the slices/reshapes are views, and
    the gradient comes back as one flat row, the leaf gradients written
    into it by one concatenate (FlatLayout.unravel_row's backward)."""
    def wrapped(flat_row, batch):
        shared = layout.unravel_row(flat_row)
        return loss_fn(partition.merge(shared, personal_i), batch)

    return wrapped


def sgd_steps(loss_fn: Callable, opt: SGD, params, opt_state: SGDState,
              batches, lr_scale, step_gate=None, grad_filter=None,
              extra: Any = None):
    """Run K SGD steps. batches leaves: (K, B, ...).

    grad_filter: optional fn(grads, params) -> grads (e.g. part masking,
    proximal terms).  extra is closed over by loss_fn via (params, batch,
    extra) if provided.
    """
    K = jax.tree.leaves(batches)[0].shape[0]

    def step(carry, xs):
        p, s = carry
        batch, k = xs
        if extra is None:
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
        else:
            loss, g = jax.value_and_grad(loss_fn)(p, batch, extra)
        if grad_filter is not None:
            g = grad_filter(g, p)
        p2, s2 = opt.update(g, s, p, lr_scale)
        if step_gate is not None:
            gate = step_gate[k]  # gate the whole update so off-steps are no-ops
            sel = lambda new, old: jax.tree.map(
                lambda a, b: (gate * a + (1.0 - gate) * b).astype(a.dtype),
                new, old)
            p2, s2 = sel(p2, p), SGDState(sel(s2.momentum, s.momentum))
        return (p2, s2), loss

    (params, opt_state), losses = jax.lax.scan(
        step, (params, opt_state), (batches, jnp.arange(K)))
    return params, opt_state, jnp.mean(losses)
