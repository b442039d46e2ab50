"""What a driver of DFedPGP rounds needs besides its loop: the weights the
benchmark makes, the readings taken from the program's state, and the
reference run after the window."""
from __future__ import annotations

import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.reference import dfedpgp as ref_round
from bench.reference import is_personal, leaf_paths


def model_module(cfg: dict):
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


def stacked_init(cfg: dict, m: int):
    """key -> the m clients' initial weights, client i drawn from the i-th
    split of the key, stacked on a leading client axis (traceable)."""
    init = model_module(cfg).init
    return lambda key: jax.vmap(lambda k: init(k, cfg))(
        jax.random.split(key, m))


def make_init_stacked(cfg: dict, key, m: int):
    """init_stacked(lo, hi): clients lo..hi-1 of `stacked_init(cfg, m)(key)`,
    made in one jitted call."""
    init = model_module(cfg).init
    keys = jax.random.split(key, m)
    fn = jax.jit(jax.vmap(lambda k: init(k, cfg)))
    return lambda lo, hi: fn(keys[lo:hi])


def shared_layout(cfg: dict):
    """[(path, offset, shape)] of the shared leaves in the program's flat
    buffer order (the tree's leaf order, personal leaves skipped)."""
    struct = jax.eval_shape(lambda: model_module(cfg).init(
        jax.random.PRNGKey(0), cfg))
    out, off = [], 0
    for path, leaf in zip(leaf_paths(struct), jax.tree.leaves(struct)):
        if is_personal(path):
            continue
        out.append((path, off, leaf.shape))
        off += int(np.prod(leaf.shape))
    return out, off


def _sq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def flat_leaf_sq(flat, layout):
    """Sum of squares of each shared leaf, over all clients."""
    return {p: _sq(flat[:, o:o + int(np.prod(s))]) for p, o, s in layout}


def sqrt_dict(d: dict) -> dict:
    return {k: float(np.sqrt(float(v))) for k, v in d.items()}


class Readings:
    """The program's readings, taken from the live state between the first
    rounds (before the next round's donation reuses its buffers)."""

    def __init__(self, cfg: dict, m: int):
        self.layout, self.d_flat = shared_layout(cfg)
        layout = self.layout
        init_fn = stacked_init(cfg, m)

        def grad_sq(opt_u_flat, opt_v_tree):
            out = flat_leaf_sq(opt_u_flat, layout)
            out.update({p: _sq(x) for p, x in
                        ref_round.flat_dict(opt_v_tree).items()})
            return out

        def change_sq(flat, personal, mu, key):
            w0 = ref_round.flat_dict(init_fn(key))
            out = {p: _sq(flat[:, o:o + int(np.prod(s))]
                          - w0[p].reshape(w0[p].shape[0], -1))
                   for p, o, s in layout}
            out.update({p: _sq(x - w0[p]) for p, x in
                        ref_round.flat_dict(personal).items()})
            out["mu"] = _sq(mu - 1.0)
            return out

        self._grad = jax.jit(grad_sq)
        self._change = jax.jit(change_sq)
        self.losses = collections.defaultdict(list)
        self.grad: dict = {}
        self.change: dict = {}

    def after_round(self, r: int, state, metrics, weights_key):
        """Record round r (0-based) of the first ROUNDS rounds."""
        self.losses["loss_u"].append(float(metrics["loss_u"]))
        self.losses["loss_v"].append(float(metrics["loss_v"]))
        if r == 0:
            self.grad = sqrt_dict(jax.device_get(self._grad(
                state.opt_u.momentum, state.opt_v.momentum)))
        if r == ref_round.ROUNDS - 1:
            self.change = sqrt_dict(jax.device_get(self._change(
                state.flat, state.personal, state.mu, weights_key)))

    def result(self) -> dict:
        return {**self.losses, "grad": self.grad, "change": self.change}


def reference_readings(cfg: dict, hp: dict, m: int, chunk: int,
                       init_stacked, batches, topology, devices,
                       precision: str = "reference", fault: str = "none"):
    q = reference.PRECISIONS[precision]
    loss_mod = model_module(cfg)
    return ref_round.readings(
        init_stacked=init_stacked,
        loss=lambda p, b: loss_mod.loss(p, b, cfg, q), hp=hp, m=m,
        batches=batches, topology=topology, devices=devices, chunk=chunk,
        fault=fault)
