"""Plain reference of the DFedPGP round, and the readings it is compared on.

One round, for each client i with shared part u_i, personal part v_i and
push-sum weight mu_i (Algorithm 1 of arXiv:2405.17876, as the program runs
it):

1. v-steps: k_v SGD steps on v_i, the shared part pinned at z = u_i / mu_i;
2. u-steps: k_u SGD steps on the biased u_i, each gradient taken at
   z = u_i / mu_i with the updated v_i;
3. push-sum mix: u' = P u and mu' = P mu over the round's pull table P.

SGD: g += weight_decay * p; m = momentum * m + g; p -= lr * decay**t * m.

`readings` follows the first `ROUNDS` rounds and returns what the check
compares: each round's mean u and v loss, the norm of each leaf's momentum
after round 1, the leaves whose optimizer took one step in round 1 (their
momentum is the first gradient as the optimizer took it), and the norm of
each leaf's change over the rounds.  `fault` plants one of the faults the
check must catch, in this reference put in the program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import HIGHEST, is_personal, path_str

ROUNDS = 3
FAULTS = ("none", "half_batch", "no_mix")


def flat_dict(tree) -> dict:
    return {path_str(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def nest(d: dict) -> dict:
    out: dict = {}
    for path, x in d.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = x
    return out


def _sgd(p, m, g, lr, hp):
    g = g + hp["weight_decay"] * p
    m = hp["momentum"] * m + g
    return p - lr * m, m


def client_round(u, v, mu, mom_u, mom_v, bv, bu, lr, *, loss, hp):
    """One client's local phase; u, v are {path: leaf} dicts."""
    full = lambda a, b: nest({**a, **b})
    z0 = jax.tree.map(lambda a: a / mu, u)
    loss_v = []
    for k in range(bv["_k"]):
        bk = jax.tree.map(lambda a: a[k], bv["b"])
        lv, g = jax.value_and_grad(lambda vv: loss(full(z0, vv), bk))(v)
        upd = {n: _sgd(v[n], mom_v[n], g[n], lr, hp) for n in v}
        v = {n: upd[n][0] for n in v}
        mom_v = {n: upd[n][1] for n in v}
        loss_v.append(lv)
    loss_u = []
    for k in range(bu["_k"]):
        bk = jax.tree.map(lambda a: a[k], bu["b"])
        z = jax.tree.map(lambda a: a / mu, u)
        lu, g = jax.value_and_grad(lambda zz: loss(full(zz, v), bk))(z)
        upd = {n: _sgd(u[n], mom_u[n], g[n], lr, hp) for n in u}
        u = {n: upd[n][0] for n in u}
        mom_u = {n: upd[n][1] for n in u}
        loss_u.append(lu)
    mean = lambda xs: jnp.mean(jnp.stack(xs)) if xs else jnp.zeros(())
    return u, v, mom_u, mom_v, mean(loss_v), mean(loss_u)


def _mix(P: np.ndarray, chunks: list, name: str, part: str, size: int):
    """Row-chunked u' = P u of one leaf, each chunk on its own device."""
    out = []
    for i, ci in enumerate(chunks):
        rows = slice(i * size, (i + 1) * size)
        dev = ci[part][name].devices()
        acc = None
        for j, cj in enumerate(chunks):
            Pij = P[rows, j * size:(j + 1) * size]
            if not Pij.any():
                continue
            x = jax.device_put(cj[part][name], next(iter(dev)))
            term = jnp.einsum("ij,j...->i...", jnp.asarray(Pij, jnp.float32),
                              x, precision=HIGHEST)
            acc = term if acc is None else acc + term
        out.append(acc)
    return out


def readings(*, init_stacked, loss, hp: dict, m: int, batches, topology,
             devices, chunk: int, fault: str = "none", rounds: int = ROUNDS):
    """The reference's readings over the first `rounds` rounds.

    init_stacked(lo, hi) -> the initial weights of clients lo..hi-1, as a
    tree stacked on a leading client axis; batches(r) -> round r's
    {"v", "u"} trees with leaves (m, K, B, ...) on the host or device;
    topology(r) -> round r's (m, m) pull matrix.  Clients run `chunk` at a
    time (vmapped), chunk c on devices[c % len(devices)].
    """
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}; known: {FAULTS}")
    if m % chunk:
        raise ValueError(f"{m} clients do not split into chunks of {chunk}")
    n_chunks = m // chunk
    step = jax.jit(jax.vmap(
        lambda u, v, mu, mu_m, mv_m, bv, bu, lr: client_round(
            u, v, mu, mu_m, mv_m, {"_k": hp["k_v"], "b": bv},
            {"_k": hp["k_u"], "b": bu}, lr, loss=loss, hp=hp),
        in_axes=(0, 0, 0, 0, 0, 0, 0, None)), donate_argnums=(0, 1, 3, 4))

    chunks = []
    for c in range(n_chunks):
        dev = devices[c % len(devices)]
        w = jax.device_put(init_stacked(c * chunk, (c + 1) * chunk), dev)
        fd = flat_dict(w)
        u = {n: x for n, x in fd.items() if not is_personal(n)}
        v = {n: x for n, x in fd.items() if is_personal(n)}
        chunks.append({"u": u, "v": v,
                       "mu_m": jax.tree.map(jnp.zeros_like, u),
                       "mv_m": jax.tree.map(jnp.zeros_like, v)})
        del w, fd
    mu = np.ones((m,), np.float32)
    losses = {"loss_u": [], "loss_v": []}
    grad = {}
    for r in range(rounds):
        lr = jnp.float32(hp["lr"] * hp["lr_decay"] ** r)
        b = batches(r)
        if fault == "half_batch":
            b = jax.tree.map(lambda a: a[:, :, :a.shape[2] // 2], b)
        lu, lv = [], []
        for c, ch in enumerate(chunks):
            dev = next(iter(ch["u"][next(iter(ch["u"]))].devices()))
            sl = lambda a: jax.device_put(a[c * chunk:(c + 1) * chunk], dev)
            out = step(ch["u"], ch["v"],
                       jax.device_put(mu[c * chunk:(c + 1) * chunk], dev),
                       ch["mu_m"], ch["mv_m"], jax.tree.map(sl, b["v"]),
                       jax.tree.map(sl, b["u"]), lr)
            ch["u"], ch["v"], ch["mu_m"], ch["mv_m"] = out[:4]
            lv.append(np.asarray(out[4], np.float64))
            lu.append(np.asarray(out[5], np.float64))
        losses["loss_u"].append(float(np.mean(np.concatenate(lu))))
        losses["loss_v"].append(float(np.mean(np.concatenate(lv))))
        if r == 0:
            for part in ("mu_m", "mv_m"):
                for n in chunks[0][part]:
                    grad[n] = _norm([ch[part][n] for ch in chunks])
        if fault != "no_mix":
            P = topology(r)
            for n in list(chunks[0]["u"]):
                new = _mix(P, chunks, n, "u", chunk)
                for ch, x in zip(chunks, new):
                    ch["u"][n] = x
            mu = (P @ mu.astype(np.float64)).astype(np.float32)
    for ch in chunks:
        del ch["mu_m"], ch["mv_m"]
    change = {}
    for c, ch in enumerate(chunks):
        w0 = flat_dict(jax.device_put(
            init_stacked(c * chunk, (c + 1) * chunk),
            next(iter(ch["u"][next(iter(ch["u"]))].devices()))))
        for n, x0 in w0.items():
            x = ch["v" if is_personal(n) else "u"][n]
            change[n] = change.get(n, 0.0) + float(
                jnp.sum(jnp.square(x - x0)))
        del w0
    change = {n: float(np.sqrt(s)) for n, s in change.items()}
    change["mu"] = float(np.linalg.norm(mu.astype(np.float64) - 1.0))
    return {**losses, "grad": grad, "change": change,
            "first_step_leaves": first_step_leaves(grad, hp)}


def first_step_leaves(names, hp: dict) -> list:
    """The leaves whose optimizer takes one step in a round: their
    momentum after round 1 is the first gradient as the optimizer took it
    (plus weight decay)."""
    return sorted(n for n in names
                  if hp["k_v" if is_personal(n) else "k_u"] == 1)


def _norm(parts) -> float:
    return float(np.sqrt(sum(float(jnp.sum(jnp.square(p))) for p in parts)))
