"""The five program-invariant detectors (docs/analysis.md).

Each detector proves one property of the COMPILED artifact, before a
single training round runs:

- `check_densify` — walks the closed jaxpr (including scan/cond/pjit
  sub-jaxprs) and flags any intermediate whose shape carries the client
  axis twice: an (m, m)-scale product is exactly the dense mix the
  O(m*k) engine exists to avoid.  Allowlisted by `jax.named_scope`
  label substrings.
- `check_donation` — confirms every leaf of the donated arg actually
  aliases an output in the lowered StableHLO (`tf.aliasing_output`
  markers).  XLA drops unusable donations with only a warning; here a
  dropped donation is a violation, because the resident buffer
  silently doubling its footprint is the bug PR 3 existed to prevent.
- `check_retrace` — a counting-compile harness: the python body of a
  jitted program must trace exactly once across N_ROUNDS rounds of
  fresh same-shape arguments (the PR 1 cached-accuracy bug, made a
  permanent gate).
- `check_host_sync` — compiles outside the guard, then runs the steady
  state rounds under `jax.transfer_guard("disallow")`: any implicit
  host transfer on the dispatch path (a numpy argument re-uploaded per
  call, a python scalar committed per round, a traced value pulled to
  host) raises.  The telemetry emit boundary stays whitelisted by
  construction — `jax.device_get` is an explicit transfer, which the
  guard permits.
- `check_topology_stochastic` / `check_schedules` — static verification
  that every SparseTopology leaving a registered `get_schedule` kind is
  row-stochastic in pull form and column-stochastic (to f32) after
  `to_push_sparse`, including over induced subgraphs — the mass-
  conservation precondition of the push-sum convergence argument.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np

from repro.core import topology as topo_mod

from .programs import N_ROUNDS, PROGRAMS, ProgramInstance


class Violation(NamedTuple):
    """One detector trip: which program, which detector, what happened."""
    program: str
    detector: str
    message: str


# ---------------------------------------------------------------------------
# 1. densification
# ---------------------------------------------------------------------------
def _iter_eqns(jaxpr: Any, prefix: str = ""):
    """(eqn, scope) over a jaxpr and its sub-jaxprs (scan bodies, cond
    branches, pjit calls...).  scope is the '/'-joined named_scope stack,
    with the enclosing eqn's scope prepended for nested jaxprs."""
    for eqn in jaxpr.eqns:
        ns = str(eqn.source_info.name_stack)
        scope = "/".join(p for p in (prefix, ns) if p)
        yield eqn, scope
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for sub in vals:
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _iter_eqns(sub.jaxpr, scope)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _iter_eqns(sub, scope)


def check_densify(inst: ProgramInstance) -> List[str]:
    """Flag intermediates whose shape contains the client axis twice."""
    if inst.m <= 1:
        return []      # every axis is "the client axis" at m = 1
    args = inst.args(0, None)
    with inst.ctx():
        closed = jax.make_jaxpr(inst.fn)(*args)
    out = []
    for eqn, scope in _iter_eqns(closed.jaxpr):
        if any(scope and allow in scope for allow in inst.allow_dense):
            continue
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if sum(1 for s in shape if s == inst.m) >= 2:
                out.append(
                    f"`{eqn.primitive.name}` materializes {tuple(shape)} "
                    f"(client axis m={inst.m} twice) at scope "
                    f"'{scope or '<top>'}'")
    return out


# ---------------------------------------------------------------------------
# 2. donation
# ---------------------------------------------------------------------------
def check_donation(inst: ProgramInstance) -> List[str]:
    """Every leaf of the donated args must alias an output in the
    lowered module — a dropped donation is only an XLA warning."""
    if not inst.donate:
        return []
    args = inst.args(0, None)
    with inst.ctx():
        lowered = jax.jit(inst.fn, donate_argnums=inst.donate,
                          **inst.jit_kwargs).lower(*args)
    text = lowered.as_text()
    got = text.count("tf.aliasing_output")
    want = sum(len(jax.tree.leaves(args[i])) for i in inst.donate)
    if got < want:
        return [f"donation dropped: only {got}/{want} donated leaves "
                f"alias an output in the lowered module (XLA would have "
                f"warned and silently doubled the buffer footprint)"]
    return []


# ---------------------------------------------------------------------------
# 3. retrace sentinel
# ---------------------------------------------------------------------------
def check_retrace(inst: ProgramInstance,
                  rounds: int = N_ROUNDS) -> List[str]:
    """The python body must trace exactly once across `rounds` rounds."""
    traces = 0

    def counting(*a, **kw):
        nonlocal traces
        traces += 1
        return inst.fn(*a, **kw)

    jitted = jax.jit(counting, donate_argnums=inst.donate,
                     **inst.jit_kwargs)
    carry = None
    with inst.ctx():
        for t in range(rounds):
            out = jitted(*inst.args(t, carry))
            carry = inst.carry_of(out)
    if traces != 1:
        return [f"retraced: {traces} traces across {rounds} same-shape "
                f"rounds (want 1) — a python-scalar closure or static "
                f"argument is flapping per round"]
    return []


# ---------------------------------------------------------------------------
# 4. host syncs
# ---------------------------------------------------------------------------
def check_host_sync(inst: ProgramInstance,
                    rounds: int = N_ROUNDS) -> List[str]:
    """Steady-state rounds under jax.transfer_guard('disallow')."""
    jitted = jax.jit(inst.fn, donate_argnums=inst.donate,
                     **inst.jit_kwargs)
    with inst.ctx():
        out = jitted(*inst.args(0, None))    # compile outside the guard
        carry = inst.carry_of(out)
        try:
            with jax.transfer_guard("disallow"):
                for t in range(1, rounds):
                    out = jitted(*inst.args(t, carry))
                    carry = inst.carry_of(out)
                    # the telemetry emit boundary: device_get is an
                    # EXPLICIT transfer, which the guard whitelists
                    jax.device_get(out[-1] if isinstance(out, tuple)
                                   else out)
        except Exception as e:  # noqa: BLE001 - guard raises jax errors
            return [f"implicit host transfer in the steady-state round: "
                    f"{type(e).__name__}: {str(e).splitlines()[0]}"]
    return []


# ---------------------------------------------------------------------------
# 5. stochasticity of every registered schedule kind
# ---------------------------------------------------------------------------
def _dense_np(P: topo_mod.SparseTopology) -> np.ndarray:
    """Host-side dense form of a small SparseTopology (analysis only)."""
    idx = np.asarray(P.idx)
    w = np.asarray(P.w, np.float64)
    n = idx.shape[0]
    D = np.zeros((n, n))
    np.add.at(D, (np.repeat(np.arange(n), idx.shape[1]),
                  idx.reshape(-1)), w.reshape(-1))
    return D


def check_topology_stochastic(P: topo_mod.SparseTopology, what: str,
                              atol: float = 1e-4) -> List[str]:
    """Pull rows sum to 1; to_push_sparse columns sum to 1 (f32)."""
    out = []
    rows = _dense_np(P).sum(1)
    if not np.allclose(rows, 1.0, atol=atol):
        out.append(f"{what}: pull form not row-stochastic — row sums in "
                   f"[{rows.min():.6f}, {rows.max():.6f}]")
        return out       # push re-weighting of a broken pull form is moot
    cols = _dense_np(topo_mod.to_push_sparse(P)).sum(0)
    if not np.allclose(cols, 1.0, atol=atol):
        out.append(f"{what}: push form not column-stochastic — column "
                   f"sums in [{cols.min():.6f}, {cols.max():.6f}] (mass "
                   f"is created or destroyed every fire)")
    return out


def _check_induced(P: topo_mod.SparseTopology, what: str,
                   atol: float = 1e-4) -> List[str]:
    """Induced subgraphs preserve the stochasticity contracts: 'row'
    keeps row sums at 1; 'col' of the push form keeps every surviving
    sender's column at 1 (fully-dormant senders drop to exactly 0)."""
    out = []
    m = P.idx.shape[0]
    active = jnp.asarray(np.arange(0, m, 2), jnp.int32)   # deterministic
    rows = _dense_np(topo_mod.induced_subgraph(P, active, "row")).sum(1)
    if not np.allclose(rows, 1.0, atol=atol):
        out.append(f"{what}: induced 'row' subgraph rows sum to "
                   f"[{rows.min():.6f}, {rows.max():.6f}], want 1")
    push = topo_mod.to_push_sparse(P)
    cols = _dense_np(topo_mod.induced_subgraph(push, active, "col")).sum(0)
    bad = ~(np.isclose(cols, 1.0, atol=atol) |
            np.isclose(cols, 0.0, atol=atol))
    if bad.any():
        out.append(f"{what}: induced 'col' push subgraph has sender "
                   f"columns summing to {cols[bad][:4].tolist()} — "
                   f"neither conserved (1) nor dormant (0)")
    return out


def check_schedules(m: int = 16, n: int = 3, seed: int = 5,
                    rounds: int = N_ROUNDS,
                    kinds: Optional[Tuple[str, ...]] = None,
                    ) -> Tuple[List[dict], List[Violation]]:
    """Run the stochasticity checks over every registered schedule kind."""
    rows, viols = [], []
    for kind in kinds or topo_mod.TopologySchedule.KINDS:
        base: List[str] = []
        induced: List[str] = []
        for t in range(rounds):
            P = topo_mod.get_schedule(kind, m, n, seed).at(t)
            base += check_topology_stochastic(P, f"{kind}@t={t}")
            induced += _check_induced(P, f"{kind}@t={t}")
        rows.append({"kind": kind,
                     "stochastic": "FAIL" if base else "ok",
                     "induced": "FAIL" if induced else "ok"})
        viols += [Violation(f"schedule:{kind}", "stochastic", msg)
                  for msg in base + induced]
    return rows, viols


# ---------------------------------------------------------------------------
# runners + report
# ---------------------------------------------------------------------------
DETECTORS: Dict[str, Callable[[ProgramInstance], List[str]]] = {
    "densify": check_densify,
    "donation": check_donation,
    "retrace": check_retrace,
    "hostsync": check_host_sync,
}


def run_program(inst: ProgramInstance) -> Tuple[dict, List[Violation]]:
    """All four program detectors over one instance -> (report row,
    violations)."""
    row: Dict[str, Any] = {"program": inst.name, "m": inst.m}
    viols: List[Violation] = []
    for name, check in DETECTORS.items():
        if name == "donation" and not inst.donate:
            row[name] = "n/a"
            continue
        msgs = check(inst)
        row[name] = "FAIL" if msgs else "ok"
        viols += [Violation(inst.name, name, msg) for msg in msgs]
    return row, viols


def run_all(names: Optional[Tuple[str, ...]] = None,
            ) -> Tuple[List[dict], List[dict], List[Violation]]:
    """The full pass: every registered program x every detector, plus the
    schedule stochasticity sweep.  -> (program rows, schedule rows,
    violations); pytest-facing — tests assert `not violations`."""
    rows, viols = [], []
    for name in names or tuple(PROGRAMS):
        row, v = run_program(PROGRAMS[name]())
        rows.append(row)
        viols += v
    srows, sviols = check_schedules()
    return rows, srows, viols + sviols


def render_report(rows: List[dict], srows: List[dict],
                  violations: List[Violation]) -> str:
    """The per-program report table (the obs report renderer)."""
    from repro.obs.report import table
    out = table(rows, ["program", "m"] + list(DETECTORS),
                "program invariants")
    out += table(srows, ["kind", "stochastic", "induced"],
                 "schedule stochasticity")
    if violations:
        out += "\n== violations ==\n"
        out += "\n".join(f"  [{v.program} / {v.detector}] {v.message}"
                         for v in violations) + "\n"
    return out
