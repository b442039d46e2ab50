"""The comparison that decides `correct`.

A training cell's readings (from the program, set up for the window, and
from the plain reference, after it) are compared on three numbers:

- `loss_gap`: the largest relative gap of a round's mean u or v loss over
  the first rounds;
- `grad_gap`: the worst leaf's gap between the norms of the first
  gradient as the optimizer took it: the momentum after round 1 of the
  leaves whose optimizer took one step in that round (both parts of a
  cell with one u- and one v-step; a part that takes several steps a
  round leaves no first gradient to read);
- `change_gap`: the worst leaf's gap between the norms of the change of the
  weights over the first rounds;
- `move_gap`: the same gap of the change measured against the leaf's own
  reference norm, so that a leaf which one side leaves unmoved, or moves
  double, reads 1 however small it is beside the others.

A leaf's gap is |norm_program - norm_reference| over the reference's norm
of that leaf or of the median leaf, whichever is larger (for `move_gap`,
over the leaf's own norm, floored at a millionth of the median leaf's:
the smallest leaf that moves, a key's bias, moves some 3e-5 of it).
A leaf whose reference gradient is under a thousandth of the median
leaf's moves by round-off alone (a key's bias under softmax), so it is
left out of the change.  A leaf that one side lacks makes the gap
infinite.
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "move_gap")
NOUGHT = 1e-3          # a leaf's gradient below this share of the median's
STILL = 1e-6           # a leaf's change below this share of the median's


def _leaf_gaps(prog: dict, ref: dict, skip=(), floor: float = 1.0) -> dict:
    names = [n for n in ref if n not in skip]
    model = [ref[n] for n in names if n != "mu"]
    med = statistics.median(model) if model else 0.0
    gaps = {}
    for n in set(names) | (set(prog) - set(skip)):
        if n not in prog or n not in ref:
            gaps[n] = math.inf
            continue
        den = max(ref[n], floor * med)
        gaps[n] = abs(prog[n] - ref[n]) / den if den > 0 else (
            0.0 if prog[n] == ref[n] else math.inf)
    return gaps


def nought_leaves(ref: dict) -> list:
    grad = ref["grad"]
    med = statistics.median(grad.values())
    return sorted(n for n, g in grad.items() if g < NOUGHT * med)


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared, with the worst leaf of each leaf gap."""
    loss, per_round = 0.0, {}
    for key in ("loss_u", "loss_v"):
        if len(prog[key]) != len(ref[key]):
            loss = math.inf
            continue
        for i, (p, r) in enumerate(zip(prog[key], ref[key])):
            g = abs(p - r) / max(abs(r), 1e-30)
            g = g if math.isfinite(g) else math.inf
            per_round[f"{key}.{i + 1}"] = g
            loss = max(loss, g)
    first = set(ref["first_step_leaves"])
    grad = _leaf_gaps({n: v for n, v in prog["grad"].items() if n in first},
                      {n: v for n, v in ref["grad"].items() if n in first})
    change = _leaf_gaps(prog["change"], ref["change"],
                        skip=nought_leaves(ref))
    move = _leaf_gaps(prog["change"], ref["change"],
                      skip=nought_leaves(ref), floor=STILL)

    def worst(d):
        n = max(d, key=lambda k: (d[k], k))
        return d[n], n

    g, gn = worst(grad)
    c, cn = worst(change)
    v, vn = worst(move)
    return {"loss_gap": loss, "grad_gap": g, "change_gap": c, "move_gap": v,
            "worst": {"grad_gap": gn, "change_gap": cn, "move_gap": vn},
            "left_out_of_change": nought_leaves(ref),
            "detail": {"loss": per_round, "grad": grad, "change": change,
                       "move": move}}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) against the cell's limits."""
    checks = {}
    ok = True
    for n in NUMBERS:
        v, lim = numbers[n], limits[n]["limit"]
        checks[n] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks
