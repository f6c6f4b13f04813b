"""The comparison that decides ``correct`` for a training cell.

Both sides report, for the compared rounds: each round's mean
supervised loss f_s and mean cross-entity loss f_u; per leaf, the norm
of the supervised optimizer's momentum after round 1 (the gradients as
the optimizer got them); and, per leaf of the parameters and of the
teacher, the norm of their change over the compared rounds.  The
compared round is the first: from the second on, rounding differences
grow by several times a step on some seeds, so later rounds read the
seed's sensitivity and not the program (PERF.md, section 2).

Three numbers are compared, each against a limit of its own:

* ``loss``: the largest relative gap |p - r| / |r| of f_s and f_u over
  the rounds the reference followed;
* ``grad``: over the momentum leaves, the worst gap | |p| - |r| |, taken
  against the larger of the reference leaf's norm and the median leaf's;
* ``change``: the same over the change of every parameter and teacher
  leaf.  A leaf whose reference gradient is under a thousandth of the
  median leaf's moves by round-off alone and is left out.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3       # gradient share of the median leaf's below which
                        # a leaf moves by round-off alone


def worst_leaf(prog: dict, ref: dict, keep=None) -> tuple:
    """(gap, leaf) of the worst leaf; inf where the trees differ."""
    if set(prog) != set(ref):
        return math.inf, f"leaves differ: {sorted(set(prog) ^ set(ref))[:4]}"
    keys = [k for k in ref if keep is None or k in keep]
    median = float(np.median([ref[k] for k in keys]))
    worst = (0.0, "")
    for k in keys:
        p, r = prog[k], ref[k]
        gap = abs(p - r) / max(r, median) if math.isfinite(p) else math.inf
        if not gap <= worst[0]:
            worst = (gap, k)
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers, each with the place its worst case is."""
    loss = (0.0, "")
    rounds = zip(prog["metrics"][:len(ref["metrics"])], ref["metrics"])
    for r, (pm, rm) in enumerate(rounds, 1):
        for name, p, q in (("f_s", pm[0], rm[0]), ("f_u", pm[1], rm[1])):
            if not math.isfinite(p):
                gap = math.inf
            else:
                gap = abs(p - q) / abs(q) if q else (0.0 if p == q
                                                     else math.inf)
            if not gap <= loss[0]:
                loss = (gap, f"round {r} {name}")
    grad = worst_leaf(prog["grad"], ref["grad"])
    g = ref["grad"]
    median = float(np.median(list(g.values())))
    moving = {k for k, v in g.items() if v >= NEGLIGIBLE * median}
    change = (0.0, "")
    for part in ("params", "teacher"):
        gap = worst_leaf(prog["change"][part], ref["change"][part], moving)
        if not gap[0] <= change[0]:
            change = (gap[0], f"{part}{gap[1]}")
    return {"loss": {"value": loss[0], "where": loss[1]},
            "grad": {"value": grad[0], "where": grad[1]},
            "change": {"value": change[0], "where": change[1]}}


def judge(checks: dict, limits: dict) -> bool:
    return all(v["value"] <= limits[k] for k, v in checks.items())
