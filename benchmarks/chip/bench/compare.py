"""The comparison that decides ``correct``.

Four numbers, each against the limit the cell's workload file gives:

* ``loss_gap``: the largest relative gap between the program's and the
  reference's mean local loss over the first rounds;
* ``grad_norm_gap``: the first round's server momentum, m_1 = Δ̄/η, is
  the gradient the server's optimizer gets; for each leaf, the gap
  between the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
* ``update_norm_gap``: the same for each leaf's change of the parameters
  over the first rounds;
* ``grad_leaf_gap``: as ``grad_norm_gap``, but over the reference's norm
  of that leaf alone, so a small leaf that stays still (a norm scale whose
  update is lost to rounding) reads about 1.

Leaves whose reference gradient norm is under a thousandth of the median
leaf's move by round-off alone and are left out of both leaf numbers.
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss_gap", "grad_norm_gap", "update_norm_gap", "grad_leaf_gap")
TINY = 1e-3


def _worst(p, r, keep, floor):
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(p - r) / floor
    i = int(np.argmax(np.where(keep, gap, -1.0)))
    return float(gap[i]), i


def readings(prog, ref):
    """-> ({name: value}, {name: index of the worst leaf})."""
    keep = ref["grad"] >= TINY * np.median(ref["grad"])
    loss = np.abs(prog["loss"] - ref["loss"]) / np.abs(ref["loss"])
    g, u = ref["grad"], ref["update"]
    grad, gi = _worst(prog["grad"], g, keep,
                      np.maximum(g, np.median(g[keep])))
    upd, ui = _worst(prog["update"], u, keep,
                     np.maximum(u, np.median(u[keep])))
    leaf, li = _worst(prog["grad"], g, keep, g)
    return ({"loss_gap": float(np.max(loss)), "grad_norm_gap": grad,
             "update_norm_gap": upd, "grad_leaf_gap": leaf},
            {"grad_norm_gap": gi, "update_norm_gap": ui,
             "grad_leaf_gap": li})


def compare(prog, ref, limits):
    """-> {name: {"value", "limit"}}; a value that is not finite is
    written as the string "nan" and fails."""
    values, _ = readings(prog, ref)
    return {n: {"value": values[n] if np.isfinite(values[n]) else "nan",
                "limit": limits[n]} for n in NAMES}


def passed(checks) -> bool:
    return all(isinstance(c["value"], float) and c["value"] <= c["limit"]
               for c in checks.values())
