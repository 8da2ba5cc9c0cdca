"""The comparison that decides ``correct`` for a training cell.

Three numbers and their geometric mean, each against the plain float32 reference that followed the
same first steps from the same weights and batches:

* ``loss_gap`` — the largest relative gap of a step's loss,
  ``|loss - ref| / |ref|``, over the compared steps;
* ``grad_gap`` — the first gradient as the optimizer got it (after
  clipping; the program's from its first moment, ``m / (1 - b1)``): per
  leaf, the gap between the program's norm and the reference's, over the
  larger of that leaf's reference norm and the median leaf's; the worst
  leaf.  Each layer's share of a stacked block leaf is a leaf of its own
  (``weights.leaf_norms``);
* ``change_gap`` — the worst leaf's gap for the change of the parameters over the
  compared steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's move by round-off alone under Adam and are left out;
* ``gap_geomean`` — the geometric mean of the three.  Each of them swings
  from seed to seed (a gap of one scalar, a loss or the norm of one small
  leaf, sits anywhere between 0 and a few times its spread), and on some
  seeds the float8 control reads low on one of them; their noises are
  independent, so the mean is steadier, and it is the number that
  separates the program from the control.

Each number has a limit of its own, set from the program's readings over a
dozen seeds and the control's (``bench/limits/<workload>.json``).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "gap_geomean")
STILL = 1e-3          # a leaf whose reference gradient is under this share
                      # of the median leaf's is left out of change_gap


def _leaf_gaps(prog: Sequence[float], ref: Sequence[float], keep=None):
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    floor = np.median(ref[keep])
    return np.abs(prog - ref)[keep] / np.maximum(ref[keep], floor)


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses``, ``grad`` (per-leaf norms
    of the first clipped gradient) and ``change`` (per-leaf norms of the
    parameters' change)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g = np.asarray(ref["grad"], np.float64)
    keep = g >= STILL * np.median(g)
    out = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": float(np.max(_leaf_gaps(prog["grad"], ref["grad"]))),
        "change_gap": float(np.max(_leaf_gaps(prog["change"], ref["change"],
                                              keep))),
    }
    out["gap_geomean"] = geomean(out)
    if not all(math.isfinite(x) for x in
               list(lp) + list(prog["grad"]) + list(prog["change"])):
        out = {k: math.inf for k in out}
    return out


def geomean(nums: Dict[str, float]) -> float:
    """The geometric mean of ``loss_gap``, ``grad_gap`` and ``change_gap``."""
    return float(np.prod([nums[k] for k in NUMBERS[:3]]) ** (1 / 3))


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """One entry per compared number: its value, its limit and whether it
    passed.  A number missing from ``limits`` fails; one whose limit is
    null has no upper reading and is not compared (``set_limits.py``)."""
    rows = []
    for name in NUMBERS:
        if name in limits and limits[name] is None:
            continue
        val, lim = nums.get(name, math.inf), limits.get(name)
        ok = lim is not None and math.isfinite(val) and val <= lim
        rows.append({"name": name, "value": val, "limit": lim, "ok": ok})
    return rows
