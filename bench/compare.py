"""The numbers that decide ``correct`` for a training cell.

The program and the reference each report, for the first steps of the
same weights and rows: every step's loss; per parameter leaf the norm of
the first gradient as the update applied it, worked out from the state
after step 1; and per leaf the norm of the change after the last step.
Norms are compared leaf by leaf as the gap between the program's norm and
the reference's, over the larger of the reference's norm of that leaf and
of the median leaf; the worst leaf is the number.  Leaves whose reference
gradient is under a thousandth of the median leaf's are left out of the
change.  Where both sides report weight codes, the codes that differ are
counted too.
"""
from __future__ import annotations

import numpy as np


def _gap(p: float, r: float, floor: float) -> float:
    if p == r:
        return 0.0
    d = max(r, floor)
    return abs(p - r) / d if d > 0 else float("inf")


def _worst(prog: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in ref]))
    gaps = [_gap(prog[k], ref[k], med) for k in ref if keep(k)]
    return max(gaps) if gaps else 0.0


def numbers(prog: dict, ref: dict) -> dict:
    lp, lr = prog["losses"], ref["losses"]
    out = {"loss_gap": max(_gap(a, b, 0.0) for a, b in zip(lp, lr))}
    g = ref["grad"]["norms"]
    med = float(np.median(list(g.values())))
    out["grad_norm_gap"] = _worst(prog["grad"]["norms"], g, lambda k: True)
    out["change_norm_gap"] = _worst(prog["change"]["norms"],
                                    ref["change"]["norms"],
                                    lambda k: g[k] >= 1e-3 * med)
    if "codes" in ref["change"]:
        out["codes_differing"] = sum(
            int(np.sum(p[0] != r[0]) + np.sum(p[1] != r[1]))
            for when in ("grad", "change")
            for p, r in ((prog[when]["codes"][k], ref[when]["codes"][k])
                         for k in ref[when]["codes"]))
    return out


def check(nums: dict, limits: dict) -> dict:
    """Each number beside its limit; a number passes at or under it."""
    return {k: {"value": nums[k], "limit": limits[k],
                "ok": bool(nums[k] <= limits[k])} for k in limits}
