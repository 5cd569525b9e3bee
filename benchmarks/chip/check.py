"""The numbers that decide ``correct`` for a training cell.

The program's first three steps are compared with the plain reference's:
each step's loss, the first gradient as the optimizer received it (worked
out from the optimizer's state after one step), and each parameter's change
after the three.  Gradients and changes are compared leaf by leaf through
their norms: the gap between the program's norm and the reference's,
measured against the reference's norm of that leaf or of the median leaf,
whichever is larger, and the worst leaf is the number.
"""
from __future__ import annotations

import numpy as np

# A leaf whose reference gradient is below this share of the median leaf's
# is nought to rounding (a key's bias under softmax): under Adam it moves by
# round-off alone, so its change is not compared.
STILL_LEAF = 1e-3


def _norms(leaves) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in leaves])


def leaf_gap(prog: list, ref: list, mask=None, diff: bool = False,
             median: bool = False) -> float:
    """Worst leaf (or, with ``median``, the median leaf) of
    |‖prog‖ − ‖ref‖| (or, with ``diff``, of ‖prog − ref‖) over
    max(‖ref leaf‖, median ‖ref leaf‖)."""
    r = _norms(ref)
    if diff:
        p = _norms([np.asarray(a, np.float64) - np.asarray(b, np.float64)
                    for a, b in zip(prog, ref)])
    else:
        p = np.abs(_norms(prog) - r)
    keep = np.ones(len(r), bool) if mask is None else np.asarray(mask)
    med = float(np.median(r[keep]))
    ratio = p[keep] / np.maximum(r[keep], med)
    return float(np.median(ratio) if median else np.max(ratio))


def numbers(prog: dict, ref: dict) -> dict:
    """Every number a limit may be set on.  prog and ref each hold
    ``losses`` (three floats), ``grad1`` and ``change3`` (lists of leaves in
    one order).  ``*_gap`` compares norms; ``*_diff`` measures the norm of
    the difference, which a loss of precision moves at first order where a
    gap of norms moves only at second; ``*_med`` reads the median leaf where
    the others read the worst."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    g = _norms(ref["grad1"])
    moved = g >= STILL_LEAF * float(np.median(g))
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "loss1_gap": float(abs(lp[0] - lr[0]) / abs(lr[0])),
        "grad_gap": leaf_gap(prog["grad1"], ref["grad1"]),
        "grad_diff": leaf_gap(prog["grad1"], ref["grad1"], diff=True),
        "change_gap": leaf_gap(prog["change3"], ref["change3"], moved),
        "change_diff": leaf_gap(prog["change3"], ref["change3"], moved,
                                diff=True),
        "grad_gap_med": leaf_gap(prog["grad1"], ref["grad1"], median=True),
        "grad_diff_med": leaf_gap(prog["grad1"], ref["grad1"], diff=True,
                                  median=True),
        "change_gap_med": leaf_gap(prog["change3"], ref["change3"], moved,
                                   median=True),
        "change_diff_med": leaf_gap(prog["change3"], ref["change3"], moved,
                                    diff=True, median=True),
    }


def judge(nums: dict, limits: dict) -> bool:
    """Every number that has a limit is finite and within it."""
    return all(np.isfinite(nums[k]) and nums[k] <= lim
               for k, lim in limits.items())
