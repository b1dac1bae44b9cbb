"""The numbers that decide ``correct``, each against its limit.

- ``loss_gap``: the largest relative gap between the program's loss and
  the reference's over the followed steps.
- ``grad_gap``: over the leaves, the largest gap between the norms of the
  program's and the reference's momentum after one step (the first
  gradient as the optimizer takes it, weight decay included).
- ``delta_gap``: the same for the change of each leaf over the followed
  steps.

A leaf's gap is the difference of the two norms, not the norm of the
difference, over the reference's norm of that leaf or of the median leaf,
whichever is larger. Leaves whose reference gradient is under a thousandth
of the median leaf's move by round-off alone, and are left out of
``delta_gap``.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "grad_gap", "delta_gap")
QUIET_LEAF = 1e-3


def _norms(leaves) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(l, np.float64).ravel())
                     for l in leaves])


def _leaf_gap(got, want, keep=None) -> float:
    a, b = _norms(got), _norms(want)
    if keep is not None:
        a, b = a[keep], b[keep]
    floor = np.maximum(b, np.median(b))
    return float(np.max(np.abs(a - b) / floor))


def readings(prog: dict, ref: dict) -> dict:
    """``prog`` holds ``losses``, ``m1`` and ``p_last`` as the program
    produced them; ``ref`` is ``sgd_reference.follow``'s output."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"{lp.size} program losses for {lr.size} steps")
    g = _norms(ref["grad0"])
    keep = g >= QUIET_LEAF * np.median(g)
    delta_p = [p - q for p, q in zip(prog["p_last"], ref["p0"])]
    delta_r = [p - q for p, q in zip(ref["p_last"], ref["p0"])]
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": _leaf_gap(prog["m1"], ref["m1"]),
            "delta_gap": _leaf_gap(delta_p, delta_r, keep)}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a missing or non-finite
    value fails."""
    checks, ok = {}, True
    for name in NAMES:
        v = values.get(name, float("nan"))
        checks[name] = {"value": v, "limit": limits[name]}
        ok = ok and math.isfinite(v) and v <= limits[name]
    return ok, checks
