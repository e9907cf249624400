"""The comparison that decides ``correct`` for a training cell.

The program and the reference each give, for the first rounds of a run:
the mean loss of every round's communication step, the first gradient's
norm per client and leaf, and the norm of each client's change of every
leaf after the last of those rounds.  Three numbers come out:

* ``loss_gap``: the largest relative gap between the two losses of a round;
* ``grad_norm_gap``: the largest gap between the two first-gradient norms of
  a (client, leaf), over the larger of the reference's norm of that leaf and
  the median one;
* ``change_gap``: the same for the change of the weights, over the leaves
  whose reference first gradient is at least a thousandth of the median
  (a leaf under that moves by round-off alone).

Each that the cell's ``bench/limits/<cell>.json`` names is held to its
limit there.
"""
from __future__ import annotations

import math

import numpy as np

#: leaves whose reference first-gradient norm is under this share of the
#: median are left out of the change (they move by rounding alone)
STILL_LEAF_SHARE = 1e-3
NUMBERS = ("loss_gap", "grad_norm_gap", "change_gap")


def _worst_gap(prog, ref, keep=None):
    """max over (client, leaf) of |prog - ref| / max(ref, median ref)."""
    p = np.concatenate([np.ravel(v) for v in prog]).astype(np.float64)
    r = np.concatenate([np.ravel(v) for v in ref]).astype(np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    floor = np.maximum(r, np.median(r))
    gaps = np.abs(p - r) / np.where(floor > 0, floor, 1.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def compare(prog: dict, ref: dict) -> dict:
    """Numbers of one run: {name: value} plus where the worst one sits."""
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    if lp.shape != lr.shape:
        raise ValueError(f"{lp.size} program losses against {lr.size}")
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    names = ref["names"]
    per = [len(np.ravel(v)) for v in ref["grad_norm"]]
    where = lambda i: names[int(np.searchsorted(np.cumsum(per), i,
                                                side="right"))]
    g, gi = _worst_gap(prog["grad_norm"], ref["grad_norm"])
    out["grad_norm_gap"] = g
    gr = np.concatenate([np.ravel(v) for v in ref["grad_norm"]])
    keep = gr >= STILL_LEAF_SHARE * np.median(gr)
    c, ci = _worst_gap(prog["change_norm"], ref["change_norm"], keep)
    out["change_gap"] = c
    out["worst_grad_leaf"] = where(gi)
    out["worst_change_leaf"] = where(int(np.flatnonzero(keep)[ci]))
    return out


def leaf_gaps(prog: dict, ref: dict, key: str) -> dict:
    """{leaf: worst gap over clients} of one reading, for the record."""
    med = np.median(np.concatenate([np.ravel(v) for v in ref[key]]))
    out = {}
    for name, p, r in zip(ref["names"], prog[key], ref[key]):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        floor = np.maximum(r, med)
        out[name] = float(np.max(np.abs(p - r) / np.where(floor > 0, floor,
                                                          1.0)))
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers the cell's
    limits file names (a cell leaves out a number that separates no
    control or fault from its sound runs); one that is not finite fails."""
    checks, ok = {}, True
    for name in NUMBERS:
        if name not in limits:
            continue
        v, lim = numbers[name], limits[name]
        checks[name] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks
