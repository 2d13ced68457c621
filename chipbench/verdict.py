"""The numbers that decide ``correct``: the program's first steps against
the reference's, each as the worst gap over steps or leaves.

* ``loss_gap``: over the first three steps, ``|L_prog - L_ref| / |L_ref|``;
  ``loss0_gap`` the same for step 0 alone (where later steps amplify
  rounding, as in a loss that climbs under Adam from random weights).
* ``grad_gap``: the first gradient, per leaf, the gap between the
  program's norm and the reference's, over the larger of the
  reference's norm of that leaf and the median leaf's norm; the worst
  leaf.
* ``change_gap``: the same for the parameters' change over the three
  updates, counting only leaves whose first reference gradient is at
  least a thousandth of the median leaf's (a leaf with no gradient
  moves under Adam by round-off alone); ``change_med_gap`` the median
  of those leaves' gaps, which one small noisy leaf does not set.
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss_gap", "loss0_gap", "grad_gap", "change_gap", "change_med_gap")


def leaf_norms(tree) -> dict:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64).ravel())) for k, v in flat}


def _diff(a, b):
    import jax

    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
                        a, b)


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per leaf: the first gradient's and the change's norm gap (the
    change only for counted leaves)."""
    gp, gr = leaf_norms(prog["g1"]), leaf_norms(ref["g1"])
    med_g = float(np.median(list(gr.values())))
    counted = [k for k in gr if gr[k] >= 1e-3 * med_g]
    dp = leaf_norms(_diff(prog["p_end"], prog["p0"]))
    dr = leaf_norms(_diff(ref["p_end"], ref["p0"]))
    med_d = float(np.median([dr[k] for k in counted]))
    return {
        "grad": {k: abs(gp[k] - gr[k]) / max(gr[k], med_g) for k in gr},
        "change": {k: abs(dp[k] - dr[k]) / max(dr[k], med_d) for k in counted},
    }


def gaps(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses`` (first three steps), ``g1``,
    ``p0`` and ``p_end`` (after three updates) as host arrays."""
    lp, lr = np.asarray(prog["losses"][:3]), np.asarray(ref["losses"][:3])
    rel = np.abs(lp - lr) / np.abs(lr)
    loss, loss0 = float(np.max(rel)), float(rel[0])
    leaves = leaf_gaps(prog, ref)
    change = list(leaves["change"].values())
    if not all(np.isfinite(lp)):
        loss = loss0 = float("inf")
    return {"loss_gap": loss, "loss0_gap": loss0,
            "grad_gap": float(max(leaves["grad"].values())),
            "change_gap": float(max(change)),
            "change_med_gap": float(np.median(change))}


def judge(numbers: dict, limits: dict) -> bool:
    """Correct iff every number that has a limit is finite and at most
    its limit (a cell's limits file names the numbers it compares)."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= v
               for k, v in limits.items())
