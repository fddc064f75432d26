"""The comparison that decides ``correct`` for a training cell.

Both sides report, for the first steps of training from one seed:

* the loss of each step;
* the per-leaf norm of the first step's gradient as the optimizer takes it
  (after global-norm clipping); the system's is worked out from its AdamW
  state after one step, |m_1| / (1 - beta_1);
* the per-leaf norm of the parameter change after the last of those steps.

From them come these numbers (``readings``):

``loss_gap``           max over steps of |loss - loss_ref| / scale_ref, the
                       scale being the reference's mean absolute per-sample
                       loss term (``first_loss_gap``: the first step's);
``grad_gap``           over leaves, the largest |norm - norm_ref| /
                       max(norm_ref, median leaf norm_ref) of the first
                       gradient;
``change_gap``         the same over the parameter change, over the leaves
                       the reference moves: leaves whose reference gradient
                       stays under a thousandth of the median leaf's at
                       every step (zero to rounding, such as the unused
                       embedding) are left out;
``median_change_gap``  the median over those leaves of the same gap;
``rollout_gap``        |x0 - x0_ref| / |x0_ref| over the first step's
                       final latents, the whole batch of rollouts (from the
                       latents themselves, by ``rollout_gap``).

The median leaf is taken over the leaves whose reference value is not 0.
A leaf the reference does not hold reads 0 there.  A cell's limits file
(``bench/limits/<cell>.json``) names the numbers that are compared and
their limits; why each is or is not compared is in PERF.md.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

import numpy as np

NOUGHT = 1e-3          # a leaf's gradient under this share of the median


def _median_nonzero(values: List[float]) -> float:
    nz = [v for v in values if v > 0.0]
    return statistics.median(nz) if nz else 0.0


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float], leaves
             ) -> Tuple[float, str]:
    """(largest relative gap of a leaf norm, that leaf)."""
    leaves = list(leaves)
    med = _median_nonzero([ref.get(k, 0.0) for k in leaves])
    worst, at = 0.0, ""
    for k in leaves:
        r = ref.get(k, 0.0)
        denom = max(r, med)
        gap = abs(prog.get(k, 0.0) - r) / denom if denom > 0 else 0.0
        if gap > worst or not at:
            worst, at = gap, k
    return worst, at


def moved_leaves(ref_grads: List[Dict[str, float]], leaves) -> List[str]:
    """Leaves whose reference gradient, at some step, reaches a thousandth
    of the median leaf's largest gradient."""
    leaves = list(leaves)
    peak = {k: max(g.get(k, 0.0) for g in ref_grads) for k in leaves}
    med = _median_nonzero(list(peak.values()))
    return [k for k in leaves if peak[k] >= NOUGHT * med and peak[k] > 0]


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Every number a limits file may compare, plus which step or leaf
    gave each widest gap."""
    steps = min(len(prog["loss"]), len(ref["loss"]))
    gaps = [abs(prog["loss"][i] - ref["loss"][i]) / ref["scale"][i]
            for i in range(steps)]
    leaves = sorted(set(prog["grad_norms"][0]) | set(ref["grad_norms"][0]))
    grad_gap, grad_leaf = leaf_gap(prog["grad_norms"][0],
                                   ref["grad_norms"][0], leaves)
    moved = moved_leaves(ref["grad_norms"], leaves)
    change_gap, change_leaf = leaf_gap(prog["change_norms"],
                                       ref["change_norms"], moved)
    return {"loss_gap": max(gaps), "loss_gap_step": gaps.index(max(gaps)),
            "first_loss_gap": gaps[0],
            "grad_gap": grad_gap, "grad_gap_leaf": grad_leaf,
            "change_gap": change_gap, "change_gap_leaf": change_leaf,
            "median_change_gap": leaf_gap_median(
                prog["change_norms"], ref["change_norms"], moved),
            "leaves_compared": len(moved)}


def leaf_gap_median(prog: Dict[str, float], ref: Dict[str, float], leaves
                    ) -> float:
    """The median over ``leaves`` of the relative gap ``leaf_gap`` takes
    the largest of."""
    med = _median_nonzero([ref.get(k, 0.0) for k in leaves])
    gaps = [abs(prog.get(k, 0.0) - ref.get(k, 0.0)) / max(ref.get(k, 0.0), med)
            for k in leaves if max(ref.get(k, 0.0), med) > 0]
    return statistics.median(gaps) if gaps else 0.0


def rollout_gap(x0, x0_ref) -> float:
    """Norm of the difference of two rollouts' final latents over the
    reference's norm."""
    a = np.asarray(x0, np.float64)
    b = np.asarray(x0_ref, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def verdict(read: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}) over the numbers ``limits``
    names.  A number over its limit, or one that is not finite, makes the
    run incorrect."""
    checks = {n: {"value": read[n], "limit": lim}
              for n, lim in limits.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
