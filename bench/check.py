"""The numbers ``correct`` compares, each against its limit.

All of them set what the timed path produced in its first steps beside the
float32 reference's run over the same weights and documents:

  loss               widest relative gap of a step's loss
  loss_first         relative gap of the first step's loss
  first_grad         worst leaf's gap of the first clipped gradient's norm
  first_grad_median  the median leaf's gap of the same
  update             worst leaf's gap of the norm of the parameters'
                     change over the checked steps
  update_median      the median leaf's gap of the same
  batch              elements of the fed batches (tokens, labels, loss
                     mask) that differ from the reference's own packing;
                     exact

A cell compares the numbers its limits file gives a limit; one with no
upper reading on the chip is not compared (``bench/set_limits.py``).

A leaf's gap is |‖program‖ − ‖reference‖| over the larger of the
reference's norm of that leaf and the median leaf's.  Leaves whose
reference gradient is under a thousandth of the median leaf's move by
rounding alone and are left out of ``update``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

#: a leaf whose first reference gradient is below this share of the median
#: leaf's moves by round-off alone under Adam
STILL_LEAF = 1e-3


def leaf_gaps(prog: Sequence[float], ref: Sequence[float],
              keep: Sequence[bool] = None) -> List[float]:
    med = float(np.median(ref))
    gaps = [abs(p - r) / max(r, med) if max(r, med) > 0 else abs(p - r)
            for i, (p, r) in enumerate(zip(prog, ref))
            if keep is None or keep[i]]
    return gaps or [0.0]


def leaf_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Sequence[bool] = None) -> float:
    return max(leaf_gaps(prog, ref, keep))


def moving_leaves(ref_first_grad: Sequence[float]) -> List[bool]:
    med = float(np.median(ref_first_grad))
    return [g >= STILL_LEAF * med for g in ref_first_grad]


def batch_mismatch(fed: Sequence[Dict[str, np.ndarray]],
                   packed: Sequence[Dict[str, np.ndarray]]) -> int:
    n = 0
    for a, b in zip(fed, packed):
        for k in ("tokens", "labels", "loss_mask"):
            if a[k].shape != b[k].shape:
                n += int(b[k].size)
            else:
                n += int(np.sum(a[k] != b[k]))
    return n


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses``, ``first_grad`` and
    ``change`` (per-leaf norms); ``prog`` may hold ``batch``."""
    losses = [abs(p - r) / abs(r) for p, r in
              zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]):
        losses = [math.inf]
    grad = leaf_gaps(prog["first_grad"], ref["first_grad"])
    change = leaf_gaps(prog["change"], ref["change"],
                       moving_leaves(ref["first_grad"]))
    out = {
        "loss": max(losses), "loss_first": losses[0],
        "first_grad": max(grad),
        "first_grad_median": float(np.median(grad)),
        "update": max(change), "update_median": float(np.median(change)),
    }
    if "batch" in prog:
        out["batch"] = float(prog["batch"])
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each number beside its limit; a number that is not finite fails."""
    return {k: {"value": values.get(k, math.inf), "limit": limits[k]}
            for k in limits}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
