"""The comparison that decides `correct`.

Program and reference each give readings over the cell's first
`check_steps` steps (one step = one window call: `eval_every` rounds and
an eval): every round's training loss, the global model before the first
step and after each step, and the test accuracy after each step. The
numbers compared:

  first_loss_gap  relative gap of the first round's loss: both sides
                  start from the same weights on the same batches, so
                  only the arithmetic separates them
  last_loss_gap   relative gap of the last checked step's mean round
                  loss: where training has settled, a lower precision
                  shows as a loss that stops falling
  update_gap      of the first step's update (model after step 1 minus
                  the initial one), the gap between the program's and
                  the reference's norm, per leaf, over the larger of the
                  reference's norm of that leaf and of the median leaf;
                  the worst leaf
  change_gap      the same for the change after the last checked step
  acc_gap         the largest gap of a step's test accuracy (the eval's
                  own output)
  plan_gap        |b - b_expected| + |V - V_expected| of the run's plan

Leaves whose reference update is nought to rounding (norm under a
thousandth of the median leaf's) are left out of update_gap and
change_gap. Each number is held to its limit from the cell's limits
file (chipbench/limits/<cell>.json, which gives the readings each limit
was set from); a number without a limit there is not compared.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import numpy as np

NOUGHT = 1e-3  # a leaf's update under this share of the median leaf's


def leaf_norms(a, b) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)
                                          - np.asarray(y, np.float64)))
                     for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def norm_gap(prog_from, prog_to, ref_from, ref_to) -> float:
    """Worst leaf's |norm(program change) - norm(reference change)| over
    max(reference norm of that leaf, median reference leaf norm)."""
    p = leaf_norms(prog_to, prog_from)
    r = leaf_norms(ref_to, ref_from)
    if not np.all(np.isfinite(p)):
        return math.inf
    med = float(np.median(r))
    keep = r >= NOUGHT * med
    denom = np.maximum(r, med)
    return float(np.max(np.abs(p - r)[keep] / denom[keep]))


def rel_gap(prog: float, ref: float) -> float:
    if not math.isfinite(prog):
        return math.inf
    return abs(prog - ref) / abs(ref)


def numbers(prog: Dict, ref: Dict, plan: Tuple[int, int],
            expected: Dict) -> Dict[str, float]:
    last = len(ref["params"]) - 1
    per_step = len(ref["losses"]) // last
    return {
        "first_loss_gap": rel_gap(prog["losses"][0], ref["losses"][0]),
        "last_loss_gap": rel_gap(float(np.mean(prog["losses"][-per_step:])),
                                 float(np.mean(ref["losses"][-per_step:]))),
        "update_gap": norm_gap(prog["params"][0], prog["params"][1],
                               ref["params"][0], ref["params"][1]),
        "change_gap": norm_gap(prog["params"][0], prog["params"][last],
                               ref["params"][0], ref["params"][last]),
        "acc_gap": float(np.max(np.abs(np.asarray(prog["acc"], np.float64)
                                       - np.asarray(ref["acc"])))),
        "plan_gap": float(abs(plan[0] - expected["b"])
                          + abs(plan[1] - expected["V"])),
    }


def judge(nums: Dict[str, float], limits: Dict[str, float],
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}) over the limited numbers;
    NaN fails."""
    shown = {k: {"value": nums[k], "limit": float(v)}
             for k, v in limits.items()}
    ok = all(s["value"] <= s["limit"] for s in shown.values())
    return ok, shown


def lines(shown: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k} {s['value']!r} limit {s['limit']!r}"
            for k, s in shown.items()]
