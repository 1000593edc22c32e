"""The comparison that decides ``correct``.

A federation of the window is replayed by the configuration's plain
reference from its own initial weights, on the same client data and with
the cohorts that the program reported, for the rounds of the program's
first chunk.  The numbers compared, each against its limit in the cell's
workload file:

``cohort_invalid``
    Rounds of every federation in the window whose cohort is not
    ``clients_per_round`` distinct ids in ``[0, C)``.  Exact: limit 0.
``kernel_gap``
    max |L_program − L_reference| / max |L_reference| of the eq.-(14)
    kernel, the reference's built from its own profiles in float64.
``first_loss_gap``
    The relative gap of the first round's mean local loss: the forward and
    one local step from the same initial weights, before later rounds
    amplify rounding.
``loss_gap``
    The largest relative gap of a round's mean local loss.
``update_gap``
    Of the change of the weights over the chunk, by the worst leaf: the gap
    between the program's norm and the reference's, over the reference's
    norm of that leaf or of the median leaf, whichever is larger.
``update_diff``
    The same ratio for the norm of the difference of the two changes: it
    sees a change in the direction of the update that leaves its norm.
``acc_gap``
    |accuracy_program − accuracy_reference| on the held-out set, of the
    params on which the stopping rule judged the federation (where it
    reached the target, or its last chunk): the program's reading, which
    the rule compares with the target, against the reference's reading of
    the same params.

Leaves whose change in the reference is under a thousandth of the median
leaf's are left out of the two update numbers: such a leaf moves by
round-off alone.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import numpy as np

__all__ = ["cohort_invalid", "compare", "verdict", "NUMBERS"]

NUMBERS = ("cohort_invalid", "kernel_gap", "first_loss_gap", "loss_gap", "update_gap",
           "update_diff", "acc_gap")
LEAF_FLOOR = 1e-3


def cohort_invalid(selected: np.ndarray, k: int, num_clients: int) -> int:
    """Rounds (rows of the last axis) whose cohort is not k distinct
    valid ids."""
    rows = np.asarray(selected).reshape(-1, np.asarray(selected).shape[-1])
    bad = 0
    for row in rows:
        if (row.shape[0] != k or len(set(row.tolist())) != k
                or row.min() < 0 or row.max() >= num_clients):
            bad += 1
    return bad


def _leaves(tree):
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def _update_numbers(p0, p1, r0, r1):
    dp = [b - a for a, b in zip(_leaves(p0), _leaves(p1))]
    dr = [b - a for a, b in zip(_leaves(r0), _leaves(r1))]
    nr = np.array([np.linalg.norm(x) for x in dr])
    med = float(np.median(nr))
    gap = diff = 0.0
    for a, b, n in zip(dp, dr, nr):
        if n < LEAF_FLOOR * med:
            continue
        scale = max(n, med)
        gap = max(gap, abs(np.linalg.norm(a) - n) / scale)
        diff = max(diff, np.linalg.norm(a - b) / scale)
    return gap, diff


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """Numbers of one federation.  Both dicts hold ``params0``, ``params``
    (after the first chunk), ``loss`` (per round), ``kernel`` and
    ``judged_acc``."""
    kp, kr = np.asarray(prog["kernel"], np.float64), np.asarray(ref["kernel"], np.float64)
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    gap, diff = _update_numbers(prog["params0"], prog["params"], ref["params0"], ref["params"])
    return {
        "kernel_gap": float(np.abs(kp - kr).max() / np.abs(kr).max()),
        "first_loss_gap": float(abs(lp[0] - lr[0]) / abs(lr[0])),
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "update_gap": float(gap),
        "update_diff": float(diff),
        "acc_gap": float(abs(float(prog["judged_acc"]) - float(ref["judged_acc"]))),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, Optional[float]]):
    """-> (correct, checks): each number with its limit; a number that is
    not finite fails; a number without a limit is shown and not compared."""
    checks, ok = {}, True
    for name in NUMBERS:
        if name not in numbers:
            continue
        value, limit = numbers[name], limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is not None and not (math.isfinite(value) and value <= limit):
            ok = False
        if limit is None and not math.isfinite(value):
            ok = False
    return ok, checks
