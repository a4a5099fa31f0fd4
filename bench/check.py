"""The comparison that decides ``correct``.

The program's first steps (the first ``check_steps`` calls of
``Session.step_rounds`` in the measured window, from x0 = 0) are
compared with the reference's. The numbers, all relative:

* ``loss_gap``: the largest |loss - reference loss| / reference loss
  over the steps;
* ``grad_gap``: after step 1 the weights are η times the first
  gradient as the optimizer took it (x0 = 0): the gap between the
  program's norm of them and the reference's, over the reference's;
* ``change_gap``: the same for the weights' change after the last step;
* ``weights_gap``: ||x - x_ref|| / ||x_ref|| after the last step. The
  gaps of norms are second order in an error that is not along x, so
  they cannot tell a product computed in a lower precision from
  rounding; this one can.

The weights are one vector, so "worst leaf" is that vector. Each cell
keeps the numbers it compares, with their limits and the readings they
were set from, in ``bench/limits/<cell>.json``.
"""

from __future__ import annotations

import math

import numpy as np


Steps = list[tuple[float, np.ndarray]]


def compare(prog: Steps, ref: Steps) -> dict[str, float]:
    """The numbers for two lists of (loss, weights), one per step."""
    if len(prog) != len(ref) or not prog:
        raise ValueError(f"steps differ: {len(prog)} vs {len(ref)}")

    def norm_gap(a, b):
        nb = float(np.linalg.norm(b))
        return abs(float(np.linalg.norm(a)) - nb) / nb

    (_, x1), (_, r1) = prog[0], ref[0]
    (_, xn), (_, rn) = prog[-1], ref[-1]
    return {
        "loss_gap": max(abs(lp - lr) / abs(lr) for (lp, _), (lr, _) in zip(prog, ref)),
        "grad_gap": norm_gap(x1, r1),
        "change_gap": norm_gap(xn, rn),
        "weights_gap": float(np.linalg.norm(xn - rn) / np.linalg.norm(rn)),
    }


def verdict(numbers: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """(correct, checks): every number that has a limit must be finite
    and at most its limit; ``checks`` maps each to its value and limit."""
    checks = {}
    ok = True
    for name, entry in limits.items():
        value = numbers[name]
        checks[name] = {"value": value, "limit": entry["limit"]}
        ok &= math.isfinite(value) and value <= entry["limit"]
    return ok, checks
