"""The comparison that decides `correct`.

A training cell compares the program's first three steps with the plain
reference (`reference.py`) run on the same rows from the same seed:

* `loss_gap`: the largest relative gap of a step's loss;
* `grad_gap`: the first gradient as the optimizer got it (its first
  moment after one step over 1 - b1), by leaf, as the gap of the two
  norms over the larger of the reference's norm of that leaf and of the
  median leaf;
* `change_gap`: the same for the parameters' change over the three
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (those move by round-off alone);

and, exactly (limit 0): rows that are not rows of the corpus, non-finite
losses, and checkpoint leaves that do not read back as they were saved.
A stacked layer leaf counts as one leaf a layer.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, Tuple

import numpy as np

NEGLIGIBLE_GRAD = 1e-3       # of the median leaf's gradient norm


def _rows(norms: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
    return out


def worst_leaf(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
               skip=frozenset()) -> Tuple[float, str]:
    """Largest |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖) over leaves;
    a leaf missing on the program's side reads infinite."""
    p, r = _rows(prog), _rows(ref)
    med = float(np.median([v for k, v in r.items() if k not in skip]))
    worst, name = 0.0, ""
    for k, rv in r.items():
        if k in skip:
            continue
        gap = (abs(p[k] - rv) / max(rv, med, 1e-30)
               if k in p and math.isfinite(p[k]) else math.inf)
        if gap > worst:
            worst, name = gap, k
    return worst, name


def negligible_leaves(ref_grads: Dict[str, np.ndarray]) -> frozenset:
    r = _rows(ref_grads)
    med = float(np.median(list(r.values())))
    return frozenset(k for k, v in r.items() if v < NEGLIGIBLE_GRAD * med)


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """prog/ref: {"losses", "grad_norms", "change_norms"}."""
    n = len(ref["losses"])
    loss_gap = max(abs(a - b) / abs(b) if math.isfinite(a) else math.inf
                   for a, b in zip(prog["losses"][:n], ref["losses"]))
    grad_gap, _ = worst_leaf(prog["grad_norms"], ref["grad_norms"])
    change_gap, _ = worst_leaf(prog["change_norms"], ref["change_norms"],
                               skip=negligible_leaves(ref["grad_norms"]))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          not_compared=()) -> Tuple[bool, Dict[str, dict]]:
    """Each number against its limit.  A number with no limit is an
    error, so a cell cannot pass on a comparison it never set, unless the
    cell names it as not compared (a number whose control and faults read
    no higher than sound runs, so that no limit could separate them)."""
    checks = {}
    ok = True
    for name, value in numbers.items():
        if name in not_compared:
            print(f"not compared: {name} = {value!r}", file=sys.stderr)
            continue
        if name not in limits:
            raise KeyError(f"no limit for {name!r}")
        lim = limits[name]
        passed = value <= lim if math.isfinite(value) else False
        ok = ok and passed
        checks[name] = {"value": value, "limit": lim}
    return ok, checks


def print_checks(checks: Dict[str, dict], file=None) -> None:
    file = file or sys.stderr
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=file, flush=True)

