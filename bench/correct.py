"""The comparisons that decide ``correct``, and how they are printed.

Training compares, by the worst leaf (each layer of a stacked weight is a
leaf), the gap between the program's norm and the reference's, never the
norm of their difference, measured against the reference's norm of that
leaf or of the median leaf, whichever is larger.  Leaves whose reference
gradient is under a thousandth of the median leaf's move by round-off alone
under Adam; they are left out of the change.
"""

from __future__ import annotations

import math
import sys
from typing import Dict, Iterable, Optional, Set, Tuple

import numpy as np

QUIET_GRAD = 1e-3     # of the median leaf's reference gradient norm


def _entries(norms: Dict[str, np.ndarray]):
    for name, arr in norms.items():
        for i, v in enumerate(np.asarray(arr, np.float64).reshape(-1)):
            yield (name, i), float(v)


def quiet_leaves(ref_grad: Dict[str, np.ndarray]) -> Set[Tuple[str, int]]:
    vals = dict(_entries(ref_grad))
    med = float(np.median(list(vals.values())))
    return {k for k, v in vals.items() if v < QUIET_GRAD * med}


def leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             skip: Iterable = ()) -> Tuple[float, str]:
    """(worst gap, which leaf) of per-leaf norms."""
    skip = set(skip)
    r = {k: v for k, v in _entries(ref) if k not in skip}
    p = dict(_entries(prog))
    if set(r) - set(p):
        return math.inf, f"missing {sorted(set(r) - set(p))[:3]}"
    med = float(np.median(list(r.values())))
    worst, where = 0.0, ""
    for k, rv in r.items():
        g = abs(p[k] - rv) / max(rv, med)
        if not math.isfinite(g):
            return math.inf, f"{k[0]}[{k[1]}]"
        if g > worst:
            worst, where = g, f"{k[0]}[{k[1]}]"
    return worst, where


def train_numbers(prog: dict, ref: dict) -> Dict[str, Tuple[float, str]]:
    """loss_gap, grad_gap, delta_gap of the program's readings against the
    reference's (or the control's against the reference's)."""
    lp, lr = prog["loss"], ref["loss"]
    if len(lp) != len(lr):
        loss = (math.inf, "steps")
    else:
        gaps = [abs(a - b) / abs(b) for a, b in zip(lp, lr)]
        i = int(np.argmax(gaps))
        loss = (gaps[i] if all(map(math.isfinite, lp)) else math.inf,
                f"step {i}")
    quiet = quiet_leaves(ref["grad"])
    delta, where = leaf_gap(prog["delta"], ref["delta"], quiet)
    return {"loss_gap": loss,
            "grad_gap": leaf_gap(prog["grad"], ref["grad"]),
            "delta_gap": (delta, f"{where}; {len(quiet)} quiet leaves left out")}


def judge(numbers: Dict[str, Tuple[float, str]], limits: dict):
    """(correct, checks) — checks maps each number to its value and limit."""
    checks, ok = {}, True
    for name, (value, where) in numbers.items():
        lim = limits[name]
        passed = math.isfinite(value) and value <= lim
        ok &= passed
        checks[name] = {"value": value, "limit": lim, "at": where}
    return ok, checks


def report(checks: dict, stream=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r} ({c['at']})",
              file=stream)


def widest(gaps: Iterable[np.ndarray]) -> Tuple[float, str]:
    worst, where = 0.0, ""
    for i, g in enumerate(gaps):
        g = np.asarray(g, np.float64)
        if g.size == 0:
            continue
        if not np.all(np.isfinite(g)):
            return math.inf, f"sequence {i}"
        j = int(np.argmax(g))
        if g[j] > worst:
            worst, where = float(g[j]), f"sequence {i} token {j}"
    return worst, where
