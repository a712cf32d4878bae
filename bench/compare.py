"""The comparison that decides ``correct``.

Each answer the timed path produced is held against the reference's
answer to the same query over the same tables (``bench/reference.py``).
An answer is *wrong* when its columns, its row count, the order of its
rows (for a query whose final Sort or TopK fixes it) or any value of an
integer column differ; these are exact. Float columns are compared by
the relative error ``|got - ref| / max(|ref|, 1)``, and the largest over
all answers is held against its limit in ``bench/limits.json``.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional

import numpy as np

from bench.reference import Answer

LIMITS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "limits.json")


def load_limits() -> Dict[str, float]:
    with open(LIMITS_FILE) as f:
        return {k: v["limit"] for k, v in json.load(f)["limits"].items()}


@dataclasses.dataclass
class Check:
    """What one answer reads against the reference."""
    wrong: Optional[str]          # why the answer is wrong, or None
    rel_err: float                # largest float-column relative error


def _row_order(cols: Dict[str, np.ndarray], exact: List[str],
               floats: List[str]) -> np.ndarray:
    # lexsort's last key is the primary one: exact columns lead
    keys = [np.asarray(cols[c], np.float64) for c in floats] + \
        [np.asarray(cols[c], np.float64) for c in exact]
    return np.lexsort(keys)


def check(got: Dict[str, np.ndarray], want: Answer) -> Check:
    if set(got) != set(want.cols):
        return Check(f"columns {sorted(got)} != {sorted(want.cols)}", 0.0)
    n = len(next(iter(want.cols.values())))
    if any(len(np.asarray(v)) != n for v in got.values()):
        return Check(f"{len(next(iter(got.values())))} rows != {n}", 0.0)
    exact = sorted(c for c, v in want.cols.items() if v.dtype.kind in "iub")
    floats = sorted(c for c in want.cols if c not in exact)
    if want.ordered or n <= 1:
        gi = wi = np.arange(n)
    else:
        gi = _row_order(got, exact, floats)
        wi = _row_order(want.cols, exact, floats)
    for c in exact:
        g = np.asarray(got[c], np.float64)[gi]
        w = np.asarray(want.cols[c], np.float64)[wi]
        if not np.array_equal(g, w):
            return Check(f"column {c} differs", 0.0)
    worst = 0.0
    for c in floats:
        g = np.asarray(got[c], np.float64)[gi]
        w = np.asarray(want.cols[c], np.float64)[wi]
        if not np.all(np.isfinite(g) == np.isfinite(w)):
            return Check(f"column {c} not finite where the reference is", 0.0)
        fin = np.isfinite(w)
        err = np.abs(g[fin] - w[fin]) / np.maximum(np.abs(w[fin]), 1.0)
        worst = max(worst, float(err.max(initial=0.0)))
    return Check(None, worst)


@dataclasses.dataclass
class Verdict:
    """The numbers a run is judged by, each beside its limit."""
    compared: int
    wrong: int
    failed: int
    max_rel_err: float
    limits: Dict[str, float]
    first_wrong: Optional[str] = None

    @property
    def correct(self) -> bool:
        return (self.compared > 0
                and self.wrong <= self.limits["answers_wrong"]
                and self.failed <= self.limits["answers_failed"]
                and self.max_rel_err <= self.limits["max_rel_err"])

    def numbers(self) -> Dict[str, Dict[str, float]]:
        return {
            "answers_wrong": {"value": self.wrong,
                              "limit": self.limits["answers_wrong"]},
            "answers_failed": {"value": self.failed,
                               "limit": self.limits["answers_failed"]},
            "max_rel_err": {"value": self.max_rel_err,
                            "limit": self.limits["max_rel_err"]},
            "answers_compared": {"value": self.compared, "limit": 1},
        }


def judge(answers: List, reference: Dict[str, Answer], failed: int,
          limits: Dict[str, float]) -> Verdict:
    """``answers`` is a list of ``(qid, cols)``; every one is compared."""
    wrong, worst, first = 0, 0.0, None
    for qid, cols in answers:
        c = check(cols, reference[qid])
        if c.wrong is not None:
            wrong += 1
            first = first or f"{qid}: {c.wrong}"
        worst = max(worst, c.rel_err)
    return Verdict(len(answers), wrong, failed, worst, limits, first)
