"""The comparison that decides ``correct``.

Every answer the timed path produced is compared with the plain reference's
answer to the same query, as pair counts within each bin edge (the
cumulative sums of the bins). ``rel_gap`` is the widest relative gap over
every answer and edge: ``|got - want| / want``. An answer of the wrong shape
counts in ``malformed``; a request that failed or never came back counts in
``missing``. ``PERF.md``, "Correctness", gives the readings each limit was
set from.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"rel_gap": 0.015, "malformed": 0, "missing": 0}


def compare(got: list, want: list, missing: int = 0) -> dict:
    """``got[i]`` against ``want[i]`` -> {name: {"value", "limit"}}."""
    gap, malformed = 0.0, 0
    for g, w in zip(got, want):
        g = np.cumsum(np.atleast_1d(np.asarray(g, np.int64)))
        w = np.cumsum(np.atleast_1d(np.asarray(w, np.int64)))
        if g.shape != w.shape:
            malformed += 1
            continue
        gap = max(gap, float(np.max(np.abs(g - w) / np.maximum(w, 1))))
    values = {"rel_gap": gap, "malformed": malformed, "missing": missing,
              "answers_checked": len(got)}
    return {k: {"value": v, "limit": LIMITS.get(k)} for k, v in values.items()}


def passed(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values()
               if v["limit"] is not None)
