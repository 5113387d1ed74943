"""The numbers a run compares with the plain reference, each beside its
limit. A run is correct when every number is at or under its limit."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def all_ok(checks: Sequence[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def as_dict(checks: Sequence[Check]) -> Dict[str, dict]:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def lines(checks: Sequence[Check]) -> List[str]:
    return [f"check {c.name} = {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}" for c in checks]


def norm_gap(got: Sequence[float], want: Sequence[float],
             *, keep: Sequence[bool] = ()) -> float:
    """Worst leaf's gap between two norms: |got - want| over the larger of
    the leaf's reference norm and the median leaf's. ``keep`` leaves out the
    leaves marked False."""
    idx = [i for i in range(len(want)) if not keep or keep[i]]
    ref = sorted(abs(want[i]) for i in idx)
    median = ref[len(ref) // 2] if ref else 0.0
    gap = 0.0
    for i in idx:
        denom = max(abs(want[i]), median)
        g = abs(got[i] - want[i]) / denom if denom > 0 else math.inf
        if not math.isfinite(got[i]):
            g = math.inf
        gap = max(gap, g)
    return gap


def loss_gap(got: Sequence[float], want: Sequence[float]) -> float:
    """Largest relative gap between the program's and the reference's loss
    over the checked steps."""
    gap = 0.0
    for g, w in zip(got, want):
        gap = max(gap, abs(g - w) / abs(w) if math.isfinite(g) else math.inf)
    return gap
