"""The numbers that decide ``correct``, each beside its limit.

A number is compared with ``value <= limit``; a value that is not finite
fails.  A cell compares the numbers its ``limits`` name, and only those.  ``gap_of_norms`` takes the worst leaf: the gap between the
program's norm of a leaf and the reference's, over the larger of the
reference's norm of that leaf and the median leaf's norm.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Optional


def gap_of_norms(prog: Dict[str, float], ref: Dict[str, float],
                 keep: Optional[Iterable[str]] = None) -> float:
    names = list(keep) if keep is not None else list(ref)
    med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in names)


def moving_leaves(grad_norms: Dict[str, float], share: float) -> list:
    """Leaves whose reference gradient is at least ``share`` of the
    median leaf's: the others move under Adam by round-off alone."""
    med = statistics.median(grad_norms.values())
    return [k for k, v in grad_norms.items() if v >= share * med]


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value": v, "limit": l}} for every number compared."""
    return {k: {"value": float(values[k]), "limit": float(lim)}
            for k, lim in limits.items()}


def passed(checks: Dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def lines(checks: Dict) -> str:
    return "\n".join(f"check {k}: {c['value']!r} (limit {c['limit']!r})"
                     for k, c in checks.items())
