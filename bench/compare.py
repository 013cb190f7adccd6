"""The comparison that decides ``correct``.

Three numbers, each against its limit from ``limits/<workload>.json``:

- ``loss_gap``: the largest relative gap of a step's loss, over the first
  steps;
- ``grad_gap``: of the first aggregated gradient (what Adam is handed),
  the worst leaf's gap between the program's norm and the reference's,
  over the larger of that leaf's reference norm and the median leaf's;
- ``delta_gap``: the same of the weights' change over the first steps,
  over the leaves whose first reference gradient is at least a thousandth
  of the median leaf's (the others move under Adam by round-off alone).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

NOUGHT = 1e-3  # a leaf's gradient under this share of the median's is nought


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                leaves: List[str]) -> float:
    floor = statistics.median(ref[n] for n in leaves)
    return max(abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
               for n in leaves)


def numbers(prog, ref) -> Dict[str, float]:
    """The compared numbers of two ``reference.Readings``: ``prog`` is
    judged against ``ref``."""
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog.losses, ref.losses,
                                                     strict=True))
    names = list(ref.grad_norms)
    floor = statistics.median(ref.grad_norms.values())
    moving = [n for n in names if ref.grad_norms[n] >= NOUGHT * floor]
    return {
        "loss_gap": loss,
        "grad_gap": _worst_leaf(prog.grad_norms, ref.grad_norms, names),
        "delta_gap": _worst_leaf(prog.delta_norms, ref.delta_norms, moving),
    }


def verdict(prog, ref, limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {number: {"value": v, "limit": l}}). A number that is not
    finite fails."""
    got = numbers(prog, ref)
    checks = {k: {"value": got[k], "limit": limits[k]} for k in got}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
