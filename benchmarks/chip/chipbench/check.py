"""The numbers that decide ``correct``, each held to a limit of its own.

Training: the gap of each of the first steps' losses (nats), and by the
worst leaf the gap between the program's and the reference's norm of the
first gradient and of the parameters' change over the checked steps,
each over the reference's norm of that leaf or of the median leaf,
whichever is larger.  Leaves whose reference gradient is under a
thousandth of the median leaf's move under Adam by round-off alone
(a key bias under softmax), so they are left out of the change.

Serving: the widest gap by which a served token's logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, Mapping, Optional

import numpy as np

ROUND_OFF_GRAD = 1e-3


def _worst_leaf(prog: Mapping[str, float], ref: Mapping[str, float],
                keep=None) -> float:
    if set(prog) != set(ref):
        return math.inf          # no leaf-for-leaf correspondence
    names = [n for n in ref if keep is None or keep(n)]
    floor = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], floor) for n in names)


def train_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref``: {"losses": [...], "grad": {leaf: norm},
    "change": {leaf: norm}}."""
    n = len(ref["losses"])
    losses = list(prog["losses"])[:n]
    loss_gap = (max(abs(a - b) for a, b in zip(losses, ref["losses"]))
                if len(losses) == n else math.inf)
    g_ref = ref["grad"]
    tiny = ROUND_OFF_GRAD * float(np.median(list(g_ref.values())))
    return {
        "loss_gap": float(loss_gap),
        "grad_gap": _worst_leaf(prog["grad"], g_ref),
        "change_gap": _worst_leaf(prog["change"], ref["change"],
                                  keep=lambda n: g_ref[n] >= tiny),
    }


def verdict(readings: Mapping[str, float], limits: Mapping[str, float]
            ) -> Dict[str, Dict[str, Optional[float]]]:
    """{name: {"value", "limit"}} for every number that has a limit.  A
    number that is missing or not finite reads ``None`` and fails."""
    out = {}
    for k, limit in limits.items():
        v = float(readings.get(k, math.nan))
        out[k] = {"value": v if math.isfinite(v) else None,
                  "limit": float(limit)}
    return out


def passed(checks: Mapping[str, Mapping[str, Optional[float]]]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def report(checks: Mapping[str, Mapping[str, float]],
           extra: Optional[Mapping[str, float]] = None, err=sys.stderr
           ) -> None:
    """The numbers compared, each beside its limit: the last lines of
    standard error."""
    for k, v in (extra or {}).items():
        print(f"reading {k} {v!r}", file=err)
    for k, c in checks.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=err)
    err.flush()
