"""The numbers that decide ``correct``, each held to a limit of its own.

Training: the gap of each of the first steps' losses (nats), and by the
worst leaf and by the median leaf the gap between the program's and the
reference's norm of the first gradient and of the parameters' change
over the checked steps, each over the reference's norm of that leaf or
of the median leaf, whichever is larger.  Leaves whose reference
gradient is under a thousandth of the median leaf's move under Adam by
round-off alone (a key bias under softmax), so they are left out of the
change.  Which of these a cell compares is up to its limits file.

Serving: the widest gap by which a served token's logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, Mapping, Optional

import numpy as np

ROUND_OFF_GRAD = 1e-3


def leaf_gaps(prog: Mapping[str, float], ref: Mapping[str, float],
              keep=None) -> Optional[Dict[str, float]]:
    """Each leaf's gap between the program's norm and the reference's,
    over the larger of the reference's norm of that leaf and of the
    median leaf; None where the two sides' leaves differ."""
    if set(prog) != set(ref):
        return None
    names = [n for n in ref if keep is None or keep(n)]
    floor = float(np.median([ref[n] for n in names]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], floor) for n in names}


def train_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref``: {"losses": [...], "grad": {leaf: norm},
    "change": {leaf: norm}}.  Besides the worst step and leaf, each
    step's loss gap (``loss_gap.<step>``) and the median leaf's gaps
    (``grad_gap.median``, ``change_gap.median``) are read, to be printed
    beside the numbers compared."""
    n = len(ref["losses"])
    losses = list(prog["losses"])[:n]
    steps = ([abs(a - b) for a, b in zip(losses, ref["losses"])]
             if len(losses) == n else [math.inf] * n)
    g_ref = ref["grad"]
    tiny = ROUND_OFF_GRAD * float(np.median(list(g_ref.values())))
    out = {"loss_gap": float(max(steps))}
    for name, gaps in (
            ("grad_gap", leaf_gaps(prog["grad"], g_ref)),
            ("change_gap", leaf_gaps(prog["change"], ref["change"],
                                     keep=lambda n: g_ref[n] >= tiny))):
        out[name] = max(gaps.values()) if gaps else math.inf
        out[f"{name}.median"] = (float(np.median(list(gaps.values())))
                                 if gaps else math.inf)
    out.update({f"loss_gap.{k}": float(g) for k, g in enumerate(steps)})
    return out


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
