"""Operations and bytes the algorithm needs, from shapes alone.

These are the yardstick's own counts: a later change to the program
cannot alter them.  They count what a family's mathematics requires,
not what the program happens to execute, so recomputation (remat) and
work spent on masked-out attention scores are not counted.  The
per-family formulas (forward FLOPs per token, bytes per cached token,
serving FLOPs) are in ``families/<family>.py``; what holds for every
family is here.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable

from chipbench.spec import HERE


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``.  A kind that is not
    in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table['devices'])}); add its published "
                       f"peaks with their source")
    return table["devices"][device_kind]


def train_flops_per_token(family, cfg: Dict[str, Any], seq: int) -> float:
    """Forward plus backward: the backward pass needs twice the forward's
    multiply-adds (one product for the input's gradient, one for the
    weight's)."""
    return 3.0 * family.program.forward_flops_per_token(cfg, seq)


def decode_step_bytes(param_bytes: int, fills: Iterable[int],
                      kv_token_bytes: int) -> int:
    """Least bytes one decode step moves from HBM: every parameter as
    stored once, and the keys and values of the filled positions of the
    live slots (``fills``: positions each live slot attends to, the new
    token's included).  Counting filled positions, not the capacity the
    cache allocates, lets a cache that reads only what is filled show
    as a gain."""
    return int(param_bytes) + int(sum(fills)) * int(kv_token_bytes)
