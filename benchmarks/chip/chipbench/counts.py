"""Operations and bytes the algorithm needs, from shapes alone.

These are the yardstick's own counts: a later change to the program
cannot alter them.  They count what GPT-2's mathematics requires, not
what the program happens to execute, so recomputation (remat) and work
spent on masked-out attention scores are not counted.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable

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


def forward_flops_per_token(cfg: Dict[str, int], seq: int) -> float:
    """Forward FLOPs per token of a GPT-2 decoder over sequences of
    ``seq`` tokens (2 FLOPs per multiply-add).

    Per layer: the q, k, v and output projections (4 d^2 MACs), the MLP
    (2 d d_ff MACs) and causal attention.  Causal attention counts half:
    token i attends to i + 1 positions, so a sequence needs
    d S (S + 1) / 2 MACs for the scores and as many for scores x values,
    which is d (S + 1) MACs per token for the two together.  Then the
    tied unembedding (d V MACs).  Embedding gathers, layer norms,
    biases, GELU and the softmax are elementwise and not counted.
    """
    d, f = cfg["n_embd"], cfg["n_inner"]
    per_layer = 4 * d * d + 2 * d * f + d * (seq + 1)
    return 2.0 * (cfg["n_layer"] * per_layer + d * cfg["vocab_size"])


def train_flops_per_token(cfg: Dict[str, int], seq: int) -> float:
    """Forward plus backward: the backward pass needs twice the forward's
    multiply-adds (one product for the input's gradient, one for the
    weight's)."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def kv_bytes_per_token(cfg: Dict[str, int], kv_itemsize: int) -> int:
    """Bytes one cached token holds over all layers: a key and a value
    row of every head."""
    return cfg["n_layer"] * 2 * cfg["n_embd"] * kv_itemsize


def decode_step_bytes(param_bytes: int, fills: Iterable[int],
                      kv_token_bytes: int) -> int:
    """Least bytes one decode step moves from HBM: every parameter as
    stored once, and the keys and values of the filled positions of the
    live slots (``fills``: positions each live slot attends to, the new
    token's included).  Counting filled positions, not the capacity the
    cache allocates, lets a cache that reads only what is filled show
    as a gain."""
    return int(param_bytes) + int(sum(fills)) * int(kv_token_bytes)


def serve_flops(cfg: Dict[str, int], requests: Iterable) -> float:
    """Forward FLOPs the algorithm needs to serve ``requests``, each a
    (prompt length P, tokens served n) pair: the prompt's layers over all
    P positions with causal attention, one unembedding for the first
    token, then n - 1 decode steps, the j-th attending to P + j
    positions."""
    d, f, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    dense = 4 * d * d + 2 * d * f          # MACs per token per layer
    head = d * cfg["vocab_size"]
    macs = 0
    for P, n in requests:
        macs += L * (P * dense + d * P * (P + 1)) + head
        steps = max(int(n) - 1, 0)
        ctx = steps * P + steps * (steps + 1) // 2   # sum of P + j
        macs += steps * (L * dense + head) + L * 2 * d * ctx
    return 2.0 * macs
