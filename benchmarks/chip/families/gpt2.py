"""The ``gpt2`` family: what the harness knows of GPT-2 besides its
mathematics (``references/gpt2.py``).

A configuration file that says ``"family": "gpt2"`` is read here by
GPT-2's own key names: the program's ``ModelConfig`` for it, the
operations and bytes the algorithm needs, the positions a sequence may
hold, and the widths a CPU rehearsal shrinks it to.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable

# the CPU rehearsal's sizes, put over the configuration's
TINY = dict(n_layer=2, n_embd=64, n_head=4, n_inner=256, vocab_size=512,
            n_positions=64)


def program_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    if cfg["activation_function"] != "gelu_new" or \
            cfg["position_embedding"] != "learned":
        raise ValueError("the harness runs GPT-2 blocks: tanh GELU and "
                         "learned positions")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], n_heads=cfg["n_head"],
        n_kv_heads=cfg["n_head"], d_ff=cfg["n_inner"],
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["n_positions"],
        rope_theta=0.0, norm="layernorm",
        norm_eps=cfg["layer_norm_epsilon"], activation="gelu",
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["compute_dtype"], source=cfg["source"])


def positions(cfg: Dict[str, Any]) -> int:
    """The positions a sequence may hold: the learned table's rows."""
    return cfg["n_positions"]


def forward_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
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


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int) -> int:
    """Bytes one cached token holds over all layers: a key and a value
    row of every head."""
    return cfg["n_layer"] * 2 * cfg["n_embd"] * kv_itemsize


def serve_flops(cfg: Dict[str, Any], requests: Iterable) -> float:
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
