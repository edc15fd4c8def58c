"""The one traffic generator: every traffic file is parameters for it.

Training traffic is packed synthetic documents: token ids drawn from a
Zipf law over a seeded permutation of the vocabulary, documents of
lognormal length closed by the end-of-text id, packed into rows of
``seq`` tokens.  Every step's rows are drawn afresh from
``(seed, step)``, so all rows differ and the work of a step does not
depend on the seed.

Serving traffic is a backlog of requests with lognormal prompt and
output lengths.  The multiset of lengths is drawn once from the traffic
file's own ``sizes_seed``: every run seed gets the same work, in its own
order and with its own token ids.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

SEED_WORDS = 2 ** 32


def seed_words(seed: int) -> List[int]:
    """A non-negative seed of any size as 32-bit words for numpy."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed % SEED_WORDS)
        seed //= SEED_WORDS
        if not seed:
            return words


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed) + [int(s) for s in stream])


class TrainBatches:
    """``batch_at(step)`` -> {"tokens", "labels"} [batch, seq] int32: the
    feed ``repro.train.train`` takes from a loader."""

    n_shards = 1

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int):
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq"])
        docs = traffic["documents"]
        self.median = float(docs["median_tokens"])
        self.sigma = float(docs["sigma"])
        self.zipf_a = float(docs["zipf_a"])
        self.eos = int(docs["eos_id"])
        self.vocab, self.seed = int(vocab), int(seed)
        # ids 1..vocab-1 in a seeded order; 0 stays out, the id the
        # program pads with
        ids = np.arange(1, vocab, dtype=np.int64)
        ids = ids[ids != self.eos]
        self.ranked = rng(seed, 0).permutation(ids).astype(np.int32)

    def rows(self, step: int) -> np.ndarray:
        """[batch, seq] packed token rows of step ``step``."""
        g = rng(self.seed, 1, step)
        ranks = np.minimum(g.zipf(self.zipf_a, self.batch * self.seq),
                           len(self.ranked)) - 1
        rows = self.ranked[ranks].reshape(self.batch, self.seq)
        for r in range(self.batch):
            # document boundaries: a packed row starts mid-document
            pos = int(g.integers(0, max(int(self.median), 1)))
            while pos < self.seq:
                rows[r, pos] = self.eos
                pos += 1 + int(g.lognormal(np.log(self.median), self.sigma))
        return rows

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """Labels are the tokens themselves: the model's loss shifts them
        by one, so position t is scored on token t + 1."""
        rows = self.rows(step)
        return {"tokens": rows, "labels": rows.copy()}


def _lognormal_lengths(g: np.random.Generator, n: int,
                       spec: Dict[str, Any]) -> np.ndarray:
    x = g.lognormal(np.log(float(spec["median"])), float(spec["sigma"]), n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def wave_sizes(traffic: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(prompt length, output budget) of every request of one wave; the
    same for every run seed."""
    g = rng(int(traffic["sizes_seed"]))
    n = int(traffic["requests_per_wave"])
    prompts = _lognormal_lengths(g, n, traffic["prompt"])
    outs = _lognormal_lengths(g, n, traffic["output"])
    outs = np.minimum(outs, int(traffic["max_len"]) - prompts)
    return [(int(p), int(o)) for p, o in zip(prompts, outs)]


def wave(traffic: Dict[str, Any], vocab: int, seed: int, index: int
         ) -> List[Tuple[np.ndarray, int]]:
    """Wave ``index`` of the run seeded ``seed``: (prompt ids, budget) per
    request, the wave's sizes in a seeded order, ids uniform over the
    vocabulary."""
    g = rng(seed, 2, index)
    sizes = wave_sizes(traffic)
    order = g.permutation(len(sizes))
    return [(g.integers(0, vocab, sizes[i][0]).astype(np.int32),
             sizes[i][1]) for i in order]
