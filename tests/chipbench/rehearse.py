"""Runs a benchmark cell on the CPU at a tiny size, whole or with a fault
planted in the timed path, for the tests beside this file.

    JAX_PLATFORMS=cpu python tests/chipbench/rehearse.py \\
        gpt2m-train-1chip [fault]

prints the run's result line.  The cell keeps its traffic mix, plan,
window and checks; only the widths (its family's ``TINY``), the batch
and the slots shrink, and the harness's look for a TPU is skipped.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# cells rehearsed here before BENCHMARK.json holds them
ENTRIES = {
    "gpt2m-serve-batch": {"name": "gpt2m-serve-batch", "config": "gpt2m",
                          "traffic": "serve_backlog_48", "chips": 1},
}


def tiny_cell(workload: str, root: str = ROOT, entry=None):
    import numpy as np

    from chipbench import spec
    cell = spec.load_cell(workload, root, entry or ENTRIES.get(workload))
    config = dict(cell.config, **cell.family.program.TINY)
    traffic = dict(cell.traffic)
    if traffic["kind"] == "train":
        traffic.update(batch=4, seq=32, reference_rows=2,
                       documents=dict(traffic["documents"],
                                      eos_id=config["vocab_size"] - 1,
                                      median_tokens=10))
    else:
        traffic.update(slots=4, max_len=64, requests_per_wave=10,
                       check_requests=10,
                       prompt=dict(median=12, sigma=0.5, min=4, max=40),
                       output=dict(median=6, sigma=0.5, min=2, max=16))
    return dataclasses.replace(
        cell, config=config, traffic=traffic,
        chips=int(np.prod(traffic["mesh"])))


@contextlib.contextmanager
def planted(fault: str):
    """A fault in the timed path: the program's step or exchange as the
    timed path calls it, broken underneath the harness."""
    import jax

    import repro.serve.engine as engine
    import repro.train.loop as loop
    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    build = loop.build_train_step
    # what a broken step hands on, from the state it was given (p, o) and
    # the state the program's step made (new_p, new_o)
    hands_on = {
        "state_unchanged": lambda p, o, new_p, new_o: (p, o),
        "moments_not_carried": lambda p, o, new_p, new_o: (new_p, o),
        "update_negated": lambda p, o, new_p, new_o: (
            jax.tree.map(lambda a, b: 2 * b - a, new_p, p), new_o),
    }
    if fault in hands_on:
        def broken_build(*a, **k):
            step, sh = build(*a, **k)

            def broken_step(params, opt_state, batch):
                new_p, new_o, metrics = step(params, opt_state, batch)
                return (*hands_on[fault](params, opt_state, new_p, new_o),
                        metrics)
            return jax.jit(broken_step), sh
        patch(loop, "build_train_step", broken_build)
    elif fault == "half_batch":
        def broken_build(model, plan, mesh, tcfg, *, batch_shapes, **k):
            half = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                (s.shape[0] // 2,) + s.shape[1:], s.dtype), batch_shapes)
            step, sh = build(model, plan, mesh, tcfg, batch_shapes=half,
                             **k)

            def step_on_half(params, opt_state, batch):
                return step(params, opt_state, jax.tree.map(
                    lambda x: x[:x.shape[0] // 2], batch))
            return jax.jit(step_on_half), sh
        patch(loop, "build_train_step", broken_build)
    elif fault == "token_altered":
        build_decode = engine.build_decode_slots_step

        def broken_decode(model, *a, **k):
            step, sh = build_decode(model, *a, **k)
            vocab = model.cfg.vocab_size

            def altered(params, cache, tokens, live):
                logits, nxt, cache = step(params, cache, tokens, live)
                return logits, nxt.at[0, 0].set((nxt[0, 0] + 1) % vocab), \
                    cache
            return altered, sh
        patch(engine, "build_decode_slots_step", broken_decode)
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)


def rehearse(workload: str, fault: str = "", seed: int = 2 ** 31 + 7,
             seconds: float = 0.0, root: str = ROOT, entry=None) -> dict:
    """The result line of one run.  A window of 0 s holds one training
    step or one serving wave, all of whose requests are checked.
    ``root`` and ``entry`` are as ``spec.load_cell`` takes them."""
    sys.path.insert(0, BENCH)
    import run
    cell = tiny_cell(workload, root, entry)
    with planted(fault):
        return run.measure(cell, seed, seconds, False, time.perf_counter(),
                           require_tpu=False)


if __name__ == "__main__":
    print(json.dumps(rehearse(*sys.argv[1:3])))
