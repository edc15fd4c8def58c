"""Serving cells: ``ContinuousEngine.run`` over whole waves of a backlog.

Set-up builds the engine and serves one warm-up wave, which compiles the
decode step, the insert and the prefill of every bucket the traffic uses.
The window then serves whole waves until ``seconds`` have passed; each
wave is the same multiset of request sizes in a seeded order, so the work
does not depend on the seed.

``ContinuousEngine.run`` donates the slot cache it starts from, and that
buffer is the engine's own template (``_slot_cache0``), so a second
``run`` on one engine finds it deleted.  Before each wave the benchmark
gives the engine a fresh zeroed template from one compiled call.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import counts, gen, harness, reference, tracefile
from chipbench.spec import Cell

KV_ENGINE = {"unquantized": "fp32", "int8": "int8"}


class DecodeMeter:
    """Keeps, on the host, how many positions each slot attends to, from
    the inserts and decode steps the engine issues, and the least bytes
    of each decode step issued while the profiler runs."""

    def __init__(self, eng, profile: harness.Profile, param_bytes: int,
                 kv_token_bytes: int):
        self.profile = profile
        self.param_bytes, self.kv_token_bytes = param_bytes, kv_token_bytes
        self.fill = np.zeros(eng.slots, np.int64)
        self.step_bytes: List[int] = []
        decode, insert, prefill = eng._decode, eng._insert, eng._prefill_one

        def metered_insert(cache, src, slot, length):
            with profile.span("engine.insert"):
                self.fill[int(slot)] = int(length)
                return insert(cache, src, slot, length)

        def metered_decode(params, cache, tokens, live):
            with profile.span("engine.decode"):
                alive = np.asarray(live)
                self.fill[alive] += 1
                if profile.active:
                    self.step_bytes.append(counts.decode_step_bytes(
                        param_bytes, self.fill[alive], kv_token_bytes))
                return decode(params, cache, tokens, live)

        def spanned_prefill(params, prompt):
            with profile.span("engine.prefill"):
                return prefill(params, prompt)

        eng._insert, eng._decode = metered_insert, metered_decode
        eng._prefill_one = spanned_prefill


def _requests(traffic, vocab: int, seed: int, index: int, first_uid: int):
    from repro.serve import Request
    return [Request(first_uid + i, prompt, budget) for i, (prompt, budget)
            in enumerate(gen.wave(traffic, vocab, seed, index))]


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float, *,
        require_tpu: bool = True) -> Dict[str, Any]:
    """One run of a serving cell; returns the result line's fields."""
    import jax
    import jax.numpy as jnp

    from repro.models import cast_params
    from repro.serve import ContinuousEngine

    cfg, traffic = cell.config, cell.traffic
    devices = harness.chips_for(cell, require_tpu)
    plan, mesh = harness.make_mesh(traffic, devices)
    model = harness.program_model(cell)
    weight_dtype = jnp.dtype(traffic["weight_dtype"])
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: cast_params(model.init(k), weight_dtype))(
            jax.random.key(seed))
    eng = ContinuousEngine(model, plan, mesh, slots=traffic["slots"],
                           max_len=traffic["max_len"],
                           kv_dtype=KV_ENGINE[traffic["kv_cache"]])
    with jax.set_mesh(mesh):
        fresh = jax.jit(lambda: model.init_slot_cache(
            eng.slots, eng.max_len, kv_dtype=eng.kv_dtype))
    vocab = cfg["vocab_size"]
    profile = harness.Profile(cell.name, trace)

    def serve(reqs, first: bool = False):
        if not first:             # the last run donated the template
            eng._slot_cache0 = None
            with jax.set_mesh(mesh):
                eng._slot_cache0 = fresh()
        return eng.run(params, reqs)

    warm = _requests(traffic, vocab, seed, 0, 0)
    serve(warm, first=True)       # compiles every shape the waves use
    meter = None
    if trace:
        param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        kv_itemsize = jnp.dtype(cfg["compute_dtype"]).itemsize \
            if traffic["kv_cache"] == "unquantized" else 1
        meter = DecodeMeter(eng, profile, param_bytes,
                            cell.family.program.kv_bytes_per_token(
                                cfg, kv_itemsize))

    t_open = time.perf_counter()
    setup_s = t_open - t0
    asked: Dict[int, Tuple[np.ndarray, int]] = {}
    served: Dict[int, np.ndarray] = {}
    n_tokens, waves, traced = 0, 0, []
    while not waves or time.perf_counter() - t_open < seconds:
        reqs = _requests(traffic, vocab, seed, 1 + waves, len(asked))
        asked.update((r.uid, (r.prompt, r.max_new)) for r in reqs)
        if waves == 0:
            profile.start()
        t_wave = time.perf_counter()
        with profile.span("serve.wave"):
            res = serve(reqs)
        profile.stop()
        print(f"wave {waves}: {len(reqs)} requests, "
              f"{res['stats'].n_tokens} tokens in "
              f"{time.perf_counter() - t_wave:.3f} s", file=sys.stderr)
        served.update((u, np.asarray(t)) for u, t in res["outputs"].items())
        if waves == 0:
            traced = [(len(r.prompt), len(res["outputs"].get(r.uid, ())))
                      for r in reqs]
        n_tokens += res["stats"].n_tokens
        waves += 1
    t_close = time.perf_counter()
    device = harness.device_record(devices)
    step_bytes = meter.step_bytes if meter else []
    del eng, params, fresh, serve, meter
    gc.collect()

    failed = [u for u, (_, budget) in asked.items()
              if u not in served or len(served[u]) != budget]
    out: Dict[str, Any] = {
        "attempted": len(asked), "failed": len(failed), "device": device,
        "setup_s": setup_s,
        "serve_tokens_per_s": n_tokens / (t_close - t_open),
    }
    if trace and profile.path:
        ctx = harness.trace_context(
            cell, profile.path, devices, "serve.wave", kind="serve",
            model=cfg, decode_bytes=step_bytes, traced_requests=traced)
        out["layer_metrics"] = harness.layer_metrics(cell, ctx)
        out["device"].update(harness.traced_device(ctx))
        out["breakdown"] = tracefile.breakdown(ctx.trace, ctx.lo, ctx.hi)

    sample = _check_sample(asked, served, traffic["check_requests"], seed)
    gaps = reference.served_gaps(cell.family, cfg, seed,
                                 traffic["weight_dtype"], sample)
    out["readings"] = {"served_gap": max(gaps) if gaps else float("inf"),
                       "unfinished": float(len(failed))}
    out["sample"] = sample
    return out


def _check_sample(asked, served, n: int, seed: int):
    """A seeded sample of the finished requests, the longest among them:
    (prompt, served tokens) each."""
    done = sorted(u for u in asked if u in served)
    if not done:
        return []
    longest = max(done, key=lambda u: len(asked[u][0]) + len(served[u]))
    rest = [u for u in done if u != longest]
    pick = gen.rng(seed, 3).choice(len(rest), min(n - 1, len(rest)),
                                   replace=False)
    return [(asked[u][0], served[u]) for u in [longest] +
            [rest[i] for i in sorted(pick)]]
