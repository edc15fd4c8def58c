"""Benchmark: continuous-batching serving (ISSUE 10) — a million-request
trace through the analytic serving model, a live bit-exactness smoke on
this host, and the topology-aware replica-placement winner map,
aggregated into the repo-root ``BENCH_10.json`` (the BENCH_6..9
perf-trajectory family).

Three sections:

  1. trace — Philox-seeded Poisson arrivals (deterministic by ``SEED``,
     independent of platform) drive 10^6 simulated requests with a
     mixed generation-length distribution (90% short / 10% long) through
     two queueing models priced by the cost model's decode/prefill
     times: *continuous* (every slot is an independent server — freed
     the step its request finishes) vs *fixed-batch* (the whole batch
     holds until its longest member finishes, the PR-5 ``Engine``
     discipline).  Two arrival regimes: an overloaded one measures
     goodput (the ISSUE gate: continuous >= 2x fixed on the mixed
     trace), a moderate one measures TTFT p50/p99.
  2. live — the tiny-model smoke: ``ContinuousEngine`` on this host's
     CPU backend, per-request greedy tokens checked bit-identical to
     per-length-group fixed ``Engine`` runs, plus measured tokens/s and
     slot occupancy.
  3. placement — the pinned ``lan2+far`` scenario (two A30 sites at
     0.2 ms LAN + one 80 ms away, full llama3.2-3b pricing, load at 50%
     of a single site's capacity): the winner map must give the
     high-latency site its own local replica while the LAN pair shares
     one.

Approximation, stated once: the continuous trace model treats each slot
as an independent server, ignoring the lockstep decode step (a freed
slot is re-filled on the next step boundary, at most one step late —
<2% of a short request's service time here).

Exit code = number of failed claim checks.
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

from benchmarks.sweep_common import write_outputs

SEED = 10
N_REQUESTS = 1_000_000
SMOKE_REQUESTS = 20_000
SLOTS = 8
PROMPT_LEN = 256
SHORT_GEN, LONG_GEN = 16, 512
LONG_FRAC = 0.1
#: arrival-rate multiples of continuous capacity for the two regimes
OVERLOAD, MODERATE = 1.4, 0.6


# --------------------------------------------------------------- #
# section 3 first: the pinned placement scenario also prices the
# decode/prefill seconds the trace simulation runs on
def pinned_scenario():
    """The ``lan2+far`` serving scenario: 4xA30 sites, two of them
    0.2 ms apart on a 10 Gb/s LAN, the third 80 ms away on 1 Gb/s."""
    from repro.configs import get_config
    from repro.core.search import PlanSearch
    from repro.core.topology import Link, Site, line
    from repro.serve.placement import decode_workload

    cfg = get_config("llama3.2-3b")
    topo = line("lan2+far",
                [Site(("A30",) * 4, name="S0"),
                 Site(("A30",) * 4, name="S1"),
                 Site(("A30",) * 4, name="S2")],
                [Link(0.2e-3, 10.0), Link(80e-3, 1.0)])
    return PlanSearch(decode_workload(cfg, slots=SLOTS), topo)


def placement_section(print_fn=print) -> dict:
    """Run the replica-placement pass on the pinned scenario and check
    the winner map: far site local, LAN pair pooled."""
    from repro.serve.placement import _price_group, place_replicas

    search = pinned_scenario()
    topo = search.topology
    single, _ = _price_group(search, topo, [0], [0.0, 0.0, 0.0],
                             slots=SLOTS, prompt_len=PROMPT_LEN,
                             gen_len=SHORT_GEN * 4)
    service_s = single.prefill_s + SHORT_GEN * 4 * single.decode_step_s
    capacity_rps = SLOTS / service_s
    rates = [0.5 * capacity_rps] * topo.n_sites
    plan = place_replicas(search, rates, slots=SLOTS,
                          prompt_len=PROMPT_LEN, gen_len=SHORT_GEN * 4)
    far_local = (2,) in plan.groups
    pair_shared = any(0 in g and 1 in g for g in plan.groups)
    print_fn(f"placement: groups {plan.groups}, "
             f"mean latency {plan.mean_latency_s * 1e3:.1f} ms")
    for r in plan.replicas:
        print_fn(f"  serves={r.serves} plan={r.plan_key} "
                 f"x{r.n_instances} rho={r.rho:.3f} "
                 f"wait={r.wait_s * 1e3:.2f}ms")
    return {
        "scenario": topo.name,
        "rates_rps": [round(x, 3) for x in rates],
        "groups": [list(g) for g in plan.groups],
        "replicas": [{
            "serves": list(r.serves),
            "plan": r.plan_key,
            "n_instances": r.n_instances,
            "rho": round(r.rho, 4),
            "wait_ms": round(r.wait_s * 1e3, 3),
        } for r in plan.replicas],
        "mean_latency_ms": round(plan.mean_latency_s * 1e3, 3),
        "single_site": {
            "plan": single.plan_key,
            "decode_step_ms": round(single.decode_step_s * 1e3, 4),
            "prefill_ms": round(single.prefill_s * 1e3, 2),
            "capacity_rps": round(capacity_rps, 3),
        },
        "far_site_local": far_local,
        "lan_pair_shared": pair_shared,
    }


# --------------------------------------------------------------- #
def make_trace(n: int, lam_rps: float) -> tuple:
    """Deterministic Poisson arrivals + mixed generation lengths.

    Philox is counter-based, so the same ``SEED`` reproduces the same
    million-request trace on any platform, in two independent streams
    (arrivals / lengths).
    """
    arr_rng = np.random.Generator(np.random.Philox(key=SEED))
    len_rng = np.random.Generator(np.random.Philox(key=SEED + 1))
    arrivals_s = np.cumsum(arr_rng.exponential(1.0 / lam_rps, n))
    gen_len = np.where(len_rng.random(n) < LONG_FRAC, LONG_GEN, SHORT_GEN)
    return arrivals_s, gen_len.astype(np.int64)


def sim_continuous(arrivals_s, gen_len, *, step_s: float,
                   prefill_s: float, slots: int = SLOTS) -> dict:
    """c-server FCFS queue: each slot serves one request and frees the
    moment it finishes (heap of slot-free times)."""
    free = [0.0] * slots
    heapq.heapify(free)
    ttft_s = np.empty(len(arrivals_s))
    busy_s = 0.0
    finish_s = 0.0
    for i in range(len(arrivals_s)):
        start = max(arrivals_s[i], heapq.heappop(free))
        service_s = prefill_s + gen_len[i] * step_s
        busy_s += service_s
        done = start + service_s
        ttft_s[i] = start + prefill_s - arrivals_s[i]
        finish_s = max(finish_s, done)
        heapq.heappush(free, done)
    makespan_s = finish_s - arrivals_s[0]
    return {
        "goodput_tok_s": float(gen_len.sum() / makespan_s),
        "ttft_s": ttft_s,
        "occupancy": float(busy_s / (slots * makespan_s)),
        "makespan_s": float(makespan_s),
    }


def sim_fixed(arrivals_s, gen_len, *, step_s: float, prefill_s: float,
              batch: int = SLOTS) -> dict:
    """Fixed-batch engine: consecutive arrivals form batches of ``batch``;
    the engine is one server and every batch holds all its slots for
    ``max(gen_len)`` steps (the pre-continuous ``Engine`` discipline)."""
    n = (len(arrivals_s) // batch) * batch
    arr = arrivals_s[:n].reshape(-1, batch)
    gl = gen_len[:n].reshape(-1, batch)
    batch_ready_s = arr[:, -1]                  # last member's arrival
    service_s = prefill_s + gl.max(axis=1) * step_s
    start_s = np.empty(len(arr))
    engine_free_s = 0.0
    for b in range(len(arr)):                   # engine-free recurrence
        start_s[b] = max(engine_free_s, batch_ready_s[b])
        engine_free_s = start_s[b] + service_s[b]
    ttft_s = (start_s[:, None] + prefill_s - arr).ravel()
    makespan_s = engine_free_s - arrivals_s[0]
    return {
        "goodput_tok_s": float(gl.sum() / makespan_s),
        "ttft_s": ttft_s,
        "makespan_s": float(makespan_s),
    }


def trace_section(n_requests: int, *, step_s: float, prefill_s: float,
                  print_fn=print) -> dict:
    """Both regimes, both engines, over the same deterministic trace."""
    service_mean_s = prefill_s + \
        (LONG_FRAC * LONG_GEN + (1 - LONG_FRAC) * SHORT_GEN) * step_s
    capacity_rps = SLOTS / service_mean_s
    out = {"n_requests": n_requests,
           "mix": {"short_gen": SHORT_GEN, "long_gen": LONG_GEN,
                   "long_frac": LONG_FRAC},
           "step_ms": round(step_s * 1e3, 4),
           "prefill_ms": round(prefill_s * 1e3, 2)}
    for regime, mult in (("overload", OVERLOAD), ("moderate", MODERATE)):
        lam_rps = mult * capacity_rps
        arrivals_s, gen_len = make_trace(n_requests, lam_rps)
        cont = sim_continuous(arrivals_s, gen_len, step_s=step_s,
                              prefill_s=prefill_s)
        fixed = sim_fixed(arrivals_s, gen_len, step_s=step_s,
                          prefill_s=prefill_s)
        ratio = cont["goodput_tok_s"] / fixed["goodput_tok_s"]
        out[regime] = {
            "lam_rps": round(lam_rps, 3),
            "goodput_tok_s": {
                "continuous": round(cont["goodput_tok_s"], 2),
                "fixed": round(fixed["goodput_tok_s"], 2),
                "ratio": round(ratio, 3),
            },
            "ttft_s": {
                "continuous": {
                    "p50": round(float(np.percentile(cont["ttft_s"], 50)), 4),
                    "p99": round(float(np.percentile(cont["ttft_s"], 99)), 4),
                },
                "fixed": {
                    "p50": round(float(np.percentile(fixed["ttft_s"], 50)), 4),
                    "p99": round(float(np.percentile(fixed["ttft_s"], 99)), 4),
                },
            },
            "slot_occupancy": round(cont["occupancy"], 4),
        }
        print_fn(f"trace[{regime}]: lam {lam_rps:.1f} rps | goodput "
                 f"cont {cont['goodput_tok_s']:.0f} vs fixed "
                 f"{fixed['goodput_tok_s']:.0f} tok/s (x{ratio:.2f}) | "
                 f"cont TTFT p50/p99 "
                 f"{out[regime]['ttft_s']['continuous']['p50']:.3f}/"
                 f"{out[regime]['ttft_s']['continuous']['p99']:.3f} s | "
                 f"occ {cont['occupancy']:.2f}")
    return out


# --------------------------------------------------------------- #
def live_section(print_fn=print) -> dict:
    """Tiny-model smoke on this host: continuous vs fixed bit-exactness
    plus measured serving stats."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core.plans import get_plan
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serve import ContinuousEngine, Engine, Request

    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              vocab_size=512)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        params = model.init(jax.random.key(0))
    rng = np.random.default_rng(SEED)
    lens = [5, 9, 9, 13, 5, 7]
    prompts = [np.asarray(rng.integers(4, 400, (n,)), np.int32)
               for n in lens]
    max_new = 6
    plan = get_plan("data")

    ref = {}
    bylen = {}
    for i, p in enumerate(prompts):
        bylen.setdefault(len(p), []).append(i)
    for n, idxs in bylen.items():
        eng = Engine(model, plan, mesh, batch_size=len(idxs), max_len=64)
        out = eng.generate(params,
                           {"tokens": np.stack([prompts[i] for i in idxs])},
                           n_tokens=max_new)
        for row, i in enumerate(idxs):
            ref[i] = out["tokens"][row]

    ce = ContinuousEngine(model, plan, mesh, slots=3, max_len=64,
                          buckets=(8, 16, 32))
    res = ce.run(params, [Request(i, p) for i, p in enumerate(prompts)],
                 max_new=max_new, timing=True)
    bit_exact = all(
        res["outputs"][i].shape == ref[i].shape
        and bool(np.all(res["outputs"][i] == ref[i]))
        for i in range(len(prompts)))
    st = res["stats"]
    print_fn(f"live: bit-exact {bit_exact} | {st.n_tokens} tokens at "
             f"{st.tokens_per_s:.1f} tok/s | occupancy "
             f"{st.mean_occupancy:.2f}")
    return {
        "n_requests": len(prompts),
        "prompt_lens": lens,
        "max_new": max_new,
        "slots": 3,
        "bit_exact": bit_exact,
        "tokens_per_s": round(st.tokens_per_s, 2),
        "mean_occupancy": round(st.mean_occupancy, 3),
        "ttft_p50_s": round(float(np.percentile(
            sorted(st.ttft_s.values()), 50)), 4),
    }


def run(smoke: bool = False, live: bool = True, print_fn=print) -> int:
    """All three sections; writes ``benchmarks/out/serving_bench.*`` and
    the repo-root ``BENCH_10.json``.  Returns the failed-claim count."""
    placement = placement_section(print_fn=print_fn)
    n_requests = SMOKE_REQUESTS if smoke else N_REQUESTS
    trace = trace_section(
        n_requests,
        step_s=placement["single_site"]["decode_step_ms"] * 1e-3,
        prefill_s=placement["single_site"]["prefill_ms"] * 1e-3,
        print_fn=print_fn)
    live_rec = live_section(print_fn=print_fn) if live else None

    checks = {
        "goodput_ratio_ge_2":
            trace["overload"]["goodput_tok_s"]["ratio"] >= 2.0,
        "bit_exact": bool(live_rec["bit_exact"]) if live_rec else None,
        "far_site_local": placement["far_site_local"],
        "lan_pair_shared": placement["lan_pair_shared"],
    }
    n_fail = sum(1 for v in checks.values() if v is False)
    for name, ok in checks.items():
        if ok is False:
            print_fn(f"CLAIM-FAIL: {name}")

    bench = {
        "pr": 10,
        "source": "benchmarks/serving_bench.py",
        "seed": SEED,
        "smoke": smoke,
        "trace": trace,
        "live": live_rec,
        "placement": placement,
        "gates": checks,
    }
    path = os.path.join(_ROOT, "BENCH_10.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
        f.write("\n")
    print_fn(f"wrote {path} ({n_fail} claim failure(s))")

    md = ["# Continuous-batching serving bench", "",
          f"- trace: {n_requests} requests, goodput ratio "
          f"x{trace['overload']['goodput_tok_s']['ratio']} (gate >= 2)",
          f"- placement: {placement['groups']} on "
          f"{placement['scenario']}", ""]
    write_outputs(os.path.join(_ROOT, "benchmarks", "out"),
                  "serving_bench", bench, "\n".join(md),
                  print_fn=print_fn)
    return n_fail


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help=f"{SMOKE_REQUESTS} trace requests instead of "
                         f"{N_REQUESTS}")
    ap.add_argument("--no-live", action="store_true",
                    help="skip the live tiny-model smoke (analytic only)")
    args = ap.parse_args()
    sys.exit(run(smoke=args.smoke, live=not args.no_live))


if __name__ == "__main__":
    main()
