"""Benchmark: Pipeshard microbatch + schedule ablations.

Two modes:

  * ``--schedules`` (analytic, seconds — the CI gate with ``--smoke``):
    sweeps the microbatch count m for each pipeline schedule (GPipe /
    1F1B / interleaved, docs/schedules.md) over two scenarios and
    machine-checks the schedule claims:

      - **bubble**: gpt2m on a 3-site A30 metro line — the interleaved
        schedule's (S-1)/(v·m) bubble makes it the fastest pipeline at
        small m, and GPipe's m-in-flight stash blows the 24 GB budget
        at large m while 1F1B (min(S, m) in flight) keeps fitting.
      - **memory flip**: gpt2L (batch 52) on a 3-site RTX continental
        line at the paper's m=4 — GPipe OOMs, 1F1B fits, and the
        schedule-aware `PlanSearch` flips the winner from a 2-site Data
        fallback to Pipeshard-on-everything under 1F1B (the ISSUE-4
        acceptance scenario; `tests/test_search.py` pins it too).

    JSON + markdown land in ``benchmarks/out/`` for
    ``tools/render_figs.py``.

  * legacy XLA mode (no flag): sweeps n_micro for llama3.2-3b × train_4k
    on the multi-pod mesh and reports bubble fraction, pod-crossing
    ppermute bytes, and per-device memory per choice.  Heavy — one
    512-device compile per point:

        PYTHONPATH=src python -m benchmarks.pipeline_ablation
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


# --------------------------------------------------------------------- #
# analytic schedule ablation
# --------------------------------------------------------------------- #

SCHEDS = ("gpipe", "1f1b", "interleaved")


def _schedule_rows(wl_base, topo, micros):
    """Per (m, schedule): bubble, in-flight stash, memory, step time."""
    import dataclasses
    from repro.core.costmodel import (pipeline_bubble_fraction,
                                      pipeline_inflight_microbatches,
                                      technique_step_cost)
    n = topo.n_sites
    rows = []
    for m in micros:
        wl = dataclasses.replace(wl_base, microbatches=m)
        for sched in SCHEDS:
            c = technique_step_cost("pipeshard", wl, topo, schedule=sched)
            rows.append({
                "n_micro": m, "schedule": sched,
                "bubble": round(pipeline_bubble_fraction(sched, n, m), 4),
                "inflight": round(
                    pipeline_inflight_microbatches(sched, n, m), 2),
                "mem_gb": round(c.mem_required_gb, 2),
                "mem_avail_gb": round(c.mem_available_gb, 2),
                "fits": c.fits,
                "step_s": round(c.total_s, 4),
                "tflops": None if not c.fits else round(
                    wl.flops_per_step / c.total_s / 1e12, 2),
            })
    return rows


def _winners(wl_base, topo, micros):
    """Full-search winner per m, legacy (GPipe-only) vs schedule-aware."""
    import dataclasses
    from repro.core.search import PlanSearch
    out = []
    for m in micros:
        wl = dataclasses.replace(wl_base, microbatches=m)
        best = PlanSearch(wl, topo).best()
        legacy = PlanSearch(wl, topo, schedules=("gpipe",)).best()
        out.append({
            "n_micro": m,
            "winner": None if best is None else best.candidate.key,
            "winner_schedule": None if best is None
            else best.candidate.schedule,
            "winner_tflops": None if best is None
            else round(best.tflops, 2),
            "legacy_winner": None if legacy is None
            else legacy.candidate.key,
            "legacy_tflops": None if legacy is None
            else round(legacy.tflops, 2),
        })
    return out


def _check_schedule_claims(bubble_rows, mem_rows, mem_winners,
                           print_fn) -> int:
    """The machine-checked schedule claims; returns #failures."""
    fails = []
    by = lambda rows, m, s: next(r for r in rows
                                 if r["n_micro"] == m and
                                 r["schedule"] == s)
    ms = sorted({r["n_micro"] for r in bubble_rows})
    for m in ms:
        gp, il = by(bubble_rows, m, "gpipe"), by(bubble_rows, m,
                                                 "interleaved")
        f1b = by(bubble_rows, m, "1f1b")
        if not (il["bubble"] < gp["bubble"] == f1b["bubble"]):
            fails.append(f"bubble ordering broken at m={m}")
        if f1b["step_s"] != gp["step_s"]:
            fails.append(f"1f1b != gpipe step time at m={m}")
        if f1b["mem_gb"] > gp["mem_gb"]:
            fails.append(f"1f1b stashes more than gpipe at m={m}")
    # the schedule contest crosses over in m: at the smallest m the
    # (S-1)/(v·m) bubble buys more than the v-fold p2p costs, so
    # interleaved is the fastest pipeline; as m grows the bubble
    # amortizes away and GPipe/1F1B retake the lead
    m_lo, m_hi = min(ms), max(ms)
    if by(bubble_rows, m_lo, "interleaved")["step_s"] >= \
            by(bubble_rows, m_lo, "gpipe")["step_s"]:
        fails.append(f"interleaved not fastest at small m={m_lo}")
    if by(bubble_rows, m_hi, "interleaved")["step_s"] <= \
            by(bubble_rows, m_hi, "gpipe")["step_s"]:
        fails.append(f"no schedule crossover by m={m_hi}")
    # large m: gpipe's stash must eventually OOM while 1f1b still fits
    last = max(ms)
    if by(bubble_rows, last, "gpipe")["fits"] or \
            not by(bubble_rows, last, "1f1b")["fits"]:
        fails.append(f"no gpipe-OOM/1f1b-fits split at m={last}")
    # the memory-flip scenario at the paper's m=4 (small m, 3 stages)
    m4 = next((w for w in mem_winners if w["n_micro"] == 4), None)
    gp4, f1b4 = by(mem_rows, 4, "gpipe"), by(mem_rows, 4, "1f1b")
    if gp4["fits"] or not f1b4["fits"]:
        fails.append("memory scenario: gpipe should OOM at m=4 and "
                     "1f1b fit")
    if m4 is None or m4["winner_schedule"] != "1f1b" \
            or "pipeshard" not in (m4["winner"] or ""):
        fails.append(f"memory scenario: winner at m=4 is {m4} — "
                     f"expected a pipeshard#1f1b flip")
    elif m4["legacy_winner"] and "pipeshard" in m4["legacy_winner"]:
        fails.append("memory scenario: legacy search already picked "
                     "pipeshard — no flip to demonstrate")
    for f in fails:
        print_fn(f"CLAIM-FAIL: {f}")
    return len(fails)


def _md_rows(rows, keys, headers):
    from benchmarks.sweep_common import md_table
    return md_table(headers, [[str(r[k]) for k in keys] for r in rows])


def run_schedules(print_fn=print, smoke: bool = False,
                  out: str = None) -> int:
    """Analytic schedule ablation; returns #failed claims."""
    from benchmarks.sweep_common import write_outputs
    from repro.configs import get_config
    from repro.core.costmodel import paper_workload
    from repro.core.topology import Link, Site, line

    t0 = time.perf_counter()
    # fully analytic, so smoke and full share the grid; --smoke only
    # switches the output stem (CI never clobbers the committed full
    # artifacts render_figs.py draws from)
    micros = (1, 2, 4, 8, 16)
    a30 = line("a30line3",
               [Site(("A30", "A30"), name=f"S{i}") for i in range(3)],
               [Link(0.1e-3, 3.0)] * 2)
    rtx = line("rtx3",
               [Site(("RTX", "RTX"), name=f"S{i}") for i in range(3)],
               [Link(57.4e-3, 3.0)] * 2)
    wl_bubble = paper_workload(get_config("gpt2m"))
    wl_mem = paper_workload(get_config("gpt2L"), global_batch=52)

    bubble_rows = _schedule_rows(wl_bubble, a30, micros)
    mem_micros = sorted(set(micros) | {3, 4})
    mem_rows = _schedule_rows(wl_mem, rtx, mem_micros)
    mem_winners = _winners(wl_mem, rtx, mem_micros)
    n_fail = _check_schedule_claims(bubble_rows, mem_rows, mem_winners,
                                    print_fn)
    elapsed = time.perf_counter() - t0
    mode = "smoke" if smoke else "full"

    keys = ("n_micro", "schedule", "bubble", "inflight", "mem_gb",
            "fits", "step_s", "tflops")
    headers = ("m", "schedule", "bubble", "in-flight", "mem GB", "fits",
               "step s", "TFLOP/s")
    md = "\n".join([
        "# Pipeline schedule ablation", "",
        "Schedules reorder ticks, not math (docs/schedules.md): GPipe "
        "and 1F1B share the `(S-1)/m` bubble but 1F1B stashes only "
        "`min(S, m)` microbatches; the interleaved schedule divides the "
        "bubble by its v virtual stages and pays v crossings of every "
        "stage boundary.", "",
        "## Bubble scenario — gpt2m, 3-site A30 metro line "
        "(0.1 ms edges)", "",
        _md_rows(bubble_rows, keys, headers),
        "## Memory scenario — gpt2L (batch 52), 3-site RTX continental "
        "line (57.4 ms edges)", "",
        _md_rows(mem_rows, keys, headers),
        "## Search winners on the memory scenario", "",
        _md_rows(mem_winners,
                 ("n_micro", "winner", "winner_tflops", "legacy_winner",
                  "legacy_tflops"),
                 ("m", "schedule-aware winner", "TFLOP/s",
                  "GPipe-only winner", "TFLOP/s")),
        f"At the paper's m=4 the schedule-aware search flips the winner "
        f"from the GPipe-only fallback to `pipeshard#1f1b` on all three "
        f"sites — GPipe's 4-microbatch stash misses the 24 GB budget "
        f"that 1F1B's min(S, m)=3 makes.", ""])
    record = {"mode": mode, "elapsed_s": round(elapsed, 2),
              "scenarios": {
                  "bubble": {"model": "gpt2m", "topology": "a30line3",
                             "latency_ms": 0.1, "rows": bubble_rows},
                  "memory": {"model": "gpt2L", "topology": "rtx3",
                             "latency_ms": 57.4, "rows": mem_rows,
                             "winners": mem_winners}}}
    if out is None:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "out")
    write_outputs(out, f"pipeline_schedules_{mode}", record, md,
                  print_fn=print_fn)
    print_fn(f"# schedule ablation ({mode}): {len(bubble_rows)} + "
             f"{len(mem_rows)} rows, {elapsed:.1f}s, {n_fail} failures")
    return n_fail


# --------------------------------------------------------------------- #
# legacy heavy mode (512 forced host devices, one compile per point)
# --------------------------------------------------------------------- #

def run(print_fn=print, micros=(2, 4, 8, 16)) -> int:
    import jax

    from repro.configs import get_config, get_shape
    from repro.configs.base import TrainConfig
    from repro.core.pipeline import pipeline_mesh
    from repro.core.plans import get_plan
    from repro.launch import roofline as rl
    from repro.launch.dryrun import build_step
    from repro.launch.mesh import make_production_mesh
    from repro.models import Model

    cfg = get_config("llama3.2-3b")
    shape = get_shape("train_4k")
    plan = get_plan("pipeshard")
    n_stages = 2
    print_fn("# Pipeshard microbatch ablation "
             "(llama3.2-3b x train_4k x 2x16x16, 2 stages)")
    print_fn("n_micro,bubble_frac,dcn_gb_per_dev,ici_gb_per_dev,"
             "collective_s,mem_gb_per_dev,compile_s")
    rows = []
    for m in micros:
        base = make_production_mesh(multi_pod=True)
        mesh = pipeline_mesh(base, n_stages)
        model = Model(cfg)
        tcfg = TrainConfig(microbatches=m)
        t0 = time.time()
        with jax.set_mesh(mesh):
            step, args, acost = build_step(model, plan, mesh, cfg, shape,
                                           tcfg)
            compiled = step.lower(*args).compile()
        roof = rl.from_compiled(
            compiled, arch=cfg.name, shape=shape.name, mesh_name="2x16x16",
            plan=f"pipeshard_m{m}", analytic=acost, n_devices=512,
            crosses_pod=True)
        bubble = (n_stages - 1) / (m + n_stages - 1)
        row = dict(n_micro=m, bubble=bubble,
                   dcn_gb=roof.dcn_bytes_per_device / 1e9,
                   ici_gb=roof.collective_bytes_per_device / 1e9,
                   coll_s=roof.collective_s,
                   mem_gb=roof.memory_per_device_bytes / 1e9,
                   compile_s=time.time() - t0)
        rows.append(row)
        print_fn(f"{m},{bubble:.3f},{row['dcn_gb']:.3f},{row['ici_gb']:.2f},"
                 f"{row['coll_s']:.2f},{row['mem_gb']:.2f},"
                 f"{row['compile_s']:.0f}")
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "pipeline_ablation.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--schedules", action="store_true",
                    help="analytic GPipe/1F1B/interleaved ablation "
                         "(seconds; the CI gate with --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="[--schedules only] write *_smoke artifacts "
                         "(same analytic grid) so CI never clobbers "
                         "the committed full outputs")
    ap.add_argument("--out", default=None,
                    help="output dir (default: benchmarks/out)")
    args = ap.parse_args(argv)
    if args.schedules:
        return run_schedules(smoke=args.smoke, out=args.out)
    # heavy XLA mode: the simulated device count must precede any jax init
    from repro.launch import simulate_host_devices
    simulate_host_devices(512)
    return run()


if __name__ == "__main__":
    sys.exit(main())
