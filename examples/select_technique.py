"""Algorithm 1 (paper §IV-H) in action: pick the pretraining technique for
a model + cluster, three ways:

  1. analytically, over the paper's five FABRIC slices (cost model),
  2. live, probing epsilon-epochs of real training on host devices,
  3. beyond the paper: full PlanSearch over an N-site topology — site
     subsets and pipeline stage orders the two-VM algorithm can't express,
  4. live + topology: a searched heterogeneous Placement (uneven
     TFLOP-weighted stage split) probed end-to-end by a LiveProber on
     forced host devices — the probe realizes the exact staged mesh.

    PYTHONPATH=src python examples/select_technique.py --model gpt2m
    PYTHONPATH=src python examples/select_technique.py --live
    PYTHONPATH=src python examples/select_technique.py --topology edge3
    PYTHONPATH=src python examples/select_technique.py --live \\
        --topology line3 --devices 3 --balance tflops
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--model", default="gpt2m")
ap.add_argument("--live", action="store_true",
                help="probe with real epsilon-epoch training runs")
ap.add_argument("--topology",
                choices=["edge3", "ring3", "hub4", "line3", "lan3"],
                help="full PlanSearch over an example N-site topology "
                     "(with --live: probe the searched placement live)")
ap.add_argument("--devices", type=int, default=8)
ap.add_argument("--delta", type=float, default=0.1)
ap.add_argument("--balance", choices=["even", "tflops"], default="even",
                help="[--topology only] pipeline stage sizing: even "
                     "(paper-faithful) or TFLOP-weighted "
                     "(docs/topology-and-search.md)")
ap.add_argument("--exact", action="store_true",
                help="[--topology only] exhaustive PlanSearch "
                     "(no pruning)")
ap.add_argument("--techniques", choices=["paper", "all"], default="paper",
                help="technique pool: the paper's four, or 'all' to add "
                     "the shard_zero/fsdp specs (docs/cost-model.md) — "
                     "with --topology lan3 --model gpt2L the extended "
                     "pool finds a shard_zero winner the paper's "
                     "selector misses")
args = ap.parse_args()
if (args.balance != "even" or args.exact) and not args.topology:
    ap.error("--balance/--exact only apply to the --topology PlanSearch "
             "modes (Algorithm 1 probes the paper's fixed plan set)")
if args.techniques != "paper" and args.live:
    ap.error("--techniques all is analytic-only here (live probes of the "
             "extended pool go through launch.mesh.placement_mesh)")
if args.live and args.topology and args.topology != "line3":
    ap.error("--live --topology currently supports line3 (single-GPU "
             "sites, so the staged mesh fits forced host devices)")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
if args.live:
    from repro.launch import simulate_host_devices
    simulate_host_devices(args.devices)

from repro.configs import get_config
from repro.core.costmodel import PAPER_CLUSTERS, paper_workload
from repro.core.search import PlanSearch
from repro.core.selector import (CostModelProber, LiveProber,
                                 select_technique)
from repro.core.topology import Link, Site, hub, line, make_topology, ring


def analytic():
    wl = paper_workload(get_config(args.model))
    print(f"Algorithm 1 over the paper's clusters ({args.model}):")
    for name, cluster in PAPER_CLUSTERS.items():
        sel = select_technique(CostModelProber(wl, cluster),
                               delta=args.delta)
        probes = {k: (f"{v:.2f}" if v else "OOM")
                  for k, v in sel.probes.items()}
        print(f"  {name:11s} -> {sel.technique}@VMs{sel.vms}   "
              f"probes(TFLOP/s): {probes}")


EXAMPLE_TOPOLOGIES = {
    # two metro-adjacent sites + one transatlantic: the search spans the
    # cheap pair with Data — a subset the two-VM algorithm never probes.
    "edge3": lambda: make_topology(
        "edge3",
        [Site(("A30", "A30"), name="A"), Site(("A30", "A30"), name="B"),
         Site(("A30", "A30"), name="C")],
        {(0, 1): Link(0.5e-3, 3.0), (1, 2): Link(60e-3, 3.0),
         (0, 2): Link(100e-3, 3.0)}),
    # asymmetric ring: the best pipeline stage order crosses the two cheap
    # links and leaves the 120 ms edge as the un-crossed return path.
    "ring3": lambda: ring(
        "ring3", [Site(("A30", "A30"), name=n) for n in "ABC"],
        [Link(5e-3, 3.0), Link(5e-3, 3.0), Link(120e-3, 3.0)]),
    # hub-and-spoke: leaf↔leaf traffic relays through the hub (2 hops).
    "hub4": lambda: hub(
        "hub4", Site(("A30", "A30"), name="HUB"),
        [Site(("RTX", "RTX"), name=f"L{k}") for k in range(3)],
        Link(25e-3, 3.0)),
    # heterogeneous A30+T4 line with single-GPU sites: the TFLOP-weighted
    # balancer gives the T4 sites strictly fewer layers, and one host
    # device per site realizes the staged mesh under --live.
    "line3": lambda: line(
        "line3",
        [Site(("A30",), name="A"), Site(("T4",), name="B"),
         Site(("T4",), name="C")],
        [Link(20e-3, 3.0), Link(20e-3, 3.0)]),
    # memory-tight metro LAN: three 16GB T4 sites a campus apart.  With
    # --model gpt2L the replicated-state plans OOM and the extended pool
    # (--techniques all) finds the shard_zero hybrid the paper's
    # four-technique selector cannot even price (docs/cost-model.md).
    "lan3": lambda: line(
        "lan3", [Site(("T4", "T4"), name=n) for n in "ABC"],
        [Link(0.1e-3, 3.0), Link(0.1e-3, 3.0)]),
}


def topology_search():
    from repro.core.costmodel import ALL_TECHNIQUES, TECHNIQUES
    from repro.core.plans import get_plan
    from repro.launch.analytic import placement_degrees

    topo = EXAMPLE_TOPOLOGIES[args.topology]()
    wl = paper_workload(get_config(args.model))
    print(topo.describe())
    pool = ALL_TECHNIQUES if args.techniques == "all" else TECHNIQUES
    search = PlanSearch(wl, topo, stage_balance=args.balance,
                        prune=not args.exact, techniques=pool)
    ranked = search.search()
    print(f"\nPlanSearch over {len(ranked)} candidates ({args.model}, "
          f"{args.techniques} pool):")
    for s in ranked[:8]:
        perf = f"{s.tflops:.2f}" if s.feasible else "OOM"
        print(f"  {s.candidate.key:30s} {perf:>8s} TFLOP/s")
    best = search.best()
    alg1 = search.select(delta=args.delta, extended=False)
    if best is None:
        print("\nbest overall : none — every candidate OOMs on this "
              "topology (need more GPU memory)")
        print(f"Algorithm 1  : {alg1.technique}@VMs{alg1.vms}")
        return
    print(f"\nbest overall : {best.candidate.key} "
          f"({best.tflops:.2f} TFLOP/s)")
    print(f"Algorithm 1  : {alg1.technique}@VMs{alg1.vms} "
          f"(probe set restricted to the paper's)")
    if args.techniques == "all":
        ext = search.select(delta=args.delta, extended=True)
        print(f"Algorithm 1+ : {ext.technique}@VMs{ext.vms} "
              f"(extended probe set: +shard_zero/fsdp)")
        paper_best = PlanSearch(wl, topo, stage_balance=args.balance,
                                prune=not args.exact).best()
        if paper_best is not None and \
                best.tflops > (paper_best.tflops or 0):
            print(f"paper pool   : {paper_best.candidate.key} "
                  f"({paper_best.tflops:.2f} TFLOP/s) — the extended "
                  f"pool wins by "
                  f"{best.tflops / paper_best.tflops - 1:+.1%}")
    plan_name = "shard_zero" if best.candidate.technique == "shard" \
        else best.candidate.technique
    placement = search.placement(best.candidate)
    dp, tp, zdeg = placement_degrees(
        get_plan(plan_name), topo, placement, wl.global_batch)
    print(f"mesh degrees : dp={dp} tp={tp} zero={zdeg} over sites "
          f"{best.candidate.sites}")
    if placement.stage_layers is not None:
        print(f"stage layers : {placement.stage_layers} "
              f"(TFLOP-weighted; even would be "
              f"{wl.cfg.n_layers // placement.n_stages} per stage)")
    if placement.schedule != "gpipe":
        print(f"schedule     : {placement.schedule} "
              f"(docs/schedules.md)")


def live():
    """epsilon-epoch probes with real training on host devices: VM1 = first
    half of the mesh, VM2 = second half (the paper's two-VM shape)."""
    import dataclasses
    from repro.configs.base import TrainConfig
    from repro.core.plans import get_plan
    from repro.core.pipeline import pipeline_mesh
    from repro.data import (Loader, Tokenizer, build_dataset,
                            synthetic_wikipedia)
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.train import model_flops_per_step, train

    texts = list(synthetic_wikipedia(300, seed=0))
    tok = Tokenizer.train(texts, 1024)
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              n_layers=4, vocab_size=tok.vocab_size)
    ds = build_dataset(texts, tok, seq_len=64)

    def probe(technique, placement):
        plan = get_plan("shard_zero" if technique == "shard" else technique)
        both = placement is None or len(placement.sites) > 1
        n = args.devices if both else args.devices // 2
        base = make_mesh((max(n // 4, 1), 2, 2), ("pod", "data", "model"))
        mesh = pipeline_mesh(base, 2) if plan.pipeline else base
        loader = Loader(ds, global_batch=8, seed=0)
        res = train(Model(cfg), plan, mesh,
                    TrainConfig(warmup_steps=2, total_steps=10,
                                microbatches=4),
                    loader, steps=6, log_every=0)
        flops = model_flops_per_step(cfg, 8 * 64)
        tf = res.tflops(flops)
        where = "both" if both else f"V{placement.sites[0] + 1}"
        print(f"  probe {technique}@{where}: {tf:.4f} TFLOP/s")
        return tf

    sel = select_technique(LiveProber(probe), delta=args.delta)
    print(f"live selection: {sel.technique}@VMs{sel.vms}")


def live_topology():
    """A LiveProber-driven placement probe: search the heterogeneous
    line3 topology with TFLOP-weighted balancing, then *execute* the
    winning Pipeshard placement — pod blocks in stage order, uneven
    stage_layers pad-and-masked — on forced host devices (one device per
    single-GPU site)."""
    import dataclasses
    import jax
    from repro.configs.base import TrainConfig
    from repro.core.costmodel import Workload
    from repro.core.plans import get_plan
    from repro.data import (Loader, Tokenizer, build_dataset,
                            synthetic_wikipedia)
    from repro.launch.mesh import placement_pipeline_mesh
    from repro.models import Model
    from repro.train import model_flops_per_step, train

    topo = EXAMPLE_TOPOLOGIES[args.topology]()
    n_gpus = sum(len(s.gpus) for s in topo.sites)
    assert args.devices >= n_gpus, \
        f"--devices {args.devices} < {n_gpus} GPUs in {topo.name}"
    print(topo.describe())

    texts = list(synthetic_wikipedia(200, seed=0))
    tok = Tokenizer.train(texts, 1024)
    cfg = dataclasses.replace(get_config("gpt2m").reduced(),
                              n_layers=6, vocab_size=tok.vocab_size)
    ds = build_dataset(texts, tok, seq_len=64)
    wl = Workload(cfg, 64, 8, steps_per_epoch=1, microbatches=4)

    # analytic search proposes; the live probe disposes.  Probe the best
    # *all-site* pipeline — the placement that exercises every topology
    # link; under --balance tflops each site gets a weighted (uneven)
    # layer share.
    search = PlanSearch(wl, topo, stage_balance=args.balance,
                        techniques=("pipeshard",))
    best = next((s for s in search.search()
                 if len(s.candidate.sites) == topo.n_sites and s.feasible),
                None)
    if best is None:
        print(f"no feasible all-site pipeline on {topo.name} — "
              f"need more GPU memory")
        sys.exit(1)
    placement = search.placement(best.candidate)
    print(f"searched placement: {best.candidate.key} "
          f"stage_layers={placement.stage_layers} "
          f"schedule={placement.schedule}")

    def run_probe(technique, placement):
        mesh = placement_pipeline_mesh(topo, placement,
                                       devices=jax.devices()[:n_gpus])
        loader = Loader(ds, global_batch=8, seed=0)
        res = train(Model(cfg), get_plan(technique), mesh,
                    TrainConfig(warmup_steps=2, total_steps=10,
                                microbatches=4),
                    loader, steps=4, log_every=0,
                    stage_layers=placement.stage_layers,
                    schedule=placement.schedule)
        return res.tflops(model_flops_per_step(cfg, 8 * 64))

    prober = LiveProber(run_probe, n_sites=topo.n_sites)
    tf = prober.probe("pipeshard", placement)
    print(f"live probe {best.candidate.key}: "
          f"{'infeasible' if tf is None else f'{tf:.4f} TFLOP/s'}")
    if tf is None:
        sys.exit(1)


if __name__ == "__main__":
    if args.topology and args.live:
        live_topology()
    elif args.topology:
        topology_search()
    elif args.live:
        live()
    else:
        analytic()
