"""Elastic re-planning launcher: recover a run after sites die.

Two modes (docs/elasticity.md):

  * recovery (default): an existing checkpoint + a degraded topology —
    re-run the plan search over the survivors, reshard the checkpoint
    onto the winner, resume to --steps:

        PYTHONPATH=src python -m repro.launch.replan \\
            --ckpt-dir /tmp/run --gpus "A30,A30;T4,T4" --dead 1 \\
            --arch gpt2 --reduced --devices 4 --steps 20

  * chaos demo (--kill-step K): self-contained end-to-end drill — train
    from scratch on the full topology, kill --dead at step K through the
    injection hook, replan, reshard, resume.  What
    ``benchmarks/chaos_bench.py`` runs as a subprocess.

The last stdout line is a JSON summary (technique, surviving sites,
steps lost, recovery seconds) for scripted consumers.
"""
import argparse
import json


def parse_gpus(spec: str):
    """``"A30,A30;T4,T4"`` -> per-site GPU tuples (';' between sites)."""
    sites = [tuple(g.strip() for g in s.split(",") if g.strip())
             for s in spec.split(";") if s.strip()]
    if not sites:
        raise ValueError(f"empty --gpus spec {spec!r}")
    return sites


def build_cli_topology(kind: str, gpus: str, lat_ms: float,
                       wan_gbps: float):
    """An N-site topology from CLI args (full / ring / line / hub)."""
    from repro.core.topology import (Link, Site, fully_connected, hub,
                                     line, ring)
    site_gpus = parse_gpus(gpus)
    sites = [Site(g, name=f"V{i + 1}") for i, g in enumerate(site_gpus)]
    edge = Link(lat_ms * 1e-3, wan_gbps)
    name = f"{kind}{len(sites)}"
    if kind == "full":
        return fully_connected(name, sites, edge)
    if kind == "ring":
        return ring(name, sites, [edge] * len(sites))
    if kind == "line":
        return line(name, sites, [edge] * (len(sites) - 1))
    if kind == "hub":
        return hub(name, sites[0], sites[1:], edge)
    raise ValueError(f"unknown --kind {kind!r}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--gpus", default="A30,A30;T4,T4",
                    help="per-site GPUs: ';' between sites, ',' within")
    ap.add_argument("--kind", default="full",
                    choices=("full", "ring", "line", "hub"))
    ap.add_argument("--latency-ms", type=float, default=20.2)
    ap.add_argument("--wan-gbps", type=float, default=3.0)
    ap.add_argument("--dead", default="1",
                    help="comma-separated dead site indices (0-based)")
    ap.add_argument("--kill-step", type=int, default=-1,
                    help=">= 0: chaos-demo mode — train from scratch and "
                         "inject the failure at this step")
    ap.add_argument("--plan", default="auto",
                    help="initial plan for the chaos demo ('auto' = "
                         "search the full topology)")
    ap.add_argument("--arch", default="gpt2m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (0 = use real devices)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--docs", type=int, default=200)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.devices:
        from repro.launch import simulate_host_devices
        simulate_host_devices(args.devices)

    import dataclasses
    import time

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.core.costmodel import Workload
    from repro.core.plans import Placement, get_plan
    from repro.core.search import PlanSearch
    from repro.data import Loader, Tokenizer, build_dataset, \
        synthetic_wikipedia
    from repro.launch.mesh import placement_mesh
    from repro.models import Model
    from repro.train import (kill_site_at, latest_checkpoint, replan,
                             reshard_checkpoint, train, train_elastic)
    from repro.train.replan import placement_devices, site_device_blocks

    topo = build_cli_topology(args.kind, args.gpus, args.latency_ms,
                              args.wan_gbps)
    dead = tuple(int(x) for x in args.dead.split(",") if x.strip())

    texts = list(synthetic_wikipedia(args.docs, seed=args.seed))
    tok = Tokenizer.train(texts, args.vocab)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, vocab_size=tok.vocab_size,
                              max_seq_len=max(cfg.max_seq_len, args.seq))
    ds = build_dataset(texts, tok, seq_len=args.seq)
    loader = Loader(ds, global_batch=args.batch, seed=args.seed)
    tcfg = TrainConfig(warmup_steps=max(args.steps // 10, 1),
                       total_steps=args.steps, seed=args.seed,
                       microbatches=args.microbatches)
    model = Model(cfg)
    wl = Workload(cfg, args.seq, args.batch, steps_per_epoch=args.steps,
                  microbatches=args.microbatches)

    print(f"{cfg.name} {cfg.param_count() / 1e6:.1f}M params on "
          f"{topo.name}: {topo.describe()}")

    if args.kill_step >= 0:
        # chaos-demo mode: full run with an injected failure
        if args.plan == "auto":
            search = PlanSearch(wl, topo, stage_balance="tflops")
            top = search.best()
            if top is None:
                raise SystemExit("no feasible plan on the full topology")
            technique = top.candidate.technique
            placement = search.placement(top.candidate)
        else:
            technique = args.plan
            placement = Placement(tuple(range(topo.n_sites)))
        print(f"initial plan: {technique}@{placement.sites}")
        run = train_elastic(
            model, topo, technique, placement, tcfg, loader,
            steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            on_step_failure=kill_site_at(args.kill_step, dead))
        summary = {
            "mode": "chaos", "failed": run.failed,
            "technique": run.replan.technique if run.replan else technique,
            "sites_old": list(run.replan.sites_old) if run.replan
            else list(placement.sites),
            "resumed_from": run.resumed_from,
            "steps_lost": run.steps_lost,
            "search_s": run.search_s, "reshard_s": run.reshard_s,
            "recovery_s": run.recovery_s,
            "final_loss": run.result.losses[-1] if run.result.losses
            else None,
        }
    else:
        # recovery mode: resume an existing checkpoint on the survivors
        ckpt = latest_checkpoint(args.ckpt_dir)
        if ckpt is None:
            raise SystemExit(f"no complete checkpoint in {args.ckpt_dir}")
        t0 = time.perf_counter()
        rp = replan(topo, dead, wl)
        blocks = site_device_blocks(topo)
        plan2 = get_plan(rp.technique)
        mesh2 = placement_mesh(rp.topology, plan2, rp.placement,
                               devices=placement_devices(
                                   blocks, rp.sites_old))
        t1 = time.perf_counter()
        params, opt, step0 = reshard_checkpoint(
            ckpt, model, plan2, mesh2, placement=rp.placement)
        reshard_s = time.perf_counter() - t1
        print(f"replanned: {rp.technique} on original sites "
              f"{rp.sites_old} ({rp.tflops:.2f} model-TFLOP/s); "
              f"resuming at step {step0}")
        res = train(model, plan2, mesh2, tcfg, loader, steps=args.steps,
                    start_step=step0, params=params, opt_state=opt,
                    ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                    stage_layers=rp.placement.stage_layers,
                    schedule=rp.placement.schedule,
                    log_every=max(args.steps // 10, 1))
        summary = {
            "mode": "recovery", "technique": rp.technique,
            "sites_old": list(rp.sites_old), "resumed_from": step0,
            "search_s": rp.search_s, "reshard_s": reshard_s,
            "recovery_s": time.perf_counter() - t0,
            "final_loss": res.losses[-1] if res.losses else None,
        }
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
