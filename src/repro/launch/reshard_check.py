"""Cross-plan reshard / chaos-recovery parity check (subprocess JSON
checker, used by tests/test_reshard.py and benchmarks/chaos_bench.py).

Two modes, one JSON report on stdout:

  * **place** (default): train a few steps under a SOURCE plan on a line
    topology of single-GPU sites, checkpoint, then ``reshard_checkpoint``
    onto a DESTINATION (plan x placement x stage_layers) layout.  Checks
    (docs/elasticity.md):
      - every resharded leaf — params AND AdamW moments — is bit-exact
        against the host-side reference re-placement
        (``repro.train.reshard.reshard_state``);
      - one further train step under the destination from the resharded
        state produces exactly the loss of a control that restored the
        same checkpoint without the reshard machinery;
      - the source plan's own continuation loss is reported for
        cross-plan comparison.

        PYTHONPATH=src python -m repro.launch.reshard_check \\
            --src-plan zero2 --src-sites 0,1 --dst-plan fsdp --dst-sites 0

  * **chaos** (``--chaos``): the pinned recovery gate — a two-site
    Pipeshard run is killed mid-epoch (``kill_site_at``), replanned onto
    the survivor, resharded, resumed.  Checks the resharded optimizer
    state is bit-exact vs the host reference AND the post-recovery loss
    sequence matches a single-site control started from the same
    checkpoint exactly.

Must run in its own process: ``--devices``/site count forces the XLA
host platform device count, which locks at first jax init.
"""
import argparse
import json
import os
import tempfile


def _sites(spec: str):
    return tuple(int(x) for x in spec.split(",") if x.strip() != "")


def _split(spec):
    return None if not spec else tuple(int(x) for x in spec.split(","))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2m")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2,
                    help="source-run steps before the checkpoint")
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    # place mode
    ap.add_argument("--src-plan", default="zero2")
    ap.add_argument("--src-sites", default="0,1")
    ap.add_argument("--src-order", default="")
    ap.add_argument("--src-layers", default="",
                    help="source stage_layers, e.g. 2,2 (pipeline only)")
    ap.add_argument("--src-schedule", default="gpipe")
    ap.add_argument("--dst-plan", default="fsdp")
    ap.add_argument("--dst-sites", default="0")
    ap.add_argument("--dst-order", default="")
    ap.add_argument("--dst-layers", default="")
    ap.add_argument("--dst-schedule", default="gpipe")
    # chaos mode
    ap.add_argument("--chaos", action="store_true")
    ap.add_argument("--kill-step", type=int, default=3)
    ap.add_argument("--dead", default="1")
    ap.add_argument("--total-steps", type=int, default=6)
    ap.add_argument("--ckpt-every", type=int, default=2)
    args = ap.parse_args()

    src_sites, dst_sites = _sites(args.src_sites), _sites(args.dst_sites)
    n_sites = max([2] + [s + 1 for s in src_sites + dst_sites])
    from repro.launch import simulate_host_devices
    simulate_host_devices(n_sites)

    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.topology import Link, Site, line
    from repro.data import Loader, Tokenizer, build_dataset, \
        synthetic_wikipedia
    from repro.models import Model

    texts = list(synthetic_wikipedia(60, seed=args.seed))
    tok = Tokenizer.train(texts, 256)
    cfg = dataclasses.replace(get_config(args.arch).reduced(),
                              n_layers=args.layers,
                              vocab_size=tok.vocab_size)
    ds = build_dataset(texts, tok, seq_len=args.seq)
    loader = Loader(ds, global_batch=args.batch, seed=args.seed)
    model = Model(cfg)
    topo = line("elastic-line",
                [Site(("A30",), name=f"V{i + 1}") for i in range(n_sites)],
                [Link(20e-3, 3.0)] * (n_sites - 1))

    def leaves_equal(a, b):
        fa = [np.asarray(jax.device_get(x)) for x in jax.tree.leaves(a)]
        fb = [np.asarray(jax.device_get(x)) for x in jax.tree.leaves(b)]
        exact = all(x.dtype == y.dtype and np.array_equal(x, y)
                    for x, y in zip(fa, fb))
        diff = max((float(np.max(np.abs(
            x.astype(np.float64) - y.astype(np.float64))))
            if x.size else 0.0) for x, y in zip(fa, fb))
        return exact, diff

    if args.chaos:
        report = run_chaos(args, model, topo, loader, leaves_equal)
    else:
        report = run_place(args, model, topo, loader, leaves_equal,
                           src_sites, dst_sites)
    print(json.dumps(report))


def run_place(args, model, topo, loader, leaves_equal, src_sites,
              dst_sites):
    import jax

    from repro.configs.base import TrainConfig
    from repro.core.plans import Placement, get_plan
    from repro.launch.mesh import placement_mesh
    from repro.train import (reshard_checkpoint, reshard_state,
                             restore_checkpoint, train)
    from repro.train.reshard import state_templates

    def _place(sites, order, layers, schedule):
        return Placement(sites, _sites(order) if order else None,
                         _split(layers), schedule=schedule)

    src_plan = get_plan(args.src_plan)
    dst_plan = get_plan(args.dst_plan)
    src_place = _place(src_sites, args.src_order, args.src_layers,
                       args.src_schedule)
    dst_place = _place(dst_sites, args.dst_order, args.dst_layers,
                       args.dst_schedule)
    # one device per single-GPU site: device block k <-> placement.sites[k]
    devs = list(jax.devices())
    src_mesh = placement_mesh(topo, src_plan, src_place,
                              devices=[devs[i] for i in src_place.sites])
    dst_mesh = placement_mesh(topo, dst_plan, dst_place,
                              devices=[devs[i] for i in dst_place.sites])
    k = args.steps
    tcfg = TrainConfig(warmup_steps=1, total_steps=k + 1, seed=args.seed,
                       microbatches=args.micro)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        src_res = train(model, src_plan, src_mesh, tcfg, loader, steps=k,
                        log_every=0, ckpt_dir=ckpt_dir,
                        stage_layers=src_place.stage_layers,
                        schedule=src_place.schedule)
        ckpt = os.path.join(ckpt_dir, f"step_{k:08d}")

        # resharded restore vs host-side reference re-placement
        params_r, opt_r, step0 = reshard_checkpoint(
            ckpt, model, dst_plan, dst_mesh, placement=dst_place)
        p_like, o_like = state_templates(model)
        params_h, opt_h, _ = restore_checkpoint(ckpt, p_like, o_like)
        with jax.set_mesh(dst_mesh):
            params_ref, opt_ref = reshard_state(
                params_h, opt_h, dst_plan, model.cfg, dst_mesh)
        p_exact, p_diff = leaves_equal(params_r, params_ref)
        o_exact, o_diff = leaves_equal(opt_r, opt_ref)
        h_exact, _ = leaves_equal(params_r, params_h)

        # one further step under dst: resharded vs unresharded control.
        # Each train() donates its state buffers, and when src and dst
        # shardings coincide (e.g. a pure stage-order change) device_put
        # aliases the restored arrays — so every reuse gets a fresh
        # host copy.
        import numpy as np

        def host_copy(tree):
            return jax.tree.map(lambda x: np.array(x), tree)

        def one_step(params, opt):
            res = train(model, dst_plan, dst_mesh, tcfg, loader,
                        steps=k + 1, start_step=k, params=params,
                        opt_state=opt, log_every=0,
                        stage_layers=dst_place.stage_layers,
                        schedule=dst_place.schedule)
            return res.losses

        loss_resharded = one_step(params_r, opt_r)
        loss_control = one_step(host_copy(params_h),
                                host_copy(opt_h))
        # the source plan's own continuation (cross-plan comparison)
        src_cont = train(model, src_plan, src_mesh, tcfg, loader,
                         steps=k + 1, start_step=k,
                         params=host_copy(params_h),
                         opt_state=host_copy(opt_h), log_every=0,
                         stage_layers=src_place.stage_layers,
                         schedule=src_place.schedule)
    return {
        "mode": "place", "step": step0,
        "src": f"{args.src_plan}@{src_sites}",
        "dst": f"{args.dst_plan}@{dst_sites}",
        "params_bitexact": p_exact, "opt_bitexact": o_exact,
        "host_bitexact": h_exact,
        "max_param_diff": p_diff, "max_opt_diff": o_diff,
        "loss_resharded": loss_resharded, "loss_control": loss_control,
        "loss_src_continue": src_cont.losses,
        "src_losses": src_res.losses,
    }


def run_chaos(args, model, topo, loader, leaves_equal):
    import jax

    from repro.configs.base import TrainConfig
    from repro.core.plans import Placement, get_plan
    from repro.launch.mesh import placement_mesh
    from repro.train import (kill_site_at, reshard_checkpoint,
                             reshard_state, restore_checkpoint, train,
                             train_elastic)
    from repro.train.replan import placement_devices, site_device_blocks
    from repro.train.reshard import state_templates

    dead = _sites(args.dead)
    total = args.total_steps
    tcfg = TrainConfig(warmup_steps=1, total_steps=total, seed=args.seed,
                       microbatches=args.micro)
    placement = Placement((0, 1))
    with tempfile.TemporaryDirectory() as ckpt_dir:
        run = train_elastic(
            model, topo, "pipeshard", placement, tcfg, loader,
            steps=total, ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every,
            on_step_failure=kill_site_at(args.kill_step, dead),
            log_every=0, log_fn=lambda s: None)
        rp = run.replan
        ckpt = os.path.join(ckpt_dir, f"step_{run.resumed_from:08d}")
        plan_c = get_plan(rp.technique)
        blocks = site_device_blocks(topo)
        mesh_c = placement_mesh(rp.topology, plan_c, rp.placement,
                                devices=placement_devices(
                                    blocks, rp.sites_old))
        # bit-exactness of the resharded state vs the host reference
        params_r, opt_r, _ = reshard_checkpoint(
            ckpt, model, plan_c, mesh_c, placement=rp.placement)
        p_like, o_like = state_templates(model)
        params_h, opt_h, _ = restore_checkpoint(ckpt, p_like, o_like)
        with jax.set_mesh(mesh_c):
            params_ref, opt_ref = reshard_state(
                params_h, opt_h, plan_c, model.cfg, mesh_c)
        p_exact, p_diff = leaves_equal(params_r, params_ref)
        o_exact, o_diff = leaves_equal(opt_r, opt_ref)
        # single-site control from the same checkpoint: the post-recovery
        # loss sequence must match it exactly
        control = train(model, plan_c, mesh_c, tcfg, loader, steps=total,
                        start_step=run.resumed_from, params=params_h,
                        opt_state=opt_h, log_every=0,
                        stage_layers=rp.placement.stage_layers,
                        schedule=rp.placement.schedule)
    return {
        "mode": "chaos", "failed": run.failed,
        "kill_step": args.kill_step, "dead": list(dead),
        "technique": rp.technique, "sites_old": list(rp.sites_old),
        "resumed_from": run.resumed_from, "steps_lost": run.steps_lost,
        "params_bitexact": p_exact, "opt_bitexact": o_exact,
        "max_param_diff": p_diff, "max_opt_diff": o_diff,
        "losses_pre": run.pre.losses, "losses_post": run.result.losses,
        "losses_control": control.losses,
        "search_s": run.search_s, "reshard_s": run.reshard_s,
        "recovery_s": run.recovery_s,
    }


if __name__ == "__main__":
    main()
