"""Numerical plan-equivalence check (used by tests/test_plans.py).

Runs a tiny model one train step under each plan on a small host-device
mesh and prints the losses as JSON: every registered plan (``--plans
all`` derives the list from ``repro.core.plans.PLANS`` — data, zero2,
shard, shard_zero, pipeshard, fsdp) must compute the same mathematical
update, so losses (and a probe-param norm) must agree.

Must run in its own process: ``--devices`` forces the XLA host platform
device count, which locks at first jax init.
"""
import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--plans", default="all",
                    help="comma-separated repro.core.plans.PLANS keys, or "
                         "'all' for every registered plan")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args()

    from repro.launch import simulate_host_devices
    simulate_host_devices(args.devices)

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.core.pipeline import pipeline_mesh
    from repro.core.plans import PLANS, get_plan
    from repro.core.steps import build_train_step
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.models.registry import abstractify, input_specs
    from repro.optim import init_adamw

    # "all" derives from the plan registry (imported only after the
    # XLA_FLAGS device-count override above) instead of a hand-kept list
    plan_names = list(PLANS) if args.plans == "all" \
        else args.plans.split(",")

    cfg = get_config(args.arch).reduced()
    cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if cfg.hybrid_attn_every:
        cfg = dataclasses.replace(cfg, hybrid_attn_every=max(
            1, args.layers // 4))
    model = Model(cfg)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=10,
                       microbatches=4, remat=True)
    shape = ShapeConfig("t", args.seq, args.batch, "train")
    rng = np.random.default_rng(0)
    batch = input_specs(cfg, shape, abstract=False, rng=rng)

    n = args.devices
    assert n % 4 == 0
    base = make_mesh((n // 4, 2, 2), ("pod", "data", "model"))

    results = {}
    for plan_name in plan_names:
        plan = get_plan(plan_name)
        mesh = pipeline_mesh(base, 2) if plan.pipeline else base
        with jax.set_mesh(mesh):
            params = model.init(jax.random.key(0))
            opt = init_adamw(params)
            p_shapes = abstractify(params)
            b_shapes = abstractify(batch)
            step, sh = build_train_step(model, plan, mesh, tcfg,
                                        params_shapes=p_shapes,
                                        batch_shapes=b_shapes)
            params = jax.device_put(params, sh["params"])
            opt = jax.device_put(opt, sh["opt"])
            b = jax.device_put(batch, sh["batch"])
            losses = []
            for _ in range(args.steps):
                params, opt, metrics = step(params, opt, b)
                losses.append(float(metrics["loss"]))
            # probe: norm of all params after updates
            pnorm = float(jnp.sqrt(sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for x in jax.tree.leaves(params))))
        results[plan_name] = {"losses": losses, "param_norm": pnorm}
    print(json.dumps(results))


if __name__ == "__main__":
    main()
