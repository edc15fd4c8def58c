"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
combination lowers AND compiles, and extract the roofline terms.

For each combination this builds the plan-sharded step (train_step for
train_4k, prefill_step for prefill_32k, serve_step for decode shapes —
ONE token against a seq_len KV cache), lowers it against
ShapeDtypeStruct inputs (zero allocation), compiles for the 16x16
single-pod mesh (and the 2x16x16 multi-pod mesh with --multi-pod) on 512
simulated CPU devices (``main`` sets them up before jax starts), prints
``compiled.memory_analysis()`` / ``cost_analysis()`` and writes the
roofline JSON consumed by benchmarks/ and EXPERIMENTS.md.
"""
import argparse
import json
import sys
import time
import traceback


def skip_reason(cfg, shape) -> str:
    """Documented skips (DESIGN.md §4)."""
    if shape.name == "long_500k":
        if cfg.family == "encdec":
            return ("whisper-small: full-attention enc-dec decoder; 500k-token "
                    "audio transcripts out of scope (DESIGN.md §4)")
        if not cfg.supports_long_context:
            return f"{cfg.name}: no sub-quadratic attention variant"
    return ""


def build_step(model, plan, mesh, cfg, shape, tcfg):
    """Returns (jitted fn, example args pytree of ShapeDtypeStructs,
    analytic cost record for the roofline)."""
    import jax
    import jax.numpy as jnp

    from repro.core.steps import (build_prefill_step, build_serve_step,
                                  build_train_step)
    from repro.launch.analytic import analytic_cost, plan_degrees
    from repro.models.model import cast_params
    from repro.models.registry import input_specs
    from repro.optim import init_adamw

    dt = jnp.dtype(cfg.dtype)
    p_shapes = jax.eval_shape(
        lambda: cast_params(model.init(jax.random.key(0)), dt))
    batch = input_specs(cfg, shape)
    n_dev = mesh.devices.size
    dp, tp, zdeg = plan_degrees(plan, mesh, shape.global_batch)

    if shape.kind == "train":
        o_shapes = jax.eval_shape(init_adamw, p_shapes)
        step, sh = build_train_step(model, plan, mesh, tcfg,
                                    params_shapes=p_shapes,
                                    batch_shapes=batch)
        args = (p_shapes, o_shapes, batch)
        cost = analytic_cost(cfg, shape, n_devices=n_dev, dp=dp, tp=tp,
                             zero_deg=zdeg, remat=tcfg.remat)
    elif shape.kind == "prefill":
        c_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        step, sh = build_prefill_step(model, plan, mesh,
                                      params_shapes=p_shapes,
                                      batch_shapes=batch,
                                      cache_shapes=c_shapes,
                                      batch_size=shape.global_batch)
        args = (p_shapes, batch, c_shapes)
        cost = analytic_cost(cfg, shape, n_devices=n_dev, dp=dp, tp=tp)
    else:  # decode
        window = 0
        if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
            window = cfg.sliding_window
        c_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                     window=window))
        step, sh = build_serve_step(model, plan, mesh,
                                    params_shapes=p_shapes,
                                    cache_shapes=c_shapes,
                                    batch_size=shape.global_batch,
                                    window=window)
        args = (p_shapes, c_shapes, batch["tokens"])
        cost = analytic_cost(cfg, shape, n_devices=n_dev, dp=dp, tp=tp,
                             window=window)
    return step, args, cost


def run_one(arch: str, shape_name: str, plan_name: str, *,
            multi_pod: bool = False, verbose: bool = True,
            grad_accum: int = 1):
    import jax

    from repro.configs import get_config, get_shape
    from repro.configs.base import TrainConfig
    from repro.core.plans import get_plan
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh
    from repro.models import Model

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "plan": plan_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skip", "reason": reason}

    plan = get_plan(plan_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    tcfg = TrainConfig(grad_accum=grad_accum)
    t0 = time.time()
    with jax.set_mesh(mesh):
        step, args, acost = build_step(model, plan, mesh, cfg, shape, tcfg)
        lowered = step.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    if verbose:
        print(f"--- {arch} x {shape_name} x "
              f"{'2x16x16' if multi_pod else '16x16'} ({plan_name}) ---")
        print(f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print("memory_analysis:", mem)
        cost = compiled.cost_analysis()
        keys = ("flops", "bytes accessed")
        print("cost_analysis:", {k: cost.get(k) for k in keys})
    roof = rl.from_compiled(
        compiled, arch=arch, shape=shape_name,
        mesh_name="2x16x16" if multi_pod else "16x16", plan=plan_name,
        analytic=acost, n_devices=mesh.devices.size,
        crosses_pod=multi_pod)
    rec = roof.to_dict()
    rec.update(status="ok", lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1))
    if verbose:
        print(f"roofline: compute {roof.compute_s * 1e3:.3f} ms | memory "
              f"{roof.memory_s * 1e3:.3f} ms | collective "
              f"{roof.collective_s * 1e3:.3f} ms | dominant {roof.dominant} "
              f"| useful-flops {roof.useful_flops_fraction:.2f} | "
              f"mem/dev {roof.memory_per_device_bytes / 1e9:.2f} GB "
              f"(fits 16GB HBM: {roof.fits_hbm})")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--plan", default=None,
                    help="default: shard_zero for train, shard for serve")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()

    from repro.launch import simulate_host_devices
    simulate_host_devices(512)
    from repro.configs import get_shape
    plan = args.plan or ("shard_zero"
                         if get_shape(args.shape).kind == "train" else "shard")
    try:
        rec = run_one(args.arch, args.shape, plan, multi_pod=args.multi_pod,
                      grad_accum=args.grad_accum)
    except Exception as e:
        traceback.print_exc()
        rec = {"arch": args.arch, "shape": args.shape, "plan": plan,
               "mesh": "multi" if args.multi_pod else "single",
               "status": "fail", "error": f"{type(e).__name__}: {e}"}
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps({k: v for k, v in rec.items()
                      if k in ("arch", "shape", "plan", "status", "dominant",
                               "reason", "error")}))
    return 0 if rec["status"] in ("ok", "skip") else 1


if __name__ == "__main__":
    sys.exit(main())
