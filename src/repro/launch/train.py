"""Training launcher: pretrain any registered architecture under any plan.

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3.2-3b --reduced --plan shard_zero \
        --devices 8 --mesh 2,2,2 --steps 100

``--devices N`` simulates N CPU devices; without it the real devices are
used, so on a TPU host drop --devices and pass --mesh to match the chips.
--reduced trains the smoke variant (and the tokenizer's vocabulary); at
full size the config's own vocabulary is kept.
"""
import argparse
import sys


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) variant")
    ap.add_argument("--plan", default="shard_zero",
                    metavar="PLAN",
                    help="execution plan — any repro.core.plans.PLANS "
                         "key (validated against the registry after the "
                         "device-count override, so the choices are "
                         "never a stale hand-kept list)")
    ap.add_argument("--devices", type=int, default=0,
                    help="simulate N host CPU devices (0 = use the real "
                         "devices)")
    ap.add_argument("--mesh", default="1,1",
                    help="mesh shape, e.g. 2,2,2 for (pod,data,model)")
    ap.add_argument("--stages", type=int, default=2,
                    help="pipeline stages (pipeshard)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--docs", type=int, default=500,
                    help="synthetic corpus size (use --data-dir for real)")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(args: argparse.Namespace):
    """Build the corpus, model, mesh and plan from ``args`` and train.
    Returns ``(cfg, TrainResult)``."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.core.pipeline import pipeline_mesh
    from repro.core.plans import get_plan
    from repro.data import (Loader, Tokenizer, build_dataset, load_text_dir,
                            synthetic_wikipedia)
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.train import model_flops_per_step, train

    texts = list(load_text_dir(args.data_dir)) if args.data_dir else \
        list(synthetic_wikipedia(args.docs, seed=args.seed))
    tok = Tokenizer.train(texts, args.vocab)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # full size keeps the published vocabulary (the tied embedding and the
    # logits matmul are a large share of the work); the token ids only
    # have to fit inside it
    vocab = tok.vocab_size if args.reduced \
        else max(cfg.vocab_size, tok.vocab_size)
    cfg = dataclasses.replace(cfg, vocab_size=vocab,
                              max_seq_len=max(cfg.max_seq_len, args.seq))
    ds = build_dataset(texts, tok, seq_len=args.seq)
    loader = Loader(ds, global_batch=args.batch, seed=args.seed)

    shape = tuple(int(x) for x in args.mesh.split(","))
    axes = ("pod", "data", "model")[-len(shape):]
    base = make_mesh(shape, axes)
    plan = get_plan(args.plan)      # KeyError lists the registry's plans
    mesh = pipeline_mesh(base, args.stages) if plan.pipeline else base

    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=args.steps // 10,
                       total_steps=args.steps, seed=args.seed,
                       microbatches=args.microbatches)
    model = Model(cfg)
    print(f"{cfg.name} [{cfg.family}] {cfg.param_count() / 1e6:.1f}M params "
          f"vocab={cfg.vocab_size} | plan={args.plan} "
          f"mesh={dict(zip(axes, shape))}")
    res = train(model, plan, mesh, tcfg, loader, steps=args.steps,
                log_every=max(args.steps // 10, 1),
                ckpt_dir=args.ckpt_dir)
    flops = model_flops_per_step(cfg, args.batch * args.seq)
    print(f"done: loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}; "
          f"{res.tflops(flops):.4f} TFLOP/s avg")
    return cfg, res


def main(argv=None) -> None:
    args = parse_args(argv)
    from repro.launch import enable_compile_cache, simulate_host_devices
    if args.devices:
        simulate_host_devices(args.devices)
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main(sys.argv[1:])
