"""End-to-end driver reproducing the paper's experiment shape: pretrain a
GPT-2-family model for a few hundred steps under a selectable technique
(Data / ZeRO2 / Shard / Pipeshard), reporting the paper's metrics — total
wall-clock and average training TFLOP/s.

Scaled to this container: a ~100M-param GPT-2 variant (the paper's gpt2m is
354M), seq 256, CPU host devices standing in for the two-VM FABRIC slice:

    PYTHONPATH=src python examples/pretrain_gpt2_fabric.py \
        --plan pipeshard --devices 8 --steps 200

Use Algorithm 1 offline first (examples/select_technique.py) to pick the
plan, exactly as the paper prescribes (§IV-H).
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--plan", default="data",
                choices=["data", "zero2", "shard", "shard_zero",
                         "pipeshard", "fsdp"])
ap.add_argument("--devices", type=int, default=8)
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--seq", type=int, default=256)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--layers", type=int, default=8)
ap.add_argument("--d-model", type=int, default=512)
args = ap.parse_args()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.launch import simulate_host_devices  # noqa: E402

simulate_host_devices(args.devices)

import dataclasses

import jax

from repro.configs.gpt2 import GPT2_MEDIUM
from repro.configs.base import TrainConfig
from repro.core.pipeline import pipeline_mesh
from repro.core.plans import get_plan
from repro.data import Loader, Tokenizer, build_dataset, synthetic_wikipedia
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.train import model_flops_per_step, train


def main():
    texts = list(synthetic_wikipedia(1500, seed=0))
    tok = Tokenizer.train(texts, vocab_size=8192)
    # ~100M-param GPT-2 variant (gpt2m scaled to the container)
    cfg = dataclasses.replace(
        GPT2_MEDIUM, n_layers=args.layers, d_model=args.d_model,
        n_heads=8, n_kv_heads=8, d_ff=4 * args.d_model,
        vocab_size=tok.vocab_size, max_seq_len=args.seq)
    print(f"model: {cfg.param_count() / 1e6:.1f}M params, plan={args.plan}")

    ds = build_dataset(texts, tok, seq_len=args.seq)
    loader = Loader(ds, global_batch=args.batch, seed=0)
    plan = get_plan(args.plan)
    n = args.devices
    base = make_mesh((max(n // 4, 1), min(n, 2), 2),
                     ("pod", "data", "model"))
    mesh = pipeline_mesh(base, 2) if plan.pipeline else base
    tcfg = TrainConfig(learning_rate=6e-4, warmup_steps=20,
                       total_steps=args.steps, microbatches=4)

    res = train(Model(cfg), plan, mesh, tcfg, loader, steps=args.steps,
                log_every=20)
    flops = model_flops_per_step(cfg, args.batch * args.seq)
    print(f"\n== paper metrics ==")
    print(f"total wall-clock: {sum(res.step_times) / 60:.2f} min "
          f"({args.steps} steps)")
    print(f"avg training performance: {res.tflops(flops):.4f} TFLOP/s "
          f"(host-CPU devices; the paper's Fig 3-7 y-axis)")
    print(f"final loss: {res.losses[-1]:.4f}")


if __name__ == "__main__":
    main()
