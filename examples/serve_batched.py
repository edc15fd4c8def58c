"""Batched serving example: prefill + autoregressive decode with the
sharded KV cache, across architecture families (dense / MLA / SSM).

    PYTHONPATH=src python examples/serve_batched.py --arch falcon-mamba-7b
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.core.plans import get_plan
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.serve import Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b",
                    help="any assigned arch id (reduced variant is served)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    batch = {"tokens": np.asarray(
        rng.integers(4, min(cfg.vocab_size, 400),
                     (args.batch, args.prompt_len)), np.int32)}
    if cfg.family == "vlm":   # stub frontend: precomputed patch embeddings
        batch["patch_embeds"] = np.asarray(
            rng.standard_normal((args.batch, cfg.n_patches, cfg.vision_dim))
            * 0.02, np.float32)
    if cfg.family == "encdec":  # stub frontend: precomputed frames
        batch["frames"] = np.asarray(
            rng.standard_normal((args.batch, cfg.enc_seq_len, cfg.d_model))
            * 0.02, np.float32)

    eng = Engine(model, get_plan("data"), mesh, batch_size=args.batch,
                 max_len=args.prompt_len + args.gen + 8,
                 temperature=args.temperature, top_k=40)
    out = eng.generate(params, batch, n_tokens=args.gen, seed=0)
    s = out["stats"]
    print(f"arch {cfg.name} [{cfg.family}] batch={args.batch}")
    print(f"prefill: {s.prefill_s * 1e3:.0f} ms for "
          f"{args.batch * args.prompt_len} tokens")
    print(f"decode:  {s.tokens_per_s:.1f} steps/s "
          f"({s.tokens_per_s * args.batch:.1f} tok/s aggregate)")
    print("generated ids [0]:", out["tokens"][0].tolist())


if __name__ == "__main__":
    main()
