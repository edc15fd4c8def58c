"""Quickstart: pretrain a tiny llama3-family model on the synthetic
wikipedia corpus with the Data plan, save a checkpoint, generate text.

    PYTHONPATH=src python examples/quickstart.py --steps 50
"""
import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.core.plans import get_plan
from repro.data import Loader, Tokenizer, build_dataset, synthetic_wikipedia
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.serve import Engine
from repro.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_quickstart")
    args = ap.parse_args()

    print("== corpus + tokenizer ==")
    texts = list(synthetic_wikipedia(400, seed=1))
    tok = Tokenizer.train(texts, vocab_size=1024)
    ds = build_dataset(texts, tok, seq_len=128)
    print(f"{len(texts)} docs -> {len(ds)} packed examples, "
          f"vocab {tok.vocab_size}")

    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              n_layers=4, vocab_size=tok.vocab_size)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    loader = Loader(ds, global_batch=8, seed=0)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=10,
                       total_steps=args.steps)

    print("== pretraining (Data plan) ==")
    res = train(model, get_plan("data"), mesh, tcfg, loader,
                steps=args.steps, log_every=10, ckpt_dir=args.ckpt_dir)
    print(f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}; "
          f"avg step {res.avg_step_time * 1e3:.0f} ms")

    print("== generation ==")
    from repro.train import latest_checkpoint, restore_checkpoint
    params = model.init(jax.random.key(0))
    params, _, _ = restore_checkpoint(latest_checkpoint(args.ckpt_dir),
                                      params)
    eng = Engine(model, get_plan("data"), mesh, batch_size=1, max_len=256,
                 temperature=0.8, top_k=40)
    prompt = tok.encode(texts[0][:80], eos=False)
    out = eng.generate(params, {"tokens": np.asarray([prompt], np.int32)},
                       n_tokens=40)
    print("prompt:", texts[0][:80])
    print("continuation:", tok.decode(out["tokens"][0].tolist()))
    print(f"decode: {out['stats'].tokens_per_s:.1f} tok/s")


if __name__ == "__main__":
    main()
