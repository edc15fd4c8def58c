"""End-to-end system tests: pretraining convergence, serving, dry-run CLI,
and engine generation — the integration layer over all substrates."""
import dataclasses
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core.plans import get_plan
from repro.data import Loader, Tokenizer, build_dataset, synthetic_wikipedia
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.serve import Engine
from repro.train import train


@pytest.fixture(scope="module")
def tiny_setup():
    texts = list(synthetic_wikipedia(200, seed=1))
    tok = Tokenizer.train(texts, 512)
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              vocab_size=tok.vocab_size)
    ds = build_dataset(texts, tok, seq_len=64)
    return cfg, tok, ds


@pytest.mark.slow
def test_pretraining_reduces_loss(tiny_setup):
    cfg, tok, ds = tiny_setup
    loader = Loader(ds, global_batch=8, seed=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    res = train(Model(cfg), get_plan("data"), mesh,
                TrainConfig(warmup_steps=5, total_steps=40), loader,
                steps=25, log_every=0)
    assert res.losses[-1] < res.losses[0] - 0.5
    assert np.isfinite(res.losses).all()


@pytest.mark.slow
def test_checkpoint_resume_continues(tiny_setup, tmp_path):
    cfg, tok, ds = tiny_setup
    loader = Loader(ds, global_batch=8, seed=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg)
    tcfg = TrainConfig(warmup_steps=2, total_steps=20)
    train(model, get_plan("data"), mesh, tcfg, loader, steps=5,
          log_every=0, ckpt_dir=str(tmp_path))
    from repro.optim import init_adamw
    from repro.train import latest_checkpoint, restore_checkpoint
    params = model.init(jax.random.key(0))
    opt = init_adamw(params)
    p2, o2, step = restore_checkpoint(latest_checkpoint(str(tmp_path)),
                                      params, opt)
    assert step == 5
    res = train(model, get_plan("data"), mesh, tcfg, loader, steps=3,
                params=p2, opt_state=o2, log_every=0)
    assert np.isfinite(res.losses).all()


@pytest.mark.slow
def test_engine_generates(tiny_setup):
    cfg, tok, ds = tiny_setup
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.key(0))
    eng = Engine(model, get_plan("data"), mesh, batch_size=2, max_len=128)
    prompts = np.stack([ds.examples[0, :16], ds.examples[1, :16]])
    out = eng.generate(params, {"tokens": np.asarray(prompts, np.int32)},
                       n_tokens=8)
    assert out["tokens"].shape == (2, 8)
    assert out["stats"].prefill_s > 0
    # greedy decode is deterministic
    out2 = eng.generate(params, {"tokens": np.asarray(prompts, np.int32)},
                        n_tokens=8)
    np.testing.assert_array_equal(out["tokens"], out2["tokens"])


@pytest.mark.slow
def test_dryrun_cli_smoke(subproc_env):
    """The dry-run entrypoint itself (512 forced devices, reduced to one
    combo) must lower + compile + emit a roofline record."""
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", "whisper-small", "--shape", "decode_32k"]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=560,
                         env=subproc_env)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads([l for l in out.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert rec["status"] == "ok"


def test_serve_matches_forward_greedy(tiny_setup):
    """Prefill logits equal the teacher-forced forward's last position."""
    cfg, tok, ds = tiny_setup
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    toks = np.asarray(ds.examples[:1, :12], np.int32)
    cache = model.init_cache(1, 64)
    lg, cache = model.prefill(params, {"tokens": jax.numpy.asarray(toks)},
                              cache)
    full, _ = model.forward(params, {"tokens": jax.numpy.asarray(toks)},
                            remat=False)
    np.testing.assert_array_equal(
        np.argmax(np.asarray(lg), -1),
        np.argmax(np.asarray(full[:, -1]), -1))


def test_grad_accum_matches_full_batch(tiny_setup):
    """grad_accum=2 must produce the same update as the full batch (equal
    per-microbatch token counts => identical mean gradients)."""
    import dataclasses
    from repro.configs.base import TrainConfig
    from repro.core.steps import build_train_step
    from repro.core.plans import get_plan
    from repro.optim import init_adamw
    cfg, tok, ds = tiny_setup
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    from repro.data import Loader
    loader = Loader(ds, global_batch=8, seed=0)
    batch = loader.batch_at(0)
    results = {}
    with jax.set_mesh(mesh):
        for ga in (1, 2, 4):
            params = model.init(jax.random.key(0))
            opt = init_adamw(params)
            tcfg = TrainConfig(warmup_steps=1, total_steps=10, grad_accum=ga)
            step, sh = build_train_step(
                model, get_plan("data"), mesh, tcfg,
                params_shapes=jax.eval_shape(lambda: params),
                batch_shapes=jax.eval_shape(lambda: batch))
            p, o, metrics = step(params, opt, batch)
            results[ga] = (float(metrics["loss"]),
                           float(metrics["grad_norm"]))
    for ga in (2, 4):
        np.testing.assert_allclose(results[ga][0], results[1][0], rtol=2e-3)
        np.testing.assert_allclose(results[ga][1], results[1][1], rtol=2e-2)
