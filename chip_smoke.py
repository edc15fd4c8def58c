#!/usr/bin/env python
"""Chip smoke test: the training and serving main paths at the published
widths of the paper's GPT-2 models, on a TPU.

    python chip_smoke.py              # one chip: gpt2m training + serving
    python chip_smoke.py --chips 4    # four-chip host: gpt2L, every plan

One chip (the default) runs, in this one process:

  * training: gpt2m (24 layers, d_model 1024, 16 heads, vocab 50257,
    context 1024) under plan ``data`` on a (1, 1) mesh, batch 8 x 1024,
    5 steps on the synthetic corpus, through ``repro.launch.train``;
  * serving: ``ContinuousEngine`` on gpt2m with 4 slots and 8 seeded
    requests (prompts of 16..128 tokens, 16 new tokens each), once with
    the unquantized KV cache and once with the int8 one.

``--chips 4`` runs only gpt2L under every plan of ``core.plans.PLANS`` on
a (1, 2, 2) mesh, 3 steps of batch 16 x 1024 from one init and one batch
sequence, against a one-device forward pass of the first batch.

Everything is generated from ``--seed``.  The last line of stdout is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed.  Without a TPU the script exits non-zero and prints no result:
it has no CPU fallback.  The phases are functions, so the tests can run
them on the CPU at ``reduced()`` widths.
"""
import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Two logits this close, relative to their size, can trade places under
# one bf16 rounding of an upstream activation (bf16 keeps 8 significant
# bits, so one rounding moves a value by up to 2^-8 of itself).
BF16_TIE = 2.0 ** -6
# Step-0 losses of every plan vs the one-device forward: the same math,
# but each plan sums its bf16 matmul partials in its own order and over
# its own devices; such differences average out over the batch's tokens
# to well under this.
STEP0_RTOL = 2e-3
# Later steps across plans: AdamW's first updates are nearly sign(grad)
# times the learning rate, so a gradient entry near zero that rounds to
# the other sign under one plan moves its weight by a whole step the
# other way.  Losses then part by more than at step 0, yet stay close.
LATER_RTOL = 1e-2


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def gib(n) -> str:
    return "not reported" if n is None else f"{n / 2**30:.3f} GiB"


# ------------------------------------------------------------------ #
# phases
# ------------------------------------------------------------------ #

def train_phase(arch: str = "gpt2m", *, reduced: bool = False,
                seq: int = 1024, batch: int = 8, steps: int = 5,
                docs: int = 500, seed: int = 0) -> dict:
    """Train through ``repro.launch.train`` under plan data on a (1, 1)
    mesh; checks that the losses are finite, start near ln(vocab) and
    fall."""
    import jax

    from repro.launch import train as train_cli

    argv = ["--arch", arch, "--plan", "data", "--mesh", "1,1",
            "--steps", str(steps), "--seq", str(seq),
            "--batch", str(batch), "--docs", str(docs),
            "--seed", str(seed)]
    if reduced:
        argv.append("--reduced")
    cfg, res = train_cli.run(train_cli.parse_args(argv))
    losses = res.losses
    ln_v = math.log(cfg.vocab_size)
    print(f"train: {cfg.name} {cfg.param_count() / 1e6:.1f}M params "
          f"vocab={cfg.vocab_size} batch={batch}x{seq} steps={steps}")
    print(f"train: losses {[round(l, 4) for l in losses]} "
          f"(ln vocab = {ln_v:.4f})")
    print(f"train: compile {res.compile_s:.2f} s; step times "
          f"{[round(t, 4) for t in res.step_times]} s; compiled step "
          f"needs {gib(res.step_bytes)}; peak "
          f"{gib(peak_bytes(jax.devices()[0]))}")
    check(all(math.isfinite(l) for l in losses), f"non-finite loss {losses}")
    # a random init gives logits of a small spread, which adds about
    # half their variance to ln(vocab)
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]:.4f} is not near ln(vocab) {ln_v:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"losses": losses, "compile_s": res.compile_s,
            "step_times": res.step_times, "vocab": cfg.vocab_size}


def _top2(row):
    import numpy as np
    order = np.argsort(row)[::-1]
    return int(order[0]), float(row[order[0]]), float(row[order[1]])


def serve_phase(arch: str = "gpt2m", *, reduced: bool = False,
                slots: int = 4, n_requests: int = 8,
                prompt_lens=(16, 128), max_new: int = 16, seed: int = 0,
                kv_dtypes=("fp32", "int8")) -> dict:
    """Serve seeded requests through ``ContinuousEngine`` once per KV
    dtype.  Every request must complete, and each first token must be
    the argmax of a full forward pass over its prompt (up to bf16
    ties).  Where the kernels compile (not on the CPU), the compiled
    int8 decode step must hold the Pallas kernel (``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.plans import get_plan
    from repro.kernels.ops import _default_interpret
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serve import ContinuousEngine, Request

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        params = jax.jit(model.init)(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    prompts = [rng.integers(4, cfg.vocab_size,
                            int(rng.integers(lo, hi + 1))).astype(np.int32)
               for _ in range(n_requests)]

    # reference: one full forward over the prompts right-padded to the
    # longest; causal attention keeps the pad invisible to position L-1
    padded = np.zeros((n_requests, hi), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    logits, _ = jax.jit(lambda p, t: model.forward(p, {"tokens": t}))(
        params, jnp.asarray(padded))
    last = np.asarray(jnp.stack([logits[i, len(p) - 1]
                                 for i, p in enumerate(prompts)]),
                      np.float32)
    ref = [_top2(row) for row in last]

    out = {}
    for kv in kv_dtypes:
        eng = ContinuousEngine(model, get_plan("data"), mesh, slots=slots,
                               max_len=2 * hi, kv_dtype=kv)
        res = eng.run(params, [Request(i, p) for i, p in enumerate(prompts)],
                      max_new=max_new)
        outs, st = res["outputs"], res["stats"]
        check(sorted(outs) == list(range(n_requests)),
              f"kv={kv}: completed {sorted(outs)} of {n_requests}")
        check(all(len(outs[i]) == max_new for i in outs),
              f"kv={kv}: lengths {[len(outs[i]) for i in sorted(outs)]}")
        ties = []
        for i, (tok, top1, top2) in enumerate(ref):
            got = int(outs[i][0])
            if got == tok:
                continue
            gap = top1 - top2
            check(gap <= BF16_TIE * max(abs(top1), 1.0) and
                  float(last[i, got]) == top2,
                  f"kv={kv} request {i}: first token {got}, reference "
                  f"argmax {tok} (top-2 gap {gap:.5f})")
            ties.append((i, got, tok, gap))
        for i, got, tok, gap in ties:
            print(f"serve: kv={kv} request {i}: first token {got} vs "
                  f"reference {tok}, top-2 gap {gap:.5f} within bf16 "
                  f"rounding")
        print(f"serve: kv={kv} {n_requests}/{n_requests} requests "
              f"completed, {st.n_tokens} tokens in {st.total_s:.2f} s, "
              f"first tokens match the reference "
              f"({len(ties)} bf16 ties)")
        if kv == "int8":
            with jax.set_mesh(mesh):
                text = eng._decode.lower(
                    params, eng._slot_cache0,
                    jnp.zeros((slots, 1), jnp.int32),
                    jnp.zeros((slots,), bool)).compile().as_text()
            has_kernel = "tpu_custom_call" in text
            print(f"serve: int8 decode step holds tpu_custom_call: "
                  f"{has_kernel}")
            if not _default_interpret():
                check(has_kernel, "int8 decode step has no Pallas kernel")
        out[kv] = {"outputs": outs, "ties": ties}
    return out


def plans_phase(arch: str = "gpt2L", *, reduced: bool = False,
                mesh_shape=(1, 2, 2), batch: int = 16, seq: int = 1024,
                steps: int = 3, docs: int = 500, seed: int = 0,
                plans=None) -> dict:
    """Train ``steps`` steps under every plan from one init and one batch
    sequence, and compare the losses with each other and with a
    one-device forward pass of the first batch."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.core.pipeline import pipeline_mesh
    from repro.core.plans import PLANS, get_plan
    from repro.data import Loader, Tokenizer, build_dataset, \
        synthetic_wikipedia
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.train import train

    plans = list(plans or PLANS)
    cfg = get_config(arch)
    cfg = cfg.reduced() if reduced else cfg
    cfg = dataclasses.replace(cfg, max_seq_len=max(cfg.max_seq_len, seq))
    texts = list(synthetic_wikipedia(docs, seed=seed))
    tok = Tokenizer.train(texts, min(cfg.vocab_size, 2048))
    loader = Loader(build_dataset(texts, tok, seq_len=seq),
                    global_batch=batch, seed=seed)
    tcfg = TrainConfig(learning_rate=3e-4, warmup_steps=0,
                       total_steps=steps, seed=seed, microbatches=4)
    n_dev = int(np.prod(mesh_shape))
    devices = jax.devices()
    check(len(devices) >= n_dev, f"{len(devices)} devices < {n_dev}")
    print(f"plans: {cfg.name} {cfg.param_count() / 1e6:.1f}M params "
          f"vocab={cfg.vocab_size} batch={batch}x{seq} steps={steps} "
          f"mesh={mesh_shape} plans={plans}")

    # the reference: one device, the same init, the first batch
    model = Model(cfg)
    one = jax.sharding.SingleDeviceSharding(devices[0])
    params = jax.jit(model.init, out_shardings=one)(
        jax.random.key(tcfg.seed))
    ref, _ = jax.jit(lambda p, b: model.loss(p, b))(
        params, jax.device_put(loader.batch_at(0), one))
    ref = float(ref)
    del params
    print(f"plans: one-device forward loss of batch 0: {ref:.6f}")

    base = make_mesh(mesh_shape, ("pod", "data", "model"))
    results = {}
    for name in plans:
        plan = get_plan(name)
        mesh = pipeline_mesh(base, 2) if plan.pipeline else base
        res = train(Model(cfg), plan, mesh, tcfg, loader, steps=steps,
                    log_every=0)
        per_dev = {}
        n_shards = 0
        for leaf in jax.tree.leaves(res.params):
            for sh in leaf.addressable_shards:
                n_shards += 1
                per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                    + sh.data.nbytes
        results[name] = {"losses": res.losses, "step_times": res.step_times,
                         "compile_s": res.compile_s,
                         "step_bytes": res.step_bytes,
                         "param_bytes": per_dev}
        stats = [d.memory_stats() or {} for d in devices[:n_dev]]
        print(f"plans: {name:10s} losses {[round(l, 5) for l in res.losses]}"
              f" compile {res.compile_s:.1f} s step "
              f"{[round(t, 4) for t in res.step_times]} s")
        print(f"plans: {name:10s} {n_shards} addressable param shards; "
              f"param bytes per device "
              f"{ {k: gib(v) for k, v in sorted(per_dev.items())} }")
        print(f"plans: {name:10s} compiled step needs "
              f"{gib(res.step_bytes)} per device; bytes in use after the "
              f"steps {[gib(s.get('bytes_in_use')) for s in stats]}; "
              f"process peak so far "
              f"{[gib(s.get('peak_bytes_in_use')) for s in stats]}")
        check(len(per_dev) == n_dev,
              f"{name}: parameters live on {sorted(per_dev)}, not on all "
              f"{n_dev} devices")
        check(all(math.isfinite(l) for l in res.losses),
              f"{name}: non-finite losses {res.losses}")
        check(abs(res.losses[0] - ref) <= STEP0_RTOL * abs(ref),
              f"{name}: step-0 loss {res.losses[0]:.6f} vs one-device "
              f"{ref:.6f} (rtol {STEP0_RTOL})")
        del res
    first = results[plans[0]]["losses"]
    for name in plans[1:]:
        for i, (a, b) in enumerate(zip(results[name]["losses"], first)):
            check(abs(a - b) <= LATER_RTOL * abs(b),
                  f"{name} step {i}: loss {a:.6f} vs {plans[0]} {b:.6f} "
                  f"(rtol {LATER_RTOL})")
    print(f"plans: step-0 losses within rtol {STEP0_RTOL} of the "
          f"one-device forward; later losses within rtol {LATER_RTOL} "
          f"across plans")
    return {"ref": ref, "plans": results}


# ------------------------------------------------------------------ #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: gpt2L under every plan on a (1, 2, 2) mesh, "
                         "and no other phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; platform {dev.platform}; device_kind "
          f"{dev.device_kind}; {len(devices)} device(s); compile cache "
          f"{cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform}); there is no "
              f"CPU fallback", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    if args.chips == 4:
        plans_phase(seed=args.seed)
    else:
        train_phase(seed=args.seed)
        serve_phase(seed=args.seed)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
