"""Serving launcher: batched prefill + decode for any registered arch,
fixed-batch by default, continuous batching with ``--continuous``.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch falcon-mamba-7b --reduced --batch 4 --gen 32

    PYTHONPATH=src python -m repro.launch.serve --reduced --continuous \
        --trace 12x8..32 --batch 3 --gen 8
"""
import argparse


def parse_trace(spec: str, max_prompt: int):
    """``<n>x<lo>..<hi>`` — n requests with prompt lengths uniform in
    [lo, hi] (deterministic, seed 0).  Plain ``<n>`` uses 8..max_prompt."""
    body = spec
    lo, hi = 8, max_prompt
    if "x" in spec:
        body, rng_part = spec.split("x", 1)
        try:
            lo, hi = (int(v) for v in rng_part.split("..", 1))
        except ValueError:
            raise SystemExit(
                f"bad --trace {spec!r}: want <n>x<lo>..<hi> or <n>")
    try:
        n = int(body)
    except ValueError:
        raise SystemExit(f"bad --trace {spec!r}: want <n>x<lo>..<hi> or <n>")
    if not (n >= 1 and 1 <= lo <= hi <= max_prompt):
        raise SystemExit(
            f"bad --trace {spec!r}: need n >= 1 and "
            f"1 <= lo <= hi <= {max_prompt}")
    return n, lo, hi


def main() -> None:
    from repro.core.plans import PLANS

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--plan", default="shard", choices=sorted(PLANS),
                    help="registered parallelism plan (core/plans.py)")
    ap.add_argument("--devices", type=int, default=0,
                    help="simulate N host CPU devices (0 = use the real "
                         "devices)")
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--batch", type=int, default=4,
                    help="fixed batch rows / continuous decode slots")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window cache (long-context decode)")
    ap.add_argument("--kv-dtype", default="fp32", choices=("fp32", "int8"),
                    help="int8: quantized KV cache + int8-KV decode kernel")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching "
                         "(greedy; --batch = slot count)")
    ap.add_argument("--trace", default=None, metavar="N[xLO..HI]",
                    help="continuous request trace: N prompts with "
                         "lengths uniform in [LO, HI] (default "
                         "2x the slot count over 8..--prompt-len)")
    args = ap.parse_args()
    if args.trace and not args.continuous:
        ap.error("--trace only applies with --continuous")

    from repro.launch import enable_compile_cache, simulate_host_devices
    if args.devices:
        simulate_host_devices(args.devices)
    enable_compile_cache()

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.plans import get_plan
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serve import ContinuousEngine, Engine, Request

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    shape = tuple(int(x) for x in args.mesh.split(","))
    axes = ("pod", "data", "model")[-len(shape):]
    mesh = make_mesh(shape, axes)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.gen + 8
    header = (f"{cfg.name} [{cfg.family}] plan={args.plan} "
              f"batch={args.batch} kv={args.kv_dtype}")

    if args.continuous:
        n, lo, hi = parse_trace(args.trace or f"{2 * args.batch}",
                                args.prompt_len)
        prompts = [np.asarray(
            rng.integers(4, min(cfg.vocab_size, 400),
                         (int(rng.integers(lo, hi + 1)),)), np.int32)
            for _ in range(n)]
        eng = ContinuousEngine(model, get_plan(args.plan), mesh,
                               slots=args.batch, max_len=max_len,
                               kv_dtype=args.kv_dtype)
        res = eng.run(params,
                      [Request(i, p) for i, p in enumerate(prompts)],
                      max_new=args.gen)
        st = res["stats"]
        lens = sorted(len(p) for p in prompts)
        print(f"{header} continuous slots={args.batch}")
        print(f"{n} requests (prompt lens {lens[0]}..{lens[-1]}) | "
              f"{st.n_tokens} tokens in {st.total_s:.2f}s | "
              f"{st.tokens_per_s:.1f} tok/s | "
              f"occupancy {st.mean_occupancy:.2f}/{args.batch} | "
              f"TTFT p50 "
              f"{np.percentile(sorted(st.ttft_s.values()), 50):.3f}s")
        return

    batch = {"tokens": np.asarray(
        rng.integers(4, min(cfg.vocab_size, 400),
                     (args.batch, args.prompt_len)), np.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = np.asarray(
            rng.standard_normal((args.batch, cfg.n_patches, cfg.vision_dim))
            * 0.02, np.float32)
    if cfg.family == "encdec":
        batch["frames"] = np.asarray(
            rng.standard_normal((args.batch, cfg.enc_seq_len, cfg.d_model))
            * 0.02, np.float32)

    eng = Engine(model, get_plan(args.plan), mesh, batch_size=args.batch,
                 max_len=max_len, window=args.window,
                 temperature=args.temperature, kv_dtype=args.kv_dtype)
    out = eng.generate(params, batch, n_tokens=args.gen)
    s = out["stats"]
    print(header)
    print(f"prefill {s.prefill_s * 1e3:.0f} ms | decode "
          f"{s.steps_per_s:.1f} steps/s "
          f"({s.tokens_per_s:.1f} tok/s aggregate)")


if __name__ == "__main__":
    main()
