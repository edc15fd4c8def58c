"""Pipeline-runtime parity check (used by tests/test_pipeline_uneven.py
and tests/test_pipeline_schedules.py).

Searches a heterogeneous single-GPU-per-site line topology (A30/T4 mix)
with TFLOP-weighted stage balancing, realizes the winning Pipeshard
``Placement`` as a (stage, 1, 1) host-device mesh, and runs the pad-and-
mask pipeline loss (core/pipeline.py) against the unsharded reference
``model.loss`` — under every requested tick-order ``--schedules``
(GPipe / 1F1B / interleaved, docs/schedules.md).  Prints a JSON report:

    {"stage_layers": [...], "splits": {...}, "ref_loss": ...,
     "losses": {...}, "ref_gnorm": ..., "gnorms": {...}, ...}

``losses``/``gnorms``/``auxes`` keys: ``searched`` (the searched,
possibly uneven split), plus — when the layer count divides the chunk
count — ``legacy`` (stage_layers=None equal-block fast path) and
``even`` (the same equal split passed explicitly, which exercises the
gather+mask path; it must be bit-identical to ``legacy``).  Non-GPipe
schedules suffix their keys, e.g. ``searched@1f1b``; schedules reorder
work without changing math, so every entry must equal the reference.

``--carrier bf16`` runs the checks with bf16 inter-stage carriers (the
halved-bytes wire format the cost model's ``carrier_dtype`` knob
prices); the fp32 default is the XLA-CPU-safe baseline.

The model computes in float32 here.  In bf16, XLA's CPU backend keeps
some elementwise intermediates in fp32 inside a fusion, and the fusions
of the pipeline's shard_map body differ from those of the plain layer
scan, so the two round differently (about 1e-5 relative on the loss)
although they run the same math in the same order.  In float32 what is
left is XLA's choice of fusion and matmul tiling per program, which can
move the loss by an ulp (the plain forward, jitted and eager, differs by
as much), so the tests hold every loss to within 2 float32 ulps of the
reference and the pipeline variants to exact agreement.

Must run in its own process: it simulates one host device per site, and
the device count locks at first jax init.
"""
import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gpus", default="A30,T4",
                    help="one GPU type per site/stage, comma-separated")
    ap.add_argument("--arch", default="gpt2m",
                    help="config name; non-dense families (moe) exercise "
                         "the aux-loss accounting across stages")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--schedules", default="gpipe",
                    help="comma-separated pipeline schedules to check "
                         "(gpipe, 1f1b, interleaved, interleaved<v>)")
    ap.add_argument("--carrier", default="fp32",
                    choices=("fp32", "bf16"),
                    help="inter-stage activation carrier dtype "
                         "(core.costmodel.CARRIER_DTYPES).  bf16 is the "
                         "halved-bytes carrier the cost model prices "
                         "(docs/cost-model.md); on XLA CPU it trips the "
                         "SPMD partitioner bug make_pipeline_loss "
                         "documents, so it stays opt-in")
    args = ap.parse_args()

    gpus = args.gpus.split(",")
    n_sites = len(gpus)
    from repro.launch import simulate_host_devices
    simulate_host_devices(n_sites)

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    carrier_dtype = jnp.bfloat16 if args.carrier == "bf16" else jnp.float32

    from repro.configs import get_config
    from repro.core.costmodel import Workload, parse_schedule
    from repro.core.pipeline import make_pipeline_loss
    from repro.core.search import PlanSearch
    from repro.core.topology import Link, Site, line
    from repro.launch.mesh import placement_pipeline_mesh
    from repro.models import Model

    schedules = args.schedules.split(",")
    cfg = dataclasses.replace(get_config(args.arch).reduced(),
                              n_layers=args.layers, dtype="float32")
    model = Model(cfg)

    topo = line("hetline",
                [Site((g,), name=f"S{i}") for i, g in enumerate(gpus)],
                [Link(20e-3, 3.0)] * (n_sites - 1))
    wl = Workload(cfg, args.seq, args.batch, steps_per_epoch=1,
                  microbatches=args.micro)
    search = PlanSearch(wl, topo, stage_balance="tflops",
                        schedules=tuple(schedules))

    def searched_placement(sched):
        cand = next(c for c in search.candidates()
                    if c.technique == "pipeshard"
                    and c.sites == tuple(range(n_sites))
                    and c.stage_order == tuple(range(n_sites))
                    and c.schedule == sched)
        return search.placement(cand)

    placement = searched_placement(schedules[0])

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (args.batch, args.seq))
    # ragged/packed-style positions: every example gets its own offset, so
    # reusing microbatch 0's rows for later microbatches would be visible
    positions = np.arange(args.seq)[None] \
        + (np.arange(args.batch)[:, None] % 3)
    batch = {"tokens": jnp.asarray(tokens, jnp.int32),
             "labels": jnp.asarray(tokens, jnp.int32),
             "positions": jnp.asarray(positions, jnp.int32)}
    params = model.init(jax.random.key(0))

    def gnorm(grads):
        return float(jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads))))

    # loss from the plain forward (the bit-for-bit comparison), grads from
    # a separate value_and_grad: under remat the forward recomputed inside
    # the vjp can differ from the plain forward by an ulp, so mixing the
    # two would blur the exactness claim.
    ref_loss, ref_metrics = model.loss(params, batch)
    ref_grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)

    losses, gnorms, auxes, split_report = {}, {}, {}, {}
    for sched in schedules:
        sched_placement = searched_placement(sched)
        _, virt = parse_schedule(sched)
        n_chunks = n_sites * virt
        splits = {"searched": sched_placement.stage_layers}
        if args.layers % n_chunks == 0:
            splits["legacy"] = None
            splits["even"] = (args.layers // n_chunks,) * n_chunks
        mesh = placement_pipeline_mesh(topo, sched_placement,
                                       devices=jax.devices())
        with jax.set_mesh(mesh):
            for name, split in splits.items():
                key = name if sched == "gpipe" else f"{name}@{sched}"
                split_report[key] = None if split is None else list(split)
                loss_fn = make_pipeline_loss(model, mesh, args.micro,
                                             stage_layers=split,
                                             schedule=sched,
                                             carrier_dtype=carrier_dtype)
                loss, metrics = jax.jit(loss_fn)(params, batch)
                grads = jax.jit(jax.grad(
                    lambda p: loss_fn(p, batch)[0]))(params)
                losses[key] = float(loss)
                gnorms[key] = gnorm(grads)
                auxes[key] = float(metrics["aux"])

    print(json.dumps({
        "stage_layers": list(placement.stage_layers or ()),
        "splits": split_report,
        "ref_loss": float(ref_loss),
        "losses": losses,
        "ref_gnorm": gnorm(ref_grads),
        "gnorms": gnorms,
        "ref_aux": float(ref_metrics["aux"]),
        "auxes": auxes,
    }))


if __name__ == "__main__":
    main()
