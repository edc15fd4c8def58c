#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/calibrate.py --workload gpt2m-train-1chip \\
        --seeds 11,12,13 --program --control --faults

One JSON line per seed and reading on standard output:

  --program   the program's readings: the cell's set-up, its checked
              steps or a short window of its traffic, and the reference
              (the lower reading of each limit is their largest);
  --control   the reference put in the program's place and computed in
              float8 (the upper reading is the smallest of these);
              a serving cell reads it on the sequences the program
              served, so it needs --program too;
  --faults    training: the reference with each fault of
              ``reference.FAULTS`` planted in its steps, or those named
              (``--faults half_batch``).

The reference is ``chipbench/reference.py`` driving the mathematics of
the family that the cell's configuration names, so every family's
limits come from the same control and the same faults.

Needs the chips the cell asks for, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import check, gen, reference, spec  # noqa: E402


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def worst_leaves(prog, ref, n=3):
    """The leaves that set ``grad_gap`` and ``change_gap``, worst first."""
    tiny = check.ROUND_OFF_GRAD * float(np.median(list(ref["grad"].values())))
    keep = {"grad": None, "change": lambda leaf: ref["grad"][leaf] >= tiny}
    return {number: sorted(check.leaf_gaps(prog[number], ref[number],
                                           keep[number]).items(),
                           key=lambda kv: -kv[1])[:n]
            for number in ("grad", "change")}


def train_seed(cell, seed, args):
    cfg, traffic = cell.config, cell.traffic
    opt, z = traffic["optimizer"], traffic["z_loss"]
    batches = gen.TrainBatches(traffic, cfg["vocab_size"], seed)
    feed = [batches.batch_at(i) for i in range(traffic["check_steps"])]
    steps = lambda **kw: reference.train_steps(
        cell.family, cfg, opt, z, seed, feed,
        rows=traffic["reference_rows"], **kw)
    ref = None
    if args.program:
        from chipbench import cell_train
        out = cell_train.run(cell, seed, 0.0, False, time.perf_counter(),
                             require_tpu=args.require_tpu)
        ref = out["reference"]
        emit(seed=seed, kind="program", readings=out["readings"],
             setup_s=out["setup_s"], losses=ref["losses"],
             leaves=worst_leaves(out["program"], ref))
    ref = ref or steps()
    if args.control:
        control = steps(precision="float8")
        emit(seed=seed, kind="control",
             readings=check.train_readings(control, ref),
             leaves=worst_leaves(control, ref))
    for fault in args.faults:
        emit(seed=seed, kind=fault,
             readings=check.train_readings(steps(fault=fault), ref))


def serve_seed(cell, seed, args):
    from chipbench import cell_serve
    out = cell_serve.run(cell, seed, 0.0, False, time.perf_counter(),
                         require_tpu=args.require_tpu)
    emit(seed=seed, kind="program", readings=out["readings"],
         setup_s=out["setup_s"], served=sum(len(s) for _, s in out["sample"]))
    if args.control:
        gaps = reference.served_gaps(cell.family, cell.config, seed,
                                     cell.traffic["weight_dtype"],
                                     out["sample"], control=True)
        emit(seed=seed, kind="control", readings={"served_gap": max(gaps)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", nargs="?", const="all", default="",
                    help="comma-separated faults of "
                    f"{', '.join(reference.FAULTS[1:])}; all of them if "
                    "none is named")
    args = ap.parse_args(argv)
    args.faults = (reference.FAULTS[1:] if args.faults == "all"
                   else [f for f in args.faults.split(",") if f])
    args.require_tpu = True
    cell = spec.load_cell(args.workload)
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.traffic["kind"] == "train":
            train_seed(cell, seed, args)
        else:
            serve_seed(cell, seed, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
