"""Benchmark/deliverable: the 40-combo (10 arch × 4 shape) baseline dry-run
sweep on the 16x16 production mesh, plus the 2x16x16 multi-pod pass.

Runs in ONE process (XLA re-uses its compilation threads; subprocess
startup costs ~15 s each on this 1-core container) and is resumable:
results land in results/dryrun/<arch>__<shape>__<mesh>.json and existing
files are skipped.

Usage:  python -m benchmarks.dryrun_sweep [--multi-pod] [--arch A] [--shape S]
"""
import argparse
import json
import os
import sys
import time
import traceback

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.launch import simulate_host_devices
    simulate_host_devices(512)
    from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_shape
    from repro.launch.dryrun import run_one

    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh_name = "multi" if args.multi_pod else "single"
    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    t_start = time.time()
    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            out = os.path.join(
                RESULTS_DIR, f"{arch}__{shape}__{mesh_name}.json")
            if os.path.exists(out) and not args.force:
                print(f"[cached] {arch} x {shape} x {mesh_name}", flush=True)
                continue
            plan = "shard_zero" if get_shape(shape).kind == "train" \
                else "shard"
            t0 = time.time()
            try:
                rec = run_one(arch, shape, plan, multi_pod=args.multi_pod,
                              verbose=False)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "plan": plan,
                       "mesh": mesh_name, "status": "fail",
                       "error": f"{type(e).__name__}: {e}"}
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
            n_ok += rec["status"] == "ok"
            n_skip += rec["status"] == "skip"
            n_fail += rec["status"] == "fail"
            msg = rec.get("dominant") or rec.get("reason") \
                or rec.get("error", "")
            print(f"[{rec['status']:4s}] {arch} x {shape} x {mesh_name} "
                  f"({time.time() - t0:.0f}s) {str(msg)[:90]}", flush=True)
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail} "
          f"({(time.time() - t_start) / 60:.1f} min)")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
