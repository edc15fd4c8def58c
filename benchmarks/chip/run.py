#!/usr/bin/env python3
"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/chip/run.py --workload gpt2m-train-1chip \\
        --seed 7 --seconds 20 --trace 0

Loads and warms up the cell (``setup_s``), measures for ``--seconds``,
checks what the timed path produced against the plain reference
(``chipbench/reference.py`` driving ``references/<family>.py``, the
family that the cell's configuration names), and prints one JSON line
last on standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first
part), ``device``, ``breakdown`` (traced runs) and ``checks`` (each
number compared, beside its limit), which comes last.  The same
numbers are the last lines of standard error.

It runs on the chips of the machine it is started on and needs a TPU:
without one, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result, as it does where a file that
``BENCHMARK.json`` leads to is missing: a configuration without a
``family``, or a family without its files.  JAX's compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import check, spec  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            t0: float, *, require_tpu: bool = True) -> dict:
    """One run of ``cell``; the result line as a dict."""
    from chipbench import cell_serve, cell_train
    runner = {"train": cell_train, "serve": cell_serve}[
        cell.traffic["kind"]]
    out = runner.run(cell, seed, seconds, trace, t0,
                     require_tpu=require_tpu)
    checks = check.verdict(out["readings"], cell.limits)
    check.report(checks, {k: v for k, v in out["readings"].items()
                          if k not in checks})
    if trace:
        metrics = out.get("layer_metrics", {})
    else:
        metrics = {m["name"]: {"value": out[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = {"correct": check.passed(checks) and out["failed"] == 0,
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": out["device"]}
    if trace and "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except (OSError, KeyError, spec.SpecError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    from repro.launch import enable_compile_cache
    from chipbench.harness import NoChip
    enable_compile_cache()
    try:
        line = measure(cell, args.seed, args.seconds, bool(args.trace), T0)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
