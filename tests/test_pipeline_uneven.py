"""Uneven-stage-split runtime parity (ROADMAP "uneven stage splits at
runtime"): a searched heterogeneous ``Placement``'s pipeline loss must
match the unsharded reference loss to float32 rounding, and the
pad-and-mask stage construction must be a no-op (bit-for-bit) for even
splits.

Runs ``repro.launch.pipeline_check`` in subprocesses (the forced host
device count locks at first jax init).  The check computes in float32.

Tolerance against the reference: ``REF_ULPS`` float32 ulps of the loss.
The reference and the pipeline are the same math in the same order, but
XLA compiles them as different programs over different matmul shapes
(one batch vs. microbatches), and its CPU fusion and tiling choices can
round a sum differently.  The plain forward alone does so: jitted and
eager, the same ``model.loss`` differs by one ulp.  Pipeline variants
(the even split vs. the equal-block path) compile alike and must agree
exactly.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

REF_ULPS = 2


def _ulps(a: float, ref: float) -> float:
    return abs(a - ref) / float(np.spacing(np.float32(ref)))


def _run_check(env, gpus, extra=()):
    cmd = [sys.executable, "-m", "repro.launch.pipeline_check",
           "--gpus", gpus, *extra]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=560,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


@pytest.mark.slow
def test_uneven_two_stage_parity(subproc_env):
    """A30+T4 line: the searched TFLOP-weighted split is uneven and its
    pipeline loss matches the unsharded reference."""
    res = _run_check(subproc_env, "A30,T4", ("--layers", "6"))
    assert res["stage_layers"] == [4, 2]
    assert _ulps(res["losses"]["searched"], res["ref_loss"]) <= REF_ULPS
    assert _ulps(res["losses"]["legacy"], res["ref_loss"]) <= REF_ULPS
    # pad-and-mask no-op: explicit even split == equal-block fast path
    assert res["losses"]["even"] == res["losses"]["legacy"]
    assert res["gnorms"]["searched"] == pytest.approx(res["ref_gnorm"],
                                                      rel=1e-4)


@pytest.mark.slow
def test_uneven_three_stage_parity_non_divisible_stack(subproc_env):
    """3 stages over 7 layers — a split no equal-block sharding could
    even represent (7 % 3 != 0) — still matches the reference."""
    res = _run_check(subproc_env, "A30,A30,T4", ("--layers", "7"))
    assert res["stage_layers"] == [3, 3, 1]
    assert _ulps(res["losses"]["searched"], res["ref_loss"]) <= REF_ULPS
    assert res["gnorms"]["searched"] == pytest.approx(res["ref_gnorm"],
                                                      rel=1e-4)


@pytest.mark.slow
def test_moe_aux_accumulates_across_stages(subproc_env):
    """MoE load-balance aux must sum over stages (each owns distinct
    expert layers) and average over microbatches — not keep only the
    last stage's aux, and not scale with the microbatch count.  The
    residual gap vs. the reference is mean-of-microbatch-means vs.
    full-batch mean, which is small; the bugs this guards against were
    a missing-stages aux and an n_micro-times overcount."""
    res = _run_check(subproc_env, "A30,T4",
                     ("--arch", "phi3.5-moe-42b-a6.6b", "--layers", "4"))
    assert res["ref_aux"] > 0                   # MoE actually has aux
    assert res["auxes"]["searched"] == pytest.approx(res["ref_aux"],
                                                     rel=0.25)
    assert res["losses"]["searched"] == pytest.approx(res["ref_loss"],
                                                      rel=5e-3)


@pytest.mark.slow
def test_even_split_pad_and_mask_is_noop_three_stages(subproc_env):
    res = _run_check(subproc_env, "A30,T4,T4",
                     ("--layers", "9", "--micro", "3", "--batch", "6"))
    assert res["stage_layers"] == [5, 2, 2]
    assert _ulps(res["losses"]["searched"], res["ref_loss"]) <= REF_ULPS
    assert res["losses"]["even"] == res["losses"]["legacy"]
    assert _ulps(res["losses"]["legacy"], res["ref_loss"]) <= REF_ULPS
