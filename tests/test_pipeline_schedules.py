"""Schedule parity: 1F1B and interleaved tick orders must match the
unsharded reference to float32 rounding — schedules reorder work; they
must not change math.

Runs ``repro.launch.pipeline_check --schedules ...`` in subprocesses
(the forced host device count locks at first jax init).  The check
computes in float32, and the losses must lie within ``REF_ULPS`` float32
ulps of the reference: XLA compiles the reference and the pipeline as
different programs, and its CPU fusion and tiling choices can round a
sum differently (test_pipeline_uneven.py states the measured case).

The in-process tests at the top check the static slot tables the
scheduled runner executes (core/pipeline.schedule_tables): every work
item runs exactly once, never before its producer's ppermute delivered,
and the tick counts match the formulas documented in docs/schedules.md.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

from repro.analysis import schedlint
from repro.core.costmodel import balanced_stage_layers
from repro.core.pipeline import schedule_tables, stage_gather_index


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


# ------------------------------------------------------------------ #
# static slot tables
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("sched,v", [("gpipe", 1), ("1f1b", 1),
                                     ("interleaved", 2),
                                     ("interleaved3", 3)])
@pytest.mark.parametrize("S,m", [(1, 1), (2, 4), (3, 2), (3, 4), (4, 7)])
def test_schedule_tables_are_valid_schedules(sched, v, S, m):
    """Each (chunk, microbatch) work item runs exactly once per stage,
    and only after its producer chunk ran on the ring predecessor at an
    earlier tick (ppermute delivers at tick+1)."""
    t = schedule_tables(sched, S, m)
    active, chunk, mb = t["active"], t["chunk"], t["mb"]
    done = {}
    T = active.shape[1]
    for tick in range(T):
        for s in range(S):
            if not active[s, tick]:
                continue
            c = int(chunk[s, tick]) * S + s
            key = (c, int(mb[s, tick]))
            assert key not in done, f"{key} ran twice"
            done[key] = tick
            if c > 0:
                prod = done.get((c - 1, key[1]))
                assert prod is not None and prod < tick, \
                    f"{key} ran before its input arrived"
    assert len(done) == S * v * m              # every item ran
    # the last chunk of every microbatch is banked on the last stage
    for i in range(m):
        assert (S * v - 1, i) in done


def test_schedule_tick_counts_match_the_docs():
    """docs/schedules.md formulas: GPipe m+S-1; 1F1B 2m+S-2 (forward
    slots interleave with the backward slots AD replays)."""
    assert schedule_tables("gpipe", 3, 4)["active"].shape[1] == 6
    assert schedule_tables("1f1b", 3, 4)["active"].shape[1] == 9
    assert schedule_tables("gpipe", 2, 8)["active"].shape[1] == 9
    assert schedule_tables("1f1b", 2, 8)["active"].shape[1] == 16


def test_1f1b_stage_never_holds_more_than_S_forwards_ahead():
    """The 1F1B property the cost model's memory term prices: at any
    tick, a stage has run at most min(S, m) more forwards than the last
    stage has retired (= backward-ready) microbatches."""
    S, m = 3, 8
    t = schedule_tables("1f1b", S, m)
    active, mb = t["active"], t["mb"]
    fwd_done = [0] * S
    retired = 0                 # last stage's completions proxy
    for tick in range(active.shape[1]):
        for s in range(S):
            if active[s, tick]:
                fwd_done[s] += 1
        retired = fwd_done[S - 1]
        for s in range(S):
            assert fwd_done[s] - retired <= min(S, m)


# ------------------------------------------------------------------ #
# edge cases (ISSUE 8 satellite): m < S, S == 1, non-divisible v > 1
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("sched", ["gpipe", "1f1b", "interleaved",
                                   "interleaved3"])
@pytest.mark.parametrize("S,m", [(4, 1), (4, 2), (3, 1), (4, 3)])
def test_fewer_microbatches_than_stages(sched, m, S):
    """m < S: the pipeline is mostly bubble, but every item must still
    run exactly once in producer order — the race detector's invariants
    are the oracle."""
    tables = schedule_tables(sched, S, m)
    assert schedlint.check_tables(tables, sched, S, m) == []


@pytest.mark.parametrize("sched,v", [("gpipe", 1), ("1f1b", 1),
                                     ("interleaved", 2),
                                     ("interleaved3", 3)])
def test_single_stage_degenerate_ring(sched, v):
    """S=1: the ring is a self-loop and every chunk's producer is the
    same stage, so chunks must serialize (chunk c strictly after c-1)
    and nothing is ever on the wire except inter-chunk hops."""
    m = 3
    tables = schedule_tables(sched, 1, m)
    assert schedlint.check_tables(tables, sched, 1, m) == []
    active, chunk, mb = tables["active"], tables["chunk"], tables["mb"]
    assert int(active.sum()) == v * m
    done = {}
    for tick in range(active.shape[1]):
        if active[0, tick]:
            done[(int(chunk[0, tick]), int(mb[0, tick]))] = tick
    for (k, i), tick in done.items():
        if k > 0:
            assert done[(k - 1, i)] < tick
    if v == 1:
        # no chunks to hand over: a pure loop, zero arrival traffic
        assert not tables["arr_valid"].any()


@pytest.mark.parametrize("layers,v,S", [(7, 2, 3), (9, 3, 2), (5, 2, 2)])
def test_interleaved_non_divisible_chunking(layers, v, S):
    """v*S chunks over a layer count that does not divide evenly: the
    pad-and-mask gather must still cover every layer exactly once, each
    chunk contiguously, and the tick tables still verify."""
    split = balanced_stage_layers(layers, [1.0] * (S * v))
    assert sum(split) == layers and min(split) >= 1
    assert max(split) != min(split)             # genuinely uneven
    idx, valid = stage_gather_index(split, S, v)
    assert idx.shape == valid.shape == (S * v * max(split),)
    covered = idx[valid]
    assert sorted(covered.tolist()) == list(range(layers))
    # each chunk's real rows are one contiguous ascending layer run
    per = max(split)
    for chunk_pos in range(S * v):
        rows = idx[chunk_pos * per:(chunk_pos + 1) * per]
        real = rows[valid[chunk_pos * per:(chunk_pos + 1) * per]]
        assert real.tolist() == list(range(real[0], real[0] + len(real)))
    m = 4
    sched = f"interleaved{v}" if v != 2 else "interleaved"
    tables = schedule_tables(sched, S, m)
    assert schedlint.check_tables(tables, sched, S, m) == []


# ------------------------------------------------------------------ #
# runtime parity (subprocess, (stage, 1, 1) meshes)
# ------------------------------------------------------------------ #

@pytest.mark.slow
def test_1f1b_parity_even_and_uneven_two_stages(subproc_env):
    """A30+T4 line: 1F1B matches the reference on both the searched
    uneven (4, 2) split and the equal-block fast path; interleaved (4 chunks over 6 layers — a
    non-divisible chunking) matches too."""
    res = _run_check(subproc_env, "A30,T4",
                     ("--layers", "6",
                      "--schedules", "gpipe,1f1b,interleaved"))
    assert res["splits"]["searched@1f1b"] == [4, 2]
    assert len(res["splits"]["searched@interleaved"]) == 4
    for key, loss in res["losses"].items():
        assert _ulps(loss, res["ref_loss"]) <= REF_ULPS, key
    assert res["gnorms"]["searched@1f1b"] == pytest.approx(
        res["ref_gnorm"], rel=1e-4)
    assert res["gnorms"]["searched@interleaved"] == pytest.approx(
        res["ref_gnorm"], rel=1e-4)


@pytest.mark.slow
def test_schedules_three_stage_parity(subproc_env):
    """3 stages: the uneven (3, 2, 1) 1F1B split and the 6-chunk
    interleaved split both match the reference, and the
    explicit even interleaved split is a no-op vs its equal-block
    path."""
    res = _run_check(subproc_env, "A30,T4,T4",
                     ("--layers", "6", "--micro", "3", "--batch", "6",
                      "--schedules", "1f1b,interleaved"))
    assert res["splits"]["searched@1f1b"] == [3, 2, 1]
    for key, loss in res["losses"].items():
        assert _ulps(loss, res["ref_loss"]) <= REF_ULPS, key
    assert res["losses"]["even@interleaved"] == \
        res["losses"]["legacy@interleaved"]
    assert res["gnorms"]["searched@1f1b"] == pytest.approx(
        res["ref_gnorm"], rel=1e-4)


@pytest.mark.slow
def test_moe_aux_is_schedule_invariant(subproc_env):
    """MoE load-balance aux: every schedule accumulates the same
    per-(stage, microbatch) aux terms, so the sums agree to an ulp
    (XLA may tree-reduce the longer 1F1B/interleaved tick axis in a
    different association) and the losses match the GPipe path and the
    reference at the uneven-grouping tolerance of the PR-3 MoE test."""
    res = _run_check(subproc_env, "A30,T4",
                     ("--arch", "phi3.5-moe-42b-a6.6b", "--layers", "4",
                      "--schedules", "gpipe,1f1b,interleaved"))
    assert res["ref_aux"] > 0
    for sched in ("1f1b", "interleaved"):
        assert res["auxes"][f"searched@{sched}"] == pytest.approx(
            res["auxes"]["searched"], rel=1e-6), sched
        assert res["losses"][f"searched@{sched}"] == pytest.approx(
            res["losses"]["searched"], rel=1e-6), sched
        assert res["losses"][f"searched@{sched}"] == pytest.approx(
            res["ref_loss"], rel=5e-3), sched
        assert res["gnorms"][f"searched@{sched}"] == pytest.approx(
            res["ref_gnorm"], rel=1e-2), sched
