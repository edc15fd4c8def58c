"""Cross-plan checkpoint resharding (ROADMAP "Elastic re-planning"):
the stage re-slicing machinery must be a bit-exact bijection between
pipeline layouts, and a checkpoint restored onto a different
(technique x placement x stage_layers) layout must carry every leaf —
params AND AdamW moments — unchanged.

Host-side tests run the canonical <-> staged-view mappers directly
(``repro.train.reshard``); the slow tests drive the full train →
checkpoint → reshard → resume path through ``repro.launch
.reshard_check`` subprocesses (forced host device counts lock at first
jax init).
"""
import json
import subprocess
import sys

import numpy as np
import pytest

from prophelpers import given, settings, st
from repro.core.pipeline import stage_gather_index
from repro.core.plans import Placement
from repro.train.reshard import (normalized_stage_layers, restage,
                                 stage_view, unstage_view)


def _stack(n_layers, extra_shape=(3,), seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal((n_layers,) + extra_shape).astype(
            np.float32),
        "b": rng.standard_normal((n_layers, 2)).astype(np.float32),
    }


# ------------------------------------------------------------------ #
# stage view mechanics (host, fast)
# ------------------------------------------------------------------ #

def test_stage_view_matches_runtime_gather_index():
    """stage_view applies exactly the trace-time gather convention."""
    split, n_stages = (3, 1), 2
    stack = _stack(4)
    staged, valid = stage_view(stack, split, n_stages)
    idx, valid_ref = stage_gather_index(split, n_stages)
    np.testing.assert_array_equal(valid, valid_ref)
    np.testing.assert_array_equal(staged["w"],
                                  np.take(stack["w"], idx, axis=0))


def test_stage_view_pads_by_repeating_last_layer():
    stack = _stack(3)
    staged, valid = stage_view(stack, (2, 1), 2)
    assert staged["w"].shape[0] == 4            # 2 stages x max(2, 1)
    # stage 1's padding slot repeats its last (only) real layer
    np.testing.assert_array_equal(staged["w"][3], stack["w"][2])
    np.testing.assert_array_equal(valid, [True, True, True, False])


@pytest.mark.parametrize("split,n_stages,schedule", [
    ((2, 2), 2, "gpipe"),
    ((3, 1), 2, "gpipe"),
    ((3, 3, 1), 3, "gpipe"),
    ((5, 2, 2), 3, "1f1b"),
    ((1, 1, 2, 2), 2, "interleaved"),           # virt=2: 4 chunks
])
def test_unstage_inverts_stage_view(split, n_stages, schedule):
    stack = _stack(sum(split))
    staged, _ = stage_view(stack, split, n_stages, schedule=schedule)
    back = unstage_view(staged, split, n_stages, schedule=schedule)
    for k in stack:
        np.testing.assert_array_equal(back[k], stack[k])


def test_restage_across_stage_counts_and_orders():
    """2-stage even -> 3-stage uneven (7 layers) equals staging the
    canonical stack directly; a reversal is just another restage."""
    stack = _stack(7)
    src, _ = stage_view(stack, (4, 3), 2)
    dst, valid = restage(src, (4, 3), 2, (3, 3, 1), 3)
    ref, valid_ref = stage_view(stack, (3, 3, 1), 3)
    for k in stack:
        np.testing.assert_array_equal(dst[k], ref[k])
    np.testing.assert_array_equal(valid, valid_ref)
    # round-trip back to the 2-stage layout is the identity
    back, _ = restage(dst, (3, 3, 1), 3, (4, 3), 2)
    for k in stack:
        np.testing.assert_array_equal(back[k], src[k])


def test_unstage_rejects_wrong_leading_axis():
    staged, _ = stage_view(_stack(4), (2, 2), 2)
    with pytest.raises(ValueError, match="leading axis"):
        unstage_view(staged, (3, 3), 2)
    with pytest.raises(ValueError, match="entries"):
        unstage_view(staged, (2, 2, 2), 2)


def test_normalized_stage_layers():
    assert normalized_stage_layers(6, Placement((0, 1))) == (3, 3)
    assert normalized_stage_layers(
        7, Placement((0, 1, 2), stage_layers=(3, 3, 1))) == (3, 3, 1)
    # interleaved doubles the chunk count
    assert normalized_stage_layers(
        8, Placement((0, 1), schedule="interleaved")) == (2, 2, 2, 2)
    with pytest.raises(ValueError, match="divide"):
        normalized_stage_layers(7, Placement((0, 1, 2)))


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_stage_roundtrip_property(data):
    """Any split of any stack round-trips bit-exactly through the
    padded stage-major view, under any virtual-stage factor."""
    n_stages = data.draw(st.integers(1, 4), label="n_stages")
    virt = data.draw(st.integers(1, 2), label="virt")
    split = tuple(data.draw(
        st.lists(st.integers(1, 4), min_size=n_stages * virt,
                 max_size=n_stages * virt), label="split"))
    schedule = "gpipe" if virt == 1 else f"interleaved{virt}"
    stack = _stack(sum(split),
                   extra_shape=tuple(data.draw(
                       st.lists(st.integers(1, 3), max_size=2),
                       label="extra")),
                   seed=data.draw(st.integers(0, 99), label="seed"))
    staged, valid = stage_view(stack, split, n_stages, schedule=schedule)
    assert staged["w"].shape[0] == n_stages * virt * max(split)
    assert int(valid.sum()) == sum(split)
    back = unstage_view(staged, split, n_stages, schedule=schedule)
    for k in stack:
        np.testing.assert_array_equal(back[k], stack[k])


# ------------------------------------------------------------------ #
# full checkpoint reshard scenarios (subprocess, slow)
# ------------------------------------------------------------------ #

def _run_check(env, extra=(), timeout=560):
    cmd = [sys.executable, "-m", "repro.launch.reshard_check", *extra]
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


def _assert_bitexact_and_step_parity(res):
    assert res["params_bitexact"], res
    assert res["opt_bitexact"], res
    assert res["host_bitexact"], res
    assert res["max_param_diff"] == 0.0
    assert res["max_opt_diff"] == 0.0
    # one further step from the resharded state == the unresharded control
    assert res["loss_resharded"] == res["loss_control"]


@pytest.mark.slow
def test_reshard_zero2_two_sites_to_fsdp_one_site(subproc_env):
    """zero2@{V1,V2} -> fsdp@V1: ZeRO-partitioned moments re-place onto
    the fully-sharded single-site layout bit-exactly."""
    res = _run_check(subproc_env, (
        "--src-plan", "zero2", "--src-sites", "0,1",
        "--dst-plan", "fsdp", "--dst-sites", "0"))
    _assert_bitexact_and_step_parity(res)


@pytest.mark.slow
def test_reshard_data_to_three_stage_uneven_pipeline(subproc_env):
    """data@V1 -> pipeshard 3 stages over 7 layers (3,3,1): the
    destination's uneven pad-and-mask layout restores bit-exactly and
    trains on."""
    res = _run_check(subproc_env, (
        "--src-plan", "data", "--src-sites", "0",
        "--dst-plan", "pipeshard", "--dst-sites", "0,1,2",
        "--dst-layers", "3,3,1", "--layers", "7"))
    _assert_bitexact_and_step_parity(res)


@pytest.mark.slow
def test_reshard_pipeline_two_to_three_stages(subproc_env):
    """pipeshard 2 stages -> 3 stages: a stage-count change (the
    elastic join/leave case) maps straight through."""
    res = _run_check(subproc_env, (
        "--src-plan", "pipeshard", "--src-sites", "0,1",
        "--dst-plan", "pipeshard", "--dst-sites", "0,1,2",
        "--layers", "6"))
    _assert_bitexact_and_step_parity(res)


@pytest.mark.slow
def test_reshard_pipeline_stage_order_reversal(subproc_env):
    """Reversing the stage->site order changes only device placement,
    never values — and one further step is placement-invariant."""
    res = _run_check(subproc_env, (
        "--src-plan", "pipeshard", "--src-sites", "0,1",
        "--dst-plan", "pipeshard", "--dst-sites", "0,1",
        "--dst-order", "1,0", "--layers", "4"))
    _assert_bitexact_and_step_parity(res)
    # the source plan's own continuation agrees too (same math)
    assert res["loss_src_continue"] == res["loss_control"]


@pytest.mark.slow
def test_chaos_kill_site_replan_resume(subproc_env):
    """The pinned recovery gate: kill one site of a two-site Pipeshard
    run mid-epoch; the replan lands on the survivor, the resharded
    optimizer state is bit-exact vs the host-side reference, and the
    resumed loss sequence matches the single-site control exactly."""
    res = _run_check(subproc_env, (
        "--chaos", "--kill-step", "3", "--dead", "1",
        "--total-steps", "6", "--ckpt-every", "2"))
    assert res["failed"]
    assert res["technique"] in ("data", "zero2", "shard")
    assert res["sites_old"] == [0]              # the survivor, original id
    assert res["resumed_from"] == 2             # newest complete checkpoint
    assert res["steps_lost"] == 1               # killed at 3, resumed at 2
    assert res["params_bitexact"] and res["opt_bitexact"]
    assert res["losses_post"] == res["losses_control"]
    assert len(res["losses_pre"]) == 3          # steps 0..2 ran
    assert len(res["losses_post"]) == 4         # steps 2..5 re-ran/ran
    assert all(np.isfinite(res["losses_post"]))
