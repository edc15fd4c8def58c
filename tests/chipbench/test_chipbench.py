"""The on-chip benchmark's own tests, on the CPU.

Counts from shapes, the reduction from a trace to metrics, the command's
refusal to run without a TPU, the discovery of a cell's files by name,
and a rehearsal of each cell at a tiny size: whole, under its control,
and with each fault its timed path can have planted underneath.
"""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import rehearse  # noqa: E402

ROOT, BENCH = rehearse.ROOT, rehearse.BENCH

from chipbench import check, counts, gen, reference, spec, tracefile  # noqa

GPT2 = spec.load_family("gpt2")
GPT2M = {"n_layer": 24, "n_embd": 1024, "n_head": 16, "n_inner": 4096,
         "vocab_size": 50257, "n_positions": 1024}


# ------------------------------------------------------------------ #
# counts
# ------------------------------------------------------------------ #

def test_train_flops_hand_count_gpt2m():
    # per layer: q,k,v,o 4 d^2 + MLP 2 d f + causal attention d (S+1)
    per_layer = 4 * 1024 ** 2 + 2 * 1024 * 4096 + 1024 * 1025
    assert per_layer == 13_632_512
    fwd = 2 * (24 * per_layer + 1024 * 50257)
    assert GPT2.program.forward_flops_per_token(GPT2M, 1024) == fwd \
        == 757_286_912
    assert counts.train_flops_per_token(GPT2, GPT2M, 1024) == 3 * fwd


@pytest.mark.parametrize("seq", [128, 512, 1024])
def test_train_flops_scale_with_sequence(seq):
    # doubling the sequence adds only attention: 2 d S per token per
    # layer, forward
    a = GPT2.program.forward_flops_per_token(GPT2M, seq)
    b = GPT2.program.forward_flops_per_token(GPT2M, 2 * seq)
    assert b - a == 2 * 24 * 1024 * seq


@pytest.mark.parametrize("slots", [1, 16, 64])
def test_decode_bytes_scale_with_filled_positions(slots):
    kv = GPT2.program.kv_bytes_per_token(GPT2M, 2)
    assert kv == 24 * 2 * 1024 * 2 == 96 * 1024
    params = 700_000_000
    one = counts.decode_step_bytes(params, [100] * slots, kv)
    two = counts.decode_step_bytes(params, [200] * slots, kv)
    assert two - one == slots * 100 * kv
    assert counts.decode_step_bytes(params, [], kv) == params


def test_serve_flops_match_a_token_by_token_count():
    cfg = dict(GPT2M, n_layer=2)
    d, f, L, V = 1024, 4096, 2, 50257
    reqs = [(5, 3), (1, 1), (7, 4)]
    macs = 0
    for P, n in reqs:
        for pos in range(P):                         # prefill, causal
            macs += L * (4 * d * d + 2 * d * f + 2 * d * (pos + 1))
        macs += d * V                                # first token
        for j in range(1, n):                        # decode, P + j keys
            macs += L * (4 * d * d + 2 * d * f + 2 * d * (P + j)) + d * V
    assert GPT2.program.serve_flops(cfg, reqs) == 2 * macs


def test_peaks_refuse_an_unknown_device():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("cpu")


# ------------------------------------------------------------------ #
# trace reduction
# ------------------------------------------------------------------ #

def _trace():
    """Two chips over a 100 ns window.  Chip 0: a module [0, 60) holding
    a while op [0, 50) over two ops, a collective [55, 70) overlapping
    compute until 60, idle [70, 80), compute [80, 90).  Chip 1: busy
    [10, 90) with one op."""
    t = tracefile.Trace()
    t.ops["/device:TPU:0"] = [
        (0, 50, "while.1"), (0, 20, "fusion.1"), (20, 50, "fusion.2"),
        (50, 60, "convolution.3"), (55, 70, "all-reduce.4"),
        (80, 90, "fusion.1")]
    t.ops["/device:TPU:1"] = [(10, 90, "fusion.9")]
    t.modules["/device:TPU:0"] = [(0, 60, "jit_train_step"),
                                  (80, 90, "jit_decode_slots")]
    t.modules["/device:TPU:1"] = [(10, 90, "jit_train_step")]
    t.spans = [(0, 100, "train.step"), (65, 85, "loader.batch_at")]
    return t


def test_union_of_busy_intervals_and_idle_share():
    t = _trace()
    busy = tracefile.busy_ns(t, 0, 100)
    assert busy == {"/device:TPU:0": 80, "/device:TPU:1": 80}
    ctx = SimpleNamespace(kind="train", trace=t, lo=0, hi=100)
    read = spec.metric_reader("device_idle_share.train")
    assert read(ctx) == pytest.approx(20.0)


def test_exposed_collective_intervals():
    t = _trace()
    exposed = tracefile.exposed_collective_ns(t, 0, 100)
    assert exposed == {"/device:TPU:0": 10, "/device:TPU:1": 0}
    ctx = SimpleNamespace(kind="train", trace=t, lo=0, hi=100)
    read = spec.metric_reader("exposed_collective_share.train")
    assert read(ctx) == pytest.approx(5.0)


def test_collectives_in_flight_count_where_no_compute_runs():
    t = _trace()
    # an asynchronous permute in flight over [85, 95): [90, 95) is bare
    t.collectives["/device:TPU:1"] = [
        (85, 95, "%collective-permute-start.2 = f32[8] "
                 "collective-permute-start(f32[8] %fusion.9)")]
    assert tracefile.exposed_collective_ns(t, 0, 100)[
        "/device:TPU:1"] == 5
    assert tracefile.has_collectives(t)


def test_hlo_instruction_text_reads_as_name_and_label():
    hlo = ("%fusion.670 = (f32[8,16,512]{2,1,0:T(8,128)}, bf16[8]{0}) "
           "fusion(f32[8]{0} %all-reduce.3), kind=kOutput, "
           "calls=%fused_computation.599")
    assert tracefile.op_name(hlo) == "fusion.670"
    assert not tracefile.is_collective(hlo)
    assert tracefile.op_label(hlo) == "fusion.670 fusion (f32[8,16,512], " \
        "bf16[8])"
    assert tracefile.is_collective("%all-reduce.3 = f32[8]{0} all-reduce("
                                   "f32[8]{0} %x), to_apply=%add")


def test_control_flow_ops_are_not_counted_twice():
    leaves = tracefile.leaves(_trace().ops["/device:TPU:0"])
    assert "while.1" not in [n for _, _, n in leaves]
    assert len(leaves) == 5


def test_module_time_grouped_by_name():
    t = _trace()
    assert tracefile.module_ns(t, "train_step", 0, 100) == {
        "/device:TPU:0": [60], "/device:TPU:1": [80]}
    assert tracefile.module_ns(t, "decode_slots", 0, 85) == {
        "/device:TPU:0": [5], "/device:TPU:1": []}


def test_breakdown_names_ops_and_idle_gaps_by_host_span():
    b = tracefile.breakdown(_trace(), 0, 100)
    ops = dict(b["device_ops"])
    assert ops["fusion.1"] == pytest.approx(30e-9 / 2)
    assert "while.1" not in ops
    gaps = dict(b["idle_gaps"])
    # chip 0 idles [70, 80) under loader.batch_at, [90, 100) under the
    # step span; chip 1 idles [0, 10) and [90, 100) under the step span
    assert gaps["loader.batch_at"] == pytest.approx(10e-9 / 2)
    assert gaps["train.step"] == pytest.approx(30e-9 / 2)


def test_mfu_and_roofline_read_under_100_percent_on_known_busy_time():
    peaks = counts.peaks("TPU v5 lite")
    # 4 steps of 8 x 1024 gpt2m tokens in a 2.0 s window at 19.4% MFU
    t = tracefile.Trace(ops={"/device:TPU:0": [(0, int(1.9e9), "f")]})
    ctx = SimpleNamespace(
        kind="train", trace=t, lo=0, hi=int(2e9), window_s=2.0,
        windows=[(0, 1, "train.step")] * 4, chips=1, peaks=peaks,
        tokens_per_step=8192,
        flops_per_token=counts.train_flops_per_token(GPT2, GPT2M, 1024))
    mfu = spec.metric_reader("step_mfu.train")(ctx)
    assert mfu == pytest.approx(100 * 4 * 8192 * 2271860736
                                / (2.0 * 197e12))
    assert 0 < mfu < 100
    # 10 decode steps, each needing 2 GB, in 10 x 3 ms of module time
    t.modules = {"/device:TPU:0": [(i * 4_000_000, i * 4_000_000 + 3_000_000,
                                    "jit_decode_slots") for i in range(10)]}
    sctx = SimpleNamespace(kind="serve", trace=t, lo=0, hi=int(1e8),
                           window_s=0.1, chips=1, peaks=peaks,
                           decode_bytes=[2_000_000_000] * 10)
    roof = spec.metric_reader("decode_roofline.serve")(sctx)
    assert roof == pytest.approx(100 * 2e9 / 819e9 / 3e-3)
    assert 0 < roof < 100


def test_a_reader_with_nothing_to_read_returns_nothing():
    t = tracefile.Trace(ops={"/device:TPU:0": [(0, 10, "fusion.1")]})
    ctx = SimpleNamespace(kind="train", trace=t, lo=0, hi=10,
                          decode_bytes=[], traced_requests=[])
    assert spec.metric_reader("exposed_collective_share.train")(ctx) is None
    assert spec.metric_reader("decode_roofline.serve")(ctx) is None
    assert spec.metric_reader("step_mfu.serve")(ctx) is None


# ------------------------------------------------------------------ #
# the command and the files it finds
# ------------------------------------------------------------------ #

def _run_cmd(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gpt2m-train-1chip", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_command_exits_without_a_result_when_no_tpu_is_found():
    res = _run_cmd(ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not [l for l in res.stdout.splitlines() if l.startswith("{")]


def test_the_command_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    spec_json = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec_json["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_cmd(tmp_path)
    assert res.returncode != 0
    assert not [l for l in res.stdout.splitlines() if l.startswith("{")]


def test_benchmark_json_names_every_file_it_needs():
    spec_json = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec_json["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer and cell.limits
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            moved = [e["name"] for e in cell.end_to_end]
            assert m["moves"] in moved
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               f"{m['name']}.py"))


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    spec_json = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec_json["workloads"].append(
        {"name": "gpt2m-train-short", "config": "gpt2m",
         "traffic": "train_data_4x512", "chips": 1, "why": "test"})
    for m in spec_json["end_to_end"] + spec_json["per_layer"]:
        if "gpt2m-train-1chip" in m.get("workloads", []):
            m["workloads"].append("gpt2m-train-short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec_json))
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = json.load(open(bench / "traffic" / "train_data_8x1024.json"))
    traffic.update(batch=4, seq=512)
    (bench / "traffic" / "train_data_4x512.json").write_text(
        json.dumps(traffic))
    (bench / "limits" / "gpt2m-train-short.json").write_text(
        json.dumps({"limits": {"loss_gap": 0.05}}))
    cell = spec.load_cell("gpt2m-train-short", str(tmp_path))
    assert (cell.traffic["batch"], cell.traffic["seq"]) == (4, 512)
    rows = gen.TrainBatches(cell.traffic, 50257, 1).batch_at(0)["tokens"]
    assert rows.shape == (4, 512)
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    assert "step_mfu.train" in [m["name"] for m in cell.per_layer]


def test_serving_waves_hold_the_same_work_for_every_seed():
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", "serve_backlog_48.json")))
    a = gen.wave(traffic, 50257, 1, 1)
    b = gen.wave(traffic, 50257, 2 ** 31 + 99, 1)
    assert sorted((len(p), n) for p, n in a) == \
        sorted((len(p), n) for p, n in b)
    assert all(len(p) + n <= traffic["max_len"] for p, n in a)


def test_training_rows_differ_and_are_seeded():
    traffic = spec.load_cell("gpt2m-train-1chip").traffic
    batches = gen.TrainBatches(dict(traffic, batch=4, seq=64), 50257,
                               2 ** 31 + 5)
    a, b = batches.batch_at(0), batches.batch_at(1)
    assert (a["tokens"] == a["labels"]).all()
    assert len({r.tobytes() for r in list(a["tokens"]) + list(b["tokens"])
                }) == 8
    again = gen.TrainBatches(dict(traffic, batch=4, seq=64), 50257,
                             2 ** 31 + 5)
    assert (again.batch_at(1)["tokens"] == b["tokens"]).all()


# ------------------------------------------------------------------ #
# the numbers that decide correct
# ------------------------------------------------------------------ #

# three checked steps; the key bias's gradient is zero under softmax, so
# Adam moves it by round-off alone
REF = {"losses": [11.0, 12.5, 13.5],
       "grad": {"w": 1.0, "b": 0.5, "bk": 1e-6},
       "change": {"w": 0.3, "b": 0.2, "bk": 0.1}}


def test_a_program_that_matches_the_reference_reads_zero():
    readings = check.train_readings(REF, REF)
    assert readings == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0,
                        "grad_gap.median": 0.0, "change_gap.median": 0.0,
                        "loss_gap.0": 0.0, "loss_gap.1": 0.0,
                        "loss_gap.2": 0.0}
    assert check.passed(check.verdict(readings, {"loss_gap": 0.0}))


def test_the_loss_gap_is_the_widest_checked_step():
    prog = dict(REF, losses=[11.001, 12.53, 13.49])
    readings = check.train_readings(prog, REF)
    assert [readings[f"loss_gap.{k}"] for k in range(3)] == \
        pytest.approx([0.001, 0.03, 0.01])
    assert readings["loss_gap"] == pytest.approx(0.03)


def test_a_norm_gap_is_over_the_larger_of_the_leaf_and_the_median_leaf():
    # median reference gradient 0.5: a gap of 0.01 reads 0.01 on the leaf
    # of norm 1, and 0.02 on the all but zero one
    prog = dict(REF, grad={"w": 1.01, "b": 0.5, "bk": 1e-6})
    assert check.train_readings(prog, REF)["grad_gap"] == pytest.approx(0.01)
    prog = dict(REF, grad={"w": 1.0, "b": 0.5, "bk": 0.01})
    assert check.train_readings(prog, REF)["grad_gap"] == \
        pytest.approx(0.02, rel=1e-3)


def test_a_leaf_moved_by_round_off_alone_is_left_out_of_the_change():
    prog = dict(REF, change={"w": 0.3, "b": 0.2, "bk": 0.5})
    assert check.train_readings(prog, REF)["change_gap"] == 0.0
    # the median is over the leaves kept: 0.25
    prog = dict(REF, change={"w": 0.3, "b": 0.1, "bk": 0.1})
    assert check.train_readings(prog, REF)["change_gap"] == \
        pytest.approx(0.4)


@pytest.mark.parametrize("number,prog", [
    ("loss_gap", dict(REF, losses=[11.0, 12.5])),
    ("grad_gap", dict(REF, grad={"w": 1.0, "b": 0.5})),
    ("change_gap", dict(REF, change={"w": 0.3, "b": 0.2, "other": 0.1})),
])
def test_a_missing_step_or_leaf_fails(number, prog):
    checks = check.verdict(check.train_readings(prog, REF),
                           {number: 1e9})
    assert checks[number]["value"] is None
    assert not check.passed(checks)


# ------------------------------------------------------------------ #
# rehearsals: each cell at a tiny size, whole, under its control, and
# with each fault its timed path can have
# ------------------------------------------------------------------ #

# The limits are set at the cells' own sizes on the chip; at the tiny
# size a sound run's readings only have to stay far below a fault's.
SOUND_AT_TINY_SIZE = 0.05


def _sound(line):
    return all(c["value"] is not None and c["value"] < SOUND_AT_TINY_SIZE
               for c in line["checks"].values())


@pytest.mark.parametrize("workload,fault", [
    ("gpt2m-train-1chip", ""),
    ("gpt2m-train-1chip", "state_unchanged"),
    ("gpt2m-train-1chip", "half_batch"),
    ("gpt2m-train-1chip", "moments_not_carried"),
    ("gpt2m-train-1chip", "update_negated"),
    ("gpt2m-serve-batch", ""),
    ("gpt2m-serve-batch", "token_altered"),
])
def test_rehearsal_on_one_device(workload, fault):
    line = rehearse.rehearse(workload, fault)
    if fault:
        assert line["correct"] is False, line["checks"]
    else:
        assert _sound(line), line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {
        m["name"] for m in rehearse.tiny_cell(workload).end_to_end}


def _reference_steps(seed):
    """``steps(**kw)``: the reference's checked steps of the training
    cell at the tiny size, as ``calibrate.py`` runs them."""
    cell = rehearse.tiny_cell("gpt2m-train-1chip")
    cfg, traffic = cell.config, cell.traffic
    batches = gen.TrainBatches(traffic, cfg["vocab_size"], seed)
    feed = [batches.batch_at(i) for i in range(traffic["check_steps"])]
    return lambda **kw: reference.train_steps(
        cell.family, cfg, traffic["optimizer"], traffic["z_loss"], seed,
        feed, rows=traffic["reference_rows"], **kw)


def test_training_control_reads_far_from_the_program():
    """The reference computed in float8, put in the program's place, at
    a size a test can hold: its median leaf's gradient gap, the number
    that fails it at the cell's own size on the chip (PERF.md), is
    several times the sound program's at the same size, as is its loss
    gap."""
    seed = 2 ** 31 + 7
    steps = _reference_steps(seed)
    ref = steps()
    control = check.train_readings(steps(precision="float8"), ref)
    program = rehearse.rehearse("gpt2m-train-1chip", seed=seed)["checks"]
    assert control["grad_gap.median"] > \
        3 * program["grad_gap.median"]["value"], (control, program)
    assert control["loss_gap"] > 3 * program["loss_gap"]["value"], \
        (control, program)


@pytest.mark.parametrize("fault,number,least", [
    ("half_batch", "grad_gap.median", 0.01),
    ("state_unchanged", "change_gap", 0.99),
    ("moments_not_carried", "change_gap", 0.01),
    ("update_negated", "loss_gap", 0.1),
])
def test_faults_planted_in_the_reference_read_far_from_it(fault, number,
                                                          least):
    """What ``calibrate.py --faults`` reads: each fault planted in the
    reference's own steps, against the sound reference.  A fault that
    starts at step 1 leaves step 0's loss as it was."""
    steps = _reference_steps(2 ** 31 + 11)
    readings = check.train_readings(steps(fault=fault), steps())
    assert readings[number] > least, readings
    if fault != "half_batch":
        assert readings["loss_gap.0"] == 0.0


def test_serving_control_reads_far_from_the_program():
    """At each served position, the token the float8 reference puts
    first, read against the float32 reference, on what the program
    served: its gap is many times the sound program's at the same size
    (at the cell's own size on the chip it fails the cell's limit)."""
    seed = 2 ** 31 + 7
    line = rehearse.rehearse("gpt2m-serve-batch", seed=seed)
    cell = rehearse.tiny_cell("gpt2m-serve-batch")
    g = gen.rng(seed, 9)
    sample = [(prompt, g.integers(0, cell.config["vocab_size"], n))
              for prompt, n in gen.wave(cell.traffic,
                                        cell.config["vocab_size"], seed, 1)]
    control = max(reference.served_gaps(
        cell.family, cell.config, seed, cell.traffic["weight_dtype"],
        sample, control=True))
    program = line["checks"]["served_gap"]["value"]
    assert control > 5 * program, (control, program)
