"""The readers of the program's own instrumentation: device time per
step by named scope, device idle per step under the loop's host spans
by interval intersection, and the registered compile time, on a trace
built by hand and a stub compiled step."""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import rehearse  # noqa: E402,F401  (puts the benchmark and src on the path)

from chipbench import program, spec, tracefile  # noqa: E402
from repro import tracing  # noqa: E402

SCOPE_METRICS = {tracing.ATTENTION: "attention_ms.train",
                 tracing.MLP: "mlp_ms.train",
                 tracing.LOGITS: "logits_ms.train",
                 tracing.OPTIMIZER: "optimizer_ms.train"}
READERS = sorted(SCOPE_METRICS.values()) + [
    "input_wait_ms.train", "sync_wait_ms.train", "compile_s.train"]
MS = 1_000_000                                   # ns


def _hlo(name, scope_path):
    return (f'  %{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, '
            f'calls=%fused_computation.1, metadata={{op_name="'
            f'jit(train_step)/{scope_path}/dot_general" source_line=1}}')


# the compiled step's text: one instruction per scope, one in none, and
# a while loop whose body holds the attention and MLP instructions
STEP_TEXT = "\n".join([
    "HloModule jit_train_step",
    _hlo("fusion.1", "jvp()/while/body/attention"),
    _hlo("fusion.2", "transpose(jvp())/while/body/checkpoint/mlp"),
    _hlo("fusion.3", "transpose(jvp(logits))"),
    _hlo("fusion.4", "optimizer"),
    _hlo("fusion.5", "jvp()/embed"),
    '  %while.6 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%c, '
    'body=%b, metadata={op_name="jit(train_step)/jvp()/while"}',
])
ATTN = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"


class StubCompiled:
    def __init__(self, text):
        self.text, self.reads = text, 0

    def as_text(self):
        self.reads += 1
        return self.text


def _trace():
    """One chip, a window [0, 100 ms) with two executions of the step's
    module, [10, 50) and [60, 95), and one of another module [95, 98)
    whose instruction names collide with the step's.

    Step 1: a while op [10, 30) over attention [10, 20) and MLP
    [20, 30); logits [30, 40); optimizer [40, 45); an unscoped op
    [45, 50).  Step 2: attention [60, 80), logits [80, 90), optimizer
    [90, 95).  Idle: [0, 10), [50, 60), [98, 100).

    Host spans: train.batch [0, 8) and [48, 56); train.sync [8, 50)
    and [56, 95), so the gap [50, 60) is shared, 6 ms under train.batch
    and 4 ms under train.sync; by its midpoint (55) it would all be put
    down to train.batch."""
    t = tracefile.Trace()
    t.ops["/device:TPU:0"] = [
        (10 * MS, 30 * MS, "%while.6 = (f32[8]{0}) while(...)"),
        (10 * MS, 20 * MS, ATTN),
        (20 * MS, 30 * MS, "%fusion.2 = f32[8]{0} fusion(...)"),
        (30 * MS, 40 * MS, "%fusion.3 = f32[8]{0} fusion(...)"),
        (40 * MS, 45 * MS, "%fusion.4 = f32[8]{0} fusion(...)"),
        (45 * MS, 50 * MS, "%fusion.5 = f32[8]{0} fusion(...)"),
        (60 * MS, 80 * MS, ATTN),
        (80 * MS, 90 * MS, "%fusion.3 = f32[8]{0} fusion(...)"),
        (90 * MS, 95 * MS, "%fusion.4 = f32[8]{0} fusion(...)"),
        (95 * MS, 98 * MS, ATTN)]
    t.modules["/device:TPU:0"] = [
        (10 * MS, 50 * MS, "jit_train_step(7)"),
        (60 * MS, 95 * MS, "jit_train_step(7)"),
        (95 * MS, 98 * MS, "jit_norms(3)")]
    t.spans = [(0, 8 * MS, "train.batch"), (8 * MS, 50 * MS, "train.sync"),
               (48 * MS, 56 * MS, "train.batch"),
               (56 * MS, 95 * MS, "train.sync"),
               (0, 50 * MS, "train.step"), (50 * MS, 100 * MS, "train.step")]
    return t


def _ctx(trace=None):
    return SimpleNamespace(kind="train", trace=trace or _trace(), lo=0,
                           hi=100 * MS)


@pytest.fixture
def stub_step(monkeypatch):
    monkeypatch.setattr(tracing, "_PROGRAMS", {})
    compiled = StubCompiled(STEP_TEXT)
    tracing.register("train_step", compiled, compile_s=2.5)
    return compiled


@pytest.mark.parametrize("scope, per_step_ms", [
    (tracing.ATTENTION, (10 + 20) / 2), (tracing.MLP, 10 / 2),
    (tracing.LOGITS, (10 + 10) / 2), (tracing.OPTIMIZER, (5 + 5) / 2)])
def test_scope_time_is_per_execution_of_the_step(stub_step, scope,
                                                 per_step_ms):
    read = spec.metric_reader(SCOPE_METRICS[scope])
    assert read(_ctx()) == pytest.approx(per_step_ms)


def test_the_step_text_is_parsed_once_on_the_first_read(stub_step):
    assert stub_step.reads == 0
    for name in SCOPE_METRICS.values():
        spec.metric_reader(name)(_ctx())
    assert stub_step.reads == 1


def test_scope_times_leave_out_control_flow_and_other_modules(stub_step):
    # leaf ops of the two steps: 75 ms busy, of which 5 ms unscoped; the
    # while op and the other module's colliding op count for nothing
    total = sum(spec.metric_reader(n)(_ctx())
                for n in SCOPE_METRICS.values())
    assert total == pytest.approx((75 - 5) / 2)


def test_idle_is_split_by_interval_intersection(stub_step):
    ctx = _ctx()
    batch = spec.metric_reader("input_wait_ms.train")(ctx)
    sync = spec.metric_reader("sync_wait_ms.train")(ctx)
    # idle [0, 10): 8 under batch, 2 under sync; [50, 60): 6 and 4;
    # [98, 100) under neither
    assert batch == pytest.approx((8 + 6) / 2)
    assert sync == pytest.approx((2 + 4) / 2)
    idle_share = spec.metric_reader("device_idle_share.train")(ctx)
    idle_per_step_ms = idle_share / 100 * 100 / 2   # 22 ms over 2 steps
    assert idle_per_step_ms == pytest.approx(11)
    assert batch + sync <= idle_per_step_ms


def test_a_shared_gap_goes_whole_to_one_span_by_its_midpoint(stub_step):
    gaps = dict(tracefile.breakdown(_trace(), 0, 100 * MS)["idle_gaps"])
    # [0, 10) by its midpoint 5 and [50, 60) by 55: all to train.batch
    assert gaps["train.batch"] == pytest.approx(20e-3)
    assert "train.sync" not in gaps
    assert spec.metric_reader("sync_wait_ms.train")(_ctx()) > 0


def test_compile_time_is_the_registered_one(stub_step):
    assert spec.metric_reader("compile_s.train")(_ctx()) == 2.5


def test_the_window_must_hold_a_step(stub_step):
    t = _trace()
    t.modules["/device:TPU:0"] = [(95 * MS, 98 * MS, "jit_norms(3)")]
    for name in READERS[:-1]:
        assert spec.metric_reader(name)(_ctx(t)) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_registered_reads_nothing(monkeypatch, name):
    monkeypatch.setattr(tracing, "_PROGRAMS", {})
    assert spec.metric_reader(name)(_ctx()) is None


@pytest.mark.parametrize("name", READERS)
def test_a_step_without_scopes_reads_nothing_for_them(monkeypatch, name):
    monkeypatch.setattr(tracing, "_PROGRAMS", {})
    tracing.register("train_step", StubCompiled(
        STEP_TEXT.replace("attention", "a").replace("mlp", "m")
        .replace("logits", "l").replace("optimizer", "o")), compile_s=2.5)
    value = spec.metric_reader(name)(_ctx())
    if name in SCOPE_METRICS.values():
        assert value is None
    else:
        assert value is not None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_tracing_reads_nothing(monkeypatch, name):
    import repro
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert program.registry() is None
    assert spec.metric_reader(name)(_ctx()) is None


@pytest.mark.parametrize("name", READERS)
def test_a_serving_trace_reads_nothing(stub_step, name):
    ctx = _ctx()
    ctx.kind = "serve"
    assert spec.metric_reader(name)(ctx) is None
