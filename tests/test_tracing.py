"""The training path's own instrumentation (``repro.tracing``), on the
CPU at a tiny dense size: the named scopes reach the compiled step, the
loop opens its host spans and no other, compiles nothing after set-up,
and keeps its state where the step hook finds it; a compiled program
without the scopes reads as an empty table."""
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.core.plans import get_plan
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.train import train

B, S = 2, 48
STEPS = 3


class Rows:
    n_shards = 1

    def __init__(self, vocab: int):
        self.vocab = vocab

    def batch_at(self, i):
        rows = np.random.default_rng(i).integers(0, self.vocab, (B, S),
                                                 dtype=np.int32)
        return {"tokens": rows, "labels": rows}


@pytest.fixture(scope="module")
def run():
    """One training run of ``STEPS`` steps with a recording stub in place
    of ``tracing.span``, a count of backend compiles from the first step
    hook on, and the hook's view of its caller's locals."""
    cfg = get_config("gpt2m").reduced()
    spans, compiles, frames = [], [], []
    counting = []

    class Recorder:
        def __init__(self, name):
            spans.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def on_compile(event, duration, **kw):
        if counting and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def hook(i):
        frames.append(set(sys._getframe(1).f_locals))
        counting.append(i)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracing, "span", Recorder)
        jax.monitoring.register_event_duration_secs_listener(on_compile)
        try:
            res = train(Model(cfg), get_plan("data"),
                        make_mesh((1, 1), ("data", "model")),
                        TrainConfig(learning_rate=1e-3, remat=True),
                        Rows(cfg.vocab_size), steps=STEPS, log_every=0,
                        on_step_failure=hook)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_compile)
    return dict(cfg=cfg, result=res, spans=spans, compiles=compiles,
                frames=frames, program=tracing.registered("train_step"),
                table=dict(tracing.op_scopes("train_step")))


@pytest.mark.parametrize("scope", tracing.SCOPES)
def test_each_scope_names_instructions_of_the_compiled_step(run, scope):
    assert scope in run["table"].values()


def test_the_attention_scores_map_to_attention(run):
    cfg = run["cfg"]
    # the score matmul: [batch, heads, queries, keys], fp32
    shape = f"f32[{B},{cfg.n_heads},{S},{S}]"
    text = run["program"].compiled.as_text()
    dots = re.findall(r"%?([\w.\-]+) = " + re.escape(shape) + r"\{[^}]*\} dot\(",
                      text)
    assert dots
    assert {run["table"].get(d) for d in dots} == {tracing.ATTENTION}


def test_the_step_is_registered_with_its_compile_time(run):
    assert run["program"].compile_s == run["result"].compile_s > 0


def test_the_loop_opens_its_spans_and_not_the_benchmarks(run):
    assert run["spans"] == ["train.batch", "train.sync"] * STEPS
    assert "train.step" not in run["spans"]
    assert "loader.batch_at" not in run["spans"]


def test_the_loop_compiles_nothing_after_set_up(run):
    assert run["frames"], "the step hook never ran"
    assert run["compiles"] == []


@pytest.mark.parametrize("name", ["params", "opt_state"])
def test_the_step_hook_sees_the_state_in_its_callers_frame(run, name):
    assert len(run["frames"]) == STEPS
    assert all(name in f for f in run["frames"])


def test_the_step_notes_which_attention_path_it_traced(run):
    # on the CPU every attention call keeps the jnp scan
    notes = run["program"].notes
    assert set(notes) == {tracing.CHUNKED_ATTENTION}
    assert notes[tracing.CHUNKED_ATTENTION] >= 1


def test_the_notes_go_with_the_program_registered_after_them(monkeypatch):
    monkeypatch.setattr(tracing, "_PROGRAMS", {})
    tracing.note(tracing.FUSED_ATTENTION)            # no lowering open
    with tracing.lowering() as first:
        tracing.note(tracing.FUSED_ATTENTION)
        with tracing.lowering() as inner:
            tracing.note(tracing.FUSED_ATTENTION)
        tracing.note(tracing.CHUNKED_ATTENTION)
    tracing.register("train_step", object(), compile_s=0.1, notes=first)
    with tracing.lowering() as second:
        pass
    tracing.register("other_step", object(), compile_s=0.1, notes=second)
    assert tracing.notes("train_step") == {tracing.FUSED_ATTENTION: 2,
                                           tracing.CHUNKED_ATTENTION: 1}
    assert inner == {tracing.FUSED_ATTENTION: 1}
    assert tracing.notes("other_step") == {}
    assert tracing.notes("not_registered") == {}
    tracing.note(tracing.CHUNKED_ATTENTION)          # after: counts nowhere
    assert tracing.notes("train_step")[tracing.CHUNKED_ATTENTION] == 1


def test_a_step_without_the_scopes_reads_as_an_empty_table(monkeypatch):
    monkeypatch.setattr(tracing, "_PROGRAMS", {})

    def attention_forward(x):              # a name, not a scope
        return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((8, 8), jnp.float32)
    compiled = jax.jit(jax.grad(attention_forward)).lower(x).compile()
    assert "attention" in compiled.as_text()
    tracing.register("train_step", compiled, compile_s=0.1)
    assert tracing.op_scopes("train_step") == {}
    assert tracing.op_scopes("not_registered") == {}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp()/while/body/attention/dot_general", "attention"),
    ("jit(train_step)/transpose(jvp(logits))/reduce_sum", "logits"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/mul", "mlp"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/logits/jit(attention)/add", "logits"),
    ("jit(chunked_attention)/dot_general", None),
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice",
     None),
])
def test_an_op_takes_the_first_scope_of_its_name_path(op_name, scope):
    assert tracing.scope_of(op_name) == scope
