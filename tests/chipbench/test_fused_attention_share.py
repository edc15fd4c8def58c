"""The reader of the share of attention calls that took the fused
kernel, on steps registered by hand with the notes of their lowering."""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import rehearse  # noqa: E402,F401  (puts the benchmark and src on the path)

from chipbench import program, spec  # noqa: E402
from repro import tracing  # noqa: E402

NAME = "fused_attention_share.train"


def _read(kind="train"):
    return spec.metric_reader(NAME)(SimpleNamespace(kind=kind))


@pytest.fixture
def programs(monkeypatch):
    monkeypatch.setattr(tracing, "_PROGRAMS", {})


@pytest.mark.parametrize("notes,share", [
    ({tracing.FUSED_ATTENTION: 2}, 100.0),
    ({tracing.FUSED_ATTENTION: 3, tracing.CHUNKED_ATTENTION: 1}, 75.0),
    ({tracing.CHUNKED_ATTENTION: 4}, 0.0),
    ({tracing.FUSED_ATTENTION: 1, "other.event": 5}, 100.0),
])
def test_the_share_of_the_registered_steps_attention_calls(programs, notes,
                                                           share):
    tracing.register(program.STEP, object(), compile_s=1.0, notes=notes)
    tracing.register("eval_step", object(), compile_s=1.0,
                     notes={tracing.CHUNKED_ATTENTION: 9})
    assert _read() == pytest.approx(share)


def test_a_serving_context_reads_nothing(programs):
    tracing.register(program.STEP, object(), compile_s=1.0,
                     notes={tracing.FUSED_ATTENTION: 1})
    assert _read("serve") is None


@pytest.mark.parametrize("notes", [None, {}, {"other.event": 2}])
def test_a_step_with_no_attention_notes_reads_nothing(programs, notes):
    tracing.register(program.STEP, object(), compile_s=1.0, notes=notes)
    assert _read() is None


def test_nothing_registered_reads_nothing(programs):
    tracing.register("eval_step", object(), compile_s=1.0,
                     notes={tracing.FUSED_ATTENTION: 1})
    assert _read() is None


def test_a_program_without_notes_reads_nothing(programs, monkeypatch):
    """The registry of a program that counts no notes, as before they
    were added: the step is registered, the reader finds no ``notes``."""
    tracing.register(program.STEP, object(), compile_s=1.0)
    monkeypatch.delattr(tracing, "notes")
    assert _read() is None
    import repro
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read() is None
