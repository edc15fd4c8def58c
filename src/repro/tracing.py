"""Named scopes and host spans of the training path, and the compiled
programs whose operations they label.

Scopes.  The model and the step put their work under
``jax.named_scope`` with one of the names below; the name lands in each
HLO instruction's ``op_name`` metadata (``.../attention/dot_general``;
backward ops under ``transpose(jvp(attention))``), so a profiler trace,
whose operations are named by instruction, can be cut by scope.

Spans.  ``span(name)`` is ``jax.profiler.TraceAnnotation``: a host span
in the profiler's own trace, on the device trace's clock, that costs
nothing measurable when no profiler runs.

Registry.  ``register(name, compiled, compile_s=...)`` keeps a compiled
program and what its compile took; ``op_scopes(name)`` maps each of its
instructions to the scope it belongs to, parsed from
``compiled.as_text()`` on the first call only.  JAX's persistent
compilation cache leaves debug info, and with it the scopes, out of its
key, so a program served from an entry that unscoped code wrote holds no
scope name: its table is then empty, never a wrong split.

Notes.  ``note(event)`` counts an event of tracing, such as which path
an attention call took (``FUSED_ATTENTION``, ``CHUNKED_ATTENTION``),
into each ``lowering()`` open around it; a note made outside one counts
nowhere.  Tracing runs while a program is lowered, so the caller opens
``lowering()`` around ``lower(...)`` and hands its counts to
``register``; ``notes(name)`` gives them back.  A program served from
the persistent cache is still traced, so its counts are there too.
"""
from __future__ import annotations

import contextlib
import re
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import jax

ATTENTION = "attention"
MLP = "mlp"
LOGITS = "logits"
OPTIMIZER = "optimizer"
SCOPES = (ATTENTION, MLP, LOGITS, OPTIMIZER)

FUSED_ATTENTION = "attention.fused"
CHUNKED_ATTENTION = "attention.chunked"

span = jax.profiler.TraceAnnotation

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')
_INNERMOST = re.compile(r"([^()]*)\)*$")


@dataclass
class Program:
    compiled: Any
    compile_s: float
    scopes: Optional[Dict[str, str]] = field(default=None, repr=False)
    notes: Dict[str, int] = field(default_factory=dict)


_PROGRAMS: Dict[str, Program] = {}
_LOWERING: List[Counter] = []


@contextlib.contextmanager
def lowering() -> Iterator[Counter]:
    """The notes made inside, counted into the ``Counter`` it yields."""
    counts: Counter = Counter()
    _LOWERING.append(counts)
    try:
        yield counts
    finally:
        _LOWERING.remove(counts)


def note(event: str) -> None:
    """Count ``event`` in every ``lowering()`` open."""
    for counts in _LOWERING:
        counts[event] += 1


def register(name: str, compiled, *, compile_s: float,
             notes: Optional[Dict[str, int]] = None) -> None:
    """Keep ``compiled`` (a ``jax.stages.Compiled``) under ``name``, with
    the notes counted while it was lowered; its text is not read until
    ``op_scopes`` asks."""
    _PROGRAMS[name] = Program(compiled, compile_s, notes=dict(notes or {}))


def registered(name: str) -> Optional[Program]:
    return _PROGRAMS.get(name)


def notes(name: str) -> Dict[str, int]:
    """The notes counted while the program registered as ``name`` was
    lowered: empty when nothing is registered or nothing was noted."""
    prog = _PROGRAMS.get(name)
    return dict(prog.notes) if prog else {}


def scope_of(op_name: str) -> Optional[str]:
    """The first scope name that is a component of an ``op_name`` path,
    a component's innermost name counting (``transpose(jvp(mlp))``)."""
    for part in op_name.split("/"):
        inner = _INNERMOST.search(part).group(1)
        if inner in SCOPES:
            return inner
    return None


def scopes_of(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` for the instructions of an HLO
    module's text whose metadata names a scope.  A fusion is attributed
    by its own metadata."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scope = scope_of(m.group(2))
            if scope is not None:
                table[m.group(1)] = scope
    return table


def op_scopes(name: str) -> Dict[str, str]:
    """The scope table of the program registered as ``name``: empty when
    nothing is registered or no instruction names a scope."""
    prog = _PROGRAMS.get(name)
    if prog is None:
        return {}
    if prog.scopes is None:
        prog.scopes = scopes_of(prog.compiled.as_text())
    return prog.scopes
