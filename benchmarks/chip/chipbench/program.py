"""What the program's own instrumentation (``repro.tracing``) gives the
training readers: device time per step by named scope, device idle per
step under the loop's host spans, and the compile time of the step.

Each function returns None, and never raises, where there is nothing to
read: a program without ``repro.tracing``, no compiled ``train_step``
registered, no operation of it mapped to a scope (a program served from
a cache entry that unscoped code wrote), no step in the window, or no
such span in the trace.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import tracefile

STEP = "train_step"
STEP_MODULE = r"^jit_train_step\b"


def registry():
    """The program's ``repro.tracing``, or None where it has none."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def compile_s() -> Optional[float]:
    tracing = registry()
    prog = tracing.registered(STEP) if tracing else None
    return float(prog.compile_s) if prog else None


def _steps(ctx) -> Dict[str, int]:
    """Per chip: the executions of the step's module in the window."""
    return {d: len(v) for d, v in tracefile.module_ns(
        ctx.trace, STEP_MODULE, ctx.lo, ctx.hi).items() if v}


def _inside(ops: Sequence[tracefile.Interval],
            modules: Sequence[tracefile.Interval]
            ) -> List[tracefile.Interval]:
    """The operations that start inside one of ``modules``."""
    spans = tracefile.merge(modules)
    out, j = [], 0
    for op in sorted(ops):
        while j < len(spans) and spans[j][1] <= op[0]:
            j += 1
        if j < len(spans) and spans[j][0] <= op[0]:
            out.append(op)
    return out


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device time per step, in ms, of the step's leaf operations that
    the compiled step puts in ``scope``; the mean over chips."""
    if getattr(ctx, "kind", None) != "train":
        return None
    tracing = registry()
    table = tracing.op_scopes(STEP) if tracing else {}
    steps = _steps(ctx)
    if not table or not steps:
        return None
    per_chip = []
    for d, n in steps.items():
        mods = [m for m in ctx.trace.modules[d]
                if re.search(STEP_MODULE, m[2])]
        ops = _inside(tracefile.clip(tracefile.leaves(ctx.trace.ops.get(
            d, [])), ctx.lo, ctx.hi), mods)
        ns = sum(e - s for s, e, name in ops
                 if table.get(tracefile.op_name(name)) == scope)
        per_chip.append(ns / n / 1e6)
    return tracefile.mean(per_chip)


def idle_intervals(ctx, device: str) -> List[Tuple[int, int]]:
    """The window's intervals in which no operation ran on ``device``."""
    busy = tracefile.merge(tracefile.clip(ctx.trace.ops.get(device, []),
                                          ctx.lo, ctx.hi))
    edges = [ctx.lo] + [x for iv in busy for x in iv] + [ctx.hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def overlap_ns(a: Sequence[Tuple[int, int]],
               b: Sequence[Tuple[int, int]]) -> int:
    """Length of the intersection of two unions of intervals."""
    a, b = tracefile.merge(a), tracefile.merge(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def wait_ms(ctx, span: str) -> Optional[float]:
    """Device idle per step, in ms, while the loop's host span ``span``
    was open: the idle intervals intersected with the span's, so a gap
    that two spans share is split between them; the mean over chips."""
    if getattr(ctx, "kind", None) != "train":
        return None
    tracing = registry()
    spans = tracefile.spans(ctx.trace, span)
    steps = _steps(ctx)
    if not tracing or not tracing.registered(STEP) or not spans \
            or not steps:
        return None
    return tracefile.mean(
        overlap_ns(idle_intervals(ctx, d), [s[:2] for s in spans])
        / n / 1e6 for d, n in steps.items())
