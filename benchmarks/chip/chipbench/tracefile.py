"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps,
exposed collectives and time per XLA module.

On a TPU each chip is a plane ``/device:TPU:n`` with the lines ``XLA
Modules`` (one event per execution of a jitted program, named
``jit_<function>(<id>)``), ``XLA Ops`` (the operations that occupy the
chip, each named by its HLO instruction text, ``%fusion.12 = ...``) and
``Async XLA Ops`` (copies and collectives in flight between their start
and done ops).  Host spans sit on the host planes, on the same clock.

``load`` keeps the operations of each chip (by instruction name), the
asynchronous collectives, the module executions and the host spans that
the benchmark's own files opened (names with one of its prefixes).
Everything else is arithmetic on intervals, so the tests build a
``Trace`` by hand.

    python benchmarks/chip/chipbench/tracefile.py <file.xplane.pb>

prints the planes, lines, event counts and sample names of a trace, to
look at one by hand.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int, str]          # (start ns, end ns, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "engine.", "train.", "loader.", "serve.")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv)")
LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_label(hlo: str) -> str:
    """A short label for the breakdown: the instruction's name, opcode
    and result shapes without layouts."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo
    rest = LAYOUT.sub("", rest.split(", calls=")[0])
    m = re.match(r"(\(.*?\)|\S+) ([a-z][\w\-]*)\(", rest)
    if not m:
        return name.lstrip("%")
    return f"{name.lstrip('%')} {m.group(2)} {m.group(1)}"


@dataclass
class Trace:
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    collectives: Dict[str, List[Interval]] = field(default_factory=dict)
    modules: Dict[str, List[Interval]] = field(default_factory=dict)
    spans: List[Interval] = field(default_factory=list)


def load(path: str, span_prefixes: Sequence[str] = SPAN_PREFIXES
         ) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                out.ops.setdefault(plane.name, []).extend(
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events)
            elif dev and line.name == ASYNC_LINE:
                out.collectives.setdefault(plane.name, []).extend(
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events if is_collective(e.name))
            elif dev and line.name == MODULES_LINE:
                out.modules.setdefault(plane.name, []).extend(
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events)
            elif not dev:
                out.spans.extend(
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events
                    if e.name.startswith(tuple(span_prefixes)))
    for d in (out.ops, out.collectives, out.modules):
        for evs in d.values():
            evs.sort()
    out.spans.sort()
    return out


# ------------------------------------------------------------------ #
# interval arithmetic
# ------------------------------------------------------------------ #

def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    out: List[List[int]] = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Tuple], lo: int, hi: int) -> List[Tuple]:
    return [(max(iv[0], lo), min(iv[1], hi)) + tuple(iv[2:])
            for iv in intervals if iv[1] > lo and iv[0] < hi]


def length(merged: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


def leaves(ops: Sequence[Interval]) -> List[Interval]:
    """Operations that contain no other operation: a control-flow op that
    spans its body's ops is left out, so no time counts twice."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, (s, e, n) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[0] >= e or nxt[1] > e:
            out.append((s, e, n))
    return out


def is_collective(hlo: str) -> bool:
    return bool(COLLECTIVE.match(op_name(hlo)))


# ------------------------------------------------------------------ #
# reductions over a window [lo, hi) of the trace clock
# ------------------------------------------------------------------ #

def busy_ns(trace: Trace, lo: int, hi: int) -> Dict[str, int]:
    """Per chip: time in which some operation ran, within the window."""
    return {d: length(merge(clip(ops, lo, hi)))
            for d, ops in trace.ops.items()}


def exposed_collective_ns(trace: Trace, lo: int, hi: int
                          ) -> Dict[str, int]:
    """Per chip: time in which a collective ran or was in flight and no
    compute ran."""
    out = {}
    for d, ops in trace.ops.items():
        ops = clip(leaves(ops), lo, hi)
        coll = merge([o for o in ops if is_collective(o[2])]
                     + clip(trace.collectives.get(d, []), lo, hi))
        comp = merge(o for o in ops if not is_collective(o[2]))
        out[d] = length(merge(coll + comp)) - length(comp)
    return out


def has_collectives(trace: Trace) -> bool:
    return any(trace.collectives.values()) or any(
        is_collective(o[2]) for ops in trace.ops.values() for o in ops)


def module_ns(trace: Trace, pattern: str, lo: int, hi: int
              ) -> Dict[str, List[int]]:
    """Per chip: the durations of the executions of XLA modules whose
    name matches ``pattern``, within the window."""
    rx = re.compile(pattern)
    return {d: [e - s for s, e, n in clip(mods, lo, hi) if rx.search(n)]
            for d, mods in trace.modules.items()}


def spans(trace: Trace, name: str) -> List[Interval]:
    return [s for s in trace.spans if s[2] == name]


def mean(values: Iterable[float]) -> float:
    values = list(values)
    return sum(values) / len(values) if values else 0.0


def _label(gap: Tuple[int, int], host: Sequence[Interval]) -> str:
    """The innermost host span over the middle of a gap."""
    mid = (gap[0] + gap[1]) // 2
    inside = [s for s in host if s[0] <= mid < s[1]]
    if not inside:
        return "no benchmark span"
    return min(inside, key=lambda s: s[1] - s[0])[2]


def breakdown(trace: Trace, lo: int, hi: int, top: int = 10
              ) -> Dict[str, List[List]]:
    """The device operations that took most time (seconds per chip, mean
    over chips, by operation, control-flow ops left out), and the time
    in which no operation ran, by the host span the benchmark had open
    (seconds per chip)."""
    n = max(len(trace.ops), 1)
    op_s: Dict[str, float] = defaultdict(float)
    idle_s: Dict[str, float] = defaultdict(float)
    for ops in trace.ops.values():
        for s, e, name in clip(leaves(ops), lo, hi):
            op_s[op_label(name)] += (e - s) / 1e9 / n
        busy = merge(clip(ops, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle_s[_label((a, b), trace.spans)] += (b - a) / 1e9 / n
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"device_ops": rank(op_s), "idle_gaps": rank(idle_s)}


# ------------------------------------------------------------------ #

def summarize(path: str, out=sys.stdout, sample: int = 8) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events", file=out)
            names = defaultdict(int)
            for e in evs:
                names[e.name] += 1
            for name, c in sorted(names.items(), key=lambda x: -x[1])[
                    :sample]:
                print(f"    {c:7d} x {name!r}", file=out)
            for e in evs[:2]:
                print(f"    e.g. {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {dict(e.stats)}", file=out)


if __name__ == "__main__":
    summarize(sys.argv[1])
