"""What the training and serving cells share: the program's model from
a cell's family, its mesh and plan from the traffic file, the device
record, the profiler window and the per-layer metrics read from it."""
from __future__ import annotations

import glob
import os
import shutil
import sys
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Sequence

from chipbench import counts, tracefile
from chipbench.spec import ROOT, Cell, metric_reader

TRACE_DIR = os.path.join(ROOT, ".bench_traces")


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def chips_for(cell: Cell, require_tpu: bool = True) -> List[Any]:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devices[0].platform!r}; "
                     f"the benchmark has no CPU fallback")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:cell.chips]


def program_model(cell: Cell):
    """The program's ``Model`` of the cell's configuration, as its family
    reads the file."""
    from repro.models import Model
    return Model(cell.family.program.program_config(cell.config))


def make_mesh(traffic: Dict[str, Any], devices: Sequence[Any]):
    """The mesh and plan a traffic file names."""
    from repro.core.pipeline import pipeline_mesh
    from repro.core.plans import get_plan
    from repro.launch.mesh import make_mesh as program_mesh
    shape = tuple(traffic["mesh"])
    axes = ("pod", "data", "model")[-len(shape):]
    base = program_mesh(shape, axes, devices=list(devices))
    plan = get_plan(traffic["plan"])
    mesh = pipeline_mesh(base, traffic["stages"]) if plan.pipeline else base
    return plan, mesh


def device_record(devices: Sequence[Any]) -> Dict[str, Any]:
    """The devices as JAX reports them.  The memory peak of a TPU chip is
    its buffers' peak (``peak_bytes_in_use``) plus the peak it reserved
    for the programs' temporaries (``peak_bytes_reserved``), which the
    first leaves out."""
    peak = None
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"memory_stats {d.id} {stats}", file=sys.stderr)
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"])
                       + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class Profile:
    """One profiler window in a fixed directory inside the checkout."""

    def __init__(self, name: str, enabled: bool):
        self.enabled = enabled
        self.dir = os.path.join(TRACE_DIR, name)
        self.active = False
        self.path: Optional[str] = None

    def start(self) -> None:
        if not self.enabled or self.active or self.path:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        jax.profiler.stop_trace()
        self.active = False
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.path = max(found, key=os.path.getmtime) if found else None

    def span(self, name: str):
        """A host span in the trace while the profiler runs."""
        import contextlib
        import jax
        if self.active:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


def layer_metrics(cell: Cell, ctx: SimpleNamespace) -> Dict[str, Any]:
    """Each per-layer metric of the cell that its reader finds something
    to read for, with its unit."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def trace_context(cell: Cell, path: str, devices: Sequence[Any],
                  window_span: str, **extra) -> SimpleNamespace:
    """What a metric reader reads: the trace cut to the chips the cell
    uses, the window (the first to the last closed ``window_span``), the
    chips' peaks, the cell's family and whatever the cell adds."""
    trace = tracefile.load(path)
    used = {f"/device:TPU:{d.id}" for d in devices}
    trace.ops = {k: v for k, v in trace.ops.items() if k in used}
    trace.modules = {k: v for k, v in trace.modules.items() if k in used}
    windows = tracefile.spans(trace, window_span)
    if not windows:
        raise RuntimeError(f"the trace holds no {window_span!r} span")
    lo, hi = windows[0][0], windows[-1][1]
    return SimpleNamespace(
        trace=trace, lo=lo, hi=hi, window_s=(hi - lo) / 1e9,
        windows=windows, chips=len(devices), family=cell.family,
        peaks=counts.peaks(devices[0].device_kind), **extra)


def traced_device(ctx: SimpleNamespace) -> Dict[str, float]:
    busy = tracefile.busy_ns(ctx.trace, ctx.lo, ctx.hi)
    return {"busy_s": tracefile.mean(busy.values()) / 1e9,
            "window_s": ctx.window_s}
