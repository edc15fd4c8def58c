"""Decode step's share of its HBM roofline.

The least time at the chip's HBM peak for the bytes each traced decode
step needs (``counts.decode_step_bytes``: the parameters as stored and
the keys and values of the positions the live slots attend to), over the
device time of the decode module's executions, summed over the steps.
Decode is bound by memory at these sizes: its FLOPs at the bf16 peak
take under a tenth of this bound.
"""
from chipbench import tracefile

DECODE_MODULE = r"decode_slots"


def read(ctx):
    if getattr(ctx, "kind", None) != "serve" or not ctx.decode_bytes:
        return None
    runs = tracefile.module_ns(ctx.trace, DECODE_MODULE, ctx.lo, ctx.hi)
    times = [t for ts in runs.values() for t in ts] if runs else []
    n = min(len(times), len(ctx.decode_bytes))
    if not n:
        return None
    least_s = sum(ctx.decode_bytes[:n]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(times[:n]) / 1e9)
