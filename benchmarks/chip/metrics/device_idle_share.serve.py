"""Share of the traced serving wave in which no operation ran on a chip,
the mean over the cell's chips."""
from chipbench import tracefile


def read(ctx):
    if getattr(ctx, "kind", None) != "serve" or not ctx.trace.ops:
        return None
    busy = tracefile.busy_ns(ctx.trace, ctx.lo, ctx.hi)
    return 100.0 * (1.0 - tracefile.mean(busy.values()) / (ctx.hi - ctx.lo))
