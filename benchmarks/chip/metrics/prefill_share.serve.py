"""Share of the traced serving wave that the chips spent in the
bucketed batch-1 prefill and the slot insert modules, the mean over the
cell's chips."""
from chipbench import tracefile

MODULES = r"prefill_at|insert"


def read(ctx):
    if getattr(ctx, "kind", None) != "serve":
        return None
    runs = tracefile.module_ns(ctx.trace, MODULES, ctx.lo, ctx.hi)
    if not any(runs.values()):
        return None
    return 100.0 * tracefile.mean(sum(v) for v in runs.values()) \
        / (ctx.hi - ctx.lo)
