"""Share of the traced training window in which a collective ran on a
chip and no compute did, the mean over the cell's chips.  A chip with no
collective in the window reads nothing."""
from chipbench import tracefile


def read(ctx):
    if getattr(ctx, "kind", None) != "train":
        return None
    if not tracefile.has_collectives(ctx.trace):
        return None
    exposed = tracefile.exposed_collective_ns(ctx.trace, ctx.lo, ctx.hi)
    return 100.0 * tracefile.mean(exposed.values()) / (ctx.hi - ctx.lo)
