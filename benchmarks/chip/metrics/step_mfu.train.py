"""Model FLOP utilisation of the whole training step, from the trace.

The FLOPs of the traced window's whole steps (``counts.
train_flops_per_token`` of the cell's family: forward and backward,
causal attention counted half, recomputation not counted) over the
window's length times the chips times each chip's bf16 peak.  The
window runs from the start of the first traced step to the end of the
last, host gaps included.
"""


def read(ctx):
    if getattr(ctx, "kind", None) != "train" or not ctx.windows:
        return None
    flops = len(ctx.windows) * ctx.tokens_per_step * ctx.flops_per_token
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (ctx.window_s * peak)
