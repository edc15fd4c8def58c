"""Model FLOP utilisation of serving, from the trace.

The forward FLOPs the traced wave's requests need (the cell's family's
``serve_flops``: each prompt's prefill, causal attention counted half,
and each decode step at its context) over the wave's length times the
chips times each chip's bf16 peak.  The wave runs from its first
admission to its last token, host gaps and the drain included.
"""


def read(ctx):
    if getattr(ctx, "kind", None) != "serve" or not ctx.traced_requests:
        return None
    flops = ctx.family.program.serve_flops(ctx.model, ctx.traced_requests)
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (ctx.window_s * peak)
