"""Device time per training step, in ms, of the operations that the
compiled step puts in the program's ``logits`` named scope
(``repro.tracing.LOGITS``): leaf operations only, cut to the traced
window, over the executions of the step's module there; the mean over
the cell's chips.  Nothing to read on a program without the scopes."""
from chipbench import program


def read(ctx):
    tracing = program.registry()
    return program.scope_ms(ctx, tracing.LOGITS) if tracing else None
