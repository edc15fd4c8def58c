"""Device idle per training step, in ms, while the loop's
``train.sync`` span was open (``float(loss)``, which waits for the
step): the idle intervals of the traced window intersected with the
span's, over the executions of the step's module there; the mean over
the cell's chips."""
from chipbench import program


def read(ctx):
    return program.wait_ms(ctx, "train.sync")
