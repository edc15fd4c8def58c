"""Device idle per training step, in ms, while the loop's
``train.batch`` span was open (the next batch read and put on the
device): the idle intervals of the traced window intersected with the
span's, over the executions of the step's module there; the mean over
the cell's chips."""
from chipbench import program


def read(ctx):
    return program.wait_ms(ctx, "train.batch")
