"""Seconds the training loop took to lower and compile its step (or to
load it from the compilation cache), as the program registered it
beside the compiled step (``repro.tracing``); part of ``setup_s``."""
from chipbench import program


def read(ctx):
    if getattr(ctx, "kind", None) != "train":
        return None
    return program.compile_s()
