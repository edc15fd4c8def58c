"""Share, in %, of the training step's attention calls that took the
fused flash kernel: the program's notes (``repro.tracing.notes``),
counted while it lowered the registered step, of ``attention.fused``
over ``attention.fused`` and ``attention.chunked``.  Nothing to read on
a program without the notes, or whose step noted no attention call."""
from chipbench import program


def read(ctx):
    if getattr(ctx, "kind", None) != "train":
        return None
    tracing = program.registry()
    notes = getattr(tracing, "notes", None) if tracing else None
    counts = notes(program.STEP) if notes else {}
    fused = counts.get("attention.fused", 0)
    calls = fused + counts.get("attention.chunked", 0)
    return 100.0 * fused / calls if calls else None
