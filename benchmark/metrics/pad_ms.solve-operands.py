"""The service's pad stage per solve, in ms, read as pad_ms.solve reads
it: at this cell the pad also copies the forbidden mask and the score
into the staging area."""

from benchmark import harness


def read(ctx):
    return harness.load_reader(ctx.run, "pad_ms.solve").read(ctx)
