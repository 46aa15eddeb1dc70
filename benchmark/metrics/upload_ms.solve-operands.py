"""The service's upload stage per solve, in ms, read as upload_ms.solve
reads it (on the one-chip Pallas path the `jax.device_put` call alone):
at this cell it carries the forbidden mask and the score too."""

from benchmark import harness


def read(ctx):
    return harness.load_reader(ctx.run, "upload_ms.solve").read(ctx)
