"""The service's pad stage per solve, in ms: its own stage_seconds{pad}
(solver/service.py: padding to the shape bucket and, on the single-chip
Pallas path, the operand fold), summed over the window by the
benchmark's wrapper of its stage recorder, over the solves."""


def read(ctx):
    layers = ctx.window.layers
    seconds = layers.get("stages", {}).get("pad")
    if seconds is None or not layers.get("solves"):
        return None
    return seconds / layers["solves"] * 1e3
