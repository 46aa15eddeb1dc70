"""The service's scatter stage per solve, in ms: its own
stage_seconds{scatter} (solver/service.py: the outputs' fetch to the
host and the crop to the caller's shape), summed over the window by the
benchmark's wrapper of its stage recorder, over the solves."""


def read(ctx):
    layers = ctx.window.layers
    seconds = layers.get("stages", {}).get("scatter")
    if seconds is None or not layers.get("solves"):
        return None
    return seconds / layers["solves"] * 1e3
