"""Manager: the program's `reconcile.tick` root span per resync tick, in
ms (controllers/engine.py `reconcile_all`: the pass lock, every
controller's reconcile, the solver's gauges and the tick hook), from
the tracer's spans of the traced window."""


def read(ctx):
    spans = ctx.window.layers.get("spans")
    ticks = [s["dur_us"] for s in spans or () if s["name"] == "reconcile.tick"]
    return sum(ticks) / len(ticks) / 1e3 if ticks else None
