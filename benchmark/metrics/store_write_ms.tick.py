"""Store and watch ingest, counted by the program: the seconds its
store's Pod writes took (counter `store.write.Pod`, store/store.py:
clone, index and the watch fan-out), summed over the root spans of the
traced window that carry the tracer's counters, in ms per 1,000 events.
The benchmark's store_apply_ms.tick times the same calls from outside,
building the Pod objects included."""

COUNTER = "store.write.Pod"


def counter_s(spans, name):
    """Seconds of the tracer counter `name` over the root spans, or None
    where no root span carries it."""
    found = [
        s["args"]["counters"][name]["seconds"] for s in spans
        if s["parent"] is None and name in s["args"].get("counters", {})
    ]
    return sum(found) if found else None


def read(ctx):
    layers = ctx.window.layers
    spans = layers.get("spans")
    if not spans or not layers.get("events"):
        return None
    seconds = counter_s(spans, COUNTER)
    if seconds is None:
        return None
    return seconds * 1e3 / (layers["events"] / 1e3)
