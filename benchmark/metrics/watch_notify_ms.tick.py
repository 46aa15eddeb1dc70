"""Store and watch ingest, counted by the program: the watch fan-out of
its Pod writes (counter `store.notify.Pod`, store/store.py: every
watcher callback of a Pod event), summed over the root spans of the
traced window, in ms per 1,000 events. The log line splits the fan-out
of every kind by watcher (the `watch.<owner>` counters)."""

import sys

from benchmark import harness

COUNTER = "store.notify.Pod"
OWNER = "watch."


def read(ctx):
    layers = ctx.window.layers
    spans = layers.get("spans")
    if not spans or not layers.get("events"):
        return None
    counter_s = harness.load_reader(ctx.run, "store_write_ms.tick").counter_s
    seconds = counter_s(spans, COUNTER)
    if seconds is None:
        return None
    per_k = 1e3 / layers["events"]
    owners = {
        name for s in spans if s["parent"] is None
        for name in s["args"].get("counters", {}) if name.startswith(OWNER)
    }
    watched = {name: counter_s(spans, name) for name in owners}
    total = sum(watched.values())
    if total > 0:
        parts = ", ".join(
            f"{name} {s * 1e3 * per_k:.3f} ms ({100.0 * s / total:.1f}%)"
            for name, s in sorted(watched.items(), key=lambda kv: -kv[1])
        )
        print(f"watch fan-out per 1,000 events, every kind: {parts}", file=sys.stderr)
    return seconds * 1e3 * per_k
