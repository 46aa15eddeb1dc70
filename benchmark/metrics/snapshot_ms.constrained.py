"""Store read: the program's `pendingcapacity.snapshot` spans (a
MetricsProducer batch reading the watch-maintained pending-pod arena,
compaction check included, metrics/producers/pendingcapacity/__init__.py),
in ms per MetricsProducer batch of the traced window. None where the
program opens no such span."""

SNAPSHOT = "pendingcapacity.snapshot"


def read(ctx):
    layers = ctx.window.layers
    reads = [s["dur_us"] for s in layers.get("spans") or () if s["name"] == SNAPSHOT]
    if not reads or not layers.get("mp_batches"):
        return None
    return sum(reads) / 1e3 / layers["mp_batches"]
