"""Census: the program's `pendingcapacity.census` spans (building the
occupancy census of a constrained solve,
metrics/producers/pendingcapacity/__init__.py) plus its `census.query`
counter (the DomainCensus queries inside the spread/anti row expansion,
encoder.py), in ms per MetricsProducer batch of the traced window. None
where the program opens no such span (an unconstrained fleet, or a
program without it)."""

CENSUS, QUERY = "pendingcapacity.census", "census.query"


def query_s(spans):
    """Seconds of the `census.query` counter over the root spans (0 where
    none carries it)."""
    return sum(
        s["args"]["counters"][QUERY]["seconds"] for s in spans
        if s["parent"] is None and QUERY in s["args"].get("counters", {})
    )


def read(ctx):
    layers = ctx.window.layers
    spans = layers.get("spans")
    builds = [s["dur_us"] for s in spans or () if s["name"] == CENSUS]
    if not builds or not layers.get("mp_batches"):
        return None
    return (sum(builds) / 1e6 + query_s(spans)) * 1e3 / layers["mp_batches"]
