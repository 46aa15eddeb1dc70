"""Encode: the program's `pendingcapacity.expand` spans (the spread and
anti-affinity row expansions of a constrained encode,
metrics/producers/pendingcapacity/encoder.py) less the `census.query`
counter inside them, which census_ms.constrained reads, in ms per
MetricsProducer batch of the traced window. None where the program
opens no such span."""

import importlib.util
from pathlib import Path

EXPAND = "pendingcapacity.expand"


def _census_reader():
    path = Path(__file__).with_name("census_ms.constrained.py")
    spec = importlib.util.spec_from_file_location("bench_metric_census_ms_constrained", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read(ctx):
    layers = ctx.window.layers
    spans = layers.get("spans")
    expands = [s["dur_us"] for s in spans or () if s["name"] == EXPAND]
    if not expands or not layers.get("mp_batches"):
        return None
    return (sum(expands) / 1e6 - _census_reader().query_s(spans)) * 1e3 / layers["mp_batches"]
