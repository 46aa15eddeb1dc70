"""snapshot_ms.constrained on hand-made span lists: the
`pendingcapacity.snapshot` spans per MetricsProducer batch, and None
where the program opens no such span."""

from __future__ import annotations

from pathlib import Path

import pytest

from benchmark import harness


def _read(layers):
    run = harness.Run("w", 1, 1.0, True, Path("."), {}, {"chips": 1}, {}, {})
    ctx = harness.Context(run, harness.Window(1, 0, 1.0, {}, layers), None, None)
    return harness.load_reader(run, "snapshot_ms.constrained").read(ctx)


def _span(name, dur_us, parent="tick", **args):
    return {"name": name, "id": name, "parent": parent, "dur_us": dur_us, "args": args}


SPANS = [
    _span("reconcile.tick", 90_000.0, parent=None),
    _span("pendingcapacity.snapshot", 4_000.0, compacted=False),
    _span("pendingcapacity.census", 11_000.0),
    _span("pendingcapacity.expand", 60_000.0),
    _span("pendingcapacity.snapshot", 8_000.0, compacted=True),
]


def test_snapshot_spans_per_mp_batch():
    assert _read({"mp_batches": 2, "spans": SPANS}) == pytest.approx(6.0)


@pytest.mark.parametrize("layers", [
    # the parent program: no such span
    {"mp_batches": 2, "spans": [s for s in SPANS if s["name"] != "pendingcapacity.snapshot"]},
    {"mp_batches": 2},  # an untraced run records no spans
    {"mp_batches": 0, "spans": SPANS},
])
def test_none_without_spans_or_batches(layers):
    assert _read(layers) is None
