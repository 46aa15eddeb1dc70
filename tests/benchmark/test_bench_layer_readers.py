"""The per-layer readers of the program's own stages, spans and counters
(benchmark/metrics/), each on a hand-made window: the number it reads,
and None where the program it reads from does not record its input."""

from __future__ import annotations

from pathlib import Path

import pytest

from benchmark import harness
from benchmark import trace as T

MS = 1_000_000  # ns


def _ctx(layers, trace=None):
    run = harness.Run("w", 1, 1.0, True, Path("."), {}, {"chips": 1}, {}, {})
    return harness.Context(run, harness.Window(1, 0, 1.0, {}, layers), trace, None)


def _read(name, ctx):
    return harness.load_reader(ctx.run, name).read(ctx)


STAGES = {"pad": 0.2, "upload": 0.1, "dispatch": 0.6, "scatter": 0.05, "queue_wait": 0.01}


@pytest.mark.parametrize("name,stage", [
    ("pad_ms.solve", "pad"), ("upload_ms.solve", "upload"), ("scatter_ms.solve", "scatter"),
])
def test_stage_readers(name, stage):
    assert _read(name, _ctx({"solves": 100, "stages": STAGES})) == pytest.approx(
        STAGES[stage] / 100 * 1e3)
    # a program that records no such stage (the Pallas path before it had one)
    missing = {k: v for k, v in STAGES.items() if k != stage}
    assert _read(name, _ctx({"solves": 100, "stages": missing})) is None
    assert _read(name, _ctx({"solves": 0, "stages": STAGES})) is None


def _reduced(with_dispatch=True):
    ops = [(0, "fusion.1", 10 * MS, 10 * MS), (0, "fusion.2", 40 * MS, 10 * MS)]
    host = [("python", T.WINDOW, 0, 100 * MS), ("python", "bench.solve", 0, 100 * MS)]
    if with_dispatch:
        # 12-35 ms on the worker thread: busy 12-20, idle 20-35
        host.append(("solver-service", "solver.dispatch", 12 * MS, 23 * MS))
        # a second call 60-70 ms, all idle
        host.append(("solver-service", "solver.dispatch", 60 * MS, 10 * MS))
    return T.Reduced(ops, [], host, labels=["bench.solve", "solver.dispatch", T.WINDOW])


def test_dispatch_idle_reader():
    ctx = _ctx({"solves": 2}, _reduced())
    # 15 + 10 ms idle inside the spans; idle_gaps puts the whole 20-40
    # gap under the span open at its midpoint, and none of 50-100
    assert _read("dispatch_idle_ms.solve", ctx) == pytest.approx(25.0 / 2)
    assert dict(map(tuple, ctx.trace.idle_gaps()))["solver.dispatch"] == pytest.approx(0.020)
    # no solver.dispatch span on the trace, no device trace, no solves
    assert _read("dispatch_idle_ms.solve", _ctx({"solves": 2}, _reduced(False))) is None
    assert _read("dispatch_idle_ms.solve", _ctx({"solves": 2})) is None
    assert _read("dispatch_idle_ms.solve", _ctx({"solves": 0}, _reduced())) is None


def _span(name, parent=None, dur_us=1000.0, counters=None):
    args = {} if counters is None else {"counters": counters}
    return {"name": name, "id": name, "parent": parent, "dur_us": dur_us, "args": args}


def _counters(write, notify, watch):
    return {
        "store.write.Pod": {"count": 2, "seconds": write},
        "store.notify.Pod": {"count": 2, "seconds": notify},
        "store.write.MetricsProducer": {"count": 1, "seconds": 9.0},
        **{f"watch.{owner}": {"count": 2, "seconds": s} for owner, s in watch.items()},
    }


SPANS = [
    _span("reconcile.tick", counters=_counters(0.004, 0.003, {"Manager._on_event": 0.002})),
    _span("reconcile.MetricsProducer", parent="reconcile.tick"),
    _span("reconcile.event_pass", counters=_counters(0.002, 0.001, {"Feed._on_event": 0.001})),
    # a child never carries counters; a stray one is not counted
    _span("solver.request", parent="reconcile.event_pass", counters=_counters(5.0, 5.0, {})),
]


@pytest.mark.parametrize("name,seconds", [
    ("store_write_ms.tick", 0.006), ("watch_notify_ms.tick", 0.004),
])
def test_ingest_counter_readers(name, seconds, capsys):
    value = _read(name, _ctx({"events": 2000, "spans": SPANS}))
    assert value == pytest.approx(seconds * 1e3 / 2)
    # the parent program: spans without counters
    bare = [_span(s["name"], s["parent"]) for s in SPANS]
    assert _read(name, _ctx({"events": 2000, "spans": bare})) is None
    assert _read(name, _ctx({"events": 2000})) is None
    assert _read(name, _ctx({"events": 0, "spans": SPANS})) is None


def test_watch_notify_logs_each_owner(capsys):
    _read("watch_notify_ms.tick", _ctx({"events": 2000, "spans": SPANS}))
    err = capsys.readouterr().err
    assert "watch.Manager._on_event 1.000 ms (66.7%)" in err
    assert "watch.Feed._on_event 0.500 ms (33.3%)" in err


def test_tick_reader():
    spans = [_span("reconcile.tick", dur_us=3000.0), _span("reconcile.tick", dur_us=5000.0),
             _span("reconcile.event_pass", dur_us=99000.0)]
    assert _read("tick_ms.tick", _ctx({"spans": spans})) == pytest.approx(4.0)
    assert _read("tick_ms.tick", _ctx({"spans": spans[2:]})) is None
    assert _read("tick_ms.tick", _ctx({})) is None
