"""Observability layer (karpenter_tpu/observability + registry histograms).

The acceptance pins (ISSUE 9 / docs/observability.md):

  * a `--simulate --trace-export` run emits valid Chrome-trace JSONL in
    which at least one coalesced solver dispatch span LINKS >= 2 request
    spans, reachable end to end from a tick-entry root to the SNG
    actuation span, and the run observes >= 1 end-to-end
    karpenter_reconcile_e2e_seconds sample;
  * a seeded chaos run produces a flight-recorder dump whose FSM-trip
    event backlinks the trace IDs of the degraded requests;
  * exposition conformance: promtool-style lint over expose_text()
    (TYPE lines, histogram bucket monotonicity, _sum/_count
    consistency, label escaping) and MetricsServer content-type/404;
  * /readyz reflects REAL state (503 in recovery warm-up / solver FSM
    tripped), /healthz stays liveness-only;
  * tracer counters (`Tracer.add`) ride root spans only, and store
    ingest adds them without a span;
  * the resync tick's root span encloses its gauges and tick hook, and
    solver.request stays a direct child of reconcile.MetricsProducer;
  * the single-chip Pallas solve records pad, upload, dispatch and
    scatter in the request's trace;
  * tracing-enabled tick overhead stays bounded (the structural guard).
"""

import json
import math
import os
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.observability import (
    FlightRecorder,
    MetricsServer,
    Tracer,
    default_flight_recorder,
    default_tracer,
    reset_default_flight_recorder,
    reset_default_tracer,
    set_default_flight_recorder,
    set_default_tracer,
)


class FakeClock:
    def __init__(self, start=1000.0):
        self.now = start

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds


@pytest.fixture
def fresh_tracer():
    """Isolated process-default tracer (instrumentation sites read the
    default dynamically)."""
    saved = default_tracer()
    tracer = reset_default_tracer()
    yield tracer
    set_default_tracer(saved)


@pytest.fixture
def fresh_recorder():
    saved = default_flight_recorder()
    recorder = reset_default_flight_recorder()
    yield recorder
    set_default_flight_recorder(saved)


# -- tracing core ------------------------------------------------------------


class TestTracer:
    def test_trace_mints_ids_and_spans_inherit(self):
        tracer = Tracer()
        with tracer.trace("tick") as root:
            with tracer.span("child") as child:
                assert child.trace_id == root.trace_id
                assert child.parent_id == root.span_id
                with tracer.span("grandchild") as grand:
                    assert grand.parent_id == child.span_id
        with tracer.trace("tick") as root2:
            assert root2.trace_id != root.trace_id
        spans = tracer.snapshot()
        assert [s["name"] for s in spans] == [
            "grandchild", "child", "tick", "tick",
        ]

    def test_begin_close_crosses_threads(self):
        """A begin() span closed on another thread keeps its parent's
        trace id and never touches the worker's TLS stack."""
        tracer = Tracer()
        with tracer.trace("tick"):
            handle = tracer.begin("solver.request")
        done = threading.Event()

        def worker():
            with tracer.span(
                "solver.dispatch", parent=handle, links=[handle]
            ):
                handle.close(ok=True)
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
        by_name = {s["name"]: s for s in tracer.snapshot()}
        request = by_name["solver.request"]
        dispatch = by_name["solver.dispatch"]
        assert request["trace"] == by_name["tick"]["trace"]
        assert dispatch["trace"] == request["trace"]
        assert dispatch["links"] == [request["id"]]

    def test_close_is_idempotent(self):
        tracer = Tracer()
        handle = tracer.begin("solver.request")
        handle.close()
        handle.close()
        assert len(tracer.snapshot()) == 1

    def test_disabled_tracer_is_allocation_free(self):
        tracer = Tracer()
        tracer.enabled = False
        first = tracer.trace("tick")
        second = tracer.span("child")
        assert first is second  # the shared no-op
        with first:
            pass
        assert tracer.begin("x") is None
        assert tracer.snapshot() == []

    def test_snapshot_limit_zero_returns_none(self):
        tracer = Tracer()
        tracer.begin("a").close()
        assert tracer.snapshot(limit=0) == []
        assert len(tracer.snapshot(limit=1)) == 1

    def test_ring_is_bounded_and_counts_drops(self):
        tracer = Tracer(capacity=4)
        for i in range(6):
            tracer.begin(f"s{i}").close()
        spans = tracer.snapshot()
        assert len(spans) == 4
        assert tracer.spans_total == 6
        assert tracer.spans_dropped == 2
        assert spans[-1]["name"] == "s5"

    def test_export_jsonl_valid_with_flow_links(self, tmp_path):
        tracer = Tracer()
        a = tracer.begin("req.a")
        b = tracer.begin("req.b")
        a.close()
        b.close()
        with tracer.span("dispatch", parent=a, links=[a, b]):
            pass
        path = str(tmp_path / "trace.jsonl")
        n = tracer.export_jsonl(path)
        lines = open(path).read().splitlines()
        assert len(lines) == n
        events = [json.loads(line) for line in lines]  # every line JSON
        complete = [e for e in events if e["ph"] == "X"]
        assert {"req.a", "req.b", "dispatch"} == {
            e["name"] for e in complete
        }
        dispatch = next(e for e in complete if e["name"] == "dispatch")
        assert len(dispatch["args"]["links"]) == 2
        # flow pairs render each link edge: one "s" at the linked span,
        # one "f" at the dispatch, per-edge ids (src>dst — two
        # dispatches linking one request must not share a flow id)
        starts = {e["id"] for e in events if e["ph"] == "s"}
        finishes = {e["id"] for e in events if e["ph"] == "f"}
        expected = {
            f"{sid}>{dispatch['id']}"
            for sid in dispatch["args"]["links"]
        }
        assert starts == finishes == expected

    def test_e2e_marks_feed_histogram(self):
        registry = GaugeRegistry()
        tracer = Tracer()
        tracer.bind_registry(registry)
        key = ("ScalableNodeGroup", "default", "grp")
        tracer.mark_observed(key)
        lead = tracer.ack_observed(key)
        assert lead is not None and lead >= 0.0
        hist = registry.gauge("reconcile", "e2e_seconds")
        assert hist.count("ScalableNodeGroup", "-") == 1
        # no mark -> no sample; drop retires a mark
        assert tracer.ack_observed(key) is None
        tracer.mark_observed(key)
        tracer.drop_observed(key)
        assert tracer.ack_observed(key) is None
        assert hist.count("ScalableNodeGroup", "-") == 1

    def test_e2e_mark_survives_renotification(self):
        """The engine's own status patches notify the watch path every
        reconcile: a pending mark must NOT be re-stamped (overwrite=
        False) or a multi-tick actuation measures ~one tick instead of
        event->ack."""
        clock = {"now": 100.0}
        tracer = Tracer(clock=lambda: clock["now"])
        key = ("ScalableNodeGroup", "default", "grp")
        tracer.mark_observed(key, overwrite=False)  # the real event
        for _ in range(5):  # deferring ticks, each with a self-patch
            clock["now"] += 10.0
            tracer.mark_observed(key, overwrite=False)
        lead = tracer.ack_observed(key)
        assert lead == pytest.approx(50.0)  # from the FIRST stamp

    def test_e2e_marks_noop_when_disabled(self):
        tracer = Tracer()
        tracer.enabled = False
        key = ("ScalableNodeGroup", "default", "grp")
        tracer.mark_observed(key)
        assert not tracer._observed  # hot path stays mark-free
        assert tracer.ack_observed(key) is None
        tracer.enabled = True
        tracer.mark_observed(key)
        tracer.enabled = False
        tracer.drop_observed(key)  # drop still clears a stale mark
        assert not tracer._observed


# -- flight recorder ---------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        recorder = FlightRecorder(capacity=3)
        for i in range(5):
            recorder.record("fault_injected", point=f"p{i}")
        events = recorder.events()
        assert len(events) == 3
        assert events[-1]["point"] == "p4"
        assert events[-1]["seq"] == 5

    def test_backlinks_current_trace(self, fresh_tracer):
        recorder = FlightRecorder()
        with fresh_tracer.trace("tick") as root:
            event = recorder.record("circuit_open", group="a/b")
        assert event["trace_ids"] == [root.trace_id]
        # explicit ids win
        event = recorder.record("fsm_trip", trace_ids=["t1", "t2"])
        assert event["trace_ids"] == ["t1", "t2"]

    def test_dump_is_crash_safe_and_pruned(self, tmp_path):
        recorder = FlightRecorder(
            dump_dir=str(tmp_path), keep_dumps=2
        )
        recorder.record("fault_injected", point="x")  # no auto-dump
        assert os.listdir(tmp_path) == []
        paths = [
            recorder.dump(reason=f"r{i}") for i in range(4)
        ]
        assert all(p is not None for p in paths)
        survivors = sorted(os.listdir(tmp_path))
        assert len(survivors) == 2  # pruned to keep_dumps
        assert not any(name.endswith(".tmp") for name in survivors)
        doc = json.load(open(os.path.join(tmp_path, survivors[-1])))
        assert doc["events"][0]["kind"] == "fault_injected"

    def test_keep_dumps_zero_keeps_nothing(self, tmp_path):
        """keep_dumps=0 must mean keep NONE, not keep all (dumps[:-0]
        would silently invert the bound)."""
        recorder = FlightRecorder(dump_dir=str(tmp_path), keep_dumps=0)
        recorder.record("fault_injected", point="x")
        recorder.dump(reason="manual")
        assert os.listdir(tmp_path) == []

    def test_auto_dump_cooldown_coalesces_storms(self, tmp_path):
        """A storm of same-kind trip events within the cooldown writes
        ONE dump (the incident-origin dump survives pruning and the
        reconcile thread pays one fsync pair, not N); a different trip
        kind and a post-cooldown repeat still dump."""
        clock = FakeClock()
        recorder = FlightRecorder(
            dump_dir=str(tmp_path), clock=clock, dump_cooldown_s=30.0
        )
        for _ in range(5):
            recorder.record("circuit_open", group="a/b")
            clock.advance(1.0)
        assert recorder.dumps_written == 1
        recorder.record("fsm_trip", trace_ids=["t1"])
        assert recorder.dumps_written == 2  # per-kind cooldown
        clock.advance(31.0)
        recorder.record("circuit_open", group="a/b")
        assert recorder.dumps_written == 3
        # manual dumps are never throttled
        assert recorder.dump(reason="manual") is not None

    def test_one_incident_one_dump(self, tmp_path):
        """The watchdog-trips-the-FSM pattern: two causally-linked trip
        events for ONE incident write ONE dump (the second, whose ring
        holds both events), via auto_dump=False on the first record;
        when the second trip never fires, maybe_auto_dump still writes
        the first kind's dump under its own cooldown."""
        recorder = FlightRecorder(dump_dir=str(tmp_path))
        recorder.record(
            "watchdog_restart", trace_ids=["t1"], auto_dump=False
        )
        assert recorder.dumps_written == 0
        recorder.record("fsm_trip", trace_ids=["t1"])
        assert recorder.dumps_written == 1
        dumps = sorted(
            name for name in os.listdir(tmp_path)
            if name.startswith("flightrecorder-")
        )
        doc = json.load(open(os.path.join(tmp_path, dumps[0])))
        assert doc["reason"] == "fsm_trip"
        assert [e["kind"] for e in doc["events"]] == [
            "watchdog_restart", "fsm_trip"
        ]
        # the no-trip variant: the deferred dump still happens
        recorder2 = FlightRecorder(dump_dir=str(tmp_path / "x"))
        os.makedirs(tmp_path / "x", exist_ok=True)
        recorder2.record(
            "watchdog_restart", trace_ids=["t2"], auto_dump=False
        )
        assert recorder2.maybe_auto_dump("watchdog_restart") is not None
        assert recorder2.dumps_written == 1

    def test_trip_kinds_auto_dump(self, tmp_path):
        recorder = FlightRecorder(dump_dir=str(tmp_path))
        recorder.record("fsm_trip", trace_ids=["t1"])
        dumps = [
            name for name in os.listdir(tmp_path)
            if name.startswith("flightrecorder-")
        ]
        assert len(dumps) == 1
        doc = json.load(open(os.path.join(tmp_path, dumps[0])))
        assert doc["reason"] == "fsm_trip"
        assert doc["events"][-1]["trace_ids"] == ["t1"]


# -- exposition conformance (promtool-style lint) ----------------------------


_SERIES_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?\s+(?P<value>\S+)$"
)


def _lint_exposition(text: str):
    """Minimal promtool check-metrics analog: returns the parsed series
    and raises AssertionError on format violations."""
    typed: dict = {}
    helped: set = set()
    series = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("gauge", "counter", "histogram"), line
            assert name not in typed, f"duplicate TYPE for {name}"
            typed[name] = kind
            continue
        if line.startswith("# HELP "):
            helped.add(line.split(" ", 3)[2])
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        match = _SERIES_RE.match(line)
        assert match, f"unparseable series line: {line!r}"
        name = match.group("name")
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        owner = name if name in typed else base
        assert owner in typed, f"series {name} has no TYPE line"
        if typed[owner] == "histogram" and owner != name:
            assert name.endswith(("_bucket", "_sum", "_count")), line
        value = match.group("value")
        if value not in ("NaN", "+Inf", "-Inf"):
            float(value)
        series.append((name, match.group("labels") or "", value))
    assert set(typed) <= helped, "TYPE without HELP"
    return typed, series


class TestExpositionConformance:
    def _registry(self):
        registry = GaugeRegistry()
        registry.register("queue", "length").set("q", "default", 41.0)
        registry.register("queue", "nan").set("n", "default", float("nan"))
        registry.register(
            "runtime", "reconciles_total", kind="counter"
        ).inc("HA", "-")
        hist = registry.register(
            "solver", "stage_seconds", kind="histogram",
            buckets=(0.001, 0.01, 0.1),
        )
        for value in (0.0005, 0.002, 0.002, 0.05, 7.0):
            hist.observe("dispatch", "-", value)
        return registry, hist

    def test_lint_passes_and_histogram_is_consistent(self):
        registry, hist = self._registry()
        typed, series = _lint_exposition(registry.expose_text())
        assert typed["karpenter_solver_stage_seconds"] == "histogram"
        buckets = [
            (labels, float(value))
            for name, labels, value in series
            if name == "karpenter_solver_stage_seconds_bucket"
        ]
        # le labels parse, cumulative counts are monotone, +Inf present
        les, counts = [], []
        for labels, value in buckets:
            le = re.search(r'le="([^"]+)"', labels).group(1)
            les.append(le)
            counts.append(value)
        assert les[-1] == "+Inf"
        assert counts == sorted(counts), "buckets not cumulative"
        count = next(
            float(v) for n, _l, v in series
            if n == "karpenter_solver_stage_seconds_count"
        )
        total = next(
            float(v) for n, _l, v in series
            if n == "karpenter_solver_stage_seconds_sum"
        )
        assert counts[-1] == count == 5  # +Inf bucket == _count
        assert math.isclose(total, 7.0545, rel_tol=1e-9)
        assert counts[:3] == [1.0, 3.0, 4.0]  # per-ladder cumulation

    def test_label_escaping(self):
        registry = GaugeRegistry()
        registry.register("queue", "length").set(
            'evil"name\\with\nnewline', "default", 1.0
        )
        text = registry.expose_text()
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("karpenter_queue_length{")
        )
        assert '\\"' in line and "\\\\" in line and "\\n" in line
        assert "\n" not in line  # the raw newline never leaks
        _lint_exposition(text)

    def test_histogram_kind_mismatch_rejected(self):
        registry = GaugeRegistry()
        registry.register("solver", "stage_seconds", kind="histogram")
        with pytest.raises(ValueError):
            registry.register("solver", "stage_seconds", kind="gauge")

    def test_histogram_bucket_conflict_rejected(self):
        """A second registration with a DIFFERENT ladder must raise like
        a kind mismatch does — silently landing observations in buckets
        the caller never chose skews histogram_quantile()."""
        registry = GaugeRegistry()
        vec = registry.register(
            "solver", "stage_seconds", kind="histogram",
            buckets=(0.001, 0.01),
        )
        # same ladder (or no ladder) re-registers fine
        assert registry.register(
            "solver", "stage_seconds", kind="histogram",
            buckets=(0.001, 0.01),
        ) is vec
        assert registry.register(
            "solver", "stage_seconds", kind="histogram"
        ) is vec
        with pytest.raises(ValueError):
            registry.register(
                "solver", "stage_seconds", kind="histogram",
                buckets=(0.005, 0.05),
            )


# -- metrics server ----------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


class TestMetricsServer:
    def test_content_types_and_404(self, fresh_tracer, fresh_recorder):
        registry = GaugeRegistry()
        registry.register("queue", "length").set("q", "default", 1.0)
        with fresh_tracer.trace("tick"):
            fresh_recorder.record("fault_injected", point="p")
        server = MetricsServer(registry, port=0, host="127.0.0.1")
        port = server.start()
        base = f"http://127.0.0.1:{port}"
        try:
            status, ctype, body = _get(f"{base}/metrics")
            assert status == 200
            assert ctype == "text/plain; version=0.0.4"
            _lint_exposition(body.decode())
            status, ctype, body = _get(f"{base}/debug/traces?limit=10")
            assert status == 200 and ctype == "application/json"
            doc = json.loads(body)
            assert doc["spans"][-1]["name"] == "tick"
            status, ctype, body = _get(f"{base}/debug/flightrecorder")
            assert status == 200
            assert json.loads(body)["events"][0]["point"] == "p"
            assert _get(f"{base}/healthz")[2] == b"ok"
            assert _get(f"{base}/readyz")[2] == b"ok"  # no check wired
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(f"{base}/nope")
            assert err.value.code == 404
        finally:
            server.stop()

    def test_readyz_reflects_real_state(self):
        state = {"ready": False, "reason": "recovery warm-up: 3 tick(s)"}
        server = MetricsServer(
            GaugeRegistry(), port=0, host="127.0.0.1",
            readiness=lambda: (state["ready"], state["reason"]),
        )
        port = server.start()
        base = f"http://127.0.0.1:{port}"
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(f"{base}/readyz")
            assert err.value.code == 503
            assert b"warm-up" in err.value.read()
            # liveness is NOT readiness: healthz stays ok while not ready
            assert _get(f"{base}/healthz")[2] == b"ok"
            state["ready"] = True
            assert _get(f"{base}/readyz")[0] == 200
        finally:
            server.stop()

    def test_readiness_check_wiring(self):
        """__main__._readiness against the real runtime surface: not
        ready while the solver FSM is degraded, ready once healthy."""
        from karpenter_tpu.__main__ import _readiness
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        runtime = KarpenterRuntime(
            Options(), cloud_provider_factory=FakeFactory()
        )
        try:
            check = _readiness(runtime)
            assert check() == (True, "ok")
            runtime.solver_service._health = "degraded"
            ready, reason = check()
            assert not ready and "degraded" in reason
            runtime.solver_service._health = "healthy"
            assert check()[0]
        finally:
            runtime.close()

    def test_readiness_holds_during_recovery_warmup(self, tmp_path):
        """A RECOVERED boot reports 503 until the warm-up ticks pass —
        the same gate that holds disruption."""
        from karpenter_tpu.__main__ import _readiness
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        opts = Options(
            journal_dir=str(tmp_path), recovery_warmup_ticks=2
        )
        first = KarpenterRuntime(
            opts, cloud_provider_factory=FakeFactory()
        )
        first.recovery.handle("breaker").set(("a", "b"), {"state": "open"})
        first.close()
        runtime = KarpenterRuntime(
            opts, cloud_provider_factory=FakeFactory()
        )
        try:
            assert runtime.recovery.recovered
            check = _readiness(runtime)
            ready, reason = check()
            assert not ready and "warm-up" in reason
            runtime.manager.converge(2)
            assert check() == (True, "ok")
        finally:
            runtime.close()


# -- solver service integration ----------------------------------------------


def _binpack_inputs(n_pods=3, n_groups=2, resources=2, taints=4, labels=4):
    from karpenter_tpu.ops.binpack import BinPackInputs

    return BinPackInputs(
        pod_requests=np.ones((n_pods, resources), np.float32),
        pod_valid=np.ones(n_pods, bool),
        pod_intolerant=np.zeros((n_pods, taints), bool),
        pod_required=np.zeros((n_pods, labels), bool),
        group_allocatable=np.full((n_groups, resources), 8.0, np.float32),
        group_taints=np.zeros((n_groups, taints), bool),
        group_labels=np.ones((n_groups, labels), bool),
    )


class TestSolverTracing:
    def test_coalesced_dispatch_links_batch(self, fresh_tracer):
        from karpenter_tpu.solver import SolverService

        service = SolverService(registry=GaugeRegistry())
        try:
            with fresh_tracer.trace("tick") as root:
                service.consolidate(
                    [_binpack_inputs() for _ in range(3)],
                    backend="numpy",
                )
        finally:
            service.close()
        spans = fresh_tracer.snapshot()
        requests = [s for s in spans if s["name"] == "solver.request"]
        assert len(requests) == 3
        assert all(s["trace"] == root.trace_id for s in requests)
        dispatch = next(
            s for s in spans if s["name"] == "solver.dispatch"
        )
        assert set(dispatch["links"]) == {s["id"] for s in requests}
        assert dispatch["trace"] == root.trace_id

    def test_batch_overflow_records_rejected_spans(self, fresh_tracer):
        """Queue-full rejection in the coalesced consolidate path must
        leave rejected request spans like the singleton path does — a
        saturation trace export has to show the rejected fleet-batch
        candidates, not just rejected singletons."""
        from karpenter_tpu.solver import SolverService

        service = SolverService(registry=GaugeRegistry(), max_queue=0)
        try:
            with fresh_tracer.trace("tick") as root:
                results = service.consolidate(
                    [_binpack_inputs() for _ in range(3)],
                    backend="numpy",
                )
        finally:
            service.close()
        assert len(results) == 3  # overflow degrades to numpy inline
        rejected = [
            s for s in fresh_tracer.snapshot()
            if s["name"] == "solver.request"
            and s["args"].get("rejected") is True
        ]
        assert len(rejected) == 3
        assert all(s["args"]["ok"] is False for s in rejected)
        assert all(s["trace"] == root.trace_id for s in rejected)

    def test_stage_and_coalesce_histograms_fill(self):
        from karpenter_tpu.solver import SolverService

        registry = GaugeRegistry()
        service = SolverService(registry=registry)
        try:
            service.solve(_binpack_inputs(), backend="numpy")
        finally:
            service.close()
        stage = registry.gauge("solver", "stage_seconds")
        assert stage.count("dispatch", "-") >= 1
        coalesce = registry.gauge("solver", "coalesce_batch_size")
        assert coalesce.count("-", "-") >= 1

    def test_abandoned_request_span_closes(self, fresh_tracer):
        """A caller-side timeout sets abandoned without finish(): the
        worker's _filter_live must close the span or the timed-out
        request vanishes from the export."""
        from karpenter_tpu.solver import SolverService
        from karpenter_tpu.solver.service import _Request

        service = SolverService(registry=GaugeRegistry())
        try:
            request = _Request(
                inputs=_binpack_inputs(), buckets=8, backend="numpy",
                key=("solve",), n_pods=3, n_groups=2,
                deadline=None, enqueued_at=0.0,
            )
            service._begin_request_span(request)
            request.abandoned = True
            assert service._filter_live([request]) == []
        finally:
            service.close()
        span = next(
            s for s in fresh_tracer.snapshot()
            if s["name"] == "solver.request"
        )
        assert span["args"]["abandoned"] is True
        assert span["args"]["ok"] is False

    def test_seeded_chaos_trip_dumps_with_backlinks(
        self, fresh_tracer, fresh_recorder, tmp_path
    ):
        """The chaos acceptance pin: injected device failures trip the
        solver FSM, and the flight-recorder dump's fsm_trip event
        backlinks the trace IDs of the degraded requests."""
        from karpenter_tpu.faults import injected_faults
        from karpenter_tpu.solver import SolverService

        fresh_recorder.configure(dump_dir=str(tmp_path))
        service = SolverService(
            registry=GaugeRegistry(), health_failure_threshold=1
        )
        try:
            with injected_faults(seed=7) as faults:
                faults.plan("solver.dispatch", mode="error", times=1)
                with fresh_tracer.trace("tick") as root:
                    out = service.solve(
                        _binpack_inputs(), backend="xla"
                    )
                assert out is not None  # degraded, still answered
        finally:
            service.close()
        assert service.stats.fsm_trips == 1
        trips = fresh_recorder.events(kind="fsm_trip")
        assert len(trips) == 1
        assert root.trace_id in trips[0]["trace_ids"]
        dumps = [
            name for name in os.listdir(tmp_path)
            if name.startswith("flightrecorder-")
            and "fsm_trip" in name
        ]
        assert dumps, "trip did not dump"
        doc = json.load(open(os.path.join(tmp_path, dumps[-1])))
        dumped_trip = next(
            e for e in doc["events"] if e["kind"] == "fsm_trip"
        )
        assert root.trace_id in dumped_trip["trace_ids"]
        injected = fresh_recorder.events(kind="fault_injected")
        assert any(e["point"] == "solver.dispatch" for e in injected)
        # the degraded request's span is DISTINGUISHABLE from a healthy
        # device-served one — the question the backlinks exist to answer
        request_spans = [
            s for s in fresh_tracer.snapshot()
            if s["name"] == "solver.request"
        ]
        assert request_spans
        assert all(
            s["args"].get("degraded") is True for s in request_spans
        )


class TestPallasStages:
    """The single-chip Mosaic path (interpret mode on the CPU) records
    the XLA path's stages, each under its span in the request's trace."""

    @staticmethod
    def _service():
        from karpenter_tpu.solver import SolverService

        service = SolverService(registry=GaugeRegistry(), backend="pallas")
        stages = []
        record = service._record_stage
        service._record_stage = lambda stage, seconds: (
            stages.append((stage, seconds)), record(stage, seconds)
        )
        return service, stages

    def test_submit_records_each_stage_once(self, fresh_tracer):
        service, stages = self._service()
        try:
            service.submit(_binpack_inputs(), buckets=8).result()
        finally:
            service.close()
        names = [stage for stage, _ in stages if stage != "queue_wait"]
        assert names == ["pad", "upload", "dispatch", "scatter"]
        spans = {s["name"]: s for s in fresh_tracer.snapshot()}
        request = spans["solver.request"]
        for name in ("solver.pad", "solver.dispatch", "solver.scatter"):
            assert spans[name]["parent"] == request["id"], name
        assert spans["solver.upload"]["parent"] == spans["solver.dispatch"]["id"]
        assert spans["solver.dispatch"]["links"] == [request["id"]]
        assert {s["trace"] for s in spans.values()} == {request["trace"]}

    def test_unchanged_inputs_record_upload_zero(self, fresh_tracer):
        from karpenter_tpu.solver.bucketing import bucket_shape

        # operands already at their bucket shape: padding returns the
        # same object, so the identity memo sees it again
        p, t, r, k, l = bucket_shape(_binpack_inputs())
        inputs = _binpack_inputs(n_pods=p, n_groups=t, resources=r,
                                 taints=k, labels=l)
        assert bucket_shape(inputs) == (p, t, r, k, l)
        service, stages = self._service()
        try:
            for _ in range(2):
                service.submit(inputs, buckets=8).result()
        finally:
            service.close()
        uploads = [seconds for stage, seconds in stages if stage == "upload"]
        assert len(uploads) == 2
        assert uploads[0] > 0.0 and uploads[1] == 0.0
        # a memo hit opens no upload span
        names = [s["name"] for s in fresh_tracer.snapshot()]
        assert names.count("solver.upload") == 1
        assert names.count("solver.dispatch") == 2


class TestTracerCounters:
    def test_counters_ride_root_spans_then_reset(self):
        tracer = Tracer()
        tracer.add("store.write.Pod", 0.25)
        with tracer.trace("reconcile.tick"):
            tracer.add("store.write.Pod", 0.5)
            with tracer.span("reconcile.MetricsProducer"):
                pass
        with tracer.trace("reconcile.tick"):
            pass
        child, first, second = tracer.snapshot()
        assert "counters" not in child["args"]
        assert first["args"]["counters"] == {
            "store.write.Pod": {"count": 2, "seconds": 0.75},
        }
        assert "counters" not in second["args"]

    def test_disabled_tracer_counts_nothing(self):
        tracer = Tracer()
        tracer.enabled = False
        tracer.add("store.write.Pod", 1.0)
        tracer.enabled = True
        with tracer.trace("reconcile.tick"):
            pass
        assert "counters" not in tracer.snapshot()[0]["args"]

    def test_clear_drops_pending_counters(self):
        tracer = Tracer()
        tracer.add("watch.Manager._on_event", 1.0)
        tracer.clear()
        with tracer.trace("reconcile.tick"):
            pass
        assert "counters" not in tracer.snapshot()[0]["args"]

    def test_add_from_threads_at_once(self):
        """The watch thread and the pass thread add at once: a lost
        read-modify-write would show as a short count."""
        import sys

        tracer = Tracer()
        n_threads, n_adds = 2 * (os.cpu_count() or 4), 2000

        def hammer():
            for _ in range(n_adds):
                tracer.add("store.notify.Pod", 1e-6)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=30)
        finally:
            sys.setswitchinterval(interval)
        assert not any(thread.is_alive() for thread in threads)
        with tracer.trace("reconcile.tick"):
            pass
        counter = tracer.snapshot()[0]["args"]["counters"]["store.notify.Pod"]
        assert counter["count"] == n_threads * n_adds
        assert counter["seconds"] == pytest.approx(n_threads * n_adds * 1e-6)


class _Watcher:
    def __init__(self):
        self.events = []

    def on_event(self, event, obj):
        self.events.append(event)


def _watched_store():
    from functools import partial

    from karpenter_tpu.store import Store

    store = Store()
    watcher = _Watcher()
    store.watch("Pod", watcher.on_event)
    store.watch(None, partial(lambda tag, event, obj: None, "any"))
    return store, watcher


class TestStoreIngestCounters:
    def test_write_adds_counters_and_no_span(self, fresh_tracer):
        from karpenter_tpu.api.core import ObjectMeta, Pod

        store, watcher = _watched_store()
        with fresh_tracer.trace("reconcile.event_pass"):
            store.create(Pod(metadata=ObjectMeta(name="p0")))
        spans = fresh_tracer.snapshot()
        assert [s["name"] for s in spans] == ["reconcile.event_pass"]
        counters = spans[0]["args"]["counters"]
        assert watcher.events == ["Added"]
        assert set(counters) == {
            "store.write.Pod", "store.notify.Pod",
            "watch._Watcher.on_event",
            "watch._watched_store.<locals>.<lambda>",  # a partial's function
        }
        assert all(c["count"] == 1 for c in counters.values())
        assert (
            counters["store.write.Pod"]["seconds"]
            >= counters["store.notify.Pod"]["seconds"]
            >= counters["watch._Watcher.on_event"]["seconds"]
        )

    def test_each_write_kind_counts(self, fresh_tracer):
        from karpenter_tpu.api.core import ObjectMeta, Pod

        store, watcher = _watched_store()
        pod = store.create(Pod(metadata=ObjectMeta(name="p0")))
        pod = store.update(pod)
        store.patch_status(pod)
        store.delete("Pod", pod.metadata.namespace, "p0")
        with fresh_tracer.trace("reconcile.tick"):
            pass
        counters = fresh_tracer.snapshot()[0]["args"]["counters"]
        assert counters["store.write.Pod"]["count"] == 4
        assert counters["store.notify.Pod"]["count"] == 4
        assert watcher.events == ["Added", "Modified", "Modified", "Deleted"]

    def test_disabled_tracer_counts_no_writes(self, fresh_tracer, monkeypatch):
        from karpenter_tpu.api.core import ObjectMeta, Pod
        from karpenter_tpu.store import store as store_module

        def no_clock():
            raise AssertionError("a disabled tracer's write read the clock")

        store, watcher = _watched_store()
        fresh_tracer.enabled = False
        with monkeypatch.context() as patched:
            patched.setattr(store_module, "_clock", no_clock)
            store.create(Pod(metadata=ObjectMeta(name="p0")))
        fresh_tracer.enabled = True
        with fresh_tracer.trace("reconcile.tick"):
            pass
        assert watcher.events == ["Added"]
        assert "counters" not in fresh_tracer.snapshot()[0]["args"]

    def test_one_counter_call_a_write(self, fresh_tracer, monkeypatch):
        """The write's, the fan-out's and every watcher's counter go to
        the tracer in one locked call."""
        from karpenter_tpu.api.core import ObjectMeta, Pod

        store, _ = _watched_store()
        calls = []
        add_all = fresh_tracer.add_all
        monkeypatch.setattr(
            fresh_tracer, "add_all",
            lambda counts: (calls.append(list(counts)), add_all(counts)),
        )
        store.create(Pod(metadata=ObjectMeta(name="p0")))
        assert len(calls) == 1
        assert {name for name, _ in calls[0]} == {
            "store.write.Pod", "store.notify.Pod",
            "watch._Watcher.on_event",
            "watch._watched_store.<locals>.<lambda>",
        }

    def test_ingest_and_scale_writes_count(self, fresh_tracer):
        """apply_event (the apiserver informer's ingest) and update_scale
        notify watchers, so they count like the CRUD writes."""
        from karpenter_tpu.api.core import ObjectMeta, Pod
        from karpenter_tpu.api.scalablenodegroup import ScalableNodeGroup
        from karpenter_tpu.store.store import ADDED, DELETED

        store, watcher = _watched_store()
        pod = Pod(metadata=ObjectMeta(name="p0", resource_version=7))
        store.apply_event(ADDED, pod)
        store.apply_event(DELETED, pod)
        group = store.create(ScalableNodeGroup(metadata=ObjectMeta(name="g")))
        scale = store.get_scale(
            "ScalableNodeGroup", group.metadata.namespace, "g"
        )
        scale.spec_replicas = 3
        store.update_scale("ScalableNodeGroup", scale)
        with fresh_tracer.trace("reconcile.tick"):
            pass
        counters = fresh_tracer.snapshot()[0]["args"]["counters"]
        assert watcher.events == ["Added", "Deleted"]
        assert counters["store.write.Pod"]["count"] == 2
        assert counters["store.notify.Pod"]["count"] == 2
        # the create and the scale write
        assert counters["store.write.ScalableNodeGroup"]["count"] == 2


class TestTickSpans:
    @staticmethod
    def _runtime():
        from karpenter_tpu.api.core import ObjectMeta
        from karpenter_tpu.api.metricsproducer import (
            MetricsProducer, MetricsProducerSpec, PendingCapacitySpec,
        )
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        runtime = KarpenterRuntime(
            Options(), cloud_provider_factory=FakeFactory()
        )
        runtime.store.create(MetricsProducer(
            metadata=ObjectMeta(name="pending"),
            spec=MetricsProducerSpec(
                pending_capacity=PendingCapacitySpec(
                    node_selector={"pool": "a"},
                )
            ),
        ))
        return runtime

    def test_tick_root_encloses_gauges_and_hook(self, fresh_tracer):
        runtime = self._runtime()
        try:
            fresh_tracer.clear()
            runtime.manager.reconcile_all()
        finally:
            runtime.close()
        spans = fresh_tracer.snapshot()
        root = next(s for s in spans if s["name"] == "reconcile.tick")
        for name in ("solver.publish_gauges", "manager.tick_hook"):
            child = next(s for s in spans if s["name"] == name)
            assert child["parent"] == root["id"], name
            assert root["ts_us"] <= child["ts_us"]
            assert (child["ts_us"] + child["dur_us"]
                    <= root["ts_us"] + root["dur_us"])

    def test_tick_root_opens_once_the_pass_lock_is_held(self, fresh_tracer):
        """tick_ms.tick reads the tick's work: a wait behind an event
        pass holding the pass lock lies before the root span."""
        runtime = self._runtime()
        held, released = threading.Event(), []

        def event_pass():
            with runtime.manager._pass_lock:
                held.set()
                time.sleep(0.2)
                released.append(time.perf_counter())

        holder = threading.Thread(target=event_pass)
        try:
            holder.start()
            assert held.wait(10)
            fresh_tracer.clear()
            runtime.manager.reconcile_all()
        finally:
            holder.join(10)
            runtime.close()
        root = next(
            s for s in fresh_tracer.snapshot() if s["name"] == "reconcile.tick"
        )
        assert root["ts_us"] >= (released[0] - fresh_tracer._epoch) * 1e6

    def test_solver_request_is_a_direct_child_of_mp_reconcile(
        self, fresh_tracer
    ):
        """mp_reconcile_ms.tick subtracts exactly the solver.request
        children of reconcile.MetricsProducer: the pendingcapacity.solve
        label must not come between them."""
        runtime = self._runtime()
        try:
            fresh_tracer.clear()
            runtime.manager.converge(1)
        finally:
            runtime.close()
        spans = fresh_tracer.snapshot()
        mp = next(s for s in spans if s["name"] == "reconcile.MetricsProducer")
        label = next(s for s in spans if s["name"] == "pendingcapacity.solve")
        requests = [s for s in spans if s["name"] == "solver.request"]
        assert requests
        assert label["parent"] == mp["id"]
        assert all(s["parent"] == mp["id"] for s in requests)


def _fleet_store(constrained: bool):
    """Three one-node groups in three zones; a Deployment of four pending
    pods (with a required zone spread when `constrained`) and one bound
    replica in zone a; a StatefulSet of two pending pods (with required
    hostname self anti-affinity when `constrained`)."""
    from karpenter_tpu.api.core import (
        Affinity, Container, LabelSelector, Node, NodeCondition, NodeStatus,
        ObjectMeta, Pod, PodAffinityTerm, PodAntiAffinity, PodSpec,
        TopologySpreadConstraint,
    )
    from karpenter_tpu.api.metricsproducer import (
        MetricsProducer, MetricsProducerSpec, PendingCapacitySpec,
    )
    from karpenter_tpu.store import Store
    from karpenter_tpu.utils.quantity import Quantity

    zone = "topology.kubernetes.io/zone"
    store = Store()
    for z in "abc":
        store.create(Node(
            metadata=ObjectMeta(name=f"n-{z}", labels={"group": z, zone: f"zone-{z}"}),
            status=NodeStatus(
                allocatable={"cpu": Quantity.parse("8"), "memory": Quantity.parse("32Gi")},
                conditions=[NodeCondition(type="Ready", status="True")],
            ),
        ))
        store.create(MetricsProducer(
            metadata=ObjectMeta(name=f"mp-{z}"),
            spec=MetricsProducerSpec(pending_capacity=PendingCapacitySpec(node_selector={"group": z})),
        ))
    spread = [TopologySpreadConstraint(
        max_skew=1, topology_key=zone, when_unsatisfiable="DoNotSchedule",
        label_selector={"matchLabels": {"app": "web"}})] if constrained else []
    anti = Affinity(pod_anti_affinity=PodAntiAffinity(
        required_during_scheduling_ignored_during_execution=[PodAffinityTerm(
            label_selector=LabelSelector(match_labels={"app": "db"}),
            topology_key="kubernetes.io/hostname")])) if constrained else None

    def pod(name, labels, node="", **spec):
        return Pod(metadata=ObjectMeta(name=name, labels=labels), spec=PodSpec(
            node_name=node, containers=[Container(requests={
                "cpu": Quantity.parse("1"), "memory": Quantity.parse("1Gi")})], **spec))

    store.create(pod("web-old", {"app": "web"}, node="n-a"))
    for i in range(4):
        store.create(pod(f"web-{i}", {"app": "web"}, topology_spread_constraints=spread))
    for i in range(2):
        store.create(pod(f"db-{i}", {"app": "db", "statefulset.kubernetes.io/pod-name": f"db-{i}"},
                         affinity=anti))
    return store, pod


class TestConstrainedEncodeSpans:
    """The census build and the spread/anti row expansion have spans, and
    the census queries inside the expansion a counter; an unconstrained
    fleet opens none of them (census_ms.constrained and
    expand_ms.constrained read these)."""

    @staticmethod
    def _solve(tracer, store, feed):
        from karpenter_tpu.metrics.producers.pendingcapacity import solve_pending

        with tracer.trace("reconcile.tick"):
            solve_pending(store, [], GaugeRegistry(), feed=feed)

    @staticmethod
    def _feed(store):
        from karpenter_tpu.metrics.producers.pendingcapacity import group_profile
        from karpenter_tpu.store.columnar import PendingFeed

        return PendingFeed(store, group_profile)

    def test_constrained_encode_opens_both_spans_and_counts_queries(self, fresh_tracer):
        store, pod = _fleet_store(constrained=True)
        feed = self._feed(store)
        self._solve(fresh_tracer, store, feed)
        spans = {s["name"]: s for s in fresh_tracer.snapshot()}
        root = spans["reconcile.tick"]
        census, expand = spans["pendingcapacity.census"], spans["pendingcapacity.expand"]
        assert census["parent"] == expand["parent"] == root["id"]
        assert census["args"]["refresh"] is True
        # web and db dedup to one row each; web splits by zone, db is exclusive
        assert expand["args"]["rows_in"] == 2
        assert expand["args"]["rows_out"] > 2
        assert expand["args"]["exclusive_rows"] == 1
        assert expand["args"]["batched_rows"] == 1
        query = root["args"]["counters"]["census.query"]
        assert query["count"] == 1
        assert 0 < query["seconds"] * 1e6 <= expand["dur_us"]

        # nothing moved: the census build still runs, the encode memo holds
        fresh_tracer.clear()
        self._solve(fresh_tracer, store, feed)
        names = [s["name"] for s in fresh_tracer.snapshot()]
        assert "pendingcapacity.expand" not in names
        census = next(s for s in fresh_tracer.snapshot() if s["name"] == "pendingcapacity.census")
        assert census["args"]["refresh"] is False

        # a bound replica moves: the census recomputes
        fresh_tracer.clear()
        store.delete("Pod", "default", "web-old")
        store.create(pod("web-moved", {"app": "web"}, node="n-b"))
        self._solve(fresh_tracer, store, feed)
        spans = {s["name"]: s for s in fresh_tracer.snapshot()}
        assert spans["pendingcapacity.census"]["args"]["refresh"] is True
        assert "pendingcapacity.expand" in spans

    @pytest.mark.parametrize("web_rows, batched", [(1, 1), (2, 0)])
    def test_expand_counts_batched_spread_rows(self, fresh_tracer, web_rows, batched):
        """batched_rows counts the spread rows filled by the one batched
        water-fill: a workload's only row, never a workload split over
        several rows (here by a second, group-pinned web row)."""
        store, pod = _fleet_store(constrained=True)
        if web_rows == 2:
            web = store.get("Pod", "default", "web-0")
            store.create(pod("web-pinned", {"app": "web"}, node_selector={"group": "b"},
                             topology_spread_constraints=web.spec.topology_spread_constraints))
        self._solve(fresh_tracer, store, self._feed(store))
        expand = next(s for s in fresh_tracer.snapshot() if s["name"] == "pendingcapacity.expand")
        assert expand["args"]["rows_in"] == 1 + web_rows
        assert expand["args"]["batched_rows"] == batched

    def test_unconstrained_encode_opens_neither(self, fresh_tracer):
        store, _ = _fleet_store(constrained=False)
        self._solve(fresh_tracer, store, self._feed(store))
        spans = fresh_tracer.snapshot()
        names = {s["name"] for s in spans}
        assert not names & {"pendingcapacity.census", "pendingcapacity.expand"}
        assert "census.query" not in spans[-1]["args"].get("counters", {})

    def test_disabled_tracer_reads_no_clock(self, fresh_tracer, monkeypatch):
        from karpenter_tpu.metrics.producers.pendingcapacity import encoder

        def no_clock():
            raise AssertionError("a disabled tracer's expansion read the clock")

        store, _ = _fleet_store(constrained=True)
        feed = self._feed(store)
        fresh_tracer.enabled = False
        monkeypatch.setattr(encoder, "_clock", no_clock)
        self._solve(fresh_tracer, store, feed)
        assert fresh_tracer.snapshot() == []
        assert feed.encode_memo is not None  # the constrained encode ran


class TestPendingSnapshotSpan:
    """Each MP batch reads the pending-pod arena inside one
    `pendingcapacity.snapshot` span, whose `compacted` arg says whether
    that read compacted the arena; every compaction adds one to the
    `pending_cache.compact` counter (snapshot_ms.constrained reads the
    span)."""

    def test_one_span_per_batch_and_compactions_counted(self, fresh_tracer):
        store, pod = _fleet_store(constrained=True)
        feed = TestConstrainedEncodeSpans._feed(store)
        solve = TestConstrainedEncodeSpans._solve

        def snapshot_spans():
            spans = fresh_tracer.snapshot()
            root = next(s for s in spans if s["name"] == "reconcile.tick")
            reads = [s for s in spans if s["name"] == "pendingcapacity.snapshot"]
            assert len(reads) == 1 and reads[0]["parent"] == root["id"]
            return reads[0], root["args"].get("counters", {})

        solve(fresh_tracer, store, feed)
        read, counters = snapshot_spans()
        assert read["args"]["compacted"] is False
        assert "pending_cache.compact" not in counters

        # a peak that drains leaves the arena mostly dead: the next read
        # compacts it, once
        for i in range(300):
            store.create(pod(f"burst-{i}", {"app": "burst"}))
        for i in range(300):
            store.delete("Pod", "default", f"burst-{i}")
        fresh_tracer.clear()
        solve(fresh_tracer, store, feed)
        read, counters = snapshot_spans()
        assert read["args"]["compacted"] is True
        assert counters["pending_cache.compact"]["count"] == 1
        assert 0 < counters["pending_cache.compact"]["seconds"] * 1e6 <= read["dur_us"]

        fresh_tracer.clear()
        solve(fresh_tracer, store, feed)
        read, counters = snapshot_spans()
        assert read["args"]["compacted"] is False
        assert "pending_cache.compact" not in counters

    def test_oracle_path_opens_no_snapshot_span(self, fresh_tracer):
        from karpenter_tpu.metrics.producers.pendingcapacity import solve_pending

        store, _ = _fleet_store(constrained=True)
        with fresh_tracer.trace("reconcile.tick"):
            solve_pending(store, [], GaugeRegistry())
        names = {s["name"] for s in fresh_tracer.snapshot()}
        assert "pendingcapacity.snapshot" not in names
        assert "pendingcapacity.census" in names


# -- the end-to-end simulate pin ---------------------------------------------


class TestTraceExportAcceptance:
    def test_simulate_trace_export_end_to_end(
        self, fresh_tracer, fresh_recorder, tmp_path, capsys
    ):
        """ISSUE 9 acceptance: the traced replay emits valid JSONL in
        which a coalesced dispatch links >= 2 request spans whose trace
        roots are tick entries, an actuation span closes the chain, and
        an e2e sample lands."""
        from karpenter_tpu.__main__ import main as cli_main

        path = str(tmp_path / "trace.jsonl")
        rc = cli_main(["--simulate", "--trace-export", path])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["max_dispatch_links"] >= 2
        assert report["actuation_spans"] >= 1
        assert report["e2e_samples"] >= 1
        assert report["replicas_after"] < 3  # the scale-down landed

        events = [
            json.loads(line)
            for line in open(path).read().splitlines()
        ]
        complete = {
            e["id"]: e for e in events if e["ph"] == "X"
        }
        roots_by_trace = {
            e["args"]["trace_id"]: e["name"]
            for e in complete.values()
            if "parent_id" not in e["args"]
        }
        dispatches = [
            e for e in complete.values()
            if e["name"].startswith("solver.dispatch")
            and len(e["args"].get("links", [])) >= 2
        ]
        assert dispatches, "no coalesced dispatch span with >=2 links"
        linked = [
            complete[sid]
            for sid in dispatches[0]["args"]["links"]
        ]
        assert all(s["name"] == "solver.request" for s in linked)
        # every linked request's trace is rooted at a tick entry
        assert all(
            roots_by_trace[s["args"]["trace_id"]] == "reconcile.tick"
            for s in linked
        )
        actuations = [
            e for e in complete.values()
            if e["name"] == "actuate.set_replicas"
        ]
        assert actuations
        assert (
            roots_by_trace[actuations[0]["args"]["trace_id"]]
            == "reconcile.tick"
        )
        # flow events pair up (Perfetto link arrows)
        assert {e["id"] for e in events if e["ph"] == "s"} == {
            e["id"] for e in events if e["ph"] == "f"
        }


# -- overhead regression guard -----------------------------------------------


class TestTracingOverheadGuard:
    def _tick_p50(self, enabled: bool, ticks: int = 30) -> float:
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options
        from karpenter_tpu.simulate import simulate_trace  # noqa: F401

        tracer = default_tracer()
        tracer.enabled = enabled
        runtime = KarpenterRuntime(
            Options(), cloud_provider_factory=FakeFactory()
        )
        try:
            from karpenter_tpu.api.core import ObjectMeta
            from karpenter_tpu.api.metricsproducer import (
                MetricsProducer, MetricsProducerSpec,
                PendingCapacitySpec,
            )

            runtime.store.create(MetricsProducer(
                metadata=ObjectMeta(name="pending"),
                spec=MetricsProducerSpec(
                    pending_capacity=PendingCapacitySpec(
                        node_selector={"pool": "a"},
                    )
                ),
            ))
            times = []
            for _ in range(5):
                runtime.manager.converge(1)  # warm caches
            for _ in range(ticks):
                t0 = time.perf_counter()
                runtime.manager.converge(1)
                times.append(time.perf_counter() - t0)
        finally:
            runtime.close()
            tracer.enabled = True
        return float(np.percentile(times, 50))

    def test_span_volume_per_tick_is_bounded(self, fresh_tracer):
        """The structural guard: tracing cost is O(spans), so pin the
        span count a tick may mint — a regression to per-object or
        per-row span work shows up here long before wall clock."""
        before = fresh_tracer.spans_total
        self._tick_p50(enabled=True, ticks=10)
        per_tick = (fresh_tracer.spans_total - before) / 15.0
        assert per_tick <= 20, f"{per_tick:.1f} spans/tick"

    def test_enabled_vs_disabled_tick_overhead(self, fresh_tracer):
        """The wall-clock guard, with generous flake headroom (the
        benchmark's cells run with the tracer on, so its cost is inside
        their numbers); this pin catches gross regressions (>75% on
        sub-ms ticks)."""
        off = self._tick_p50(enabled=False)
        on = self._tick_p50(enabled=True)
        assert on <= off * 1.75 + 0.002, (
            f"tracing overhead p50 {off * 1e3:.3f}ms -> "
            f"{on * 1e3:.3f}ms"
        )


class TestTenantDebugFilters:
    """Satellite (ISSUE 12): per-tenant filtering on the existing debug
    surfaces — /debug/traces?tenant= keeps whole traces that touched
    the tenant (tenant-stamped solver-request / tenancy-serve spans),
    /debug/flightrecorder?tenant= keeps that tenant's events."""

    def test_traces_tenant_filter_keeps_whole_traces(
        self, fresh_tracer, fresh_recorder
    ):
        with fresh_tracer.trace("tick-a"):
            with fresh_tracer.span("solver.request", tenant="t1"):
                pass
            with fresh_tracer.span("actuate"):
                pass
        with fresh_tracer.trace("tick-b"):
            with fresh_tracer.span("solver.request", tenant="t2"):
                pass
        registry = GaugeRegistry()
        server = MetricsServer(registry, port=0, host="127.0.0.1")
        port = server.start()
        base = f"http://127.0.0.1:{port}"
        try:
            _status, _ctype, body = _get(f"{base}/debug/traces?tenant=t1")
            spans = json.loads(body)["spans"]
            names = sorted(s["name"] for s in spans)
            # the WHOLE trace that touched t1 — including its untagged
            # actuation span and root — but nothing of tick-b
            assert names == ["actuate", "solver.request", "tick-a"]
            _status, _c, body = _get(f"{base}/debug/traces?tenant=nope")
            assert json.loads(body)["spans"] == []
            # limit applies AFTER the filter
            _status, _c, body = _get(
                f"{base}/debug/traces?tenant=t1&limit=1"
            )
            assert len(json.loads(body)["spans"]) == 1
        finally:
            server.stop()

    def test_flightrecorder_tenant_filter(self, fresh_recorder):
        fresh_recorder.record(
            "tenant_breaker_trip", tenant="t1", error="boom"
        )
        fresh_recorder.record(
            "tenant_breaker_trip", tenant="t2", error="boom"
        )
        fresh_recorder.record("fsm_trip", subsystem="solver")
        server = MetricsServer(GaugeRegistry(), port=0, host="127.0.0.1")
        port = server.start()
        base = f"http://127.0.0.1:{port}"
        try:
            _s, _c, body = _get(
                f"{base}/debug/flightrecorder?tenant=t1"
            )
            events = json.loads(body)["events"]
            assert len(events) == 1
            assert events[0]["tenant"] == "t1"
            _s, _c, body = _get(
                f"{base}/debug/flightrecorder"
                f"?kind=tenant_breaker_trip&tenant=t2"
            )
            events = json.loads(body)["events"]
            assert [e["tenant"] for e in events] == ["t2"]
        finally:
            server.stop()

    def test_scheduler_breaker_trip_records_tenant_event(
        self, fresh_recorder
    ):
        """The tenancy board's breaker trips land in the flight
        recorder WITH the tenant field the filter keys on."""
        from karpenter_tpu.metrics.registry import (
            GaugeRegistry as Registry,
        )
        from karpenter_tpu.solver import SolverService
        from karpenter_tpu.tenancy import (
            MultiTenantScheduler,
            TenantRegistry,
            TenantSpec,
        )

        service = SolverService(registry=Registry())
        registry = TenantRegistry(
            service=service, registry=Registry(),
            specs=[TenantSpec(id="bad"), TenantSpec(id="good")],
        )
        scheduler = MultiTenantScheduler(
            registry, service, breaker_threshold=1
        )
        try:
            from karpenter_tpu import faults
            from karpenter_tpu.faults import FaultRegistry
            from karpenter_tpu.simulate import (
                multitenant_fleet_inputs,
            )

            fault_registry = faults.install(FaultRegistry(seed=7))
            fault_registry.plan(
                "tenancy.gather.bad", probability=1.0
            )
            batch = {
                tenant: multitenant_fleet_inputs(
                    i, 2, 1, 0, 0,
                    __import__("numpy").full(2, 2, "int32"), 1e6,
                )
                for i, tenant in enumerate(("bad", "good"))
            }
            scheduler.decide_all(batch)
            trips = [
                e for e in fresh_recorder.events()
                if e["kind"] == "tenant_breaker_trip"
            ]
            assert trips and trips[0]["tenant"] == "bad"
        finally:
            faults.uninstall()
            service.close()
