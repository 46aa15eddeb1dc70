"""Solver introspection plane (observability/devicetelemetry.py).

The acceptance pins (ISSUE 15 / docs/observability.md "Device
telemetry & introspection"):

  * the compile ledger records every compile-cache miss — family,
    bucket rung, shard extents, wall compile seconds, the trace ids
    that paid for it — and captures XLA cost attribution (flops/bytes)
    per cache entry, which subsequent dispatch spans carry;
  * `--introspect` off (the default posture) yields BYTE-IDENTICAL
    decisions and a mark-free hot path (records_total stays 0) — the
    same property the tracing-off and provenance-off pins established;
  * STEADY-STATE COMPILE GUARD: past warm-up, the churn world records
    ZERO new ledger entries — pinning the jit-cache-key discipline the
    repo keeps re-fixing (PR 13 "signature cache stays logarithmic");
  * seeded chaos: a forced compile storm (reset_caches mid-run) trips
    exactly ONE `compile_storm` flight-recorder dump, the self-SLO
    device-memory source stays quiet, and the steady-state guard is
    green again after re-warm-up;
  * device memory telemetry publishes karpenter_device_* and the
    per-entry resident-LRU byte accounting, retires evicted entries'
    series, and feeds the self-SLO monitor as its fourth source;
  * /debug/solver reports the full posture in one JSON document;
  * overhead stays bounded (the structural guard).
"""

import json
import urllib.request

import numpy as np
import pytest

from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.observability import (
    MetricsServer,
    SelfSLOMonitor,
    SolverIntrospection,
)
from karpenter_tpu.observability.devicetelemetry import CompileLedger
from karpenter_tpu.observability.flightrecorder import (
    DUMP_KINDS,
    FlightRecorder,
    default_flight_recorder,
    reset_default_flight_recorder,
    set_default_flight_recorder,
)
from karpenter_tpu.ops.binpack import BinPackInputs
from karpenter_tpu.solver.service import SolverService


def _binpack_inputs(pods=5, groups=3, seed=0):
    rng = np.random.default_rng(seed)
    return BinPackInputs(
        pod_requests=rng.uniform(
            0.5, 2.0, (pods, 2)
        ).astype(np.float32),
        pod_valid=np.ones(pods, bool),
        pod_intolerant=np.zeros((pods, 1), bool),
        pod_required=np.zeros((pods, 1), bool),
        group_allocatable=np.full((groups, 2), 8.0, np.float32),
        group_taints=np.zeros((groups, 1), bool),
        group_labels=np.zeros((groups, 1), bool),
        pod_weight=np.ones(pods, np.int32),
    )


@pytest.fixture
def fresh_recorder():
    saved = default_flight_recorder()
    recorder = reset_default_flight_recorder()
    yield recorder
    set_default_flight_recorder(saved)


class TestCompileLedger:
    def test_records_and_tail_order(self):
        ledger = CompileLedger(capacity=8)
        for i in range(3):
            ledger.record(
                family="solve", rung=f"r{i}", seconds=0.1 * (i + 1),
                trace_ids=[f"t{i}"], flops=float(i),
            )
        rows = ledger.tail()
        assert [r["rung"] for r in rows] == ["r0", "r1", "r2"]
        assert [r["seq"] for r in rows] == [1, 2, 3]
        assert rows[0]["trace_ids"] == ["t0"]
        assert rows[2]["flops"] == 2.0
        assert ledger.records_total == 3
        assert ledger.by_family == {"solve": 3}
        assert len(ledger.tail(limit=2)) == 2
        assert ledger.tail(limit=0) == []

    def test_ring_bounds(self):
        ledger = CompileLedger(capacity=4)
        for i in range(10):
            ledger.record(family="f", rung=f"r{i}", seconds=0.0)
        rows = ledger.tail()
        assert len(rows) == 4
        assert [r["rung"] for r in rows] == ["r6", "r7", "r8", "r9"]
        assert ledger.records_total == 10

    def test_extents_and_attribution_columns(self):
        ledger = CompileLedger(capacity=4)
        ledger.record(
            family="solve", rung="r", seconds=1.0, extents=(4, 2),
            flops=10.0, bytes_accessed=20.0,
        )
        row = ledger.tail()[0]
        assert row["extents"] == (4, 2)
        assert row["bytes_accessed"] == 20.0


class TestServiceCompileLedger:
    """The ledger riding real SolverService dispatches."""

    def _service(self, recorder=None, **kw):
        registry = GaugeRegistry()
        service = SolverService(registry=registry, backend="xla")
        plane = SolverIntrospection(
            enabled=True, registry=registry,
            recorder=recorder or FlightRecorder(),
            **kw,
        ).attach(service)
        return service, plane, registry

    def test_miss_recorded_with_cost_attribution(self):
        service, plane, registry = self._service()
        try:
            service.solve(_binpack_inputs())
            assert plane.ledger.records_total == 1
            row = plane.ledger.tail()[0]
            assert row["family"] == "solve"
            assert row["seconds"] > 0
            assert "xla" in row["rung"]
            # jax 0.4.37 reports analytical flops/bytes at lowering;
            # the columns exist and are populated on this backend
            assert row["flops"] is not None and row["flops"] > 0
            assert row["bytes_accessed"] is not None
            # a second identical solve HITS the cache: no new row
            service.solve(_binpack_inputs(seed=1))
            assert plane.ledger.records_total == 1
            # the histogram family landed
            hist = registry.gauge("solver", "compile_seconds")
            assert hist.count("solve", "-") == 1
        finally:
            service.close()

    def test_forecast_family_recorded(self):
        from karpenter_tpu.forecast.models import ForecastInputs

        service, plane, _ = self._service()
        try:
            S, T = 3, 16
            values = np.tile(np.arange(T, dtype=np.float32), (S, 1))
            inputs = ForecastInputs(
                values=values,
                valid=np.ones((S, T), bool),
                times=np.tile(
                    np.arange(-T + 1, 1, dtype=np.float32) * 10.0,
                    (S, 1),
                ),
                weights=np.ones((S, T), np.float32),
                horizon=np.full(S, 30.0, np.float32),
                step_s=np.full(S, 10.0, np.float32),
                model=np.zeros(S, np.int32),
                season=np.full(S, 4, np.int32),
                alpha=np.full(S, 0.5, np.float32),
                beta=np.full(S, 0.1, np.float32),
                gamma=np.full(S, 0.1, np.float32),
            )
            service.forecast(inputs)
            assert plane.ledger.by_family.get("forecast") == 1
        finally:
            service.close()

    def test_disabled_plane_is_mark_free(self):
        service, plane, _ = self._service()
        plane.enabled = False
        try:
            service.solve(_binpack_inputs())
            service.solve(_binpack_inputs(seed=1))
            assert plane.ledger.records_total == 0
            assert plane.ledger.tail() == []
            plane.on_tick()
            assert plane.storms_total == 0
        finally:
            service.close()

    def test_dispatch_spans_gain_cost_args(self):
        from karpenter_tpu.observability import (
            default_tracer,
            reset_default_tracer,
            set_default_tracer,
        )

        saved = default_tracer()
        tracer = reset_default_tracer()
        service, plane, _ = self._service()
        try:
            with tracer.trace("tick"):
                service.solve(_binpack_inputs())
            with tracer.trace("tick"):
                service.solve(_binpack_inputs(seed=1))
            spans = [
                s for s in tracer.snapshot()
                if s["name"] == "solver.dispatch"
            ]
            assert len(spans) == 2
            # attribution is captured at compile time (first dispatch),
            # so the SECOND dispatch's span carries it
            assert "flops" in spans[1]["args"]
            assert spans[1]["args"]["flops"] > 0
            assert "bytes" in spans[1]["args"]
            # and the ledger row backlinks the paying trace
            assert plane.ledger.tail()[0]["trace_ids"]
        finally:
            service.close()
            set_default_tracer(saved)


class TestCompileStormDetector:
    def _plane(self, recorder, threshold=2):
        registry = GaugeRegistry()
        return SolverIntrospection(
            enabled=True, registry=registry, recorder=recorder,
            storm_threshold=threshold,
        ), registry

    def test_cold_boot_taper_never_trips(self):
        recorder = FlightRecorder()
        plane, _ = self._plane(recorder)
        # boot: misses taper 3 -> 1 -> 0; the detector is not yet
        # armed, so no storm fires even above threshold
        for n in (3, 1):
            for _ in range(n):
                plane.ledger.record(family="solve", rung="r", seconds=0.1)
            plane.on_tick()
        assert plane.storms_total == 0
        plane.on_tick()  # zero-miss tick: armed
        assert plane.storms_total == 0

    def test_steady_state_burst_trips_once_with_hysteresis(self):
        recorder = FlightRecorder()
        plane, registry = self._plane(recorder)
        plane.on_tick()  # zero-miss tick arms the detector
        for i in range(3):
            plane.ledger.record(
                family="solve", rung=f"r{i}", seconds=0.1,
                trace_ids=[f"t{i}"],
            )
        plane.on_tick()
        assert plane.storms_total == 1
        events = recorder.events(kind="compile_storm")
        assert len(events) == 1
        assert events[0]["misses"] == 3
        assert events[0]["families"] == ["solve"]
        assert set(events[0]["trace_ids"]) == {"t0", "t1", "t2"}
        assert "compile_storm" in DUMP_KINDS
        # continued misses in the SAME incident do not re-trip
        plane.ledger.record(family="solve", rung="r9", seconds=0.1)
        plane.ledger.record(family="solve", rung="r10", seconds=0.1)
        plane.on_tick()
        assert plane.storms_total == 1
        # a zero-miss tick re-arms; the next burst is a new incident
        plane.on_tick()
        plane.ledger.record(family="solve", rung="r11", seconds=0.1)
        plane.ledger.record(family="solve", rung="r12", seconds=0.1)
        plane.on_tick()
        assert plane.storms_total == 2
        counter = registry.gauge("solver", "compile_storms_total")
        assert counter.get("-", "-") == 2.0

    def test_below_threshold_misses_do_not_trip(self):
        plane, _ = self._plane(FlightRecorder(), threshold=3)
        plane.on_tick()
        plane.ledger.record(family="solve", rung="r", seconds=0.1)
        plane.on_tick()
        assert plane.storms_total == 0


class TestDeviceMemoryTelemetry:
    def test_gauges_and_watermark(self):
        registry = GaugeRegistry()
        stats = [{
            "device": "tpu:0",
            "bytes_in_use": 950,
            "bytes_limit": 1000,
        }]
        plane = SolverIntrospection(
            enabled=True, registry=registry,
            recorder=FlightRecorder(),
            stats_source=lambda: stats,
            watermark=0.9,
        )
        plane.on_tick()
        in_use = registry.gauge("device", "bytes_in_use")
        limit = registry.gauge("device", "bytes_limit")
        assert in_use.get("tpu:0", "-") == 950.0
        assert limit.get("tpu:0", "-") == 1000.0
        assert plane.memory_source() is True
        stats[0]["bytes_in_use"] = 100
        plane.on_tick()
        assert plane.memory_source() is False

    def test_no_stats_backend_is_quiet(self):
        plane = SolverIntrospection(
            enabled=True, registry=GaugeRegistry(),
            recorder=FlightRecorder(), stats_source=lambda: [],
        )
        plane.on_tick()
        assert plane.memory_source() is None

    def test_disabled_plane_memory_source_is_none(self):
        plane = SolverIntrospection(
            enabled=False,
            stats_source=lambda: [{
                "device": "d", "bytes_in_use": 99, "bytes_limit": 100,
            }],
        )
        plane.on_tick()
        assert plane.memory_source() is None

    def test_selfslo_counts_memory_events(self):
        high = {"value": True}
        monitor = SelfSLOMonitor(
            registry=GaugeRegistry(),
            memory_source=lambda: high["value"],
        )
        report = monitor.evaluate(now=1000.0)
        assert report["windows"]["5m"]["bad"] == 1
        high["value"] = False
        report = monitor.evaluate(now=1010.0)
        assert report["windows"]["5m"]["total"] == 2
        assert report["windows"]["5m"]["bad"] == 1
        board = monitor.scoreboard()
        assert board["device_memory"] == "ok"
        high["value"] = None
        report = monitor.evaluate(now=1020.0)
        # None contributes NO event — the quiet contract
        assert report["windows"]["5m"]["total"] == 2
        assert monitor.scoreboard()["device_memory"] == "off"

    def test_resident_entry_gauges_publish_and_retire(self):
        import types

        registry = GaugeRegistry()
        entries = [
            {"slot": "entry0", "bytes": 128, "rows": 8,
             "shape": (8, 2), "mode": "single", "tenant": "t1",
             "age_s": 1.0},
            {"slot": "entry1", "bytes": 256, "rows": 8,
             "shape": (8, 2), "mode": "single", "tenant": None,
             "age_s": 0.5},
        ]
        resident = types.SimpleNamespace(
            entries=lambda now=None: list(entries)
        )
        service = types.SimpleNamespace(_resident=resident)
        plane = SolverIntrospection(
            enabled=True, registry=registry,
            recorder=FlightRecorder(), stats_source=lambda: [],
        )
        plane.service = service
        plane.on_tick()
        vec = registry.gauge("solver", "resident_entry_bytes")
        assert vec.get("entry0", "t1") == 128.0
        assert vec.get("entry1", "-") == 256.0
        # LRU churn: entry1 evicted — its series must RETIRE
        del entries[1]
        plane.on_tick()
        assert vec.get("entry1", "-") is None
        assert vec.get("entry0", "t1") == 128.0


class TestResidentEntries:
    def test_entries_carry_bytes_rows_tenant_age(self):
        from karpenter_tpu.solver.resident import ResidentFleetState

        resident = ResidentFleetState(scatter="never")
        inputs = _binpack_inputs()
        stacked, kind = resident.obtain(
            inputs, (8, 4, 2, 1, 1), ("single",),
            lambda tree: tree, tenant="t7", now=100.0,
        )
        assert kind == "rebuild"
        entries = resident.entries(now=103.5)
        assert len(entries) == 1
        entry = entries[0]
        assert entry["tenant"] == "t7"
        assert entry["age_s"] == 3.5
        assert entry["rows"] == 8
        assert entry["bytes"] == resident.resident_bytes()
        assert entry["bytes"] > 0


# -- the runtime worlds -------------------------------------------------------


def _churn_world(tmp_path=None, introspect=True, storm_threshold=4,
                 **options_kw):
    """A compact watch-fed churn world (the bench _churn_runtime
    shape): every tick toggles a churn pod so the encode memo misses
    and the tick pays a real solve through the service."""
    from karpenter_tpu.api.core import (
        Node,
        NodeCondition,
        NodeSpec,
        NodeStatus,
        ObjectMeta,
        Pod,
        PodSpec,
        resource_list,
    )
    from karpenter_tpu.api.horizontalautoscaler import (
        Behavior,
        CrossVersionObjectReference,
        HorizontalAutoscaler,
        HorizontalAutoscalerSpec,
        Metric,
        MetricTarget,
        PrometheusMetricSource,
        ScalingRules,
    )
    from karpenter_tpu.api.metricsproducer import (
        MetricsProducer,
        MetricsProducerSpec,
        PendingCapacitySpec,
    )
    from karpenter_tpu.api.scalablenodegroup import (
        ScalableNodeGroup,
        ScalableNodeGroupSpec,
    )
    from karpenter_tpu.cloudprovider.fake import FakeFactory
    from karpenter_tpu.runtime import KarpenterRuntime, Options

    clock = {"now": 1_000_000.0}
    provider = FakeFactory()
    provider.node_replicas["g"] = 3
    runtime = KarpenterRuntime(
        Options(
            introspect=introspect,
            introspect_storm_threshold=storm_threshold,
            journal_dir=str(tmp_path) if tmp_path else None,
            **options_kw,
        ),
        cloud_provider_factory=provider,
        clock=lambda: clock["now"],
    )
    # force the compiled XLA path: "auto" resolves to the numpy host
    # program on the CPU test backend, which exercises no compile
    # cache at all — the ledger/storm pins need the jitted path (the
    # numpy/XLA bit-parity contract keeps decisions identical)
    runtime.solver_service.backend = "xla"
    store = runtime.store
    store.create(Node(
        metadata=ObjectMeta(name="n1", labels={"pool": "a"}),
        spec=NodeSpec(),
        status=NodeStatus(
            allocatable=resource_list(cpu="8", memory="16Gi", pods="16"),
            conditions=[NodeCondition("Ready", "True")],
        ),
    ))
    store.create(Pod(metadata=ObjectMeta(name="p1"), spec=PodSpec()))
    store.create(MetricsProducer(
        metadata=ObjectMeta(name="pending"),
        spec=MetricsProducerSpec(
            pending_capacity=PendingCapacitySpec(
                node_selector={"pool": "a"}, node_group_ref="g",
            )
        ),
    ))
    store.create(ScalableNodeGroup(
        metadata=ObjectMeta(name="g"),
        spec=ScalableNodeGroupSpec(
            replicas=3, type="FakeNodeGroup", id="g"
        ),
    ))
    store.create(HorizontalAutoscaler(
        metadata=ObjectMeta(name="ha"),
        spec=HorizontalAutoscalerSpec(
            scale_target_ref=CrossVersionObjectReference(
                kind="ScalableNodeGroup", name="g"
            ),
            min_replicas=1, max_replicas=100,
            metrics=[Metric(prometheus=PrometheusMetricSource(
                query='karpenter_queue_length{name="q"}',
                target=MetricTarget(type="AverageValue", value=4),
            ))],
            behavior=Behavior(
                scale_down=ScalingRules(stabilization_window_seconds=0)
            ),
        ),
    ))
    gauge = runtime.registry.register("queue", "length")
    gauge.set("q", "default", 12.0)
    flip = {"high": False}

    def tick():
        from karpenter_tpu.api.core import ObjectMeta, Pod, PodSpec

        try:
            runtime.store.delete("Pod", "default", "churn-pod")
        except KeyError:
            runtime.store.create(Pod(
                metadata=ObjectMeta(name="churn-pod"), spec=PodSpec()
            ))
        flip["high"] = not flip["high"]
        gauge.set("q", "default", 20.0 if flip["high"] else 12.0)
        clock["now"] += 61.0
        runtime.manager._due = {k: 0.0 for k in runtime.manager._due}
        runtime.manager.reconcile_all()

    return runtime, provider, tick


class TestSteadyStateCompileGuard:
    def test_zero_new_compiles_past_warmup(self, fresh_recorder):
        """The steady-state compile-count regression guard: the churn
        world, N ticks past warm-up, records ZERO new compile-ledger
        entries — the jit-cache-key discipline pin."""
        runtime, _provider, tick = _churn_world()
        try:
            for _ in range(5):  # warm-up: compiles + first encodes
                tick()
            plane = runtime.solver_introspection
            before = plane.ledger.records_total
            misses_before = (
                runtime.solver_service.stats.compile_cache_misses
            )
            for _ in range(8):
                tick()
            assert plane.ledger.records_total == before, (
                "steady-state churn ticks must not compile: "
                f"{plane.ledger.tail()}"
            )
            assert (
                runtime.solver_service.stats.compile_cache_misses
                == misses_before
            )
        finally:
            runtime.close()

    def test_zero_new_compiles_past_warmup_fused(self, fresh_recorder):
        """The fused-family extension of the guard: the same churn
        world with --fused-tick routes every steady-state tick through
        the ONE fused program, and N ticks past warm-up still record
        ZERO new compile-ledger rows — the fused compile key (shape
        buckets + stage presence) holds steady under churn."""
        runtime, _provider, tick = _churn_world(fused_tick=True)
        try:
            for _ in range(5):  # warm-up: compiles + first encodes
                tick()
            service = runtime.solver_service
            assert service.stats.fused_dispatches > 0, (
                "--fused-tick must actually route the tick through "
                "the fused program"
            )
            plane = runtime.solver_introspection
            before = plane.ledger.records_total
            misses_before = service.stats.compile_cache_misses
            dispatched = service.stats.fused_dispatches
            for _ in range(8):
                tick()
            assert service.stats.fused_dispatches > dispatched
            assert plane.ledger.records_total == before, (
                "steady-state fused ticks must not compile: "
                f"{plane.ledger.tail()}"
            )
            assert (
                service.stats.compile_cache_misses == misses_before
            )
        finally:
            runtime.close()


class TestCompileStormChaos:
    def test_reset_caches_storm_trips_one_dump(
        self, tmp_path, fresh_recorder
    ):
        """ISSUE 15 chaos acceptance: a forced compile storm
        (reset_caches mid-run) trips exactly ONE compile_storm
        flight-recorder dump, the self-SLO device-memory source stays
        quiet, and the steady-state guard is green after re-warm-up."""
        runtime, _provider, tick = _churn_world(
            tmp_path=tmp_path, storm_threshold=1,
        )
        try:
            plane = runtime.solver_introspection
            for _ in range(5):  # warm-up; the taper must not trip
                tick()
            assert plane.storms_total == 0
            # the forced storm: a mid-run cache reset (the recovery-
            # boot seam) makes the next tick recompile its rungs
            runtime.solver_service.reset_caches()
            for _ in range(3):
                tick()
            assert plane.storms_total == 1
            dumps = [
                p.name for p in tmp_path.iterdir()
                if p.name.startswith("flightrecorder-")
                and "compile_storm" in p.name
            ]
            assert len(dumps) == 1, dumps
            # the self-SLO device-memory source stayed quiet (CPU
            # backend: no memory stats -> no events either way)
            assert plane.memory_source() is None
            assert runtime.selfslo.scoreboard().get(
                "device_memory"
            ) == "off"
            # re-warmed: the steady-state guard is green again
            before = plane.ledger.records_total
            for _ in range(4):
                tick()
            assert plane.ledger.records_total == before
            assert plane.storms_total == 1  # still the one incident
        finally:
            runtime.close()


class TestIntrospectOffPin:
    def test_off_is_byte_identical_and_mark_free(self, fresh_recorder):
        """--introspect off (the default): the desired-replica trail is
        byte-identical with the plane on or off, and the off path
        records nothing — mirroring the tracing/provenance off pins."""

        def run(introspect, ticks=8):
            runtime, provider, tick = _churn_world(
                introspect=introspect
            )
            trail = []
            try:
                for _ in range(ticks):
                    tick()
                    trail.append(provider.node_replicas["g"])
                records = (
                    runtime.solver_introspection.ledger.records_total
                )
            finally:
                runtime.close()
            return trail, records

        on_trail, on_records = run(True)
        assert on_records > 0, "enabled world must record compiles"
        off_trail, off_records = run(False)
        assert off_trail == on_trail, (
            "introspection observes; it must never change a decision"
        )
        assert off_records == 0
        assert off_trail  # the world actually actuated


class TestDebugSolverEndpoint:
    def _get(self, url):
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, json.loads(resp.read())

    def test_posture_document(self, fresh_recorder):
        registry = GaugeRegistry()
        service = SolverService(registry=registry, backend="xla")
        plane = SolverIntrospection(
            enabled=True, registry=registry,
            recorder=FlightRecorder(),
            stats_source=lambda: [{
                "device": "tpu:0", "bytes_in_use": 10,
                "bytes_limit": 100,
            }],
        ).attach(service)
        server = MetricsServer(
            registry, port=0, host="127.0.0.1", introspection=plane
        )
        port = server.start()
        try:
            service.solve(_binpack_inputs())
            plane.on_tick()
            status, doc = self._get(
                f"http://127.0.0.1:{port}/debug/solver"
            )
            assert status == 200
            assert doc["enabled"] is True
            assert doc["compile"]["records_total"] == 1
            assert doc["compile"]["by_family"] == {"solve": 1}
            assert doc["compile"]["cache"]["misses"] == 1
            assert doc["compile"]["cache"]["rungs"]["solve"]
            assert doc["compile"]["ledger_tail"][0]["family"] == "solve"
            assert doc["backend"]["state"] == "healthy"
            assert doc["queue"]["requests"] == 1
            assert doc["queue"]["depth"] == 0
            assert doc["shard"]["broken"] is False
            assert doc["device_memory"]["devices"][0]["device"] == (
                "tpu:0"
            )
            assert "resident" in doc
            # the ledger tail honors ?limit=
            _, limited = self._get(
                f"http://127.0.0.1:{port}/debug/solver?limit=0"
            )
            assert limited["compile"]["ledger_tail"] == []
        finally:
            server.stop()
            service.close()

    def test_unwired_endpoint_reports_disabled(self):
        server = MetricsServer(GaugeRegistry(), port=0, host="127.0.0.1")
        port = server.start()
        try:
            status, doc = self._get(
                f"http://127.0.0.1:{port}/debug/solver"
            )
            assert status == 200
            assert doc == {"enabled": False}
        finally:
            server.stop()

    def test_disabled_plane_exposes_no_posture(self):
        """--introspect off is the opt-in for the WHOLE surface: a
        wired-but-disabled plane must not leak compile rungs, resident
        tenants, or queue internals through /debug/solver."""
        registry = GaugeRegistry()
        service = SolverService(registry=registry, backend="xla")
        plane = SolverIntrospection(
            enabled=False, registry=registry,
            recorder=FlightRecorder(),
        ).attach(service)
        server = MetricsServer(
            registry, port=0, host="127.0.0.1", introspection=plane
        )
        port = server.start()
        try:
            status, doc = self._get(
                f"http://127.0.0.1:{port}/debug/solver"
            )
            assert status == 200
            assert doc == {"enabled": False}
        finally:
            server.stop()
            service.close()


class TestIntrospectOverheadGuard:
    def test_enabled_vs_disabled_tick_overhead(self, fresh_recorder):
        """The wall-clock guard with generous flake headroom (the
        overhead is not measured on the chip); this pin catches gross
        regressions."""
        import time

        runtime, _provider, tick = _churn_world()
        plane = runtime.solver_introspection

        def run(enabled, ticks=10):
            plane.enabled = enabled
            times = []
            for _ in range(ticks):
                t0 = time.perf_counter()
                tick()
                times.append(time.perf_counter() - t0)
            return float(np.percentile(times, 50))

        try:
            for _ in range(4):  # warm-up
                tick()
            off = run(False)
            on = run(True)
        finally:
            runtime.close()
        assert on <= off * 1.75 + 0.002, (
            f"introspection overhead p50 {off * 1e3:.3f}ms -> "
            f"{on * 1e3:.3f}ms"
        )
