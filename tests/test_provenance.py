"""Decision provenance ledger (observability/provenance.py).

The acceptance pins (ISSUE 12 / docs/observability.md "Decision
provenance"):

  * /debug/decisions answers the provenance question end to end in a
    seeded multi-tenant replay: for a pinned tick, the ledger record
    for a chosen HA names the winning stage, the solver rung used, and
    a trace id that resolves in the exported trace JSONL;
  * a disabled ledger (--provenance off, the default posture) yields
    BYTE-IDENTICAL decisions and a mark-free hot path (records_total
    stays 0) — the same property the tracing-off pin established;
  * the ring is columnar and bounded: batch appends, oldest-drop,
    filtered queries, crash-safe JSONL export;
  * overhead stays bounded (the structural guard).
"""

import json
import urllib.request

import numpy as np
import pytest

from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.observability import (
    DecisionLedger,
    MetricsServer,
    default_ledger,
    reset_default_ledger,
    set_default_ledger,
)
from karpenter_tpu.observability.provenance import (
    STAGE_ADMISSION_DEFERRAL,
    STAGE_COST_BLIND,
    STAGE_COST_CLAMP,
    STAGE_COST_RAISE,
    STAGE_DEGRADED_FLOOR,
    STAGE_FORECAST_BLEND,
    STAGE_REACTIVE,
    decisions_export_path,
)


@pytest.fixture
def fresh_ledger():
    """Isolated process-default ledger (annotation sites read the
    default dynamically), ENABLED for the test."""
    saved = default_ledger()
    ledger = reset_default_ledger(enabled=True)
    yield ledger
    set_default_ledger(saved)


def _commit(ledger, kind="ha", n=1, **columns):
    batch = ledger.begin(kind, n, **columns)
    ledger.commit(batch)
    return batch


class TestDecisionLedger:
    def test_disabled_ledger_stages_nothing(self):
        ledger = DecisionLedger(enabled=False)
        assert ledger.begin("ha", 4, name="x") is None
        assert ledger.current() is None
        assert ledger.commit() == 0
        assert ledger.records_total == 0

    def test_columnar_batch_commit_and_query_filters(self):
        ledger = DecisionLedger(capacity=64, enabled=True)
        batch = ledger.begin(
            "ha", 3,
            tenant="t1",
            namespace=["default"] * 3,
            name=["a", "b", "c"],
            group=["g1", "g1", "g2"],
            observed=np.arange(12, dtype=np.float32).reshape(3, 4),
            observed_n=np.array([2, 1, 4], np.int16),
            prev_replicas=np.array([1, 2, 3], np.int32),
        )
        batch.annotate(
            base_desired=np.array([5, 2, 3], np.int32),
            final_desired=np.array([5, 2, 3], np.int32),
        )
        assert ledger.commit(batch) == 3
        assert ledger.records_total == 3
        assert len(ledger.query(group="g1")) == 2
        assert len(ledger.query(tenant="t1")) == 3
        assert len(ledger.query(tenant="nope")) == 0
        assert len(ledger.query(name="c")) == 1
        assert len(ledger.query(limit=1)) == 1
        record = ledger.query(name="a")[0]
        # observed values trim to the row's real metric count
        assert record["observed"] == [0.0, 1.0]
        assert record["prev_replicas"] == 1
        assert record["base_desired"] == 5
        # never-annotated numerics render as null, not sentinel -1
        assert record["cost_candidate"] is None
        assert record["forecast_value"] is None

    def test_ring_bounds_and_drop_accounting(self):
        ledger = DecisionLedger(capacity=8, enabled=True)
        _commit(ledger, n=5, name="first")
        _commit(ledger, n=5, name="second")
        assert ledger.records_total == 10
        assert ledger.records_dropped == 2
        records = ledger.query()
        assert len(records) == 8
        # oldest-first order survives the wrap, seq stays monotone
        seqs = [r["seq"] for r in records]
        assert seqs == sorted(seqs)
        assert [r["name"] for r in records] == (
            ["first"] * 3 + ["second"] * 5
        )

    def test_oversized_batch_keeps_newest_rows(self):
        ledger = DecisionLedger(capacity=4, enabled=True)
        batch = ledger.begin(
            "ha", 6, name=[f"r{i}" for i in range(6)]
        )
        ledger.commit(batch)
        assert [r["name"] for r in ledger.query()] == [
            "r2", "r3", "r4", "r5",
        ]
        assert ledger.records_dropped == 2

    def test_winning_stage_precedence(self):
        ledger = DecisionLedger(capacity=16, enabled=True)
        batch = ledger.begin("ha", 6, name=[
            "reactive", "raise", "clamp", "blend", "blind", "floor",
        ])
        batch.annotate(
            base_desired=np.array([3, 3, 3, 3, 3, 3], np.int32),
            final_desired=np.array([3, 5, 2, 3, 3, 3], np.int32),
            forecast_blend=np.array(
                [False, False, False, True, False, False]
            ),
            cost_blind=np.array(
                [False, False, False, False, True, False]
            ),
            solver_rung=np.array(
                ["device", "device", "device", "device", "device",
                 "floor"], object,
            ),
        )
        ledger.commit(batch)
        stages = {
            r["name"]: r["winning_stage"] for r in ledger.query()
        }
        assert stages == {
            "reactive": STAGE_REACTIVE,
            "raise": STAGE_COST_RAISE,
            "clamp": STAGE_COST_CLAMP,
            "blend": STAGE_FORECAST_BLEND,
            "blind": STAGE_COST_BLIND,
            "floor": STAGE_DEGRADED_FLOOR,
        }

    def test_deferred_rows_name_admission(self):
        ledger = DecisionLedger(capacity=8, enabled=True)
        batch = ledger.begin("tenant", 2, name=["r0", "r1"])
        batch.annotate(
            base_desired=np.array([2, 2], np.int32),
            final_desired=np.array([2, 2], np.int32),
            deferred=np.array([False, True]),
        )
        ledger.commit(batch)
        stages = [r["winning_stage"] for r in ledger.query()]
        assert stages == [STAGE_REACTIVE, STAGE_ADMISSION_DEFERRAL]

    def test_export_jsonl_is_valid_and_nan_free(self, tmp_path):
        ledger = DecisionLedger(capacity=8, enabled=True)
        _commit(ledger, n=3, name=["a", "b", "c"])
        path = str(tmp_path / "decisions.jsonl")
        assert ledger.export_jsonl(path) == 3
        lines = open(path).read().splitlines()
        assert len(lines) == 3
        for line in lines:
            record = json.loads(line)  # strict: no NaN literals
            assert record["kind"] == "ha"
            assert record["forecast_value"] is None

    def test_decisions_export_path_sibling(self):
        assert decisions_export_path("/x/trace.jsonl") == (
            "/x/trace.decisions.jsonl"
        )
        assert decisions_export_path("/x/trace") == (
            "/x/trace.decisions.jsonl"
        )

    def test_annotate_rows_composes_with_scalars(self):
        ledger = DecisionLedger(capacity=8, enabled=True)
        batch = ledger.begin("ha", 3, name=["a", "b", "c"])
        batch.annotate(solver_rung="device")
        batch.annotate_rows([2], solver_rung="floor")
        batch.annotate_rows(
            [0, 2], cost_risk=np.array([0.5, 0.0, 0.75], np.float32)
        )
        ledger.commit(batch)
        records = {r["name"]: r for r in ledger.query()}
        assert records["a"]["solver_rung"] == "device"
        assert records["c"]["solver_rung"] == "floor"
        assert records["a"]["cost_risk"] == 0.5
        assert records["b"]["cost_risk"] is None
        assert records["c"]["cost_risk"] == 0.75


# -- the off pin: byte-identical decisions, mark-free hot path ---------------


def _decision_world():
    """A seeded runtime whose every tick exercises decide + forecast +
    cost (SLO-opted HA with a forecast spec over a scripted metric):
    the full annotation surface of the ledger."""
    from karpenter_tpu.api.core import ObjectMeta
    from karpenter_tpu.api.horizontalautoscaler import (
        Behavior,
        CrossVersionObjectReference,
        ForecastSpec,
        HorizontalAutoscaler,
        HorizontalAutoscalerSpec,
        Metric,
        MetricTarget,
        PrometheusMetricSource,
        ScalingRules,
        SLOSpec,
    )
    from karpenter_tpu.api.scalablenodegroup import (
        ScalableNodeGroup,
        ScalableNodeGroupSpec,
    )
    from karpenter_tpu.cloudprovider.fake import FakeFactory
    from karpenter_tpu.runtime import KarpenterRuntime, Options

    clock = {"now": 1_000_000.0}
    provider = FakeFactory()
    provider.node_replicas["g"] = 2
    runtime = KarpenterRuntime(
        Options(), cloud_provider_factory=provider,
        clock=lambda: clock["now"],
    )
    runtime.store.create(ScalableNodeGroup(
        metadata=ObjectMeta(name="g"),
        spec=ScalableNodeGroupSpec(
            replicas=2, type="FakeNodeGroup", id="g"
        ),
    ))
    runtime.store.create(HorizontalAutoscaler(
        metadata=ObjectMeta(name="ha"),
        spec=HorizontalAutoscalerSpec(
            scale_target_ref=CrossVersionObjectReference(
                kind="ScalableNodeGroup", name="g"
            ),
            min_replicas=1, max_replicas=50,
            metrics=[Metric(prometheus=PrometheusMetricSource(
                query='karpenter_queue_length{name="q"}',
                target=MetricTarget(type="AverageValue", value=4),
            ))],
            behavior=Behavior(
                scale_down=ScalingRules(
                    stabilization_window_seconds=0
                ),
                forecast=ForecastSpec(
                    horizon_seconds=30, min_samples=3, model="linear",
                ),
                slo=SLOSpec(
                    target_value=3.0, violation_cost_weight=25.0,
                ),
            ),
        ),
    ))
    gauge = runtime.registry.register("queue", "length")
    return runtime, provider, gauge, clock


def _run_world(ticks: int = 12):
    runtime, provider, gauge, clock = _decision_world()
    desired_trail = []
    try:
        for tick in range(ticks):
            gauge.set("q", "default", 8.0 + 3.0 * tick)
            runtime.manager._due = {k: 0.0 for k in runtime.manager._due}
            runtime.manager.reconcile_all()
            clock["now"] += 10.0
            desired_trail.append(provider.node_replicas["g"])
    finally:
        runtime.close()
    return desired_trail


class TestProvenanceOffPin:
    def test_off_is_byte_identical_and_mark_free(self, fresh_ledger):
        """The --provenance off posture (default): decisions are
        byte-identical with the ledger on or off, and the off path
        records nothing (mark-free hot path) — mirroring the PR 9
        tracing-off pin."""
        fresh_ledger.enabled = True
        with_ledger = _run_world()
        on_records = default_ledger().records_total
        assert on_records > 0, "enabled world must record decisions"
        on_stages = {
            r["winning_stage"] for r in default_ledger().query()
        }
        assert on_stages and on_stages <= {
            "reactive", "forecast_blend", "cost_raise", "cost_clamp",
            "cost_blind",
        }

        off = reset_default_ledger(enabled=False)
        without_ledger = _run_world()
        assert without_ledger == with_ledger, (
            "the ledger observes; it must never change a decision"
        )
        assert off.records_total == 0
        assert off.query() == []

    def test_runtime_option_enables_default_off(self, fresh_ledger):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        fresh_ledger.enabled = False
        runtime = KarpenterRuntime(
            Options(), cloud_provider_factory=FakeFactory()
        )
        try:
            assert runtime.decision_ledger.enabled is False
        finally:
            runtime.close()
        runtime = KarpenterRuntime(
            Options(provenance=True),
            cloud_provider_factory=FakeFactory(),
        )
        try:
            assert runtime.decision_ledger.enabled is True
        finally:
            runtime.close()
            fresh_ledger.enabled = True


# -- /debug/decisions end to end ---------------------------------------------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, json.loads(resp.read())


class TestDebugDecisionsEndpoint:
    def test_filters_and_shape(self):
        ledger = DecisionLedger(capacity=32, enabled=True)
        batch = ledger.begin(
            "tenant", 4,
            tenant=np.array(["t1", "t1", "t2", "t2"], object),
            name=["row0", "row1", "row0", "row1"],
            group=np.array(["t1", "t1", "t2", "t2"], object),
        )
        batch.annotate(
            base_desired=np.array([1, 2, 3, 4], np.int32),
            final_desired=np.array([1, 2, 5, 4], np.int32),
        )
        ledger.commit(batch)
        server = MetricsServer(
            GaugeRegistry(), port=0, host="127.0.0.1", ledger=ledger
        )
        port = server.start()
        base = f"http://127.0.0.1:{port}"
        try:
            status, body = _get_json(f"{base}/debug/decisions")
            assert status == 200
            assert body["enabled"] is True
            assert len(body["decisions"]) == 4
            _, t2 = _get_json(f"{base}/debug/decisions?tenant=t2")
            assert len(t2["decisions"]) == 2
            assert t2["decisions"][0]["winning_stage"] == "cost_raise"
            _, limited = _get_json(
                f"{base}/debug/decisions?kind=tenant&limit=1"
            )
            assert len(limited["decisions"]) == 1
            _, nothing = _get_json(
                f"{base}/debug/decisions?group=missing"
            )
            assert nothing["decisions"] == []
        finally:
            server.stop()


# -- the multi-tenant acceptance replay --------------------------------------


class TestMultitenantProvenanceAcceptance:
    def test_pinned_tick_names_stage_rung_and_trace(
        self, tmp_path, fresh_ledger
    ):
        """ISSUE 12 acceptance: in a seeded --simulate --cost
        --multitenant replay, the pinned tick's ledger records name the
        winning stage, the solver rung used, and a trace id that
        resolves in the exported trace JSONL."""
        from karpenter_tpu.observability import (
            reset_default_tracer,
            set_default_tracer,
        )
        from karpenter_tpu.observability.tracing import default_tracer
        from karpenter_tpu.simulate import simulate_multitenant

        saved_tracer = default_tracer()
        reset_default_tracer()
        trace_path = str(tmp_path / "trace.jsonl")
        try:
            report = simulate_multitenant(
                tenants=4, ticks=6, provenance=True,
                trace_export=trace_path,
            )
        finally:
            set_default_tracer(saved_tracer)
        prov = report["provenance"]
        assert prov["records"] == 4 * 4 * 6  # tenants x rows x ticks
        pinned = prov["pinned"]
        assert len(pinned) == 4 * 4
        for row in pinned:
            assert row["why"] in (
                "reactive", "cost_raise", "cost_clamp",
                "forecast_blend", "admission_deferral", "cost_blind",
                "degraded_floor",
            )
            assert row["rung"] in (
                "device", "isolated", "mirror", "floor", "sidecar",
                "numpy",
            )
            assert row["trace"], "pinned records must backlink a trace"
        # cost refinement must actually have explained at least one
        # count (the seeded demand guarantees SLO raises)
        assert prov["by_stage"].get("cost_raise", 0) > 0
        # the trace ids RESOLVE in the exported Chrome-trace JSONL
        exported_traces = set()
        with open(trace_path) as fh:
            for line in fh:
                event = json.loads(line)
                if event.get("ph") == "X":
                    exported_traces.add(event["cat"])
        assert {row["trace"] for row in pinned} <= exported_traces
        # and the decision JSONL landed NEXT TO the trace export
        decisions_path = report["decisions_export"]
        assert decisions_path == decisions_export_path(trace_path)
        records = [
            json.loads(line) for line in open(decisions_path)
        ]
        assert len(records) == report["decision_records"]
        assert {r["tenant"] for r in records} == {
            "t0000", "t0001", "t0002", "t0003",
        }


# -- structural overhead guard -----------------------------------------------


class TestProvenanceOverheadGuard:
    def test_enabled_vs_disabled_tick_overhead(self, fresh_ledger):
        """The wall-clock guard with generous flake headroom (the
        overhead is not measured on the chip); this pin catches gross
        regressions."""
        import time

        import numpy as _np

        def run(enabled: bool, ticks: int = 12):
            fresh_ledger.enabled = enabled
            runtime, provider, gauge, clock = _decision_world()
            times = []
            try:
                for tick in range(4):
                    gauge.set("q", "default", 8.0 + tick)
                    runtime.manager.converge(1)
                    clock["now"] += 10.0
                for tick in range(ticks):
                    gauge.set("q", "default", 8.0 + tick)
                    runtime.manager._due = {
                        k: 0.0 for k in runtime.manager._due
                    }
                    t0 = time.perf_counter()
                    runtime.manager.reconcile_all()
                    times.append(time.perf_counter() - t0)
                    clock["now"] += 10.0
            finally:
                runtime.close()
            return float(_np.percentile(times, 50))

        off = run(False)
        on = run(True)
        assert on <= off * 1.75 + 0.002, (
            f"provenance overhead p50 {off * 1e3:.3f}ms -> "
            f"{on * 1e3:.3f}ms"
        )
