"""Cost subsystem tests (docs/cost.md).

The load-bearing pins:

  * PARITY — the multi-objective kernel's XLA and numpy paths produce
    bit-identical outputs on every field over randomized fleets
    (ops/cost.py module docstring contract).
  * WIRE-COMPAT — absent/zero cost operands reproduce today's decisions
    bit-identically: slo-less rows pass through exactly, and a
    weight-0/uncapped row chooses its base desired exactly.
  * the CostEngine's never-block contract and zero-overhead opt-out;
  * warm pools actuating spec.replicas + warm through the ordinary
    ScalableNodeGroup controller door;
  * karpenter_cost_* / karpenter_warmpool_* passing the promtool-style
    exposition lint;
  * the non-slow batched-vs-per-HA regression guard.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from karpenter_tpu.api.core import ObjectMeta
from karpenter_tpu.api.horizontalautoscaler import (
    Behavior,
    CrossVersionObjectReference,
    HorizontalAutoscaler,
    HorizontalAutoscalerSpec,
    Metric,
    MetricTarget,
    PrometheusMetricSource,
    SLOSpec,
)
from karpenter_tpu.api.scalablenodegroup import (
    ScalableNodeGroup,
    ScalableNodeGroupSpec,
    WarmPoolSpec,
)
from karpenter_tpu.cost import (
    CostEngine,
    CostModel,
    HOURLY_COST_ANNOTATION,
    INSTANCE_TYPE_ANNOTATION,
    WarmPoolEngine,
)
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.ops import cost as CK
from karpenter_tpu.store import Store

from test_observability import _lint_exposition


def random_inputs(seed: int, n: int = 64, m: int = 3) -> CK.CostInputs:
    rng = np.random.RandomState(seed)
    base = rng.randint(0, 100, n).astype(np.int32)
    return CK.CostInputs(
        base_desired=base,
        min_replicas=rng.randint(0, 5, n).astype(np.int32),
        max_replicas=(base + rng.randint(0, 300, n)).astype(np.int32),
        unit_cost=rng.choice([0.0, 0.07, 0.3, 1.7, 12.5], n).astype(
            np.float32
        ),
        slo_weight=rng.choice([0.0, 1.0, 50.0, 333.3], n).astype(
            np.float32
        ),
        max_hourly_cost=rng.choice([0.0, 2.0, 10.0, 55.5], n).astype(
            np.float32
        ),
        slo_valid=rng.rand(n) > 0.3,
        slo_target=rng.uniform(0.5, 10, (n, m)).astype(np.float32),
        demand_mu=rng.uniform(0, 500, (n, m)).astype(np.float32),
        demand_sigma=rng.choice([0.0, 3.0, 25.0], (n, m)).astype(
            np.float32
        ),
        demand_valid=rng.rand(n, m) > 0.2,
    )


def make_inputs(**overrides) -> CK.CostInputs:
    """One-row inputs with benign defaults, field-overridable."""
    fields = dict(
        base_desired=np.asarray([10], np.int32),
        min_replicas=np.asarray([0], np.int32),
        max_replicas=np.asarray([1000], np.int32),
        unit_cost=np.asarray([1.0], np.float32),
        slo_weight=np.asarray([0.0], np.float32),
        max_hourly_cost=np.asarray([0.0], np.float32),
        slo_valid=np.asarray([True]),
        slo_target=np.asarray([[4.0]], np.float32),
        demand_mu=np.asarray([[40.0]], np.float32),
        demand_sigma=np.asarray([[0.0]], np.float32),
        demand_valid=np.asarray([[True]]),
    )
    fields.update(overrides)
    return CK.CostInputs(**fields)


class TestKernelParity:
    def test_xla_matches_numpy_bitwise_over_random_fleets(self):
        """The parity contract: every output field of cost_jit and
        cost_numpy is bit-identical across randomized fleets and
        shapes."""
        for seed in range(8):
            for n, m in ((64, 3), (256, 1), (8, 5)):
                inputs = random_inputs(seed, n, m)
                dev = CK.cost_jit(inputs)
                host = CK.cost_numpy(inputs)
                for f in dataclasses.fields(CK.CostOutputs):
                    a = np.asarray(getattr(dev, f.name))
                    b = np.asarray(getattr(host, f.name))
                    assert np.array_equal(a, b), (
                        f"seed={seed} n={n} m={m}: {f.name} diverged"
                    )

    def test_invalid_rows_pass_through_exactly(self):
        """Wire-compat: slo_valid False reproduces the base decision
        bit for bit — an SLO-free fleet is untouched."""
        inputs = random_inputs(1)
        inputs = dataclasses.replace(
            inputs, slo_valid=np.zeros(64, bool)
        )
        out = CK.cost_jit(inputs)
        assert np.array_equal(
            np.asarray(out.desired), np.asarray(inputs.base_desired)
        )
        assert not np.asarray(out.slo_raised).any()
        assert not np.asarray(out.cost_limited).any()

    def test_zero_weight_uncapped_keeps_base(self):
        """Wire-compat: a valid row with violationCostWeight 0 and no
        budget scores minimal at candidate 0 — the base decision,
        exactly (argmin ties break first)."""
        out = CK.cost_jit(make_inputs(
            demand_mu=np.asarray([[400.0]], np.float32),  # underwater
        ))
        assert int(out.desired[0]) == 10
        assert not bool(out.slo_raised[0])

    def test_risk_weight_buys_replicas(self):
        """A heavy violation weight raises desired toward the count
        whose SLO capacity covers the one-sigma demand."""
        out = CK.cost_jit(make_inputs(
            slo_weight=np.asarray([100.0], np.float32),
            demand_mu=np.asarray([[56.0]], np.float32),  # needs 14
        ))
        assert int(out.desired[0]) == 14
        assert bool(out.slo_raised[0])
        assert float(out.violation_risk[0]) == 0.0

    def test_forecast_sigma_widens_the_buy(self):
        """The PR 5 forecast distribution as the risk input: sigma adds
        pessimism, so the same mu buys more replicas."""
        base = CK.cost_jit(make_inputs(
            slo_weight=np.asarray([100.0], np.float32),
            demand_mu=np.asarray([[48.0]], np.float32),
        ))
        widened = CK.cost_jit(make_inputs(
            slo_weight=np.asarray([100.0], np.float32),
            demand_mu=np.asarray([[48.0]], np.float32),
            demand_sigma=np.asarray([[8.0]], np.float32),
        ))
        assert int(widened.desired[0]) > int(base.desired[0])

    def test_budget_cap_trims_but_respects_min_replicas(self):
        out = CK.cost_jit(make_inputs(
            base_desired=np.asarray([20], np.int32),
            max_hourly_cost=np.asarray([8.0], np.float32),  # caps at 8
        ))
        assert int(out.desired[0]) == 8
        assert bool(out.cost_limited[0])
        floored = CK.cost_jit(make_inputs(
            base_desired=np.asarray([20], np.int32),
            min_replicas=np.asarray([12], np.int32),
            max_hourly_cost=np.asarray([8.0], np.float32),
        ))
        # the budget never takes a workload below its declared floor
        assert int(floored.desired[0]) == 12

    def test_headroom_reports_one_sigma_surplus(self):
        """The warm-pool sizing signal: replicas the pessimistic demand
        needs beyond the chosen count."""
        out = CK.cost_jit(make_inputs(
            demand_mu=np.asarray([[48.0]], np.float32),
            demand_sigma=np.asarray([[16.0]], np.float32),
        ))
        # needs ceil(64/4)=16, chose 10 (weight 0) -> headroom 6
        assert int(out.headroom[0]) == 6

    def test_expected_hourly_prices_the_choice(self):
        out = CK.cost_jit(make_inputs(
            unit_cost=np.asarray([0.5], np.float32),
        ))
        assert float(out.expected_hourly[0]) == pytest.approx(5.0)


class TestCostModel:
    def test_catalog_and_default(self):
        model = CostModel()
        assert model.on_demand("m5.large") == pytest.approx(0.096)
        assert model.on_demand("no-such-type") == 1.0
        assert model.on_demand(None) == 1.0

    def test_spot_tier_composes_with_capacity_labels(self):
        """The SAME spot labels the packing kernels steer on price the
        spot tier here (api/core.capacity_tier_of composition)."""
        model = CostModel()
        on_demand = model.node_cost(
            {"node.kubernetes.io/instance-type": "m5.large"}
        )
        spot = model.node_cost({
            "node.kubernetes.io/instance-type": "m5.large",
            "karpenter.sh/capacity-type": "spot",
        })
        assert spot == pytest.approx(on_demand * 0.35)

    def test_group_costs_is_columnar_over_profiles(self):
        model = CostModel()
        profiles = [
            ({}, {"node.kubernetes.io/instance-type": "m5.large"}, set()),
            ({}, {"karpenter.sh/capacity-type": "spot"}, set()),
            ({}, {}, set()),
        ]
        costs = model.group_costs(profiles)
        assert costs.dtype == np.float32
        assert costs.shape == (3,)
        assert costs[0] == pytest.approx(0.096)
        assert costs[1] == pytest.approx(0.35)
        assert costs[2] == pytest.approx(1.0)

    def test_unit_cost_annotation_overrides(self):
        model = CostModel()
        sng = ScalableNodeGroup(
            metadata=ObjectMeta(
                name="g", annotations={HOURLY_COST_ANNOTATION: "7.25"}
            ),
            spec=ScalableNodeGroupSpec(type="FakeNodeGroup", id="g"),
        )
        assert model.unit_cost(sng) == pytest.approx(7.25)
        sng.metadata.annotations = {
            INSTANCE_TYPE_ANNOTATION: "m5.xlarge"
        }
        assert model.unit_cost(sng) == pytest.approx(0.192)
        sng.spec.preemptible = True
        assert model.unit_cost(sng) == pytest.approx(0.192 * 0.35)

    def test_unparseable_override_falls_through(self):
        model = CostModel()
        sng = ScalableNodeGroup(
            metadata=ObjectMeta(
                name="g",
                annotations={HOURLY_COST_ANNOTATION: "not-a-price"},
            ),
            spec=ScalableNodeGroupSpec(type="FakeNodeGroup", id="g"),
        )
        assert model.unit_cost(sng) == 1.0

    def test_unit_cost_none_resource(self):
        assert CostModel().unit_cost(None) == 1.0


def _world(slo=None, queue=41.0, replicas=5, annotations=None):
    """(store, registry, batch-autoscaler world) around one SNG-backed
    queue HA — the chaos-suite shape, minus the runtime."""
    from karpenter_tpu.autoscaler import BatchAutoscaler
    from karpenter_tpu.metrics.clients import MetricsClientFactory

    store = Store()
    registry = GaugeRegistry()
    registry.register("queue", "length").set("q", "default", queue)
    store.create(ScalableNodeGroup(
        metadata=ObjectMeta(name="g", annotations=annotations or {}),
        spec=ScalableNodeGroupSpec(
            replicas=replicas, type="FakeNodeGroup", id="g"
        ),
    ))
    store.create(HorizontalAutoscaler(
        metadata=ObjectMeta(name="ha"),
        spec=HorizontalAutoscalerSpec(
            scale_target_ref=CrossVersionObjectReference(
                kind="ScalableNodeGroup", name="g"
            ),
            min_replicas=1,
            max_replicas=1000,
            metrics=[Metric(prometheus=PrometheusMetricSource(
                query='karpenter_queue_length{name="q"}',
                target=MetricTarget(type="AverageValue", value=4),
            ))],
            behavior=Behavior(slo=slo),
        ),
    ))
    engine = CostEngine(store=store, registry=registry)
    autoscaler = BatchAutoscaler(
        MetricsClientFactory(registry=registry), store,
        cost_engine=engine,
    )
    return store, registry, engine, autoscaler


def _reconcile(store, autoscaler):
    ha = store.get("HorizontalAutoscaler", "default", "ha")
    errors = autoscaler.reconcile_batch([ha])
    error = errors[("default", "ha")]
    if error is not None:
        raise error
    return store.get_scale("ScalableNodeGroup", "default", "g")


class TestCostEngine:
    REACTIVE = 11  # queue 41 / AverageValue target 4 -> ceil

    def test_slo_free_fleet_is_bit_identical_and_zero_overhead(self):
        store, _registry, engine, autoscaler = _world(slo=None)
        calls = []
        engine.cost_fn = lambda inputs: calls.append(1)
        scale = _reconcile(store, autoscaler)
        assert scale.spec_replicas == self.REACTIVE
        assert calls == []  # no dispatch, no arrays — the opt-out

    def test_slo_risk_raises_desired(self):
        """sloTarget below the HPA target prices risk into extra
        replicas: 41 demand / 3-per-replica SLO needs 14."""
        slo = SLOSpec(target_value=3.0, violation_cost_weight=100.0)
        store, registry, _engine, autoscaler = _world(slo=slo)
        scale = _reconcile(store, autoscaler)
        assert scale.spec_replicas == 14
        assert registry.gauge("cost", "violation_risk").get(
            "ha", "default"
        ) == 0.0
        assert registry.gauge("cost", "expected_hourly").get(
            "ha", "default"
        ) == pytest.approx(14.0)  # default model: $1/replica-hour

    def test_max_hourly_cost_caps_desired(self):
        slo = SLOSpec(max_hourly_cost=8.0)
        store, _registry, _engine, autoscaler = _world(slo=slo)
        scale = _reconcile(store, autoscaler)
        assert scale.spec_replicas == 8  # floor(8 / $1)

    def test_unit_cost_prices_through_the_scale_target(self):
        """The SNG's cost annotations reach the kernel: a $2/replica
        group affords only 4 replicas under an $8 budget."""
        slo = SLOSpec(max_hourly_cost=8.0)
        store, _registry, _engine, autoscaler = _world(
            slo=slo, annotations={HOURLY_COST_ANNOTATION: "2.0"}
        )
        scale = _reconcile(store, autoscaler)
        assert scale.spec_replicas == 4

    def test_never_block_on_cost_failure(self):
        """Any cost_fn failure degrades to the base (cost-blind)
        decision and counts blind_total — the tick never fails."""
        slo = SLOSpec(target_value=3.0, violation_cost_weight=100.0)
        store, registry, engine, autoscaler = _world(slo=slo)

        def boom(inputs):
            raise RuntimeError("injected cost failure")

        engine.cost_fn = boom
        scale = _reconcile(store, autoscaler)
        assert scale.spec_replicas == self.REACTIVE
        assert registry.gauge("cost", "blind_total").get(
            "ha", "default"
        ) == 1.0

    def test_headroom_decays_for_vanished_targets(self):
        slo = SLOSpec(target_value=3.0, violation_cost_weight=100.0)
        store, registry, engine, autoscaler = _world(slo=slo)
        _reconcile(store, autoscaler)
        assert engine.headroom("default", "g") >= 0
        assert ("default", "ha") in engine._contrib
        assert registry.gauge("cost", "violation_risk").get(
            "ha", "default"
        ) is not None
        # the HA drops its slo spec: the next pass drops its headroom
        # entry AND its gauge series — a frozen pre-opt-out value would
        # mislead dashboards
        ha = store.get("HorizontalAutoscaler", "default", "ha")
        ha.spec.behavior.slo = None
        store.update(ha)
        _reconcile(store, autoscaler)
        assert engine.headroom("default", "g") == 0
        assert registry.gauge("cost", "violation_risk").get(
            "ha", "default"
        ) is None
        assert registry.gauge("cost", "expected_hourly").get(
            "ha", "default"
        ) is None

    def test_prune_drops_deleted_has_headroom(self):
        """A DELETED HA never appears in another pass — prune() must
        retire its headroom contribution or its group would hold
        risk-sized warm capacity forever."""
        slo = SLOSpec(target_value=3.0, violation_cost_weight=100.0)
        store, _registry, engine, autoscaler = _world(slo=slo)
        _reconcile(store, autoscaler)
        assert ("default", "ha") in engine._contrib
        engine.prune("default", "ha")
        assert engine.headroom("default", "g") == 0

    def test_refine_honors_decide_movement_bounds(self):
        """The candidate ladder must respect the decide kernel's
        per-tick movement bounds (up_ceiling/down_floor): an SLO raise
        converges at the declared scaleUp rate, never in one jump past
        it."""
        slo = SLOSpec(target_value=3.0, violation_cost_weight=100.0)
        store, _registry, _engine, autoscaler = _world(slo=slo)
        base = autoscaler.decider

        def capped(inputs):
            # a Pods:1/period scaleUp policy, as the decide kernel
            # models it: this tick moves at most +-1 from current spec,
            # and up_ceiling/down_floor report exactly that bound
            out = base(inputs)
            spec = np.asarray(inputs.spec_replicas, np.int32)
            ceiling = (spec + 1).astype(np.int32)
            floor = np.maximum(spec - 1, 0).astype(np.int32)
            return dataclasses.replace(
                out,
                desired=np.clip(
                    np.asarray(out.desired, np.int32), floor, ceiling
                ),
                up_ceiling=ceiling,
                down_floor=floor,
            )

        autoscaler.decider = capped
        # without the bound the SLO raise would go straight toward 14
        # (test_slo_risk_raises_desired); the refinement must instead
        # converge at the declared +1-per-tick rate
        assert _reconcile(store, autoscaler).spec_replicas == 6
        assert _reconcile(store, autoscaler).spec_replicas == 7

    def test_gauges_pass_exposition_lint(self):
        """Satellite pin: the new karpenter_cost_* series survive the
        promtool-style lint next to everything else."""
        slo = SLOSpec(target_value=3.0, violation_cost_weight=100.0)
        store, registry, _engine, autoscaler = _world(slo=slo)
        _reconcile(store, autoscaler)
        typed, series = _lint_exposition(registry.expose_text())
        names = {name for name, _labels, _v in series}
        assert "karpenter_cost_expected_hourly" in names
        assert "karpenter_cost_violation_risk" in names
        assert "karpenter_cost_adjusted_total" in names
        assert typed["karpenter_cost_adjusted_total"] == "counter"


class TestWarmPool:
    def _controller(self, headroom=0, registry=None):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.controllers import ScalableNodeGroupController

        provider = FakeFactory()
        provider.node_replicas["g"] = 3
        warmpool = WarmPoolEngine(
            headroom_source=lambda ns, name: headroom,
            registry=registry,
        )
        controller = ScalableNodeGroupController(
            provider, warmpool=warmpool, registry=registry
        )
        return provider, controller

    def _sng(self, warm_pool=None, replicas=3):
        return ScalableNodeGroup(
            metadata=ObjectMeta(name="g"),
            spec=ScalableNodeGroupSpec(
                replicas=replicas, type="FakeNodeGroup", id="g",
                warm_pool=warm_pool,
            ),
        )

    def test_warm_target_actuates_through_the_controller(self):
        provider, controller = self._controller()
        sng = self._sng(WarmPoolSpec(min_warm=2, max_warm=6))
        controller.reconcile(sng)
        assert provider.node_replicas["g"] == 5  # 3 desired + 2 warm
        assert sng.status.replicas == 3  # the pre-actuation observation

    def test_risk_headroom_grows_warm_within_bounds(self):
        provider, controller = self._controller(headroom=4)
        controller.reconcile(self._sng(WarmPoolSpec(2, 6)))
        assert provider.node_replicas["g"] == 7  # 3 + clip(4, [2,6])
        provider2, controller2 = self._controller(headroom=50)
        controller2.reconcile(self._sng(WarmPoolSpec(2, 6)))
        assert provider2.node_replicas["g"] == 9  # maxWarm caps at 6

    def test_no_warm_pool_is_byte_identical(self):
        provider, controller = self._controller(headroom=4)
        controller.reconcile(self._sng(warm_pool=None))
        assert provider.node_replicas["g"] == 3  # converged, no write

    def test_broken_risk_source_degrades_to_min_warm(self):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.controllers import ScalableNodeGroupController

        provider = FakeFactory()
        provider.node_replicas["g"] = 3

        def boom(ns, name):
            raise RuntimeError("risk source down")

        controller = ScalableNodeGroupController(
            provider, warmpool=WarmPoolEngine(headroom_source=boom)
        )
        controller.reconcile(self._sng(WarmPoolSpec(2, 6)))
        assert provider.node_replicas["g"] == 5  # minWarm floor held

    def test_status_replicas_excludes_warm_headroom(self):
        """status.replicas feeds the HPA's proportional math as current
        replicas — warm nodes counted there would ratchet
        Value/Utilization fleets up by the warm amount every tick. Only
        nodes beyond spec.replicas are warm: mid-transition everything
        observed up to spec is serving."""
        provider, controller = self._controller()
        provider.node_replicas["g"] = 5  # converged: 3 desired + 2 warm
        sng = self._sng(WarmPoolSpec(min_warm=2, max_warm=6))
        controller.reconcile(sng)
        assert sng.status.replicas == 3  # serving only, warm excluded
        # mid-scale-up (warm not yet provisioned): all observed serve
        provider2, controller2 = self._controller()
        provider2.node_replicas["g"] = 3
        sng2 = self._sng(WarmPoolSpec(min_warm=2, max_warm=6))
        controller2.reconcile(sng2)
        assert sng2.status.replicas == 3

    def test_warm_gauges_pass_exposition_lint(self):
        registry = GaugeRegistry()
        provider, controller = self._controller(
            headroom=4, registry=registry
        )
        controller.reconcile(self._sng(WarmPoolSpec(2, 6)))
        typed, series = _lint_exposition(registry.expose_text())
        names = {name for name, _labels, _v in series}
        assert "karpenter_warmpool_replicas" in names
        assert "karpenter_warmpool_risk_replicas" in names

    def test_on_deleted_drops_gauges(self):
        registry = GaugeRegistry()
        provider, controller = self._controller(
            headroom=1, registry=registry
        )
        sng = self._sng(WarmPoolSpec(1, 3))
        controller.reconcile(sng)
        assert registry.gauge("warmpool", "replicas").get(
            "g", "default"
        ) is not None
        controller.on_deleted(sng)
        assert registry.gauge("warmpool", "replicas").get(
            "g", "default"
        ) is None


class TestServiceSeam:
    def test_numpy_backend_serves_the_mirror(self):
        from karpenter_tpu.solver import SolverService

        service = SolverService(backend="numpy")
        try:
            inputs = random_inputs(3)
            out = service.cost(inputs)
            mirror = CK.cost_numpy(inputs)
            assert np.array_equal(
                np.asarray(out.desired), np.asarray(mirror.desired)
            )
            assert service.stats.cost_calls == 1
            assert service.stats.cost_dispatches == 0
        finally:
            service.close()

    def test_degraded_fsm_short_circuits_cost_blind(self):
        """A tripped backend FSM makes cost() fail fast (the caller
        goes cost-blind) instead of billing the sick device; a due
        probe rides the device path again."""
        from karpenter_tpu.solver.service import (
            CostUnavailable,
            DEGRADED,
            SolverService,
        )

        clock = {"now": 1000.0}
        service = SolverService(
            backend="xla", health_probe_interval_s=30.0,
            clock=lambda: clock["now"],
        )
        try:
            with service._health_lock:
                service._health = DEGRADED
                service._next_probe = clock["now"] + 30.0
            with pytest.raises(CostUnavailable):
                service.cost(random_inputs(0))
            assert service.stats.cost_errors == 1
            # probe due: the device path runs and recovery follows
            clock["now"] += 31.0
            out = service.cost(random_inputs(0))
            assert out is not None
            assert service.backend_health() == "healthy"
        finally:
            service.close()

    def test_device_failure_feeds_fsm_and_propagates(self):
        from karpenter_tpu import faults
        from karpenter_tpu.faults import FaultRegistry
        from karpenter_tpu.solver import SolverService

        service = SolverService(backend="xla", health_failure_threshold=2)
        try:
            with FaultRegistry(seed=1) as registry:
                registry.plan("cost.score", probability=1.0)
                for _ in range(2):
                    with pytest.raises(faults.FaultInjected):
                        service.cost(random_inputs(0))
            assert service.stats.fsm_trips == 1
            assert service.stats.cost_errors == 2
        finally:
            service.close()


class TestApiValidation:
    def test_slo_spec_bounds(self):
        SLOSpec(target_value=1.0, violation_cost_weight=5.0).validate()
        with pytest.raises(ValueError):
            SLOSpec(target_value=0.0).validate()
        with pytest.raises(ValueError):
            SLOSpec(violation_cost_weight=-1.0).validate()
        with pytest.raises(ValueError):
            SLOSpec(max_hourly_cost=-0.5).validate()

    def test_warm_pool_bounds(self):
        WarmPoolSpec(min_warm=0, max_warm=4).validate()
        with pytest.raises(ValueError):
            WarmPoolSpec(min_warm=-1, max_warm=4).validate()
        with pytest.raises(ValueError):
            WarmPoolSpec(min_warm=5, max_warm=4).validate()

    def test_ha_validate_reaches_slo(self):
        ha = HorizontalAutoscaler(
            spec=HorizontalAutoscalerSpec(
                max_replicas=10,
                behavior=Behavior(slo=SLOSpec(target_value=-2.0)),
            )
        )
        with pytest.raises(ValueError):
            ha.validate()

    def test_sng_validate_reaches_warm_pool(self):
        sng = ScalableNodeGroup(
            spec=ScalableNodeGroupSpec(
                type="FakeNodeGroup", id="g",
                warm_pool=WarmPoolSpec(min_warm=3, max_warm=1),
            )
        )
        with pytest.raises(ValueError):
            sng.validate()

    def test_specs_serialize_round_trip(self):
        from karpenter_tpu.api.serialization import from_dict, to_dict

        ha = HorizontalAutoscaler(
            metadata=ObjectMeta(name="ha"),
            spec=HorizontalAutoscalerSpec(
                max_replicas=10,
                behavior=Behavior(slo=SLOSpec(
                    target_value=3.0, violation_cost_weight=50.0,
                    max_hourly_cost=12.0,
                )),
            ),
        )
        doc = to_dict(ha)
        assert doc["spec"]["behavior"]["slo"]["violationCostWeight"] == 50.0
        back = from_dict(HorizontalAutoscaler, doc)
        assert back.spec.behavior.slo.max_hourly_cost == 12.0

        sng = ScalableNodeGroup(
            metadata=ObjectMeta(name="g"),
            spec=ScalableNodeGroupSpec(
                type="FakeNodeGroup", id="g",
                warm_pool=WarmPoolSpec(min_warm=1, max_warm=4),
            ),
        )
        doc = to_dict(sng)
        assert doc["spec"]["warmPool"]["minWarm"] == 1
        back = from_dict(ScalableNodeGroup, doc)
        assert back.spec.warm_pool.max_warm == 4


class TestRegressionGuard:
    def test_batched_refine_beats_per_ha_loop(self):
        """Non-slow guard for the batched refine: one fleet dispatch
        must beat N single-row dispatches (generously; the refine on its
        own is not measured on the chip)."""
        import jax

        inputs = random_inputs(0, n=64, m=3)
        rows = [
            dataclasses.replace(
                inputs,
                **{
                    f.name: np.asarray(getattr(inputs, f.name))[i: i + 1]
                    for f in dataclasses.fields(inputs)
                },
            )
            for i in range(64)
        ]
        jax.block_until_ready(CK.cost_jit(inputs))  # warm both shapes
        jax.block_until_ready(CK.cost_jit(rows[0]))

        def best_of(fn, reps=3):
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            return min(times)

        batched = best_of(
            lambda: jax.block_until_ready(CK.cost_jit(inputs))
        )
        sequential = best_of(
            lambda: [
                jax.block_until_ready(CK.cost_jit(row)) for row in rows
            ]
        )
        assert batched < sequential, (
            f"batched {batched * 1e3:.2f}ms not faster than per-HA "
            f"loop {sequential * 1e3:.2f}ms"
        )
