"""Seeded chaos suite: the runtime and simulator under injected faults.

Every scenario runs under a FIXED fault-registry seed (plans own their
RNG streams — faults/registry.py), so these are deterministic replays,
not flaky roulette. The invariants asserted are the ISSUE's acceptance
bar for the degradation ladder:

  * no lost solver requests — every solve completes (device, numpy
    fallback, or watchdog drain), the queue ends empty;
  * the solver backend FSM trips to numpy under repeated device faults
    and recovers via probes once the device heals;
  * the actuation circuit breaker opens on a flapping provider (with
    the structured ActuationCircuitOpen condition + error code) and
    closes through a half-open probe;
  * no duplicate scale actuations — each successful (group, count)
    provider write happens at most once;
  * fleet replicas converge to the no-fault fixed point within 10 ticks
    of faults clearing.

`make test-chaos` runs exactly this file + tests/test_faults.py.
"""

from __future__ import annotations

import numpy as np
import pytest

from karpenter_tpu import faults
from karpenter_tpu.api import conditions as cond
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
    CrossVersionObjectReference,
    HorizontalAutoscaler,
    HorizontalAutoscalerSpec,
    Metric,
    MetricTarget,
    PrometheusMetricSource,
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
from karpenter_tpu.cloudprovider.fake import FakeFactory, FakeNodeGroup, retryable_error
from karpenter_tpu.faults import FaultRegistry
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.runtime import KarpenterRuntime, Options
from karpenter_tpu.solver import SolverService

from test_binpack import make_inputs

CHAOS_SEED = 20260803


@pytest.fixture(autouse=True)
def _no_leaked_registry():
    yield
    faults.uninstall()


class FakeClock:
    def __init__(self, start=1000.0):
        self.now = start

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds


class RecordingNodeGroup(FakeNodeGroup):
    def set_replicas(self, count):
        super().set_replicas(count)
        self._factory.actuations.append((self._id, count))


class RecordingFactory(FakeFactory):
    """FakeFactory that records every SUCCESSFUL actuation — retries of
    a failed write are legitimate; a repeated successful write of the
    same transition is a duplicate actuation."""

    def __init__(self):
        super().__init__()
        self.actuations = []

    def node_group_for(self, spec):
        return RecordingNodeGroup(self, spec.id)


def sng_of(name, replicas):
    return ScalableNodeGroup(
        metadata=ObjectMeta(name=name),
        spec=ScalableNodeGroupSpec(
            replicas=replicas, type="FakeNodeGroup", id=name
        ),
    )


def queue_ha(name, target_query, min_replicas=3, max_replicas=100):
    return HorizontalAutoscaler(
        metadata=ObjectMeta(name="ha"),
        spec=HorizontalAutoscalerSpec(
            scale_target_ref=CrossVersionObjectReference(
                kind="ScalableNodeGroup", name=name
            ),
            min_replicas=min_replicas,
            max_replicas=max_replicas,
            metrics=[
                Metric(
                    prometheus=PrometheusMetricSource(
                        query=target_query,
                        target=MetricTarget(type="AverageValue", value=4),
                    )
                )
            ],
        ),
    )


def pending_capacity_world(store):
    """One profiled node group + one pending pod: every producer tick
    drives exactly one solve through the shared service."""
    store.create(
        Node(
            metadata=ObjectMeta(name="n1", labels={"pool": "a"}),
            spec=NodeSpec(),
            status=NodeStatus(
                allocatable=resource_list(cpu="8", memory="16Gi", pods="16"),
                conditions=[NodeCondition("Ready", "True")],
            ),
        )
    )
    store.create(
        Pod(metadata=ObjectMeta(name="p1"), spec=PodSpec())  # pending
    )
    mp = MetricsProducer(
        metadata=ObjectMeta(name="pending"),
        spec=MetricsProducerSpec(
            pending_capacity=PendingCapacitySpec(node_selector={"pool": "a"})
        ),
    )
    store.create(mp)
    return mp


class TestChaosScenario:
    """The acceptance scenario: 50 ticks with solver device faults at
    30%, a flapping provider, flaky metric reads and status writes —
    then faults clear and the fleet must converge within 10 ticks."""

    FIXED_POINT = 11  # queue=41, AverageValue target=4 -> ceil(41/4)

    def make_runtime(self):
        clock = FakeClock()
        provider = RecordingFactory()
        provider.node_replicas["g"] = 5
        runtime = KarpenterRuntime(
            Options(
                # ladder knobs tightened so 50 short ticks exercise
                # every rung (docs/resilience.md documents the defaults)
                solver_health_threshold=2,
                solver_probe_interval_s=0.0,  # probe every dispatch
                circuit_failure_threshold=3,
                circuit_reset_s=100.0,
                backoff_base_s=1.0,
                backoff_cap_s=60.0,
            ),
            cloud_provider_factory=provider,
            clock=clock,
        )
        # the virtual-CPU test backend resolves "auto" to numpy; pin the
        # XLA device path so solver faults hit a real device dispatch
        runtime.solver_service.backend = "xla"
        return runtime, provider, clock

    def tick(self, runtime, clock, n=1):
        """One manager tick with CLUSTER CHURN: a pod toggles existence
        each tick, so the producer's encode-memo (which rightly
        short-circuits solves for an unchanged cluster) misses and every
        tick drives a real solve through the service."""
        for _ in range(n):
            self._toggle_churn_pod(runtime)
            clock.advance(61.0)  # everything (SNG interval 60) is due
            runtime.manager.reconcile_all()

    def _toggle_churn_pod(self, runtime):
        try:
            runtime.store.delete("Pod", "default", "churn-pod")
        except KeyError:
            runtime.store.create(
                Pod(metadata=ObjectMeta(name="churn-pod"), spec=PodSpec())
            )

    def _remove_churn_pod(self, runtime):
        try:
            runtime.store.delete("Pod", "default", "churn-pod")
        except KeyError:
            pass

    def test_converges_after_faults_clear(self):
        runtime, provider, clock = self.make_runtime()
        mp = pending_capacity_world(runtime.store)
        runtime.registry.register("queue", "length").set(
            "q", "default", 41.0
        )
        runtime.store.create(sng_of("g", replicas=5))
        runtime.store.create(
            queue_ha("g", 'karpenter_queue_length{name="q"}')
        )
        service = runtime.solver_service
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan(
                "solver.dispatch", probability=0.3, code="DeviceFault"
            )
            registry.plan(
                "cloud.set_replicas", probability=0.9, code="Throttling"
            )
            registry.plan(
                "cloud.get_replicas", probability=0.3, code="Throttling"
            )
            registry.plan("metrics.query", probability=0.2)
            registry.plan("store.patch_status", probability=0.05)
            self.tick(runtime, clock, n=50)

            assert registry.injected.get("solver.dispatch", 0) >= 5, (
                "the scenario must actually have exercised device faults"
            )
            # the FSM tripped under the 30% device-fault stream and
            # recovered through a probe while faults were still active
            assert service.stats.fsm_trips >= 1
            assert service.stats.fsm_recoveries >= 1
            # no lost requests: everything submitted was answered
            # (device or numpy) and nothing is stuck in the queue
            assert service.queue_depth() == 0
            assert service.stats.requests >= 50
            assert service.stats.fallbacks >= 1
            # the circuit opened at least once against the 90%-flaky
            # actuation path
            opens = runtime.registry.gauge(
                "resilience", "circuit_open_total"
            ).get("g", "default")
            assert opens is not None and opens >= 1

            faults.uninstall()  # ---- faults clear ----

            converged_at = None
            for i in range(10):
                self.tick(runtime, clock)
                if provider.node_replicas["g"] == self.FIXED_POINT:
                    converged_at = i
                    break
            assert converged_at is not None, (
                f"fleet must converge to {self.FIXED_POINT} within 10 "
                f"ticks of faults clearing; stuck at "
                f"{provider.node_replicas['g']}"
            )
            self._remove_churn_pod(runtime)
            self.tick(runtime, clock, n=2)  # settle status/conditions
            self._remove_churn_pod(runtime)
            clock.advance(61.0)
            runtime.manager.reconcile_all()  # final churn-free solve

            assert service.backend_health() == "healthy"
            ha = runtime.store.get("HorizontalAutoscaler", "default", "ha")
            assert ha.status.desired_replicas == self.FIXED_POINT
            sng = runtime.store.get("ScalableNodeGroup", "default", "g")
            assert sng.status.replicas == self.FIXED_POINT
            assert (
                sng.status_conditions().get(cond.ABLE_TO_SCALE).status
                == cond.TRUE
            )
            # the pending-capacity producer kept producing through the
            # whole outage (numpy fallback): status populated and happy
            mp = runtime.store.get(
                "MetricsProducer", "default", "pending"
            )
            assert mp.status.pending_capacity is not None
            assert mp.status.pending_capacity.pending_pods == 1
            assert (
                mp.status_conditions().get(cond.ACTIVE).status == cond.TRUE
            )
            # no duplicate actuations: every successful (group, count)
            # write is unique — retries of FAILED writes don't repeat a
            # landed transition
            assert len(provider.actuations) == len(
                set(provider.actuations)
            ), f"duplicate actuation in {provider.actuations}"
        finally:
            runtime.close()

    def test_scenario_is_deterministic(self):
        """Same seed, same world → identical actuation history and
        fault counts: the suite is a replay, not a dice roll."""

        def run():
            runtime, provider, clock = self.make_runtime()
            pending_capacity_world(runtime.store)
            runtime.registry.register("queue", "length").set(
                "q", "default", 41.0
            )
            runtime.store.create(sng_of("g", replicas=5))
            runtime.store.create(
                queue_ha("g", 'karpenter_queue_length{name="q"}')
            )
            try:
                with FaultRegistry(seed=CHAOS_SEED) as registry:
                    registry.plan("cloud.set_replicas", probability=0.9)
                    registry.plan("cloud.get_replicas", probability=0.3)
                    registry.plan("metrics.query", probability=0.2)
                    self.tick(runtime, clock, n=25)
                    return (
                        list(provider.actuations),
                        dict(registry.injected),
                        provider.node_replicas["g"],
                    )
            finally:
                runtime.close()

        assert run() == run()


class TestSimulateUnderFaults:
    def test_simulate_report_identical_with_device_faults(self):
        """The dry-run simulator under 100% device faults: every solve
        degrades to numpy and the REPORT IS IDENTICAL — the fallback
        path is not a lesser answer (device/numpy parity is pinned by
        the solver oracle suites)."""
        from karpenter_tpu.simulate import simulate
        from karpenter_tpu.store import Store

        store = Store()
        pending_capacity_world(store)
        service = SolverService(
            registry=GaugeRegistry(), backend="xla",
            health_failure_threshold=3,
        )
        try:
            baseline = simulate(store, solver=service.solve)
            with FaultRegistry(seed=CHAOS_SEED) as registry:
                registry.plan("solver.dispatch", probability=1.0)
                for _ in range(4):  # enough to trip the FSM mid-run
                    faulty = simulate(store, solver=service.solve)
                    assert faulty == baseline
            assert service.stats.fallbacks >= 1
            assert service.stats.fsm_trips == 1
        finally:
            service.close()


class TestForecastChaos:
    """Satellite pin: forecast-path device faults degrade DOWN the
    ladder (numpy mirror first, reactive-only at worst) and NEVER block
    the reconcile loop — the fleet converges to the same fixed point a
    fault-free reactive run reaches."""

    FIXED_POINT = 11  # queue=41, AverageValue target=4 -> ceil(41/4)

    def test_forecast_device_faults_degrade_not_block(self):
        from karpenter_tpu.api.horizontalautoscaler import ForecastSpec

        clock = FakeClock()
        provider = RecordingFactory()
        provider.node_replicas["g"] = 5
        runtime = KarpenterRuntime(
            Options(solver_health_threshold=2,
                    solver_probe_interval_s=0.0),
            cloud_provider_factory=provider,
            clock=clock,
        )
        runtime.solver_service.backend = "xla"
        runtime.registry.register("queue", "length").set(
            "q", "default", 41.0
        )
        runtime.store.create(sng_of("g", replicas=5))
        ha = queue_ha("g", 'karpenter_queue_length{name="q"}')
        ha.spec.behavior.forecast = ForecastSpec(
            horizon_seconds=30.0, model="linear", min_samples=3
        )
        runtime.store.create(ha)
        service = runtime.solver_service
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("forecast.predict", probability=1.0)
            for _ in range(30):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert registry.injected.get("forecast.predict", 0) >= 1, (
                "the scenario must actually have exercised forecast "
                "faults"
            )
            # every forecast answered from the numpy mirror; the
            # reconcile loop never stalled and the fleet sits at the
            # reactive fixed point (a flat metric forecasts flat)
            assert service.stats.forecast_calls >= 20
            assert service.stats.fallbacks >= 1
            assert service.queue_depth() == 0
            assert provider.node_replicas["g"] == self.FIXED_POINT
            got = runtime.store.get(
                "HorizontalAutoscaler", "default", "ha"
            )
            assert got.status.desired_replicas == self.FIXED_POINT
            # the repeated device faults tripped the backend FSM — the
            # forecast path feeds the SAME health ladder bin-packs do
            assert service.stats.fsm_trips >= 1

            faults.uninstall()  # ---- faults clear ----
            for _ in range(3):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert service.backend_health() == "healthy"
            assert provider.node_replicas["g"] == self.FIXED_POINT
        finally:
            faults.uninstall()
            runtime.close()


class TestFusedTickChaos:
    """Satellite pin (docs/solver-service.md "Fused tick"): 100%
    fused-program faults walk the never-block ladder — chained
    per-stage fallback first, the numpy floor once the FSM trips —
    and the fleet still reaches the fixed point a never-fused run
    reaches. reset_caches() re-arms the fused compile key."""

    FIXED_POINT = 11  # queue=41, AverageValue target=4 -> ceil(41/4)

    def _world(self, fused: bool):
        clock = FakeClock()
        provider = RecordingFactory()
        provider.node_replicas["g"] = 5
        runtime = KarpenterRuntime(
            Options(fused_tick=fused,
                    solver_health_threshold=2,
                    solver_probe_interval_s=0.0),
            cloud_provider_factory=provider,
            clock=clock,
        )
        runtime.solver_service.backend = "xla"
        runtime.registry.register("queue", "length").set(
            "q", "default", 41.0
        )
        runtime.store.create(sng_of("g", replicas=5))
        runtime.store.create(
            queue_ha("g", 'karpenter_queue_length{name="q"}')
        )
        return runtime, provider, clock

    def test_fused_faults_degrade_down_the_ladder(self):
        # the never-fused reference: same world, no faults
        baseline, base_provider, base_clock = self._world(fused=False)
        try:
            for _ in range(10):
                base_clock.advance(61.0)
                baseline.manager.reconcile_all()
            assert base_provider.node_replicas["g"] == self.FIXED_POINT
        finally:
            baseline.close()

        runtime, provider, clock = self._world(fused=True)
        service = runtime.solver_service
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("fused.tick", probability=1.0)
            for _ in range(10):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert registry.injected.get("fused.tick", 0) >= 1, (
                "the scenario must actually have exercised fused faults"
            )
            # every faulted tick served from the CHAINED rung (probe
            # interval 0 keeps the device attempt live), bit-identical
            # to the never-fused wire: same fixed point
            assert service.stats.fused_chained_serves >= 1
            assert service.stats.fused_dispatches == 0
            assert service.queue_depth() == 0
            assert provider.node_replicas["g"] == self.FIXED_POINT
            # the fused path feeds the SAME backend-health FSM
            assert service.stats.fsm_trips >= 1

            # park the probes: a DEGRADED plane short-circuits to the
            # numpy floor without touching the device
            service.health_probe_interval_s = 3600.0
            mirrors_before = service.stats.fused_mirror_serves
            for _ in range(4):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert service.stats.fused_mirror_serves > mirrors_before
            assert provider.node_replicas["g"] == self.FIXED_POINT

            faults.uninstall()  # ---- faults clear ----
            service.health_probe_interval_s = 0.0
            service._next_probe = 0.0
            for _ in range(3):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert service.backend_health() == "healthy"
            assert service.stats.fused_dispatches >= 1
            assert provider.node_replicas["g"] == self.FIXED_POINT

            # reset_caches re-arms the fused compile key: the next
            # dispatch counts a fresh compile again
            misses = service.stats.compile_cache_misses
            service.reset_caches()
            clock.advance(61.0)
            runtime.manager.reconcile_all()
            assert service.stats.compile_cache_misses == misses + 1
            assert provider.node_replicas["g"] == self.FIXED_POINT
        finally:
            faults.uninstall()
            runtime.close()


class TestPreemptChaos:
    """Satellite pin (docs/preemption.md): eviction planning under
    device faults degrades to the BIT-IDENTICAL numpy mirror — plans
    keep landing, budgets hold, no victim is ever evicted twice — and
    the repeated faults trip the shared backend-health FSM."""

    BUDGET = 2

    def _storm(self):
        from test_preemption import make_pod, storm_store

        store = storm_store(eviction_budget=self.BUDGET)
        for i in range(3):
            store.create(
                make_pod(f"crit-{i}", cpu="2", priority=1000 - i)
            )
        return store

    def test_device_faults_degrade_to_mirror_with_budgets_held(self):
        from karpenter_tpu.preemption import (
            PreemptionConfig,
            PreemptionEngine,
        )

        store = self._storm()
        clock = FakeClock()
        service = SolverService(
            registry=GaugeRegistry(), backend="xla",
            health_failure_threshold=2,
            health_probe_interval_s=0.0,
        )
        engine = PreemptionEngine(
            store, service,
            config=PreemptionConfig(
                min_candidate_priority=1, plan_interval_s=0.0,
                budget_per_group=self.BUDGET, hold_s=30.0,
            ),
            clock=clock,
        )
        evicted_ever = []
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("preempt.plan", probability=1.0)
            per_round = []
            for _ in range(6):
                plans = engine.plan(clock.now)
                round_evicted = [
                    key
                    for p in plans.values()
                    if p
                    for key in p["evictions"]
                ]
                per_round.append(len(round_evicted))
                evicted_ever.extend(round_evicted)
                clock.advance(61.0)  # holds + budget charges expire
            assert registry.injected.get("preempt.plan", 0) >= 1, (
                "the scenario must actually have exercised preempt "
                "faults"
            )
            # every plan answered from the bit-identical numpy mirror:
            # evictions still landed, the loop never stalled
            assert service.stats.fallbacks >= 1
            assert sum(per_round) >= 2
            assert service.queue_depth() == 0
            # budgets NEVER exceeded, even while degraded
            assert all(n <= self.BUDGET for n in per_round), per_round
            # no duplicate evictions across the whole storm
            assert len(evicted_ever) == len(set(evicted_ever))
            # the repeated device faults fed the shared FSM
            assert service.stats.fsm_trips >= 1

            faults.uninstall()  # ---- faults clear ----
            clock.advance(61.0)
            engine.plan(clock.now)
            assert service.backend_health() == "healthy"
        finally:
            faults.uninstall()
            service.close()


class TestSolverFSM:
    def test_trips_wholesale_and_recovers_via_probe(self):
        service = SolverService(
            registry=GaugeRegistry(), backend="xla",
            health_failure_threshold=2,
            health_probe_interval_s=3600.0,  # no implicit probes
        )
        inputs = make_inputs(
            pod_requests=[[1, 1], [3, 1]], group_allocatable=[[4, 4]]
        )
        expect = None
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("solver.dispatch", mode="flaky", times=2)
            for _ in range(2):
                out = service.solve(inputs, buckets=8)
            assert service.backend_health() == "degraded"
            assert service.stats.fsm_trips == 1
            attempts_at_trip = registry.attempts["solver.dispatch"]
            # degraded: requests are served WHOLESALE from numpy — the
            # device (and so the injection point) is never attempted
            for _ in range(3):
                out = service.solve(inputs, buckets=8)
            assert registry.attempts["solver.dispatch"] == attempts_at_trip
            assert service.stats.fsm_short_circuits >= 3
            # force the probe window open: the next dispatch rides the
            # device (plan exhausted -> succeeds) and recovers the FSM
            with service._health_lock:
                service._next_probe = 0.0
            out = service.solve(inputs, buckets=8)
            assert service.backend_health() == "healthy"
            assert service.stats.fsm_probes >= 1
            assert service.stats.fsm_recoveries == 1
            from karpenter_tpu.ops.numpy_binpack import binpack_numpy

            expect = binpack_numpy(inputs, buckets=8)
            np.testing.assert_array_equal(
                np.asarray(out.assigned), np.asarray(expect.assigned)
            )
        finally:
            faults.uninstall()
            service.close()


class TestWatchdog:
    def test_restarts_hung_worker_and_drains_to_numpy(self):
        """A hang plan wedges the worker inside a device section; the
        watchdog must supersede it, answer the stuck request from numpy,
        and leave the service serving on a fresh worker."""
        from karpenter_tpu.ops.numpy_binpack import binpack_numpy

        service = SolverService(
            registry=GaugeRegistry(), backend="xla",
            watchdog_timeout_s=0.2,
            health_failure_threshold=10,  # one hang must not trip FSM
        )
        inputs = make_inputs(
            pod_requests=[[1, 1], [3, 1]], group_allocatable=[[4, 4]]
        )
        expect = binpack_numpy(inputs, buckets=8)
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("solver.dispatch", mode="hang", times=1)
            out = service.solve(inputs, buckets=8, timeout=30.0)
            np.testing.assert_array_equal(
                np.asarray(out.assigned), np.asarray(expect.assigned)
            )
            assert service.stats.watchdog_restarts == 1
            assert service.backend_health() == "healthy"
            # release the superseded worker's hang; its late unwind must
            # not disturb the fresh worker
            faults.uninstall()
            out2 = service.solve(inputs, buckets=8, timeout=30.0)
            np.testing.assert_array_equal(
                np.asarray(out2.assigned), np.asarray(expect.assigned)
            )
            assert service.stats.watchdog_restarts == 1
        finally:
            faults.uninstall()
            service.close()


class TestActuationCircuit:
    def test_opens_with_structured_condition_then_probe_heals(self):
        clock = FakeClock()
        provider = FakeFactory()
        provider.node_replicas["g"] = 1
        runtime = KarpenterRuntime(
            Options(circuit_failure_threshold=3, circuit_reset_s=100.0),
            cloud_provider_factory=provider,
            clock=clock,
        )
        try:
            runtime.store.create(sng_of("g", replicas=2))
            provider.want_err = retryable_error("Throttling")
            for _ in range(3):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            sng = runtime.store.get("ScalableNodeGroup", "default", "g")
            able = sng.status_conditions().get(cond.ABLE_TO_SCALE)
            assert able.status == cond.FALSE
            assert able.reason == cond.ACTUATION_CIRCUIT_OPEN
            assert "Throttling" in able.message, (
                "the RetryableError.code must thread into the message"
            )
            assert "next probe" in able.message
            # resource stays ACTIVE: an open circuit is supervised
            # degradation, not a resource fault
            assert (
                sng.status_conditions().get(cond.ACTIVE).status
                == cond.TRUE
            )
            # while open, the provider is NOT called (attempts counted
            # by an empty fault registry — observation only)
            with FaultRegistry(seed=0) as registry:
                clock.advance(61.0)
                runtime.manager.reconcile_all()
                assert registry.attempts.get("cloud.get_replicas", 0) == 0
                assert registry.attempts.get("cloud.set_replicas", 0) == 0
            state = runtime.registry.gauge(
                "resilience", "circuit_state"
            ).get("g", "default")
            assert state == 1.0  # open
            # provider heals; once the reset window passes, the single
            # half-open probe reconcile closes the circuit AND actuates
            provider.want_err = None
            clock.advance(61.0)  # cumulative > reset_s since opening
            runtime.manager.reconcile_all()
            assert provider.node_replicas["g"] == 2
            sng = runtime.store.get("ScalableNodeGroup", "default", "g")
            able = sng.status_conditions().get(cond.ABLE_TO_SCALE)
            assert able.status == cond.TRUE
            assert runtime.registry.gauge(
                "resilience", "circuit_state"
            ).get("g", "default") == 0.0
        finally:
            runtime.close()


class TestCostChaos:
    """Satellite pin (docs/cost.md degradation contract): cost-kernel
    faults make the tick COST-BLIND — the base reactive decision stands,
    the reconcile loop never blocks — and the repeated device failures
    feed the SAME backend-health FSM everything else rides; once faults
    clear, probes recover the device path and the multi-objective
    refinement resumes."""

    REACTIVE = 11  # queue 41 / AverageValue target 4 -> ceil
    COST_AWARE = 14  # 41 demand / 3-per-replica sloTarget -> ceil

    def test_cost_faults_degrade_to_cost_blind_then_recover(self):
        from karpenter_tpu.api.horizontalautoscaler import SLOSpec

        clock = FakeClock()
        provider = RecordingFactory()
        provider.node_replicas["g"] = 5
        runtime = KarpenterRuntime(
            Options(solver_health_threshold=2,
                    solver_probe_interval_s=0.0),
            cloud_provider_factory=provider,
            clock=clock,
        )
        runtime.solver_service.backend = "xla"
        runtime.registry.register("queue", "length").set(
            "q", "default", 41.0
        )
        runtime.store.create(sng_of("g", replicas=5))
        ha = queue_ha("g", 'karpenter_queue_length{name="q"}')
        # an sloTarget below the HPA target prices risk into extra
        # replicas, so the cost-aware and cost-blind fixed points are
        # DISTINGUISHABLE (14 vs 11) and the degradation is observable
        ha.spec.behavior.slo = SLOSpec(
            target_value=3.0, violation_cost_weight=100.0
        )
        runtime.store.create(ha)
        service = runtime.solver_service
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("cost.score", probability=1.0)
            for _ in range(30):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert registry.injected.get("cost.score", 0) >= 1, (
                "the scenario must actually have exercised cost faults"
            )
            # every tick went COST-BLIND (the unrefined reactive
            # decision, NOT a mirror-served refinement) and the loop
            # never stalled
            assert service.stats.cost_errors >= 1
            assert service.queue_depth() == 0
            assert provider.node_replicas["g"] == self.REACTIVE
            got = runtime.store.get(
                "HorizontalAutoscaler", "default", "ha"
            )
            assert got.status.desired_replicas == self.REACTIVE
            assert runtime.registry.gauge("cost", "blind_total").get(
                "ha", "default"
            ) >= 1.0
            # the repeated device faults tripped the shared FSM — the
            # cost path feeds the SAME health ladder bin-packs do
            assert service.stats.fsm_trips >= 1

            faults.uninstall()  # ---- faults clear ----
            for _ in range(5):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            # probes re-arm the device path; the refinement resumes and
            # the fleet moves to the cost-aware fixed point
            assert service.backend_health() == "healthy"
            assert service.stats.cost_dispatches >= 1
            assert provider.node_replicas["g"] == self.COST_AWARE
        finally:
            faults.uninstall()
            runtime.close()


class TestPoolGroupChaos:
    """PR 20 satellite (docs/poolgroups.md degradation contract): at
    100% `poolgroup.solve` faults the joint allocator degrades to
    INDEPENDENT per-pool cost ladders — each pool still refines, the
    declared ratio band goes advisory, the reconcile loop never blocks
    — and the repeated device failures feed the SAME backend-health FSM
    every other family rides; once faults clear, probes recover the
    device path and the fleet converges to the JOINT fixed point."""

    PREFILL = 11  # queue 41 / AverageValue target 4 -> ceil
    DECODE_INDEPENDENT = 40  # queue 160 / 4: the per-pool ladder's point
    DECODE_JOINT = 44  # min band decode:prefill >= 4:1 -> 4 * 11

    def _world(self):
        from karpenter_tpu.api.poolgroup import (
            PoolGroup,
            PoolGroupSpec,
            PoolMember,
            RatioConstraint,
        )

        clock = FakeClock()
        provider = RecordingFactory()
        provider.node_replicas["g-prefill"] = 5
        provider.node_replicas["g-decode"] = 5
        runtime = KarpenterRuntime(
            Options(poolgroups=True, solver_health_threshold=2,
                    solver_probe_interval_s=0.0),
            cloud_provider_factory=provider,
            clock=clock,
        )
        runtime.solver_service.backend = "xla"
        queue = runtime.registry.register("queue", "length")
        queue.set("qp", "default", 41.0)
        queue.set("qd", "default", 160.0)
        for name, q in (("prefill", "qp"), ("decode", "qd")):
            runtime.store.create(sng_of(f"g-{name}", replicas=5))
            ha = queue_ha(
                f"g-{name}",
                f'karpenter_queue_length{{name="{q}"}}',
                min_replicas=1, max_replicas=1000,
            )
            ha.metadata.name = name
            runtime.store.create(ha)
        # a min-band out of reach of the independent points (decode 40
        # < 4 x prefill 11), so the joint and degraded-independent
        # fixed points are DISTINGUISHABLE (44 vs 40) and the
        # degradation is observable on the wire
        runtime.store.create(PoolGroup(
            metadata=ObjectMeta(name="serving"),
            spec=PoolGroupSpec(
                pools=[PoolMember(name="prefill"),
                       PoolMember(name="decode")],
                ratios=[RatioConstraint(
                    numerator="decode", denominator="prefill",
                    min_numerator=4, min_denominator=1,
                )],
            ),
        ))
        return clock, provider, runtime

    def test_joint_faults_degrade_to_independent_then_recover(self):
        clock, provider, runtime = self._world()
        service = runtime.solver_service
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("poolgroup.solve", probability=1.0)
            for _ in range(30):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert registry.injected.get("poolgroup.solve", 0) >= 1, (
                "the scenario must actually have exercised joint faults"
            )
            # every tick served the INDEPENDENT per-pool ladders (each
            # pool at its own reactive point, the band advisory) and
            # the loop never stalled
            assert service.stats.poolgroup_independent_serves >= 1
            assert service.queue_depth() == 0
            assert provider.node_replicas["g-prefill"] == self.PREFILL
            assert (
                provider.node_replicas["g-decode"]
                == self.DECODE_INDEPENDENT
            )
            # the degradation is visible on the group: uncoordinated
            # status, ratio_ok gauge down, coordinated counter flat
            group = runtime.store.get("PoolGroup", "default", "serving")
            assert group.status.coordinated is False
            assert runtime.registry.gauge("poolgroup", "ratio_ok").get(
                "serving", "default"
            ) == 0.0
            assert not runtime.registry.gauge(
                "poolgroup", "coordinated_total"
            ).get("serving", "default")
            # the repeated device faults tripped the shared FSM — the
            # joint path feeds the SAME health ladder bin-packs do
            assert service.stats.fsm_trips >= 1

            faults.uninstall()  # ---- faults clear ----
            for _ in range(5):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            # probes re-arm the device path; the joint dispatch resumes
            # and the fleet converges to the coordinated fixed point
            assert service.backend_health() == "healthy"
            assert service.stats.poolgroup_dispatches >= 1
            assert provider.node_replicas["g-prefill"] == self.PREFILL
            assert (
                provider.node_replicas["g-decode"] == self.DECODE_JOINT
            )
            group = runtime.store.get("PoolGroup", "default", "serving")
            assert group.status.coordinated is True
            assert runtime.registry.gauge("poolgroup", "ratio_ok").get(
                "serving", "default"
            ) == 1.0
            assert runtime.registry.gauge(
                "poolgroup", "coordinated_total"
            ).get("serving", "default") >= 1.0
        finally:
            faults.uninstall()
            runtime.close()


class TestEventStormChaos:
    """ISSUE 14 acceptance: a seeded 1k-event churn storm inside one
    debounce window coalesces into a handful of event passes (not one
    per event), lands on the same fixed point as the tick-paced loop,
    keeps the self-SLO fast windows under threshold — and at 100%
    solver faults the event pass degrades through the same numpy
    ladder without ever blocking the watch callback thread."""

    FIXED_POINT = 11  # queue=41, AverageValue target=4 -> ceil(41/4)
    STORM = 1000

    def make_runtime(self, event_driven, event_thread=False):
        clock = FakeClock()
        provider = RecordingFactory()
        provider.node_replicas["g"] = 5
        runtime = KarpenterRuntime(
            Options(
                event_driven=event_driven,
                event_debounce_s=0.01,
                event_thread=event_thread,
                solver_health_threshold=2,
                solver_probe_interval_s=0.0,  # probe every dispatch
            ),
            cloud_provider_factory=provider,
            clock=clock,
        )
        # pin the XLA device path so solver faults hit a real dispatch
        runtime.solver_service.backend = "xla"
        pending_capacity_world(runtime.store)
        runtime.registry.register("queue", "length").set(
            "q", "default", 41.0
        )
        runtime.store.create(sng_of("g", replicas=5))
        runtime.store.create(
            queue_ha("g", 'karpenter_queue_length{name="q"}')
        )
        return runtime, provider, clock

    def drain(self, runtime, clock, limit=8):
        """The debounce thread's job, driven deterministically."""
        for _ in range(limit):
            if runtime.manager.dirty_count() == 0:
                return
            clock.advance(0.01)
            runtime.manager.run_event_pass()

    def settle(self, runtime, clock, n):
        for _ in range(n):
            clock.advance(61.0)
            runtime.manager.reconcile_all()

    def _storm(self, runtime):
        for i in range(self.STORM):
            runtime.store.create(Pod(
                metadata=ObjectMeta(name=f"storm-{i}"), spec=PodSpec()
            ))

    def test_storm_coalesces_matches_fixed_point_and_slo(self):
        # tick-paced comparator: same world, same storm, ticks only
        runtime, provider, clock = self.make_runtime(False)
        try:
            self._storm(runtime)
            self.settle(runtime, clock, 6)
            tick_fixed = provider.node_replicas["g"]
        finally:
            runtime.close()
        assert tick_fixed == self.FIXED_POINT

        runtime, provider, clock = self.make_runtime(True)
        manager = runtime.manager
        passes_gauge = runtime.registry.gauge(
            "runtime", "event_passes_total"
        )
        try:
            self.settle(runtime, clock, 2)
            self.drain(runtime, clock)
            before = passes_gauge.get("manager", "-") or 0.0
            self._storm(runtime)  # 1k events, ONE debounce window
            self.drain(runtime, clock)
            coalesced = (passes_gauge.get("manager", "-") or 0.0) - before
            assert 1 <= coalesced <= 4, (
                f"a 1k-event storm must coalesce into a handful of "
                f"passes (producer -> autoscaler -> node-group hops), "
                f"got {coalesced}"
            )
            self.settle(runtime, clock, 4)
            self.drain(runtime, clock)
            assert provider.node_replicas["g"] == tick_fixed, (
                "the event-driven fixed point must equal tick-paced"
            )
            # self-SLO fast windows under threshold: sub-second event
            # passes are exactly what the objective grades
            monitor = runtime.selfslo
            assert not monitor.tripped
            windows = monitor._last_eval["windows"]
            fast = [windows[w.name] for w in monitor.windows[:2]]
            assert not any(w["violating"] for w in fast), (
                f"fast burn windows must stay under threshold: {fast}"
            )
        finally:
            runtime.close()

    def test_storm_is_deterministic(self):
        """Same seed, same world -> identical pass/solve counts and
        actuation history: the storm is a replay, not a dice roll."""

        def run():
            runtime, provider, clock = self.make_runtime(True)
            try:
                self.settle(runtime, clock, 2)
                self.drain(runtime, clock)
                self._storm(runtime)
                self.drain(runtime, clock)
                self.settle(runtime, clock, 2)
                return (
                    list(provider.actuations),
                    runtime.registry.gauge(
                        "runtime", "event_passes_total"
                    ).get("manager", "-"),
                    runtime.solver_service.stats.requests,
                    provider.node_replicas["g"],
                )
            finally:
                runtime.close()

        assert run() == run()

    def test_total_solver_faults_degrade_without_blocking_watch(self):
        """100% device faults during the storm: the watch callback
        thread only marks dirty (returns in microseconds per event —
        no solve ever runs on it), the manager's REAL debounce thread
        drains the storm through the numpy ladder, and the backend FSM
        trips wholesale exactly as a tick-paced outage would."""
        import time as _t

        runtime, provider, clock = self.make_runtime(
            True, event_thread=True
        )
        service = runtime.solver_service
        try:
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan(
                "solver.dispatch", probability=1.0, code="DeviceFault"
            )
            t0 = _t.perf_counter()
            self._storm(runtime)
            callback_wall = _t.perf_counter() - t0
            assert callback_wall < 5.0, (
                f"1k watch callbacks took {callback_wall:.1f}s — the "
                f"callback thread must never run (or wait on) a solve"
            )
            # the event thread owns the passes: wait for it to drain
            # the storm through the degradation ladder (an empty dirty
            # set alone can mean a pass is still running: it swaps the
            # set out before it reconciles, under the pass lock)
            deadline = _t.monotonic() + 30.0
            while _t.monotonic() < deadline:
                if (
                    runtime.manager.dirty_count() == 0
                    and service.queue_depth() == 0
                    and not runtime.manager._pass_lock.locked()
                ):
                    break
                _t.sleep(0.02)
            assert runtime.manager.dirty_count() == 0, (
                "the debounce thread must drain the storm"
            )
            assert service.queue_depth() == 0
            assert registry.injected.get("solver.dispatch", 0) >= 1
            assert service.stats.fallbacks >= 1, (
                "event passes must degrade through the numpy ladder"
            )
            # the whole 1k-event storm coalesced into ONE failed
            # dispatch; follow-up event rounds accumulate the
            # CONSECUTIVE failures the wholesale FSM trip needs
            for n in range(4):
                runtime.store.create(Pod(
                    metadata=ObjectMeta(name=f"probe-{n}"),
                    spec=PodSpec(),
                ))
                deadline = _t.monotonic() + 10.0
                while _t.monotonic() < deadline:
                    if runtime.manager.dirty_count() == 0:
                        break
                    _t.sleep(0.02)
            assert service.backend_health() == "degraded", (
                "consecutive device faults must trip the FSM wholesale"
            )
        finally:
            faults.uninstall()
            runtime.close()


class TestSelfSLOChaos:
    """ISSUE 12 acceptance: a seeded chaos run at 100% solver faults
    drives the self-SLO fast-burn window over threshold, emits the
    `selfslo_burn` flight-recorder dump (trip-class machinery,
    observability/selfslo.py), and RECOVERS budget after faults clear —
    the control plane detecting its own degradation, not a human."""

    def test_fast_burn_trips_dumps_and_recovers(self, tmp_path):
        from karpenter_tpu.observability import (
            default_flight_recorder,
            reset_default_flight_recorder,
            set_default_flight_recorder,
        )

        saved_recorder = default_flight_recorder()
        reset_default_flight_recorder()
        clock = FakeClock()
        # plain FakeFactory: --journal-dir fences actuations with a
        # token, which the recording subclass's narrower signature
        # doesn't carry (actuation accounting isn't this scenario's
        # concern)
        provider = FakeFactory()
        provider.node_replicas["g"] = 5
        runtime = KarpenterRuntime(
            Options(
                solver_health_threshold=2,
                solver_probe_interval_s=0.0,
                journal_dir=str(tmp_path / "journal"),
            ),
            cloud_provider_factory=provider,
            clock=clock,
        )
        runtime.solver_service.backend = "xla"
        runtime.registry.register("queue", "length").set(
            "q", "default", 41.0
        )
        runtime.store.create(sng_of("g", replicas=5))
        runtime.store.create(
            queue_ha("g", 'karpenter_queue_length{name="q"}')
        )
        pending_capacity_world(runtime.store)
        monitor = runtime.selfslo
        service = runtime.solver_service

        def tick(n):
            # cluster churn (TestChaosScenario.tick): a toggling pod
            # defeats the encode memo so EVERY tick drives a real solve
            # through the service — the surface the faults poison
            for _ in range(n):
                try:
                    runtime.store.delete("Pod", "default", "churn-pod")
                except KeyError:
                    runtime.store.create(Pod(
                        metadata=ObjectMeta(name="churn-pod"),
                        spec=PodSpec(),
                    ))
                clock.advance(10.0)
                runtime.manager.reconcile_all()

        try:
            # healthy warm-up: the budget starts full
            tick(10)
            assert not monitor.tripped
            assert runtime.registry.gauge(
                "selfslo", "budget_remaining"
            ).get("5m", "-") == 1.0

            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan("solver.dispatch", probability=1.0)
            tick(40)
            assert service.backend_health() == "degraded"
            assert monitor.tripped, (
                "100% solver faults must drive the fast-burn pair "
                "over threshold"
            )
            fast = monitor._last_eval["windows"]["5m"]
            assert fast["burn_rate"] > 14.4
            assert fast["budget_remaining"] < 1.0
            burns = [
                e for e in runtime.flight_recorder.events()
                if e["kind"] == "selfslo_burn"
            ]
            assert len(burns) == 1, "one incident, one burn event"
            dumps = [
                p.name for p in (tmp_path / "journal").iterdir()
                if p.name.startswith("flightrecorder-")
                and "selfslo_burn" in p.name
            ]
            assert dumps, (
                "the selfslo_burn trip must auto-dump the ring into "
                "--journal-dir"
            )

            faults.uninstall()  # ---- faults clear ----
            tick(60)
            assert service.backend_health() == "healthy"
            assert not monitor.tripped, "the trip must re-arm"
            recovered = monitor._last_eval["windows"]["5m"]
            assert recovered["burn_rate"] < 14.4
            assert recovered["budget_remaining"] > 0.9, (
                "budget must RECOVER once bad events age out of the "
                "sliding window"
            )
            assert monitor.trips_total == 1
        finally:
            faults.uninstall()
            runtime.close()
            set_default_flight_recorder(saved_recorder)


class TestConstraintChaos:
    """PR 16 satellite: 100% faults on `constraints.mask` must NEVER
    block the signal — every tick degrades to the unconstrained-but-
    feasible wire with the fallback counted and the breaker FSM fed
    (closed -> open -> short-circuit), and clearing the faults recovers
    the constrained fixed point."""

    def make_runtime(self):
        from karpenter_tpu.api.core import (
            Container, RESERVATION_LABEL, ZONE_LABEL,
        )
        from karpenter_tpu.constraints import ConstraintGroup, SpreadSpec

        clock = FakeClock()
        runtime = KarpenterRuntime(
            Options(),
            cloud_provider_factory=FakeFactory(),
            clock=clock,
        )
        store = runtime.store
        for zone in ("z1", "z2"):
            store.create(Node(
                metadata=ObjectMeta(
                    name=f"{zone}-n0",
                    labels={"pool": "serving", ZONE_LABEL: zone},
                ),
                spec=NodeSpec(),
                status=NodeStatus(
                    allocatable=resource_list(
                        cpu="8", memory="32Gi", pods="32"
                    ),
                    conditions=[NodeCondition("Ready", "True")],
                ),
            ))
        store.create(Node(
            metadata=ObjectMeta(
                name="reserved-0",
                labels={"pool": "reserved", RESERVATION_LABEL: "gold"},
            ),
            spec=NodeSpec(),
            status=NodeStatus(
                allocatable=resource_list(
                    cpu="8", memory="32Gi", pods="32"
                ),
                conditions=[NodeCondition("Ready", "True")],
            ),
        ))
        for zone, cons in (("z1", True), ("z2", False)):
            store.create(MetricsProducer(
                metadata=ObjectMeta(name=f"serving-{zone}"),
                spec=MetricsProducerSpec(
                    pending_capacity=PendingCapacitySpec(
                        node_selector={
                            "pool": "serving", ZONE_LABEL: zone
                        },
                        constraints=[
                            ConstraintGroup(
                                name="web",
                                pod_selector={"app": "web"},
                                spread=SpreadSpec(),
                            ),
                            ConstraintGroup(
                                name="gold",
                                pod_selector={"tier": "gold"},
                                reservation="gold",
                            ),
                        ] if cons else [],
                    )
                ),
            ))
        store.create(MetricsProducer(
            metadata=ObjectMeta(name="serving-reserved"),
            spec=MetricsProducerSpec(
                pending_capacity=PendingCapacitySpec(
                    node_selector={"pool": "reserved"},
                )
            ),
        ))
        for i in range(4):
            store.create(Pod(
                metadata=ObjectMeta(
                    name=f"web-{i}", labels={"app": "web"}
                ),
                spec=PodSpec(node_name="", containers=[Container(
                    requests=resource_list(cpu="1", memory="1Gi")
                )]),
            ))
        store.create(Pod(
            metadata=ObjectMeta(name="gold-0", labels={"tier": "gold"}),
            spec=PodSpec(node_name="", containers=[Container(
                requests=resource_list(cpu="1", memory="1Gi")
            )]),
        ))
        return runtime, clock

    def tick(self, runtime, clock, n=1):
        """Churned ticks: the producer memo rightly short-circuits an
        unchanged cluster and a memo hit never reaches the encoder's
        fault point, so each tick toggles a pod."""
        for _ in range(n):
            try:
                runtime.store.delete("Pod", "default", "churn-pod")
            except KeyError:
                runtime.store.create(Pod(
                    metadata=ObjectMeta(name="churn-pod"),
                    spec=PodSpec(),
                ))
            clock.advance(61.0)
            runtime.manager.reconcile_all()

    def _pending(self, runtime, name):
        status = runtime.store.get(
            "MetricsProducer", "default", name
        ).status.pending_capacity
        return status.pending_pods if status else -1

    def test_mask_faults_never_block_then_recover(self):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            encoder as E,
        )
        from karpenter_tpu.resilience import CircuitBreaker

        runtime, clock = self.make_runtime()
        saved_breaker = E._constraint_breaker
        E.reset_constraint_state()
        # the module breaker's reset window runs on REAL monotonic
        # time; pin it to the scenario clock so the open window (and
        # the recovery probe) replay deterministically
        E._constraint_breaker = CircuitBreaker(
            failure_threshold=3, reset_s=1000.0, clock=clock
        )
        try:
            # ---- the constrained fixed point ----
            self.tick(runtime, clock, 2)
            stats = E.constraint_stats
            assert stats["compiles"] >= 1
            assert stats["fallbacks"] == 0
            assert not stats["degraded"]
            skew = runtime.registry.gauge(
                "constraints", "spread_skew"
            ).get("web", "-")
            assert skew == 0.0  # 4 web pods spread 2/2
            assert runtime.registry.gauge(
                "constraints", "reservation_fill"
            ).get("gold", "-") == 1.0
            assert self._pending(runtime, "serving-reserved") == 1
            fixed_point = {
                name: self._pending(runtime, name)
                for name in ("serving-z1", "serving-z2",
                             "serving-reserved")
            }

            # ---- 100% mask faults ----
            registry = faults.install(FaultRegistry(seed=CHAOS_SEED))
            registry.plan(
                "constraints.mask", mode="error", probability=1.0
            )
            self.tick(runtime, clock, 6)
            stats = E.constraint_stats
            assert stats["degraded"]
            assert stats["fallbacks"] >= 6, (
                "every churned tick must fall back, not block"
            )
            # the breaker FSM was fed: 3 failures trip it open and the
            # remaining ticks short-circuit without re-probing the
            # faulty compile path
            assert stats["short_circuits"] >= 1
            assert runtime.registry.gauge(
                "constraints", "breaker_state"
            ).get("-", "-") == 1.0
            assert runtime.registry.gauge(
                "constraints", "fallback_total"
            ).get("-", "-") == float(stats["fallbacks"])
            # never-block: the unconstrained-but-feasible wire keeps
            # publishing a live signal for every producer
            total = sum(
                self._pending(runtime, name)
                for name in ("serving-z1", "serving-z2",
                             "serving-reserved")
            )
            assert total >= 5, "all pods still placed somewhere"
            assert self._pending(runtime, "serving-z1") >= 0

            # ---- faults clear ----
            faults.uninstall()
            clock.advance(1000.0)  # past the breaker's open window
            self.tick(runtime, clock, 2)
            stats = E.constraint_stats
            assert not stats["degraded"], (
                "the degraded-epoch fingerprint must retry the compile "
                "and converge back"
            )
            assert runtime.registry.gauge(
                "constraints", "breaker_state"
            ).get("-", "-") == 0.0
            recovered = {
                name: self._pending(runtime, name)
                for name in ("serving-z1", "serving-z2",
                             "serving-reserved")
            }
            assert recovered == fixed_point, (
                "clearing faults must restore the constrained verdicts"
            )
            assert runtime.registry.gauge(
                "constraints", "reservation_fill"
            ).get("gold", "-") == 1.0
        finally:
            faults.uninstall()
            E._constraint_breaker = saved_breaker
            E.reset_constraint_state()
            runtime.close()
