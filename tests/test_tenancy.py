"""Multi-tenant control plane tests (docs/multitenancy.md).

The load-bearing pins:

  * PARITY — a cross-tenant concatenated dispatch is bit-identical to N
    independent per-tenant dispatches on every output field, for the
    decide, cost, and forecast families, on BOTH the device (xla) and
    numpy paths (the kernels are row-independent; the concat/scatter
    helpers must keep them that way).
  * ISOLATION — a tenant at 100% injected faults degrades ALONE: its
    rows serve from the bit-identical numpy mirror, its breaker opens,
    and every tenant's lockstep fixed point (including the faulted
    one's, since the mirror is bit-identical) equals the no-fault run.
  * FAIRNESS — deficit-weighted admission: oversized tenants dispatch
    alone, deferred tenants carry credit, shares converge to weights.
  * the per-tenant registry: stack namespacing, per-tenant fencing
    independence, and karpenter_tenant_* retirement on deletion;
  * the pluggable pricing feed (--pricing-file): mtime reload,
    never-block on a broken file, per-tenant sources via the registry;
  * per-metric SLO targets (spec.behavior.slo.metrics) feeding
    worst-case risk;
  * the non-slow batched-vs-sequential regression guard.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np
import pytest

from karpenter_tpu.faults import injected_faults
from karpenter_tpu.forecast import models as FM
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.ops import cost as CK
from karpenter_tpu.ops import decision as D
from karpenter_tpu.simulate import (
    multitenant_cost_inputs,
    multitenant_fleet_inputs,
    simulate_multitenant,
)
from karpenter_tpu.solver import SolverService
from karpenter_tpu.tenancy import (
    MultiTenantScheduler,
    TenantBreakerBoard,
    TenantRegistry,
    TenantSpec,
    WeightedAdmission,
    load_tenant_config,
)
from karpenter_tpu.tenancy.scheduler import (
    concat_cost_inputs,
    concat_decision_inputs,
    slice_cost_outputs,
)

from test_observability import _lint_exposition


def random_decide_inputs(
    seed: int, n: int = 6, m: int = 2, k: int = 1,
    now: float = 1000.0, forecast: bool = False,
) -> D.DecisionInputs:
    """A random one-tenant fleet: mixed target types, some invalid
    metrics, random windows/policies — the adversarial shape for the
    row-independence claim."""
    rng = np.random.RandomState(seed)
    spec = rng.randint(0, 50, n).astype(np.int32)
    d = dict(
        metric_value=rng.uniform(0, 200, (n, m)).astype(np.float32),
        target_value=rng.choice([0.0, 2.0, 8.0], (n, m)).astype(
            np.float32
        ),
        target_type=rng.randint(0, 4, (n, m)).astype(np.int32),
        metric_valid=rng.rand(n, m) > 0.2,
        spec_replicas=spec,
        status_replicas=np.clip(
            spec + rng.randint(-2, 3, n), 0, None
        ).astype(np.int32),
        min_replicas=rng.randint(0, 3, n).astype(np.int32),
        max_replicas=(spec + rng.randint(1, 100, n)).astype(np.int32),
        up_window=rng.choice([0, 60], n).astype(np.int32),
        down_window=rng.choice([0, 300], n).astype(np.int32),
        up_policy=rng.randint(0, 3, n).astype(np.int32),
        down_policy=rng.randint(0, 3, n).astype(np.int32),
        last_scale_time=rng.uniform(0, 900, n).astype(np.float32),
        has_last_scale=rng.rand(n) > 0.5,
        now=np.float32(now),
        up_ptype=rng.randint(0, 2, (n, k)).astype(np.int32),
        up_pvalue=rng.randint(1, 20, (n, k)).astype(np.int32),
        up_pperiod=rng.randint(1, 600, (n, k)).astype(np.int32),
        up_pvalid=rng.rand(n, k) > 0.5,
        down_ptype=rng.randint(0, 2, (n, k)).astype(np.int32),
        down_pvalue=rng.randint(1, 20, (n, k)).astype(np.int32),
        down_pperiod=rng.randint(1, 600, (n, k)).astype(np.int32),
        down_pvalid=rng.rand(n, k) > 0.5,
    )
    if forecast:
        d["forecast_value"] = rng.uniform(0, 300, (n, m)).astype(
            np.float32
        )
        d["forecast_valid"] = rng.rand(n, m) > 0.3
    return D.DecisionInputs(**d)


def random_cost_inputs(seed: int, n: int = 6, m: int = 2) -> CK.CostInputs:
    rng = np.random.RandomState(seed)
    base = rng.randint(0, 100, n).astype(np.int32)
    return CK.CostInputs(
        base_desired=base,
        min_replicas=rng.randint(0, 5, n).astype(np.int32),
        max_replicas=(base + rng.randint(0, 300, n)).astype(np.int32),
        unit_cost=rng.choice([0.0, 0.07, 1.7, 12.5], n).astype(
            np.float32
        ),
        slo_weight=rng.choice([0.0, 1.0, 333.3], n).astype(np.float32),
        max_hourly_cost=rng.choice([0.0, 2.0, 55.5], n).astype(
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


def random_forecast_inputs(seed: int, s: int = 4, t: int = 20):
    rng = np.random.RandomState(seed)
    return FM.ForecastInputs(
        values=rng.uniform(0, 100, (s, t)).astype(np.float32),
        valid=rng.rand(s, t) > 0.1,
        times=(
            -np.arange(t, dtype=np.float32)[::-1][None, :].repeat(s, 0)
            * 10.0
        ),
        weights=rng.uniform(0.1, 1.0, (s, t)).astype(np.float32),
        horizon=np.full(s, 60.0, np.float32),
        step_s=np.full(s, 10.0, np.float32),
        model=rng.randint(0, 2, s).astype(np.int32),
        season=np.zeros(s, np.int32),
        alpha=np.full(s, 0.5, np.float32),
        beta=np.full(s, 0.1, np.float32),
        gamma=np.full(s, 0.3, np.float32),
    )


def make_world(n_tenants: int = 4, weights=None, **scheduler_kw):
    """(service, registry, scheduler) with gauges in a fresh registry."""
    service = SolverService(registry=GaugeRegistry())
    metrics_registry = GaugeRegistry()
    registry = TenantRegistry(
        service=service, registry=metrics_registry,
        specs=[
            TenantSpec(
                id=f"t{i}",
                weight=(weights[i] if weights else 1.0),
            )
            for i in range(n_tenants)
        ],
    )
    scheduler = MultiTenantScheduler(registry, service, **scheduler_kw)
    return service, registry, scheduler


def assert_outputs_equal(kind, got, want, context=""):
    for f in dataclasses.fields(kind):
        a = np.asarray(getattr(got, f.name))
        b = np.asarray(getattr(want, f.name))
        assert np.array_equal(a, b), f"{context}.{f.name}: {a} != {b}"


class TestConcatParity:
    """The tentpole pin: concatenated slices == independent dispatches,
    bit for bit, device and numpy paths."""

    @pytest.mark.parametrize("backend", ["xla", "numpy"])
    def test_cost_concat_matches_independent(self, backend):
        service, _reg, scheduler = make_world(5)
        try:
            batch = {
                f"t{i}": random_cost_inputs(i, n=3 + i, m=1 + i % 3)
                for i in range(5)
            }
            out = scheduler.cost_all(batch, backend=backend)
            for tid, inputs in batch.items():
                indep = service.cost(inputs, backend=backend)
                assert_outputs_equal(
                    CK.CostOutputs, out[tid], indep, f"{backend}:{tid}"
                )
        finally:
            service.close()

    def test_cost_concat_matches_numpy_mirror_directly(self):
        """The host-path parity pin without the service in the loop:
        concat -> cost_numpy -> slice == per-tenant cost_numpy."""
        batch = [random_cost_inputs(40 + i, n=4, m=2) for i in range(4)]
        host = CK.cost_numpy(concat_cost_inputs(batch))
        offset = 0
        for i, inputs in enumerate(batch):
            n = int(inputs.base_desired.shape[0])
            mine = slice_cost_outputs(host, offset, offset + n)
            offset += n
            assert_outputs_equal(
                CK.CostOutputs, mine, CK.cost_numpy(inputs), f"t{i}"
            )

    def test_decide_concat_matches_independent(self):
        service, _reg, scheduler = make_world(6)
        try:
            batch = {
                f"t{i}": random_decide_inputs(
                    i, n=3 + i, m=1 + i % 3, k=1 + i % 2,
                    forecast=(i % 2 == 0),
                )
                for i in range(6)
            }
            out = scheduler.decide_all(batch)
            for tid, inputs in batch.items():
                assert_outputs_equal(
                    D.DecisionOutputs, out[tid], service.decide(inputs),
                    tid,
                )
        finally:
            service.close()

    def test_decide_groups_by_now_epoch(self):
        """Tenants at different now epochs must not concatenate (the
        stabilization math is epoch-relative); each group still comes
        back bit-identical to its independent dispatch."""
        service, _reg, scheduler = make_world(4)
        try:
            batch = {
                f"t{i}": random_decide_inputs(
                    i, now=1000.0 + 500.0 * (i % 2)
                )
                for i in range(4)
            }
            out = scheduler.decide_all(batch)
            assert scheduler.stats.decide_dispatches == 2
            for tid, inputs in batch.items():
                assert_outputs_equal(
                    D.DecisionOutputs, out[tid], service.decide(inputs),
                    tid,
                )
        finally:
            service.close()

    def test_concat_mixed_now_raises(self):
        with pytest.raises(ValueError):
            concat_decision_inputs(
                [
                    random_decide_inputs(0, now=1.0),
                    random_decide_inputs(1, now=2.0),
                ]
            )

    @pytest.mark.parametrize("backend", ["xla", "numpy"])
    def test_forecast_concat_matches_independent(self, backend):
        service, _reg, scheduler = make_world(3)
        try:
            batch = {
                f"t{i}": random_forecast_inputs(i, s=2 + i, t=12 + 4 * i)
                for i in range(3)
            }
            out = scheduler.forecast_all(batch, backend=backend)
            for tid, inputs in batch.items():
                indep = service.forecast(inputs, backend=backend)
                assert_outputs_equal(
                    FM.ForecastOutputs, out[tid], indep,
                    f"{backend}:{tid}",
                )
        finally:
            service.close()

    def test_solve_all_rides_the_coalescing_queue(self):
        """Cross-tenant bin-packs answer through the existing queue and
        match direct numpy solves (CPU resolution) per tenant."""
        from karpenter_tpu.ops.binpack import BinPackInputs
        from karpenter_tpu.ops.numpy_binpack import binpack_numpy

        rng = np.random.RandomState(0)
        service, _reg, scheduler = make_world(3)
        try:
            batch = {}
            for i in range(3):
                batch[f"t{i}"] = BinPackInputs(
                    pod_requests=rng.uniform(
                        0.1, 2.0, (8, 2)
                    ).astype(np.float32),
                    pod_valid=np.ones(8, bool),
                    pod_intolerant=np.zeros((8, 1), bool),
                    pod_required=np.zeros((8, 1), bool),
                    group_allocatable=rng.uniform(
                        4.0, 16.0, (3, 2)
                    ).astype(np.float32),
                    group_taints=np.zeros((3, 1), bool),
                    group_labels=np.zeros((3, 1), bool),
                )
            out = scheduler.solve_all(batch, buckets=8)
            assert scheduler.stats.solve_requests == 3
            for tid, inputs in batch.items():
                want = binpack_numpy(inputs, buckets=8)
                np.testing.assert_array_equal(
                    np.asarray(out[tid].assigned_count),
                    np.asarray(want.assigned_count),
                    err_msg=tid,
                )
        finally:
            service.close()


class TestIsolationChaos:
    """The chaos pin: one tenant at 100% faults degrades ALONE."""

    def test_faulted_tenant_mirror_served_others_on_device(self):
        service, _reg, scheduler = make_world(
            4, breaker_threshold=2, breaker_reset_s=3600.0
        )
        try:
            batch = {
                f"t{i}": random_cost_inputs(20 + i) for i in range(4)
            }
            with injected_faults(seed=7) as faults:
                faults.plan(
                    "tenancy.gather.t2", mode="error", probability=1.0
                )
                for _ in range(4):
                    out = scheduler.cost_all(batch, backend="xla")
                    # the faulted tenant still answers — from the
                    # bit-identical mirror
                    assert_outputs_equal(
                        CK.CostOutputs, out["t2"],
                        CK.cost_numpy(batch["t2"]), "t2",
                    )
                    # healthy tenants keep their device answers
                    for tid in ("t0", "t1", "t3"):
                        assert_outputs_equal(
                            CK.CostOutputs, out[tid],
                            service.cost(batch[tid], backend="xla"),
                            tid,
                        )
            assert scheduler.breakers.is_open("t2")
            assert scheduler.stats.breaker_trips == 1
            assert scheduler.stats.mirror_served >= 3
            # breaker open: later rounds skip the fault point entirely
            # (no probe within the reset window) and keep mirror-serving
            assert not scheduler.breakers.allow("t2")
        finally:
            service.close()

    def test_lockstep_fixed_points_hold_under_one_tenant_chaos(self):
        """Seeded end-to-end chaos: replay the SAME lockstep world with
        and without one tenant at 100% faults. Because the mirror is
        bit-identical, EVERY tenant's trajectory — the faulted one
        included — must match the no-fault run exactly, and the healthy
        tenants must keep riding shared dispatches."""

        def replay(fault_tenant=None):
            service, _reg, scheduler = make_world(
                4, breaker_threshold=2, breaker_reset_s=3600.0
            )
            try:
                replicas = {
                    f"t{i}": np.full(3, 2, np.int32) for i in range(4)
                }
                ctx = (
                    injected_faults(seed=11)
                    if fault_tenant
                    else _null_context()
                )
                with ctx as faults:
                    if fault_tenant:
                        faults.plan(
                            f"tenancy.gather.{fault_tenant}",
                            mode="error", probability=1.0,
                        )
                    for tick in range(6):
                        now = 1000.0 + tick * 10.0
                        batch = {
                            tid: multitenant_fleet_inputs(
                                i, 3, 2, 5, tick, replicas[tid], now
                            )
                            for i, tid in enumerate(sorted(replicas))
                        }
                        decided = scheduler.decide_all(batch)
                        refined = scheduler.cost_all(
                            {
                                tid: multitenant_cost_inputs(
                                    batch[tid], decided[tid].desired
                                )
                                for tid in decided
                            },
                            backend="xla",
                        )
                        for tid in refined:
                            replicas[tid] = np.asarray(
                                refined[tid].desired, np.int32
                            )
                return {
                    tid: r.copy() for tid, r in replicas.items()
                }, scheduler.stats
            finally:
                service.close()

        clean, _clean_stats = replay()
        chaotic, stats = replay(fault_tenant="t1")
        for tid in clean:
            np.testing.assert_array_equal(
                clean[tid], chaotic[tid], err_msg=tid
            )
        assert stats.breaker_trips >= 1
        assert stats.mirror_served >= 1
        # healthy tenants stayed on shared dispatches every tick
        assert stats.cost_dispatches >= 6

    def test_shared_dispatch_failure_isolates_per_tenant(self):
        """A failure of the SHARED dispatch itself (cost.score fault:
        the whole concatenated program dies) falls back to per-tenant
        isolation — every tenant still answers bit-identically via its
        mirror, and nothing raises."""
        service, _reg, scheduler = make_world(3)
        try:
            batch = {
                f"t{i}": random_cost_inputs(60 + i) for i in range(3)
            }
            with injected_faults(seed=3) as faults:
                faults.plan(
                    "cost.score", mode="error", probability=1.0
                )
                out = scheduler.cost_all(batch, backend="xla")
            for tid, inputs in batch.items():
                assert_outputs_equal(
                    CK.CostOutputs, out[tid], CK.cost_numpy(inputs), tid
                )
            assert scheduler.stats.mirror_served == 3
        finally:
            service.close()


    def test_probe_runs_isolated_and_recovery_rejoins_shared(self):
        """An open breaker's probe must NOT re-enter the shared batch
        (a still-poisoned tenant would re-break every healthy tenant's
        round once per window): the probe is an isolated dispatch, and
        only a SUCCESSFUL probe rejoins the tenant to the shared
        concatenation on the following round."""
        clock = {"now": 0.0}
        service, _reg, scheduler = make_world(
            3, breaker_threshold=2, breaker_reset_s=10.0,
            clock=lambda: clock["now"],
        )
        try:
            batch = {
                f"t{i}": random_cost_inputs(80 + i) for i in range(3)
            }
            with injected_faults(seed=5) as faults:
                faults.plan(
                    "tenancy.gather.t1", mode="error", probability=1.0
                )
                scheduler.cost_all(batch, backend="xla")
                scheduler.cost_all(batch, backend="xla")
            assert scheduler.breakers.is_open("t1")
            # fault cleared; probe window elapses
            clock["now"] = 11.0
            shared_before = scheduler.stats.cost_dispatches
            out = scheduler.cost_all(batch, backend="xla")
            # the probe round: t1 answered ISOLATED (correctly), the
            # other two still rode a shared dispatch
            assert scheduler.stats.probes == 1
            assert scheduler.stats.cost_dispatches == shared_before + 1
            assert_outputs_equal(
                CK.CostOutputs, out["t1"],
                service.cost(batch["t1"], backend="xla"), "t1",
            )
            assert not scheduler.breakers.is_open("t1")
            # next round: t1 is back in the shared concatenation
            iso_before = scheduler.stats.isolated_dispatches
            scheduler.cost_all(batch, backend="xla")
            assert scheduler.stats.isolated_dispatches == iso_before
        finally:
            service.close()

    def test_never_an_exception_result_even_when_decide_dies(self):
        """The never-block floor: with the decide seam itself raising
        (shared AND isolated dispatches fail), every tenant still gets
        a REAL DecisionOutputs — hold-current-replicas — never an
        exception object the caller would trip over."""
        from karpenter_tpu.tenancy.scheduler import decide_hold

        def boom(_inputs):
            raise RuntimeError("decider dead")

        service = SolverService(registry=GaugeRegistry(), decider=boom)
        registry = TenantRegistry(
            service=service, registry=GaugeRegistry(),
            specs=[TenantSpec(id="t0"), TenantSpec(id="t1")],
        )
        scheduler = MultiTenantScheduler(registry, service)
        try:
            batch = {
                "t0": random_decide_inputs(0),
                "t1": random_decide_inputs(1),
            }
            out = scheduler.decide_all(batch)
            for tid, inputs in batch.items():
                assert_outputs_equal(
                    D.DecisionOutputs, out[tid], decide_hold(inputs),
                    tid,
                )
            assert scheduler.stats.tenant_failures >= 2
        finally:
            service.close()


class _null_context:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


class TestFairness:
    def test_small_fleet_rides_one_round(self):
        admission = WeightedAdmission(budget_rows=100)
        schedule = admission.rounds(
            {"a": 10, "b": 20, "c": 30}, {"a": 1, "b": 1, "c": 1}
        )
        assert len(schedule) == 1
        assert sorted(schedule[0]) == ["a", "b", "c"]

    def test_noisy_tenant_cannot_starve_the_queue(self):
        """A tenant demanding 10x the budget dispatches ALONE; the
        small tenants ride their own round rather than waiting behind
        it forever."""
        admission = WeightedAdmission(budget_rows=64)
        schedule = admission.rounds(
            {"noisy": 640, "a": 8, "b": 8},
            {"noisy": 1, "a": 1, "b": 1},
        )
        assert len(schedule) == 2
        flat = [t for r in schedule for t in r]
        assert sorted(flat) == ["a", "b", "noisy"]
        lone = [r for r in schedule if r == ["noisy"]]
        assert lone, f"noisy tenant should dispatch alone: {schedule}"

    def test_weighted_shares_converge(self):
        """Over many rounds, admitted-first counts track weights: the
        weight-3 tenant reaches the head of the schedule about three
        times as often as the weight-1 tenant."""
        admission = WeightedAdmission(budget_rows=32)
        first = {"heavy": 0, "light": 0}
        for _ in range(60):
            # both want more than one budget together: one defers
            schedule = admission.rounds(
                {"heavy": 24, "light": 24},
                {"heavy": 3.0, "light": 1.0},
            )
            first[schedule[0][0]] += 1
        assert first["heavy"] > first["light"] * 2, first

    def test_every_round_admits_at_least_one(self):
        admission = WeightedAdmission(budget_rows=4)
        schedule = admission.rounds(
            {"big1": 100, "big2": 100}, {"big1": 1, "big2": 1}
        )
        assert len(schedule) == 2
        assert all(len(r) == 1 for r in schedule)


class TestTenantRegistry:
    def test_namespaced_stacks_are_independent(self):
        service = SolverService(registry=GaugeRegistry())
        try:
            registry = TenantRegistry(
                service=service, registry=GaugeRegistry(),
                specs=[TenantSpec(id="a"), TenantSpec(id="b")],
            )
            a, b = registry.get("a"), registry.get("b")
            assert a.store is not b.store
            assert a.forecaster is not b.forecaster
            assert a.cost_engine is not b.cost_engine
            # per-tenant history is namespaced: feeding a's forecaster
            # leaves b's empty
            a.forecaster.history.append(("q", "x"), 1.0, 5.0)
            assert b.forecaster.history.count(("q", "x")) == 0
        finally:
            service.close()

    def test_remove_retires_tenant_gauge_series(self):
        service = SolverService(registry=GaugeRegistry())
        metrics_registry = GaugeRegistry()
        try:
            registry = TenantRegistry(
                service=service, registry=metrics_registry,
                specs=[TenantSpec(id="a"), TenantSpec(id="b")],
            )
            scheduler = MultiTenantScheduler(registry, service)
            batch = {
                "a": random_cost_inputs(1),
                "b": random_cost_inputs(2),
            }
            scheduler.cost_all(batch, backend="xla")
            text = metrics_registry.expose_text()
            assert 'karpenter_tenant_backlog_rows{name="a"' in text
            registry.remove("a")
            text = metrics_registry.expose_text()
            assert 'name="a"' not in text, (
                "deleted tenant's series must retire"
            )
            assert 'karpenter_tenant_backlog_rows{name="b"' in text
            # breaker + admission credit forgotten too
            assert not scheduler.breakers.is_open("a")
            assert registry.metrics.active.get("-", "-") == 1.0
        finally:
            service.close()

    def test_per_tenant_fencing_is_independent(self, tmp_path):
        """Two tenants' recovery state lives in disjoint journal dirs:
        re-claiming tenant a's fence bumps a's generation only."""
        from karpenter_tpu.recovery.fence import read_generation

        service = SolverService(registry=GaugeRegistry())
        try:
            registry = TenantRegistry(
                service=service, registry=GaugeRegistry(),
                journal_dir=str(tmp_path),
                specs=[TenantSpec(id="a"), TenantSpec(id="b")],
            )
            dir_a = registry.journal_dir_for("a")
            dir_b = registry.journal_dir_for("b")
            assert dir_a != dir_b and os.path.isdir(dir_a)
            rec_a = registry.get("a").recovery()
            rec_b = registry.get("b").recovery()
            assert rec_a is not None and rec_b is not None
            gen_b = rec_b.fence.generation
            rec_a.close()
            registry.get("a")._recovery = None
            rec_a2 = registry.get("a").recovery()  # a "restart" of a
            assert rec_a2.fence.generation > 1
            # b's durable generation is untouched by a's restart
            assert read_generation(dir_b) == gen_b
        finally:
            registry.close()
            service.close()

    def test_load_tenant_config_shapes_and_errors(self, tmp_path):
        path = tmp_path / "tenants.json"
        path.write_text(json.dumps({
            "tenants": [
                {"id": "prod", "weight": 3.0},
                {"id": "dev", "pricingFile": "x.json"},
            ]
        }))
        specs = load_tenant_config(str(path))
        assert [s.id for s in specs] == ["prod", "dev"]
        assert specs[0].weight == 3.0
        assert specs[1].pricing_file == "x.json"
        path.write_text(json.dumps([{"id": "a"}, {"id": "a"}]))
        with pytest.raises(ValueError, match="duplicate"):
            load_tenant_config(str(path))
        path.write_text(json.dumps([{"id": "../evil"}]))
        with pytest.raises(ValueError, match="path-safe"):
            load_tenant_config(str(path))
        path.write_text(json.dumps([{"id": "a", "weight": 0}]))
        with pytest.raises(ValueError, match="weight"):
            load_tenant_config(str(path))

    def test_tenant_gauges_pass_exposition_lint(self):
        service = SolverService(registry=GaugeRegistry())
        metrics_registry = GaugeRegistry()
        try:
            registry = TenantRegistry(
                service=service, registry=metrics_registry,
                specs=[TenantSpec(id="t0"), TenantSpec(id="t1")],
            )
            scheduler = MultiTenantScheduler(registry, service)
            with injected_faults(seed=1) as faults:
                faults.plan(
                    "tenancy.gather.t1", mode="error", probability=1.0
                )
                for _ in range(4):
                    scheduler.cost_all(
                        {
                            "t0": random_cost_inputs(0),
                            "t1": random_cost_inputs(1),
                        },
                        backend="xla",
                    )
            typed, series = _lint_exposition(
                metrics_registry.expose_text()
            )
            for family in (
                "karpenter_tenant_active",
                "karpenter_tenant_weight",
                "karpenter_tenant_degraded",
                "karpenter_tenant_backlog_rows",
                "karpenter_tenant_admission_rounds",
                "karpenter_tenant_decisions_total",
                "karpenter_tenant_dispatches_total",
                "karpenter_tenant_mirror_served_total",
                "karpenter_tenant_fallback_served_total",
                "karpenter_tenant_breaker_trips_total",
                "karpenter_tenant_deferrals_total",
            ):
                assert family in typed, family
            assert typed["karpenter_tenant_breaker_trips_total"] == (
                "counter"
            )
        finally:
            service.close()


class TestBreakerBoard:
    def test_trip_probe_recover(self):
        clock = {"now": 0.0}
        board = TenantBreakerBoard(
            threshold=2, reset_s=10.0, clock=lambda: clock["now"]
        )
        assert board.allow("t")
        assert not board.record_failure("t")
        assert board.record_failure("t")  # trips
        assert board.is_open("t")
        assert not board.allow("t")  # inside the open window
        clock["now"] = 11.0
        assert board.allow("t")  # the probe
        assert not board.allow("t")  # next probe already scheduled
        assert board.record_success("t")  # probe success closes
        assert not board.is_open("t")
        assert board.allow("t")


class TestPricingFeed:
    def test_file_source_reads_and_reloads_on_mtime(self, tmp_path):
        from karpenter_tpu.cost import CostModel, FilePricingSource

        path = tmp_path / "prices.json"
        path.write_text(json.dumps({"m5.large": 0.5}))
        source = FilePricingSource(str(path))
        model = CostModel(pricing=source)
        assert model.on_demand("m5.large") == 0.5
        # catalog fallback for types the feed doesn't carry
        assert model.on_demand("g5.xlarge") == pytest.approx(1.006)
        path.write_text(
            json.dumps(
                {"catalog": {"m5.large": 0.75}, "spotMultiplier": 0.2}
            )
        )
        os.utime(path, (time.time() + 5, time.time() + 5))
        source._next_check = 0.0  # skip the 1s mtime-poll throttle
        assert model.on_demand("m5.large") == 0.75
        assert model.effective_spot_multiplier() == 0.2

    def test_broken_reload_keeps_last_good_catalog(self, tmp_path):
        from karpenter_tpu.cost import FilePricingSource

        path = tmp_path / "prices.json"
        path.write_text(json.dumps({"m5.large": 0.5}))
        source = FilePricingSource(str(path))
        assert source.price("m5.large") == 0.5
        path.write_text("{not json at all")
        os.utime(path, (time.time() + 5, time.time() + 5))
        source._next_check = 0.0  # skip the 1s mtime-poll throttle
        assert source.price("m5.large") == 0.5  # never-block
        path.unlink()
        source._next_check = 0.0
        assert source.price("m5.large") == 0.5  # vanished file too

    def test_first_load_fails_loudly(self, tmp_path):
        from karpenter_tpu.cost import FilePricingSource

        with pytest.raises(ValueError):
            FilePricingSource(str(tmp_path / "missing.json"))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"m5.large": -1}))
        with pytest.raises(ValueError, match="negative"):
            FilePricingSource(str(bad))

    def test_per_tenant_pricing_via_registry(self, tmp_path):
        cheap = tmp_path / "cheap.json"
        cheap.write_text(json.dumps({"m5.large": 0.01}))
        dear = tmp_path / "dear.json"
        dear.write_text(json.dumps({"m5.large": 9.99}))
        service = SolverService(registry=GaugeRegistry())
        try:
            registry = TenantRegistry(
                service=service,
                specs=[
                    TenantSpec(id="a", pricing_file=str(cheap)),
                    TenantSpec(id="b", pricing_file=str(dear)),
                ],
            )
            assert registry.get("a").cost_model.on_demand(
                "m5.large"
            ) == 0.01
            assert registry.get("b").cost_model.on_demand(
                "m5.large"
            ) == 9.99
        finally:
            service.close()


class TestPerMetricSLO:
    def test_target_for_fallback_chain(self):
        from karpenter_tpu.api.horizontalautoscaler import (
            SLOMetricTarget,
            SLOSpec,
        )

        slo = SLOSpec(
            target_value=4.0,
            metrics=[
                SLOMetricTarget(target_value=10.0),
                SLOMetricTarget(),  # falls back to the spec-wide value
            ],
        )
        assert slo.target_for(0) == 10.0
        assert slo.target_for(1) == 4.0
        assert slo.target_for(2) == 4.0  # beyond the list
        assert SLOSpec().target_for(0) is None

    def test_validation_rejects_nonpositive_per_metric_target(self):
        from karpenter_tpu.api.horizontalautoscaler import (
            SLOMetricTarget,
            SLOSpec,
        )

        with pytest.raises(ValueError):
            SLOSpec(
                metrics=[SLOMetricTarget(target_value=0.0)]
            ).validate()

    def test_per_metric_targets_serialize_round_trip(self):
        from karpenter_tpu.api.horizontalautoscaler import (
            SLOMetricTarget,
            SLOSpec,
        )
        from karpenter_tpu.api.serialization import from_dict, to_dict

        slo = SLOSpec(
            target_value=4.0,
            violation_cost_weight=10.0,
            metrics=[SLOMetricTarget(target_value=7.5)],
        )
        doc = to_dict(slo)
        assert doc["metrics"][0]["targetValue"] == 7.5
        back = from_dict(SLOSpec, doc)
        assert back.metrics[0].target_value == 7.5

    def test_worst_case_risk_across_per_metric_targets(self):
        """A tight per-metric target on metric 1 must dominate the risk
        even when metric 0's shared target is comfortable — the kernel
        maxes over metrics, the engine feeds per-metric capacities."""
        from karpenter_tpu.api.core import ObjectMeta
        from karpenter_tpu.api.horizontalautoscaler import (
            Behavior,
            CrossVersionObjectReference,
            HorizontalAutoscaler,
            HorizontalAutoscalerSpec,
            MetricTarget,
            SLOMetricTarget,
            SLOSpec,
        )
        from karpenter_tpu.cost import CostEngine

        class Row:
            def __init__(self, ha, observed):
                self.ha = ha
                self.observed = observed
                self.values = [value for (_s, _t, value) in observed]
                self.custom = False

        def build_engine_and_rows(per_metric):
            ha = HorizontalAutoscaler(
                metadata=ObjectMeta(name="ha", namespace="default"),
                spec=HorizontalAutoscalerSpec(
                    scale_target_ref=CrossVersionObjectReference(
                        kind="ScalableNodeGroup", name="g"
                    ),
                    min_replicas=1,
                    max_replicas=100,
                    behavior=Behavior(
                        slo=SLOSpec(
                            target_value=100.0,
                            violation_cost_weight=1000.0,
                            metrics=per_metric,
                        )
                    ),
                ),
            )
            target = MetricTarget(type="AverageValue", value=100.0)
            rows = [Row(ha, [(None, target, 80.0), (None, target, 80.0)])]
            return CostEngine(cost_fn=CK.cost_numpy), rows

        base = D.DecisionOutputs(
            desired=np.asarray([2], np.int32),
            recommendation=np.asarray([2], np.int32),
            limited=np.asarray([2], np.int32),
            able_to_scale=np.asarray([True]),
            scaling_unbounded=np.asarray([True]),
            able_at=np.asarray([0.0], np.float32),
            rate_limited=np.asarray([False]),
            up_ceiling=np.asarray([100], np.int32),
            down_floor=np.asarray([1], np.int32),
        )
        # shared 100-per-replica target: 2 replicas absorb the demand
        engine, rows = build_engine_and_rows(None)
        relaxed = engine.adjust(rows, base)
        # metric 1 tightened to 10-per-replica: worst-case risk forces
        # replicas up
        engine, rows = build_engine_and_rows(
            [SLOMetricTarget(), SLOMetricTarget(target_value=10.0)]
        )
        tight = engine.adjust(rows, base)
        assert int(tight.desired[0]) > int(relaxed.desired[0])


class TestForecastGaugeRetirement:
    def test_dropping_forecast_spec_retires_series(self):
        """The frozen-series audit (docs/multitenancy.md satellite): an
        HA that DROPS spec.behavior.forecast must lose its
        karpenter_forecast_* series on the next pass, not pin the last
        pre-opt-out skill forever."""
        from karpenter_tpu.api.core import ObjectMeta
        from karpenter_tpu.api.horizontalautoscaler import (
            Behavior,
            ForecastSpec,
            HorizontalAutoscaler,
            HorizontalAutoscalerSpec,
            MetricTarget,
        )
        from karpenter_tpu.forecast import FleetForecaster

        registry = GaugeRegistry()
        clock = {"now": 1000.0}
        forecaster = FleetForecaster(
            forecast_fn=FM.forecast_numpy,
            registry=registry,
            clock=lambda: clock["now"],
        )
        ha = HorizontalAutoscaler(
            metadata=ObjectMeta(name="ha", namespace="default"),
            spec=HorizontalAutoscalerSpec(
                behavior=Behavior(
                    forecast=ForecastSpec(min_samples=2)
                )
            ),
        )

        class Row:
            def __init__(self, ha, value):
                self.ha = ha
                self.observed = [
                    (
                        None,
                        MetricTarget(type="AverageValue", value=4.0),
                        value,
                    )
                ]
                self.custom = False
                self.stale_metrics = set()

        for i in range(6):
            clock["now"] += 10.0
            forecaster.forecast_rows(
                [Row(ha, 10.0 + i)], clock["now"]
            )
        assert (
            registry.gauge("forecast", "skill").get("ha", "default")
            is not None
        )
        # the HA drops its forecast spec: next pass retires the series
        ha.spec.behavior.forecast = None
        clock["now"] += 10.0
        forecaster.forecast_rows([Row(ha, 20.0)], clock["now"])
        assert (
            registry.gauge("forecast", "skill").get("ha", "default")
            is None
        )
        assert (
            registry.gauge("forecast", "horizon_value").get(
                "ha", "default"
            )
            is None
        )


class TestProducerGaugeRetirement:
    def test_deleted_producer_retires_queue_series(self):
        """The other frozen-series find of the audit: a deleted
        MetricsProducer's queue/capacity gauges must leave /metrics."""
        from karpenter_tpu.api.core import ObjectMeta
        from karpenter_tpu.api.metricsproducer import MetricsProducer
        from karpenter_tpu.controllers.metricsproducer import (
            MetricsProducerController,
        )

        class Factory:
            def __init__(self, registry):
                self.registry = registry

        registry = GaugeRegistry()
        registry.register("queue", "length").set("mq", "default", 41.0)
        registry.register("pending_capacity", "pending_pods").set(
            "mq", "default", 7.0
        )
        controller = MetricsProducerController(Factory(registry))
        mp = MetricsProducer(
            metadata=ObjectMeta(name="mq", namespace="default")
        )
        controller.on_deleted(mp)
        assert registry.gauge("queue", "length").get(
            "mq", "default"
        ) is None
        assert registry.gauge("pending_capacity", "pending_pods").get(
            "mq", "default"
        ) is None

    def test_deleted_producer_retires_reserved_capacity_matrix(self):
        """reserved_capacity names are {resource}_{metric_type} — the
        retirement hook must cover the whole matrix subsystem-wide, not
        a hand-enumerated name list."""
        from karpenter_tpu.api.core import ObjectMeta
        from karpenter_tpu.api.metricsproducer import MetricsProducer
        from karpenter_tpu.controllers.metricsproducer import (
            MetricsProducerController,
        )
        from karpenter_tpu.metrics.producers import reservedcapacity as RC

        class Factory:
            def __init__(self, registry):
                self.registry = registry

        registry = GaugeRegistry()
        RC.register_gauges(registry)
        registry.gauge("reserved_capacity", "cpu_utilization").set(
            "rc", "default", 0.8
        )
        registry.gauge("reserved_capacity", "memory_capacity").set(
            "rc", "default", 64.0
        )
        MetricsProducerController(Factory(registry)).on_deleted(
            MetricsProducer(
                metadata=ObjectMeta(name="rc", namespace="default")
            )
        )
        assert registry.gauge("reserved_capacity", "cpu_utilization").get(
            "rc", "default"
        ) is None
        assert registry.gauge("reserved_capacity", "memory_capacity").get(
            "rc", "default"
        ) is None


class TestSimulateMultitenant:
    def test_deterministic_and_amortizing(self):
        a = simulate_multitenant(tenants=6, ticks=6, rows=3, seed=0)
        b = simulate_multitenant(tenants=6, ticks=6, rows=3, seed=0)
        assert a == b, "seeded replay must be deterministic"
        assert a["tenants"] == 6
        assert a["decisions"] == 6 * 6 * 3
        # the whole point: far fewer dispatches than the sequential
        # per-tenant loop would pay
        assert a["dispatch_amortization"] >= 3.0
        assert a["mirror_served"] == 0
        assert set(a["aggregate_replicas"]) == {
            "tick_0", "tick_3", "tick_5"
        }

    def test_tenant_config_drives_the_replay(self, tmp_path):
        path = tmp_path / "tenants.json"
        path.write_text(json.dumps([
            {"id": "alpha", "weight": 2.0}, {"id": "beta"},
        ]))
        report = simulate_multitenant(
            ticks=3, rows=2, tenant_config=str(path)
        )
        assert report["tenants"] == 2


class TestSidecarTenantMetadata:
    def test_tenant_id_rides_grpc_metadata(self):
        grpc = pytest.importorskip("grpc")  # noqa: F841
        from karpenter_tpu.metrics.registry import GaugeRegistry as GR
        from karpenter_tpu.sidecar.client import SolverClient
        from karpenter_tpu.sidecar.server import SolverServer

        registry = GR()
        server = SolverServer(port=0, host="127.0.0.1", registry=registry)
        port = server.start()
        client = SolverClient(f"127.0.0.1:{port}", tenant="acme")
        try:
            ok, _meta = client.health()
            assert ok
            assert registry.gauge("tenant", "rpcs_total").get(
                "acme", "-"
            ) == 1.0
        finally:
            client.close()
            server.stop()

    def test_no_tenant_is_wire_compatible(self):
        grpc = pytest.importorskip("grpc")  # noqa: F841
        from karpenter_tpu.metrics.registry import GaugeRegistry as GR
        from karpenter_tpu.sidecar.client import SolverClient
        from karpenter_tpu.sidecar.server import SolverServer

        registry = GR()
        server = SolverServer(port=0, host="127.0.0.1", registry=registry)
        port = server.start()
        client = SolverClient(f"127.0.0.1:{port}")
        try:
            ok, _meta = client.health()
            assert ok
            assert not registry.gauge("tenant", "rpcs_total").samples()
        finally:
            client.close()
            server.stop()


class TestRegressionGuard:
    def test_batched_multitenant_beats_sequential_loop(self):
        """Non-slow guard for cross-tenant concatenation: one
        concatenated decide+cost tick over 64 tenants must beat 64
        per-tenant dispatch pairs (generously; no benchmark cell
        measures it on the chip)."""
        service, _reg, scheduler = make_world(
            64, max_rows_per_round=64 * 4
        )
        try:
            decide_batch = {
                f"t{i}": multitenant_fleet_inputs(
                    i, 4, 2, 0, 3, np.full(4, 2, np.int32), 1000.0
                )
                for i in range(64)
            }
            cost_batch = {
                tid: multitenant_cost_inputs(
                    decide_batch[tid], np.full(4, 5, np.int32)
                )
                for tid in decide_batch
            }

            def batched():
                scheduler.decide_all(decide_batch)
                scheduler.cost_all(cost_batch, backend="xla")

            def sequential():
                for tid in decide_batch:
                    service.decide(decide_batch[tid])
                    service.cost(cost_batch[tid], backend="xla")

            batched()  # warm both program shapes
            sequential()

            def best_of(fn, reps=3):
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    fn()
                    times.append(time.perf_counter() - t0)
                return min(times)

            t_batched = best_of(batched)
            t_sequential = best_of(sequential)
            assert t_batched < t_sequential, (
                f"batched {t_batched * 1e3:.2f}ms not faster than "
                f"sequential {t_sequential * 1e3:.2f}ms"
            )
        finally:
            service.close()


class TestTenantWeightedDeadlines:
    """PR 11 named follow-up, closed in PR 13: fairness bounds rows per
    round, not per-tenant latency — deadline_s bounds the latter, with
    each tenant's budget scaled by weight / mean weight. An exhausted
    budget serves the tenant immediately from the bit-identical mirror
    and counts a DEFERRAL (karpenter_tenant_deferrals_total), never a
    breaker failure."""

    def test_light_tenant_escapes_heavy_waits(self):
        # one tenant's rows fill a round, so the schedule is 3 rounds;
        # the ticking clock makes every round cost 'wall time', and the
        # lightweight tenants' small budgets expire mid-schedule
        ticks = {"now": 0.0}

        def clock():
            ticks["now"] += 1.0
            return ticks["now"]

        service, registry, scheduler = make_world(
            n_tenants=3, weights=[10.0, 0.1, 0.1],
            max_rows_per_round=6, deadline_s=10.0, clock=clock,
        )
        try:
            # budgets: mean weight 3.4 -> heavy ~29.4s (never expires
            # under the ticking clock), lights ~0.29s (expire by the
            # first deferred round)
            batch = {
                f"t{i}": random_cost_inputs(seed=40 + i, n=6)
                for i in range(3)
            }
            out = scheduler.cost_all(batch, backend="numpy")
            assert scheduler.stats.deadline_escapes >= 1
            assert scheduler.stats.deferrals >= (
                scheduler.stats.deadline_escapes
            )
            # no breaker charge: backlog is the plane's condition, not
            # the tenant's fault
            assert scheduler.stats.tenant_failures == 0
            for tid in batch:
                assert not scheduler.breakers.is_open(tid)
            # the escaped tenants' answers are the bit-identical mirror
            for tid, inputs in batch.items():
                assert_outputs_equal(
                    CK.CostOutputs, out[tid], CK.cost_numpy(inputs),
                    context=tid,
                )
        finally:
            service.close()

    def test_no_deadline_means_no_escapes(self):
        ticks = {"now": 0.0}

        def clock():
            ticks["now"] += 1.0
            return ticks["now"]

        service, registry, scheduler = make_world(
            n_tenants=3, weights=[10.0, 0.1, 0.1],
            max_rows_per_round=6, clock=clock,
        )
        try:
            batch = {
                f"t{i}": random_cost_inputs(seed=50 + i, n=6)
                for i in range(3)
            }
            out = scheduler.cost_all(batch, backend="numpy")
            assert scheduler.stats.deadline_escapes == 0
            for tid, inputs in batch.items():
                assert_outputs_equal(
                    CK.CostOutputs, out[tid], CK.cost_numpy(inputs),
                    context=tid,
                )
        finally:
            service.close()

    def test_solve_all_weighted_timeouts(self):
        """The bin-pack face: each tenant's queue deadline is its
        weighted budget — an expiry serves binpack_numpy and counts a
        deferral, not a breaker failure."""
        import time as _time

        from karpenter_tpu.ops.binpack import BinPackInputs
        from karpenter_tpu.ops.numpy_binpack import binpack_numpy

        def make_binpack_inputs(seed):
            rng = np.random.RandomState(seed)
            return BinPackInputs(
                pod_requests=rng.uniform(0.1, 2.0, (8, 2)).astype(
                    np.float32
                ),
                pod_valid=np.ones(8, bool),
                pod_intolerant=np.zeros((8, 1), bool),
                pod_required=np.zeros((8, 1), bool),
                group_allocatable=rng.uniform(4.0, 16.0, (3, 2)).astype(
                    np.float32
                ),
                group_taints=np.zeros((3, 1), bool),
                group_labels=np.zeros((3, 1), bool),
            )

        def slow_solver(inputs, buckets=32, backend=None):
            _time.sleep(0.25)
            return binpack_numpy(inputs, buckets=buckets)

        service = SolverService(
            registry=GaugeRegistry(), device_solver=slow_solver,
        )
        metrics_registry = GaugeRegistry()
        registry = TenantRegistry(
            service=service, registry=metrics_registry,
            specs=[
                TenantSpec(id="heavy", weight=1.999),
                TenantSpec(id="light", weight=0.001),
            ],
        )
        scheduler = MultiTenantScheduler(
            registry, service, deadline_s=10.0
        )
        try:
            batch = {
                "heavy": make_binpack_inputs(seed=3),
                "light": make_binpack_inputs(seed=4),
            }
            out = scheduler.solve_all(batch, buckets=8)
            # light's budget (10s x 0.001 / 1.0 = 10ms) expires inside
            # the 250ms dispatch; heavy's (~20s) does not
            assert scheduler.stats.deadline_escapes >= 1
            assert scheduler.stats.tenant_failures == 0
            assert not scheduler.breakers.is_open("light")
            for tid, inputs in batch.items():
                ref = binpack_numpy(inputs, buckets=8)
                np.testing.assert_array_equal(
                    np.asarray(out[tid].assigned),
                    np.asarray(ref.assigned), err_msg=tid,
                )
        finally:
            service.close()

    def test_budgets_scale_with_weight(self):
        service, registry, scheduler = make_world(
            n_tenants=2, weights=[3.0, 1.0], deadline_s=8.0,
        )
        try:
            budgets = scheduler._deadline_budgets(
                ["t0", "t1"], registry.weights()
            )
            # mean weight 2.0: t0 = 8 * 3/2 = 12s, t1 = 8 * 1/2 = 4s
            assert budgets["t0"] == pytest.approx(12.0)
            assert budgets["t1"] == pytest.approx(4.0)
        finally:
            service.close()


class TestEventPassAdmission:
    """ISSUE 14 tenancy pin: event passes are ordinary solver traffic.
    A tenant runtime whose fleet-decide seam routes through the shared
    MultiTenantScheduler keeps riding WeightedAdmission when the decide
    is triggered by a coalesced EVENT PASS (engine event-driven mode)
    instead of a tick — sub-second reaction must not become a fairness
    bypass."""

    def test_event_pass_decides_ride_weighted_admission(self):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        from test_chaos import queue_ha, sng_of

        service, _tenants, scheduler = make_world(2)
        runtimes = []
        try:
            for tid in ("t0", "t1"):
                clock = {"now": 1000.0}
                provider = FakeFactory()
                provider.node_replicas["g"] = 5
                runtime = KarpenterRuntime(
                    Options(
                        event_driven=True,
                        event_debounce_s=0.01,
                        event_thread=False,
                    ),
                    cloud_provider_factory=provider,
                    clock=(lambda c=clock: c["now"]),
                )
                # the tenant's decide seam: through the SHARED scheduler
                # (concat + WeightedAdmission + per-tenant isolation),
                # exactly how a live multi-tenant deployment fronts the
                # one solver service
                runtime.batch_autoscaler.decider = (
                    lambda inputs, t=tid:
                    scheduler.decide_all({t: inputs})[t]
                )
                runtime.registry.register("queue", "length").set(
                    "q", "default", 41.0
                )
                runtime.store.create(sng_of("g", replicas=5))
                runtime.store.create(
                    queue_ha("g", 'karpenter_queue_length{name="q"}')
                )
                runtimes.append((runtime, provider, clock))

            rounds_before = scheduler.stats.admission_rounds
            decides_before = scheduler.stats.decide_calls
            for runtime, provider, clock in runtimes:
                # NO ticks: the create events alone must cascade the
                # decide -> scale patch -> actuation through passes
                for _ in range(6):
                    if runtime.manager.dirty_count() == 0:
                        break
                    clock["now"] += 0.01
                    runtime.manager.run_event_pass()
                assert provider.node_replicas["g"] == 11, (
                    "event passes must actuate the fleet decide "
                    "(queue 41 / target 4 -> 11)"
                )
            assert scheduler.stats.decide_calls - decides_before >= 2, (
                "each tenant's event-pass decide must flow through the "
                "shared scheduler"
            )
            assert scheduler.stats.admission_rounds - rounds_before >= 2, (
                "event-pass dispatches must take WeightedAdmission "
                "rounds, not bypass fairness"
            )
        finally:
            for runtime, _provider, _clock in runtimes:
                runtime.close()
            service.close()
