"""Fused steady-state tick (ops/fusedtick.py + SolverService.fused_tick).

The ISSUE acceptance pins, in suite form:

  * property pin — fused megakernel == chained forecast -> decide ->
    cost wire == numpy mirror, BITWISE, on the device and numpy service
    paths, across every stage-presence combination;
  * masked-operand contract — an all-masked forecast/SLO group is
    byte-identical to the absent-operand wire (the PR 16 posture);
  * per-tenant batch slices — a tenant's slice of the shared fused
    dispatch equals its own independent fused dispatch, bit for bit;
  * runtime fixed point — --fused-tick on/off produce the same replica
    trail while the dispatches-per-tick gauge collapses 3+ -> 1;
  * compile-cache restart — Options.compile_cache_dir persists the
    fused program; a rebooted service prewarns from disk with ZERO
    fresh compile-ledger rows;
  * regression guard — fused must not get slower than the chained wire
    (live, non-slow).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from karpenter_tpu.forecast import models as FM
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.ops import decision as D
from karpenter_tpu.ops import fusedtick as FT
from karpenter_tpu.solver.service import SolverService

# -- seeded operand builders --------------------------------------------------


def mk_decision(seed, n, m, now=1000.0):
    r = np.random.RandomState(seed)
    k = 2
    return D.DecisionInputs(
        metric_value=r.uniform(0, 100, (n, m)).astype(np.float32),
        target_value=r.uniform(1, 80, (n, m)).astype(np.float32),
        target_type=r.randint(0, 3, (n, m)).astype(np.int32),
        metric_valid=r.rand(n, m) > 0.2,
        spec_replicas=r.randint(1, 20, n).astype(np.int32),
        status_replicas=r.randint(1, 20, n).astype(np.int32),
        min_replicas=r.randint(0, 3, n).astype(np.int32),
        max_replicas=r.randint(20, 40, n).astype(np.int32),
        up_window=r.randint(0, 60, n).astype(np.int32),
        down_window=r.randint(0, 120, n).astype(np.int32),
        up_policy=r.randint(0, 2, n).astype(np.int32),
        down_policy=r.randint(0, 2, n).astype(np.int32),
        last_scale_time=(now - r.uniform(0, 300, n)).astype(np.float32),
        has_last_scale=r.rand(n) > 0.3,
        now=np.float32(now),
        up_ptype=r.randint(0, 3, (n, k)).astype(np.int32),
        up_pvalue=r.randint(1, 10, (n, k)).astype(np.int32),
        up_pperiod=r.randint(15, 120, (n, k)).astype(np.int32),
        up_pvalid=r.rand(n, k) > 0.4,
        down_ptype=r.randint(0, 3, (n, k)).astype(np.int32),
        down_pvalue=r.randint(1, 10, (n, k)).astype(np.int32),
        down_pperiod=r.randint(15, 120, (n, k)).astype(np.int32),
        down_pvalid=r.rand(n, k) > 0.4,
    )


def mk_forecast_group(seed, s, t, n, m):
    r = np.random.RandomState(seed + 1)
    return dict(
        forecast=FM.ForecastInputs(
            values=r.uniform(0, 100, (s, t)).astype(np.float32),
            valid=r.rand(s, t) > 0.2,
            times=np.cumsum(r.uniform(10, 20, (s, t)), 1).astype(
                np.float32
            ),
            weights=np.ones((s, t), np.float32),
            horizon=np.full(s, 60.0, np.float32),
            step_s=np.full(s, 15.0, np.float32),
            model=r.randint(0, 2, s).astype(np.int32),
            season=np.full(s, 4, np.int32),
            alpha=np.full(s, 0.5, np.float32),
            beta=np.full(s, 0.1, np.float32),
            gamma=np.full(s, 0.1, np.float32),
        ),
        series_row=r.randint(0, n, s).astype(np.int32),
        series_col=r.randint(0, m, s).astype(np.int32),
        series_need=np.full(s, 2, np.int32),
        series_blend=r.rand(s) > 0.3,
    )


def mk_cost_group(seed, n, m):
    r = np.random.RandomState(seed + 2)
    return dict(
        ha_min=r.randint(0, 3, n).astype(np.int32),
        ha_max=r.randint(20, 40, n).astype(np.int32),
        unit_cost=r.uniform(0.1, 3.0, n).astype(np.float32),
        slo_weight=r.uniform(0, 2, n).astype(np.float32),
        max_hourly_cost=r.uniform(5, 50, n).astype(np.float32),
        slo_valid=r.rand(n) > 0.4,
        slo_target=r.uniform(1, 80, (n, m)).astype(np.float32),
        observed=r.uniform(0, 100, (n, m)).astype(np.float32),
        demand_base_valid=r.rand(n, m) > 0.3,
        prior_point=r.uniform(0, 100, (n, m)).astype(np.float32),
        prior_sigma2=r.uniform(0, 10, (n, m)).astype(np.float32),
        prior_valid=r.rand(n, m) > 0.5,
    )


def mk_inputs(seed, n, m, s=0, t=0, forecast=True, cost=True, now=1000.0):
    kwargs = dict(decision=mk_decision(seed, n, m, now=now))
    if forecast:
        kwargs.update(mk_forecast_group(seed, s, t, n, m))
    if cost:
        kwargs.update(mk_cost_group(seed, n, m))
    return FT.FusedTickInputs(**kwargs)


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, tree)
    )


def assert_bitwise(a, b, context=""):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb), f"{context}: leaf count {len(la)}!={len(lb)}"
    for i, (x, y) in enumerate(zip(la, lb)):
        assert x.dtype == y.dtype and x.shape == y.shape, (
            f"{context}: leaf {i} {x.dtype}{x.shape} vs {y.dtype}{y.shape}"
        )
        assert x.tobytes() == y.tobytes(), (
            f"{context}: leaf {i} differs bitwise"
        )


# -- the property pin: fused == chained == numpy, bitwise --------------------


class TestFusedKernelParity:
    PRESENCE = [
        (True, True), (True, False), (False, True), (False, False)
    ]

    def test_fused_chained_numpy_bitwise(self):
        """The tentpole contract: ONE compiled program returns exactly
        the bytes the chained per-stage wire returns, which returns
        exactly the bytes the numpy mirror returns — every presence
        combination, several seeds."""
        for has_forecast, has_cost in self.PRESENCE:
            for seed in (0, 1, 2):
                inputs = mk_inputs(
                    seed, n=16, m=2, s=12, t=10,
                    forecast=has_forecast, cost=has_cost,
                )
                ctx = f"f={has_forecast} c={has_cost} seed={seed}"
                fused = FT.fused_tick_jit(inputs)
                chained = FT.fused_tick_chained(inputs)
                mirror = FT.fused_tick_numpy(inputs)
                assert_bitwise(fused, chained, f"fused/chained {ctx}")
                assert_bitwise(fused, mirror, f"fused/numpy {ctx}")
                assert (fused.forecast is None) == (not has_forecast)
                assert (fused.cost is None) == (not has_cost)

    def test_masked_forecast_rows_match_absent_wire(self):
        """An all-masked forecast group (the tenancy concat's pad-row
        mask: blend gate False + an unreachable sample need) is
        byte-identical to the absent-forecast dispatch on the decision
        and cost planes — the PR 16 masked-operand contract carried
        into the megakernel."""
        base = mk_inputs(seed=5, n=12, m=2, s=9, t=8)
        masked = dataclasses.replace(
            base,
            series_blend=np.zeros(9, bool),
            series_need=np.full(9, np.iinfo(np.int32).max, np.int32),
        )
        absent = dataclasses.replace(
            base, forecast=None, series_row=None, series_col=None,
            series_need=None, series_blend=None,
        )
        out_masked = FT.fused_tick_jit(masked)
        out_absent = FT.fused_tick_jit(absent)
        assert_bitwise(
            out_masked.decision, out_absent.decision, "decision"
        )
        assert_bitwise(out_masked.cost, out_absent.cost, "cost")
        assert_bitwise(
            FT.fused_tick_numpy(masked).decision,
            out_absent.decision, "numpy decision",
        )
        # a blend-gate-only mask still feeds the cost stage's demand
        # distribution (the skill gate governs the decide blend alone)
        # but must leave the DECISION plane absent-identical
        blend_only = dataclasses.replace(
            base, series_blend=np.zeros(9, bool)
        )
        assert_bitwise(
            FT.fused_tick_jit(blend_only).decision,
            out_absent.decision, "blend-only decision",
        )

    def test_masked_slo_rows_match_absent_wire(self):
        """An all-masked cost group (every slo_valid False) passes the
        blended decision through untouched and leaves the decision +
        forecast planes byte-identical to the absent-SLO dispatch."""
        base = mk_inputs(seed=6, n=12, m=2, s=9, t=8)
        masked = dataclasses.replace(
            base, slo_valid=np.zeros(12, bool)
        )
        absent = FT.FusedTickInputs(
            decision=base.decision, forecast=base.forecast,
            series_row=base.series_row, series_col=base.series_col,
            series_need=base.series_need, series_blend=base.series_blend,
        )
        out_masked = FT.fused_tick_jit(masked)
        out_absent = FT.fused_tick_jit(absent)
        assert out_masked.cost is not None and out_absent.cost is None
        assert_bitwise(
            out_masked.decision, out_absent.decision, "decision"
        )
        assert_bitwise(
            out_masked.forecast, out_absent.forecast, "forecast"
        )
        # pass-through: the masked ladder never moves the blended base
        assert (
            np.asarray(out_masked.cost.desired).tobytes()
            == np.asarray(out_masked.decision.desired).tobytes()
        )

    def test_programs_counts_the_chained_wire(self):
        full = mk_inputs(0, n=8, m=2, s=4, t=6)
        assert FT.programs(full) == 3
        assert FT.programs(
            dataclasses.replace(full, slo_valid=None)
        ) == 2
        assert FT.programs(
            FT.FusedTickInputs(decision=full.decision)
        ) == 1


# -- the service seam ---------------------------------------------------------


class TestFusedServiceSeam:
    def _service(self, **kw):
        kw.setdefault("registry", GaugeRegistry())
        kw.setdefault("backend", "xla")
        return SolverService(**kw)

    def test_device_and_numpy_paths_bitwise(self):
        service = self._service()
        try:
            inputs = mk_inputs(7, n=10, m=2, s=6, t=8)
            device = service.fused_tick(inputs)
            host = service.fused_tick(inputs, backend="numpy")
            assert_bitwise(device, host, "device/numpy service paths")
            assert service.stats.fused_calls == 2
            assert service.stats.fused_dispatches == 1
            assert service.stats.fused_chained_serves == 0
            # an EXPLICIT numpy request is not a degraded serve
            assert service.stats.fused_mirror_serves == 0
        finally:
            service.close()

    def test_forecast_sliced_back_to_caller_s(self):
        """The door pads S up the forecast shape ladder; the caller
        gets exactly its own series back (padding rows are
        service-internal, like the queue family's)."""
        service = self._service()
        try:
            inputs = mk_inputs(8, n=10, m=2, s=5, t=8)
            out = service.fused_tick(inputs)
            assert np.asarray(out.forecast.point).shape[0] == 5
            assert np.asarray(out.forecast.sigma2).shape[0] == 5
        finally:
            service.close()

    def test_note_tick_collapses_gauge_to_one(self):
        """The dispatches-per-tick observable: a fused tick pays ONE
        device program where the chained wire pays one per stage."""
        service = self._service()
        try:
            inputs = mk_inputs(9, n=10, m=2, s=6, t=8)
            service.fused_tick(inputs)
            service.note_tick()
            assert service.stats.last_dispatches_per_tick == 1
            gauge = service.registry.gauge(
                "solver", "dispatches_per_tick"
            )
            assert gauge.get("-", "-") == 1.0
        finally:
            service.close()

    def test_prewarm_fused_family(self):
        service = self._service()
        try:
            service.reset_caches()  # order-independence: re-arm fused
            report = service.prewarm(("fused",))
            assert report["fused"]["skipped"] is False
            assert report["fused"]["fresh_compiles"] == 1
            assert service.stats.fused_dispatches == 1
            again = service.prewarm(("fused",))
            assert again["fused"] == {"skipped": True}
        finally:
            service.close()


# -- per-tenant batch slices --------------------------------------------------


class TestFusedTenancySlices:
    def test_shared_dispatch_slices_match_isolated(self):
        """Four tenants with mixed stage presence concatenated into ONE
        fused dispatch: every tenant's slice is byte-identical to its
        own isolated service.fused_tick, and the group really shares a
        single fused program."""
        from karpenter_tpu.tenancy import (
            MultiTenantScheduler,
            TenantRegistry,
            TenantSpec,
        )

        shapes = [
            # (seed, n, m, forecast, cost)
            (11, 12, 3, True, True),
            (12, 7, 2, True, False),
            (13, 9, 3, False, True),
            (14, 5, 1, False, False),
        ]
        batch = {
            f"t{i}": mk_inputs(
                seed, n=n, m=m, s=max(2, n // 2), t=8,
                forecast=fc, cost=cc,
            )
            for i, (seed, n, m, fc, cc) in enumerate(shapes)
        }
        shared = SolverService(registry=GaugeRegistry(), backend="xla")
        isolated = SolverService(
            registry=GaugeRegistry(), backend="xla"
        )
        try:
            registry = TenantRegistry(
                service=shared, registry=GaugeRegistry(),
                specs=[TenantSpec(id=t) for t in batch],
            )
            scheduler = MultiTenantScheduler(registry, shared)
            results = scheduler.fused_tick_all(batch)
            assert set(results) == set(batch)
            for tenant, inputs in batch.items():
                assert_bitwise(
                    results[tenant],
                    isolated.fused_tick(inputs),
                    f"tenant {tenant}",
                )
            assert scheduler.stats.fused_calls == 1
            # the mixed batch concatenates into TWO shared dispatches:
            # forecast-carrying tenants share one t-bucket group,
            # forecast-less tenants the other (grouping by forecast
            # time bucket keeps the T padding bit-preserving)
            assert scheduler.stats.fused_dispatches == 2
            assert shared.stats.fused_dispatches == 2

            # a homogeneous-forecast batch (cost presence still mixed
            # — absent tenants ride as all-masked rows) really shares
            # ONE fused program
            uniform = {
                f"u{i}": mk_inputs(
                    30 + i, n=6 + i, m=2, s=4, t=8,
                    forecast=True, cost=(i % 2 == 0),
                )
                for i in range(4)
            }
            registry2 = TenantRegistry(
                service=shared, registry=GaugeRegistry(),
                specs=[TenantSpec(id=t) for t in uniform],
            )
            scheduler2 = MultiTenantScheduler(registry2, shared)
            before = shared.stats.fused_dispatches
            results2 = scheduler2.fused_tick_all(uniform)
            assert shared.stats.fused_dispatches == before + 1
            for tenant, inputs in uniform.items():
                assert_bitwise(
                    results2[tenant],
                    isolated.fused_tick(inputs),
                    f"tenant {tenant}",
                )
        finally:
            shared.close()
            isolated.close()


# -- the runtime fixed point: fused on == fused off ---------------------------


def _decision_world(**options_kw):
    """A seeded runtime whose every tick exercises decide + forecast +
    cost (the test_provenance world): the full fused-stage surface."""
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
        Options(**options_kw), cloud_provider_factory=provider,
        clock=lambda: clock["now"],
    )
    # the fused/chained device paths both need the compiled backend:
    # "auto" resolves to numpy on the CPU test backend (bit-parity
    # keeps the decisions identical either way; the dispatch-count
    # observable needs the device rung)
    runtime.solver_service.backend = "xla"
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


def _run_world(ticks=12, **options_kw):
    runtime, provider, gauge, clock = _decision_world(**options_kw)
    trail = []
    try:
        for tick in range(ticks):
            gauge.set("q", "default", 8.0 + 3.0 * tick)
            runtime.manager._due = {k: 0.0 for k in runtime.manager._due}
            runtime.manager.reconcile_all()
            clock["now"] += 10.0
            trail.append(provider.node_replicas["g"])
        stats = dataclasses.replace(runtime.solver_service.stats)
    finally:
        runtime.close()
    return trail, stats


class TestFusedRuntimeFixedPoint:
    def test_fused_on_off_same_trail_one_program_per_tick(self):
        """--fused-tick keeps the replica trail byte-identical to the
        chained wire while the steady-state tick collapses to ONE
        device program (the dispatches-per-tick gauge delta the bench
        publishes)."""
        chained_trail, chained_stats = _run_world()
        fused_trail, fused_stats = _run_world(fused_tick=True)
        assert fused_trail == chained_trail, (
            "the fused tick observes the same math; it must never "
            "change a decision"
        )
        assert fused_stats.fused_calls > 0
        assert fused_stats.fused_dispatches > 0
        assert fused_stats.fused_chained_serves == 0
        assert fused_stats.fused_mirror_serves == 0
        # the headline observable: forecast + decide + cost engaged,
        # yet the last steady-state tick paid exactly one program —
        # while the chained wire pays one per engaged stage
        assert fused_stats.last_dispatches_per_tick == 1
        assert chained_stats.last_dispatches_per_tick >= 2
        assert chained_stats.fused_calls == 0

    def test_default_off_never_routes_fused(self):
        _, stats = _run_world(ticks=4)
        assert stats.fused_calls == 0
        assert stats.fused_dispatches == 0


# -- compile-cache restart: prewarm from disk, zero fresh ledger rows ---------


class TestCompileCacheRestart:
    def test_restart_prewarns_from_cache_zero_fresh_rows(self, tmp_path):
        """Options.compile_cache_dir (--compile-cache-dir): the first
        boot persists the fused program; a restarted service prewarns
        the fused family with ZERO fresh compile-ledger rows and writes
        nothing new to the cache."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        old_dir = jax.config.jax_compilation_cache_dir
        old_min = jax.config.jax_persistent_cache_min_compile_time_secs
        # conftest keeps the suite cache-free; this test is the cache's
        jax.config.update("jax_enable_compilation_cache", True)
        try:
            runtime1 = KarpenterRuntime(
                Options(
                    fused_tick=True,
                    compile_cache_dir=str(tmp_path),
                ),
                cloud_provider_factory=FakeFactory(),
            )
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
            # CPU test compiles finish in <1s; the production threshold
            # (JAX's 1s default) would persist none
            # of them — lower it so this test exercises the disk layer
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0
            )
            # "auto" resolves to the numpy floor on the CPU test
            # backend — the compile/persist layers need the jitted path
            runtime1.solver_service.backend = "xla"
            try:
                # force a genuinely fresh fused compile regardless of
                # what earlier tests warmed in this process
                runtime1.solver_service.reset_caches()
                jax.clear_caches()
                report1 = runtime1.solver_service.prewarm(("fused",))
                assert report1["fused"]["fresh_compiles"] == 1
            finally:
                runtime1.close()
            cached = sorted(p.name for p in tmp_path.iterdir())
            assert cached, (
                "the fused prewarm compile must persist to the cache dir"
            )

            # -- "restart": drop the in-process compiled programs; the
            # disk cache (and the process fused-seen keys) survive
            jax.clear_caches()
            runtime2 = KarpenterRuntime(
                Options(
                    fused_tick=True,
                    compile_cache_dir=str(tmp_path),
                    introspect=True,
                ),
                cloud_provider_factory=FakeFactory(),
            )
            runtime2.solver_service.backend = "xla"
            try:
                plane = runtime2.solver_introspection
                before = plane.ledger.records_total
                report2 = runtime2.solver_service.prewarm(("fused",))
                assert report2["fused"]["skipped"] is False
                assert report2["fused"]["fresh_compiles"] == 0, (
                    "a rebooted plane must prewarm from the persistent "
                    "cache, not pay the compile again"
                )
                assert "ms" in report2["fused"]
                assert plane.ledger.records_total == before
                assert plane.ledger.by_family.get("fused") is None
            finally:
                runtime2.close()
            assert sorted(p.name for p in tmp_path.iterdir()) == cached, (
                "the warm reboot must add no new cache entries"
            )
        finally:
            jax.config.update("jax_enable_compilation_cache", False)
            jax.config.update("jax_compilation_cache_dir", old_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", old_min
            )
            compilation_cache.reset_cache()

    def test_flag_parses_and_defaults_off(self, tmp_path):
        """--compile-cache-dir parses to the directory and defaults to
        None (the resolver then picks JAX_COMPILATION_CACHE_DIR or the
        in-checkout default), and the fused tick defaults off."""
        from karpenter_tpu.__main__ import parse_args

        args = parse_args(["--compile-cache-dir", str(tmp_path)])
        assert args.compile_cache_dir == str(tmp_path)
        args = parse_args([])
        assert args.compile_cache_dir is None
        assert args.fused_tick is False  # default off

    def test_production_profile_enables_fused_tick(self):
        from karpenter_tpu.__main__ import parse_args

        args = parse_args(["--profile", "production"])
        assert args.fused_tick is True
        args = parse_args(["--profile", "production", "--no-fused-tick"])
        assert args.fused_tick is False


# -- the regression guard (live) ----------------------------------------------


class TestFusedRegressionGuard:
    def test_live_fused_not_slower_than_chained(self):
        """The live guard: one warmed fused dispatch must not fall
        behind the warmed chained wire (generous margin — this catches
        a fusion regression, not timer noise)."""
        import jax

        inputs = mk_inputs(21, n=256, m=3, s=128, t=32)
        jax.block_until_ready(_leaves(FT.fused_tick_jit(inputs)))
        FT.fused_tick_chained(inputs)

        def best(fn, reps=3):
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            return min(times)

        fused = best(
            lambda: jax.block_until_ready(
                _leaves(FT.fused_tick_jit(inputs))
            )
        )
        chained = best(lambda: FT.fused_tick_chained(inputs))
        assert fused < chained * 1.5, (
            f"fused tick {fused * 1e3:.3f}ms fell behind the chained "
            f"wire {chained * 1e3:.3f}ms"
        )
