"""Shared solve service (karpenter_tpu/solver): coalescing, shape-bucketed
compile cache, backpressure/deadlines, numpy fallback, metrics surface,
and the public pendingcapacity encoding API it rides with.

The acceptance pin: 8 concurrent same-bucket requests produce at most 2
device dispatches; a post-warmup stream of jittered pod counts within one
bucket causes zero recompiles (per the service's compile-cache counters);
and every service result is element-for-element identical to a direct
ops/binpack call.
"""

import threading
import time

import numpy as np
import pytest

from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.ops import binpack as B
from karpenter_tpu.ops.numpy_binpack import binpack_numpy
from karpenter_tpu.solver import (
    SolverSaturated,
    SolverService,
    SolverTimeout,
    bucket_up,
)


def make_inputs(pods, types, seed=0, weighted=False, constrained=False):
    """Integer-valued requests: every float reduction in the solve is then
    exact, so equality assertions are bitwise, not approximate."""
    rng = np.random.default_rng(seed)
    req = np.stack(
        [
            rng.integers(1, 8, pods),
            rng.integers(1, 32, pods),
            np.ones(pods),
        ],
        axis=1,
    ).astype(np.float32)
    alloc = np.stack(
        [
            rng.choice([8, 16, 32, 64], types),
            rng.choice([32, 64, 128], types),
            np.full(types, 110.0),
        ],
        axis=1,
    ).astype(np.float32)
    kwargs = {}
    if weighted:
        kwargs["pod_weight"] = rng.integers(1, 5, pods).astype(np.int32)
    if constrained:
        kwargs["pod_group_forbidden"] = rng.random((pods, types)) < 0.2
        kwargs["pod_group_score"] = rng.integers(
            0, 3, (pods, types)
        ).astype(np.float32)
        kwargs["pod_exclusive"] = rng.random(pods) < 0.1
    return B.BinPackInputs(
        pod_requests=req,
        pod_valid=np.ones(pods, bool),
        pod_intolerant=rng.random((pods, 16)) < 0.05,
        pod_required=rng.random((pods, 16)) < 0.03,
        group_allocatable=alloc,
        group_taints=rng.random((types, 16)) < 0.1,
        group_labels=rng.random((types, 16)) < 0.8,
        **kwargs,
    )


def assert_outputs_equal(got, want):
    for name in ("assigned", "assigned_count", "nodes_needed", "lp_bound"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(want, name)),
            err_msg=name,
        )
    assert int(got.unschedulable) == int(want.unschedulable)


@pytest.fixture
def service():
    svc = SolverService(
        registry=GaugeRegistry(), window_s=0.05, max_batch=8
    )
    yield svc
    svc.close()


class TestBucketLadder:
    def test_bucket_up_rungs(self):
        assert bucket_up(1, 256) == 256
        assert bucket_up(256, 256) == 256
        assert bucket_up(257, 256) == 384
        assert bucket_up(385, 256) == 512
        assert bucket_up(513, 256) == 768
        assert bucket_up(1000, 256) == 1024
        # consecutive rungs <= 1.5x apart: padding waste bounded
        rungs = sorted({bucket_up(n, 8) for n in range(1, 4096)})
        for a, b in zip(rungs, rungs[1:]):
            assert b <= a * 1.5 + 1e-9

    def test_padding_is_identity_at_bucket_shape(self):
        from karpenter_tpu.solver import bucket_shape, pad_to_bucket

        inputs = make_inputs(256, 8)
        # 16-wide taint/label universes pad up to their floors, so
        # build one already at floor widths to check identity
        padded_once = pad_to_bucket(inputs, bucket_shape(inputs))
        again = pad_to_bucket(padded_once, bucket_shape(padded_once))
        assert again is padded_once


class TestAcceptance:
    def test_coalescing_cache_stability_and_bitwise_identity(self, service):
        """The ISSUE acceptance pin, in one flow."""
        inputs = [make_inputs(100 + i, 5, seed=i) for i in range(8)]

        # warm the two batch sizes this test will see (batch=8 coalesced,
        # batch=1 sequential) so the streaming phase measures steady state
        service.solve(make_inputs(90, 5, seed=99), backend="xla")

        results = [None] * 8
        barrier = threading.Barrier(8)

        def submit(i):
            barrier.wait()
            results[i] = service.solve(inputs[i], backend="xla")

        dispatches_before = service.stats.dispatches
        threads = [
            threading.Thread(target=submit, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # 8 concurrent same-bucket requests -> at most 2 device dispatches
        assert service.stats.dispatches - dispatches_before <= 2
        assert service.stats.last_coalesce_factor >= 4

        # results identical to direct ops/binpack calls
        for i in range(8):
            assert_outputs_equal(
                results[i], B.solve(inputs[i], backend="xla")
            )

        # post-warmup stream of jittered pod counts within one bucket:
        # ZERO recompiles (the batch=1 and batch<=8 programs are warm)
        misses_before = service.stats.compile_cache_misses
        for pods in (70, 110, 200, 255, 130, 64, 256):
            out = service.solve(
                make_inputs(pods, 5, seed=pods), backend="xla"
            )
            assert out.assigned.shape == (pods,)
        assert service.stats.compile_cache_misses == misses_before
        assert service.stats.compile_cache_hits > 0


class TestEquality:
    @pytest.mark.parametrize("weighted", [False, True])
    @pytest.mark.parametrize("constrained", [False, True])
    def test_service_matches_direct_across_operand_shapes(
        self, service, weighted, constrained
    ):
        for pods, types in ((17, 3), (256, 8), (300, 12)):
            inputs = make_inputs(
                pods, types, seed=pods,
                weighted=weighted, constrained=constrained,
            )
            assert_outputs_equal(
                service.solve(inputs, backend="xla"),
                B.solve(inputs, backend="xla"),
            )

    def test_numpy_backend_matches_direct(self, service):
        inputs = make_inputs(40, 4, seed=7)
        assert_outputs_equal(
            service.solve(inputs, backend="numpy"),
            binpack_numpy(inputs, buckets=32),
        )
        # the host program never touches the device path
        assert service.stats.dispatches == 0

    def test_distinct_buckets_solve_independently(self, service):
        """Requests in different shape buckets coalesce into separate
        device calls but all complete correctly."""
        small = make_inputs(50, 4, seed=1)
        large = make_inputs(300, 4, seed=2)
        results = {}
        barrier = threading.Barrier(2)

        def submit(name, inputs):
            barrier.wait()
            results[name] = service.solve(inputs, backend="xla")

        threads = [
            threading.Thread(target=submit, args=("small", small)),
            threading.Thread(target=submit, args=("large", large)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert_outputs_equal(results["small"], B.solve(small, backend="xla"))
        assert_outputs_equal(results["large"], B.solve(large, backend="xla"))


class TestBackpressureAndDeadlines:
    def test_deadline_expiry_raises_when_configured(self):
        release = threading.Event()

        def stuck_device(inputs, buckets=32, backend="auto"):
            release.wait(5.0)
            return binpack_numpy(inputs, buckets=buckets)

        svc = SolverService(
            registry=GaugeRegistry(),
            device_solver=stuck_device,
            on_timeout="raise",
            window_s=0.0,
        )
        try:
            with pytest.raises(SolverTimeout):
                svc.solve(make_inputs(20, 3), timeout=0.05)
            assert svc.stats.deadline_expired == 1
        finally:
            release.set()
            svc.close()

    def test_deadline_expiry_falls_back_to_numpy_by_default(self):
        release = threading.Event()

        def stuck_device(inputs, buckets=32, backend="auto"):
            release.wait(5.0)
            return binpack_numpy(inputs, buckets=buckets)

        svc = SolverService(
            registry=GaugeRegistry(),
            device_solver=stuck_device,
            window_s=0.0,
        )
        try:
            inputs = make_inputs(20, 3)
            out = svc.solve(inputs, timeout=0.05)
            assert_outputs_equal(out, binpack_numpy(inputs, buckets=32))
            assert svc.stats.deadline_expired == 1
            assert svc.stats.fallbacks == 1
        finally:
            release.set()
            svc.close()

    def test_device_failure_falls_back_to_numpy(self):
        def broken_device(inputs, buckets=32, backend="auto"):
            raise RuntimeError("injected device failure")

        svc = SolverService(
            registry=GaugeRegistry(), device_solver=broken_device,
            window_s=0.0,
        )
        try:
            inputs = make_inputs(30, 4, seed=3)
            out = svc.solve(inputs)
            assert_outputs_equal(out, binpack_numpy(inputs, buckets=32))
            assert svc.stats.fallbacks == 1
        finally:
            svc.close()

    def test_saturated_queue_degrades_inline(self):
        """A full bounded queue must answer the overflow request from the
        numpy backend instead of queueing without bound."""
        release = threading.Event()
        started = threading.Event()

        def slow_device(inputs, buckets=32, backend="auto"):
            started.set()
            release.wait(5.0)
            return binpack_numpy(inputs, buckets=buckets)

        svc = SolverService(
            registry=GaugeRegistry(),
            device_solver=slow_device,
            max_queue=1,
            window_s=0.0,
        )
        try:
            # occupy the worker, then fill the single queue slot
            blocked = svc.submit(make_inputs(10, 2, seed=1))
            assert started.wait(2.0)
            svc.submit(make_inputs(10, 2, seed=2))
            with pytest.raises(SolverSaturated):
                svc.submit(make_inputs(10, 2, seed=3))
            # solve() turns saturation into the inline numpy answer
            inputs = make_inputs(10, 2, seed=4)
            out = svc.solve(inputs)
            assert_outputs_equal(out, binpack_numpy(inputs, buckets=32))
            assert svc.stats.rejected == 2
            assert svc.stats.fallbacks == 1
            release.set()
            blocked.result(5.0)
        finally:
            release.set()
            svc.close()


class TestMetricsSurface:
    @pytest.mark.parametrize("n", [1, 2, 7, 100, 256])
    def test_stage_percentiles_match_numpy(self, n):
        from karpenter_tpu.solver.service import _p50_p99

        samples = list(np.random.default_rng(n).exponential(3.0, n))
        assert _p50_p99(samples) == pytest.approx(
            tuple(np.percentile(samples, (50, 99))), rel=1e-12
        )

    def test_gauges_registered_and_published(self):
        registry = GaugeRegistry()
        svc = SolverService(registry=registry, window_s=0.0)
        try:
            svc.solve(make_inputs(20, 3), backend="xla")
            svc.publish_gauges()
            text = registry.expose_text()
            for series in (
                "karpenter_solver_queue_depth",
                "karpenter_solver_coalesce_factor",
                "karpenter_solver_requests_total",
                "karpenter_solver_dispatch_total",
                "karpenter_solver_compile_cache_misses_total",
                "karpenter_solver_stage_p50_ms",
                "karpenter_solver_window_ms",
                "karpenter_solver_pipeline_depth",
            ):
                assert series in text, series
        finally:
            svc.close()

    def test_manager_publishes_service_gauges_each_tick(self):
        """The satellite fix: /metrics shows queue depth + coalesce
        factor through the Manager with no extra wiring in __main__."""
        from karpenter_tpu.controllers import Manager
        from karpenter_tpu.store import Store

        registry = GaugeRegistry()
        svc = SolverService(registry=registry, window_s=0.0)
        try:
            manager = Manager(
                Store(), registry=registry, solver_service=svc
            )
            manager.reconcile_all()
            gauge = registry.gauge("solver", "queue_depth")
            assert gauge.get("-", "-") == 0.0
        finally:
            svc.close()

    def test_runtime_wires_all_callers_through_service(self):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        rt = KarpenterRuntime(
            Options(cloud_provider="fake"),
            cloud_provider_factory=FakeFactory(),
        )
        try:
            assert rt.producer_factory.solver == rt.solver_service.solve
            assert rt.batch_autoscaler.decider == rt.solver_service.decide
            # the service's gauges live in the runtime registry the
            # MetricsServer serves
            assert rt.solver_service.registry is rt.registry
        finally:
            rt.close()

    def test_decide_routes_and_counts(self):
        from karpenter_tpu.ops.decision import decide_jit
        from karpenter_tpu.parallel.mesh import example_decision_inputs

        svc = SolverService(registry=GaugeRegistry())
        try:
            inputs = example_decision_inputs(N=4, M=2, seed=0)
            out = svc.decide(inputs)
            want = decide_jit(inputs)
            np.testing.assert_array_equal(
                np.asarray(out.desired), np.asarray(want.desired)
            )
            assert svc.stats.decide_calls == 1
        finally:
            svc.close()


class TestPublicEncodingAPI:
    def test_encode_snapshot_matches_encoder_module(self):
        from karpenter_tpu.metrics.producers import pendingcapacity as PC
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            encoder,
        )
        from karpenter_tpu.store.columnar import snapshot_from_pods

        snap = snapshot_from_pods([])
        profiles = [({"cpu": 8.0, "pods": 110.0}, set(), set())]
        public = PC.encode_snapshot(snap, profiles)
        private = encoder._encode_full(snap, profiles)
        np.testing.assert_array_equal(
            public.group_allocatable, private.group_allocatable
        )

    def test_group_profile_public_name(self):
        from karpenter_tpu.metrics.producers import pendingcapacity as PC

        assert PC.group_profile([], {}) == ({}, set(), set())

    def test_underscore_shims_are_gone(self):
        """The deprecated PR-1 compat shims were removed: the package no
        longer re-exports the private helpers (their home submodules do
        — encoder, partition, spread, anti, exclusion)."""
        import importlib

        module = importlib.import_module(
            "karpenter_tpu.metrics.producers.pendingcapacity"
        )
        for name in (
            "_group_profile",
            "_encode_from_cache",
            "_dedup_rows",
            "_group_arrays",
            "_water_fill",
            "_expand_spread_rows",
            "_expand_anti_rows",
        ):
            with pytest.raises(AttributeError):
                getattr(module, name)

    def test_encode_snapshot_honors_patched_seam(self, monkeypatch):
        """Internal solve paths resolve `encode_snapshot` through the
        package namespace at call time, so patching it intercepts every
        encode (the seam the encode-counting tests rely on)."""
        from karpenter_tpu.metrics.producers import pendingcapacity as PC
        from karpenter_tpu.metrics.registry import GaugeRegistry
        from karpenter_tpu.store.store import Store

        calls = []
        real = PC.encode_snapshot

        def counting(*args, **kwargs):
            calls.append(1)
            return real(*args, **kwargs)

        monkeypatch.setattr(PC, "encode_snapshot", counting)
        store = Store()
        from karpenter_tpu.api.core import (
            Container,
            ObjectMeta,
            Pod,
            PodSpec,
        )
        from karpenter_tpu.api.metricsproducer import (
            MetricsProducer,
            MetricsProducerSpec,
            PendingCapacitySpec,
        )
        from karpenter_tpu.utils.quantity import Quantity

        store.create(
            MetricsProducer(
                metadata=ObjectMeta(name="mp"),
                spec=MetricsProducerSpec(
                    pending_capacity=PendingCapacitySpec(
                        node_selector={"g": "a"}
                    )
                ),
            )
        )
        store.create(
            Pod(
                metadata=ObjectMeta(name="p0"),
                spec=PodSpec(
                    containers=[
                        Container(requests={"cpu": Quantity.parse("1")})
                    ]
                ),
            )
        )
        mps = store.list("MetricsProducer")
        PC.solve_pending(store, mps, GaugeRegistry())
        assert calls == [1]


class TestCoalesceTiming:
    def test_fixed_window_holds_for_stragglers(self):
        """adaptive_window=False pins the pre-overhaul fixed window: a
        submit landing inside it joins the open batch."""
        svc = SolverService(
            registry=GaugeRegistry(), window_s=0.2, max_batch=4,
            adaptive_window=False,
        )
        try:
            results = {}

            def submit(name, delay):
                time.sleep(delay)
                results[name] = svc.solve(
                    make_inputs(25, 3, seed=len(name)), backend="xla"
                )

            threads = [
                threading.Thread(target=submit, args=("a", 0.0)),
                threading.Thread(target=submit, args=("b", 0.05)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert len(results) == 2
            assert svc.stats.dispatches == 1
            assert svc.stats.last_coalesce_factor == 2
        finally:
            svc.close()

    def test_adaptive_idle_queue_skips_the_window(self):
        """The tentpole fix: a lone request on an idle queue must NOT
        wait out the batching timer. With a punitive 0.5 s max window,
        sequential solves complete in far less than one window each."""
        svc = SolverService(
            registry=GaugeRegistry(), window_s=0.5, max_batch=8
        )
        try:
            inputs = make_inputs(40, 4, seed=1)
            svc.solve(inputs, backend="xla")  # warm the compile
            t0 = time.perf_counter()
            for _ in range(3):
                svc.solve(inputs, backend="xla")
            elapsed = time.perf_counter() - t0
            assert elapsed < 0.5, (
                f"3 idle-queue solves took {elapsed:.3f}s — the fixed "
                "window is back"
            )
            assert svc.stats.immediate_dispatches >= 3
        finally:
            svc.close()

    def test_adaptive_window_widens_under_concurrency(self):
        """Concurrent submitters must still coalesce (the acceptance
        criterion: coalesce factor >= 4 under concurrency >= 4) even
        with the adaptive window dispatching idle traffic immediately."""
        svc = SolverService(
            registry=GaugeRegistry(), window_s=0.05, max_batch=8
        )
        try:
            inputs = [make_inputs(60 + i, 4, seed=i) for i in range(8)]
            svc.solve(make_inputs(50, 4, seed=99), backend="xla")  # warm
            results = [None] * 8
            barrier = threading.Barrier(8)

            def submit(i):
                barrier.wait()
                results[i] = svc.solve(inputs[i], backend="xla")

            threads = [
                threading.Thread(target=submit, args=(i,))
                for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert all(r is not None for r in results)
            assert svc.stats.last_coalesce_factor >= 4
        finally:
            svc.close()


class TestPipelinedDispatch:
    def test_sustained_load_overlaps_dispatches(self):
        """With max_batch capping each dispatch, a burst larger than one
        batch must pipeline: at least one dispatch is issued while the
        previous one is still in flight — and every result stays
        bitwise-correct."""
        svc = SolverService(
            registry=GaugeRegistry(), window_s=0.02, max_batch=2,
            pipeline_depth=1,
        )
        try:
            inputs = [make_inputs(30 + i, 3, seed=i) for i in range(6)]
            svc.solve(inputs[0], backend="xla")  # warm batch=1
            futures = [
                svc.submit(inp, backend="xla") for inp in inputs
            ]
            results = [f.result(30.0) for f in futures]
            for inp, out in zip(inputs, results):
                assert_outputs_equal(out, B.solve(inp, backend="xla"))
            assert svc.stats.pipeline_overlaps >= 1
        finally:
            svc.close()

    def test_closed_loop_concurrency_overlaps_dispatches(self):
        """The PR 8 satellite pin: CLOSED-LOOP concurrent callers (the
        shape that used to record pipeline_overlaps: 0)
        must record >= 1 overlap. The fix: a lone coalesced batch with
        nothing in flight splits into pipeline chunks, so chunk k+1's
        dispatch overlaps chunk k's compute — and every result stays
        bitwise-correct."""
        svc = SolverService(
            registry=GaugeRegistry(), window_s=0.2, max_batch=8,
            adaptive_window=False, pipeline_depth=1,
        )
        try:
            inputs = [make_inputs(30 + i, 3, seed=i) for i in range(8)]
            svc.solve(inputs[0], backend="xla")  # warm
            results = [None] * 8
            barrier = threading.Barrier(8)

            def submit(i):
                barrier.wait()
                results[i] = svc.solve(inputs[i], backend="xla")

            threads = [
                threading.Thread(target=submit, args=(i,))
                for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for inp, out in zip(inputs, results):
                assert_outputs_equal(out, B.solve(inp, backend="xla"))
            assert svc.stats.pipeline_splits >= 1
            assert svc.stats.pipeline_overlaps >= 1
        finally:
            svc.close()

    def test_small_batches_never_split(self):
        """The coalescing contract: batches below the split floor keep
        riding ONE dispatch (the fixed-window straggler test above pins
        dispatches == 1 for a pair — this pins the boundary)."""
        svc = SolverService(
            registry=GaugeRegistry(), window_s=0.2, max_batch=8,
            adaptive_window=False, pipeline_depth=1,
        )
        try:
            inputs = [make_inputs(25 + i, 3, seed=i) for i in range(3)]
            svc.solve(inputs[0], backend="xla")  # warm
            dispatches = svc.stats.dispatches
            results = [None] * 3
            barrier = threading.Barrier(3)

            def submit(i):
                barrier.wait()
                results[i] = svc.solve(inputs[i], backend="xla")

            threads = [
                threading.Thread(target=submit, args=(i,))
                for i in range(3)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert svc.stats.dispatches - dispatches == 1
            assert svc.stats.pipeline_splits == 0
            for inp, out in zip(inputs, results):
                assert_outputs_equal(out, B.solve(inp, backend="xla"))
        finally:
            svc.close()

    def test_pipeline_depth_zero_is_serial(self):
        svc = SolverService(
            registry=GaugeRegistry(), window_s=0.0, pipeline_depth=0
        )
        try:
            inputs = make_inputs(20, 3, seed=5)
            assert_outputs_equal(
                svc.solve(inputs, backend="xla"),
                B.solve(inputs, backend="xla"),
            )
            assert svc.stats.pipeline_overlaps == 0
        finally:
            svc.close()

    def test_inflight_device_failure_degrades_to_numpy(self):
        """An async dispatch whose failure surfaces at drain time (not
        dispatch time) must still answer every request from numpy."""
        svc = SolverService(registry=GaugeRegistry(), window_s=0.0)
        try:
            import dataclasses

            calls = {"n": 0}
            real = svc._compiled_for

            def exploding(cache_key, donate=False):
                fn, fresh = real(cache_key, donate=donate)

                def wrapped(stacked, buckets):
                    calls["n"] += 1
                    out = fn(stacked, buckets)
                    # poison the result so the block_until_ready in the
                    # drain path raises (async-failure analog)
                    return dataclasses.replace(
                        out, assigned=_Exploding()
                    )

                return wrapped, fresh

            class _Exploding:
                def block_until_ready(self):
                    raise RuntimeError("injected in-flight failure")

                @property
                def shape(self):
                    return (0,)

            svc._compiled_for = exploding
            inputs = make_inputs(15, 3, seed=9)
            out = svc.solve(inputs, backend="xla")
            assert_outputs_equal(out, binpack_numpy(inputs, buckets=32))
            assert svc.stats.fallbacks == 1
            assert calls["n"] == 1
        finally:
            svc.close()


class TestDonationParity:
    def test_donating_compile_matches_non_donating(self):
        """The donation-backed program family must produce outputs
        bitwise-identical to the non-donating family on the same
        stacked operands (donation changes buffer lifetime, never
        values) — compiled explicitly on BOTH families regardless of
        whether this backend supports donation."""
        import warnings

        import jax

        from karpenter_tpu.solver.bucketing import (
            bucket_shape,
            pad_to_bucket,
        )
        from karpenter_tpu.solver.service import _stack_inputs

        svc = SolverService(registry=GaugeRegistry(), window_s=0.0)
        try:
            inputs = make_inputs(40, 4, seed=11)
            shape = bucket_shape(inputs)
            padded = pad_to_bucket(inputs, shape)
            key = ("xla", shape, 1, 32, (False, False, False, False),
                   "map")
            keep, _ = svc._compiled_for(key, donate=False)
            donate, _ = svc._compiled_for(key, donate=True)
            out_keep = jax.device_get(
                keep(jax.device_put(_stack_inputs([padded])), 32)
            )
            with warnings.catch_warnings():
                # on CPU donation is a no-op and jax says so per
                # executable; this test compiles the donating family
                # here deliberately
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable",
                )
                out_donate = jax.device_get(
                    donate(jax.device_put(_stack_inputs([padded])), 32)
                )
            for name in (
                "assigned", "assigned_count", "nodes_needed",
                "lp_bound", "unschedulable",
            ):
                np.testing.assert_array_equal(
                    np.asarray(getattr(out_donate, name)),
                    np.asarray(getattr(out_keep, name)),
                    err_msg=name,
                )
            # two distinct compile-cache families, no aliasing
            assert svc.stats.compile_cache_misses == 2
        finally:
            svc.close()


class TestLatencyRegressionGuard:
    def test_idle_service_p50_within_2x_of_direct(self):
        """The coalescing tax must not return: on an idle queue the
        service path stays within 2x of a direct ops/binpack call on a
        small fixed workload (the non-slow canary for the idle-queue
        path)."""
        inputs = make_inputs(256, 8, seed=42)
        iters = 15

        def p50(fn):
            times = []
            for _ in range(iters):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            return float(np.percentile(times, 50))

        import jax

        def direct():
            jax.block_until_ready(B.solve(inputs, backend="xla"))

        direct()  # warm
        direct_p50 = p50(direct)

        svc = SolverService(registry=GaugeRegistry(), max_batch=8)
        try:
            svc.solve(inputs, backend="xla")  # warm
            service_p50 = p50(
                lambda: svc.solve(inputs, backend="xla")
            )
        finally:
            svc.close()
        # generous absolute floor: at sub-millisecond direct solves the
        # thread handoff dominates and the RATIO is meaningless noise
        assert service_p50 <= max(2 * direct_p50, 0.01), (
            f"idle service p50 {service_p50 * 1e3:.2f}ms vs direct "
            f"{direct_p50 * 1e3:.2f}ms — coalescing tax is back"
        )


class TestShardedDispatch:
    """The PR 8 tentpole (docs/solver-service.md "Sharded dispatch"):
    above the cell threshold a request routes through the pods x groups
    mesh behind the SAME service seam, bit-identical to the
    single-device program, degrading shard -> single-device -> numpy."""

    def test_above_threshold_routes_through_mesh_with_parity(self):
        svc = SolverService(registry=GaugeRegistry(), shard_threshold=1)
        try:
            inputs = make_inputs(333, 13, seed=3, weighted=True,
                                 constrained=True)
            out = svc.solve(inputs, backend="xla")
            assert svc.stats.shard_requests == 1
            assert svc.stats.shard_dispatches == 1
            assert svc.stats.fallbacks == 0
            assert_outputs_equal(out, B.solve(inputs, backend="xla"))
            assert_outputs_equal(out, binpack_numpy(inputs, buckets=32))
            # the sharded route is visible on the latency surface too
            assert "upload" in svc.stage_percentiles()
        finally:
            svc.close()

    def test_below_threshold_stays_single_device(self):
        svc = SolverService(
            registry=GaugeRegistry(), shard_threshold=10**9
        )
        try:
            inputs = make_inputs(64, 4, seed=1)
            out = svc.solve(inputs, backend="xla")
            assert svc.stats.shard_requests == 0
            assert svc.stats.shard_dispatches == 0
            assert_outputs_equal(out, B.solve(inputs, backend="xla"))
        finally:
            svc.close()

    def test_threshold_zero_disables_sharding(self):
        svc = SolverService(registry=GaugeRegistry(), shard_threshold=0)
        try:
            svc.solve(make_inputs(300, 12, seed=2), backend="xla")
            assert svc.stats.shard_dispatches == 0
            assert svc._shard_mesh() is None
        finally:
            svc.close()

    def test_single_device_mesh_shapes_stay_unsharded(self):
        """An explicit 1x1 --shard-mesh (or a 1-device cap) must NOT
        build a mesh: routing above-threshold traffic through the
        inline sharded path with zero parallelism gain while reporting
        sharding active is strictly worse than the single-device
        program."""
        for kwargs in (
            {"shard_mesh_shape": (1, 1)},
            {"shard_devices": 1},
        ):
            svc = SolverService(
                registry=GaugeRegistry(), shard_threshold=1, **kwargs
            )
            try:
                inputs = make_inputs(128, 6, seed=6)
                out = svc.solve(inputs, backend="xla")
                assert svc._shard_mesh() is None, kwargs
                assert svc.stats.shard_dispatches == 0, kwargs
                assert_outputs_equal(out, B.solve(inputs, backend="xla"))
            finally:
                svc.close()

    def test_mesh_build_failure_is_counted_and_served_unsharded(self):
        """A mesh that cannot be built (here: more devices than exist)
        is logged and counted in stats.shard_mesh_errors — the number a
        multi-chip run fails on — while the single-device program
        serves, and stats.served names the kernel that answered."""
        svc = SolverService(
            registry=GaugeRegistry(), shard_threshold=1,
            shard_mesh_shape=(64, 2),
        )
        try:
            inputs = make_inputs(150, 7, seed=5)
            out = svc.solve(inputs, backend="xla")
            assert_outputs_equal(out, B.solve(inputs, backend="xla"))
            assert svc.stats.shard_mesh_errors == 1
            assert svc.stats.shard_dispatches == 0
            assert svc.stats.served == {"solve/xla": 1}
        finally:
            svc.close()

    def test_shard_failure_degrades_to_single_device_then_sticks(self):
        """The ladder: a shard-path failure re-runs the SAME batch on
        the single-device program (answered on device, NOT from numpy)
        and stops routing new traffic to the mesh; reset_caches — the
        recovery-boot seam — re-arms it."""
        svc = SolverService(registry=GaugeRegistry(), shard_threshold=1)

        def explode(*_a, **_k):
            raise RuntimeError("injected shard failure")

        svc._sharded_xla = explode
        try:
            inputs = make_inputs(200, 9, seed=4)
            out = svc.solve(inputs, backend="xla")
            assert_outputs_equal(out, B.solve(inputs, backend="xla"))
            assert svc.stats.shard_fallbacks == 1
            assert svc.stats.fallbacks == 0  # device answered, not numpy
            assert svc._shard_broken
            # subsequent traffic routes single-device straight away
            out2 = svc.solve(inputs, backend="xla")
            assert_outputs_equal(out2, B.solve(inputs, backend="xla"))
            assert svc.stats.shard_fallbacks == 1
            svc.reset_caches()
            assert not svc._shard_broken
        finally:
            svc.close()

    def test_shard_and_single_device_compile_families_never_alias(self):
        """Shard-count is part of the bucket key: the same bucket shape
        compiled sharded and unsharded must be two cache entries."""
        svc = SolverService(registry=GaugeRegistry(), shard_threshold=1)
        try:
            inputs = make_inputs(128, 6, seed=8)
            svc.solve(inputs, backend="xla")
            misses_sharded = svc.stats.compile_cache_misses
            assert misses_sharded >= 1
            svc.shard_threshold = 10**12  # same shapes, unsharded now
            svc.solve(inputs, backend="xla")
            assert svc.stats.compile_cache_misses == misses_sharded + 1
            # and a REPEAT on each route hits its own program
            hits = svc.stats.compile_cache_hits
            svc.solve(inputs, backend="xla")
            svc.shard_threshold = 1
            svc.solve(inputs, backend="xla")
            assert svc.stats.compile_cache_hits == hits + 2
            assert svc.stats.compile_cache_misses == misses_sharded + 1
        finally:
            svc.close()

    def test_consolidate_routes_through_mesh_with_parity(self):
        svc = SolverService(registry=GaugeRegistry(), shard_threshold=1)
        try:
            inputs_list = [
                make_inputs(96, 8, seed=10 + i) for i in range(4)
            ]
            results = svc.consolidate(inputs_list, backend="xla")
            assert svc.stats.shard_dispatches >= 1
            assert svc.stats.fallbacks == 0
            for inputs, out in zip(inputs_list, results):
                assert_outputs_equal(
                    out, B.solve(inputs, backend="xla")
                )
        finally:
            svc.close()

    def test_forecast_and_preempt_never_shard(self):
        """This PR pins the forecast/preempt seams to the single-device
        path: their kernels carry no sharded parity pin, so no request
        of theirs may acquire a shard key even with the threshold
        floored."""
        from karpenter_tpu.forecast.models import ForecastInputs

        svc = SolverService(registry=GaugeRegistry(), shard_threshold=1)
        try:
            rng = np.random.default_rng(0)
            S, T = 6, 16
            svc.forecast(
                ForecastInputs(
                    values=rng.uniform(0, 10, (S, T)).astype(np.float32),
                    valid=np.ones((S, T), bool),
                    times=np.tile(
                        (np.arange(T, dtype=np.float32) - (T - 1)) * 10,
                        (S, 1),
                    ),
                    weights=np.ones((S, T), np.float32),
                    horizon=np.full(S, 30.0, np.float32),
                    step_s=np.full(S, 10.0, np.float32),
                    model=np.zeros(S, np.int32),
                    season=np.zeros(S, np.int32),
                    alpha=np.full(S, 0.5, np.float32),
                    beta=np.full(S, 0.2, np.float32),
                    gamma=np.full(S, 0.2, np.float32),
                )
            )
            assert svc.stats.shard_dispatches == 0
            assert svc.stats.shard_requests == 0
        finally:
            svc.close()


class TestUploadStage:
    def test_upload_stage_and_gauge_recorded(self):
        """The satellite: host->device transfer isolated as its own
        stage (the measured baseline ROADMAP item 4's device-resident
        state attacks) and published as karpenter_solver_upload_ms."""
        registry = GaugeRegistry()
        svc = SolverService(registry=registry)
        try:
            svc.solve(make_inputs(64, 4, seed=3), backend="xla")
            stages = svc.stage_percentiles()
            assert "upload" in stages
            assert stages["upload"]["n"] >= 1
            svc.publish_gauges()
            text = registry.expose_text()
            assert "karpenter_solver_upload_ms" in text
        finally:
            svc.close()


class TestCompilePrewarm:
    """Boot-time compile pre-warm (ISSUE 14 satellite,
    docs/solver-service.md "Compile pre-warm"): one tiny real dispatch
    per always-on family through the normal queue, counted in the
    prewarm gauges, skipped once warmed, re-armed by reset_caches, and
    never able to block boot."""

    def test_warms_both_families_and_skips_on_rewarm(self):
        registry = GaugeRegistry()
        service = SolverService(registry=registry, backend="xla")
        try:
            report = service.prewarm()
            assert set(report) == {"solve", "decide"}
            for family in ("solve", "decide"):
                assert report[family]["skipped"] is False
                assert report[family]["ms"] >= 0.0
                assert registry.gauge(
                    "solver", "prewarm_compiles_total"
                ).get(family, "-") == 1.0
                assert registry.gauge(
                    "solver", "prewarm_ms"
                ).get(family, "-") is not None
            # the solve family rides the queue's compile counters: a
            # cold service's warm-up IS a fresh compile there; decide
            # rides jax.jit's own cache, so the report must NOT claim
            # a (meaningless) zero for it
            assert report["solve"]["fresh_compiles"] >= 1
            assert "fresh_compiles" not in report["decide"]

            again = service.prewarm()
            assert again == {
                "solve": {"skipped": True},
                "decide": {"skipped": True},
            }
            assert registry.gauge(
                "solver", "prewarm_compiles_total"
            ).get("solve", "-") == 1.0, "a skip must not re-count"
        finally:
            service.close()

    def test_reset_caches_rearms_the_warmup(self):
        service = SolverService(registry=GaugeRegistry(), backend="xla")
        try:
            service.prewarm()
            service.reset_caches()  # the recovery-boot seam
            report = service.prewarm(families=("solve",))
            assert report["solve"]["skipped"] is False, (
                "a reset plane must be able to re-warm"
            )
        finally:
            service.close()

    def test_unknown_family_degrades_never_raises(self):
        service = SolverService(registry=GaugeRegistry())
        try:
            report = service.prewarm(families=("solve", "nope"))
            assert report["nope"] == {
                "skipped": False, "error": "ValueError",
            }
            assert report["solve"]["skipped"] is False, (
                "one family's failure must not stop the rest"
            )
            # a failed family is retryable (not marked warmed)
            assert "nope" not in service._prewarmed
        finally:
            service.close()

    def test_runtime_wires_prewarm_compile_option(self):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        runtime = KarpenterRuntime(
            Options(prewarm_compile=True),
            cloud_provider_factory=FakeFactory(),
        )
        try:
            gauge = runtime.registry.gauge(
                "solver", "prewarm_compiles_total"
            )
            assert gauge.get("solve", "-") == 1.0
            assert gauge.get("decide", "-") == 1.0
        finally:
            runtime.close()
