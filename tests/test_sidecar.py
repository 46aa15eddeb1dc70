"""Solver sidecar: codec roundtrip, gRPC server/client over loopback, and
the producer path routed through a remote solver.

The sidecar is the BASELINE.json north-star process split (control plane ->
gRPC -> JAX solver); these tests run server and client in one process over
an ephemeral loopback port.
"""

import numpy as np
import pytest

from karpenter_tpu.ops.binpack import BinPackInputs, binpack
from karpenter_tpu.ops.decision import DecisionInputs, decide_jit
from karpenter_tpu.sidecar import SolverClient, SolverServer, codec

from test_binpack import make_inputs


@pytest.fixture(scope="module")
def server():
    s = SolverServer(port=0, host="127.0.0.1")
    port = s.start()
    yield f"127.0.0.1:{port}"
    s.stop()


@pytest.fixture(scope="module")
def client(server):
    with SolverClient(server) as c:
        yield c


class TestCodec:
    def test_roundtrip_arrays(self):
        arrays = {
            "a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.asarray([True, False, True]),
            "scalar": np.asarray(7, np.int32),
        }
        packed = codec.pack(arrays, meta={"k": "v"})
        out, meta = codec.unpack(packed)
        assert meta == {"k": "v"}
        for name, arr in arrays.items():
            np.testing.assert_array_equal(out[name], arr)
            assert out[name].dtype == arr.dtype
            assert out[name].shape == arr.shape  # 0-d stays 0-d

    def test_roundtrip_dataclass(self):
        inputs = make_inputs(
            pod_requests=[[1, 1], [3, 1]], group_allocatable=[[4, 4]]
        )
        back, _ = codec.unpack_dataclass(
            BinPackInputs, codec.pack_dataclass(inputs)
        )
        np.testing.assert_array_equal(
            np.asarray(back.pod_requests), np.asarray(inputs.pod_requests)
        )
        np.testing.assert_array_equal(
            np.asarray(back.group_taints), np.asarray(inputs.group_taints)
        )

    def test_tensor_set_mismatch_rejected(self):
        packed = codec.pack({"bogus": np.zeros(3)})
        with pytest.raises(ValueError):
            codec.unpack_dataclass(BinPackInputs, packed)


class TestSolverRPC:
    def test_health(self, client):
        ok, meta = client.health()
        assert ok
        assert "backend" in meta

    def test_solve_matches_inprocess(self, client):
        inputs = make_inputs(
            pod_requests=[[1, 1], [3, 1], [9, 9]],
            group_allocatable=[[2, 2], [4, 4]],
        )
        local = binpack(inputs, buckets=16)
        remote = client.solve(inputs, buckets=16)
        np.testing.assert_array_equal(
            np.asarray(remote.assigned), np.asarray(local.assigned)
        )
        np.testing.assert_array_equal(
            np.asarray(remote.nodes_needed), np.asarray(local.nodes_needed)
        )
        assert int(remote.unschedulable) == int(local.unschedulable)

    def test_decide_matches_inprocess(self, client):
        n, m = 4, 2
        inputs = DecisionInputs(
            metric_value=np.asarray([[0.85, 0], [41, 0], [1, 0], [5, 0]], np.float32),
            target_value=np.asarray([[0.6, 1], [4, 1], [2, 1], [5, 1]], np.float32),
            target_type=np.full((n, m), 2, np.int32),
            metric_valid=np.asarray([[True, False]] * n),
            spec_replicas=np.asarray([5, 1, 3, 2], np.int32),
            status_replicas=np.asarray([5, 1, 3, 2], np.int32),
            min_replicas=np.zeros(n, np.int32),
            max_replicas=np.full(n, 100, np.int32),
            up_window=np.zeros(n, np.int32),
            down_window=np.zeros(n, np.int32),
            up_policy=np.zeros(n, np.int32),
            down_policy=np.zeros(n, np.int32),
            last_scale_time=np.zeros(n, np.float32),
            has_last_scale=np.zeros(n, bool),
            now=np.asarray(1000.0, np.float32),
            up_ptype=np.zeros((n, 1), np.int32),
            up_pvalue=np.asarray([[4]] * n, np.int32),
            up_pperiod=np.full((n, 1), 60, np.int32),
            up_pvalid=np.asarray([[True], [False], [False], [False]]),
            down_ptype=np.zeros((n, 1), np.int32),
            down_pvalue=np.ones((n, 1), np.int32),
            down_pperiod=np.full((n, 1), 60, np.int32),
            down_pvalid=np.zeros((n, 1), bool),
        )
        local = decide_jit(inputs)
        remote = client.decide(inputs)
        np.testing.assert_array_equal(
            np.asarray(remote.desired), np.asarray(local.desired)
        )
        np.testing.assert_array_equal(
            np.asarray(remote.able_to_scale), np.asarray(local.able_to_scale)
        )

    def test_error_surfaces_as_status(self, client, server):
        import grpc

        # a malformed request must produce INTERNAL with a message, not a
        # hung/dead channel
        with SolverClient(server) as c:
            with pytest.raises(grpc.RpcError) as e:
                c._solve(b"\x00" * 4, timeout=5.0)
            assert e.value.code() == grpc.StatusCode.INTERNAL


class TestProducerThroughSidecar:
    def test_pending_capacity_via_remote_solver(self, client):
        """The full producer path with the sidecar at the Algorithm seam."""
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
        from karpenter_tpu.api.metricsproducer import (
            MetricsProducer,
            MetricsProducerSpec,
            PendingCapacitySpec,
        )
        from karpenter_tpu.metrics.registry import GaugeRegistry
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            solve_pending,
        )
        from karpenter_tpu.store import Store

        store = Store()
        store.create(
            Node(
                metadata=ObjectMeta(
                    name="n1", labels={"pool": "a"}
                ),
                spec=NodeSpec(),
                status=NodeStatus(
                    allocatable=resource_list(cpu="8", memory="16Gi", pods="16"),
                    conditions=[NodeCondition("Ready", "True")],
                ),
            )
        )
        store.create(
            Pod(
                metadata=ObjectMeta(name="p1"),
                spec=PodSpec(),  # pending, no node
            )
        )
        mp = MetricsProducer(
            metadata=ObjectMeta(name="pending"),
            spec=MetricsProducerSpec(
                pending_capacity=PendingCapacitySpec(
                    node_selector={"pool": "a"}
                )
            ),
        )
        store.create(mp)
        registry = GaugeRegistry()
        solve_pending(store, [mp], registry, solver=client.solve)
        status = mp.status.pending_capacity
        assert status is not None
        assert status.pending_pods == 1
        assert status.additional_nodes_needed >= 1


class TestDecideSplit:
    def test_control_plane_decides_through_sidecar(self):
        """With --solver-uri the decision kernel rides the gRPC split too:
        the full HA pipeline (metric read -> remote decide -> scale write)
        must produce the canonical 85%/60%/5 -> 8 result with the device
        math in the sidecar process."""
        from karpenter_tpu.api.core import ObjectMeta
        from karpenter_tpu.api.horizontalautoscaler import (
            CrossVersionObjectReference,
            HorizontalAutoscaler,
            HorizontalAutoscalerSpec,
            Metric,
            MetricTarget,
            PrometheusMetricSource,
        )
        from karpenter_tpu.api.scalablenodegroup import (
            ScalableNodeGroup,
            ScalableNodeGroupSpec,
        )
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options
        from karpenter_tpu.sidecar.server import SolverServer

        server = SolverServer(port=0)
        port = server.start()
        try:
            provider = FakeFactory()
            provider.node_replicas["g"] = 5
            rt = KarpenterRuntime(
                Options(
                    cloud_provider="fake",
                    solver_uri=f"127.0.0.1:{port}",
                ),
                cloud_provider_factory=provider,
            )
            # the shared solve service fronts the sidecar client: the
            # autoscaler submits through the service, whose decider seam
            # is the remote decide — device math stays out of process
            assert rt.batch_autoscaler.decider == rt.solver_service.decide
            assert rt.solver_service._decider == rt.solver_client.decide
            assert (
                rt.solver_service.device_solver == rt.solver_client.solve
            )
            gauge = rt.registry.register("reserved_capacity",
                                         "cpu_utilization")
            gauge.set("g", "default", 0.85)
            rt.store.create(ScalableNodeGroup(
                metadata=ObjectMeta(name="g"),
                spec=ScalableNodeGroupSpec(
                    replicas=5, type="FakeNodeGroup", id="g")))
            rt.store.create(HorizontalAutoscaler(
                metadata=ObjectMeta(name="ha"),
                spec=HorizontalAutoscalerSpec(
                    scale_target_ref=CrossVersionObjectReference(
                        kind="ScalableNodeGroup", name="g"),
                    min_replicas=3, max_replicas=23,
                    metrics=[Metric(prometheus=PrometheusMetricSource(
                        query='karpenter_reserved_capacity_cpu_utilization{name="g"}',
                        target=MetricTarget(type="Utilization", value=60)))])))
            rt.manager.reconcile_all()
            ha = rt.store.get("HorizontalAutoscaler", "default", "ha")
            assert ha.status.desired_replicas == 8
            rt.close()  # release the gRPC channel before the server stops
        finally:
            server.stop()


class TestCompileCache:
    def test_arm_writes_entries_to_the_flag_directory(
        self, tmp_path, monkeypatch
    ):
        """--compile-cache-dir wires JAX's persistent compilation cache
        (restart survival for the TPU solver compiles): with
        JAX_COMPILATION_CACHE_DIR unset, a fresh jit lands an entry in
        the flag's directory (the wiring, not just the flag)."""
        import jax
        import numpy as np
        from jax.experimental.compilation_cache import compilation_cache

        from karpenter_tpu.utils.backend import arm_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        cache = tmp_path / "xla-cache"
        old_dir = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_enable_compilation_cache", True)
        try:
            assert arm_compile_cache(str(cache)) == str(cache)
            assert jax.config.jax_compilation_cache_dir == str(cache)
            # floor the write thresholds: CPU compiles finish in <1s
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0
            )
            fn = jax.jit(lambda x: x * 2.0 + 1.0)
            fn(np.arange(8, dtype=np.float32)).block_until_ready()
            assert any(cache.iterdir()), "no cache entry written"
        finally:
            jax.config.update("jax_enable_compilation_cache", False)
            jax.config.update("jax_compilation_cache_dir", old_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0
            )
            compilation_cache.reset_cache()
