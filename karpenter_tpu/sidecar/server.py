"""gRPC solver sidecar server: owns the TPU, serves Solve/Decide/Health.

The service/method names and message semantics are proto/solver.proto;
handlers are registered generically (no generated stubs, see
sidecar/__init__.py). Solve routes the pending-pods bin-pack through the
process-shared solve service (solver/service.py): concurrent Solve RPCs
from the gRPC thread pool coalesce into one batched device call, shapes
are bucketed through the shared compile cache, and a sick device path
degrades to the numpy backend instead of erroring every caller. Decide
runs the batched HPA decision kernel (ops/decision.decide_jit). Both are
stateless: all inputs arrive in the request, matching the reference's
checkpoint/resume posture (all durable state in the store; SURVEY.md §5).
"""

from __future__ import annotations

import json
from concurrent import futures
from typing import Optional

import numpy as np

from karpenter_tpu.observability import default_tracer
from karpenter_tpu.sidecar import codec

SERVICE = "karpenter.solver.v1.Solver"

# tenant-scoped RPCs (docs/multitenancy.md): a multi-tenant control
# plane stamps its tenant id into this gRPC metadata key; the server
# attributes solver traffic per tenant on /metrics
# (karpenter_tenant_rpcs_total{name=<tenant>}).
TENANT_METADATA_KEY = "x-karpenter-tenant"


def _solve(request: bytes) -> bytes:
    from karpenter_tpu.ops.binpack import BinPackInputs
    from karpenter_tpu.solver import default_service

    # optional tensors (pod_weight) may be absent from the wire; the codec
    # fills dataclass defaults and rejects missing-required/extra tensors
    inputs, meta = codec.unpack_dataclass(BinPackInputs, request)
    buckets = int(meta.get("buckets", 32))
    backend = meta.get("backend", "auto")
    with default_tracer().span("sidecar.solve"):
        # the shared service owns device access: concurrent RPCs from the
        # gRPC worker pool coalesce into one dispatch, and outputs come
        # back as host numpy ready for the wire
        out = default_service().solve(
            inputs, buckets=buckets, backend=backend
        )
    return codec.pack_dataclass(out)


def _decide(request: bytes) -> bytes:
    import jax

    from karpenter_tpu.ops.decision import DecisionInputs, decide_jit

    inputs, _ = codec.unpack_dataclass(DecisionInputs, request)
    with default_tracer().span("sidecar.decide"):
        out = decide_jit(jax.device_put(inputs))
        jax.block_until_ready(out)
    return codec.pack_dataclass(out)


def _health(request: bytes) -> bytes:
    import jax

    return codec.pack(
        {"ok": np.asarray(True)},
        meta={
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
        },
    )


class SolverServer:
    """port=0 binds an ephemeral port; `port` holds the bound port after
    start()."""

    def __init__(self, port: int = 9090, host: str = "0.0.0.0",
                 max_workers: int = 4, registry=None):
        from karpenter_tpu.metrics.registry import default_registry

        self.host = host
        self.port = port
        self.max_workers = max_workers
        self._server = None
        registry = registry if registry is not None else default_registry()
        # per-tenant RPC attribution (docs/multitenancy.md): counted by
        # the tenant id the client stamped into TENANT_METADATA_KEY;
        # single-tenant clients (no metadata) count nothing. The label
        # value is CLIENT-SUPPLIED, so it is sanitized and the distinct
        # series are CAPPED — an adversarial or misconfigured fleet
        # stamping unbounded ids must not grow /metrics without bound;
        # past the cap, traffic counts under the "_overflow" series.
        self._c_tenant_rpcs = registry.register(
            "tenant", "rpcs_total", kind="counter"
        )
        self._tenant_labels: set = set()

    # distinct tenant label values one server will track; chosen well
    # above any sane tenant fleet per sidecar, far below scrape pain
    MAX_TENANT_SERIES = 1024

    def _tenant_label(self, value: str):
        """Sanitized, cardinality-capped label for a client-supplied
        tenant id: printable, bounded length, no label-breaking
        characters (the exposition escaper handles quoting, this bounds
        SIZE); ids beyond the series cap collapse to "_overflow"."""
        value = str(value)[:64]
        if not value or not value.isprintable():
            return None
        if value in self._tenant_labels:
            return value
        if len(self._tenant_labels) >= self.MAX_TENANT_SERIES:
            return "_overflow"
        self._tenant_labels.add(value)
        return value

    def _count_tenant(self, context) -> None:
        try:
            for key, value in context.invocation_metadata() or ():
                if key == TENANT_METADATA_KEY and value:
                    label = self._tenant_label(value)
                    if label is not None:
                        self._c_tenant_rpcs.inc(label, "-")
                    return
        except Exception:  # noqa: BLE001 — attribution must never fail an RPC
            pass

    def start(self) -> int:
        import grpc

        def wrap(fn):
            def handler(request: bytes, context) -> bytes:
                self._count_tenant(context)
                try:
                    return fn(request)
                except Exception as e:  # noqa: BLE001 — errors go to the
                    # client as INTERNAL with the message, not a dead channel
                    context.abort(
                        grpc.StatusCode.INTERNAL, f"{type(e).__name__}: {e}"
                    )

            return grpc.unary_unary_rpc_method_handler(
                handler,
                request_deserializer=None,  # raw bytes both ways
                response_serializer=None,
            )

        handlers = grpc.method_handlers_generic_handler(
            SERVICE,
            {
                "Solve": wrap(_solve),
                "Decide": wrap(_decide),
                "Health": wrap(_health),
            },
        )
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=self.max_workers)
        )
        self._server.add_generic_rpc_handlers((handlers,))
        self.port = self._server.add_insecure_port(f"{self.host}:{self.port}")
        if self.port == 0:
            raise RuntimeError(f"could not bind {self.host}")
        self._server.start()
        return self.port

    def wait(self) -> None:
        self._server.wait_for_termination()

    def stop(self, grace: Optional[float] = 1.0) -> None:
        if self._server is not None:
            self._server.stop(grace).wait()
            self._server = None


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="karpenter-tpu solver sidecar")
    ap.add_argument("--port", type=int, default=9090)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument(
        "--warmup-pods",
        type=int,
        default=0,
        help="pre-compile the bin-pack at this pod count before serving",
    )
    ap.add_argument(
        "--multihost",
        action="store_true",
        help="join a multi-host jax.distributed deployment before serving "
        "(topology from TPU pod metadata or JAX_COORDINATOR_ADDRESS/"
        "JAX_NUM_PROCESSES/JAX_PROCESS_ID; see parallel/multihost.py)",
    )
    ap.add_argument(
        "--compile-cache-dir",
        default=None,
        help="persistent XLA compilation cache directory: a restarted "
        "sidecar reloads compiled solver programs instead of paying the "
        "TPU compile again; point it at an emptyDir/PVC in the pod spec. "
        "JAX_COMPILATION_CACHE_DIR wins over it; with neither, the "
        "cache is <checkout>/.jax_compile_cache",
    )
    args = ap.parse_args(argv)

    from karpenter_tpu.utils.backend import arm_compile_cache, require_backend

    arm_compile_cache(args.compile_cache_dir)

    if args.multihost:
        # the join must precede ANY backend touch (jax.distributed
        # refuses after XLA initializes). A configured-but-broken
        # topology raises and kills the process — correct: N independent
        # solvers would double-solve the fleet.
        from karpenter_tpu.parallel.multihost import initialize_multihost

        if not initialize_multihost():
            import sys

            print(
                "multihost: no topology configured; serving single-host",
                file=sys.stderr,
            )

    # the sidecar owns the chip: without one it fails at start instead
    # of serving from the CPU
    try:
        require_backend()
    except RuntimeError as error:
        raise SystemExit(f"karpenter-tpu sidecar: {error}") from error

    if args.warmup_pods:
        import jax

        from karpenter_tpu.ops.binpack import BinPackInputs, solve

        p = args.warmup_pods
        inputs = BinPackInputs(
            pod_requests=np.ones((p, 3), np.float32),
            pod_valid=np.ones((p,), bool),
            pod_intolerant=np.zeros((p, 64), bool),
            pod_required=np.zeros((p, 64), bool),
            group_allocatable=np.ones((300, 3), np.float32),
            group_taints=np.zeros((300, 64), bool),
            group_labels=np.ones((300, 64), bool),
        )
        jax.block_until_ready(solve(jax.device_put(inputs)))

    server = SolverServer(port=args.port, host=args.host)
    port = server.start()
    print(json.dumps({"serving": f"{args.host}:{port}", "service": SERVICE}))
    server.wait()


if __name__ == "__main__":
    main()
