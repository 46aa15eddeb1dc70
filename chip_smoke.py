"""The quickest proof that the control plane's main path runs on the chip.

    python chip_smoke.py             # one chip: runtime tick, north-star
                                     # solve, decision batch, kernel parity
    python chip_smoke.py --chips 4   # four chips: the sharded fleet solve

Everything runs in this one process, through the entry points a user
calls (KarpenterRuntime as `python -m karpenter_tpu --cloud-provider fake`
builds it, and SolverService with backend="auto"), at the north-star size
of BASELINE.json. Every result is checked against the plain references:
binpack_numpy with oracle_shelf_bfd for node counts, decide_numpy for
decisions, and the numpy mirrors of the forecast, cost and pool-group
kernels. The lines before the last say which kernel served each phase,
the service's fallback counters, the persistent compile cache's hits and
the compile seconds; their times are smoke figures from the host clock,
not benchmarks. The last line is one JSON object naming the device.

A missing TPU, a mismatch or a fallback prints the reason on stderr and
exits 1, with no result line. Outputs of the forecast, cost and
pool-group kernels in f32 are reported, not failed, when they differ
from their mirrors by rounding; their integer and boolean decisions
must match.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SEED = 0
PODS, GROUPS, TAINTS, LABELS = 100_000, 300, 64, 64  # BASELINE.json
DECISION_ROWS, DECISION_METRICS = 100_000, 4
FLEET_PODS, FLEET_GROUPS = 1_000_000, 1_000  # README's sharded claim
BUCKETS = 32
TICKS = 3
SCALED_GROUPS = 8  # groups that carry an HA + SNG besides their MP


class SmokeFailure(RuntimeError):
    """A phase did not hold: wrong output, fallback, or missing device."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- compile-cache and provenance counters ---------------------------------


class CompileCounters:
    """JAX's own monitoring events: persistent-cache hits and misses
    (a miss is a compile written to the cache) and backend compile
    seconds."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds


def served_since(service, before: dict) -> dict:
    """The kernels that answered since `before` (SolverStatistics.served)."""
    now = service.stats.served
    return {k: now[k] - before.get(k, 0) for k in now if now[k] != before.get(k, 0)}


def check_kernels(served: dict, phase: str) -> None:
    check(bool(served), f"{phase}: the service answered nothing")
    bad = [k for k in served if k.split("/")[1] not in ("pallas", "xla")]
    check(not bad, f"{phase}: served by {bad}, not pallas or xla")


# -- the plain references ----------------------------------------------------


def reference_binpack(inputs, buckets: int = BUCKETS):
    """binpack_numpy's pure-numpy stages, with nodes_needed re-derived by
    the scalar oracle from the assignment (oracle_shelf_bfd)."""
    from karpenter_tpu.ops.binpack import oracle_shelf_bfd
    from karpenter_tpu.ops.numpy_binpack import binpack_numpy

    out = binpack_numpy(inputs, buckets=buckets, use_native=False)
    rows = np.nonzero(np.asarray(out.assigned) >= 0)[0]
    groups = np.asarray(out.assigned)[rows]
    requests = np.asarray(inputs.pod_requests, np.float32)[rows]
    alloc = np.asarray(inputs.group_allocatable, np.float32)[groups]
    share = np.zeros(len(rows), np.float32)
    for r in range(requests.shape[1]):
        a, q = alloc[:, r], requests[:, r]
        s = np.where(a > 0, q / np.maximum(a, np.float32(1e-30)), np.inf)
        share = np.maximum(share, np.where((a <= 0) & (q <= 0), 0, s))
    bucket = np.clip(np.ceil(share * np.float32(buckets)), 1, buckets)
    bucket = bucket.astype(np.int64)
    if inputs.pod_exclusive is not None:
        bucket = np.where(np.asarray(inputs.pod_exclusive)[rows], buckets, bucket)
    weight = (
        np.ones(len(rows), np.int64)
        if inputs.pod_weight is None
        else np.asarray(inputs.pod_weight, np.int64)[rows]
    )
    histogram = np.zeros((len(out.nodes_needed), buckets), np.int64)
    np.add.at(histogram, (groups, bucket - 1), weight)
    oracle = oracle_shelf_bfd(histogram, buckets)
    check(
        np.array_equal(oracle, np.asarray(out.nodes_needed)),
        "reference: binpack_numpy's node counts disagree with the oracle",
    )
    return out


def integer_mismatches(got, want) -> dict:
    """{field: differing entries} over the integer and boolean outputs."""
    return {
        field: int(np.sum(np.asarray(getattr(got, field)) != np.asarray(value)))
        for field, value in vars(want).items()
        if np.asarray(value).dtype.kind in "iub"
    }


def compare_binpack(got, want, phase: str) -> dict:
    """Integer outputs exactly; lp_bound within 1. The device sums demand
    in f32 and the reference in f64, and the repo's contract allows one
    unit where an f32 ulp crosses the ceil guard (ops/numpy_binpack.py)."""
    for field in ("assigned", "assigned_count", "nodes_needed", "unschedulable"):
        a, b = np.asarray(getattr(got, field)), np.asarray(getattr(want, field))
        check(
            np.array_equal(a, b),
            f"{phase}: {field} differs from the reference in "
            f"{int(np.sum(a != b))} of {a.size} entries",
        )
    lp = np.abs(
        np.asarray(got.lp_bound, np.int64) - np.asarray(want.lp_bound, np.int64)
    )
    check(int(lp.max(initial=0)) <= 1, f"{phase}: lp_bound off by {lp.max()}")
    return {"lp_bound_off_by_1": int(np.sum(lp == 1))}


def build_binpack(pods, groups, seed, affinity=0.0, anti=0.0):
    """bench.py:build_inputs' shapes and distributions, in host numpy: one
    row per pod (no deduplication)."""
    from karpenter_tpu.ops.binpack import BinPackInputs

    rng = np.random.default_rng(seed)
    req = np.stack(
        [rng.uniform(0.05, 8.0, pods), rng.uniform(0.1, 32.0, pods), np.ones(pods)],
        axis=1,
    ).astype(np.float32)
    cpu = rng.choice([2, 4, 8, 16, 32, 64, 96, 128], groups).astype(np.float32)
    mem = cpu * rng.choice([2.0, 4.0, 8.0], groups).astype(np.float32)
    forbidden = exclusive = None
    if affinity:
        prototypes = rng.random((4, groups)) < 0.3
        which = rng.integers(0, 4, pods)
        forbidden = prototypes[which] & (rng.random((pods, 1)) < affinity)
    if anti:
        exclusive = rng.random(pods) < anti
    return BinPackInputs(
        pod_requests=req,
        pod_valid=np.ones(pods, bool),
        pod_intolerant=rng.random((pods, TAINTS)) < 0.05,
        pod_required=rng.random((pods, LABELS)) < 0.03,
        group_allocatable=np.stack(
            [cpu, mem, np.full(groups, 110.0, np.float32)], axis=1
        ),
        group_taints=rng.random((groups, TAINTS)) < 0.1,
        group_labels=rng.random((groups, LABELS)) < 0.8,
        pod_group_forbidden=forbidden,
        pod_exclusive=exclusive,
    )


# -- phase: the runtime tick at cluster scale --------------------------------


def _e2e_anti_affinity(app: str):
    """Required hostname self-anti-affinity: the StatefulSet
    one-replica-per-node pattern, through the REAL spec
    parse -> columnar anti-shape intern -> _expand_anti_rows ->
    pod_exclusive operand path."""
    from karpenter_tpu.api.core import (
        Affinity,
        LabelSelector,
        PodAffinityTerm,
        PodAntiAffinity,
    )

    return Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                PodAffinityTerm(
                    label_selector=LabelSelector(
                        match_labels={"app": app}
                    ),
                    topology_key="kubernetes.io/hostname",
                )
            ]
        )
    )


def _e2e_affinity_shapes():
    """A few realistic node-affinity variants: require ssd, forbid hdd,
    prefer ssd (weight 80)."""
    from karpenter_tpu.api.core import (
        Affinity,
        NodeAffinity,
        NodeSelector,
        NodeSelectorRequirement,
        NodeSelectorTerm,
        PreferredSchedulingTerm,
    )

    def required(operator, values):
        return Affinity(
            node_affinity=NodeAffinity(
                required_during_scheduling_ignored_during_execution=(
                    NodeSelector(
                        node_selector_terms=[
                            NodeSelectorTerm(
                                match_expressions=[
                                    NodeSelectorRequirement(
                                        key="disk",
                                        operator=operator,
                                        values=values,
                                    )
                                ]
                            )
                        ]
                    )
                )
            )
        )

    prefer_ssd = Affinity(
        node_affinity=NodeAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                PreferredSchedulingTerm(
                    weight=80,
                    preference=NodeSelectorTerm(
                        match_expressions=[
                            NodeSelectorRequirement(
                                key="disk", operator="In", values=["ssd"]
                            )
                        ]
                    ),
                )
            ]
        )
    )
    return [
        required("In", ["ssd"]),
        required("NotIn", ["hdd"]),
        prefer_ssd,
    ]


def _populate(store, pods: int, groups: int, rng):
    """The smoke fleet: `pods` pending pods (with some node affinity,
    hostname anti-affinity and zone spread), one node per group, and a
    pendingCapacity MetricsProducer per group. Returns the pod factory
    for churn."""
    from karpenter_tpu.api.core import (
        Affinity,
        Container,
        Node,
        NodeCondition,
        NodeStatus,
        ObjectMeta,
        Pod,
        PodSpec,
        TopologySpreadConstraint,
    )
    from karpenter_tpu.api.metricsproducer import (
        MetricsProducer,
        MetricsProducerSpec,
        PendingCapacitySpec,
    )
    from karpenter_tpu.utils.quantity import Quantity

    cpus = [Quantity.parse(q) for q in ("100m", "250m", "500m", "1", "2", "4")]
    mems = [Quantity.parse(q) for q in ("128Mi", "512Mi", "1Gi", "4Gi")]
    shapes = _e2e_affinity_shapes()

    def make_pod(name):
        affinity, labels, spread = None, {}, []
        draw = rng.random()
        if draw < 0.10:  # required or preferred node affinity
            affinity = shapes[int(rng.integers(0, len(shapes)))]
        elif draw < 0.12:  # one replica per node
            app = f"svc{int(rng.integers(0, 8))}"
            labels = {"app": app}
            affinity = Affinity(
                pod_anti_affinity=_e2e_anti_affinity(app).pod_anti_affinity
            )
        elif draw < 0.14:  # hard zone spread
            app = f"web{int(rng.integers(0, 8))}"
            labels = {"spread-app": app}
            spread = [
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"spread-app": app}},
                )
            ]
        return Pod(
            metadata=ObjectMeta(name=name, labels=labels),
            spec=PodSpec(
                containers=[
                    Container(
                        requests={"cpu": rng.choice(cpus), "memory": rng.choice(mems)}
                    )
                ],
                affinity=affinity,
                topology_spread_constraints=spread,
            ),
        )

    for i in range(pods):
        store.create(make_pod(f"p{i}"))
    for g in range(groups):
        cores = int(rng.choice([8, 16, 32, 64, 96]))
        store.create(
            Node(
                metadata=ObjectMeta(
                    name=f"n{g}",
                    labels={
                        "group": f"g{g}",
                        "disk": "ssd" if g % 2 else "hdd",
                        "topology.kubernetes.io/zone": f"z{g % 16}",
                    },
                ),
                status=NodeStatus(
                    allocatable={
                        "cpu": Quantity.parse(str(cores)),
                        "memory": Quantity.parse(f"{cores * 4}Gi"),
                    },
                    conditions=[NodeCondition(type="Ready", status="True")],
                ),
            )
        )
        store.create(
            MetricsProducer(
                metadata=ObjectMeta(name=f"mp{g}"),
                spec=MetricsProducerSpec(
                    pending_capacity=PendingCapacitySpec(node_selector={"group": f"g{g}"})
                ),
            )
        )
    return make_pod


def _scale_group(store, provider, g: int) -> None:
    """A HorizontalAutoscaler on group g's additional-nodes signal and
    the ScalableNodeGroup it drives (docs/examples/pending-capacity-binpack.yaml)."""
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

    provider.node_replicas[f"g{g}"] = 1
    store.create(
        ScalableNodeGroup(
            metadata=ObjectMeta(name=f"g{g}"),
            spec=ScalableNodeGroupSpec(replicas=1, type="FakeNodeGroup", id=f"g{g}"),
        )
    )
    store.create(
        HorizontalAutoscaler(
            metadata=ObjectMeta(name=f"g{g}"),
            spec=HorizontalAutoscalerSpec(
                scale_target_ref=CrossVersionObjectReference(
                    kind="ScalableNodeGroup", name=f"g{g}"
                ),
                min_replicas=0,
                max_replicas=100_000,
                metrics=[
                    Metric(
                        prometheus=PrometheusMetricSource(
                            query="karpenter_pending_capacity_additional_nodes_needed"
                            f'{{name="mp{g}"}}',
                            target=MetricTarget(type="AverageValue", value=1),
                        )
                    )
                ],
            ),
        )
    )


def _reference_signal(store) -> dict:
    """The pending-capacity gauges the plain reference computes from the
    same store: the full re-list + re-encode path (no feed, no memo) with
    the reference bin-pack."""
    from karpenter_tpu.metrics.producers.pendingcapacity import (
        SUBSYSTEM,
        register_gauges,
        solve_pending,
    )
    from karpenter_tpu.metrics.registry import GaugeRegistry

    registry = GaugeRegistry()
    register_gauges(registry)
    solve_pending(
        store, [], registry,
        solver=lambda inputs, buckets=BUCKETS, **_: reference_binpack(inputs, buckets),
    )
    return _signal(registry, SUBSYSTEM)


def _signal(registry, subsystem) -> dict:
    from karpenter_tpu.metrics.producers.pendingcapacity import (
        ADDITIONAL_NODES_NEEDED,
        LP_LOWER_BOUND,
        PENDING_PODS,
        UNSCHEDULABLE_PODS,
    )

    return {
        name: {
            (s.labels["name"], s.labels["namespace"]): s.value
            for s in registry.gauge(subsystem, name).samples()
        }
        for name in (PENDING_PODS, ADDITIONAL_NODES_NEEDED, LP_LOWER_BOUND, UNSCHEDULABLE_PODS)
    }


def runtime_tick_phase(pods=PODS, groups=GROUPS, ticks=TICKS, backend="auto") -> dict:
    """Load the fleet into the runtime's in-process store, tick
    reconcile_all() with 1% pod churn in between, and hold the signal,
    the decisions and the fake provider's replicas to the references."""
    import jax

    from karpenter_tpu.metrics.producers.pendingcapacity import SUBSYSTEM
    from karpenter_tpu.ops.decision import decide_numpy
    from karpenter_tpu.runtime import KarpenterRuntime, Options

    now = [1000.0]
    runtime = KarpenterRuntime(Options(cloud_provider="fake"), clock=lambda: now[0])
    try:
        service, provider, store = (
            runtime.solver_service, runtime.cloud_provider, runtime.store,
        )
        service.backend = backend
        rng = np.random.default_rng(SEED)
        t0 = time.perf_counter()
        make_pod = _populate(store, pods, groups, rng)
        for g in range(min(SCALED_GROUPS, groups)):
            _scale_group(store, provider, g)
        load_s = time.perf_counter() - t0

        decisions = []  # (inputs, outputs) of every decide dispatch
        decider = runtime.batch_autoscaler.decider

        def recording(inputs):
            out = decider(inputs)
            decisions.append((inputs, out))
            return out

        runtime.batch_autoscaler.decider = recording
        before = dict(service.stats.served)
        tick_s, lp_off, next_id = [], 0, pods
        churn = max(1, pods // 100)
        scaled = [f"g{g}" for g in range(min(SCALED_GROUPS, groups))]
        decided = {name: 1 for name in scaled}  # SNG spec after the last tick
        for tick in range(ticks):
            if tick:
                for j in range(churn):
                    store.delete("Pod", "default", f"p{next_id - pods + j}")
                    store.create(make_pod(f"p{next_id + j}"))
                next_id += churn
            now[0] += 61.0
            t0 = time.perf_counter()
            runtime.manager.reconcile_all()
            tick_s.append(time.perf_counter() - t0)

            got, want = _signal(runtime.registry, SUBSYSTEM), _reference_signal(store)
            for name in got:
                for key, value in want[name].items():
                    ok = got[name].get(key) == value or (
                        name == "lp_lower_bound" and abs(got[name].get(key, -9) - value) <= 1
                    )
                    check(ok, f"tick {tick}: {name}{key} = {got[name].get(key)}, reference {value}")
            lp_off += sum(got["lp_lower_bound"][k] != v for k, v in want["lp_lower_bound"].items())
            check(
                len(got["pending_pods"]) == groups
                and sum(got["pending_pods"].values()) > 0,
                f"tick {tick}: the signal covers {len(got['pending_pods'])} of {groups} groups",
            )
            # controllers run MP, SNG, HA in turn: the SNG actuates what
            # the HA decided one tick earlier
            for name in scaled:
                ha = store.get("HorizontalAutoscaler", "default", name)
                sng = store.get("ScalableNodeGroup", "default", name)
                check(
                    provider.node_replicas[name] == decided[name],
                    f"tick {tick}: {name} actuated {provider.node_replicas[name]}, "
                    f"decided {decided[name]}",
                )
                check(
                    ha.status.desired_replicas in (None, sng.spec.replicas),
                    f"tick {tick}: {name} desired {ha.status.desired_replicas}, "
                    f"SNG spec {sng.spec.replicas}",
                )
                decided[name] = sng.spec.replicas
        for inputs, out in decisions:
            want = decide_numpy(jax.tree_util.tree_map(np.asarray, inputs))
            differ = integer_mismatches(out, want)
            check(not any(differ.values()), f"runtime decide vs decide_numpy: {differ}")
        check(bool(decisions), "no HorizontalAutoscaler decision was made")
        check(
            any(replicas > 1 for replicas in decided.values()),
            f"no scaled group moved off 1 replica: {decided}",
        )
        return {
            "replicas": decided,
            "served": served_since(service, before),
            "stats": service.stats,
            "load_s": load_s,
            "tick_s": tick_s,
            "decide_batches": len(decisions),
            "lp_off_by_1": lp_off,
        }
    finally:
        runtime.close()


# -- phase: the north-star solve and the decision batch ---------------------


def solve_phase(service, pods=PODS, groups=GROUPS) -> list:
    """100k x 300 x 64 x 64 through SolverService.submit, one row per pod:
    plain, and again with the affinity mask and exclusive rows."""
    import jax

    reports = []
    for variant, extra in (("plain", {}), ("operands", {"affinity": 0.3, "anti": 0.05})):
        inputs = build_binpack(pods, groups, SEED, **extra)
        before = dict(service.stats.served)
        t0 = time.perf_counter()
        got = service.submit(inputs, buckets=BUCKETS).result()
        jax.block_until_ready(got)
        wall = time.perf_counter() - t0
        t1 = time.perf_counter()
        got = service.submit(inputs, buckets=BUCKETS).result()
        warm = time.perf_counter() - t1
        detail = compare_binpack(got, reference_binpack(inputs), f"solve[{variant}]")
        reports.append({
            "phase": f"solve[{variant}]", "served": served_since(service, before),
            "first_s": wall, "warm_s": warm, **detail,
        })
    return reports


def decide_phase(service, rows=DECISION_ROWS) -> dict:
    import jax

    from karpenter_tpu.ops.decision import decide_numpy
    from karpenter_tpu.parallel.mesh import example_decision_inputs

    inputs = jax.tree_util.tree_map(
        np.asarray, example_decision_inputs(N=rows, M=DECISION_METRICS, seed=SEED)
    )
    before = dict(service.stats.served)
    t0 = time.perf_counter()
    got = jax.block_until_ready(service.decide(inputs))
    wall = time.perf_counter() - t0
    differ = integer_mismatches(got, decide_numpy(inputs))
    check(not any(differ.values()), f"decide vs decide_numpy: {differ}")
    return {"phase": "decide", "served": served_since(service, before), "first_s": wall}


# -- phase: the float kernels against their numpy mirrors --------------------


def _ulps(a: np.ndarray, b: np.ndarray) -> int:
    a, b = np.asarray(a, np.float32).ravel(), np.asarray(b, np.float32).ravel()
    both = np.isfinite(a) & np.isfinite(b)
    check(np.array_equal(np.isfinite(a), np.isfinite(b)), "non-finite mismatch")
    ia, ib = a[both].view(np.int32).astype(np.int64), b[both].view(np.int32).astype(np.int64)
    ia = np.where(ia < 0, -(ia & 0x7FFFFFFF), ia)
    ib = np.where(ib < 0, -(ib & 0x7FFFFFFF), ib)
    return int(np.abs(ia - ib).max(initial=0))


def parity_phase() -> dict:
    """forecast, cost and pool-group kernels on the device against their
    numpy mirrors, on the tests' adversarial seeded inputs: the largest
    ulp distance of each f32 output and the number of differing entries
    of each integer or boolean output (run() fails on the latter)."""
    tests = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests")
    if tests not in sys.path:
        sys.path.insert(0, tests)
    from test_cost import random_inputs as cost_inputs
    from test_forecast import random_forecast_inputs
    from test_poolgroup import random_group_inputs

    from karpenter_tpu.forecast import models as FM
    from karpenter_tpu.ops import cost as CK
    from karpenter_tpu.ops import poolgroup as PGK

    cases = {
        "forecast": (FM.forecast_jit, FM.forecast_numpy, [
            random_forecast_inputs(np.random.RandomState(s), S, T)
            for s, (S, T) in enumerate([(1, 8), (33, 64), (512, 64)])
        ]),
        "cost": (CK.cost_jit, CK.cost_numpy, [cost_inputs(s, 4096, 3) for s in range(3)]),
        "poolgroup": (PGK.poolgroup_jit, PGK.poolgroup_numpy, [
            random_group_inputs(s, 64, 4, 2) for s in range(3)
        ]),
    }
    report = {}
    for name, (device, mirror, inputs_list) in cases.items():
        worst = {}
        for inputs in inputs_list:
            got, want = device(inputs), mirror(inputs)
            for field, n in integer_mismatches(got, want).items():
                worst[f"{field}_mismatches"] = worst.get(f"{field}_mismatches", 0) + n
            for field in vars(want):
                a, b = np.asarray(getattr(got, field)), np.asarray(getattr(want, field))
                if a.dtype.kind == "f":
                    worst[f"{field}_ulps"] = max(worst.get(f"{field}_ulps", 0), _ulps(a, b))
                    gap = float(np.max(np.abs(a.astype(np.float64) - b), initial=0.0))
                    worst[f"{field}_abs"] = max(worst.get(f"{field}_abs", 0.0), gap)
        report[name] = worst
    return report


# -- phase: the sharded fleet solve on four chips ---------------------------


def fleet_phase(pods=FLEET_PODS, groups=FLEET_GROUPS, chips=4, **service_options) -> dict:
    """1M pods x 1,000 groups through the service's sharded dispatch over
    a 2x2 build_mesh, and the sharded program alone to see its output
    shards, both against the reference. (`service_options` let a CPU
    rehearsal run it small on virtual devices.)"""
    import jax

    from karpenter_tpu.parallel.mesh import build_mesh, sharded_binpack
    from karpenter_tpu.solver import SolverService

    devices = jax.devices()
    check(len(devices) >= chips, f"--chips {chips} found {len(devices)} devices")
    inputs = build_binpack(pods, groups, SEED)
    t0 = time.perf_counter()
    want = reference_binpack(inputs)
    reference_s = time.perf_counter() - t0
    service = SolverService(
        watchdog_timeout_s=30.0, shard_devices=chips, **service_options
    )
    try:
        t0 = time.perf_counter()
        got = service.submit(inputs, buckets=BUCKETS).result()
        wall = time.perf_counter() - t0
        stats = service.stats
        check(stats.shard_mesh_errors == 0, "the service could not build its mesh")
        check(stats.shard_dispatches >= 1, "the fleet solve did not ride the mesh")
        detail = compare_binpack(got, want, "fleet[service]")
        served = dict(stats.served)
        check_kernels(served, "fleet[service]")
    finally:
        service.close()
    mesh = build_mesh(n_devices=chips)
    out = sharded_binpack(mesh, inputs, buckets=BUCKETS)
    jax.block_until_ready(out)
    compare_binpack(out, want, "fleet[sharded_binpack]")
    shard_devices = {s.device for s in out.assigned.addressable_shards}
    check(
        len(shard_devices) == chips
        and all(d.platform == devices[0].platform for d in shard_devices),
        f"output shards on {sorted(map(str, shard_devices))}",
    )
    return {
        "phase": "fleet", "served": served, "stats": stats, "first_s": wall,
        "reference_s": reference_s, "mesh": dict(mesh.shape),
        "shard_devices": sorted(str(d) for d in shard_devices), **detail,
    }


# -- driver -------------------------------------------------------------------


def _phase_line(report: dict) -> str:
    parts = [f"{k}={v}" for k, v in report.items() if k not in ("stats", "phase")]
    return f"phase {report['phase']}: " + " ".join(parts)


def _stats_line(label: str, stats) -> str:
    return (
        f"{label}: fallbacks={stats.fallbacks} shard_fallbacks={stats.shard_fallbacks} "
        f"shard_mesh_errors={stats.shard_mesh_errors} "
        f"watchdog_restarts={stats.watchdog_restarts} fsm_trips={stats.fsm_trips} "
        f"device_failures={stats.device_failures}"
    )


def _check_stats(stats, label: str) -> None:
    for name in (
        "fallbacks", "shard_fallbacks", "shard_mesh_errors",
        "watchdog_restarts", "fsm_trips", "device_failures",
    ):
        check(getattr(stats, name) == 0, f"{label}: {name} = {getattr(stats, name)}")


def run(chips: int) -> dict:
    import jax

    from karpenter_tpu.solver import SolverService
    from karpenter_tpu.utils.backend import arm_compile_cache, require_backend

    cache_dir = arm_compile_cache()
    counters = CompileCounters()
    platform = require_backend()
    check(platform == "tpu", f"no TPU: JAX resolved to {platform!r}")
    devices = jax.devices()
    print(
        f"device: {devices[0].device_kind} x{len(devices)}; jax {jax.__version__}; "
        f"compile cache {cache_dir}",
        flush=True,
    )
    if chips == 4:
        report = fleet_phase(chips=chips)
        print(_phase_line(report), flush=True)
        print(_stats_line("fleet service", report["stats"]), flush=True)
        _check_stats(report["stats"], "fleet service")
    else:
        service = SolverService(watchdog_timeout_s=30.0)
        try:
            print(f"resolved backend: auto -> {service._resolve_backend('auto')}", flush=True)
            tick = runtime_tick_phase()
            check_kernels(tick["served"], "runtime tick")
            print(_phase_line({"phase": "runtime_tick", **tick}), flush=True)
            print(_stats_line("runtime service", tick["stats"]), flush=True)
            _check_stats(tick["stats"], "runtime service")
            for report in solve_phase(service):
                check_kernels(report["served"], report["phase"])
                print(_phase_line(report), flush=True)
            report = decide_phase(service)
            check_kernels(report["served"], report["phase"])
            print(_phase_line(report), flush=True)
            print(_stats_line("front-door service", service.stats), flush=True)
            _check_stats(service.stats, "front-door service")
        finally:
            service.close()
        parity = parity_phase()
        print(f"parity of the float kernels vs their numpy mirrors: {parity}", flush=True)
        flipped = {
            f"{name}.{key}": n
            for name, fields in parity.items()
            for key, n in fields.items()
            if key.endswith("_mismatches") and n
        }
        check(not flipped, f"integer decisions differ from the numpy mirrors: {flipped}")
    print(
        f"compile cache: hits={counters.hits} misses={counters.misses} "
        f"backend_compile_s={counters.compile_s:.2f}",
        flush=True,
    )
    print("times above are host-clock smoke figures, not benchmarks", flush=True)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the sharded fleet solve over four chips",
    )
    args = ap.parse_args(argv)
    try:
        device = run(args.chips)
    except Exception as error:  # noqa: BLE001 — any failure fails the smoke
        import traceback

        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(error).__name__}: {error}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
