"""Columnar pending-pod cache: incremental maintenance + solver-input
equivalence with the store.list oracle path under churn.

The cache (store/columnar.py) must produce EXACTLY the outputs of the
original list+encode path for any store history — adds, request changes,
binding (pod gets a nodeName), deletion, slot reuse, universe growth —
because the solver is permutation-invariant over pods.
"""

import numpy as np
import pytest

from karpenter_tpu.api.core import (
    Container,
    Node,
    NodeCondition,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    Toleration,
)
from karpenter_tpu.api.metricsproducer import (
    MetricsProducer,
    MetricsProducerSpec,
    PendingCapacitySpec,
)
from karpenter_tpu.metrics.producers.pendingcapacity import solve_pending
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.store import Store
from karpenter_tpu.store.columnar import (
    _COMPACT_FACTOR,
    _COMPACT_FLOOR,
    PendingPodCache,
)
from karpenter_tpu.utils.quantity import Quantity


def pod(name, cpu="100m", mem="128Mi", node=None, selector=None,
        tolerations=None, extra=None, phase="Pending"):
    from karpenter_tpu.api.core import PodStatus

    requests = {"cpu": Quantity.parse(cpu), "memory": Quantity.parse(mem)}
    for r, v in (extra or {}).items():
        requests[r] = Quantity.parse(v)
    return Pod(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=PodSpec(
            node_name=node,
            containers=[Container(requests=requests)],
            node_selector=dict(selector or {}),
            tolerations=list(tolerations or []),
        ),
        status=PodStatus(phase=phase),
    )


def node(name, labels, cpu="32", mem="128Gi", taints=None):
    from karpenter_tpu.api.core import Taint

    return Node(
        metadata=ObjectMeta(name=name, labels=dict(labels)),
        spec=__import__(
            "karpenter_tpu.api.core", fromlist=["NodeSpec"]
        ).NodeSpec(taints=[Taint(**t) for t in (taints or [])]),
        status=NodeStatus(
            allocatable={
                "cpu": Quantity.parse(cpu),
                "memory": Quantity.parse(mem),
            },
            conditions=[NodeCondition(type="Ready", status="True")],
        ),
    )


def producer(name, selector):
    return MetricsProducer(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=MetricsProducerSpec(
            pending_capacity=PendingCapacitySpec(node_selector=dict(selector))
        ),
    )


def statuses(store):
    out = {}
    for mp in store.list("MetricsProducer"):
        s = mp.status.pending_capacity
        out[mp.metadata.name] = None if s is None else (
            s.pending_pods,
            s.additional_nodes_needed,
            s.lp_lower_bound,
            s.unschedulable_pods,
        )
    return out


def solve_both(store, cache, feed=None):
    """Run the oracle (list) path, the pod-cache path, and (when given)
    the full-feed path; return all status maps. Producers are re-fetched
    fresh so statuses don't leak across."""
    results = []
    variants = [{"pod_cache": None}, {"pod_cache": cache}]
    if feed is not None:
        variants.append({"feed": feed})
    for kwargs in variants:
        mps = [
            mp for mp in store.list("MetricsProducer")
            if mp.spec.pending_capacity is not None
        ]
        solve_pending(store, mps, GaugeRegistry(), **kwargs)
        results.append(
            {
                mp.metadata.name: (
                    mp.status.pending_capacity.pending_pods,
                    mp.status.pending_capacity.additional_nodes_needed,
                    mp.status.pending_capacity.lp_lower_bound,
                    mp.status.pending_capacity.unschedulable_pods,
                )
                for mp in mps
            }
        )
    return results


class TestMaintenance:
    def test_add_bind_delete(self):
        store = Store()
        cache = PendingPodCache(store)
        created = store.create(pod("a"))
        store.create(pod("b"))
        assert len(cache) == 2
        created.spec.node_name = "n1"  # scheduled -> no longer pending
        store.update(created)
        assert len(cache) == 1
        store.delete("Pod", "default", "b")
        assert len(cache) == 0

    def test_adopts_preexisting_pods(self):
        store = Store()
        store.create(pod("a"))
        store.create(pod("b", node="n1"))  # bound: not pending
        cache = PendingPodCache(store)
        assert len(cache) == 1

    def test_non_pending_phase_excluded(self):
        store = Store()
        cache = PendingPodCache(store)
        store.create(pod("done", phase="Succeeded"))
        assert len(cache) == 0

    def test_slot_reuse_and_growth(self):
        store = Store()
        cache = PendingPodCache(store, capacity=16)
        for i in range(40):  # forces arena growth
            store.create(pod(f"p{i}"))
        for i in range(0, 40, 2):
            store.delete("Pod", "default", f"p{i}")
        for i in range(40, 60):  # reuses freed slots
            store.create(pod(f"p{i}"))
        assert len(cache) == 40
        snap = cache.snapshot()
        assert int(snap.valid.sum()) == 40

    def test_universe_growth_new_resource_and_label(self):
        store = Store()
        cache = PendingPodCache(store, capacity=16)
        store.create(pod("a"))
        for i in range(20):  # outgrow both column arenas
            store.create(
                pod(
                    f"x{i}",
                    extra={f"vendor.io/res{i}": "1"},
                    selector={f"zone{i}": "z"},
                )
            )
        snap = cache.snapshot()
        assert "vendor.io/res7" in snap.resources
        assert ("zone7", "z") in snap.labels
        row = snap.requests[:, snap.resources.index("vendor.io/res7")]
        assert row.sum() == pytest.approx(1.0)

    def test_snapshot_isolation(self):
        store = Store()
        cache = PendingPodCache(store)
        store.create(pod("a"))
        snap = cache.snapshot()
        before = snap.requests.copy()
        store.create(pod("b", cpu="4"))
        np.testing.assert_array_equal(snap.requests, before)

    def test_modify_requests_reencodes(self):
        store = Store()
        cache = PendingPodCache(store)
        created = store.create(pod("a", cpu="1"))
        created.spec.containers[0].requests["cpu"] = Quantity.parse("2")
        store.update(created)
        snap = cache.snapshot()
        cpu = snap.resources.index("cpu")
        assert snap.requests[:, cpu].max() == pytest.approx(2.0)
        assert len(cache) == 1


class TestCompaction:
    def test_peak_drain_restores_live_cost(self):
        """After an incident peak drains, snapshot size must track the LIVE
        pending set, not the historical high-water mark."""
        store = Store()
        cache = PendingPodCache(store, capacity=16)
        for i in range(600):
            store.create(pod(f"p{i}"))
        assert cache.snapshot().requests.shape[0] >= 600
        for i in range(590):
            store.delete("Pod", "default", f"p{i}")
        snap = cache.snapshot()  # triggers compaction (peak >> live)
        assert snap.requests.shape[0] < 64
        assert int(snap.valid.sum()) == 10

    def test_universe_churn_compacts(self):
        """Per-job selector labels must not accumulate forever."""
        store = Store()
        cache = PendingPodCache(store, capacity=16)
        for i in range(600):  # each adds a unique label, then leaves
            store.create(pod(f"p{i}", selector={f"job{i}": "x"}))
            store.delete("Pod", "default", f"p{i}")
        store.create(pod("steady", selector={"zone": "z"}))
        snap = cache.snapshot()
        assert len(snap.labels) < 16
        assert ("zone", "z") in snap.labels

    def test_compaction_preserves_solver_outputs(self):
        store = Store()
        cache = PendingPodCache(store, capacity=16)
        store.create(node("n0", {"group": "small"}, cpu="8"))
        store.create(producer("small", {"group": "small"}))
        for i in range(400):
            store.create(pod(f"p{i}", cpu="1"))
        for i in range(380):
            store.delete("Pod", "default", f"p{i}")
        oracle, cached = solve_both(store, cache)
        assert oracle == cached
        assert cached["small"][0] == 20


# -- compaction check: live-id counts kept at write time ---------------------

ZONE = "topology.kubernetes.io/zone"


def _scan_live(cache):
    """The O(live) scans the compaction check used to run, kept here as
    the oracle: per id column, interned id -> live slots holding it, and
    required-label item -> live slots selecting it."""
    from collections import Counter

    live = list(cache._slot.values())
    columns = (
        cache._shape_id, cache._affinity_id, cache._preferred_id,
        cache._spread_id, cache._anti_id, cache._soft_spread_id,
        cache._soft_anti_id, cache._labels_id,
    )
    ids = [dict(Counter(int(col[s]) for s in live)) for col in columns]
    labels = dict(Counter(
        item for s in live for item in cache._sparse[s].selector
    ))
    return ids, labels


def _scan_needs_compaction(cache):
    """The scan's compaction answer, as the cache gave it before its
    live counts were kept at write time."""
    live = len(cache._slot)
    if cache._hi >= _COMPACT_FLOOR and cache._hi > _COMPACT_FACTOR * live:
        return True
    for registry, ids in (
        (cache._shapes, cache._shape_id),
        (cache._affinity_shapes, cache._affinity_id),
        (cache._preferred_shapes, cache._preferred_id),
        (cache._spread_shapes, cache._spread_id),
        (cache._anti_shapes, cache._anti_id),
        (cache._soft_spread_shapes, cache._soft_spread_id),
        (cache._soft_anti_shapes, cache._soft_anti_id),
        (cache._label_sets, cache._labels_id),
    ):
        if len(registry) >= _COMPACT_FLOOR:
            live_ids = len({int(ids[s]) for s in cache._slot.values()})
            if len(registry) > _COMPACT_FACTOR * max(1, live_ids):
                return True
    if len(cache._labels) >= _COMPACT_FLOOR:
        live_labels = set()
        for sparse in cache._sparse.values():
            live_labels.update(sparse.selector)
        if len(cache._labels) > _COMPACT_FACTOR * max(1, len(live_labels)):
            return True
    return False


def constrained_pod(name, rng, step, hot):
    """A pending pod whose every interned field is absent or one of a
    few shared values, except in the `hot` registry (a _live_ids column
    name, or "selector" for the label universe), which mostly draws a
    value of its own: its ids churn away while the others stay put."""
    from karpenter_tpu.api.core import (
        Affinity,
        LabelSelector,
        NodeAffinity,
        NodeSelector,
        NodeSelectorRequirement,
        NodeSelectorTerm,
        PodAffinityTerm,
        PodAntiAffinity,
        PreferredSchedulingTerm,
        TopologySpreadConstraint,
        WeightedPodAffinityTerm,
    )

    def pick(kind):
        draw = rng.random()
        if draw < 0.2:
            return None
        if kind == hot and draw < 0.8:
            return f"{kind}-{step}"
        return f"{kind}-{rng.integers(3)}"

    def term(zone):
        return NodeSelectorTerm(match_expressions=[
            NodeSelectorRequirement(key="zone", operator="In", values=[zone])
        ])

    def own(kind):  # self-matching, so the self slice keeps the term
        return LabelSelector(match_labels={"app": app}, match_expressions=[
            NodeSelectorRequirement(key="app", operator="In", values=[app, pick(kind) or ""])
        ])

    p = pod(name, cpu=str(rng.choice(["100m", "1"])))
    app = pick("labels") or "web"
    p.metadata.labels = {"app": app}
    if hot == "labels" and rng.random() < 0.3:  # a StatefulSet pod's own label
        p.metadata.labels["statefulset.kubernetes.io/pod-name"] = name
    selector, toleration = pick("selector"), pick("shape")
    if selector is not None:
        p.spec.node_selector = {"disk": selector}
    if toleration is not None:
        p.spec.tolerations = [Toleration(key=toleration, operator="Exists")]
    required, preferred = pick("affinity"), pick("preferred")
    node_affinity = NodeAffinity(
        required_during_scheduling_ignored_during_execution=(
            None if required is None
            else NodeSelector(node_selector_terms=[term(required)])
        ),
        preferred_during_scheduling_ignored_during_execution=(
            [] if preferred is None
            else [PreferredSchedulingTerm(weight=5, preference=term(preferred))]
        ),
    )
    anti = PodAntiAffinity()
    if rng.random() < 0.7:
        anti.required_during_scheduling_ignored_during_execution = [
            PodAffinityTerm(label_selector=own("anti"), topology_key=str(
                rng.choice([ZONE, "kubernetes.io/hostname"])))
        ]
    if rng.random() < 0.7:
        anti.preferred_during_scheduling_ignored_during_execution = [
            WeightedPodAffinityTerm(weight=10, pod_affinity_term=PodAffinityTerm(
                label_selector=own("soft_anti"), topology_key=ZONE))
        ]
    p.spec.affinity = Affinity(node_affinity=node_affinity, pod_anti_affinity=anti)
    spread = []
    for when, kind in (("DoNotSchedule", "spread"), ("ScheduleAnyway", "soft_spread")):
        if rng.random() < 0.7:
            spread.append(TopologySpreadConstraint(
                max_skew=int(rng.integers(1, 3)), topology_key=ZONE,
                when_unsatisfiable=when,
                label_selector={"matchLabels": {"app": app, "tier": pick(kind) or ""}},
            ))
    p.spec.topology_spread_constraints = spread
    return p


class TestLiveCounts:
    """_needs_compaction reads live-distinct counts kept at write time;
    the O(live) scans they replace are the oracle here."""

    @staticmethod
    def _assert_counts_match_scan(cache):
        ids, labels = _scan_live(cache)
        assert [dict(c) for c in cache._live_ids] == ids
        assert cache._live_labels == labels
        assert cache._needs_compaction() == _scan_needs_compaction(cache)

    @pytest.mark.parametrize("seed,hot", list(enumerate((
        "shape", "affinity", "preferred", "spread", "anti", "soft_spread",
        "soft_anti", "labels", "selector",
    ), start=2**31)))
    def test_counts_equal_the_scan_under_churn(self, seed, hot):
        """Seeded upserts, in-place re-upserts that change shapes and
        labels, binds, removes and compactions: every count equals the
        scan, and the compaction answer equals the scan's, at each step.
        The `hot` registry churns fastest, so it is the one that trips."""
        rng = np.random.default_rng(seed)
        store = Store()
        cache = PendingPodCache(store, capacity=16)
        registry = {
            "shape": "_shapes", "labels": "_label_sets", "selector": "_labels",
        }.get(hot, f"_{hot}_shapes")
        live, answers, peak = [], set(), 0
        for step in range(2500):
            draw = rng.random()
            # creates thin out as the live set grows: it stays near 20,
            # so churned-away ids soon outnumber live ones four to one
            if draw < 0.7 - len(live) / 50 or not live:
                name = f"p{step}"
                store.create(constrained_pod(name, rng, step, hot))
                live.append(name)
            elif draw < 0.6:
                name = live[int(rng.integers(len(live)))]
                store.update(constrained_pod(name, rng, step, hot))
            elif draw < 0.9:
                name = live.pop(int(rng.integers(len(live))))
                store.delete("Pod", "default", name)
            else:
                name = live.pop(int(rng.integers(len(live))))
                bound = constrained_pod(name, rng, step, hot)
                bound.spec.node_name = "n0"
                store.update(bound)
            with cache._lock:
                self._assert_counts_match_scan(cache)
                answers.add(cache._needs_compaction())
                peak = max(peak, len(getattr(cache, registry)))
            if step % 25 == 0:
                before = cache.compactions
                cache.snapshot()  # compacts where the answer is True
                with cache._lock:
                    self._assert_counts_match_scan(cache)
                    assert not cache._needs_compaction() or (
                        cache.compactions == before
                    )
        assert len(cache) == len(live)
        # the hot registry crossed the floor: both answers were checked
        assert peak >= _COMPACT_FLOOR
        assert answers == {True, False}
        assert cache.compactions > 0

    def test_many_live_workloads_check_without_a_scan(self):
        """257+ live label sets and spread shapes, all live: the check
        answers False without iterating the live slots."""
        from karpenter_tpu.api.core import TopologySpreadConstraint

        class NoScan(dict):
            def values(self):
                raise AssertionError("the compaction check scanned live slots")

            items = keys = __iter__ = values

        store = Store()
        cache = PendingPodCache(store)
        for i in range(300):
            p = pod(f"web-{i}")
            p.metadata.labels = {"app": f"web-{i}"}
            p.spec.topology_spread_constraints = [TopologySpreadConstraint(
                topology_key=ZONE, label_selector={"matchLabels": {"app": f"web-{i}"}})]
            store.create(p)
        assert len(cache._label_sets) >= 257 and len(cache._spread_shapes) >= 257
        with cache._lock:
            assert _scan_needs_compaction(cache) is False
        cache._slot, cache._sparse = NoScan(cache._slot), NoScan(cache._sparse)
        assert cache._needs_compaction() is False
        snap = cache.snapshot()
        assert len(snap.spread_shapes) == 301 and cache.compactions == 0


class TestReservationsCache:
    def _reserved(self, store, cache=None, mirror=None):
        from karpenter_tpu.api.metricsproducer import ReservedCapacitySpec
        from karpenter_tpu.metrics.producers.reservedcapacity import (
            ReservedCapacityProducer,
        )

        mp = MetricsProducer(
            metadata=ObjectMeta(name="rc", namespace="default"),
            spec=MetricsProducerSpec(
                reserved_capacity=ReservedCapacitySpec(
                    node_selector={"group": "small"}
                )
            ),
        )
        ReservedCapacityProducer(
            mp, store, registry=GaugeRegistry(),
            reservations=cache, node_mirror=mirror,
        ).reconcile()
        return dict(mp.status.reserved_capacity)

    def test_matches_oracle_under_churn(self):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import (
            NodeMirror,
            ReservationsCache,
        )

        rng = np.random.default_rng(3)
        store = Store()
        cache = ReservationsCache(store)
        mirror = NodeMirror(store, group_profile)
        store.create(node("n0", {"group": "small"}, cpu="16", mem="64Gi"))
        store.create(node("n1", {"group": "small"}, cpu="8", mem="32Gi"))
        live = {}
        serial = 0
        for _ in range(200):
            action = rng.choice(["add", "rebind", "delete", "resize"])
            if action == "add" or not live:
                name = f"p{serial}"
                serial += 1
                target = rng.choice(["n0", "n1", None])
                store.create(
                    pod(name, cpu=f"{rng.integers(1, 5) * 250}m",
                        mem=f"{rng.integers(1, 9) * 256}Mi", node=target)
                )
                live[name] = True
            elif action == "rebind":
                name = rng.choice(list(live))
                obj = store.get("Pod", "default", name)
                obj.spec.node_name = rng.choice(["n0", "n1"])
                store.update(obj)
            elif action == "delete":
                name = rng.choice(list(live))
                store.delete("Pod", "default", name)
                del live[name]
            else:
                name = rng.choice(list(live))
                obj = store.get("Pod", "default", name)
                obj.spec.containers[0].requests["cpu"] = Quantity.parse(
                    f"{rng.integers(1, 9) * 125}m"
                )
                store.update(obj)
        oracle = self._reserved(store)
        cached = self._reserved(store, cache=cache, mirror=mirror)
        assert oracle == cached  # exact strings, incl. formats

    def test_mixed_format_sums_render_identically(self):
        """Quantity.add adopts the first non-zero operand's format, and the
        cache path accumulates in pod-creation order while the oracle path
        accumulates node-by-node — so mixed-format memory requests used to
        render value-equal but differently-formatted status strings.
        512Mi (binary) + 536870912 (decimal) = 1Gi exactly: binary-first
        renders "1Gi", decimal-first "1073741824". The producer now
        canonicalizes to the capacity side's format (order-stable), so both
        paths must render the SAME string."""
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import (
            NodeMirror,
            ReservationsCache,
        )

        store = Store()
        cache = ReservationsCache(store)
        mirror = NodeMirror(store, group_profile)
        store.create(node("n0", {"group": "small"}, cpu="16", mem="96Gi"))
        # same node, creation order ("z" first, decimal) opposite to the
        # oracle's sorted-key order ("a" first, binary): the cache's
        # per-node sum adopts decimal, the oracle's adopts binary
        store.create(pod("z", cpu="1", mem="536870912", node="n0"))
        store.create(pod("a", cpu="1", mem="512Mi", node="n0"))
        oracle = self._reserved(store)
        cached = self._reserved(store, cache=cache, mirror=mirror)
        assert oracle == cached
        # capacity is 96Gi (binary), so the canonical rendering is binary
        assert oracle["memory"].endswith(", 1Gi/96Gi")

    def test_unready_nodes_excluded(self):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import (
            NodeMirror,
            ReservationsCache,
        )
        from karpenter_tpu.api.core import NodeCondition

        store = Store()
        cache = ReservationsCache(store)
        mirror = NodeMirror(store, group_profile)
        store.create(node("ready", {"group": "small"}, cpu="8"))
        broken = node("broken", {"group": "small"}, cpu="8")
        broken.status.conditions = [
            NodeCondition(type="Ready", status="False")
        ]
        store.create(broken)
        store.create(pod("a", cpu="1", node="ready"))
        store.create(pod("b", cpu="1", node="broken"))  # must not count
        oracle = self._reserved(store)
        cached = self._reserved(store, cache=cache, mirror=mirror)
        assert oracle == cached
        assert oracle["cpu"].startswith("12.50%")  # 1 of 8, broken excluded


class TestLazyFactoryCache:
    def test_not_created_without_pending_producer(self):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.metrics.producers import ProducerFactory

        store = Store()
        factory = ProducerFactory(store, FakeFactory(), registry=GaugeRegistry())
        assert factory._pending_feed is None
        assert factory.pending_feed() is factory.pending_feed()  # memoized


class TestEquivalence:
    def _cluster(self, store):
        store.create(node("n0", {"group": "small"}, cpu="8", mem="32Gi"))
        store.create(
            node(
                "n1",
                {"group": "big"},
                cpu="64",
                mem="256Gi",
                taints=[
                    {"key": "accel", "value": "tpu", "effect": "NoSchedule"}
                ],
            )
        )
        store.create(producer("small", {"group": "small"}))
        store.create(producer("big", {"group": "big"}))

    def test_simple_equivalence(self):
        store = Store()
        cache = PendingPodCache(store)
        self._cluster(store)
        for i in range(10):
            store.create(pod(f"p{i}", cpu="2"))
        oracle, cached = solve_both(store, cache)
        assert oracle == cached
        assert oracle["small"][0] > 0

    def test_equivalence_with_tolerations_and_selectors(self):
        store = Store()
        cache = PendingPodCache(store)
        self._cluster(store)
        tol = [
            Toleration(
                key="accel", operator="Equal", value="tpu",
                effect="NoSchedule",
            )
        ]
        for i in range(6):
            store.create(
                pod(f"t{i}", cpu="16", tolerations=tol,
                    selector={"group": "big"})
            )
        for i in range(6):
            store.create(pod(f"u{i}", cpu="16"))  # intolerant of big's taint
        oracle, cached = solve_both(store, cache)
        assert oracle == cached
        assert cached["big"][0] == 6  # tolerant+selected pods land on big

    def test_feed_equivalence_with_node_and_producer_churn(self):
        """The full feed (pod arena + node-profile memo + producer index)
        must match the oracle after nodes and producers change too."""
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed

        store = Store()
        feed = PendingFeed(store, group_profile)
        cache = PendingPodCache(store)
        self._cluster(store)
        for i in range(12):
            store.create(pod(f"p{i}", cpu="2"))
        # node churn: grow the small group with a bigger node, cordon none
        store.create(node("n2", {"group": "small"}, cpu="16", mem="64Gi"))
        # producer churn: add a group after the feed exists, remove later
        store.create(producer("late", {"group": "big"}))
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
        store.delete("MetricsProducer", "default", "late")
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
        assert "late" not in fed

    def test_equivalence_under_random_churn(self):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed

        rng = np.random.default_rng(7)
        store = Store()
        cache = PendingPodCache(store, capacity=16)
        feed = PendingFeed(store, group_profile)
        self._cluster(store)
        live = {}
        serial = 0
        for _ in range(300):
            action = rng.choice(["add", "bind", "delete", "resize"])
            if action == "add" or not live:
                name = f"p{serial}"
                serial += 1
                extra = (
                    {"vendor.io/widget": "2"} if rng.random() < 0.2 else None
                )
                selector = {"group": "big"} if rng.random() < 0.3 else None
                obj = store.create(
                    pod(
                        name,
                        cpu=f"{rng.integers(1, 9)}",
                        selector=selector,
                        extra=extra,
                    )
                )
                live[name] = obj
            elif action == "bind":
                name = rng.choice(list(live))
                obj = store.get("Pod", "default", name)
                obj.spec.node_name = "n0"
                store.update(obj)
                del live[name]
            elif action == "delete":
                name = rng.choice(list(live))
                store.delete("Pod", "default", name)
                del live[name]
            else:  # resize
                name = rng.choice(list(live))
                obj = store.get("Pod", "default", name)
                obj.spec.containers[0].requests["cpu"] = Quantity.parse(
                    f"{rng.integers(1, 17)}"
                )
                store.update(obj)
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed


class TestSolveCaching:
    """The tick-collapse caches: snapshot memo (same object per unchanged
    generation), encode memo (same BinPackInputs object per unchanged
    fleet), and their invalidation on pod/node/producer churn. These are
    what turn an unchanged 100k-pod tick into a single device round-trip
    (see _dispatch_and_record's packed fetch + ops/binpack.device_resident)."""

    def test_snapshot_identity_stable_until_mutation(self):
        store = Store()
        cache = PendingPodCache(store)
        store.create(pod("p0"))
        s1 = cache.snapshot()
        assert cache.snapshot() is s1
        store.create(pod("p1"))
        s2 = cache.snapshot()
        assert s2 is not s1
        assert s2.generation > s1.generation
        store.delete("Pod", "default", "p1")
        s3 = cache.snapshot()
        assert s3 is not s2
        # non-mutating churn (delete of an unknown pod) keeps the memo
        assert cache.snapshot() is s3

    def test_encode_memo_reuse_and_invalidation(self, monkeypatch):
        import karpenter_tpu.metrics.producers.pendingcapacity as PC
        from karpenter_tpu.store.columnar import PendingFeed

        store = Store()
        feed = PendingFeed(store, PC.group_profile)
        store.create(node("n0", {"group": "g"}, cpu="8", mem="32Gi"))
        store.create(producer("mp", {"group": "g"}))
        for i in range(3):
            store.create(pod(f"p{i}"))

        calls = []
        real = PC.encode_snapshot

        def counting(*args, **kwargs):
            calls.append(1)
            return real(*args, **kwargs)

        monkeypatch.setattr(PC, "encode_snapshot", counting)
        solves = []
        from karpenter_tpu.ops import binpack as B

        def counting_solver(inputs, **kwargs):
            solves.append(1)
            return B.solve(inputs, **kwargs)

        registry = GaugeRegistry()

        def tick():
            mps = store.list("MetricsProducer")
            PC.solve_pending(
                store, mps, registry, feed=feed, solver=counting_solver
            )
            return registry.gauge(
                PC.SUBSYSTEM, PC.ADDITIONAL_NODES_NEEDED
            ).get("mp", "default")

        first = tick()
        assert len(calls) == 1
        assert tick() == first  # memo hit: same outputs, no re-encode
        assert len(calls) == 1
        # an unchanged tick skips the DEVICE too: the memoized host
        # outputs are republished without a solve
        assert len(solves) == 1
        store.create(pod("p9"))  # pod churn invalidates
        tick()
        assert len(calls) == 2
        assert len(solves) == 2  # fresh inputs MUST re-solve (no stale outputs)
        tick()
        assert len(calls) == 2
        assert len(solves) == 2  # and the new outputs are memoized again
        store.create(node("n1", {"group": "g"}, cpu="4", mem="16Gi"))
        tick()  # node churn invalidates (profile shape changed)
        assert len(calls) == 3
        store.create(producer("mp2", {"group": "g"}))
        tick()  # producer-set churn invalidates (group axis changed)
        assert len(calls) == 4


class TestShapeDedup:
    """_dedup_rows + pod_weight: the encoder collapses identical pods into
    weighted shape rows (what turns the 100k-pod upload into KBs)."""

    def test_duplicate_pods_collapse_with_counts(self):
        import karpenter_tpu.metrics.producers.pendingcapacity as PC

        store = Store()
        cache = PendingPodCache(store)
        for i in range(50):
            store.create(pod(f"a{i}", cpu="2"))      # 50 x shape A
        for i in range(30):
            store.create(pod(f"b{i}", cpu="500m"))   # 30 x shape B
        store.create(pod("c0", cpu="2", selector={"zone": "z"}))  # 1 x C
        snap = cache.snapshot()
        profiles = [({"cpu": 8.0, "memory": 64.0, "pods": 110.0},
                     set(), set())]
        inputs = PC.encode_snapshot(snap, profiles)
        weights = np.asarray(inputs.pod_weight)
        live = sorted(int(w) for w in weights[weights > 0])
        assert live == [1, 30, 50]  # 81 pods -> 3 weighted shape rows
        # aggregates over the weighted solve equal the pod count
        from karpenter_tpu.ops import binpack as B

        out = B.binpack(inputs, buckets=16)
        assert int(np.sum(np.asarray(out.assigned_count))) + int(
            out.unschedulable
        ) == 81

    def test_incremental_dedup_equals_full_unique_under_churn(self):
        """The watch-maintained dedup (PendingPodCache._dedup_slots) must
        agree with the np.unique-over-all-rows fallback for any history:
        adds, mutations that change a pod's shape, deletes, slot reuse,
        and compaction. Weights are compared as multisets keyed by row
        content (row ORDER is canonicalized by byte-sort either way)."""
        import dataclasses

        from karpenter_tpu.metrics.producers.pendingcapacity import encoder as PCE

        rng = np.random.default_rng(11)
        store = Store()
        cache = PendingPodCache(store)
        cpus = ["100m", "250m", "2", "4"]
        live = {}
        for step in range(600):
            op = rng.random()
            if op < 0.55 or not live:
                name = f"p{step}"
                cpu = str(rng.choice(cpus))
                sel = {"zone": "z"} if rng.random() < 0.3 else None
                store.create(pod(name, cpu=cpu, selector=sel))
                live[name] = True
            elif op < 0.8:
                victim = str(rng.choice(list(live)))
                store.delete("Pod", "default", victim)
                del live[victim]
            else:
                victim = str(rng.choice(list(live)))
                store.update(pod(victim, cpu=str(rng.choice(cpus))))
        snap = cache.snapshot()
        assert snap.dedup_idx is not None
        inc_idx, inc_w = PCE._dedup_rows(snap)
        # force the np.unique fallback on the same snapshot content
        full = dataclasses.replace(snap, dedup_idx=None, dedup_weight=None)
        uni_idx, uni_w = PCE._dedup_rows(full)

        def keyed(idx, weights, include_invalid):
            out = {}
            for i, w in zip(idx, weights):
                if not snap.valid[i] and not include_invalid:
                    continue
                key = (
                    snap.requests[i].tobytes(),
                    snap.required[i].tobytes(),
                    int(snap.shape_id[i]),
                    bool(snap.valid[i]),
                )
                out[key] = out.get(key, 0) + int(w)
            return out

        # the fallback also emits the collapsed free-slot (invalid) row;
        # the incremental path drops it — output-equal, filtered here
        assert keyed(inc_idx, inc_w, True) == keyed(uni_idx, uni_w, False)
        assert sum(keyed(inc_idx, inc_w, True).values()) == len(live)

    def test_node_affinity_constrains_the_solve(self):
        """Required node affinity (NotIn) steers pods off a group on every
        encode path, and pods differing ONLY by affinity dedup apart."""
        from karpenter_tpu.api.core import (
            Affinity,
            NodeAffinity,
            NodeSelector,
            NodeSelectorRequirement,
            NodeSelectorTerm,
        )
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed

        store = Store()
        feed = PendingFeed(store, group_profile)
        cache = PendingPodCache(store)
        store.create(
            node("n0", {"group": "a", "disk": "hdd"}, cpu="8", mem="32Gi")
        )
        store.create(
            node("n1", {"group": "b", "disk": "ssd"}, cpu="8", mem="32Gi")
        )
        store.create(producer("mpa", {"group": "a"}))
        store.create(producer("mpb", {"group": "b"}))
        not_hdd = Affinity(
            node_affinity=NodeAffinity(
                required_during_scheduling_ignored_during_execution=(
                    NodeSelector(
                        node_selector_terms=[
                            NodeSelectorTerm(
                                match_expressions=[
                                    NodeSelectorRequirement(
                                        key="disk",
                                        operator="NotIn",
                                        values=["hdd"],
                                    )
                                ]
                            )
                        ]
                    )
                )
            )
        )
        # 4 unconstrained pods (first-feasible: group a) + 4 identical
        # pods that refuse hdd (must go to group b)
        for i in range(4):
            store.create(pod(f"free{i}", cpu="2"))
        for i in range(4):
            p = pod(f"ssd{i}", cpu="2")
            p.spec.affinity = not_hdd
            store.create(p)
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
        assert oracle["mpa"][0] == 4 and oracle["mpb"][0] == 4
        assert oracle["mpa"][3] == 0 and oracle["mpb"][3] == 0  # none unsched
        snap = cache.snapshot()
        assert len(snap.dedup_idx) == 2  # same size/labels, split by affinity

        # once every affinity pod is gone, the encode drops back to the
        # maskless (no pod_group_forbidden) path even though the shape
        # registry still remembers the affinity shape
        import karpenter_tpu.metrics.producers.pendingcapacity as PC

        for i in range(4):
            store.delete("Pod", "default", f"ssd{i}")
        snap = cache.snapshot()
        assert any(s for s in snap.affinity_shapes)  # registry not pruned
        profiles = [
            ({"cpu": 8.0, "memory": 32.0 * 1024**3, "pods": 110.0},
             {("group", "a"), ("disk", "hdd")}, set()),
        ]
        inputs = PC.encode_snapshot(snap, profiles)
        assert inputs.pod_group_forbidden is None

    def test_preferred_affinity_steers_assignment(self):
        """A pod preferring ssd (weight 80) goes to the ssd group even
        though the hdd group comes first in producer order; identical on
        every encode path; preferences never rescue infeasibility."""
        from karpenter_tpu.api.core import (
            Affinity,
            NodeAffinity,
            NodeSelectorRequirement,
            NodeSelectorTerm,
            PreferredSchedulingTerm,
        )
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed

        store = Store()
        feed = PendingFeed(store, group_profile)
        cache = PendingPodCache(store)
        store.create(
            node("n0", {"group": "a", "disk": "hdd"}, cpu="8", mem="32Gi")
        )
        store.create(
            node("n1", {"group": "b", "disk": "ssd"}, cpu="8", mem="32Gi")
        )
        store.create(producer("mpa", {"group": "a"}))
        store.create(producer("mpb", {"group": "b"}))
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
        for i in range(3):
            store.create(pod(f"free{i}", cpu="2"))  # first-feasible -> a
        for i in range(3):
            p = pod(f"pref{i}", cpu="2")
            p.spec.affinity = prefer_ssd
            store.create(p)
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
        assert oracle["mpa"][0] == 3  # unpreferring pods: first feasible
        assert oracle["mpb"][0] == 3  # preferring pods steered to ssd
        assert oracle["mpa"][3] == 0 and oracle["mpb"][3] == 0
        # a preference for a group that can't fit the pod does NOT make it
        # feasible: a 32-cpu pod preferring ssd is simply unschedulable
        big = pod("big", cpu="32")
        big.spec.affinity = prefer_ssd
        store.create(big)
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
        assert oracle["mpa"][3] == 1 and oracle["mpb"][3] == 1  # global count

    def test_affinity_shape_registry_compacts_after_churn(self):
        """A stream of Jobs each pinning a DISTINCT affinity must not grow
        the shape registry unboundedly: _needs_compaction watches
        _affinity_shapes like the toleration-shape universe."""
        from karpenter_tpu.api.core import (
            Affinity,
            NodeAffinity,
            NodeSelector,
            NodeSelectorRequirement,
            NodeSelectorTerm,
        )

        def pin(zone):
            return Affinity(
                node_affinity=NodeAffinity(
                    required_during_scheduling_ignored_during_execution=(
                        NodeSelector(
                            node_selector_terms=[
                                NodeSelectorTerm(
                                    match_expressions=[
                                        NodeSelectorRequirement(
                                            key="zone",
                                            operator="In",
                                            values=[zone],
                                        )
                                    ]
                                )
                            ]
                        )
                    )
                )
            )

        store = Store()
        cache = PendingPodCache(store)
        for i in range(300):  # distinct shapes, all churned away
            p = pod(f"job{i}", cpu="1")
            p.spec.affinity = pin(f"z{i}")
            store.create(p)
            store.delete("Pod", "default", f"job{i}")
        for i in range(5):  # small live set
            p = pod(f"live{i}", cpu="1")
            p.spec.affinity = pin("keep")
            store.create(p)
        snap = cache.snapshot()  # snapshot() compacts when peak >> live
        assert len(snap.affinity_shapes) < 300 // 4
        assert len(cache) == 5

    def test_effective_requests_drive_the_solve(self):
        """A pod whose init phase dwarfs its main phase must be packed by
        the init size (k8s scheduler fit semantics), on BOTH the feed and
        the oracle path."""
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed

        store = Store()
        feed = PendingFeed(store, group_profile)
        cache = PendingPodCache(store)
        store.create(node("n0", {"group": "g"}, cpu="8", mem="32Gi"))
        store.create(producer("mp", {"group": "g"}))
        # main phase 100m, init phase 4 cpu: 8-cpu nodes hold 2 each (by
        # init size), NOT 80 (by main size)
        for i in range(10):
            p = pod(f"p{i}", cpu="100m")
            p.spec.init_containers = [
                Container(requests={"cpu": Quantity.parse("4")})
            ]
            store.create(p)
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
        pending, nodes_needed, lp, unsched = oracle["mp"]
        assert pending == 10 and unsched == 0
        assert nodes_needed == 5  # 10 pods x 4 cpu / 8 cpu per node

    def test_dedup_survives_pending_set_draining_to_zero(self):
        """All pods scheduling away (the success state) leaves hi > 0
        freed arena rows with an EMPTY incremental dedup — the encode
        must yield the empty solve, not crash on a 0-row gather."""
        import karpenter_tpu.metrics.producers.pendingcapacity as PC
        from karpenter_tpu.metrics.producers.pendingcapacity import encoder as PCE

        store = Store()
        cache = PendingPodCache(store)
        for i in range(5):
            store.create(pod(f"p{i}", cpu="1"))
        for i in range(5):
            store.delete("Pod", "default", f"p{i}")
        snap = cache.snapshot()
        assert snap.requests.shape[0] > 0 and len(snap.dedup_idx) == 0
        idx, weights = PCE._dedup_rows(snap)
        assert len(idx) == 0 and len(weights) == 0
        profiles = [({"cpu": 8.0, "memory": 64.0, "pods": 110.0},
                     set(), set())]
        inputs = PC.encode_snapshot(snap, profiles)
        from karpenter_tpu.ops import binpack as B

        out = B.binpack(inputs, buckets=16)
        assert int(np.sum(np.asarray(out.assigned_count))) == 0
        assert int(out.unschedulable) == 0

    def test_dedup_statuses_equal_across_paths(self):
        """The dedup must be output-invisible: feed path, pod-cache path,
        and oracle path still agree after heavy duplication + churn."""
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed

        store = Store()
        feed = PendingFeed(store, group_profile)
        cache = PendingPodCache(store)
        store.create(node("n0", {"group": "small"}, cpu="8", mem="32Gi"))
        store.create(node("n1", {"group": "big"}, cpu="64", mem="256Gi"))
        store.create(producer("small", {"group": "small"}))
        store.create(producer("big", {"group": "big"}))
        for i in range(40):
            store.create(pod(f"p{i}", cpu="2"))
        for i in range(20):
            store.create(pod(f"q{i}", cpu="16"))  # only fits big
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
        for i in range(10):
            store.delete("Pod", "default", f"p{i}")
        oracle, cached, fed = solve_both(store, cache, feed)
        assert oracle == cached == fed
