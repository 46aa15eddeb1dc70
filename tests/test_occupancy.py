"""Existing-pod domain occupancy: the pending-pods signal evaluated
against the pods the cluster has ALREADY placed.

The kube-scheduler counts existing matching pods per topology domain
when it checks topologySpreadConstraints skew and required inter-pod
(anti-)affinity; a signal that ignores them can promise a placement
(e.g. a replica into a zone that already holds one) the scheduler then
refuses. store/columnar.ScheduledOccupancy maintains the census
incrementally; producers/pendingcapacity.DomainCensus answers the
spread/anti expansions.

reference anchor: the reference stubs the whole producer
(pendingcapacity/producer.go:29-31); the fidelity bar here is the
kube-scheduler's PodTopologySpread and InterPodAffinity filters.
"""

import pytest

from karpenter_tpu.api.core import (
    Affinity,
    Container,
    LabelSelector,
    Node,
    NodeCondition,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    PodSpec,
    PodStatus,
    TopologySpreadConstraint,
    resource_list,
)
from karpenter_tpu.api.metricsproducer import (
    MetricsProducer,
    MetricsProducerSpec,
    PendingCapacitySpec,
)
from karpenter_tpu.cloudprovider.fake import FakeFactory
from karpenter_tpu.runtime import KarpenterRuntime

ZONE_KEY = "topology.kubernetes.io/zone"


class FakeClock:
    def __init__(self, start=1000.0):
        self.now = start

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds


@pytest.fixture
def env():
    provider = FakeFactory()
    clock = FakeClock()
    runtime = KarpenterRuntime(cloud_provider_factory=provider, clock=clock)
    runtime.clock = clock
    return runtime, provider


def ready_node(name, labels, cpu="64", memory="64Gi", pods="110"):
    return Node(
        metadata=ObjectMeta(name=name, labels=dict(labels)),
        spec=NodeSpec(),
        status=NodeStatus(
            allocatable=resource_list(cpu=cpu, memory=memory, pods=pods),
            conditions=[NodeCondition("Ready", "True")],
        ),
    )


def pending_mp(name, selector):
    return MetricsProducer(
        metadata=ObjectMeta(name=name),
        spec=MetricsProducerSpec(
            pending_capacity=PendingCapacitySpec(node_selector=dict(selector))
        ),
    )


def bound_pod(name, labels, node, phase="Running", namespace="default"):
    """A pod the scheduler already placed — the occupancy the census
    counts (assigned and not terminal)."""
    return Pod(
        metadata=ObjectMeta(
            name=name, namespace=namespace, labels=dict(labels)
        ),
        spec=PodSpec(
            node_name=node,
            containers=[
                Container(requests=resource_list(cpu="1", memory="1Gi"))
            ],
        ),
        status=PodStatus(phase=phase),
    )


def spread_pod(name, labels, selector=None, max_skew=1, min_domains=None,
               node_selector=None):
    """A pending pod with one hard zone-spread constraint; selector
    defaults to the pod's own labels (the realistic workload shape)."""
    pod = Pod(
        metadata=ObjectMeta(name=name, labels=dict(labels)),
        spec=PodSpec(
            node_name="",
            containers=[
                Container(requests=resource_list(cpu="1", memory="1Gi"))
            ],
            node_selector=dict(node_selector or {}),
        ),
    )
    pod.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=max_skew,
            topology_key=ZONE_KEY,
            when_unsatisfiable="DoNotSchedule",
            label_selector={
                "matchLabels": dict(selector if selector is not None
                                    else labels)
            },
            min_domains=min_domains,
        )
    ]
    return pod


def anti_pod(name, labels=None, keys=(ZONE_KEY,), co_keys=(),
             selector_labels=None):
    """A pending pod with required self-anti-affinity on `keys` and
    required self-affinity (co-location) on `co_keys`."""
    labels = dict(labels or {"app": "db"})
    pod = Pod(
        metadata=ObjectMeta(name=name, labels=labels),
        spec=PodSpec(
            node_name="",
            containers=[
                Container(requests=resource_list(cpu="1", memory="1Gi"))
            ],
        ),
    )
    selector = LabelSelector(
        match_labels=dict(selector_labels or labels)
    )
    pod.spec.affinity = Affinity(
        pod_anti_affinity=(
            PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=[
                    PodAffinityTerm(label_selector=selector, topology_key=k)
                    for k in keys
                ]
            )
            if keys
            else None
        ),
        pod_affinity=(
            PodAffinity(
                required_during_scheduling_ignored_during_execution=[
                    PodAffinityTerm(label_selector=selector, topology_key=k)
                    for k in co_keys
                ]
            )
            if co_keys
            else None
        ),
    )
    return pod


def zoned(runtime, zones=("a", "b"), extra_node_labels=None):
    for z in zones:
        labels = {"group": z, ZONE_KEY: f"us-{z}"}
        labels.update(extra_node_labels or {})
        runtime.store.create(ready_node(f"n-{z}", labels))
        runtime.store.create(pending_mp(f"group-{z}", {"group": z}))


def pods_per_group(runtime, names):
    return {
        n: runtime.store.get("MetricsProducer", "default", n)
        .status.pending_capacity.pending_pods
        for n in names
    }


def total_unschedulable(runtime, name):
    return (
        runtime.store.get("MetricsProducer", "default", name)
        .status.pending_capacity.unschedulable_pods
    )


class TestWaterFill:
    """Property tests of the water-fill against a scalar placement
    oracle: place pods ONE AT A TIME into a current-minimum domain
    (the only order the kube-scheduler skew check always admits) and
    compare final totals."""

    def _oracle(self, counts, caps, schedulable):
        totals = list(counts)
        placed = [0] * len(counts)
        for _ in range(schedulable):
            candidates = [
                j
                for j in range(len(totals))
                if caps is None or placed[j] < caps[j]
            ]
            if not candidates:
                break
            j = min(candidates, key=lambda j: (totals[j], j))
            totals[j] += 1
            placed[j] += 1
        return placed

    def test_matches_scalar_oracle_totals(self):
        import numpy as np

        from karpenter_tpu.metrics.producers.pendingcapacity.partition import (
            _water_fill,
        )

        rng = np.random.default_rng(7)
        for trial in range(300):
            d = int(rng.integers(1, 9))
            counts = rng.integers(0, 12, d).tolist()
            caps = (
                None
                if rng.random() < 0.3
                else rng.integers(0, 10, d).tolist()
            )
            capacity = (
                10 ** 9 if caps is None else int(sum(caps))
            )
            schedulable = min(int(rng.integers(0, 40)), capacity)
            got = _water_fill(counts, caps, schedulable, int(rng.integers(0, 97)))
            assert int(got.sum()) == schedulable
            if caps is not None:
                assert (got <= np.asarray(caps)).all()
            # water-filling and lowest-first placement agree on the
            # FINAL LEVELS (multiset of totals); the remainder rotation
            # may pick different equal-level domains than the oracle's
            # index tie-break, so compare sorted totals
            oracle = self._oracle(counts, caps, schedulable)
            assert sorted(
                c + int(g) for c, g in zip(counts, got)
            ) == sorted(c + p for c, p in zip(counts, oracle))

    def test_every_placement_is_skew_legal(self):
        """Replaying the water-fill result lowest-first never places
        into a domain more than maxSkew above the running minimum —
        the incremental admissibility the split promises. Modeled with
        caps = m_out + skew - c (the frozen-outside-minimum rule)."""
        import numpy as np

        from karpenter_tpu.metrics.producers.pendingcapacity.partition import (
            _water_fill,
        )

        rng = np.random.default_rng(11)
        for trial in range(200):
            d = int(rng.integers(1, 7))
            skew = int(rng.integers(1, 4))
            counts = rng.integers(0, 8, d).tolist()
            m_out = int(rng.integers(0, 8))
            caps = [max(0, m_out + skew - c) for c in counts]
            schedulable = min(int(rng.integers(0, 30)), sum(caps))
            got = _water_fill(counts, caps, schedulable, trial)
            totals = list(counts)
            remaining = [int(g) for g in got]
            for _ in range(schedulable):
                # place into the lowest destination domain still owed
                j = min(
                    (j for j in range(d) if remaining[j]),
                    key=lambda j: (totals[j], j),
                )
                global_min = min([*totals, m_out])
                assert totals[j] + 1 - global_min <= skew
                totals[j] += 1
                remaining[j] -= 1


class TestScheduledOccupancy:
    """The incremental census itself (store/columnar)."""

    def _store(self):
        from karpenter_tpu.store.store import Store

        return Store()

    def test_counts_bound_nonterminal_pods_only(self):
        from karpenter_tpu.store.columnar import ScheduledOccupancy

        store = self._store()
        census = ScheduledOccupancy(store)
        store.create(bound_pod("running", {"app": "web"}, "n1"))
        store.create(bound_pod("done", {"app": "web"}, "n1",
                               phase="Succeeded"))
        store.create(bound_pod("crashed", {"app": "web"}, "n1",
                               phase="Failed"))
        store.create(
            Pod(metadata=ObjectMeta(name="pending",
                                    labels={"app": "web"}),
                spec=PodSpec(node_name=""))
        )
        with census.view() as (_, spaces):
            key = ("default", (("app", "web"),))
            assert spaces["default"][key[1]] == {"n1": 1}

    def test_rebind_and_delete_undo_exactly(self):
        from karpenter_tpu.store.columnar import ScheduledOccupancy

        store = self._store()
        census = ScheduledOccupancy(store)
        pod = bound_pod("p", {"app": "web"}, "n1")
        store.create(pod)
        g1 = census.generation
        moved = bound_pod("p", {"app": "web"}, "n2")
        moved.metadata.resource_version = pod.metadata.resource_version
        store.update(moved)
        with census.view() as (_, spaces):
            assert spaces["default"][(("app", "web"),)] == {"n2": 1}
        assert census.generation > g1
        store.delete("Pod", "default", "p")
        with census.view() as (_, spaces):
            assert spaces == {}

    def test_no_op_update_keeps_generation(self):
        from karpenter_tpu.store.columnar import ScheduledOccupancy

        store = self._store()
        census = ScheduledOccupancy(store)
        pod = bound_pod("p", {"app": "web"}, "n1")
        store.create(pod)
        g = census.generation
        census._on_event("Modified", pod)  # same placement
        assert census.generation == g

    def test_view_cap_evicts_lru_and_counts(self):
        from karpenter_tpu.store.columnar import ScheduledOccupancy

        store = self._store()
        census = ScheduledOccupancy(store)
        store.create(bound_pod("p", {"app": "a0"}, "n1"))
        cap = ScheduledOccupancy.VIEW_CAP
        for i in range(cap + 3):
            census.view_counts(
                "default", ((("app", f"a{i}"),), ())
            )
        assert census.view_evictions == 3
        # the oldest views were evicted; the newest still resolve from
        # the live set and stay maintained by the event path
        _, counts = census.view_counts("default", ((("app", "a0"),), ()))
        assert counts == {"n1": 1}

    def test_view_counts_many_is_single_generation(self):
        """Multi-form reads share one lock hold: the returned set is
        generation-consistent by construction (a replica moving nodes
        between reads can't appear on neither)."""
        from karpenter_tpu.store.columnar import ScheduledOccupancy

        store = self._store()
        census = ScheduledOccupancy(store)
        store.create(bound_pod("p", {"app": "x", "tier": "db"}, "n1"))
        generation, per_form = census.view_counts_many(
            "default",
            (((("app", "x"),), ()), ((("tier", "db"),), ())),
        )
        assert per_form == [{"n1": 1}, {"n1": 1}]
        assert generation == census.generation

    def test_detached_matches_watch_maintained(self):
        from karpenter_tpu.store.columnar import (
            ScheduledOccupancy,
            occupancy_from_pods,
        )

        store = self._store()
        census = ScheduledOccupancy(store)
        for i in range(3):
            store.create(bound_pod(f"p{i}", {"app": "web"}, f"n{i % 2}"))
        oracle = occupancy_from_pods(store.list("Pod"))
        with census.view() as (_, live):
            with oracle.view() as (_, detached):
                assert live == detached


class TestDomainCensusEpochs:
    """DomainCensus recomputes pod-side answers on bound-pod churn and
    node-side ones (the walk of the nodes' labels) on node churn only."""

    def _census(self):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            DomainCensus,
        )
        from karpenter_tpu.store.columnar import ScheduledOccupancy
        from karpenter_tpu.store.store import Store

        store = Store()
        nodes = [ready_node(f"n-{z}", {ZONE_KEY: f"us-{z}"}) for z in "ab"]
        walks, version = [], [0]

        def nodes_fn():
            walks.append(1)
            return nodes

        census = DomainCensus(
            ScheduledOccupancy(store), nodes_fn, lambda: version[0]
        )
        return store, census, nodes, walks, version

    def test_bound_pod_churn_keeps_the_node_walk(self):
        store, census, _, walks, _ = self._census()
        sel = ((("app", "web"),), ())

        def spread():
            return census.spread(
                "default", sel, ZONE_KEY, ("ignore",), lambda labels: True
            )

        store.create(bound_pod("p0", {"app": "web"}, "n-a"))
        assert spread() == ({"us-a": 1}, {"us-a", "us-b"})
        store.create(bound_pod("p1", {"app": "web"}, "n-b"))
        assert census.stale()
        assert spread() == ({"us-a": 1, "us-b": 1}, {"us-a", "us-b"})
        assert not census.stale()
        assert len(walks) == 1  # the pods moved, the nodes did not
        assert census.refreshes == 2

    def test_node_churn_walks_the_nodes_again(self):
        store, census, nodes, walks, version = self._census()
        sel = ((("app", "web"),), ())
        store.create(bound_pod("p0", {"app": "web"}, "n-c"))
        counts, present = census.spread(
            "default", sel, ZONE_KEY, ("ignore",), lambda labels: True
        )
        assert (counts, present) == ({}, {"us-a", "us-b"})
        nodes.append(ready_node("n-c", {ZONE_KEY: "us-c"}))
        version[0] += 1
        counts, present = census.spread(
            "default", sel, ZONE_KEY, ("ignore",), lambda labels: True
        )
        assert (counts, present) == ({"us-c": 1}, {"us-a", "us-b", "us-c"})
        assert len(walks) == 2


class TestSpreadOccupancy:
    """Water-filled spread splits against existing per-domain counts."""

    def test_new_replicas_fill_less_loaded_domains(self, env):
        """2 existing replicas in zone a: 4 new ones go 1/3 so final
        totals level at 3/3 — the scheduler's skew check admits exactly
        the least-loaded-first order."""
        runtime, _ = env
        zoned(runtime)
        for i in range(2):
            runtime.store.create(
                bound_pod(f"old{i}", {"app": "web"}, "n-a")
            )
        for i in range(4):
            runtime.store.create(spread_pod(f"new{i}", {"app": "web"}))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 3,
        }
        assert total_unschedulable(runtime, "group-a") == 0

    def test_unfillable_outside_domain_caps_by_skew(self, env):
        """A zone among filter-passing live nodes that NO candidate
        group serves freezes the global minimum (the well-known k8s
        spread footgun): each eligible domain caps at outside-min +
        maxSkew, the excess is unschedulable."""
        runtime, _ = env
        zoned(runtime)
        runtime.store.create(
            ready_node("unmanaged", {ZONE_KEY: "us-c"})
        )
        for i in range(5):
            runtime.store.create(spread_pod(f"p{i}", {"app": "web"}))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 1,
        }
        assert total_unschedulable(runtime, "group-a") == 3

    def test_node_filter_excludes_outside_domain(self, env):
        """Same topology, but the pods' nodeSelector excludes the
        unmanaged node (nodeAffinityPolicy=Honor): its zone defines no
        domain for these pods and the split is plain balanced."""
        runtime, _ = env
        zoned(runtime, extra_node_labels={"tier": "app"})
        runtime.store.create(
            ready_node("unmanaged", {ZONE_KEY: "us-c"})
        )
        for i in range(5):
            runtime.store.create(
                spread_pod(f"p{i}", {"app": "web"},
                           node_selector={"tier": "app"})
            )
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sorted(counts.values()) == [2, 3]
        assert total_unschedulable(runtime, "group-a") == 0

    def test_mixed_nil_and_set_soft_selectors_do_not_crash(self, env):
        """Two rows of one hard-spread workload whose SOFT rack
        constraints differ only in selector presence (nil labelSelector
        vs a set one): the canonical row-key ordering used for multi-row
        hand-out must stay totally ordered — None-vs-tuple selector
        forms inside shape tuples crashed every reconcile with TypeError
        before _total_order (r3 advisor, high)."""
        runtime, _ = env
        zoned(runtime)
        rack = "topology.kubernetes.io/rack"
        soft = [
            None,  # nil labelSelector: counts nothing (metav1 nil)
            {"matchLabels": {"app": "web"}},
        ]
        for i, sel in enumerate(soft):
            pod = spread_pod(f"p{i}", {"app": "web"})
            pod.spec.topology_spread_constraints.append(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=rack,
                    when_unsatisfiable="ScheduleAnyway",
                    label_selector=sel,
                )
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # the reconcile completes and the hard zone spread still splits
        # the two replicas across the zones
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 1,
        }
        assert total_unschedulable(runtime, "group-a") == 0

    def test_same_key_different_selectors_enforce_own_counts(self, env):
        """Two DoNotSchedule constraints on the SAME topology key with
        DIFFERENT selectors: each selector's skew binds against its OWN
        census counts. 4 bound tier=backend pods sit in zone a; 2
        pending {app:web, tier:backend} pods carry maxSkew-1 zone
        constraints on both selectors. tier=backend forbids zone a
        (4+1-min > 1) and app=web forbids a second pod in zone b
        (0+2-0 > 1): exactly one pod schedules, into zone b. The
        pre-fix signal promised a pod into zone a — a placement the
        scheduler's second skew check denies (r3 advisor, medium)."""
        runtime, _ = env
        zoned(runtime)
        for i in range(4):
            runtime.store.create(
                bound_pod(f"old{i}", {"tier": "backend"}, "n-a")
            )
        for i in range(2):
            pod = spread_pod(
                f"p{i}", {"app": "web", "tier": "backend"},
                selector={"app": "web"},
            )
            pod.spec.topology_spread_constraints.append(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=ZONE_KEY,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"tier": "backend"}},
                )
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }
        assert total_unschedulable(runtime, "group-a") == 1

    def test_min_domains_cap_subtracts_existing(self, env):
        """minDomains unsatisfied treats the global minimum as 0: each
        domain holds at most maxSkew matching pods INCLUDING existing
        ones."""
        runtime, _ = env
        zoned(runtime)
        runtime.store.create(bound_pod("old", {"app": "web"}, "n-a"))
        for i in range(6):
            runtime.store.create(
                spread_pod(f"p{i}", {"app": "web"}, max_skew=2,
                           min_domains=3)
            )
        runtime.manager.reconcile_all()
        # caps: zone a 2-1=1, zone b 2-0=2 -> 3 schedulable, 3 stuck
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 2,
        }
        assert total_unschedulable(runtime, "group-a") == 3

    def test_non_self_matching_selector_is_static_exclusion(self, env):
        """A pod that does not match its own constraint's selector never
        moves the counts (selfMatchNum=0): domains whose existing skew
        already exceeds maxSkew are excluded, the rest are unbounded."""
        runtime, _ = env
        zoned(runtime)
        for i in range(2):
            runtime.store.create(
                bound_pod(f"other{i}", {"app": "other"}, "n-a")
            )
        for i in range(4):
            runtime.store.create(
                spread_pod(f"p{i}", {"app": "web"},
                           selector={"app": "other"})
            )
        runtime.manager.reconcile_all()
        # zone a holds skew 2 > maxSkew 1 over zone b's 0: excluded
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 4,
        }

    def test_node_affinity_policy_ignore_counts_all_nodes(self, env):
        """nodeAffinityPolicy: Ignore — the unmanaged node's zone
        defines a domain even though the pods' nodeSelector excludes
        it, so the frozen-minimum cap applies (the inverse of
        test_node_filter_excludes_outside_domain)."""
        runtime, _ = env
        zoned(runtime, extra_node_labels={"tier": "app"})
        runtime.store.create(
            ready_node("unmanaged", {ZONE_KEY: "us-c"})
        )
        for i in range(5):
            pod = spread_pod(f"p{i}", {"app": "web"},
                             node_selector={"tier": "app"})
            pod.spec.topology_spread_constraints[0].node_affinity_policy = (
                "Ignore"
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 1,
        }
        assert total_unschedulable(runtime, "group-a") == 3

    def test_anti_census_is_fresh_across_ticks(self, env):
        """Regression (r3 code review): the census memo must be dropped
        when occupancy changes — a replica bound between ticks spends
        its domain on the very next solve, on the PERSISTENT feed-path
        census."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        for i in range(2):
            runtime.store.create(anti_pod(f"db-{i}"))
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sorted(counts.values()) == [1, 1]
        # one replica lands: bind it where the solver put it (zone a),
        # and keep the OTHER one pending
        runtime.store.delete("Pod", "default", "db-0")
        runtime.store.create(
            bound_pod(
                "db-0",
                {"app": "db"},
                "n-a",
            )
        )
        runtime.clock.advance(6)  # past the 5 s producer interval
        runtime.manager.reconcile_all()
        # zone a is now spent: the remaining replica must sit in b only
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_match_label_keys_refines_the_selector(self, env):
        """matchLabelKeys (the pod-template-hash pattern): the pod's own
        values for the listed keys AND into the selector, so a NEW
        revision spreads independently of the old one's placement."""
        runtime, _ = env
        zoned(runtime)
        for i in range(2):
            runtime.store.create(
                bound_pod(
                    f"v1-{i}",
                    {"app": "web", "pod-template-hash": "v1"},
                    "n-a",
                )
            )
        for i in range(4):
            pod = spread_pod(
                f"v2-{i}",
                {"app": "web", "pod-template-hash": "v2"},
                selector={"app": "web"},
            )
            pod.spec.topology_spread_constraints[0].match_label_keys = [
                "pod-template-hash"
            ]
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # v1's zone-a pods don't count against v2: plain balanced split
        # (without matchLabelKeys the water-fill would send 3 to b)
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sorted(counts.values()) == [2, 2]

    def test_match_label_keys_missing_on_pod_is_ignored(self):
        from karpenter_tpu.api.core import (
            TopologySpreadConstraint,
            spread_shape,
        )

        constraint = TopologySpreadConstraint(
            max_skew=1,
            topology_key=ZONE_KEY,
            when_unsatisfiable="DoNotSchedule",
            label_selector={"matchLabels": {"app": "web"}},
            match_label_keys=["pod-template-hash"],
        )
        with_key = spread_shape(
            [constraint], "default",
            {"app": "web", "pod-template-hash": "v2"},
        )
        without_key = spread_shape(
            [constraint], "default", {"app": "web"}
        )
        sel_with = with_key[1][0][3]
        sel_without = without_key[1][0][3]
        assert ("pod-template-hash", "v2") in sel_with[0]
        assert sel_without == ((("app", "web"),), ())

    def test_other_key_zero_capacity_domains_are_excluded(self, env):
        """Multi-key spread: the non-split key can't drive the split,
        but a domain of it with ZERO remaining capacity is a hard
        exclusion — replicas must not be promised to racks the second
        constraint already fills (r3)."""
        # sorts AFTER the zone key: the split must run on zone and
        # treat this as the non-split (budgeted) key
        rack = "x-topology.example.com/rack"
        runtime, _ = env
        for z, r in (("a", "r1"), ("b", "r2")):
            runtime.store.create(
                ready_node(
                    f"n-{z}",
                    {"group": z, ZONE_KEY: f"us-{z}", rack: r},
                )
            )
            runtime.store.create(pending_mp(f"group-{z}", {"group": z}))
        # rack r1 already holds maxSkew matching pods under the
        # minDomains-unsatisfied rule (2 racks < minDomains 3)
        runtime.store.create(bound_pod("old", {"app": "web"}, "n-a"))
        for i in range(4):
            pod = spread_pod(f"p{i}", {"app": "web"})
            pod.spec.topology_spread_constraints.append(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=rack,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"app": "web"}},
                    min_domains=3,
                )
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        # rack r1 (group-a) capped at 1-1=0 by the rack entry; the rack
        # total (0 + 1) also bounds schedulable at 1
        assert counts == {"group-a": 0, "group-b": 1}
        assert total_unschedulable(runtime, "group-a") == 3

    def test_other_key_without_existing_pods_is_unchanged(self, env):
        """No occupancy: the secondary key contributes key-presence
        exclusion only, exactly the prior behavior."""
        # sorts AFTER the zone key: the split must run on zone and
        # treat this as the non-split (budgeted) key
        rack = "x-topology.example.com/rack"
        runtime, _ = env
        for z, r in (("a", "r1"), ("b", "r2")):
            runtime.store.create(
                ready_node(
                    f"n-{z}",
                    {"group": z, ZONE_KEY: f"us-{z}", rack: r},
                )
            )
            runtime.store.create(pending_mp(f"group-{z}", {"group": z}))
        for i in range(4):
            pod = spread_pod(f"p{i}", {"app": "web"})
            pod.spec.topology_spread_constraints.append(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=rack,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"app": "web"}},
                )
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sorted(counts.values()) == [2, 2]
        assert total_unschedulable(runtime, "group-a") == 0

    def test_other_key_positive_caps_are_designated_not_overdrawn(
        self, env
    ):
        """Regression (r3 code review): positive finite capacity on a
        non-split key must bound the DISTRIBUTION, not just the total —
        each chunk pins to one of that key's domains and consumes its
        budget, so concentration can't overdraw a rack."""
        # sorts AFTER the zone key: the split must run on zone and
        # treat this as the non-split (budgeted) key
        rack = "x-topology.example.com/rack"
        runtime, _ = env
        for z, r in (("a", "r1"), ("b", "r2")):
            runtime.store.create(
                ready_node(
                    f"n-{z}",
                    {"group": z, ZONE_KEY: f"us-{z}", rack: r},
                )
            )
            runtime.store.create(pending_mp(f"group-{z}", {"group": z}))
        # rack budget under the minDomains-unsatisfied rule (2 < 3),
        # selector tier=db: r1 admits 2, r2 admits 2-1=1
        runtime.store.create(bound_pod("old", {"tier": "db"}, "n-b"))
        for i in range(4):
            pod = spread_pod(
                f"p{i}", {"app": "web", "tier": "db"},
                selector={"app": "web"},
            )
            pod.spec.topology_spread_constraints.append(
                TopologySpreadConstraint(
                    max_skew=2,
                    topology_key=rack,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"tier": "db"}},
                    min_domains=3,
                )
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        # zone split is balanced [2, 2]; rack budgets cap group-a at 2
        # and group-b at 1 — the 4th replica must NOT be promised to
        # rack r2 just because the total (3) had room elsewhere
        assert counts == {"group-a": 2, "group-b": 1}
        assert total_unschedulable(runtime, "group-a") == 1

    def test_rows_with_different_node_filters_share_the_budget(
        self, env
    ):
        """Regression (r3 code review): a mid-rollout workload whose new
        revision adds a nodeSelector still spends ONE budget — per-(row
        filter) cap views must not each get a fresh ledger."""
        runtime, _ = env
        zoned(runtime, extra_node_labels={"tier": "app"})
        # unmanaged empty zone passing BOTH rows' filters: every row's
        # view caps each zone at maxSkew=1 total for the workload
        runtime.store.create(
            ready_node("unmanaged", {ZONE_KEY: "us-c", "tier": "app"})
        )
        for i in range(2):
            runtime.store.create(
                spread_pod(f"plain-{i}", {"app": "web"})
            )
        for i in range(2):
            runtime.store.create(
                spread_pod(
                    f"selector-{i}", {"app": "web"},
                    node_selector={"tier": "app"},
                )
            )
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 2
        assert total_unschedulable(runtime, "group-a") == 2

    def test_dead_split_domain_freezes_the_minimum(self, env):
        """Regression (r3 code review): a split domain whose groups are
        all excluded by a non-split key is unfillable — it freezes the
        split-key global minimum, capping the surviving domains at its
        count + maxSkew, exactly like an unfillable outside zone."""
        # sorts AFTER the zone key: the split must run on zone and
        # treat this as the non-split (budgeted) key
        rack = "x-topology.example.com/rack"
        runtime, _ = env
        for z, r in (("a", "r1"), ("b", "r2")):
            runtime.store.create(
                ready_node(
                    f"n-{z}",
                    {"group": z, ZONE_KEY: f"us-{z}", rack: r},
                )
            )
            runtime.store.create(pending_mp(f"group-{z}", {"group": z}))
        # rack r1 already violates the foreign-selector rack constraint
        for i in range(2):
            runtime.store.create(
                bound_pod(f"db-{i}", {"tier": "db"}, "n-a")
            )
        for i in range(4):
            pod = spread_pod(f"p{i}", {"app": "web"})
            pod.spec.topology_spread_constraints.append(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=rack,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"tier": "db"}},
                )
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        # zone a is dead (rack r1 over-skewed for the rack selector);
        # its frozen count 0 caps zone b at 0 + maxSkew = 1 — NOT the 2
        # a balanced-then-masked split would promise
        assert counts == {"group-a": 0, "group-b": 1}
        assert total_unschedulable(runtime, "group-a") == 3

    def test_rows_of_one_workload_share_the_budget(self, env):
        """Regression (r3 code review): a workload split across
        request-distinct rows (mid-VPA) draws from ONE budget — two
        rows must not each spend the same per-domain capacity."""
        runtime, _ = env
        zoned(runtime)
        # empty zone c among filter-passing nodes freezes the global
        # minimum: each zone admits maxSkew=1 new replicas TOTAL
        runtime.store.create(
            ready_node("unmanaged", {ZONE_KEY: "us-c"})
        )
        for i in range(2):
            runtime.store.create(
                spread_pod(f"small-{i}", {"app": "web"})
            )
        for i in range(2):
            pod = spread_pod(f"big-{i}", {"app": "web"})
            pod.spec.containers[0].requests = resource_list(
                cpu="2", memory="2Gi"
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        # 2 schedulable TOTAL across both rows (1 per zone), 2 stuck —
        # independent per-row budgets would have promised all 4
        assert sum(counts.values()) == 2
        assert total_unschedulable(runtime, "group-a") == 2

    def test_same_key_dual_policy_takes_the_tighter_cap(self, env):
        """Regression (r3 code review): two same-key constraints with
        different policies are BOTH enforced — the per-domain cap is
        the min over every same-key entry, so a loose Ignore entry
        can't mask a tight Honor one."""
        runtime, _ = env
        zoned(runtime)
        runtime.store.create(
            ready_node("unmanaged", {ZONE_KEY: "us-c"})
        )
        for i in range(5):
            pod = spread_pod(f"p{i}", {"app": "web"}, max_skew=3)
            pod.spec.topology_spread_constraints[0].node_affinity_policy = (
                "Ignore"
            )
            pod.spec.topology_spread_constraints.append(
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=ZONE_KEY,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"app": "web"}},
                )
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # the skew-1 Honor entry caps each zone at 1 (empty zone c
        # freezes the minimum); enforcing only the sorted-first Ignore
        # skew-3 entry would have admitted all 5
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 1,
        }
        assert total_unschedulable(runtime, "group-a") == 3

    def test_differing_affinity_policies_stay_separate_entries(self):
        """Regression (r3 code review): a Honor and an Ignore constraint
        on the same (key, selector) are enforced independently by the
        scheduler — merging them could loosen the caps either enforces
        alone. They must canonicalize to two entries."""
        from karpenter_tpu.api.core import (
            TopologySpreadConstraint,
            spread_shape,
        )

        def constraint(policy):
            return TopologySpreadConstraint(
                max_skew=1,
                topology_key=ZONE_KEY,
                when_unsatisfiable="DoNotSchedule",
                label_selector={"matchLabels": {"app": "web"}},
                node_affinity_policy=policy,
            )

        shape = spread_shape(
            [constraint(""), constraint("Ignore")],
            "default",
            {"app": "web"},
        )
        entries = shape[1]
        assert len(entries) == 2
        assert {entry[5] for entry in entries} == {True, False}
        # same policy twice still merges to the most restrictive
        merged = spread_shape(
            [constraint(""), constraint("")], "default", {"app": "web"}
        )
        assert len(merged[1]) == 1

    def test_namespaces_do_not_share_counts(self, env):
        """Occupancy is namespace-scoped like the scheduler's: another
        namespace's identical pods don't skew this workload."""
        runtime, _ = env
        zoned(runtime)
        for i in range(2):
            runtime.store.create(
                bound_pod(f"old{i}", {"app": "web"}, "n-a",
                          namespace="elsewhere")
            )
        for i in range(4):
            runtime.store.create(spread_pod(f"p{i}", {"app": "web"}))
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sorted(counts.values()) == [2, 2]

    def test_all_encode_paths_agree_with_occupancy(self):
        """Oracle, pod-cache, and feed paths must emit identical
        statuses when existing pods shape the split."""
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
            solve_pending,
        )
        from karpenter_tpu.metrics.registry import GaugeRegistry
        from karpenter_tpu.store.columnar import (
            PendingFeed,
            PendingPodCache,
        )
        from karpenter_tpu.store.store import Store

        store = Store()
        cache = PendingPodCache(store)
        feed = PendingFeed(store, group_profile)
        for z in ("a", "b"):
            store.create(
                ready_node(f"n-{z}", {"group": z, ZONE_KEY: f"us-{z}"})
            )
            store.create(pending_mp(f"group-{z}", {"group": z}))
        store.create(bound_pod("old", {"app": "web"}, "n-a"))
        for i in range(3):
            store.create(spread_pod(f"p{i}", {"app": "web"}))
        store.create(anti_pod("db-0"))
        store.create(bound_pod("db-live", {"app": "db"}, "n-b"))

        results = []
        for kwargs in ({}, {"pod_cache": cache}, {"feed": feed}):
            mps = [
                mp for mp in store.list("MetricsProducer")
                if mp.spec.pending_capacity is not None
            ]
            solve_pending(store, mps, GaugeRegistry(), **kwargs)
            results.append(
                {
                    mp.metadata.name: (
                        mp.status.pending_capacity.pending_pods,
                        mp.status.pending_capacity.unschedulable_pods,
                    )
                    for mp in mps
                }
            )
        assert results[0] == results[1] == results[2]
        # spread: a holds 1 -> water-fill sends 2 to b, 1 to a;
        # anti: db-live occupies zone b -> the db replica lands in a
        assert results[0]["group-a"] == (2, 0)
        assert results[0]["group-b"] == (2, 0)


class TestAntiAffinityOccupancy:
    """Occupied domains are spent; co-location pins to existing pods."""

    def test_occupied_zone_is_spent(self, env):
        """An existing replica in zone a: 3 new zone-anti replicas have
        only zones b and c left — one each, one unschedulable."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b", "c"))
        runtime.store.create(bound_pod("db-live", {"app": "db"}, "n-a"))
        for i in range(3):
            runtime.store.create(anti_pod(f"db-{i}"))
        runtime.manager.reconcile_all()
        assert pods_per_group(
            runtime, ["group-a", "group-b", "group-c"]
        ) == {"group-a": 0, "group-b": 1, "group-c": 1}
        assert total_unschedulable(runtime, "group-a") == 1

    def test_statefulset_labels_still_block(self, env):
        """The existing replica carries per-pod labels (the StatefulSet
        pod-name label); it matches the workload's SELECTOR and must
        still spend its zone."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod(
                "db-0",
                {"app": "db",
                 "statefulset.kubernetes.io/pod-name": "db-0"},
                "n-a",
            )
        )
        runtime.store.create(
            anti_pod(
                "db-1",
                labels={"app": "db",
                        "statefulset.kubernetes.io/pod-name": "db-1"},
                selector_labels={"app": "db"},
            )
        )
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_foreign_pods_do_not_block(self, env):
        """Scheduled pods that don't match the workload selector leave
        its domains free."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(bound_pod("web", {"app": "web"}, "n-a"))
        for i in range(2):
            runtime.store.create(anti_pod(f"db-{i}"))
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sorted(counts.values()) == [1, 1]
        assert total_unschedulable(runtime, "group-a") == 0

    def test_anti_split_respects_spread_zero_capacity(self, env):
        """Regression (r3 code review): a row with BOTH hard spread and
        zone anti-affinity splits by the anti rule, but a zone whose
        spread capacity is already spent (here by a foreign-selector
        constraint over existing cache pods) must never receive the
        anti replica."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b", "c"))
        for i in range(2):
            runtime.store.create(
                bound_pod(f"cache-{i}", {"tier": "cache"}, "n-a")
            )
        for i in range(2):
            pod = anti_pod(f"db-{i}")
            pod.spec.topology_spread_constraints = [
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=ZONE_KEY,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={"matchLabels": {"tier": "cache"}},
                )
            ]
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # zone a holds 2 cache pods, skew 2 > maxSkew 1 over b/c's 0:
        # its spread capacity is zero, so the anti hand-out must use
        # zones b and c even though a is anti-free
        assert pods_per_group(
            runtime, ["group-a", "group-b", "group-c"]
        ) == {"group-a": 0, "group-b": 1, "group-c": 1}
        assert total_unschedulable(runtime, "group-a") == 0

    def test_co_location_pins_to_existing_domain(self, env):
        """Required self-affinity with a live replica: new replicas must
        join a domain that already holds a matching pod."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b", "c"))
        runtime.store.create(bound_pod("db-live", {"app": "db"}, "n-b"))
        for i in range(3):
            runtime.store.create(
                anti_pod(f"db-{i}", keys=(), co_keys=(ZONE_KEY,))
            )
        runtime.manager.reconcile_all()
        assert pods_per_group(
            runtime, ["group-a", "group-b", "group-c"]
        ) == {"group-a": 0, "group-b": 3, "group-c": 0}

    def test_co_location_bootstrap_without_existing_pods(self, env):
        """No matching pod anywhere: the k8s first-replica special case
        — the term imposes nothing beyond one-domain co-location."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        for i in range(3):
            runtime.store.create(
                anti_pod(f"db-{i}", keys=(), co_keys=(ZONE_KEY,))
            )
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sorted(counts.values()) == [0, 3]
        assert total_unschedulable(runtime, "group-a") == 0

    def test_co_and_anti_with_existing_pods(self, env):
        """Rack anti + zone co with a live replica: new replicas join
        the live zone but must take fresh racks."""
        runtime, _ = env
        rack = "topology.kubernetes.io/rack"
        for z, r in (("a", "r1"), ("b", "r2"), ("c", "r3")):
            zone = "z1" if z in ("a", "b") else "z2"
            runtime.store.create(
                ready_node(
                    f"n-{z}",
                    {"group": z, ZONE_KEY: zone, rack: r},
                )
            )
            runtime.store.create(pending_mp(f"group-{z}", {"group": z}))
        # live replica in zone z1 / rack r1
        runtime.store.create(bound_pod("db-live", {"app": "db"}, "n-a"))
        for i in range(2):
            runtime.store.create(
                anti_pod(f"db-{i}", keys=(rack,), co_keys=(ZONE_KEY,))
            )
        runtime.manager.reconcile_all()
        # zone pinned to z1 (groups a, b); rack r1 spent -> only b fits
        # one replica; the second has no rack left in z1
        assert pods_per_group(
            runtime, ["group-a", "group-b", "group-c"]
        ) == {"group-a": 0, "group-b": 1, "group-c": 0}
        assert total_unschedulable(runtime, "group-a") == 1


def soft_spread_pod(name, labels, node_selector=None):
    """A pending pod with a ScheduleAnyway zone-spread constraint —
    scored, never constraining."""
    pod = Pod(
        metadata=ObjectMeta(name=name, labels=dict(labels)),
        spec=PodSpec(
            node_name="",
            containers=[
                Container(requests=resource_list(cpu="1", memory="1Gi"))
            ],
            node_selector=dict(node_selector or {}),
        ),
    )
    pod.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=1,
            topology_key=ZONE_KEY,
            when_unsatisfiable="ScheduleAnyway",
            label_selector={"matchLabels": dict(labels)},
        )
    ]
    return pod


def soft_anti_pod(name, labels=None, weight=100, sign="anti"):
    """A pending pod with PREFERRED self-(anti-)affinity on the zone
    key — the spread-replicas-apart (anti) / pack-replicas-together
    (affinity) preference."""
    from karpenter_tpu.api.core import WeightedPodAffinityTerm

    labels = dict(labels or {"app": "db"})
    pod = Pod(
        metadata=ObjectMeta(name=name, labels=labels),
        spec=PodSpec(
            node_name="",
            containers=[
                Container(requests=resource_list(cpu="1", memory="1Gi"))
            ],
        ),
    )
    term = WeightedPodAffinityTerm(
        weight=weight,
        pod_affinity_term=PodAffinityTerm(
            label_selector=LabelSelector(match_labels=dict(labels)),
            topology_key=ZONE_KEY,
        ),
    )
    pod.spec.affinity = Affinity(
        pod_anti_affinity=(
            PodAntiAffinity(
                preferred_during_scheduling_ignored_during_execution=[term]
            )
            if sign == "anti"
            else None
        ),
        pod_affinity=(
            PodAffinity(
                preferred_during_scheduling_ignored_during_execution=[term]
            )
            if sign == "co"
            else None
        ),
    )
    return pod


class TestSoftConstraintScoring:
    """ScheduleAnyway spread and preferred self-(anti-)affinity as
    pod_group_score contributions — the kube-scheduler's scoring
    plugins, steering but never constraining."""

    def test_prefer_no_schedule_taint_steers_but_never_blocks(self, env):
        """The TaintToleration scoring plugin: a PreferNoSchedule taint
        steers intolerant pods to the untainted group; a tolerating pod
        is indifferent (index tie-break); and with ONLY the tainted
        group present the pods still schedule — a preference, never a
        constraint."""
        from karpenter_tpu.api.core import Taint, Toleration

        runtime, _ = env
        soft = Taint(key="burst", value="spot", effect="PreferNoSchedule")
        tainted = ready_node("n-a", {"group": "a"})
        tainted.spec.taints = [soft]
        runtime.store.create(tainted)
        runtime.store.create(pending_mp("group-a", {"group": "a"}))
        runtime.store.create(ready_node("n-b", {"group": "b"}))
        runtime.store.create(pending_mp("group-b", {"group": "b"}))
        for i in range(3):
            runtime.store.create(
                bound_pod(f"x{i}", {"app": "w"}, "n-a")
            )  # occupancy noise; scoring ignores it
        intolerant = Pod(
            metadata=ObjectMeta(name="plain", labels={"app": "w"}),
            spec=PodSpec(
                node_name="",
                containers=[
                    Container(requests=resource_list(cpu="1", memory="1Gi"))
                ],
            ),
        )
        tolerating = Pod(
            metadata=ObjectMeta(name="tol", labels={"app": "w"}),
            spec=PodSpec(
                node_name="",
                containers=[
                    Container(requests=resource_list(cpu="1", memory="1Gi"))
                ],
            ),
        )
        tolerating.spec.tolerations = [
            Toleration(key="burst", value="spot",
                       effect="PreferNoSchedule")
        ]
        runtime.store.create(intolerant)
        runtime.store.create(tolerating)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        # intolerant steers to b; tolerating ties -> group-a (index 0)
        assert counts == {"group-a": 1, "group-b": 1}, counts
        assert total_unschedulable(runtime, "group-a") == 0

    def test_prefer_no_schedule_only_group_still_schedules(self, env):
        from karpenter_tpu.api.core import Taint

        runtime, _ = env
        tainted = ready_node("n-a", {"group": "a"})
        tainted.spec.taints = [
            Taint(key="burst", value="spot", effect="PreferNoSchedule")
        ]
        runtime.store.create(tainted)
        runtime.store.create(pending_mp("group-a", {"group": "a"}))
        for i in range(2):
            runtime.store.create(
                Pod(
                    metadata=ObjectMeta(name=f"p{i}",
                                        labels={"app": "w"}),
                    spec=PodSpec(
                        node_name="",
                        containers=[
                            Container(
                                requests=resource_list(
                                    cpu="1", memory="1Gi"
                                )
                            )
                        ],
                    ),
                )
            )
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a"]) == {"group-a": 2}
        assert total_unschedulable(runtime, "group-a") == 0

    def test_schedule_anyway_steers_to_emptier_domain(self, env):
        runtime, _ = env
        zoned(runtime)
        for i in range(2):
            runtime.store.create(
                bound_pod(f"old{i}", {"app": "web"}, "n-a")
            )
        for i in range(4):
            runtime.store.create(soft_spread_pod(f"p{i}", {"app": "web"}))
        runtime.manager.reconcile_all()
        # a preference steers the whole shape to the emptier zone; it
        # must never mark anything unschedulable
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 4,
        }
        assert total_unschedulable(runtime, "group-a") == 0

    def test_schedule_anyway_never_blocks(self, env):
        """Only the loaded zone is feasible: ScheduleAnyway yields."""
        runtime, _ = env
        zoned(runtime, zones=("a",))
        runtime.store.create(bound_pod("old", {"app": "web"}, "n-a"))
        for i in range(3):
            runtime.store.create(soft_spread_pod(f"p{i}", {"app": "web"}))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a"]) == {"group-a": 3}
        assert total_unschedulable(runtime, "group-a") == 0

    def test_schedule_anyway_ranks_keyless_groups_last(self, env):
        runtime, _ = env
        # group-a has NO zone label; group-b is keyed and empty
        runtime.store.create(ready_node("n-a", {"group": "a"}))
        runtime.store.create(pending_mp("group-a", {"group": "a"}))
        runtime.store.create(
            ready_node("n-b", {"group": "b", ZONE_KEY: "us-b"})
        )
        runtime.store.create(pending_mp("group-b", {"group": "b"}))
        for i in range(2):
            runtime.store.create(soft_spread_pod(f"p{i}", {"app": "web"}))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 2,
        }

    def test_preferred_anti_avoids_occupied_zone(self, env):
        runtime, _ = env
        zoned(runtime)
        runtime.store.create(bound_pod("db-live", {"app": "db"}, "n-a"))
        for i in range(2):
            runtime.store.create(soft_anti_pod(f"db-{i}"))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 2,
        }
        assert total_unschedulable(runtime, "group-a") == 0

    def test_preferred_anti_yields_when_only_occupied_zone_fits(self, env):
        runtime, _ = env
        zoned(runtime, zones=("a",))
        runtime.store.create(bound_pod("db-live", {"app": "db"}, "n-a"))
        runtime.store.create(soft_anti_pod("db-1"))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a"]) == {"group-a": 1}
        assert total_unschedulable(runtime, "group-a") == 0

    def test_preferred_co_packs_toward_existing_replicas(self, env):
        runtime, _ = env
        zoned(runtime)
        runtime.store.create(bound_pod("db-live", {"app": "db"}, "n-b"))
        for i in range(2):
            runtime.store.create(soft_anti_pod(f"db-{i}", sign="co"))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 2,
        }

    def test_foreign_selector_preference_is_not_modeled(self, env):
        """A preferred anti term over ANOTHER workload's labels is not
        self-matching: decoded, no score contribution — the row stays
        on plain first-feasible assignment."""
        runtime, _ = env
        zoned(runtime)
        runtime.store.create(bound_pod("web", {"app": "web"}, "n-a"))
        pod = soft_anti_pod("db-1", labels={"app": "db"})
        term = (
            pod.spec.affinity.pod_anti_affinity
            .preferred_during_scheduling_ignored_during_execution[0]
        )
        term.pod_affinity_term.label_selector = LabelSelector(
            match_labels={"app": "web"}
        )
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # first feasible group wins (group-a), despite web's presence
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 0,
        }

    def test_all_encode_paths_agree_with_soft_scoring(self):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
            solve_pending,
        )
        from karpenter_tpu.metrics.registry import GaugeRegistry
        from karpenter_tpu.store.columnar import (
            PendingFeed,
            PendingPodCache,
        )
        from karpenter_tpu.store.store import Store

        store = Store()
        cache = PendingPodCache(store)
        feed = PendingFeed(store, group_profile)
        for z in ("a", "b"):
            store.create(
                ready_node(f"n-{z}", {"group": z, ZONE_KEY: f"us-{z}"})
            )
            store.create(pending_mp(f"group-{z}", {"group": z}))
        store.create(bound_pod("old", {"app": "web"}, "n-a"))
        for i in range(3):
            store.create(soft_spread_pod(f"p{i}", {"app": "web"}))
        store.create(soft_anti_pod("db-1"))
        results = []
        for kwargs in ({}, {"pod_cache": cache}, {"feed": feed}):
            mps = [
                mp for mp in store.list("MetricsProducer")
                if mp.spec.pending_capacity is not None
            ]
            solve_pending(store, mps, GaugeRegistry(), **kwargs)
            results.append(
                {
                    mp.metadata.name: (
                        mp.status.pending_capacity.pending_pods,
                        mp.status.pending_capacity.unschedulable_pods,
                    )
                    for mp in mps
                }
            )
        assert results[0] == results[1] == results[2]
        # web steers to the emptier zone b; db has no occupancy signal
        # and stays first-feasible (a)
        assert results[0]["group-b"][0] == 3


def foreign_pod(name, sign="anti", key=ZONE_KEY, selector=None,
                namespaces=()):
    """A pending pod with a required (anti-)affinity term whose selector
    matches ANOTHER workload's pods (app=redis), not its own."""
    pod = Pod(
        metadata=ObjectMeta(name=name, labels={"app": "web"}),
        spec=PodSpec(
            node_name="",
            containers=[
                Container(requests=resource_list(cpu="1", memory="1Gi"))
            ],
        ),
    )
    term = PodAffinityTerm(
        label_selector=LabelSelector(
            match_labels=dict(selector or {"app": "redis"})
        ),
        topology_key=key,
        namespaces=list(namespaces),
    )
    pod.spec.affinity = Affinity(
        pod_anti_affinity=(
            PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=[term]
            )
            if sign == "anti"
            else None
        ),
        pod_affinity=(
            PodAffinity(
                required_during_scheduling_ignored_during_execution=[term]
            )
            if sign == "co"
            else None
        ),
    )
    return pod


class TestForeignAffinityOccupancy:
    """Required (anti-)affinity against OTHER workloads' pods, enforced
    against SCHEDULED state through the census (the pending-vs-pending
    interaction stays out of scope, docs/OPERATIONS.md)."""

    def test_foreign_anti_blocks_occupied_domains(self, env):
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(bound_pod("redis", {"app": "redis"}, "n-a"))
        for i in range(3):
            runtime.store.create(foreign_pod(f"web-{i}"))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 3,
        }
        assert total_unschedulable(runtime, "group-a") == 0

    def test_foreign_anti_without_matching_pods_is_free(self, env):
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        for i in range(2):
            runtime.store.create(foreign_pod(f"web-{i}"))
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 2
        assert total_unschedulable(runtime, "group-a") == 0

    def test_foreign_co_requires_an_occupied_domain(self, env):
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(bound_pod("redis", {"app": "redis"}, "n-b"))
        for i in range(2):
            runtime.store.create(foreign_pod(f"web-{i}", sign="co"))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 2,
        }

    def test_foreign_co_without_matching_pods_is_unschedulable(self, env):
        """No first-replica bootstrap for a foreign selector: if no
        matching pod exists anywhere, the scheduler will never admit
        the pod — the signal must not size a scale-up for it."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(foreign_pod("web-0", sign="co"))
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 0
        assert total_unschedulable(runtime, "group-a") == 1

    def test_foreign_namespaces_scope_the_census(self, env):
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod("redis", {"app": "redis"}, "n-a",
                      namespace="other")
        )
        # the term scopes to namespace "other": the redis there blocks
        runtime.store.create(
            foreign_pod("web-0", namespaces=("other",))
        )
        # an unscoped term sees only the pod's OWN namespace: free
        runtime.store.create(foreign_pod("web-1"))
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 1,
        }

    def test_self_anti_with_extra_namespaces_blocks_there_too(self, env):
        """Regression (r3 code review): a SELF-matching anti term whose
        namespaces list spans the own namespace plus others must also
        block on matching pods in those other namespaces — the self
        machinery only censuses the own one."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod("twin", {"app": "db"}, "n-a", namespace="staging")
        )
        pod = anti_pod("db-0")
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespaces = ["default", "staging"]
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # the staging twin occupies zone a: the replica must land in b
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_self_co_with_extra_namespaces_pins_to_their_domains(self, env):
        """Regression (r3 advisor, low): a SELF-matching required
        co-location term whose namespaces list spans the own namespace
        plus others — matching pods in THOSE namespaces pin the
        scheduler to their domains even when the own namespace holds no
        match. The pre-fix model granted first-replica bootstrap and
        promised placement anywhere."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod("twin", {"app": "db"}, "n-b", namespace="staging")
        )
        pod = anti_pod("db-0", keys=(), co_keys=(ZONE_KEY,))
        term = (
            pod.spec.affinity.pod_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespaces = ["default", "staging"]
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # the staging twin occupies zone b: the replica is pinned there
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_self_co_with_extra_namespaces_keeps_bootstrap(self, env):
        """The +2 projection keeps the scheduler's first-replica grace:
        with NO matching pod in ANY in-scope namespace the term imposes
        nothing (the pod itself is in scope and matches), unlike a true
        foreign co term."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        pod = anti_pod("db-0", keys=(), co_keys=(ZONE_KEY,))
        term = (
            pod.spec.affinity.pod_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespaces = ["default", "staging"]
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 1
        assert total_unschedulable(runtime, "group-a") == 0

    def test_self_co_hostname_with_extra_namespaces_is_honest(self, env):
        """A hostname-keyed self co term with extra namespaces: a
        matching pod in a foreign in-scope namespace pins the pod to an
        EXISTING node, which fresh nodes can never satisfy — the row is
        honestly unschedulable; with no matching pod anywhere the
        first-replica grace applies (r4 code review)."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod("twin", {"app": "db"}, "n-b", namespace="staging")
        )
        pod = anti_pod("db-0", keys=(), co_keys=("kubernetes.io/hostname",))
        term = (
            pod.spec.affinity.pod_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespaces = ["default", "staging"]
        runtime.store.create(pod)
        # grace case: no matching pod in scope for this second workload
        pod2 = anti_pod(
            "web-0", labels={"app": "web"}, keys=(),
            co_keys=("kubernetes.io/hostname",),
        )
        term2 = (
            pod2.spec.affinity.pod_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term2.namespaces = ["default", "staging"]
        runtime.store.create(pod2)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        # db-0 pinned to the staging twin's node: unschedulable on any
        # scale-up; web-0 bootstraps freely
        assert sum(counts.values()) == 1
        assert total_unschedulable(runtime, "group-a") == 1

    def test_hostname_self_co_pins_to_existing_node(self, env):
        """Required self co-location on kubernetes.io/hostname with a
        matching scheduled pod: new replicas must join its EXISTING
        node, which no scale-up's fresh node can satisfy — honestly
        unschedulable (was silently unconstrained before r4)."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(bound_pod("db-live", {"app": "db"}, "n-a"))
        for i in range(2):
            runtime.store.create(
                anti_pod(f"db-{i}", keys=(),
                         co_keys=("kubernetes.io/hostname",))
            )
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 0
        assert total_unschedulable(runtime, "group-a") == 2

    def test_hostname_self_co_bootstrap_promises_one(self, env):
        """With NO matching pod anywhere, the first replica bootstraps
        onto a fresh node — but replicas beyond the first must join
        ITS node, which a group-level pack cannot promise: exactly one
        replica is promised, the rest honestly unschedulable."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        for i in range(3):
            runtime.store.create(
                anti_pod(f"db-{i}", keys=(),
                         co_keys=("kubernetes.io/hostname",))
            )
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 1
        assert total_unschedulable(runtime, "group-a") == 2

    def test_hostname_self_co_multi_row_promises_one_total(self, env):
        """A hostname-co workload split across request-distinct rows
        (mid-VPA rollout): the single bootstrap promise is handed to
        the canonically-first row — one replica total, never one per
        row."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        for i, cpu in enumerate(("1", "2", "2", "1")):
            pod = anti_pod(f"db-{i}", keys=(),
                           co_keys=("kubernetes.io/hostname",))
            pod.spec.containers[0].requests = resource_list(
                cpu=cpu, memory="1Gi"
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 1
        assert total_unschedulable(runtime, "group-a") == 3

    def test_hostname_self_co_with_zone_anti_promises_one(self, env):
        """Required zone ANTI-affinity (one per zone) combined with
        required hostname CO-location (all on one node) is contradictory
        beyond a single replica: the per-domain hand-out is truncated to
        ONE promise total, never one per anti domain."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        for i in range(3):
            runtime.store.create(
                anti_pod(f"db-{i}", keys=(ZONE_KEY,),
                         co_keys=("kubernetes.io/hostname",))
            )
        runtime.manager.reconcile_all()
        counts = pods_per_group(runtime, ["group-a", "group-b"])
        assert sum(counts.values()) == 1
        assert total_unschedulable(runtime, "group-a") == 2

    def test_none_namespaces_field_is_tolerated(self):
        """namespaces: null hydrates to None — the shape build must not
        crash (r3 code review)."""
        from karpenter_tpu.api.core import pod_affinity_shape

        pod = foreign_pod("web-0")
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespaces = None
        shape = pod_affinity_shape(
            pod.spec.affinity, pod.metadata.labels, "default"
        )
        assert shape[4] == (
            (-1, ZONE_KEY, ((("app", "redis"),), ()),
             ("names", ("default",))),
        )

    def test_match_label_keys_make_per_revision_anti_groups(self, env):
        """podAffinityTerm.matchLabelKeys (k8s >= 1.29): the incoming
        pod's values refine the selector, so two revisions of one app
        form SEPARATE anti-groups — v1's zone doesn't block v2."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod(
                "v1-0",
                {"app": "db", "pod-template-hash": "v1"},
                "n-a",
            )
        )
        pod = anti_pod(
            "v2-0",
            labels={"app": "db", "pod-template-hash": "v2"},
            selector_labels={"app": "db"},
        )
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.match_label_keys = ["pod-template-hash"]
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # the v1 replica in zone a does NOT match the refined selector
        # (hash=v2): zone a stays open and first-feasible wins
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 1,
            "group-b": 0,
        }

    def test_mismatch_label_keys_turn_self_terms_foreign(self, env):
        """mismatchLabelKeys excludes the pod's own value: the term can
        only match OTHER revisions — enforced as a foreign term against
        their scheduled replicas."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod(
                "v1-0",
                {"app": "db", "pod-template-hash": "v1"},
                "n-a",
            )
        )
        pod = anti_pod(
            "v2-0",
            labels={"app": "db", "pod-template-hash": "v2"},
            selector_labels={"app": "db"},
        )
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.mismatch_label_keys = ["pod-template-hash"]
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # the refined selector (app=db AND hash NotIn [v2]) matches the
        # v1 replica: its zone a is forbidden
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_namespace_selector_resolves_against_labels(self, env):
        """A namespaceSelector term censuses every namespace whose
        labels match — the Namespace mirror closes the last decode-only
        slice."""
        from karpenter_tpu.api.core import Namespace

        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            Namespace(metadata=ObjectMeta(
                name="data", namespace="", labels={"team": "data"}))
        )
        runtime.store.create(
            Namespace(metadata=ObjectMeta(
                name="web", namespace="", labels={"team": "web"}))
        )
        runtime.store.create(
            bound_pod("redis", {"app": "redis"}, "n-a", namespace="data")
        )
        pod = foreign_pod("app-0")
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespace_selector = LabelSelector(
            match_labels={"team": "data"}
        )
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # the data namespace's redis occupies zone a: blocked there
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_empty_namespace_selector_means_all_namespaces(self, env):
        from karpenter_tpu.api.core import Namespace

        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            Namespace(metadata=ObjectMeta(name="anywhere", namespace=""))
        )
        runtime.store.create(
            bound_pod("redis", {"app": "redis"}, "n-a",
                      namespace="anywhere")
        )
        pod = foreign_pod("app-0")
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespace_selector = LabelSelector()  # {} = every namespace
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_self_anti_with_namespace_selector_stays_one_per_domain(
        self, env
    ):
        """A namespaceSelector anti term whose selector matches the
        pod's OWN labels keeps the self 1-per-domain rule (conservative:
        whether the own namespace matches can't be known at shape
        build) AND blocks on matching pods in selector-matching
        namespaces."""
        from karpenter_tpu.api.core import Namespace

        runtime, _ = env
        zoned(runtime, zones=("a", "b", "c"))
        runtime.store.create(
            Namespace(metadata=ObjectMeta(
                name="prod", namespace="", labels={"env": "prod"}))
        )
        runtime.store.create(
            bound_pod("db-live", {"app": "db"}, "n-a", namespace="prod")
        )
        for i in range(3):
            pod = anti_pod(f"db-{i}")
            term = (
                pod.spec.affinity.pod_anti_affinity
                .required_during_scheduling_ignored_during_execution[0]
            )
            term.namespace_selector = LabelSelector(
                match_labels={"env": "prod"}
            )
            runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # zone a blocked by prod's replica; the three pending replicas
        # still spread one-per-domain over b and c: one unschedulable
        assert pods_per_group(
            runtime, ["group-a", "group-b", "group-c"]
        ) == {"group-a": 0, "group-b": 1, "group-c": 1}
        assert total_unschedulable(runtime, "group-a") == 1

    def test_namespace_selector_unions_with_explicit_list(self, env):
        """The k8s combination rule: namespaces + namespaceSelector is
        the UNION of both scopes."""
        from karpenter_tpu.api.core import Namespace

        runtime, _ = env
        zoned(runtime, zones=("a", "b", "c"))
        runtime.store.create(
            Namespace(metadata=ObjectMeta(
                name="data", namespace="", labels={"team": "data"}))
        )
        runtime.store.create(
            bound_pod("redis-1", {"app": "redis"}, "n-a",
                      namespace="data")
        )
        runtime.store.create(
            bound_pod("redis-2", {"app": "redis"}, "n-b",
                      namespace="legacy")
        )
        pod = foreign_pod("app-0", namespaces=("legacy",))
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespace_selector = LabelSelector(
            match_labels={"team": "data"}
        )
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # both scopes block: data's redis in zone a, legacy's in zone b
        assert pods_per_group(
            runtime, ["group-a", "group-b", "group-c"]
        ) == {"group-a": 0, "group-b": 0, "group-c": 1}

    def test_co_with_namespace_selector_requires_matching_ns(self, env):
        from karpenter_tpu.api.core import Namespace

        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            Namespace(metadata=ObjectMeta(
                name="data", namespace="", labels={"team": "data"}))
        )
        runtime.store.create(
            bound_pod("redis", {"app": "redis"}, "n-b", namespace="data")
        )
        pod = foreign_pod("app-0", sign="co")
        term = (
            pod.spec.affinity.pod_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespace_selector = LabelSelector(
            match_labels={"team": "data"}
        )
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        # must join data's redis zone
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_anti_selector_falls_back_without_namespace_objects(
        self, env
    ):
        """Regression (r3 code review): with NO Namespace objects to
        resolve against (fixtures, simulations), an anti
        namespaceSelector must block conservatively against every
        namespace the occupancy knows — silent non-enforcement would
        over-promise."""
        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        runtime.store.create(
            bound_pod("redis", {"app": "redis"}, "n-a", namespace="data")
        )
        pod = foreign_pod("app-0")
        term = (
            pod.spec.affinity.pod_anti_affinity
            .required_during_scheduling_ignored_during_execution[0]
        )
        term.namespace_selector = LabelSelector(
            match_labels={"team": "data"}
        )
        runtime.store.create(pod)
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a", "group-b"]) == {
            "group-a": 0,
            "group-b": 1,
        }

    def test_foreign_hostname_co_is_unschedulable(self, env):
        """'Must share a NODE with an existing pod' can never be met by
        a scale-up's fresh nodes."""
        runtime, _ = env
        zoned(runtime, zones=("a",))
        runtime.store.create(bound_pod("redis", {"app": "redis"}, "n-a"))
        runtime.store.create(
            foreign_pod("web-0", sign="co",
                        key="kubernetes.io/hostname")
        )
        runtime.manager.reconcile_all()
        assert pods_per_group(runtime, ["group-a"]) == {"group-a": 0}
        assert total_unschedulable(runtime, "group-a") == 1


class TestEncodeMemoWithOccupancy:
    """Bound-pod churn must not thrash the encode memo of fleets without
    spread/anti constraints — and must invalidate it for fleets with."""

    def _solve(self, store, feed, counter):
        from karpenter_tpu.metrics.producers import pendingcapacity as PC
        from karpenter_tpu.metrics.registry import GaugeRegistry

        mps = [
            mp for mp in store.list("MetricsProducer")
            if mp.spec.pending_capacity is not None
        ]
        PC.solve_pending(store, mps, GaugeRegistry(), feed=feed)
        return counter[0]

    @pytest.fixture
    def counting_encode(self, monkeypatch):
        from karpenter_tpu.metrics.producers import pendingcapacity as PC

        counter = [0]
        real = PC.encode_snapshot

        def counting(*args, **kwargs):
            counter[0] += 1
            return real(*args, **kwargs)

        monkeypatch.setattr(PC, "encode_snapshot", counting)
        return counter

    def test_unconstrained_fleet_ignores_bound_churn(self, counting_encode):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed
        from karpenter_tpu.store.store import Store

        store = Store()
        feed = PendingFeed(store, group_profile)
        store.create(ready_node("n1", {"group": "a"}))
        store.create(pending_mp("group-a", {"group": "a"}))
        store.create(
            Pod(metadata=ObjectMeta(name="p0"),
                spec=PodSpec(
                    node_name="",
                    containers=[Container(
                        requests=resource_list(cpu="1", memory="1Gi"))],
                ))
        )
        assert self._solve(store, feed, counting_encode) == 1
        store.create(bound_pod("scheduled", {"app": "web"}, "n1"))
        assert self._solve(store, feed, counting_encode) == 1  # memo hit

    def test_census_refresh_counter_published(self):
        from karpenter_tpu.metrics.producers import pendingcapacity as PC
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.metrics.registry import GaugeRegistry
        from karpenter_tpu.store.columnar import PendingFeed
        from karpenter_tpu.store.store import Store

        store = Store()
        feed = PendingFeed(store, group_profile)
        registry = GaugeRegistry()
        store.create(ready_node("n1", {"group": "a", ZONE_KEY: "us-a"}))
        store.create(pending_mp("group-a", {"group": "a"}))
        store.create(spread_pod("p0", {"app": "web"}))

        def solve():
            mps = [
                mp for mp in store.list("MetricsProducer")
                if mp.spec.pending_capacity is not None
            ]
            PC.solve_pending(store, mps, registry, feed=feed)

        solve()
        counter = registry.register(
            "runtime", "census_refresh_total", kind="counter"
        )
        first = counter.get("-", "-") or 0
        assert first >= 1  # the first constrained solve recomputed
        solve()  # nothing churned: served from the census memo
        assert (counter.get("-", "-") or 0) == first
        store.create(bound_pod("scheduled", {"app": "web"}, "n1"))
        solve()
        assert (counter.get("-", "-") or 0) == first + 1

    def test_constrained_fleet_reencodes_on_bound_churn(
        self, counting_encode
    ):
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
        )
        from karpenter_tpu.store.columnar import PendingFeed
        from karpenter_tpu.store.store import Store

        store = Store()
        feed = PendingFeed(store, group_profile)
        store.create(ready_node("n1", {"group": "a", ZONE_KEY: "us-a"}))
        store.create(pending_mp("group-a", {"group": "a"}))
        store.create(spread_pod("p0", {"app": "web"}))
        assert self._solve(store, feed, counting_encode) == 1
        store.create(bound_pod("scheduled", {"app": "web"}, "n1"))
        # the mask inputs depend on occupancy now: must re-encode
        assert self._solve(store, feed, counting_encode) == 2


class TestLongRunBoundedState:
    @pytest.mark.skipif(
        not __import__("os").environ.get("KARPENTER_SCALE_TESTS"),
        reason="soak loop; battletest sets KARPENTER_SCALE_TESTS=1",
    )
    def test_churning_workloads_keep_caches_bounded(self, env):
        """Soak: 300 ticks of constrained workloads appearing, binding,
        and vanishing (enough churn to cross the compaction floor).
        Every watch-maintained structure must track the LIVE state, not
        the history: pending arena and shape registries compact, census
        groups drain, views stay under the cap."""
        from karpenter_tpu.store.columnar import ScheduledOccupancy

        runtime, _ = env
        zoned(runtime, zones=("a", "b"))
        for tick in range(300):
            workload = f"w{tick}"
            for i in range(4):
                runtime.store.create(
                    spread_pod(f"{workload}-p{i}", {"app": workload})
                )
            runtime.store.create(
                bound_pod(f"{workload}-live", {"app": workload}, "n-a")
            )
            runtime.clock.advance(6)
            runtime.manager.reconcile_all()
            # the previous workload schedules and vanishes entirely
            if tick:
                old = f"w{tick - 1}"
                for i in range(4):
                    runtime.store.delete("Pod", "default", f"{old}-p{i}")
                runtime.store.delete("Pod", "default", f"{old}-live")
        feed = runtime.producer_factory._pending_feed
        occupancy = feed.occupancy
        with occupancy.view() as (_, spaces):
            live_groups = sum(len(g) for g in spaces.values())
        assert live_groups <= 2  # only the newest workload's pods
        # one view per distinct selector ever queried, still under the
        # cap here — no spurious per-tick registrations (cap ENFORCEMENT
        # is exercised by test_view_cap_evicts_lru_and_counts, which
        # crosses it)
        assert len(occupancy._views) <= 301
        assert occupancy.view_evictions == 0
        assert ScheduledOccupancy.VIEW_CAP >= 301  # soak stays below
        # pending arena compacted: slot peak tracks the handful of live
        # pods plus growth since the last compaction, not the 1500
        # churned through
        assert feed.pods._hi < 600
        snap = feed.pods.snapshot()
        # registry compaction dropped the dead workloads' shapes
        assert len(snap.spread_shapes) < 100


class TestSimulateWithOccupancy:
    def test_what_if_zone_relieves_spread_pressure(self):
        """A hypothetical group in a FRESH zone becomes an eligible
        domain with zero occupancy: the water-fill routes the overflow
        there, and the delta report shows the unschedulable pods it
        absorbs."""
        from karpenter_tpu.simulate import simulate_delta
        from karpenter_tpu.store.store import Store

        store = Store()
        store.create(
            ready_node("n-a", {"group": "a", ZONE_KEY: "us-a"})
        )
        store.create(pending_mp("group-a", {"group": "a"}))
        # an empty unmanaged zone freezes the minimum: one zone-a slot
        store.create(ready_node("unmanaged", {ZONE_KEY: "us-b"}))
        for i in range(3):
            store.create(spread_pod(f"p{i}", {"app": "web"}))
        report = simulate_delta(
            store,
            [
                {
                    "name": "what-if-b",
                    "allocatable": {"cpu": "64", "memory": "64Gi"},
                    "labels": {ZONE_KEY: "us-b"},
                }
            ],
        )
        base = report["baseline"]["groups"]["default/group-a"]
        assert base["pending_pods"] == 1  # frozen minimum caps zone a
        assert report["baseline"]["unschedulable_pods"] == 2
        # the hypothetical zone-b group fills the frozen zone itself:
        # every pod schedules — and the what-if group absorbs ONLY the
        # overflow no real group can take (the no-steal invariant)
        assert report["what_if"]["unschedulable_pods"] == 0
        assert report["delta"]["unschedulable_pods"] == -2
        groups = report["what_if"]["groups"]
        assert groups["default/group-a"]["pending_pods"] == 2
        assert groups["what-if-b"]["pending_pods"] == 1

    def test_simulation_respects_existing_replicas(self):
        """The dry-run solve sees the same census the production tick
        does: an occupied zone never receives the simulated replica."""
        from karpenter_tpu.simulate import simulate
        from karpenter_tpu.store.store import Store

        store = Store()
        for z in ("a", "b"):
            store.create(
                ready_node(f"n-{z}", {"group": z, ZONE_KEY: f"us-{z}"})
            )
            store.create(pending_mp(f"group-{z}", {"group": z}))
        store.create(bound_pod("db-live", {"app": "db"}, "n-a"))
        store.create(anti_pod("db-1"))
        report = simulate(store)
        assert report["groups"]["default/group-a"]["pending_pods"] == 0
        assert report["groups"]["default/group-b"]["pending_pods"] == 1
        assert report["unschedulable_pods"] == 0
