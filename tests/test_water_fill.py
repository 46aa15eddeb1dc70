"""The batched water-fill (pendingcapacity/partition._water_fill_rows)
against the one-row fill and a plain reference of the rule, and the spread
expansion that batches a workload's only row against the per-row rule."""

import numpy as np
import pytest

from karpenter_tpu.api.core import (
    Affinity,
    Container,
    LabelSelector,
    Node,
    NodeCondition,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodAffinityTerm,
    PodAntiAffinity,
    PodSpec,
    TopologySpreadConstraint,
    resource_list,
)
from karpenter_tpu.api.metricsproducer import (
    MetricsProducer,
    MetricsProducerSpec,
    PendingCapacitySpec,
)
from karpenter_tpu.metrics.producers.pendingcapacity import (
    encoder,
    group_profile,
    solve_pending,
    spread,
)
from karpenter_tpu.metrics.producers.pendingcapacity.partition import (
    _UNBOUNDED,
    _water_fill,
    _water_fill_rows,
)
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.store.columnar import PendingFeed, PendingPodCache
from karpenter_tpu.store.store import Store


def _reference_fill(counts, caps, schedulable, seed):
    """The rule in plain ints: raise the water level one step at a time
    while the capped additions still fit, then hand the remainder to the
    domains at that level that can still grow, from position
    seed % candidates on."""
    d = len(counts)
    cap = [None] * d if caps is None else [int(c) for c in caps]

    def adds(level):
        return [
            max(level - c, 0) if k is None else min(max(level - c, 0), k)
            for c, k in zip(counts, cap)
        ]

    level = min(counts)
    top = (
        max(counts) + schedulable
        if caps is None
        else max(c + k for c, k in zip(counts, cap))
    )
    while level < top and sum(adds(level + 1)) <= schedulable:
        level += 1
    out = adds(level)
    remainder = schedulable - sum(out)
    candidates = [
        j for j in range(d)
        if counts[j] + out[j] == level and (cap[j] is None or out[j] < cap[j])
    ]
    if remainder > 0 and candidates:
        offset = seed % len(candidates)
        for position, j in enumerate(candidates):
            if (position - offset) % len(candidates) < remainder:
                out[j] += 1
    return out


def _fill_case(regime, seed):
    """[R, D] counts and caps (None = all unbounded), schedulable and
    seeds for one regime of the fuzz."""
    rng = np.random.default_rng(seed)
    rows, d = 40, int(rng.integers(1, 7))
    counts = rng.integers(0, 12, (rows, d))
    if regime == "zeros":
        counts[rng.random((rows, d)) < 0.5] = 0
        caps = rng.integers(0, 4, (rows, d))
    elif regime == "unbounded":
        caps = None
    elif regime == "unbounded_entries":
        caps = rng.integers(0, 10, (rows, d))
        caps[rng.random((rows, d)) < 0.4] = _UNBOUNDED
    else:  # "under_sum": caps summing below schedulable as often as not
        caps = rng.integers(0, 6, (rows, d))
    if caps is None or regime == "unbounded_entries":
        limit = np.full(rows, 60)
    else:
        limit = caps.sum(axis=1) + 8
    schedulable = np.array([int(rng.integers(0, n + 1)) for n in limit])
    seeds = rng.integers(0, 2**40, rows)
    return counts, caps, schedulable, seeds


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize(
    "regime", ["zeros", "unbounded", "unbounded_entries", "under_sum"]
)
def test_rows_fill_as_each_row_alone(regime, seed):
    counts, caps, schedulable, seeds = _fill_case(regime, seed)
    got = _water_fill_rows(counts, caps, schedulable, seeds)
    assert got.shape == counts.shape and got.dtype == np.int64
    for r in range(len(counts)):
        row_caps = None if caps is None else caps[r].tolist()
        one = _water_fill(
            counts[r].tolist(), row_caps, int(schedulable[r]), int(seeds[r])
        )
        want = _reference_fill(
            counts[r].tolist(), row_caps, int(schedulable[r]), int(seeds[r])
        )
        assert got[r].tolist() == one.tolist() == want, (regime, seed, r)


# -- the spread expansion: batched rows against the per-row rule ----------

ZONE = "topology.kubernetes.io/zone"
RACK = "example.com/rack"


def _pod(name, app, cpu="1", node="", keys=(ZONE,), max_skew=1,
         avoid=None):
    """A pod of workload `app` with a required spread over `keys`
    selecting its own workload; `avoid` adds a required zone
    anti-affinity against another workload's pods."""
    spread_on = [
        TopologySpreadConstraint(
            max_skew=max_skew, topology_key=key,
            when_unsatisfiable="DoNotSchedule",
            label_selector={"matchLabels": {"app": app}},
        )
        for key in keys
    ]
    affinity = None if avoid is None else Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                PodAffinityTerm(
                    label_selector=LabelSelector(match_labels={"app": avoid}),
                    topology_key=ZONE,
                )
            ]
        )
    )
    return Pod(
        metadata=ObjectMeta(name=name, labels={"app": app}),
        spec=PodSpec(
            node_name=node,
            containers=[Container(requests=resource_list(cpu=cpu, memory="1Gi"))],
            topology_spread_constraints=spread_on if keys else [],
            affinity=affinity,
        ),
    )


def _mixed_fleet():
    """Three one-node groups in three zones over two racks. Two
    workloads whose only row batches (web, api); one split across two
    request-distinct rows (batch); one spread over zone and rack
    (cache); one whose zone anti-affinity against db freezes zone a
    (front); and unconstrained pods."""
    store = Store()
    cache = PendingPodCache(store)
    feed = PendingFeed(store, group_profile)
    for z, rack in (("a", "r1"), ("b", "r1"), ("c", "r2")):
        store.create(Node(
            metadata=ObjectMeta(
                name=f"n-{z}",
                labels={"group": z, ZONE: f"zone-{z}", RACK: rack},
            ),
            status=NodeStatus(
                allocatable=resource_list(cpu="64", memory="256Gi", pods="110"),
                conditions=[NodeCondition("Ready", "True")],
            ),
        ))
        store.create(MetricsProducer(
            metadata=ObjectMeta(name=f"group-{z}"),
            spec=MetricsProducerSpec(
                pending_capacity=PendingCapacitySpec(node_selector={"group": z})
            ),
        ))
    store.create(_pod("web-old", "web", node="n-a"))
    store.create(_pod("api-old-0", "api", node="n-b"))
    store.create(_pod("api-old-1", "api", node="n-b"))
    store.create(_pod("db-0", "db", node="n-a", keys=()))
    for i in range(4):
        store.create(_pod(f"web-{i}", "web"))
    for i in range(7):
        store.create(_pod(f"api-{i}", "api", max_skew=2))
    for i in range(5):
        store.create(_pod(f"batch-{i}", "batch", cpu="1" if i < 3 else "2"))
    for i in range(5):
        store.create(_pod(f"cache-{i}", "cache", keys=(ZONE, RACK)))
    for i in range(4):
        store.create(_pod(f"front-{i}", "front", avoid="db"))
    for i in range(3):
        store.create(_pod(f"plain-{i}", "plain", keys=()))
    return store, cache, feed


def _producers(store):
    return [
        mp for mp in store.list("MetricsProducer")
        if mp.spec.pending_capacity is not None
    ]


def _expansion_inputs(monkeypatch, path):
    """The arguments of the one spread expansion a solve of the mixed
    fleet makes on encode `path`."""
    store, cache, feed = _mixed_fleet()
    calls = []
    expand = encoder._expand_spread_rows

    def recording(*args, **kwargs):
        calls.append((args, kwargs))
        return expand(*args, **kwargs)

    monkeypatch.setattr(encoder, "_expand_spread_rows", recording)
    kwargs = {"oracle": {}, "pod_cache": {"pod_cache": cache},
              "feed": {"feed": feed}}[path]
    solve_pending(store, _producers(store), GaugeRegistry(), **kwargs)
    monkeypatch.setattr(encoder, "_expand_spread_rows", expand)
    assert len(calls) == 1
    args, kwargs = calls[0]
    kwargs.pop("stats", None)
    return args, kwargs


@pytest.mark.parametrize("path", ["oracle", "pod_cache", "feed"])
def test_batched_rows_match_the_per_row_rule(monkeypatch, path):
    """A view whose dead mask is all-False instead of None changes no
    cap, fill or mask, but keeps every row off the batch: the per-row
    rule's output, to hold the batched expansion to."""
    args, kwargs = _expansion_inputs(monkeypatch, path)
    batched_stats = {}
    got = spread._expand_spread_rows(*args, **kwargs, stats=batched_stats)

    state = spread._spread_state

    def never_batched(*a, **k):
        view = state(*a, **k)
        if view["dead"] is None:
            view = dict(view, dead=np.zeros(len(args[1]), bool))
        return view

    monkeypatch.setattr(spread, "_spread_state", never_batched)
    per_row_stats = {}
    want = spread._expand_spread_rows(*args, **kwargs, stats=per_row_stats)

    # web and api batch; batch (two rows), cache (two keys) and front
    # (anti-dead zone a) take the per-row code
    assert batched_stats == {"batched_rows": 2}
    assert per_row_stats == {"batched_rows": 0}
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    snap, row_idx = args[0], args[2]
    assert int((snap.spread_id[row_idx] != 0).sum()) == 6


def test_all_encode_paths_agree_on_a_mixed_spread_fleet():
    """Oracle (store.list), pod-cache and feed paths emit the same
    statuses where batched and per-row spread rows share one solve."""
    store, cache, feed = _mixed_fleet()
    results = []
    for kwargs in ({}, {"pod_cache": cache}, {"feed": feed}):
        mps = _producers(store)
        solve_pending(store, mps, GaugeRegistry(), **kwargs)
        results.append({
            mp.metadata.name: (
                mp.status.pending_capacity.pending_pods,
                mp.status.pending_capacity.additional_nodes_needed,
                mp.status.pending_capacity.unschedulable_pods,
            )
            for mp in mps
        })
    assert results[0] == results[1] == results[2]
    # every producer reports the solve's unschedulable count
    (unschedulable,) = {u for _, _, u in results[0].values()}
    pending = sum(p for p, _, _ in results[0].values())
    assert pending + unschedulable == 4 + 7 + 5 + 5 + 4 + 3
