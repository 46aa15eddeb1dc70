"""Device-resident fleet state (solver/resident.py) — the resident
scatter-update path must be BIT-IDENTICAL to a cold full re-encode +
full upload, across every churn shape the control plane produces.

Structure: a ResidentWorld drives the REAL pipeline — watch-style churn
into a PendingPodCache, delta encoding through a SnapshotDeltaCache
(which publishes the scatter plans), and dispatch through a
SolverService whose residency layer consumes them. Every tick asserts
three ways:

  * the service's outputs equal the plain XLA program's outputs on the
    cold inputs (no service in between) and the numpy mirror's (integer
    fields exact, lp_bound within the established ±1 contract — though
    on the same backend it is in fact equal);
  * the RESIDENT DEVICE BUFFERS equal pad_to_bucket(cold full encode)
    leaf for leaf, byte for byte — the direct pin that scattering
    changed rows reproduces the full upload exactly;
  * the residency counters report the expected serve kind (hit /
    scatter / rebuild), so the fast path can't silently rot into
    rebuild-every-tick while outputs stay green.
"""

import dataclasses

import numpy as np
import pytest

from karpenter_tpu.api.core import (
    Container,
    ObjectMeta,
    Pod,
    PodSpec,
    PodStatus,
)
from karpenter_tpu.metrics.producers.pendingcapacity import encoder as E
from karpenter_tpu.metrics.producers.pendingcapacity.encoder import (
    SnapshotDeltaCache,
    _encode_full,
    resident_plan,
)
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.ops.binpack import solve as binpack_solve
from karpenter_tpu.ops.numpy_binpack import binpack_numpy
from karpenter_tpu.solver import SolverService
from karpenter_tpu.solver.bucketing import pad_to_bucket
from karpenter_tpu.store.columnar import PendingPodCache
from karpenter_tpu.utils.quantity import Quantity

BUCKETS = 8


def pod(name, cpu="100m", mem="128Mi", selector=None):
    return Pod(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=PodSpec(
            containers=[Container(requests={
                "cpu": Quantity.parse(cpu),
                "memory": Quantity.parse(mem),
            })],
            node_selector=dict(selector or {}),
        ),
        status=PodStatus(phase="Pending"),
    )


def make_profiles():
    """Stable profile tuples — reused across ticks like NodeMirror's
    memo, which is what arms the delta cache's identity check."""
    return [
        ({"cpu": 8.0, "memory": 32.0 * 1024**3, "pods": 110.0},
         {("zone", "z"), ("group", "a")}, set()),
        ({"cpu": 64.0, "memory": 256.0 * 1024**3, "pods": 110.0},
         {("group", "b")}, set()),
    ]


class ResidentWorld:
    """One tenant's real encode->solve pipeline through the resident
    service; outputs are checked against the plain XLA program."""

    def __init__(self, shard_threshold=0):
        self.cache = PendingPodCache(store=None, capacity=64)
        self.profiles = make_profiles()
        self.delta = SnapshotDeltaCache()
        self.svc = SolverService(
            registry=GaugeRegistry(), shard_threshold=shard_threshold,
        )
        # force the scatter rung: the auto gate keeps it off CPU
        # "devices" (scatter ~= upload there), but these tests PIN the
        # scatter math itself and run on the virtual-CPU harness
        self.svc._resident.scatter = "always"

    def close(self):
        self.svc.close()

    def upsert(self, p):
        self.cache._upsert((p.metadata.namespace, p.metadata.name), p)

    def remove(self, name):
        self.cache._remove(("default", name))

    def tick(self, expect=None):
        """Encode + solve one tick; assert output parity (device ref +
        numpy mirror) and resident-buffer parity vs the cold encode."""
        snap = self.cache.snapshot()
        inputs = self.delta.encode(snap, self.profiles)
        before = (
            self.svc.stats.resident_hits,
            self.svc.stats.resident_scatters,
            self.svc.stats.resident_rebuilds,
        )
        out = self.svc.solve(inputs, buckets=BUCKETS, backend="xla")
        cold = _encode_full(snap, self.profiles)
        ref = binpack_solve(cold, buckets=BUCKETS, backend="xla")
        ref_np = binpack_numpy(cold, buckets=BUCKETS)
        for mirror, label in ((ref, "xla"), (ref_np, "numpy")):
            np.testing.assert_array_equal(
                out.assigned, np.asarray(mirror.assigned), err_msg=label
            )
            np.testing.assert_array_equal(
                out.assigned_count, np.asarray(mirror.assigned_count),
                err_msg=label,
            )
            np.testing.assert_array_equal(
                out.nodes_needed, np.asarray(mirror.nodes_needed),
                err_msg=label,
            )
            assert int(out.unschedulable) == int(mirror.unschedulable)
        self._assert_buffers_equal_cold(inputs, cold)
        if expect is not None:
            after = (
                self.svc.stats.resident_hits,
                self.svc.stats.resident_scatters,
                self.svc.stats.resident_rebuilds,
            )
            deltas = tuple(b - a for a, b in zip(before, after))
            want = {
                "hit": (1, 0, 0),
                "scatter": (0, 1, 0),
                "rebuild": (0, 0, 1),
            }[expect]
            assert deltas == want, (expect, deltas)
        return out

    def _assert_buffers_equal_cold(self, inputs, cold):
        """The strong pin: the resident device buffers byte-equal the
        padded cold encode — scattering reproduced the full upload."""
        entry = None
        with self.svc._resident._lock:
            for e in self.svc._resident._entries.values():
                if e.host is inputs:
                    entry = e
        if entry is None:
            return  # served without residency (e.g. coalesced) — outputs
            # parity above still holds
        padded = pad_to_bucket(cold, entry.shape[:5])
        for field in dataclasses.fields(padded):
            want = getattr(padded, field.name)
            got = getattr(entry.stacked, field.name)
            if want is None or got is None:
                assert want is None and got is None, field.name
                continue
            np.testing.assert_array_equal(
                np.asarray(got)[0], np.asarray(want),
                err_msg=field.name,
            )


@pytest.fixture
def world():
    w = ResidentWorld()
    yield w
    w.close()


class TestResidentChurn:
    def test_unchanged_fleet_is_identity_hit(self, world):
        for i in range(12):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        # no churn: the SAME inputs object comes back (delta-cache
        # memo) and the dispatch serves the resident buffers untouched
        world.tick(expect="hit")
        world.tick(expect="hit")

    def test_add_remove_rows_scatter(self, world):
        for i in range(16):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        world.upsert(pod("fresh-a", cpu="900m"))
        world.upsert(pod("fresh-b", cpu="901m"))
        world.tick(expect="scatter")
        world.remove("p3")
        world.remove("p7")
        world.tick(expect="scatter")

    def test_resize_and_relabel_rows_scatter(self, world):
        for i in range(12):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m",
                             selector={"zone": "z"}))
        # pre-intern the second label pair so the later relabel stays
        # inside one label universe (universe growth is a full-pass
        # cache-key change by design, not a delta)
        world.upsert(pod("seed", cpu="400m", selector={"group": "a"}))
        world.tick(expect="rebuild")
        # resize: same pod, new request vector
        world.upsert(pod("p4", cpu="750m", selector={"zone": "z"}))
        world.tick(expect="scatter")
        # relabel within the existing label universe: selectors move to
        # the already-interned label pair
        world.upsert(pod("p5", cpu="105m", selector={"group": "a"}))
        world.upsert(pod("p0", cpu="100m", selector={"group": "a"}))
        world.tick(expect="scatter")

    def test_weight_only_churn_scatter(self, world):
        for i in range(10):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        # a replica of an EXISTING shape: dedup keys unchanged, only
        # the multiplicity column moves
        world.upsert(pod("p3-replica", cpu="103m"))
        inputs = world.delta.encode(
            world.cache.snapshot(), world.profiles
        )
        plan = resident_plan(inputs)
        assert plan is not None
        assert len(plan.weight_rows) >= 1
        world.tick(expect="scatter")

    def test_group_churn_full_reencode_rebuild(self, world):
        for i in range(10):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        # node churn: NEW profile tuples (identity differs) force the
        # delta cache through the full pass — no plan, residency
        # rebuilds, outputs still exact
        world.profiles = make_profiles()
        world.upsert(pod("extra", cpu="500m"))
        world.tick(expect="rebuild")

    def test_recovery_restart_drops_residency(self, world):
        for i in range(10):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        world.upsert(pod("fresh", cpu="800m"))
        # the recovery-boot seam: service caches + delta entries +
        # scatter plans all drop; the next tick is a cold rebuild and
        # the one after scatters again
        world.svc.reset_caches()
        world.delta.reset()
        assert world.svc._resident.resident_bytes() == 0
        world.tick(expect="rebuild")
        world.upsert(pod("fresh-2", cpu="801m"))
        world.tick(expect="scatter")

    def test_tenant_chains_keep_separate_entries(self):
        a, b = ResidentWorld(), ResidentWorld()
        # one SHARED service (the multi-tenant topology): each tenant's
        # identity chain occupies its own resident entry
        b.svc.close()
        b.svc = a.svc
        try:
            for i in range(8):
                a.upsert(pod(f"a{i}", cpu=f"{100 + i}m"))
                b.upsert(pod(f"b{i}", cpu=f"{300 + i}m"))
            a.tick(expect="rebuild")
            b.tick(expect="rebuild")
            # interleaved unchanged ticks: both chains stay resident
            a.tick(expect="hit")
            b.tick(expect="hit")
            # tenant churn scatters its own chain only
            a.upsert(pod("a-new", cpu="950m"))
            a.tick(expect="scatter")
            b.tick(expect="hit")
            # tenant removal: b's chain simply stops being dispatched;
            # a keeps serving resident
            a.tick(expect="hit")
        finally:
            a.close()


class TestShardThresholdCrossing:
    def test_crossing_rebuilds_then_scatters_both_modes(self):
        w = ResidentWorld(shard_threshold=1 << 60)
        try:
            for i in range(16):
                w.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
            w.tick(expect="rebuild")  # single-device mode
            w.tick(expect="hit")
            # cross UP: the same fleet now routes through the mesh —
            # mode changes, residency rebuilds under NamedShardings
            w.svc.shard_threshold = 1
            w.upsert(pod("up-a", cpu="700m"))
            w.tick(expect="rebuild")
            assert w.svc.stats.shard_dispatches >= 1
            w.upsert(pod("up-b", cpu="701m"))
            w.tick(expect="scatter")  # sharded-mode scatter
            w.tick(expect="hit")
            # cross DOWN: back to the single-device program — mode
            # changes again, residency rebuilds again
            w.svc.shard_threshold = 1 << 60
            w.upsert(pod("down-a", cpu="702m"))
            w.tick(expect="rebuild")
            w.tick(expect="hit")
        finally:
            w.close()


class TestNeverBlock:
    def test_device_failure_drops_residency_and_recovers(self, world):
        from karpenter_tpu.faults import injected_faults

        for i in range(10):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        with injected_faults(seed=7) as reg:
            reg.plan("solver.dispatch", mode="error")
            world.upsert(pod("during-fault", cpu="600m"))
            snap = world.cache.snapshot()
            inputs = world.delta.encode(snap, world.profiles)
            out = world.svc.solve(inputs, buckets=BUCKETS, backend="xla")
            ref = binpack_numpy(
                _encode_full(snap, world.profiles), buckets=BUCKETS
            )
            np.testing.assert_array_equal(out.assigned, ref.assigned)
            # the ladder discarded residency wholesale
            assert world.svc._resident.resident_bytes() == 0
        # post-fault: the next tick re-establishes residency cold
        world.upsert(pod("after-fault", cpu="601m"))
        world.tick(expect="rebuild")
        world.tick(expect="hit")

    def test_poisoned_plan_falls_back_to_rebuild(self, world):
        for i in range(10):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        world.upsert(pod("fresh", cpu="888m"))
        snap = world.cache.snapshot()
        inputs = world.delta.encode(snap, world.profiles)
        plan = resident_plan(inputs)
        assert plan is not None
        # poison: rows past the resident extent must rebuild, not raise
        plan.rows = np.asarray([10**6], np.int32)
        world.tick(expect="rebuild")


class TestUnchangedTickSkipsEncodeAndUpload:
    """The residency regression guard (non-slow): an unchanged
    fleet tick costs zero encode and zero upload."""

    def test_unchanged_tick_zero_encode_zero_upload(self, world):
        for i in range(12):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        snap = world.cache.snapshot()
        first = world.delta.encode(snap, world.profiles)
        world.svc.solve(first, buckets=BUCKETS, backend="xla")
        fulls_before = world.delta.fulls
        uploads_before = list(world.svc._stages.get("upload", ()))
        # the unchanged tick: same snapshot generation -> same inputs
        # OBJECT from the delta memo -> resident identity hit
        again = world.delta.encode(world.cache.snapshot(), world.profiles)
        assert again is first  # zero host encode
        world.svc.solve(again, buckets=BUCKETS, backend="xla")
        assert world.delta.fulls == fulls_before  # no full pass
        assert world.svc.stats.resident_hits >= 1
        # the upload ring gained only the 0.0 marker — nothing crossed
        # the transfer link for this dispatch
        uploads = list(world.svc._stages["upload"])
        new = uploads[len(uploads_before):]
        assert new and max(new) == 0.0

    def test_resident_gauges_exposed(self, world):
        for i in range(8):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        world.svc.publish_gauges()
        reg = world.svc.registry
        assert reg.gauge("solver", "resident_bytes").get("-", "-") > 0
        assert reg.gauge("solver", "resident_rows").get("-", "-") > 0


class TestEntryLifecycle:
    def test_scatter_chain_keeps_one_live_entry(self, world):
        """A superseded predecessor is EVICTED when its successor
        stores (scatter and rebuild rungs alike): one churning chain
        must occupy one LRU slot, not fill MAX_ENTRIES with dead
        stacks that would evict other tenants' live chains."""
        for i in range(10):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        for k in range(2 * world.svc._resident.MAX_ENTRIES):
            world.upsert(pod(f"churn-{k}", cpu=f"{500 + k}m"))
            world.tick(expect="scatter")
        with world.svc._resident._lock:
            assert len(world.svc._resident._entries) == 1
        # and the CPU auto-gated rebuild rung evicts the same way
        world.svc._resident.scatter = "auto"
        for k in range(3):
            world.upsert(pod(f"auto-{k}", cpu=f"{700 + k}m"))
            world.tick(expect="rebuild")
        with world.svc._resident._lock:
            assert len(world.svc._resident._entries) == 1


class TestScatterAutoGate:
    def test_cpu_auto_mode_rebuilds_instead_of_scattering(self, world):
        """The shipped default: on a CPU jax backend the scatter rung
        stays OFF (device memory IS host memory — a copy-on-write
        scatter costs what the memcpy upload costs), so churn rebuilds;
        identity hits still serve with zero upload."""
        world.svc._resident.scatter = "auto"
        for i in range(10):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.tick(expect="rebuild")
        world.tick(expect="hit")
        world.upsert(pod("fresh", cpu="900m"))
        world.tick(expect="rebuild")  # plan exists but the gate holds
        world.tick(expect="hit")


class TestPlanRegistry:
    def test_plan_chain_is_bounded(self, world):
        """Successive deltas must not chain prev references without
        bound: registering tick k's plan drops tick k-1's entry."""
        for i in range(8):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        prev_inputs = world.delta.encode(
            world.cache.snapshot(), world.profiles
        )
        for k in range(4):
            world.upsert(pod(f"churn-{k}", cpu=f"{500 + k}m"))
            inputs = world.delta.encode(
                world.cache.snapshot(), world.profiles
            )
            assert resident_plan(inputs) is not None
            assert resident_plan(prev_inputs) is None
            prev_inputs = inputs

    def test_reset_clears_plans(self, world):
        for i in range(8):
            world.upsert(pod(f"p{i}", cpu=f"{100 + i}m"))
        world.delta.encode(world.cache.snapshot(), world.profiles)
        world.upsert(pod("x", cpu="400m"))
        inputs = world.delta.encode(world.cache.snapshot(), world.profiles)
        assert resident_plan(inputs) is not None
        E.reset_delta_cache()
        world.delta.reset()
        assert resident_plan(inputs) is None
