"""Replicated control plane: clock discipline, partition leases, fenced
handoff, the split-brain regression, and the seeded failover world.

The acceptance bar (ISSUE: replicated control plane):

  * a wall clock stepped backward cannot extend a stale lease, and one
    stepped forward within the skew tolerance cannot steal a fresh one;
  * two electors racing one lease resolve by CAS — the stale
    resourceVersion loser's fenced actuation is rejected with
    `FenceRejected` and the flight recorder attributes the rejection to
    the loser's trace;
  * killing the leader mid-storm reassigns its tenants to survivors and
    reconverges to the no-fault fixed point within 10 ticks, with zero
    duplicate and zero lost `set_replicas` writes (journal-audited);
  * without `--partitions` the runtime is byte-identical to the
    single-replica deployment: no replication plane, no Lease objects,
    no lease fault-point traffic, no karpenter_replica_* metrics.

`make test-failover` runs exactly this file.
"""

from __future__ import annotations

import json
import os
import urllib.request

import pytest

from karpenter_tpu import faults
from karpenter_tpu.faults import FaultRegistry, ProcessCrash
from karpenter_tpu.leaderelection import LeaderElector
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.recovery.fence import (
    FenceRejectedError,
    FenceValidator,
)
from karpenter_tpu.replication import (
    PartitionLeaseManager,
    ReplicatedControlPlane,
    SkewedClock,
    TenantHandoff,
    crash_plan,
    partition_of,
    partition_plans,
    rendezvous_rank,
)
from karpenter_tpu.store import Store
from karpenter_tpu.store.store import ConflictError

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_registry():
    yield
    faults.uninstall()


class FakeClock:
    def __init__(self, start=1000.0):
        self.now = start

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds


class TestClockDiscipline:
    """Satellite: monotonic lease expiry + skew tolerance in the
    LeaderElector."""

    def test_backward_wall_step_cannot_extend_stale_lease(self):
        """The holder dies; a candidate whose wall clock then steps
        BACKWARD (so wall expiry never fires) still takes over once its
        monotonic observation of the frozen stamp ages past the
        margin."""
        store = Store()
        wall = FakeClock(1000.0)
        holder = LeaderElector(
            store, identity="a", clock=wall, lease_duration=5.0
        )
        assert holder.try_acquire()
        # candidate: wall clock stepped back BEFORE the renew stamp, an
        # honest separate monotonic clock
        skewed = SkewedClock(wall, offset_s=-30.0)
        mono = FakeClock(0.0)
        candidate = LeaderElector(
            store, identity="b", clock=skewed, monotonic=mono,
            lease_duration=5.0,
        )
        # wall expiry can never fire: skewed now (970) < renew (1000)
        assert not candidate.try_acquire()
        mono.advance(4.0)  # within lease_duration + skew_tolerance
        assert not candidate.try_acquire()
        mono.advance(3.0)  # observation age 7 > 5 + 1: stale
        assert candidate.try_acquire()
        assert candidate.is_leader()

    def test_forward_step_within_skew_cannot_steal_fresh_lease(self):
        """A candidate whose wall clock runs ahead by less than
        lease_duration + skew_tolerance never preempts a holder that
        renews on time."""
        store = Store()
        wall = FakeClock(1000.0)
        holder = LeaderElector(
            store, identity="a", clock=wall, lease_duration=5.0
        )
        ahead = SkewedClock(wall, offset_s=5.5)  # < 5 + 1 margin
        candidate = LeaderElector(
            store, identity="b", clock=ahead, monotonic=FakeClock(0.0),
            lease_duration=5.0,
        )
        assert holder.try_acquire()
        for _ in range(20):
            wall.advance(2.0)  # holder renews well inside the lease
            assert holder.try_acquire()
            assert not candidate.try_acquire()
        assert holder.is_leader()

    def test_forward_step_past_margin_does_steal(self):
        """The complement: a skew larger than the margin IS a dead
        holder as far as the candidate can tell — takeover happens (and
        the fence, not the lease, is what protects actuation)."""
        store = Store()
        wall = FakeClock(1000.0)
        holder = LeaderElector(
            store, identity="a", clock=wall, lease_duration=5.0
        )
        ahead = SkewedClock(wall, offset_s=7.0)  # > 5 + 1 margin
        candidate = LeaderElector(
            store, identity="b", clock=ahead, monotonic=FakeClock(0.0),
            lease_duration=5.0,
        )
        assert holder.try_acquire()
        assert candidate.try_acquire()

    def test_own_leadership_lapses_on_monotonic_clock(self):
        """is_leader() is judged on OUR monotonic renew age, so a
        holder that stops renewing stops believing it leads even if the
        store still names it."""
        store = Store()
        wall = FakeClock(1000.0)
        mono = FakeClock(0.0)
        holder = LeaderElector(
            store, identity="a", clock=wall, monotonic=mono,
            lease_duration=5.0,
        )
        assert holder.try_acquire()
        assert holder.is_leader()
        mono.advance(6.0)  # no renew for > lease_duration
        assert not holder.is_leader()

    def test_release_allows_immediate_takeover(self):
        store, clock = Store(), FakeClock()
        a = LeaderElector(store, identity="a", clock=clock,
                          lease_duration=15.0)
        b = LeaderElector(store, identity="b", clock=clock,
                          lease_duration=15.0)
        assert a.try_acquire()
        assert not b.try_acquire()
        a.release()
        assert b.try_acquire()  # no lease_duration wait
        assert b.is_leader()


class TestSplitBrainRegression:
    """Satellite: two electors race one lease; the stale
    resourceVersion loser's fenced actuation is rejected and the flight
    recorder attributes the rejection to the loser's trace."""

    def test_stale_resource_version_loses_the_cas(self):
        store, clock = Store(), FakeClock()
        old = LeaderElector(store, identity="old", clock=clock,
                            lease_duration=5.0)
        new = LeaderElector(store, identity="new", clock=clock,
                            lease_duration=5.0)
        assert old.try_acquire()
        clock.advance(7.0)  # old partitioned: lease lapses
        # the race: old READS the expired lease, then new's takeover
        # lands first — old's update now carries a stale resourceVersion
        stale = store.try_get(
            "Lease", old.namespace, old.name
        )
        assert new.try_acquire()
        stale.holder = "old"
        stale.renew_time = clock()
        with pytest.raises(ConflictError):
            store.update(stale)
        assert new.is_leader()
        # and through the elector API the loser just loses the round
        assert not old.try_acquire()

    def test_loser_actuation_fence_rejected_and_recorded(self, tmp_path):
        from karpenter_tpu.observability import default_tracer
        from karpenter_tpu.observability.flightrecorder import (
            default_flight_recorder,
            reset_default_flight_recorder,
            set_default_flight_recorder,
        )

        journal_dir = str(tmp_path / "tenant")
        validator = FenceValidator()
        clock = FakeClock()
        # deposed owner claimed generation 1; the winner's adoption
        # claims generation 2 and seeds the provider validator
        deposed = TenantHandoff(
            "t0", journal_dir=journal_dir, validator=validator,
            clock=clock,
        )
        winner = TenantHandoff(
            "t0", journal_dir=journal_dir, validator=validator,
            clock=clock,
        )
        assert deposed.generation == 1
        assert winner.generation == 2
        saved = default_flight_recorder()
        recorder = reset_default_flight_recorder()
        try:
            tracer = default_tracer()
            with tracer.trace("reconcile-deposed") as span:
                loser_trace = span.trace_id
                with pytest.raises(FenceRejectedError) as err:
                    validator.admit(deposed.token())
                assert err.value.code == "FenceRejected"
                # the ScalableNodeGroup controller's rejection path
                # (controllers/scalablenodegroup.py)
                deposed.recovery.count_fence_rejection()
            events = recorder.events(kind="fence_rejection")
            assert len(events) == 1
            assert events[0]["generation"] == 1
            assert loser_trace in events[0]["trace_ids"]
        finally:
            set_default_flight_recorder(saved)
            deposed.release()
            winner.release()
        # the winner's stamp still lands
        validator.admit(winner.token())


class TestPartitionsAndRendezvous:
    def test_partition_of_is_deterministic_and_in_range(self):
        for tenant in (f"t{i}" for i in range(64)):
            p = partition_of(tenant, 8)
            assert 0 <= p < 8
            assert p == partition_of(tenant, 8)

    def test_partition_of_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            partition_of("t", 0)

    def test_rendezvous_rank_deterministic_and_complete(self):
        replicas = ["r0", "r1", "r2", "r3"]
        for partition in range(16):
            rank = rendezvous_rank(partition, replicas)
            assert sorted(rank) == sorted(replicas)
            assert rank == rendezvous_rank(
                partition, list(reversed(replicas))
            )

    def test_rendezvous_minimal_disruption(self):
        """The rendezvous property the sticky assignment leans on:
        removing a replica only moves the partitions IT topped — every
        other partition keeps its winner."""
        replicas = ["r0", "r1", "r2", "r3"]
        tops = {
            p: rendezvous_rank(p, replicas)[0] for p in range(64)
        }
        survivors = [r for r in replicas if r != "r1"]
        for p, top in tops.items():
            if top != "r1":
                assert rendezvous_rank(p, survivors)[0] == top


class TestPartitionLeases:
    def _manager(self, store, clock, replica_id, partitions=6):
        return PartitionLeaseManager(
            store, replica_id=replica_id, partitions=partitions,
            lease_duration=5.0, clock=clock,
        )

    def test_single_replica_owns_everything(self):
        store, clock = Store(), FakeClock()
        m = self._manager(store, clock, "a")
        m.round()  # observation round: heartbeat only
        clock.advance(1.0)
        round_ = m.round()
        assert round_.owned == set(range(6))
        assert round_.live == ["a"]

    def test_two_replicas_partition_disjointly(self):
        store, clock = Store(), FakeClock()
        a = self._manager(store, clock, "a")
        b = self._manager(store, clock, "b")
        for _ in range(3):
            clock.advance(1.0)
            a.round()
            b.round()
        assert a.owned | b.owned == set(range(6))
        assert not (a.owned & b.owned)
        assert a.owned  # rendezvous over 6 partitions gives both work
        assert b.owned

    def test_ownership_sticky_when_a_replica_joins(self):
        store, clock = Store(), FakeClock()
        a = self._manager(store, clock, "a")
        for _ in range(2):
            clock.advance(1.0)
            a.round()
        before = set(a.owned)
        assert before == set(range(6))
        c = self._manager(store, clock, "c")
        for _ in range(3):
            clock.advance(1.0)
            a.round()
            c.round()
        # the holder renews first every round: nothing moves
        assert a.owned == before
        assert not c.owned

    def test_dead_replica_partitions_adopted_after_expiry(self):
        store, clock = Store(), FakeClock()
        a = self._manager(store, clock, "a")
        b = self._manager(store, clock, "b")
        for _ in range(3):
            clock.advance(1.0)
            a.round()
            b.round()
        dead_partitions = set(a.owned)
        assert dead_partitions
        # a dies: no rounds, its heartbeat and partition leases lapse
        clock.advance(7.0)  # > lease_duration + skew
        for _ in range(2):
            clock.advance(1.0)
            b.round()
        assert b.owned == set(range(6))
        assert b.live_replicas() == ["b"]

    def test_release_all_hands_over_without_expiry_wait(self):
        store, clock = Store(), FakeClock()
        a = self._manager(store, clock, "a")
        b = self._manager(store, clock, "b")
        for _ in range(3):
            clock.advance(1.0)
            a.round()
            b.round()
        a.release_all()
        clock.advance(1.0)  # well inside the lease duration
        b.round()
        clock.advance(1.0)
        b.round()
        assert b.owned == set(range(6))


class TestTenantHandoff:
    def test_unfenced_warmup_gates_disruption(self):
        h = TenantHandoff("t", warmup_ticks=2)
        assert h.state == "warmup"
        assert not h.ready()
        assert not h.allow_disruption()
        h.on_tick()
        assert not h.ready()
        h.on_tick()
        assert h.ready()
        assert h.allow_disruption()
        assert h.state == "serving"
        h.release()
        assert h.state == "released"
        assert not h.ready()

    def test_fenced_adoption_replays_predecessor_intent(self, tmp_path):
        from karpenter_tpu.recovery.journal import key_str

        journal_dir = str(tmp_path / "tenant")
        first = TenantHandoff("t", journal_dir=journal_dir)
        first.recovery.handle("intent").set(("t",), {"desired": 7})
        first.release()  # checkpoints + closes
        second = TenantHandoff("t", journal_dir=journal_dir)
        try:
            assert second.generation == first.generation + 1
            table = second.recovery.table("intent")
            assert table[key_str(("t",))] == {"desired": 7}
        finally:
            second.release()


class TestReplicatedControlPlane:
    def _plane(self, store, clock, replica_id, tenants, registry=None,
               partitions=4):
        return ReplicatedControlPlane(
            store, replica_id=replica_id, partitions=partitions,
            lease_duration=5.0, tenants_source=lambda: tenants,
            warmup_ticks=1, registry=registry, clock=clock,
        )

    def test_adoption_metrics_and_scoreboard(self):
        store, clock = Store(), FakeClock()
        registry = GaugeRegistry()
        tenants = ["t0", "t1", "t2"]
        plane = self._plane(store, clock, "a", tenants, registry)
        assert plane.slo_source() is None  # no round yet
        plane.on_tick()
        clock.advance(1.0)
        plane.on_tick()
        assert {t for t in tenants if plane.owns(t)} == set(tenants)
        # adopted this tick: still warming -> mid-failover for the SLO
        assert plane.slo_source() is True
        clock.advance(1.0)
        plane.on_tick()
        assert plane.slo_source() is False
        assert all(plane.serving(t) for t in tenants)
        assert all(plane.allow_disruption(t) for t in tenants)
        board = plane.scoreboard()
        assert board["replica"] == "a"
        assert set(board["tenants"]) == set(tenants)
        assert board["adopted_total"] == 3
        assert all(
            info["state"] == "serving"
            for info in board["tenants"].values()
        )
        text = registry.expose_text()
        assert "karpenter_replica_partitions_owned" in text
        assert "karpenter_handoff_tenants_adopted_total" in text
        plane.close()
        assert plane.scoreboard()["tenants"] == {}

    def test_crash_plan_kills_the_tick(self):
        store, clock = Store(), FakeClock()
        plane = self._plane(store, clock, "a", ["t0"])
        registry = FaultRegistry(seed=1)
        crash_plan(registry, "a", times=1)
        faults.install(registry)
        with pytest.raises(ProcessCrash):
            plane.on_tick()
        faults.uninstall()
        plane.on_tick()  # the plan is spent: the next tick lives

    def test_partition_plans_cut_off_the_lease_store(self):
        store, clock = Store(), FakeClock()
        plane = self._plane(store, clock, "a", ["t0"])
        registry = FaultRegistry(seed=1)
        acquire_plan, renew_plan = partition_plans(registry, "a")
        faults.install(registry)
        for _ in range(4):
            clock.advance(1.0)
            round_ = plane.on_tick()
        assert not round_.owned  # never acquired anything
        assert acquire_plan.fired > 0
        assert renew_plan.fired == 0  # never held, so never renewed
        faults.uninstall()
        for _ in range(2):
            clock.advance(1.0)
            round_ = plane.on_tick()
        assert round_.owned == set(range(4))  # partition healed
        # partition the HOLDER: renew rounds now fail and are counted
        registry2 = FaultRegistry(seed=2)
        _, renew_plan2 = partition_plans(registry2, "a")
        faults.install(registry2)
        # past the renew throttle (lease/3) but still holding: the
        # round is a RENEW, and it fails
        clock.advance(2.0)
        round_ = plane.on_tick()
        assert renew_plan2.fired > 0
        assert round_.failures > 0
        assert not round_.owned  # renew failed: ownership lapses


FAILOVER_SEED = 20260807


@pytest.fixture(scope="module")
def failover_report():
    from karpenter_tpu.simulate import simulate_failover

    return simulate_failover(seed=FAILOVER_SEED)


class TestFailoverWorld:
    """The seeded leader-kill world (`--simulate --failover`): the
    ISSUE's acceptance criteria, asserted on one deterministic run."""

    def test_victim_tenants_reassigned(self, failover_report):
        r = failover_report
        assert r["victim"] is not None
        assert r["victim_tenants"]
        assert r["tenants_reassigned"] == r["victim_tenants"]
        assert set(r["adopters"].values()).isdisjoint({r["victim"], None})

    def test_reconverges_within_ten_ticks(self, failover_report):
        r = failover_report
        assert r["converged"]
        assert r["reconverge_ticks"] is not None
        assert r["reconverge_ticks"] <= 10

    def test_exactly_once_actuation_across_handoff(self, failover_report):
        assert failover_report["duplicate_actuations"] == 0
        assert failover_report["lost_actuations"] == 0

    def test_deposed_late_write_fence_rejected(self, failover_report):
        r = failover_report
        assert r["stale_write_rejected"]
        assert not r["stale_write_applied"]
        assert r["fence_rejections"] >= 1
        # every victim tenant was re-fenced by its adopter
        assert all(
            gen >= 2 for gen in r["fence_generations"].values()
        )

    def test_world_is_deterministic(self, failover_report):
        from karpenter_tpu.simulate import simulate_failover

        again = simulate_failover(seed=FAILOVER_SEED)
        assert again["writes_digest"] == failover_report["writes_digest"]
        assert again["reconverge_ticks"] == (
            failover_report["reconverge_ticks"]
        )


class TestSingleReplicaPath:
    """Satellite: without --partitions the runtime is byte-identical to
    the single-replica deployment — no replication plane, no lease
    traffic, no replica metrics."""

    def test_no_partitions_builds_nothing_and_touches_nothing(self):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        clock = FakeClock()
        registry = FaultRegistry(seed=0)
        lease_plans = partition_plans(registry)  # glob: every identity
        crash_plans = [
            registry.plan("replica.crash.*", mode="error")
        ]
        faults.install(registry)
        runtime = KarpenterRuntime(
            Options(),  # partitions defaults to 0
            cloud_provider_factory=FakeFactory(),
            clock=clock,
        )
        try:
            assert runtime.replication is None
            for _ in range(3):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            # no lease objects, no lease/replica fault-point traffic
            assert runtime.store.list("Lease") == []
            assert all(
                p.fired == 0 for p in lease_plans + crash_plans
            )
            text = runtime.registry.expose_text()
            assert "karpenter_replica_" not in text
            assert "karpenter_handoff_" not in text
        finally:
            faults.uninstall()
            runtime.close()

    def test_partitions_flag_builds_the_plane(self):
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        clock = FakeClock()
        runtime = KarpenterRuntime(
            Options(partitions=4, replica_id="r0", lease_duration_s=5.0),
            cloud_provider_factory=FakeFactory(),
            clock=clock,
        )
        try:
            assert runtime.replication is not None
            assert runtime.replication.replica_id == "r0"
            for _ in range(2):
                clock.advance(61.0)
                runtime.manager.reconcile_all()
            assert runtime.replication.leases.owned == set(range(4))
            assert runtime.store.list("Lease") != []
            text = runtime.registry.expose_text()
            assert "karpenter_replica_partitions_owned" in text
        finally:
            runtime.close()


class TestDebugReplicasEndpoint:
    def _get(self, port, path):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}"
        ) as resp:
            return json.loads(resp.read())

    def test_disabled_without_replication(self):
        from karpenter_tpu.observability import MetricsServer

        server = MetricsServer(GaugeRegistry(), port=0, host="127.0.0.1")
        port = server.start()
        try:
            assert self._get(port, "/debug/replicas") == {
                "enabled": False
            }
        finally:
            server.stop()

    def test_scoreboard_served(self):
        from karpenter_tpu.observability import MetricsServer

        store, clock = Store(), FakeClock()
        plane = ReplicatedControlPlane(
            store, replica_id="a", partitions=2, lease_duration=5.0,
            tenants_source=lambda: ["t0"], clock=clock,
        )
        plane.on_tick()
        clock.advance(1.0)
        plane.on_tick()
        server = MetricsServer(
            GaugeRegistry(), port=0, host="127.0.0.1", replication=plane
        )
        port = server.start()
        try:
            board = self._get(port, "/debug/replicas")
            assert board["enabled"] is True
            assert board["replica"] == "a"
            assert board["owned"] == [0, 1]
            assert "t0" in board["tenants"]
        finally:
            server.stop()
            plane.close()
