"""What-if simulation API: per-row assignments surfaced from the solve
the production tick already runs, plus hypothetical-group deltas.

reference anchor: no reference analog (the producer is stubbed there);
intent is DESIGN.md 'Pending Pods' — show the placement the signal
promises, without mutating anything.
"""

import json

import pytest

from karpenter_tpu.simulate import simulate, simulate_delta
from karpenter_tpu.store.store import Store

from tests.test_pendingcapacity import pending_mp, pending_pod, ready_node


@pytest.fixture
def cluster():
    store = Store()
    store.create(ready_node("n-a", {"group": "a"}, cpu="4", memory="8Gi"))
    store.create(pending_mp("group-a", {"group": "a"}))
    return store


class TestSimulate:
    def test_rows_map_back_to_pods(self, cluster):
        for i in range(3):
            cluster.create(pending_pod(f"small-{i}", cpu="1", memory="1Gi"))
        cluster.create(pending_pod("huge", cpu="64", memory="1Gi"))
        report = simulate(cluster)

        assert report["groups"]["default/group-a"]["pending_pods"] == 3
        assert not report["groups"]["default/group-a"]["what_if"]
        assert report["unschedulable_pods"] == 1
        by_pod = {row["pod"]: row for row in report["rows"]}
        # the 3 identical pods dedup into one row under a representative
        small_rows = [
            r for r in report["rows"]
            if r["pod"].startswith("default/small")
        ]
        assert len(small_rows) == 1 and small_rows[0]["pods"] == 3
        assert small_rows[0]["assigned"] == "default/group-a"
        assert by_pod["default/huge"]["assigned"] is None

    def test_simulation_mutates_nothing(self, cluster):
        cluster.create(pending_pod("p", cpu="1", memory="1Gi"))
        before = cluster.get("MetricsProducer", "default", "group-a")
        simulate(cluster)
        after = cluster.get("MetricsProducer", "default", "group-a")
        assert after.metadata.resource_version == before.metadata.resource_version
        assert after.status.pending_capacity is None

    def test_what_if_group_absorbs_only_unserved_pods(self, cluster):
        """Hypothetical groups are appended last: first-feasible keeps
        pods on real groups, the what-if group only shows the capacity
        the fleet genuinely lacks."""
        for i in range(2):
            cluster.create(pending_pod(f"small-{i}", cpu="1", memory="1Gi"))
        cluster.create(pending_pod("huge", cpu="64", memory="64Gi"))
        report = simulate(
            cluster,
            what_if_groups=[
                {
                    "name": "metal",
                    "allocatable": {
                        "cpu": "96", "memory": "128Gi", "pods": "110",
                    },
                }
            ],
        )
        assert report["groups"]["default/group-a"]["pending_pods"] == 2
        assert report["groups"]["metal"]["what_if"]
        assert report["groups"]["metal"]["pending_pods"] == 1
        assert report["groups"]["metal"]["additional_nodes_needed"] == 1
        assert report["unschedulable_pods"] == 0

    def test_what_if_respects_taints_and_labels(self, cluster):
        cluster.create(
            pending_pod("picky", cpu="1", node_selector={"disk": "ssd"})
        )
        no_label = simulate(
            cluster,
            what_if_groups=[
                {"name": "plain", "allocatable": {
                    "cpu": "8", "memory": "16Gi", "pods": "64"}}
            ],
        )
        assert no_label["unschedulable_pods"] == 1
        labeled = simulate(
            cluster,
            what_if_groups=[
                {
                    "name": "ssd",
                    "allocatable": {
                        "cpu": "8", "memory": "16Gi", "pods": "64",
                    },
                    "labels": {"disk": "ssd"},
                }
            ],
        )
        assert labeled["groups"]["ssd"]["pending_pods"] == 1
        tainted = simulate(
            cluster,
            what_if_groups=[
                {
                    "name": "ssd-tainted",
                    "allocatable": {
                        "cpu": "8", "memory": "16Gi", "pods": "64",
                    },
                    "labels": {"disk": "ssd"},
                    "taints": [
                        {"key": "d", "value": "x", "effect": "NoSchedule"}
                    ],
                }
            ],
        )
        assert tainted["unschedulable_pods"] == 1

    def test_delta_report(self, cluster):
        cluster.create(pending_pod("huge", cpu="64", memory="64Gi"))
        report = simulate_delta(
            cluster,
            [{"name": "metal", "allocatable": {
                "cpu": "96", "memory": "128Gi", "pods": "110"}}],
        )
        assert report["baseline"]["unschedulable_pods"] == 1
        assert report["what_if"]["unschedulable_pods"] == 0
        assert report["delta"]["unschedulable_pods"] == -1
        assert report["delta"]["groups"]["metal"] == {
            "pending_pods": 1,
            "additional_nodes_needed": 1,
        }

    def test_empty_pending_set(self, cluster):
        report = simulate(cluster)
        assert report["rows"] == []
        assert report["unschedulable_pods"] == 0
        assert report["groups"]["default/group-a"]["pending_pods"] == 0


class TestSimulateCLI:
    def test_cli_simulate_with_what_if(self, tmp_path, capsys):
        """`python -m karpenter_tpu --simulate` end to end over a WAL
        store, the documented operator workflow (OPERATIONS.md)."""
        from karpenter_tpu.__main__ import main
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        data_dir = str(tmp_path / "state")
        seed = KarpenterRuntime(Options(data_dir=data_dir))
        seed.store.create(ready_node("n-a", {"group": "a"}, cpu="4"))
        seed.store.create(pending_mp("group-a", {"group": "a"}))
        seed.store.create(pending_pod("huge", cpu="64", memory="64Gi"))
        seed.close()

        what_if = tmp_path / "what-if.json"
        what_if.write_text(json.dumps([
            {"name": "metal",
             "allocatable": {"cpu": "96", "memory": "128Gi", "pods": "110"}}
        ]))
        rc = main([
            "--simulate", "--what-if", str(what_if),
            "--data-dir", data_dir, "--no-leader-elect",
        ])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["delta"]["unschedulable_pods"] == -1
        assert report["what_if"]["groups"]["metal"]["what_if"]

    def test_cli_rejects_non_list_what_if(self, tmp_path, capsys):
        from karpenter_tpu.__main__ import main

        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"name": "not-a-list"}))
        rc = main([
            "--simulate", "--what-if", str(bad),
            "--data-dir", str(tmp_path / "s"), "--no-leader-elect",
        ])
        assert rc == 2


class TestSimulateFidelity:
    def test_pods_resource_defaults_like_live_profiles(self, cluster):
        """A what-if spec declaring only cpu/memory must not be silently
        infeasible: the pods resource defaults exactly as it does for
        live-node profiles and provider templates."""
        cluster.create(pending_pod("huge", cpu="64", memory="64Gi"))
        report = simulate(
            cluster,
            what_if_groups=[
                {"name": "metal",
                 "allocatable": {"cpu": "96", "memory": "128Gi"}}
            ],
        )
        assert report["groups"]["metal"]["pending_pods"] == 1
        assert report["unschedulable_pods"] == 0

    def test_cloud_api_taint_dialect_constrains(self, cluster):
        """NO_SCHEDULE (the GKE/EKS enum spelling) must constrain like
        NoSchedule — specs are declared like provider raw templates."""
        cluster.create(pending_pod("huge", cpu="64", memory="64Gi"))
        report = simulate(
            cluster,
            what_if_groups=[
                {
                    "name": "metal",
                    "allocatable": {"cpu": "96", "memory": "128Gi"},
                    "taints": [
                        {"key": "d", "value": "x", "effect": "NO_SCHEDULE"}
                    ],
                }
            ],
        )
        assert report["unschedulable_pods"] == 1

    def test_scale_from_zero_groups_use_template_resolver(self):
        """An empty group with a nodeGroupRef resolves its declared shape
        through the same seam the production solve uses, keeping the
        baseline honest."""
        store = Store()
        mp = pending_mp("empty-group", {"group": "zero"})
        mp.spec.pending_capacity.node_group_ref = "pool"
        store.create(mp)
        store.create(pending_pod("p", cpu="1", memory="1Gi"))

        def resolver(namespace, ref):
            assert (namespace, ref) == ("default", "pool")
            return (
                {"cpu": 8.0, "memory": 2**34, "pods": 110.0},
                {("group", "zero")},
                set(),
            )

        report = simulate(store, template_resolver=resolver)
        assert report["groups"]["default/empty-group"]["pending_pods"] == 1
        assert report["unschedulable_pods"] == 0

    def test_poisoned_producer_is_row_isolated(self, cluster):
        """One producer with a selector that blows up profile computation
        reports an error on its own group; the rest still solve."""
        bad = pending_mp("poisoned", {"group": "x"})
        bad.spec.pending_capacity.node_selector = None  # blows up matching
        cluster.create(bad)
        cluster.create(pending_pod("p", cpu="1", memory="1Gi"))
        report = simulate(cluster)
        assert "error" in report["groups"]["default/poisoned"]
        assert report["groups"]["default/poisoned"]["pending_pods"] == 0
        assert report["groups"]["default/group-a"]["pending_pods"] == 1

    def test_rows_are_namespace_qualified(self, cluster):
        cluster.create(pending_pod("p", cpu="1", memory="1Gi"))
        report = simulate(cluster)
        assert report["rows"][0]["pod"] == "default/p"

    def test_empty_what_if_list_still_yields_delta_shape(
        self, tmp_path, capsys
    ):
        """--what-if pointing at [] must produce the documented
        baseline/what_if/delta report, not the plain one."""
        from karpenter_tpu.__main__ import main
        from karpenter_tpu.runtime import KarpenterRuntime, Options

        data_dir = str(tmp_path / "state")
        seed = KarpenterRuntime(Options(data_dir=data_dir))
        seed.store.create(pending_mp("group-a", {"group": "a"}))
        seed.close()
        empty = tmp_path / "none.json"
        empty.write_text("[]")
        rc = main([
            "--simulate", "--what-if", str(empty),
            "--data-dir", data_dir, "--no-leader-elect",
        ])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert set(report) == {"baseline", "what_if", "delta"}

    def test_preferred_affinity_cannot_steal_into_what_if(self, cluster):
        """The solver steers by preference score among feasible groups; a
        what-if group matching a pod's preference must NOT attract a pod
        a real group serves — score columns of hypothetical groups are
        zeroed, preserving the delta's 'genuinely lacking' meaning."""
        from karpenter_tpu.api.core import (
            Affinity,
            NodeAffinity,
            NodeSelectorRequirement,
            NodeSelectorTerm,
            PreferredSchedulingTerm,
        )

        pod = pending_pod("prefers-ssd", cpu="1", memory="1Gi")
        pod.spec.affinity = Affinity(
            node_affinity=NodeAffinity(
                preferred_during_scheduling_ignored_during_execution=[
                    PreferredSchedulingTerm(
                        weight=100,
                        preference=NodeSelectorTerm(
                            match_expressions=[
                                NodeSelectorRequirement(
                                    key="disk", operator="In",
                                    values=["ssd"],
                                )
                            ]
                        ),
                    )
                ]
            )
        )
        cluster.create(pod)
        report = simulate(
            cluster,
            what_if_groups=[
                {
                    "name": "ssd-pool",
                    "allocatable": {"cpu": "8", "memory": "16Gi"},
                    "labels": {"disk": "ssd"},
                }
            ],
        )
        assert report["groups"]["default/group-a"]["pending_pods"] == 1
        assert report["groups"]["ssd-pool"]["pending_pods"] == 0

    def test_what_if_name_collision_is_uniquified(self, cluster):
        cluster.create(pending_pod("p", cpu="1", memory="1Gi"))
        report = simulate(
            cluster,
            what_if_groups=[
                {"name": "metal", "allocatable": {"cpu": "8", "memory": "8Gi"}},
                {"name": "metal", "allocatable": {"cpu": "8", "memory": "8Gi"}},
            ],
        )
        assert "metal" in report["groups"]
        assert "metal#2" in report["groups"]


class TestSimulatePreempt:
    """--simulate --preempt: the seeded spot-reclaim storm replay
    (docs/preemption.md). Deterministic under a fixed seed, so the
    regression pins exact counts, and mutation-free toward any caller
    state (the replay owns its store)."""

    def test_storm_replay_is_deterministic_and_preempts(self):
        from karpenter_tpu.simulate import simulate_preempt

        kwargs = dict(
            on_demand_nodes=2, spot_nodes=4, node_cpu=4.0,
            ticks=12, reclaim_tick=2, provision_lag=3, seed=7,
        )
        report = simulate_preempt(**kwargs)
        again = simulate_preempt(**kwargs)
        assert report == again, "seeded replay must be deterministic"

        # the storm actually displaced work and the engine actually
        # planned evictions through the service
        assert report["evictions_total"] >= 1
        assert report["preempt_dispatches"] >= 1
        assert report["scale_ups_total"] >= 1
        # the fleet recovered: services first, everything eventually
        assert report["service_recovery_tick"] is not None
        assert report["full_recovery_tick"] is not None
        assert (
            report["service_recovery_tick"]
            <= report["full_recovery_tick"]
        )
        # high-priority pods drained ahead of (or with) the general
        # pending set on every tick after the reclaim
        for tick in report["ticks"]:
            assert (
                tick["pending_high_priority"] <= tick["pending"]
            )

    def test_report_shape(self):
        from karpenter_tpu.simulate import simulate_preempt

        report = simulate_preempt(
            on_demand_nodes=2, spot_nodes=2, node_cpu=4.0,
            ticks=6, reclaim_tick=1, seed=0,
        )
        assert set(report) >= {
            "config", "ticks", "evictions_total", "scale_ups_total",
            "service_recovery_tick", "full_recovery_tick",
            "preempt_dispatches",
        }
        assert len(report["ticks"]) == 6


class TestSimulateRestartStorm:
    def test_storm_pins_crash_safety_contract(self, tmp_path):
        """The --simulate --restart-storm replay: exactly-once cloud
        actuation across every incarnation, FSM resumption (no
        re-cordon of a restored drain), a fence generation per boot,
        and the stale-incarnation replay probe REJECTED."""
        from karpenter_tpu.simulate import simulate_restart_storm

        report = simulate_restart_storm(
            nodes=4, crashes=2, seed=0, journal_dir=str(tmp_path)
        )
        assert report["restarts"] == 3
        assert report["duplicate_actuations"] == 0
        assert report["fence_rejections"] == 1
        assert report["stale_replay_applied"] is False
        assert report["resumed_not_recordoned"] is True
        assert report["fence_generation"] == 4  # one per boot + probe
        assert report["drains_completed"] == 3  # every empty node gone

    def test_storm_is_deterministic(self, tmp_path):
        from karpenter_tpu.simulate import simulate_restart_storm

        def run(sub):
            report = simulate_restart_storm(
                nodes=3, crashes=1, seed=7,
                journal_dir=str(tmp_path / sub),
            )
            report.pop("nodes_remaining")
            return report

        assert run("a") == run("b")


class TestSimulateEventloop:
    """ISSUE 14 acceptance (non-slow regression guard): the seeded
    pod-arrival replay must show tick-paced e2e p99 at interval scale
    (multi-second), event-driven e2e p99 SUB-SECOND on the same
    karpenter_reconcile_e2e_seconds histogram, the same fleet fixed
    point in both arms, and churn-storm solve amplification <= 2x."""

    CONFIG = dict(ticks=12, arrivals=10, storm_events=200, seed=7)

    def test_event_driven_is_sub_second_with_bounded_amplification(self):
        from karpenter_tpu.simulate import simulate_eventloop

        report = simulate_eventloop(**self.CONFIG)
        assert report["fixed_point_match"], (
            "event-driven and tick-paced arms must converge to the "
            "same fleet"
        )
        tick = report["tick_paced"]["e2e_seconds"]
        event = report["event_driven"]["e2e_seconds"]
        assert tick["n"] >= 1 and event["n"] >= 1
        assert tick["p99_s"] > 1.0, (
            "tick pacing must dominate the tick-paced arm's lead time"
        )
        assert event["p99_s"] < 1.0, (
            f"event passes must deliver sub-second e2e p99, got "
            f"{event['p99_s']}s"
        )
        storm = report["event_driven"]["storm"]
        assert storm["amplification"] <= 2.0, (
            f"churn-storm solve amplification must stay bounded: "
            f"{storm}"
        )
        assert storm["passes"] <= 4, (
            f"{storm['events']} events in one debounce window must "
            f"coalesce into a handful of passes, got {storm['passes']}"
        )

    def test_eventloop_replay_is_deterministic(self):
        """Scripted clock + manual passes + seeded arrivals: the whole
        report (latencies included) is a pure function of the seed."""
        from karpenter_tpu.simulate import simulate_eventloop

        assert (
            simulate_eventloop(**self.CONFIG)
            == simulate_eventloop(**self.CONFIG)
        )


class TestSimulateCost:
    """Satellite pin (docs/cost.md "Dry-running"): the --simulate --cost
    warm-pool replay must show a MEASURED provisioning lead-time
    reduction at equal-or-lower SLO-violation count — the acceptance
    headline — and the deterministic halves of the report must replay
    identically (the e2e histogram carries real wall time and is pinned
    by shape only)."""

    CONFIG = dict(
        ticks=60, ramp_start=15, ramp_ticks=10, spot_step_tick=40,
        provision_lag=4, min_samples=3, seed=7,
    )

    def _deterministic_view(self, report):
        view = {
            k: report[k]
            for k in ("config", "hourly_cost", "slo_violations",
                      "provisioning_lead")
        }
        view["provisioned"] = {
            run: report["runs"][run]["provisioned"]
            for run in ("warm_on", "warm_off")
        }
        return view

    def test_warm_pool_cuts_provisioning_lead_within_slo(self):
        from karpenter_tpu.simulate import simulate_cost

        report = simulate_cost(**self.CONFIG)
        lead = report["provisioning_lead"]
        assert lead["reduction_ticks"] > 0, (
            "warm pool must reduce the mean capacity-coverage lag"
        )
        assert lead["warm_on_mean_lag_ticks"] < lead[
            "warm_off_mean_lag_ticks"
        ]
        viol = report["slo_violations"]
        assert viol["warm_on"] <= viol["warm_off"]
        assert (
            viol["warm_on_shortfall_replica_ticks"]
            <= viol["warm_off_shortfall_replica_ticks"]
        )
        # warm capacity costs real money — the report must price it,
        # not hide it
        assert report["hourly_cost"]["warm_on_mean"] > 0
        # both worlds refined through the batched cost seam and filled
        # the PR 9 e2e histogram (the lead-time observable)
        for run in ("warm_on", "warm_off"):
            world = report["runs"][run]
            assert world["cost_dispatches"] >= 1
            assert world["e2e_seconds"]["n"] >= 1
            assert world["e2e_seconds"]["p50_s"] is not None
            assert (
                world["e2e_seconds"]["p99_s"]
                >= world["e2e_seconds"]["p50_s"]
            )

    def test_cost_replay_is_deterministic(self):
        from karpenter_tpu.simulate import simulate_cost

        a = simulate_cost(**self.CONFIG)
        b = simulate_cost(**self.CONFIG)
        assert self._deterministic_view(a) == self._deterministic_view(b)


class TestSimulatePoolGroups:
    """PR 20 satellite (docs/poolgroups.md "Dry-running"): the
    --simulate --poolgroups decode-heavy storm must show the
    coordinated arm HOLDING the declared decode:prefill band (under the
    shared budget, through one joint dispatch per tick) while the
    uncoordinated per-pool baseline violates it — the acceptance
    headline — and the whole report is a pure function of the seed."""

    def test_storm_holds_band_under_coordination_only(self):
        from karpenter_tpu.simulate import simulate_poolgroups

        report = simulate_poolgroups()
        band = report["band"]
        assert band["held_through_storm"] is True
        assert band["coordinated_violation_ticks"] == 0
        assert band["uncoordinated_violation_ticks"] > 0, (
            "the uncoordinated baseline must violate the band — "
            "otherwise the storm proves nothing"
        )
        # the joint point stayed coordinated every tick and spent under
        # the declared shared budget
        on = report["runs"]["coordinated"]
        assert on["coordinated_ticks"] == report["config"]["ticks"]
        assert report["budget"]["under_cap"] is True
        # dispatch collapse: grouped rows leave the per-pool cost
        # ladder (0 cost dispatches) and ride ONE joint dispatch per
        # tick; the baseline keeps the N per-pool cost path
        collapse = report["dispatch_collapse"]
        assert collapse["coordinated_cost_dispatches"] == 0
        assert (
            collapse["coordinated_poolgroup_dispatches"]
            == report["config"]["ticks"]
        )
        assert collapse["uncoordinated_cost_dispatches"] > 0

    def test_replay_digest_is_pinned(self):
        """crc32 of canonical JSON (the constraints-replay discipline):
        the report is deterministic end to end — no wall-time fields —
        so the WHOLE report digests to one pinned value."""
        import json
        import zlib

        from karpenter_tpu.simulate import simulate_poolgroups

        report = simulate_poolgroups()
        canon = json.dumps(
            report, sort_keys=True, separators=(",", ":")
        )
        assert zlib.crc32(canon.encode()) == 762078142
        assert report == simulate_poolgroups()


class TestSimulateConstraints:
    """PR 16 satellite (docs/constraints.md "Dry-running"): the
    --simulate --constraints zonal-outage replay runs the REAL
    producer/encoder/solver path and its report is a pure function of
    the seed — the digests are pinned, not just compared run-to-run."""

    def test_outage_rebalances_without_dropping_the_fence(self):
        from karpenter_tpu.simulate import simulate_constraints

        report = simulate_constraints()
        before, after = report["before"], report["after"]
        # before: the web group spreads evenly and gold fills
        assert before["spread_skew"] == {"web": 0.0}
        assert before["reservation_fill"] == {"gold": 1.0}
        assert before["unschedulable"] == 0
        dead = f"serving-{report['dead_zone']}"
        assert before["groups"][dead]["pending_pods"] > 0
        # after the outage: the dead zone absorbs nothing, the spread
        # rebalances over the survivors (skew stays bounded) and the
        # reservation fence holds
        assert after["groups"][dead]["pending_pods"] == 0
        assert after["groups"][dead]["nodes_needed"] == 0
        assert after["spread_skew"]["web"] <= 1.0
        assert after["reservation_fill"] == {"gold": 1.0}
        assert after["unschedulable"] == 0
        survivors = sum(
            after["groups"][g]["pending_pods"]
            for g in after["groups"]
            if g != dead
        )
        assert survivors == sum(
            before["groups"][g]["pending_pods"]
            for g in before["groups"]
        )
        # the solve stayed healthy the whole replay: constrained
        # encodes compiled, never degraded to the unconstrained wire
        health = report["constraint_health"]
        assert health["compiles"] >= 1
        assert health["fallbacks"] == 0
        assert not health["degraded"]

    def test_replay_digests_are_pinned(self):
        """Deterministic digests over the phase reports (crc32 of
        canonical JSON — stable across processes, unlike hash())."""
        from karpenter_tpu.simulate import simulate_constraints

        report = simulate_constraints()
        assert report["dead_zone"] == "z3"
        assert report["digests"] == {
            "before": 1761739094,
            "after": 2968639679,
        }
        assert report == simulate_constraints()
