"""PoolGroups (PR 20): the joint-allocation kernel, the engine, and
the wire-compat contracts (docs/poolgroups.md).

The pins mirror the cost-subsystem discipline one rank up:

  * numpy == XLA bitwise on every output leaf, both enforce modes —
    the mirror IS the device program;
  * joint == independent per-pool cost ladders when the declared
    couplings are slack — a PoolGroup whose constraints don't bind is
    byte-identical to the ungrouped plane;
  * an ungrouped fleet is byte-identical with --poolgroups set or
    unset — the subsystem's zero-overhead opt-out;
  * the engine never blocks: a failing joint seam leaves the base
    decisions standing and counts the degradation;
  * group gauges retire with the group (the frozen-series discipline);
  * tenants sharing a PoolGroup ride the same admission round.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from karpenter_tpu.api.core import ObjectMeta
from karpenter_tpu.api.horizontalautoscaler import (
    Behavior,
    CrossVersionObjectReference,
    HorizontalAutoscaler,
    HorizontalAutoscalerSpec,
    Metric,
    MetricTarget,
    PrometheusMetricSource,
    SLOSpec,
)
from karpenter_tpu.api.poolgroup import (
    PoolGroup,
    PoolGroupSpec,
    PoolMember,
    RatioConstraint,
)
from karpenter_tpu.api.scalablenodegroup import (
    ScalableNodeGroup,
    ScalableNodeGroupSpec,
)
from karpenter_tpu.autoscaler import BatchAutoscaler
from karpenter_tpu.cost import CostEngine
from karpenter_tpu.metrics.clients import MetricsClientFactory
from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.ops import cost as CK
from karpenter_tpu.ops import poolgroup as PGK
from karpenter_tpu.poolgroups import PoolGroupEngine
from karpenter_tpu.store import Store

PREFILL = 11  # queue 41 / AverageValue target 4 -> ceil
DECODE = 40  # queue 160 / 4


def random_group_inputs(
    seed: int, g: int = 4, p: int = 4, m: int = 2
) -> PGK.PoolGroupInputs:
    rng = np.random.RandomState(seed)
    base = rng.randint(0, 100, (g, p)).astype(np.int32)
    ratio_a = rng.randint(0, p, (g, PGK.RATIO_SLOTS)).astype(np.int32)
    ratio_b = rng.randint(0, p, (g, PGK.RATIO_SLOTS)).astype(np.int32)
    return PGK.PoolGroupInputs(
        base_desired=base,
        min_replicas=rng.randint(0, 5, (g, p)).astype(np.int32),
        max_replicas=(base + rng.randint(0, 300, (g, p))).astype(
            np.int32
        ),
        unit_cost=rng.choice(
            [0.0, 0.07, 0.3, 1.7, 12.5], (g, p)
        ).astype(np.float32),
        slo_weight=rng.choice([0.0, 1.0, 50.0, 333.3], (g, p)).astype(
            np.float32
        ),
        max_hourly_cost=rng.choice([0.0, 2.0, 55.5], (g, p)).astype(
            np.float32
        ),
        tier_penalty=rng.choice([0.0, 0.1, 2.0], (g, p)).astype(
            np.float32
        ),
        pool_valid=rng.rand(g, p) > 0.25,
        slo_target=rng.uniform(0.5, 10, (g, p, m)).astype(np.float32),
        demand_mu=rng.uniform(0, 500, (g, p, m)).astype(np.float32),
        demand_sigma=rng.choice([0.0, 3.0, 25.0], (g, p, m)).astype(
            np.float32
        ),
        demand_valid=rng.rand(g, p, m) > 0.2,
        ratio_a=ratio_a,
        # a == b would be a degenerate self-ratio the api layer rejects;
        # keep generated bands honest by bumping collisions off-diagonal
        ratio_b=np.where(
            ratio_a == ratio_b, (ratio_b + 1) % p, ratio_b
        ).astype(np.int32),
        ratio_min_num=rng.randint(
            0, 6, (g, PGK.RATIO_SLOTS)
        ).astype(np.int32),
        ratio_min_den=rng.randint(
            1, 4, (g, PGK.RATIO_SLOTS)
        ).astype(np.int32),
        ratio_max_num=rng.choice(
            [0, 4, 8, 1024], (g, PGK.RATIO_SLOTS)
        ).astype(np.int32),
        ratio_max_den=rng.choice(
            [1, 2], (g, PGK.RATIO_SLOTS)
        ).astype(np.int32),
        ratio_valid=rng.rand(g, PGK.RATIO_SLOTS) > 0.4,
        group_budget=rng.choice([0.0, 40.0, 400.0], g).astype(
            np.float32
        ),
        group_valid=rng.rand(g) > 0.2,
    )


class TestJointKernelParity:
    def test_xla_matches_numpy_bitwise_all_leaves(self):
        """The parity contract, both rungs: the enforcing joint program
        and the degraded independent program each match their numpy
        mirror bit for bit on EVERY output leaf."""
        for seed in range(6):
            for g, p, m in ((4, 4, 2), (1, 2, 1), (8, 3, 4)):
                inputs = random_group_inputs(seed, g, p, m)
                for dev_fn, enforce in (
                    (PGK.poolgroup_jit, True),
                    (PGK.poolgroup_independent_jit, False),
                ):
                    dev = dev_fn(inputs)
                    host = PGK.poolgroup_numpy(inputs, enforce=enforce)
                    for f in dataclasses.fields(PGK.PoolGroupOutputs):
                        a = np.asarray(getattr(dev, f.name))
                        b = np.asarray(getattr(host, f.name))
                        assert np.array_equal(a, b), (
                            f"seed={seed} g={g} p={p} m={m} "
                            f"enforce={enforce}: {f.name} diverged"
                        )

    def test_slack_constraints_match_the_per_pool_cost_ladder(self):
        """Wire compat one rank down: with every ratio and budget slack
        (invalid), each pool's joint choice equals what the PR 10 cost
        kernel picks for the identical operands — the joint program IS
        N cost ladders plus constraint selection, bit for bit."""
        for seed in range(4):
            inputs = random_group_inputs(seed, g=4, p=4, m=3)
            inputs = dataclasses.replace(
                inputs,
                tier_penalty=np.zeros_like(inputs.tier_penalty),
                ratio_valid=np.zeros_like(inputs.ratio_valid),
                group_valid=np.zeros_like(inputs.group_valid),
            )
            joint = PGK.poolgroup_jit(inputs)
            flat = CK.cost_jit(CK.CostInputs(
                base_desired=inputs.base_desired.reshape(-1),
                min_replicas=inputs.min_replicas.reshape(-1),
                max_replicas=inputs.max_replicas.reshape(-1),
                unit_cost=inputs.unit_cost.reshape(-1),
                slo_weight=inputs.slo_weight.reshape(-1),
                max_hourly_cost=inputs.max_hourly_cost.reshape(-1),
                slo_valid=inputs.pool_valid.reshape(-1),
                slo_target=inputs.slo_target.reshape(
                    -1, inputs.slo_target.shape[-1]
                ),
                demand_mu=inputs.demand_mu.reshape(
                    -1, inputs.demand_mu.shape[-1]
                ),
                demand_sigma=inputs.demand_sigma.reshape(
                    -1, inputs.demand_sigma.shape[-1]
                ),
                demand_valid=inputs.demand_valid.reshape(
                    -1, inputs.demand_valid.shape[-1]
                ),
            ))
            assert np.array_equal(
                np.asarray(joint.desired).reshape(-1),
                np.asarray(flat.desired),
            ), f"seed={seed}: joint != per-pool cost ladder"
            assert not np.asarray(joint.joint_repair).any()

    def test_invalid_pools_pass_through_exactly(self):
        inputs = random_group_inputs(2)
        inputs = dataclasses.replace(
            inputs, pool_valid=np.zeros_like(inputs.pool_valid)
        )
        out = PGK.poolgroup_jit(inputs)
        assert np.array_equal(
            np.asarray(out.desired), np.asarray(inputs.base_desired)
        )

    def test_repair_raises_a_pool_into_the_band(self):
        """A min-band the independent points violate, reachable within
        the candidate ladder: the joint selection raises the numerator
        pool (decode 40 -> 44 under decode:prefill >= 4:1) instead of
        serving the cheap violating point."""
        g, p, m = 1, PGK.pad_pool_count(2), 1
        inputs = PGK.PoolGroupInputs(
            base_desired=np.asarray([[11, 40]], np.int32).repeat(
                1, axis=0
            ),
            min_replicas=np.zeros((g, p), np.int32),
            max_replicas=np.full((g, p), 1000, np.int32),
            unit_cost=np.ones((g, p), np.float32),
            slo_weight=np.zeros((g, p), np.float32),
            max_hourly_cost=np.zeros((g, p), np.float32),
            tier_penalty=np.zeros((g, p), np.float32),
            pool_valid=np.asarray([[True, True]]),
            slo_target=np.ones((g, p, m), np.float32),
            demand_mu=np.zeros((g, p, m), np.float32),
            demand_sigma=np.zeros((g, p, m), np.float32),
            demand_valid=np.zeros((g, p, m), bool),
            ratio_a=np.asarray([[1] + [0] * 3], np.int32),
            ratio_b=np.asarray([[0] + [1] * 3], np.int32),
            ratio_min_num=np.asarray([[4] + [0] * 3], np.int32),
            ratio_min_den=np.ones((g, PGK.RATIO_SLOTS), np.int32),
            ratio_max_num=np.zeros((g, PGK.RATIO_SLOTS), np.int32),
            ratio_max_den=np.zeros((g, PGK.RATIO_SLOTS), np.int32),
            ratio_valid=np.asarray([[True, False, False, False]]),
            group_budget=np.zeros(g, np.float32),
            group_valid=np.asarray([True]),
        )
        if p > 2:
            inputs = dataclasses.replace(
                inputs,
                base_desired=np.pad(
                    np.asarray([[11, 40]], np.int32),
                    ((0, 0), (0, p - 2)),
                ),
                pool_valid=np.pad(
                    np.asarray([[True, True]]), ((0, 0), (0, p - 2))
                ),
            )
        out = PGK.poolgroup_jit(inputs)
        assert int(np.asarray(out.desired)[0, 0]) == 11
        assert int(np.asarray(out.desired)[0, 1]) == 44
        assert bool(np.asarray(out.ratio_ok)[0])
        assert bool(np.asarray(out.joint_repair)[0])
        # the degraded rung pins the independent point and reports the
        # violation honestly
        deg = PGK.poolgroup_numpy(inputs, enforce=False)
        assert int(np.asarray(deg.desired)[0, 1]) == 40
        assert not bool(np.asarray(deg.ratio_ok)[0])


class TestPoolGroupValidation:
    def _group(self, **spec):
        base = dict(
            pools=[PoolMember(name="a"), PoolMember(name="b")],
            ratios=[],
        )
        base.update(spec)
        return PoolGroup(
            metadata=ObjectMeta(name="g"), spec=PoolGroupSpec(**base)
        )

    def test_pool_count_bounds(self):
        with pytest.raises(ValueError, match="2..4 pools"):
            self._group(pools=[PoolMember(name="a")]).validate()
        with pytest.raises(ValueError, match="2..4 pools"):
            self._group(
                pools=[PoolMember(name=f"p{i}") for i in range(5)]
            ).validate()

    def test_duplicate_names_rejected(self):
        with pytest.raises(ValueError, match="unique"):
            self._group(
                pools=[PoolMember(name="a"), PoolMember(name="a")]
            ).validate()

    def test_ratio_must_reference_declared_pools(self):
        with pytest.raises(ValueError, match="unknown pool"):
            self._group(ratios=[RatioConstraint(
                numerator="a", denominator="ghost", min_numerator=1,
            )]).validate()

    def test_empty_band_rejected(self):
        with pytest.raises(ValueError, match="band is empty"):
            self._group(ratios=[RatioConstraint(
                numerator="a", denominator="b",
                min_numerator=4, min_denominator=1,
                max_numerator=2, max_denominator=1,
            )]).validate()

    def test_ratio_slot_limit(self):
        ratios = [
            RatioConstraint(
                numerator="a", denominator="b", min_numerator=i + 1
            )
            for i in range(5)
        ]
        with pytest.raises(ValueError, match="at most 4 ratio"):
            self._group(ratios=ratios).validate()

    def test_role_alias_resolves(self):
        group = self._group(pools=[
            PoolMember(name="x", role="prefill"),
            PoolMember(name="y", role="decode"),
        ])
        assert group.member_index("decode") == 1
        assert group.member_index("x") == 0

    def test_kernel_limits_mirror_the_api(self):
        import karpenter_tpu.api.poolgroup as api_pg

        assert api_pg.MAX_POOLS == PGK.MAX_POOLS
        assert api_pg.RATIO_SLOTS == PGK.RATIO_SLOTS
        assert api_pg.RATIO_BOUND == PGK.RATIO_BOUND


def _world(groups=(), pool_engine=True, poolgroup_fn=None, slo=True):
    """A two-pool fleet (prefill queue 41, decode queue 160, target 4)
    with the given PoolGroup objects; returns (store, registry, auto,
    engine)."""
    store = Store()
    registry = GaugeRegistry()
    queue = registry.register("queue", "length")
    queue.set("qp", "default", 41.0)
    queue.set("qd", "default", 160.0)
    for name, q in (("prefill", "qp"), ("decode", "qd")):
        store.create(ScalableNodeGroup(
            metadata=ObjectMeta(name=f"g-{name}"),
            spec=ScalableNodeGroupSpec(
                replicas=5, type="FakeNodeGroup", id=f"g-{name}"
            ),
        ))
        store.create(HorizontalAutoscaler(
            metadata=ObjectMeta(name=name),
            spec=HorizontalAutoscalerSpec(
                scale_target_ref=CrossVersionObjectReference(
                    kind="ScalableNodeGroup", name=f"g-{name}"
                ),
                min_replicas=1,
                max_replicas=1000,
                metrics=[Metric(prometheus=PrometheusMetricSource(
                    query=f'karpenter_queue_length{{name="{q}"}}',
                    target=MetricTarget(type="AverageValue", value=4),
                ))],
                behavior=Behavior(
                    slo=SLOSpec(violation_cost_weight=100.0)
                    if slo else None
                ),
            ),
        ))
    for group in groups:
        store.create(group)
    engine = None
    if pool_engine:
        engine = PoolGroupEngine(
            store=store, poolgroup_fn=poolgroup_fn, registry=registry
        )
    auto = BatchAutoscaler(
        MetricsClientFactory(registry=registry), store,
        cost_engine=CostEngine(store=store, registry=registry),
        pool_engine=engine,
    )
    return store, registry, auto, engine


def _tick(store, auto):
    has = [
        store.get("HorizontalAutoscaler", "default", n)
        for n in ("prefill", "decode")
    ]
    errs = auto.reconcile_batch(has)
    assert all(e is None for e in errs.values()), errs
    return {
        n: store.get_scale(
            "ScalableNodeGroup", "default", f"g-{n}"
        ).spec_replicas
        for n in ("prefill", "decode")
    }


def _serving_group(ratios, name="serving", pools=None):
    return PoolGroup(
        metadata=ObjectMeta(name=name),
        spec=PoolGroupSpec(
            pools=pools or [
                PoolMember(name="prefill"), PoolMember(name="decode")
            ],
            ratios=ratios,
        ),
    )


SLACK_BAND = [RatioConstraint(
    numerator="decode", denominator="prefill",
    min_numerator=2, min_denominator=1,
    max_numerator=8, max_denominator=1,
)]  # 40/11 = 3.6: the independent points already satisfy it

REPAIR_BAND = [RatioConstraint(
    numerator="decode", denominator="prefill",
    min_numerator=4, min_denominator=1,
)]  # needs decode 44: in ladder reach of the independent 40


class TestWireCompat:
    def test_ungrouped_fleet_byte_identical_with_engine_on(self):
        """Zero-overhead opt-out: no PoolGroup objects -> the engine's
        plan is None and the wire is byte-identical to a fleet with no
        pool engine at all."""
        store_a, _, auto_a, _ = _world(pool_engine=True)
        store_b, _, auto_b, _ = _world(pool_engine=False)
        for _ in range(3):
            assert _tick(store_a, auto_a) == _tick(store_b, auto_b)
        for name in ("prefill", "decode"):
            a = store_a.get("HorizontalAutoscaler", "default", name)
            b = store_b.get("HorizontalAutoscaler", "default", name)
            assert a.status.desired_replicas == b.status.desired_replicas

    def test_slack_band_matches_the_ungrouped_plane(self):
        """joint == independent when the declared couplings don't bind:
        the grouped fleet lands on exactly the ungrouped counts."""
        store_g, _, auto_g, _ = _world(groups=[_serving_group(SLACK_BAND)])
        store_u, _, auto_u, _ = _world(pool_engine=False)
        for _ in range(3):
            assert _tick(store_g, auto_g) == _tick(store_u, auto_u)
        group = store_g.get("PoolGroup", "default", "serving")
        assert group.status.coordinated is True

    def test_repair_band_raises_decode_into_the_band(self):
        store, _, auto, _ = _world(groups=[_serving_group(REPAIR_BAND)])
        assert _tick(store, auto) == {"prefill": PREFILL, "decode": 44}
        group = store.get("PoolGroup", "default", "serving")
        assert group.status.coordinated is True
        assert group.status.expected_hourly == 55.0

    def test_fused_tick_matches_the_chained_path(self):
        """The --fused-tick joint stage lands tick-for-tick on the
        chained engine path's counts, repair included."""
        import jax

        from karpenter_tpu.ops import fusedtick as FT

        store_c, _, auto_c, _ = _world(groups=[_serving_group(REPAIR_BAND)])
        store_f, _, auto_f, _ = _world(groups=[_serving_group(REPAIR_BAND)])
        auto_f.fused_tick_fn = jax.jit(FT.fused_tick)
        for _ in range(3):
            assert _tick(store_c, auto_c) == _tick(store_f, auto_f)


class TestPoolGroupEngine:
    def test_unresolvable_member_sits_the_group_out(self):
        """A group naming a missing HA is skipped WHOLE — the live
        members keep their independent counts rather than being jointly
        allocated against a phantom."""
        ghost = _serving_group(
            [], pools=[
                PoolMember(name="prefill"), PoolMember(name="ghost")
            ],
        )
        store, registry, auto, _ = _world(groups=[ghost])
        assert _tick(store, auto) == {
            "prefill": PREFILL, "decode": DECODE
        }
        assert registry.gauge("poolgroup", "ratio_ok").get(
            "serving", "default"
        ) is None

    def test_overlapping_groups_first_listed_wins(self):
        first = _serving_group(REPAIR_BAND, name="a-first")
        second = _serving_group(SLACK_BAND, name="b-second")
        store, registry, auto, _ = _world(groups=[first, second])
        assert _tick(store, auto) == {"prefill": PREFILL, "decode": 44}
        assert registry.gauge("poolgroup", "ratio_ok").get(
            "a-first", "default"
        ) == 1.0
        assert registry.gauge("poolgroup", "ratio_ok").get(
            "b-second", "default"
        ) is None

    def test_failing_seam_never_blocks_and_counts_degraded(self):
        def boom(inputs):
            raise RuntimeError("joint seam down")

        store, registry, auto, _ = _world(
            groups=[_serving_group(REPAIR_BAND)], poolgroup_fn=boom
        )
        assert _tick(store, auto) == {
            "prefill": PREFILL, "decode": DECODE
        }
        assert registry.gauge("poolgroup", "degraded_total").get(
            "serving", "default"
        ) == 1.0

    def test_gauges_retire_when_the_group_is_deleted(self):
        store, registry, auto, _ = _world(
            groups=[_serving_group(REPAIR_BAND)]
        )
        _tick(store, auto)
        gauge = registry.gauge("poolgroup", "ratio_ok")
        assert gauge.get("serving", "default") == 1.0
        store.delete("PoolGroup", "default", "serving")
        _tick(store, auto)
        assert gauge.get("serving", "default") is None
        assert registry.gauge("poolgroup", "expected_hourly").get(
            "serving", "default"
        ) is None

    def test_headroom_feeds_the_warm_pool_signal(self):
        store, _, auto, engine = _world(
            groups=[_serving_group(REPAIR_BAND)]
        )
        _tick(store, auto)
        assert engine.headroom("default", "g-decode") >= 0
        assert engine.headroom("default", "nope") == 0


class TestGroupAwareAdmission:
    def test_grouped_tenants_ride_one_round(self):
        from karpenter_tpu.tenancy.fairness import WeightedAdmission

        adm = WeightedAdmission(budget_rows=100)
        schedule = adm.rounds(
            {"a": 60, "b": 60, "c": 10}, {},
            {"a": "pg1", "b": "pg1"},
        )
        for admitted in schedule:
            assert ("a" in admitted) == ("b" in admitted), (
                "coalition members split across rounds"
            )
        assert any({"a", "b"} <= set(r) for r in schedule)

    def test_ungrouped_schedule_is_unchanged(self):
        from karpenter_tpu.tenancy.fairness import WeightedAdmission

        demand = {"a": 30, "b": 50, "c": 40}
        weights = {"a": 2.0, "b": 1.0, "c": 1.0}
        plain = WeightedAdmission(budget_rows=64)
        grouped = WeightedAdmission(budget_rows=64)
        assert plain.rounds(demand, weights) == grouped.rounds(
            demand, weights, {}
        )

    def test_registry_exposes_pool_groups(self):
        from karpenter_tpu.tenancy.registry import (
            TenantRegistry,
            TenantSpec,
        )

        registry = TenantRegistry(specs=[
            TenantSpec(id="t1", pool_group="serving"),
            TenantSpec(id="t2", pool_group="serving"),
            TenantSpec(id="t3"),
        ])
        assert registry.pool_groups() == {
            "t1": "serving", "t2": "serving"
        }
        with pytest.raises(ValueError, match="poolGroup"):
            TenantSpec(id="bad", pool_group="").validate()


# -- the regression guard (live) ----------------------------------------------


def build_poolgroup_inputs(groups: int, pools: int, metrics: int,
                           seed: int):
    """A fleet of pool groups with every member pool live and every
    coupling SLACK (ratios/budget invalid, tier penalties zero): the
    one configuration where the joint program is provably the per-pool
    cost ladder bit for bit, so the timed comparison is the same math
    in two dispatch shapes — the guard measures the dispatch collapse,
    not a different algorithm. The masked constraint operands still
    run inside the joint program, so its timing is honest for the
    enforcing case too."""
    rng = np.random.RandomState(seed)
    G, P, M = groups, pools, metrics
    base = rng.randint(1, 200, (G, P)).astype(np.int32)
    ratio_a = rng.randint(0, P, (G, PGK.RATIO_SLOTS)).astype(np.int32)
    return PGK.PoolGroupInputs(
        base_desired=base,
        min_replicas=np.maximum(base - 50, 0).astype(np.int32),
        max_replicas=(base + rng.randint(50, 500, (G, P))).astype(
            np.int32
        ),
        unit_cost=rng.choice([0.07, 0.19, 1.0, 4.8], (G, P)).astype(
            np.float32
        ),
        slo_weight=rng.choice([0.0, 5.0, 50.0, 500.0], (G, P)).astype(
            np.float32
        ),
        max_hourly_cost=rng.choice([0.0, 25.0, 250.0], (G, P)).astype(
            np.float32
        ),
        tier_penalty=np.zeros((G, P), np.float32),
        pool_valid=np.ones((G, P), bool),
        slo_target=rng.uniform(0.5, 10, (G, P, M)).astype(np.float32),
        demand_mu=rng.uniform(0, 1000, (G, P, M)).astype(np.float32),
        demand_sigma=rng.choice([0.0, 5.0, 50.0], (G, P, M)).astype(
            np.float32
        ),
        demand_valid=rng.rand(G, P, M) > 0.1,
        ratio_a=ratio_a,
        ratio_b=((ratio_a + 1) % P).astype(np.int32),
        ratio_min_num=np.zeros((G, PGK.RATIO_SLOTS), np.int32),
        ratio_min_den=np.ones((G, PGK.RATIO_SLOTS), np.int32),
        ratio_max_num=np.zeros((G, PGK.RATIO_SLOTS), np.int32),
        ratio_max_den=np.zeros((G, PGK.RATIO_SLOTS), np.int32),
        ratio_valid=np.zeros((G, PGK.RATIO_SLOTS), bool),
        group_budget=np.zeros(G, np.float32),
        group_valid=np.zeros(G, bool),
    )


class TestPoolGroupRegressionGuard:
    def test_live_joint_not_slower_than_per_pool(self):
        """The live guard: one warmed joint dispatch must not fall
        behind the warmed per-pool loop it replaces (generous margin —
        this catches a dispatch-collapse regression, not timer noise)."""
        import time

        import jax

        inputs = build_poolgroup_inputs(16, 3, 2, seed=7)
        rows = []
        G, P = 16, 3
        for i in range(G * P):
            g, p = divmod(i, P)
            rows.append(CK.CostInputs(
                base_desired=inputs.base_desired[g, p: p + 1],
                min_replicas=inputs.min_replicas[g, p: p + 1],
                max_replicas=inputs.max_replicas[g, p: p + 1],
                unit_cost=inputs.unit_cost[g, p: p + 1],
                slo_weight=inputs.slo_weight[g, p: p + 1],
                max_hourly_cost=inputs.max_hourly_cost[g, p: p + 1],
                slo_valid=inputs.pool_valid[g, p: p + 1],
                slo_target=inputs.slo_target[g, p: p + 1],
                demand_mu=inputs.demand_mu[g, p: p + 1],
                demand_sigma=inputs.demand_sigma[g, p: p + 1],
                demand_valid=inputs.demand_valid[g, p: p + 1],
            ))
        jax.block_until_ready(PGK.poolgroup_jit(inputs))  # warm
        jax.block_until_ready(CK.cost_jit(rows[0]))       # warm

        def p50(fn, iters=5):
            times = []
            for _ in range(iters):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            return sorted(times)[len(times) // 2]

        joint = p50(
            lambda: jax.block_until_ready(PGK.poolgroup_jit(inputs))
        )
        loop = p50(lambda: [
            jax.block_until_ready(CK.cost_jit(r)) for r in rows
        ])
        assert joint <= loop * 1.5, (
            f"one joint dispatch ({joint * 1e3:.2f}ms) fell behind the "
            f"{G * P}-dispatch per-pool loop ({loop * 1e3:.2f}ms)"
        )
