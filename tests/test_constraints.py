"""Constraint plane (karpenter_tpu/constraints + the ops/binpack
constraint operands): compiler units, kernel semantics, XLA == numpy
bitwise parity, absent-operand wire compat, and the seeded property pin
that batched constrained verdicts equal independent per-group solves."""

import dataclasses

import jax
import numpy as np
import pytest

from karpenter_tpu.api.core import (
    Container,
    ObjectMeta,
    Pod,
    PodSpec,
    RESERVATION_LABEL,
    ZONE_LABEL,
    reservation_of,
    resource_list,
    zone_of,
)
from karpenter_tpu.constraints import (
    ConstraintGroup,
    SpreadSpec,
    canonical_constraints,
    compile_membership,
    compile_rows,
    constraint_meta,
    reservation_fill,
    spread_skew,
    validate_constraints,
)
from karpenter_tpu.metrics.producers.pendingcapacity import encode_snapshot
from karpenter_tpu.metrics.producers.pendingcapacity import (
    encoder as encoder_mod,
)
from karpenter_tpu.ops import binpack as B
from karpenter_tpu.ops.numpy_binpack import binpack_numpy
from karpenter_tpu.store.columnar import snapshot_from_pods


# -- world builders ----------------------------------------------------------


def _pod(name, labels=None, cpu="1"):
    return Pod(
        metadata=ObjectMeta(name=name, labels=dict(labels or {})),
        spec=PodSpec(
            node_name="",
            containers=[
                Container(requests=resource_list(cpu=cpu, memory="1Gi"))
            ],
        ),
    )


def _profile(zone="", reservation="", cpu=8.0):
    labels = set()
    if zone:
        labels.add((ZONE_LABEL, zone))
    if reservation:
        labels.add((RESERVATION_LABEL, reservation))
    return (
        {"cpu": cpu, "memory": 32.0, "pods": 32.0},
        labels,
        set(),
    )


def _random_world(rng, n_groups_spec=3):
    """(pods, profiles, groups): a random fleet whose constraint specs
    exercise every operand family."""
    zones = ["z1", "z2", "z3"][: int(rng.integers(2, 4))]
    profiles = [_profile(zone=z) for z in zones]
    profiles.append(_profile(reservation="gold"))
    profiles.append(_profile())  # zone-less open capacity
    groups = []
    kinds = rng.permutation(
        ["spread", "reservation", "anti", "compact"]
    )[:n_groups_spec]
    for i, kind in enumerate(kinds):
        sel = {"team": f"t{i}"}
        if kind == "spread":
            groups.append(
                ConstraintGroup(
                    name=f"g{i}", pod_selector=sel, spread=SpreadSpec()
                )
            )
        elif kind == "reservation":
            groups.append(
                ConstraintGroup(
                    name=f"g{i}", pod_selector=sel, reservation="gold"
                )
            )
        elif kind == "anti":
            groups.append(
                ConstraintGroup(
                    name=f"g{i}", pod_selector=sel, anti_affinity=True
                )
            )
        else:
            groups.append(
                ConstraintGroup(
                    name=f"g{i}", pod_selector=sel, compact=True
                )
            )
    pods = []
    for p in range(int(rng.integers(8, 28))):
        team = int(rng.integers(0, n_groups_spec + 2))  # some unmatched
        labels = (
            {"team": f"t{team}"} if team < n_groups_spec else {}
        )
        pods.append(
            _pod(f"p{p}", labels, cpu=str(int(rng.integers(1, 3))))
        )
    return pods, profiles, groups


def _encode(pods, profiles, groups):
    snap = snapshot_from_pods(pods)
    return encode_snapshot(snap, profiles, constraints=groups)


@pytest.fixture(autouse=True)
def _fresh_constraint_state():
    encoder_mod.reset_constraint_state()
    yield
    encoder_mod.reset_constraint_state()


# -- compiler units ----------------------------------------------------------


class TestSpec:
    def test_validation_rules(self):
        with pytest.raises(ValueError, match="requires a name"):
            ConstraintGroup(pod_selector={"a": "b"}, compact=True).validate()
        with pytest.raises(ValueError, match="podSelector"):
            ConstraintGroup(name="x", compact=True).validate()
        with pytest.raises(ValueError, match="declares no constraint"):
            ConstraintGroup(name="x", pod_selector={"a": "b"}).validate()
        with pytest.raises(ValueError, match="mutually exclusive"):
            ConstraintGroup(
                name="x", pod_selector={"a": "b"},
                anti_affinity=True, compact=True,
            ).validate()
        # any non-empty label key is a legal spread axis now
        SpreadSpec(topology_key="kubernetes.io/hostname").validate()
        with pytest.raises(ValueError, match="topologyKey"):
            SpreadSpec(topology_key="").validate()
        with pytest.raises(ValueError, match="maxSkew"):
            SpreadSpec(max_skew=0).validate()
        with pytest.raises(ValueError, match="single"):
            validate_constraints([
                ConstraintGroup(
                    name="x", pod_selector={"a": "b"},
                    spread=SpreadSpec(topology_key="rack"),
                ),
                ConstraintGroup(
                    name="y", pod_selector={"c": "d"},
                    spread=SpreadSpec(),
                ),
            ])
        with pytest.raises(ValueError, match="duplicate"):
            validate_constraints([
                ConstraintGroup(
                    name="x", pod_selector={"a": "b"}, compact=True
                ),
                ConstraintGroup(
                    name="x", pod_selector={"c": "d"}, compact=True
                ),
            ])

    def test_canonical_form_is_hashable_and_order_sensitive(self):
        g1 = ConstraintGroup(
            name="a", pod_selector={"k": "v"}, compact=True
        )
        g2 = ConstraintGroup(
            name="b", pod_selector={"k": "w"}, spread=SpreadSpec()
        )
        assert canonical_constraints([]) == ()
        assert hash(canonical_constraints([g1, g2])) != hash(
            canonical_constraints([g2, g1])
        )


class TestCompiler:
    def test_membership_first_match_wins(self):
        label_sets = [(), (("a", "1"),), (("a", "1"), ("b", "2"))]
        labels_id = np.array([0, 1, 2, 2], np.int32)
        groups = [
            ConstraintGroup(
                name="g0", pod_selector={"b": "2"}, compact=True
            ),
            ConstraintGroup(
                name="g1", pod_selector={"a": "1"}, compact=True
            ),
        ]
        m = compile_membership(label_sets, labels_id, groups)
        # set 1 matches only g1 (-> 2); set 2 matches g0 first (-> 1)
        assert m.tolist() == [0, 2, 1, 1]

    def test_meta_universes(self):
        profiles = [
            _profile(zone="z2"),
            _profile(zone="z1", reservation="silver"),
            _profile(),
        ]
        groups = [
            ConstraintGroup(
                name="s", pod_selector={"a": "1"}, spread=SpreadSpec()
            ),
            ConstraintGroup(
                name="r", pod_selector={"b": "1"}, reservation="gold"
            ),
            ConstraintGroup(
                name="c", pod_selector={"c": "1"}, compact=True
            ),
        ]
        meta = constraint_meta(groups, profiles)
        # spec claims UNION group reservation labels, sorted
        assert meta.reservations == ["gold", "silver"]
        assert meta.zones == ["z1", "z2"]
        assert meta.spread_names == ["s"]
        assert meta.compact_names == ["c"]

    def test_spread_split_balanced_caps_and_boundary_cuts(self):
        groups = [
            ConstraintGroup(
                name="s", pod_selector={"a": "1"}, spread=SpreadSpec()
            )
        ]
        profiles = [_profile(zone="z1"), _profile(zone="z2")]
        membership = np.array([1, 1, 0], np.int32)
        weights = np.array([5, 2, 3], np.int32)
        valid = np.array([True, True, True])
        compiled = compile_rows(
            membership, weights, valid, profiles, groups
        )
        # total member weight 7 over 2 zones -> caps [4, 3] (+ sink 0)
        np.testing.assert_array_equal(
            compiled.spread_cap, [[4, 3, 0]]
        )
        # row 0 (w=5) straddles the z1 quota boundary at rank 4: split
        # 4+1; row 1 fits inside z2's quota; row 2 passes through
        np.testing.assert_array_equal(
            compiled.rep, [0, 0, 1, 2]
        )
        np.testing.assert_array_equal(
            compiled.row_weight, [4, 1, 2, 3]
        )
        np.testing.assert_array_equal(
            compiled.spread_slot, [1, 1, 1, 0]
        )
        # weight is conserved per source row
        assert compiled.row_weight[:2].sum() == 5

    def test_inert_when_no_members_or_no_zones(self):
        groups = [
            ConstraintGroup(
                name="s", pod_selector={"a": "1"}, spread=SpreadSpec()
            )
        ]
        # members exist but no zoned profiles -> inert spread
        compiled = compile_rows(
            np.array([1], np.int32),
            np.array([3], np.int32),
            np.array([True]),
            [_profile()],
            groups,
        )
        assert compiled.spread_slot is None
        assert compiled.spread_cap is None
        np.testing.assert_array_equal(compiled.rep, [0])

    def test_reservation_operands_and_fencing_universe(self):
        groups = [
            ConstraintGroup(
                name="r", pod_selector={"t": "1"}, reservation="gold"
            )
        ]
        profiles = [_profile(reservation="gold"), _profile()]
        compiled = compile_rows(
            np.array([1, 0], np.int32),
            np.array([1, 1], np.int32),
            np.array([True, True]),
            profiles,
            groups,
        )
        np.testing.assert_array_equal(compiled.claim, [1, 0])
        np.testing.assert_array_equal(
            compiled.group_reservation, [1, 0]
        )

    def test_reserved_group_fences_even_without_claimants(self):
        """A karpenter.sh/reservation-labeled group joins the operand
        universe even when NO spec claims it — unclaimed pods must be
        fenced off reserved capacity."""
        groups = [
            ConstraintGroup(
                name="c", pod_selector={"t": "1"}, compact=True
            )
        ]
        profiles = [_profile(reservation="idle"), _profile()]
        compiled = compile_rows(
            np.array([1, 0], np.int32),
            np.array([1, 1], np.int32),
            np.array([True, True]),
            profiles,
            groups,
        )
        # nobody claims -> claim all zeros, but the reserved group is
        # still marked so the kernel fences unclaimed pods off it
        np.testing.assert_array_equal(compiled.claim, [0, 0])
        np.testing.assert_array_equal(
            compiled.group_reservation, [1, 0]
        )

    def test_zone_reservation_label_helpers(self):
        labels = {ZONE_LABEL: "z9", RESERVATION_LABEL: "gold"}
        assert zone_of(labels) == "z9"
        assert reservation_of(labels) == "gold"
        assert zone_of({}) == ""
        assert reservation_of({}) == ""


# -- kernel semantics --------------------------------------------------------


def _inputs_from_compiled(requests, alloc, compiled, weights=None):
    """Hand-assemble BinPackInputs from a CompiledConstraints the way
    the encoder does (unpadded: the kernel accepts any extents)."""
    import jax.numpy as jnp

    P = len(compiled.rep)
    T = len(alloc)
    base = dict(
        pod_requests=jnp.asarray(
            np.asarray(requests, np.float32)[compiled.rep]
        ),
        pod_valid=jnp.ones(P, bool),
        pod_intolerant=jnp.zeros((P, 4), bool),
        pod_required=jnp.zeros((P, 4), bool),
        group_allocatable=jnp.asarray(np.asarray(alloc, np.float32)),
        group_taints=jnp.zeros((T, 4), bool),
        group_labels=jnp.zeros((T, 4), bool),
        pod_weight=jnp.asarray(compiled.row_weight),
    )
    for name, value in (
        ("pod_claim", compiled.claim),
        ("group_reservation", compiled.group_reservation),
        ("pod_pack_class", compiled.pack_class),
        ("pod_spread_slot", compiled.spread_slot),
        ("group_domain", compiled.group_domain),
        ("spread_cap", compiled.spread_cap),
        ("pod_exclusive", compiled.exclusive),
    ):
        if value is not None:
            base[name] = jnp.asarray(value)
    return B.BinPackInputs(**base)


class TestKernelSemantics:
    def test_reservation_fences_both_ways(self):
        groups = [
            ConstraintGroup(
                name="r", pod_selector={"t": "1"}, reservation="gold"
            )
        ]
        profiles = [_profile(reservation="gold"), _profile()]
        compiled = compile_rows(
            np.array([1, 0], np.int32),
            np.array([1, 1], np.int32),
            np.array([True, True]),
            profiles,
            groups,
        )
        inputs = _inputs_from_compiled(
            [[1, 1], [1, 1]],
            [[8, 8], [8, 8]],
            compiled,
        )
        out = jax.device_get(B.binpack(inputs, buckets=8))
        # claimant -> reserved group 0; unclaimed -> fenced to group 1
        assert out.assigned.tolist() == [0, 1]

    def test_spread_balances_across_zones(self):
        groups = [
            ConstraintGroup(
                name="s", pod_selector={"t": "1"}, spread=SpreadSpec()
            )
        ]
        profiles = [_profile(zone="z1"), _profile(zone="z2")]
        membership = np.ones(4, np.int32)
        compiled = compile_rows(
            membership,
            np.ones(4, np.int32),
            np.ones(4, bool),
            profiles,
            groups,
        )
        inputs = _inputs_from_compiled(
            [[1, 1]] * 4, [[8, 8], [8, 8]], compiled
        )
        out = jax.device_get(B.binpack(inputs, buckets=8))
        # without spread every pod would land on group 0; with balanced
        # quotas the assignment splits 2/2
        assert out.assigned_count.tolist() == [2, 2]
        meta = compiled.meta
        assert spread_skew(inputs, out.assigned, meta) == {"s": 0}

    def test_spread_custom_key_parity_with_zone(self):
        # the balanced-spread pin, extended to an arbitrary topology
        # axis: the SAME fleet labeled on a custom key compiles to
        # byte-identical operands and the kernel balances identically
        rack = "example.com/rack"
        z_groups = [
            ConstraintGroup(
                name="s", pod_selector={"t": "1"}, spread=SpreadSpec()
            )
        ]
        r_groups = [
            ConstraintGroup(
                name="s",
                pod_selector={"t": "1"},
                spread=SpreadSpec(topology_key=rack),
            )
        ]
        validate_constraints(r_groups)
        z_profiles = [_profile(zone="z1"), _profile(zone="z2")]
        r_profiles = [
            (
                {"cpu": 8.0, "memory": 32.0, "pods": 32.0},
                {(rack, z)},
                set(),
            )
            for z in ("z1", "z2")
        ]
        membership = np.ones(4, np.int32)
        compiled = []
        for profiles, groups in (
            (z_profiles, z_groups),
            (r_profiles, r_groups),
        ):
            compiled.append(
                compile_rows(
                    membership,
                    np.ones(4, np.int32),
                    np.ones(4, bool),
                    profiles,
                    groups,
                )
            )
        a, b = compiled
        for name in (
            "rep",
            "row_weight",
            "spread_slot",
            "group_domain",
            "spread_cap",
        ):
            assert np.array_equal(getattr(a, name), getattr(b, name))
        assert b.meta.topology_key == rack
        assert b.meta.zones == ["z1", "z2"]
        inputs = _inputs_from_compiled(
            [[1, 1]] * 4, [[8, 8], [8, 8]], b
        )
        out = jax.device_get(B.binpack(inputs, buckets=8))
        assert out.assigned_count.tolist() == [2, 2]
        assert spread_skew(inputs, out.assigned, b.meta) == {"s": 0}

    def test_compact_members_never_share_nodes(self):
        groups = [
            ConstraintGroup(
                name="c", pod_selector={"t": "1"}, compact=True
            )
        ]
        profiles = [_profile()]
        # 2 compact members + 2 plain pods, all 1 cpu on an 8-cpu node:
        # unconstrained everything fits one node; compact isolation
        # needs a second node for the members
        compiled = compile_rows(
            np.array([1, 1, 0, 0], np.int32),
            np.ones(4, np.int32),
            np.ones(4, bool),
            profiles,
            groups,
        )
        inputs = _inputs_from_compiled(
            [[1, 1]] * 4, [[8.0, 8.0]], compiled
        )
        out = jax.device_get(B.binpack(inputs, buckets=8))
        assert out.nodes_needed.tolist() == [2]
        un = dataclasses.replace(inputs, pod_pack_class=None)
        assert jax.device_get(
            B.binpack(un, buckets=8)
        ).nodes_needed.tolist() == [1]

    def test_anti_affinity_members_take_whole_nodes(self):
        groups = [
            ConstraintGroup(
                name="a", pod_selector={"t": "1"}, anti_affinity=True
            )
        ]
        compiled = compile_rows(
            np.array([1, 1, 0], np.int32),
            np.ones(3, np.int32),
            np.ones(3, bool),
            [_profile()],
            groups,
        )
        inputs = _inputs_from_compiled(
            [[1, 1]] * 3, [[8.0, 8.0]], compiled
        )
        out = jax.device_get(B.binpack(inputs, buckets=8))
        # 2 exclusive nodes + 1 shared node
        assert out.nodes_needed.tolist() == [3]

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_xla_equals_numpy_bitwise(self, seed):
        rng = np.random.default_rng(seed)
        pods, profiles, groups = _random_world(rng)
        inputs = _encode(pods, profiles, groups)
        assert B.has_constraint_operands(inputs)
        out = jax.device_get(B.binpack(inputs, buckets=8))
        ref = binpack_numpy(inputs, buckets=8)
        np.testing.assert_array_equal(
            out.assigned, np.asarray(ref.assigned)
        )
        np.testing.assert_array_equal(
            out.assigned_count, np.asarray(ref.assigned_count)
        )
        np.testing.assert_array_equal(
            out.nodes_needed, np.asarray(ref.nodes_needed)
        )
        assert int(out.unschedulable) == int(ref.unschedulable)

    def test_constraint_mask_parity_jnp_np(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        P, T, S, D = 13, 5, 2, 3
        claim = rng.integers(0, 3, P).astype(np.int32)
        reservation = rng.integers(0, 3, T).astype(np.int32)
        slot = rng.integers(0, S + 1, P).astype(np.int32)
        domain = rng.integers(0, D + 1, T).astype(np.int32)
        caps = rng.integers(0, 6, (S, D + 1)).astype(np.int32)
        weight = rng.integers(1, 4, P).astype(np.int32)
        valid = rng.random(P) < 0.9
        got_np = B.constraint_mask(
            claim, reservation, slot, domain, caps, weight, valid,
            xp=np,
        )
        got_jnp = np.asarray(
            B.constraint_mask(
                jnp.asarray(claim), jnp.asarray(reservation),
                jnp.asarray(slot), jnp.asarray(domain),
                jnp.asarray(caps), jnp.asarray(weight),
                jnp.asarray(valid), xp=jnp,
            )
        )
        np.testing.assert_array_equal(got_np, got_jnp)
        # absent halves broadcast instead of materializing zeros
        np.testing.assert_array_equal(
            np.broadcast_to(
                B.constraint_mask(
                    claim, None, None, None, None, weight, valid, xp=np
                ),
                (P, T),
            ),
            np.broadcast_to((claim == 0)[:, None], (P, T)),
        )


# -- the seeded property pin -------------------------------------------------


class TestBatchedEqualsPerGroup:
    @pytest.mark.parametrize("seed", list(range(6)))
    def test_batched_verdicts_equal_independent_per_group_solves(
        self, seed
    ):
        """Per-pod verdicts of the ONE batched constrained dispatch ==
        solving each constraint group's members independently (the
        batched inputs with every other row invalidated). Spread ranks
        only accumulate over valid same-slot rows and every other
        operand is per-row, so the per-group solve is exact — lp_bound
        is excluded (an LP over a subset is not additive)."""
        import jax.numpy as jnp

        rng = np.random.default_rng(100 + seed)
        pods, profiles, groups = _random_world(rng)
        inputs = _encode(pods, profiles, groups)
        membership = _row_membership(inputs, pods, groups)
        batched = jax.device_get(B.binpack(inputs, buckets=8))
        for g in range(len(groups) + 1):  # 0 = the unconstrained rest
            rows = membership == g
            if not rows.any():
                continue
            solo_valid = np.asarray(inputs.pod_valid) & rows
            solo = dataclasses.replace(
                inputs,
                pod_valid=jnp.asarray(solo_valid),
                pod_weight=jnp.asarray(
                    np.where(rows, np.asarray(inputs.pod_weight), 0)
                    .astype(np.int32)
                ),
            )
            out = jax.device_get(B.binpack(solo, buckets=8))
            np.testing.assert_array_equal(
                out.assigned[rows],
                batched.assigned[rows],
                err_msg=f"seed {seed} group {g}",
            )


def _row_membership(inputs, pods, groups):
    """Recompute per-ROW membership of the encoded inputs by matching
    each row's claim/slot/class/exclusive signature back to its group —
    rows are post-dedup, so pod-level membership can't be indexed
    directly."""
    P = np.asarray(inputs.pod_valid).shape[0]
    membership = np.zeros(P, np.int32)
    # the encoder guarantees: row operands were gathered from compiled
    # membership; reconstruct via the operand signatures
    claim = (
        np.asarray(inputs.pod_claim)
        if inputs.pod_claim is not None
        else np.zeros(P, np.int32)
    )
    slot = (
        np.asarray(inputs.pod_spread_slot)
        if inputs.pod_spread_slot is not None
        else np.zeros(P, np.int32)
    )
    pc = (
        np.asarray(inputs.pod_pack_class)
        if inputs.pod_pack_class is not None
        else None
    )
    excl = (
        np.asarray(inputs.pod_exclusive)
        if inputs.pod_exclusive is not None
        else np.zeros(P, bool)
    )
    meta = constraint_meta(groups, [])
    for gidx, group in enumerate(groups):
        sig = np.ones(P, bool)
        if group.reservation:
            c = 1 + meta.reservations.index(group.reservation)
            sig &= claim == c
        elif group.spread is not None:
            s = 1 + meta.spread_names.index(group.name)
            sig &= slot == s
        elif group.compact:
            k = 1 + meta.compact_names.index(group.name)
            sig &= (
                pc[:, k]
                if pc is not None and k < pc.shape[1]
                else np.zeros(P, bool)
            )
        elif group.anti_affinity:
            sig &= excl
        membership[sig & (membership == 0)] = gidx + 1
    return membership


# -- wire compat -------------------------------------------------------------


def _assert_inputs_identical(a, b):
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if va is None or vb is None:
            assert va is vb, f.name
        else:
            np.testing.assert_array_equal(
                np.asarray(va), np.asarray(vb), err_msg=f.name
            )


class TestWireCompat:
    def test_absent_constraints_encode_byte_identical(self):
        """No spec.constraints anywhere -> the six constraint operands
        stay None and every other operand is byte-identical to an
        encode that predates the constraint plane (constraints=None and
        constraints=[] take the same path)."""
        rng = np.random.default_rng(3)
        pods, profiles, _ = _random_world(rng)
        snap = snapshot_from_pods(pods)
        base = encode_snapshot(snap, profiles)
        for variant in (None, [], ()):
            got = encode_snapshot(
                snapshot_from_pods(pods), profiles, constraints=variant
            )
            for f in B._CONSTRAINT_FIELDS:
                assert getattr(got, f) is None, f
            _assert_inputs_identical(base, got)
        assert encoder_mod.constraint_stats["compiles"] == 0

    def test_nonmatching_constraints_keep_wire_unchanged(self):
        """In a fleet with NO reserved capacity, groups whose selectors
        match no pod attach nothing: the operands stay None and the
        arrays are byte-identical. (With reserved profiles present,
        admitting a constraint plane activates reservation fencing even
        without claimants — pinned in TestCompiler.)"""
        rng = np.random.default_rng(4)
        pods, profiles, _ = _random_world(rng)
        profiles = [
            p for p in profiles
            if not any(k == RESERVATION_LABEL for k, _ in p[1])
        ]
        groups = [
            ConstraintGroup(
                name="ghost",
                pod_selector={"no-such-label": "x"},
                spread=SpreadSpec(),
            )
        ]
        base = encode_snapshot(snapshot_from_pods(pods), profiles)
        got = encode_snapshot(
            snapshot_from_pods(pods), profiles, constraints=groups
        )
        for f in B._CONSTRAINT_FIELDS:
            assert getattr(got, f) is None, f
        _assert_inputs_identical(base, got)

    def test_membership_splits_dedup_of_identical_specs(self):
        """Two spec-identical pods in different groups must dedup apart
        (labels are not part of the unconstrained dedup identity)."""
        pods = [
            _pod("a", {"team": "t0"}),
            _pod("b", {"team": "t1"}),
        ]
        profiles = [_profile(reservation="gold"), _profile()]
        groups = [
            ConstraintGroup(
                name="g0", pod_selector={"team": "t0"},
                reservation="gold",
            ),
        ]
        un = encode_snapshot(snapshot_from_pods(pods), profiles)
        # unconstrained: one deduped row of weight 2
        assert int(np.asarray(un.pod_weight).sum()) == 2
        assert int((np.asarray(un.pod_weight) > 0).sum()) == 1
        con = encode_snapshot(
            snapshot_from_pods(pods), profiles, constraints=groups
        )
        live = np.asarray(con.pod_weight) > 0
        assert int(live.sum()) == 2  # membership split the row
        claims = np.asarray(con.pod_claim)[live]
        assert sorted(claims.tolist()) == [0, 1]


# -- pallas guard (third dispatch site) --------------------------------------


class TestPallasGuard:
    def test_fold_for_pallas_reroutes_constrained_inputs(self):
        rng = np.random.default_rng(11)
        pods, profiles, groups = _random_world(rng)
        inputs = _encode(pods, profiles, groups)
        assert B.has_constraint_operands(inputs)
        _, route = B.fold_for_pallas(inputs)
        assert route == "xla"

    def test_service_reroutes_and_counts(self):
        from karpenter_tpu.metrics.registry import GaugeRegistry
        from karpenter_tpu.solver import SolverService

        rng = np.random.default_rng(12)
        pods, profiles, groups = _random_world(rng)
        inputs = _encode(pods, profiles, groups)
        service = SolverService(
            registry=GaugeRegistry(), backend="pallas",
            health_failure_threshold=100,
        )
        try:
            out = service.solve(inputs, buckets=8)
            assert service.stats.constraint_reroutes >= 1
            ref = binpack_numpy(inputs, buckets=8)
            np.testing.assert_array_equal(
                np.asarray(out.assigned), np.asarray(ref.assigned)
            )
        finally:
            service.close()


# -- verdict helpers ---------------------------------------------------------


class TestVerdicts:
    def test_reservation_fill_counts_placed_claimants(self):
        groups = [
            ConstraintGroup(
                name="r", pod_selector={"t": "1"}, reservation="gold"
            )
        ]
        profiles = [_profile(reservation="gold")]
        compiled = compile_rows(
            np.array([1, 1], np.int32),
            np.array([1, 1], np.int32),
            np.array([True, True]),
            profiles,
            groups,
        )
        inputs = _inputs_from_compiled(
            [[1, 1], [99, 99]], [[8, 8]], compiled
        )
        out = jax.device_get(B.binpack(inputs, buckets=8))
        fill = reservation_fill(inputs, out.assigned, compiled.meta)
        assert fill == {"gold": 0.5}  # one of two claimants placed

    def test_idle_reservation_reports_full(self):
        meta = constraint_meta(
            [
                ConstraintGroup(
                    name="r", pod_selector={"t": "1"},
                    reservation="gold",
                )
            ],
            [],
        )
        inputs = B.BinPackInputs(
            pod_requests=np.zeros((1, 2), np.float32),
            pod_valid=np.zeros(1, bool),
            pod_intolerant=np.zeros((1, 1), bool),
            pod_required=np.zeros((1, 1), bool),
            group_allocatable=np.zeros((1, 2), np.float32),
            group_taints=np.zeros((1, 1), bool),
            group_labels=np.zeros((1, 1), bool),
        )
        assert reservation_fill(
            inputs, np.array([-1]), meta
        ) == {"gold": 1.0}


class TestRegressionGuard:
    def test_batched_constrained_beats_per_group_loop(self):
        """Non-slow guard for the batched constraint plane: ONE batched
        masked-operand dispatch must beat the per-group sequential loop
        (generously; no benchmark cell measures this plane on the
        chip)."""
        import time

        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        pods, profiles, groups = _random_world(rng, n_groups_spec=3)
        pods = pods * 12  # enough work for a stable timing signal
        for i, p in enumerate(pods):
            p.metadata.name = f"p{i}"
        inputs = _encode(pods, profiles, groups)
        membership = _row_membership(inputs, pods, groups)
        solos = []
        for g in range(len(groups) + 1):
            rows = membership == g
            solos.append(dataclasses.replace(
                inputs,
                pod_valid=jnp.asarray(
                    np.asarray(inputs.pod_valid) & rows
                ),
                pod_weight=jnp.asarray(np.where(
                    rows, np.asarray(inputs.pod_weight), 0
                ).astype(np.int32)),
            ))
        # warm both programs (same shapes: the solos share one compile)
        jax.block_until_ready(B.binpack(inputs, buckets=8))
        jax.block_until_ready(B.binpack(solos[0], buckets=8))

        def best_of(fn, reps=3):
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            return min(times)

        batched = best_of(
            lambda: jax.block_until_ready(B.binpack(inputs, buckets=8))
        )
        sequential = best_of(lambda: [
            jax.block_until_ready(B.binpack(s, buckets=8))
            for s in solos
        ])
        assert batched < sequential, (
            f"batched {batched * 1e3:.2f}ms not faster than the "
            f"per-group loop {sequential * 1e3:.2f}ms"
        )
