"""Bin-pack solver tests: feasibility masks, assignment, packing quality.

Three tiers (SURVEY.md §4 "solver correctness needs a new tier"):
- exact: device shelf-BFD == NumPy oracle of the same algorithm
- sandwich: LP lower bound <= result, and result close to full-precision FFD
- semantics: taints/tolerations, nodeSelector, resource fit, assignment rules
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from karpenter_tpu.ops import binpack as B


def make_inputs(
    pod_requests,
    group_allocatable,
    pod_valid=None,
    pod_intolerant=None,
    pod_required=None,
    group_taints=None,
    group_labels=None,
    n_taints=4,
    n_labels=4,
):
    req = np.asarray(pod_requests, np.float32)
    alloc = np.asarray(group_allocatable, np.float32)
    p, t = req.shape[0], alloc.shape[0]
    default = lambda arr, shape: (
        np.asarray(arr, bool) if arr is not None else np.zeros(shape, bool)
    )
    return B.BinPackInputs(
        pod_requests=jnp.asarray(req),
        pod_valid=jnp.asarray(
            np.ones(p, bool) if pod_valid is None else np.asarray(pod_valid, bool)
        ),
        pod_intolerant=jnp.asarray(default(pod_intolerant, (p, n_taints))),
        pod_required=jnp.asarray(default(pod_required, (p, n_labels))),
        group_allocatable=jnp.asarray(alloc),
        group_taints=jnp.asarray(default(group_taints, (t, n_taints))),
        group_labels=jnp.asarray(default(group_labels, (t, n_labels))),
    )


class TestFeasibilityAndAssignment:
    def test_resource_fit(self):
        # pod 0 fits both groups; pod 1 only the big group; pod 2 neither
        out = B.binpack(
            make_inputs(
                pod_requests=[[1, 1], [3, 1], [9, 9]],
                group_allocatable=[[2, 2], [4, 4]],
            )
        )
        assert out.assigned.tolist() == [0, 1, -1]
        assert int(out.unschedulable) == 1
        assert out.assigned_count.tolist() == [1, 1]

    def test_first_feasible_group_wins(self):
        """DESIGN.md: only a single node group scales up per pod."""
        out = B.binpack(
            make_inputs(
                pod_requests=[[1, 1]], group_allocatable=[[4, 4], [4, 4]]
            )
        )
        assert out.assigned.tolist() == [0]
        assert out.assigned_count.tolist() == [1, 0]
        assert out.nodes_needed.tolist()[1] == 0

    def test_taints_block_intolerant_pods(self):
        # group 0 carries taint 0; pod 0 doesn't tolerate it, pod 1 does
        out = B.binpack(
            make_inputs(
                pod_requests=[[1, 1], [1, 1]],
                group_allocatable=[[4, 4], [4, 4]],
                group_taints=[[True, False, False, False], [False] * 4],
                pod_intolerant=[
                    [True, False, False, False],
                    [False, False, False, False],
                ],
            )
        )
        assert out.assigned.tolist() == [1, 0]

    def test_node_selector_requires_group_label(self):
        # pod 0 requires label 2, only group 1 has it
        out = B.binpack(
            make_inputs(
                pod_requests=[[1, 1]],
                group_allocatable=[[4, 4], [4, 4]],
                group_labels=[[False] * 4, [False, False, True, False]],
                pod_required=[[False, False, True, False]],
            )
        )
        assert out.assigned.tolist() == [1]

    def test_invalid_pods_ignored(self):
        out = B.binpack(
            make_inputs(
                pod_requests=[[1, 1], [1, 1]],
                group_allocatable=[[4, 4]],
                pod_valid=[True, False],
            )
        )
        assert out.assigned_count.tolist() == [1]
        assert int(out.unschedulable) == 0  # padding rows don't count

    def test_empty_group_infeasible(self):
        out = B.binpack(
            make_inputs(pod_requests=[[1, 1]], group_allocatable=[[0, 0]])
        )
        assert out.assigned.tolist() == [-1]
        assert int(out.unschedulable) == 1


class TestPackingCounts:
    def test_simple_counts(self):
        # 6 pods of half a node each -> 3 nodes
        out = B.binpack(
            make_inputs(
                pod_requests=[[2, 2]] * 6, group_allocatable=[[4, 4]]
            )
        )
        assert out.nodes_needed.tolist() == [3]
        assert out.lp_bound.tolist() == [3]

    def test_whole_node_pods(self):
        out = B.binpack(
            make_inputs(pod_requests=[[4, 4]] * 5, group_allocatable=[[4, 4]])
        )
        assert out.nodes_needed.tolist() == [5]

    def test_mixed_sizes_shelf_packing(self):
        # two 3/4 pods + two 1/4 pods: 2 nodes (3/4+1/4 each)
        out = B.binpack(
            make_inputs(
                pod_requests=[[3, 1], [3, 1], [1, 1], [1, 1]],
                group_allocatable=[[4, 4]],
            )
        )
        assert out.nodes_needed.tolist() == [2]

    def test_dominant_resource_drives_size(self):
        # memory-dominant pod: cpu would allow 4/node but memory only 1/node
        out = B.binpack(
            make_inputs(
                pod_requests=[[1, 4]] * 3, group_allocatable=[[4, 4]]
            )
        )
        assert out.nodes_needed.tolist() == [3]

    def test_zero_pending_pods(self):
        out = B.binpack(
            make_inputs(
                pod_requests=[[1, 1]],
                group_allocatable=[[4, 4]],
                pod_valid=[False],
            )
        )
        assert out.nodes_needed.tolist() == [0]
        assert out.lp_bound.tolist() == [0]


class TestOracleExactness:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_kernel_matches_numpy_oracle(self, seed):
        rng = np.random.default_rng(seed)
        t, buckets = 7, 32
        histogram = rng.integers(0, 40, (t, buckets)).astype(np.int32)
        got = np.asarray(B._shelf_bfd(jnp.asarray(histogram), buckets))
        want = B.oracle_shelf_bfd(histogram, buckets)
        np.testing.assert_array_equal(got, want)

    def test_full_pipeline_against_oracle(self):
        rng = np.random.default_rng(7)
        p, t = 300, 5
        req = rng.uniform(0.1, 3.9, (p, 2)).astype(np.float32)
        alloc = np.asarray([[4, 4], [8, 8], [2, 4], [16, 8], [4, 16]], np.float32)
        out = B.binpack(make_inputs(req, alloc))

        # recompute membership + histogram on host, then oracle-pack
        feasible = np.all(req[:, None, :] <= alloc[None, :, :], axis=2)
        assigned = np.where(feasible.any(1), feasible.argmax(1), -1)
        buckets = B.DEFAULT_BUCKETS
        histogram = np.zeros((t, buckets), np.int32)
        for pi in range(p):
            ti = assigned[pi]
            if ti < 0:
                continue
            share = max(req[pi] / alloc[ti])
            b = min(buckets, max(1, int(np.ceil(share * buckets - 1e-6))))
            histogram[ti, b - 1] += 1
        np.testing.assert_array_equal(
            np.asarray(out.assigned), assigned.astype(np.int32)
        )
        np.testing.assert_array_equal(
            np.asarray(out.nodes_needed), B.oracle_shelf_bfd(histogram, buckets)
        )


class TestPackingQuality:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_lp_sandwich_and_ffd_proximity(self, seed):
        rng = np.random.default_rng(seed)
        p = 500
        sizes = rng.uniform(0.05, 1.0, p).astype(np.float32)
        req = np.stack([sizes * 4, sizes * 4], axis=1)
        out = B.binpack(make_inputs(req, [[4, 4]]))
        nodes = int(out.nodes_needed[0])
        lp = int(out.lp_bound[0])
        ffd = B.oracle_ffd(sizes)
        assert lp <= nodes
        # quantization (1/32 ceil) + shelf placement keep us near true FFD
        assert nodes <= ffd * 1.15 + 2, (nodes, ffd, lp)

    # size distributions spanning the regimes that stress bucketized
    # packing differently: quantization inflation (small), near-full nodes
    # (large), shelf reuse (bimodal/harmonic)
    DISTRIBUTIONS = {
        "uniform": lambda rng, p: rng.uniform(0.02, 1.0, p),
        "small": lambda rng, p: rng.uniform(0.01, 0.12, p),
        "large": lambda rng, p: rng.uniform(0.45, 0.95, p),
        "bimodal": lambda rng, p: np.where(
            rng.random(p) < 0.5,
            rng.uniform(0.05, 0.15, p),
            rng.uniform(0.55, 0.8, p),
        ),
        "harmonic": lambda rng, p: 1.0 / rng.integers(1, 20, p),
    }
    # empirical ratchet: grand-total nodes over every (distribution,
    # buckets, seed) case below, measured at the time this test was
    # written. 1% headroom absorbs float-rounding drift across jax
    # versions; a systematic packing-quality regression trips it.
    RATCHET_TOTAL = 22221

    def _fleet_cases(self):
        for buckets in (8, 16, 32, 64):
            for name, gen in self.DISTRIBUTIONS.items():
                for seed in range(6):
                    rng = np.random.default_rng(seed)
                    yield buckets, name, gen(rng, 400).astype(np.float32)

    def test_quality_bounds_over_randomized_fleets(self):
        """Pins the bucketized shelf-BFD's packing quality three ways:

        1. ANALYTIC soundness per fleet: lp <= nodes <= 2*ffd + 2*ceil(P/B)
           + 1. Derivation: any-fit packings never leave two bins that
           would fit together, so nodes <= 2*sum(q) + 1; quantizing up
           adds < 1/B per item, sum(q) <= sum(s) + P/B; and
           ffd >= sum(s). Never flaky, catches catastrophic regressions
           (e.g. one-item-per-bin placement).
        2. FIDELITY per fleet: nodes <= FFD run on the SAME quantized
           sizes — the device shelf algorithm (best-fit by remaining
           capacity) must never pack worse than canonical first-fit-
           decreasing at equal granularity. Holds with equality-or-better
           on every case today.
        3. RATCHET in aggregate: total nodes across all cases within 1%
           of the recorded measurement, so a broad quality drift fails CI
           even if each fleet stays under the loose analytic bound.
        """
        total = 0
        for buckets, name, sizes in self._fleet_cases():
            p = len(sizes)
            req = np.stack([sizes * 4, sizes * 4], axis=1)
            out = B.binpack(make_inputs(req, [[4, 4]]), buckets=buckets)
            nodes = int(out.nodes_needed[0])
            lp = int(out.lp_bound[0])
            ffd = B.oracle_ffd(sizes)
            label = (name, buckets, nodes, ffd, lp)
            assert lp <= nodes, label
            assert nodes <= 2 * ffd + 2 * int(np.ceil(p / buckets)) + 1, label
            quantized = (
                np.clip(
                    np.ceil(sizes.astype(np.float64) * buckets - 1e-6),
                    1,
                    buckets,
                )
                / buckets
            )
            assert nodes <= B.oracle_ffd(quantized), label
            total += nodes
        assert total <= int(self.RATCHET_TOTAL * 1.01), total

    def test_result_is_sufficient_capacity(self):
        """The count must be a VALID packing bound: verify by re-packing the
        true sizes into that many nodes greedily."""
        rng = np.random.default_rng(11)
        sizes = rng.uniform(0.05, 0.95, 200).astype(np.float32)
        req = np.stack([sizes * 4, sizes * 4], axis=1)
        out = B.binpack(make_inputs(req, [[4, 4]]))
        nodes = int(out.nodes_needed[0])
        bins = [1.0] * nodes
        for s in sorted(sizes, reverse=True):
            for i in range(len(bins)):
                if s <= bins[i] + 1e-6:
                    bins[i] -= s
                    break
            else:
                pytest.fail(f"{nodes} nodes insufficient for true sizes")


class TestDeviceResidencyCache:
    """solve() caches the device_put of the last inputs OBJECT (identity-
    keyed): a repeated tick over an unchanged fleet skips the host->device
    transfer. Fresh objects must always recompute."""

    def test_identity_hit_returns_equal_outputs(self):
        # backend="xla" explicitly: auto routes to numpy on the CPU test
        # mesh, which never touches the residency cache under test
        rng = np.random.default_rng(3)
        req = rng.uniform(0.1, 2.0, (40, 2)).astype(np.float32)
        inputs = make_inputs(req, [[4, 4], [8, 8]])
        first = B.solve(inputs, backend="xla")
        again = B.solve(inputs, backend="xla")  # identity hit: cached device arrays
        np.testing.assert_array_equal(
            np.asarray(first.assigned), np.asarray(again.assigned)
        )
        np.testing.assert_array_equal(
            np.asarray(first.nodes_needed), np.asarray(again.nodes_needed)
        )

    def test_fresh_object_recomputes(self):
        req = np.full((10, 2), 0.5, np.float32)
        small = make_inputs(req, [[1, 1]])
        out_small = B.solve(small, backend="xla")
        big = make_inputs(req, [[8, 8]])
        out_big = B.solve(big, backend="xla")  # different object: must not reuse cache
        assert int(out_small.nodes_needed[0]) > int(out_big.nodes_needed[0])


class TestWeightedDedup:
    """pod_weight semantics: solving W duplicate rows as one row with
    weight W must produce identical aggregates — the exactness claim the
    encoder's shape-dedup (_dedup_rows) rests on."""

    def _random_dup_inputs(self, rng, shapes=12, dup_max=40, types=6):
        base_req = rng.uniform(0.05, 4.0, (shapes, 2)).astype(np.float32)
        counts = rng.integers(1, dup_max, shapes)
        full_req = np.repeat(base_req, counts, axis=0)
        alloc = rng.uniform(4.0, 16.0, (types, 2)).astype(np.float32)
        intol_base = rng.random((shapes, 4)) < 0.3
        required_base = rng.random((shapes, 4)) < 0.2
        taints = rng.random((types, 4)) < 0.3
        labels = rng.random((types, 4)) < 0.7
        full = make_inputs(
            full_req, alloc,
            pod_intolerant=np.repeat(intol_base, counts, axis=0),
            pod_required=np.repeat(required_base, counts, axis=0),
            group_taints=taints, group_labels=labels,
        )
        dedup = make_inputs(
            base_req, alloc,
            pod_intolerant=intol_base, pod_required=required_base,
            group_taints=taints, group_labels=labels,
        )
        dedup = dataclasses.replace(
            dedup, pod_weight=jnp.asarray(counts.astype(np.int32))
        )
        return full, dedup, counts

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_weighted_equals_expanded(self, seed):
        rng = np.random.default_rng(seed)
        full, dedup, counts = self._random_dup_inputs(rng)
        a = B.binpack(full, buckets=16)
        b = B.binpack(dedup, buckets=16)
        np.testing.assert_array_equal(
            np.asarray(a.assigned_count), np.asarray(b.assigned_count)
        )
        np.testing.assert_array_equal(
            np.asarray(a.nodes_needed), np.asarray(b.nodes_needed)
        )
        np.testing.assert_array_equal(
            np.asarray(a.lp_bound), np.asarray(b.lp_bound)
        )
        assert int(a.unschedulable) == int(b.unschedulable)
        assert int(np.sum(np.asarray(b.assigned_count))) + int(
            b.unschedulable
        ) == int(np.sum(counts))

    def test_zero_weight_rows_are_inert(self):
        req = np.full((4, 2), 0.5, np.float32)
        inputs = B.BinPackInputs(
            pod_requests=jnp.asarray(req),
            pod_valid=jnp.ones(4, bool),
            pod_intolerant=jnp.zeros((4, 4), bool),
            pod_required=jnp.zeros((4, 4), bool),
            group_allocatable=jnp.asarray([[4.0, 4.0]], np.float32),
            group_taints=jnp.zeros((1, 4), bool),
            group_labels=jnp.zeros((1, 4), bool),
            pod_weight=jnp.asarray([3, 0, 0, 5], np.int32),
        )
        out = B.binpack(inputs, buckets=8)
        assert out.assigned_count.tolist() == [8]
        assert out.nodes_needed.tolist() == [1]


def build_inputs(pods: int, types: int, taints: int, labels: int, seed: int):
    """Random pods and instance types: resource requests, taints and
    required labels at fixed densities."""
    rng = np.random.default_rng(seed)
    # pod requests: cpu in cores, memory in GiB, 1 pod slot
    req = np.stack(
        [
            rng.uniform(0.05, 8.0, pods),
            rng.uniform(0.1, 32.0, pods),
            np.ones(pods),
        ],
        axis=1,
    ).astype(np.float32)
    # instance types: cpu 2..128 cores, proportional memory, 110 pod slots
    cpu = rng.choice([2, 4, 8, 16, 32, 64, 96, 128], types).astype(np.float32)
    mem = cpu * rng.choice([2.0, 4.0, 8.0], types).astype(np.float32)
    alloc = np.stack([cpu, mem, np.full(types, 110.0, np.float32)], axis=1)
    intol = rng.random((pods, taints)) < 0.05
    group_taints = rng.random((types, taints)) < 0.1
    required = rng.random((pods, labels)) < 0.03
    group_labels = rng.random((types, labels)) < 0.8
    return B.BinPackInputs(
        pod_requests=jnp.asarray(req),
        pod_valid=jnp.ones((pods,), bool),
        pod_intolerant=jnp.asarray(intol),
        pod_required=jnp.asarray(required),
        group_allocatable=jnp.asarray(alloc),
        group_taints=jnp.asarray(group_taints),
        group_labels=jnp.asarray(group_labels),
    )


def build_multicluster_inputs(
    pods: int, clusters: int, types_per_cluster: int,
    taints: int, labels: int, seed: int, flex_fraction: float = 0.3,
):
    """BASELINE.json config 5: spot-interruption re-pack across clusters.

    K clusters each contribute types_per_cluster node groups carrying a
    cluster-identity label (first K slots of the label universe). Pods are
    spot-interruption refugees: 70% must stay in their home cluster
    (required cluster label — the nodeSelector a real multi-cluster
    scheduler would stamp), 30% are flexible and may re-pack anywhere.
    Same solver, same encoding — the cluster boundary IS a label
    constraint, so multi-cluster costs nothing extra on device.
    """
    types = clusters * types_per_cluster
    base = build_inputs(pods, types, taints, labels, seed)
    rng = np.random.default_rng(seed + 1)

    group_labels = np.asarray(base.group_labels).copy()
    group_labels[:, :clusters] = False
    for c in range(clusters):
        group_labels[
            c * types_per_cluster : (c + 1) * types_per_cluster, c
        ] = True

    pod_required = np.asarray(base.pod_required).copy()
    pod_required[:, :clusters] = False
    home = rng.integers(0, clusters, pods)
    pinned = rng.random(pods) >= flex_fraction
    pod_required[np.arange(pods)[pinned], home[pinned]] = True

    return dataclasses.replace(
        base,
        group_labels=jnp.asarray(group_labels),
        pod_required=jnp.asarray(pod_required),
    )


class TestMultiClusterRepack:
    def test_pinned_pods_stay_home_flexible_cross(self):
        """BASELINE config 5: the cluster boundary is a required-label
        constraint — pinned pods must land on their home cluster's
        groups; flexible pods may re-pack anywhere."""
        pods, clusters, tpc = 600, 4, 5
        inputs = build_multicluster_inputs(
            pods, clusters, tpc, taints=8, labels=12, seed=3
        )
        out = B.binpack(inputs, buckets=16)
        assigned = np.asarray(out.assigned)
        required = np.asarray(inputs.pod_required)
        crossed = 0
        for p in range(pods):
            t = int(assigned[p])
            if t < 0:
                continue
            cluster_of_group = t // tpc
            pinned_to = np.flatnonzero(required[p, :clusters])
            if len(pinned_to):
                assert cluster_of_group == int(pinned_to[0]), (
                    p, t, pinned_to
                )
            elif cluster_of_group != 0:
                crossed += 1
        assert crossed > 0  # flexible pods actually used other clusters
