"""Pallas fused bin-pack kernel == XLA reference path, element for element.

The Pallas kernel (ops/pallas_binpack.py) runs compiled Mosaic on TPU; on
the CPU test mesh it runs in interpreter mode, which executes the same
kernel logic (tiling, grid accumulation, padding) without the TPU compiler.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from karpenter_tpu.ops import binpack as B
from karpenter_tpu.ops import pallas_binpack as PB

from test_binpack import make_inputs


def random_inputs(rng, pods, types, taints=8, labels=8, n_resources=3):
    req = rng.uniform(0.05, 8.0, (pods, n_resources)).astype(np.float32)
    alloc = rng.uniform(1.0, 64.0, (types, n_resources)).astype(np.float32)
    # a few empty groups exercise the zero-allocatable rule
    empty = rng.random(types) < 0.1
    alloc[empty] = 0.0
    return B.BinPackInputs(
        pod_requests=jnp.asarray(req),
        pod_valid=jnp.asarray(rng.random(pods) > 0.05),
        pod_intolerant=jnp.asarray(rng.random((pods, taints)) < 0.1),
        pod_required=jnp.asarray(rng.random((pods, labels)) < 0.05),
        group_allocatable=jnp.asarray(alloc),
        group_taints=jnp.asarray(rng.random((types, taints)) < 0.15),
        group_labels=jnp.asarray(rng.random((types, labels)) < 0.8),
    )


def assert_outputs_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.assigned), np.asarray(b.assigned))
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


class TestPallasMatchesXLA:
    @pytest.mark.parametrize("seed", range(5))
    def test_random_parity(self, seed):
        rng = np.random.default_rng(seed)
        inputs = random_inputs(rng, pods=203, types=37)
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=64, interpret=True
        )
        assert_outputs_equal(xla, pallas)

    def test_padding_invisible(self):
        """P not a multiple of tile_p, T/K/L far from the 128 lane."""
        rng = np.random.default_rng(99)
        inputs = random_inputs(rng, pods=65, types=5, taints=3, labels=2)
        xla = B.binpack(inputs, buckets=8)
        pallas = PB.binpack_pallas(inputs, buckets=8, tile_p=64, interpret=True)
        assert_outputs_equal(xla, pallas)

    @pytest.mark.parametrize("seed", range(3))
    def test_score_parity(self, seed):
        """pod_group_score (preferred node affinity) steers assignment
        identically in both backends: max score among feasible, lowest
        index tie-break."""
        import dataclasses

        rng = np.random.default_rng(200 + seed)
        inputs = dataclasses.replace(
            random_inputs(rng, pods=203, types=37),
            pod_group_score=jnp.asarray(
                rng.integers(0, 100, (203, 37)).astype(np.float32)
            ),
            pod_weight=jnp.asarray(
                rng.integers(1, 2000, 203).astype(np.int32)
            ),
        )
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=64, interpret=True
        )
        assert_outputs_equal(xla, pallas)
        # scoring changed the assignment vs first-feasible
        free = B.binpack(
            dataclasses.replace(inputs, pod_group_score=None), buckets=16
        )
        assert not np.array_equal(
            np.asarray(free.assigned), np.asarray(xla.assigned)
        )
        # and never assigned an infeasible group: pod counts conserved
        # over the VALID rows (invalid rows never enter any aggregate)
        total = int(np.sum(np.asarray(xla.assigned_count))) + int(
            xla.unschedulable
        )
        valid = np.asarray(inputs.pod_valid)
        assert total == int(np.sum(np.asarray(inputs.pod_weight)[valid]))

    def test_score_tiebreak_is_lowest_index(self):
        """Uniform scores must reproduce first-feasible exactly."""
        import dataclasses

        rng = np.random.default_rng(42)
        base = random_inputs(rng, pods=90, types=11)
        uniform = dataclasses.replace(
            base,
            pod_group_score=jnp.full((90, 11), 7.0, jnp.float32),
        )
        assert_outputs_equal(
            B.binpack(base, buckets=8), B.binpack(uniform, buckets=8)
        )
        assert_outputs_equal(
            B.binpack(base, buckets=8),
            PB.binpack_pallas(uniform, buckets=8, tile_p=64, interpret=True),
        )

    @pytest.mark.parametrize("seed", range(3))
    def test_forbidden_parity(self, seed):
        """pod_group_forbidden (required node affinity, host-evaluated)
        masks feasibility identically in both backends, weighted rows
        included, and the constraint is actually enforced."""
        import dataclasses

        rng = np.random.default_rng(100 + seed)
        base = random_inputs(rng, pods=203, types=37)
        inputs = dataclasses.replace(
            base,
            pod_group_forbidden=jnp.asarray(rng.random((203, 37)) < 0.4),
            pod_weight=jnp.asarray(
                rng.integers(1, 2000, 203).astype(np.int32)
            ),
        )
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=64, interpret=True
        )
        assert_outputs_equal(xla, pallas)
        assigned = np.asarray(xla.assigned)
        forbidden = np.asarray(inputs.pod_group_forbidden)
        rows = np.arange(len(assigned))[assigned >= 0]
        assert not forbidden[rows, assigned[assigned >= 0]].any()
        # and the mask changes the outcome vs the unconstrained solve
        free = B.binpack(
            dataclasses.replace(inputs, pod_group_forbidden=None), buckets=16
        )
        assert not np.array_equal(np.asarray(free.assigned), assigned)

    @pytest.mark.parametrize("seed", range(3))
    def test_exclusive_parity(self, seed):
        """pod_exclusive (hostname self-anti-affinity: a pod takes a
        whole node) forces bucket=B identically in both backends, and a
        group's node count always covers its exclusive weight."""
        import dataclasses

        rng = np.random.default_rng(300 + seed)
        inputs = dataclasses.replace(
            random_inputs(rng, pods=203, types=37),
            pod_exclusive=jnp.asarray(rng.random(203) < 0.3),
            pod_weight=jnp.asarray(rng.integers(1, 40, 203).astype(np.int32)),
        )
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=64, interpret=True
        )
        assert_outputs_equal(xla, pallas)
        assigned = np.asarray(xla.assigned)
        excl = np.asarray(inputs.pod_exclusive)
        w = np.asarray(inputs.pod_weight)
        for t in range(37):
            assert int(xla.nodes_needed[t]) >= int(
                w[(assigned == t) & excl].sum()
            )
        # the flag changes packing (same assignment, more nodes) on at
        # least one group vs the unconstrained solve
        free = B.binpack(
            dataclasses.replace(inputs, pod_exclusive=None), buckets=16
        )
        np.testing.assert_array_equal(
            np.asarray(free.assigned), assigned
        )  # feasibility/assignment untouched
        assert (
            np.asarray(xla.nodes_needed) >= np.asarray(free.nodes_needed)
        ).all()
        # and the flag is not a silent no-op: 30% exclusive of 203
        # weighted rows must strictly raise some group's node count
        assert (
            np.asarray(xla.nodes_needed) > np.asarray(free.nodes_needed)
        ).any()

    @pytest.mark.parametrize("seed", range(3))
    def test_all_operands_through_the_service_match_the_reference(self, seed):
        """forbidden, score and exclusive together, on weighted rows at a
        shape the service pads (203 x 37 to its 256 x 48 bucket), through
        SolverService.submit on the Mosaic path, equal the benchmark's
        plain reference (benchmark/reference/binpack.py); each operand
        moves the answer."""
        import dataclasses

        from benchmark.reference import binpack as ref
        from karpenter_tpu.metrics.registry import GaugeRegistry
        from karpenter_tpu.solver import SolverService

        rng = np.random.default_rng(400 + seed)
        pods, types = 203, 37
        base = random_inputs(rng, pods, types)
        operands = dict(
            forbidden=rng.random((pods, types)) < 0.3,
            score=(rng.integers(0, 3, (pods, types)) * 40).astype(np.float32),
            exclusive=rng.random(pods) < 0.2,
        )
        weight = rng.integers(1, 50, pods).astype(np.int32)
        inputs = dataclasses.replace(
            base,
            pod_group_forbidden=operands["forbidden"],
            pod_group_score=operands["score"],
            pod_exclusive=operands["exclusive"],
            pod_weight=weight,
        )
        service = SolverService(registry=GaugeRegistry(), backend="pallas")
        try:
            got = service.submit(inputs, buckets=16).result()
            assert service.stats.served == {"solve/pallas": 1}
            assert service.stats.constraint_reroutes == 0
        finally:
            service.close()
        plain = dict(
            requests=np.asarray(base.pod_requests),
            valid=np.asarray(base.pod_valid),
            intolerant=np.asarray(base.pod_intolerant),
            required=np.asarray(base.pod_required),
            alloc=np.asarray(base.group_allocatable),
            taints=np.asarray(base.group_taints),
            labels=np.asarray(base.group_labels),
            weight=weight,
            buckets=16,
        )
        diff = ref.compare(got, ref.binpack(**plain, **operands))
        assert diff["mismatches"] == 0 and diff["lp_off"] <= 1, diff
        for name in operands:
            without = {k: v for k, v in operands.items() if k != name}
            assert ref.compare(got, ref.binpack(**plain, **without))["mismatches"] > 0, name

    def test_semantics_taints_and_labels(self):
        # group 0 tainted (pod 0 intolerant); group 1 lacks pod 1's label
        inputs = make_inputs(
            pod_requests=[[1, 1], [1, 1]],
            group_allocatable=[[4, 4], [4, 4]],
            pod_intolerant=[[True, False], [False, False]],
            group_taints=[[True, False], [False, False]],
            pod_required=[[False, False], [False, True]],
            group_labels=[[True, True], [True, False]],
            n_taints=2,
            n_labels=2,
        )
        out = PB.binpack_pallas(inputs, buckets=8, tile_p=8, interpret=True)
        assert out.assigned.tolist() == [1, 0]

    def test_all_unschedulable(self):
        inputs = make_inputs(
            pod_requests=[[9, 9]], group_allocatable=[[1, 1]]
        )
        out = PB.binpack_pallas(inputs, buckets=8, tile_p=8, interpret=True)
        assert out.assigned.tolist() == [-1]
        assert int(out.unschedulable) == 1
        assert out.nodes_needed.tolist() == [0]

    def test_fused_stage_outputs(self):
        """Histogram and demand from the kernel match a NumPy recomputation."""
        rng = np.random.default_rng(7)
        inputs = random_inputs(rng, pods=130, types=9)
        buckets = 12
        assigned, hist, demand = PB.fused_assign(
            inputs, buckets=buckets, tile_p=64, interpret=True
        )
        assigned = np.asarray(assigned)
        req = np.asarray(inputs.pod_requests)
        alloc = np.asarray(inputs.group_allocatable)
        want_hist = np.zeros((alloc.shape[0], buckets), np.int64)
        want_demand = np.zeros_like(alloc, dtype=np.float64)
        for p, t in enumerate(assigned):
            if t < 0:
                continue
            shares = [
                (req[p, r] / alloc[t, r]) if alloc[t, r] > 0 else
                (0.0 if req[p, r] <= 0 else np.inf)
                for r in range(req.shape[1])
            ]
            b = int(np.clip(np.ceil(max(shares) * buckets), 1, buckets))
            want_hist[t, b - 1] += 1
            want_demand[t] += req[p]
        np.testing.assert_array_equal(np.asarray(hist), want_hist)
        np.testing.assert_allclose(
            np.asarray(demand), want_demand, rtol=1e-5, atol=1e-4
        )


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs a real TPU: exercises the compiled Mosaic path "
    "(interpret=False); the CPU suite covers the same kernel logic in "
    "interpreter mode",
)
class TestCompiledMosaic:
    """The Pallas kernel compiled by Mosaic on the chip, not only
    interpreted, equals the XLA program. Run on a TPU host with
    `KARPENTER_TEST_REAL_BACKEND=1 python -m pytest
    tests/test_pallas_binpack.py -k Compiled` (the CPU-forced suite skips
    this; tests/test_tpu_compile.py compiles the kernel without a chip)."""

    def test_compiled_equals_xla_on_tpu(self):
        rng = np.random.default_rng(5)
        inputs = random_inputs(rng, pods=512, types=24)
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=128, interpret=False
        )
        assert_outputs_equal(xla, pallas)

    def test_compiled_weighted_equals_xla_on_tpu(self):
        """The encoder always emits pod_weight now, so the WEIGHTED path
        is the production Mosaic path — pin it compiled too.

        Weights are drawn from [1000, 5000): past bf16's 8-bit mantissa,
        so this FAILS if the hist/demand accumulators drop to the MXU's
        default bf16 operand rounding (small weights would round
        losslessly and mask it) — production dedup multiplicities at
        bench scale are ~4000/row."""
        import dataclasses

        rng = np.random.default_rng(6)
        weighted = dataclasses.replace(
            random_inputs(rng, pods=512, types=24),
            pod_weight=jnp.asarray(
                rng.integers(1000, 5000, 512).astype(np.int32)
            ),
        )
        xla = B.binpack(weighted, buckets=16)
        pallas = PB.binpack_pallas(
            weighted, buckets=16, tile_p=128, interpret=False
        )
        assert_outputs_equal(xla, pallas)

    def test_compiled_score_equals_xla_on_tpu(self):
        """The preference-score operand compiles through Mosaic and
        matches XLA on hardware (max-score + min-index selection)."""
        import dataclasses

        rng = np.random.default_rng(9)
        inputs = dataclasses.replace(
            random_inputs(rng, pods=512, types=24),
            pod_group_score=jnp.asarray(
                rng.integers(0, 100, (512, 24)).astype(np.float32)
            ),
            pod_weight=jnp.asarray(
                rng.integers(1000, 5000, 512).astype(np.int32)
            ),
        )
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=128, interpret=False
        )
        assert_outputs_equal(xla, pallas)

    def test_compiled_forbidden_equals_xla_on_tpu(self):
        """The affinity mask input compiles through Mosaic (one more
        [TILE_P, T] VMEM operand) and matches XLA on hardware."""
        import dataclasses

        rng = np.random.default_rng(8)
        inputs = dataclasses.replace(
            random_inputs(rng, pods=512, types=24),
            pod_group_forbidden=jnp.asarray(rng.random((512, 24)) < 0.3),
            pod_weight=jnp.asarray(
                rng.integers(1000, 5000, 512).astype(np.int32)
            ),
        )
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=128, interpret=False
        )
        assert_outputs_equal(xla, pallas)

    def test_compiled_exclusive_equals_xla_on_tpu(self):
        """The hostname self-anti-affinity flag compiles through Mosaic
        (one [TILE_P, 1] VMEM operand) and matches XLA on hardware."""
        import dataclasses

        rng = np.random.default_rng(10)
        inputs = dataclasses.replace(
            random_inputs(rng, pods=512, types=24),
            pod_exclusive=jnp.asarray(rng.random(512) < 0.3),
            pod_weight=jnp.asarray(
                rng.integers(1000, 5000, 512).astype(np.int32)
            ),
        )
        xla = B.binpack(inputs, buckets=16)
        pallas = PB.binpack_pallas(
            inputs, buckets=16, tile_p=128, interpret=False
        )
        assert_outputs_equal(xla, pallas)


class TestWeightedPallas:
    def test_weighted_matches_xla(self):
        import dataclasses

        rng = np.random.default_rng(9)
        inputs = random_inputs(rng, pods=90, types=7)
        weighted = dataclasses.replace(
            inputs,
            pod_weight=jnp.asarray(rng.integers(0, 9, 90).astype(np.int32)),
        )
        xla = B.binpack(weighted, buckets=12)
        pallas = PB.binpack_pallas(
            weighted, buckets=12, tile_p=64, interpret=True
        )
        assert_outputs_equal(xla, pallas)
