"""The one-chip Mosaic path's pad into reused host staging areas
(solver/bucketing.PadStaging, SolverService._staged_pad): a staged pad
equals a fresh one element for element whatever came before it in the
area, the service's answers on that path equal direct ops/binpack calls,
the counters, who owns the areas, and the identity memo."""

import dataclasses
import threading

import numpy as np
import pytest

from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.observability import (
    default_tracer,
    reset_default_tracer,
    set_default_tracer,
)
from karpenter_tpu.ops import binpack as B
from karpenter_tpu.solver import SolverService
from karpenter_tpu.solver.bucketing import (
    PadStaging,
    bucket_shape,
    pad_to_bucket,
)


@pytest.fixture
def fresh_tracer():
    saved = default_tracer()
    yield reset_default_tracer()
    set_default_tracer(saved)


def make_inputs(pods, groups, taints=16, labels=16, seed=0, optional=False):
    """Integer-valued requests, so every float reduction of the solve is
    exact and equality is bitwise."""
    rng = np.random.default_rng(seed)
    requests = np.stack(
        [rng.integers(1, 8, pods), rng.integers(1, 32, pods), np.ones(pods)],
        axis=1,
    ).astype(np.float32)
    alloc = np.stack(
        [
            rng.choice([8, 16, 32, 64], groups),
            rng.choice([32, 64, 128], groups),
            np.full(groups, 110.0),
        ],
        axis=1,
    ).astype(np.float32)
    extra = {}
    if optional:
        extra = dict(
            pod_weight=rng.integers(1, 5, pods).astype(np.int32),
            pod_group_score=rng.integers(0, 3, (pods, groups)).astype(
                np.float32
            ),
            pod_exclusive=rng.random(pods) < 0.1,
        )
    return B.BinPackInputs(
        pod_requests=requests,
        pod_valid=np.ones(pods, bool),
        pod_intolerant=rng.random((pods, taints)) < 0.05,
        pod_required=rng.random((pods, labels)) < 0.03,
        group_allocatable=alloc,
        group_taints=rng.random((groups, taints)) < 0.1,
        group_labels=rng.random((groups, labels)) < 0.8,
        **extra,
    )


def assert_same_operands(got, want):
    for field in dataclasses.fields(B.BinPackInputs):
        a, b = getattr(got, field.name), getattr(want, field.name)
        if b is None:
            assert a is None, field.name
            continue
        assert a.dtype == b.dtype and a.shape == b.shape, field.name
        np.testing.assert_array_equal(a, b, err_msg=field.name)


# (pods, groups, taints, labels) in turn, every step in the first's bucket
SEQUENCES = {
    "fewer_pods": [(380, 20, 40, 70), (260, 20, 40, 70), (380, 20, 40, 70)],
    "narrower_taints_and_labels": [
        (300, 20, 48, 96), (300, 20, 33, 65), (300, 20, 40, 80),
    ],
    "fewer_groups": [(300, 24, 40, 70), (300, 17, 40, 70), (300, 20, 40, 70)],
    "every_axis_shrinks_then_grows": [
        (380, 24, 48, 96), (257, 17, 33, 65), (380, 24, 48, 96),
        (300, 17, 40, 70),
    ],
}


@pytest.mark.parametrize("optional", [False, True], ids=["plain", "weight_score_exclusive"])
@pytest.mark.parametrize("sequence", sorted(SEQUENCES))
def test_staged_pad_equals_fresh_pad(sequence, optional):
    steps = SEQUENCES[sequence]
    area = PadStaging()
    shape = bucket_shape(make_inputs(*steps[0]))
    previous = None
    for seed, sizes in enumerate(steps):
        inputs = make_inputs(*sizes, seed=seed, optional=optional)
        assert bucket_shape(inputs) == shape
        staged = pad_to_bucket(inputs, shape, out=area)
        assert_same_operands(staged, pad_to_bucket(inputs, shape))
        # a new wrapper on every call, over the same reused buffers
        if previous is not None:
            assert staged is not previous
            assert staged.pod_requests is previous.pod_requests
        previous = staged


def test_bucketed_inputs_pass_through_the_area():
    inputs = make_inputs(300, 20)
    shape = bucket_shape(inputs)
    bucketed = pad_to_bucket(inputs, shape)
    area = PadStaging()
    assert pad_to_bucket(bucketed, shape, out=area) is bucketed
    assert not area._buffers


def _pallas_service(**kwargs):
    return SolverService(registry=GaugeRegistry(), backend="pallas", **kwargs)


class TestServicePath:
    """SolverService on the Mosaic path, in interpret mode on the CPU."""

    def test_alternating_sizes_equal_direct_calls(self):
        service = _pallas_service()
        try:
            for seed, pods in enumerate((300, 260, 380, 270)):
                inputs = make_inputs(pods, 20 - seed, seed=seed)
                got = service.submit(inputs, buckets=8).result()
                want = B.solve(inputs, buckets=8, backend="xla")
                for name in ("assigned", "assigned_count", "nodes_needed",
                             "lp_bound", "unschedulable"):
                    np.testing.assert_array_equal(
                        np.asarray(getattr(got, name)),
                        np.asarray(getattr(want, name)),
                        err_msg=name,
                    )
            assert service.stats.pad_staging_allocs == 1
        finally:
            service.close()

    def test_one_shape_reuses_after_the_first_call(self):
        service = _pallas_service()
        n = 4
        try:
            for seed in range(n):
                service.submit(
                    make_inputs(300 - seed, 20, seed=seed), buckets=8
                ).result()
            assert service.stats.pad_staging_reuses == n - 1
            assert service.stats.pad_staging_allocs == 1
            counter = service.registry.gauge("solver", "pad_staging_total")
            assert counter.get("reuse", "-") == n - 1
            assert counter.get("alloc", "-") == 1
        finally:
            service.close()

    def test_allocating_an_area_keeps_freed_heap(self, monkeypatch):
        from karpenter_tpu.solver import service as S

        kept = []
        monkeypatch.setattr(S, "_keep_freed_heap", lambda: kept.append(1))
        service = _pallas_service()
        try:
            for seed in range(3):
                service.submit(
                    make_inputs(300, 20, seed=seed), buckets=8
                ).result()
        finally:
            service.close()
        assert kept == [1]  # the one allocation, no reuse
        S._keep_freed_heap()  # and the real one runs in this process

    def test_pad_span_says_whether_it_reused(self, fresh_tracer):
        service = _pallas_service()
        try:
            for seed in range(2):
                service.submit(
                    make_inputs(300, 20, seed=seed), buckets=8
                ).result()
        finally:
            service.close()
        staged = [
            s["args"].get("staged") for s in fresh_tracer.snapshot()
            if s["name"] == "solver.pad"
        ]
        assert staged == ["alloc", "reuse"]

    def test_operand_bytes_count_the_padded_operands(self, fresh_tracer):
        """stats.operand_bytes, solve_operand_bytes_total and the pad
        span's operand_bytes count the padded mask, score and exclusive
        flags of each call, and stay at 0 for a bare solve."""
        service = _pallas_service()
        with_operands = make_inputs(300, 20, seed=1, optional=True)
        with_operands = dataclasses.replace(
            with_operands,
            pod_group_forbidden=np.random.default_rng(1).random((300, 20)) < 0.3,
        )
        try:
            service.submit(make_inputs(300, 20), buckets=8).result()
            assert service.stats.operand_bytes == {}
            for _ in range(2):
                service.submit(with_operands, buckets=8).result()
        finally:
            service.close()
        pods, groups = bucket_shape(with_operands)[:2]
        assert (pods, groups) == (384, 24)
        per_call = {"forbidden": pods * groups, "score": 4 * pods * groups,
                    "exclusive": pods}
        assert service.stats.operand_bytes == {k: 2 * v for k, v in per_call.items()}
        counter = service.registry.gauge("solver", "solve_operand_bytes_total")
        for name, size in per_call.items():
            assert counter.get(name, "-") == 2 * size
        spans = [
            s["args"].get("operand_bytes") for s in fresh_tracer.snapshot()
            if s["name"] == "solver.pad"
        ]
        assert spans == [0, sum(per_call.values()), sum(per_call.values())]

    def test_areas_are_bounded_least_recent_first_out(self):
        from karpenter_tpu.solver.service import _STAGING_AREAS

        service = _pallas_service()
        # one bucket more than the areas kept, then the first again: it
        # was the least recently used, so it was dropped
        pods = [256 * 2 ** i + 1 for i in range(_STAGING_AREAS + 1)]
        staged = []

        def worker():  # pads as the worker that owns the areas
            service._tls.gen = service._staging[0]
            for p in pods + pods[:1] + pods[-1:]:
                inputs = make_inputs(p, 20)
                staged.append(
                    service._staged_pad(inputs, bucket_shape(inputs))[1]
                )

        try:
            thread = threading.Thread(target=worker)
            thread.start()
            thread.join()
            assert staged == ["alloc"] * (len(pods) + 1) + ["reuse"]
            assert len(service._staging[1]) == _STAGING_AREAS
        finally:
            service.close()


class TestOwnership:
    @staticmethod
    def _solve(service, seed=0):
        service.submit(make_inputs(300, 20, seed=seed), buckets=8).result()

    def test_reset_caches_drops_the_areas(self):
        service = _pallas_service()
        try:
            self._solve(service)
            assert len(service._staging[1]) == 1
            service.reset_caches()
            assert len(service._staging[1]) == 0
            self._solve(service, seed=1)
            assert service.stats.pad_staging_allocs == 2
            assert service.stats.pad_staging_reuses == 0
        finally:
            service.close()

    def test_watchdog_supersede_drops_the_areas(self):
        service = _pallas_service()
        try:
            self._solve(service)
            owner, areas = service._staging
            assert len(areas) == 1
            with service._cond:
                service._busy_since = service._clock() - 1.0
            service._watchdog_check()
            assert service.stats.watchdog_restarts == 1
            new_owner, new_areas = service._staging
            assert new_owner == owner + 1 and not new_areas
            # whichever worker serves it, nothing pads into the old area
            self._solve(service, seed=1)
            assert service.stats.pad_staging_reuses == 0
            assert service._staging[1] is new_areas
        finally:
            service.close()

    def test_superseded_worker_pads_into_fresh_arrays(self):
        service = _pallas_service()
        try:
            self._solve(service)
            inputs = make_inputs(290, 20, seed=3)
            shape = bucket_shape(inputs)
            result = {}

            def stale_worker():
                service._tls.gen = service._staging[0] - 1
                result["pad"] = service._staged_pad(inputs, shape)

            thread = threading.Thread(target=stale_worker)
            thread.start()
            thread.join()
            padded, staged = result["pad"]
            assert staged is None
            assert_same_operands(padded, pad_to_bucket(inputs, shape))
            assert service.stats.pad_staging_reuses == 0
        finally:
            service.close()


def test_staged_requests_never_hit_the_identity_memo(monkeypatch):
    held = []
    resident = B.device_resident

    def recording(inputs, put=None):
        operands, hit = resident(inputs, put)
        held.append(hit)
        return operands, hit

    monkeypatch.setattr(B, "device_resident", recording)
    service = _pallas_service()
    try:
        for seed in range(3):
            service.submit(
                make_inputs(300, 20, seed=seed), buckets=8
            ).result()
        assert service.stats.pad_staging_reuses == 2
    finally:
        service.close()
    assert held == [False, False, False]
