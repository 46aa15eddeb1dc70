"""The envelope-100k.solve-operands cell on the CPU at a small size: its
stream of operands, its work count, its readers, and the faults its
check must catch.

- The stream replays from the seed, its operand shares land within a
  few points of the traffic's, a redraw touches only the redrawn rows,
  and every call's inputs are fresh arrays.
- benchmark/workcount_operands.py is workcount.py's count with no
  operand present, and adds each operand's bytes and operations.
- operand_mb and binpack_roofline of the cell on hand-made windows.
- Dropping the mask, the score or the exclusive flags on the way into
  the service makes a tiny run of the cell not correct.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import run_cell, tiny_root
from benchmark import harness, workcount, workcount_operands
from benchmark.drivers import solver_submit_operands as O

CELL = "envelope-100k.solve-operands"


def _config_and_traffic(pods=20_000, groups=300):
    config = json.loads((harness.ROOT / "benchmark/configs/envelope-100k-affinity.json").read_text())
    config.update(pending_pods=pods, node_groups=groups)
    traffic = json.loads((harness.ROOT / "benchmark/traffic/solve-operands.json").read_text())
    return config, traffic


def _stream(seed, calls=0, **sizes):
    config, traffic = _config_and_traffic(**sizes)
    stream = O.Stream(config, traffic, seed)
    for _ in range(calls):
        stream.advance()
    return stream


def test_stream_replays_from_the_seed():
    a, b = _stream(3_000_000_041, 3, pods=2_000), _stream(3_000_000_041, 3, pods=2_000)
    for name, value in a.operands().items():
        np.testing.assert_array_equal(value, b.operands()[name], err_msg=name)
    other = _stream(3_000_000_043, 3, pods=2_000)
    assert not np.array_equal(a.forbidden, other.forbidden)


def test_operand_shares_match_the_traffic():
    stream = _stream(2_147_490_011)
    _, traffic = _config_and_traffic()
    assert stream.forbidden.any(axis=1).mean() == pytest.approx(traffic["affinity_share"], abs=0.02)
    assert stream.prototypes.mean() == pytest.approx(traffic["prototype_forbid_p"], abs=0.03)
    # each required-affinity row is one of the prototype rows
    rows = stream.forbidden[stream.forbidden.any(axis=1)]
    assert (rows[:, None, :] == stream.prototypes[None]).all(axis=2).any(axis=1).all()
    preferred = stream.score.any(axis=1)
    assert preferred.mean() == pytest.approx(traffic["preferred_share"], abs=0.02)
    ssd = np.arange(stream.groups) % 2 == 1
    assert (stream.score[preferred][:, ssd] == traffic["preferred_weight"]).all()
    assert not stream.score[:, ~ssd].any()
    assert stream.exclusive.mean() == pytest.approx(traffic["exclusive_share"], abs=0.01)


def test_a_redraw_redraws_every_operand_of_its_rows_only():
    stream = _stream(2_147_490_013, pods=5_000)
    before = {k: np.copy(v) for k, v in stream.operands().items()}
    stream.advance()
    after = stream.operands()
    redrawn = (before["requests"] != after["requests"]).any(axis=1)
    assert redrawn.sum() == stream.redraw
    changed = ((before["forbidden"] != after["forbidden"]).any(axis=1)
               | (before["score"] != after["score"]).any(axis=1)
               | (before["exclusive"] != after["exclusive"]))
    assert changed.any() and not (changed & ~redrawn).any()


def test_inputs_are_fresh_arrays_every_call():
    stream = _stream(5, pods=1_000, groups=40)
    first, second = stream.inputs(), stream.inputs()
    for field in ("pod_group_forbidden", "pod_group_score", "pod_exclusive", "pod_requests"):
        a, b = getattr(first, field), getattr(second, field)
        assert a is not b and not np.shares_memory(a, b), field
        np.testing.assert_array_equal(a, b)
    assert not np.shares_memory(first.pod_group_score, stream.score)


SHAPE = {"pods": 100_000, "groups": 300, "resources": 3, "taint_keys": 64, "label_keys": 64}


def test_workcount_reduces_to_the_bare_count_without_operands():
    args = tuple(SHAPE.values())
    assert workcount_operands.binpack_ops(SHAPE) == workcount.binpack_ops(*args)
    assert workcount_operands.binpack_bytes(SHAPE) == workcount.binpack_bytes(*args)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert workcount_operands.least_time(SHAPE, peaks) == workcount.least_time(SHAPE, peaks)
    full = dict(SHAPE, forbidden=True, score=True, exclusive=True)
    cells = SHAPE["pods"] * SHAPE["groups"]
    assert workcount_operands.binpack_ops(full) == workcount.binpack_ops(*args) + 3 * cells
    assert workcount_operands.binpack_bytes(full) == (
        workcount.binpack_bytes(*args) + 5 * cells + SHAPE["pods"])


def _ctx(layers, trace=None, peaks=None):
    run = harness.Run("w", 1, 1.0, True, Path("."), {}, {"chips": 1}, {}, {})
    return harness.Context(run, harness.Window(1, 0, 1.0, {}, layers), trace, peaks)


def _read(name, ctx):
    return harness.load_reader(ctx.run, name).read(ctx)


def test_operand_mb_reader():
    counted = {"forbidden": 4 * 50_331_648, "score": 4 * 201_326_592, "exclusive": 4 * 131_072}
    assert _read("operand_mb.solve-operands", _ctx({"solves": 4, "operand_bytes": counted})) == (
        pytest.approx(251.789312))
    # a program that keeps no such count, and a window with no solve
    assert _read("operand_mb.solve-operands", _ctx({"solves": 4})) is None
    assert _read("operand_mb.solve-operands", _ctx({"solves": 0, "operand_bytes": counted})) is None


@pytest.mark.parametrize("name,solve", [
    ("pad_ms.solve-operands", "pad_ms.solve"), ("upload_ms.solve-operands", "upload_ms.solve"),
])
def test_stage_readers_read_as_the_solve_cell(name, solve):
    ctx = _ctx({"solves": 10, "stages": {"pad": 0.5, "upload": 0.2}})
    assert _read(name, ctx) == _read(solve, ctx) > 0


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def module_s(self, pattern):
        return self.seconds


def test_roofline_reader_counts_the_operand_work():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    shape = dict(SHAPE, forbidden=True, score=True, exclusive=True)
    ctx = _ctx({"binpack_calls": 10, "shape": shape}, _Trace(0.05), peaks)
    least, bound = workcount_operands.least_time(shape, peaks)
    assert bound == "memory"
    assert _read("binpack_roofline.solve-operands", ctx) == pytest.approx(100 * least / 0.005)
    assert _read("binpack_roofline.solve-operands", _ctx({"binpack_calls": 10, "shape": shape})) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def _drop(field):
    def plant(monkeypatch):
        from karpenter_tpu.solver import service

        submit = service.SolverService.submit

        def dropped(self, inputs, *a, **k):
            return submit(self, dataclasses.replace(inputs, **{field: None}), *a, **k)

        monkeypatch.setattr(service.SolverService, "submit", dropped)

    plant.__name__ = f"{field}_dropped"
    return plant


@pytest.mark.parametrize(
    "fault", [_drop("pod_group_forbidden"), _drop("pod_group_score"), _drop("pod_exclusive")],
    ids=lambda f: f.__name__,
)
def test_fault_makes_the_run_incorrect(root, fault, monkeypatch, capsys):
    fault(monkeypatch)
    result = run_cell(root, CELL, 3_000_000_047, 0.5, 0, capsys)
    assert result["correct"] is False, result["checks"]
