"""chip_smoke.py's phases on the CPU at small sizes.

The script itself needs the TPU (it exits 1 on the CPU, which the last
test pins); its phases take sizes and a backend so the same checks run
here against the same references: the runtime tick, the front-door solve
and decision batch, the float-kernel parity report, and the sharded fleet
solve on four of the suite's virtual devices.
"""

from __future__ import annotations

import json

import pytest

import chip_smoke as C
from karpenter_tpu.solver import SolverService


def test_runtime_tick_phase_matches_reference():
    report = C.runtime_tick_phase(pods=2000, groups=24, ticks=3, backend="xla")
    assert report["served"].keys() == {"solve/xla", "decide/xla"}
    assert report["decide_batches"] == 3
    assert report["stats"].fallbacks == 0


def test_front_door_phases_match_reference():
    service = SolverService(backend="xla", watchdog_timeout_s=30.0)
    try:
        reports = C.solve_phase(service, pods=3000, groups=40)
        reports.append(C.decide_phase(service, rows=3000))
    finally:
        service.close()
    assert [r["phase"] for r in reports] == [
        "solve[plain]", "solve[operands]", "decide",
    ]
    for report in reports:
        C.check_kernels(report["served"], report["phase"])
    assert service.stats.fallbacks == 0


def test_float_kernels_are_bitwise_on_cpu():
    """On the CPU the forecast, cost and pool-group kernels equal their
    numpy mirrors bit for bit; the chip run reports what the TPU gives."""
    report = C.parity_phase()
    assert set(report) == {"forecast", "cost", "poolgroup"}
    assert all(n == 0 for fields in report.values() for n in fields.values())


def test_fleet_phase_on_four_virtual_devices():
    report = C.fleet_phase(
        pods=8000, groups=64, chips=4, shard_threshold=1, backend="xla",
    )
    assert report["stats"].shard_dispatches >= 1
    assert report["stats"].shard_fallbacks == 0
    assert len(report["shard_devices"]) == 4


def test_reference_catches_a_wrong_answer():
    inputs = C.build_binpack(500, 12, seed=1)
    want = C.reference_binpack(inputs)
    wrong = type(want)(**{**vars(want), "nodes_needed": want.nodes_needed + 1})
    with pytest.raises(C.SmokeFailure, match="nodes_needed"):
        C.compare_binpack(wrong, want, "solve")


def test_main_fails_without_a_tpu(capsys, monkeypatch, tmp_path):
    """On the CPU the script exits 1 with the reason on stderr and
    prints no result line."""
    # the variable keeps arm_compile_cache off the suite's JAX config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert C.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU" in err
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
