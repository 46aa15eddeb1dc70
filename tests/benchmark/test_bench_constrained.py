"""The schedperf-5k cell's plain reference against the program, its
readers, and the faults its check must catch, on the CPU at a small size.

- On seeded fleets of about 100 nodes in 3 zones, with spread
  Deployments, StatefulSets, bound replicas and a plan of rollouts and
  reschedules applied, the program's published signal (solve_pending on
  the feed path) equals benchmark/reference/constrained_signal.py
  exactly, and the reference's own placement keeps the scheduler's
  rules.
- Each planted fault makes a tiny run of the cell not correct, and the
  bfloat16 control, run as `python -m benchmark.control` runs it, too.
- census_ms.constrained and expand_ms.constrained on a recorded span
  list.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import run_cell, tiny_root
from benchmark import harness
from benchmark.drivers import constrained_events as CE
from benchmark.drivers import runtime_events as R
from benchmark.reference import constrained_signal as C

CELL = "schedperf-5k.constrained"


def _config(seed: int) -> dict:
    config = json.loads((harness.ROOT / "benchmark/configs/schedperf-5k.json").read_text())
    rng = np.random.default_rng(seed)
    config.update(nodes=int(rng.integers(90, 110)), node_groups=int(rng.integers(9, 16)),
                  pending_pods=int(rng.integers(500, 900)), bound_pods=int(rng.integers(200, 400)))
    return config


def _fleet(seed: int, pairs: int = 60):
    """A seeded fleet after `pairs` rollouts and reschedules: (its
    configuration, the fleet, its API objects, every pod alive)."""
    config = _config(seed)
    fleet = CE.Fleet(config, seed)
    rng = R._rng(seed, 1)
    initial = CE._populate(fleet, config, rng)
    events = R.Events(initial)
    for _, victim, new in CE._schedule({"cluster": fleet, "rng": rng, **CE._live(fleet, initial)},
                                       pairs, 1.0):
        events.log += [(victim, -1), (new, +1)]
    alive = events.pending_after(len(events.log), len(fleet.cpu_idx))
    return config, fleet, CE.Pods(fleet), alive


def _published(config, fleet, objects, alive) -> dict:
    from karpenter_tpu.metrics.producers.pendingcapacity import (
        SUBSYSTEM, group_profile, solve_pending,
    )
    from karpenter_tpu.metrics.registry import GaugeRegistry
    from karpenter_tpu.store import Store
    from karpenter_tpu.store.columnar import PendingFeed

    store = Store()
    per_group = np.array_split(np.arange(config["nodes"]), config["node_groups"])
    for g, ids in enumerate(per_group):
        for j in range(len(ids)):
            store.create(objects.node(g, j, config["zones"]))
        store.create(R._producer(g))
    for i in alive:
        store.create(objects.pod(int(i)))
    registry = GaugeRegistry()
    solve_pending(store, [], registry, feed=PendingFeed(store, group_profile))
    return {name: {s.labels["name"]: s.value for s in registry.gauge(SUBSYSTEM, name).samples()}
            for name in R.GAUGES}


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_program_equals_the_reference(seed):
    config, fleet, objects, alive = _fleet(seed)
    got = _published(config, fleet, objects, alive)
    want = fleet.reference(alive)
    for g in range(fleet.groups):
        name = R.producer_name(g)
        assert got["pending_pods"][name] == want.assigned_count[g], name
        assert got["additional_nodes_needed"][name] == want.nodes_needed[g], name
        assert got["lp_lower_bound"][name] == want.lp_bound[g], name
        assert got["unschedulable_pods"][name] == want.unschedulable


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_reference_keeps_the_scheduler_rules(seed):
    """The soundness fuzz's scalar rules (tests/test_soundness_fuzz.py) on
    the reference's own placement: a zone that receives a spread replica
    ends within maxSkew of the least-loaded zone; a StatefulSet replica
    takes a node of its own; every pending pod is promised or
    unschedulable."""
    _, fleet, _, alive = _fleet(seed)
    want = fleet.reference(alive)
    pending = alive[fleet.node[alive] < 0]
    bound = alive[fleet.node[alive] >= 0]
    assert int(want.assigned_count.sum()) + want.unschedulable == len(pending)
    group_zone = np.arange(fleet.groups) % fleet.zones
    for w in np.flatnonzero(fleet.kind == C.SPREAD):
        final = np.bincount(fleet.node_zone[fleet.node[bound[fleet.workload[bound] == w]]],
                            minlength=fleet.zones)
        placed = group_zone[want.assigned[fleet.workload[pending] == w]]
        final += np.bincount(placed, minlength=fleet.zones)
        for z in set(placed.tolist()):
            assert final[z] - final.min() <= fleet.max_skew, (w, final)
    sts = fleet.kind[np.maximum(fleet.workload[pending], 0)] == C.STATEFULSET
    sts &= fleet.workload[pending] >= 0
    per_group = np.bincount(want.assigned[sts], minlength=fleet.groups)
    assert (want.nodes_needed >= per_group).all()


def test_water_fill_ties_rotate_from_the_seed():
    assert C.water_fill([2, 0, 1], 4, seed=0).tolist() == [1, 2, 1]
    # five to place over counts [1, 1, 1]: one level each, two left over
    # for the three tied zones, from position seed mod 3 on
    assert C.water_fill([1, 1, 1], 5, seed=0).tolist() == [2, 2, 1]
    assert C.water_fill([1, 1, 1], 5, seed=1).tolist() == [1, 2, 2]
    assert C.water_fill([1, 1, 1], 5, seed=2).tolist() == [2, 1, 2]


# -- planted faults: each must make a tiny run of the cell not correct --------


def census_empty(monkeypatch):
    from karpenter_tpu.store.columnar import ScheduledOccupancy

    view_counts = ScheduledOccupancy.view_counts
    monkeypatch.setattr(ScheduledOccupancy, "view_counts",
                        lambda self, *a: (view_counts(self, *a)[0], {}))


def spread_mask_dropped(monkeypatch):
    from karpenter_tpu.metrics.producers.pendingcapacity import encoder

    expand = encoder._expand_spread_rows
    monkeypatch.setattr(encoder, "_expand_spread_rows",
                        lambda *a, **k: (*expand(*a, **k)[:2], None))


def exclusive_dropped(monkeypatch):
    from karpenter_tpu.metrics.producers import pendingcapacity as pc

    encode = pc.encode_snapshot
    monkeypatch.setattr(pc, "encode_snapshot", lambda *a, **k: dataclasses.replace(
        encode(*a, **k), pod_exclusive=None))


def reschedule_unrecorded(monkeypatch):
    """The store takes every reschedule; the reference's tables keep each
    moved replica where it was."""
    moved = set()
    schedule = CE._schedule

    def recording(state, pairs, seconds):
        plan = schedule(state, pairs, seconds)
        fleet = state["cluster"]
        moved.update(p for _, victim, new in plan if fleet.node[new] >= 0 for p in (victim, new))
        return plan

    pending_after = R.Events.pending_after

    def unrecorded(self, applied, n_pods):
        log = self.log
        self.log = [e for e in log[:applied] if e[0] not in moved]
        try:
            return pending_after(self, len(self.log), n_pods)
        finally:
            self.log = log

    monkeypatch.setattr(CE, "_schedule", recording)
    monkeypatch.setattr(R.Events, "pending_after", unrecorded)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("fault", [census_empty, spread_mask_dropped, exclusive_dropped,
                                   reschedule_unrecorded], ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(root, fault, monkeypatch, capsys):
    fault(monkeypatch)
    result = run_cell(root, CELL, 3_000_000_023, 0.5, 0, capsys)
    assert result["correct"] is False, result["checks"]


CONTROL_AS_A_SCRIPT = """
import runpy, sys
from pathlib import Path
from benchmark import harness
claim = harness.claim_devices
harness.claim_devices = lambda run, cpu: claim(run, cpu=True)
harness.ROOT = Path(sys.argv[1])
sys.argv = ["control", "--workload", "%s", "--seeds", "7", "--seconds", "0.5"]
runpy.run_module("benchmark.control", run_name="__main__", alter_sys=True)
""" % CELL


def test_control_runs_as_a_script(root):
    """`python -m benchmark.control` runs the control module as __main__,
    whose table constrained_events must register into too (run on the
    CPU here)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(harness.ROOT))
    done = subprocess.run([sys.executable, "-c", CONTROL_AS_A_SCRIPT, str(root)], env=env,
                          capture_output=True, text=True, timeout=300, cwd=harness.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["workload"] == CELL and result["correct"] is False, result


# -- the readers ----------------------------------------------------------------


def _span(name, parent=None, dur_us=1000.0, counters=None):
    args = {} if counters is None else {"counters": counters}
    return {"name": name, "id": name, "parent": parent, "dur_us": dur_us, "args": args}


def _ctx(layers):
    run = harness.Run("w", 1, 1.0, True, Path("."), {}, {"chips": 1}, {}, {})
    return harness.Context(run, harness.Window(1, 0, 1.0, {}, layers), None, None)


QUERY = {"census.query": {"count": 2, "seconds": 0.003}}
SPANS = [
    _span("reconcile.tick", counters={"census.query": {"count": 1, "seconds": 0.001}}),
    _span("pendingcapacity.census", "reconcile.tick", 500.0),
    _span("pendingcapacity.expand", "reconcile.tick", 4000.0),
    _span("reconcile.event_pass", counters=QUERY),
    _span("pendingcapacity.census", "reconcile.event_pass", 1500.0),
    _span("pendingcapacity.expand", "reconcile.event_pass", 6000.0),
]


@pytest.mark.parametrize("name,ms", [
    # (0.5 + 1.5 ms of builds + 4 ms of queries) over 2 batches
    ("census_ms.constrained", 3.0),
    # (4 + 6 ms of expansions - 4 ms of queries) over 2 batches
    ("expand_ms.constrained", 3.0),
])
def test_readers(name, ms):
    reader = harness.load_reader(_ctx({}).run, name)
    assert reader.read(_ctx({"mp_batches": 2, "spans": SPANS})) == pytest.approx(ms)
    # the parent program, and an unconstrained fleet: no such span
    bare = [s for s in SPANS if not s["name"].startswith("pendingcapacity.")]
    assert reader.read(_ctx({"mp_batches": 2, "spans": bare})) is None
    assert reader.read(_ctx({"mp_batches": 2})) is None
    assert reader.read(_ctx({"mp_batches": 0, "spans": SPANS})) is None
