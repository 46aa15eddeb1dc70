"""The plain pending-capacity signal of a cluster whose pending pods carry
the kube-scheduler's two commonest required constraints: a topology
spread on the zone key (whenUnsatisfiable DoNotSchedule) and self
anti-affinity on the hostname key.

Computed from the benchmark's own tables, never from the program's
objects or encodings: one row per pending pod, with no dedup, no
census views and no memo. A spread workload's existing replicas per
zone are counted from the benchmark's table of bound pods.

Semantics, as the program's docstrings state them
(karpenter_tpu/metrics/producers/pendingcapacity/spread.py
`_expand_spread_rows`, partition.py `_water_fill`, anti.py
`_expand_anti_rows`), for the case this configuration builds: every
group lies in one zone, every zone is served by some group, every
constrained pod fits every group and carries no node affinity.

- Spread. With every zone served, no domain outside the groups freezes
  the global minimum, so no zone is capped. A workload's pending
  replicas fill the least-loaded zones first against its existing
  counts: the level rises until one more step would place more
  replicas than are pending, each zone below the level is filled to
  it, and the replicas left over go one each to the zones standing at
  the level, taken in zone-name order from position `seed mod (number
  of such zones)` on. The seed is the replica count plus the sum of
  the bytes of the pod's requests as float32 (cpu in cores, memory in
  bytes): the content-keyed rotation, so that no zone is favoured
  across workloads. A replica may then go only to its zone's groups.
- Hostname anti-affinity. The pod takes a whole node (its bucket is
  the whole node), in whichever group it goes to.
- Everything else is benchmark/reference/signal.py: the pod's own cpu,
  memory and one pod slot, required node affinity on `disk`, preferred
  affinity scored by weight, and the bin-pack benchmark/reference/binpack.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import binpack as ref
from benchmark.reference import signal as signal_ref

NONE, SPREAD, STATEFULSET = 0, 1, 2  # a pending pod's constraint kind


def content_seed(replicas: int, cpu: float, memory: float) -> int:
    """The rotation seed of a spread workload's remainder."""
    row = np.array([cpu, memory], np.float32)
    return int(replicas) + int(row.view(np.uint8).sum())


def water_fill(counts: np.ndarray, replicas: int, seed: int) -> np.ndarray:
    """New replicas per zone (zones in name order) for a workload that
    holds `counts` replicas per zone already."""
    counts = np.asarray(counts, np.int64)
    level = int(counts.min())
    while np.clip(level + 1 - counts, 0, None).sum() <= replicas:
        level += 1
    adds = np.clip(level - counts, 0, None)
    left = replicas - int(adds.sum())
    tied = np.flatnonzero(counts + adds == level)
    offset = seed % len(tied)
    for position, zone in enumerate(tied):
        if (position - offset) % len(tied) < left:
            adds[zone] += 1
    return adds


def signal(cpu, memory, affinity, kind, workload, bound_workload, bound_zone,
           group_cpu, group_memory, group_ssd, group_zone, buckets: int = 32,
           precision=np.float32):
    """Per pending pod: cpu and memory (cores and bytes), its node-affinity
    kind (benchmark/reference/signal.py), its constraint kind and its
    workload (any int where kind is NONE). Per bound replica of a spread
    workload: its workload and its node's zone name. Per group (in
    producer order): the node's cpu and memory, whether its disk is ssd,
    and its zone name. Returns the bin-pack Answer. maxSkew does not
    enter: every maxSkew of at least 1 admits the least-loaded fill."""
    cpu, memory = np.asarray(cpu, float), np.asarray(memory, float)
    kind, workload = np.asarray(kind), np.asarray(workload)
    group_zone = np.asarray(group_zone)
    zones = sorted(set(group_zone.tolist()))
    zone_of = {z: k for k, z in enumerate(zones)}
    n = len(cpu)
    requests = np.stack([cpu, memory, np.ones(n)], axis=1).astype(np.float32)
    alloc = np.stack(
        [group_cpu, group_memory, np.full(len(group_cpu), signal_ref.PODS_PER_NODE)], axis=1
    ).astype(np.float32)

    forbidden = np.zeros((n, len(group_zone)), bool)
    ssd = np.asarray(group_ssd, bool)
    forbidden[np.asarray(affinity) == signal_ref.REQUIRE_SSD] = ~ssd
    forbidden[np.asarray(affinity) == signal_ref.NOT_HDD] = ~ssd
    score = None
    if (np.asarray(affinity) == signal_ref.PREFER_SSD).any():
        score = np.zeros((n, len(ssd)), np.float32)
        score[np.asarray(affinity) == signal_ref.PREFER_SSD] = np.where(
            ssd, signal_ref.PREFER_WEIGHT, 0.0)

    existing = {}
    for w, z in zip(np.asarray(bound_workload).tolist(), np.asarray(bound_zone).tolist()):
        counts = existing.setdefault(w, np.zeros(len(zones), np.int64))
        if z in zone_of:
            counts[zone_of[z]] += 1
    spread = np.flatnonzero(kind == SPREAD)
    for w in np.unique(workload[spread]):
        pods = spread[workload[spread] == w]
        seed = content_seed(len(pods), cpu[pods[0]], memory[pods[0]])
        adds = water_fill(existing.get(int(w), np.zeros(len(zones), np.int64)), len(pods), seed)
        pod_zone = np.repeat(np.arange(len(zones)), adds)
        for z in range(len(zones)):
            forbidden[pods[pod_zone == z]] |= group_zone != zones[z]

    return ref.binpack(
        requests, np.ones(n, bool), np.zeros((n, 0), bool), np.zeros((n, 0), bool), alloc,
        np.zeros((len(ssd), 0), bool), np.zeros((len(ssd), 0), bool),
        forbidden=forbidden if forbidden.any() else None, score=score,
        exclusive=kind == STATEFULSET, buckets=buckets, precision=precision,
    )
