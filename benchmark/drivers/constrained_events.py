"""Open loop of pending rollouts and bound-replica reschedules into the
control plane, on a cluster whose workloads carry the kube-scheduler's
two commonest required constraints: zone-spread Deployments and
StatefulSets with one replica a node (configuration keys `constrained`,
`zones`, and runtime_events' cluster keys).

Everything from the watch event to the decision is runtime_events': the
runtime as `--profile production` runs it, its event loop (`_drive`),
the recorder, the latency accounting and the check. This module brings
the cluster's workloads, their pods, the event plan and the plain
reference they are checked against (benchmark/reference/
constrained_signal.py).

Each workload has one pod template, so its replicas share one request
shape; workload sizes cycle over `workload_sizes` until each kind's
share of `pending_pods` is met. Bound replicas: a spread workload's go
round-robin over the zones from a seeded start (skew at most 1, as the
scheduler leaves them); a StatefulSet holds at most one a node. The
rest of the pods are runtime_events' unconstrained pods.

Pairs of events arrive at Poisson times, as in runtime_events. Each pair
is, equally likely, (a) a pending rollout: one pending pod deleted and
one of the same workload created (an unconstrained pod is replaced by a
fresh unconstrained one), or (b) a reschedule: one bound replica of a
constrained workload deleted and a new replica of it bound to a node of
a seeded-random other zone (for a StatefulSet, a node holding none of
its replicas). A reschedule moves the workload's zone counts and the
occupancy census's generation.

Importing this module registers its control (the plain reference in
bfloat16) into benchmark.control.SUBSTITUTE.
"""

from __future__ import annotations

import collections
import sys
import time

import numpy as np

from benchmark import control as _control
from benchmark.drivers import runtime_events as R
from benchmark.harness import Window
from benchmark.reference import constrained_signal as C

ZONE_KEY = "topology.kubernetes.io/zone"
HOSTNAME_KEY = "kubernetes.io/hostname"
POD_NAME_KEY = "statefulset.kubernetes.io/pod-name"
KIND_NAMES = {C.SPREAD: "spread", C.STATEFULSET: "sts"}


class Fleet(R.Cluster):
    """runtime_events' cluster tables, with per pod its workload (-1:
    none) and its node (-1: pending; ids are never reused, so a pod's
    node never changes), per workload its kind and template, and per
    node its zone."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        c = config["constrained"]
        self.zones = config["zones"]
        if self.groups < self.zones:
            raise ValueError("every zone needs a node group")
        per_group = np.array_split(np.arange(config["nodes"]), self.groups)
        self.node_names = [f"n{g}-{j}" for g, ids in enumerate(per_group) for j in range(len(ids))]
        self.node_zone = np.concatenate([np.full(len(ids), g % self.zones) for g, ids in enumerate(per_group)])
        self.group_zone = np.array([f"z{g % self.zones}" for g in range(self.groups)])
        kinds, sizes = [], []
        for kind, share in ((C.SPREAD, c["spread_share"]), (C.STATEFULSET, c["statefulset_share"])):
            left = int(round(share * config["pending_pods"]))
            while left > 0:
                size = min(c["workload_sizes"][len(sizes) % len(c["workload_sizes"])], left)
                kinds.append(kind)
                sizes.append(size)
                left -= size
        self.kind = np.array(kinds, np.int8)
        self.size = np.array(sizes, np.int64)
        self.max_skew = c["max_skew"]
        rng = R._rng(seed, 4)
        k = np.arange(len(kinds))
        self.template_cpu = rng.permutation(k % len(self.cpu_values)).astype(np.int8)
        self.template_mem = rng.permutation(k % len(self.mem_values)).astype(np.int8)
        self.workload = np.zeros(0, np.int32)
        self.node = np.zeros(0, np.int32)

    def name(self, w: int) -> str:
        return f"{KIND_NAMES[int(self.kind[w])]}-{w:04d}"

    def draw(self, rng, n: int) -> np.ndarray:
        """n unconstrained pending pods, as runtime_events draws them."""
        self.workload = np.concatenate([self.workload, np.full(n, -1, np.int32)])
        self.node = np.concatenate([self.node, np.full(n, -1, np.int32)])
        return super().draw(rng, n)

    def add(self, workloads, nodes) -> np.ndarray:
        """Replicas of `workloads` (one each) on `nodes` (-1: pending), as
        their templates; returns their ids."""
        workloads = np.asarray(workloads, np.int32)
        first = len(self.cpu_idx)
        self.cpu_idx = np.concatenate([self.cpu_idx, self.template_cpu[workloads]])
        self.mem_idx = np.concatenate([self.mem_idx, self.template_mem[workloads]])
        self.affinity = np.concatenate([self.affinity, np.zeros(len(workloads), np.int8)])
        self.workload = np.concatenate([self.workload, workloads])
        self.node = np.concatenate([self.node, np.asarray(nodes, np.int32)])
        return np.arange(first, first + len(workloads))

    def reference(self, alive: np.ndarray, buckets: int = 32, precision=np.float32):
        """The plain signal over the pods `alive` holds, pending and bound
        alike (runtime_events.Events.pending_after over every initial
        pod): the pending ones are the rows, the bound replicas of spread
        workloads the zone counts."""
        pending = alive[self.node[alive] < 0]
        bound = alive[self.node[alive] >= 0]
        w = self.workload[pending]
        kind = np.where(w >= 0, self.kind[np.maximum(w, 0)], C.NONE)
        spread_bound = bound[(self.workload[bound] >= 0)
                             & (self.kind[np.maximum(self.workload[bound], 0)] == C.SPREAD)]
        return C.signal(
            self.cpu_values[self.cpu_idx[pending]], self.mem_values[self.mem_idx[pending]],
            self.affinity[pending], kind, w, self.workload[spread_bound],
            np.array([f"z{z}" for z in self.node_zone[self.node[spread_bound]]]),
            self.group_cores.astype(float), self.group_memory(), self.group_ssd, self.group_zone,
            buckets=buckets, precision=precision,
        )


class Pods(R.Objects):
    """runtime_events' API objects, with a constrained pod built from its
    workload's template and bound to the node the tables give it."""

    def pod(self, i: int):
        fl, c = self.cluster, self.core
        node = fl.node_names[fl.node[i]] if fl.node[i] >= 0 else ""
        w = int(fl.workload[i])
        if w < 0:
            return super().pod(i, node)
        app = fl.name(w)
        labels, spread, affinity = {"app": app}, [], None
        if fl.kind[w] == C.SPREAD:
            spread = [c.TopologySpreadConstraint(
                max_skew=fl.max_skew, topology_key=ZONE_KEY, when_unsatisfiable="DoNotSchedule",
                label_selector={"matchLabels": {"app": app}})]
        else:
            labels[POD_NAME_KEY] = f"{app}-{i}"
            affinity = c.Affinity(pod_anti_affinity=c.PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=[c.PodAffinityTerm(
                    label_selector=c.LabelSelector(match_labels={"app": app}),
                    topology_key=HOSTNAME_KEY)]))
        return c.Pod(
            metadata=c.ObjectMeta(name=f"p{i}", labels=labels),
            spec=c.PodSpec(
                node_name=node,
                containers=[c.Container(requests={
                    "cpu": self.cpus[fl.cpu_idx[i]], "memory": self.mems[fl.mem_idx[i]]})],
                affinity=affinity, topology_spread_constraints=spread,
            ),
        )


def _apportion(sizes: np.ndarray, total: int) -> np.ndarray:
    """`total` split over workloads in proportion to `sizes`."""
    replicas = np.repeat(np.arange(len(sizes)), sizes)
    if not len(replicas):
        return np.zeros(len(sizes), np.int64)
    return np.bincount(replicas[(np.arange(total) * len(replicas)) // total], minlength=len(sizes))


def _populate(fleet: Fleet, config: dict, rng) -> np.ndarray:
    """Every initial pod's id: the workloads' pending replicas, their
    bound replicas, and the unconstrained pods, pending and bound."""
    c = config["constrained"]
    n_nodes = len(fleet.node_names)
    pending = [fleet.draw(rng, config["pending_pods"] - int(fleet.size.sum()))]
    pending.append(fleet.add(np.repeat(np.arange(len(fleet.size)), fleet.size), np.full(fleet.size.sum(), -1)))
    order = rng.permutation(n_nodes)  # one cursor over all nodes, one per zone
    cursor = 0
    zone_nodes = [rng.permutation(np.flatnonzero(fleet.node_zone == z)) for z in range(fleet.zones)]
    zone_cursor = np.zeros(fleet.zones, np.int64)
    workloads, nodes = [], []
    for kind, share in ((C.SPREAD, c["bound_spread_share"]), (C.STATEFULSET, c["bound_statefulset_share"])):
        ids = np.flatnonzero(fleet.kind == kind)
        for w, b in zip(ids, _apportion(fleet.size[ids], int(round(share * config["bound_pods"])))):
            if kind == C.SPREAD:
                start = int(rng.integers(0, fleet.zones))
                for j in range(b):
                    z = (start + j) % fleet.zones
                    nodes.append(zone_nodes[z][zone_cursor[z] % len(zone_nodes[z])])
                    zone_cursor[z] += 1
            else:
                if b > n_nodes:
                    raise ValueError(f"a StatefulSet of {b} bound replicas needs as many nodes")
                nodes.extend(order[(cursor + np.arange(b)) % n_nodes])
                cursor += b
            workloads.extend([w] * b)
    bound = [fleet.add(workloads, nodes)]
    rest = config["bound_pods"] - len(workloads)
    free = fleet.draw(rng, rest)
    fleet.node[free] = order[(cursor + np.arange(rest)) % n_nodes]
    bound.append(free)
    return np.concatenate(pending + bound)


def _live(fleet: Fleet, initial: np.ndarray) -> dict:
    """What the plan draws its victims from: the pending pods, the bound
    replicas of constrained workloads, and each StatefulSet's nodes."""
    bound = initial[(fleet.node[initial] >= 0) & (fleet.workload[initial] >= 0)]
    sts_nodes = collections.defaultdict(set)
    for i in bound[fleet.kind[fleet.workload[bound]] == C.STATEFULSET]:
        sts_nodes[int(fleet.workload[i])].add(int(fleet.node[i]))
    return {"pending": list(initial[fleet.node[initial] < 0]), "bound": list(bound),
            "sts_nodes": sts_nodes}


def setup(run):
    from karpenter_tpu.observability.tracing import Tracer, default_tracer, set_default_tracer
    from karpenter_tpu.runtime import KarpenterRuntime, Options

    cfg, traffic = run.config, run.traffic
    tracer = default_tracer()
    if run.trace:  # room for every span of the traced window
        set_default_tracer(Tracer(capacity=1 << 20))
    t0 = time.perf_counter()
    fleet = Fleet(cfg, run.seed)
    rng = R._rng(run.seed, 1)
    initial = _populate(fleet, cfg, rng)
    objects = Pods(fleet)
    per_group = np.array_split(np.arange(cfg["nodes"]), cfg["node_groups"])
    nodes = [objects.node(g, j, cfg["zones"]) for g, ids in enumerate(per_group) for j in range(len(ids))]
    pods = [objects.pod(int(i)) for i in initial]
    generate_s = time.perf_counter() - t0

    runtime = KarpenterRuntime(Options(
        cloud_provider="fake", event_driven=True, prewarm_compile=True, fused_tick=True,
        selfslo_objective_s=0.5, event_thread=False,
    ))
    if run.platform == "cpu":  # the CPU rehearsal drives the device programs
        runtime.solver_service.backend = "xla"
    store, provider = runtime.store, runtime.cloud_provider
    t0 = time.perf_counter()
    for obj in nodes:
        store.create(obj)
    for g in range(cfg["node_groups"]):
        store.create(R._producer(g))
    for g in range(cfg["autoscaled_groups"]):
        R._autoscaled(store, provider, g)
    for obj in pods:
        store.create(obj)
    load_s = time.perf_counter() - t0
    del pods, nodes
    pending = initial[fleet.node[initial] < 0]
    run.log(f"generated {len(fleet.size)} workloads ({int((fleet.kind == C.SPREAD).sum())} spread, "
            f"{int((fleet.kind == C.STATEFULSET).sum())} StatefulSets), {len(pending)} pending and "
            f"{len(initial) - len(pending)} bound pods in {generate_s:.3f} s; store.create of them "
            f"and {cfg['nodes']} nodes in {load_s:.3f} s")

    recorder = R.Recorder(runtime, run)
    state = {"runtime": runtime, "epoch": runtime.batch_autoscaler.epoch, "tracer": tracer,
             "cluster": fleet, "objects": objects, "recorder": recorder,
             # Events over every initial pod: its pending_after is the live
             # set, which Fleet.reference splits into rows and zone counts
             "events": R.Events(initial), "rng": rng, **_live(fleet, initial),
             "timing": {"generate_s": generate_s, "store_load_s": load_s}}
    t0 = time.perf_counter()
    R._settle(state, run)
    warm = _schedule(state, traffic["warmup_pairs"], traffic["warmup_s"])
    R._drive(state, run, warm, traffic["warmup_s"], traffic["drain_s"])
    state["timing"]["warmup_s"] = time.perf_counter() - t0
    run.log(f"first passes and warm-up events {state['timing']['warmup_s']:.3f} s; "
            f"served {dict(runtime.solver_service.stats.served)}")
    return state


def _schedule(state, pairs: int, seconds: float):
    """`pairs` events over [0, seconds): (time, victim pod id, new pod id)
    each, at runtime_events' Poisson times (the exponential quantiles, in
    a seeded order), each a rollout or a reschedule (module docstring)."""
    fleet, rng = state["cluster"], state["rng"]
    gaps = -np.log1p(-(np.arange(pairs) + 0.5) / pairs)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reschedule = rng.random(pairs) < 0.5
    fresh = iter(fleet.draw(rng, int((~reschedule).sum())).tolist())
    pending, bound, sts_nodes = state["pending"], state["bound"], state["sts_nodes"]
    first = len(fleet.cpu_idx)
    workloads, nodes, plan = [], [], []

    def of(pod):  # (workload, node) of a pod, this plan's new ones included
        if pod < first:
            return int(fleet.workload[pod]), int(fleet.node[pod])
        return workloads[pod - first], nodes[pod - first]

    for k in range(pairs):
        if reschedule[k]:
            slot = int(rng.integers(0, len(bound)))
            victim = bound[slot]
            w, old = of(victim)
            zone = (fleet.node_zone[old] + 1 + int(rng.integers(0, fleet.zones - 1))) % fleet.zones
            candidates = np.flatnonzero(fleet.node_zone == zone)
            node = int(rng.choice(candidates))
            if fleet.kind[w] == C.STATEFULSET:
                held = sts_nodes[w]
                free = candidates[~np.isin(candidates, list(held))]
                if not len(free):
                    raise ValueError(f"no node of zone {zone} is free of {fleet.name(w)}'s replicas")
                node = int(rng.choice(free))
                held.discard(old)
                held.add(node)
            new = first + len(workloads)
            workloads.append(w)
            nodes.append(node)
            bound[slot] = new
        else:
            slot = int(rng.integers(0, len(pending)))
            victim = pending[slot]
            w, _ = of(victim)
            if w < 0:
                new = next(fresh)
            else:
                new = first + len(workloads)
                workloads.append(w)
                nodes.append(-1)
            pending[slot] = new
        plan.append((float(times[k]), int(victim), int(new)))
    fleet.add(workloads, nodes)
    return plan


def window(state, run) -> Window:
    """runtime_events.window's measurement over this module's plan."""
    began = time.perf_counter()
    traffic, recorder, runtime = run.traffic, state["recorder"], state["runtime"]
    plan = _schedule(state, int(round(traffic["events_per_s"] * run.seconds / 2)), run.seconds)
    service = runtime.solver_service
    stages = collections.defaultdict(float)
    record_stage = service._record_stage

    def recording(stage, seconds):  # the benchmark's wrapper: sums per stage
        record_stage(stage, seconds)
        stages[stage] += seconds

    service._record_stage = recording
    if run.trace:
        _tracer().clear()
    mp_before, ha_before = len(recorder.batches[R.MP]), len(recorder.batches[R.HA])
    recorder.live = True
    record = state["last_record"] = R._drive(state, run, plan, run.seconds, traffic["drain_s"])
    recorder.live = False
    recorder.keep_last()
    service._record_stage = record_stage
    lat, bad = R.latencies(recorder, record)
    decided = ~np.isnan(lat)
    lag = record["applied_at"] - record["sched"]
    mp_batches = len(recorder.batches[R.MP]) - mp_before
    run.log(f"{len(lat)} events, {int(decided.sum())} decided; generator lag p50 "
            f"{np.percentile(lag, 50) * 1e3:.3f} ms p95 {np.percentile(lag, 95) * 1e3:.3f} ms; "
            f"{mp_batches} MP and {len(recorder.batches[R.HA]) - ha_before} HA batches; "
            f"window {record['elapsed']:.3f} s with the drain")
    run.log(f"the loop began +{record['t0'] - began:.3f} s into the window; "
            + R._stalls(record, recorder, lat))
    end_to_end = {}
    if decided.any():
        q = np.percentile(lat[decided] * 1e3, [50, 95, 99])
        run.log(f"event to decision p50 {q[0]:.3f} ms p95 {q[1]:.3f} ms p99 {q[2]:.3f} ms")
        end_to_end = {"event_to_decision_p50_ms": float(q[0]), "event_to_decision_p95_ms": float(q[1])}
    layers = {"events": len(lat), "apply_s": record["apply_s"], "mp_batches": mp_batches,
              "binpack_calls": mp_batches, "ha_batches": len(recorder.batches[R.HA]) - ha_before,
              "stages": dict(stages)}
    if run.trace:
        layers["spans"] = _tracer().snapshot()
    return Window(attempted=len(lat), failed=int((~decided).sum() + bad.sum()), seconds=run.seconds,
                  end_to_end=end_to_end, layers=layers)


def _tracer():
    from karpenter_tpu.observability.tracing import default_tracer

    return default_tracer()


close = R.close
check = R.check

# `python -m benchmark.control` runs that module as __main__, a second
# copy with a table of its own: register into each copy there is
for _module in (_control, sys.modules.get("__main__")):
    if getattr(getattr(_module, "__spec__", None), "name", None) == _control.__name__:
        _module.SUBSTITUTE.setdefault("constrained_events", _module.substitute_runtime)
