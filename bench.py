"""Headline benchmark: the north-star bin-pack (BASELINE.json).

100k pending pods × 300 instance types — resource fit + taint/toleration +
required-label feasibility, first-feasible assignment, shelf-BFD node counts
— as one device call. The reference STUBS this signal entirely
(pkg/metrics/producers/pendingcapacity/producer.go:29-31) and its design doc
warns the naive host-side form "scales linearly with node groups and
unschedulable pods" (docs/designs/DESIGN.md); the baseline BUDGET here is
the north-star target of 200 ms p50 on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline > 1 means faster than the 200 ms budget.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

import numpy as np

BASELINE_MS = 200.0

# Raw-evidence sidecar (r3 verdict): every run appends its full raw record
# — argv, backend, devices, per-iteration times, transport-floor probe —
# to bench_evidence/runs.jsonl (and mirrors the newest to latest.json), so
# a perf claim is always reconstructable from committed data instead of
# resting on a summarized p50 in a doc table.
EVIDENCE: dict = {}
EVIDENCE_DIR = os.environ.get(
    "KARPENTER_BENCH_EVIDENCE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "bench_evidence"),
)


def record_evidence(**kv) -> None:
    """Stash raw measurement context for the evidence sidecar. Values
    must be JSON-serializable (lists, not ndarrays)."""
    EVIDENCE.update(kv)


def measure_transport_floor(iters: int = 20) -> dict:
    """p50 cost of the smallest possible host<->device interactions —
    the transport floor every sync'd measurement sits on top of:

    - put_ms: device_put of one f32 scalar + block_until_ready;
    - dispatch_ms: a compiled 1-element add, dispatch + block;
    - fetch_ms: device_get of a 1-element array.

    Recording them next to every solve p50 shows how much of a sync'd
    number is the host<->device link rather than the solve."""
    try:
        import jax
        import jax.numpy as jnp

        tiny = jnp.ones((1,), jnp.float32)
        add = jax.jit(lambda x: x + 1.0)
        jax.block_until_ready(add(tiny))  # compile outside timing
        put, disp, fetch = [], [], []
        host = np.ones((1,), np.float32)
        for _ in range(iters):
            t0 = time.perf_counter()
            dev = jax.device_put(host)
            jax.block_until_ready(dev)
            put.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            jax.block_until_ready(add(tiny))
            disp.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            jax.device_get(tiny)
            fetch.append((time.perf_counter() - t0) * 1e3)
        floor = {
            "put_ms": round(float(np.percentile(put, 50)), 4),
            "dispatch_ms": round(float(np.percentile(disp, 50)), 4),
            "fetch_ms": round(float(np.percentile(fetch, 50)), 4),
            "iters": iters,
        }
        print(
            "transport floor: "
            f"put={floor['put_ms']}ms dispatch={floor['dispatch_ms']}ms "
            f"fetch={floor['fetch_ms']}ms",
            file=sys.stderr,
        )
        return floor
    except Exception as e:  # noqa: BLE001 — evidence-only, never fatal
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _backend_evidence() -> dict:
    """Backend identity for the evidence record (safe pre-init): the
    RESOLVED jax backend, device kind/count, and jax version — the
    provenance stamp that names the device in every result line, so a
    CPU run (JAX_PLATFORMS=cpu) never reads as a TPU number."""
    try:
        import jax

        devices = jax.devices()
        return {
            "backend": jax.default_backend(),
            "devices": [str(d) for d in devices],
            "device_kind": (
                devices[0].device_kind if devices else None
            ),
            "device_count": len(devices),
            "jax_version": jax.__version__,
        }
    except Exception as e:  # noqa: BLE001
        return {"backend_error": f"{type(e).__name__}: {e}"[:200]}


def warn_cpu_fallback(backend_info: dict) -> None:
    """The LOUD warning: a bench run that resolved to the CPU backend
    is a trajectory number, not a TPU claim — say so where the driver
    (and every human reading the captured stderr) cannot miss it."""
    if backend_info.get("backend") == "cpu":
        print(
            "=" * 72 + "\n"
            "WARNING: jax resolved to the CPU backend — this run is a "
            "CPU\ntrajectory number, NOT a TPU measurement. The result "
            "json carries\nbackend/device_kind/jax_version provenance; "
            "do not read it as a\nreal-chip claim.\n" + "=" * 72,
            file=sys.stderr,
        )


def _write_evidence(rec: dict) -> None:
    """Append the full raw record; never let evidence IO break the ONE
    JSON line contract."""
    try:
        full = {
            "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
                timespec="seconds"
            ),
            "argv": sys.argv[1:],
            **_backend_evidence(),
            **EVIDENCE,
            "result": rec,
        }
        # a run under a profiler/tracer is a DIAGNOSTIC, not a
        # measurement — instrumentation overhead inflates every number
        # (an r5 cProfile run recorded a 3.5x-inflated e2e tick before
        # this flag existed). Tag it so evidence consumers can filter.
        # On 3.12+ cProfile registers via sys.monitoring, not
        # sys.setprofile, so both registries are consulted.
        tool = None
        monitoring = getattr(sys, "monitoring", None)
        if monitoring is not None:
            tool = monitoring.get_tool(
                monitoring.PROFILER_ID
            ) or monitoring.get_tool(monitoring.DEBUGGER_ID)
        if (
            sys.getprofile() is not None
            or sys.gettrace() is not None
            or tool is not None
        ):
            full["diagnostic"] = "profiled"
        os.makedirs(EVIDENCE_DIR, exist_ok=True)
        line = json.dumps(full)
        with open(os.path.join(EVIDENCE_DIR, "runs.jsonl"), "a") as f:
            f.write(line + "\n")
        with open(os.path.join(EVIDENCE_DIR, "latest.json"), "w") as f:
            f.write(line + "\n")
    except Exception as e:  # noqa: BLE001
        print(f"evidence write failed: {e}", file=sys.stderr)


def emit(
    metric: str,
    value,
    note: str = "",
    error: str = "",
    against_baseline: bool = True,
) -> None:
    """The ONE JSON line the driver records. Every exit path goes through
    here so a transient failure can never erase the round's evidence
    again. against_baseline=False suppresses the ratio for measurements
    the 200 ms full-tick budget doesn't apply to (e.g. --host-only,
    whose device half is deliberately stubbed)."""
    backend_info = _backend_evidence()
    rec = {
        "metric": metric,
        "value": (round(value, 3) if value is not None else None),
        "unit": "ms",
        "vs_baseline": (
            round(BASELINE_MS / value, 3)
            if value and against_baseline
            else None
        ),
        # backend provenance stamped into the BENCH json itself (not
        # just the evidence sidecar): resolved backend, device
        # kind/count, jax version — no more silent "cpu" rows posing
        # as TPU trajectory
        "backend": backend_info.get("backend"),
        "device_kind": backend_info.get("device_kind"),
        "device_count": backend_info.get("device_count"),
        "jax_version": backend_info.get("jax_version"),
    }
    if note:
        rec["note"] = note
    if error:
        rec["error"] = error
    warn_cpu_fallback(backend_info)
    _write_evidence(rec)
    print(json.dumps(rec))


def build_inputs(
    pods: int, types: int, taints: int, labels: int, seed: int,
    affinity: float = 0.0, anti: float = 0.0,
):
    import jax.numpy as jnp

    from karpenter_tpu.ops.binpack import BinPackInputs

    rng = np.random.default_rng(seed)
    R = 3  # cpu, memory, pods
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
    forbidden = None
    if affinity > 0:
        # fraction `affinity` of pods carry required node affinity; as in
        # production, pods share a handful of distinct affinity shapes —
        # each shape is a prototype forbidden row over the groups (the
        # host-evaluated matchExpression verdicts)
        prototypes = rng.random((4, types)) < 0.3
        which = rng.integers(0, prototypes.shape[0], pods)
        forbidden = prototypes[which] & (rng.random((pods, 1)) < affinity)
    return BinPackInputs(
        pod_requests=jnp.asarray(req),
        pod_valid=jnp.ones((pods,), bool),
        pod_intolerant=jnp.asarray(intol),
        pod_required=jnp.asarray(required),
        group_allocatable=jnp.asarray(alloc),
        group_taints=jnp.asarray(group_taints),
        group_labels=jnp.asarray(group_labels),
        pod_group_forbidden=(
            None if forbidden is None else jnp.asarray(forbidden)
        ),
        pod_exclusive=(
            # fraction `anti` of pods carry hostname self-anti-affinity
            # (one replica per node): the encoder's pod_exclusive operand
            None
            if anti <= 0
            else jnp.asarray(rng.random(pods) < anti)
        ),
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
    import dataclasses

    import jax.numpy as jnp

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


# -- device-resident fleet state (make bench-resident) ------------------------


def _resident_world(pods: int, types: int, seed: int):
    """(cache, profiles, delta): a watch-fed pending-pod arena of `pods`
    DISTINCT shapes (the adversarial fleet — replicated workloads dedup
    away and make residency trivially cheap) over `types` group
    profiles, plus a private SnapshotDeltaCache. The REAL encode
    pipeline: churn events -> arena -> delta splice -> scatter plan."""
    from karpenter_tpu.api.core import Container, ObjectMeta, Pod, PodSpec
    from karpenter_tpu.api.core import PodStatus
    from karpenter_tpu.metrics.producers.pendingcapacity.encoder import (
        SnapshotDeltaCache,
    )
    from karpenter_tpu.store.columnar import PendingPodCache
    from karpenter_tpu.utils.quantity import Quantity

    rng = np.random.default_rng(seed)
    cache = PendingPodCache(store=None, capacity=2 * pods)

    def make_pod(name, cpu_millis):
        return Pod(
            metadata=ObjectMeta(name=name, namespace="default"),
            spec=PodSpec(containers=[Container(requests={
                "cpu": Quantity.parse(f"{cpu_millis}m"),
            })]),
            status=PodStatus(phase="Pending"),
        )

    for i in range(pods):
        p = make_pod(f"p{i}", 50 + i)  # every pod a distinct shape
        cache._upsert((p.metadata.namespace, p.metadata.name), p)
    profiles = []
    t_rng = np.random.default_rng(seed + 1)
    for t in range(types):
        cpu = float(t_rng.integers(2, 129))
        profiles.append((
            {"cpu": cpu, "memory": cpu * 4.0 * 1024**3, "pods": 110.0},
            {("pool", f"g{t}")},
            set(),
        ))
    return cache, profiles, SnapshotDeltaCache(), make_pod, rng


def _append_resident_row(path: str, record: dict) -> None:
    marker = "## Device-resident fleet state (make bench-resident)"
    header = (
        f"\n{marker}\n\n"
        "Steady-state tick latency with the device-resident fleet "
        "state ON vs OFF, interleaved over one watch-fed world (each "
        "tick: delta encode shared, then the SAME inputs dispatched "
        "through a resident-ON and a resident-OFF service back to back "
        "— drift cancels pairwise). Columns: churn ticks in the "
        "SHIPPED mode (scatter auto-gated to accelerator backends), "
        "unchanged-fleet ticks (the identity hit: zero encode, upload "
        "p50 ~0), and the forced-scatter mechanism speedup. "
        "HONEST READING on CPU: the \"device\" is host memory, so the "
        "scatter's copy-on-write cancels the memcpy upload it avoids "
        "(forced-scatter < 1x is expected there) and the auto gate "
        "keeps CPU on the hit/rebuild rungs; the transfer the scatter "
        "eliminates is the real host<->accelerator link.\n\n"
        "| Date | Backend | Pods x Types | Ticks | Churn p50 off/on "
        "(ms) | Churn speedup | Unchanged p50 off/on (ms) | Unchanged "
        "speedup | Unchanged upload p50 (ms) | Forced-scatter speedup "
        "(rows) |\n"
        "|---|---|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['pods']} x "
        f"{record['types']} | {record['ticks']} "
        f"| {record['solve_p50_off_ms']} / {record['solve_p50_on_ms']} "
        f"| {record['speedup']}x "
        f"| {record['unchanged_p50_off_ms']} / "
        f"{record['unchanged_p50_on_ms']} "
        f"| {record['unchanged_speedup']}x "
        f"| {record['unchanged_upload_p50_ms']} "
        f"| {record['scatter_speedup']}x "
        f"({record['scatter_rows_mean']}) |\n"
    )
    _append_table_row(path, marker, header, row)


def _resident_phase(  # lint: allow-complexity — one interleave arm per order flip + the unchanged-tick tail, each a couple of guards
    args, world, backend: str, scatter: str
) -> dict:
    """One interleaved resident-ON vs resident-OFF measurement phase
    over the shared churn world. `scatter` pins the ON service's
    scatter-rung gate ("auto" = the shipped default, "always" = force
    the changed-row scatter mechanism so its cost is measured even
    where the auto gate would hold). Parity is pinned every tick."""
    from karpenter_tpu.metrics.registry import GaugeRegistry
    from karpenter_tpu.solver import SolverService

    cache, profiles, delta, make_pod, rng, next_name = world
    svc_on = SolverService(registry=GaugeRegistry(), shard_threshold=0)
    svc_on._resident.scatter = scatter
    svc_off = SolverService(
        registry=GaugeRegistry(), shard_threshold=0, resident=False
    )
    on_ms, off_ms, scatter_rows, encode_ms = [], [], [], []

    def churn():
        cache._remove(
            ("default", f"p{int(rng.integers(0, next_name[0]))}")
        )
        p = make_pod(f"p{next_name[0]}", 50 + next_name[0])
        cache._upsert((p.metadata.namespace, p.metadata.name), p)
        next_name[0] += 1
        t0 = time.perf_counter()
        inputs = delta.encode(cache.snapshot(), profiles)
        encode_ms.append((time.perf_counter() - t0) * 1e3)
        return inputs

    def timed(svc, inputs):
        t0 = time.perf_counter()
        out = svc.solve(inputs, buckets=args.buckets, backend=backend)
        return (time.perf_counter() - t0) * 1e3, out

    try:
        for _ in range(5):  # warmup: compiles, first encodes, residency
            inputs = churn()
            timed(svc_on, inputs)
            timed(svc_off, inputs)
        for round_i in range(args.resident_ticks):
            inputs = churn()
            if round_i % 2 == 0:
                t_off, out_off = timed(svc_off, inputs)
                t_on, out_on = timed(svc_on, inputs)
            else:
                t_on, out_on = timed(svc_on, inputs)
                t_off, out_off = timed(svc_off, inputs)
            off_ms.append(t_off)
            on_ms.append(t_on)
            scatter_rows.append(svc_on._resident.last_scatter_rows)
            # parity pinned FIRST, every tick: resident == re-upload
            np.testing.assert_array_equal(
                np.asarray(out_on.assigned),
                np.asarray(out_off.assigned),
            )
            assert int(out_on.unschedulable) == int(
                out_off.unschedulable
            )
        # unchanged-fleet ticks: the SAME inputs object re-dispatches
        # against the resident buffers — zero encode, upload p50 ~0
        inputs = churn()
        timed(svc_on, inputs)
        timed(svc_off, inputs)
        hits_before = svc_on.stats.resident_hits
        unchanged_on, unchanged_off = [], []
        for _ in range(10):
            t_hit, _ = timed(svc_on, inputs)
            unchanged_on.append(t_hit)
            t_cold, _ = timed(svc_off, inputs)
            unchanged_off.append(t_cold)
        assert svc_on.stats.resident_hits - hits_before == 10
        uploads_on = list(svc_on._stages.get("upload", ()))
        uploads_off = list(svc_off._stages.get("upload", ()))
        stats = {
            "hits": svc_on.stats.resident_hits,
            "scatters": svc_on.stats.resident_scatters,
            "rebuilds": svc_on.stats.resident_rebuilds,
        }
    finally:
        svc_on.close()
        svc_off.close()
    p50_off = float(np.percentile(off_ms, 50))
    p50_on = float(np.percentile(on_ms, 50))
    return {
        "scatter_mode": scatter,
        "solve_p50_off_ms": round(p50_off, 3),
        "solve_p50_on_ms": round(p50_on, 3),
        "speedup": round(p50_off / p50_on, 2) if p50_on else None,
        "encode_p50_ms": round(float(np.percentile(encode_ms, 50)), 3),
        "scatter_rows_mean": int(np.mean(scatter_rows)),
        "upload_p50_off_ms": round(
            float(np.percentile(uploads_off, 50)), 4
        ) if uploads_off else None,
        "upload_p50_on_ms": round(
            float(np.percentile(uploads_on, 50)), 4
        ) if uploads_on else None,
        "unchanged_p50_on_ms": round(
            float(np.percentile(unchanged_on, 50)), 3
        ),
        "unchanged_p50_off_ms": round(
            float(np.percentile(unchanged_off, 50)), 3
        ),
        "unchanged_upload_p50_ms": round(
            float(np.percentile(uploads_on[-10:], 50)), 4
        ) if uploads_on else None,
        "solve_on_ms_raw": [round(t, 4) for t in on_ms],
        "solve_off_ms_raw": [round(t, 4) for t in off_ms],
        **stats,
    }


def run_resident(args, metric: str) -> None:
    """Device-resident fleet state: resident-ON vs resident-OFF over
    the identical churn-tick sequence, in the SHIPPED default mode
    (scatter auto-gated to accelerator backends) and with the scatter
    mechanism forced, plus the unchanged-tick identity-hit column
    (ISSUE 13 acceptance: honest note where the CPU transport floor
    mutes the win — on CPU "device" memory IS host memory, so the
    scatter's copy-on-write cancels the memcpy upload it avoids)."""
    import jax

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    backend = "xla" if args.backend in ("auto", "numpy") else args.backend
    cache, profiles, delta, make_pod, rng = _resident_world(
        args.pods, args.types, args.seed
    )
    world = (cache, profiles, delta, make_pod, rng, [args.pods])
    shipped = _resident_phase(args, world, backend, "auto")
    forced = _resident_phase(args, world, backend, "always")
    unchanged_speedup = (
        round(
            shipped["unchanged_p50_off_ms"]
            / shipped["unchanged_p50_on_ms"], 2,
        )
        if shipped["unchanged_p50_on_ms"]
        else None
    )
    record = {
        "config": f"{args.pods} pods x {args.types} types resident",
        "backend": jax.default_backend(),
        "pods": args.pods,
        "types": args.types,
        "ticks": args.resident_ticks,
        # headline: the shipped default
        "solve_p50_off_ms": shipped["solve_p50_off_ms"],
        "solve_p50_on_ms": shipped["solve_p50_on_ms"],
        "speedup": shipped["speedup"],
        "unchanged_p50_on_ms": shipped["unchanged_p50_on_ms"],
        "unchanged_p50_off_ms": shipped["unchanged_p50_off_ms"],
        "unchanged_speedup": unchanged_speedup,
        "unchanged_upload_p50_ms": shipped["unchanged_upload_p50_ms"],
        "encode_p50_ms": shipped["encode_p50_ms"],
        # the forced-scatter mechanism measurement
        "scatter_speedup": forced["speedup"],
        "scatter_rows_mean": forced["scatter_rows_mean"],
        "scatter_upload_p50_on_ms": forced["upload_p50_on_ms"],
        "upload_p50_off_ms": shipped["upload_p50_off_ms"],
        "hits": shipped["hits"],
        "rebuilds": shipped["rebuilds"],
        "scatters": forced["scatters"],
    }
    record_evidence(
        resident_shipped=shipped, resident_forced=forced,
        resident=record,
    )
    print(
        f"shipped: solve p50 off={record['solve_p50_off_ms']}ms "
        f"on={record['solve_p50_on_ms']}ms "
        f"speedup={record['speedup']}x | unchanged tick "
        f"{record['unchanged_p50_off_ms']}ms -> "
        f"{record['unchanged_p50_on_ms']}ms "
        f"({record['unchanged_speedup']}x, upload p50 "
        f"{record['unchanged_upload_p50_ms']}ms) | forced scatter "
        f"{record['scatter_speedup']}x @ {record['scatter_rows_mean']} "
        f"rows",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_resident_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        record["solve_p50_on_ms"],
        note=f"resident churn speedup {record['speedup']}x, "
        f"unchanged-tick {record['unchanged_speedup']}x (upload p50 "
        f"{record['unchanged_upload_p50_ms']}ms), forced-scatter "
        f"{record['scatter_speedup']}x on this backend",
        against_baseline=False,
    )


def main() -> None:  # lint: allow-complexity — bench config dispatch, one arm per measured configuration
    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=100_000)
    ap.add_argument("--types", type=int, default=300)
    ap.add_argument("--taints", type=int, default=64)
    ap.add_argument("--labels", type=int, default=64)
    ap.add_argument("--buckets", type=int, default=32)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--affinity", type=float, default=0.0,
        help="fraction of pods carrying required node affinity (adds the "
        "pod_group_forbidden [P, T] mask operand to the solve)",
    )
    ap.add_argument(
        "--anti", type=float, default=0.0,
        help="fraction of pods carrying hostname self-anti-affinity — "
        "one replica per node (adds the pod_exclusive [P] operand to "
        "the solve)",
    )
    ap.add_argument(
        "--spread", type=float, default=0.0,
        help="with --e2e: fraction of pods carrying a hard zone "
        "topologySpreadConstraint with a self-matching selector; nodes "
        "gain zone labels and a slab of BOUND pods churns each tick, so "
        "every measured tick pays the existing-pod occupancy census "
        "(DomainCensus) recompute on top of the split expansion",
    )
    ap.add_argument(
        "--backend",
        choices=("auto", "xla", "pallas", "numpy"),
        default="auto",
        help="auto = fused Pallas kernel on TPU, the numpy degraded-mode "
        "program on a CPU default backend, XLA elsewhere",
    )
    ap.add_argument(
        "--churn",
        type=int,
        default=-1,
        help="pods replaced per e2e tick through the store watch path "
        "(-1 = 1%% of --pods); keeps the e2e number honest: every tick "
        "pays incremental feed maintenance + re-encode + re-transfer",
    )
    ap.add_argument(
        "--slices",
        type=int,
        default=1,
        metavar="S",
        help="with --mesh: model S TPU slices (3D slice x pods x groups "
        "mesh; pod rows shard across slices, the one histogram reduction "
        "rides DCN)",
    )
    ap.add_argument(
        "--clusters",
        type=int,
        default=0,
        metavar="K",
        help="multi-cluster re-pack (BASELINE config 5): K clusters of "
        "--types node groups each; 70%% of pods pinned to their home "
        "cluster via required labels, 30%% free to re-pack across",
    )
    ap.add_argument(
        "--decide",
        type=int,
        default=0,
        metavar="N",
        help="benchmark the batched HPA decision kernel over a fleet of "
        "N autoscalers x 4 metrics instead of the bin-pack",
    )
    ap.add_argument(
        "--solver-service",
        action="store_true",
        help="benchmark the shared solve service (karpenter_tpu/solver): "
        "--concurrency threads submit concurrently through the coalescing "
        "queue vs. the same load on direct ops/binpack calls; reports both "
        "p50/p99 plus coalesce factor and dispatch counts",
    )
    ap.add_argument(
        "--concurrency",
        type=int,
        default=8,
        help="with --solver-service/--hotpath: concurrent submitter "
        "threads",
    )
    ap.add_argument(
        "--hotpath",
        action="store_true",
        help="benchmark the solver-service HOT PATH: idle-queue "
        "single-caller latency through the service vs a direct "
        "ops/binpack call (the adaptive-window acceptance ratio), the "
        "coalesce factor preserved under --concurrency concurrent "
        "callers, and the per-stage breakdown (queue-wait / pad / "
        "dispatch / scatter) from the service's latency rings",
    )
    ap.add_argument(
        "--consolidate",
        action="store_true",
        help="benchmark batched consolidation candidate evaluation "
        "(karpenter_tpu/consolidation): --candidates drain candidates "
        "evaluated in ONE service.consolidate dispatch vs. the same "
        "masked bin-packs submitted sequentially; reports candidates/sec "
        "both ways and the speedup",
    )
    ap.add_argument(
        "--candidates",
        type=int,
        default=32,
        help="with --consolidate: cluster nodes (every loaded node is a "
        "drain candidate); --pods spread across them",
    )
    ap.add_argument(
        "--preempt",
        action="store_true",
        help="benchmark batched eviction planning (ops/preempt.py via "
        "service.preempt): --candidates pending pods planned against "
        "--types node columns x --pods victims in ONE dispatch vs. the "
        "same plans submitted one candidate at a time; reports "
        "candidates/sec both ways and the speedup",
    )
    ap.add_argument(
        "--forecast",
        action="store_true",
        help="benchmark the batched forecast kernel "
        "(karpenter_tpu/forecast): --series metric series forecast in "
        "ONE device dispatch vs the same series dispatched one at a "
        "time; reports series/sec both ways and the speedup",
    )
    ap.add_argument(
        "--series",
        type=int,
        default=512,
        help="with --forecast: number of metric series in the fleet",
    )
    ap.add_argument(
        "--history",
        type=int,
        default=64,
        help="with --forecast: history samples per series",
    )
    ap.add_argument(
        "--journal",
        action="store_true",
        help="benchmark protective-state journal overhead on the "
        "reconcile hot path (karpenter_tpu/recovery): the same seeded "
        "world ticks with the journal ON vs OFF (target: <5%% tick-"
        "latency regression), plus raw StateJournal.append throughput",
    )
    ap.add_argument(
        "--journal-ticks",
        type=int,
        default=40,
        help="with --journal: measured manager ticks per configuration",
    )
    ap.add_argument(
        "--trace",
        action="store_true",
        help="benchmark reconcile-tracing overhead on the hot path "
        "(karpenter_tpu/observability): the same seeded world ticks "
        "with the tracer ENABLED vs DISABLED (target: <5%% tick-"
        "latency regression), plus raw span open/close throughput",
    )
    ap.add_argument(
        "--trace-ticks",
        type=int,
        default=40,
        help="with --trace: measured manager ticks per configuration",
    )
    ap.add_argument(
        "--provenance",
        action="store_true",
        help="benchmark decision-provenance overhead on the reconcile "
        "hot path (karpenter_tpu/observability/provenance.py): the "
        "same seeded world ticks with the ledger ENABLED vs DISABLED "
        "interleaved (target: <=5%% tick-latency regression), plus raw "
        "batch-commit throughput",
    )
    ap.add_argument(
        "--provenance-ticks",
        type=int,
        default=200,
        help="with --provenance: measured manager ticks per "
        "configuration",
    )
    ap.add_argument(
        "--cost",
        action="store_true",
        help="benchmark the batched multi-objective cost/SLO refinement "
        "(ops/cost.py via karpenter_tpu/cost): --cost-rows autoscalers "
        "refined in ONE device dispatch vs the same rows dispatched one "
        "HA at a time; pins XLA == numpy bit-parity on every output "
        "before timing; reports rows/sec both ways and the speedup",
    )
    ap.add_argument(
        "--cost-rows",
        type=int,
        default=512,
        help="with --cost: SLO-opted autoscaler rows in the fleet",
    )
    ap.add_argument(
        "--cost-metrics",
        type=int,
        default=3,
        help="with --cost: metrics per autoscaler row",
    )
    ap.add_argument(
        "--poolgroup",
        action="store_true",
        help="benchmark the joint pool-group allocation "
        "(ops/poolgroup.py via karpenter_tpu/poolgroups): "
        "--poolgroup-groups groups' candidate ladders scored in ONE "
        "batched dispatch vs the groups*pools per-pool cost dispatches "
        "the joint plane replaces; pins XLA == numpy bit-parity on "
        "every output leaf AND joint == per-pool cost ladder under "
        "slack constraints before timing",
    )
    ap.add_argument(
        "--poolgroup-groups",
        type=int,
        default=64,
        help="with --poolgroup: pool groups in the fleet",
    )
    ap.add_argument(
        "--poolgroup-pools",
        type=int,
        default=4,
        help="with --poolgroup: member pools per group (2..4)",
    )
    ap.add_argument(
        "--poolgroup-metrics",
        type=int,
        default=3,
        help="with --poolgroup: metrics per member pool",
    )
    ap.add_argument(
        "--multitenant",
        action="store_true",
        help="benchmark the multi-tenant control plane "
        "(docs/multitenancy.md): --tenants simulated tenant clusters' "
        "decide+cost matrices through ONE MultiTenantScheduler "
        "(cross-tenant concatenated dispatches) vs a sequential "
        "per-tenant loop through the same SolverService seam; "
        "cross-tenant == independent parity is pinned on the device "
        "AND numpy paths before timing",
    )
    ap.add_argument(
        "--tenants",
        type=int,
        default=1000,
        help="with --multitenant: simulated tenant cluster count",
    )
    ap.add_argument(
        "--tenant-rows",
        type=int,
        default=4,
        help="with --multitenant: autoscaler rows per tenant cluster",
    )
    ap.add_argument(
        "--tenant-metrics",
        type=int,
        default=2,
        help="with --multitenant: metrics per autoscaler row",
    )
    ap.add_argument(
        "--shard",
        action="store_true",
        help="benchmark the SHARDED dispatch strategy (docs/solver-"
        "service.md 'Sharded dispatch'): one fleet-scale bin-pack "
        "(--pods x --types) through the SolverService seam on meshes of "
        "--shard-scaling device counts (fails when fewer devices are "
        "present), with sharded outputs pinned against the "
        "single-device and numpy paths before timing",
    )
    ap.add_argument(
        "--shard-scaling",
        default="1,2,4,8",
        help="with --shard: comma-separated mesh device counts; 1 = the "
        "single-device baseline through the same service seam",
    )
    ap.add_argument(
        "--constraints",
        action="store_true",
        help="benchmark the declarative constraint plane "
        "(docs/constraints.md): ONE batched constrained solve (spread "
        "+ reservation + anti-affinity + compact groups as masked "
        "integer operands) vs the per-group sequential loop a "
        "constraint-naive integration would run, interleaved arms, "
        "with per-group verdict parity pinned before timing",
    )
    ap.add_argument(
        "--constraint-groups",
        type=int,
        default=8,
        help="with --constraints: constraint group count (cycling "
        "spread/reservation/anti/compact kinds)",
    )
    ap.add_argument(
        "--publish-baseline",
        action="store_true",
        help="with --solver-service: write the result into BASELINE.json's "
        "'published' map",
    )
    ap.add_argument(
        "--append-benchmarks",
        default="",
        metavar="FILE",
        help="with --solver-service: append a markdown row to this "
        "benchmarks table (e.g. docs/BENCHMARKS.md)",
    )
    ap.add_argument(
        "--resident",
        action="store_true",
        help="benchmark the device-resident fleet state: churn-tick "
        "solve latency with residency ON (changed-row scatter) vs OFF "
        "(full re-upload) interleaved over one watch-fed world, plus "
        "the unchanged-tick identity-hit column "
        "(docs/solver-service.md 'Device-resident fleet state')",
    )
    ap.add_argument(
        "--resident-ticks",
        type=int,
        default=60,
        help="with --resident: measured churn ticks per configuration",
    )
    ap.add_argument(
        "--introspect",
        action="store_true",
        help="benchmark the solver introspection plane "
        "(docs/observability.md 'Device telemetry & introspection'): "
        "reconcile tick latency with the compile ledger + device "
        "memory telemetry + XLA cost attribution ENABLED vs DISABLED, "
        "interleaved over the shared churn world (the bench-trace "
        "discipline; target <=2% median paired overhead)",
    )
    ap.add_argument(
        "--introspect-ticks",
        type=int,
        default=200,
        help="with --introspect: measured ticks per configuration",
    )
    ap.add_argument(
        "--eventloop",
        action="store_true",
        help="benchmark the event-driven reconcile loop: the seeded "
        "pod-arrival trace replayed tick-paced vs event-driven "
        "(simulate.simulate_eventloop), reporting e2e p50/p99 off the "
        "karpenter_reconcile_e2e_seconds histogram, the solve-"
        "amplification factor, and the churn-storm coalescing proof "
        "(docs/solver-service.md 'Event-driven reconcile')",
    )
    ap.add_argument(
        "--eventloop-ticks",
        type=int,
        default=40,
        help="with --eventloop: backstop ticks per replayed arm",
    )
    ap.add_argument(
        "--eventloop-arrivals",
        type=int,
        default=60,
        help="with --eventloop: seeded pod arrivals in the trace",
    )
    ap.add_argument(
        "--eventloop-storm",
        type=int,
        default=1000,
        help="with --eventloop: churn-storm events in one debounce "
        "window",
    )
    ap.add_argument(
        "--eventloop-debounce",
        type=float,
        default=0.05,
        help="with --eventloop: replayed event-pass debounce window "
        "seconds",
    )
    ap.add_argument(
        "--simlab",
        action="store_true",
        help="benchmark SimLab batched cluster stepping "
        "(docs/simulator.md): N independently-seeded simulated "
        "clusters advanced as ONE vmapped sim_rollout dispatch through "
        "the SolverService seam vs the per-cluster sequential loop a "
        "simulator-naive harness would run (N dispatches of the same "
        "compiled program); batched == sequential == numpy parity "
        "pinned bitwise before timing",
    )
    ap.add_argument(
        "--simlab-clusters",
        type=int,
        default=256,
        help="with --simlab: simulated clusters per batched dispatch",
    )
    ap.add_argument(
        "--simlab-ticks",
        type=int,
        default=64,
        help="with --simlab: episode length in ticks per cluster",
    )
    ap.add_argument(
        "--simlab-rows",
        type=int,
        default=8,
        help="with --simlab: HA rows (replica columns) per cluster",
    )
    ap.add_argument(
        "--fusedtick",
        action="store_true",
        help="benchmark the fused steady-state tick "
        "(docs/solver-service.md 'Fused tick'): the whole fleet's "
        "forecast -> decide -> cost ladder as ONE compiled program "
        "through SolverService.fused_tick vs the chained per-stage "
        "wire (one program per stage + host glue between); fused == "
        "chained == numpy pinned bitwise before timing, plus the "
        "--fused-tick dispatches-per-tick collapse over the shared "
        "churn-runtime world",
    )
    ap.add_argument(
        "--fusedtick-rows",
        type=int,
        default=256,
        help="with --fusedtick: autoscaler rows per fleet batch",
    )
    ap.add_argument(
        "--fusedtick-metrics",
        type=int,
        default=3,
        help="with --fusedtick: metric columns per autoscaler row",
    )
    ap.add_argument(
        "--fusedtick-series",
        type=int,
        default=128,
        help="with --fusedtick: forecast series scattered into the "
        "fleet grid",
    )
    ap.add_argument(
        "--fusedtick-samples",
        type=int,
        default=32,
        help="with --fusedtick: history samples per forecast series",
    )
    ap.add_argument(
        "--fusedtick-ticks",
        type=int,
        default=40,
        help="with --fusedtick: timed reconcile ticks per runtime arm "
        "(the dispatches-per-tick observable)",
    )
    ap.add_argument(
        "--failover",
        action="store_true",
        help="benchmark replicated-control-plane failover "
        "(karpenter_tpu/replication): the seeded leader-kill world at "
        "fleet scale — kill the biggest owner mid-storm, measure the "
        "handoff blackout (ticks from kill to every victim tenant back "
        "at its desired level) and audit exactly-once actuation across "
        "the handoff",
    )
    ap.add_argument(
        "--failover-tenants",
        type=int,
        default=256,
        help="with --failover: tenants partitioned across the replicas",
    )
    ap.add_argument(
        "--failover-replicas",
        type=int,
        default=4,
        help="with --failover: solver replicas contending for partitions",
    )
    ap.add_argument(
        "--failover-partitions",
        type=int,
        default=16,
        help="with --failover: tenant partitions (lease granularity)",
    )
    ap.add_argument(
        "--failover-ticks",
        type=int,
        default=40,
        help="with --failover: total simulated ticks (kill at tick 12)",
    )
    ap.add_argument(
        "--e2e",
        action="store_true",
        help="headline the full reconcile tick (columnar-cache snapshot + "
        "encode + host->device transfer + solve) instead of the solver",
    )
    ap.add_argument(
        "--host-only",
        action="store_true",
        help="with --e2e: swap the device solve for a shape-correct no-op "
        "so the tick measures ONLY the host half (store churn + watch "
        "fan-out + profiles + snapshot + dedup encode + status/gauge "
        "writes) — the docs/BENCHMARKS.md host-path number",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=0,
        metavar="N",
        help="run the solve sharded over an N-device pods x groups mesh "
        "(fails when fewer than N devices are present), assert "
        "equality with the single-device solve, report the sharded p50",
    )
    args = ap.parse_args()

    if args.clusters and (args.mesh or args.e2e or args.decide):
        ap.error(
            "--clusters models its own workload (BASELINE config 5) and "
            "cannot combine with --mesh/--e2e/--decide; run it standalone"
        )
    if args.affinity and (args.clusters or args.decide):
        ap.error(
            "--affinity applies to the solver bench, --mesh, and --e2e; "
            "--clusters/--decide build their own workloads"
        )
    if not 0.0 <= args.affinity <= 1.0:
        ap.error("--affinity must be a fraction in [0, 1]")
    if args.anti and (args.clusters or args.decide or args.mesh):
        ap.error(
            "--anti applies to the solver bench and --e2e (which builds "
            "real podAntiAffinity specs); --clusters/--decide/--mesh "
            "build their own workloads"
        )
    if not 0.0 <= args.anti <= 1.0:
        ap.error("--anti must be a fraction in [0, 1]")
    if args.spread and not args.e2e:
        ap.error("--spread applies to --e2e only (it builds real "
                 "topologySpreadConstraint specs + bound-pod occupancy)")
    if not 0.0 <= args.spread <= 1.0:
        ap.error("--spread must be a fraction in [0, 1]")
    if args.slices < 1:
        ap.error("--slices must be >= 1")
    if args.slices > 1 and not args.mesh:
        ap.error("--slices requires --mesh")
    if args.slices > 1 and args.mesh % args.slices:
        ap.error(
            f"--mesh {args.mesh} not divisible into --slices {args.slices}"
        )
    if args.host_only and not args.e2e:
        ap.error("--host-only only applies to --e2e")
    if args.solver_service and (
        args.mesh or args.e2e or args.decide or args.clusters
    ):
        ap.error(
            "--solver-service benchmarks the service front door on the "
            "plain solver workload; it cannot combine with "
            "--mesh/--e2e/--decide/--clusters"
        )
    if args.hotpath and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.consolidate
    ):
        ap.error(
            "--hotpath benchmarks the service hot path on the plain "
            "solver workload; it cannot combine with other modes"
        )
    if args.consolidate and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service
    ):
        ap.error(
            "--consolidate builds its own cluster workload; it cannot "
            "combine with --mesh/--e2e/--decide/--clusters/"
            "--solver-service"
        )
    if args.candidates < 2:
        ap.error("--candidates must be >= 2 (a drain needs a receiver)")
    if args.concurrency < 1:
        ap.error("--concurrency must be >= 1")
    if args.forecast and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
    ):
        ap.error(
            "--forecast builds its own workload (metric histories); it "
            "cannot combine with other modes"
        )
    if args.preempt and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast
    ):
        ap.error(
            "--preempt builds its own workload (candidates x nodes x "
            "victims); it cannot combine with other modes"
        )
    if args.series < 2:
        ap.error("--series must be >= 2")
    if args.history < 4:
        ap.error("--history must be >= 4")
    if args.journal and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt
    ):
        ap.error(
            "--journal builds its own ticking world; it cannot combine "
            "with other modes"
        )
    if args.trace and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.shard
    ):
        ap.error(
            "--trace builds its own ticking world; it cannot combine "
            "with other modes"
        )
    if args.provenance and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.multitenant or args.cost
    ):
        ap.error(
            "--provenance builds its own ticking world; it cannot "
            "combine with other modes"
        )
    if args.cost and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.provenance
    ):
        ap.error(
            "--cost builds its own workload (SLO-opted fleet rows); it "
            "cannot combine with other modes"
        )
    if args.cost_rows < 2:
        ap.error("--cost-rows must be >= 2")
    if args.cost_metrics < 1:
        ap.error("--cost-metrics must be >= 1")
    if args.poolgroup and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.provenance or args.cost
    ):
        ap.error(
            "--poolgroup builds its own workload (a fleet of pool "
            "groups); it cannot combine with other modes"
        )
    if args.poolgroup_groups < 1:
        ap.error("--poolgroup-groups must be >= 1")
    if not 2 <= args.poolgroup_pools <= 4:
        ap.error("--poolgroup-pools must be in 2..4")
    if args.poolgroup_metrics < 1:
        ap.error("--poolgroup-metrics must be >= 1")
    if args.multitenant and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.cost or args.shard
    ):
        ap.error(
            "--multitenant builds its own workload (N tenant fleets); "
            "it cannot combine with other modes"
        )
    if args.multitenant and args.tenants < 2:
        ap.error("--tenants must be >= 2")
    if args.multitenant and (
        args.tenant_rows < 1 or args.tenant_metrics < 1
    ):
        ap.error("--tenant-rows and --tenant-metrics must be >= 1")
    if args.shard and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal
    ):
        ap.error(
            "--shard benchmarks the service's sharded dispatch on the "
            "plain solver workload; it cannot combine with other modes"
        )
    if args.shard:
        try:
            scaling = [int(n) for n in args.shard_scaling.split(",")]
        except ValueError:
            ap.error(f"--shard-scaling {args.shard_scaling!r}: expected "
                     "comma-separated device counts")
        if not scaling or any(n < 1 for n in scaling):
            ap.error("--shard-scaling device counts must be >= 1")
        args.shard_scaling = scaling
    if args.resident and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.cost or args.multitenant or args.provenance
    ):
        ap.error(
            "--resident builds its own watch-fed churn world; it cannot "
            "combine with other modes"
        )
    if args.resident and args.resident_ticks < 4:
        ap.error("--resident-ticks must be >= 4")
    if args.eventloop and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.cost or args.multitenant
        or args.provenance or args.resident
    ):
        ap.error(
            "--eventloop replays its own two-arm arrival trace; it "
            "cannot combine with other modes"
        )
    if args.introspect and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.cost or args.multitenant
        or args.provenance or args.resident or args.eventloop
    ):
        ap.error(
            "--introspect builds its own ticking world; it cannot "
            "combine with other modes"
        )
    if args.introspect and args.introspect_ticks < 4:
        ap.error("--introspect-ticks must be >= 4")
    if args.eventloop and (
        args.eventloop_ticks < 4 or args.eventloop_arrivals < 1
        or args.eventloop_storm < 1 or args.eventloop_debounce <= 0
    ):
        ap.error(
            "--eventloop needs ticks >= 4, arrivals/storm >= 1, "
            "debounce > 0"
        )
    if args.constraints and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.cost or args.multitenant
        or args.provenance or args.resident or args.eventloop
        or args.introspect
    ):
        ap.error(
            "--constraints builds its own constrained workload; it "
            "cannot combine with other modes"
        )
    if args.constraints and args.constraint_groups < 1:
        ap.error("--constraint-groups must be >= 1")
    if args.simlab and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.cost or args.multitenant
        or args.provenance or args.resident or args.eventloop
        or args.introspect or args.constraints
    ):
        ap.error(
            "--simlab builds its own simulated-cluster workload; it "
            "cannot combine with other modes"
        )
    if args.simlab and (
        args.simlab_clusters < 2 or args.simlab_ticks < 4
        or args.simlab_rows < 1
    ):
        ap.error(
            "--simlab needs clusters >= 2, ticks >= 4, rows >= 1"
        )
    if args.fusedtick and (
        args.mesh or args.e2e or args.decide or args.clusters
        or args.solver_service or args.hotpath or args.consolidate
        or args.forecast or args.preempt or args.journal or args.trace
        or args.shard or args.cost or args.multitenant
        or args.provenance or args.resident or args.eventloop
        or args.introspect or args.constraints or args.simlab
        or args.failover
    ):
        ap.error(
            "--fusedtick builds its own fleet-batch workload; it "
            "cannot combine with other modes"
        )
    if args.fusedtick and (
        args.fusedtick_rows < 2 or args.fusedtick_metrics < 1
        or args.fusedtick_series < 1 or args.fusedtick_samples < 4
        or args.fusedtick_ticks < 4
    ):
        ap.error(
            "--fusedtick needs rows >= 2, metrics >= 1, series >= 1, "
            "samples >= 4, ticks >= 4"
        )
    if (args.publish_baseline or args.append_benchmarks) and not (
        args.solver_service or args.consolidate or args.hotpath
        or args.forecast or args.preempt or args.journal or args.shard
        or args.trace or args.cost or args.poolgroup
        or args.multitenant
        or args.provenance or args.resident or args.eventloop
        or args.introspect or args.constraints or args.simlab
        or args.fusedtick or args.failover
    ):
        ap.error(
            "--publish-baseline/--append-benchmarks only apply to "
            "--solver-service/--consolidate/--hotpath/--forecast/"
            "--preempt/--journal/--shard/--trace/--cost/--poolgroup/"
            "--multitenant/"
            "--provenance/--resident/--eventloop/--introspect/"
            "--constraints/--simlab/--fusedtick/--failover (nothing "
            "would be published otherwise)"
        )

    if args.fusedtick:
        metric = (
            f"fused steady-state tick p50, {args.fusedtick_rows} "
            f"autoscalers x {args.fusedtick_metrics} metrics x "
            f"{args.fusedtick_series} forecast series (one fused "
            f"forecast->decide->cost program vs the chained per-stage "
            f"wire, interleaved; bitwise parity pinned)"
        )
    elif args.failover:
        metric = (
            f"failover handoff blackout p99, {args.failover_tenants} "
            f"tenants x {args.failover_replicas} replicas over "
            f"{args.failover_partitions} partitions (leader killed "
            f"mid-storm; exactly-once actuation journal-audited)"
        )
    elif args.simlab:
        metric = (
            f"vmapped batched cluster-stepping p50, "
            f"{args.simlab_clusters} clusters x {args.simlab_ticks} "
            f"ticks x {args.simlab_rows} rows (one sim_rollout "
            f"dispatch vs the per-cluster sequential loop; numpy "
            f"parity pinned)"
        )
    elif args.constraints:
        metric = (
            f"batched constrained solve p50, {args.pods} pods x "
            f"{args.types} instance types x {args.constraint_groups} "
            f"constraint groups (one masked-operand dispatch vs the "
            f"per-group sequential loop, interleaved)"
        )
    elif args.introspect:
        metric = (
            f"reconcile tick p50 with the solver introspection plane, "
            f"{args.introspect_ticks} ticks (compile ledger + device "
            f"telemetry + cost attribution ENABLED vs DISABLED)"
        )
    elif args.eventloop:
        metric = (
            f"watch-event -> actuation e2e p99 with event-driven "
            f"reconcile, {args.eventloop_arrivals} arrivals x "
            f"{args.eventloop_ticks} ticks (event passes vs tick-paced "
            f"on one seeded trace; {args.eventloop_storm}-event churn "
            f"storm coalesced)"
        )
    elif args.resident:
        metric = (
            f"churn-tick solve p50 with the device-resident fleet "
            f"state, {args.pods} pods x {args.types} types, "
            f"{args.resident_ticks} ticks (resident scatter ON vs full "
            f"re-upload OFF, parity pinned every tick)"
        )
    elif args.shard:
        metric = (
            f"sharded fleet solve p50 through the SolverService seam, "
            f"{args.pods} pods x {args.types} instance types over "
            f"{max(args.shard_scaling)}-device mesh (device-count "
            f"scaling {args.shard_scaling}; sharded == single-device "
            f"== numpy pinned)"
        )
    elif args.journal:
        metric = (
            f"reconcile tick p50 with the protective-state journal, "
            f"{args.journal_ticks} ticks (journal ON vs OFF + raw "
            f"append throughput)"
        )
    elif args.trace:
        metric = (
            f"reconcile tick p50 with reconcile tracing, "
            f"{args.trace_ticks} ticks (tracer ENABLED vs DISABLED + "
            f"raw span throughput)"
        )
    elif args.provenance:
        metric = (
            f"reconcile tick p50 with the decision-provenance ledger, "
            f"{args.provenance_ticks} ticks (ledger ENABLED vs "
            f"DISABLED + raw batch-commit throughput)"
        )
    elif args.multitenant:
        metric = (
            f"multi-tenant aggregate decisions/sec, {args.tenants} "
            f"tenant clusters x {args.tenant_rows} autoscalers "
            f"(cross-tenant concatenated decide+cost vs sequential "
            f"per-tenant loop; concat == independent parity pinned)"
        )
    elif args.poolgroup:
        metric = (
            f"joint pool-group allocation p50, "
            f"{args.poolgroup_groups} groups x {args.poolgroup_pools} "
            f"pools (one batched dispatch vs the per-pool cost "
            f"dispatches it replaces; numpy + cost-ladder parity "
            f"pinned)"
        )
    elif args.cost:
        metric = (
            f"batched multi-objective cost/SLO refine p50, "
            f"{args.cost_rows} autoscalers x {args.cost_metrics} "
            f"metrics (one dispatch vs per-HA loop; numpy parity "
            f"pinned)"
        )
    elif args.preempt:
        metric = (
            f"batched eviction-planning p50, {args.candidates} "
            f"candidates x {args.types} node columns x {args.pods} "
            f"victims (one dispatch vs per-candidate loop)"
        )
    elif args.forecast:
        metric = (
            f"batched metric forecast p50, {args.series} series x "
            f"{args.history} history samples (Holt-Winters + robust "
            f"linear, one dispatch vs per-series loop)"
        )
    elif args.hotpath:
        metric = (
            f"solver-service idle-queue bin-pack p50 latency, "
            f"{args.pods} pods x {args.types} instance types "
            f"(vs direct; coalesce preserved at "
            f"{args.concurrency} callers)"
        )
    elif args.solver_service:
        metric = (
            f"solver-service coalesced bin-pack p50 latency, {args.pods} "
            f"pods x {args.types} instance types, {args.concurrency} "
            f"concurrent callers"
        )
    elif args.consolidate:
        metric = (
            f"batched consolidation candidate evaluation p50, "
            f"{args.candidates} drain candidates x {args.pods} bound "
            f"pods (one masked bin-pack per candidate, one dispatch)"
        )
    elif args.decide:
        metric = (
            f"batched HPA decision kernel p50 latency, fleet of "
            f"{args.decide} autoscalers x 4 metrics (recommendation + "
            f"select policy + stabilization + rate-limit policies + bounds)"
        )
    elif args.mesh:
        shape = (
            f"{args.slices}-slice x pods x groups"
            if args.slices > 1
            else "pods x groups"
        )
        metric = (
            f"sharded bin-pack p50 latency over a {args.mesh}-device "
            f"{shape} mesh, {args.pods} pods x {args.types} "
            f"instance types (outputs == single-device)"
        )
    elif args.e2e:
        metric = (
            f"end-to-end reconcile tick p50, {args.pods} pods x "
            f"{args.types} node groups (full solve_pending: profile"
            f" + snapshot + encode + transfer + solve + status)"
        )
    elif args.clusters:
        metric = (
            f"multi-cluster re-pack p50 latency, {args.pods} pods across "
            f"{args.clusters} clusters x {args.types} instance types each "
            f"(70% cluster-pinned, 30% flexible)"
        )
    else:
        metric = (
            f"pending-pods bin-pack p50 latency, "
            f"{args.pods} pods x {args.types} instance types"
        )
    if args.affinity:
        # distinct metric key: affinity-constrained runs must never mix
        # into the unconstrained series when aggregated by metric name
        metric += f", {args.affinity:.0%} pods with node affinity"
    if args.anti:
        metric += f", {args.anti:.0%} pods one-per-node"
    if args.spread:
        metric += (
            f", {args.spread:.0%} pods zone-spread w/ occupancy census"
        )
    try:
        # the TPU, or JAX_PLATFORMS=cpu on purpose: never a quiet CPU run
        from karpenter_tpu.utils.backend import (
            arm_compile_cache,
            require_backend,
        )

        arm_compile_cache()
        require_backend()
        if args.mesh:
            run_mesh(args, metric)
            return
        if args.shard:
            run_shard(args, metric)
            return
        run(args, metric)
    except Exception as e:  # noqa: BLE001 — the JSON error line, then rc 1
        import traceback

        traceback.print_exc()
        emit(metric, None, error=f"{type(e).__name__}: {e}"[:300])
        sys.exit(1)


def _warm_native_kernel(args) -> None:
    """Block on the C kernel build before ANY dispatch (incl. --e2e/
    --decide) and outside every timed region — the async production path
    would otherwise leave early measured iterations on the numpy
    fallback (like jit warmup, one-time setup is excluded from the
    measurement)."""
    import jax

    if jax.default_backend() == "cpu" and args.backend in ("auto", "numpy"):
        from karpenter_tpu.native import load_kbinpack

        if load_kbinpack() is None:
            print("native kernel unavailable: numpy stages", file=sys.stderr)


def _bench_inputs(args):
    if args.clusters:
        return build_multicluster_inputs(
            args.pods, args.clusters, args.types,
            max(args.taints, 8), max(args.labels, args.clusters + 8),
            args.seed,
        )
    return build_inputs(
        args.pods, args.types, args.taints, args.labels, args.seed,
        affinity=args.affinity, anti=args.anti,
    )


def _journal_world(runtime):
    """The chaos-suite world: one profiled node group, one pending pod,
    an SNG, and a queue-metric HA — every tick drives an encode + solve
    + decide + status writes, i.e. the real reconcile hot path the
    journal must not slow down."""
    from karpenter_tpu.api.core import (
        Node, NodeCondition, NodeSpec, NodeStatus, ObjectMeta, Pod,
        PodSpec, resource_list,
    )
    from karpenter_tpu.api.horizontalautoscaler import (
        Behavior, CrossVersionObjectReference, HorizontalAutoscaler,
        HorizontalAutoscalerSpec, Metric, MetricTarget,
        PrometheusMetricSource, ScalingRules,
    )
    from karpenter_tpu.api.metricsproducer import (
        MetricsProducer, MetricsProducerSpec, PendingCapacitySpec,
    )
    from karpenter_tpu.api.scalablenodegroup import (
        ScalableNodeGroup, ScalableNodeGroupSpec,
    )

    store = runtime.store
    store.create(Node(
        metadata=ObjectMeta(name="n1", labels={"pool": "a"}),
        spec=NodeSpec(),
        status=NodeStatus(
            allocatable=resource_list(cpu="8", memory="16Gi", pods="16"),
            conditions=[NodeCondition("Ready", "True")],
        ),
    ))
    store.create(Pod(metadata=ObjectMeta(name="p1"), spec=PodSpec()))
    store.create(MetricsProducer(
        metadata=ObjectMeta(name="pending"),
        spec=MetricsProducerSpec(
            pending_capacity=PendingCapacitySpec(
                node_selector={"pool": "a"}, node_group_ref="g",
            )
        ),
    ))
    store.create(ScalableNodeGroup(
        metadata=ObjectMeta(name="g"),
        spec=ScalableNodeGroupSpec(
            replicas=3, type="FakeNodeGroup", id="g"
        ),
    ))
    store.create(HorizontalAutoscaler(
        metadata=ObjectMeta(name="ha"),
        spec=HorizontalAutoscalerSpec(
            scale_target_ref=CrossVersionObjectReference(
                kind="ScalableNodeGroup", name="g"
            ),
            min_replicas=1, max_replicas=100,
            metrics=[Metric(prometheus=PrometheusMetricSource(
                query='karpenter_queue_length{name="q"}',
                target=MetricTarget(type="AverageValue", value=4),
            ))],
            # no scale-down hold: the churn tick toggles the queue
            # metric (see _churn_runtime) so every tick actuates and
            # the karpenter_reconcile_e2e_seconds histogram fills —
            # the lead-time surface bench-journal publishes
            behavior=Behavior(
                scale_down=ScalingRules(stabilization_window_seconds=0)
            ),
        ),
    ))
    runtime.registry.register("queue", "length").set("q", "default", 12.0)


def _churn_runtime(journal_dir=None, **options_kw):
    """The seeded churn world both overhead benches (--journal and
    --trace-overhead) measure: a consolidating runtime over
    _journal_world with a tick() that toggles a churn pod so the encode
    memo misses and every tick pays a real solve. Their overhead
    percentages sit side by side in BASELINE.json against the same
    ~4ms tick, so both MUST measure this exact world. Returns
    (runtime, tick); the caller owns runtime.close()."""
    from karpenter_tpu.api.core import ObjectMeta, Pod, PodSpec
    from karpenter_tpu.cloudprovider.fake import FakeFactory
    from karpenter_tpu.runtime import KarpenterRuntime, Options

    clock = {"now": 1_000_000.0}
    provider = FakeFactory()
    provider.node_replicas["g"] = 3
    opts = dict(consolidate=True, journal_dir=journal_dir)
    opts.update(options_kw)
    runtime = KarpenterRuntime(
        Options(**opts),
        cloud_provider_factory=provider,
        clock=lambda: clock["now"],
    )
    _journal_world(runtime)
    queue_gauge = runtime.registry.gauge("queue", "length")
    flip = {"high": False}

    def tick():
        try:
            runtime.store.delete("Pod", "default", "churn-pod")
        except KeyError:
            runtime.store.create(
                Pod(metadata=ObjectMeta(name="churn-pod"), spec=PodSpec())
            )
        # toggle the decision signal so every tick carries a REAL
        # actuation (desired 3 <-> 5): the provider write path and the
        # e2e lead-time histogram are part of the tick both overhead
        # benches claim to measure
        flip["high"] = not flip["high"]
        queue_gauge.set("q", "default", 20.0 if flip["high"] else 12.0)
        clock["now"] += 61.0
        runtime.manager.reconcile_all()

    return runtime, tick


def _journal_tick_times(args, journal_dir):
    """(per-tick wall times, e2e lead-time percentiles) for one
    configuration (journal on/off) over the identical seeded world.
    The e2e numbers come from the PR 9 karpenter_reconcile_e2e_seconds
    histogram the churn world's per-tick actuations fill — the
    provisioning-lead observable warm pools attack (docs/cost.md)."""
    runtime, tick = _churn_runtime(journal_dir)

    times = []
    try:
        for _ in range(5):  # warmup: compiles, first encodes
            tick()
        for _ in range(args.journal_ticks):
            t0 = time.perf_counter()
            tick()
            times.append((time.perf_counter() - t0) * 1e3)
        hist = runtime.registry.gauge("reconcile", "e2e_seconds")
        e2e = {
            "e2e_p50_ms": round(
                (hist.percentile("ScalableNodeGroup", "-", 50) or 0.0)
                * 1e3, 3,
            ),
            "e2e_p99_ms": round(
                (hist.percentile("ScalableNodeGroup", "-", 99) or 0.0)
                * 1e3, 3,
            ),
            "e2e_samples": hist.count("ScalableNodeGroup", "-"),
        }
    finally:
        runtime.close()
    return times, e2e


def _append_throughput(journal_dir, n=20_000):
    from karpenter_tpu.recovery import StateJournal

    journal = StateJournal(journal_dir)
    handle = journal.handle("bench")
    t0 = time.perf_counter()
    for i in range(n):
        handle.set(("k", i % 64), {"v": i})
    elapsed = time.perf_counter() - t0
    journal.close()
    return {
        "append_us": round(elapsed / n * 1e6, 3),
        "appends_per_sec": int(n / elapsed),
    }


def _append_journal_row(path: str, record: dict) -> None:
    marker = "## Journal overhead (make bench-journal)"
    header = (
        f"\n{marker}\n\n"
        "Reconcile tick latency with the protective-state journal "
        "(karpenter_tpu/recovery) ON vs OFF over the identical seeded "
        "world, plus raw append throughput. Acceptance target: journal "
        "overhead under 5% of tick latency.\n\n"
        "| Date | Backend | Ticks | Tick p50 off/on (ms) | Overhead | "
        "Append (µs) | Appends/s |\n"
        "|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['ticks']} "
        f"| {record['tick_p50_off_ms']} / {record['tick_p50_on_ms']} "
        f"| {record['overhead_pct']}% "
        f"| {record['append_us']} | {record['appends_per_sec']} |\n"
    )
    _append_table_row(path, marker, header, row)


def run_journal(args, metric: str) -> None:
    """Journal append overhead on the reconcile hot path (ISSUE 7
    acceptance: <5% tick-latency regression vs the unjournaled tick).
    Same seeded world both ways; the ON configuration journals FSM
    transitions, breaker/backoff state, and forecast history through
    the real runtime wiring."""
    import shutil
    import tempfile

    import jax

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    off, _ = _journal_tick_times(args, None)
    root = tempfile.mkdtemp(prefix="karpenter-bench-journal-")
    try:
        on, e2e = _journal_tick_times(args, os.path.join(root, "ticks"))
        throughput = _append_throughput(os.path.join(root, "appends"))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    p50_off = float(np.percentile(off, 50))
    p50_on = float(np.percentile(on, 50))
    overhead = (p50_on / p50_off - 1.0) * 100.0 if p50_off else 0.0
    record = {
        "config": f"{args.journal_ticks} ticks",
        "backend": jax.default_backend(),
        "ticks": args.journal_ticks,
        "tick_p50_off_ms": round(p50_off, 3),
        "tick_p50_on_ms": round(p50_on, 3),
        "tick_p99_off_ms": round(float(np.percentile(off, 99)), 3),
        "tick_p99_on_ms": round(float(np.percentile(on, 99)), 3),
        "overhead_pct": round(overhead, 2),
        **throughput,
        # event-observed -> actuation-acked lead time over the journaled
        # run (the PR 9 histogram; docs/cost.md quantifies warm pools
        # against the same observable)
        **e2e,
    }
    record_evidence(
        tick_off_ms=[round(t, 4) for t in off],
        tick_on_ms=[round(t, 4) for t in on],
        journal=record,
    )
    print(
        f"tick p50 off={record['tick_p50_off_ms']}ms "
        f"on={record['tick_p50_on_ms']}ms "
        f"overhead={record['overhead_pct']}% | append "
        f"{record['append_us']}µs ({record['appends_per_sec']}/s) | "
        f"e2e lead p50={record['e2e_p50_ms']}ms "
        f"p99={record['e2e_p99_ms']}ms (n={record['e2e_samples']})",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} journal overhead ({record['backend']})",
            record,
        )
    if args.append_benchmarks:
        _append_journal_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        p50_on,
        note=f"journal overhead {record['overhead_pct']}% "
        f"(off p50 {record['tick_p50_off_ms']}ms), append "
        f"{record['append_us']}µs",
        against_baseline=False,
    )


def _trace_tick_times(args):
    """Per-tick wall times with the tracer ENABLED vs DISABLED, measured
    INTERLEAVED over one seeded world (the one `--journal` measures —
    churn pod toggled each tick so every tick pays a real encode +
    solve + decide, i.e. the span-instrumented hot path). The only
    difference between adjacent ticks is the tracer's `enabled` flag,
    so wall-clock drift (thermal, page cache, background load) that
    dominates a sub-5% effect in back-to-back runs cancels; the
    off/on order flips each round so the churn create/delete asymmetry
    balances across configurations too. Returns
    (off_ms, on_ms, spans_per_tick)."""
    from karpenter_tpu.observability import default_tracer

    tracer = default_tracer()
    runtime, tick = _churn_runtime()

    def timed(enabled):
        tracer.enabled = enabled
        t0 = time.perf_counter()
        tick()
        return (time.perf_counter() - t0) * 1e3

    off, on = [], []
    try:
        for _ in range(5):  # warmup: compiles, first encodes
            tick()
        spans_before = tracer.spans_total
        for round_i in range(args.trace_ticks):
            if round_i % 2 == 0:
                off.append(timed(False))
                on.append(timed(True))
            else:
                on.append(timed(True))
                off.append(timed(False))
        spans_per_tick = (
            (tracer.spans_total - spans_before) / args.trace_ticks
        )
    finally:
        tracer.enabled = True
        tracer.clear()
        runtime.close()
    return off, on, round(spans_per_tick, 1)


def _span_throughput(n: int = 20_000) -> dict:
    """Raw open/close cost of one span on a private tracer — the
    per-span floor the per-tick overhead decomposes into."""
    from karpenter_tpu.observability.tracing import Tracer

    tracer = Tracer(capacity=1024)
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.span("bench"):
            pass
    elapsed = time.perf_counter() - t0
    return {
        "span_us": round(elapsed / n * 1e6, 3),
        "spans_per_sec": int(n / elapsed),
    }


def _append_trace_row(path: str, record: dict) -> None:
    marker = "## Tracing overhead (make bench-trace)"
    header = (
        f"\n{marker}\n\n"
        "Reconcile tick latency with the reconcile tracer "
        "(karpenter_tpu/observability) ENABLED vs DISABLED over the "
        "identical seeded world, plus span volume and raw span "
        "open/close throughput. Acceptance target: tracing overhead "
        "under 5% of tick latency.\n\n"
        "| Date | Backend | Ticks | Tick p50 off/on (ms) | Overhead | "
        "Spans/tick | Span (µs) | Spans/s |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['ticks']} "
        f"| {record['tick_p50_off_ms']} / {record['tick_p50_on_ms']} "
        f"| {record['overhead_pct']}% | {record['spans_per_tick']} "
        f"| {record['span_us']} | {record['spans_per_sec']} |\n"
    )
    _append_table_row(path, marker, header, row)


def run_trace(args, metric: str) -> None:
    """Tracing overhead on the reconcile hot path (ISSUE 9 acceptance:
    <5% tick-latency regression vs the untraced tick). Same seeded
    world both ways; the ENABLED configuration mints a trace per tick
    and spans every layer through the real runtime wiring (manager ->
    metrics query -> solver request/dispatch -> SNG actuation)."""
    import jax

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    off, on, spans_per_tick = _trace_tick_times(args)
    throughput = _span_throughput()
    p50_off = float(np.percentile(off, 50))
    p50_on = float(np.percentile(on, 50))
    # overhead from the MEDIAN PAIRED per-round difference, not the
    # ratio of two independent p50s: each round's off/on ticks are
    # wall-clock adjacent, so drift that swamps a sub-5% effect in
    # independent percentiles cancels pairwise
    delta = float(np.median(np.asarray(on) - np.asarray(off)))
    overhead = (delta / p50_off) * 100.0 if p50_off else 0.0
    record = {
        "config": f"{args.trace_ticks} ticks",
        "backend": jax.default_backend(),
        "ticks": args.trace_ticks,
        "tick_p50_off_ms": round(p50_off, 3),
        "tick_p50_on_ms": round(p50_on, 3),
        "tick_p99_off_ms": round(float(np.percentile(off, 99)), 3),
        "tick_p99_on_ms": round(float(np.percentile(on, 99)), 3),
        "overhead_pct": round(overhead, 2),
        "spans_per_tick": spans_per_tick,
        **throughput,
    }
    record_evidence(
        tick_off_ms=[round(t, 4) for t in off],
        tick_on_ms=[round(t, 4) for t in on],
        trace=record,
    )
    print(
        f"tick p50 off={record['tick_p50_off_ms']}ms "
        f"on={record['tick_p50_on_ms']}ms "
        f"overhead={record['overhead_pct']}% | "
        f"{record['spans_per_tick']} spans/tick, span "
        f"{record['span_us']}µs ({record['spans_per_sec']}/s)",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} tracing overhead ({record['backend']})",
            record,
        )
    if args.append_benchmarks:
        _append_trace_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        p50_on,
        note=f"tracing overhead {record['overhead_pct']}% "
        f"(off p50 {record['tick_p50_off_ms']}ms), "
        f"{record['spans_per_tick']} spans/tick @ "
        f"{record['span_us']}µs",
        against_baseline=False,
    )


def _append_eventloop_row(path: str, record: dict) -> None:
    marker = "## Event-driven reconcile (make bench-eventloop)"
    header = (
        f"\n{marker}\n\n"
        "One seeded pod-arrival trace replayed tick-paced vs "
        "event-driven (debounced coalesced event passes; the tick "
        "demoted to a resync backstop). e2e = the "
        "karpenter_reconcile_e2e_seconds histogram (watch-event -> "
        "actuation-ack), read via HistogramVec.percentile. "
        "Amplification = event-arm solver work / tick-arm solver work; "
        "the storm column is the churn-storm arm (N events inside one "
        "debounce window must coalesce, not fan out).\n\n"
        "| Date | Backend | Trace | e2e p50/p99 tick (s) | "
        "e2e p50/p99 event (s) | p99 speedup | Amplification | "
        "Storm events -> passes | Storm amp |\n"
        "|---|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['tick_p50_s']} / {record['tick_p99_s']} "
        f"| {record['event_p50_s']} / {record['event_p99_s']} "
        f"| {record['p99_speedup']}x | {record['amplification']}x "
        f"| {record['storm_events']} -> {record['storm_passes']} "
        f"| {record['storm_amplification']}x |\n"
    )
    _append_table_row(path, marker, header, row)


def run_eventloop(args, metric: str) -> None:
    """Event-driven reconcile proof (ISSUE 14 acceptance): the seeded
    arrival trace replayed through both loop modes by
    simulate.simulate_eventloop — wall-clock-free (scripted clock,
    manual event passes), so the published latencies are the SIMULATED
    lead times an operator's histogram would show at the replayed tick
    interval, not artifacts of how fast this host replays ticks."""
    import jax

    from karpenter_tpu.simulate import simulate_eventloop

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    report = simulate_eventloop(
        ticks=args.eventloop_ticks,
        arrivals=args.eventloop_arrivals,
        storm_events=args.eventloop_storm,
        debounce_s=args.eventloop_debounce,
        seed=args.seed,
    )
    tick = report["tick_paced"]["e2e_seconds"]
    event = report["event_driven"]["e2e_seconds"]
    storm = report["event_driven"]["storm"]
    record = {
        "config": (
            f"{args.eventloop_arrivals} arrivals x "
            f"{args.eventloop_ticks} ticks"
        ),
        "backend": jax.default_backend(),
        "interval_s": report["config"]["interval_s"],
        "debounce_s": report["config"]["debounce_s"],
        "tick_p50_s": round(tick["p50_s"] or 0.0, 4),
        "tick_p99_s": round(tick["p99_s"] or 0.0, 4),
        "event_p50_s": round(event["p50_s"] or 0.0, 4),
        "event_p99_s": round(event["p99_s"] or 0.0, 4),
        "p99_speedup": report["e2e_p99_s"]["speedup"],
        "amplification": report["solve_amplification"],
        "event_passes": report["event_driven"]["event_passes"],
        "storm_events": storm["events"],
        "storm_passes": storm["passes"],
        "storm_amplification": storm["amplification"],
        "fixed_point_match": report["fixed_point_match"],
    }
    record_evidence(eventloop=report)
    print(
        f"e2e p99 tick={record['tick_p99_s']}s "
        f"event={record['event_p99_s']}s "
        f"({record['p99_speedup']}x); amplification "
        f"{record['amplification']}x; storm {record['storm_events']} "
        f"events -> {record['storm_passes']} passes "
        f"({record['storm_amplification']}x)",
        file=sys.stderr,
    )
    if not record["fixed_point_match"]:
        emit(metric, None, error="event-driven fixed point diverged "
             "from the tick-paced run")
        sys.exit(0)
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} eventloop ({record['backend']})",
            record,
        )
    if args.append_benchmarks:
        _append_eventloop_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        record["event_p99_s"] * 1e3,  # emit()'s unit is ms
        note=f"tick-paced p99 {record['tick_p99_s']}s -> event-driven "
        f"p99 {record['event_p99_s']}s ({record['p99_speedup']}x) at "
        f"debounce {record['debounce_s']}s; solve amplification "
        f"{record['amplification']}x; {record['storm_events']}-event "
        f"storm -> {record['storm_passes']} passes",
        against_baseline=False,
    )


def run_simlab(args, metric: str) -> None:  # lint: allow-complexity — bench arm: parity pin + interleaved timing + publish, linear
    """SimLab batched cluster stepping (ISSUE 17 acceptance): N
    independently-seeded simulated clusters advanced as ONE vmapped
    sim_rollout dispatch through the SolverService seam vs the
    per-cluster sequential loop (N dispatches of the same compiled
    program). Parity — batched == sequential == numpy mirror, bitwise
    on every output field — is pinned BEFORE any timing; interleaved
    arms so drift cancels."""
    import jax

    from karpenter_tpu.metrics.registry import GaugeRegistry
    from karpenter_tpu.ops import simstep as SK
    from karpenter_tpu.simlab import BatchedSimEnv
    from karpenter_tpu.simlab.builtin import make_trails
    from karpenter_tpu.simlab.policy import FROZEN_KNOBS
    from karpenter_tpu.solver.service import SolverService

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    clusters, ticks, rows = (
        args.simlab_clusters, args.simlab_ticks, args.simlab_rows
    )
    svc = SolverService(registry=GaugeRegistry())
    # the cost theme: diurnal demand + spot spikes + a seeded fault
    # schedule, so the measured program carries every kernel feature
    env = BatchedSimEnv(
        lambda seed: make_trails(
            seed, ticks=ticks, rows=rows, diurnal=True, amplitude=96.0,
            price_spike=1.5, fault_probability=0.05,
        ),
        clusters=clusters,
        seed=args.seed,
        service=svc,
        backend="xla",
    )
    batched_inputs = SK.SimRolloutInputs(
        replicas0=np.asarray(env.trails.replicas0, np.float32),
        streak0=np.zeros_like(
            np.asarray(env.trails.replicas0, np.float32)
        ),
        demand=env.trails.demand, forecast=env.trails.forecast,
        price=env.trails.price, fault=env.trails.fault,
        knobs=np.broadcast_to(
            FROZEN_KNOBS, (clusters, FROZEN_KNOBS.shape[0])
        ).copy(),
        cap=np.float32(env.params.cap),
        hourly=np.float32(env.params.hourly),
        step_limit=np.float32(env.params.step_limit),
        min_replicas=np.float32(env.params.min_replicas),
        max_replicas=np.float32(env.params.max_replicas),
    )
    slices = [
        SK._cluster_slice(batched_inputs, b) for b in range(clusters)
    ]

    # parity pin BEFORE timing: batched == sequential == numpy, bitwise
    batched = svc.sim_rollout(batched_inputs, backend="xla")
    mirror = SK.sim_rollout_numpy(batched_inputs)
    fields = ("replicas", "violation", "cost", "backlog", "target")
    for field in fields:
        if not (
            np.asarray(getattr(batched, field))
            == np.asarray(getattr(mirror, field))
        ).all():
            emit(metric, None, error=f"batched/numpy mismatch: {field}")
            sys.exit(0)
    for b in (0, clusters // 2, clusters - 1):
        seq = svc.sim_rollout(slices[b], backend="xla")
        for field in fields:
            if not (
                np.asarray(getattr(seq, field))
                == np.asarray(getattr(batched, field))[b]
            ).all():
                emit(
                    metric, None,
                    error=f"batched/sequential mismatch: {field} "
                    f"cluster {b}",
                )
                sys.exit(0)
    if svc.stats.sim_mirror_serves:
        emit(
            metric, None,
            error="device path unavailable (mirror served during "
            "parity); the batched-vs-sequential comparison needs XLA",
        )
        sys.exit(0)
    print("parity: batched == sequential == numpy (bitwise)",
          file=sys.stderr)

    # warm both compiled programs outside the timed region
    jax.block_until_ready(SK.sim_rollout_vmapped(batched_inputs).replicas)
    jax.block_until_ready(SK.sim_rollout_jit(slices[0]).replicas)

    base_dispatches = svc.stats.sim_dispatches
    batched_times, seq_times = [], []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        svc.sim_rollout(batched_inputs, backend="xla")
        batched_times.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        for one in slices:
            svc.sim_rollout(one, backend="xla")
        seq_times.append((time.perf_counter() - t0) * 1e3)
    if svc.stats.sim_mirror_serves:
        emit(metric, None, error="mirror served during timing")
        sys.exit(0)

    batched_p50 = float(np.percentile(batched_times, 50))
    seq_p50 = float(np.percentile(seq_times, 50))
    speedup = seq_p50 / max(batched_p50, 1e-9)
    # cluster-days per minute at a 10s simulated tick: the ROADMAP
    # "thousands of cluster-days per minute" claim, measured
    sim_days = clusters * ticks * 10.0 / 86_400.0
    days_per_min = sim_days / (batched_p50 / 1e3) * 60.0
    record = {
        "config": f"{clusters} clusters x {ticks} ticks x {rows} rows",
        "backend": jax.default_backend(),
        "batched_p50_ms": round(batched_p50, 3),
        "sequential_p50_ms": round(seq_p50, 3),
        "speedup": round(speedup, 1),
        "dispatches_sequential": clusters,
        "cluster_days_per_min": round(days_per_min, 1),
        "parity": "bitwise",
    }
    record_evidence(
        simlab={
            "batched_ms": [round(t, 4) for t in batched_times],
            "sequential_ms": [round(t, 4) for t in seq_times],
            "dispatches": svc.stats.sim_dispatches - base_dispatches,
        }
    )
    print(
        f"batched p50 {record['batched_p50_ms']}ms vs sequential "
        f"{record['sequential_p50_ms']}ms ({record['speedup']}x); "
        f"{record['cluster_days_per_min']} simulated cluster-days/min",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} simlab ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_simlab_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        record["batched_p50_ms"],
        note=f"one vmapped dispatch {record['batched_p50_ms']}ms vs "
        f"{clusters} sequential dispatches "
        f"{record['sequential_p50_ms']}ms ({record['speedup']}x); "
        f"{record['cluster_days_per_min']} cluster-days/min; parity "
        f"pinned bitwise",
        against_baseline=False,
    )


def _append_simlab_row(path: str, record: dict) -> None:
    marker = "## SimLab batched cluster stepping (make bench-simlab)"
    header = (
        f"\n{marker}\n\n"
        "N independently-seeded simulated clusters (docs/simulator.md) "
        "advanced one whole episode as ONE vmapped sim_rollout "
        "dispatch through the SolverService seam, vs the per-cluster "
        "sequential loop (N dispatches of the same compiled program). "
        "Batched == sequential == numpy mirror pinned bitwise before "
        "timing; interleaved arms.\n\n"
        "| Date | Backend | Problem | Batched p50 (ms) | "
        "Sequential p50 (ms) | Speedup | Cluster-days/min |\n"
        "|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['batched_p50_ms']} | {record['sequential_p50_ms']} "
        f"| {record['speedup']}x | {record['cluster_days_per_min']} |\n"
    )
    _append_table_row(path, marker, header, row)


def _fusedtick_inputs(seed, n, m, s, t):
    """A seeded full-presence fleet batch: every stage of the fused
    megakernel engaged (forecast series scattered over the grid, SLO
    rows mostly valid) so the measured program carries the whole
    forecast -> decide -> cost ladder."""
    from karpenter_tpu.forecast import models as FM
    from karpenter_tpu.ops import decision as DK
    from karpenter_tpu.ops import fusedtick as FT

    r = np.random.RandomState(seed)
    k = 2
    now = 1000.0
    decision = DK.DecisionInputs(
        metric_value=r.uniform(0, 100, (n, m)).astype(np.float32),
        target_value=r.uniform(1, 80, (n, m)).astype(np.float32),
        target_type=r.randint(0, 3, (n, m)).astype(np.int32),
        metric_valid=r.rand(n, m) > 0.2,
        spec_replicas=r.randint(1, 20, n).astype(np.int32),
        status_replicas=r.randint(1, 20, n).astype(np.int32),
        min_replicas=r.randint(0, 3, n).astype(np.int32),
        max_replicas=r.randint(20, 40, n).astype(np.int32),
        up_window=r.randint(0, 60, n).astype(np.int32),
        down_window=r.randint(0, 120, n).astype(np.int32),
        up_policy=r.randint(0, 2, n).astype(np.int32),
        down_policy=r.randint(0, 2, n).astype(np.int32),
        last_scale_time=(now - r.uniform(0, 300, n)).astype(np.float32),
        has_last_scale=r.rand(n) > 0.3,
        now=np.float32(now),
        up_ptype=r.randint(0, 3, (n, k)).astype(np.int32),
        up_pvalue=r.randint(1, 10, (n, k)).astype(np.int32),
        up_pperiod=r.randint(15, 120, (n, k)).astype(np.int32),
        up_pvalid=r.rand(n, k) > 0.4,
        down_ptype=r.randint(0, 3, (n, k)).astype(np.int32),
        down_pvalue=r.randint(1, 10, (n, k)).astype(np.int32),
        down_pperiod=r.randint(15, 120, (n, k)).astype(np.int32),
        down_pvalid=r.rand(n, k) > 0.4,
    )
    forecast = FM.ForecastInputs(
        values=r.uniform(0, 100, (s, t)).astype(np.float32),
        valid=r.rand(s, t) > 0.2,
        times=np.cumsum(r.uniform(10, 20, (s, t)), 1).astype(np.float32),
        weights=np.ones((s, t), np.float32),
        horizon=np.full(s, 60.0, np.float32),
        step_s=np.full(s, 15.0, np.float32),
        model=r.randint(0, 2, s).astype(np.int32),
        season=np.full(s, 4, np.int32),
        alpha=np.full(s, 0.5, np.float32),
        beta=np.full(s, 0.1, np.float32),
        gamma=np.full(s, 0.1, np.float32),
    )
    return FT.FusedTickInputs(
        decision=decision,
        forecast=forecast,
        series_row=r.randint(0, n, s).astype(np.int32),
        series_col=r.randint(0, m, s).astype(np.int32),
        series_need=np.full(s, 2, np.int32),
        series_blend=r.rand(s) > 0.3,
        ha_min=r.randint(0, 3, n).astype(np.int32),
        ha_max=r.randint(20, 40, n).astype(np.int32),
        unit_cost=r.uniform(0.1, 3.0, n).astype(np.float32),
        slo_weight=r.uniform(0, 2, n).astype(np.float32),
        max_hourly_cost=r.uniform(5, 50, n).astype(np.float32),
        slo_valid=r.rand(n) > 0.4,
        slo_target=r.uniform(1, 80, (n, m)).astype(np.float32),
        observed=r.uniform(0, 100, (n, m)).astype(np.float32),
        demand_base_valid=r.rand(n, m) > 0.3,
        prior_point=r.uniform(0, 100, (n, m)).astype(np.float32),
        prior_sigma2=r.uniform(0, 10, (n, m)).astype(np.float32),
        prior_valid=r.rand(n, m) > 0.5,
    )


def _fusedtick_world_ticks(fused: bool, warmup: int, ticks: int):
    """(per-tick wall times, dispatches-per-tick) over the shared
    churn-runtime world with --fused-tick on/off: the HA plane's
    forecast + SLO stages engaged so the chained arm pays one program
    per stage while the fused arm pays ONE (the
    karpenter_solver_dispatches_per_tick observable)."""
    from karpenter_tpu.api.horizontalautoscaler import (
        ForecastSpec, SLOSpec,
    )

    runtime, tick = _churn_runtime(
        consolidate=False, fused_tick=fused,
    )
    times = []
    try:
        # the dispatch-count observable needs the compiled path ("auto"
        # resolves to numpy on CPU; decisions are bit-identical)
        runtime.solver_service.backend = "xla"
        # the producer's pending-capacity solve would ride along in
        # both arms; drop it so the gauge isolates the HA-plane ladder
        runtime.store.delete("MetricsProducer", "default", "pending")
        ha = runtime.store.get("HorizontalAutoscaler", "default", "ha")
        ha.spec.behavior.forecast = ForecastSpec(
            horizon_seconds=30.0, min_samples=3, model="linear",
        )
        ha.spec.behavior.slo = SLOSpec(
            target_value=3.0, violation_cost_weight=25.0,
        )
        # store.get hands back a clone; write the engaged stages back
        runtime.store.update(ha)
        for _ in range(warmup):
            tick()
        for _ in range(ticks):
            t0 = time.perf_counter()
            tick()
            times.append((time.perf_counter() - t0) * 1e3)
        dispatches = (
            runtime.solver_service.stats.last_dispatches_per_tick
        )
        stats = runtime.solver_service.stats
        if fused and not stats.fused_dispatches:
            raise RuntimeError(
                "--fused-tick runtime arm never dispatched the fused "
                "program"
            )
    finally:
        runtime.close()
    return times, dispatches


def run_fusedtick(args, metric: str) -> None:  # lint: allow-complexity — bench arm: parity pin + interleaved timing + publish, linear
    """The fused steady-state tick (ISSUE 18 acceptance): the whole
    fleet batch's forecast -> decide -> cost ladder as ONE compiled
    program through SolverService.fused_tick vs the chained per-stage
    wire (one program per stage, numpy host glue between). Parity —
    fused == chained == numpy mirror, bitwise on every output leaf —
    is pinned BEFORE any timing; interleaved arms so drift cancels.
    A second arm replays the shared churn-runtime world with
    --fused-tick on/off and reads the dispatches-per-tick collapse
    from the introspection stats."""
    import jax

    from karpenter_tpu.metrics.registry import GaugeRegistry
    from karpenter_tpu.ops import fusedtick as FT
    from karpenter_tpu.solver.service import SolverService

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    n, m = args.fusedtick_rows, args.fusedtick_metrics
    s, t = args.fusedtick_series, args.fusedtick_samples
    inputs = _fusedtick_inputs(args.seed, n, m, s, t)
    svc = SolverService(registry=GaugeRegistry(), backend="xla")

    # parity pin BEFORE timing: fused == chained == numpy, bitwise
    fused_out = svc.fused_tick(inputs)
    chained_out = FT.fused_tick_chained(inputs)
    mirror_out = FT.fused_tick_numpy(inputs)
    if svc.stats.fused_mirror_serves or svc.stats.fused_chained_serves:
        emit(
            metric, None,
            error="device path unavailable (fallback served during "
            "parity); the fused-vs-chained comparison needs XLA",
        )
        sys.exit(0)
    as_np = lambda out: jax.tree_util.tree_leaves(  # noqa: E731
        jax.tree_util.tree_map(np.asarray, out)
    )
    for other, name in ((chained_out, "chained"), (mirror_out, "numpy")):
        for i, (a, b) in enumerate(zip(as_np(fused_out), as_np(other))):
            if a.tobytes() != b.tobytes():
                emit(
                    metric, None,
                    error=f"fused/{name} mismatch: leaf {i}",
                )
                sys.exit(0)
    print("parity: fused == chained == numpy (bitwise)", file=sys.stderr)

    # kernel arm: interleaved fused vs chained dispatch, both timed at
    # the ops seam (the parity pin above already exercised — and
    # compiled — the full service ladder; timing the raw programs keeps
    # the service-wrapper overhead out of BOTH arms symmetrically)
    fused_times, chained_times = [], []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        jax.block_until_ready(FT.fused_tick_jit(inputs))
        fused_times.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        FT.fused_tick_chained(inputs)
        chained_times.append((time.perf_counter() - t0) * 1e3)
    svc.close()

    fused_p50 = float(np.percentile(fused_times, 50))
    chained_p50 = float(np.percentile(chained_times, 50))
    speedup = chained_p50 / max(fused_p50, 1e-9)
    decisions_per_s = n / max(fused_p50 / 1e3, 1e-9)

    # runtime arm: the shared churn world, fused on vs off
    warmup = 6
    chained_ticks, tick_d_chained = _fusedtick_world_ticks(
        False, warmup, args.fusedtick_ticks
    )
    fused_ticks, tick_d_fused = _fusedtick_world_ticks(
        True, warmup, args.fusedtick_ticks
    )

    record = {
        "config": (
            f"{n} autoscalers x {m} metrics x {s} series x "
            f"{t} samples"
        ),
        "backend": jax.default_backend(),
        "fused_p50_ms": round(fused_p50, 3),
        "chained_p50_ms": round(chained_p50, 3),
        "speedup": round(speedup, 2),
        "decisions_per_s": int(decisions_per_s),
        "programs_fused": 1,
        "programs_chained": FT.programs(inputs),
        "tick_p50_fused_ms": round(
            float(np.percentile(fused_ticks, 50)), 3
        ),
        "tick_p50_chained_ms": round(
            float(np.percentile(chained_ticks, 50)), 3
        ),
        "tick_dispatches_fused": tick_d_fused,
        "tick_dispatches_chained": tick_d_chained,
        "parity": "bitwise",
    }
    record_evidence(
        fusedtick={
            "fused_ms": [round(x, 4) for x in fused_times],
            "chained_ms": [round(x, 4) for x in chained_times],
            "tick_fused_ms": [round(x, 4) for x in fused_ticks],
            "tick_chained_ms": [round(x, 4) for x in chained_ticks],
        }
    )
    print(
        f"fused p50 {record['fused_p50_ms']}ms vs chained "
        f"{record['chained_p50_ms']}ms ({record['speedup']}x); "
        f"{record['decisions_per_s']} decisions/sec; runtime tick "
        f"dispatches {record['tick_dispatches_chained']} -> "
        f"{record['tick_dispatches_fused']}",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} fusedtick ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_fusedtick_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        record["fused_p50_ms"],
        note=f"one fused program {record['fused_p50_ms']}ms vs "
        f"{record['programs_chained']}-program chained wire "
        f"{record['chained_p50_ms']}ms ({record['speedup']}x); "
        f"{record['decisions_per_s']} decisions/sec; runtime "
        f"dispatches/tick {record['tick_dispatches_chained']} -> "
        f"{record['tick_dispatches_fused']}; parity pinned bitwise",
        against_baseline=False,
    )


def _append_fusedtick_row(path: str, record: dict) -> None:
    marker = "## Fused steady-state tick (make bench-fusedtick)"
    header = (
        f"\n{marker}\n\n"
        "The whole fleet batch's forecast -> decide -> cost ladder as "
        "ONE compiled program (SolverService.fused_tick, --fused-tick) "
        "vs the chained per-stage wire — one program per stage with "
        "numpy host glue between. Fused == chained == numpy mirror "
        "pinned bitwise before timing; interleaved arms. The runtime "
        "columns replay the shared churn world and read the "
        "karpenter_solver_dispatches_per_tick collapse.\n\n"
        "| Date | Backend | Problem | Fused p50 (ms) | "
        "Chained p50 (ms) | Speedup | Decisions/sec | "
        "Dispatches/tick |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['fused_p50_ms']} | {record['chained_p50_ms']} "
        f"| {record['speedup']}x | {record['decisions_per_s']} "
        f"| {record['tick_dispatches_chained']} -> "
        f"{record['tick_dispatches_fused']} |\n"
    )
    _append_table_row(path, marker, header, row)


def _provenance_tick_times(args):
    """Per-tick wall times with the decision-provenance ledger ENABLED
    vs DISABLED, measured INTERLEAVED over the shared churn world (the
    exact world bench-journal and bench-trace measure, so the three
    published overhead percentages sit side by side against the same
    ~4ms tick). Adjacent off/on ticks + flipped order per round: drift
    cancels pairwise (the bench-trace rationale). Returns
    (off_ms, on_ms, records_per_tick)."""
    from karpenter_tpu.observability import (
        default_ledger,
        reset_default_ledger,
        set_default_ledger,
    )

    saved = default_ledger()
    ledger = reset_default_ledger(enabled=False)
    runtime, tick = _churn_runtime()

    def timed(enabled):
        ledger.enabled = enabled
        t0 = time.perf_counter()
        tick()
        return (time.perf_counter() - t0) * 1e3

    off, on = [], []
    try:
        for _ in range(5):  # warmup: compiles, first encodes
            tick()
        records_before = ledger.records_total
        for round_i in range(args.provenance_ticks):
            if round_i % 2 == 0:
                off.append(timed(False))
                on.append(timed(True))
            else:
                on.append(timed(True))
                off.append(timed(False))
        records_per_tick = (
            (ledger.records_total - records_before)
            / args.provenance_ticks
        )
    finally:
        runtime.close()
        set_default_ledger(saved)
    return off, on, round(records_per_tick, 1)


def _ledger_throughput(n: int = 5_000, rows: int = 8) -> dict:
    """Raw begin+annotate+commit cost of one `rows`-row batch on a
    private ledger — the per-batch floor the per-tick overhead
    decomposes into."""
    from karpenter_tpu.observability.provenance import DecisionLedger

    ledger = DecisionLedger(capacity=4096, enabled=True)
    names = [f"r{i}" for i in range(rows)]
    desired = np.arange(rows, dtype=np.int32)
    t0 = time.perf_counter()
    for _ in range(n):
        batch = ledger.begin("ha", rows, name=names)
        batch.annotate(base_desired=desired, final_desired=desired)
        ledger.commit(batch)
    elapsed = time.perf_counter() - t0
    return {
        "commit_us": round(elapsed / n * 1e6, 3),
        "commits_per_sec": int(n / elapsed),
    }


def _append_provenance_row(path: str, record: dict) -> None:
    marker = "## Provenance overhead (make bench-provenance)"
    header = (
        f"\n{marker}\n\n"
        "Reconcile tick latency with the decision-provenance ledger "
        "(karpenter_tpu/observability/provenance.py) ENABLED vs "
        "DISABLED over the identical seeded world (the bench-journal/"
        "bench-trace churn world), plus raw batch-commit throughput. "
        "Acceptance target: provenance overhead under 5% of tick "
        "latency; provenance OFF is property-pinned byte-identical "
        "(tests/test_provenance.py).\n\n"
        "| Date | Backend | Ticks | Tick p50 off/on (ms) | Overhead | "
        "Records/tick | Commit (µs) | Commits/s |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['ticks']} "
        f"| {record['tick_p50_off_ms']} / {record['tick_p50_on_ms']} "
        f"| {record['overhead_pct']}% | {record['records_per_tick']} "
        f"| {record['commit_us']} | {record['commits_per_sec']} |\n"
    )
    _append_table_row(path, marker, header, row)


def run_provenance(args, metric: str) -> None:
    """Decision-provenance overhead on the reconcile hot path (ISSUE 12
    acceptance: <=5% median paired tick overhead with the ledger on).
    Same seeded world both ways; the ENABLED configuration records one
    columnar batch per tick through the real annotation sites
    (BatchAutoscaler -> forecast -> cost -> solver decide)."""
    import jax

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    off, on, records_per_tick = _provenance_tick_times(args)
    throughput = _ledger_throughput()
    p50_off = float(np.percentile(off, 50))
    p50_on = float(np.percentile(on, 50))
    # median PAIRED difference (the bench-trace discipline): adjacent
    # off/on ticks cancel wall-clock drift a sub-5% effect drowns in
    delta = float(np.median(np.asarray(on) - np.asarray(off)))
    overhead = (delta / p50_off) * 100.0 if p50_off else 0.0
    record = {
        "config": f"{args.provenance_ticks} ticks",
        "backend": jax.default_backend(),
        "ticks": args.provenance_ticks,
        "tick_p50_off_ms": round(p50_off, 3),
        "tick_p50_on_ms": round(p50_on, 3),
        "tick_p99_off_ms": round(float(np.percentile(off, 99)), 3),
        "tick_p99_on_ms": round(float(np.percentile(on, 99)), 3),
        "overhead_pct": round(overhead, 2),
        "records_per_tick": records_per_tick,
        **throughput,
    }
    record_evidence(
        tick_off_ms=[round(t, 4) for t in off],
        tick_on_ms=[round(t, 4) for t in on],
        provenance=record,
    )
    print(
        f"tick p50 off={record['tick_p50_off_ms']}ms "
        f"on={record['tick_p50_on_ms']}ms "
        f"overhead={record['overhead_pct']}% | "
        f"{record['records_per_tick']} records/tick, commit "
        f"{record['commit_us']}µs ({record['commits_per_sec']}/s)",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} provenance overhead "
            f"({record['backend']})",
            record,
        )
    if args.append_benchmarks:
        _append_provenance_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        p50_on,
        note=f"provenance overhead {record['overhead_pct']}% "
        f"(off p50 {record['tick_p50_off_ms']}ms), "
        f"{record['records_per_tick']} records/tick @ "
        f"{record['commit_us']}µs/commit",
        against_baseline=False,
    )


def _introspect_tick_times(args):
    """Per-tick wall times with the solver introspection plane ENABLED
    vs DISABLED, measured INTERLEAVED over the shared churn world (the
    exact world bench-journal/bench-trace/bench-provenance measure, so
    the four published overhead percentages sit side by side against
    the same ~4ms tick). Adjacent off/on ticks + flipped order per
    round: drift cancels pairwise (the bench-trace rationale). Warm-up
    runs ENABLED so the compile ledger and cost attribution are paid
    there; steady-state ticks then measure the honest per-tick cost
    (storm-window close + memory poll + resident gauges). Returns
    (off_ms, on_ms, ledger_records)."""
    runtime, tick = _churn_runtime()
    plane = runtime.solver_introspection
    # force the compiled XLA path ("auto" resolves to the numpy host
    # program on CPU): the compile ledger observes jitted dispatches,
    # and both interleaved arms pay the identical tick either way
    runtime.solver_service.backend = "xla"

    def timed(enabled):
        plane.enabled = enabled
        t0 = time.perf_counter()
        tick()
        return (time.perf_counter() - t0) * 1e3

    off, on = [], []
    try:
        plane.enabled = True
        for _ in range(5):  # warmup: compiles (ledger-recorded), encodes
            tick()
        for round_i in range(args.introspect_ticks):
            if round_i % 2 == 0:
                off.append(timed(False))
                on.append(timed(True))
            else:
                on.append(timed(True))
                off.append(timed(False))
        records = plane.ledger.records_total
    finally:
        runtime.close()
    return off, on, records


def _append_introspect_row(path: str, record: dict) -> None:
    marker = "## Introspection overhead (make bench-introspect)"
    header = (
        f"\n{marker}\n\n"
        "Reconcile tick latency with the solver introspection plane "
        "(karpenter_tpu/observability/devicetelemetry.py: compile "
        "ledger + storm detection, device memory telemetry, resident-"
        "LRU byte accounting, XLA cost attribution) ENABLED vs "
        "DISABLED over the identical seeded world (the bench-journal/"
        "bench-trace/bench-provenance churn world). Acceptance target: "
        "introspection overhead under 2% of tick latency; introspect "
        "OFF is property-pinned byte-identical "
        "(tests/test_introspect.py).\n\n"
        "| Date | Backend | Ticks | Tick p50 off/on (ms) | Overhead | "
        "Ledger rows |\n"
        "|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['ticks']} "
        f"| {record['tick_p50_off_ms']} / {record['tick_p50_on_ms']} "
        f"| {record['overhead_pct']}% | {record['ledger_records']} |\n"
    )
    _append_table_row(path, marker, header, row)


def run_introspect(args, metric: str) -> None:
    """Solver-introspection overhead on the reconcile hot path (ISSUE
    15 acceptance: <=2% median paired tick overhead with telemetry
    on). Same seeded world both ways; the ENABLED configuration runs
    the real per-tick pass (compile-storm window, device memory poll,
    resident entry gauges) plus per-miss ledger/attribution work —
    zero at steady state, which is the point."""
    import jax

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    off, on, ledger_records = _introspect_tick_times(args)
    p50_off = float(np.percentile(off, 50))
    p50_on = float(np.percentile(on, 50))
    # median PAIRED difference (the bench-trace discipline)
    delta = float(np.median(np.asarray(on) - np.asarray(off)))
    overhead = (delta / p50_off) * 100.0 if p50_off else 0.0
    record = {
        "config": f"{args.introspect_ticks} ticks",
        "backend": jax.default_backend(),
        "ticks": args.introspect_ticks,
        "tick_p50_off_ms": round(p50_off, 3),
        "tick_p50_on_ms": round(p50_on, 3),
        "tick_p99_off_ms": round(float(np.percentile(off, 99)), 3),
        "tick_p99_on_ms": round(float(np.percentile(on, 99)), 3),
        "overhead_pct": round(overhead, 2),
        "ledger_records": ledger_records,
    }
    record_evidence(
        tick_off_ms=[round(t, 4) for t in off],
        tick_on_ms=[round(t, 4) for t in on],
        introspect=record,
    )
    print(
        f"tick p50 off={record['tick_p50_off_ms']}ms "
        f"on={record['tick_p50_on_ms']}ms "
        f"overhead={record['overhead_pct']}% | "
        f"{record['ledger_records']} compile-ledger rows (warm-up)",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} introspection overhead "
            f"({record['backend']})",
            record,
        )
    if args.append_benchmarks:
        _append_introspect_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        p50_on,
        note=f"introspection overhead {record['overhead_pct']}% "
        f"(off p50 {record['tick_p50_off_ms']}ms), "
        f"{record['ledger_records']} compile-ledger rows",
        against_baseline=False,
    )


def _constraint_bench_world(args):
    """The constrained workload: membership over cycling constraint
    kinds compiled into masked operands on one BinPackInputs (the
    compiler path, so the spread exactness contract holds)."""
    import jax
    import jax.numpy as jnp

    from karpenter_tpu.api.core import RESERVATION_LABEL, ZONE_LABEL
    from karpenter_tpu.constraints import ConstraintGroup, SpreadSpec
    from karpenter_tpu.constraints.compiler import compile_rows

    rng = np.random.default_rng(args.seed)
    G = args.constraint_groups
    alloc = {"cpu": 64.0, "memory": 256.0, "pods": 110.0}
    zones = [f"z{i + 1}" for i in range(4)]
    profiles = []
    for t in range(args.types):
        labels = {(ZONE_LABEL, zones[t % len(zones)])}
        if t % 7 == 3:
            labels = {(RESERVATION_LABEL, f"res{t % 3}")}
        profiles.append((dict(alloc), labels, set()))
    kinds = ["spread", "reservation", "anti", "compact"]
    groups = []
    for g in range(G):
        kind = kinds[g % len(kinds)]
        sel = {"team": f"t{g}"}
        if kind == "spread":
            groups.append(ConstraintGroup(
                name=f"g{g}", pod_selector=sel, spread=SpreadSpec()
            ))
        elif kind == "reservation":
            groups.append(ConstraintGroup(
                name=f"g{g}", pod_selector=sel,
                reservation=f"res{g % 3}",
            ))
        elif kind == "anti":
            groups.append(ConstraintGroup(
                name=f"g{g}", pod_selector=sel, anti_affinity=True
            ))
        else:
            groups.append(ConstraintGroup(
                name=f"g{g}", pod_selector=sel, compact=True
            ))
    P = args.pods
    membership = rng.integers(0, G + 1, P).astype(np.int32)
    weights = rng.integers(1, 4, P).astype(np.int32)
    valid = np.ones(P, bool)
    compiled = compile_rows(membership, weights, valid, profiles, groups)
    P2 = len(compiled.rep)
    requests = np.zeros((P2, 3), np.float32)
    requests[:, 0] = rng.integers(1, 8, P2)  # cpu
    requests[:, 1] = rng.integers(1, 16, P2)  # memory
    requests[:, 2] = 1.0  # pods
    group_allocatable = np.tile(
        np.asarray([alloc["cpu"], alloc["memory"], alloc["pods"]],
                   np.float32),
        (args.types, 1),
    )
    from karpenter_tpu.ops.binpack import BinPackInputs

    base = dict(
        pod_requests=jnp.asarray(requests),
        pod_valid=jnp.ones(P2, bool),
        pod_intolerant=jnp.zeros((P2, 4), bool),
        pod_required=jnp.zeros((P2, 4), bool),
        group_allocatable=jnp.asarray(group_allocatable),
        group_taints=jnp.zeros((args.types, 4), bool),
        group_labels=jnp.zeros((args.types, 4), bool),
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
    inputs = jax.device_put(BinPackInputs(**base))
    jax.block_until_ready(inputs)
    row_membership = membership[compiled.rep]
    return inputs, row_membership, G


def run_constraints(args, metric: str) -> None:  # lint: allow-complexity — bench arm: parity pin + interleaved timing + publish, linear
    """The constraint-plane headline: ONE batched masked-operand solve
    over every constraint group vs the per-group sequential loop a
    constraint-naive integration would run (G+1 dispatches of the same
    compiled program with the other groups' rows invalidated).
    Interleaved arms; per-group verdict parity pinned before timing."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from karpenter_tpu.ops.binpack import binpack

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs, row_membership, G = _constraint_bench_world(args)

    def solo(g):
        rows = row_membership == g
        return _dc.replace(
            inputs,
            pod_valid=jnp.asarray(
                np.asarray(inputs.pod_valid) & rows
            ),
            pod_weight=jnp.asarray(np.where(
                rows, np.asarray(inputs.pod_weight), 0
            ).astype(np.int32)),
        )
    solos = [solo(g) for g in range(G + 1)]

    def batched_arm():
        return jax.block_until_ready(binpack(inputs, buckets=args.buckets))

    def sequential_arm():
        outs = []
        for s in solos:
            outs.append(
                jax.block_until_ready(binpack(s, buckets=args.buckets))
            )
        return outs

    # warm both programs, then pin parity: the batched verdicts on each
    # group's rows must equal that group's independent solve
    ref = batched_arm()
    per_group = sequential_arm()
    ref_assigned = np.asarray(ref.assigned)
    for g, out in enumerate(per_group):
        rows = row_membership == g
        if not rows.any():
            continue
        if not np.array_equal(
            np.asarray(out.assigned)[rows], ref_assigned[rows]
        ):
            emit(metric, None, error=(
                f"parity failure: group {g} solo verdicts diverge "
                f"from the batched solve"
            ))
            raise SystemExit(1)

    batched_ms, sequential_ms = [], []
    for i in range(args.iters):
        arms = [("b", batched_arm), ("s", sequential_arm)]
        if i % 2:  # interleave: flip arm order every iteration
            arms.reverse()
        for tag, fn in arms:
            t0 = time.perf_counter()
            fn()
            dt = (time.perf_counter() - t0) * 1e3
            (batched_ms if tag == "b" else sequential_ms).append(dt)

    p50_b = float(np.percentile(batched_ms, 50))
    p50_s = float(np.percentile(sequential_ms, 50))
    record = {
        "config": (
            f"{args.pods} pods x {args.types} types x "
            f"{G} constraint groups"
        ),
        "backend": jax.default_backend(),
        "groups": G,
        "batched_p50_ms": round(p50_b, 3),
        "sequential_p50_ms": round(p50_s, 3),
        "speedup": round(p50_s / p50_b, 2) if p50_b else 0.0,
        "dispatches_batched": 1,
        "dispatches_sequential": G + 1,
    }
    record_evidence(
        batched_ms=[round(t, 4) for t in batched_ms],
        sequential_ms=[round(t, 4) for t in sequential_ms],
        constraints=record,
    )
    print(
        f"batched p50={record['batched_p50_ms']}ms vs per-group "
        f"p50={record['sequential_p50_ms']}ms "
        f"({record['speedup']}x, {G + 1} dispatches -> 1)",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} constrained solve "
            f"({record['backend']})",
            record,
        )
    if args.append_benchmarks:
        _append_constraints_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        p50_b,
        note=f"per-group sequential p50 {record['sequential_p50_ms']}ms "
        f"({record['speedup']}x); parity pinned",
        against_baseline=False,
    )


def _append_constraints_row(path: str, record: dict) -> None:
    marker = "## Constraint plane (make bench-constraints)"
    header = (
        f"\n{marker}\n\n"
        "One batched masked-operand solve carrying EVERY constraint "
        "group (zone spread + reservation claims + anti-affinity + "
        "compact placement compiled to integer operands; "
        "docs/constraints.md) vs the per-group sequential loop a "
        "constraint-naive integration would run (G+1 dispatches of the "
        "same compiled program). Interleaved arms; per-group verdict "
        "parity pinned before timing.\n\n"
        "| Date | Backend | Problem | Batched p50 (ms) | "
        "Per-group p50 (ms) | Speedup | Dispatches |\n"
        "|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['batched_p50_ms']} | {record['sequential_p50_ms']} "
        f"| {record['speedup']}x "
        f"| {record['dispatches_sequential']} -> 1 |\n"
    )
    _append_table_row(path, marker, header, row)


def _append_failover_row(path: str, record: dict) -> None:
    marker = "## Failover blackout (make bench-failover)"
    header = (
        f"\n{marker}\n\n"
        "Replicated-control-plane leader kill (karpenter_tpu/"
        "replication): the seeded failover world kills the biggest "
        "owner mid-storm; blackout is ticks from the kill until every "
        "victim tenant is back at its desired level under a survivor, "
        "with exactly-once actuation journal-audited across the "
        "handoff. Acceptance: blackout p99 within 3 lease durations, "
        "zero duplicate and zero lost writes.\n\n"
        "| Date | Backend | Tenants x Replicas | Partitions | Lease (s) "
        "| Blackout p99 (ticks / s) | Reconverge (ticks) | Dup / Lost "
        "|\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['tenants']} x "
        f"{record['replicas']} | {record['partitions']} "
        f"| {record['lease_duration_s']} "
        f"| {record['blackout_ticks_p99']} / {record['blackout_p99_s']} "
        f"| {record['reconverge_ticks']} "
        f"| {record['duplicate_actuations']} / "
        f"{record['lost_actuations']} |\n"
    )
    _append_table_row(path, marker, header, row)


def run_failover(args, metric: str) -> None:
    """Replicated-control-plane failover at fleet scale (ISSUE:
    replicated control plane): the seeded leader-kill world
    (simulate_failover — the `--simulate --failover` scenario) at
    --failover-tenants x --failover-replicas, auditing the handoff
    blackout and the exactly-once contract. Pure host-side control
    plane — no device dispatch — but the backend provenance is stamped
    anyway so a published row names the environment it ran in."""
    import time as _time

    import jax

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    from karpenter_tpu.simulate import simulate_failover

    t0 = _time.perf_counter()
    report = simulate_failover(
        tenants=args.failover_tenants,
        replicas=args.failover_replicas,
        partitions=args.failover_partitions,
        ticks=args.failover_ticks,
        seed=args.seed,
    )
    wall_s = _time.perf_counter() - t0
    if not report["converged"]:
        raise RuntimeError(
            "failover world failed to reconverge: "
            + json.dumps(report, sort_keys=True)[:500]
        )
    config = report["config"]
    record = {
        "config": f"{config['tenants']} tenants x "
        f"{config['replicas']} replicas",
        "backend": jax.default_backend(),
        "tenants": config["tenants"],
        "replicas": config["replicas"],
        "partitions": config["partitions"],
        "ticks": config["ticks"],
        "kill_tick": config["kill_tick"],
        "lease_duration_s": config["lease_duration_s"],
        "blackout_ticks_p99": report["blackout_ticks_p99"],
        "blackout_p99_s": report["blackout_s_p99"],
        "reconverge_ticks": report["reconverge_ticks"],
        "converged": report["converged"],
        "duplicate_actuations": report["duplicate_actuations"],
        "lost_actuations": report["lost_actuations"],
        "stale_write_rejected": report["stale_write_rejected"],
        "fence_rejections": report["fence_rejections"],
        "victim_tenants": len(report["victim_tenants"]),
        "handoffs_after_kill": report["handoffs_after_kill"],
        "writes_digest": report["writes_digest"],
        "wall_s": round(wall_s, 3),
    }
    record_evidence(failover=record)
    print(
        f"blackout p99={record['blackout_ticks_p99']} ticks "
        f"({record['blackout_p99_s']}s) reconverge="
        f"{record['reconverge_ticks']} ticks | victims="
        f"{record['victim_tenants']} dup="
        f"{record['duplicate_actuations']} lost="
        f"{record['lost_actuations']} stale_rejected="
        f"{record['stale_write_rejected']} | wall {record['wall_s']}s",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} failover ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_failover_row(args.append_benchmarks, record)
    emit(
        f"{metric} ({jax.default_backend()})",
        record["blackout_p99_s"] * 1e3,  # emit()'s unit is ms
        note=f"reconverge {record['reconverge_ticks']} ticks, "
        f"{record['victim_tenants']} victim tenants, dup/lost "
        f"{record['duplicate_actuations']}/"
        f"{record['lost_actuations']}",
        against_baseline=False,
    )


def run(args, metric: str) -> None:  # lint: allow-complexity — bench mode dispatch, one arm per measured configuration
    import jax

    _warm_native_kernel(args)

    if args.fusedtick:
        run_fusedtick(args, metric)
        return
    if args.failover:
        run_failover(args, metric)
        return
    if args.simlab:
        run_simlab(args, metric)
        return
    if args.constraints:
        run_constraints(args, metric)
        return
    if args.introspect:
        run_introspect(args, metric)
        return
    if args.eventloop:
        run_eventloop(args, metric)
        return
    if args.resident:
        run_resident(args, metric)
        return
    if args.journal:
        run_journal(args, metric)
        return
    if args.trace:
        run_trace(args, metric)
        return
    if args.provenance:
        run_provenance(args, metric)
        return
    if args.multitenant:
        run_multitenant(args, metric)
        return
    if args.poolgroup:
        run_poolgroup(args, metric)
        return
    if args.cost:
        run_cost(args, metric)
        return
    if args.preempt:
        run_preempt(args, metric)
        return
    if args.forecast:
        run_forecast(args, metric)
        return
    if args.hotpath:
        run_hotpath(args, metric)
        return
    if args.solver_service:
        run_solver_service(args, metric)
        return
    if args.consolidate:
        run_consolidate(args, metric)
        return
    if args.decide:
        run_decide(args, metric)
        return
    if args.e2e:
        run_e2e(args, metric)
        return

    from karpenter_tpu.ops.binpack import solve

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs = jax.device_put(_bench_inputs(args))
    jax.block_until_ready(inputs)

    t0 = time.perf_counter()
    out = solve(inputs, buckets=args.buckets, backend=args.backend)
    jax.block_until_ready(out)
    compile_ms = (time.perf_counter() - t0) * 1e3
    print(f"first call (compile+run): {compile_ms:.1f} ms", file=sys.stderr)

    times = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        out = solve(inputs, buckets=args.buckets, backend=args.backend)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3)
    record_evidence(
        compile_ms=round(compile_ms, 3),
        iter_ms=[round(t, 4) for t in times],
        transport_floor=measure_transport_floor(),
    )
    p50 = float(np.percentile(times, 50))
    p95 = float(np.percentile(times, 95))
    scheduled = int(np.sum(np.asarray(out.assigned) >= 0))
    # BASELINE.json's other axis: full-fleet bin-pack DECISIONS per
    # second, i.e. back-to-back solves of the whole problem
    dps = 1000.0 / p50 if p50 else 0.0
    print(
        f"p50={p50:.2f}ms p95={p95:.2f}ms scheduled={scheduled}/{args.pods} "
        f"unschedulable={int(out.unschedulable)} "
        f"nodes={int(np.sum(np.asarray(out.nodes_needed)))} "
        f"decisions/sec={dps:.0f}",
        file=sys.stderr,
    )
    extra = f"{dps:.0f} full-fleet decisions/sec"
    emit(
        f"{metric} ({jax.default_backend()})",
        p50,
        note=extra,
    )


def _measure_concurrent(call, inputs_list, iters: int):
    """Per-request latencies (ms) with len(inputs_list) submitter threads
    issuing `iters` sequential calls each — the concurrent-callers load
    shape the solver service's coalescing window exists for."""
    import threading

    latencies = [[] for _ in inputs_list]
    barrier = threading.Barrier(len(inputs_list))

    def submitter(i):
        barrier.wait()
        for _ in range(iters):
            t0 = time.perf_counter()
            call(inputs_list[i])
            latencies[i].append((time.perf_counter() - t0) * 1e3)

    threads = [
        threading.Thread(target=submitter, args=(i,))
        for i in range(len(inputs_list))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [t for per in latencies for t in per]


def _solver_service_record(args, backend, direct, service, svc) -> dict:
    reqs = max(1, svc.stats.requests)
    return {
        "config": f"{args.pods} pods x {args.types} types",
        "backend": backend,
        "concurrency": args.concurrency,
        "direct_p50_ms": round(float(np.percentile(direct, 50)), 3),
        "direct_p99_ms": round(float(np.percentile(direct, 99)), 3),
        "service_p50_ms": round(float(np.percentile(service, 50)), 3),
        "service_p99_ms": round(float(np.percentile(service, 99)), 3),
        "avg_coalesce_factor": round(reqs / max(1, svc.stats.dispatches), 2),
        "dispatches": svc.stats.dispatches,
        "requests": svc.stats.requests,
        "compile_cache_misses": svc.stats.compile_cache_misses,
    }


def _publish_to_baseline(key: str, record: dict) -> None:
    """Land a result in BASELINE.json's `published` map (the satellite
    contract: measured configs graduate from claim to committed data).
    Shared by every publishing bench mode."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE.json")
    with open(path) as f:
        baseline = json.load(f)
    baseline.setdefault("published", {})[key] = {
        k: v for k, v in record.items() if k != "config"
    }
    with open(path, "w") as f:
        json.dump(baseline, f, indent=2)
        f.write("\n")
    print(f"published to BASELINE.json: {key}", file=sys.stderr)


def _append_table_row(path: str, marker: str, header: str, row: str) -> None:
    """Append one markdown row to the benchmarks table identified by
    `marker`, creating the section (at end of file) on first use.
    Shared by every publishing bench mode.

    The row lands at the end of the MARKER'S OWN table, not the end of
    the file: once several sections exist, an EOF append would splice a
    row into whatever table happened to be last (which is exactly how
    the hot-path table once grew a bench-solver-shaped row)."""
    with open(path) as f:
        content = f.read()
    if not row.endswith("\n"):
        row += "\n"
    if marker not in content:
        with open(path, "w") as f:
            f.write(
                content.rstrip("\n") + "\n"
                + header.rstrip("\n") + "\n" + row
            )
        print(f"appended row to {path}", file=sys.stderr)
        return
    lines = content.splitlines(keepends=True)
    start = next(
        i for i, line in enumerate(lines) if line.startswith(marker)
    )
    insert_at = len(lines)
    last_table_line = None
    for i in range(start + 1, len(lines)):
        if lines[i].startswith("## "):  # the next section
            insert_at = i
            break
        if lines[i].lstrip().startswith("|"):
            last_table_line = i
    if last_table_line is not None:
        insert_at = last_table_line + 1
    lines.insert(insert_at, row)
    with open(path, "w") as f:
        f.write("".join(lines))
    print(f"appended row to {path}", file=sys.stderr)


def _publish_solver_baseline(record: dict) -> None:
    _publish_to_baseline(
        f"{record['config']} solver service ({record['backend']})", record
    )


def _append_benchmarks_row(path: str, record: dict) -> None:
    marker = "## Solver service (make bench-solver)"
    header = (
        f"\n{marker}\n\n"
        "Direct `ops/binpack` calls vs. the shared solve service "
        "(coalescing + shape-bucketed compile cache), same concurrent "
        "load on both paths.\n\n"
        "| Date | Backend | Config | Callers | Direct p50/p99 (ms) | "
        "Service p50/p99 (ms) | Avg coalesce | Dispatches |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['concurrency']} "
        f"| {record['direct_p50_ms']} / {record['direct_p99_ms']} "
        f"| {record['service_p50_ms']} / {record['service_p99_ms']} "
        f"| {record['avg_coalesce_factor']}x "
        f"| {record['dispatches']} |\n"
    )
    _append_table_row(path, marker, header, row)


def run_solver_service(args, metric: str) -> None:
    """Direct vs. coalesced: the same C-concurrent-callers load through
    plain ops/binpack.solve and through the shared solve service. The
    service number includes its queue/window/scatter overhead — the
    honest cost of coalescing — while direct calls contend for the
    device serially."""
    import jax

    from karpenter_tpu.ops.binpack import solve as direct_solve
    from karpenter_tpu.solver import SolverService

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    # distinct seeds so coalesced problems are genuinely different work;
    # same shape = one compile bucket, as in a fleet of same-scale ticks
    inputs_list = [
        build_inputs(
            args.pods, args.types, args.taints, args.labels,
            args.seed + i, affinity=args.affinity, anti=args.anti,
        )
        for i in range(args.concurrency)
    ]

    def direct(x):
        jax.block_until_ready(
            direct_solve(x, buckets=args.buckets, backend=args.backend)
        )

    svc = SolverService(
        window_s=0.002, max_batch=args.concurrency, backend=args.backend
    )

    def through_service(x):
        svc.solve(x, buckets=args.buckets)

    try:
        # warm both paths outside the timed region (compiles + first
        # coalesced batch size)
        t0 = time.perf_counter()
        direct(inputs_list[0])
        _measure_concurrent(through_service, inputs_list, 1)
        print(
            f"warmup (compiles): {(time.perf_counter() - t0) * 1e3:.1f} ms",
            file=sys.stderr,
        )
        direct_lat = _measure_concurrent(direct, inputs_list, args.iters)
        service_lat = _measure_concurrent(
            through_service, inputs_list, args.iters
        )
        record = _solver_service_record(
            args, jax.default_backend(), direct_lat, service_lat, svc
        )
    finally:
        svc.close()
    record_evidence(
        direct_iter_ms=[round(t, 4) for t in direct_lat],
        service_iter_ms=[round(t, 4) for t in service_lat],
        solver_service=record,
        transport_floor=measure_transport_floor(),
    )
    print(
        f"direct p50={record['direct_p50_ms']}ms "
        f"p99={record['direct_p99_ms']}ms | service "
        f"p50={record['service_p50_ms']}ms "
        f"p99={record['service_p99_ms']}ms "
        f"coalesce={record['avg_coalesce_factor']}x "
        f"dispatches={record['dispatches']}",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_solver_baseline(record)
    if args.append_benchmarks:
        _append_benchmarks_row(args.append_benchmarks, record)
    extra = (
        f"direct p50={record['direct_p50_ms']}ms/"
        f"p99={record['direct_p99_ms']}ms; coalesce "
        f"{record['avg_coalesce_factor']}x over "
        f"{record['requests']} requests in "
        f"{record['dispatches']} dispatches"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["service_p50_ms"],
        note=extra,
    )


def _hotpath_record(args, backend, direct_idle, service_idle,
                    service_conc, svc, idle_stages=None) -> dict:
    """The hotpath evidence record: idle-queue service-vs-direct (the
    acceptance ratio), the concurrent coalesce factor (must be
    preserved), and the per-stage breakdown — queue-wait, pad
    (the service-side encode), dispatch, scatter (the crop).
    `idle_stages` is the stage snapshot taken right after the
    closed-loop idle phase — its `upload` p50 is the unchanged-fleet
    transfer cost the device-resident fleet state drives to ~0
    (identity hits record 0.0 upload samples)."""
    direct_p50 = float(np.percentile(direct_idle, 50))
    service_p50 = float(np.percentile(service_idle, 50))
    reqs = max(1, svc.stats.requests)
    idle_upload = None
    if idle_stages and "upload" in idle_stages:
        idle_upload = idle_stages["upload"]["p50_ms"]
    return {
        "idle_upload_p50_ms": idle_upload,
        "resident_hits": svc.stats.resident_hits,
        "config": f"{args.pods} pods x {args.types} types",
        "backend": backend,
        "concurrency": args.concurrency,
        "direct_idle_p50_ms": round(direct_p50, 3),
        "direct_idle_p99_ms": round(
            float(np.percentile(direct_idle, 99)), 3
        ),
        "service_idle_p50_ms": round(service_p50, 3),
        "service_idle_p99_ms": round(
            float(np.percentile(service_idle, 99)), 3
        ),
        "idle_ratio": round(service_p50 / max(direct_p50, 1e-9), 3),
        "service_concurrent_p50_ms": round(
            float(np.percentile(service_conc, 50)), 3
        ),
        "avg_coalesce_factor": round(
            reqs / max(1, svc.stats.dispatches), 2
        ),
        "dispatches": svc.stats.dispatches,
        "requests": svc.stats.requests,
        "compile_cache_misses": svc.stats.compile_cache_misses,
        "immediate_dispatches": svc.stats.immediate_dispatches,
        "pipeline_overlaps": svc.stats.pipeline_overlaps,
        "stage_p50_ms": {
            stage: p["p50_ms"]
            for stage, p in svc.stage_percentiles().items()
        },
    }


def _publish_hotpath_baseline(record: dict) -> None:
    _publish_to_baseline(
        f"{record['config']} solver hotpath ({record['backend']})", record
    )


def _append_hotpath_row(path: str, record: dict) -> None:
    marker = "## Solver hot path (make bench-hotpath)"
    header = (
        f"\n{marker}\n\n"
        "Idle-queue single-caller latency through the service vs a "
        "direct `ops/binpack` call — the adaptive-window guard (the "
        "ratio is the acceptance bound) — plus the coalesce factor "
        "under concurrent load, which pipelined dispatch must "
        "preserve. Stage columns are the service-side breakdown: "
        "queue-wait, pad (encode), upload (host->device transfer, "
        "isolated), dispatch, scatter (crop).\n\n"
        "| Date | Backend | Config | Direct idle p50 (ms) | Service "
        "idle p50 (ms) | Ratio | Coalesce (concurrent) | queue-wait / "
        "pad / upload / dispatch / scatter p50 (ms) |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    stages = record["stage_p50_ms"]
    breakdown = " / ".join(
        str(stages.get(s, "-"))
        for s in ("queue_wait", "pad", "upload", "dispatch", "scatter")
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['direct_idle_p50_ms']} "
        f"| {record['service_idle_p50_ms']} "
        f"| {record['idle_ratio']}x "
        f"| {record['avg_coalesce_factor']}x @ {record['concurrency']} "
        f"| {breakdown} |\n"
    )
    _append_table_row(path, marker, header, row)


def run_hotpath(args, metric: str) -> None:
    """The solver hot-path acceptance measurement: a LONE caller on an
    idle queue must ride the service at direct-call latency (adaptive
    window: no batching-timer tax), while a concurrent burst must still
    coalesce. Per-stage p50s localize any regression to queue-wait /
    pad / dispatch / scatter."""
    import jax

    from karpenter_tpu.ops.binpack import solve as direct_solve
    from karpenter_tpu.solver import SolverService

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs_list = [
        build_inputs(
            args.pods, args.types, args.taints, args.labels,
            args.seed + i, affinity=args.affinity, anti=args.anti,
        )
        for i in range(args.concurrency)
    ]
    single = inputs_list[0]

    def direct(x):
        jax.block_until_ready(
            direct_solve(x, buckets=args.buckets, backend=args.backend)
        )

    svc = SolverService(
        window_s=0.002, max_batch=args.concurrency, backend=args.backend
    )

    def through_service(x):
        svc.solve(x, buckets=args.buckets)

    try:
        t0 = time.perf_counter()
        direct(single)
        _measure_concurrent(through_service, inputs_list, 1)  # warm
        print(
            f"warmup (compiles): {(time.perf_counter() - t0) * 1e3:.1f} ms",
            file=sys.stderr,
        )
        direct_idle, service_idle = [], []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            direct(single)
            direct_idle.append((time.perf_counter() - t0) * 1e3)
        for _ in range(args.iters):
            t0 = time.perf_counter()
            through_service(single)
            service_idle.append((time.perf_counter() - t0) * 1e3)
        # snapshot the stage rings HERE: the idle loop is the
        # unchanged-fleet closed loop (same inputs object each tick),
        # whose upload p50 the resident fleet state drives to ~0 — the
        # concurrent burst below would dilute it with real uploads
        idle_stages = svc.stage_percentiles()
        service_conc = _measure_concurrent(
            through_service, inputs_list, args.iters
        )
        record = _hotpath_record(
            args, jax.default_backend(), direct_idle, service_idle,
            service_conc, svc, idle_stages=idle_stages,
        )
    finally:
        svc.close()
    record_evidence(
        direct_idle_iter_ms=[round(t, 4) for t in direct_idle],
        service_idle_iter_ms=[round(t, 4) for t in service_idle],
        service_concurrent_iter_ms=[round(t, 4) for t in service_conc],
        hotpath=record,
        stage_percentiles=record["stage_p50_ms"],
        transport_floor=measure_transport_floor(),
    )
    print(
        f"idle: direct p50={record['direct_idle_p50_ms']}ms | service "
        f"p50={record['service_idle_p50_ms']}ms "
        f"(ratio {record['idle_ratio']}x) | concurrent service "
        f"p50={record['service_concurrent_p50_ms']}ms "
        f"coalesce={record['avg_coalesce_factor']}x | unchanged-fleet "
        f"upload p50={record['idle_upload_p50_ms']}ms "
        f"({record['resident_hits']} resident hits) | stages "
        f"{record['stage_p50_ms']}",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_hotpath_baseline(record)
    if args.append_benchmarks:
        _append_hotpath_row(args.append_benchmarks, record)
    extra = (
        f"direct idle p50={record['direct_idle_p50_ms']}ms (ratio "
        f"{record['idle_ratio']}x); coalesce "
        f"{record['avg_coalesce_factor']}x under {args.concurrency} "
        f"callers; stages(ms) {record['stage_p50_ms']}"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["service_idle_p50_ms"],
        note=extra,
        against_baseline=False,
    )


def build_consolidation_cluster(candidates: int, pods: int, seed: int):
    """A synthetic fragmented cluster in the in-memory store: every node
    is a drain candidate; utilization is deliberately uneven (rng pod
    counts, small requests) so a realistic fraction of drains fit."""
    from karpenter_tpu.api.core import (
        Container,
        Node,
        NodeCondition,
        NodeSpec,
        NodeStatus,
        ObjectMeta,
        Pod,
        PodSpec,
    )
    from karpenter_tpu.api.metricsproducer import (
        MetricsProducer,
        MetricsProducerSpec,
        PendingCapacitySpec,
    )
    from karpenter_tpu.store import Store
    from karpenter_tpu.utils.quantity import Quantity

    rng = np.random.default_rng(seed)
    store = Store()
    store.create(
        MetricsProducer(
            metadata=ObjectMeta(name="bench"),
            spec=MetricsProducerSpec(
                pending_capacity=PendingCapacitySpec(
                    node_selector={"pool": "bench"},
                    node_group_ref="bench-group",
                )
            ),
        )
    )
    for n in range(candidates):
        store.create(
            Node(
                metadata=ObjectMeta(
                    name=f"node-{n:04d}", labels={"pool": "bench"}
                ),
                spec=NodeSpec(),
                status=NodeStatus(
                    allocatable={
                        "cpu": Quantity.parse("16"),
                        "memory": Quantity.parse("64Gi"),
                        "pods": Quantity.parse("110"),
                    },
                    conditions=[NodeCondition("Ready", "True")],
                ),
            )
        )
    # skewed spread (u^2 concentrates pods on low-index nodes): the head
    # nodes run hot and veto, the long tail is lightly loaded and drains
    # — the fragmented-cluster shape consolidation exists for
    for i in range(pods):
        n = int(candidates * rng.random() ** 2) % candidates
        cpu = float(rng.choice([0.25, 0.5, 1.0, 2.0]))
        store.create(
            Pod(
                metadata=ObjectMeta(name=f"pod-{i:05d}"),
                spec=PodSpec(
                    node_name=f"node-{n:04d}",
                    containers=[
                        Container(
                            requests={
                                "cpu": Quantity.parse(str(cpu)),
                                "memory": Quantity.parse(
                                    f"{int(cpu * 2048)}Mi"
                                ),
                            }
                        )
                    ],
                ),
            )
        )
    return store


def _consolidate_record(args, backend, batched, sequential,
                        candidates: int, drainable: int, svc) -> dict:
    batched_p50 = float(np.percentile(batched, 50))
    sequential_p50 = float(np.percentile(sequential, 50))
    return {
        "config": (
            f"{candidates} candidates x {args.pods} bound pods "
            f"consolidation"
        ),
        "backend": backend,
        "candidates": candidates,
        "drainable": drainable,
        "batched_p50_ms": round(batched_p50, 3),
        "sequential_p50_ms": round(sequential_p50, 3),
        "batched_cps": round(candidates * 1000.0 / batched_p50, 1),
        "sequential_cps": round(
            candidates * 1000.0 / sequential_p50, 1
        ),
        "speedup": round(sequential_p50 / batched_p50, 2),
        "dispatches": svc.stats.dispatches,
        "compile_cache_misses": svc.stats.compile_cache_misses,
    }


def _publish_consolidate_baseline(record: dict) -> None:
    _publish_to_baseline(
        f"{record['config']} ({record['backend']})", record
    )


def _append_consolidate_row(path: str, record: dict) -> None:
    marker = "## Consolidation (make bench-consolidate)"
    header = (
        f"\n{marker}\n\n"
        "Batched drain-candidate evaluation (`service.consolidate`: one "
        "device dispatch for every candidate in a shape bucket) vs. the "
        "same masked bin-packs submitted sequentially through the "
        "service.\n\n"
        "| Date | Backend | Config | Batched p50 (ms) | Sequential p50 "
        "(ms) | Batched cand/s | Sequential cand/s | Speedup |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['batched_p50_ms']} | {record['sequential_p50_ms']} "
        f"| {record['batched_cps']} | {record['sequential_cps']} "
        f"| {record['speedup']}x |\n"
    )
    _append_table_row(path, marker, header, row)


def _warm_and_check_consolidate(svc, inputs, args) -> int:
    """Warm both submission paths' compiles outside the timed region and
    assert their verdicts agree; returns the drainable count."""
    from karpenter_tpu.consolidation import drainable

    batched_out = svc.consolidate(inputs, buckets=args.buckets)
    sequential_out = [
        svc.solve(x, buckets=args.buckets) for x in inputs
    ]
    mismatch = sum(
        drainable(a) != drainable(b)
        for a, b in zip(batched_out, sequential_out)
    )
    if mismatch:
        raise AssertionError(
            f"{mismatch} verdict(s) differ between batched and "
            "sequential paths"
        )
    return sum(drainable(o) for o in batched_out)


def run_consolidate(args, metric: str) -> None:
    """Batched vs sequential candidate evaluation: the consolidation
    acceptance claim. Both paths run the IDENTICAL masked per-candidate
    bin-packs through the shared solve service; only the submission
    shape differs — one atomic `consolidate` batch (one dispatch per
    shape bucket) vs. one `solve` at a time (one dispatch each)."""
    import jax

    from karpenter_tpu.consolidation import (
        build_problems,
        cluster_view,
        drainable,
    )
    from karpenter_tpu.solver import SolverService

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    store = build_consolidation_cluster(
        args.candidates, args.pods, args.seed
    )
    view = cluster_view(store)
    solved, inputs, trivial = build_problems(
        view, [nv.name for nv in view.nodes]
    )
    print(
        f"candidates: {len(solved)} solved + {len(trivial)} empty",
        file=sys.stderr,
    )
    backend = args.backend
    svc = SolverService(window_s=0.002, max_batch=8, backend=backend)
    try:
        n_drainable = _warm_and_check_consolidate(svc, inputs, args)
        batched_times, sequential_times = [], []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            svc.consolidate(inputs, buckets=args.buckets)
            batched_times.append((time.perf_counter() - t0) * 1e3)
        for _ in range(args.iters):
            t0 = time.perf_counter()
            for x in inputs:
                svc.solve(x, buckets=args.buckets)
            sequential_times.append((time.perf_counter() - t0) * 1e3)
        record = _consolidate_record(
            args, jax.default_backend(), batched_times,
            sequential_times, len(solved), n_drainable, svc,
        )
    finally:
        svc.close()
    record_evidence(
        batched_iter_ms=[round(t, 4) for t in batched_times],
        sequential_iter_ms=[round(t, 4) for t in sequential_times],
        consolidate=record,
        transport_floor=measure_transport_floor(),
    )
    print(
        f"batched p50={record['batched_p50_ms']}ms "
        f"({record['batched_cps']} cand/s) | sequential "
        f"p50={record['sequential_p50_ms']}ms "
        f"({record['sequential_cps']} cand/s) | "
        f"speedup={record['speedup']}x "
        f"drainable={record['drainable']}/{record['candidates']}",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_consolidate_baseline(record)
    if args.append_benchmarks:
        _append_consolidate_row(args.append_benchmarks, record)
    extra = (
        f"{record['batched_cps']} vs {record['sequential_cps']} "
        f"candidates/sec batched vs sequential "
        f"({record['speedup']}x); {record['drainable']}/"
        f"{record['candidates']} drainable"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["batched_p50_ms"],
        note=extra,
        against_baseline=False,
    )


def build_preempt_inputs(candidates: int, types: int, pods: int, seed: int):
    """A synthetic contended fleet for eviction planning: mostly-full
    node columns, priority-striped victim occupancy (sorted by
    (node, priority) — the kernel's contract), and high-priority
    candidate pods big enough that most placements need evictions."""
    from karpenter_tpu.ops.preempt import PreemptInputs

    rng = np.random.default_rng(seed)
    C, N, V, R = candidates, types, pods, 4
    node_free = rng.uniform(0.0, 2.0, (N, R)).astype(np.float32)
    node_tier = (rng.random(N) < 0.3).astype(np.int32)
    victim_node = np.sort(rng.integers(0, N, V)).astype(np.int32)
    victim_priority = np.zeros(V, np.int32)
    for n in range(N):
        seg = victim_node == n
        victim_priority[seg] = np.sort(rng.integers(0, 500, seg.sum()))
    return PreemptInputs(
        pod_requests=rng.uniform(1.0, 6.0, (C, R)).astype(np.float32),
        pod_priority=rng.integers(100, 1000, C).astype(np.int32),
        pod_valid=np.ones(C, bool),
        pod_node_forbidden=rng.random((C, N)) < 0.1,
        node_free=node_free,
        node_tier=node_tier,
        victim_requests=rng.uniform(0.1, 2.0, (V, R)).astype(
            np.float32
        ),
        victim_priority=victim_priority,
        victim_node=victim_node,
        victim_valid=np.ones(V, bool),
        victim_evictable=rng.random(V) < 0.95,
    )


def _single_candidate_inputs(inputs, c: int):
    """The same fleet, one candidate — what a per-candidate caller
    would submit (quantization scales are fleet-derived, so the plans
    match the batched rows bit for bit)."""
    import dataclasses

    return dataclasses.replace(
        inputs,
        pod_requests=inputs.pod_requests[c : c + 1],
        pod_priority=inputs.pod_priority[c : c + 1],
        pod_valid=inputs.pod_valid[c : c + 1],
        pod_node_forbidden=inputs.pod_node_forbidden[c : c + 1],
    )


def _warm_and_check_preempt(svc, inputs, args) -> int:
    """Warm both submission paths' compiles outside the timed region;
    assert batched plans == independent per-candidate plans == the
    numpy mirror, element for element. Returns the placed count."""
    from karpenter_tpu.ops.preempt import preempt_numpy

    batched = svc.preempt(inputs)
    mirror = preempt_numpy(inputs)
    for field in ("chosen_node", "evict_count", "evict_mask"):
        if not np.array_equal(
            np.asarray(getattr(batched, field)),
            np.asarray(getattr(mirror, field)),
        ):
            raise AssertionError(f"device/numpy mismatch on {field}")
    for c in range(args.candidates):
        single = svc.preempt(_single_candidate_inputs(inputs, c))
        if int(single.chosen_node[0]) != int(batched.chosen_node[c]):
            raise AssertionError(
                f"candidate {c}: batched plan != independent plan"
            )
    return int((np.asarray(batched.chosen_node) >= 0).sum())


def _preempt_record(args, backend, batched, sequential, placed: int,
                    svc) -> dict:
    batched_p50 = float(np.percentile(batched, 50))
    sequential_p50 = float(np.percentile(sequential, 50))
    return {
        "config": (
            f"{args.candidates} candidates x {args.types} node "
            f"columns x {args.pods} victims eviction planning"
        ),
        "backend": backend,
        "candidates": args.candidates,
        "placed": placed,
        "batched_p50_ms": round(batched_p50, 3),
        "sequential_p50_ms": round(sequential_p50, 3),
        "batched_cps": round(
            args.candidates * 1000.0 / batched_p50, 1
        ),
        "sequential_cps": round(
            args.candidates * 1000.0 / sequential_p50, 1
        ),
        "speedup": round(sequential_p50 / batched_p50, 2),
        "dispatches": svc.stats.preempt_dispatches,
        "compile_cache_misses": svc.stats.compile_cache_misses,
    }


def _append_preempt_row(path: str, record: dict) -> None:
    marker = "## Preemption (make bench-preempt)"
    header = (
        f"\n{marker}\n\n"
        "Batched eviction planning (`service.preempt`: every candidate "
        "pod's minimal-eviction placement in ONE device dispatch) vs. "
        "the same plans submitted one candidate at a time.\n\n"
        "| Date | Backend | Config | Batched p50 (ms) | Sequential p50 "
        "(ms) | Batched cand/s | Sequential cand/s | Speedup |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['batched_p50_ms']} | {record['sequential_p50_ms']} "
        f"| {record['batched_cps']} | {record['sequential_cps']} "
        f"| {record['speedup']}x |\n"
    )
    _append_table_row(path, marker, header, row)


def run_preempt(args, metric: str) -> None:
    """Batched vs sequential eviction planning: the preemption
    acceptance claim (docs/preemption.md). Both paths run IDENTICAL
    per-candidate plans through the shared solve service; only the
    submission shape differs — all candidates in one PreemptInputs
    (one dispatch) vs. one single-candidate problem at a time."""
    import jax

    from karpenter_tpu.solver import SolverService

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs = build_preempt_inputs(
        args.candidates, args.types, args.pods, args.seed
    )
    singles = [
        _single_candidate_inputs(inputs, c)
        for c in range(args.candidates)
    ]
    backend = args.backend
    svc = SolverService(window_s=0.002, max_batch=8, backend=backend)
    try:
        placed = _warm_and_check_preempt(svc, inputs, args)
        batched_times, sequential_times = [], []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            svc.preempt(inputs)
            batched_times.append((time.perf_counter() - t0) * 1e3)
        for _ in range(args.iters):
            t0 = time.perf_counter()
            for single in singles:
                svc.preempt(single)
            sequential_times.append((time.perf_counter() - t0) * 1e3)
        record = _preempt_record(
            args, jax.default_backend(), batched_times,
            sequential_times, placed, svc,
        )
    finally:
        svc.close()
    record_evidence(
        batched_iter_ms=[round(t, 4) for t in batched_times],
        sequential_iter_ms=[round(t, 4) for t in sequential_times],
        preempt=record,
        transport_floor=measure_transport_floor(),
    )
    print(
        f"batched p50={record['batched_p50_ms']}ms "
        f"({record['batched_cps']} cand/s) | sequential "
        f"p50={record['sequential_p50_ms']}ms "
        f"({record['sequential_cps']} cand/s) | "
        f"speedup={record['speedup']}x "
        f"placed={record['placed']}/{record['candidates']}",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_preempt_row(args.append_benchmarks, record)
    extra = (
        f"{record['batched_cps']} vs {record['sequential_cps']} "
        f"candidates/sec batched vs sequential "
        f"({record['speedup']}x); {record['placed']}/"
        f"{record['candidates']} placeable"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["batched_p50_ms"],
        note=extra,
        against_baseline=False,
    )


def build_forecast_inputs(series: int, history: int, seed: int):
    """A fleet of metric histories: mixed flat/ramping/seasonal series
    with gaps, half Holt-Winters and half robust-linear — the shape the
    BatchAutoscaler hands the service every tick."""
    from karpenter_tpu.forecast.models import ForecastInputs

    rng = np.random.RandomState(seed)
    S, T = series, history
    base = rng.uniform(5, 500, (S, 1)).astype(np.float32)
    slope = rng.uniform(-0.5, 2.0, (S, 1)).astype(np.float32)
    ticks = np.arange(T, dtype=np.float32)[None, :]
    seasonal = (
        rng.uniform(0, 30, (S, 1))
        * np.sin(ticks * 2 * np.pi / 12)
    ).astype(np.float32)
    noise = rng.normal(0, 3, (S, T)).astype(np.float32)
    values = (base + slope * ticks * 10.0 + seasonal + noise).astype(
        np.float32
    )
    valid = rng.rand(S, T) > 0.1
    times = ((ticks - (T - 1)) * 10.0).repeat(S, axis=0).astype(np.float32)
    horizon = rng.uniform(30, 120, S).astype(np.float32)
    weights = np.power(
        np.float32(0.5), (-times) / horizon[:, None]
    ).astype(np.float32)
    return ForecastInputs(
        values=values, valid=valid, times=times, weights=weights,
        horizon=horizon,
        step_s=np.full(S, 10.0, np.float32),
        model=(np.arange(S) % 2).astype(np.int32),
        season=rng.choice([0, 6, 12], S).astype(np.int32),
        alpha=np.full(S, 0.5, np.float32),
        beta=np.full(S, 0.1, np.float32),
        gamma=np.full(S, 0.3, np.float32),
    )


def _forecast_record(args, backend, batched, per_series) -> dict:
    batched_p50 = float(np.percentile(batched, 50))
    loop_p50 = float(np.percentile(per_series, 50))
    return {
        "config": f"{args.series} series x {args.history} samples "
                  "forecast",
        "backend": backend,
        "series": args.series,
        "history": args.history,
        "batched_p50_ms": round(batched_p50, 3),
        "per_series_p50_ms": round(loop_p50, 3),
        "batched_sps": round(args.series * 1000.0 / batched_p50, 1),
        "per_series_sps": round(args.series * 1000.0 / loop_p50, 1),
        "speedup": round(loop_p50 / batched_p50, 2),
    }


def _publish_forecast_baseline(record: dict) -> None:
    _publish_to_baseline(
        f"{record['config']} ({record['backend']})", record
    )


def _append_forecast_row(path: str, record: dict) -> None:
    marker = "## Forecast (make bench-forecast)"
    header = (
        f"\n{marker}\n\n"
        "Batched fleet forecast (every metric series in ONE device "
        "dispatch — the shape the BatchAutoscaler submits each tick) "
        "vs. the same series forecast one dispatch at a time.\n\n"
        "| Date | Backend | Config | Batched p50 (ms) | Per-series p50 "
        "(ms) | Batched series/s | Per-series series/s | Speedup |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['batched_p50_ms']} | {record['per_series_p50_ms']} "
        f"| {record['batched_sps']} | {record['per_series_sps']} "
        f"| {record['speedup']}x |\n"
    )
    _append_table_row(path, marker, header, row)


def _measure_forecast(args, inputs, rows):
    """Timed batched vs per-series loops (compiles warmed outside)."""
    import jax

    from karpenter_tpu.forecast.models import forecast_jit

    jax.block_until_ready(forecast_jit(inputs))
    jax.block_until_ready(forecast_jit(rows[0]))
    batched_times, per_series_times = [], []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        jax.block_until_ready(forecast_jit(inputs))
        batched_times.append((time.perf_counter() - t0) * 1e3)
    for _ in range(args.iters):
        t0 = time.perf_counter()
        for row in rows:
            jax.block_until_ready(forecast_jit(row))
        per_series_times.append((time.perf_counter() - t0) * 1e3)
    return batched_times, per_series_times


def run_forecast(args, metric: str) -> None:
    """Batched vs per-series forecasting: the predictive subsystem's
    one-dispatch claim (docs/forecasting.md). Both paths run the
    IDENTICAL jitted kernel on the identical histories; only the
    dispatch shape differs — one [S, T] program vs S [1, T] programs
    (the second compiled once and reused, so the gap is pure dispatch
    and launch overhead, not recompiles)."""
    import dataclasses

    import jax

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs = build_forecast_inputs(args.series, args.history, args.seed)
    rows = [
        dataclasses.replace(
            inputs,
            **{
                f.name: np.asarray(getattr(inputs, f.name))[i: i + 1]
                for f in dataclasses.fields(inputs)
            },
        )
        for i in range(args.series)
    ]
    batched_times, per_series_times = _measure_forecast(
        args, inputs, rows
    )
    record = _forecast_record(
        args, jax.default_backend(), batched_times, per_series_times
    )
    record_evidence(
        batched_iter_ms=[round(t, 4) for t in batched_times],
        per_series_iter_ms=[round(t, 4) for t in per_series_times],
        forecast=record,
        transport_floor=measure_transport_floor(),
    )
    print(
        f"batched p50={record['batched_p50_ms']}ms "
        f"({record['batched_sps']} series/s) | per-series "
        f"p50={record['per_series_p50_ms']}ms "
        f"({record['per_series_sps']} series/s) | "
        f"speedup={record['speedup']}x",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_forecast_baseline(record)
    if args.append_benchmarks:
        _append_forecast_row(args.append_benchmarks, record)
    extra = (
        f"{record['batched_sps']} vs {record['per_series_sps']} "
        f"series/sec batched vs per-series ({record['speedup']}x)"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["batched_p50_ms"],
        note=extra,
        against_baseline=False,
    )


def build_cost_inputs(rows: int, metrics: int, seed: int):
    """A fleet of SLO-opted autoscaler rows: mixed demand regimes, a
    spread of unit costs and violation weights, some budget-capped and
    some forecast-sigma'd — the shape the CostEngine hands the service
    each tick (every row slo_valid: the bench measures the refine, not
    the opt-out)."""
    from karpenter_tpu.ops.cost import CostInputs

    rng = np.random.RandomState(seed)
    N, M = rows, metrics
    base = rng.randint(1, 200, N).astype(np.int32)
    return CostInputs(
        base_desired=base,
        min_replicas=np.maximum(base - 50, 0).astype(np.int32),
        max_replicas=(base + rng.randint(50, 500, N)).astype(np.int32),
        unit_cost=rng.choice([0.07, 0.19, 1.0, 4.8], N).astype(np.float32),
        slo_weight=rng.choice([0.0, 5.0, 50.0, 500.0], N).astype(
            np.float32
        ),
        max_hourly_cost=rng.choice([0.0, 25.0, 250.0], N).astype(
            np.float32
        ),
        slo_valid=np.ones(N, bool),
        slo_target=rng.uniform(0.5, 10, (N, M)).astype(np.float32),
        demand_mu=rng.uniform(0, 1000, (N, M)).astype(np.float32),
        demand_sigma=rng.choice([0.0, 5.0, 50.0], (N, M)).astype(
            np.float32
        ),
        demand_valid=rng.rand(N, M) > 0.1,
    )


def _cost_record(args, backend, batched, per_row) -> dict:
    batched_p50 = float(np.percentile(batched, 50))
    loop_p50 = float(np.percentile(per_row, 50))
    return {
        "config": f"{args.cost_rows} autoscalers x {args.cost_metrics} "
                  "metrics cost refine",
        "backend": backend,
        "rows": args.cost_rows,
        "metrics": args.cost_metrics,
        "batched_p50_ms": round(batched_p50, 3),
        "per_ha_p50_ms": round(loop_p50, 3),
        "batched_rps": round(args.cost_rows * 1000.0 / batched_p50, 1),
        "per_ha_rps": round(args.cost_rows * 1000.0 / loop_p50, 1),
        "speedup": round(loop_p50 / batched_p50, 2),
    }


def _append_cost_row(path: str, record: dict) -> None:
    marker = "## Cost refine (make bench-cost)"
    header = (
        f"\n{marker}\n\n"
        "Batched multi-objective cost/SLO refinement (every SLO-opted "
        "autoscaler's candidate ladder scored in ONE device dispatch — "
        "the shape the CostEngine submits each tick) vs. the same rows "
        "refined one HA at a time. XLA == numpy bit-parity on every "
        "output field is asserted before timing.\n\n"
        "| Date | Backend | Config | Batched p50 (ms) | Per-HA p50 "
        "(ms) | Batched rows/s | Per-HA rows/s | Speedup |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['batched_p50_ms']} | {record['per_ha_p50_ms']} "
        f"| {record['batched_rps']} | {record['per_ha_rps']} "
        f"| {record['speedup']}x |\n"
    )
    _append_table_row(path, marker, header, row)


def run_cost(args, metric: str) -> None:  # lint: allow-complexity — bench arm: parity pin + two timed dispatch shapes inline
    """Batched vs per-HA multi-objective refinement: the cost
    subsystem's one-dispatch claim (docs/cost.md). Both paths run the
    IDENTICAL jitted kernel on identical rows; only the dispatch shape
    differs — one [N, K, M] program vs N [1, K, M] programs (the second
    compiled once and reused, so the gap is pure dispatch/launch
    overhead, not recompiles). The numpy mirror is asserted
    bit-identical on every output field before any timing."""
    import dataclasses

    import jax

    from karpenter_tpu.ops.cost import CostOutputs, cost_jit, cost_numpy

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs = build_cost_inputs(args.cost_rows, args.cost_metrics, args.seed)
    rows = [
        dataclasses.replace(
            inputs,
            **{
                f.name: np.asarray(getattr(inputs, f.name))[i: i + 1]
                for f in dataclasses.fields(inputs)
            },
        )
        for i in range(args.cost_rows)
    ]
    # parity pin FIRST (the bench's acceptance gate): device == mirror,
    # bit for bit, on the exact workload about to be timed
    device_out = cost_jit(inputs)
    jax.block_until_ready(device_out)
    host_out = cost_numpy(inputs)
    for f in dataclasses.fields(CostOutputs):
        a = np.asarray(getattr(device_out, f.name))
        b = np.asarray(getattr(host_out, f.name))
        if not np.array_equal(a, b):
            raise AssertionError(
                f"cost kernel parity violated on {f.name}: "
                f"device != numpy mirror"
            )
    jax.block_until_ready(cost_jit(rows[0]))  # warm the per-HA shape

    batched_times, per_row_times = [], []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        jax.block_until_ready(cost_jit(inputs))
        batched_times.append((time.perf_counter() - t0) * 1e3)
    for _ in range(args.iters):
        t0 = time.perf_counter()
        for row in rows:
            jax.block_until_ready(cost_jit(row))
        per_row_times.append((time.perf_counter() - t0) * 1e3)

    record = _cost_record(
        args, jax.default_backend(), batched_times, per_row_times
    )
    record_evidence(
        batched_iter_ms=[round(t, 4) for t in batched_times],
        per_ha_iter_ms=[round(t, 4) for t in per_row_times],
        cost=record,
        transport_floor=measure_transport_floor(),
    )
    print(
        f"batched p50={record['batched_p50_ms']}ms "
        f"({record['batched_rps']} rows/s) | per-HA "
        f"p50={record['per_ha_p50_ms']}ms "
        f"({record['per_ha_rps']} rows/s) | "
        f"speedup={record['speedup']}x",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_cost_row(args.append_benchmarks, record)
    extra = (
        f"{record['batched_rps']} vs {record['per_ha_rps']} rows/sec "
        f"batched vs per-HA ({record['speedup']}x); numpy parity pinned"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["batched_p50_ms"],
        note=extra,
        against_baseline=False,
    )


def build_poolgroup_inputs(groups: int, pools: int, metrics: int,
                           seed: int):
    """A fleet of pool groups with every member pool live and every
    coupling SLACK (ratios/budget invalid, tier penalties zero): the
    one configuration where the joint program is provably the per-pool
    cost ladder bit for bit, so the timed comparison is the same math
    in two dispatch shapes — the bench measures the dispatch collapse,
    not a different algorithm. The masked constraint operands still
    run inside the joint program, so its timing is honest for the
    enforcing case too."""
    from karpenter_tpu.ops.poolgroup import RATIO_SLOTS, PoolGroupInputs

    rng = np.random.RandomState(seed)
    G, P, M = groups, pools, metrics
    base = rng.randint(1, 200, (G, P)).astype(np.int32)
    ratio_a = rng.randint(0, P, (G, RATIO_SLOTS)).astype(np.int32)
    return PoolGroupInputs(
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
        ratio_min_num=np.zeros((G, RATIO_SLOTS), np.int32),
        ratio_min_den=np.ones((G, RATIO_SLOTS), np.int32),
        ratio_max_num=np.zeros((G, RATIO_SLOTS), np.int32),
        ratio_max_den=np.zeros((G, RATIO_SLOTS), np.int32),
        ratio_valid=np.zeros((G, RATIO_SLOTS), bool),
        group_budget=np.zeros(G, np.float32),
        group_valid=np.zeros(G, bool),
    )


def _poolgroup_record(args, backend, joint, per_pool) -> dict:
    joint_p50 = float(np.percentile(joint, 50))
    loop_p50 = float(np.percentile(per_pool, 50))
    n = args.poolgroup_groups * args.poolgroup_pools
    return {
        "config": f"{args.poolgroup_groups} pool groups x "
                  f"{args.poolgroup_pools} pools x "
                  f"{args.poolgroup_metrics} metrics joint allocation",
        "backend": backend,
        "groups": args.poolgroup_groups,
        "pools": args.poolgroup_pools,
        "metrics": args.poolgroup_metrics,
        "joint_p50_ms": round(joint_p50, 3),
        "per_pool_p50_ms": round(loop_p50, 3),
        "joint_pools_ps": round(n * 1000.0 / joint_p50, 1),
        "per_pool_pools_ps": round(n * 1000.0 / loop_p50, 1),
        "speedup": round(loop_p50 / joint_p50, 2),
        "dispatches_joint": 1,
        "dispatches_per_pool": n,
        "parity": "bitwise",
    }


def _append_poolgroup_row(path: str, record: dict) -> None:
    marker = "## Pool-group joint allocation (make bench-poolgroup)"
    header = (
        f"\n{marker}\n\n"
        "One batched joint pool-group dispatch (every group's "
        "cross-product candidate ladder scored together, constraint "
        "operands masked in-program) vs. the groups*pools per-pool "
        "cost dispatches the joint plane replaces. Before timing, XLA "
        "== numpy bit-parity is asserted on every output leaf AND the "
        "joint selection under slack constraints is asserted "
        "bit-identical to the per-pool cost ladder — same math, two "
        "dispatch shapes.\n\n"
        "| Date | Backend | Config | Joint p50 (ms) | Per-pool p50 "
        "(ms) | Dispatches | Speedup |\n"
        "|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['joint_p50_ms']} | {record['per_pool_p50_ms']} "
        f"| 1 vs {record['dispatches_per_pool']} "
        f"| {record['speedup']}x |\n"
    )
    _append_table_row(path, marker, header, row)


def run_poolgroup(args, metric: str) -> None:  # lint: allow-complexity — bench arm: two parity pins + two timed dispatch shapes inline
    """One batched joint pool-group dispatch vs the per-pool cost
    dispatches it replaces (docs/poolgroups.md). The workload keeps
    every coupling slack so the joint program's selection is provably
    the per-pool cost ladder bit for bit (the wire-compat property
    tests/test_poolgroup.py pins); the timed gap is then pure dispatch
    shape — one [G, P, ...] program vs G*P [1, ...] programs, the
    second compiled once and reused."""
    import dataclasses

    import jax

    from karpenter_tpu.ops.cost import CostInputs, cost_jit
    from karpenter_tpu.ops.poolgroup import (
        PoolGroupOutputs,
        poolgroup_jit,
        poolgroup_numpy,
    )

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs = build_poolgroup_inputs(
        args.poolgroup_groups, args.poolgroup_pools,
        args.poolgroup_metrics, args.seed,
    )
    flat = CostInputs(
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
    )
    n = args.poolgroup_groups * args.poolgroup_pools
    rows = [
        dataclasses.replace(
            flat,
            **{
                f.name: np.asarray(getattr(flat, f.name))[i: i + 1]
                for f in dataclasses.fields(flat)
            },
        )
        for i in range(n)
    ]
    # parity pin 1 (the bench's acceptance gate): joint device == numpy
    # mirror, bit for bit, on every output leaf of the timed workload
    joint_out = poolgroup_jit(inputs)
    jax.block_until_ready(joint_out)
    host_out = poolgroup_numpy(inputs)
    for f in dataclasses.fields(PoolGroupOutputs):
        a = np.asarray(getattr(joint_out, f.name))
        b = np.asarray(getattr(host_out, f.name))
        if not np.array_equal(a, b):
            raise AssertionError(
                f"poolgroup kernel parity violated on {f.name}: "
                f"device != numpy mirror"
            )
    # parity pin 2 (the replaces claim): under slack couplings the joint
    # selection IS the per-pool cost ladder — same math, so the timed
    # comparison below measures dispatch shape and nothing else
    flat_out = cost_jit(flat)
    jax.block_until_ready(flat_out)
    if not np.array_equal(
        np.asarray(joint_out.desired).reshape(-1),
        np.asarray(flat_out.desired),
    ):
        raise AssertionError(
            "joint selection != per-pool cost ladder under slack "
            "couplings — the dispatch comparison would be dishonest"
        )
    jax.block_until_ready(cost_jit(rows[0]))  # warm the per-pool shape

    joint_times, per_pool_times = [], []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        jax.block_until_ready(poolgroup_jit(inputs))
        joint_times.append((time.perf_counter() - t0) * 1e3)
    for _ in range(args.iters):
        t0 = time.perf_counter()
        for row in rows:
            jax.block_until_ready(cost_jit(row))
        per_pool_times.append((time.perf_counter() - t0) * 1e3)

    record = _poolgroup_record(
        args, jax.default_backend(), joint_times, per_pool_times
    )
    record_evidence(
        joint_iter_ms=[round(t, 4) for t in joint_times],
        per_pool_iter_ms=[round(t, 4) for t in per_pool_times],
        poolgroup=record,
        transport_floor=measure_transport_floor(),
    )
    print(
        f"joint p50={record['joint_p50_ms']}ms "
        f"({record['joint_pools_ps']} pools/s, 1 dispatch) | per-pool "
        f"p50={record['per_pool_p50_ms']}ms "
        f"({record['per_pool_pools_ps']} pools/s, "
        f"{record['dispatches_per_pool']} dispatches) | "
        f"speedup={record['speedup']}x",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_poolgroup_row(args.append_benchmarks, record)
    extra = (
        f"1 vs {record['dispatches_per_pool']} dispatches "
        f"({record['speedup']}x); numpy + cost-ladder parity pinned"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["joint_p50_ms"],
        note=extra,
        against_baseline=False,
    )


def run_decide(args, metric: str) -> None:
    """The reference computes ONE scalar HPA decision per object per 10 s
    tick (pkg/autoscaler/autoscaler.go:81-113). Here the whole fleet's
    decisions — per-metric recommendation, select policy, stabilization
    window, Count/Percent rate-limit policies, min/max bounds — run as one
    device call (ops/decision.decide_jit)."""
    import jax

    from karpenter_tpu.ops.decision import decide_jit
    from karpenter_tpu.parallel.mesh import example_decision_inputs

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    inputs = jax.device_put(
        example_decision_inputs(N=args.decide, M=4, seed=args.seed)
    )
    jax.block_until_ready(inputs)
    t0 = time.perf_counter()
    jax.block_until_ready(decide_jit(inputs))
    print(
        f"first call (compile+run): {(time.perf_counter() - t0) * 1e3:.1f} ms",
        file=sys.stderr,
    )
    times = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        jax.block_until_ready(decide_jit(inputs))
        times.append((time.perf_counter() - t0) * 1e3)
    record_evidence(
        iter_ms=[round(t, 4) for t in times],
        transport_floor=measure_transport_floor(),
    )
    p50 = float(np.percentile(times, 50))
    dps = args.decide * 1000.0 / p50 if p50 else 0.0
    print(
        f"p50={p50:.2f}ms p95={float(np.percentile(times, 95)):.2f}ms "
        f"autoscaler decisions/sec={dps:.0f}",
        file=sys.stderr,
    )
    extra = f"{dps:.0f} autoscaler decisions/sec"
    emit(
        f"{metric} ({jax.default_backend()})",
        p50,
        note=extra,
    )


def run_mesh(args, metric: str) -> None:
    """Sharded solve over an N-device pods x groups mesh — the scale story
    the reference concedes ('breaks down as the cluster scales',
    docs/designs/DESIGN.md): rows (pods) and columns (instance types) are
    sharded with NamedShardings and GSPMD partitions the whole program.
    A run that finds fewer than N devices fails. Outputs are asserted
    element-for-element equal to the single-device solve before
    timing."""
    import jax

    from karpenter_tpu.ops.binpack import binpack
    from karpenter_tpu.parallel.mesh import build_mesh, sharded_binpack

    if len(jax.devices()) < args.mesh:
        raise RuntimeError(
            f"--mesh {args.mesh} needs {args.mesh} devices, "
            f"found {len(jax.devices())}"
        )
    mesh = build_mesh(n_devices=args.mesh, slices=args.slices)
    print(f"mesh: {dict(mesh.shape)} on {jax.default_backend()}", file=sys.stderr)
    inputs = build_inputs(
        args.pods, args.types, args.taints, args.labels, args.seed,
        affinity=args.affinity,
    )

    single = jax.device_get(binpack(inputs, buckets=args.buckets))
    sharded = jax.device_get(
        sharded_binpack(mesh, inputs, buckets=args.buckets)
    )
    np.testing.assert_array_equal(sharded.assigned, single.assigned)
    np.testing.assert_array_equal(sharded.nodes_needed, single.nodes_needed)
    np.testing.assert_array_equal(sharded.lp_bound, single.lp_bound)
    assert int(sharded.unschedulable) == int(single.unschedulable)
    print("sharded outputs == single-device outputs", file=sys.stderr)

    times = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        out = sharded_binpack(mesh, inputs, buckets=args.buckets)
        jax.block_until_ready(out.nodes_needed)
        times.append((time.perf_counter() - t0) * 1e3)
    record_evidence(
        iter_ms=[round(t, 4) for t in times],
        mesh_shape=dict(mesh.shape),
        transport_floor=measure_transport_floor(),
    )
    p50 = float(np.percentile(times, 50))
    print(f"sharded p50={p50:.2f}ms over {args.iters} iters", file=sys.stderr)
    emit(f"{metric} ({jax.default_backend()})", p50)



def _shard_parity(out, ref, label: str, lp_tol: int = 1) -> None:
    """Pin the sharded-output contract: integer outputs EXACT, lp_bound
    within the ±1 reduction-order tolerance the numpy-parity contract
    already carves out (ops/numpy_binpack.py docstring — sharding the
    pod axis reorders the same f32 demand accumulation)."""
    for name in ("assigned", "assigned_count", "nodes_needed"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, name)),
            np.asarray(getattr(ref, name)),
            err_msg=f"{label}: {name}",
        )
    assert int(out.unschedulable) == int(ref.unschedulable), label
    drift = np.abs(
        np.asarray(out.lp_bound, np.int64)
        - np.asarray(ref.lp_bound, np.int64)
    )
    assert int(drift.max(initial=0)) <= lp_tol, (
        f"{label}: lp_bound drift {int(drift.max())} > {lp_tol}"
    )


def _publish_shard_baseline(record: dict) -> None:
    _publish_to_baseline(
        f"{record['config']} sharded fleet solve ({record['backend']})",
        record,
    )


def _append_shard_row(path: str, record: dict) -> None:
    marker = "## Sharded fleet solve (make bench-shard)"
    header = (
        f"\n{marker}\n\n"
        "One fleet-scale bin-pack through the `SolverService` seam, "
        "routed by the sharded dispatch strategy onto a pods×groups "
        "mesh ([solver-service.md](solver-service.md) \"Sharded "
        "dispatch\"), per mesh device count. Outputs are pinned against "
        "the single-device and numpy paths before timing (integer "
        "fields exact, lp_bound ±1). Honest-reading note: on the "
        "host-emulated CPU mesh all virtual devices share one socket's "
        "cores and DRAM bandwidth — the single-device baseline is "
        "already multi-threaded, so the curve here is compressed "
        "relative to real multi-chip hardware, where each shard owns "
        "its cores/HBM and the pods axis is embarrassingly parallel up "
        "to one cross-shard reduction per aggregate.\n\n"
        "| Date | Backend | Config | Mesh | p50 by device count (ms) | "
        "Speedup @ max | Upload p50 @ max (ms) | numpy mirror (ms) |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    per = record["per_device_p50_ms"]
    p50s = " / ".join(f"{n}: {per[n]}" for n in sorted(per, key=int))
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['mesh']} | {p50s} "
        f"| {record['speedup_at_max']}x @ {record['max_devices']} "
        f"| {record['upload_p50_ms_at_max']} "
        f"| {record['numpy_mirror_ms']} |\n"
    )
    _append_table_row(path, marker, header, row)


def _measure_shard_config(args, inputs, ref_np, n: int, timeout_s: float):
    """(p50_ms, upload_p50_ms, iter_ms) for one mesh device count: a
    fresh SolverService capped at n devices, warm + parity-checked
    against the numpy mirror before timing. n=1 cannot build a mesh and
    is the single-device baseline through the same seam."""
    from karpenter_tpu.metrics.registry import GaugeRegistry
    from karpenter_tpu.solver import SolverService

    svc = SolverService(
        registry=GaugeRegistry(),
        backend=args.backend,
        shard_devices=n,
        default_timeout_s=timeout_s,
    )
    try:
        out = svc.solve(inputs, buckets=args.buckets)  # warm/compile
        if n > 1:
            assert svc.stats.shard_dispatches >= 1, (
                f"{n}-device run did not route through the sharded "
                f"dispatch strategy: {svc.stats}"
            )
        else:
            assert svc.stats.shard_dispatches == 0, svc.stats
        _shard_parity(out, ref_np, f"{n}-device vs numpy")
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            svc.solve(inputs, buckets=args.buckets)
            times.append((time.perf_counter() - t0) * 1e3)
        assert svc.stats.fallbacks == 0, (
            f"device path degraded during the measurement: {svc.stats}"
        )
        upload = svc.stage_percentiles().get("upload", {})
        return (
            round(float(np.percentile(times, 50)), 1),
            upload.get("p50_ms", 0.0),
            [round(t, 2) for t in times],
        )
    finally:
        svc.close()


def run_shard(args, metric: str) -> None:
    """The sharded-dispatch acceptance measurement (ROADMAP item 1):
    ONE fleet decision at --pods x --types through the production
    SolverService seam, on meshes of increasing device count. The
    service routes the request itself (the cell count crosses
    DEFAULT_SHARD_THRESHOLD; a 1-device run cannot build a mesh and is
    the single-device baseline through the SAME seam). A run that finds
    fewer devices than its largest scaling point fails."""
    need = max(args.shard_scaling)
    import jax

    if len(jax.devices()) < need:
        raise RuntimeError(
            f"--shard needs {need} devices, found {len(jax.devices())}"
        )

    from karpenter_tpu.ops.numpy_binpack import binpack_numpy
    from karpenter_tpu.parallel.mesh import factorize

    backend = jax.default_backend()
    print(
        f"backend={backend} devices={len(jax.devices())}",
        file=sys.stderr,
    )
    inputs = build_inputs(
        args.pods, args.types, args.taints, args.labels, args.seed,
        affinity=args.affinity,
    )
    # generous per-solve deadline: a 10^9-cell solve on emulated
    # hardware runs tens of seconds, and a deadline expiry would
    # silently swap the numpy fallback into the timing
    timeout_s = 1800.0

    t0 = time.perf_counter()
    ref_np = binpack_numpy(inputs, buckets=args.buckets)
    numpy_ms = (time.perf_counter() - t0) * 1e3
    print(f"numpy mirror: {numpy_ms:.0f} ms", file=sys.stderr)

    per_p50, per_upload, per_iters = {}, {}, {}
    for n in args.shard_scaling:
        per_p50[n], per_upload[n], per_iters[n] = _measure_shard_config(
            args, inputs, ref_np, n, timeout_s
        )
        print(
            f"{n}-device p50 {per_p50[n]:.1f} ms "
            f"(upload p50 {per_upload[n]:.2f} ms)",
            file=sys.stderr,
        )

    base = per_p50.get(1, per_p50[min(per_p50)])
    cells = args.pods * args.types
    record = {
        "config": f"{args.pods} pods x {args.types} types",
        "backend": backend,
        "mesh": "x".join(str(e) for e in factorize(need)),
        "max_devices": need,
        "per_device_p50_ms": {str(n): per_p50[n] for n in per_p50},
        "per_device_upload_ms": {
            str(n): per_upload[n] for n in per_upload
        },
        "speedup_at_max": round(base / max(per_p50[need], 1e-9), 2),
        "upload_p50_ms_at_max": per_upload[need],
        "cells_per_sec_at_max": round(
            cells / max(per_p50[need] / 1e3, 1e-9)
        ),
        "numpy_mirror_ms": round(numpy_ms, 1),
        "parity": "int outputs exact vs single-device+numpy; lp ±1",
    }
    record_evidence(
        shard=record,
        per_device_iter_ms={str(n): per_iters[n] for n in per_iters},
        transport_floor=measure_transport_floor(),
    )
    print(
        f"sharded fleet solve: {record['per_device_p50_ms']} ms "
        f"(speedup {record['speedup_at_max']}x @ {need} devices; "
        f"numpy mirror {record['numpy_mirror_ms']} ms)",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_shard_baseline(record)
    if args.append_benchmarks:
        _append_shard_row(args.append_benchmarks, record)
    extra = (
        f"device-count p50s (ms) {record['per_device_p50_ms']}; "
        f"speedup {record['speedup_at_max']}x @ {need} devices on a "
        f"shared-host emulated mesh; numpy mirror "
        f"{record['numpy_mirror_ms']} ms; sharded == single-device == "
        f"numpy (int exact, lp ±1)"
    )
    emit(
        f"{metric} ({backend})",
        per_p50[need],
        note=extra,
        against_baseline=False,
    )


def _e2e_anti_affinity(app: str):
    """Required hostname self-anti-affinity for --e2e --anti: the
    StatefulSet one-replica-per-node pattern, through the REAL spec
    parse -> columnar anti-shape intern -> _expand_anti_rows ->
    pod_exclusive operand path."""
    from karpenter_tpu.api.core import (
        Affinity,
        LabelSelector,
        PodAffinityTerm,
        PodAntiAffinity,
    )

    return Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                PodAffinityTerm(
                    label_selector=LabelSelector(
                        match_labels={"app": app}
                    ),
                    topology_key="kubernetes.io/hostname",
                )
            ]
        )
    )


def _e2e_affinity_shapes():
    """A few realistic affinity variants for --e2e --affinity: require
    ssd, forbid hdd, prefer ssd (weight 80)."""
    from karpenter_tpu.api.core import (
        Affinity,
        NodeAffinity,
        NodeSelector,
        NodeSelectorRequirement,
        NodeSelectorTerm,
        PreferredSchedulingTerm,
    )

    def required(operator, values):
        return Affinity(
            node_affinity=NodeAffinity(
                required_during_scheduling_ignored_during_execution=(
                    NodeSelector(
                        node_selector_terms=[
                            NodeSelectorTerm(
                                match_expressions=[
                                    NodeSelectorRequirement(
                                        key="disk",
                                        operator=operator,
                                        values=values,
                                    )
                                ]
                            )
                        ]
                    )
                )
            )
        )

    prefer_ssd = Affinity(
        node_affinity=NodeAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                PreferredSchedulingTerm(
                    weight=80,
                    preference=NodeSelectorTerm(
                        match_expressions=[
                            NodeSelectorRequirement(
                                key="disk", operator="In", values=["ssd"]
                            )
                        ]
                    ),
                )
            ]
        )
    )
    return [
        required("In", ["ssd"]),
        required("NotIn", ["hdd"]),
        prefer_ssd,
    ]


def run_e2e(args, metric: str) -> None:  # lint: allow-complexity — honest e2e: every stage of the tick measured inline
    """Full control-plane tick at scale: one solve_pending call — node
    listing, group profiling, columnar cache snapshot, encode, transfer,
    device bin-pack, status + gauge writes — exactly the path a
    MetricsProducer reconcile runs (BASELINE.json 'p50 reconcile
    latency'). Store population cost is excluded: pods arrive via watch
    events over the fleet's lifetime."""
    import jax

    from karpenter_tpu.api.core import (
        Container,
        Node,
        NodeCondition,
        NodeStatus,
        ObjectMeta,
        Pod,
        PodSpec,
    )
    from karpenter_tpu.api.metricsproducer import (
        MetricsProducer,
        MetricsProducerSpec,
        PendingCapacitySpec,
    )
    import functools

    from karpenter_tpu.metrics.producers.pendingcapacity import (
        register_gauges,
        solve_pending,
    )
    from karpenter_tpu.ops.binpack import solve
    from karpenter_tpu.metrics.registry import GaugeRegistry
    from karpenter_tpu.metrics.producers.pendingcapacity import (
        group_profile,
    )
    from karpenter_tpu.store import Store
    from karpenter_tpu.store.columnar import PendingFeed
    from karpenter_tpu.utils.quantity import Quantity

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    rng = np.random.default_rng(args.seed)
    store = Store()
    feed = PendingFeed(store, group_profile)
    cpu_choices = [Quantity.parse(q) for q in ("100m", "250m", "500m", "1", "2", "4")]
    mem_choices = [Quantity.parse(q) for q in ("128Mi", "512Mi", "1Gi", "4Gi")]

    # --affinity F: fraction F of pods carry required OR preferred node
    # affinity over the disk label the nodes alternate — exercising the
    # host shape evaluation + the mask/score device operands in the tick
    affinity_shapes = _e2e_affinity_shapes() if args.affinity else []

    def make_pod(name):
        # independent draws: the metric label promises each fraction
        # unconditionally, and a pod can legitimately carry BOTH node
        # affinity and pod anti-affinity
        affinity = None
        labels = {}
        constraints = []
        if affinity_shapes and rng.random() < args.affinity:
            affinity = affinity_shapes[
                int(rng.integers(0, len(affinity_shapes)))
            ]
        if args.anti and rng.random() < args.anti:
            # a handful of one-per-node workloads (distinct selectors =
            # distinct anti shapes, like production StatefulSets)
            app = f"svc{int(rng.integers(0, 8))}"
            labels = {"app": app}
            from karpenter_tpu.api.core import Affinity

            anti = _e2e_anti_affinity(app)
            affinity = Affinity(
                node_affinity=(
                    affinity.node_affinity if affinity else None
                ),
                pod_anti_affinity=anti.pod_anti_affinity,
            )
        if args.spread and rng.random() < args.spread:
            # a handful of zone-spread Deployments (distinct selectors =
            # distinct spread shapes + distinct census queries)
            from karpenter_tpu.api.core import TopologySpreadConstraint

            app = f"web{int(rng.integers(0, 8))}"
            labels = {**labels, "spread-app": app}
            constraints = [
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector={
                        "matchLabels": {"spread-app": app}
                    },
                )
            ]
        return Pod(
            metadata=ObjectMeta(name=name, labels=labels),
            spec=PodSpec(
                containers=[
                    Container(
                        requests={
                            "cpu": rng.choice(cpu_choices),
                            "memory": rng.choice(mem_choices),
                        }
                    )
                ],
                affinity=affinity,
                topology_spread_constraints=constraints,
            ),
        )

    for i in range(args.pods):
        store.create(make_pod(f"p{i}"))
    nodes = []
    for g in range(args.types):
        cores = int(rng.choice([8, 16, 32, 64, 96]))
        node_labels = {
            "group": f"g{g}",
            "disk": "ssd" if g % 2 else "hdd",
        }
        if args.spread:
            # 16 zones across the groups: domains for the split + the
            # occupancy census
            node_labels["topology.kubernetes.io/zone"] = f"z{g % 16}"
        node = Node(
            metadata=ObjectMeta(
                name=f"n{g}",
                labels=node_labels,
            ),
            status=NodeStatus(
                allocatable={
                    "cpu": Quantity.parse(str(cores)),
                    "memory": Quantity.parse(f"{cores * 4}Gi"),
                },
                conditions=[NodeCondition(type="Ready", status="True")],
            ),
        )
        store.create(node)
        nodes.append(node)
    # --spread: a slab of BOUND pods (10% of the fleet, capped) feeds the
    # existing-pod occupancy census; a slice of it churns every measured
    # tick so the census epoch invalidates and the recompute is IN the
    # number, not amortized away by the memo
    def make_bound(name):
        app = f"web{int(rng.integers(0, 8))}"
        return Pod(
            metadata=ObjectMeta(
                name=name,
                labels={
                    "spread-app": app,
                    # per-pod-unique label (the StatefulSet pod-name
                    # pattern): fragments the census into one label
                    # group per pod, so the measured tick exercises the
                    # materialized-view path, not a shared-group lookup
                    "statefulset.kubernetes.io/pod-name": name,
                },
            ),
            spec=PodSpec(
                node_name=f"n{int(rng.integers(0, args.types))}",
                containers=[
                    Container(requests={"cpu": cpu_choices[0]})
                ],
            ),
        )

    bound_count = 0
    if args.spread:
        bound_count = min(max(args.pods // 10, 1), 10000)
        for i in range(bound_count):
            store.create(make_bound(f"b{i}"))

    producers = [
        store.create(
            MetricsProducer(
                metadata=ObjectMeta(name=f"mp{g}"),
                spec=MetricsProducerSpec(
                    pending_capacity=PendingCapacitySpec(
                        node_selector={"group": f"g{g}"}
                    )
                ),
            )
        )
        for g in range(args.types)
    ]
    registry = GaugeRegistry()
    register_gauges(registry)

    solver = functools.partial(
        solve, buckets=args.buckets, backend=args.backend
    )
    if args.host_only:
        # shape-correct no-op: everything around the device call still
        # runs (encode memo invalidation, status + gauge writes), so the
        # number is the honest host half of the churned tick
        from karpenter_tpu.ops.binpack import BinPackOutputs

        def solver(inputs, **_):  # noqa: ARG001
            groups = inputs.group_allocatable.shape[0]
            return BinPackOutputs(
                assigned=np.full(
                    inputs.pod_requests.shape[0], -1, np.int32
                ),
                assigned_count=np.zeros(groups, np.int32),
                nodes_needed=np.zeros(groups, np.int32),
                lp_bound=np.zeros(groups, np.int32),
                unschedulable=np.int32(0),
            )

    def tick():
        # the REAL production path, nothing hoisted: node listing + group
        # profiling + cache snapshot + encode + device solve + status and
        # gauge writes for every producer
        solve_pending(store, producers, registry, feed=feed, solver=solver)

    t0 = time.perf_counter()
    tick()
    print(
        f"first tick (compile+run): {(time.perf_counter() - t0) * 1e3:.1f} ms",
        file=sys.stderr,
    )

    # steady state: nothing changed between ticks, so the encode memo +
    # device-residency cache collapse the tick to (dispatch + one packed
    # output fetch)
    steady = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        tick()
        steady.append((time.perf_counter() - t0) * 1e3)
    s50 = float(np.percentile(steady, 50))
    print(
        f"steady-state tick p50={s50:.1f}ms "
        f"p95={float(np.percentile(steady, 95)):.1f}ms",
        file=sys.stderr,
    )

    # churned: replace pods through the store each tick (watch events feed
    # the incremental caches), so every measured tick includes cache
    # maintenance, full re-encode, and full input re-transfer — the honest
    # production number, reported as THE metric. Pod OBJECT construction is
    # the load generator's cost (a kubelet/scheduler analog), so the
    # replacement pods are pre-built; the timed region starts where the
    # controller's work starts: the store mutation and its watch fan-out.
    churn = args.churn if args.churn >= 0 else max(1, args.pods // 100)
    next_id = args.pods
    next_bound = bound_count
    # honest labeling: --churn 0 must stay a genuinely churn-free tick;
    # the window can never exceed the slab (victims must exist)
    bound_churn = (
        min(bound_count, max(1, churn // 10))
        if (bound_count and churn)
        else 0
    )
    times = []
    for it in range(args.iters):
        fresh = [make_pod(f"p{next_id + j}") for j in range(churn)]
        victims = [f"p{next_id - args.pods + j}" for j in range(churn)]
        next_id += churn
        fresh_bound = [
            make_bound(f"b{next_bound + j}") for j in range(bound_churn)
        ]
        bound_victims = [
            f"b{next_bound - bound_count + j}" for j in range(bound_churn)
        ]
        next_bound += bound_churn
        t0 = time.perf_counter()
        for victim, pod in zip(victims, fresh):
            store.delete("Pod", "default", victim)
            store.create(pod)
        for victim, pod in zip(bound_victims, fresh_bound):
            store.delete("Pod", "default", victim)
            store.create(pod)
        tick()
        times.append((time.perf_counter() - t0) * 1e3)
    record_evidence(
        steady_iter_ms=[round(t, 4) for t in steady],
        iter_ms=[round(t, 4) for t in times],
        churn=churn,
        transport_floor=(
            measure_transport_floor() if not args.host_only else None
        ),
    )
    p50 = float(np.percentile(times, 50))
    p95 = float(np.percentile(times, 95))
    print(
        f"e2e tick (churn={churn} pods/tick) p50={p50:.1f}ms p95={p95:.1f}ms",
        file=sys.stderr,
    )
    extra = f"churn={churn}/tick; steady-state p50={s50:.1f}ms"
    if args.host_only:
        metric += ", host half only"
        extra += "; device solve stubbed"
    emit(
        f"{metric} ({jax.default_backend()})",
        p50,
        note=extra,
        against_baseline=not args.host_only,
    )


def build_multitenant_batch(args, seed: int):
    """N tenant clusters' decide matrices for one lockstep tick — the
    same seeded world `--simulate --multitenant` steps
    (simulate.multitenant_fleet_inputs), so the bench times exactly the
    matrices the simulator replays."""
    from karpenter_tpu.simulate import (
        multitenant_cost_inputs,
        multitenant_fleet_inputs,
    )

    decide_batch = {}
    cost_batch = {}
    for i in range(args.tenants):
        tid = f"t{i:04d}"
        inputs = multitenant_fleet_inputs(
            i, args.tenant_rows, args.tenant_metrics, seed,
            tick=3, spec_replicas=np.full(args.tenant_rows, 2, np.int32),
            now=1_000_000.0,
        )
        decide_batch[tid] = inputs
        cost_batch[tid] = multitenant_cost_inputs(
            inputs, np.full(args.tenant_rows, 5, np.int32)
        )
    return decide_batch, cost_batch


def _multitenant_record(args, backend, batched, sequential) -> dict:
    batched_p50 = float(np.percentile(batched, 50))
    sequential_p50 = float(np.percentile(sequential, 50))
    decisions = args.tenants * args.tenant_rows
    return {
        "config": f"{args.tenants} tenants x {args.tenant_rows} "
                  "autoscalers multitenant",
        "backend": backend,
        "tenants": args.tenants,
        "rows_per_tenant": args.tenant_rows,
        "metrics_per_row": args.tenant_metrics,
        "batched_p50_ms": round(batched_p50, 3),
        "sequential_p50_ms": round(sequential_p50, 3),
        "batched_dps": round(decisions * 1000.0 / batched_p50, 1),
        "sequential_dps": round(decisions * 1000.0 / sequential_p50, 1),
        "speedup": round(sequential_p50 / batched_p50, 2),
    }


def _append_multitenant_row(path: str, record: dict) -> None:
    marker = "## Multi-tenant control plane (make bench-multitenant)"
    header = (
        f"\n{marker}\n\n"
        "One lockstep tick over N simulated tenant clusters: every "
        "tenant's decide + cost matrices concatenated into shared "
        "dispatches by the MultiTenantScheduler "
        "(docs/multitenancy.md) vs the same matrices dispatched one "
        "tenant at a time through the same SolverService seam. "
        "Cross-tenant slices == independent dispatches (device and "
        "numpy paths) is asserted before timing. Decisions/sec counts "
        "autoscaler rows decided+refined per wall second.\n\n"
        "| Date | Backend | Config | Batched tick p50 (ms) | "
        "Sequential tick p50 (ms) | Batched decisions/s | Sequential "
        "decisions/s | Speedup |\n"
        "|---|---|---|---|---|---|---|---|\n"
    )
    date = datetime.date.today().isoformat()
    row = (
        f"| {date} | {record['backend']} | {record['config']} "
        f"| {record['batched_p50_ms']} | {record['sequential_p50_ms']} "
        f"| {record['batched_dps']} | {record['sequential_dps']} "
        f"| {record['speedup']}x |\n"
    )
    _append_table_row(path, marker, header, row)


def _pin_multitenant_parity(scheduler, service, decide_batch, cost_batch,  # lint: allow-complexity — parity gate: one loop per family x path
                            backend: str) -> None:
    """The acceptance gate (docs/multitenancy.md): a subsample of
    tenants' concatenated-slice outputs must be bit-identical to their
    own independent dispatches — decide + cost on the requested device
    backend AND cost on the numpy mirror path."""
    import dataclasses

    from karpenter_tpu.ops.cost import CostOutputs, cost_numpy
    from karpenter_tpu.ops.decision import DecisionOutputs
    from karpenter_tpu.tenancy import concat_cost_inputs, slice_cost_outputs

    sample = sorted(decide_batch)[:: max(1, len(decide_batch) // 16)]
    decided = scheduler.decide_all(decide_batch)
    costed = scheduler.cost_all(cost_batch, backend=backend)
    for tid in sample:
        indep_d = service.decide(decide_batch[tid])
        for f in dataclasses.fields(DecisionOutputs):
            a = np.asarray(getattr(decided[tid], f.name))
            b = np.asarray(getattr(indep_d, f.name))
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"decide concat parity violated for {tid}.{f.name}"
                )
        indep_c = service.cost(cost_batch[tid], backend=backend)
        for f in dataclasses.fields(CostOutputs):
            a = np.asarray(getattr(costed[tid], f.name))
            b = np.asarray(getattr(indep_c, f.name))
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"cost concat parity violated for {tid}.{f.name} "
                    f"({backend})"
                )
    # numpy-mirror path: the concatenated host program's slices must
    # equal per-tenant host calls bit for bit too
    sample_batch = {tid: cost_batch[tid] for tid in sample}
    order = sorted(sample_batch)
    stacked = concat_cost_inputs([sample_batch[t] for t in order])
    host = cost_numpy(stacked)
    offset = 0
    for tid in order:
        n = int(np.asarray(sample_batch[tid].base_desired).shape[0])
        mine = slice_cost_outputs(host, offset, offset + n)
        offset += n
        indep = cost_numpy(sample_batch[tid])
        for f in dataclasses.fields(CostOutputs):
            a = np.asarray(getattr(mine, f.name))
            b = np.asarray(getattr(indep, f.name))
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"cost concat parity violated for {tid}.{f.name} "
                    f"(numpy)"
                )


def run_multitenant(args, metric: str) -> None:
    """Aggregate decisions/sec at N tenants: the multi-tenant control
    plane's one-dispatch claim (docs/multitenancy.md). Both paths run
    the IDENTICAL kernels on identical per-tenant matrices through the
    same SolverService seam; only the dispatch shape differs — shared
    cross-tenant concatenated programs vs one decide + one cost
    dispatch per tenant. Parity is pinned before timing."""
    import jax

    from karpenter_tpu.metrics.registry import GaugeRegistry
    from karpenter_tpu.solver import SolverService
    from karpenter_tpu.tenancy import (
        MultiTenantScheduler,
        TenantRegistry,
        TenantSpec,
    )

    print(
        f"backend={jax.default_backend()} devices={jax.devices()}",
        file=sys.stderr,
    )
    decide_batch, cost_batch = build_multitenant_batch(args, args.seed)
    service = SolverService(
        backend=args.backend, registry=GaugeRegistry()
    )
    registry = TenantRegistry(
        service=service, registry=GaugeRegistry(),
        specs=[
            TenantSpec(id=tid, weight=1.0 + (i % 3))
            for i, tid in enumerate(sorted(decide_batch))
        ],
    )
    scheduler = MultiTenantScheduler(
        registry, service,
        max_rows_per_round=args.tenants * args.tenant_rows,
    )
    try:
        # parity pin FIRST (also warms every compiled shape both paths
        # will time)
        _pin_multitenant_parity(
            scheduler, service, decide_batch, cost_batch, args.backend
        )
        print(
            "parity pinned: cross-tenant slices == independent "
            "dispatches (device + numpy)",
            file=sys.stderr,
        )

        batched_times, sequential_times = [], []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            scheduler.decide_all(decide_batch)
            scheduler.cost_all(cost_batch, backend=args.backend)
            batched_times.append((time.perf_counter() - t0) * 1e3)
        for _ in range(args.iters):
            t0 = time.perf_counter()
            for tid in decide_batch:
                service.decide(decide_batch[tid])
                service.cost(cost_batch[tid], backend=args.backend)
            sequential_times.append((time.perf_counter() - t0) * 1e3)
    finally:
        service.close()

    record = _multitenant_record(
        args, jax.default_backend(), batched_times, sequential_times
    )
    record_evidence(
        batched_iter_ms=[round(t, 4) for t in batched_times],
        sequential_iter_ms=[round(t, 4) for t in sequential_times],
        multitenant=record,
        transport_floor=measure_transport_floor(),
    )
    print(
        f"batched tick p50={record['batched_p50_ms']}ms "
        f"({record['batched_dps']} decisions/s) | sequential "
        f"p50={record['sequential_p50_ms']}ms "
        f"({record['sequential_dps']} decisions/s) | "
        f"speedup={record['speedup']}x",
        file=sys.stderr,
    )
    if args.publish_baseline:
        _publish_to_baseline(
            f"{record['config']} ({record['backend']})", record
        )
    if args.append_benchmarks:
        _append_multitenant_row(args.append_benchmarks, record)
    extra = (
        f"{record['batched_dps']} vs {record['sequential_dps']} "
        f"decisions/sec batched vs sequential "
        f"({record['speedup']}x); concat==independent parity pinned "
        f"(device + numpy)"
    )
    emit(
        f"{metric} ({jax.default_backend()})",
        record["batched_p50_ms"],
        note=extra,
        against_baseline=False,
    )


if __name__ == "__main__":
    main()
