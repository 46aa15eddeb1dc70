"""Shared solve service: one amortized device pipeline for every caller.

Before this subsystem, each bin-pack caller — the pendingCapacity batch
solve, simulate, the gRPC sidecar's concurrent Solve RPCs, bench — drove
ops/binpack on its own: concurrent ticks paid separate XLA dispatches,
and every novel operand shape paid a fresh compile. The service is the
single in-process front door that turns those independent calls into one
production pipeline:

  submit → coalesce → pad → dispatch → scatter

  * ADAPTIVE COALESCING QUEUE: requests arriving within a gather window
    are batched, grouped by compatibility key, and same-key requests
    ride ONE batched device call (`lax.map` over the stacked operands —
    the per-item program is the same HLO as a direct solve, so results
    match a direct ops/binpack call element for element). The window is
    LOAD-ADAPTIVE: an idle queue dispatches immediately (a lone
    reconcile tick pays no batching-timer tax), and the window widens
    to `window_s` only while recent traffic was actually concurrent
    (backlog present, or the batch-size EWMA above the idle threshold).
  * PIPELINED DISPATCH: the worker double-buffers device work — while
    batch k computes on device, batch k+1 is gathered, padded, stacked,
    and dispatched, and only then is batch k's host-side fetch/crop
    paid. Steady-state dispatches stop paying the host round-trip in
    line, and `donate_argnums` on the (device-put) stacked operands
    lets XLA reuse batch buffers instead of reallocating per dispatch.
  * SHAPE BUCKETING + COMPILE CACHE: operands are padded up the
    power-of-two-ish ladder (solver/bucketing.py) and the compiled
    program is cached per (shape bucket, batch bucket, buckets,
    operand presence, backend). Steady-state traffic whose sizes jitter
    inside one rung never recompiles; the hit/miss counters make that
    claim testable.
  * BACKPRESSURE + DEADLINES: the queue is bounded — a full queue
    degrades the overflow request to the numpy backend inline instead
    of growing an unbounded backlog; each request carries a deadline,
    and an expired wait degrades the same way (or raises, per
    `on_timeout`). A device-path failure falls back to numpy per
    request: the control plane keeps producing signals through an
    accelerator outage, the posture every entry point in this repo
    takes (utils/backend.py).
  * BACKEND HEALTH FSM + WATCHDOG (docs/resilience.md): per-request
    fallback is the first rung; the FSM is the wholesale one. After
    `health_failure_threshold` CONSECUTIVE device failures the service
    trips to DEGRADED: every request routes straight to numpy with no
    device attempt (a dead accelerator stops billing each request a
    failed dispatch), and one probe dispatch per
    `health_probe_interval_s` rides the device path — a probe success
    flips back to HEALTHY. Separately, a watchdog (enabled by
    `watchdog_timeout_s` > 0) detects a worker HUNG inside a device
    call — the failure mode fallback can't catch, because the except
    never runs — restarts the worker thread (generation-stamped; the
    stale thread's late results are discarded) and drains the stuck
    requests to numpy, so no caller waits out a dead device. Both
    export karpenter_resilience_* metrics.
  * METRICS: queue depth, coalesce factor, compile-cache hits/misses,
    rejections/expiries/fallbacks, and per-stage latency percentiles,
    registered through the same GaugeRegistry the runtime serves on
    /metrics (subsystem "solver").

Besides bin-packs the queue carries three more program families through
the same pipeline: `decide` (the HPA decision kernel — no coalescing,
the batch autoscaler already evaluates the whole fleet at once),
`forecast` (forecast/models.py — concurrent forecast requests
concatenate along the series axis and ride ONE dispatch), and `preempt`
(ops/preempt.py — fleet-wide placement-with-eviction planning, every
candidate in one dispatch). Both of the latter degrade to numpy mirrors
that are bit-identical to their device kernels. A fourth synchronous
family, `cost` (ops/cost.py — the fleet's multi-objective cost/SLO
refinement in one dispatch), rides the same FSM with a deliberately
different failure posture: cost-blind, not mirror-served (docs/cost.md).

The service holds no DOMAIN state — results are a pure function of each
request — but it does own one derived cache: the DEVICE-RESIDENT fleet
state (solver/resident.py, docs/solver-service.md "Device-resident
fleet state"). Singleton solve dispatches keep their padded operand
stack resident on device, keyed by the host inputs object's identity:
an unchanged fleet re-dispatches with zero host encode and zero upload,
and a delta-encoded successor (the encoder's SnapshotDeltaCache
publishes the changed-row plan) applies as a batched scatter instead of
a full re-upload. Residency is bit-identical to the cold path by
construction, falls back to a full upload on any inconsistency, and is
discarded wholesale by the degradation ladder and the recovery boot
(reset_caches).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time as _time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from karpenter_tpu.faults import inject
from karpenter_tpu.metrics.registry import GaugeRegistry, default_registry
from karpenter_tpu.observability import (
    default_flight_recorder,
    default_tracer,
)
from karpenter_tpu.ops.binpack import (
    DEFAULT_BUCKETS,
    BinPackInputs,
    has_constraint_operands,
)
from karpenter_tpu.solver.bucketing import (
    PadStaging,
    bucket_up,
    bucket_shape,
    constraint_shape,
    crop_outputs,
    crop_preempt_outputs,
    is_bucketed,
    pad_preempt_inputs,
    pad_to_bucket,
    preempt_bucket_shape,
    presence,
    staging_key,
)
from karpenter_tpu.utils.log import logger

SUBSYSTEM = "solver"

QUEUE_DEPTH = "queue_depth"
COALESCE_FACTOR = "coalesce_factor"
REQUESTS_TOTAL = "requests_total"
DISPATCH_TOTAL = "dispatch_total"
COMPILE_CACHE_HITS = "compile_cache_hits_total"
COMPILE_CACHE_MISSES = "compile_cache_misses_total"
FALLBACK_TOTAL = "fallback_total"
REJECTED_TOTAL = "rejected_total"
DEADLINE_EXPIRED_TOTAL = "deadline_expired_total"
STAGE_P50_MS = "stage_p50_ms"
STAGE_P99_MS = "stage_p99_ms"
STAGE_SECONDS = "stage_seconds"
COALESCE_BATCH_SIZE = "coalesce_batch_size"
WINDOW_MS = "window_ms"
PIPELINE_DEPTH = "pipeline_depth"
UPLOAD_MS = "upload_ms"
SHARD_DEVICES = "shard_devices"
# device-resident fleet state (solver/resident.py)
RESIDENT_BYTES = "resident_bytes"
RESIDENT_ROWS = "resident_rows"
RESIDENT_SCATTER_MS = "resident_scatter_ms"
RESIDENT_REBUILDS = "resident_rebuilds_total"
# the one-chip Mosaic path's pad into reused host staging areas
# {name=reuse|alloc} (docs/solver-service.md "Shape-bucket ladder")
PAD_STAGING = "pad_staging_total"
# boot-time compile pre-warm (docs/solver-service.md "Compile pre-warm")
PREWARM_COMPILES = "prewarm_compiles_total"
PREWARM_MS = "prewarm_ms"
# device programs the last reconcile tick paid (docs/solver-service.md
# "Fused tick"): 3+ on the chained steady-state path, 1 once
# --fused-tick engages — the production observable behind the bench's
# dispatch-count claim
DISPATCHES_PER_TICK = "dispatches_per_tick"

# Fused-family compile keys the PROCESS has already paid for: the fused
# program rides the module-level fused_tick_jit (process-global jit
# cache, disk-global under --compile-cache-dir), so freshness — and the
# compile-ledger rows it drives — is a process property, not a
# per-service one (_count_fused_compile). reset_caches() re-arms.
_FUSED_COMPILE_SEEN: set = set()

# Sharded dispatch (docs/solver-service.md "Sharded dispatch"): a request
# whose pods x groups constraint matrix reaches this many cells routes
# through the multi-device mesh (parallel/mesh.py) instead of the
# single-device program — when a mesh with >= 2 devices exists. 2^24
# cells ≈ the north-star 100k x 300 fleet at 5% occupancy headroom:
# small-fleet traffic (10k x 50 = 5 x 10^5) never pays mesh padding or
# the sharded compile, fleet-scale decisions (1M x 1k = 10^9) always
# shard. 0 disables sharding outright.
DEFAULT_SHARD_THRESHOLD = 1 << 24

# A lone coalesced map-strategy batch splits into pipeline_depth+1
# chunked dispatches (so the double buffer has something to overlap)
# only at or above this size — smaller batches aren't worth a second
# dispatch's fixed cost, and 2-request batches must keep riding one
# dispatch (the coalescing contract tests pin).
_PIPELINE_SPLIT_MIN = 4

# Backend health FSM states (karpenter_resilience_solver_backend_state)
HEALTHY = "healthy"
DEGRADED = "degraded"

# Forecast shape ladders (forecast requests share the bin-pack compile
# cache but bucket on (series, history-length) instead)
FORECAST_T_FLOOR = 16
FORECAST_S_FLOOR = 8

# Extra watchdog headroom for a dispatch that MISSED the compile cache:
# first-call XLA/Mosaic compiles legitimately run tens of seconds (TPU
# solver programs: 20-40s), and a restart mid-compile would loop — the
# fresh worker would just compile again. Steady-state dispatches (cache
# hits) get the bare watchdog_timeout_s.
COMPILE_GRACE_S = 120.0

_STAGE_WINDOW = 256  # per-stage latency ring size (fleet-scale constant)

# Host staging areas the one-chip Mosaic path keeps (_staged_pad), least
# recently used first out: three hold a fleet whose size jitters across
# one rung boundary (two adjacent rungs) beside the MetricsProducer's
# own small rung, at ~19 MB an area at the 131,072-row rung.
_STAGING_AREAS = 3

# native-histogram ladders (docs/observability.md): stage latencies run
# from sub-ms host work to tens-of-seconds first compiles; coalesce
# batch sizes follow the power-of-two batch ladder
_STAGE_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 30.0,
)
_COALESCE_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
# Adaptive-window load tracking: EWMA of gathered batch sizes. Below the
# threshold the queue is treated as idle (dispatch immediately); at or
# above it the full window holds so concurrent bursts keep coalescing.
_LOAD_ALPHA = 0.5
_LOAD_IDLE = 1.5


def _keep_freed_heap() -> None:
    """Pin glibc malloc's thresholds where its own dynamic rule leaves
    them once a 32 MiB block is freed: blocks up to 32 MiB come from the
    heap, and up to 64 MiB of freed heap stays mapped. A pad into fresh
    arrays freed 8-19 MB blocks every call, which raised the thresholds
    as a side effect; with the pad staged nothing does, they stay at
    128 KiB, and a caller that builds fresh operands for every call (the
    sidecar's decode) maps, faults and unmaps them every call: on a v5e
    host a 100k-pod solve call then took 22.4 ms, against 16.4-17.0 ms
    with the thresholds pinned. Nothing where the C library has no
    mallopt."""
    import ctypes

    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (AttributeError, OSError):
        return
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    mallopt(-3, 32 << 20)  # M_MMAP_THRESHOLD
    mallopt(-1, 64 << 20)  # M_TRIM_THRESHOLD


def _p50_p99(samples) -> Tuple[float, float]:
    """The 50th and 99th percentiles of a latency ring, interpolated
    linearly as np.percentile does by default. Sorting at most
    _STAGE_WINDOW floats costs less than np.percentile's per-call
    overhead, and the worker refreshes these gauges after every batch,
    holding the interpreter lock the callers' threads need."""
    ordered = sorted(samples)
    top = len(ordered) - 1

    def at(x: float) -> float:
        i = int(x)
        return ordered[i] + (ordered[min(i + 1, top)] - ordered[i]) * (x - i)

    return at(0.50 * top), at(0.99 * top)


class SolverSaturated(RuntimeError):
    """The bounded request queue is full (backpressure signal)."""


class CostUnavailable(RuntimeError):
    """The cost-refinement path is short-circuited (backend-health FSM
    degraded, no probe due): the caller proceeds cost-blind this tick
    (docs/cost.md degradation contract)."""


class SolverTimeout(TimeoutError):
    """A request's deadline expired before the device path answered."""


@dataclass
class SolverStatistics:
    """Plain-int mirror of the service counters (tests and callers read
    these directly; the registry carries the same values for /metrics)."""

    requests: int = 0
    dispatches: int = 0
    coalesced_batches: int = 0
    compile_cache_hits: int = 0
    compile_cache_misses: int = 0
    fallbacks: int = 0
    rejected: int = 0
    deadline_expired: int = 0
    last_coalesce_factor: int = 0
    immediate_dispatches: int = 0  # idle-queue batches that skipped the window
    pipeline_overlaps: int = 0  # dispatches issued while another was in flight
    decide_calls: int = 0
    decide_errors: int = 0
    # cost-refinement seam (karpenter_tpu/cost, docs/cost.md)
    cost_calls: int = 0  # cost() entries
    cost_errors: int = 0  # cost() failures (the caller goes cost-blind)
    cost_dispatches: int = 0  # cost device dispatches
    # joint pool-group allocation seam (poolgroups/, docs/poolgroups.md)
    poolgroup_calls: int = 0  # poolgroup() entries
    poolgroup_errors: int = 0  # poolgroup() failures (even the floor died)
    poolgroup_dispatches: int = 0  # joint device dispatches
    poolgroup_independent_serves: int = 0  # degraded independent-ladder serves
    consolidate_calls: int = 0
    consolidate_candidates: int = 0
    # forecast seam (forecast/, docs/forecasting.md)
    forecast_calls: int = 0  # forecast() entries
    forecast_series: int = 0  # total series submitted across calls
    forecast_dispatches: int = 0  # coalesced forecast device dispatches
    # eviction-planning seam (ops/preempt.py, docs/preemption.md)
    preempt_calls: int = 0  # preempt() entries
    preempt_candidates: int = 0  # total candidates submitted across calls
    preempt_dispatches: int = 0  # preempt device dispatches
    # constraint plane (docs/constraints.md): pallas-resolved requests
    # carrying constraint operands rerouted to the XLA family (Mosaic
    # has no constraint entry — counted, never silently dropped)
    constraint_reroutes: int = 0
    # simlab cluster-stepping seam (ops/simstep.py, docs/simulator.md)
    sim_calls: int = 0  # sim_step() + sim_rollout() entries
    sim_dispatches: int = 0  # sim device dispatches (1 per batched call)
    sim_mirror_serves: int = 0  # sim calls served by the numpy mirror
    # fused steady-state tick (ops/fusedtick.py, docs/solver-service.md
    # "Fused tick")
    fused_calls: int = 0  # fused_tick() entries
    fused_dispatches: int = 0  # ticks answered by the ONE fused program
    fused_chained_serves: int = 0  # ticks served by the per-stage rung
    fused_mirror_serves: int = 0  # ticks served by the numpy floor
    last_dispatches_per_tick: int = 0  # note_tick() delta (the gauge)
    # sharded dispatch (docs/solver-service.md "Sharded dispatch")
    shard_dispatches: int = 0  # batches answered by the mesh-sharded program
    shard_requests: int = 0  # requests routed onto the mesh at submit
    shard_fallbacks: int = 0  # shard-path failures retried single-device
    shard_mesh_errors: int = 0  # mesh builds that failed (served unsharded)
    # device-resident fleet state (solver/resident.py)
    resident_hits: int = 0  # dispatches served from resident buffers as-is
    resident_scatters: int = 0  # dispatches served via a changed-row scatter
    resident_rebuilds: int = 0  # full uploads (re)establishing residency
    resident_drops: int = 0  # wholesale discards (ladder / recovery boot)
    # the one-chip Mosaic path's pads into host staging areas
    pad_staging_reuses: int = 0  # pads into an area an earlier call made
    pad_staging_allocs: int = 0  # pads that had to make a new area
    pipeline_splits: int = 0  # lone batches chunked so the pipeline overlaps
    # backend health FSM + watchdog (docs/resilience.md)
    device_failures: int = 0  # total device-path failures (any rung)
    fsm_trips: int = 0  # healthy -> degraded transitions
    fsm_recoveries: int = 0  # degraded -> healthy transitions
    fsm_probes: int = 0  # device probes granted while degraded
    fsm_short_circuits: int = 0  # batches routed to numpy with no attempt
    watchdog_restarts: int = 0  # hung-worker restarts
    # provenance: answers handed to callers, keyed "family/kernel" with
    # kernel the one that actually served ("pallas", "xla", "numpy", or
    # the requested backend under a device_solver override)
    served: Dict[str, int] = field(default_factory=dict)


@dataclass
class _Request:
    inputs: BinPackInputs
    buckets: int
    backend: str
    key: tuple
    n_pods: int
    n_groups: int
    deadline: Optional[float]
    enqueued_at: float
    event: threading.Event = field(default_factory=threading.Event)
    result: Optional[object] = None
    error: Optional[BaseException] = None
    abandoned: bool = False
    degraded: bool = False  # answered by numpy after a device failure
    # consolidate() batch marker: requests sharing an id were enqueued
    # atomically and must ride ONE dispatch — _collect keeps draining the
    # queue past max_batch while the head continues the same batch
    coalesce_id: Optional[int] = None
    # reconcile-trace span opened at submit (observability.tracing):
    # covers queue wait through completion; the coalesced dispatch span
    # LINKS it, and the FSM-trip flight-recorder event backlinks its
    # trace id. None with tracing disabled.
    span: Optional[object] = None
    # tenant id the submitter stamped (multi-tenant scheduler), carried
    # into the span args so /debug/traces?tenant= finds the request
    tenant: Optional[str] = None
    _finish_lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )

    def trace_id(self) -> Optional[str]:
        return self.span.trace_id if self.span is not None else None

    def finish(self, result=None, error=None, degraded: bool = False) -> bool:
        """First finisher wins (idempotent): the watchdog may drain a
        stuck request to numpy while the stale worker later unwedges and
        tries to answer it too — the caller must see exactly one result.
        `degraded` marks the span of a request the ladder answered from
        numpy AFTER a device failure/hang — a trace reader must be able
        to tell those from healthy device-served requests (the
        fsm_trip flight-recorder event backlinks their traces)."""
        with self._finish_lock:
            if self.event.is_set():
                return False
            self.result = result
            self.error = error
            self.degraded = degraded
            self.event.set()
            if self.span is not None:
                self.span.close(
                    ok=error is None, degraded=degraded or None
                )
            return True


class SolveFuture:
    """Handle returned by submit(); result() blocks with a deadline."""

    def __init__(self, request: _Request, service: "SolverService"):
        self._request = request
        self._service = service

    def result(self, timeout: Optional[float] = None):
        req = self._request
        if not req.event.wait(timeout):
            req.abandoned = True  # the worker will skip it
            self._service._on_expired(req)
            raise SolverTimeout(
                f"solve deadline expired after {timeout}s "
                f"(queue depth {self._service.queue_depth()})"
            )
        if req.error is not None:
            raise req.error
        family = req.key[0] if isinstance(req.key[0], str) else "solve"
        self._service._note_served(
            family, "numpy" if req.degraded else req.backend
        )
        return req.result


class SolverService:
    """Long-lived in-process solve service (module docstring).

    `device_solver` overrides the in-process device path with any
    (inputs, buckets=..., backend=...) -> BinPackOutputs callable — the
    sidecar SolverClient.solve under the gRPC process split, or a fault
    injector in tests. With an override the worker dispatches requests
    individually (the wire codec carries one problem per message), but
    queueing, deadlines, backpressure, fallback, and metrics still
    apply. `decider` seams the HPA decision kernel the same way.
    """

    def __init__(
        self,
        registry: Optional[GaugeRegistry] = None,
        *,
        window_s: float = 0.002,
        adaptive_window: bool = True,
        pipeline_depth: int = 1,
        max_queue: int = 64,
        max_batch: int = 8,
        default_timeout_s: float = 30.0,
        backend: str = "auto",
        on_timeout: str = "fallback",  # or "raise"
        device_solver: Optional[Callable] = None,
        decider: Optional[Callable] = None,
        clock: Callable[[], float] = _time.monotonic,
        health_failure_threshold: int = 3,
        health_probe_interval_s: float = 5.0,
        watchdog_timeout_s: float = 0.0,  # 0 = watchdog disabled
        shard_threshold: int = DEFAULT_SHARD_THRESHOLD,
        shard_devices: Optional[int] = None,
        shard_mesh_shape: Optional[tuple] = None,
    ):
        if on_timeout not in ("fallback", "raise"):
            raise ValueError(f"on_timeout must be fallback|raise, got {on_timeout!r}")
        self.registry = registry if registry is not None else default_registry()
        # window_s is now the MAX gather window: with adaptive_window an
        # idle queue dispatches immediately and only concurrent traffic
        # waits the window out; adaptive_window=False pins the fixed
        # always-wait window (the pre-overhaul behavior)
        self.window_s = window_s
        self.adaptive_window = adaptive_window
        # how many dispatched-but-unfetched batches may be in flight (1 =
        # double buffering: host scatter of batch k overlaps device
        # compute of batch k+1); 0 disables pipelining
        self.pipeline_depth = pipeline_depth
        self.max_queue = max_queue
        self.max_batch = max_batch
        self.default_timeout_s = default_timeout_s
        self.backend = backend
        self.on_timeout = on_timeout
        self.device_solver = device_solver
        self._decider = decider
        self._clock = clock
        self.stats = SolverStatistics()
        self._queue: collections.deque = collections.deque()
        self._coalesce_seq = 0  # consolidate() batch-marker source
        self._cond = threading.Condition()
        self._worker: Optional[threading.Thread] = None
        self._closed = False
        # (backend, shape, batch, buckets, presence) -> compiled callable
        self._compiled: Dict[tuple, Callable] = {}
        self._compile_seen: set = set()
        # kernel families already pre-warmed this process lifetime
        # (prewarm; reset_caches re-arms)
        self._prewarmed: set = set()
        self._stages: Dict[str, collections.deque] = {}
        self._stage_lock = threading.Lock()
        self._served_lock = threading.Lock()  # stats.served, from callers
        # worker-only state: batch-size EWMA (adaptive window), in-flight
        # dispatches (pipeline), and the gauges mirroring both
        self._load = 0.0
        self._window_now_s = 0.0 if adaptive_window else window_s
        self._inflight: collections.deque = collections.deque()
        self._last_pipeline_depth = 0
        # sharded dispatch (docs/solver-service.md "Sharded dispatch"):
        # requests whose pods x groups cell count reaches the threshold
        # route through a multi-device mesh, built lazily on first use.
        # shard_devices caps the device count (None = all), and
        # shard_mesh_shape pins explicit (pods, groups) extents instead
        # of the pods-major factorization.
        self.shard_threshold = shard_threshold
        self.shard_devices = shard_devices
        self.shard_mesh_shape = (
            tuple(shard_mesh_shape) if shard_mesh_shape else None
        )
        self._mesh = None
        self._mesh_ready = False
        self._mesh_lock = threading.Lock()
        # one shard-path failure stops routing NEW traffic to the mesh
        # (the single-device program keeps serving); reset_caches — the
        # recovery-boot seam — re-arms it
        self._shard_broken = False
        # device-resident fleet state (solver/resident.py): singleton
        # solve dispatches keep their operand stack on device and churn
        # applies as batched scatters.
        from karpenter_tpu.solver.resident import ResidentFleetState

        self._resident = ResidentFleetState()
        # solver introspection plane (observability/devicetelemetry.py,
        # --introspect): compile ledger + XLA cost attribution + device
        # memory telemetry. None (the default) keeps every hot-path
        # hook a single attribute read — the off-path pin.
        self._introspect = None
        # whether the decide family was given an injected kernel (the
        # gRPC split / tests): an injected decider owns its own device
        # semantics, so the sharded decide route must stay out of it
        self._decider_injected = decider is not None
        # backend health FSM (module docstring): trips wholesale to numpy
        # after K consecutive device failures, probes recovery
        self.health_failure_threshold = health_failure_threshold
        self.health_probe_interval_s = health_probe_interval_s
        self.watchdog_timeout_s = watchdog_timeout_s
        self._health_lock = threading.Lock()
        self._health = HEALTHY
        self._consec_device_failures = 0
        self._next_probe = 0.0
        # watchdog: generation-stamped worker threads; a restart bumps
        # the generation and the superseded thread discards its results
        self._worker_gen = 0
        self._watchdog: Optional[threading.Thread] = None
        self._busy_since: Optional[float] = None
        self._busy_requests: List[_Request] = []
        # the FULL batch the worker is currently processing (already
        # popped from the queue): on a watchdog restart, groups not yet
        # dispatched live only here and must be drained too
        self._current_batch: List[_Request] = []
        self._tls = threading.local()
        # (owning worker generation, staging key -> PadStaging): the
        # host areas _solve_pallas pads into, replaced whole for every
        # new worker and by reset_caches
        self._staging = self._fresh_staging()
        # per-tick dispatch accounting (note_tick): the gauge shows the
        # delta of stats.dispatches between manager ticks
        self._tick_dispatch_mark = 0
        self._register_metrics()

    # -- metrics ----------------------------------------------------------

    def _register_metrics(self) -> None:
        reg = self.registry.register
        self._g_queue = reg(SUBSYSTEM, QUEUE_DEPTH)
        self._g_coalesce = reg(SUBSYSTEM, COALESCE_FACTOR)
        self._c_requests = reg(SUBSYSTEM, REQUESTS_TOTAL, kind="counter")
        self._c_dispatch = reg(SUBSYSTEM, DISPATCH_TOTAL, kind="counter")
        self._c_hits = reg(SUBSYSTEM, COMPILE_CACHE_HITS, kind="counter")
        self._c_misses = reg(SUBSYSTEM, COMPILE_CACHE_MISSES, kind="counter")
        self._c_fallback = reg(SUBSYSTEM, FALLBACK_TOTAL, kind="counter")
        self._c_rejected = reg(SUBSYSTEM, REJECTED_TOTAL, kind="counter")
        self._c_expired = reg(
            SUBSYSTEM, DEADLINE_EXPIRED_TOTAL, kind="counter"
        )
        self._g_stage_p50 = reg(SUBSYSTEM, STAGE_P50_MS)
        self._g_stage_p99 = reg(SUBSYSTEM, STAGE_P99_MS)
        # native histograms (docs/observability.md): the stage rings as
        # real bucketed distributions {name=<stage>}, and the coalesce
        # factor as a batch-size histogram — histogram_quantile() works
        # where the p50/p99 gauge snapshots only sampled
        self._h_stage = reg(
            SUBSYSTEM, STAGE_SECONDS, kind="histogram",
            buckets=_STAGE_BUCKETS,
        )
        self._h_coalesce = reg(
            SUBSYSTEM, COALESCE_BATCH_SIZE, kind="histogram",
            buckets=_COALESCE_BUCKETS,
        )
        self._g_window = reg(SUBSYSTEM, WINDOW_MS)
        self._g_pipeline = reg(SUBSYSTEM, PIPELINE_DEPTH)
        # host->device transfer p50 of recent dispatches — the measured
        # baseline the device-resident-state work (ROADMAP item 4)
        # attacks; also present per-dispatch under stage_p50_ms{upload}
        self._g_upload = reg(SUBSYSTEM, UPLOAD_MS)
        # devices behind the sharded dispatch strategy (0 = single-device:
        # no mesh, below threshold traffic only, or shard path tripped)
        self._g_shard = reg(SUBSYSTEM, SHARD_DEVICES)
        # device-resident fleet state (solver/resident.py): bytes/rows
        # currently resident, the last scatter's wall time, and how
        # often residency had to rebuild from a full upload
        self._g_resident_bytes = reg(SUBSYSTEM, RESIDENT_BYTES)
        self._g_resident_rows = reg(SUBSYSTEM, RESIDENT_ROWS)
        self._g_resident_scatter = reg(SUBSYSTEM, RESIDENT_SCATTER_MS)
        self._c_resident_rebuilds = reg(
            SUBSYSTEM, RESIDENT_REBUILDS, kind="counter"
        )
        self._c_pad_staging = reg(SUBSYSTEM, PAD_STAGING, kind="counter")
        # boot-time pre-warm: rungs compiled {name=<family>} and the
        # wall cost of each family's warm dispatch — near-zero when the
        # persistent compile cache (JAX_COMPILATION_CACHE_DIR) served it
        self._c_prewarm = reg(SUBSYSTEM, PREWARM_COMPILES, kind="counter")
        self._g_prewarm_ms = reg(SUBSYSTEM, PREWARM_MS)
        # device programs per reconcile tick (note_tick): the fused-tick
        # 3+ → 1 program-count claim as a production observable
        self._g_dispatches_tick = reg(SUBSYSTEM, DISPATCHES_PER_TICK)
        # degradation-ladder surface (docs/resilience.md): FSM state
        # (0 healthy / 1 degraded) + transition and watchdog counters
        self._g_backend_state = reg("resilience", "solver_backend_state")
        self._g_backend_state.set("-", "-", 0.0)
        self._c_trips = reg(
            "resilience", "solver_trips_total", kind="counter"
        )
        self._c_probes = reg(
            "resilience", "solver_probes_total", kind="counter"
        )
        self._c_recoveries = reg(
            "resilience", "solver_recoveries_total", kind="counter"
        )
        self._c_watchdog = reg(
            "resilience", "solver_watchdog_restarts_total", kind="counter"
        )

    def _record_stage(self, stage: str, seconds: float) -> None:
        ms = seconds * 1e3
        with self._stage_lock:
            ring = self._stages.get(stage)
            if ring is None:
                ring = self._stages[stage] = collections.deque(
                    maxlen=_STAGE_WINDOW
                )
            ring.append(ms)
        self._h_stage.observe(stage, "-", seconds)

    def publish_gauges(self) -> None:
        """Refresh the point-in-time gauges (queue depth, coalesce
        factor, per-stage latency percentiles). Counters are incremented
        at event time and need no refresh; the Manager calls this each
        tick so /metrics stays current even across idle windows."""
        self._g_queue.set("-", "-", float(self.queue_depth()))
        self._g_coalesce.set(
            "-", "-", float(self.stats.last_coalesce_factor)
        )
        # the EFFECTIVE window of the last gather (0 on an idle queue,
        # window_s under concurrency) and the in-flight depth of the
        # last dispatch — the two tuning signals docs/solver-service.md's
        # latency section reads
        self._g_window.set("-", "-", self._window_now_s * 1e3)
        self._g_pipeline.set("-", "-", float(self._last_pipeline_depth))
        n_shard = 0
        if self._mesh is not None and not self._shard_broken:
            n_shard = int(self._mesh.devices.size)
        self._g_shard.set("-", "-", float(n_shard))
        self._g_resident_bytes.set(
            "-", "-", float(self._resident.resident_bytes())
        )
        self._g_resident_rows.set(
            "-", "-", float(self._resident.resident_rows())
        )
        with self._stage_lock:
            snapshot = {k: list(v) for k, v in self._stages.items()}
        for stage, samples in snapshot.items():
            if samples:
                p50, p99 = _p50_p99(samples)
                self._g_stage_p50.set(stage, "-", p50)
                self._g_stage_p99.set(stage, "-", p99)
                if stage == "upload":
                    self._g_upload.set("-", "-", p50)

    def note_tick(self) -> None:
        """Per-tick dispatch accounting behind the
        karpenter_solver_dispatches_per_tick gauge: the Manager calls
        this once at the end of every reconcile tick; the gauge then
        shows how many device programs that tick paid — 3+ on the
        chained steady-state path (forecast + decide + cost), exactly 1
        once --fused-tick engages (docs/solver-service.md "Fused
        tick")."""
        delta = self.stats.dispatches - self._tick_dispatch_mark
        self._tick_dispatch_mark = self.stats.dispatches
        self.stats.last_dispatches_per_tick = delta
        self._g_dispatches_tick.set("-", "-", float(delta))

    @contextlib.contextmanager
    def track(self, stage: str, span: Optional[str] = None, parent=None):
        """Record an arbitrary caller stage (e.g. the HA controller's
        fleet decide) into the service's latency surface. With `span`,
        the block is also that tracer span (a child of `parent` when
        given, else of this thread's current span): the bin-pack's
        host stages are timed at one boundary for both. Yields the open
        span, None where there is none to annotate."""
        t0 = _time.perf_counter()
        try:
            if span is None:
                yield None
            else:
                with default_tracer().span(span, parent=parent) as opened:
                    yield opened
        finally:
            self._record_stage(stage, _time.perf_counter() - t0)

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    def reset_caches(self) -> None:
        """Recovery-boot seam (docs/resilience.md "Crash recovery"):
        drop the compiled-program cache and the compile-seen keys so
        post-restart dispatches rebuild from scratch. Identity-keyed
        device caches (the encoder's delta layer hands back the SAME
        inputs object to skip re-upload) are only sound within one
        process lifetime of consistent state — a recovery boot must not
        silently reuse pre-crash arrays. Fresh dict/set objects are
        swapped in whole, so a worker mid-lookup keeps a consistent
        (old) view and the next lookup sees the reset."""
        with self._cond:
            self._compiled = {}
            self._compile_seen = set()
        # the fused family tracks freshness process-globally (its
        # program cache IS process-global — _count_fused_compile);
        # a recovery boot re-arms it alongside the instance caches
        _FUSED_COMPILE_SEEN.clear()
        # a recovery boot also re-arms the sharded dispatch strategy: a
        # pre-crash shard failure shouldn't pin the successor single-
        # device forever (the ladder re-trips on the next failure)
        self._shard_broken = False
        # and drops every device-resident operand stack: post-recovery
        # encodes must not scatter into pre-crash buffers (the encoder
        # clears its scatter plans through the same boot seam)
        self._resident.drop_all()
        # a reset plane may legitimately want a fresh warm-up
        self._prewarmed = set()
        # and the pad's host staging areas go with the arrays they held
        with self._cond:
            self._staging = self._fresh_staging()

    # -- introspection plane (observability/devicetelemetry.py) ------------

    def attach_introspection(self, plane) -> None:
        """Wire the solver introspection plane (--introspect): dispatch
        sites note compile-cache misses into its ledger and dispatch
        spans gain the XLA cost attribution captured at compile time.
        Detached (the default), every hook below is one attribute
        read."""
        self._introspect = plane

    def _note_compile(
        self, family: str, key: tuple, seconds: float,
        live: List[_Request] = (), extents=None, cost_fn=None,
    ) -> None:
        """One compile-cache miss into the introspection ledger: the
        wall time the first dispatch paid (compile + dispatch for this
        rung), the trace ids that paid for it, and — lazily, only with
        the plane enabled — the lowered program's XLA cost analysis.
        Never raises into the dispatch path."""
        plane = self._introspect
        if plane is None:
            return
        try:
            plane.note_compile(
                family, key, seconds,
                trace_ids=self._trace_ids(list(live)),
                extents=extents, cost_fn=cost_fn,
            )
        except Exception:  # noqa: BLE001 — telemetry must never break a solve
            pass

    def _fresh_cost_thunk(self, fresh: bool, fn, stacked, buckets: int):
        """The lazy XLA cost-analysis thunk for a FRESH batched solve
        dispatch, or None when nothing will consume it (cache hit, or
        the introspection plane detached/disabled)."""
        plane = self._introspect
        if not fresh or plane is None or not plane.enabled:
            return None
        return self._cost_thunk(fn, (stacked,), {"buckets": buckets})

    def _note_fresh_compile(
        self, fresh: bool, family: str, key: tuple, t0: float,
        live: List[_Request], cost_fn=None, extents=None,
    ) -> None:
        """Ledger the compile a FRESH dispatch just paid — the jit call
        returns once tracing + compile are done (execution is what
        stays async), so perf_counter() - t0 IS the compile wall time
        this rung's first dispatch paid. No-op for cache hits and on a
        watchdog-superseded worker."""
        if not fresh or self._stale():
            return
        self._note_compile(
            family, key, _time.perf_counter() - t0, live,
            extents=extents, cost_fn=cost_fn,
        )

    @staticmethod
    def _cost_thunk(fn, args: tuple, static: dict):
        """Zero-arg thunk returning the XLA cost analysis of `fn`
        lowered at `args`' shapes. Shapes are captured EAGERLY as
        ShapeDtypeStructs (donated operands may be deleted by the time
        the thunk runs) and the analysis runs on the LOWERED module —
        jax.stages.Lowered.cost_analysis, the analytical model with no
        second backend compile."""
        import jax

        shapes = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
            args,
        )

        def thunk():
            return fn.lower(*shapes, **static).cost_analysis()

        return thunk

    def _span_cost_args(self, key: tuple) -> dict:
        """{flops, bytes} args for this dispatch's span, {} when the
        introspection plane is off or never attributed the key — the
        off path adds nothing to any span."""
        plane = self._introspect
        if plane is None:
            return {}
        return plane.dispatch_cost_args(key)

    # -- boot-time compile pre-warm ----------------------------------------

    def prewarm(self, families=("solve", "decide")) -> Dict[str, dict]:
        """Compile the SMALLEST bucket rungs of the named kernel
        families before the first real request arrives
        (docs/solver-service.md "Compile pre-warm").

        Why: a cold service's idle-queue p99 is many times its p50 —
        the tail is first-touch jit compiles, which would
        otherwise eat the entire sub-second budget on a cold plane's
        first EVENT PASS (the latency the event-driven reconcile loop
        exists to remove). The warm-up drives one tiny REAL dispatch per
        family through the normal queue — same bucketing, same compile
        cache, same FSM accounting — so the compiled program is exactly
        the one a small fleet's first touch hits:

          solve  — 1 pod x 1 group, padded up to the floor rung
                   (256 pods x 8 groups), weight operand present (the
                   encoder always carries pod_weight);
          decide — 1 autoscaler x 1 metric, padded to the decision
                   kernel's row bucket (ops/decision.pad_to);
          fused  — the --fused-tick megakernel with every stage
                   present (forecast + decide + cost) at the smallest
                   bucket rung; the runtime adds it to the warm list
                   when the fused tick is enabled.

        A family already warmed this process lifetime is SKIPPED (the
        compile cache hits; reset_caches re-arms). With the persistent
        compile cache (utils/backend.arm_compile_cache) the compile
        itself is a disk read and the per-family prewarm_ms gauge shows
        it.
        Failures degrade, never block boot: a family whose warm dispatch
        errors is reported and skipped — the ladder serves real traffic
        from numpy exactly as it would have without the warm-up."""
        report: Dict[str, dict] = {}
        for family in families:
            if family in self._prewarmed:
                report[family] = {"skipped": True}
                continue
            misses_before = self.stats.compile_cache_misses
            t0 = _time.perf_counter()
            try:
                self._prewarm_dispatch(family)
            except Exception as error:  # noqa: BLE001 — never block boot
                logger().warning(
                    "compile pre-warm for family %r failed (%s: %s); "
                    "first-touch traffic will compile (or degrade) "
                    "instead", family, type(error).__name__, error,
                )
                report[family] = {
                    "skipped": False, "error": type(error).__name__,
                }
                continue
            elapsed_ms = (_time.perf_counter() - t0) * 1e3
            self._prewarmed.add(family)
            self._c_prewarm.inc(family, "-")
            self._g_prewarm_ms.set(family, "-", elapsed_ms)
            report[family] = {
                "skipped": False,
                "ms": round(elapsed_ms, 3),
            }
            if family in ("solve", "fused"):
                # only families that count compiles in the service's
                # cache counters report the number; decide rides
                # jax.jit's own cache, so claiming fresh_compiles=0
                # there would read as "cache-served" when the ms column
                # IS a first-touch compile — report the counter only
                # where it's real
                report[family]["fresh_compiles"] = (
                    self.stats.compile_cache_misses - misses_before
                )
        return report

    def _prewarm_dispatch(self, family: str) -> None:
        """One tiny real dispatch for `family` (see prewarm)."""
        if family == "solve":
            self.solve(_prewarm_solve_inputs())
            return
        if family == "decide":
            self.decide(_prewarm_decide_inputs())
            return
        if family == "fused":
            # the full-presence fused program (forecast + decide + cost
            # all engaged) at the smallest bucket rung — the program a
            # small fleet's first --fused-tick reconcile hits
            self.fused_tick(_prewarm_fused_inputs())
            return
        raise ValueError(f"unknown pre-warm family {family!r}")

    def stage_percentiles(self) -> Dict[str, Dict[str, float]]:
        """{stage: {"p50_ms", "p99_ms", "n"}} over the retained latency
        rings — the per-stage breakdown (pad, upload, dispatch,
        scatter, ...) the benchmark's solve cell reads."""
        with self._stage_lock:
            snapshot = {k: list(v) for k, v in self._stages.items()}
        out = {}
        for stage, samples in snapshot.items():
            if samples:
                p50, p99 = _p50_p99(samples)
                out[stage] = {
                    "p50_ms": round(p50, 4),
                    "p99_ms": round(p99, 4),
                    "n": len(samples),
                }
        return out

    # -- submission -------------------------------------------------------

    def _resolve_backend(self, backend: Optional[str]) -> str:
        backend = backend or self.backend
        if self.device_solver is not None:
            return backend  # the override owns backend semantics
        if backend == "auto":
            import jax

            if jax.default_backend() == "tpu":
                return "pallas"
            if jax.default_backend() == "cpu":
                return "numpy"
            return "xla"
        return backend

    def _shard_mesh(self):
        """The lazily-built dispatch mesh (parallel/mesh.py), or None
        when sharding is unavailable: disabled (shard_threshold <= 0),
        fewer than 2 devices and no explicit shape, or mesh construction
        failed (logged once and counted in stats.shard_mesh_errors; the
        single-device path serves)."""
        if self._mesh_ready:
            return self._mesh
        with self._mesh_lock:
            if self._mesh_ready:
                return self._mesh
            mesh = None
            try:
                if self.shard_threshold > 0:
                    import jax

                    from karpenter_tpu.parallel.mesh import build_mesh

                    devices = jax.devices()
                    n = len(devices)
                    if self.shard_devices is not None:
                        n = min(n, self.shard_devices)
                    shape = self.shard_mesh_shape
                    if shape is not None and shape[0] * shape[1] >= 2:
                        mesh = build_mesh(
                            devices=devices[:n], shape=shape
                        )
                    elif shape is None and n >= 2:
                        # a 1-device "mesh" (explicit 1x1 included)
                        # would route traffic through the inline
                        # sharded path with zero parallelism gain while
                        # reporting sharding active — below 2 devices
                        # the single-device program IS the right path
                        mesh = build_mesh(n_devices=n, devices=devices)
            except Exception as error:  # noqa: BLE001 — optional fast path
                self.stats.shard_mesh_errors += 1
                logger().warning(
                    "sharded dispatch unavailable (%s: %s); staying "
                    "single-device",
                    type(error).__name__, error,
                )
            self._mesh = mesh
            self._mesh_ready = True
            return mesh

    def _shard_extents(self, resolved: str, n_pods: int, n_groups: int):
        """Route one request: (effective backend, mesh extents | None).

        A request whose pods x groups cell count reaches shard_threshold
        rides the mesh — including pallas-resolved traffic: the fused
        Mosaic kernel has no multi-chip entry, and above the threshold
        using every chip through the GSPMD-partitioned XLA program beats
        one chip's fused kernel. Below threshold (or with sharding
        unavailable/tripped, or under a device_solver override where
        device math lives out of process) nothing changes."""
        if (
            self.shard_threshold <= 0
            or self._shard_broken
            or self.device_solver is not None
            or resolved not in ("xla", "pallas")
            or n_pods * n_groups < self.shard_threshold
        ):
            return resolved, None
        mesh = self._shard_mesh()
        if mesh is None:
            return resolved, None
        from karpenter_tpu.parallel.mesh import mesh_extents

        return "xla", mesh_extents(mesh)

    def submit(
        self,
        inputs: BinPackInputs,
        buckets: int = DEFAULT_BUCKETS,
        backend: Optional[str] = None,
        timeout: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> SolveFuture:
        """Enqueue one solve; raises SolverSaturated when the bounded
        queue is full (solve() turns that into the numpy fallback).
        `tenant` stamps the request's trace span (the multi-tenant
        scheduler passes it, so /debug/traces?tenant= finds the
        request — docs/multitenancy.md)."""
        if self._closed:
            raise RuntimeError("solver service is closed")
        n_pods = inputs.pod_requests.shape[0]
        n_groups = inputs.group_allocatable.shape[0]
        resolved = self._resolve_backend(backend)
        if resolved == "pallas" and has_constraint_operands(inputs):
            # the Mosaic kernel has no constraint entry; route to the
            # XLA family (exact, still on-device) and COUNT it — the
            # PR 8 silent-operand-drop bug class, closed at this third
            # dispatch site
            resolved = "xla"
            self.stats.constraint_reroutes += 1
        resolved, extents = self._shard_extents(resolved, n_pods, n_groups)
        key = (
            bucket_shape(inputs), buckets, resolved, presence(inputs),
            constraint_shape(inputs),
        )
        if extents is not None:
            key += ("shard", extents)
            self.stats.shard_requests += 1
        timeout = self.default_timeout_s if timeout is None else timeout
        now = self._clock()
        request = _Request(
            inputs=inputs,
            buckets=buckets,
            backend=resolved,
            key=key,
            n_pods=n_pods,
            n_groups=n_groups,
            deadline=(now + timeout) if timeout else None,
            enqueued_at=now,
            tenant=tenant,
        )
        self._enqueue_one(request)
        return SolveFuture(request, self)

    def _begin_request_span(self, request: _Request) -> None:
        """Open the request's reconcile-trace span (parented to the
        submitter's current span, so a tick-minted trace id follows the
        request across the worker-thread boundary). No-op — request.span
        stays None — when tracing is disabled."""
        family = (
            request.key[0] if isinstance(request.key[0], str) else "binpack"
        )
        request.span = default_tracer().begin(
            "solver.request", family=family, backend=request.backend,
            tenant=request.tenant,
        )

    def _record_rejected_span(self, key, backend: str) -> None:
        """Open-and-close a rejected request span for an overflow slot
        that never became a _Request (the coalesced batch path) — a
        trace export taken during saturation must show the rejected
        fleet-batch candidates, not just rejected singletons."""
        family = key[0] if isinstance(key[0], str) else "binpack"
        span = default_tracer().begin(
            "solver.request", family=family, backend=backend,
        )
        if span is not None:
            span.close(ok=False, rejected=True)

    def _enqueue_one(self, request: _Request) -> None:
        """Admit one request to the bounded queue (raises
        SolverSaturated when full) and wake the worker."""
        self._begin_request_span(request)
        with self._cond:
            if len(self._queue) >= self.max_queue:
                self.stats.rejected += 1
                self._c_rejected.inc("-", "-")
                if request.span is not None:
                    request.span.close(ok=False, rejected=True)
                raise SolverSaturated(
                    f"solver queue full ({self.max_queue})"
                )
            self._ensure_worker()
            self._queue.append(request)
            self.stats.requests += 1
            self._c_requests.inc("-", "-")
            self._g_queue.set("-", "-", float(len(self._queue)))
            self._cond.notify_all()

    def solve(
        self,
        inputs: BinPackInputs,
        buckets: int = DEFAULT_BUCKETS,
        backend: Optional[str] = None,
        timeout: Optional[float] = None,
    ):
        """Synchronous solve through the service — the drop-in `solver`
        seam every caller already takes (any (inputs, buckets=...) ->
        BinPackOutputs callable). Saturation and (by default) deadline
        expiry degrade to the numpy backend inline, so a caller always
        gets an answer while the device path is sick."""
        timeout = self.default_timeout_s if timeout is None else timeout
        try:
            future = self.submit(
                inputs, buckets=buckets, backend=backend, timeout=timeout
            )
        except SolverSaturated:
            logger().warning(
                "solver queue saturated; degrading one request to numpy"
            )
            self._note_served("solve", "numpy")
            return self._numpy_fallback(inputs, buckets)
        try:
            return future.result(timeout if timeout else None)
        except SolverTimeout:
            if self.on_timeout == "raise":
                raise
            logger().warning(
                "solve deadline expired; degrading one request to numpy"
            )
            self._note_served("solve", "numpy")
            return self._numpy_fallback(inputs, buckets)

    def consolidate(
        self,
        inputs_list,
        buckets: int = DEFAULT_BUCKETS,
        backend: Optional[str] = None,
        timeout: Optional[float] = None,
    ) -> list:
        """Batched candidate evaluation for the consolidation planner:
        N masked bin-packs (one per drain candidate) submitted ATOMICALLY
        and answered as a list in input order.

        The requests ride the normal pipeline — same coalescing queue,
        shape ladder, compile cache, numpy fallback — but carry a shared
        batch marker, so the worker drains the whole set past `max_batch`
        and same-bucket candidates ride ONE device dispatch (lax.map over
        the stacked candidates). Candidate-count jitter only moves along
        the batch ladder, so steady-state consolidation never recompiles.

        Degradations match solve(): a full queue answers the overflow
        candidates from numpy inline; a deadline expiry degrades (or
        raises, per `on_timeout`) per candidate."""
        if not inputs_list:
            return []
        if self._closed:
            raise RuntimeError("solver service is closed")
        self.stats.consolidate_calls += 1
        self.stats.consolidate_candidates += len(inputs_list)
        resolved = self._resolve_backend(backend)
        timeout = self.default_timeout_s if timeout is None else timeout
        requests = self._enqueue_batch(
            inputs_list, buckets, resolved, timeout
        )
        return self._gather_batch(requests, inputs_list, buckets, timeout)

    def _gather_batch(
        self, requests, inputs_list, buckets: int, timeout
    ) -> list:
        """Wait out a consolidate() batch in input order; overflow slots
        (None) and expired candidates degrade to numpy per solve()'s
        semantics."""
        results = []
        for i, request in enumerate(requests):
            if request is None:
                logger().warning(
                    "solver queue saturated; degrading one consolidation "
                    "candidate to numpy"
                )
                results.append(
                    self._numpy_fallback(inputs_list[i], buckets)
                )
                continue
            try:
                results.append(
                    SolveFuture(request, self).result(
                        timeout if timeout else None
                    )
                )
            except SolverTimeout:
                if self.on_timeout == "raise":
                    # nobody will wait on the rest of the batch: flag
                    # them abandoned so the worker skips them instead of
                    # burning a full dispatch for dropped results
                    for rest in requests[i + 1:]:
                        if rest is not None:
                            rest.abandoned = True
                    raise
                logger().warning(
                    "consolidation candidate deadline expired; degrading "
                    "to numpy"
                )
                results.append(
                    self._numpy_fallback(inputs_list[i], buckets)
                )
        return results

    def _consolidate_key(self, inputs, buckets: int, resolved: str):
        """(key, effective backend) for one consolidate() candidate.

        5th key element: consolidation batches vectorize across
        candidates (jax.vmap) instead of scanning (lax.map) —
        cluster-scale operands make the C× memory amplification
        trivial, and vectorization is where the batched >> sequential
        throughput comes from. The distinct key keeps these groups from
        mixing with plain solve() traffic compiled for the
        memory-bounded scan. Fleet-scale candidate evaluations
        additionally ride the mesh ("vmap_shard" + extents — the
        sharded dispatch strategy, same ladder as solve())."""
        if resolved == "pallas" and has_constraint_operands(inputs):
            # same reroute as submit(): Mosaic has no constraint entry
            resolved = "xla"
            self.stats.constraint_reroutes += 1
        backend_eff, extents = self._shard_extents(
            resolved,
            inputs.pod_requests.shape[0],
            inputs.group_allocatable.shape[0],
        )
        if extents is None:
            return (
                bucket_shape(inputs), buckets, backend_eff,
                presence(inputs), constraint_shape(inputs), "vmap",
            ), backend_eff
        self.stats.shard_requests += 1
        return (
            bucket_shape(inputs), buckets, backend_eff,
            presence(inputs), constraint_shape(inputs), "vmap_shard",
            extents,
        ), backend_eff

    def _enqueue_batch(
        self, inputs_list, buckets: int, resolved: str, timeout
    ) -> List[Optional[_Request]]:
        """Enqueue a consolidate() batch atomically under one lock hold
        (contiguous in the deque, shared coalesce_id). Overflow slots
        come back as None, in order, for inline numpy degradation.

        Routing (keys + shard extents) resolves BEFORE the lock: the
        first fleet-scale batch lazily initializes the backend and
        builds the mesh, and doing that under self._cond would stall
        every submitter, the worker, and the watchdog."""
        now = self._clock()
        keyed = [
            self._consolidate_key(inputs, buckets, resolved)
            for inputs in inputs_list
        ]
        requests: List[Optional[_Request]] = []
        with self._cond:
            self._coalesce_seq += 1
            cid = self._coalesce_seq
            for inputs, (key, backend_eff) in zip(inputs_list, keyed):
                if len(self._queue) >= self.max_queue:
                    self.stats.rejected += 1
                    self._c_rejected.inc("-", "-")
                    self._record_rejected_span(key, backend_eff)
                    requests.append(None)
                    continue
                request = _Request(
                    inputs=inputs,
                    buckets=buckets,
                    backend=backend_eff,
                    key=key,
                    n_pods=inputs.pod_requests.shape[0],
                    n_groups=inputs.group_allocatable.shape[0],
                    deadline=(now + timeout) if timeout else None,
                    enqueued_at=now,
                    coalesce_id=cid,
                )
                self._begin_request_span(request)
                self._queue.append(request)
                self.stats.requests += 1
                self._c_requests.inc("-", "-")
                requests.append(request)
            self._ensure_worker()
            self._g_queue.set("-", "-", float(len(self._queue)))
            self._cond.notify_all()
        return requests

    def forecast(self, inputs, backend: Optional[str] = None,
                 timeout: Optional[float] = None):
        """Batched metric forecasting through the service
        (forecast/models.py, docs/forecasting.md): one ForecastInputs
        matrix of S series in, one ForecastOutputs out.

        Requests ride the SAME coalescing queue as bin-packs: concurrent
        forecast() callers whose histories share a time-axis bucket are
        concatenated along the series axis and answered by ONE device
        dispatch through the shared compile cache (shape-bucketed on
        (series, history) — steady fleets never recompile). Degradations
        match solve(): a full queue or expired deadline answers from the
        bit-identical numpy mirror inline, a device failure falls back
        per batch, and the backend-health FSM short-circuits a sick
        device wholesale. `forecast.predict` is the fault-injection
        point on the device path (docs/resilience.md)."""
        n_series = int(np.asarray(inputs.values).shape[0])
        self.stats.forecast_calls += 1
        self.stats.forecast_series += n_series
        if n_series == 0:
            from karpenter_tpu.forecast.models import ForecastOutputs

            empty = np.zeros(0, np.float32)
            return ForecastOutputs(
                point=empty, sigma2=empty.copy(),
                n_valid=np.zeros(0, np.int32),
            )
        if self._closed:
            raise RuntimeError("solver service is closed")
        timeout = self.default_timeout_s if timeout is None else timeout
        request = self._forecast_request(
            inputs, n_series, backend, timeout
        )
        try:
            self._enqueue_one(request)
        except SolverSaturated:
            logger().warning(
                "solver queue saturated; degrading one forecast to numpy"
            )
            return self._numpy_fallback(request.inputs, 0)
        try:
            return SolveFuture(request, self).result(
                timeout if timeout else None
            )
        except SolverTimeout:
            if self.on_timeout == "raise":
                raise
            logger().warning(
                "forecast deadline expired; degrading to numpy"
            )
            return self._numpy_fallback(request.inputs, 0)

    def _forecast_request(
        self, inputs, n_series: int, backend: Optional[str], timeout
    ) -> _Request:
        """Resolve the backend and build one queue-ready forecast
        request, padded up the history-length ladder."""
        from karpenter_tpu.forecast.models import pad_forecast_inputs

        resolved = self._resolve_backend(backend)
        if self.device_solver is not None:
            # the sidecar wire carries bin-packs only: under the gRPC
            # process split the control plane must not run device math,
            # so forecasts serve from the numpy mirror
            resolved = "numpy"
        elif resolved == "pallas":
            resolved = "xla"  # no Mosaic forecast kernel; XLA runs on TPU
        now = self._clock()
        t_bucket = bucket_up(
            int(np.asarray(inputs.values).shape[1]), FORECAST_T_FLOOR
        )
        # fleet-scale forecasts shard their SERIES axis over the mesh
        # rows (cells = series x history slots, same threshold as
        # bin-packs); below threshold the key is unchanged
        resolved, extents = self._shard_extents(
            resolved, n_series, t_bucket
        )
        key = ("forecast", t_bucket, resolved)
        if extents is not None:
            key += ("shard", extents)
            self.stats.shard_requests += 1
        return _Request(
            inputs=pad_forecast_inputs(inputs, t_bucket),
            buckets=0,
            backend=resolved,
            key=key,
            n_pods=n_series,
            n_groups=0,
            deadline=(now + timeout) if timeout else None,
            enqueued_at=now,
        )

    def preempt(self, inputs, backend: Optional[str] = None,
                timeout: Optional[float] = None):
        """Fleet-wide placement-with-eviction through the service
        (ops/preempt.py, docs/preemption.md): one PreemptInputs problem
        — C candidate pods x N node columns x V victims — in, one
        PreemptOutputs out, ONE device dispatch planning every
        candidate. Requests ride the same coalescing queue, shape-
        bucketed compile cache (preempt_bucket_shape ladder), numpy-
        fallback ladder, and backend-health FSM as bin-packs; the numpy
        mirror is BIT-IDENTICAL to the device kernel (integer-capacity
        arithmetic — ops/preempt.py docstring), so a degraded answer is
        the same answer. `preempt.plan` is the fault-injection point on
        the device path (docs/resilience.md)."""
        from karpenter_tpu.ops.preempt import MAX_VICTIMS, PreemptOutputs

        n_candidates = int(np.asarray(inputs.pod_requests).shape[0])
        n_victims = int(np.asarray(inputs.victim_requests).shape[0])
        self.stats.preempt_calls += 1
        self.stats.preempt_candidates += n_candidates
        if n_candidates == 0:
            return PreemptOutputs(
                chosen_node=np.zeros(0, np.int32),
                evict_count=np.zeros(0, np.int32),
                evict_mask=np.zeros((0, n_victims), bool),
                unplaceable=np.int32(0),
            )
        if n_victims > MAX_VICTIMS:
            raise ValueError(
                f"preempt solve supports at most {MAX_VICTIMS} victims, "
                f"got {n_victims}"
            )
        if self._closed:
            raise RuntimeError("solver service is closed")
        timeout = self.default_timeout_s if timeout is None else timeout
        request = self._preempt_request(
            inputs, n_candidates, n_victims, backend, timeout
        )
        try:
            self._enqueue_one(request)
        except SolverSaturated:
            logger().warning(
                "solver queue saturated; degrading one eviction plan "
                "to numpy"
            )
            return self._numpy_fallback(inputs, 0)
        try:
            return SolveFuture(request, self).result(
                timeout if timeout else None
            )
        except SolverTimeout:
            if self.on_timeout == "raise":
                raise
            logger().warning(
                "eviction-plan deadline expired; degrading to numpy"
            )
            return self._numpy_fallback(inputs, 0)

    def _preempt_request(
        self, inputs, n_candidates: int, n_victims: int,
        backend: Optional[str], timeout,
    ) -> _Request:
        """Resolve the backend and build one queue-ready eviction-plan
        request (keyed on the preempt shape ladder)."""
        resolved = self._resolve_backend(backend)
        if self.device_solver is not None:
            # the sidecar wire carries bin-packs only: under the gRPC
            # process split eviction plans serve from the numpy mirror
            resolved = "numpy"
        elif resolved == "pallas":
            resolved = "xla"  # no Mosaic preempt kernel; XLA runs on TPU
        now = self._clock()
        # fleet-scale eviction storms shard their CANDIDATE axis over
        # the mesh rows (cells = candidates x victims — the dominant
        # [C, V] evictability/prefix matrices — same threshold as
        # bin-packs); below threshold the key is unchanged
        resolved, extents = self._shard_extents(
            resolved, n_candidates, max(n_victims, 1)
        )
        key = ("preempt", preempt_bucket_shape(inputs), resolved)
        if extents is not None:
            key += ("shard", extents)
            self.stats.shard_requests += 1
        return _Request(
            inputs=inputs,
            buckets=0,
            backend=resolved,
            key=key,
            n_pods=n_candidates,
            n_groups=n_victims,
            deadline=(now + timeout) if timeout else None,
            enqueued_at=now,
        )

    def cost(self, inputs, backend: Optional[str] = None):
        """The multi-objective cost/SLO refinement through the service
        (ops/cost.py, docs/cost.md): one CostInputs matrix for the whole
        fleet in, one CostOutputs out, ONE device dispatch — synchronous
        like decide() (the BatchAutoscaler already batches the fleet).
        Shapes ride the decision kernel's pad_to bucket, so steady
        fleets never recompile (the module-level jit IS the cache).

        Degradation posture (deliberately different from forecast):
        the refinement is ADVISORY — on any failure the right answer is
        the UNREFINED base decision (the caller's never-block contract,
        CostEngine.adjust), not a host re-score every tick through an
        outage. So: the numpy mirror serves as the REQUESTED backend
        (CPU auto-resolution, the gRPC process split — bit-identical,
        tests/test_cost.py), device failures count toward the shared
        backend-health FSM and PROPAGATE (the tick goes cost-blind),
        and a DEGRADED FSM short-circuits with CostUnavailable instead
        of attempting the sick device — probes ride the normal recovery
        path. `cost.score` is the fault-injection point
        (faults/registry.py, docs/resilience.md)."""
        from karpenter_tpu.ops import cost as CK

        self.stats.cost_calls += 1
        resolved = self._resolve_backend(backend)
        if self.device_solver is not None:
            # the sidecar wire carries bin-packs only: under the gRPC
            # process split cost refinement serves from the numpy mirror
            resolved = "numpy"
        elif resolved == "pallas":
            resolved = "xla"  # no Mosaic cost kernel; XLA runs on TPU
        t0 = _time.perf_counter()
        try:
            if resolved == "numpy":
                # the REQUESTED backend, not a degradation: the
                # bit-identical mirror, no fallback counting
                with default_tracer().span("solver.cost", backend="numpy"):
                    out = CK.cost_numpy(inputs)
                self._annotate_provenance("numpy", "numpy")
                return out
            if not self._device_allowed():
                raise CostUnavailable(
                    "solver backend degraded; scaling cost-blind until "
                    "a probe recovers the device path"
                )
            import jax

            try:
                with default_tracer().span("solver.cost", backend=resolved):
                    # the cost-path fault-injection point: an error
                    # plan exercises the cost-blind degradation +
                    # FSM trip (docs/resilience.md)
                    inject("cost.score")
                    out = CK.cost_jit(inputs)
                    jax.block_until_ready(out)
            except Exception:
                self._record_device_failure()
                raise
            self._record_device_success()
            self.stats.cost_dispatches += 1
            self._count_dispatch()
            self._annotate_provenance(resolved, "device")
            return CK.CostOutputs(
                desired=np.asarray(out.desired),
                expected_hourly=np.asarray(out.expected_hourly),
                violation_risk=np.asarray(out.violation_risk),
                headroom=np.asarray(out.headroom),
                cost_limited=np.asarray(out.cost_limited),
                slo_raised=np.asarray(out.slo_raised),
            )
        except Exception:
            self.stats.cost_errors += 1
            raise
        finally:
            self._record_stage("cost", _time.perf_counter() - t0)

    def poolgroup(self, inputs, backend: Optional[str] = None):
        """The joint pool-group allocation through the service
        (ops/poolgroup.py, docs/poolgroups.md): every PoolGroup's joint
        candidate ladder scored in ONE batched dispatch — the grouped
        HAs' replacement for N independent cost dispatches.

        Degradation is the never-block ladder and it is SEMANTIC, not
        just a backend swap: device joint kernel → INDEPENDENT per-pool
        ladders (the numpy mirror with joint selection disabled — each
        pool still refines exactly as the cost family would, but ratios
        and the shared budget go advisory for the tick) → the caller's
        own never-block contract. A numpy-resolved backend serves the
        full JOINT mirror (bit-identical, the REQUESTED backend, like
        cost()). Device failures feed the shared backend-health FSM; a
        DEGRADED FSM short-circuits straight to the independent rung so
        probes ride the normal recovery path. `poolgroup.solve` is the
        fault-injection point (faults/registry.py)."""
        from karpenter_tpu.ops import poolgroup as PGK

        self.stats.poolgroup_calls += 1
        resolved = self._resolve_backend(backend)
        if self.device_solver is not None:
            resolved = "numpy"  # the gRPC wire carries bin-packs only
        elif resolved == "pallas":
            resolved = "xla"  # no Mosaic poolgroup kernel; XLA runs on TPU
        t0 = _time.perf_counter()
        try:
            if resolved == "numpy":
                # the REQUESTED backend, not a degradation: the
                # bit-identical joint mirror, constraints fully enforced
                with default_tracer().span(
                    "solver.poolgroup", backend="numpy"
                ):
                    out = PGK.poolgroup_numpy(inputs)
                self._annotate_provenance("numpy", "numpy")
                return out
            if self._device_allowed():
                try:
                    import jax

                    with default_tracer().span(
                        "solver.poolgroup", backend=resolved
                    ):
                        # the joint-path fault-injection point: an
                        # error plan exercises the independent-
                        # ladder degradation + FSM trip
                        inject("poolgroup.solve")
                        out = PGK.poolgroup_jit(inputs)
                        jax.block_until_ready(out)
                    self._record_device_success()
                    self.stats.poolgroup_dispatches += 1
                    self._count_dispatch()
                    self._annotate_provenance(resolved, "device")
                    return jax.tree_util.tree_map(np.asarray, out)
                except Exception as error:  # noqa: BLE001 — never-block
                    self._record_device_failure()
                    logger().warning(
                        "joint poolgroup dispatch failed (%s: %s); "
                        "serving INDEPENDENT per-pool ladders this tick "
                        "(ratios advisory)",
                        type(error).__name__, error,
                    )
            with default_tracer().span(
                "solver.poolgroup", backend="independent"
            ):
                self.stats.poolgroup_independent_serves += 1
                out = PGK.poolgroup_numpy(inputs, enforce=False)
            self._annotate_provenance("numpy", "numpy")
            return out
        except Exception:
            self.stats.poolgroup_errors += 1
            raise
        finally:
            self._record_stage("poolgroup", _time.perf_counter() - t0)

    def sim_step(self, inputs, backend: Optional[str] = None):
        """One simulated-cluster tick through the service (ops/simstep.py,
        docs/simulator.md): elementwise over any leading batch shape, so
        a BatchedSimEnv's N clusters advance as ONE dispatch."""
        from karpenter_tpu.ops import simstep as SK

        return self._sim_dispatch(
            "solver.sim_step", SK.sim_step_jit, SK.sim_step_numpy, inputs,
            backend,
        )

    def sim_rollout(self, inputs, backend: Optional[str] = None):
        """A whole simulated episode (in-kernel tuned policy) through
        the service: batched trails ride the vmapped program — N
        clusters x T ticks in one device dispatch (docs/simulator.md)."""
        from karpenter_tpu.ops import simstep as SK

        batched = np.asarray(inputs.replicas0).ndim > 1
        return self._sim_dispatch(
            "solver.sim_rollout",
            SK.sim_rollout_vmapped if batched else SK.sim_rollout_jit,
            SK.sim_rollout_numpy, inputs, backend,
        )

    def _sim_dispatch(self, span, device_fn, numpy_fn, inputs, backend):
        """The simlab family's one door: tracing + stats + backend
        resolution like cost(), but a NEVER-BLOCK degradation posture —
        the numpy mirror is bit-identical (tests/test_simlab.py), so a
        device failure serves the mirror instead of raising; failures
        still feed the shared backend-health FSM. `simlab.step` is the
        fault-injection point (faults/registry.py)."""
        self.stats.sim_calls += 1
        resolved = self._resolve_backend(backend)
        if self.device_solver is not None:
            resolved = "numpy"  # the gRPC wire carries bin-packs only
        elif resolved == "pallas":
            resolved = "xla"  # no Mosaic sim kernel; XLA runs on TPU
        t0 = _time.perf_counter()
        try:
            if resolved != "numpy" and self._device_allowed():
                try:
                    import jax

                    with default_tracer().span(span, backend=resolved):
                        inject("simlab.step")
                        out = device_fn(inputs)
                        jax.block_until_ready(out)
                    self._record_device_success()
                    self.stats.sim_dispatches += 1
                    self._count_dispatch()
                    return jax.tree_util.tree_map(np.asarray, out)
                except Exception as error:  # noqa: BLE001 — never-block
                    self._record_device_failure()
                    logger().warning(
                        "sim device dispatch failed (%s: %s); serving "
                        "the bit-identical numpy mirror",
                        type(error).__name__, error,
                    )
            with default_tracer().span(span, backend="numpy"):
                self.stats.sim_mirror_serves += 1
                return numpy_fn(inputs)
        finally:
            self._record_stage("sim", _time.perf_counter() - t0)

    def fused_tick(self, inputs, backend: Optional[str] = None):
        """The fused steady-state tick through the service
        (ops/fusedtick.py, docs/solver-service.md "Fused tick"):
        forecast → decide → cost as ONE compiled program, zero host
        round-trips between stages — the whole fleet's reconcile math
        in a single dispatch.

        Degradation posture is the never-block ladder: a fused-program
        failure falls back to the CHAINED per-stage path (the exact
        pre-fusion wire, bit-identical outputs), a chained failure
        serves the numpy floor — the tick always completes. Fused
        failures feed the shared backend-health FSM; the chained rung
        is a degraded serve and leaves the FSM counting, so a
        persistently faulting fused program still trips wholesale to
        numpy and probes recovery like every other family. Fleets whose
        N x M cells reach shard_threshold take the chained rung by
        design: its decide stage rides the mesh-sharded program (the
        megakernel has no multi-chip entry). `fused.tick` is the
        fault-injection point (faults/registry.py)."""
        from karpenter_tpu.ops import fusedtick as FT

        self.stats.fused_calls += 1
        resolved = self._resolve_backend(backend)
        if self.device_solver is not None:
            resolved = "numpy"  # the gRPC wire carries bin-packs only
        elif resolved == "pallas":
            resolved = "xla"  # no Mosaic fused kernel; XLA runs on TPU
        # pad the forecast group up the forecast family's shape ladders
        # ONCE at the door — every rung (fused, chained, numpy) consumes
        # the SAME padded operands, so the ladder can switch rungs
        # mid-tick bit for bit and compile keys bucket like the
        # standalone forecast family's
        t_bucket = s_bucket = n_series = 0
        if inputs.forecast is not None:
            import dataclasses

            from karpenter_tpu.forecast.models import pad_forecast_inputs

            shape = np.asarray(inputs.forecast.values).shape
            n_series = int(shape[0])
            t_bucket = bucket_up(int(shape[1]), FORECAST_T_FLOOR)
            s_bucket = bucket_up(n_series, FORECAST_S_FLOOR)
            inputs = dataclasses.replace(
                inputs,
                forecast=pad_forecast_inputs(inputs.forecast, t_bucket),
            )
            inputs = FT.pad_series(inputs, s_bucket)
        n = int(np.asarray(inputs.decision.spec_replicas).shape[0])
        m = int(np.asarray(inputs.decision.metric_value).shape[1])
        t0 = _time.perf_counter()
        try:
            if resolved != "numpy" and self._device_allowed():
                out = self._fused_device(
                    inputs, resolved, n, m, t_bucket, s_bucket, t0
                )
                if out is not None:
                    return self._fused_slice(out, n_series)
            with default_tracer().span(
                "solver.fused_tick", backend="numpy"
            ):
                out = FT.fused_tick_numpy(inputs)
            if resolved != "numpy":
                self.stats.fused_mirror_serves += 1
            self._annotate_provenance("numpy", "numpy")
            return self._fused_slice(out, n_series)
        finally:
            self._record_stage("fused", _time.perf_counter() - t0)

    def _fused_device(
        self, inputs, resolved: str, n: int, m: int,
        t_bucket: int, s_bucket: int, t0: float,
    ):
        """The fused + chained device rungs of fused_tick's ladder;
        None = both failed (the caller serves the numpy floor)."""
        import jax

        from karpenter_tpu.ops import fusedtick as FT

        _, extents = self._shard_extents("xla", n, max(m, 1))
        if extents is None:
            key = (
                "fused", n, m, t_bucket, s_bucket,
                inputs.forecast is not None,
                inputs.slo_valid is not None,
                inputs.poolgroup is not None,
                resolved,
            )
            try:
                fresh = self._count_fused_compile(key)
                cost_fn = None
                plane = self._introspect
                if fresh and plane is not None and plane.enabled:
                    cost_fn = self._cost_thunk(
                        FT.fused_tick_jit, (inputs,), {}
                    )
                with default_tracer().span(
                    "solver.fused_tick", backend=resolved,
                    **self._span_cost_args(key),
                ):
                    # the fused-path fault-injection point: an
                    # error plan exercises the fused → chained →
                    # numpy ladder + FSM trip (docs/resilience.md)
                    inject("fused.tick")
                    out = FT.fused_tick_jit(inputs)
                    jax.block_until_ready(out)
                self._note_fresh_compile(
                    fresh, "fused", key, t0, [], cost_fn=cost_fn,
                )
                self._record_device_success()
                self.stats.fused_dispatches += 1
                self._count_dispatch()
                self._annotate_provenance(resolved, "device")
                return jax.tree_util.tree_map(np.asarray, out)
            except Exception as error:  # noqa: BLE001 — never-block
                self._record_device_failure()
                logger().warning(
                    "fused tick dispatch failed (%s: %s); falling back "
                    "to the chained per-stage path",
                    type(error).__name__, error,
                )
        try:
            with default_tracer().span(
                "solver.fused_tick", backend="chained"
            ):
                out = FT.fused_tick_chained(inputs)
            # a degraded serve: stage dispatches are counted (the
            # dispatches-per-tick gauge must show the real program
            # count) but the FSM keeps counting fused failures — a
            # persistently faulting megakernel must still trip
            self.stats.fused_chained_serves += 1
            for _ in range(FT.programs(inputs)):
                self._count_dispatch()
            self._annotate_provenance("xla", "device")
            return out
        except Exception as error:  # noqa: BLE001 — never-block
            self._record_device_failure()
            logger().warning(
                "chained fused-tick fallback failed (%s: %s); serving "
                "the bit-identical numpy floor",
                type(error).__name__, error,
            )
            return None

    @staticmethod
    def _fused_slice(out, n_series: int):
        """Slice the forecast outputs back to the caller's S (padding
        series are service-internal, exactly like the queue family)."""
        if out.forecast is None:
            return out
        import dataclasses

        from karpenter_tpu.forecast.models import slice_forecast_outputs

        return dataclasses.replace(
            out,
            forecast=slice_forecast_outputs(out.forecast, 0, n_series),
        )

    def _note_served(self, family: str, kernel: str) -> None:
        key = f"{family}/{kernel}"
        with self._served_lock:
            self.stats.served[key] = self.stats.served.get(key, 0) + 1

    def _annotate_provenance(self, backend: str, rung: str) -> None:
        """Provenance slice (observability/provenance.py): stamp the
        backend + degradation rung that actually served onto the
        CURRENT ledger batch — only for batches whose owner opted into
        service-side stamping (autosolver: the BatchAutoscaler flow;
        the MultiTenantScheduler stamps rungs per tenant slice itself).
        One attribute read when the ledger is off."""
        from karpenter_tpu.observability import default_ledger

        ledger = default_ledger()
        if not ledger.enabled:
            return
        batch = ledger.current()
        if batch is not None and batch.autosolver:
            batch.annotate(solver_backend=backend, solver_rung=rung)

    def decide(self, inputs):
        """The HPA decision kernel through the service: same metrics
        surface and error accounting, no coalescing (the batch
        autoscaler already evaluates the whole fleet in one call). A
        fleet whose N x M cell count reaches shard_threshold rides the
        mesh — the decision fleet axis shards over the mesh rows
        (parallel/mesh.decision_shardings), with a single-device retry
        on any mesh failure (the same ladder posture as bin-packs)."""
        self.stats.decide_calls += 1
        t0 = _time.perf_counter()
        try:
            with default_tracer().span("solver.decide"):
                out = self._decide_dispatch(inputs)
            # the decide kernel has no numpy mirror: it is served by
            # the in-process jitted program ("device": XLA on whatever
            # backend jax resolved) or across the gRPC split
            self._note_served(
                "decide", "grpc" if self.device_solver is not None else "xla"
            )
            self._annotate_provenance(
                "grpc" if self.device_solver is not None else "xla",
                "sidecar" if self.device_solver is not None
                else "device",
            )
            return out
        except Exception:
            self.stats.decide_errors += 1
            raise
        finally:
            self._record_stage("decide", _time.perf_counter() - t0)

    def _decide_fn(self):
        if self._decider is None:
            from karpenter_tpu.ops.decision import decide_jit

            self._decider = decide_jit
        return self._decider

    def _decide_dispatch(self, inputs):
        """Route one fleet decide: the sharded program above threshold
        (in-process default kernel only — an injected decider or the
        gRPC split owns its own device semantics), the single-device
        jit otherwise. A mesh failure retries single-device inline and
        trips the shard route, exactly like the bin-pack ladder —
        decide stays the never-block kernel either way."""
        fn = self._decide_fn()
        if self._decider_injected:
            return fn(inputs)
        # the SAME routing guards every queue family takes
        # (_shard_extents: threshold, shard-broken trip, device_solver,
        # mesh availability) — decide's cells are fleet x metric columns
        n = int(inputs.spec_replicas.shape[0])
        m = int(inputs.metric_value.shape[1])
        _, extents = self._shard_extents("xla", n, max(m, 1))
        if extents is None:
            return fn(inputs)
        mesh = self._shard_mesh()
        from karpenter_tpu.parallel.mesh import sharded_decide

        self.stats.shard_requests += 1
        try:
            out = sharded_decide(mesh, inputs)
            self.stats.shard_dispatches += 1
            return out
        except Exception as error:  # noqa: BLE001 — shard-rung failure
            self.stats.shard_fallbacks += 1
            self._shard_broken = True
            logger().warning(
                "sharded decide failed (%s: %s); retrying single-device "
                "and disabling the shard route",
                type(error).__name__, error,
            )
            default_flight_recorder().record(
                "shard_fallback",
                subsystem="solver",
                error=type(error).__name__,
                family="decide",
            )
            return fn(inputs)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        worker = self._worker
        if worker is not None:
            worker.join(timeout=5.0)
            self._worker = None
        watchdog = self._watchdog
        if watchdog is not None:
            watchdog.join(timeout=2.0)
            self._watchdog = None

    # -- backend health FSM + watchdog ------------------------------------

    def backend_health(self) -> str:
        with self._health_lock:
            return self._health

    def _device_allowed(self) -> bool:
        """Gate one batch's device attempt through the FSM: always in
        HEALTHY; in DEGRADED only the periodic probe — everything else
        short-circuits to numpy without billing a failed dispatch."""
        with self._health_lock:
            if self._health == HEALTHY:
                return True
            now = self._clock()
            if now >= self._next_probe:
                # this dispatch IS the recovery probe; schedule the next
                # one now so concurrent groups don't all probe at once
                self._next_probe = now + self.health_probe_interval_s
                self.stats.fsm_probes += 1
                self._c_probes.inc("-", "-")
                return True
            self.stats.fsm_short_circuits += 1
            return False

    def _record_device_failure(self, requests: List[_Request] = ()) -> bool:
        # the degradation ladder discards residency cleanly: after ANY
        # device-path failure the resident buffers are suspect (a hung
        # or faulted device may hold poisoned state), so the next
        # healthy dispatch re-establishes them from a full upload
        self._resident.drop_all()
        with self._health_lock:
            self.stats.device_failures += 1
            self._consec_device_failures += 1
            tripped = (
                self._health == HEALTHY
                and self._consec_device_failures
                >= self.health_failure_threshold
            )
            if tripped:
                self._health = DEGRADED
                self._next_probe = (
                    self._clock() + self.health_probe_interval_s
                )
                self.stats.fsm_trips += 1
                self._c_trips.inc("-", "-")
                self._g_backend_state.set("-", "-", 1.0)
        if tripped:
            logger().warning(
                "solver backend DEGRADED after %d consecutive device "
                "failures; serving from numpy, probing recovery every "
                "%.1fs",
                self._consec_device_failures,
                self.health_probe_interval_s,
            )
            # post-mortem surface (observability.flightrecorder): WHICH
            # reconcile traces the trip degraded, not just that it
            # happened — dumps crash-safely when a dump dir is wired
            default_flight_recorder().record(
                "fsm_trip",
                trace_ids=self._trace_ids(requests),
                subsystem="solver",
                consecutive_failures=self._consec_device_failures,
                requests=len(requests),
            )
        return tripped

    @staticmethod
    def _trace_ids(requests: List[_Request]) -> List[str]:
        """Distinct trace ids of the requests a degradation touched
        (insertion-ordered, deduped)."""
        return list(dict.fromkeys(
            tid for r in requests
            if (tid := r.trace_id()) is not None
        ))

    def _record_device_success(self) -> None:
        with self._health_lock:
            self._consec_device_failures = 0
            recovered = self._health == DEGRADED
            if recovered:
                self._health = HEALTHY
                self.stats.fsm_recoveries += 1
                self._c_recoveries.inc("-", "-")
                self._g_backend_state.set("-", "-", 0.0)
        if recovered:
            logger().info(
                "solver backend recovered; device path re-enabled"
            )

    def _stale(self) -> bool:
        """True on a worker thread superseded by a watchdog restart: its
        late results are discarded (the watchdog already answered its
        requests from numpy)."""
        gen = getattr(self._tls, "gen", None)
        return gen is not None and gen != self._worker_gen

    @contextlib.contextmanager
    def _device_section(self, requests: List[_Request], grace: float = 0.0):
        """Mark the worker busy inside a device call — the window the
        watchdog supervises. A hang here never raises, so supervision
        must come from outside the thread. `grace` shifts the busy mark
        forward (compile-miss dispatches get COMPILE_GRACE_S headroom)."""
        with self._cond:
            self._busy_since = self._clock() + grace
            self._busy_requests = list(requests)
        try:
            yield
        finally:
            with self._cond:
                if not self._stale():  # a restart already reset these
                    self._busy_since = None
                    self._busy_requests = []

    def _watchdog_loop(self) -> None:
        poll = max(0.05, self.watchdog_timeout_s / 4.0)
        while not self._closed:
            _time.sleep(poll)
            self._watchdog_check()

    def _watchdog_check(self) -> None:
        """One supervision pass: if the worker has been inside a device
        call longer than watchdog_timeout_s, supersede it (generation
        bump + fresh thread) and drain every request it held — the stuck
        batch AND the pipelined in-flight ones — to numpy."""
        stuck: List[_Request] = []
        with self._cond:
            busy = self._busy_since
            if busy is None or (
                self._clock() - busy <= self.watchdog_timeout_s
            ):
                return
            # everything the superseded worker holds: the stuck device
            # batch, pipelined in-flight batches, AND the not-yet-
            # dispatched groups of its current batch (already popped
            # from the queue — they live nowhere else). Dedup by
            # identity: a request can appear in more than one list.
            stuck.extend(self._busy_requests)
            for _out, live, _t in self._inflight:
                stuck.extend(live)
            stuck.extend(self._current_batch)
            self._inflight.clear()
            self._busy_since = None
            self._busy_requests = []
            self._current_batch = []
            self.stats.watchdog_restarts += 1
            self._c_watchdog.inc("-", "-")
            if not self._closed:
                self._spawn_worker()
        stuck = list({id(r): r for r in stuck}.values())
        logger().warning(
            "solver worker hung in a device call > %.1fs; restarted the "
            "worker and draining %d request(s) to numpy",
            self.watchdog_timeout_s, len(stuck),
        )
        recorder = default_flight_recorder()
        # one incident, one dump: when the hang also trips the FSM, the
        # fsm_trip auto-dump lands milliseconds later with THIS event
        # already in the ring, so dumping here too would write two
        # near-identical fsync'd files and burn two retention slots
        recorder.record(
            "watchdog_restart",
            trace_ids=self._trace_ids(stuck),
            subsystem="solver",
            requests=len(stuck),
            auto_dump=False,
        )
        tripped = self._record_device_failure(stuck)  # hang counts toward trip
        if not tripped:
            recorder.maybe_auto_dump("watchdog_restart")
        self._finish_from_numpy(stuck)

    # -- worker -----------------------------------------------------------

    def _spawn_worker(self) -> None:
        # called under self._cond; the new worker never pads into the
        # staging areas of the one it replaces (a superseded worker may
        # still be reading its own)
        self._worker_gen += 1
        self._staging = self._fresh_staging()
        self._worker = threading.Thread(
            target=self._run, args=(self._worker_gen,),
            name="solver-service", daemon=True,
        )
        self._worker.start()

    def _ensure_worker(self) -> None:
        # called under self._cond
        if self._worker is None or not self._worker.is_alive():
            self._spawn_worker()
        if self.watchdog_timeout_s > 0 and (
            self._watchdog is None or not self._watchdog.is_alive()
        ):
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="solver-watchdog",
                daemon=True,
            )
            self._watchdog.start()

    def _run(self, gen: Optional[int] = None) -> None:
        self._tls.gen = gen
        while not self._stale():
            if self._inflight:
                # a dispatch is computing on device: gather the NEXT
                # batch without blocking — if nothing is queued, the
                # useful work left is fetching the in-flight results
                batch = self._collect(block=False)
                if batch is None:
                    self._drain_inflight()
                    return
                if not batch:
                    self._drain_one()
                    continue
            else:
                batch = self._collect()
                if batch is None:
                    self._drain_inflight()
                    return
            with self._cond:
                self._current_batch = list(batch)
            groups: Dict[tuple, List[_Request]] = {}
            for request in batch:
                groups.setdefault(request.key, []).append(request)
            # lone = this batch is one compatibility group with nothing
            # else in flight to overlap — the shape the pipeline
            # chunk-split exists for (multi-group batches overlap
            # naturally: group k+1 dispatches while group k computes)
            for key, requests in groups.items():
                self._dispatch_group(
                    key, requests, lone=len(groups) == 1
                )
            with self._cond:
                if not self._stale():  # a restart already drained it
                    self._current_batch = []
            if not self._queue:
                # nothing else waiting: complete in-flight work now
                # rather than holding a lone batch's results hostage to
                # traffic that may never come
                self._drain_inflight()
            self.publish_gauges()

    def _collect(self, block: bool = True) -> Optional[List[_Request]]:
        """Gather one batch: block for the first request (block=True),
        then hold the ADAPTIVE coalescing window open, gathering up to
        max_batch requests. The window is 0 — dispatch immediately —
        when the queue was empty behind the first request and recent
        batches were singletons; it widens to window_s while traffic is
        concurrent. None = closed+drained; [] = block=False and idle."""
        with self._cond:
            while not self._queue:
                if self._closed:
                    return None
                if not block:
                    return []
                self._cond.wait()
            batch = [self._queue.popleft()]
            backlog = len(self._queue)
        window = self._effective_window(backlog)
        self._window_now_s = window
        if window > 0:
            self._gather_window(batch, window)
        else:
            self.stats.immediate_dispatches += 1
            with self._cond:
                while self._queue and len(batch) < self.max_batch:
                    batch.append(self._queue.popleft())
        with self._cond:
            self._drain_batch_tail(batch)
            self._g_queue.set("-", "-", float(len(self._queue)))
        # worker-only EWMA of observed concurrency: decays back to 1 a
        # few idle batches after a burst, so steady singleton traffic
        # keeps dispatching immediately
        self._load = (
            (1 - _LOAD_ALPHA) * self._load + _LOAD_ALPHA * len(batch)
        )
        return batch

    def _gather_window(self, batch: List[_Request], window: float) -> None:
        """Hold the coalescing window open, folding arrivals into the
        batch until it fills or the window closes."""
        window_end = self._clock() + window
        while len(batch) < self.max_batch:
            remaining = window_end - self._clock()
            if remaining <= 0:
                return
            with self._cond:
                if not self._queue:
                    self._cond.wait(timeout=remaining)
                while self._queue and len(batch) < self.max_batch:
                    batch.append(self._queue.popleft())

    def _effective_window(self, backlog: int) -> float:
        """The gather window for this batch: 0 (dispatch now) on an idle
        queue, window_s while concurrency is observed — either directly
        (requests already queued behind the head) or recently (the
        batch-size EWMA is still above the idle threshold)."""
        if not self.adaptive_window:
            return self.window_s
        if backlog > 0 or self._load >= _LOAD_IDLE:
            return self.window_s
        return 0.0

    def _drain_batch_tail(self, batch: List[_Request]) -> None:
        """consolidate() batches are enqueued atomically (contiguous in
        the deque) and must ride one dispatch: keep draining past
        max_batch while the queue head continues a batch already
        partially collected. Called under self._cond."""
        taken = {
            r.coalesce_id for r in batch if r.coalesce_id is not None
        }
        while (
            self._queue
            and self._queue[0].coalesce_id is not None
            and self._queue[0].coalesce_id in taken
        ):
            batch.append(self._queue.popleft())

    def _filter_live(self, requests: List[_Request]) -> List[_Request]:
        """Drop abandoned and queue-expired requests; the survivors are
        the batch that actually dispatches."""
        now = self._clock()
        live: List[_Request] = []
        for request in requests:
            if request.abandoned:
                # caller already gave up (counted there) — but the
                # caller-side timeout never calls finish(), so close
                # the trace span HERE or the timed-out request (the
                # most diagnosis-worthy kind) vanishes from the export
                if request.span is not None:
                    request.span.close(ok=False, abandoned=True)
                continue
            if request.deadline is not None and now > request.deadline:
                self._on_expired(request)
                request.finish(
                    error=SolverTimeout("deadline expired in queue")
                )
                continue
            self._record_stage("queue_wait", now - request.enqueued_at)
            live.append(request)
        return live

    @staticmethod
    def _shard_strategy(key: tuple) -> Optional[str]:
        """The shard strategy marker of a request key, or None for a
        single-device key. Sharded bin-pack keys: (shape, buckets,
        backend, presence, cshape, "shard"|"vmap_shard", extents).
        Sharded forecast/preempt keys: ("forecast"|"preempt", shape-ish,
        backend, "shard", extents)."""
        if key[0] in ("forecast", "preempt"):
            return (
                "shard" if len(key) > 3 and key[3] == "shard" else None
            )
        if len(key) > 6 and key[5] in ("shard", "vmap_shard"):
            return key[5]
        return None

    @staticmethod
    def _single_device_key(key: tuple) -> tuple:
        """The single-device key a sharded group degrades to — same
        bucket shape/buckets/backend/presence, mesh routing stripped
        ("vmap_shard" keeps the vectorized consolidate program;
        forecast/preempt keys drop their trailing shard marker)."""
        if key[0] in ("forecast", "preempt"):
            return key[:3]
        if key[5] == "vmap_shard":
            return key[:5] + ("vmap",)
        return key[:5]

    def _dispatch_group(
        self, key: tuple, requests: List[_Request], lone: bool = False
    ) -> None:
        live = self._filter_live(requests)
        if not live:
            return
        self.stats.last_coalesce_factor = len(live)
        self.stats.coalesced_batches += len(live) > 1
        self._g_coalesce.set("-", "-", float(len(live)))
        self._h_coalesce.observe("-", "-", float(len(live)))
        device_path = key[2] != "numpy"
        if device_path and not self._device_allowed():
            # FSM degraded, not this window's probe: serve the whole
            # batch from numpy without attempting the sick device
            self._finish_from_numpy(live)
            return
        try:
            self._solve_group(key, live, lone=lone)
            return
        except Exception as exc:  # noqa: BLE001 — device failure path
            error: BaseException = exc
            if device_path and not self._stale():
                self._record_device_failure(live)
        if self._shard_strategy(key) is not None and not self._stale():
            error = self._retry_unsharded(key, live, error)
            if error is None:
                return
        logger().warning(
            "solver device path failed (%s: %s); degrading %d "
            "request(s) to numpy",
            type(error).__name__, error, len(live),
        )
        self._finish_from_numpy(live)

    def _retry_unsharded(
        self, key: tuple, live: List[_Request], error: BaseException
    ) -> Optional[BaseException]:
        """The sharded rung of the degradation ladder
        (docs/solver-service.md): shard -> single-device BEFORE numpy —
        the mesh failing is not the device failing, so the same program
        re-runs unpartitioned. One shard failure also stops routing NEW
        traffic to the mesh (reset_caches, the recovery-boot seam,
        re-arms it). Returns None on success, else the error the numpy
        rung should report."""
        self.stats.shard_fallbacks += 1
        self._shard_broken = True
        logger().warning(
            "sharded dispatch failed (%s: %s); retrying %d request(s) "
            "on the single-device path and disabling the shard route",
            type(error).__name__, error, len(live),
        )
        default_flight_recorder().record(
            "shard_fallback",
            trace_ids=self._trace_ids(live),
            error=type(error).__name__,
            requests=len(live),
        )
        try:
            self._solve_group(self._single_device_key(key), live)
            return None
        except Exception as single_error:  # noqa: BLE001
            if not self._stale():
                self._record_device_failure(live)
            return single_error

    def _dispatch_span(self, name: str, live: List[_Request], **args):
        """The coalesced dispatch span (observability.tracing): opened
        on the worker thread, parented into the FIRST rider's trace for
        correlation, and LINKING every request span that rode the
        dispatch — the one-to-many join the coalescing queue otherwise
        erases (trace-export renders the links as Perfetto flow
        arrows)."""
        tracer = default_tracer()
        if not tracer.enabled:
            return tracer.span(name)  # the no-op (or profiler-only) span
        spans = [r.span for r in live if r.span is not None]
        return tracer.span(
            name,
            parent=spans[0] if spans else None,
            links=spans,
            n_requests=len(live),
            **args,
        )

    def _finish_from_numpy(self, live: List[_Request]) -> None:
        for request in live:
            if request.event.is_set():
                # already answered (watchdog drain vs. stale-worker
                # unwind): don't burn a redundant host solve on a
                # result finish() would discard anyway
                continue
            try:
                request.finish(
                    result=self._numpy_fallback(
                        request.inputs, request.buckets
                    ),
                    degraded=True,
                )
            except Exception as numpy_error:  # noqa: BLE001
                request.finish(error=numpy_error, degraded=True)

    def _solve_group(
        self, key: tuple, live: List[_Request], lone: bool = False
    ) -> None:
        # forecast and preempt ride the mesh too (PR 13 closed the
        # PR 8 "no sharded parity pin" caveat): a request whose cell
        # count reached shard_threshold carries a ("shard", extents)
        # marker and its group dispatches mesh-partitioned — with the
        # same shard -> single-device -> numpy ladder as bin-packs
        # (parity pinned in tests/test_parallel.py). Below threshold —
        # the common S series x T history / C candidates x V victims
        # fleet — nothing changes.
        if key[0] == "forecast":
            self._forecast_group(key, live)
            return
        if key[0] == "preempt":
            self._preempt_group(key, live)
            return
        shape, buckets, backend = key[0], key[1], key[2]
        if backend == "numpy":
            # host program: no device dispatch, no padding (the sparse
            # numpy stages don't compile, so shape stability buys
            # nothing), and no fallback counting — this is the REQUESTED
            # backend, not a degradation. Completes inline, so in-flight
            # device work drains first to keep completion ordered. It is
            # still ONE coalesced group answer, so the dispatch span
            # links its riders like the device paths do.
            self._drain_inflight()
            with self._dispatch_span(
                "solver.dispatch", live, strategy="host"
            ):
                for request in live:
                    t0 = _time.perf_counter()
                    request.finish(
                        result=self._numpy_solve(request.inputs, buckets)
                    )
                    self._record_stage(
                        "dispatch", _time.perf_counter() - t0
                    )
            return
        # the device-dispatch injection point (faults/registry.py): an
        # error plan here exercises the per-request numpy fallback and
        # the FSM trip; a hang plan blocks inside a supervised device
        # section, exercising the watchdog restart + drain
        with self._device_section(live):
            inject("solver.dispatch")
        if self.device_solver is not None:
            self._drain_inflight()
            with self._device_section(live):
                for request in live:
                    t0 = _time.perf_counter()
                    out = self.device_solver(
                        request.inputs, buckets=buckets, backend=backend
                    )
                    self._record_stage(
                        "dispatch", _time.perf_counter() - t0
                    )
                    self._count_dispatch()
                    request.finish(result=out)
            self._record_device_success()
            return
        if backend == "pallas":
            # the fused Mosaic kernel has no batched entry; requests
            # still share the bucketed shapes (compile stability) and
            # the single worker (bounded device pressure). Supervision
            # happens per request inside (_solve_pallas), where the
            # compile-miss grace is known.
            self._drain_inflight()
            self._solve_pallas(shape, buckets, live)
            self._record_device_success()
            return
        if self._shard_strategy(key) is not None:
            # mesh-partitioned dispatch: completes INLINE — fleet-scale
            # operands must not double-buffer (two in-flight 10^9-cell
            # batches would double peak memory), and a synchronous
            # failure is what lets _dispatch_group walk the
            # shard -> single-device -> numpy ladder. Success is
            # recorded INSIDE (_sharded_xla), after its stale check —
            # a watchdog-superseded dispatch completing late must not
            # erase the failure the watchdog just counted.
            self._drain_inflight()
            self._sharded_xla(shape, buckets, live, key)
            return
        self._begin_pipelined_xla(
            shape, buckets, live,
            strategy=key[5] if len(key) > 5 else "map", lone=lone,
        )

    def _begin_pipelined_xla(
        self, shape, buckets: int, live: List[_Request],
        strategy: str, lone: bool,
    ) -> None:
        """Dispatch a map/vmap group, SPLITTING a lone map batch into
        pipeline chunks — the dead-pipeline fix: a lone coalesced batch
        with nothing in flight would dispatch once and drain immediately
        (closed-loop callers can't enqueue the next round until this one
        answers), so the double buffer never engaged. Chunking gives it
        the overlap: chunk k+1's pad/stack/dispatch runs while chunk k
        computes, and chunk k's fetch/scatter overlaps chunk k+1's
        compute. lax.map scans the batch SERIALLY on device, so halving
        a batch costs no device efficiency — only the vmap (consolidate)
        family, whose vectorization is the point, never splits."""
        split = (
            strategy == "map"
            and lone
            and self.pipeline_depth > 0
            and not self._inflight
            and len(live) >= _PIPELINE_SPLIT_MIN
        )
        if not split:
            self._begin_batched_xla(
                shape, buckets, live, strategy=strategy
            )
            return
        n_chunks = min(self.pipeline_depth + 1, len(live) // 2)
        size = -(-len(live) // n_chunks)
        self.stats.pipeline_splits += 1
        for start in range(0, len(live), size):
            self._begin_batched_xla(
                shape, buckets, live[start:start + size],
                strategy=strategy,
            )

    def _forecast_group(  # lint: allow-complexity — one guard per shard rung (route/pad/place/count), numpy short-circuit
        self, key: tuple, live: List[_Request]
    ) -> None:
        """One coalesced forecast dispatch: same-T-bucket requests are
        concatenated along the series axis, padded up the series ladder,
        and answered by ONE compiled program; results slice back per
        request. backend == "numpy" serves the mirror inline (the
        REQUESTED backend, not a degradation). Device failures raise to
        _dispatch_group, which degrades the batch to numpy and feeds the
        backend-health FSM like any other device path."""
        from karpenter_tpu.forecast import models as FM

        t_bucket, backend = key[1], key[2]
        # completes inline (no pipelining: forecast batches are small
        # and latency-bound), so drain in-flight bin-pack work first to
        # keep completion ordered
        self._drain_inflight()
        if backend == "numpy":
            for request in live:
                t0 = _time.perf_counter()
                request.finish(result=FM.forecast_numpy(request.inputs))
                self._record_stage("dispatch", _time.perf_counter() - t0)
            return
        shard = self._shard_strategy(key) is not None
        mesh = self._shard_mesh() if shard else None
        if shard and mesh is None:
            raise RuntimeError(
                "shard mesh unavailable for a shard-routed forecast"
            )
        t0 = _time.perf_counter()
        sizes = [request.n_pods for request in live]
        s_bucket = bucket_up(sum(sizes), FORECAST_S_FLOOR)
        if shard:
            # grow the series bucket to the mesh-row extent GSPMD
            # requires; padding series are all-invalid and sliced off
            from karpenter_tpu.utils.functional import pad_to_multiple

            s_bucket = pad_to_multiple(s_bucket, key[4][0])
        stacked = FM.concat_forecast_inputs(
            [request.inputs for request in live], s_bucket
        )
        self._record_stage("pad", _time.perf_counter() - t0)
        cache_key = ("forecast", s_bucket, t_bucket, backend)
        if shard:
            cache_key += ("shard", key[4])
        fn, fresh = self._forecast_compiled(cache_key)
        import jax

        t0 = _time.perf_counter()
        with self._dispatch_span(
            "solver.dispatch.forecast" + (".shard" if shard else ""),
            live, **self._span_cost_args(cache_key),
        ):
            with self._device_section(
                live, grace=COMPILE_GRACE_S if fresh else 0.0
            ):
                with default_tracer().span("solver.forecast"):
                    # the forecast-path fault-injection point
                    # (faults/registry.py, docs/resilience.md): an error
                    # plan exercises the numpy degradation + FSM, a hang
                    # plan the watchdog drain
                    inject("forecast.predict")
                    if shard:
                        from karpenter_tpu.parallel.mesh import (
                            forecast_shardings,
                        )

                        stacked = self._upload(
                            stacked, forecast_shardings(mesh)
                        )
                    out = fn(stacked)
                    jax.block_until_ready(out)
        if self._stale():
            return  # watchdog already answered these from numpy
        if fresh:
            self._note_compile(
                "forecast", cache_key, _time.perf_counter() - t0, live,
                extents=key[4] if shard else None,
                cost_fn=(
                    self._cost_thunk(fn, (stacked,), {})
                    if self._introspect is not None
                    and self._introspect.enabled else None
                ),
            )
        self._record_stage("dispatch", _time.perf_counter() - t0)
        self._count_dispatch()
        self.stats.forecast_dispatches += 1
        if shard:
            self.stats.shard_dispatches += 1
        t0 = _time.perf_counter()
        offset = 0
        for request, size in zip(live, sizes):
            request.finish(
                result=FM.slice_forecast_outputs(
                    out, offset, offset + size
                )
            )
            offset += size
        self._record_stage("scatter", _time.perf_counter() - t0)
        self._record_device_success()

    def _preempt_group(  # lint: allow-complexity — one guard per shard rung (route/pad/place/count), numpy short-circuit
        self, key: tuple, live: List[_Request]
    ) -> None:
        """Eviction-planning dispatches: each request is already a
        whole-fleet batched problem (the candidate axis IS the batch —
        ops/preempt.py plans candidates data-parallel), so same-key
        requests dispatch one after another through one compiled
        program. Completes inline (latency-bound, like forecasts), so
        in-flight bin-pack work drains first to keep completion
        ordered. Device failures raise to _dispatch_group, which
        degrades the batch to the bit-identical numpy mirror and feeds
        the backend-health FSM like any other device path."""
        from karpenter_tpu.ops import preempt as PK

        shape, backend = key[1], key[2]
        self._drain_inflight()
        if backend == "numpy":
            # the REQUESTED backend, not a degradation: no padding (the
            # host program doesn't compile), no fallback counting
            for request in live:
                t0 = _time.perf_counter()
                request.finish(result=PK.preempt_numpy(request.inputs))
                self._record_stage("dispatch", _time.perf_counter() - t0)
            return
        import jax

        shard = self._shard_strategy(key) is not None
        mesh = self._shard_mesh() if shard else None
        if shard and mesh is None:
            raise RuntimeError(
                "shard mesh unavailable for a shard-routed eviction plan"
            )
        shardings = None
        if shard:
            # grow the CANDIDATE axis (the data-parallel one the mesh
            # rows shard) to the mesh extent; padding candidates are
            # invalid + all-forbidden, cropped off below
            from karpenter_tpu.parallel.mesh import preempt_shardings
            from karpenter_tpu.utils.functional import pad_to_multiple

            c, n, r, v = shape
            shape = (pad_to_multiple(c, key[4][0]), n, r, v)
            shardings = preempt_shardings(mesh)
        cache_key = ("preempt", shape, backend)
        if shard:
            cache_key += ("shard", key[4])
        fresh = self._count_compile(cache_key)
        grace = COMPILE_GRACE_S if fresh else 0.0
        for request in live:
            t0 = _time.perf_counter()
            padded = pad_preempt_inputs(request.inputs, shape)
            self._record_stage("pad", _time.perf_counter() - t0)
            t0 = _time.perf_counter()
            with self._dispatch_span(
                "solver.dispatch.preempt" + (".shard" if shard else ""),
                [request], **self._span_cost_args(cache_key),
            ):
                with self._device_section([request], grace=grace):
                    with default_tracer().span("solver.preempt"):
                        # the preempt-path fault-injection point
                        # (faults/registry.py, docs/resilience.md): an
                        # error plan exercises the numpy degradation +
                        # FSM, a hang plan the watchdog drain
                        inject("preempt.plan")
                        placed = (
                            self._upload(padded, shardings)
                            if shard
                            else jax.device_put(padded)
                        )
                        out = PK.preempt_plan(placed)
                        jax.block_until_ready(out)
            grace = 0.0  # only the first dispatch of the batch compiles
            if self._stale():
                return  # watchdog already answered these from numpy
            if fresh:
                fresh = False  # only the first dispatch paid the compile
                self._note_compile(
                    "preempt", cache_key, _time.perf_counter() - t0,
                    [request], extents=key[4] if shard else None,
                    cost_fn=(
                        self._cost_thunk(PK.preempt_plan, (padded,), {})
                        if self._introspect is not None
                        and self._introspect.enabled else None
                    ),
                )
            self._record_stage("dispatch", _time.perf_counter() - t0)
            self._count_dispatch()
            self.stats.preempt_dispatches += 1
            if shard:
                self.stats.shard_dispatches += 1
            t0 = _time.perf_counter()
            host = PK.PreemptOutputs(
                chosen_node=np.asarray(out.chosen_node),
                evict_count=np.asarray(out.evict_count),
                evict_mask=np.asarray(out.evict_mask),
                unplaceable=np.asarray(out.unplaceable),
            )
            request.finish(
                result=crop_preempt_outputs(
                    host, request.n_pods, request.n_groups
                )
            )
            self._record_stage("scatter", _time.perf_counter() - t0)
        self._record_device_success()

    def _forecast_compiled(self, cache_key: tuple):
        """(compiled batched forecast program, fresh) — the forecast
        face of the shared compile cache (same hit/miss counters)."""
        fresh = self._count_compile(cache_key)
        fn = self._compiled.get(cache_key)
        if fn is None:
            import jax

            from karpenter_tpu.forecast import models as FM

            fn = self._compiled[cache_key] = jax.jit(FM.forecast)
        return fn, fresh

    def _solve_pallas(self, shape, buckets: int, live: List[_Request]) -> None:
        """The single-chip Mosaic solve, one request at a time, in the
        XLA path's stages and spans, all in the request's trace: pad
        (bucket padding and the operand fold), dispatch (transfer to
        block_until_ready) enclosing upload, then scatter (the fetch and
        crop). Operands the one-slot identity memo (ops/binpack) already
        holds record an upload of 0.0."""
        import jax

        from karpenter_tpu.ops import binpack as B

        cache_key = ("pallas", shape, buckets, live[0].key[3])
        fresh = self._count_compile(cache_key)
        grace = COMPILE_GRACE_S if fresh else 0.0
        for request in live:
            # the pad reuses the previous request's host staging area:
            # that request's transfer and solve were waited on
            # (block_until_ready) and its result scattered before here
            with self.track("pad", "solver.pad", request.span) as span:
                staged_inputs, staged = self._staged_pad(
                    request.inputs, shape
                )
                if span is not None:
                    span.args["staged"] = staged
                padded, kernel = B.fold_for_pallas(staged_inputs)
            t0 = _time.perf_counter()
            with self._dispatch_span("solver.dispatch", [request]):
                with self._device_section([request], grace=grace):
                    operands, held = B.device_resident(
                        padded, put=lambda tree: self._upload(tree, wait=False)
                    )
                    if held:
                        self._record_stage("upload", 0.0)
                    out = B.solve_resident(operands, buckets, kernel)
                    jax.block_until_ready(out)
            grace = 0.0  # only the first call of the batch compiles
            if fresh:
                # no jit handle to lower here (B.solve_resident resolves the
                # fused Mosaic kernel internally), so the ledger row
                # carries the wall time without cost attribution; the
                # helper's stale check keeps a watchdog-superseded
                # worker's discarded dispatch out of the ledger
                self._note_fresh_compile(
                    fresh, "solve", cache_key, t0, [request]
                )
                fresh = False
            self._record_stage("dispatch", _time.perf_counter() - t0)
            self._count_dispatch()
            with self.track("scatter", "solver.scatter", request.span):
                result = self._crop_host(out, request)
            request.finish(result=result)

    def _fresh_staging(self) -> tuple:
        return (self._worker_gen, collections.OrderedDict())

    def _staged_pad(self, inputs: BinPackInputs, shape):
        """(inputs padded to `shape`, "reuse" | "alloc" | None): the
        one-chip Mosaic path's pad, into a host staging area this worker
        keeps for the staging key (bucketing.staging_key), at most
        _STAGING_AREAS of them. None where nothing is staged: inputs
        already at the bucket pass through, and a worker the watchdog
        superseded pads into fresh arrays. Only this path stages: the
        batched, sharded and pipelined paths hold several padded sets at
        once, or free them after a dispatch they do not wait on."""
        if is_bucketed(inputs, shape):
            return inputs, None
        owner, areas = self._staging
        if owner != getattr(self._tls, "gen", None):
            return pad_to_bucket(inputs, shape), None
        key = staging_key(inputs, shape)
        area = areas.pop(key, None)
        if area is None:
            area, staged = PadStaging(), "alloc"
            self.stats.pad_staging_allocs += 1
            _keep_freed_heap()
            if len(areas) >= _STAGING_AREAS:
                areas.popitem(last=False)
        else:
            staged = "reuse"
            self.stats.pad_staging_reuses += 1
        areas[key] = area
        self._c_pad_staging.inc(staged, "-")
        return pad_to_bucket(inputs, shape, out=area), staged

    def _begin_batched_xla(
        self, shape, buckets: int, live: List[_Request],
        strategy: str = "map",
    ) -> None:
        """The coalesced path: pad each request to the shape bucket,
        stack along a new leading axis, pad the batch axis up its own
        ladder, dispatch ONE compiled program — and DON'T wait for it.
        The dispatch joins the in-flight pipeline; its host-side fetch +
        crop + scatter are paid by _drain_one, which the worker calls
        after dispatching the NEXT batch (overlap) or when the queue
        goes idle (no result is ever held hostage to future traffic).

        strategy="map" (plain solve() traffic) scans the batch with
        lax.map: the per-item program inside the scan is the same HLO as
        a direct binpack call on the same (padded) shapes, so outputs
        match direct calls element for element, and peak memory stays at
        one item's working set (coalesced 100k-pod ticks must not pay a
        batch× amplification). strategy="vmap" (consolidate() batches)
        vectorizes across the batch instead — candidates are cluster-
        scale operands, so the amplification is trivial and the batched
        throughput gain is the whole point.

        The stacked operands are device_put FIRST and the compiled
        program donates them (donate_argnums): on backends with real
        donation support the batch buffers are reused instead of
        reallocated every dispatch; where donation is unimplemented it
        is a no-op with identical outputs (pinned by the donation-parity
        test).

        Singleton map groups first consult the DEVICE-RESIDENT fleet
        state (solver/resident.py): an identity hit or changed-row
        scatter skips the pad/stack/upload entirely, and the dispatch
        compiles the donate=False family so the resident buffers
        survive the solve."""
        resident = self._resident_stack(shape, live, strategy)
        if resident is not None:
            stacked, n_batch, donate = resident, 1, False
        else:
            stacked, n_batch = self._stack_group(shape, live)
            donate = self._donation_supported()
        cache_key = (
            "xla", shape, n_batch, buckets, live[0].key[3],
            live[0].key[4], strategy,
        )
        fn, fresh = self._compiled_for(cache_key, donate=donate)
        # shape capture must precede the dispatch: donated operand
        # buffers are deleted by the time the thunk could run
        cost_fn = self._fresh_cost_thunk(fresh, fn, stacked, buckets)
        t0 = _time.perf_counter()
        with self._dispatch_span(
            "solver.dispatch", live, strategy=strategy, batch=n_batch,
            **self._span_cost_args(cache_key),
        ):
            with self._device_section(
                live, grace=COMPILE_GRACE_S if fresh else 0.0
            ):
                if resident is None:
                    stacked = self._upload(stacked)
                out = fn(stacked, buckets)
        self._note_fresh_compile(
            fresh, "solve", cache_key, t0, live, cost_fn=cost_fn
        )
        if self._stale():
            # superseded by a watchdog restart while dispatching: the
            # watchdog already answered these requests from numpy —
            # discard the late device results
            return
        if self._inflight:
            self.stats.pipeline_overlaps += 1
        self._inflight.append((out, live, t0))
        self._last_pipeline_depth = len(self._inflight)
        self._count_dispatch()
        # cap in-flight work at pipeline_depth, draining OLDEST first:
        # with depth 1 this is classic double buffering (batch k's fetch
        # is paid here, after batch k+1's dispatch); depth 0 restores
        # the serial dispatch→wait→scatter loop
        while len(self._inflight) > max(0, self.pipeline_depth):
            self._drain_one()

    def _stack_group(self, shape, live: List[_Request]):
        """(stacked operands, batch bucket): pad each request to the
        shape bucket, stack along a new leading axis, pad the batch
        axis up its own ladder — batch padding replicates the first
        request, the cheapest valid filler (its outputs are computed
        and discarded). Shared by the single-device and sharded
        dispatch paths; records the "pad" stage."""
        with self.track("pad", "solver.pad", _first_span(live)):
            padded = [pad_to_bucket(r.inputs, shape) for r in live]
            n_batch = bucket_up(len(padded), 1)
            padded.extend(padded[:1] * (n_batch - len(padded)))
            stacked = _stack_inputs(padded)
        return stacked, n_batch

    def _resident_stack(
        self, shape, live: List[_Request], strategy: str,
        shardings=None, extents: Optional[tuple] = None,
    ):
        """The device-resident serve path for a SINGLETON map-strategy
        group: returns the resident stacked operands (batch axis 1), or
        None when residency does not apply — a coalesced
        multi-request batch (those stacks are ephemeral by nature), the
        vmap consolidate family, or an out-of-process device solver.

        kind accounting: a "hit" records a 0.0 upload sample (nothing
        crossed the link, so a steady fleet's upload p50 falls to ~0),
        a "scatter" records the scatter wall time under the
        resident_scatter stage + gauge (its host->device traffic is the
        changed-row blocks inside the jitted scatter), and a "rebuild"
        billed its full upload through the normal _upload hook."""
        if (
            strategy != "map"
            or len(live) != 1
            or self.device_solver is not None
        ):
            return None
        request = live[0]
        mode = ("single",) if extents is None else ("shard", extents)
        t0 = _time.perf_counter()
        try:
            stacked, kind = self._resident.obtain(
                request.inputs, shape, mode,
                lambda tree: self._upload(tree, shardings, request.span),
                tenant=request.tenant,
                now=self._clock(),
            )
        except Exception as error:  # noqa: BLE001 — optimization layer
            logger().warning(
                "resident fleet state unavailable (%s: %s); "
                "re-uploading the full operand stack",
                type(error).__name__, error,
            )
            return None
        if kind == "hit":
            self.stats.resident_hits += 1
            self._record_stage("upload", 0.0)
        elif kind == "scatter":
            self.stats.resident_scatters += 1
            elapsed = _time.perf_counter() - t0
            self._record_stage("resident_scatter", elapsed)
            self._g_resident_scatter.set("-", "-", elapsed * 1e3)
        else:
            self.stats.resident_rebuilds += 1
            self._c_resident_rebuilds.inc("-", "-")
        self.stats.resident_drops = self._resident.drops
        return stacked

    def _upload(self, stacked, shardings=None, parent=None, wait=True):
        """device_put the stack (with NamedShardings on the sharded
        path) and record the ISOLATED host->device transfer cost (the
        device-resident-state target, ROADMAP item 4): compute waits on
        the transfer either way, so the sync point only moves the wait
        to where it can be measured. wait=False (the one-chip Mosaic
        path) times device_put alone, the host side of the transfer:
        there the sync point before the launch cost 9% of the solves a
        window on the v5e (PERF.md)."""
        import jax

        with self.track("upload", "solver.upload", parent):
            stacked = (
                jax.device_put(stacked)
                if shardings is None
                else jax.device_put(stacked, shardings)
            )
            if wait:
                jax.block_until_ready(stacked)
        return stacked

    def _sharded_xla(
        self, shape, buckets: int, live: List[_Request], key: tuple
    ) -> None:
        """The sharded dispatch strategy (docs/solver-service.md
        "Sharded dispatch"): the same batched program the single-device
        path compiles, partitioned over the pods x groups mesh by GSPMD.

        Each request pads up the normal bucket ladder GROWN to
        mesh-divisible pod/group extents (mesh_aligned_shape — padding
        stays semantics-preserving: extra rows invalid, extra columns
        infeasible), the stack is device_put with NamedShardings (pod
        axis over mesh rows, group axis over mesh columns, batch axis
        replicated), and the jitted lax.map/vmap program runs with its
        feasibility matmuls as local blocks and one cross-shard
        reduction per aggregate. Results merge host-side: one fetch per
        batch, then the standard per-request crop — the caller-visible
        slices carry no mesh padding. Outputs are BIT-IDENTICAL to the
        single-device program on integer fields (the padding argument of
        solver/bucketing.py; property-pinned in tests/test_parallel.py
        and tests/test_solver_service.py); the f32 lp_bound may differ
        by the reduction-order ulp the numpy-parity contract already
        carves out."""
        import jax

        from karpenter_tpu.parallel.mesh import stacked_binpack_shardings
        from karpenter_tpu.solver.bucketing import mesh_aligned_shape

        mesh = self._shard_mesh()
        if mesh is None:
            raise RuntimeError(
                "shard mesh unavailable for a shard-routed batch"
            )
        extents = key[6]
        strategy = "vmap" if key[5] == "vmap_shard" else "map"
        aligned = mesh_aligned_shape(shape, extents)
        shardings = stacked_binpack_shardings(mesh, key[3])
        # sharded residency: the resident entry holds the NamedSharding-
        # placed stack, so an unchanged/delta tick skips the full
        # sharded upload too; a threshold crossing (either direction)
        # misses on mode and rebuilds under the new placement
        resident = self._resident_stack(
            aligned, live, strategy, shardings=shardings, extents=extents
        )
        if resident is not None:
            stacked, n_batch, donate = resident, 1, False
        else:
            stacked, n_batch = self._stack_group(aligned, live)
            donate = self._donation_supported()
        cache_key = (
            "xla", aligned, n_batch, buckets, key[3], key[4], strategy,
            "shard", extents,
        )
        fn, fresh = self._compiled_for(cache_key, donate=donate)
        cost_fn = self._fresh_cost_thunk(fresh, fn, stacked, buckets)
        t0 = _time.perf_counter()
        with self._dispatch_span(
            "solver.dispatch.shard", live,
            strategy=strategy, devices=int(mesh.devices.size),
            **self._span_cost_args(cache_key),
        ):
            with self._device_section(
                live, grace=COMPILE_GRACE_S if fresh else 0.0
            ):
                with default_tracer().span("solver.shard"):
                    if resident is None:
                        stacked = self._upload(stacked, shardings)
                    out = fn(stacked, buckets)
                    jax.block_until_ready(out)
        if self._stale():
            return  # watchdog already answered these from numpy
        self._note_fresh_compile(
            fresh, "solve", cache_key, t0, live,
            extents=extents, cost_fn=cost_fn,
        )
        self._record_stage("dispatch", _time.perf_counter() - t0)
        self._count_dispatch()
        self.stats.shard_dispatches += 1
        with self.track("scatter", "solver.scatter", _first_span(live)):
            host = _fetch_outputs(out)
            for i, request in enumerate(live):
                request.finish(
                    result=crop_outputs(
                        _index_outputs(host, i),
                        request.n_pods, request.n_groups,
                    )
                )
        self._record_device_success()

    def _drain_one(self) -> None:
        """Complete the OLDEST in-flight dispatch: wait out the device,
        fetch once, crop + scatter per request. Device-path failures
        surface here (async dispatch defers them to the wait) and
        degrade each request to numpy exactly like a sync failure.

        Stage-metric caveat: under pipelining the "dispatch" sample is
        dispatch-to-drain WALL time — it includes whatever gather/pad
        work for the next batch overlapped the device compute, not pure
        device time. On an idle queue (drain immediately follows
        dispatch) it degenerates to the device latency; under load read
        it as "time a batch spent in flight" (docs/solver-service.md
        "Latency tuning")."""
        with self._cond:
            # pop under the lock: the watchdog clears _inflight when it
            # supersedes a hung worker, and a stale worker must not
            # steal the NEW worker's in-flight batches
            if self._stale() or not self._inflight:
                return
            out, live, t_dispatch = self._inflight.popleft()
        try:
            import jax

            with self._device_section(live):
                jax.block_until_ready(out)
            self._record_stage(
                "dispatch", _time.perf_counter() - t_dispatch
            )
            with self.track("scatter", "solver.scatter", _first_span(live)):
                host = _fetch_outputs(out)
                for i, request in enumerate(live):
                    request.finish(
                        result=self._crop_host(
                            _index_outputs(host, i), request
                        )
                    )
            self._record_device_success()
        except Exception as error:  # noqa: BLE001 — device failure path
            if not self._stale():
                self._record_device_failure(live)
            logger().warning(
                "solver device path failed in flight (%s: %s); degrading "
                "%d request(s) to numpy",
                type(error).__name__, error, len(live),
            )
            self._finish_from_numpy(live)

    def _drain_inflight(self) -> None:
        while self._inflight:
            self._drain_one()

    def _crop_host(self, out, request: _Request):
        return crop_outputs(
            _fetch_outputs(out), request.n_pods, request.n_groups
        )

    _donation_ok: Optional[bool] = None

    def _donation_supported(self) -> bool:
        """Donate only on the TPU, which aliases donated buffers; on CPU
        donation is a warning-per-executable no-op, so the worker
        compiles the non-donating family there. Outputs are identical
        either way — the donation-parity test compiles BOTH families
        explicitly regardless of backend."""
        if SolverService._donation_ok is None:
            import jax

            SolverService._donation_ok = jax.default_backend() == "tpu"
        return SolverService._donation_ok

    def _compiled_for(self, cache_key: tuple, donate: bool = False):
        """(compiled batched program, fresh) for the cache key — fresh
        means the key was a compile-cache MISS, so the first dispatch
        pays the compile (and gets the watchdog grace). donate=True
        marks the stacked operand pytree donated (donate_argnums=0): the
        worker device_puts the stack first, so backends with donation
        support recycle the batch buffers instead of allocating fresh
        ones every dispatch; outputs are identical either way (the
        donation-parity test pins it). The flag is part of the cache key
        so the two program families never alias."""
        cache_key = (*cache_key, "donate" if donate else "keep")
        fresh = self._count_compile(cache_key)
        fn = self._compiled.get(cache_key)
        if fn is not None:
            return fn, fresh

        from functools import partial

        import jax
        from jax import lax

        from karpenter_tpu.ops import binpack as B

        jit = partial(
            jax.jit,
            static_argnames=("buckets",),
            **({"donate_argnums": (0,)} if donate else {}),
        )
        if "vmap" in cache_key:

            @jit
            def batched(stacked, buckets):
                return jax.vmap(
                    lambda one: B.binpack(one, buckets=buckets)
                )(stacked)

        else:

            @jit
            def batched(stacked, buckets):
                return lax.map(
                    lambda one: B.binpack(one, buckets=buckets), stacked
                )

        self._compiled[cache_key] = batched
        return batched, fresh

    def _count_compile(self, cache_key: tuple) -> bool:
        """Count a compile-cache lookup; True = MISS (first sight of the
        key — the following dispatch pays a fresh compile and earns the
        watchdog's COMPILE_GRACE_S headroom)."""
        if cache_key in self._compile_seen:
            self.stats.compile_cache_hits += 1
            self._c_hits.inc("-", "-")
            return False
        self._compile_seen.add(cache_key)
        self.stats.compile_cache_misses += 1
        self._c_misses.inc("-", "-")
        return True

    def _count_fused_compile(self, cache_key: tuple) -> bool:
        """Fused-family compile-cache lookup. Unlike the solve family
        (whose compiled closures live on THIS service instance), the
        fused program rides the module-level fused_tick_jit whose
        compile cache is process-global — and disk-global under the
        persistent compile cache — so freshness is tracked in the
        module-level set: a rebooted service in a warm process pays no
        compile and must not ledger one (the restart contract
        --compile-cache-dir exists for). reset_caches() re-arms."""
        if cache_key in _FUSED_COMPILE_SEEN:
            self.stats.compile_cache_hits += 1
            self._c_hits.inc("-", "-")
            return False
        _FUSED_COMPILE_SEEN.add(cache_key)
        self.stats.compile_cache_misses += 1
        self._c_misses.inc("-", "-")
        return True

    def _count_dispatch(self) -> None:
        self.stats.dispatches += 1
        self._c_dispatch.inc("-", "-")

    def _on_expired(self, request: _Request) -> None:
        self.stats.deadline_expired += 1
        self._c_expired.inc("-", "-")

    def _numpy_fallback(self, inputs: BinPackInputs, buckets: int):
        self.stats.fallbacks += 1
        self._c_fallback.inc("-", "-")
        return self._numpy_solve(inputs, buckets)

    def _numpy_solve(self, inputs, buckets: int):
        from karpenter_tpu.forecast.models import (
            ForecastInputs,
            forecast_numpy,
        )

        if isinstance(inputs, ForecastInputs):
            # bit-identical mirror of the device kernel
            # (forecast/models.py parity contract)
            return forecast_numpy(inputs)
        from karpenter_tpu.ops.preempt import PreemptInputs, preempt_numpy

        if isinstance(inputs, PreemptInputs):
            # bit-identical mirror (ops/preempt.py parity contract)
            return preempt_numpy(inputs)
        from karpenter_tpu.ops.numpy_binpack import binpack_numpy

        return binpack_numpy(inputs, buckets=buckets)


def _first_span(live: List[_Request]):
    """The first rider's request span (None untraced): a coalesced
    batch's host stages join its trace, as its dispatch span does."""
    return next((r.span for r in live if r.span is not None), None)


def _stack_inputs(padded: List[BinPackInputs]) -> BinPackInputs:
    """Stack same-shaped requests along a new leading batch axis (host
    numpy; one device transfer happens inside the jitted dispatch).
    Optional operands are presence-consistent across the batch (the
    compatibility key includes the presence tuple)."""
    import dataclasses

    def stack(name: str):
        leaves = [getattr(p, name) for p in padded]
        if leaves[0] is None:
            return None
        return np.stack([np.asarray(leaf) for leaf in leaves], axis=0)

    return BinPackInputs(
        **{
            f.name: stack(f.name)
            for f in dataclasses.fields(BinPackInputs)
        }
    )


def _fetch_outputs(out):
    """Device outputs -> host numpy (one transfer per leaf, amortized
    over the whole coalesced batch). Host outputs pass through."""
    import dataclasses

    import jax

    if not isinstance(out.assigned, jax.Array):
        return out
    return dataclasses.replace(
        out,
        assigned=np.asarray(out.assigned),
        assigned_count=np.asarray(out.assigned_count),
        nodes_needed=np.asarray(out.nodes_needed),
        lp_bound=np.asarray(out.lp_bound),
        unschedulable=np.asarray(out.unschedulable),
    )


def _index_outputs(host, i: int):
    import dataclasses

    return dataclasses.replace(
        host,
        assigned=host.assigned[i],
        assigned_count=host.assigned_count[i],
        nodes_needed=host.nodes_needed[i],
        lp_bound=host.lp_bound[i],
        unschedulable=host.unschedulable[i],
    )


# -- pre-warm problem builders (prewarm docstring) ---------------------------


def _prewarm_solve_inputs() -> BinPackInputs:
    """1 pod x 1 group, weight present: pads up to the floor rung
    (256 x 8 x 4 x 32 x 64) inside the queue — the exact program a
    small fleet's first pendingCapacity solve compiles."""
    return BinPackInputs(
        pod_requests=np.ones((1, 1), np.float32),
        pod_valid=np.ones(1, bool),
        pod_intolerant=np.zeros((1, 1), bool),
        pod_required=np.zeros((1, 1), bool),
        group_allocatable=np.full((1, 1), 8.0, np.float32),
        group_taints=np.zeros((1, 1), bool),
        group_labels=np.zeros((1, 1), bool),
        pod_weight=np.ones(1, np.int32),
    )


def _prewarm_decide_inputs():
    """1 autoscaler x 1 metric at the decision kernel's smallest row
    bucket (ops/decision.pad_to) — the first fleet decide's program."""
    from karpenter_tpu.ops import decision as D

    n = D.pad_to(1)
    zeros_i = np.zeros(n, np.int32)
    zeros_f = np.zeros(n, np.float32)
    col_i = np.zeros((n, 1), np.int32)
    col_b = np.zeros((n, 1), bool)
    return D.DecisionInputs(
        metric_value=np.zeros((n, 1), np.float32),
        target_value=np.ones((n, 1), np.float32),
        target_type=np.full((n, 1), D.TYPE_AVERAGE_VALUE, np.int32),
        metric_valid=col_b.copy(),
        spec_replicas=zeros_i.copy(),
        status_replicas=zeros_i.copy(),
        min_replicas=zeros_i.copy(),
        max_replicas=np.ones(n, np.int32),
        up_window=zeros_i.copy(),
        down_window=zeros_i.copy(),
        up_policy=np.full(n, D.POLICY_MAX, np.int32),
        down_policy=np.full(n, D.POLICY_MAX, np.int32),
        last_scale_time=zeros_f.copy(),
        has_last_scale=np.zeros(n, bool),
        now=np.float32(0.0),
        up_ptype=col_i.copy(),
        up_pvalue=col_i.copy(),
        up_pperiod=np.ones((n, 1), np.int32),
        up_pvalid=col_b.copy(),
        down_ptype=col_i.copy(),
        down_pvalue=col_i.copy(),
        down_pperiod=np.ones((n, 1), np.int32),
        down_pvalid=col_b.copy(),
    )


def _prewarm_fused_inputs():
    """The full-presence fused tick (forecast + decide + cost engaged)
    at the smallest rung of every shape ladder: 1 series x 1 sample
    (padded to 8 x 16 inside fused_tick), the decide kernel's smallest
    row bucket, 1 metric column — the program a small fleet's first
    --fused-tick reconcile compiles."""
    from karpenter_tpu.forecast.models import ForecastInputs
    from karpenter_tpu.ops import fusedtick as FT

    dec = _prewarm_decide_inputs()
    n = int(dec.spec_replicas.shape[0])
    return FT.FusedTickInputs(
        decision=dec,
        forecast=ForecastInputs(
            values=np.zeros((1, 1), np.float32),
            valid=np.zeros((1, 1), bool),
            times=np.zeros((1, 1), np.float32),
            weights=np.ones((1, 1), np.float32),
            horizon=np.ones(1, np.float32),
            step_s=np.ones(1, np.float32),
            model=np.zeros(1, np.int32),
            season=np.zeros(1, np.int32),
            alpha=np.full(1, 0.5, np.float32),
            beta=np.full(1, 0.1, np.float32),
            gamma=np.full(1, 0.1, np.float32),
        ),
        series_row=np.zeros(1, np.int32),
        series_col=np.zeros(1, np.int32),
        series_need=np.full(1, 2, np.int32),
        series_blend=np.zeros(1, bool),
        ha_min=np.zeros(n, np.int32),
        ha_max=np.ones(n, np.int32),
        unit_cost=np.zeros(n, np.float32),
        slo_weight=np.zeros(n, np.float32),
        max_hourly_cost=np.zeros(n, np.float32),
        slo_valid=np.zeros(n, bool),
        slo_target=np.ones((n, 1), np.float32),
        observed=np.zeros((n, 1), np.float32),
        demand_base_valid=np.zeros((n, 1), bool),
        prior_point=np.zeros((n, 1), np.float32),
        prior_sigma2=np.zeros((n, 1), np.float32),
        prior_valid=np.zeros((n, 1), bool),
    )


# -- process-default service -------------------------------------------------
# simulate and the sidecar server share one service per process (the whole
# point: concurrent callers coalesce); the runtime builds its OWN instance
# so its gauges land in the runtime registry.

_default_lock = threading.Lock()
_default_service: Optional[SolverService] = None


def default_service() -> SolverService:
    global _default_service
    with _default_lock:
        if _default_service is None:
            _default_service = SolverService()
        return _default_service


def reset_default_service() -> None:
    """Close and drop the process-default service (test isolation)."""
    global _default_service
    with _default_lock:
        if _default_service is not None:
            _default_service.close()
            _default_service = None


def reset_default_service_caches() -> None:
    """Invalidate the process-default service's compile caches WITHOUT
    closing it — the recovery-boot seam for the one solver instance
    that can genuinely outlive an in-process controller restart
    (simulate/sidecar embedders share it across runtime incarnations)."""
    with _default_lock:
        if _default_service is not None:
            _default_service.reset_caches()
