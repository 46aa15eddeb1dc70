"""Device-resident fleet state: the canonical solve operands live ON
device and churn arrives as batched scatter updates.

Before this module, every dispatch re-uploaded the full padded operand
stack — PR 8 isolated that as the "upload" stage (~1.4 ms p50 at the
bench fleet, plus a ~0.06 ms device_put floor per tick per subsystem) —
even when the encoder's delta layer (SnapshotDeltaCache) had proven
that only a handful of rows changed since the last tick. BLITZSCALE
makes the same observation for model state (PAPERS.md: "Fast and Live
Large Model Autoscaling with O(1) Host Caching"): keep the hot state
resident where the compute is and ship only deltas, so per-decision
transfer cost stops scaling with fleet size.

ResidentFleetState is the SolverService-owned cache that closes the
loop:

  * each entry holds ONE caller's padded, batch-stacked BinPackInputs
    as live device buffers (NamedSharding-placed on the mesh-sharded
    path), keyed by the IDENTITY of the host inputs object the encoder
    produced — the same identity contract the encode memo and the delta
    layer already uphold (an unchanged dedup set returns the SAME
    object);
  * an identical inputs object re-dispatches against the resident
    buffers with ZERO host encode and ZERO upload;
  * a delta-encoded successor (encoder.resident_plan carries the
    changed-row indices the splice computed) applies as a batched
    scatter — `.at[:, rows].set(updates)` under jit — shipping only the
    changed rows over the transfer link; group operands are reused
    outright (the delta layer only engages when profiles are
    identity-equal);
  * anything else — unknown inputs, a bucket/mode change (the
    shard-threshold crossing), a dropped plan — REBUILDS: one full
    device_put, after which the entry is resident again.

Per-tenant resident slices fall out of the identity keying: every
tenant stack owns its own feed -> delta-cache identity chain, so each
occupies its own entry under the shared service (the LRU holds
MAX_ENTRIES chains).

Correctness posture (pinned by tests/test_resident.py):

  * the scatter result is BIT-IDENTICAL to a cold full upload by
    construction — unchanged rows are byte-equal between consecutive
    delta encodes (that is the delta layer's contract) and changed rows
    are written with exactly the new host bytes;
  * residency is an OPTIMIZATION LAYER only: any inconsistency (shape
    drift, a failed scatter, a missing plan) falls back to the full
    upload, never an error — the never-block contract;
  * resident buffers are NEVER donated to the solve program (the
    dispatch compiles the donate=False family) and scatters build new
    arrays functionally, so an in-flight pipelined dispatch keeps
    reading a consistent buffer;
  * the degradation ladder discards residency cleanly: a device-path
    failure or a recovery boot (SolverService.reset_caches) drops every
    entry, so a numpy-served or post-crash tick can never splice into
    stale device state.
"""

from __future__ import annotations

import collections
import threading
from typing import Optional, Tuple

import numpy as np

from karpenter_tpu.ops.binpack import BinPackInputs
from karpenter_tpu.solver.bucketing import bucket_up, pad_to_bucket

# scatter row counts pad up the shared {1, 1.5} x 2^k ladder so churn
# jitter (3 rows changed, then 7, then 4) reuses one compiled scatter
# program instead of compiling per distinct count
_ROW_FLOOR = 8

# operand leaves the delta layer splices row-wise (everything else in a
# delta-encoded successor is either reused by identity — the group
# arrays — or absent on the delta path; pod_weight has its own row set)
_ROW_LEAVES = ("pod_requests", "pod_valid", "pod_required", "pod_intolerant")


class _Entry:
    """One resident operand stack: the host inputs identity it mirrors,
    the (shape, mode) it was padded/stacked/placed for, and the device
    pytree the dispatch consumes. `tenant`/`created_at` are telemetry
    only (the introspection plane's per-entry byte accounting,
    observability/devicetelemetry.py) — neither participates in
    lookup."""

    __slots__ = (
        "host", "shape", "mode", "stacked", "nbytes", "rows",
        "tenant", "created_at",
    )

    def __init__(self, host, shape, mode, stacked, tenant=None,
                 created_at: float = 0.0):
        self.host = host
        self.shape = shape
        self.mode = mode
        self.stacked = stacked
        self.nbytes = _stack_bytes(stacked)
        self.rows = int(shape[0])
        self.tenant = tenant
        self.created_at = created_at


def _stack_bytes(stacked: BinPackInputs) -> int:
    import dataclasses

    total = 0
    for f in dataclasses.fields(BinPackInputs):
        leaf = getattr(stacked, f.name)
        if leaf is not None:
            total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


def _scatter_rows(buf, rows, updates):
    """Batch-stacked row scatter: buf [B, P, ...] <- updates [n, ...]
    at row indices `rows`, replicated across the batch axis (resident
    entries are singleton stacks, B == 1). Padded index slots repeat a
    real row with its own values, so duplicate indices always write
    identical bytes and the result is deterministic."""
    return buf.at[:, rows].set(updates[None])


def _scatter_stack(stacked, rows, u_req, u_val, u_reqd, u_int, u_w):
    """ONE fused scatter over every spliced row leaf (including the
    weight column) — a single compiled dispatch instead of one per
    leaf, which matters on backends where per-dispatch overhead rivals
    the copies. Rows whose bytes didn't actually change (the union-set
    over-approximation) are rewritten with identical values."""
    import dataclasses

    return dataclasses.replace(
        stacked,
        pod_requests=_scatter_rows(stacked.pod_requests, rows, u_req),
        pod_valid=_scatter_rows(stacked.pod_valid, rows, u_val),
        pod_required=_scatter_rows(stacked.pod_required, rows, u_reqd),
        pod_intolerant=_scatter_rows(stacked.pod_intolerant, rows, u_int),
        pod_weight=_scatter_rows(stacked.pod_weight, rows, u_w),
    )


class ResidentFleetState:
    """Bounded identity-keyed cache of device-resident operand stacks
    (module docstring). All mutation happens on the service worker
    thread; `drop_all` (recovery boot / ladder discard) may race it,
    so the entry table swaps whole under a lock and a worker mid-lookup
    keeps a consistent view."""

    MAX_ENTRIES = 8  # distinct caller identity chains (tenants) kept live

    def __init__(self, scatter: str = "auto"):
        self._lock = threading.Lock()
        # insertion-ordered LRU keyed by id(host inputs); entries hold
        # the host object strongly, so a live entry's id is never reused
        self._entries: "collections.OrderedDict[int, _Entry]" = (
            collections.OrderedDict()
        )
        self._stack_scatter_jit = None
        # the scatter rung's gate: "auto" engages it only where device
        # memory is a REAL accelerator behind a transfer link (TPU/GPU
        # — the backends with donation support). On CPU the "device"
        # memory IS host memory, so a copy-on-write scatter costs about
        # what the memcpy upload it avoids costs, and auto mode serves
        # identity hits + rebuilds instead. "always"/"never" force it
        # (tests).
        self.scatter = scatter
        self._scatter_auto: Optional[bool] = None
        # drop generation: drop_all bumps it, and a store whose serve
        # began under an older generation is DISCARDED — a recovery
        # boot racing the worker must not have its drop undone by an
        # entry built from pre-drop buffers
        self._generation = 0
        # plain-int observability, mirrored into the
        # karpenter_solver_resident_* gauges by the owning service
        self.hits = 0
        self.scatters = 0
        self.rebuilds = 0
        self.drops = 0
        self.last_scatter_rows = 0

    # -- bookkeeping -------------------------------------------------------

    def drop_all(self) -> None:
        """Discard every resident buffer (recovery boot, device-path
        failure, shard-route trip): the next dispatch rebuilds from a
        full upload."""
        with self._lock:
            if self._entries:
                self.drops += 1
            self._entries = collections.OrderedDict()
            self._generation += 1

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    def resident_rows(self) -> int:
        with self._lock:
            return sum(e.rows for e in self._entries.values())

    def entries(self, now: Optional[float] = None) -> list:
        """Per-entry telemetry view of the LRU, oldest-use first — the
        EXACT byte accounting the introspection plane publishes as
        karpenter_solver_resident_entry_bytes and /debug/solver renders
        (observability/devicetelemetry.py). `slot` is the LRU position
        at snapshot time; `age_s` needs `now` on the same clock that
        stamped created_at (the owning service's)."""
        with self._lock:
            snapshot = list(self._entries.values())
        return [
            {
                "slot": f"entry{i}",
                "bytes": entry.nbytes,
                "rows": entry.rows,
                "shape": tuple(entry.shape),
                "mode": entry.mode[0],
                "tenant": entry.tenant,
                "age_s": (
                    round(max(0.0, now - entry.created_at), 3)
                    if now is not None else None
                ),
            }
            for i, entry in enumerate(snapshot)
        ]

    def _find(self, host, shape, mode) -> Optional[_Entry]:
        with self._lock:
            for key, entry in self._entries.items():
                if (
                    entry.host is host
                    and entry.shape == shape
                    and entry.mode == mode
                ):
                    self._entries.move_to_end(key)
                    return entry
        return None

    def _store(self, entry: _Entry, generation: int, evict=None) -> None:
        """Admit one entry, unless drop_all ran since the serve began
        (`generation` mismatch: the entry was built from pre-drop
        buffers and must not resurrect them). `evict` removes the
        superseded predecessor of a scatter — its identity can never be
        looked up again (plans chain forward only), and leaving it
        would fill the LRU with dead stacks that evict other tenants'
        LIVE chains."""
        with self._lock:
            if generation != self._generation:
                return
            if evict is not None:
                self._entries.pop(id(evict), None)
            self._entries[id(entry.host)] = entry
            self._entries.move_to_end(id(entry.host))
            while len(self._entries) > self.MAX_ENTRIES:
                self._entries.popitem(last=False)

    # -- the serve path ----------------------------------------------------

    def obtain(
        self,
        inputs: BinPackInputs,
        shape: Tuple[int, int, int, int, int],
        mode: tuple,
        put,
        tenant=None,
        now: float = 0.0,
    ) -> Tuple[BinPackInputs, str]:
        """(device-resident stacked operands, kind) for one singleton
        dispatch. kind is "hit" (identity match — zero encode, zero
        upload), "scatter" (delta plan applied — only the changed rows
        crossed the link), or "rebuild" (full upload through `put`).

        `put` is the service's placement hook — (pytree) -> device
        pytree, device_put with NamedShardings on the sharded path —
        billed to the "upload" stage ring only by the rebuild's full
        stack (a scatter result passes through it to re-pin shardings,
        a device-side no-op). `mode` keys the placement: a mode change
        (the shard-threshold crossing, either direction) misses
        identity on purpose and rebuilds under the new placement.

        Never raises past the full-upload fallback: a scatter that
        fails for ANY reason rebuilds instead."""
        with self._lock:
            generation = self._generation
        entry = self._find(inputs, shape, mode)
        if entry is not None:
            self.hits += 1
            return entry.stacked, "hit"
        # the plan is consulted even when the scatter gate holds (CPU
        # auto mode): a successor ALWAYS supersedes its predecessor's
        # entry, whichever rung serves it
        plan = _plan_for(inputs)
        if plan is not None and self._scatter_allowed():
            prev_entry = self._find(plan.prev, shape, mode)
            if prev_entry is not None:
                try:
                    stacked = self._apply_plan(prev_entry, inputs, plan)
                    if len(mode) > 1:
                        # mesh placement: re-pin the NamedShardings on
                        # the scatter result (device-side, no host
                        # bytes); single-device output is already home
                        stacked = put(stacked)
                    self._store(
                        _Entry(inputs, shape, mode, stacked,
                               tenant=tenant, created_at=now),
                        generation, evict=plan.prev,
                    )
                    self.scatters += 1
                    return stacked, "scatter"
                except Exception:  # noqa: BLE001 — optimization layer:
                    # any scatter-path inconsistency rebuilds instead
                    pass
        stacked = put(_stack_one(pad_to_bucket(inputs, shape)))
        self._store(
            _Entry(inputs, shape, mode, stacked,
                   tenant=tenant, created_at=now),
            generation,
            evict=plan.prev if plan is not None else None,
        )
        self.rebuilds += 1
        return stacked, "rebuild"

    def _apply_plan(self, entry, inputs, plan) -> BinPackInputs:
        """Scatter the changed rows into a NEW stacked pytree: every
        spliced row leaf (and the weight column) updates at the UNION
        of plan.rows and plan.weight_rows in ONE fused dispatch; group
        leaves (identity-reused by the delta layer) carry over
        untouched. The padded update blocks are the only host bytes the
        jitted scatter ships to the device."""
        import jax

        stacked = entry.stacked
        P = entry.shape[0]
        union = (
            plan.rows
            if not len(plan.weight_rows)
            else np.union1d(plan.rows, plan.weight_rows).astype(np.int32)
        )
        if not len(union):
            return stacked
        if int(union.max()) >= P:
            raise ValueError("plan rows exceed resident extent")
        if stacked.pod_weight is None:
            raise ValueError("resident stack lacks the weight operand")
        rows = _pad_rows(union)
        out = self._stack_scatter_fn()(
            stacked, rows,
            *(
                _gather_update(
                    getattr(inputs, name), rows, getattr(stacked, name)
                )
                for name in (*_ROW_LEAVES, "pod_weight")
            ),
        )
        jax.block_until_ready(out)
        self.last_scatter_rows = int(len(union))
        return out

    def _scatter_allowed(self) -> bool:
        if self.scatter == "always":
            return True
        if self.scatter == "never":
            return False
        if self._scatter_auto is None:
            import jax

            self._scatter_auto = jax.default_backend() in (
                "tpu", "gpu", "cuda", "rocm"
            )
        return self._scatter_auto

    def _stack_scatter_fn(self):
        """The fused all-leaves row scatter (one dispatch), compiled
        once per (buffer shapes, padded row count) signature by jax's
        own cache — the row-count ladder (_pad_rows) keeps that
        signature set logarithmic. Donation is deliberately OFF: the
        previous resident buffer may still be read by an in-flight
        pipelined dispatch, and the device-local copy costs no
        transfer."""
        if self._stack_scatter_jit is None:
            import jax

            self._stack_scatter_jit = jax.jit(_scatter_stack)
        return self._stack_scatter_jit


def _pad_rows(rows: np.ndarray) -> np.ndarray:
    """Pad a changed-row index vector up the bucket ladder by repeating
    the FIRST index (its update row is duplicated alongside, so the
    duplicate writes carry identical bytes)."""
    n = len(rows)
    target = bucket_up(n, _ROW_FLOOR)
    out = np.full(target, rows[0], np.int32)
    out[:n] = rows
    return out


def _gather_update(leaf, padded_rows: np.ndarray, buf):
    """Gather the (padded) changed rows of one host operand into the
    update block [n_padded, *tail]. Rows at/past the host extent (the
    shrunk-fleet case: the new encode has fewer rows than the resident
    buffer) read as zeros — exactly what the padded resident rows must
    hold there."""
    host = np.asarray(leaf)
    tail = tuple(buf.shape[2:])
    out = np.zeros((len(padded_rows), *tail), buf.dtype)
    in_range = padded_rows < host.shape[0]
    if in_range.any():
        out[in_range] = host[padded_rows[in_range]]
    return out


def _stack_one(padded: BinPackInputs) -> BinPackInputs:
    """Host stack of ONE padded request (batch axis 1) — the resident
    mirror of the service's _stack_group singleton case."""
    import dataclasses

    def one(name):
        leaf = getattr(padded, name)
        if leaf is None:
            return None
        return np.asarray(leaf)[None]

    return BinPackInputs(
        **{f.name: one(f.name) for f in dataclasses.fields(BinPackInputs)}
    )


def _plan_for(inputs):
    """The delta layer's changed-row plan for `inputs`, or None (cold
    encode, full rebuild, or a non-delta caller). Imported lazily: the
    encoder module owns the registry, so plan production and
    consumption share one lifetime."""
    from karpenter_tpu.metrics.producers.pendingcapacity.encoder import (
        resident_plan,
    )

    return resident_plan(inputs)
