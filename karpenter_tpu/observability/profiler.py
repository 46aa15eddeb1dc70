"""JAX-profiler integration: the xprof server and on-demand captures.

reference: the reference has NO tracing/profiling (OTel is future work,
docs/designs/DESIGN.md) — these hooks are an addition the TPU build
needs: device-side timelines via the JAX profiler (xprof), so a 200 ms
budget regression is attributable to feed vs compile vs compute. The
host side of those timelines is the reconcile tracer
(observability.tracing): while a capture records, its spans mirror onto
the profiler's host plane, on the same clock as the device ops.
"""

from __future__ import annotations

import json
import os
import threading
import time as _time

from karpenter_tpu.observability.tracing import profiler_available


def start_profiler_server(port: int = 9999) -> bool:
    """Expose the JAX profiler so xprof/tensorboard can attach and
    capture device traces of the solver. Returns False if unavailable —
    with the reason LOGGED (a silent False left operators staring at a
    missing :9999 with nothing in the logs to explain it)."""
    try:
        import jax.profiler

        jax.profiler.start_server(port)
        return True
    except Exception as error:  # noqa: BLE001
        from karpenter_tpu.utils.log import logger

        logger().warning(
            "jax profiler server failed to start on :%d (%s: %s); "
            "device-timeline capture unavailable",
            port, type(error).__name__, error,
        )
        return False


# -- on-demand capture (/debug/profile) ---------------------------------------

# bounds for one on-demand capture window: long enough to span several
# manager ticks, short enough that a fat-fingered query can't park the
# profiler (and its overhead) on a production plane for minutes
MIN_CAPTURE_MS = 1
MAX_CAPTURE_MS = 30_000

PROFILE_PREFIX = "profile-"

# single-flight: the jax profiler is a process-global singleton — two
# concurrent start_trace calls corrupt each other's sessions
_capture_lock = threading.Lock()
_capture_seq = 0


class ProfileBusy(RuntimeError):
    """A capture is already in flight (single-flight contract)."""


class ProfileUnavailable(RuntimeError):
    """The jax.profiler probe failed — no capture possible."""


def capture_profile(
    ms: int, out_dir: str, trace_id=None, sleep=_time.sleep
) -> dict:
    """One bounded on-demand jax.profiler capture (/debug/profile?ms=N):
    profile the process for `ms` milliseconds (clamped to
    [MIN_CAPTURE_MS, MAX_CAPTURE_MS]) into
    `out_dir/profile-<seq>-<stamp>/` — the runtime passes --journal-dir,
    so captures land next to the flight-recorder dumps an incident
    already wrote. The capture directory is written under a tmp name
    and renamed into place ATOMICALLY (the flight-recorder dump
    discipline: a crash mid-capture leaves a .tmp orphan, never a
    half-readable capture), with a manifest.json stamping the active
    trace id, window, and wall time.

    Raises ProfileUnavailable when the jax.profiler probe failed and
    ProfileBusy when a capture is already in flight (single-flight) —
    the HTTP surface maps both to 503."""
    if not profiler_available():
        raise ProfileUnavailable("jax.profiler unavailable")
    import jax.profiler

    global _capture_seq
    ms = max(MIN_CAPTURE_MS, min(MAX_CAPTURE_MS, int(ms)))
    if not _capture_lock.acquire(blocking=False):
        raise ProfileBusy("a profiler capture is already in flight")
    try:
        _capture_seq += 1
        stamp = _time.strftime("%Y%m%d-%H%M%S")
        final = os.path.join(
            out_dir, f"{PROFILE_PREFIX}{_capture_seq:04d}-{stamp}"
        )
        tmp = final + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        t0 = _time.perf_counter()
        jax.profiler.start_trace(tmp)
        try:
            sleep(ms / 1e3)
        finally:
            jax.profiler.stop_trace()
        elapsed_ms = (_time.perf_counter() - t0) * 1e3
        manifest = {
            "ms_requested": ms,
            "ms_captured": round(elapsed_ms, 3),
            "trace_id": trace_id,
            "captured_at": _time.time(),
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as fh:
            json.dump(manifest, fh, sort_keys=True)
        os.rename(tmp, final)
        return {"path": final, **manifest}
    finally:
        _capture_lock.release()
