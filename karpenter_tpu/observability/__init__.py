"""Observability package: tracing, flight recorder, metrics/debug HTTP.

Grown from the single-module observability.py (which held MetricsServer
and the xprof hooks) into the correlation layer for the whole control
plane — docs/observability.md is the operator guide:

  tracing.py         per-reconcile trace IDs + spans, coalesced-dispatch
                     links, Chrome-trace/Perfetto JSONL export, and the
                     karpenter_reconcile_e2e_seconds lead-time histogram
  flightrecorder.py  bounded structured event ring (fault injections,
                     FSM trips, circuit opens, fence rejections, shard
                     fallbacks, journal compactions) with trace-ID
                     backlinks and crash-safe dumps into --journal-dir
  provenance.py      the decision provenance ledger — a bounded
                     columnar ring answering "why did this group scale
                     to N this tick" (/debug/decisions, JSONL export
                     next to --trace-export; default off, --provenance)
  selfslo.py         the control plane's self-SLO monitor: multi-window
                     burn rates over karpenter_reconcile_e2e_seconds +
                     solver FSM + tenant breakers (/debug/selfslo,
                     karpenter_selfslo_*, selfslo_burn auto-dump)
  devicetelemetry.py the solver introspection plane: compile ledger
                     (every compile-cache miss with rung/extents/wall
                     time/trace ids + XLA cost attribution,
                     karpenter_solver_compile_seconds, compile_storm
                     trip-class events), device memory telemetry
                     (karpenter_device_*, resident-LRU byte accounting,
                     the self-SLO memory source), /debug/solver
                     (default off, --introspect)
  server.py          /metrics, /healthz (liveness), /readyz (real
                     readiness), /debug/traces, /debug/flightrecorder,
                     /debug/decisions, /debug/selfslo, /debug/solver,
                     /debug/profile
  profiler.py        the xprof profiler server and the bounded
                     single-flight on-demand capture (/debug/profile);
                     the tracer's spans mirror onto its host plane

The public names below are the pre-package import surface
(`from karpenter_tpu.observability import MetricsServer,
start_profiler_server`).
"""

from karpenter_tpu.observability.devicetelemetry import (
    CompileLedger,
    SolverIntrospection,
)
from karpenter_tpu.observability.flightrecorder import (
    FlightRecorder,
    default_flight_recorder,
    reset_default_flight_recorder,
    set_default_flight_recorder,
)
from karpenter_tpu.observability.profiler import start_profiler_server
from karpenter_tpu.observability.provenance import (
    DecisionLedger,
    default_ledger,
    reset_default_ledger,
    set_default_ledger,
)
from karpenter_tpu.observability.selfslo import SelfSLOMonitor
from karpenter_tpu.observability.server import MetricsServer
from karpenter_tpu.observability.tracing import (
    Tracer,
    default_tracer,
    reset_default_tracer,
    set_default_tracer,
)

__all__ = [
    "CompileLedger",
    "DecisionLedger",
    "FlightRecorder",
    "MetricsServer",
    "SelfSLOMonitor",
    "SolverIntrospection",
    "Tracer",
    "default_flight_recorder",
    "default_ledger",
    "default_tracer",
    "reset_default_flight_recorder",
    "reset_default_ledger",
    "reset_default_tracer",
    "set_default_flight_recorder",
    "set_default_ledger",
    "set_default_tracer",
    "start_profiler_server",
]
