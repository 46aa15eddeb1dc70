"""Multi-host initialization: the jax.distributed seam for multi-slice /
multi-host solver deployments.

reference analog: the reference's distributed backend is the
kube-apiserver bus + NCCL-less singleton control plane (SURVEY.md §2.2 —
it has no multi-node compute at all). The TPU build's compute CAN span
hosts: `parallel/mesh.py` builds 2D/3D meshes over whatever devices jax
exposes, and on a multi-host slice jax exposes the GLOBAL device set
only after `jax.distributed.initialize` — this module is the one place
that call lives.

Deployment contract (docs/OPERATIONS.md "Scaling past one chip"): run
one solver sidecar per host (`python -m karpenter_tpu.sidecar
--multihost`); on TPU pods the coordinator/process topology
auto-detects from the TPU environment, elsewhere it comes from the
standard env (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES,
JAX_PROCESS_ID) or explicit arguments. After initialization,
`build_mesh(n_devices=jax.device_count())` spans the whole slice and
the sharded programs in parallel/mesh.py run unchanged — pod rows over
ICI, the one cross-slice reduction over DCN.
"""

from __future__ import annotations

import os
from typing import Optional

from karpenter_tpu.utils.log import logger

_initialized = False


def _resolve_topology(coordinator_address, num_processes, process_id):
    """Resolve each parameter (explicit argument, then standard env var)
    and enforce all-or-nothing: a PARTIAL explicit topology raises —
    silently degrading a mis-wired multi-host fleet to N independent
    single-host solvers would double-solve the fleet."""
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_processes = os.environ.get("JAX_NUM_PROCESSES")
    num_processes = (
        num_processes
        if num_processes is not None
        else (int(env_processes) if env_processes else None)
    )
    env_process_id = os.environ.get("JAX_PROCESS_ID")
    process_id = (
        process_id
        if process_id is not None
        else (int(env_process_id) if env_process_id else None)
    )
    explicit = (coordinator_address, num_processes, process_id)
    configured = [value for value in explicit if value is not None]
    if configured and len(configured) != len(explicit):
        raise ValueError(
            "partial multihost topology: coordinator_address, "
            f"num_processes, process_id must be set together (got "
            f"{explicit!r}); a half-configured host joining single-host "
            "would double-solve the fleet while the rest hang"
        )
    return explicit


def _auto_initialize(jax) -> bool:
    """The auto path: let jax's cluster detection decide. Attempted
    UNCONDITIONALLY (probing the backend first would itself initialize
    XLA and poison the join). Returns False only on the EXACT no-cluster
    sentinel: jax's cluster auto-detection found no cluster and fell
    through to the bare-args validation (jax._src.distributed raises
    RuntimeError 'coordinator_address should be defined.'). Anything
    else — a detected-but-unreachable coordinator, a partial detection,
    'must be called before any JAX calls' (an ordering bug in the
    caller) — is a REAL failure and raises: degrading a detected
    multi-host fleet to N independent solvers would double-solve the
    fleet while the other hosts hang in initialize. Substring matching
    here once misread real join failures (r3 code review)."""
    try:
        jax.distributed.initialize()
    except Exception as e:  # noqa: BLE001 — classified above
        if str(e).strip() == "coordinator_address should be defined.":
            # the normal single-host case
            logger().info("no multihost topology detected: %s", e)
            return False
        raise
    return True


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize jax.distributed for a multi-host deployment.

    MUST be called before anything initializes the in-process XLA
    backend (jax.devices(), any computation, even jax.default_backend())
    — jax.distributed.initialize refuses afterwards. The sidecar
    therefore joins BEFORE it initializes its backend.

    Resolution order per parameter: explicit argument, then standard env
    var. With a FULL explicit topology (all three of coordinator /
    num_processes / process_id) the join is mandatory and any failure
    raises. With NO explicit topology, jax's own cluster auto-detection
    runs (TPU pod metadata, GKE, Slurm); "no cluster found" returns
    False — the normal single-host case — while any other failure
    raises. A PARTIAL explicit topology always raises: silently
    degrading a mis-wired multi-host fleet to N independent single-host
    solvers would double-solve the fleet.

    Idempotent per process (jax.distributed.initialize is once-only).
    """
    global _initialized
    if _initialized:
        return True
    coordinator_address, num_processes, process_id = _resolve_topology(
        coordinator_address, num_processes, process_id
    )

    import jax

    if coordinator_address is None:
        if not _auto_initialize(jax):
            return False
        _initialized = True
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
    logger().info(
        "multihost: process %d/%d, %d global device(s)",
        jax.process_index(),
        jax.process_count(),
        jax.device_count(),
    )
    return True
