"""Multi-host initialization seam (parallel/multihost.py).

jax.distributed.initialize is once-per-process, so the live join runs in
a SUBPROCESS: a 1-process CPU "fleet" joins itself as coordinator,
builds the mesh over its global devices, and runs the certified sharded
program — proving the deployment path (initialize -> build_mesh ->
fleet step) composes, without multi-host hardware.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest


def _clean_cpu_env() -> dict:
    """Subprocess env forcing the CPU backend with no inherited
    multihost topology (the pytest process's topology vars must not
    leak into the spawned fleet)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"):
        env.pop(var, None)
    return env


def test_single_process_fleet_joins_and_solves():
    script = r"""
from karpenter_tpu.utils.backend import force_virtual_cpu
force_virtual_cpu(4)  # the one owner of the XLA_FLAGS/platform sequence
import jax
from karpenter_tpu.parallel.multihost import initialize_multihost
joined = initialize_multihost(
    coordinator_address="localhost:12399", num_processes=1, process_id=0
)
assert joined, "explicit 1-process topology must join"
assert jax.process_count() == 1
assert jax.device_count() >= 4
# idempotent
assert initialize_multihost() is True
from karpenter_tpu.parallel.mesh import dryrun_fleet_step
dryrun_fleet_step(jax.device_count())
print("MULTIHOST-OK")
"""
    env = _clean_cpu_env()
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MULTIHOST-OK" in proc.stdout


_TWO_PROCESS_SCRIPT = r"""
import sys
pid = int(sys.argv[1]); port = sys.argv[2]
from karpenter_tpu.utils.backend import force_virtual_cpu
force_virtual_cpu(4)  # 4 local devices per process -> 8 global
from karpenter_tpu.parallel.multihost import initialize_multihost
joined = initialize_multihost(
    coordinator_address=f"localhost:{port}", num_processes=2, process_id=pid
)
assert joined, "explicit 2-process topology must join"
import jax
import numpy as np
assert jax.process_count() == 2, jax.process_count()
# the conftest env may pre-force a LARGER per-process device count (the
# flag is never reduced); the invariant is the split, not the total
n_local = len(jax.local_devices())
assert n_local >= 4
assert jax.device_count() == 2 * n_local, (jax.device_count(), n_local)

import dataclasses
import jax.numpy as jnp
from karpenter_tpu.parallel.mesh import (
    build_mesh, example_binpack_inputs, example_decision_inputs,
    fleet_step, shard_binpack_inputs, shard_decision_inputs,
)

rng = np.random.default_rng(7)
weights = np.ones(33, np.int32); weights[:4] = 5
d_in = example_decision_inputs(N=16, M=4)
b_in = dataclasses.replace(
    example_binpack_inputs(P_=33, T=8, K=8, L=8),
    pod_weight=jnp.asarray(weights),
    pod_group_forbidden=jnp.asarray(rng.random((33, 8)) < 0.3),
    pod_group_score=jnp.asarray(rng.integers(0, 100, (33, 8)).astype(np.float32)),
    pod_exclusive=jnp.asarray(rng.random(33) < 0.25),
)
# the GLOBAL slice x pods x groups mesh spans both processes
mesh = build_mesh(n_devices=jax.device_count(), slices=2)

# single-process reference on LOCAL devices over the SAME mesh-padded
# inputs (identical on both processes by construction: same seeds), so
# shard indices line up with the padded global shape
from karpenter_tpu.parallel.mesh import (
    pad_binpack_inputs_for_mesh, pad_decision_inputs_for_mesh,
)
pb_in = pad_binpack_inputs_for_mesh(b_in, mesh)
pd_in = pad_decision_inputs_for_mesh(d_in, mesh)
d_ref, b_ref = jax.device_get(fleet_step(pd_in, pb_in, buckets=8))
gd_in = shard_decision_inputs(mesh, d_in)
gb_in = shard_binpack_inputs(mesh, b_in)
d_out, b_out = fleet_step(gd_in, gb_in, buckets=8)

def check(global_arr, ref):
    for shard in global_arr.addressable_shards:
        np.testing.assert_array_equal(
            np.asarray(shard.data), np.asarray(ref[shard.index])
        )

check(b_out.assigned, np.asarray(b_ref.assigned))       # includes mesh padding rows
check(d_out.desired, np.asarray(d_ref.desired))
check(b_out.nodes_needed, np.asarray(b_ref.nodes_needed))
check(b_out.assigned_count, np.asarray(b_ref.assigned_count))
print(f"TWOPROC-OK pid={pid}")
"""


def test_two_process_fleet_joins_and_matches_single_process():
    """THE multi-host seam, exercised with two real processes
    (coordinator + worker) on the CPU backend: both join via
    jax.distributed, build one GLOBAL 2-slice mesh over 8 devices split
    4+4 across the processes, run the collective fleet_step, and every
    addressable output shard equals the single-process reference
    (r3 verdict item 5 — the one seam the single-process dryrun cannot
    prove)."""
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = _clean_cpu_env()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _TWO_PROCESS_SCRIPT, str(pid), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for proc in procs:
            out, err = proc.communicate(timeout=420)
            outs.append((proc.returncode, out, err))
    finally:
        for proc in procs:
            if proc.poll() is None:
                # kill then reap: drain the pipes so a hung join still
                # leaves its stderr for diagnosis, and no zombie
                # survives into the rest of the pytest run
                proc.kill()
                out, err = proc.communicate()
                print(f"killed pid={proc.pid} stderr tail:\n{err[-2000:]}")
    for pid, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"process {pid} failed:\n{err[-3000:]}"
        assert f"TWOPROC-OK pid={pid}" in out
    # padding rows equal too: the check covered the full padded arrays


def test_two_sidecar_fleet_joins_and_serves():
    """The DEPLOYMENT contract (docs/OPERATIONS.md 'Scaling past one
    chip'): one solver sidecar per host, `--multihost`, topology from
    the standard env. Two real `python -m karpenter_tpu.sidecar`
    processes join one jax.distributed fleet; the coordinator's Health
    reports the GLOBAL device count (both processes' devices) and its
    Solve RPC answers identically to an in-process solve."""
    import json
    import socket

    import numpy as np

    ports = []
    for _ in range(3):  # coordinator + two gRPC ports
        with socket.socket() as s:
            s.bind(("localhost", 0))
            ports.append(s.getsockname()[1])
    coord, grpc0, grpc1 = ports
    procs = []
    try:
        for pid, gport in ((0, grpc0), (1, grpc1)):
            env = _clean_cpu_env()
            # pin the per-process device count so the global-count
            # assertion below can DISTINGUISH a joined fleet (8) from a
            # lone sidecar that failed to join (4)
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            env["JAX_COORDINATOR_ADDRESS"] = f"localhost:{coord}"
            env["JAX_NUM_PROCESSES"] = "2"
            env["JAX_PROCESS_ID"] = str(pid)
            procs.append(
                subprocess.Popen(
                    [
                        # -u: the banner must not sit in a block buffer
                        sys.executable, "-u", "-m",
                        "karpenter_tpu.sidecar",
                        "--multihost", "--host", "127.0.0.1",
                        "--port", str(gport),
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                    env=env,
                )
            )
        # wait for the coordinator's serving banner: select-based so a
        # stuck coordinator trips the deadline and a crashed one fails
        # fast with its stderr (drained in the finally block)
        import select
        import time

        deadline = time.monotonic() + 120
        banner = None
        while time.monotonic() < deadline and banner is None:
            if procs[0].poll() is not None:
                break  # coordinator died; finally drains its stderr
            ready, _, _ = select.select([procs[0].stdout], [], [], 0.5)
            if ready:
                line = procs[0].stdout.readline()
                if line:
                    banner = json.loads(line)
        assert banner and banner["serving"].endswith(str(grpc0)), (
            f"no serving banner (coordinator rc={procs[0].poll()})"
        )

        from karpenter_tpu.ops.binpack import solve
        from karpenter_tpu.parallel.mesh import example_binpack_inputs
        from karpenter_tpu.sidecar.client import SolverClient

        client = SolverClient(f"127.0.0.1:{grpc0}", timeout_seconds=60.0)
        ok, health = client.health()
        assert ok
        # the coordinator sees the GLOBAL device set (4 local + 4 from
        # the worker); a lone sidecar that failed to join would see 4
        assert health["device_count"] == 8, health

        inputs = example_binpack_inputs(P_=64, T=8)
        remote = client.solve(inputs, buckets=8)
        local = solve(inputs, buckets=8)
        np.testing.assert_array_equal(
            np.asarray(remote.assigned), np.asarray(local.assigned)
        )
        assert int(remote.unschedulable) == int(local.unschedulable)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            # drain ALIVE and DEAD alike: a crashed sidecar's stderr is
            # the diagnostic for why the banner never came
            _out, err = proc.communicate()
            tail = err[-1500:] if err else ""
            print(f"sidecar pid={proc.pid} rc={proc.returncode} "
                  f"stderr tail:\n{tail}")


def test_no_topology_is_single_host_noop():
    """Without a coordinator/env topology on a non-TPU host, the seam
    reports False and the caller proceeds single-host. Runs in a fresh
    subprocess: the join must precede backend initialization, and the
    pytest process has long initialized its virtual mesh."""
    script = r"""
from karpenter_tpu.parallel.multihost import initialize_multihost
assert initialize_multihost() is False
print("NOOP-OK")
"""
    env = _clean_cpu_env()
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NOOP-OK" in proc.stdout


def test_join_after_backend_init_raises_loudly():
    """Calling the seam after XLA initialized (a caller ordering bug)
    must raise, never be classified as 'no topology'."""
    import jax
    import pytest

    from karpenter_tpu.parallel import multihost

    jax.devices()  # deterministically initialize the in-process backend
    multihost._initialized = False
    with pytest.raises(RuntimeError, match="before"):
        multihost.initialize_multihost()


def test_partial_topology_raises(monkeypatch):
    """A half-configured host must crash loudly, never serve single-host
    while the rest of the fleet hangs waiting for it."""
    import importlib

    import pytest

    from karpenter_tpu.parallel import multihost

    importlib.reload(multihost)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    monkeypatch.setenv("JAX_PROCESS_ID", "3")
    with pytest.raises(ValueError, match="partial multihost topology"):
        multihost.initialize_multihost()
