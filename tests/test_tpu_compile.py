"""The main path's kernels compile for a v5e chip, at the sizes chip_smoke.py
runs them — with no chip attached.

The TPU compiler is installed here, and it compiles for a topology that is
described, not attached (the `on-chip-measurement` guide, section 2). What
it refuses here — a tile not aligned to the chip's layout, more fast memory
than a kernel may take, a program that does not fit in HBM — would
otherwise cost a chip run to find. Nothing runs, so these say nothing
about results or times; chip_smoke.py checks those on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports every
test file. The persistent compile cache stays off around the compiles
(tests/conftest.py turns it off for the suite; the fixture makes sure).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from karpenter_tpu.ops.binpack import BinPackInputs, binpack
from karpenter_tpu.ops.decision import DecisionInputs, decide_jit
from karpenter_tpu.ops.pallas_binpack import binpack_pallas
from karpenter_tpu.solver.bucketing import bucket_up, mesh_aligned_shape

# the service bucket of chip_smoke.py's north-star solve: 100k pods x 300
# groups x 3 resources x 64 taints x 64 labels pads up to this rung
NORTH_STAR = (131072, 384, 4, 64, 64)
# and of its four-chip solve: 1M pods x 1000 groups
FLEET = (1048576, 1024, 4, 64, 64)
DECIDE_ROWS = bucket_up(100_000, 64)
DECIDE_METRICS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as error:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {error}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    from karpenter_tpu.parallel.mesh import build_mesh

    return build_mesh(devices=topo.devices)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def binpack_shapes(shape, sharding, all_operands: bool, batch=()):
    """BinPackInputs of ShapeDtypeStructs; `sharding` is one sharding for
    every leaf or a BinPackInputs of shardings (parallel/mesh.py)."""
    p, t, r, k, l = shape
    pick = (
        (lambda name: getattr(sharding, name))
        if isinstance(sharding, BinPackInputs)
        else (lambda name: sharding)
    )
    dims = {
        "pod_requests": ((p, r), jnp.float32),
        "pod_valid": ((p,), jnp.bool_),
        "pod_intolerant": ((p, k), jnp.bool_),
        "pod_required": ((p, l), jnp.bool_),
        "group_allocatable": ((t, r), jnp.float32),
        "group_taints": ((t, k), jnp.bool_),
        "group_labels": ((t, l), jnp.bool_),
    }
    if all_operands:
        dims.update(
            pod_weight=((p,), jnp.int32),
            pod_group_forbidden=((p, t), jnp.bool_),
            pod_group_score=((p, t), jnp.float32),
            pod_exclusive=((p,), jnp.bool_),
        )
    return BinPackInputs(
        **{
            name: _spec((*batch, *dim), dtype, pick(name))
            for name, (dim, dtype) in dims.items()
        }
    )


def _kernel_bytes(compiled) -> int:
    memory = compiled.memory_analysis()
    return (
        memory.argument_size_in_bytes
        + memory.output_size_in_bytes
        + memory.temp_size_in_bytes
    )


@pytest.mark.parametrize("operands", ["plain", "all"])
@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_binpack_compiles_for_one_chip(one_chip, kernel, operands):
    """The compiled Mosaic kernel (what backend="auto" picks on the TPU)
    and the XLA program, at the north-star service bucket, with and
    without the optional operands the runtime encoder emits."""
    inputs = binpack_shapes(NORTH_STAR, one_chip, operands == "all")
    if kernel == "pallas":
        fn = jax.jit(
            lambda x: binpack_pallas(x, buckets=32, interpret=False)
        )
    else:
        fn = jax.jit(lambda x: binpack(x, buckets=32))
    compiled = fn.lower(inputs).compile()
    assert _kernel_bytes(compiled) < 16 * 2**30  # one v5e chip's HBM
    if kernel == "pallas":
        assert "tpu_custom_call" in compiled.as_text()


def test_decide_compiles_for_one_chip(one_chip):
    """The HPA decision kernel at the 100,000-row x 4-metric batch."""
    n, m = DECIDE_ROWS, DECIDE_METRICS
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    row = lambda dtype: _spec((n,), dtype, one_chip)  # noqa: E731
    mat = lambda dtype, cols=m: _spec((n, cols), dtype, one_chip)  # noqa: E731
    inputs = DecisionInputs(
        metric_value=mat(f32), target_value=mat(f32),
        target_type=mat(i32), metric_valid=mat(b),
        spec_replicas=row(i32), status_replicas=row(i32),
        min_replicas=row(i32), max_replicas=row(i32),
        up_window=row(i32), down_window=row(i32),
        up_policy=row(i32), down_policy=row(i32),
        last_scale_time=row(f32), has_last_scale=row(b),
        now=_spec((), f32, one_chip),
        up_ptype=mat(i32, 2), up_pvalue=mat(i32, 2),
        up_pperiod=mat(i32, 2), up_pvalid=mat(b, 2),
        down_ptype=mat(i32, 2), down_pvalue=mat(i32, 2),
        down_pperiod=mat(i32, 2), down_pvalid=mat(b, 2),
    )
    decide_jit.lower(inputs).compile()


@pytest.mark.parametrize("program", ["sharded_binpack", "service"])
def test_fleet_solve_compiles_for_four_chips(mesh, program):
    """The four-chip fleet solve of `chip_smoke.py --chips 4` on a 2x2
    mesh of the described chips: sharded_binpack's partitioned program,
    and the service's batched lax.map program over the mesh-aligned
    stack (SolverService._sharded_xla). Each chip's share must fit its
    HBM."""
    from karpenter_tpu.parallel.mesh import (
        binpack_shardings,
        mesh_extents,
        stacked_binpack_shardings,
    )
    from karpenter_tpu.solver import SolverService

    shape = mesh_aligned_shape(FLEET, mesh_extents(mesh))
    if program == "sharded_binpack":
        inputs = binpack_shapes(shape, binpack_shardings(mesh), False)
        fn = binpack
    else:
        presence = (False,) * 12
        inputs = binpack_shapes(
            shape, stacked_binpack_shardings(mesh, presence), False,
            batch=(1,),
        )
        service = SolverService(shard_threshold=0)
        try:
            fn, _ = service._compiled_for(
                ("xla", shape, 1, 32, presence, (), "map", "shard",
                 mesh_extents(mesh))
            )
        finally:
            service.close()
    compiled = fn.lower(inputs, buckets=32).compile()
    assert _kernel_bytes(compiled) < 16 * 2**30
    # the per-group histogram sums over the sharded pods axis
    text = compiled.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
