"""Pallas TPU kernel: the fused hot stage of the pending-pods bin-pack.

The XLA path (ops/binpack.py) materializes several [P, T] intermediates in
HBM (feasibility, dominant share, membership, bucket index — ~120 MB each at
the 100k x 300 bench scale). This kernel fuses the whole per-pod stage into
one VMEM-resident pass over pod tiles:

  feasibility (resource compare + taint/label bitset matmuls on the MXU)
  -> first-feasible assignment (min-index reduction)
  -> dominant-share bucket quantization
  -> histogram [T, B] + demand [T, R] accumulation (transpose matmuls on
     the MXU, accumulated across sequential grid steps in VMEM)

so the only HBM traffic is the structure-of-arrays inputs once and the tiny
[T, *] outputs. The shelf-BFD node-count scan stays in XLA (ops/binpack.py
_shelf_bfd): it is O(B^2) on [T, B] state — not worth a kernel.

reference: this signal is the one the reference STUBS
(pkg/metrics/producers/pendingcapacity/producer.go:29-31, design intent in
docs/designs/DESIGN.md "Pending Pods"); there is no reference kernel to
mirror — the algorithm contract is pinned by ops/binpack.py and its scalar
oracle, and this kernel must match it bit-for-bit (tests/test_pallas_binpack).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from karpenter_tpu.ops.binpack import BinPackInputs

DEFAULT_TILE_P = 512
_LANE = 128


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _kernel(
    req_ref,  # f32[TILE_P, R]
    valid_ref,  # f32[TILE_P, 1]  (bool as f32: VMEM-friendly layout)
    intol_ref,  # f32[TILE_P, K]
    required_ref,  # f32[TILE_P, L]
    weight_ref,  # f32[TILE_P, 1] row multiplicity (1.0 when undeduplicated)
    alloc_t_ref,  # f32[R_pad, T] — transposed so resource rows are slices
    taints_ref,  # f32[T, K]
    labels_ref,  # f32[T, L]
    *rest,  # [forbidden_ref f32[TILE_P, T] when has_forbidden,]
    #         [score_ref f32[TILE_P, T] when has_score,]
    #         [exclusive_ref f32[TILE_P, 1] when has_exclusive,]
    #         assigned_ref i32[TILE_P, 1], hist_ref f32[T, B],
    #         demand_ref f32[T, R]
    buckets: int,
    n_resources: int,
    has_forbidden: bool = False,
    has_score: bool = False,
    has_exclusive: bool = False,
):
    rest = list(rest)
    forbidden_ref = rest.pop(0) if has_forbidden else None
    score_ref = rest.pop(0) if has_score else None
    exclusive_ref = rest.pop(0) if has_exclusive else None
    assigned_ref, hist_ref, demand_ref = rest
    # Everything stays 2D: Mosaic lowers static row/column slices and 2D
    # broadcasts, but not the gathers that 1D intermediates / fancy
    # indexing produce.
    step = pl.program_id(0)

    req = req_ref[:]  # [TILE_P, R]
    alloc_t = alloc_t_ref[:]  # [R_pad, T]
    tile_p = req.shape[0]
    n_groups = alloc_t.shape[1]

    # --- feasibility [TILE_P, T] ---------------------------------------
    fits = jnp.ones((tile_p, n_groups), jnp.float32)
    for r in range(n_resources):  # R tiny+static: unrolled by design
        fits = fits * (
            req[:, r : r + 1] <= alloc_t[r : r + 1, :]
        ).astype(jnp.float32)
    # zero-alloc group: nothing fits (padded resource rows are zero)
    nonempty = jnp.max(alloc_t, axis=0, keepdims=True) > 0  # [1, T]
    fits = fits * nonempty.astype(jnp.float32)

    # taints / required labels as bitset matmuls -> MXU
    taint_violations = jax.lax.dot_general(
        intol_ref[:],
        taints_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [TILE_P, T]
    label_violations = jax.lax.dot_general(
        required_ref[:],
        1.0 - labels_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [TILE_P, T]
    fits = fits * (taint_violations < 0.5) * (label_violations < 0.5)
    if forbidden_ref is not None:  # required node affinity (host-evaluated)
        fits = fits * (1.0 - forbidden_ref[:])
    fits = fits * valid_ref[:]  # [TILE_P, 1] broadcast

    feasible = fits > 0.5  # bool[TILE_P, T]

    # --- assignment: min feasible column index, or (with preference
    # scores) the min index among max-score feasible groups — f32 score
    # equality is exact because scores are integer weight sums ----------
    col = jax.lax.broadcasted_iota(jnp.int32, (tile_p, n_groups), 1)
    if score_ref is not None:
        big = jnp.float32(3.4e38)
        masked = jnp.where(feasible, score_ref[:], -big)
        best = jnp.max(masked, axis=1, keepdims=True)  # [TILE_P, 1]
        candidate = feasible & (masked == best)
    else:
        candidate = feasible
    first = jnp.min(
        jnp.where(candidate, col, n_groups), axis=1, keepdims=True
    )  # [TILE_P, 1], == n_groups when none
    has = first < n_groups  # [TILE_P, 1]
    assigned_ref[:] = jnp.where(has, first, -1)

    member = (col == first) & has  # one-hot [TILE_P, T]
    member_f = member.astype(jnp.float32)
    # weighted membership: the hist/demand accumulators count each row
    # `weight` times (rows are deduplicated pod shapes)
    member_w = member_f * weight_ref[:]  # [TILE_P, 1] broadcast

    # --- dominant share of the assigned group -> bucket one-hot --------
    share = jnp.zeros((tile_p, n_groups), jnp.float32)
    for r in range(n_resources):
        a = alloc_t[r : r + 1, :]  # [1, T]
        big = jnp.float32(3.4e38)  # stand-in for inf: req>0 on 0-alloc
        s = jnp.where(a > 0, req[:, r : r + 1] / jnp.maximum(a, 1e-30), big)
        s = jnp.where((a <= 0) & (req[:, r : r + 1] <= 0), 0.0, s)
        share = jnp.maximum(share, s)
    share_assigned = jnp.sum(
        member_f * share, axis=1, keepdims=True
    )  # [TILE_P, 1]
    bucket = jnp.clip(
        jnp.ceil(share_assigned * buckets).astype(jnp.int32), 1, buckets
    )  # [TILE_P, 1]
    if exclusive_ref is not None:
        # hostname self-anti-affinity: the pod takes a whole node
        bucket = jnp.where(exclusive_ref[:] > 0.5, buckets, bucket)
    bcol = jax.lax.broadcasted_iota(jnp.int32, (tile_p, buckets), 1)
    bucket_onehot = ((bcol == (bucket - 1)) & has).astype(
        jnp.float32
    )  # [TILE_P, B]

    # --- accumulate [T, B] histogram + [T, R] demand (MXU transposes) ---
    # Both accumulators pin precision=HIGHEST: Mosaic's default MXU path
    # rounds f32 operands to bf16, and member_w carries pod multiplicities
    # (dedup weights reach ~1e4 at bench scale — past bf16's 8-bit
    # mantissa), so the default would miscount the histogram and drift the
    # demand sum. ops/binpack.py's einsum is pinned the same way.
    hist_update = jax.lax.dot_general(
        member_w,
        bucket_onehot,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [T, B]
    demand_update = jax.lax.dot_general(
        member_w,
        req,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [T, R]

    @pl.when(step == 0)
    def _():
        hist_ref[:] = jnp.zeros_like(hist_ref)
        demand_ref[:] = jnp.zeros_like(demand_ref)

    hist_ref[:] += hist_update
    demand_ref[:] += demand_update


@partial(
    jax.jit, static_argnames=("buckets", "tile_p", "interpret")
)
def fused_assign(
    inputs: BinPackInputs,
    buckets: int,
    tile_p: int = DEFAULT_TILE_P,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused assignment stage on TPU via Pallas.

    Returns (assigned i32[P], histogram i32[T, B], demand f32[T, R]) with
    identical semantics to the corresponding ops/binpack.py stage. P is
    padded to tile_p and T/K/L to the 128-lane width internally; padding is
    invisible in the outputs (padded pods are invalid, padded groups have
    zero allocatable so nothing fits them).
    """
    if tile_p % 8 != 0:
        raise ValueError(f"tile_p must be a multiple of 8, got {tile_p}")
    n_pods, n_resources = inputs.pod_requests.shape
    n_groups = inputs.group_allocatable.shape[0]
    n_taints = inputs.pod_intolerant.shape[1]
    n_labels = inputs.pod_required.shape[1]

    pad_p = _round_up(max(n_pods, 1), tile_p)
    pad_t = _round_up(max(n_groups, 1), _LANE)
    pad_k = _round_up(max(n_taints, 1), _LANE)
    pad_l = _round_up(max(n_labels, 1), _LANE)

    def pad(x, rows, cols=None):
        pads = [(0, rows - x.shape[0])]
        if cols is not None:
            pads.append((0, cols - x.shape[1]))
        return jnp.pad(x.astype(jnp.float32), pads)

    pad_r = 8  # alloc_t sublane dim: R resource rows zero-padded to 8

    req = pad(inputs.pod_requests, pad_p, n_resources)
    valid = pad(inputs.pod_valid[:, None], pad_p, 1)
    intol = pad(inputs.pod_intolerant, pad_p, pad_k)
    required = pad(inputs.pod_required, pad_p, pad_l)
    weight = (
        jnp.ones((pad_p, 1), jnp.float32)
        if inputs.pod_weight is None
        else pad(inputs.pod_weight[:, None], pad_p, 1)
    )
    alloc_t = pad(inputs.group_allocatable.T, pad_r, pad_t)
    taints = pad(inputs.group_taints, pad_t, pad_k)
    labels = pad(inputs.group_labels, pad_t, pad_l)

    has_forbidden = inputs.pod_group_forbidden is not None
    has_score = inputs.pod_group_score is not None
    has_exclusive = inputs.pod_exclusive is not None
    operands = [req, valid, intol, required, weight, alloc_t, taints, labels]
    in_specs = [
        pl.BlockSpec(
            (tile_p, n_resources), lambda i: (i, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (tile_p, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (tile_p, pad_k), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (tile_p, pad_l), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (tile_p, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (pad_r, pad_t), lambda i: (0, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (pad_t, pad_k), lambda i: (0, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (pad_t, pad_l), lambda i: (0, 0), memory_space=pltpu.VMEM
        ),
    ]
    if has_forbidden:
        operands.append(pad(inputs.pod_group_forbidden, pad_p, pad_t))
        in_specs.append(
            pl.BlockSpec(
                (tile_p, pad_t), lambda i: (i, 0), memory_space=pltpu.VMEM
            )
        )
    if has_score:
        # score padding is 0 on padded group columns; they are infeasible
        # (zero allocatable), so the -big mask keeps them out regardless
        operands.append(pad(inputs.pod_group_score, pad_p, pad_t))
        in_specs.append(
            pl.BlockSpec(
                (tile_p, pad_t), lambda i: (i, 0), memory_space=pltpu.VMEM
            )
        )
    if has_exclusive:
        # padded pod rows are 0.0 (non-exclusive) and invalid anyway
        operands.append(pad(inputs.pod_exclusive[:, None], pad_p, 1))
        in_specs.append(
            pl.BlockSpec(
                (tile_p, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
            )
        )

    n_tiles = pad_p // tile_p
    grid = (n_tiles,)

    assigned2d, hist, demand = pl.pallas_call(
        partial(
            _kernel,
            buckets=buckets,
            n_resources=n_resources,
            has_forbidden=has_forbidden,
            has_score=has_score,
            has_exclusive=has_exclusive,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (tile_p, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (pad_t, buckets), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (pad_t, n_resources), lambda i: (0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pad_p, 1), jnp.int32),
            jax.ShapeDtypeStruct((pad_t, buckets), jnp.float32),
            jax.ShapeDtypeStruct((pad_t, n_resources), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * pad_p * pad_t * (pad_k + pad_l + buckets + n_resources),
            bytes_accessed=4
            * (
                pad_p * (n_resources + pad_k + pad_l + 2)
                + pad_t * (n_resources + pad_k + pad_l + buckets)
            ),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)

    assigned = assigned2d.reshape(-1)[:n_pods]
    # padded groups are index >= n_groups and never win the min-index
    # reduction, so clipping the accumulators is a pure slice
    hist = lax.round(hist[:n_groups]).astype(jnp.int32)
    demand = demand[:n_groups]
    return assigned, hist, demand


@partial(jax.jit, static_argnames=("buckets", "tile_p", "interpret"))
def binpack_pallas(
    inputs: BinPackInputs,
    buckets: int = 32,
    tile_p: int = DEFAULT_TILE_P,
    interpret: bool = False,
):
    """Full bin-pack via the fused Pallas stage + the shared XLA tail.

    Same contract as ops/binpack.binpack (BinPackOutputs); tests pin the two
    backends equal element-for-element.
    """
    from karpenter_tpu.ops.binpack import BinPackOutputs, _shelf_bfd

    assigned, hist, demand = fused_assign(
        inputs, buckets=buckets, tile_p=tile_p, interpret=interpret
    )
    assigned_count = jnp.sum(hist, axis=1)
    nodes_needed = _shelf_bfd(hist, buckets)
    alloc = inputs.group_allocatable
    per_resource = jnp.where(
        alloc > 0,
        jnp.ceil(demand / jnp.maximum(alloc, 1e-30) - 1e-5),
        0.0,
    )
    lp_bound = jnp.max(per_resource, axis=1).astype(jnp.int32)
    unsched_mask = ((assigned < 0) & inputs.pod_valid).astype(jnp.int32)
    unschedulable = jnp.sum(
        unsched_mask
        if inputs.pod_weight is None
        else unsched_mask * inputs.pod_weight,
        dtype=jnp.int32,
    )
    return BinPackOutputs(
        assigned=assigned,
        assigned_count=assigned_count,
        nodes_needed=nodes_needed,
        lp_bound=lp_bound,
        unschedulable=unschedulable,
    )


def default_interpret() -> bool:
    """Compiled Mosaic on the TPU, the interpreter on the CPU (tests).
    Any other platform raises: interpreting there would pass for the
    compiled kernel while running orders of magnitude slower."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"no Pallas binpack mode for platform {platform!r}: the "
            "kernel compiles for 'tpu' and interprets on 'cpu' only"
        )
    return platform == "cpu"


def pallas_available() -> bool:
    try:
        import jax.experimental.pallas  # noqa: F401

        return True
    except Exception:  # noqa: BLE001 — availability probe only
        return False


__all__ = [
    "fused_assign",
    "binpack_pallas",
    "default_interpret",
    "pallas_available",
    "DEFAULT_TILE_P",
]
