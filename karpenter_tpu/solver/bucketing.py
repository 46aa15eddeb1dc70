"""Shape bucketing for the shared solve service.

Every distinct operand shape is a distinct XLA program: a fleet whose
pending-pod count wanders 9,812 → 10,407 → 9,955 across ticks would
recompile the bin-pack on every tick if requests were dispatched at
their natural sizes. The encoder already pads to coarse multiples
(producers/pendingcapacity/constants.py), but other callers — the
sidecar's wire requests, simulate, bench — arrive at arbitrary shapes,
and even encoder-padded shapes step at every +256 pods.

The service therefore rounds every axis UP a power-of-two-ish ladder
(1, 1.5, 2, 3, 4, 6, 8, ... × floor): consecutive rungs are ≤ 1.5×
apart, so padding waste is bounded at 50% (33% amortized) while the
number of distinct compiled shapes for traffic in [floor, N] is
O(log N), not O(N). Steady-state traffic whose sizes jitter inside one
rung hits the same compiled program forever — zero recompiles after
warmup, which is what turns the 20–40 s TPU compile from a per-tick
hazard into a once-per-deployment cost.

Padding is SEMANTICS-PRESERVING by construction (the same argument the
encoder's own padding rests on):

  * extra pod rows: valid=False, weight=0 — excluded from assignment,
    every aggregate they touch adds exact zeros;
  * extra group columns: zero allocatable — `_feasibility` rejects them
    outright, so no pod is ever assigned to a padding group and their
    output rows are sliced off before results scatter back;
  * extra taint/label bits: zero on both sides of the bitset matmuls —
    they contribute nothing to either violation count.

Integer outputs (assigned, counts, node totals, unschedulable) are
therefore EQUAL to the unpadded solve, not merely close; the float
intermediate (the LP-bound einsum) only gains exactly-zero terms.
tests/test_solver_service.py pins service outputs against direct
ops/binpack calls element for element.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from karpenter_tpu.ops.binpack import BinPackInputs

# Axis floors: the smallest bucket on each ladder. Pods/groups mirror the
# encoder's pads (a fleet encoded at POD_PAD multiples lands exactly on a
# rung for small fleets); constraint universes mirror their pad constants.
POD_FLOOR = 256
GROUP_FLOOR = 8
TAINT_FLOOR = 32
LABEL_FLOOR = 64
RESOURCE_FLOOR = 4
# Coalesced batches are padded up this ladder too (1, 2, 3, 4, 6, 8, ...)
# so the number of distinct batched programs stays logarithmic in the
# coalesce cap.
BATCH_FLOOR = 1
# Constraint-plane axes (pack classes C, spread slots S, spread domains
# D) ride their own tiny ladders: real fleets have a handful of
# anti-affinity groups and zones, so a floor of 2 keeps the rung count
# in the low single digits.
CLASS_FLOOR = 2
SLOT_FLOOR = 2
DOMAIN_FLOOR = 2


def bucket_up(n: int, floor: int) -> int:
    """Round `n` up to the next rung of the {1, 1.5} × 2^k ladder above
    `floor` (floor, 1.5·floor, 2·floor, 3·floor, 4·floor, ...)."""
    if n <= floor:
        return floor
    rung = floor
    while True:
        if n <= rung:
            return rung
        if n <= rung + rung // 2:
            return rung + rung // 2
        rung *= 2


def bucket_shape(inputs: BinPackInputs) -> Tuple[int, int, int, int, int]:
    """(P, T, R, K, L) rounded up their ladders — the shape half of the
    compile-cache key."""
    p, r = inputs.pod_requests.shape
    t = inputs.group_allocatable.shape[0]
    k = inputs.pod_intolerant.shape[1]
    l = inputs.pod_required.shape[1]
    return (
        bucket_up(p, POD_FLOOR),
        bucket_up(t, GROUP_FLOOR),
        bucket_up(r, RESOURCE_FLOOR),
        bucket_up(k, TAINT_FLOOR),
        bucket_up(l, LABEL_FLOOR),
    )


def constraint_shape(inputs: BinPackInputs) -> Tuple[int, ...]:
    """(C, S, D) constraint-plane axes rounded up their ladders — joins
    the compile-cache key beside bucket_shape. Returns () when no
    shape-bearing constraint operand rides the request, so
    constraint-free traffic keeps a compact key. Padding these axes is
    inert by construction: all-false pack-class columns contribute empty
    histograms, appended zero-cap domains never change the first-fit
    target, and padded cap rows are never referenced (slot <= S_real)."""
    pc = inputs.pod_pack_class
    caps = inputs.spread_cap
    if pc is None and caps is None:
        return ()
    c = 0 if pc is None else bucket_up(pc.shape[1], CLASS_FLOOR)
    s = 0 if caps is None else bucket_up(caps.shape[0], SLOT_FLOOR)
    d = 0 if caps is None else bucket_up(caps.shape[1], DOMAIN_FLOOR)
    return (c, s, d)


def mesh_aligned_shape(
    shape: Tuple[int, int, int, int, int], extents: Tuple[int, int]
) -> Tuple[int, int, int, int, int]:
    """Grow a bucket shape's pod/group axes to the mesh-divisible
    multiples GSPMD requires (extents = parallel.mesh.mesh_extents).
    Constraint-universe axes are replicated on the mesh and stay on
    their own ladders. The result is a deterministic function of
    (bucket shape, extents), so the sharded compile-cache key only
    needs to carry the extents — same-rung traffic still never
    recompiles."""
    from karpenter_tpu.utils.functional import pad_to_multiple

    p, t, r, k, l = shape
    rows, cols = extents
    return (pad_to_multiple(p, rows) if rows > 1 else p,
            pad_to_multiple(t, cols) if cols > 1 else t,
            r, k, l)


def _rung_part(part) -> str:
    """One compile-cache key element, compactly: bool tuples
    (presence) as a 10-string, int tuples (shapes, extents) as
    PxTx..., everything else via str()."""
    if not isinstance(part, tuple) or not part:
        return str(part)
    if all(isinstance(x, bool) for x in part):
        return "".join("1" if x else "0" for x in part)
    if all(isinstance(x, int) for x in part):
        return "x".join(str(x) for x in part)
    return str(part)


def rung_label(key: tuple) -> str:
    """Human-readable rung of one compile-cache key, for telemetry —
    the compile ledger and /debug/solver render cache keys through
    this one formatter (observability/devicetelemetry.py). Unknown
    key vocabularies degrade to repr() rather than raise: a telemetry
    label must never break the dispatch it describes."""
    try:
        return "/".join(_rung_part(part) for part in key)
    except Exception:  # noqa: BLE001 — labels are best-effort
        return repr(key)


def presence(inputs: BinPackInputs) -> Tuple[bool, ...]:
    """Which optional operands ride this request — the other half of the
    compile-cache key (an absent operand removes whole program stages)."""
    return (
        inputs.pod_weight is not None,
        inputs.pod_group_forbidden is not None,
        inputs.pod_group_score is not None,
        inputs.pod_exclusive is not None,
        inputs.pod_priority is not None,
        inputs.group_tier is not None,
        inputs.pod_claim is not None,
        inputs.group_reservation is not None,
        inputs.pod_pack_class is not None,
        inputs.pod_spread_slot is not None,
        inputs.group_domain is not None,
        inputs.spread_cap is not None,
    )


def _pad2(a, rows: int, cols: Optional[int] = None,
          out: Optional["PadStaging"] = None, name: str = ""):
    """Zero-pad a 1-D/2-D array up to (rows[, cols]); the same object is
    returned when no padding is needed so already-bucketed traffic (the
    encoder's steady state) keeps identity-based device caches warm.
    With `out`, the padded array is the staging area's reused buffer for
    operand `name`, zeroed beyond the array's extent as a fresh one is."""
    a = np.asarray(a)
    shape = (rows,) if a.ndim == 1 else (rows, cols)
    if a.shape == shape:
        return a
    if out is None:
        padded = np.zeros(shape, a.dtype)
    else:
        padded, stale = out.take(name, shape, a.dtype, a.shape)
        for region in stale:
            padded[region] = 0
    padded[tuple(slice(0, n) for n in a.shape)] = a
    return padded


def _beyond(last: Tuple[int, ...], now: Tuple[int, ...]) -> list:
    """The part of extent `last` outside extent `now`, as disjoint index
    regions, one for each axis on which `last` reaches further."""
    regions = []
    for axis, (was, new) in enumerate(zip(last, now)):
        if was > new:
            regions.append(
                tuple(slice(0, min(w, n)) for w, n in zip(last[:axis], now[:axis]))
                + (slice(new, was),)
                + tuple(slice(0, w) for w in last[axis + 1:])
            )
    return regions


class PadStaging:
    """A host staging area that `pad_to_bucket(..., out=area)` pads into:
    one buffer for each padded operand, allocated on first use and reused
    by every later pad. Each buffer remembers the extent its last write
    covered, so a smaller request after a larger one re-zeroes only what
    the larger one wrote beyond the new extent: the padding holds zeros
    because the area keeps it zero, not because the memory is new. A pad
    into the area overwrites the arrays the previous pad returned, so
    the caller must be done with them (copied to the device, the solve
    waited on) before the next."""

    __slots__ = ("_buffers",)

    def __init__(self):
        self._buffers: dict = {}  # name -> (buffer, extent last written)

    def take(self, name: str, shape: tuple, dtype, extent: tuple):
        """(the buffer for operand `name`, the regions of it the last
        write reached beyond `extent`), recording `extent` as written."""
        held = self._buffers.get(name)
        if held is None or held[0].shape != shape or held[0].dtype != dtype:
            buffer, stale = np.zeros(shape, dtype), []
        else:
            buffer, stale = held[0], _beyond(held[1], extent)
        self._buffers[name] = (buffer, extent)
        return buffer, stale


# The padded axes of each operand: p pods, t groups, r resources, k
# taints, l labels, and the constraint plane's c classes, s slots, d
# domains. Every pad is zero (module docstring): extra rows are invalid
# with zero weight, priority, claim and slot; extra groups have zero
# allocatable, tier, reservation and domain; extra taint, label and
# pack-class bits are unset; extra class, slot and domain columns are
# inert (constraint_shape).
_OPERAND_AXES = {
    "pod_requests": "pr",
    "pod_valid": "p",
    "pod_intolerant": "pk",
    "pod_required": "pl",
    "group_allocatable": "tr",
    "group_taints": "tk",
    "group_labels": "tl",
    "pod_weight": "p",
    "pod_group_forbidden": "pt",
    "pod_group_score": "pt",
    "pod_exclusive": "p",
    "pod_priority": "p",
    "group_tier": "t",
    "pod_claim": "p",
    "group_reservation": "t",
    "pod_pack_class": "pc",
    "pod_spread_slot": "p",
    "group_domain": "t",
    "spread_cap": "sd",
}


def _padded_sizes(inputs: BinPackInputs, shape) -> dict:
    p, t, r, k, l = shape
    sizes = dict(p=p, t=t, r=r, k=k, l=l)
    if inputs.pod_pack_class is not None:
        sizes["c"] = bucket_up(inputs.pod_pack_class.shape[1], CLASS_FLOOR)
    if inputs.spread_cap is not None:
        sizes["s"] = bucket_up(inputs.spread_cap.shape[0], SLOT_FLOOR)
        sizes["d"] = bucket_up(inputs.spread_cap.shape[1], DOMAIN_FLOOR)
    return sizes


def is_bucketed(inputs: BinPackInputs, shape) -> bool:
    """Whether `inputs` already has the bucket `shape`, so that
    pad_to_bucket hands them back as they are."""
    sizes = _padded_sizes(inputs, shape)
    return all(
        getattr(inputs, name) is None
        or tuple(getattr(inputs, name).shape)
        == tuple(sizes[axis] for axis in _OPERAND_AXES[name])
        for name in ("pod_requests", "group_allocatable", "pod_intolerant",
                     "pod_required", "pod_pack_class", "spread_cap")
    )


def _dtype(a):
    return a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype


def staging_key(inputs: BinPackInputs, shape) -> tuple:
    """The PadStaging area `inputs` pad into at the bucket `shape`: one
    for each bucket shape, presence tuple and set of operand dtypes.
    The natural shape is left out, so sizes that jitter inside one rung
    share one area."""
    dtypes = tuple(
        _dtype(a) for a in (getattr(inputs, n) for n in _OPERAND_AXES)
        if a is not None
    )
    return (tuple(shape), presence(inputs), dtypes)


def pad_to_bucket(
    inputs: BinPackInputs, shape: Tuple[int, int, int, int, int],
    out: Optional[PadStaging] = None,
) -> BinPackInputs:
    """Pad every operand to the bucket `shape` (see module docstring for
    why this is exact). Returns `inputs` unchanged when already there.
    With `out`, the padded operands are that staging area's buffers, equal
    element for element to the fresh arrays a pad without it returns, in
    a new BinPackInputs on every call."""
    if is_bucketed(inputs, shape):
        return inputs
    sizes = _padded_sizes(inputs, shape)
    fields = {}
    for name, axes in _OPERAND_AXES.items():
        a = getattr(inputs, name)
        if a is not None:
            a = _pad2(a, *(sizes[axis] for axis in axes), out=out, name=name)
        fields[name] = a
    return BinPackInputs(**fields)


# -- eviction-planning (ops/preempt.py) shape ladder --------------------------
# Candidate counts are preemption-scale (a handful of high-priority
# pending pods), victim counts are occupancy-scale; each gets its own
# floor so both single-candidate probes and fleet-wide storms land on
# stable rungs.
CANDIDATE_FLOOR = 8
VICTIM_FLOOR = 64


def preempt_bucket_shape(inputs) -> Tuple[int, int, int, int]:
    """(C, N, R, V) rounded up their ladders — the shape half of the
    preempt compile-cache key."""
    c, r = inputs.pod_requests.shape
    n = inputs.node_free.shape[0]
    v = inputs.victim_requests.shape[0]
    return (
        bucket_up(c, CANDIDATE_FLOOR),
        bucket_up(n, GROUP_FLOOR),
        bucket_up(r, RESOURCE_FLOOR),
        bucket_up(v, VICTIM_FLOOR),
    )


def pad_preempt_inputs(inputs, shape: Tuple[int, int, int, int]):
    """Zero-pad a PreemptInputs up to the bucket `shape`, semantics-
    preserving: padding candidates are invalid (excluded from every
    aggregate), padding node columns are zero-free AND forbidden for
    every candidate (never chosen), padding victims are invalid +
    zero-request with the LAST node column (the sorted-victim contract
    survives) and contribute nothing to prefix sums or maxima."""
    from karpenter_tpu.ops.preempt import PreemptInputs

    c, n, r, v = shape
    if (
        inputs.pod_requests.shape == (c, r)
        and inputs.node_free.shape == (n, r)
        and inputs.victim_requests.shape == (v, r)
    ):
        return inputs
    c0, n0, v0 = (
        inputs.pod_requests.shape[0],
        inputs.node_free.shape[0],
        inputs.victim_requests.shape[0],
    )
    forbidden = np.ones((c, n), bool)
    forbidden[:c0, :n0] = inputs.pod_node_forbidden
    victim_node = np.full(v, n - 1, np.int32)
    victim_node[:v0] = np.asarray(inputs.victim_node, np.int32)
    return PreemptInputs(
        pod_requests=_pad2(inputs.pod_requests, c, r),
        pod_priority=_pad2(inputs.pod_priority, c),
        pod_valid=_pad2(inputs.pod_valid, c),
        pod_node_forbidden=forbidden,
        node_free=_pad2(inputs.node_free, n, r),
        node_tier=_pad2(inputs.node_tier, n),
        victim_requests=_pad2(inputs.victim_requests, v, r),
        victim_priority=_pad2(inputs.victim_priority, v),
        victim_node=victim_node,
        victim_valid=_pad2(inputs.victim_valid, v),
        victim_evictable=_pad2(inputs.victim_evictable, v),
    )


def crop_preempt_outputs(out, n_candidates: int, n_victims: int):
    """Slice a padded preempt solve back to the true candidate/victim
    axes. Padding nodes are forbidden, so no real candidate's
    chosen_node points past the real columns; padding candidates are
    invalid, so `unplaceable` never counts them."""
    return dataclasses.replace(
        out,
        chosen_node=out.chosen_node[:n_candidates],
        evict_count=out.evict_count[:n_candidates],
        evict_mask=out.evict_mask[:n_candidates, :n_victims],
    )


def crop_outputs(out, n_pods: int, n_groups: int):
    """Slice a padded solve's outputs back to the request's true axes.

    Padding groups are all-infeasible, so no real pod's `assigned` index
    ever points past n_groups; padding pods are invalid, so the scalar
    `unschedulable` never counts them. Host numpy in, host numpy out."""
    return dataclasses.replace(
        out,
        assigned=out.assigned[:n_pods],
        assigned_count=out.assigned_count[:n_groups],
        nodes_needed=out.nodes_needed[:n_groups],
        lp_bound=out.lp_bound[:n_groups],
    )
