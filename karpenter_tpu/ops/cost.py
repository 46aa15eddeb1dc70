"""Batched multi-objective cost x SLO-risk refinement of fleet decisions.

The decision kernel (ops/decision.py) answers "how many replicas does
the observed load need" — cost is invisible and SLO risk is implicit in
the metric targets. This kernel is the second half of a multi-objective
solve (docs/cost.md, PAPERS.md "An SLO Driven and Cost-Aware Autoscaling
Framework for Kubernetes"): given the whole fleet's base decisions, it
evaluates K candidate replica counts per autoscaler IN ONE array program
and picks, per row, the count minimizing

    score(n) = violationCostWeight * risk(n)  +  n * unitHourlyCost

where risk(n) is the normalized one-sigma demand shortfall — the
fraction of pessimistic demand (forecast mean + one forecast sigma, the
PR 5 forecast distribution as the risk input; observed value with sigma
0 when no forecast) that n replicas' SLO capacity (n * sloTarget) cannot
absorb, maxed over the autoscaler's metrics. A hard budget
(spec.behavior.slo.maxHourlyCost) caps candidates at the affordable
replica ceiling (never below minReplicas — the budget trims headroom,
it must not take a workload below its declared floor).

Wire-compat contract (property-pinned in tests/test_cost.py): a row
whose slo_valid is False — no spec.behavior.slo — comes out EXACTLY as
it went in, and a valid row with violationCostWeight 0 and no budget cap
scores minimal at candidate 0 (ties break to the first index), so
absent/zero cost operands reproduce today's decisions bit-identically.

Parity contract (pinned bit-for-bit by tests/test_cost.py): the jitted
kernel and `cost_numpy` produce IDENTICAL f32 bits, the same discipline
as forecast/models.py — the one multiply-accumulate (the score line) is
written in single-mul `a * b + c` form, which XLA:CPU contracts into one
FMA, reproduced on host by a float64 round-trip; every other operation
(mul, div, ceil, floor, clip, max, argmin-first-index) is IEEE-exact
elementwise on both sides, and the only reduction (max over the metric
axis) is order-free.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_tpu.ops import ieee
from karpenter_tpu.ops.decision import _I32_SAFE_MAX, _I32_SAFE_MIN

# Candidate ladder width: each row scores replica counts
# base..base+CANDIDATES-1 (clipped to bounds and the budget cap). Static
# so the whole fleet stays one compiled program; 8 covers a one-sigma
# demand excursion of 8 replicas per tick — larger jumps converge over
# consecutive ticks exactly like the reactive path does.
CANDIDATES = 8

_EPS = np.float32(1e-6)
_ZERO = np.float32(0.0)
_ONE = np.float32(1.0)


@jax.tree_util.register_dataclass
@dataclass
class CostInputs:
    """Structure-of-arrays cost/SLO view of the fleet, padded to the
    decision kernel's row bucket (rows beyond the live fleet carry
    slo_valid=False and pass through untouched)."""

    base_desired: jax.Array  # i32[N] the decide() output being refined
    min_replicas: jax.Array  # i32[N]
    max_replicas: jax.Array  # i32[N]
    unit_cost: jax.Array  # f32[N] hourly cost per replica (0 = unknown)
    slo_weight: jax.Array  # f32[N] violationCostWeight ($/h at risk 1.0)
    max_hourly_cost: jax.Array  # f32[N] hard budget (0 = uncapped)
    slo_valid: jax.Array  # bool[N] row carries spec.behavior.slo
    slo_target: jax.Array  # f32[N, M] per-replica SLO capacity per metric
    demand_mu: jax.Array  # f32[N, M] demand point (forecast or observed)
    demand_sigma: jax.Array  # f32[N, M] forecast spread (0 = none)
    demand_valid: jax.Array  # bool[N, M]


@jax.tree_util.register_dataclass
@dataclass
class CostOutputs:
    desired: jax.Array  # i32[N] multi-objective choice (== base when !valid)
    expected_hourly: jax.Array  # f32[N] desired * unit_cost
    violation_risk: jax.Array  # f32[N] risk at the chosen count
    headroom: jax.Array  # i32[N] one-sigma demand replicas beyond desired
    cost_limited: jax.Array  # bool[N] budget capped below the base desire
    slo_raised: jax.Array  # bool[N] risk term bought replicas above base


def _to_i32(x: jax.Array) -> jax.Array:
    return jnp.clip(
        x, jnp.float32(_I32_SAFE_MIN), jnp.float32(_I32_SAFE_MAX)
    ).astype(jnp.int32)


def cost_decide(inputs: CostInputs) -> CostOutputs:
    """The batched refinement program (module docstring)."""
    base = inputs.base_desired.astype(jnp.float32)  # [N]
    min_f = inputs.min_replicas.astype(jnp.float32)
    max_f = inputs.max_replicas.astype(jnp.float32)

    # candidate replica counts: base + 0..K-1, bounded by [min, max] and
    # the affordable ceiling floor(maxHourlyCost / unitCost) — the
    # budget never forces a row below its minReplicas floor
    offsets = jnp.arange(CANDIDATES, dtype=jnp.float32)  # [K]
    cap_on = (
        inputs.slo_valid
        & (inputs.unit_cost > 0)
        & (inputs.max_hourly_cost > 0)
    )
    safe_unit = jnp.where(inputs.unit_cost > 0, inputs.unit_cost, _ONE)
    cap = jnp.floor(ieee.divide(inputs.max_hourly_cost, safe_unit))
    hi = jnp.where(cap_on, jnp.minimum(max_f, jnp.maximum(cap, min_f)), max_f)
    cand = jnp.clip(
        base[:, None] + offsets[None, :], min_f[:, None], hi[:, None]
    )  # [N, K]

    # one-sigma pessimistic demand vs candidate SLO capacity, as a
    # normalized shortfall fraction in [0, 1], maxed over valid metrics
    demand_hi = inputs.demand_mu + inputs.demand_sigma  # [N, M]
    capacity = cand[:, :, None] * inputs.slo_target[:, None, :]  # [N, K, M]
    denom = jnp.maximum(demand_hi, _EPS)[:, None, :]
    short = jnp.clip(
        ieee.divide(demand_hi[:, None, :] - capacity, denom), _ZERO, _ONE
    )
    short = jnp.where(inputs.demand_valid[:, None, :], short, _ZERO)
    risk = jnp.max(short, axis=2)  # [N, K]

    # the multi-objective score (single-mul FMA form — module docstring)
    hourly = cand * inputs.unit_cost[:, None]  # [N, K]
    score = inputs.slo_weight[:, None] * risk + hourly

    # argmin ties break to the FIRST (cheapest) candidate on both jnp
    # and np — the wire-compat anchor: weight 0 scores flat-or-rising,
    # so candidate 0 (the base decision) wins exactly
    k_star = jnp.argmin(score, axis=1)  # [N]
    take = lambda a: jnp.take_along_axis(a, k_star[:, None], axis=1)[:, 0]
    chosen = take(cand)
    chosen_risk = take(risk)

    # warm-pool sizing signal (docs/cost.md "Warm pools"): how many
    # replicas the one-sigma demand needs BEYOND the chosen count —
    # pre-provisioned headroom sized by forecast risk
    needed = jnp.ceil(
        ieee.divide(demand_hi, jnp.maximum(inputs.slo_target, _EPS))
    )
    needed = jnp.where(inputs.demand_valid, needed, _ZERO)
    headroom = jnp.maximum(jnp.max(needed, axis=1) - chosen, _ZERO)

    valid = inputs.slo_valid
    desired = jnp.where(valid, chosen, base)
    return CostOutputs(
        desired=_to_i32(desired),
        expected_hourly=desired * inputs.unit_cost,
        violation_risk=jnp.where(valid, chosen_risk, _ZERO),
        headroom=_to_i32(jnp.where(valid, headroom, _ZERO)),
        cost_limited=cap_on & (base > hi),
        slo_raised=valid & (chosen > base),
    )


cost_jit = jax.jit(cost_decide)


# -- numpy mirror -------------------------------------------------------------
# The parity oracle AND the requested-numpy backend (CPU auto-resolution,
# the gRPC process split) — every line mirrors the kernel's op order;
# _fma reproduces XLA:CPU's mul-add contraction exactly
# (forecast/models.py discipline).


def _fma(a, b, c):
    return (
        np.asarray(a, np.float64) * np.asarray(b, np.float64)
        + np.asarray(c, np.float64)
    ).astype(np.float32)


def cost_numpy(inputs: CostInputs) -> CostOutputs:
    """Host mirror of cost_decide() — bit-identical f32 outputs (module
    docstring parity contract)."""
    base = np.asarray(inputs.base_desired, np.int32).astype(np.float32)
    min_f = np.asarray(inputs.min_replicas, np.int32).astype(np.float32)
    max_f = np.asarray(inputs.max_replicas, np.int32).astype(np.float32)
    unit = np.asarray(inputs.unit_cost, np.float32)
    weight = np.asarray(inputs.slo_weight, np.float32)
    budget = np.asarray(inputs.max_hourly_cost, np.float32)
    valid = np.asarray(inputs.slo_valid, bool)
    slo_target = np.asarray(inputs.slo_target, np.float32)
    mu = np.asarray(inputs.demand_mu, np.float32)
    sigma = np.asarray(inputs.demand_sigma, np.float32)
    dvalid = np.asarray(inputs.demand_valid, bool)

    offsets = np.arange(CANDIDATES, dtype=np.float32)
    cap_on = valid & (unit > 0) & (budget > 0)
    safe_unit = np.where(unit > 0, unit, _ONE).astype(np.float32)
    cap = np.floor(budget / safe_unit).astype(np.float32)
    hi = np.where(
        cap_on, np.minimum(max_f, np.maximum(cap, min_f)), max_f
    ).astype(np.float32)
    cand = np.clip(
        base[:, None] + offsets[None, :], min_f[:, None], hi[:, None]
    ).astype(np.float32)

    demand_hi = (mu + sigma).astype(np.float32)
    denom = np.maximum(demand_hi, _EPS)[:, None, :].astype(np.float32)
    # demand_hi - cand*slo_target: XLA:CPU contracts the subtract-of-a-
    # product into one negated FMA, mirrored by the f64 round-trip
    # (_fma broadcasts like the kernel's [N,K,1] x [N,1,M] operands)
    shortfall = _fma(
        -cand[:, :, None], slo_target[:, None, :], demand_hi[:, None, :]
    )
    short = np.clip((shortfall / denom).astype(np.float32), _ZERO, _ONE)
    short = np.where(dvalid[:, None, :], short, _ZERO).astype(np.float32)
    risk = np.max(short, axis=2)

    hourly = (cand * unit[:, None]).astype(np.float32)
    score = _fma(weight[:, None], risk, hourly)

    k_star = np.argmin(score, axis=1)
    rows = np.arange(len(base))
    chosen = cand[rows, k_star]
    chosen_risk = risk[rows, k_star]

    needed = np.ceil(
        (demand_hi / np.maximum(slo_target, _EPS)).astype(np.float32)
    ).astype(np.float32)
    needed = np.where(dvalid, needed, _ZERO).astype(np.float32)
    headroom = np.maximum(np.max(needed, axis=1) - chosen, _ZERO)

    desired = np.where(valid, chosen, base).astype(np.float32)

    def to_i32(x):
        return np.clip(
            x, np.float32(_I32_SAFE_MIN), np.float32(_I32_SAFE_MAX)
        ).astype(np.int32)

    return CostOutputs(
        desired=to_i32(desired),
        expected_hourly=(desired * unit).astype(np.float32),
        violation_risk=np.where(valid, chosen_risk, _ZERO).astype(np.float32),
        headroom=to_i32(np.where(valid, headroom, _ZERO)),
        cost_limited=cap_on & (base > hi),
        slo_raised=valid & (chosen > base),
    )
