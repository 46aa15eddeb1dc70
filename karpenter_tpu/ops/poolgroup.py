"""Joint candidate-ladder allocation for PoolGroups — coordinated
heterogeneous / disaggregated scaling as ONE batched device program.

The cost kernel (ops/cost.py) refines each autoscaler in isolation: a
K-candidate ladder per row, argmin of risk-vs-cost per row. Serving
workloads split into interdependent pools (prefill vs decode, router vs
worker) need the refinement to be JOINT — "Taming the Chaos" (PAPERS.md)
shows per-pool loops oscillate and strand capacity because each pool's
optimum ignores the ratio the workload actually needs. This kernel
generalizes the candidate ladder to the PRODUCT of the member pools'
ladders: for every group of P pools it enumerates all K^P joint
candidates (mixed-radix digits over the per-pool K=8 ladders), scores
each pool's digit with EXACTLY the cost kernel's op sequence, and adds
exact-integer penalty operands for the group's declared constraints:

- cross-pool ratio bands (decode:prefill in [2:1, 4:1]) — integer
  cross-multiplication, no division, bit-exact on both backends
- a shared group budget cap (sum of pool spends vs maxHourlyCost)

Selection is two-level, which makes the wire-compat pin exact BY
CONSTRUCTION instead of probabilistically: first each pool's INDEPENDENT
argmin is computed exactly as cost_decide computes it; if that joint
point violates nothing, it IS the answer (so slack constraints reproduce
the uncoordinated fixed point bit for bit — a float argmin over summed
scores could not promise that: a strictly larger addend can round to an
equal sum at a smaller index and steal the tie-break). Only when the
independent point violates a constraint does the repair argmin engage:
fewest violations, then cheapest joint score, then first index.

Capacity-tier preference folds into the objective as a per-pool
`tierPenalty` added to the hourly rate (score only — the budget cap
stays in real dollars); a penalty of 0.0 adds f32 zero to a
non-negative rate, bit-identical to the cost kernel's term, so the
joint == independent parity pin holds whenever penalties are absent.

Parity contract (pinned bitwise in tests/test_poolgroup.py, the
ops/cost.py discipline): the jitted kernel and `poolgroup_numpy`
produce IDENTICAL bits on every output leaf. The two multiply-
accumulates (per-pool score, group spend accumulation) are written in
single-mul `a * b + c` form — XLA:CPU contracts each into one FMA,
reproduced on host by a float64 round-trip; the joint score total and
the spend are accumulated pool-by-pool in UNROLLED static order
(identical add order on both sides); every violation operand is exact
int32; both argmins break ties to the first index on both backends.

Pool and ratio axes are padded to static buckets (pad pools carry
base=min=max=unit=weight=0, scoring 0 at every candidate — inert in
every sum and argmin) so steady fleets never recompile.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_tpu.ops import ieee
from karpenter_tpu.ops.cost import CANDIDATES, _EPS, _ONE, _ZERO, _fma
from karpenter_tpu.ops.decision import _I32_SAFE_MAX, _I32_SAFE_MIN

# Pool-count ceiling per group and the static pool-axis buckets: the
# joint candidate space is K^P, so P is hard-bounded (4 pools x K=8 is
# 4096 joint candidates — one gather-heavy but small program) and padded
# to 2 or 4 to keep compiled shapes stable as groups gain a pool.
MAX_POOLS = 4
POOL_BUCKETS = (2, 4)

# Ratio-constraint slots per group (static axis; unused slots are
# ratio_valid=False and integer-self-disabling — see _violations).
RATIO_SLOTS = 4

# Ratio numerators/denominators are bounded so the int32 cross products
# n * den can never overflow: counts up to ~2M replicas stay exact.
RATIO_BOUND = 1024

_INF = np.float32(np.inf)


def pad_pool_count(pools: int) -> int:
    """The static pool-axis bucket for a fleet whose widest group has
    `pools` members (compile-key stability: 2 covers the common
    prefill/decode pair, 4 everything the validator admits)."""
    for bucket in POOL_BUCKETS:
        if pools <= bucket:
            return bucket
    raise ValueError(f"pool groups support at most {MAX_POOLS} pools")


def joint_digits(pools: int) -> np.ndarray:
    """i32[P, K^P] mixed-radix digit matrix: digits[p, c] is pool p's
    ladder index within joint candidate c. A host constant folded into
    the compiled program (pure function of the static pool bucket)."""
    c = CANDIDATES ** pools
    return (
        (np.arange(c)[None, :] // (CANDIDATES ** np.arange(pools)[:, None]))
        % CANDIDATES
    ).astype(np.int32)


@jax.tree_util.register_dataclass
@dataclass
class PoolGroupInputs:
    """Structure-of-arrays joint view of every PoolGroup: G groups x P
    pools (both padded to static buckets) x M metrics. Per-pool fields
    carry exactly what the cost kernel sees for that pool's row;
    movement bounds (min/max_replicas) arrive PRE-CLAMPED to each HA's
    rate-limited movement interval (the engine's job, the CostEngine
    discipline), so the joint choice can never outrun a pool's scaling
    policies."""

    base_desired: jax.Array  # i32[G, P] the decide() output per pool
    min_replicas: jax.Array  # i32[G, P] movement-clamped floor
    max_replicas: jax.Array  # i32[G, P] movement-clamped ceiling
    unit_cost: jax.Array  # f32[G, P] hourly cost per replica (0 = unknown)
    slo_weight: jax.Array  # f32[G, P] violationCostWeight per pool
    max_hourly_cost: jax.Array  # f32[G, P] per-pool budget (0 = uncapped)
    tier_penalty: jax.Array  # f32[G, P] capacity-tier score penalty ($/h)
    pool_valid: jax.Array  # bool[G, P] slot holds a live member pool
    slo_target: jax.Array  # f32[G, P, M] per-replica SLO capacity
    demand_mu: jax.Array  # f32[G, P, M] demand point (forecast/observed)
    demand_sigma: jax.Array  # f32[G, P, M] forecast spread (0 = none)
    demand_valid: jax.Array  # bool[G, P, M]
    ratio_a: jax.Array  # i32[G, R] numerator pool index per ratio slot
    ratio_b: jax.Array  # i32[G, R] denominator pool index
    ratio_min_num: jax.Array  # i32[G, R] lower band: a/b >= min_num/min_den
    ratio_min_den: jax.Array  # i32[G, R]
    ratio_max_num: jax.Array  # i32[G, R] upper band: a/b <= max_num/max_den
    ratio_max_den: jax.Array  # i32[G, R] (0/0 = no upper bound)
    ratio_valid: jax.Array  # bool[G, R]
    group_budget: jax.Array  # f32[G] shared maxHourlyCost (0 = uncapped)
    group_valid: jax.Array  # bool[G]


@jax.tree_util.register_dataclass
@dataclass
class PoolGroupOutputs:
    desired: jax.Array  # i32[G, P] joint choice (== base when pool invalid)
    expected_hourly: jax.Array  # f32[G, P] desired * unit_cost
    violation_risk: jax.Array  # f32[G, P] SLO risk at the chosen count
    headroom: jax.Array  # i32[G, P] one-sigma demand beyond desired
    cost_limited: jax.Array  # bool[G, P] per-pool budget capped below base
    slo_raised: jax.Array  # bool[G, P] risk bought replicas above base
    ratio_ok: jax.Array  # bool[G] selected point satisfies every constraint
    group_hourly: jax.Array  # f32[G] summed pool spend at the selection
    joint_repair: jax.Array  # bool[G] coordination moved a pool off its
    #                          independent optimum this tick


def _to_i32(x: jax.Array) -> jax.Array:
    return jnp.clip(
        x, jnp.float32(_I32_SAFE_MIN), jnp.float32(_I32_SAFE_MAX)
    ).astype(jnp.int32)


def poolgroup_decide(
    inputs: PoolGroupInputs, enforce: bool = True
) -> PoolGroupOutputs:
    """The batched joint program (module docstring). `enforce=False` is
    the DEGRADED independent rung the solver-service ladder serves when
    the joint device path is down: the per-pool cost ladders still
    refine every pool (same math, same bits), but the selection is
    pinned to the independent point — ratios and the group budget go
    advisory for the tick (ratio_ok still reports them honestly)."""
    base = inputs.base_desired.astype(jnp.float32)  # [G, P]
    min_f = inputs.min_replicas.astype(jnp.float32)
    max_f = inputs.max_replicas.astype(jnp.float32)
    g, p = base.shape
    c = CANDIDATES ** p
    digits = jnp.asarray(joint_digits(p))  # i32[P, C] host constant

    # -- per-pool half: EXACTLY cost_decide's op sequence, one rank up --
    offsets = jnp.arange(CANDIDATES, dtype=jnp.float32)  # [K]
    cap_on = (
        inputs.pool_valid
        & (inputs.unit_cost > 0)
        & (inputs.max_hourly_cost > 0)
    )
    safe_unit = jnp.where(inputs.unit_cost > 0, inputs.unit_cost, _ONE)
    cap = jnp.floor(ieee.divide(inputs.max_hourly_cost, safe_unit))
    hi = jnp.where(
        cap_on, jnp.minimum(max_f, jnp.maximum(cap, min_f)), max_f
    )
    cand = jnp.clip(
        base[:, :, None] + offsets[None, None, :],
        min_f[:, :, None],
        hi[:, :, None],
    )  # [G, P, K]

    demand_hi = inputs.demand_mu + inputs.demand_sigma  # [G, P, M]
    capacity = cand[:, :, :, None] * inputs.slo_target[:, :, None, :]
    denom = jnp.maximum(demand_hi, _EPS)[:, :, None, :]
    short = jnp.clip(
        ieee.divide(demand_hi[:, :, None, :] - capacity, denom), _ZERO, _ONE
    )
    short = jnp.where(inputs.demand_valid[:, :, None, :], short, _ZERO)
    risk = jnp.max(short, axis=3)  # [G, P, K]

    # tier preference rides the score's hourly rate only (the budget cap
    # above stays real dollars); penalty 0 adds f32 zero to unit >= 0 —
    # bit-identical to the cost kernel's term
    rate = inputs.unit_cost + inputs.tier_penalty
    hourly = cand * rate[:, :, None]  # [G, P, K]
    score = inputs.slo_weight[:, :, None] * risk + hourly

    # each pool's INDEPENDENT first-index argmin — the cost kernel's
    # k_star, the anchor of the two-level selection
    k_star = jnp.argmin(score, axis=2).astype(jnp.int32)  # [G, P]

    # -- joint half: gather ladders into the K^P candidate space --------
    idx = jnp.broadcast_to(digits[None, :, :], (g, p, c))
    cand_j = jnp.take_along_axis(cand, idx, axis=2)  # [G, P, C]
    score_j = jnp.take_along_axis(score, idx, axis=2)
    risk_j = jnp.take_along_axis(risk, idx, axis=2)
    n_j = cand_j.astype(jnp.int32)  # integer-valued f32 by construction

    # joint score and group spend, accumulated in UNROLLED static pool
    # order (the parity contract forbids a reduction whose association
    # the backend may reorder); spend accumulation is single-mul FMA form
    total = score_j[:, 0, :]
    spend = cand_j[:, 0, :] * inputs.unit_cost[:, 0, None]
    for pool in range(1, p):
        total = score_j[:, pool, :] + total
        spend = (
            cand_j[:, pool, :] * inputs.unit_cost[:, pool, None] + spend
        )

    viol = _violations(inputs, n_j, spend, jnp)  # i32[G, C]

    # -- two-level selection --------------------------------------------
    indep_c = k_star[:, 0]
    for pool in range(1, p):
        indep_c = indep_c + k_star[:, pool] * jnp.int32(CANDIDATES ** pool)
    indep_viol = jnp.take_along_axis(viol, indep_c[:, None], axis=1)[:, 0]
    min_viol = jnp.min(viol, axis=1)
    masked_total = jnp.where(viol == min_viol[:, None], total, _INF)
    repair_c = jnp.argmin(masked_total, axis=1).astype(jnp.int32)
    if enforce:
        selected = jnp.where(indep_viol == 0, indep_c, repair_c)
    else:
        selected = indep_c

    sel = jnp.broadcast_to(selected[:, None, None], (g, p, 1))
    chosen = jnp.take_along_axis(cand_j, sel, axis=2)[:, :, 0]  # [G, P]
    chosen_risk = jnp.take_along_axis(risk_j, sel, axis=2)[:, :, 0]
    sel_viol = jnp.take_along_axis(viol, selected[:, None], axis=1)[:, 0]
    sel_spend = jnp.take_along_axis(spend, selected[:, None], axis=1)[:, 0]

    needed = jnp.ceil(
        ieee.divide(demand_hi, jnp.maximum(inputs.slo_target, _EPS))
    )
    needed = jnp.where(inputs.demand_valid, needed, _ZERO)
    headroom = jnp.maximum(jnp.max(needed, axis=2) - chosen, _ZERO)

    valid = inputs.pool_valid
    desired = jnp.where(valid, chosen, base)
    return PoolGroupOutputs(
        desired=_to_i32(desired),
        expected_hourly=desired * inputs.unit_cost,
        violation_risk=jnp.where(valid, chosen_risk, _ZERO),
        headroom=_to_i32(jnp.where(valid, headroom, _ZERO)),
        cost_limited=cap_on & (base > hi),
        slo_raised=valid & (chosen > base),
        ratio_ok=inputs.group_valid & (sel_viol == 0),
        group_hourly=jnp.where(inputs.group_valid, sel_spend, _ZERO),
        joint_repair=inputs.group_valid & (selected != indep_c),
    )


def _violations(inputs, n_j, spend, xp):
    """Exact-i32 constraint-violation count per joint candidate,
    identical op-for-op under `xp` in {jnp, np} (int math only, plus
    one f32 compare for the budget whose operand `spend` the caller
    already computed under the parity discipline).

    Ratio bands compare by integer cross-multiplication — a/b >= lo is
    a*lo_den >= b*lo_num — so a slot with min_num=0 self-disables the
    lower bound (n*den < 0 is false for n >= 0) and max_num=max_den=0
    self-disables the upper (0 > 0 is false): absent bounds need no
    masks, only genuinely invalid slots do."""
    g, p, c = n_j.shape
    viol = xp.zeros((g, c), np.int32)
    for r in range(RATIO_SLOTS):
        a_idx = xp.clip(inputs.ratio_a[:, r], 0, p - 1).astype(np.int32)
        b_idx = xp.clip(inputs.ratio_b[:, r], 0, p - 1).astype(np.int32)
        if xp is jnp:
            n_a = xp.take_along_axis(
                n_j, xp.broadcast_to(a_idx[:, None, None], (g, 1, c)),
                axis=1,
            )[:, 0, :]
            n_b = xp.take_along_axis(
                n_j, xp.broadcast_to(b_idx[:, None, None], (g, 1, c)),
                axis=1,
            )[:, 0, :]
        else:
            rows = np.arange(g)
            n_a = n_j[rows, a_idx]
            n_b = n_j[rows, b_idx]
        low = (
            n_a * inputs.ratio_min_den[:, r, None]
            < n_b * inputs.ratio_min_num[:, r, None]
        )
        high = (
            n_a * inputs.ratio_max_den[:, r, None]
            > n_b * inputs.ratio_max_num[:, r, None]
        )
        live = inputs.ratio_valid[:, r, None]
        viol = viol + xp.where(live & low, np.int32(1), np.int32(0))
        viol = viol + xp.where(live & high, np.int32(1), np.int32(0))
    over = (inputs.group_budget[:, None] > 0) & (
        spend > inputs.group_budget[:, None]
    )
    return viol + xp.where(over, np.int32(1), np.int32(0))


poolgroup_jit = jax.jit(partial(poolgroup_decide, enforce=True))
poolgroup_independent_jit = jax.jit(partial(poolgroup_decide, enforce=False))


# -- numpy mirror -------------------------------------------------------------
# The parity oracle AND the requested-numpy backend — every line mirrors
# the kernel's op order; _fma reproduces XLA:CPU's mul-add contraction
# (ops/cost.py discipline).


def poolgroup_numpy(
    inputs: PoolGroupInputs, enforce: bool = True
) -> PoolGroupOutputs:
    """Host mirror of poolgroup_decide() — bit-identical output leaves
    (module docstring parity contract)."""
    base = np.asarray(inputs.base_desired, np.int32).astype(np.float32)
    min_f = np.asarray(inputs.min_replicas, np.int32).astype(np.float32)
    max_f = np.asarray(inputs.max_replicas, np.int32).astype(np.float32)
    unit = np.asarray(inputs.unit_cost, np.float32)
    weight = np.asarray(inputs.slo_weight, np.float32)
    budget = np.asarray(inputs.max_hourly_cost, np.float32)
    tier = np.asarray(inputs.tier_penalty, np.float32)
    valid = np.asarray(inputs.pool_valid, bool)
    slo_target = np.asarray(inputs.slo_target, np.float32)
    mu = np.asarray(inputs.demand_mu, np.float32)
    sigma = np.asarray(inputs.demand_sigma, np.float32)
    dvalid = np.asarray(inputs.demand_valid, bool)
    group_valid = np.asarray(inputs.group_valid, bool)
    g, p = base.shape
    c = CANDIDATES ** p
    digits = joint_digits(p)  # [P, C]

    offsets = np.arange(CANDIDATES, dtype=np.float32)
    cap_on = valid & (unit > 0) & (budget > 0)
    safe_unit = np.where(unit > 0, unit, _ONE).astype(np.float32)
    cap = np.floor(budget / safe_unit).astype(np.float32)
    hi = np.where(
        cap_on, np.minimum(max_f, np.maximum(cap, min_f)), max_f
    ).astype(np.float32)
    cand = np.clip(
        base[:, :, None] + offsets[None, None, :],
        min_f[:, :, None],
        hi[:, :, None],
    ).astype(np.float32)

    demand_hi = (mu + sigma).astype(np.float32)
    denom = np.maximum(demand_hi, _EPS)[:, :, None, :].astype(np.float32)
    shortfall = _fma(
        -cand[:, :, :, None],
        slo_target[:, :, None, :],
        demand_hi[:, :, None, :],
    )
    short = np.clip((shortfall / denom).astype(np.float32), _ZERO, _ONE)
    short = np.where(dvalid[:, :, None, :], short, _ZERO).astype(np.float32)
    risk = np.max(short, axis=3)

    rate = (unit + tier).astype(np.float32)
    hourly = (cand * rate[:, :, None]).astype(np.float32)
    score = _fma(weight[:, :, None], risk, hourly)

    k_star = np.argmin(score, axis=2).astype(np.int32)

    idx = np.broadcast_to(digits[None, :, :], (g, p, c))
    cand_j = np.take_along_axis(cand, idx, axis=2)
    score_j = np.take_along_axis(score, idx, axis=2)
    risk_j = np.take_along_axis(risk, idx, axis=2)
    n_j = cand_j.astype(np.int32)

    total = score_j[:, 0, :]
    spend = (cand_j[:, 0, :] * unit[:, 0, None]).astype(np.float32)
    for pool in range(1, p):
        total = (score_j[:, pool, :] + total).astype(np.float32)
        spend = _fma(cand_j[:, pool, :], unit[:, pool, None], spend)

    viol = _violations(inputs, n_j, spend, np)

    indep_c = k_star[:, 0].copy()
    for pool in range(1, p):
        indep_c = (
            indep_c + k_star[:, pool] * np.int32(CANDIDATES ** pool)
        ).astype(np.int32)
    rows = np.arange(g)
    indep_viol = viol[rows, indep_c]
    min_viol = np.min(viol, axis=1)
    masked_total = np.where(
        viol == min_viol[:, None], total, _INF
    ).astype(np.float32)
    repair_c = np.argmin(masked_total, axis=1).astype(np.int32)
    if enforce:
        selected = np.where(indep_viol == 0, indep_c, repair_c).astype(
            np.int32
        )
    else:
        selected = indep_c

    chosen = cand_j[rows[:, None], np.arange(p)[None, :], selected[:, None]]
    chosen_risk = risk_j[
        rows[:, None], np.arange(p)[None, :], selected[:, None]
    ]
    sel_viol = viol[rows, selected]
    sel_spend = spend[rows, selected]

    needed = np.ceil(
        (demand_hi / np.maximum(slo_target, _EPS)).astype(np.float32)
    ).astype(np.float32)
    needed = np.where(dvalid, needed, _ZERO).astype(np.float32)
    headroom = np.maximum(np.max(needed, axis=2) - chosen, _ZERO)

    desired = np.where(valid, chosen, base).astype(np.float32)

    def to_i32(x):
        return np.clip(
            x, np.float32(_I32_SAFE_MIN), np.float32(_I32_SAFE_MAX)
        ).astype(np.int32)

    return PoolGroupOutputs(
        desired=to_i32(desired),
        expected_hourly=(desired * unit).astype(np.float32),
        violation_risk=np.where(valid, chosen_risk, _ZERO).astype(
            np.float32
        ),
        headroom=to_i32(np.where(valid, headroom, _ZERO)),
        cost_limited=cap_on & (base > hi),
        slo_raised=valid & (chosen > base),
        ratio_ok=group_valid & (sel_viol == 0),
        group_hourly=np.where(group_valid, sel_spend, _ZERO).astype(
            np.float32
        ),
        joint_repair=group_valid & (selected != indep_c),
    )
