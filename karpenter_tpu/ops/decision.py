"""Batched HorizontalAutoscaler decision kernel.

The reference computes one scalar decision per autoscaler per 10s tick
(reference: pkg/autoscaler/autoscaler.go:144-194 calling
pkg/autoscaler/algorithms/proportional.go:30-47 and the behavior logic in
pkg/apis/autoscaling/v1alpha1/horizontalautoscaler.go:226-275). Here the
same semantics run as ONE jitted array program over all N autoscalers ×
M metrics at once:

    recommendation -> select policy (Max/Min/Disabled by direction)
                   -> stabilization window mask
                   -> [min, max] clamp + condition flags

Design notes (TPU):
- everything is fixed-shape f32/i32 tensors; ragged metric lists are padded
  and masked with metric_valid, so one compiled program serves any fleet
  size up to the padded bucket (no per-object recompiles, no host loop).
- time stays on the host: last_scale_time/now enter as f32 seconds relative
  to a host-chosen epoch (SURVEY.md §7 hard part (e)).
- ceil() is computed with a 1e-5 guard band so f32 rounding cannot round an
  exactly-representable f64 quotient across an integer boundary (the Go
  implementation computes in f64).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_tpu.ops import ieee

# target types (order matters: encoded into int arrays)
TYPE_VALUE = 0
TYPE_AVERAGE_VALUE = 1
TYPE_UTILIZATION = 2
TYPE_UNKNOWN = 3

# select policies
POLICY_MAX = 0
POLICY_MIN = 1
POLICY_DISABLED = 2

# scaling-policy types (reference: horizontalautoscaler.go:131-138)
POLICY_TYPE_COUNT = 0
POLICY_TYPE_PERCENT = 1

_CEIL_GUARD = 1e-5

# f32 saturation bounds for the final int32 cast. 2**31-1 is NOT exactly
# representable in f32 (rounds up to 2**31, which fptosi wraps to INT32_MIN),
# so saturate at 2**31-128 = 2^7*(2^24-1), the largest f32-exact value below
# the int32 ceiling.
_I32_SAFE_MAX = float(2**31 - 128)
_I32_SAFE_MIN = float(-(2**31))  # exact power of two, representable


@jax.tree_util.register_dataclass
@dataclass
class DecisionInputs:
    """Structure-of-arrays snapshot of every HorizontalAutoscaler."""

    metric_value: jax.Array  # f32[N, M]
    target_value: jax.Array  # f32[N, M]
    target_type: jax.Array  # i32[N, M]
    metric_valid: jax.Array  # bool[N, M]
    spec_replicas: jax.Array  # i32[N]  scale target .spec.replicas
    status_replicas: jax.Array  # i32[N]  scale target .status.replicas
    min_replicas: jax.Array  # i32[N]
    max_replicas: jax.Array  # i32[N]
    up_window: jax.Array  # i32[N] stabilization seconds (default 0)
    down_window: jax.Array  # i32[N] stabilization seconds (default 300)
    up_policy: jax.Array  # i32[N]
    down_policy: jax.Array  # i32[N]
    last_scale_time: jax.Array  # f32[N] seconds since epoch0
    has_last_scale: jax.Array  # bool[N]
    now: jax.Array  # f32 scalar, seconds since epoch0
    # Count/Percent scaling policies, K fixed slots per direction
    # (reference MODELS these, horizontalautoscaler.go:111-146, but leaves
    # application a TODO at autoscaler.go:186-189 — applied here)
    up_ptype: jax.Array  # i32[N, K] POLICY_TYPE_*
    up_pvalue: jax.Array  # i32[N, K] permitted change (count or percent)
    up_pperiod: jax.Array  # i32[N, K] periodSeconds
    up_pvalid: jax.Array  # bool[N, K]
    down_ptype: jax.Array  # i32[N, K]
    down_pvalue: jax.Array  # i32[N, K]
    down_pperiod: jax.Array  # i32[N, K]
    down_pvalid: jax.Array  # bool[N, K]
    # proactive blend (docs/forecasting.md): the metric values the
    # forecaster predicts `horizon` seconds ahead. Optional — None (the
    # reactive-only fleet) keeps the pre-forecast program; when present,
    # each valid forecast's recommendation is max()-blended into the
    # reactive one, so a predicted breach scales up EARLY while
    # scale-down stays governed by the observed values alone (the blend
    # can only raise a recommendation — monotonicity is pinned by
    # tests/test_forecast.py).
    forecast_value: Optional[jax.Array] = None  # f32[N, M]
    forecast_valid: Optional[jax.Array] = None  # bool[N, M]


@jax.tree_util.register_dataclass
@dataclass
class DecisionOutputs:
    desired: jax.Array  # i32[N] final bounded decision
    recommendation: jax.Array  # i32[N] post-select, pre-limit
    limited: jax.Array  # i32[N] post-window/policy, pre-[min,max] value
    able_to_scale: jax.Array  # bool[N] False iff held by window or policy
    scaling_unbounded: jax.Array  # bool[N] False iff clamped by [min, max]
    able_at: jax.Array  # f32[N] hold end time (valid when !able_to_scale)
    rate_limited: jax.Array  # bool[N] True iff a scaling policy clamped
    # furthest a hypothetical up/down move could go this tick under the
    # declared stabilization windows + rate policies (the bound the cost
    # refinement's candidate ladder must respect — cost/engine.py)
    up_ceiling: jax.Array  # i32[N]
    down_floor: jax.Array  # i32[N]


def _ceil_guarded(x: jax.Array) -> jax.Array:
    return jnp.ceil(x - _CEIL_GUARD)


def _recommendations(
    inputs: DecisionInputs, values: Optional[jax.Array] = None
) -> jax.Array:
    """Per-metric desired replicas, f32[N, M] (reference: proportional.go:30-47).
    `values` overrides the observed metric values (the forecast blend
    runs the identical HPA math on the predicted values)."""
    if values is None:
        values = inputs.metric_value
    # zero target: ratio collapses to 0, matching the scalar oracle
    # (algorithms/proportional.py) — float division by zero never reaches XLA
    safe_target = jnp.where(inputs.target_value != 0, inputs.target_value, 1.0)
    ratio = jnp.where(
        inputs.target_value != 0, ieee.divide(values, safe_target), 0.0
    )
    status = inputs.status_replicas[:, None].astype(jnp.float32)
    proportional = status * ratio

    by_value = jnp.maximum(1.0, _ceil_guarded(proportional))
    by_average = _ceil_guarded(ratio)
    by_utilization = jnp.maximum(1.0, _ceil_guarded(proportional * 100.0))
    fallback = status  # unknown target type keeps current replicas

    rec = jnp.select(
        [
            inputs.target_type == TYPE_VALUE,
            inputs.target_type == TYPE_AVERAGE_VALUE,
            inputs.target_type == TYPE_UTILIZATION,
        ],
        [by_value, by_average, by_utilization],
        fallback,
    )
    return rec


def decide(inputs: DecisionInputs) -> DecisionOutputs:
    """The full decision pipeline (reference: autoscaler.go:144-194)."""
    rec = _recommendations(inputs)  # f32[N, M]
    if inputs.forecast_value is not None:
        # proactive blend: run the SAME per-metric math on the predicted
        # values and take the max — a forecasted breach raises the
        # recommendation early, a forecasted lull changes nothing (the
        # blend is monotone up; everything downstream — select policy,
        # stabilization, rate limits, bounds — applies unchanged)
        rec_forecast = _recommendations(inputs, inputs.forecast_value)
        blend = inputs.forecast_valid & inputs.metric_valid
        rec = jnp.where(blend, jnp.maximum(rec, rec_forecast), rec)
    valid = inputs.metric_valid
    spec = inputs.spec_replicas.astype(jnp.float32)  # [N]

    # --- select policy (reference: horizontalautoscaler.go:226-247) -------
    any_valid = jnp.any(valid, axis=1)
    any_up = jnp.any(valid & (rec > spec[:, None]), axis=1)
    any_down = jnp.any(valid & (rec < spec[:, None]), axis=1)
    # direction picks which rules apply; no movement (or no metrics) disables
    policy = jnp.where(
        any_up,
        inputs.up_policy,
        jnp.where(any_down, inputs.down_policy, POLICY_DISABLED),
    )
    neg_inf = jnp.float32(np.finfo(np.float32).min)
    pos_inf = jnp.float32(np.finfo(np.float32).max)
    rec_max = jnp.max(jnp.where(valid, rec, neg_inf), axis=1)
    rec_min = jnp.min(jnp.where(valid, rec, pos_inf), axis=1)
    selected = jnp.select(
        [policy == POLICY_MAX, policy == POLICY_MIN],
        [rec_max, rec_min],
        spec,
    )
    selected = jnp.where(any_valid, selected, spec)

    # --- transient limits: stabilization window (autoscaler.go:172-194) ---
    going_up = selected > spec
    going_down = selected < spec
    window = jnp.where(
        going_up,
        inputs.up_window,
        jnp.where(going_down, inputs.down_window, 0),
    ).astype(jnp.float32)
    elapsed = inputs.now - inputs.last_scale_time
    moving = going_up | going_down
    within = (
        moving & inputs.has_last_scale & (elapsed < window)
    )
    window_end = inputs.last_scale_time + window
    limited = jnp.where(within, spec, selected)

    # --- scaling policies: per-direction allowed-delta clamp --------------
    # The reference models Count/Percent policies with periodSeconds
    # (horizontalautoscaler.go:111-146) and leaves application a TODO
    # (autoscaler.go:186-189). Semantics here, with the state the CRD
    # actually carries (LastScaleTime only — no replica-change history):
    # a policy's budget is `value` (Count) or ceil(max(spec,1)*value/100)
    # (Percent — floored at one replica's worth so a Percent-only policy
    # can still escape zero replicas; percent-of-zero would deadlock the
    # autoscaler at 0 forever) per periodSeconds; a scale event inside the
    # trailing period is conservatively assumed to have spent the budget,
    # so the policy contributes 0 until the period elapses. The
    # direction's select policy combines multiple policies (Max = most
    # permissive, Min = most restrictive); no policies, or no scale
    # history, means unlimited (matching the reference's policy-free
    # default rules, horizontalautoscaler.go:249-265).
    def _allowed(ptype, pvalue, pperiod, pvalid, select):
        base = jnp.maximum(spec[:, None], 1.0)
        budget = jnp.where(
            ptype == POLICY_TYPE_PERCENT,
            _ceil_guarded(
                ieee.divide(base * pvalue.astype(jnp.float32), 100.0)
            ),
            pvalue.astype(jnp.float32),
        )
        spent = inputs.has_last_scale[:, None] & (
            elapsed[:, None] < pperiod.astype(jnp.float32)
        )
        per_policy = jnp.where(spent, 0.0, budget)
        a_max = jnp.max(jnp.where(pvalid, per_policy, neg_inf), axis=1)
        a_min = jnp.min(jnp.where(pvalid, per_policy, pos_inf), axis=1)
        allowed = jnp.where(select == POLICY_MIN, a_min, a_max)
        unlimited = ~jnp.any(pvalid, axis=1) | ~inputs.has_last_scale
        # soonest the binding budget frees: Max select frees when ANY
        # period elapses (min), Min select when ALL do (max)
        p_f32 = pperiod.astype(jnp.float32)
        p_min = jnp.min(jnp.where(pvalid, p_f32, pos_inf), axis=1)
        p_max = jnp.max(jnp.where(pvalid, p_f32, neg_inf), axis=1)
        frees = jnp.where(select == POLICY_MIN, p_max, p_min)
        return jnp.where(unlimited, pos_inf, allowed), frees

    allowed_up, up_frees = _allowed(
        inputs.up_ptype,
        inputs.up_pvalue,
        inputs.up_pperiod,
        inputs.up_pvalid,
        inputs.up_policy,
    )
    allowed_down, down_frees = _allowed(
        inputs.down_ptype,
        inputs.down_pvalue,
        inputs.down_pperiod,
        inputs.down_pvalid,
        inputs.down_policy,
    )
    rate_clamped = jnp.clip(limited, spec - allowed_down, spec + allowed_up)
    rate_limited = rate_clamped != limited
    # budget exhausted entirely (no movement possible despite a desired
    # move): a transient hold exactly like the stabilization window
    fully_held = rate_limited & (rate_clamped == spec)
    rate_end = inputs.last_scale_time + jnp.where(
        limited > spec, up_frees, down_frees
    )
    limited = rate_clamped

    # within => limited==spec => the rate clamp is a no-op, so the two
    # holds are mutually exclusive and able_at needs no combining
    able_to_scale = ~within & ~fully_held
    able_at = jnp.where(fully_held, rate_end, window_end)

    # --- bounded limits: [min, max] clamp (autoscaler.go:155-170) ---------
    bounded = jnp.clip(
        limited,
        inputs.min_replicas.astype(jnp.float32),
        inputs.max_replicas.astype(jnp.float32),
    )
    scaling_unbounded = bounded == limited

    # --- per-direction movement bounds (the cost-refinement contract) -----
    # The furthest a HYPOTHETICAL move could go this tick under the
    # declared behavior, independent of where the reactive recommendation
    # actually landed: a direction still inside its stabilization window
    # holds at spec, otherwise the rate budget bounds the step. The cost
    # subsystem (cost/engine.py) clamps its candidate ladder to
    # [down_floor, up_ceiling] so an SLO raise or budget trim can never
    # outrun the scaleUp/scaleDown rules the operator declared.
    up_hold = inputs.has_last_scale & (
        elapsed < inputs.up_window.astype(jnp.float32)
    )
    down_hold = inputs.has_last_scale & (
        elapsed < inputs.down_window.astype(jnp.float32)
    )
    up_ceiling = jnp.where(up_hold, spec, spec + allowed_up)
    down_floor = jnp.where(down_hold, spec, spec - allowed_down)

    to_i32 = lambda x: jnp.clip(
        x, jnp.float32(_I32_SAFE_MIN), jnp.float32(_I32_SAFE_MAX)
    ).astype(jnp.int32)
    return DecisionOutputs(
        desired=to_i32(bounded),
        recommendation=to_i32(selected),
        limited=to_i32(limited),
        able_to_scale=able_to_scale,
        scaling_unbounded=scaling_unbounded,
        able_at=able_at,
        rate_limited=rate_limited,
        up_ceiling=to_i32(up_ceiling),
        down_floor=to_i32(down_floor),
    )


decide_jit = jax.jit(decide)


# -- numpy mirror -------------------------------------------------------------
# The parity oracle for the fused steady-state tick (ops/fusedtick.py)
# and the decide stage of its numpy floor. Every line mirrors the
# kernel's op order; decide() carries no reductions that depend on
# order (any/max/min over masked lanes are order-free) and no
# multiply-accumulate in single-mul form except the Percent-budget
# line, whose divide sits between the multiply and the subtract, so no
# XLA:CPU FMA contraction applies and plain f32 ops reproduce the
# kernel bit for bit (pinned by tests/test_fusedtick.py).

_F32_ONE = np.float32(1.0)
_F32_ZERO = np.float32(0.0)
_F32_GUARD = np.float32(_CEIL_GUARD)
_F32_NEG = np.float32(np.finfo(np.float32).min)
_F32_POS = np.float32(np.finfo(np.float32).max)


def _ceil_guarded_np(x: np.ndarray) -> np.ndarray:
    return np.ceil((x - _F32_GUARD).astype(np.float32)).astype(np.float32)


def _recommendations_numpy(
    inputs: DecisionInputs, values: Optional[np.ndarray] = None
) -> np.ndarray:
    """Host mirror of _recommendations() — bit-identical f32."""
    if values is None:
        values = inputs.metric_value
    values = np.asarray(values, np.float32)
    target = np.asarray(inputs.target_value, np.float32)
    target_type = np.asarray(inputs.target_type, np.int32)
    safe_target = np.where(target != 0, target, _F32_ONE).astype(np.float32)
    ratio = np.where(
        target != 0, (values / safe_target).astype(np.float32), _F32_ZERO
    ).astype(np.float32)
    status = (
        np.asarray(inputs.status_replicas, np.int32)[:, None]
        .astype(np.float32)
    )
    proportional = (status * ratio).astype(np.float32)

    by_value = np.maximum(_F32_ONE, _ceil_guarded_np(proportional))
    by_average = _ceil_guarded_np(ratio)
    by_utilization = np.maximum(
        _F32_ONE,
        _ceil_guarded_np((proportional * np.float32(100.0)).astype(np.float32)),
    )
    fallback = np.broadcast_to(status, ratio.shape)

    return np.select(
        [
            target_type == TYPE_VALUE,
            target_type == TYPE_AVERAGE_VALUE,
            target_type == TYPE_UTILIZATION,
        ],
        [by_value, by_average, by_utilization],
        fallback,
    ).astype(np.float32)


def decide_numpy(inputs: DecisionInputs) -> DecisionOutputs:  # lint: allow-complexity — line-for-line kernel mirror, linear
    """Host mirror of decide() — bit-identical f32/i32 outputs (the
    fused-tick parity contract; see the mirror banner above)."""
    rec = _recommendations_numpy(inputs)
    if inputs.forecast_value is not None:
        rec_forecast = _recommendations_numpy(inputs, inputs.forecast_value)
        blend = (
            np.asarray(inputs.forecast_valid, bool)
            & np.asarray(inputs.metric_valid, bool)
        )
        rec = np.where(
            blend, np.maximum(rec, rec_forecast), rec
        ).astype(np.float32)
    valid = np.asarray(inputs.metric_valid, bool)
    spec = np.asarray(inputs.spec_replicas, np.int32).astype(np.float32)

    any_valid = np.any(valid, axis=1)
    any_up = np.any(valid & (rec > spec[:, None]), axis=1)
    any_down = np.any(valid & (rec < spec[:, None]), axis=1)
    policy = np.where(
        any_up,
        np.asarray(inputs.up_policy, np.int32),
        np.where(
            any_down, np.asarray(inputs.down_policy, np.int32),
            POLICY_DISABLED,
        ),
    ).astype(np.int32)
    rec_max = np.max(np.where(valid, rec, _F32_NEG), axis=1).astype(np.float32)
    rec_min = np.min(np.where(valid, rec, _F32_POS), axis=1).astype(np.float32)
    selected = np.select(
        [policy == POLICY_MAX, policy == POLICY_MIN],
        [rec_max, rec_min],
        spec,
    ).astype(np.float32)
    selected = np.where(any_valid, selected, spec).astype(np.float32)

    going_up = selected > spec
    going_down = selected < spec
    window = np.where(
        going_up,
        np.asarray(inputs.up_window, np.int32),
        np.where(going_down, np.asarray(inputs.down_window, np.int32), 0),
    ).astype(np.float32)
    last = np.asarray(inputs.last_scale_time, np.float32)
    has_last = np.asarray(inputs.has_last_scale, bool)
    elapsed = (np.float32(inputs.now) - last).astype(np.float32)
    moving = going_up | going_down
    within = moving & has_last & (elapsed < window)
    window_end = (last + window).astype(np.float32)
    limited = np.where(within, spec, selected).astype(np.float32)

    def _allowed(ptype, pvalue, pperiod, pvalid, select):
        ptype = np.asarray(ptype, np.int32)
        pvalue_f = np.asarray(pvalue, np.int32).astype(np.float32)
        pperiod_f = np.asarray(pperiod, np.int32).astype(np.float32)
        pvalid = np.asarray(pvalid, bool)
        select = np.asarray(select, np.int32)
        base = np.maximum(spec[:, None], _F32_ONE).astype(np.float32)
        budget = np.where(
            ptype == POLICY_TYPE_PERCENT,
            _ceil_guarded_np(
                (
                    (base * pvalue_f).astype(np.float32)
                    / np.float32(100.0)
                ).astype(np.float32)
            ),
            pvalue_f,
        ).astype(np.float32)
        spent = has_last[:, None] & (elapsed[:, None] < pperiod_f)
        per_policy = np.where(spent, _F32_ZERO, budget).astype(np.float32)
        a_max = np.max(np.where(pvalid, per_policy, _F32_NEG), axis=1)
        a_min = np.min(np.where(pvalid, per_policy, _F32_POS), axis=1)
        allowed = np.where(
            select == POLICY_MIN, a_min, a_max
        ).astype(np.float32)
        unlimited = ~np.any(pvalid, axis=1) | ~has_last
        p_min = np.min(np.where(pvalid, pperiod_f, _F32_POS), axis=1)
        p_max = np.max(np.where(pvalid, pperiod_f, _F32_NEG), axis=1)
        frees = np.where(
            select == POLICY_MIN, p_max, p_min
        ).astype(np.float32)
        return (
            np.where(unlimited, _F32_POS, allowed).astype(np.float32),
            frees,
        )

    allowed_up, up_frees = _allowed(
        inputs.up_ptype,
        inputs.up_pvalue,
        inputs.up_pperiod,
        inputs.up_pvalid,
        inputs.up_policy,
    )
    allowed_down, down_frees = _allowed(
        inputs.down_ptype,
        inputs.down_pvalue,
        inputs.down_pperiod,
        inputs.down_pvalid,
        inputs.down_policy,
    )
    rate_clamped = np.clip(
        limited,
        (spec - allowed_down).astype(np.float32),
        (spec + allowed_up).astype(np.float32),
    ).astype(np.float32)
    rate_limited = rate_clamped != limited
    fully_held = rate_limited & (rate_clamped == spec)
    rate_end = (
        last + np.where(limited > spec, up_frees, down_frees)
    ).astype(np.float32)
    limited = rate_clamped

    able_to_scale = ~within & ~fully_held
    able_at = np.where(fully_held, rate_end, window_end).astype(np.float32)

    bounded = np.clip(
        limited,
        np.asarray(inputs.min_replicas, np.int32).astype(np.float32),
        np.asarray(inputs.max_replicas, np.int32).astype(np.float32),
    ).astype(np.float32)
    scaling_unbounded = bounded == limited

    up_hold = has_last & (
        elapsed < np.asarray(inputs.up_window, np.int32).astype(np.float32)
    )
    down_hold = has_last & (
        elapsed < np.asarray(inputs.down_window, np.int32).astype(np.float32)
    )
    up_ceiling = np.where(
        up_hold, spec, (spec + allowed_up).astype(np.float32)
    ).astype(np.float32)
    down_floor = np.where(
        down_hold, spec, (spec - allowed_down).astype(np.float32)
    ).astype(np.float32)

    def to_i32(x):
        return np.clip(
            x, np.float32(_I32_SAFE_MIN), np.float32(_I32_SAFE_MAX)
        ).astype(np.int32)

    return DecisionOutputs(
        desired=to_i32(bounded),
        recommendation=to_i32(selected),
        limited=to_i32(limited),
        able_to_scale=able_to_scale,
        scaling_unbounded=scaling_unbounded,
        able_at=able_at,
        rate_limited=rate_limited,
        up_ceiling=to_i32(up_ceiling),
        down_floor=to_i32(down_floor),
    )


def pad_to(n: int, bucket: int = 64) -> int:
    """Round a fleet size up to a compile bucket so recompiles only happen on
    bucket crossings, not every added autoscaler."""
    if n <= 0:
        return bucket
    return ((n + bucket - 1) // bucket) * bucket
