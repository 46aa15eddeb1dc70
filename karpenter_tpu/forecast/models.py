"""Batched on-device forecasting models: every HA series in ONE dispatch.

The reference autoscaler is purely reactive — one instantaneous PromQL
value per reconcile — so a TPU node-group ramp is always chased from
behind by the full node-provisioning latency. This module is the math
half of the predictive subsystem (docs/forecasting.md): given the fleet's
metric histories as ONE [S, T] matrix, produce a point forecast at each
series' horizon as ONE array program. Two models, selected per series by
an i32 code so the whole fleet rides a single compiled program:

  MODEL_LINEAR       robust linear trend: an OLS fit over (time, value)
                     re-weighted once by Huber-style weights on the OLS
                     residuals (one IRLS round), projected `horizon`
                     seconds past the newest sample. Robust to the step
                     outliers a flaky exporter or a deploy blip writes
                     into the window.
  MODEL_HOLT_WINTERS additive Holt-Winters: level + trend + a seasonal
                     buffer of `season` sample slots (season < 2 runs
                     plain Holt — level/trend only). Smoothing factors
                     alpha/beta/gamma ride per series.

Parity contract (pinned bit-for-bit by tests/test_forecast.py): the
jitted kernel and `forecast_numpy` produce IDENTICAL f32 bits. Float
parity across XLA and numpy is only achievable by construction, so the
kernel obeys two rules mirrored exactly on the host:

  * every multiply-accumulate is written in single-mul form
    (`a * b + c`, the lerp form `c + a*(x - c)` for smoothing updates)
    and its product has no other user: XLA:CPU contracts exactly that
    shape into one FMA for every operand shape (a product shared by
    several sums is contracted for some shapes and not others), which
    the numpy mirror reproduces with a float64 round-trip
    (`f32(f64(a)*f64(b) + f64(c))` — the product is exact in f64, so
    the round-trip equals the fused single rounding);
  * every reduction over time is a SEQUENTIAL scan (lax.scan on device,
    an explicit loop on host) — never jnp.sum/np.sum, whose pairwise
    orders differ.

Histories are RIGHT-ALIGNED: the newest sample sits at column T-1 and
shorter series are left-padded with valid=False (the mask, not the
padding, decides what the recurrences see), so shape-bucketing the T
axis never perturbs results.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

MODEL_LINEAR = 0
MODEL_HOLT_WINTERS = 1

MODEL_CODES = {
    "linear": MODEL_LINEAR,
    "holt-winters": MODEL_HOLT_WINTERS,
}

_ONE = np.float32(1.0)
_ZERO = np.float32(0.0)
# Huber-style reweighting threshold, in units of the OLS residual RMS:
# residuals inside the tube keep weight 1, outliers decay as k/|r|.
_HUBER_K = np.float32(1.5)
# guard for per-step horizon conversion: a degenerate (single-sample or
# zero-spacing) series must not divide by zero
_MIN_STEP_S = np.float32(1e-3)


@jax.tree_util.register_dataclass
@dataclass
class ForecastInputs:
    """Structure-of-arrays snapshot of every forecastable metric series.

    All arrays are host numpy (the service device_puts on dispatch);
    shapes are [S, T] / [S] with S = series and T = history slots.
    """

    values: jax.Array  # f32[S, T] observed values, right-aligned
    valid: jax.Array  # bool[S, T] sample-present mask
    times: jax.Array  # f32[S, T] seconds relative to now (<= 0)
    # base regression weights (linear model only) — recency decay is
    # computed on the HOST (engine.py) and enters as data, because a
    # transcendental (exp/pow) inside the kernel would break the
    # bit-parity contract between XLA and the numpy mirror
    weights: jax.Array  # f32[S, T]
    horizon: jax.Array  # f32[S] forecast horizon seconds (> 0)
    step_s: jax.Array  # f32[S] mean sample spacing seconds
    model: jax.Array  # i32[S] MODEL_* code
    season: jax.Array  # i32[S] Holt-Winters season length in SAMPLES
    alpha: jax.Array  # f32[S] level smoothing
    beta: jax.Array  # f32[S] trend smoothing
    gamma: jax.Array  # f32[S] seasonal smoothing


@jax.tree_util.register_dataclass
@dataclass
class ForecastOutputs:
    point: jax.Array  # f32[S] forecast value `horizon` seconds ahead
    sigma2: jax.Array  # f32[S] robust residual variance (fit quality)
    n_valid: jax.Array  # i32[S] samples the fit actually saw


# -- device kernel ------------------------------------------------------------


def _hw_scan(inputs: ForecastInputs):
    """Masked Holt-Winters recurrence over the T axis; returns final
    (level, trend, seasonal buffer, valid-step count)."""
    S, T = inputs.values.shape
    # effective season length, clamped to the buffer (a season longer
    # than the retained history cannot be estimated anyway)
    m = jnp.clip(inputs.season, 1, T)  # [S]
    seasonal_on = (inputs.season >= 2)[:, None]  # [S, 1]
    slots = jnp.arange(T, dtype=jnp.int32)[None, :]  # [1, T]

    def step(carry, xt):
        level, trend, seas, cnt, seen = carry
        x, v = xt
        idx = jnp.mod(cnt, m)  # [S] current seasonal slot
        s_old = jnp.where(
            seasonal_on,
            jnp.take_along_axis(seas, idx[:, None], axis=1),
            _ZERO,
        )[:, 0]
        init = v & ~seen
        # single-mul lerp forms (module docstring: the FMA contract)
        q = level + trend
        nl = inputs.alpha * ((x - s_old) - q) + q
        nt = inputs.beta * ((nl - level) - trend) + trend
        ns = inputs.gamma * ((x - nl) - s_old) + s_old
        level2 = jnp.where(init, x, jnp.where(v, nl, level))
        trend2 = jnp.where(init, _ZERO, jnp.where(v, nt, trend))
        write = (slots == idx[:, None]) & v[:, None] & seasonal_on
        seas2 = jnp.where(write, ns[:, None], seas)
        cnt2 = jnp.where(v, cnt + 1, cnt)
        return (level2, trend2, seas2, cnt2, seen | v), None

    z = jnp.zeros(S, jnp.float32)
    carry0 = (
        z, z, jnp.zeros((S, T), jnp.float32),
        jnp.zeros(S, jnp.int32), jnp.zeros(S, bool),
    )
    (level, trend, seas, cnt, _), _ = jax.lax.scan(
        step, carry0, (inputs.values.T, inputs.valid.T)
    )
    return level, trend, seas, cnt


def _linear_sums(values, valid, times, weights):
    """Sequentially accumulated weighted regression sums (FMA forms)."""
    S = values.shape[0]

    def step(carry, xt):
        sw, st, sv, stt, stv = carry
        x, v, t, w0 = xt
        w = jnp.where(v, w0, _ZERO)
        # every product feeding an add has that add as its ONLY user:
        # XLA:CPU contracts a shared product (one `w * t` feeding three
        # sums) into an FMA for some shapes and not others, while a
        # single-use `a * b + c` is contracted for every shape
        return (
            sw + w,
            w * t + st,
            w * x + sv,
            w * (t * t) + stt,
            w * (t * x) + stv,
        ), None

    z = jnp.zeros(S, jnp.float32)
    (sw, st, sv, stt, stv), _ = jax.lax.scan(
        step, (z, z, z, z, z),
        (values.T, valid.T, times.T, weights.T),
    )
    return sw, st, sv, stt, stv


def _linear_fit(values, valid, times, weights):
    """Weighted least squares of value on time; returns (slope,
    intercept-at-t=0, sw). Degenerate fits (fewer than 2 points, zero
    time spread) collapse to slope 0 through the `den` guard."""
    sw, st, sv, stt, stv = _linear_sums(values, valid, times, weights)
    den = sw * stt + -(st * st)
    num = sw * stv + -(st * sv)
    ok = den > 0
    slope = jnp.where(ok, num / jnp.where(ok, den, _ONE), _ZERO)
    sw_safe = jnp.where(sw > 0, sw, _ONE)
    mean_t = st / sw_safe
    mean_v = sv / sw_safe
    intercept = -slope * mean_t + mean_v
    return slope, intercept, sw


def _residual_stats(values, valid, times, weights, slope, intercept):
    """Weighted (sum of squared residuals, sum of weights) — sequential."""
    S = values.shape[0]

    def step(carry, xt):
        sse, sw = carry
        x, v, t, w0 = xt
        w = jnp.where(v, w0, _ZERO)
        r = x - (slope * t + intercept)
        wr = w * r
        return (wr * r + sse, sw + w), None

    z = jnp.zeros(S, jnp.float32)
    (sse, sw), _ = jax.lax.scan(
        step, (z, z), (values.T, valid.T, times.T, weights.T)
    )
    return sse, sw


def forecast(inputs: ForecastInputs) -> ForecastOutputs:
    """The batched forecast program (see module docstring)."""
    values, valid, times = inputs.values, inputs.valid, inputs.times
    base = inputs.weights

    # --- robust linear: WLS -> residual scale -> one Huber reweight ---
    slope0, icept0, _ = _linear_fit(values, valid, times, base)
    sse0, sw0 = _residual_stats(values, valid, times, base, slope0, icept0)
    sw0_safe = jnp.where(sw0 > 0, sw0, _ONE)
    scale2 = sse0 / sw0_safe  # residual mean square (variance proxy)
    # w = min(1, k*scale/|r|) without sqrt: w^2 = min(1, k^2*scale2/r^2),
    # applied as w2 directly (a monotone reweighting with the same
    # outlier-downweighting shape; keeps the kernel sqrt-free)
    r = values - (slope0[:, None] * times + icept0[:, None])
    r2 = r * r
    k2s = (_HUBER_K * _HUBER_K) * scale2
    w_rob = base * jnp.where(r2 > k2s[:, None], k2s[:, None] / jnp.where(
        r2 > 0, r2, _ONE
    ), _ONE)
    slope, icept, _ = _linear_fit(values, valid, times, w_rob)
    sse, swr = _residual_stats(values, valid, times, w_rob, slope, icept)
    sigma2_lin = sse / jnp.where(swr > 0, swr, _ONE)
    point_lin = slope * inputs.horizon + icept

    # --- Holt-Winters ---
    level, trend, seas, cnt = _hw_scan(inputs)
    step_s = jnp.maximum(inputs.step_s, _MIN_STEP_S)
    h_steps = inputs.horizon / step_s
    point_hw = trend * h_steps + level
    m = jnp.clip(inputs.season, 1, values.shape[1])
    seasonal_on = inputs.season >= 2
    # phase of the forecast target: the newest sample sat at phase
    # (cnt-1) mod m; the target sits round(h_steps) later
    h_i = jnp.round(h_steps).astype(jnp.int32)
    idx_f = jnp.mod(jnp.maximum(cnt - 1, 0) + h_i, m)
    seas_at = jnp.where(
        seasonal_on,
        jnp.take_along_axis(seas, idx_f[:, None], axis=1)[:, 0],
        _ZERO,
    )
    point_hw = point_hw + seas_at

    n_valid = cnt
    is_hw = inputs.model == MODEL_HOLT_WINTERS
    point = jnp.where(is_hw, point_hw, point_lin)
    # both models report the robust linear residual variance as the fit-
    # quality signal (a dedicated HW one-step-ahead error scan would
    # double the program for a gauge-only output)
    sigma2 = sigma2_lin
    # a series with no samples forecasts 0 with infinite-variance
    # semantics left to the caller (n_valid carries the evidence count)
    point = jnp.where(n_valid > 0, point, _ZERO)
    return ForecastOutputs(
        point=point, sigma2=sigma2, n_valid=n_valid.astype(jnp.int32)
    )


forecast_jit = jax.jit(forecast)


# -- shape plumbing for the solve service -------------------------------------
# Padding is semantics-preserving by construction: extra T slots are
# left-padded valid=False (the recurrences carry state through masked
# steps unchanged and masked regression terms add exact zeros), and
# extra S rows are fully invalid, per-series independent, and sliced off
# before results scatter back — so bucketed outputs EQUAL unbucketed
# ones bit for bit (the same argument solver/bucketing.py makes).


def pad_forecast_inputs(inputs: ForecastInputs, t_pad: int) -> ForecastInputs:
    """Left-pad the time axis to `t_pad` slots (right-alignment keeps
    the newest sample at T-1). Returns `inputs` unchanged when already
    there."""
    t = np.asarray(inputs.values).shape[1]
    if t == t_pad:
        return inputs
    if t > t_pad:
        raise ValueError(f"history length {t} exceeds bucket {t_pad}")

    def left(a, fill=0):
        a = np.asarray(a)
        out = np.full((a.shape[0], t_pad), fill, a.dtype)
        out[:, t_pad - t:] = a
        return out

    return ForecastInputs(
        values=left(inputs.values),
        valid=left(inputs.valid, False),
        times=left(inputs.times),
        weights=left(inputs.weights),
        horizon=np.asarray(inputs.horizon),
        step_s=np.asarray(inputs.step_s),
        model=np.asarray(inputs.model),
        season=np.asarray(inputs.season),
        alpha=np.asarray(inputs.alpha),
        beta=np.asarray(inputs.beta),
        gamma=np.asarray(inputs.gamma),
    )


def concat_forecast_inputs(
    padded: List["ForecastInputs"], s_pad: int
) -> ForecastInputs:
    """Stack same-T requests along the series axis and bottom-pad with
    all-invalid rows to `s_pad` (the coalesced-dispatch stack)."""
    import dataclasses

    total = sum(np.asarray(p.values).shape[0] for p in padded)
    extra = s_pad - total

    def cat(name: str, fill=0):
        parts = [np.asarray(getattr(p, name)) for p in padded]
        out = np.concatenate(parts, axis=0)
        if extra > 0:
            pad_shape = (extra,) + out.shape[1:]
            out = np.concatenate(
                [out, np.full(pad_shape, fill, out.dtype)], axis=0
            )
        return out

    return ForecastInputs(
        **{
            f.name: cat(f.name, False if f.name == "valid" else 0)
            for f in dataclasses.fields(ForecastInputs)
        }
    )


def slice_forecast_outputs(out, start: int, stop: int) -> ForecastOutputs:
    """One request's rows out of a coalesced dispatch's host outputs."""
    return ForecastOutputs(
        point=np.asarray(out.point)[start:stop],
        sigma2=np.asarray(out.sigma2)[start:stop],
        n_valid=np.asarray(out.n_valid)[start:stop],
    )


# -- numpy mirror -------------------------------------------------------------
# The degradation target (service numpy fallback) AND the parity oracle.
# Every line mirrors the kernel's op order; _fma reproduces XLA:CPU's
# mul-add contraction exactly (module docstring).


def _fma(a, b, c):
    return (
        np.asarray(a, np.float64) * np.asarray(b, np.float64)
        + np.asarray(c, np.float64)
    ).astype(np.float32)


def _np_hw_scan(inputs: ForecastInputs):
    values = np.asarray(inputs.values, np.float32)
    valid = np.asarray(inputs.valid, bool)
    S, T = values.shape
    m = np.clip(np.asarray(inputs.season, np.int32), 1, T)
    seasonal_on = np.asarray(inputs.season, np.int32) >= 2
    alpha = np.asarray(inputs.alpha, np.float32)
    beta = np.asarray(inputs.beta, np.float32)
    gamma = np.asarray(inputs.gamma, np.float32)

    level = np.zeros(S, np.float32)
    trend = np.zeros(S, np.float32)
    seas = np.zeros((S, T), np.float32)
    cnt = np.zeros(S, np.int32)
    seen = np.zeros(S, bool)
    rows = np.arange(S)
    for t in range(T):
        x, v = values[:, t], valid[:, t]
        idx = np.mod(cnt, m)
        s_old = np.where(seasonal_on, seas[rows, idx], _ZERO)
        init = v & ~seen
        q = level + trend
        nl = _fma(alpha, (x - s_old) - q, q)
        nt = _fma(beta, (nl - level) - trend, trend)
        ns = _fma(gamma, (x - nl) - s_old, s_old)
        level = np.where(init, x, np.where(v, nl, level)).astype(np.float32)
        trend = np.where(init, _ZERO, np.where(v, nt, trend)).astype(
            np.float32
        )
        write = v & seasonal_on
        seas[rows[write], idx[write]] = ns[write]
        cnt = np.where(v, cnt + 1, cnt).astype(np.int32)
        seen |= v
    return level, trend, seas, cnt


def _np_linear_fit(values, valid, times, weights):
    S, T = values.shape
    z = np.zeros(S, np.float32)
    sw, st, sv, stt, stv = z.copy(), z.copy(), z.copy(), z.copy(), z.copy()
    for t in range(T):
        x, v, tt, w0 = values[:, t], valid[:, t], times[:, t], weights[:, t]
        w = np.where(v, w0, _ZERO).astype(np.float32)
        sw = sw + w
        st = _fma(w, tt, st)
        sv = _fma(w, x, sv)
        stt = _fma(w, tt * tt, stt)
        stv = _fma(w, tt * x, stv)
    den = _fma(sw, stt, -(st * st))
    num = _fma(sw, stv, -(st * sv))
    ok = den > 0
    slope = np.where(ok, num / np.where(ok, den, _ONE), _ZERO).astype(
        np.float32
    )
    sw_safe = np.where(sw > 0, sw, _ONE).astype(np.float32)
    mean_t = st / sw_safe
    mean_v = sv / sw_safe
    intercept = _fma(-slope, mean_t, mean_v)
    return slope, intercept, sw


def _np_residual_stats(values, valid, times, weights, slope, intercept):
    S, T = values.shape
    sse, sw = np.zeros(S, np.float32), np.zeros(S, np.float32)
    for t in range(T):
        x, v, tt, w0 = values[:, t], valid[:, t], times[:, t], weights[:, t]
        w = np.where(v, w0, _ZERO).astype(np.float32)
        r = x - _fma(slope, tt, intercept)
        wr = w * r
        sse = _fma(wr, r, sse)
        sw = sw + w
    return sse, sw


def forecast_numpy(inputs: ForecastInputs) -> ForecastOutputs:
    """Host mirror of forecast() — the numpy degradation path. Produces
    bit-identical f32 outputs (module docstring parity contract)."""
    values = np.asarray(inputs.values, np.float32)
    valid = np.asarray(inputs.valid, bool)
    times = np.asarray(inputs.times, np.float32)
    horizon = np.asarray(inputs.horizon, np.float32)
    base = np.asarray(inputs.weights, np.float32)

    slope0, icept0, _ = _np_linear_fit(values, valid, times, base)
    sse0, sw0 = _np_residual_stats(
        values, valid, times, base, slope0, icept0
    )
    sw0_safe = np.where(sw0 > 0, sw0, _ONE).astype(np.float32)
    scale2 = sse0 / sw0_safe
    r = values - _fma(
        slope0[:, None], times, np.broadcast_to(icept0[:, None], values.shape)
    )
    r2 = r * r
    k2s = (_HUBER_K * _HUBER_K) * scale2
    w_rob = base * np.where(
        r2 > k2s[:, None],
        k2s[:, None] / np.where(r2 > 0, r2, _ONE),
        _ONE,
    ).astype(np.float32)
    slope, icept, _ = _np_linear_fit(values, valid, times, w_rob)
    sse, swr = _np_residual_stats(values, valid, times, w_rob, slope, icept)
    sigma2_lin = (sse / np.where(swr > 0, swr, _ONE)).astype(np.float32)
    point_lin = _fma(slope, horizon, icept)

    level, trend, seas, cnt = _np_hw_scan(inputs)
    step_s = np.maximum(np.asarray(inputs.step_s, np.float32), _MIN_STEP_S)
    h_steps = (horizon / step_s).astype(np.float32)
    point_hw = _fma(trend, h_steps, level)
    m = np.clip(np.asarray(inputs.season, np.int32), 1, values.shape[1])
    seasonal_on = np.asarray(inputs.season, np.int32) >= 2
    h_i = np.round(h_steps).astype(np.int32)
    idx_f = np.mod(np.maximum(cnt - 1, 0) + h_i, m)
    rows = np.arange(values.shape[0])
    seas_at = np.where(seasonal_on, seas[rows, idx_f], _ZERO)
    point_hw = point_hw + seas_at

    is_hw = np.asarray(inputs.model, np.int32) == MODEL_HOLT_WINTERS
    point = np.where(is_hw, point_hw, point_lin).astype(np.float32)
    sigma2 = sigma2_lin
    point = np.where(cnt > 0, point, _ZERO).astype(np.float32)
    return ForecastOutputs(
        point=point, sigma2=sigma2, n_valid=cnt.astype(np.int32)
    )
