"""ops/ieee.divide: the correctly rounded f32 quotient, the same bits as
numpy's IEEE division, on any backend (the TPU's own division is not
correctly rounded; chip_smoke.py found 1-2 ulp quotients there)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from karpenter_tpu.ops.ieee import divide

_divide = jax.jit(divide)


@pytest.mark.parametrize("scale", [1e-30, 1e-3, 1.0, 1e3, 1e6, 1e30])
def test_matches_numpy_bit_for_bit(scale):
    rng = np.random.default_rng(int(np.log10(scale)) + 40)
    a = (rng.standard_normal(50_000) * scale).astype(np.float32)
    b = (
        rng.standard_normal(50_000) * rng.choice([1e-3, 1.0, 1e3], 50_000)
    ).astype(np.float32)
    got = np.asarray(_divide(a, b))
    np.testing.assert_array_equal(got.view(np.int32), (a / b).view(np.int32))


def test_round_to_nearest_even_and_carry():
    """Quotients on a rounding boundary: 1/3 rounds down, 2/3 up, and a
    significand that rounds up past 2 carries into the exponent."""
    a = np.array([1.0, 2.0, np.nextafter(np.float32(2), np.float32(0)), 7.0],
                 np.float32)
    b = np.array([3.0, 3.0, np.nextafter(np.float32(1), np.float32(2)), 7.0],
                 np.float32)
    got = np.asarray(_divide(a, b))
    np.testing.assert_array_equal(got.view(np.int32), (a / b).view(np.int32))


def test_zero_and_non_finite_take_the_backend_division():
    a = np.array([0.0, -0.0, np.inf, 5.0, np.nan], np.float32)
    b = np.array([2.0, 2.0, 2.0, np.inf, 1.0], np.float32)
    got = np.asarray(_divide(a, b))
    want = a / b
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    finite = ~np.isnan(want)
    np.testing.assert_array_equal(got[finite], want[finite])
    assert np.signbit(got[1])
