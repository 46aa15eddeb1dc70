"""IEEE round-to-nearest f32 division on any backend.

The TPU divides f32 through a reciprocal refinement that is not correctly
rounded: on a v5e chip 35% of random quotients came out 1-2 ulps from
IEEE (chip_smoke.py diagnostics, PR 21), and a decision such as
`ceil(replicas * value / target * 100)` then flips where the numpy mirror
does not. `divide` computes the correctly rounded quotient with integer
long division of the significands, which every backend does exactly, so
the kernels that round quotients into integer decisions agree with their
numpy mirrors (whose `/` is IEEE) bit for bit.

Operands or quotients that are zero, subnormal, infinite or NaN, or that
leave the normal exponent range, take the backend's own division.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_QUOTIENT_BITS = 25  # 24 significand bits + the round bit


def divide(a, b):
    """a / b in f32, correctly rounded to nearest-even (module docstring)."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    ua = lax.bitcast_convert_type(a, jnp.uint32)
    ub = lax.bitcast_convert_type(b, jnp.uint32)
    ea = ((ua >> 23) & 0xFF).astype(jnp.int32)
    eb = ((ub >> 23) & 0xFF).astype(jnp.int32)
    ma = (ua & 0x7FFFFF) | 0x800000
    mb = (ub & 0x7FFFFF) | 0x800000
    # align so the quotient of significands lies in [1, 2)
    low = ma < mb
    r = jnp.where(low, ma << 1, ma)
    exponent = ea - eb + 127 - low.astype(jnp.int32)

    def step(_, carry):
        q, r = carry
        bit = r >= mb
        return (q << 1) | bit.astype(jnp.uint32), (
            r - jnp.where(bit, mb, jnp.uint32(0))
        ) << 1

    # a rolled loop: unrolled, the 25 steps double the decide compile
    q, r = lax.fori_loop(0, _QUOTIENT_BITS, step, (jnp.zeros_like(ma), r))
    m = q >> 1
    round_up = ((q & 1) == 1) & ((r != 0) | ((m & 1) == 1))
    m = m + round_up.astype(jnp.uint32)
    carry = m == 0x1000000
    m = jnp.where(carry, jnp.uint32(0x800000), m)
    exponent = exponent + carry.astype(jnp.int32)
    bits = (
        ((ua ^ ub) & jnp.uint32(0x80000000))
        | (exponent.astype(jnp.uint32) << 23)
        | (m & 0x7FFFFF)
    )
    exact = (
        (ea > 0) & (ea < 255) & (eb > 0) & (eb < 255)
        & (exponent >= 1) & (exponent <= 254)
    )
    return jnp.where(exact, lax.bitcast_convert_type(bits, jnp.float32), a / b)
