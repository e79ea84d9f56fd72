"""IEEE binary64 addition, maximum and ordering on int64 bit patterns.

XLA's TPU backend has no f64 unit: it rewrites every f64 operation into a
pair of f32s (about 48 mantissa bits, the f32 exponent range), so an f64
loop compiled there does not reproduce the host's IEEE doubles.  Integer
arithmetic is exact on every backend.  A loop that only adds, takes
maxima and compares doubles can therefore carry them as their int64 bit
patterns and use these functions: each result is the IEEE result (round
to nearest even), bit for bit, wherever the loop runs.

Scope: finite values, subnormals and ±inf.  NaN operands and
``inf + -inf`` are not handled; no caller produces them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_MAG = 0x7FFF_FFFF_FFFF_FFFF          # every bit but the sign
_FRAC = (1 << 52) - 1
_EXP = 0x7FF
INF = 0x7FF0_0000_0000_0000           # +inf's bit pattern (and its key)


def native() -> bool:
    """True when the default backend computes f64 as IEEE binary64 (the
    TPU emulates it with f32 pairs)."""
    return jax.default_backend() != "tpu"


def bits(x) -> np.ndarray:
    """Host doubles → their int64 bit patterns."""
    return np.asarray(x, np.float64).view(np.int64)


def doubles(b) -> np.ndarray:
    """Int64 bit patterns → the doubles they encode (host side)."""
    return np.asarray(b, np.int64).view(np.float64)


def key(b):
    """An int64 that orders as the double ``b`` does (with -0 just below
    +0): compare keys to compare values, take an argmin over keys."""
    return jnp.where(b < 0, b ^ _MAG, b)


def maximum(a, b):
    return jnp.where(key(a) >= key(b), a, b)


def add(a, b):
    """``a + b`` in IEEE binary64, round to nearest even."""
    a, b = jnp.asarray(a, jnp.int64), jnp.asarray(b, jnp.int64)
    a, b = jnp.broadcast_arrays(a, b)
    a_big = (a & _MAG) >= (b & _MAG)
    x = jnp.where(a_big, a, b)                 # the larger magnitude
    y = jnp.where(a_big, b, a)
    ex, ey = (x >> 52) & _EXP, (y >> 52) & _EXP
    # Significands with the hidden bit, three guard bits below the ulp;
    # a subnormal's exponent counts as 1.
    mx = ((x & _FRAC) | jnp.where(ex > 0, 1 << 52, 0)) << 3
    my = ((y & _FRAC) | jnp.where(ey > 0, 1 << 52, 0)) << 3
    e = jnp.maximum(ex, 1)
    d = jnp.minimum(e - jnp.maximum(ey, 1), 60)
    sticky = (my & ((jnp.int64(1) << d) - 1)) != 0
    my = (my >> d) | sticky.astype(jnp.int64)
    same = (x ^ y) >= 0
    s = jnp.where(same, mx + my, mx - my)
    # A carry out of an addition: renormalize right, keeping the sticky bit.
    carry = s >> 56
    s = jnp.where(carry > 0, (s >> 1) | (s & 1), s)
    e = e + carry
    # Cancellation in a subtraction (exact: it needs d <= 1): renormalize
    # left, no further than the subnormal exponent.
    shift = jnp.clip(jnp.minimum(jax.lax.clz(s) - 8, e - 1), 0, None)
    s, e = s << shift, e - shift
    q = s >> 3
    g = s & 7
    q = q + ((g > 4) | ((g == 4) & ((q & 1) == 1))).astype(jnp.int64)
    # The hidden bit carries into the exponent field (a rounding carry to
    # 2^53 bumps the exponent; a subnormal sum that reaches 2^52 becomes
    # normal); anything past the largest finite value is inf.
    mag = jnp.minimum(((e - 1) << 52) + q, INF)
    sign = x & ~_MAG
    out = jnp.where(s == 0, jnp.where(same, sign, 0), sign | mag)
    return jnp.where(ex == _EXP, x, out)
