"""f32 logarithms and exponentials that hold a few ulps on every backend.

A TPU computes f32 ``log``, ``log1p`` and ``exp`` by approximations far
coarser than a CPU's: on a TPU v5e ``log`` and ``log1p`` read up to ~6,000
ulps from float64, ``softplus`` 4,400 and ``exp`` 136.  A density reuses
each entry of its parameter tables (``log p``, ``log(1 - p)``, ``log`` of
a transition matrix) once per observation, so such an error does not
average out: summed over a corpus of 30,000 steps it moves the potential
by nats and the gradient by 2e-3 of its norm.

Each function here is built from additions, multiplications, one division
and exponent bit operations, which every backend rounds correctly, and
agrees with float64 within 3 ulps over the whole f32 range (the
distributions' tests hold it there).  Inputs of another dtype go to the
``jax.numpy`` function of the same name.  Derivatives are the closed forms,
through the same functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_LN2_HI = np.float32(0.693145751953125)     # 16 significant bits: e * hi
_LN2_LO = np.float32(1.428606820309417e-06)  # is exact for any exponent e
_LOG2E = np.float32(1.4426950408889634)
_SQRT_HALF = np.float32(0.7071067811865476)
_BITS_SQRT_HALF = 0x3F3504F3                 # bits of f32 sqrt(1/2)
_LOG_SHIFT = 0x3F800000 - _BITS_SQRT_HALF
_SQRT_TWO_M1 = np.float32(0.41421356237309503)


def _is_f32(x):
    return jnp.result_type(x) == jnp.float32


def _log1p_near_zero(f):
    """``log(1 + f)`` for ``f`` in ``[sqrt(1/2) - 1, sqrt(2) - 1]``:
    ``2 atanh(s)`` with ``s = f / (2 + f)``, written as ``f - s (f - R)``
    so that only the small correction carries rounding."""
    s = f / (2.0 + f)
    z = s * s
    r = 2.0 * z * (1 / 3 + z * (1 / 5 + z * (1 / 7 + z * (1 / 9 + z * (
        1 / 11 + z * (1 / 13 + z / 15))))))
    return f - s * (f - r)


def _log(x):
    # x = 2^k (1 + f) with f in [sqrt(1/2) - 1, sqrt(2) - 1), read from x's
    # bits: adding 1 - bits(sqrt(1/2)) carries into the exponent exactly
    # where the mantissa passes sqrt(2)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) + _LOG_SHIFT
    k = ((bits >> 23) - 127).astype(jnp.float32)
    m = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) + _BITS_SQRT_HALF,
                                     jnp.float32)
    out = k * _LN2_HI + (k * _LN2_LO + _log1p_near_zero(m - 1.0))
    out = jnp.where(x == jnp.inf, jnp.inf, out)
    out = jnp.where(x == 0, -jnp.inf, out)
    return jnp.where(x >= 0, out, jnp.nan)     # below 0, and NaN


def _log1p(x):
    # 1 + x is not formed near 0, where it would round away x's digits
    near = (x > _SQRT_HALF - 1.0) & (x < _SQRT_TWO_M1)
    return jnp.where(near, _log1p_near_zero(jnp.where(near, x, 0.0)),
                     _log(1.0 + x))


def _pow2(k):
    """2^k for integer k in [-126, 127], from its bits."""
    return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)


def _exp(x):
    k = jnp.round(jnp.clip(x, -104.0, 89.0) * _LOG2E)
    r = (x - k * _LN2_HI) - k * _LN2_LO        # |r| <= ln(2) / 2
    p = 1.0 + r * (1.0 + r * (1 / 2 + r * (1 / 6 + r * (1 / 24 + r * (
        1 / 120 + r * (1 / 720 + r / 5040))))))
    k = k.astype(jnp.int32)
    half = k >> 1                   # 2^k in two factors, so neither leaves
    out = p * _pow2(half) * _pow2(k - half)    # the normal range
    out = jnp.where(x < -104.0, 0.0, out)
    out = jnp.where(x > 89.0, jnp.inf, out)
    return jnp.where(jnp.isnan(x), x, out)


def _sigmoid_pair(x):
    """``(sigmoid(x), sigmoid(-x))``.  Above 0, ``1 - s`` is the f32 number
    nearest the sigmoid (s's own error lies far below p's spacing there),
    so near 1 a density reads ``log(1 - p)`` of the correctly rounded p."""
    e = _exp(-jnp.abs(x))
    s = e / (1.0 + e)
    up = x >= 0
    return jnp.where(up, 1.0 - s, s), jnp.where(up, s, 1.0 - s)


def _log_slope(x):
    return _log(x), 1.0 / x


def _log1p_slope(x):
    return _log1p(x), 1.0 / (1.0 + x)


def _exp_slope(x):
    y = _exp(x)
    return y, y


def _sigmoid_slope(x):
    y, y_neg = _sigmoid_pair(x)
    return y, y * y_neg


def _softplus_slope(x):
    e = _exp(-jnp.abs(x))
    s = e / (1.0 + e)
    return (jnp.maximum(x, 0.0) + _log1p(e),
            jnp.where(x >= 0, 1.0 - s, s))


def _f32(value_and_slope, other):
    """The function whose value and derivative ``value_and_slope`` gives
    (from shared intermediates) for f32 inputs, ``other`` (the
    ``jax.numpy`` function) for any other dtype."""
    f32 = jax.custom_jvp(lambda x: value_and_slope(x)[0])

    @f32.defjvp
    def _jvp(primals, tangents):
        y, slope = value_and_slope(primals[0])
        return y, slope * tangents[0]

    return lambda x: f32(x) if _is_f32(x) else other(x)


log = _f32(_log_slope, jnp.log)
log1p = _f32(_log1p_slope, jnp.log1p)
exp = _f32(_exp_slope, jnp.exp)
sigmoid = _f32(_sigmoid_slope, jax.nn.sigmoid)
softplus = _f32(_softplus_slope, jax.nn.softplus)


def log_softmax(x, axis=-1):
    """``x - logsumexp(x)`` over ``axis``."""
    if not _is_f32(x):
        return jax.nn.log_softmax(x, axis=axis)
    m = jax.lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
    shifted = x - jnp.where(jnp.isfinite(m), m, 0.0)
    return shifted - log(jnp.sum(exp(shifted), axis=axis, keepdims=True))
