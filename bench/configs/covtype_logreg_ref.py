"""Plain reference of covtype_logreg, in jax.numpy, independent of the program.

Potential (negative log density) of ``w ~ N(0, I)``,
``y_i ~ Bernoulli(sigmoid(x_i . w))``:

    U(w) = sum_i [softplus(x_i . w) - y_i (x_i . w)] + sum_j [w_j^2 / 2 + log(2 pi) / 2]
    grad U(w) = x.T (sigmoid(x w) - y) + w

evaluated for many draws at once, in blocks of rows whose partial sums are
added in float64 on the host.  ``dtype="float32"`` multiplies at full f32
precision (``HIGHEST``); ``dtype="bfloat16"`` rounds x and w to bfloat16
before the products (f32 accumulation): the control one precision below the
configuration's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 32768
_HALF_LOG_2PI = 0.5 * float(np.log(2 * np.pi))


def _dot(a, b, dtype):
    if dtype == "bfloat16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _block(x, y, w, dtype):
    """x (b, d), y (b,), w (m, d) -> nll partials (m,), grad partials (m, d)."""
    logits = _dot(x, w.T, dtype)                                  # (b, m)
    nll = jnp.sum(jax.nn.softplus(logits) - y[:, None] * logits, axis=0)
    resid = jax.nn.sigmoid(logits) - y[:, None]
    grad = _dot(resid.T, x, dtype)                                # (m, d)
    return nll, grad


def potential_and_grad(inputs, w, dtype="float32"):
    """U and grad U at each row of ``w`` (m, d), as float64 numpy arrays."""
    x, y = inputs["x"], inputs["y"]
    w = jnp.asarray(w, jnp.float32)
    n = x.shape[0]
    nll = np.zeros(w.shape[0])
    grad = np.zeros(w.shape, np.float64)
    for start in range(0, n, BLOCK_ROWS):
        part_nll, part_grad = _block(x[start:start + BLOCK_ROWS],
                                     y[start:start + BLOCK_ROWS], w, dtype)
        nll += np.asarray(part_nll, np.float64)
        grad += np.asarray(part_grad, np.float64)
    w64 = np.asarray(w, np.float64)
    u = nll + np.sum(0.5 * w64 ** 2 + _HALF_LOG_2PI, axis=-1)
    return u, grad + w64
