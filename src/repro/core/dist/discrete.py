"""Discrete distributions (Bernoulli, Categorical, DiscreteUniform).

``Bernoulli``/``Categorical`` accept either ``probs`` or ``logits`` (exactly
one) and compute ``log_prob`` in the parameterization given — ``logits``
never round-trip through probabilities, so densities stay finite for extreme
logits, and ``probs`` never through logits.  All three have finite supports
and implement ``enumerate_support``, which is what lets the enumeration
subsystem (:mod:`repro.core.infer.enum`) marginalize them exactly instead of
requiring a ``biject_to`` bijection: use them as observed sites, or leave them
latent and let ``log_density``/NUTS sum them out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import constraints, fmath
from .distribution import Distribution


def _clip_probs(probs):
    eps = jnp.finfo(jnp.result_type(probs, jnp.float32)).eps
    return jnp.clip(probs, eps, 1.0 - eps)


def _enum_values(num, batch_shape, expand):
    """(K,) + (1,)*len(batch_shape) int32 support stack, broadcast on
    request — the shared tail of every ``enumerate_support``."""
    values = jnp.arange(num, dtype=jnp.int32)
    values = values.reshape((num,) + (1,) * len(batch_shape))
    if expand:
        values = jnp.broadcast_to(values, (num,) + tuple(batch_shape))
    return values


class Bernoulli(Distribution):
    arg_constraints = {"probs": constraints.unit_interval,
                       "logits": constraints.real}
    support = constraints.boolean
    has_enumerate_support = True

    def __init__(self, probs=None, logits=None):
        if (probs is None) == (logits is None):
            raise ValueError("provide exactly one of probs, logits")
        self.probs = probs
        self.logits = logits
        param = probs if probs is not None else logits
        super().__init__(jnp.shape(param))

    def _probs(self):
        if self.probs is not None:
            return self.probs
        return fmath.sigmoid(self.logits)

    def sample(self, rng_key=None, sample_shape=()):
        draws = jax.random.bernoulli(rng_key, self._probs(),
                                     self.shape(sample_shape))
        return draws.astype(jnp.int32)

    def log_prob(self, value):
        if self.logits is not None:
            return value * self.logits - fmath.softplus(self.logits)
        # one multiply-add a value over tables of the parameters' shape,
        # as the logits form costs
        p = _clip_probs(self.probs)
        log_1mp = fmath.log1p(-p)
        return value * (fmath.log(p) - log_1mp) + log_1mp

    def enumerate_support(self, expand=True):
        return _enum_values(2, self.batch_shape, expand)


class Categorical(Distribution):
    arg_constraints = {"probs": constraints.simplex,
                       "logits": constraints.real_vector}
    has_enumerate_support = True

    def __init__(self, probs=None, logits=None):
        if (probs is None) == (logits is None):
            raise ValueError("provide exactly one of probs, logits")
        self.probs = probs
        self.logits = logits
        param = probs if probs is not None else logits
        shape = jnp.shape(param)
        if len(shape) < 1:
            raise ValueError("Categorical parameters must be at least 1-d")
        self._num_categories = shape[-1]
        super().__init__(shape[:-1])

    @property
    def support(self):
        return constraints.integer_interval(0, self._num_categories - 1)

    def _logits(self):
        if self.logits is not None:
            return self.logits
        return fmath.log(_clip_probs(self.probs))

    def sample(self, rng_key=None, sample_shape=()):
        return jax.random.categorical(rng_key, self._logits(),
                                      shape=self.shape(sample_shape))

    def log_prob(self, value):
        log_pmf = fmath.log_softmax(self._logits(), axis=-1)
        value = jnp.asarray(value, jnp.int32)
        batch = jnp.broadcast_shapes(jnp.shape(value), self.batch_shape)
        log_pmf = jnp.broadcast_to(log_pmf, batch + (self._num_categories,))
        value = jnp.broadcast_to(value, batch)
        return jnp.take_along_axis(log_pmf, value[..., None], axis=-1)[..., 0]

    def enumerate_support(self, expand=True):
        return _enum_values(self._num_categories, self.batch_shape, expand)


class DiscreteUniform(Distribution):
    """Uniform over the integers ``low .. high`` (both inclusive).

    ``low``/``high`` are static Python ints (pytree aux data): the support
    size must be known at trace time for ``enumerate_support`` to produce a
    statically-shaped stack.
    """

    arg_constraints: dict = {}
    pytree_aux_fields = ("low", "high")
    has_enumerate_support = True

    def __init__(self, low=0, high=1):
        low, high = int(low), int(high)
        if high < low:
            raise ValueError(
                f"DiscreteUniform needs low <= high, got [{low}, {high}]")
        self.low = low
        self.high = high
        super().__init__(())

    @property
    def support(self):
        return constraints.integer_interval(self.low, self.high)

    def sample(self, rng_key=None, sample_shape=()):
        return jax.random.randint(rng_key, self.shape(sample_shape),
                                  self.low, self.high + 1, dtype=jnp.int32)

    def log_prob(self, value):
        in_support = self.support(value)
        n = self.high - self.low + 1
        lp = jnp.full(jnp.shape(value), -fmath.log(float(n)))
        return jnp.where(in_support, lp, -jnp.inf)

    def enumerate_support(self, expand=True):
        return _enum_values(self.high - self.low + 1, self.batch_shape,
                            expand) + self.low
