"""Continuous distributions.

Samplers ride on :mod:`jax.random` and clamp away from support boundaries by
the smallest representable step, so ``log_prob(sample())`` is finite in
float32 even for extreme parameters (heavy-tailed Beta/Gamma mass piles up
within one ulp of the boundary) — a precondition for the end-to-end-jitted
NUTS chain, where a single non-finite density poisons the whole trajectory.
``log_prob`` itself is the bare closed form (no support masking): inference
only evaluates it inside the support via ``biject_to``, and masking with
``where`` would leak NaNs through the untaken gradient branch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.scipy.special import betaln, gammaln

from . import constraints, fmath
from .distribution import Distribution, ExpandedDistribution

_HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)


def _tiny(x):
    return jnp.finfo(jnp.result_type(x, jnp.float32)).tiny


def _below_one(x):
    # largest representable value strictly below 1.0
    return 1.0 - jnp.finfo(jnp.result_type(x, jnp.float32)).epsneg


class Normal(Distribution):
    arg_constraints = {"loc": constraints.real, "scale": constraints.positive}
    support = constraints.real

    def __init__(self, loc=0.0, scale=1.0):
        self.loc = loc
        self.scale = scale
        super().__init__(jnp.broadcast_shapes(jnp.shape(loc), jnp.shape(scale)))

    def sample(self, rng_key=None, sample_shape=()):
        eps = jax.random.normal(rng_key, self.shape(sample_shape))
        return self.loc + self.scale * eps

    def log_prob(self, value):
        z = (value - self.loc) / self.scale
        return -0.5 * z * z - fmath.log(self.scale) - _HALF_LOG_2PI


class LogNormal(Distribution):
    arg_constraints = {"loc": constraints.real, "scale": constraints.positive}
    support = constraints.positive

    def __init__(self, loc=0.0, scale=1.0):
        self.loc = loc
        self.scale = scale
        super().__init__(jnp.broadcast_shapes(jnp.shape(loc), jnp.shape(scale)))

    def sample(self, rng_key=None, sample_shape=()):
        eps = jax.random.normal(rng_key, self.shape(sample_shape))
        return fmath.exp(self.loc + self.scale * eps)

    def log_prob(self, value):
        log_value = fmath.log(value)
        z = (log_value - self.loc) / self.scale
        return (-0.5 * z * z - fmath.log(self.scale) - _HALF_LOG_2PI
                - log_value)


class Cauchy(Distribution):
    arg_constraints = {"loc": constraints.real, "scale": constraints.positive}
    support = constraints.real

    def __init__(self, loc=0.0, scale=1.0):
        self.loc = loc
        self.scale = scale
        super().__init__(jnp.broadcast_shapes(jnp.shape(loc), jnp.shape(scale)))

    def sample(self, rng_key=None, sample_shape=()):
        eps = jax.random.cauchy(rng_key, self.shape(sample_shape))
        return self.loc + self.scale * eps

    def log_prob(self, value):
        z = (value - self.loc) / self.scale
        return -math.log(math.pi) - fmath.log(self.scale) - fmath.log1p(z * z)


class StudentT(Distribution):
    arg_constraints = {"df": constraints.positive, "loc": constraints.real,
                       "scale": constraints.positive}
    support = constraints.real

    def __init__(self, df, loc=0.0, scale=1.0):
        self.df = df
        self.loc = loc
        self.scale = scale
        super().__init__(jnp.broadcast_shapes(
            jnp.shape(df), jnp.shape(loc), jnp.shape(scale)))

    def sample(self, rng_key=None, sample_shape=()):
        key_z, key_g = jax.random.split(rng_key)
        shape = self.shape(sample_shape)
        z = jax.random.normal(key_z, shape)
        half_df = jnp.broadcast_to(jnp.asarray(self.df) / 2.0, shape)
        chi2 = 2.0 * jax.random.gamma(key_g, half_df)
        # clamp the chi2 draw so extreme small-df tails stay finite in f32
        chi2 = jnp.clip(chi2, _tiny(chi2))
        return self.loc + self.scale * z * jnp.sqrt(self.df / chi2)

    def log_prob(self, value):
        df = self.df
        z = (value - self.loc) / self.scale
        return (gammaln((df + 1.0) / 2.0) - gammaln(df / 2.0)
                - 0.5 * fmath.log(df * math.pi) - fmath.log(self.scale)
                - 0.5 * (df + 1.0) * fmath.log1p(z * z / df))


class Gamma(Distribution):
    arg_constraints = {"concentration": constraints.positive,
                       "rate": constraints.positive}
    support = constraints.positive

    def __init__(self, concentration, rate=1.0):
        self.concentration = concentration
        self.rate = rate
        super().__init__(jnp.broadcast_shapes(
            jnp.shape(concentration), jnp.shape(rate)))

    def sample(self, rng_key=None, sample_shape=()):
        shape = self.shape(sample_shape)
        conc = jnp.broadcast_to(jnp.asarray(self.concentration), shape)
        std = jax.random.gamma(rng_key, conc)
        return jnp.clip(std, _tiny(std)) / self.rate

    def log_prob(self, value):
        conc = self.concentration
        return (conc * fmath.log(self.rate) + (conc - 1.0) * fmath.log(value)
                - self.rate * value - gammaln(conc))


class InverseGamma(Distribution):
    """If X ~ Gamma(concentration, rate') then rate/X ~ InverseGamma with
    density rate^c / Gamma(c) * x^{-c-1} exp(-rate/x) (scipy's ``invgamma``
    with ``a=concentration, scale=rate``)."""

    arg_constraints = {"concentration": constraints.positive,
                       "rate": constraints.positive}
    support = constraints.positive

    def __init__(self, concentration, rate=1.0):
        self.concentration = concentration
        self.rate = rate
        super().__init__(jnp.broadcast_shapes(
            jnp.shape(concentration), jnp.shape(rate)))

    def sample(self, rng_key=None, sample_shape=()):
        shape = self.shape(sample_shape)
        conc = jnp.broadcast_to(jnp.asarray(self.concentration), shape)
        std = jax.random.gamma(rng_key, conc)
        return self.rate / jnp.clip(std, _tiny(std))

    def log_prob(self, value):
        conc = self.concentration
        return (conc * fmath.log(self.rate) - (conc + 1.0) * fmath.log(value)
                - self.rate / value - gammaln(conc))


class Beta(Distribution):
    arg_constraints = {"concentration1": constraints.positive,
                       "concentration0": constraints.positive}
    support = constraints.unit_interval

    def __init__(self, concentration1, concentration0):
        self.concentration1 = concentration1
        self.concentration0 = concentration0
        super().__init__(jnp.broadcast_shapes(
            jnp.shape(concentration1), jnp.shape(concentration0)))

    def sample(self, rng_key=None, sample_shape=()):
        shape = self.shape(sample_shape)
        x = jax.random.beta(rng_key, self.concentration1,
                            self.concentration0, shape)
        return jnp.clip(x, _tiny(x), _below_one(x))

    def log_prob(self, value):
        a, b = self.concentration1, self.concentration0
        return ((a - 1.0) * fmath.log(value) + (b - 1.0) * fmath.log1p(-value)
                - betaln(a, b))


class Exponential(Distribution):
    arg_constraints = {"rate": constraints.positive}
    support = constraints.positive

    def __init__(self, rate=1.0):
        self.rate = rate
        super().__init__(jnp.shape(rate))

    def sample(self, rng_key=None, sample_shape=()):
        std = jax.random.exponential(rng_key, self.shape(sample_shape))
        return jnp.clip(std, _tiny(std)) / self.rate

    def log_prob(self, value):
        return fmath.log(self.rate) - self.rate * value


class HalfNormal(Distribution):
    arg_constraints = {"scale": constraints.positive}
    support = constraints.positive

    def __init__(self, scale=1.0):
        self.scale = scale
        super().__init__(jnp.shape(scale))

    def sample(self, rng_key=None, sample_shape=()):
        eps = jax.random.normal(rng_key, self.shape(sample_shape))
        x = jnp.abs(self.scale * eps)
        return jnp.clip(x, _tiny(x))

    def log_prob(self, value):
        z = value / self.scale
        return (math.log(2.0) - 0.5 * z * z - fmath.log(self.scale)
                - _HALF_LOG_2PI)


class HalfCauchy(Distribution):
    arg_constraints = {"scale": constraints.positive}
    support = constraints.positive

    def __init__(self, scale=1.0):
        self.scale = scale
        super().__init__(jnp.shape(scale))

    def sample(self, rng_key=None, sample_shape=()):
        eps = jax.random.cauchy(rng_key, self.shape(sample_shape))
        x = jnp.abs(self.scale * eps)
        return jnp.clip(x, _tiny(x))

    def log_prob(self, value):
        z = value / self.scale
        return (math.log(2.0 / math.pi) - fmath.log(self.scale)
                - fmath.log1p(z * z))


class Dirichlet(Distribution):
    arg_constraints = {"concentration": constraints.positive_vector}
    support = constraints.simplex

    def __init__(self, concentration):
        self.concentration = concentration
        shape = jnp.shape(concentration)
        if len(shape) < 1:
            raise ValueError("Dirichlet concentration must be at least 1-d")
        super().__init__(shape[:-1], shape[-1:])

    def sample(self, rng_key=None, sample_shape=()):
        batch = tuple(sample_shape) + self.batch_shape
        x = jax.random.dirichlet(rng_key, self.concentration, batch)
        x = jnp.clip(x, _tiny(x))
        return x / jnp.sum(x, axis=-1, keepdims=True)

    def log_prob(self, value):
        conc = self.concentration
        normalizer = gammaln(jnp.sum(conc, axis=-1)) - jnp.sum(
            gammaln(conc), axis=-1)
        return jnp.sum((conc - 1.0) * fmath.log(value), axis=-1) + normalizer


class MultivariateNormal(Distribution):
    arg_constraints = {"loc": constraints.real_vector,
                       "scale_tril": constraints.lower_cholesky}
    support = constraints.real_vector

    def __init__(self, loc=0.0, covariance_matrix=None, precision_matrix=None,
                 scale_tril=None):
        if sum(p is not None for p in
               (covariance_matrix, precision_matrix, scale_tril)) != 1:
            raise ValueError("provide exactly one of covariance_matrix, "
                             "precision_matrix, scale_tril")
        if covariance_matrix is not None:
            scale_tril = jnp.linalg.cholesky(covariance_matrix)
        elif precision_matrix is not None:
            scale_tril = jnp.linalg.cholesky(jnp.linalg.inv(precision_matrix))
        dim = scale_tril.shape[-1]
        if jnp.ndim(loc) == 0:
            loc = jnp.broadcast_to(loc, (dim,))
        self.loc = loc
        self.scale_tril = scale_tril
        batch_shape = jnp.broadcast_shapes(jnp.shape(loc)[:-1],
                                           jnp.shape(scale_tril)[:-2])
        super().__init__(batch_shape, (dim,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(loc=children[0], scale_tril=children[1])

    def sample(self, rng_key=None, sample_shape=()):
        eps = jax.random.normal(rng_key, self.shape(sample_shape))
        return self.loc + jnp.squeeze(
            self.scale_tril @ eps[..., None], axis=-1)

    def log_prob(self, value):
        diff = value - self.loc
        batch = jnp.broadcast_shapes(jnp.shape(diff)[:-1],
                                     jnp.shape(self.scale_tril)[:-2])
        tril = jnp.broadcast_to(self.scale_tril,
                                batch + self.scale_tril.shape[-2:])
        diff = jnp.broadcast_to(diff, batch + diff.shape[-1:])
        m = jax.scipy.linalg.solve_triangular(tril, diff[..., None],
                                              lower=True)[..., 0]
        half_log_det = jnp.sum(
            fmath.log(jnp.diagonal(tril, axis1=-2, axis2=-1)), axis=-1)
        dim = self.event_shape[0]
        return (-0.5 * jnp.sum(m * m, axis=-1) - half_log_det
                - dim * _HALF_LOG_2PI)


class Delta(Distribution):
    """Point mass at ``v``, optionally carrying an extra ``log_density`` term
    (used to book-keep change-of-variable corrections in autoguides and
    marginalized factors in models)."""

    arg_constraints = {"v": constraints.real, "log_density": constraints.real}
    support = constraints.real
    pytree_aux_fields = ("event_dim",)

    def __init__(self, v=0.0, log_density=0.0, event_dim=0):
        if event_dim > jnp.ndim(v):
            raise ValueError("event_dim exceeds ndim of the Delta value")
        self.v = v
        self.log_density = log_density
        shape = jnp.shape(v)
        split = len(shape) - event_dim
        super().__init__(shape[:split], shape[split:])

    # NamedTuple-style property clash: Distribution.event_dim already derives
    # from event_shape, which init computed from this arg — keep them in sync.
    @property
    def event_dim(self):
        return len(self.event_shape)

    def sample(self, rng_key=None, sample_shape=()):
        return jnp.broadcast_to(self.v, self.shape(sample_shape))

    def log_prob(self, value):
        log_prob = jnp.where(value == self.v, 0.0, -jnp.inf)
        log_prob = log_prob + self.log_density
        axes = tuple(range(-len(self.event_shape), 0))
        return jnp.sum(log_prob, axis=axes) if axes else log_prob

    def expand(self, batch_shape):
        return ExpandedDistribution(self, tuple(batch_shape))
