"""Bijective transforms and the ``biject_to`` constraint registry.

A :class:`Transform` ``t`` maps unconstrained space to a constrained support:
``x = t(u)``, ``u = t.inv(x)``, with ``t.log_abs_det_jacobian(u, x)`` giving
``log |det dx/du|``.  ``biject_to(constraint)`` dispatches a constraint (see
:mod:`repro.core.dist.constraints`) to the transform whose codomain is that
constraint's support — the mechanism ``infer/util.py`` uses to move every
latent site onto R^n where HMC/NUTS and autoguides operate.

``log_abs_det_jacobian`` is elementwise for scalar-event transforms and
reduced over the event dimension for vector/matrix-event transforms
(stick-breaking, lower-Cholesky); callers sum whatever remains, so both
conventions compose with ``potential_energy``.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from . import constraints, fmath

__all__ = [
    "Transform",
    "AffineTransform",
    "IdentityTransform",
    "ExpTransform",
    "SigmoidTransform",
    "IntervalTransform",
    "StickBreakingTransform",
    "LowerCholeskyTransform",
    "biject_to",
    "register_biject_to",
]


class Transform:
    domain = constraints.real
    codomain = constraints.real

    def __call__(self, x):
        raise NotImplementedError

    def inv(self, y):
        raise NotImplementedError

    def log_abs_det_jacobian(self, x, y):
        raise NotImplementedError

    def __repr__(self):
        return self.__class__.__name__ + "()"


class IdentityTransform(Transform):
    def __call__(self, x):
        return x

    def inv(self, y):
        return y

    def log_abs_det_jacobian(self, x, y):
        return jnp.zeros_like(x)


class AffineTransform(Transform):
    """``x -> loc + scale * x`` (elementwise; ``scale`` must be nonzero).

    The workhorse of non-centered reparameterizations:
    ``TransformedDistribution(Normal(0, 1), AffineTransform(mu, tau))`` is
    ``Normal(mu, tau)`` with the location/scale split out as a deterministic
    transform that ``TransformReparam`` can peel off.
    """

    def __init__(self, loc, scale):
        self.loc = loc
        self.scale = scale

    def __call__(self, x):
        return self.loc + self.scale * x

    def inv(self, y):
        return (y - self.loc) / self.scale

    def log_abs_det_jacobian(self, x, y):
        return jnp.broadcast_to(fmath.log(jnp.abs(self.scale)), jnp.shape(x))


class ExpTransform(Transform):
    codomain = constraints.positive

    def __call__(self, x):
        return fmath.exp(x)

    def inv(self, y):
        return fmath.log(y)

    def log_abs_det_jacobian(self, x, y):
        return x


def _clipped_sigmoid(x):
    """``sigmoid(x)`` kept inside ``[tiny, 1 - eps]`` of its dtype, as
    NumPyro's transforms keep it.  Past ``x < -87`` f32 underflows to 0
    (the TPU flushes subnormals), and a density with a ``log(p)`` term
    whose factor is negative (a Beta or Dirichlet concentration below 1)
    would read +inf there: a hole the sampler falls into.  The log
    Jacobian stays the exact one of the unclipped map, so the potential
    still rises past the clip."""
    finfo = jnp.finfo(jnp.result_type(x, jnp.float32))
    return jnp.clip(fmath.sigmoid(x), finfo.tiny, 1.0 - finfo.eps)


class SigmoidTransform(Transform):
    codomain = constraints.unit_interval

    def __call__(self, x):
        return _clipped_sigmoid(x)

    def inv(self, y):
        return fmath.log(y) - fmath.log1p(-y)

    def log_abs_det_jacobian(self, x, y):
        # log sigma(x) + log sigma(-x)
        return -fmath.softplus(x) - fmath.softplus(-x)


class IntervalTransform(Transform):
    """u -> lower + (upper - lower) * sigmoid(u), the sigmoid held off 0
    and 1 as :class:`SigmoidTransform` holds it (``unit_interval`` maps
    here)."""

    def __init__(self, lower_bound=0.0, upper_bound=1.0):
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound
        self.codomain = constraints.interval(lower_bound, upper_bound)

    def __call__(self, x):
        width = self.upper_bound - self.lower_bound
        return self.lower_bound + width * _clipped_sigmoid(x)

    def inv(self, y):
        z = (y - self.lower_bound) / (self.upper_bound - self.lower_bound)
        return fmath.log(z) - fmath.log1p(-z)

    def log_abs_det_jacobian(self, x, y):
        width = self.upper_bound - self.lower_bound
        return fmath.log(width) - fmath.softplus(x) - fmath.softplus(-x)


class StickBreakingTransform(Transform):
    """R^{K-1} -> K-simplex via the stick-breaking construction (Stan 10.7).

    ``z_k = sigmoid(u_k - log(K - k - 1))`` (0-indexed offset keeps u = 0 at
    the uniform simplex point), ``y_k = z_k * prod_{i<k}(1 - z_i)``.  As in
    :class:`SigmoidTransform`, ``z`` and ``y`` are kept at or above the
    dtype's smallest normal number, so no coordinate is an exact 0.
    """

    codomain = constraints.simplex

    def _offset(self, size):
        # [size, ..., 1] from an iota, not from a host array: jax 0.9 cannot
        # pass a host-array constant to a program as an argument
        # (JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS=1)
        return fmath.log(size - jnp.arange(size, dtype=jnp.float32))

    def __call__(self, x):
        xo = x - self._offset(x.shape[-1])
        z = _clipped_sigmoid(xo)
        # 1 - z from -xo: ``1.0 - z`` keeps a few digits at best where z is
        # within a few ulps of 1, and the rest of the stick is then off by
        # up to tens of percent
        finfo = jnp.finfo(jnp.result_type(x, jnp.float32))
        z1m = jnp.maximum(fmath.sigmoid(-xo), finfo.eps)
        z1m_cumprod = jnp.cumprod(z1m, axis=-1)
        pad_shape = x.shape[:-1] + (1,)
        lead = jnp.concatenate(
            [jnp.ones(pad_shape, x.dtype), z1m_cumprod[..., :-1]], axis=-1)
        y = jnp.concatenate([z * lead, z1m_cumprod[..., -1:]], axis=-1)
        return jnp.maximum(y, jnp.finfo(y.dtype).tiny)

    def inv(self, y):
        # remainder before stick k: 1 - sum_{i<k} y_i
        cs = jnp.cumsum(y[..., :-1], axis=-1)
        pad_shape = y.shape[:-1] + (1,)
        remainder = jnp.concatenate(
            [jnp.ones(pad_shape, y.dtype), 1.0 - cs[..., :-1]], axis=-1)
        z = jnp.clip(y[..., :-1] / remainder, 1e-30, 1.0 - 1e-7)
        u = fmath.log(z) - fmath.log1p(-z)
        return u + self._offset(u.shape[-1])

    def log_abs_det_jacobian(self, x, y):
        xo = x - self._offset(x.shape[-1])
        # dy_k/du_k = z_k (1 - z_k) * remainder_k, triangular Jacobian; the
        # log remainder before stick k, sum_{i<k} log(1 - z_i), is summed
        # from x: 1 - cumsum(y) would cancel where the sticks take nearly
        # everything
        log_1mz = -fmath.softplus(xo)
        log_remainder = jnp.cumsum(log_1mz, axis=-1) - log_1mz
        elem = -fmath.softplus(-xo) + log_1mz + log_remainder
        return jnp.sum(elem, axis=-1)


class LowerCholeskyTransform(Transform):
    """R^{d(d+1)/2} -> lower-triangular with positive (exp'd) diagonal.

    Layout: the first d(d-1)/2 entries fill the strict lower triangle
    row-major; the last d entries are the log-diagonal.
    """

    codomain = constraints.lower_cholesky

    @staticmethod
    def _matrix_dim(flat_size):
        d = int(round((math.sqrt(8.0 * flat_size + 1.0) - 1.0) / 2.0))
        if d * (d + 1) // 2 != flat_size:
            raise ValueError(
                f"size {flat_size} is not a triangular number d(d+1)/2")
        return d

    def __call__(self, x):
        d = self._matrix_dim(x.shape[-1])
        idx = jnp.tril_indices(d, -1)
        m = jnp.zeros(x.shape[:-1] + (d, d), x.dtype)
        m = m.at[..., idx[0], idx[1]].set(x[..., : d * (d - 1) // 2])
        diag = fmath.exp(x[..., d * (d - 1) // 2:])
        return m.at[..., jnp.arange(d), jnp.arange(d)].set(diag)

    def inv(self, y):
        d = y.shape[-1]
        idx = jnp.tril_indices(d, -1)
        offdiag = y[..., idx[0], idx[1]]
        log_diag = fmath.log(jnp.diagonal(y, axis1=-2, axis2=-1))
        return jnp.concatenate([offdiag, log_diag], axis=-1)

    def log_abs_det_jacobian(self, x, y):
        d = self._matrix_dim(x.shape[-1])
        return jnp.sum(x[..., d * (d - 1) // 2:], axis=-1)


# ---------------------------------------------------------------------------
# biject_to: constraint -> transform dispatch
# ---------------------------------------------------------------------------

_REGISTRY = {}


def register_biject_to(constraint_type, factory=None):
    """Register ``factory(constraint) -> Transform`` for a constraint class.
    Usable as a decorator: ``@register_biject_to(_MyConstraint)``."""
    if factory is None:
        return lambda f: register_biject_to(constraint_type, f)
    _REGISTRY[constraint_type] = factory
    return factory


register_biject_to(constraints._Real, lambda c: IdentityTransform())
register_biject_to(constraints._RealVector, lambda c: IdentityTransform())
register_biject_to(constraints._Positive, lambda c: ExpTransform())
register_biject_to(constraints._UnitInterval,
                   lambda c: IntervalTransform(0.0, 1.0))
register_biject_to(
    constraints._Interval,
    lambda c: IntervalTransform(c.lower_bound, c.upper_bound))
register_biject_to(constraints._Simplex, lambda c: StickBreakingTransform())
register_biject_to(constraints._LowerCholesky,
                   lambda c: LowerCholeskyTransform())


def biject_to(constraint):
    """Return a bijection from unconstrained reals onto ``constraint``'s
    support.  Dispatch walks the constraint's MRO so subclassed constraints
    inherit their parent's transform unless overridden."""
    for klass in type(constraint).__mro__:
        factory = _REGISTRY.get(klass)
        if factory is not None:
            return factory(constraint)
    raise NotImplementedError(
        f"no biject_to bijection registered for constraint {constraint!r}; "
        "discrete supports (boolean/integer_interval) have no bijection — "
        "observe those sites or marginalize them out.")
