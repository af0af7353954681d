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

import jax
import jax.numpy as jnp

from . import constraints

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
        return jnp.broadcast_to(jnp.log(jnp.abs(self.scale)), jnp.shape(x))


class ExpTransform(Transform):
    codomain = constraints.positive

    def __call__(self, x):
        return jnp.exp(x)

    def inv(self, y):
        return jnp.log(y)

    def log_abs_det_jacobian(self, x, y):
        return x


class SigmoidTransform(Transform):
    codomain = constraints.unit_interval

    def __call__(self, x):
        return jax.nn.sigmoid(x)

    def inv(self, y):
        return jnp.log(y) - jnp.log1p(-y)

    def log_abs_det_jacobian(self, x, y):
        # log sigma(x) + log sigma(-x)
        return -jax.nn.softplus(x) - jax.nn.softplus(-x)


class IntervalTransform(Transform):
    """u -> lower + (upper - lower) * sigmoid(u)."""

    def __init__(self, lower_bound=0.0, upper_bound=1.0):
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound
        self.codomain = constraints.interval(lower_bound, upper_bound)

    def __call__(self, x):
        width = self.upper_bound - self.lower_bound
        return self.lower_bound + width * jax.nn.sigmoid(x)

    def inv(self, y):
        z = (y - self.lower_bound) / (self.upper_bound - self.lower_bound)
        return jnp.log(z) - jnp.log1p(-z)

    def log_abs_det_jacobian(self, x, y):
        width = self.upper_bound - self.lower_bound
        return jnp.log(width) - jax.nn.softplus(x) - jax.nn.softplus(-x)


class StickBreakingTransform(Transform):
    """R^{K-1} -> K-simplex via the stick-breaking construction (Stan 10.7).

    ``z_k = sigmoid(u_k - log(K - k - 1))`` (0-indexed offset keeps u = 0 at
    the uniform simplex point), ``y_k = z_k * prod_{i<k}(1 - z_i)``.
    """

    codomain = constraints.simplex

    def _offset(self, size):
        # [size, ..., 1] from an iota, not from a host array: jax 0.9 cannot
        # pass a host-array constant to a program as an argument
        # (JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS=1)
        return jnp.log(size - jnp.arange(size, dtype=jnp.float32))

    def __call__(self, x):
        z = jax.nn.sigmoid(x - self._offset(x.shape[-1]))
        z1m_cumprod = jnp.cumprod(1.0 - z, axis=-1)
        pad_shape = x.shape[:-1] + (1,)
        lead = jnp.concatenate(
            [jnp.ones(pad_shape, x.dtype), z1m_cumprod[..., :-1]], axis=-1)
        return jnp.concatenate([z * lead, z1m_cumprod[..., -1:]], axis=-1)

    def inv(self, y):
        # remainder before stick k: 1 - sum_{i<k} y_i
        cs = jnp.cumsum(y[..., :-1], axis=-1)
        pad_shape = y.shape[:-1] + (1,)
        remainder = jnp.concatenate(
            [jnp.ones(pad_shape, y.dtype), 1.0 - cs[..., :-1]], axis=-1)
        z = jnp.clip(y[..., :-1] / remainder, 1e-30, 1.0 - 1e-7)
        u = jnp.log(z) - jnp.log1p(-z)
        return u + self._offset(u.shape[-1])

    def log_abs_det_jacobian(self, x, y):
        xo = x - self._offset(x.shape[-1])
        cs = jnp.cumsum(y[..., :-1], axis=-1)
        pad_shape = y.shape[:-1] + (1,)
        remainder = jnp.concatenate(
            [jnp.ones(pad_shape, y.dtype), 1.0 - cs[..., :-1]], axis=-1)
        # dy_k/du_k = z_k (1 - z_k) * remainder_k, triangular Jacobian
        elem = (-jax.nn.softplus(xo) - jax.nn.softplus(-xo)
                + jnp.log(jnp.clip(remainder, 1e-30)))
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
        diag = jnp.exp(x[..., d * (d - 1) // 2:])
        return m.at[..., jnp.arange(d), jnp.arange(d)].set(diag)

    def inv(self, y):
        d = y.shape[-1]
        idx = jnp.tril_indices(d, -1)
        offdiag = y[..., idx[0], idx[1]]
        log_diag = jnp.log(jnp.diagonal(y, axis1=-2, axis2=-1))
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
