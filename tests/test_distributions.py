"""Property-based tests of the distribution library (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax import random

from repro.core import dist
from repro.core.dist import biject_to

jax.config.update("jax_enable_x64", False)

# allow_subnormal=False: XLA sets FTZ/DAZ processor flags which trip
# hypothesis' float validation (simonbyrne.github.io/notes/fastmath);
# bounds are powers of two so they are exactly representable at width=32
finite = st.floats(-4.0, 4.0, allow_nan=False, width=32,
                   allow_subnormal=False)
positive = st.floats(0.125, 4.0, allow_nan=False, width=32,
                     allow_subnormal=False)

CASES = [
    (dist.Normal, (finite, positive)),
    (dist.LogNormal, (finite, positive)),
    (dist.Cauchy, (finite, positive)),
    (dist.StudentT, (positive, finite, positive)),
    (dist.Gamma, (positive, positive)),
    (dist.Beta, (positive, positive)),
    (dist.Exponential, (positive,)),
    (dist.HalfNormal, (positive,)),
    (dist.HalfCauchy, (positive,)),
    (dist.InverseGamma, (positive, positive)),
]


@pytest.mark.parametrize("cls,strats", CASES,
                         ids=[c.__name__ for c, _ in CASES])
@settings(max_examples=20, deadline=None)
@given(data=st.data(), seed=st.integers(0, 2**31 - 1))
def test_sample_in_support_logprob_finite(cls, strats, data, seed):
    params = [data.draw(s) for s in strats]
    d = cls(*params)
    x = d.sample(rng_key=random.PRNGKey(seed), sample_shape=(7,))
    assert x.shape == (7,)
    lp = d.log_prob(x)
    assert bool(jnp.all(jnp.isfinite(lp))), (params, x, lp)
    # support constraint check
    assert bool(jnp.all(d.support(x))), (cls.__name__, params, x)


@pytest.mark.parametrize("cls,strats", CASES,
                         ids=[c.__name__ for c, _ in CASES])
@settings(max_examples=15, deadline=None)
@given(data=st.data(), u=st.floats(-2.0, 2.0, width=32,
                                   allow_subnormal=False))
def test_biject_roundtrip(cls, strats, data, u):
    params = [data.draw(s) for s in strats]
    d = cls(*params)
    t = biject_to(d.support)
    x = t(jnp.asarray(u))
    assert bool(d.support(x)), (cls.__name__, params, float(x))
    u2 = t.inv(x)
    assert abs(float(u2) - u) < 1e-3


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_dirichlet_simplex(seed):
    d = dist.Dirichlet(jnp.array([0.5, 1.5, 3.0]))
    x = d.sample(rng_key=random.PRNGKey(seed))
    assert abs(float(x.sum()) - 1.0) < 1e-5
    assert bool(jnp.isfinite(d.log_prob(x)))


def test_normal_moments_mc():
    d = dist.Normal(1.5, 2.0)
    x = d.sample(rng_key=random.PRNGKey(0), sample_shape=(50000,))
    assert abs(float(x.mean()) - 1.5) < 0.05
    assert abs(float(x.std()) - 2.0) < 0.05


def test_logprob_matches_scipy_normal():
    from math import log, pi
    d = dist.Normal(0.0, 1.0)
    x = jnp.array([0.0, 1.0, -2.0])
    expected = -0.5 * x**2 - 0.5 * log(2 * pi)
    assert np.allclose(d.log_prob(x), expected, atol=1e-5)


def test_categorical_bernoulli():
    logits = jnp.array([0.1, 0.5, -0.3])
    c = dist.Categorical(logits=logits)
    x = c.sample(rng_key=random.PRNGKey(0), sample_shape=(1000,))
    assert set(np.unique(np.asarray(x))) <= {0, 1, 2}
    lp = c.log_prob(x)
    assert bool(jnp.all(lp <= 0.0))
    b = dist.Bernoulli(logits=jnp.array(0.3))
    xb = b.sample(rng_key=random.PRNGKey(1), sample_shape=(1000,))
    p = jax.nn.sigmoid(0.3)
    assert abs(float(xb.mean()) - float(p)) < 0.06


def test_independent_event_dims():
    d = dist.Normal(jnp.zeros((3, 4)), 1.0).to_event(1)
    assert d.batch_shape == (3,) and d.event_shape == (4,)
    x = d.sample(rng_key=random.PRNGKey(0))
    assert d.log_prob(x).shape == (3,)


def test_mvn_logprob_vs_dense_formula():
    cov = jnp.array([[2.0, 0.3], [0.3, 1.0]])
    loc = jnp.array([1.0, -1.0])
    d = dist.MultivariateNormal(loc, covariance_matrix=cov)
    x = jnp.array([0.5, 0.5])
    diff = x - loc
    expected = (-0.5 * diff @ jnp.linalg.inv(cov) @ diff
                - 0.5 * jnp.log(jnp.linalg.det(cov))
                - jnp.log(2 * jnp.pi))
    assert abs(float(d.log_prob(x)) - float(expected)) < 1e-4


def _sigmoid64(v):
    return np.where(v >= 0, 1 / (1 + np.exp(-v)), np.exp(v) / (1 + np.exp(v)))


FMATH_CASES = {
    "log": (np.logspace(-37, 30, 20001), np.log),
    "log1p": (np.concatenate([-np.logspace(-12, -1e-7, 10001),
                              np.logspace(-12, 6, 10001)]), np.log1p),
    "exp": (np.linspace(-87, 88, 20001), np.exp),
    "sigmoid": (np.linspace(-100, 100, 20001), _sigmoid64),
    "softplus": (np.linspace(-100, 100, 20001),
                 lambda v: np.logaddexp(0, v)),
}


@pytest.mark.parametrize("name", sorted(FMATH_CASES))
def test_fmath_holds_a_few_ulps(name):
    """The densities' log/exp family agrees with float64 within 3 ulps
    (a TPU's own f32 log1p reads up to 3.7e-4, about 6,000 ulps), and its
    derivative is the closed form."""
    from repro.core.dist import fmath
    x, exact = FMATH_CASES[name]
    x = x.astype(np.float32)
    got = np.asarray(jax.jit(getattr(fmath, name))(x), np.float64)
    want = exact(x.astype(np.float64))
    ok = np.abs(want) > np.finfo(np.float32).tiny
    ulps = np.abs(got - want)[ok] / np.abs(want)[ok] / np.finfo(np.float32).eps
    assert ulps.max() <= 3.0
    f = getattr(fmath, name)
    at = jnp.float32(0.3)
    grad = {"log": 1 / 0.3, "log1p": 1 / 1.3, "exp": np.exp(0.3),
            "sigmoid": _sigmoid64(0.3) * _sigmoid64(-0.3),
            "softplus": _sigmoid64(0.3)}[name]
    np.testing.assert_allclose(jax.grad(f)(at), grad, rtol=1e-6)


def test_fmath_ends_of_the_range():
    from repro.core.dist import fmath
    np.testing.assert_array_equal(
        fmath.log(jnp.array([0.0, 1.0, jnp.inf])), [-np.inf, 0.0, np.inf])
    for f in (fmath.log, fmath.log1p, fmath.exp, fmath.sigmoid,
              fmath.softplus):
        assert np.isnan(f(jnp.float32(np.nan)))
    assert np.isnan(fmath.log(jnp.float32(-1.0)))
    np.testing.assert_array_equal(fmath.log1p(jnp.array([-1.0, 0.0])),
                                  [-np.inf, 0.0])
    np.testing.assert_array_equal(
        fmath.exp(jnp.array([-200.0, 0.0, 200.0])), [0.0, 1.0, np.inf])
    np.testing.assert_array_equal(fmath.sigmoid(jnp.array([-200.0, 200.0])),
                                  [0.0, 1.0])
    # other dtypes take jax.numpy's own functions
    assert fmath.log(jnp.ones(2, jnp.bfloat16)).dtype == jnp.bfloat16


def test_fmath_sigmoid_is_correctly_rounded_near_one():
    """Above 0 the sigmoid is the f32 number nearest the true value: near
    p = 1, f32 numbers lie 6e-8 apart, so a density's log(1 - p) moves by
    1 % from one to the next at p = 1 - 6e-6."""
    from repro.core.dist import fmath
    x = np.linspace(9, 16, 20001).astype(np.float32)
    want = (1 / (1 + np.exp(-x.astype(np.float64)))).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(jax.jit(fmath.sigmoid)(x)),
                                  want)
