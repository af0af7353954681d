"""covtype_logreg: Bayesian logistic regression at the Covertype size.

The model is written against the system under test (``repro.core``) and
marks its likelihood for the fused GLM potential.  The data is made on the
device from the seed in one jitted call.  The plain reference is in
``covtype_logreg_ref.py`` beside this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import random

import repro.core as pc
from repro.core import dist

# Pallas kernels the compiled sample chunk runs on the chip
KERNELS = ("glm_potential_grad", "leapfrog_halfstep")


def model(x, y=None):
    d = x.shape[-1]
    w = pc.sample("w", dist.Normal(jnp.zeros(d), jnp.ones(d)).to_event(1))
    return pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y,
                     infer={"potential": "glm"})


@functools.partial(jax.jit, static_argnames=("n", "d", "w_scale"))
def _make(key, n, d, w_scale):
    k1, k2, k3 = random.split(key, 3)
    x = random.normal(k1, (n, d), jnp.float32)       # normalized features
    true_w = random.normal(k2, (d,), jnp.float32) * w_scale
    y = random.bernoulli(k3, jax.nn.sigmoid(x @ true_w)).astype(jnp.float32)
    return x, y, true_w


def make_data(key, spec):
    """``(model_args, inputs)``: the arguments of ``MCMC.run`` and the
    arrays the reference reads."""
    x, y, true_w = _make(key, spec["n"], spec["d"], spec["true_w_scale"])
    return (x, y), {"x": x, "y": y, "true_w": true_w}


def flops_per_grad(spec):
    """Operations of one potential-and-gradient evaluation: the forward
    ``x @ w`` and the backward ``x.T @ r``, two multiply-adds per entry of
    x each."""
    return 4 * spec["n"] * spec["d"]


def glm_call_cost(spec, chains):
    """Least work of one ``glm_potential_grad`` call over ``chains``
    chains, whatever implements it: x and y read once (f32), and the
    forward and backward products for every chain."""
    n, d = spec["n"], spec["d"]
    return {"flops": 4 * n * d * chains, "bytes": 4 * (n * d + n)}
