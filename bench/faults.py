"""Faults planted underneath the timed path.

Each is a context manager that patches the program while it is open and
puts it back after.  ``bench/tests/test_correctness.py`` runs the harness
with each and sees ``correct`` come out false; ``bench/readings.py
--faults`` reads the numbers each gives on the chip, at the cell's size.
A program is traced after the fault is planted, so build the ``MCMC``
inside the ``with``.
"""
from __future__ import annotations

import contextlib
import math


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def frozen_state():
    """The transition returns its state unchanged."""
    from repro.core.infer import NUTS
    setup = NUTS.setup

    def frozen(self, *args, **kwargs):
        return setup(self, *args, **kwargs)._replace(sample_fn=lambda s: s)

    return _patched(NUTS, "setup", frozen)


def half_batch():
    """The fused GLM potential sums the first half of the rows and doubles
    the result, inside the compiled programs (an eager call stays whole)."""
    import jax

    from repro.kernels import ops
    whole = ops.glm_potential_grad

    def half(x, y, w, offset=None, scale=None, family="bernoulli_logit"):
        if not isinstance(w, jax.core.Tracer):
            return whole(x, y, w, offset, scale, family)
        h = x.shape[0] // 2
        val, grad = whole(x[:h], y[:h], w,
                          None if offset is None else offset[:h], scale,
                          family)
        return 2 * val, 2 * grad

    return _patched(ops, "glm_potential_grad", half)


def altered_draw(step):
    """The draw collected when the state's counter reads ``step`` (it
    counts warmup steps too) is moved by 0.5 in every coordinate after it
    was produced."""
    import jax.numpy as jnp

    from repro.core.infer import hmc
    collect = hmc._collect_fn

    def altered(state):
        out = collect(state)
        out["z"] = jnp.where(state.i == step, out["z"] + 0.5, out["z"])
        return out

    return _patched(hmc, "_collect_fn", altered)


def hot_momentum():
    """Momenta drawn with twice the variance the kinetic energy assumes."""
    from repro.core.infer import hmc
    draw = hmc.momentum_sample

    def hot(rng_key, inverse_mass_matrix, dtype=None):
        return draw(rng_key, inverse_mass_matrix, dtype) * math.sqrt(2.0)

    return _patched(hmc, "momentum_sample", hot)


def uniform_choice():
    """The tree picks its draw among the trajectory's states with equal
    weights, not in proportion to ``exp(-H)``."""
    from repro.core.infer import hmc_util
    leaf = hmc_util._leaf_tree

    def uniform(*args, **kwargs):
        tree = leaf(*args, **kwargs)
        return tree._replace(weight=0.0 * tree.weight)

    return _patched(hmc_util, "_leaf_tree", uniform)


FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
          "altered_draw": altered_draw, "hot_momentum": hot_momentum,
          "uniform_choice": uniform_choice}
