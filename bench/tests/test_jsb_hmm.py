"""The jsb_hmm cell's comparison that decides ``correct``, on the CPU at a
small size of its configuration.

A sound run is ``correct``; the control (the reference in bfloat16 in the
program's place), momenta drawn too hot, and a fault planted here are not.
The planted fault lets each masked step of a padded sequence through as a
zeroed factor: every such step then adds log K to its sequence's
marginal, (T - L_s) log K in all, while the gradient stays right, so only
``pe_gap`` can see it.
"""
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import checks, faults, harness, spec

SMALL = {"num_sequences": 8, "max_length": 16, "data_dim": 6,
         "hidden_dim": 3, "min_length": 4}
SEED = 2**31 + 151


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(harness, "device_info",
                        lambda chips: (jax.devices()[:chips], dict(cpu)))
    monkeypatch.setattr(spec, "peaks", lambda kind: None)
    monkeypatch.setattr(harness, "check_kernels", lambda cell, mcmc: None)


def small_cell():
    cell = spec.Cell("jsb_hmm_nuts4")
    cell.config = dict(cell.config, **SMALL)
    return cell


def run(cell):
    return harness.run_cell(cell, SEED, 0.1, False, t_start=time.time())


def masked_steps_let_through(monkeypatch):
    """Each masked step's transition scores 0 for every pair of states, as
    a plain masked site would, in place of holding the row's state."""
    enum = importlib.import_module("repro.core.infer.enum")
    step_factors = enum._step_factors

    def zeroed(*args, **kwargs):
        unary, pair, keep = step_factors(*args, **kwargs)
        if pair is None:
            return unary, pair, keep
        pair = jnp.where(keep[:, None, None], pair, 0.0)
        return unary, pair, jnp.ones_like(keep)

    monkeypatch.setattr(enum, "_step_factors", zeroed)


def test_small_config_keeps_the_shapes_of_the_model():
    cell = small_cell()
    lengths = cell.model.lengths(cell.config)
    assert lengths.shape == (8,) and lengths.max() == 16
    assert lengths.min() >= 4


def test_sound_run_is_correct():
    result = run(small_cell())
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_control_fails_the_limits():
    cell = small_cell()
    keys = harness.seed_keys(SEED)
    model_args, inputs = cell.model.make_data(keys["data"], cell.config)
    fit = harness.Fit(harness.build_mcmc(cell), keys["fits"],
                      model_args).fetch()
    program = checks.gaps(cell.reference, inputs, [fit])
    control = checks.gaps(cell.reference, inputs, [fit], control=True)
    assert checks.judge(program, cell.limits)[0]
    assert not checks.judge(control, cell.limits)[0]


def test_hot_momentum_is_not_correct():
    cell = small_cell()
    with faults.hot_momentum():
        result = run(cell)
    assert not result["correct"]
    row = result["checks"]["virial_z"]
    assert float(row["value"]) > row["limit"]


def test_masked_steps_let_through_are_not_correct(monkeypatch):
    cell = small_cell()
    masked_steps_let_through(monkeypatch)
    result = run(cell)
    assert not result["correct"]
    checks_ = result["checks"]
    assert float(checks_["pe_gap"]["value"]) > checks_["pe_gap"]["limit"]
    # the gradient is right: only the density's value moved
    assert float(checks_["grad_gap"]["value"]) <= checks_["grad_gap"]["limit"]


@pytest.mark.parametrize("logit", [11.0, 12.0, 12.5, 15.0, 15.5, 16.2])
def test_program_and_reference_agree_where_a_tone_is_nearly_sure(logit):
    """Draws that put an emission probability within 1e-5 of 1, where
    f32 numbers lie 6e-8 apart, so that log(1 - p) moves by ~1 % from one
    to the next and is reused at every step in that state.  The program
    holds the correctly rounded p and the reference the f32 number nearest
    its own float64 p: they agree to f32 rounding, as they do elsewhere
    (one f32 number off moved a full-size potential by 7 nats)."""
    from repro.core.infer.util import initialize_model_structure
    cell = small_cell()
    keys = harness.seed_keys(SEED)
    model_args, inputs = cell.model.make_data(keys["data"], cell.config)
    potential = initialize_model_structure(jax.random.PRNGKey(0),
                                           cell.model.model, model_args)[0]
    k, d = cell.config["hidden_dim"], cell.config["data_dim"]
    z = jax.random.normal(jax.random.PRNGKey(1), (4, k * (k - 1) + k * d))
    z = z.at[:, k * (k - 1) + jnp.arange(4) * (d + 1)].set(logit)
    u, g = jax.vmap(jax.value_and_grad(potential))(z)
    u_ref, g_ref = cell.reference.potential_and_grad(inputs, z)
    assert np.max(np.abs(np.asarray(u, np.float64) - u_ref)) < 1e-3
    rel = (np.linalg.norm(np.asarray(g, np.float64) - g_ref, axis=-1)
           / np.linalg.norm(g_ref, axis=-1))
    assert rel.max() < 1e-5
