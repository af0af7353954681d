"""jsb_hmm: NumPyro's JSB-chorales HMM, its hidden state enumerated.

The model is NumPyro's ``examples/hmm_enum.py`` ``model_1``, written against
the system under test (``repro.core``): one ``markov`` chain per sequence,
the ``sequences`` plate around the chain and the ``tones`` plate inside
the step, with each padded step masked out of its sequence.  NUTS moves
the 16 x 15 stick-breaking and 16 x 51 sigmoid coordinates; every
potential evaluation sums the hidden states out, every step of every
sequence in one ``enum_contract_chain`` call, and its gradient in one more.
The data is made on the device from the seed in one jitted call.  The plain reference is in ``jsb_hmm_ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, random

import repro.core as pc
from repro.core import dist
from repro.core.handlers import mask
from repro.core.infer import markov

# Pallas kernels the compiled sample chunk runs on the chip: the chain's
# forward pass (and, by name, its backward pass) and the integrator
KERNELS = ("enum_contract_chain", "enum_contract_chain_vjp",
           "leapfrog_halfstep")


def model(sequences, lengths, transition_prior, emission_prior):
    num_sequences, max_length, data_dim = sequences.shape
    hidden_dim = transition_prior.shape[0]
    probs_x = pc.sample("probs_x", dist.Dirichlet(transition_prior)
                        .to_event(1))
    probs_y = pc.sample("probs_y", dist.Beta(emission_prior[0],
                                             emission_prior[1])
                        .expand([hidden_dim, data_dim]).to_event(2))

    def transition(x_prev, step):
        y_t, t = step
        with mask(mask=(t < lengths)[:, None]):
            x = pc.sample("x", dist.Categorical(probs_x[x_prev]))
            with pc.plate("tones", data_dim, dim=-1):
                pc.sample("y", dist.Bernoulli(probs_y[x.squeeze(-1)]),
                          obs=y_t)
        return x

    with pc.plate("sequences", num_sequences, dim=-2):
        markov(transition, 0, (jnp.swapaxes(sequences, 0, 1),
                               jnp.arange(max_length)))


def lengths(spec):
    """The sequences' lengths, fixed by the configuration."""
    rng = np.random.default_rng(spec["lengths_seed"])
    out = rng.integers(spec["min_length"], spec["max_length"] + 1,
                       size=spec["num_sequences"])
    out[rng.integers(spec["num_sequences"])] = spec["max_length"]
    return out.astype(np.int32)


def priors(spec):
    """The Dirichlet concentration of each transition row, (K, K), and
    the Beta prior of each emission, (2,)."""
    k = spec["hidden_dim"]
    tp, ep = spec["transition_prior"], spec["emission_prior"]
    conc = (tp["off_diagonal"] * np.ones((k, k))
            + tp["diagonal"] * np.eye(k)).astype(np.float32)
    return conc, np.array([ep["alpha"], ep["beta"]], np.float32)


@functools.partial(jax.jit,
                   static_argnames=("max_length", "data_dim", "hidden_dim"))
def _make(key, lengths, beta, max_length, data_dim, hidden_dim):
    k_x, k_y, k_path, k_tones = random.split(key, 4)
    # planted parameters: sticky transitions, sparse emissions
    probs_x = random.dirichlet(k_x, 1.0 + 20.0 * jnp.eye(hidden_dim))
    probs_y = random.beta(k_y, beta[0], beta[1], (hidden_dim, data_dim))

    def step(x_prev, key):
        x = random.categorical(key, jnp.log(probs_x[x_prev]))
        return x, x

    _, path = lax.scan(step, jnp.zeros(lengths.shape, jnp.int32),
                       random.split(k_path, max_length))          # (T, S)
    tones = random.bernoulli(k_tones, probs_y[path.T])          # (S, T, D)
    live = jnp.arange(max_length) < lengths[:, None]
    return jnp.where(live[..., None], tones, False).astype(jnp.float32)


def make_data(key, spec):
    """``(model_args, inputs)``: the arguments of ``MCMC.run`` and the
    arrays the reference reads."""
    conc, beta = priors(spec)
    lens = jnp.asarray(lengths(spec))
    sequences = _make(key, lens, jnp.asarray(beta), spec["max_length"],
                      spec["data_dim"], spec["hidden_dim"])
    args = (sequences, lens, jnp.asarray(conc), jnp.asarray(beta))
    return args, {"sequences": sequences, "lengths": lens,
                  "transition_prior": args[2], "emission_prior": args[3]}


def flops_per_grad(spec):
    """Operations of one potential-and-gradient evaluation over the
    sequences' own lengths (padding counts as waste): per sequence and
    step the emission, 2 x K x D multiply-adds against log p and
    log(1 - p), and past the first step the contraction, K^2 additions
    and K^2 terms summed; the gradient counted as twice the forward."""
    k, d = spec["hidden_dim"], spec["data_dim"]
    steps = int(lengths(spec).sum())
    forward = steps * 4 * k * d + (steps - spec["num_sequences"]) * 2 * k * k
    return 3 * forward


def enum_call_cost(spec, chains):
    """Least work of one ``enum_contract_chain`` call, forward or backward,
    whatever implements it: one pass over every step of B = chains x
    sequences rows, reading each step's emission (B, K) and each chain's
    transition (K, K) once, the (T-1, B) step mask as bytes, and writing
    each step's (B, K) message, f32; and B K^2 log-sum-exp terms a step
    (an addition and an accumulation each)."""
    k = spec["hidden_dim"]
    rows = chains * spec["num_sequences"]
    steps = spec["max_length"] - 1
    return {"flops": 2 * steps * rows * k * k,
            "bytes": 4 * (2 * steps * rows * k + chains * k * k)
            + steps * rows}
