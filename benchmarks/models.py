"""The paper's three benchmark models (Sec. 4 / App. C), in the repro API.

Datasets are synthesized to the paper's specs (offline container): the HMM
matches Stan manual §2.6 semi-supervised setup; logistic regression uses a
CoverType-shaped synthetic (581,012 x 54, binarized most-frequent class);
SKIM generates N=200 with 3 planted pairwise interactions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import random

import repro.core as pc
from repro.core import dist


# ---------------------------------------------------------------------------
# HMM (semi-supervised, 3 latent states, 10-dim categorical emissions)
# ---------------------------------------------------------------------------

def hmm_data(rng_key=None, T=600, T_sup=100, K=3, V=10):
    key = rng_key if rng_key is not None else random.PRNGKey(0)
    k1, k2, k3, k4 = random.split(key, 4)
    theta = dist.Dirichlet(jnp.full((K, K), 2.0)).sample(rng_key=k1)
    phi = dist.Dirichlet(jnp.full((K, V), 1.0)).sample(rng_key=k2)
    zs, ws = [jnp.zeros((), jnp.int32)], []
    key_seq = random.split(k3, T)
    key_emit = random.split(k4, T)
    z = jnp.zeros((), jnp.int32)
    for t in range(T):
        z = dist.Categorical(probs=theta[z]).sample(rng_key=key_seq[t])
        w = dist.Categorical(probs=phi[z]).sample(rng_key=key_emit[t])
        zs.append(z)
        ws.append(w)
    return {"w": jnp.stack(ws), "z_sup": jnp.stack(zs[1:T_sup + 1]),
            "T_sup": T_sup, "K": K, "V": V}


def hmm_model(data):
    K, V, T_sup = data["K"], data["V"], data["T_sup"]
    w = data["w"]
    theta = pc.sample("theta",
                      dist.Dirichlet(jnp.full((K, K), 2.0)).to_event(1))
    phi = pc.sample("phi", dist.Dirichlet(jnp.full((K, V), 1.0)).to_event(1))
    # supervised prefix: observed states
    z_sup = data["z_sup"]
    with pc.plate("sup", T_sup - 1):
        pc.sample("z_trans", dist.Categorical(probs=theta[z_sup[:-1]]),
                  obs=z_sup[1:])
        pc.sample("w_sup", dist.Categorical(probs=phi[z_sup[:-1]]),
                  obs=w[:T_sup - 1])
    # unsupervised suffix: marginalize latent states with a forward pass
    log_theta = jnp.log(theta)
    log_phi = jnp.log(phi)

    def step(log_alpha, wt):
        la = jax.nn.logsumexp(log_alpha[:, None] + log_theta, axis=0)
        la = la + log_phi[:, wt]
        return la, None

    init = log_theta[z_sup[-1]] + log_phi[:, w[T_sup - 1]]
    log_alpha, _ = jax.lax.scan(step, init, w[T_sup:])
    pc.sample("marginal", dist.Delta(jnp.zeros(()),
                                     log_density=jax.nn.logsumexp(log_alpha)),
              obs=jnp.zeros(()))


# ---------------------------------------------------------------------------
# fully-latent HMM (no supervision, no manual marginalization): the hidden
# states are summed out by the enumeration subsystem's `markov` combinator
# at O(T·K²) inside the jit'd NUTS potential (benchmarks/enum_hmm.py)
# ---------------------------------------------------------------------------

def enum_hmm_data(K, rng_key=None, T=120, V=16):
    key = rng_key if rng_key is not None else random.PRNGKey(0)
    k1, k2, k3 = random.split(key, 3)
    theta = dist.Dirichlet(jnp.full((K, K), 0.5)).sample(rng_key=k1)
    phi = dist.Dirichlet(jnp.full((K, V), 0.3)).sample(rng_key=k2)
    keys = random.split(k3, 2 * T)
    z, ws = jnp.zeros((), jnp.int32), []
    for t in range(T):
        z = dist.Categorical(probs=theta[z]).sample(rng_key=keys[2 * t])
        ws.append(dist.Categorical(probs=phi[z]).sample(rng_key=keys[2 * t + 1]))
    return {"w": jnp.stack(ws), "K": K, "V": V}


def enum_hmm_model(data):
    from repro.core.infer import markov
    K, V, w = data["K"], data["V"], data["w"]
    theta = pc.sample("theta",
                      dist.Dirichlet(jnp.full((K, K), 1.0)).to_event(1))
    phi = pc.sample("phi", dist.Dirichlet(jnp.full((K, V), 1.0)).to_event(1))

    def step(z_prev, w_t):
        z = pc.sample("z", dist.Categorical(probs=theta[z_prev]))
        pc.sample("w", dist.Categorical(probs=phi[z]), obs=w_t)
        return z

    markov(step, 0, w)


# ---------------------------------------------------------------------------
# logistic regression, CoverType-shaped (581012 x 54)
# ---------------------------------------------------------------------------

def covtype_data(rng_key=None, n=581_012, d=54):
    key = rng_key if rng_key is not None else random.PRNGKey(0)
    k1, k2, k3 = random.split(key, 3)
    x = random.normal(k1, (n, d))                 # features are normalized
    true_w = random.normal(k2, (d,)) * 0.5
    logits = x @ true_w
    y = dist.Bernoulli(logits=logits).sample(rng_key=k3)
    return {"x": x, "y": y.astype(jnp.float32), "true_w": true_w}


def logreg_model(x, y=None):
    d = x.shape[-1]
    w = pc.sample("w", dist.Normal(jnp.zeros(d), jnp.ones(d)).to_event(1))
    return pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y)


def logreg_model_glm(x, y=None):
    """Same model, opted into the fused GLM potential: the likelihood value
    AND its gradient come from one ``ops.glm_potential_grad`` pass over x
    (verified affine at setup; falls back to the plain potential if not)."""
    d = x.shape[-1]
    w = pc.sample("w", dist.Normal(jnp.zeros(d), jnp.ones(d)).to_event(1))
    return pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y,
                     infer={"potential": "glm"})


# ---------------------------------------------------------------------------
# SKIM — sparse kernel interaction model (Agrawal et al. 2019)
# ---------------------------------------------------------------------------

def skim_data(p, rng_key=None, n=200, n_inter=3):
    key = rng_key if rng_key is not None else random.PRNGKey(0)
    k1, k2, k3, k4 = random.split(key, 4)
    x = random.normal(k1, (n, p))
    pairs = random.choice(k2, p, shape=(n_inter, 2), replace=False)
    beta = jnp.zeros(p).at[pairs[:, 0]].set(1.0)
    inter = jnp.prod(x[:, pairs], axis=-1) @ jnp.ones(n_inter)
    y = x @ beta + 2.0 * inter + 0.1 * random.normal(k4, (n,))
    return {"x": x, "y": y}


def skim_model(x, y=None):
    """Kernel-trick formulation: per-dimension sparsity scales kappa with a
    horseshoe-like prior; interactions live in the quadratic kernel."""
    n, p = x.shape
    lam = pc.sample("lambda", dist.HalfCauchy(jnp.ones(p)).to_event(1))
    tau = pc.sample("tau", dist.HalfCauchy(1.0))
    eta1 = pc.sample("eta1", dist.HalfCauchy(1.0))
    c2 = pc.sample("c2", dist.InverseGamma(2.0, 2.0))
    sigma = pc.sample("sigma", dist.HalfNormal(1.0))
    lam2 = lam ** 2
    kappa = jnp.sqrt(eta1 ** 2 * c2 * lam2 / (eta1 ** 2 + c2 * lam2))
    xk = x * kappa * tau
    # quadratic kernel captures main + pairwise effects (kernel trick)
    k1 = xk @ xk.T
    K = (k1 + 1.0) ** 2 - 1.0
    K = K + (sigma ** 2 + 1e-4) * jnp.eye(n)
    pc.sample("y", dist.MultivariateNormal(jnp.zeros(n),
                                           covariance_matrix=K), obs=y)
