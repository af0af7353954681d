"""Discrete-latent enumeration subsystem: enum-aware log_density vs brute
force, markov chain elimination (correctness + O(T·K²) cost shape),
infer_discrete posteriors vs exact forward-backward, and the jit'd NUTS
executor running mixture/HMM models with untouched model code."""
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

import repro.core as pc
from repro.core import dist
from repro.core.handlers import seed, substitute, trace
from repro.core.infer import (
    MCMC,
    NUTS,
    config_enumerate,
    infer_discrete,
    log_density,
    markov,
    print_summary,
)

pytestmark = pytest.mark.enum

K, N = 3, 7
WEIGHTS = jnp.array([0.2, 0.5, 0.3])
MUS = jnp.array([-2.0, 0.0, 2.0])
X = random.normal(random.PRNGKey(0), (N,)) * 2.0


def gmm(x):
    mu = pc.sample("mu", dist.Normal(jnp.zeros(K), jnp.ones(K)).to_event(1))
    with pc.plate("data", x.shape[0]):
        z = pc.sample("z", dist.Categorical(probs=WEIGHTS),
                      infer={"enumerate": "parallel"})
        pc.sample("obs", dist.Normal(mu[z], 1.0), obs=x)


def gmm_brute(mus, x):
    prior = dist.Normal(jnp.zeros(K), jnp.ones(K)).log_prob(mus).sum()
    mix = jax.nn.logsumexp(
        jnp.log(WEIGHTS)[None, :]
        + dist.Normal(mus[None, :], 1.0).log_prob(x[:, None]), axis=-1)
    return prior + mix.sum()


# ---------------------------------------------------------------------------
# parallel enumeration: log_density == brute-force mixture density
# ---------------------------------------------------------------------------


def test_gmm_enum_log_density_matches_brute_force():
    lp, tr = log_density(gmm, (X,), {}, {"mu": MUS})
    assert abs(float(lp) - float(gmm_brute(MUS, X))) <= 1e-5
    # the trace records the enumerated site with its allocated dim
    assert tr["z"]["infer"]["_enumerate_dim"] == -2
    assert tr["z"]["infer"]["_enum_total"] == K
    assert tr["z"]["value"].shape == (K, 1)


def test_config_enumerate_marks_unmarked_models():
    def plain(x):
        mu = pc.sample("mu",
                       dist.Normal(jnp.zeros(K), jnp.ones(K)).to_event(1))
        with pc.plate("data", x.shape[0]):
            z = pc.sample("z", dist.Categorical(probs=WEIGHTS))
            pc.sample("obs", dist.Normal(mu[z], 1.0), obs=x)

    lp, _ = log_density(config_enumerate(plain), (X,), {}, {"mu": MUS})
    assert abs(float(lp) - float(gmm_brute(MUS, X))) <= 1e-5


def test_global_discrete_outside_plate():
    """Enum variable outside a plate it influences: plate dims must be
    summed per factor *before* the logsumexp contraction."""
    def model(x):
        mu = pc.sample("mu",
                       dist.Normal(jnp.zeros(K), jnp.ones(K)).to_event(1))
        z = pc.sample("z", dist.Categorical(probs=WEIGHTS),
                      infer={"enumerate": "parallel"})
        with pc.plate("data", x.shape[0]):
            pc.sample("obs", dist.Normal(mu[z], 1.0), obs=x)

    lp, _ = log_density(model, (X,), {}, {"mu": MUS})
    expected = (
        dist.Normal(jnp.zeros(K), jnp.ones(K)).log_prob(MUS).sum()
        + jax.nn.logsumexp(
            jnp.log(WEIGHTS)
            + dist.Normal(MUS[None, :], 1.0).log_prob(X[:, None]).sum(0)))
    assert abs(float(lp) - float(expected)) <= 1e-5


def test_chained_discrete_latents():
    """Two coupled enumerated sites (z2's distribution indexed by z1)."""
    T12 = jnp.array([[0.8, 0.2], [0.3, 0.7]])
    mus = jnp.array([-1.0, 1.5])

    def model(x):
        pc.sample("mu", dist.Normal(jnp.zeros(2), jnp.ones(2)).to_event(1))
        z1 = pc.sample("z1", dist.Bernoulli(probs=0.4),
                       infer={"enumerate": "parallel"})
        z2 = pc.sample("z2", dist.Categorical(probs=T12[z1]),
                       infer={"enumerate": "parallel"})
        with pc.plate("data", x.shape[0]):
            pc.sample("obs", dist.Normal(mus[z2], 1.0), obs=x)

    lp, _ = log_density(model, (X,), {}, {"mu": jnp.zeros(2)})
    acc = -np.inf
    for z1, z2 in itertools.product(range(2), range(2)):
        acc = np.logaddexp(
            acc,
            float(dist.Bernoulli(probs=0.4).log_prob(z1))
            + float(jnp.log(T12[z1, z2]))
            + float(dist.Normal(mus[z2], 1.0).log_prob(X).sum()))
    prior = float(dist.Normal(jnp.zeros(2),
                              jnp.ones(2)).log_prob(jnp.zeros(2)).sum())
    assert abs(float(lp) - (prior + acc)) <= 1e-5


def test_discrete_uniform_enumerates():
    def model():
        pc.sample("loc", dist.Normal(0.0, 1.0))
        z = pc.sample("z", dist.DiscreteUniform(1, 3),
                      infer={"enumerate": "parallel"})
        pc.sample("obs", dist.Normal(z.astype(jnp.float32), 1.0), obs=2.0)

    lp, _ = log_density(model, (), {}, {"loc": jnp.array(0.1)})
    expected = (
        float(dist.Normal(0.0, 1.0).log_prob(0.1))
        + jax.nn.logsumexp(jnp.array([
            -jnp.log(3.0) + dist.Normal(float(v), 1.0).log_prob(2.0)
            for v in (1, 2, 3)])))
    assert abs(float(lp) - float(expected)) <= 1e-5


def test_unmarked_model_takes_plain_path():
    """No enumeration marks -> single-pass accumulation, latent discrete
    sites drawn by seed exactly as before."""
    def model():
        z = pc.sample("z", dist.Bernoulli(probs=0.3))
        pc.sample("obs", dist.Normal(z.astype(jnp.float32), 1.0), obs=0.5)

    lp, tr = log_density(seed(model, random.PRNGKey(0)), (), {}, {})
    assert "_enumerate_dim" not in tr["z"]["infer"]
    assert jnp.ndim(tr["z"]["value"]) == 0


# ---------------------------------------------------------------------------
# markov: chain elimination
# ---------------------------------------------------------------------------

KH, V, T = 3, 5, 6
THETA = dist.Dirichlet(jnp.full((KH, KH), 2.0)).sample(
    rng_key=random.PRNGKey(1))
PHI = dist.Dirichlet(jnp.full((KH, V), 1.0)).sample(rng_key=random.PRNGKey(2))
W = random.randint(random.PRNGKey(3), (T,), 0, V)


def hmm(w, k=KH, v=V):
    th = pc.sample("theta",
                   dist.Dirichlet(jnp.full((k, k), 2.0)).to_event(1))
    ph = pc.sample("phi", dist.Dirichlet(jnp.full((k, v), 1.0)).to_event(1))

    def step(z_prev, w_t):
        z = pc.sample("z", dist.Categorical(probs=th[z_prev]))
        pc.sample("w", dist.Categorical(probs=ph[z]), obs=w_t)
        return z

    return markov(step, 0, w, name="chain")


def _hmm_prior(theta, phi, k=KH, v=V):
    return float(
        dist.Dirichlet(jnp.full((k, k), 2.0)).to_event(1).log_prob(theta)
        + dist.Dirichlet(jnp.full((k, v), 1.0)).to_event(1).log_prob(phi))


def test_markov_matches_brute_force_paths():
    lp, tr = log_density(hmm, (W,), {}, {"theta": THETA, "phi": PHI})
    acc = -np.inf
    for path in itertools.product(range(KH), repeat=T):
        l, zp = 0.0, 0
        for t in range(T):
            l += (np.log(float(THETA[zp, path[t]]))
                  + np.log(float(PHI[path[t], int(W[t])])))
            zp = path[t]
        acc = np.logaddexp(acc, l)
    assert abs(float(lp) - (_hmm_prior(THETA, PHI) + acc)) <= 1e-5
    assert "chain_marginal" in tr


def test_markov_matches_forward_algorithm():
    lp, _ = log_density(hmm, (W,), {}, {"theta": THETA, "phi": PHI})
    la = jnp.log(THETA[0]) + jnp.log(PHI[:, W[0]])
    for t in range(1, T):
        la = (jax.nn.logsumexp(la[:, None] + jnp.log(THETA), axis=0)
              + jnp.log(PHI[:, W[t]]))
    expected = _hmm_prior(THETA, PHI) + float(jax.nn.logsumexp(la))
    assert abs(float(lp) - expected) <= 1e-5


def test_markov_grad_flows():
    g = jax.grad(lambda th: log_density(
        hmm, (W,), {}, {"theta": th, "phi": PHI})[0])(THETA)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_markov_simulation_path_scoped_sites():
    with trace() as tr:
        states = seed(hmm, random.PRNGKey(5))(W)
    assert states.shape == (T,)
    assert "chain/0/z" in tr and f"chain/{T - 1}/w" in tr


def test_markov_cost_is_T_K2_not_K_pow_T():
    """Compile/size checks: the eliminated density is a lax.scan — its jaxpr
    does not grow with T, and a (T, K) far beyond any K^T budget evaluates
    fast."""
    def lp_fn(T_, k):
        w = random.randint(random.PRNGKey(6), (T_,), 0, V)
        th = dist.Dirichlet(jnp.full((k, k), 2.0)).sample(
            rng_key=random.PRNGKey(7))
        ph = dist.Dirichlet(jnp.full((k, V), 1.0)).sample(
            rng_key=random.PRNGKey(8))
        return jax.make_jaxpr(
            lambda t, p: log_density(lambda ww: hmm(ww, k=k), (w,), {},
                                     {"theta": t, "phi": p})[0])(th, ph)

    short, long_ = lp_fn(20, 4), lp_fn(200, 4)
    assert len(long_.eqns) == len(short.eqns)  # scan: size independent of T

    # K = 25, T = 120: 25^120 paths is unthinkable; elimination is instant
    k, t_len = 25, 120
    w = random.randint(random.PRNGKey(9), (t_len,), 0, V)
    th = dist.Dirichlet(jnp.full((k, k), 2.0)).sample(
        rng_key=random.PRNGKey(10))
    ph = dist.Dirichlet(jnp.full((k, V), 1.0)).sample(
        rng_key=random.PRNGKey(11))
    f = jax.jit(lambda t, p: log_density(
        lambda ww: hmm(ww, k=k), (w,), {}, {"theta": t, "phi": p})[0])
    assert bool(jnp.isfinite(f(th, ph)))  # compile + run
    t0 = time.time()
    jax.block_until_ready(f(th, ph))
    assert time.time() - t0 < 1.0  # warm eval: device-time only


def test_markov_timing_scales_polynomially_in_K():
    """Warm per-eval time for K=24 must be nowhere near (24/4)^... of K=4 —
    a very loose bound that still rules out exponential K^T behavior."""
    def warm_eval_time(k):
        w = random.randint(random.PRNGKey(12), (60,), 0, V)
        th = dist.Dirichlet(jnp.full((k, k), 2.0)).sample(
            rng_key=random.PRNGKey(13))
        ph = dist.Dirichlet(jnp.full((k, V), 1.0)).sample(
            rng_key=random.PRNGKey(14))
        f = jax.jit(lambda t, p: log_density(
            lambda ww: hmm(ww, k=k), (w,), {}, {"theta": t, "phi": p})[0])
        jax.block_until_ready(f(th, ph))
        t0 = time.time()
        for _ in range(3):
            jax.block_until_ready(f(th, ph))
        return (time.time() - t0) / 3

    slow, fast = warm_eval_time(24), warm_eval_time(4)
    # O(T K^2) predicts 36x; exponential K^T would be astronomically larger
    assert slow < max(fast, 1e-4) * 2000


def test_markov_guards():
    def step(z_prev, w_t):
        z = pc.sample("z", dist.Categorical(probs=THETA[z_prev]))
        pc.sample("w", dist.Categorical(probs=PHI[z]), obs=w_t)
        return z

    def in_plate(w):
        # the state inside a plate opened within the transition: RPL014
        def plated_step(z_prev, w_t):
            with pc.plate("batch", 2):
                return step(z_prev, w_t)
        markov(plated_step, 0, w)

    with pytest.raises(NotImplementedError, match="plate"):
        log_density(config_enumerate(in_plate), (W,), {}, {})

    def cont_inside(w):
        def bad_step(z_prev, w_t):
            loc = pc.sample("loc", dist.Normal(0.0, 1.0))
            z = pc.sample("z", dist.Categorical(probs=THETA[z_prev]))
            pc.sample("w", dist.Normal(loc + z, 1.0), obs=w_t.astype(float))
            return z
        markov(bad_step, 0, w)

    with pytest.raises(RuntimeError, match="markov transition"):
        log_density(cont_inside, (W,), {}, {})

    def no_state(w):
        def empty_step(z_prev, w_t):
            pc.sample("w", dist.Categorical(probs=PHI[z_prev]), obs=w_t)
            return z_prev
        markov(empty_step, 0, w)

    with pytest.raises(ValueError, match="exactly one"):
        log_density(no_state, (W,), {}, {})


# ---------------------------------------------------------------------------
# infer_discrete: posterior of the marginalized sites
# ---------------------------------------------------------------------------


def test_infer_discrete_gmm_matches_exact_posterior():
    pinned = substitute(gmm, data={"mu": MUS})
    logits = (jnp.log(WEIGHTS)[None, :]
              + dist.Normal(MUS[None, :], 1.0).log_prob(X[:, None]))
    exact = jax.nn.softmax(logits, axis=-1)
    M = 3000
    zs = jax.vmap(lambda k: infer_discrete(pinned, k)(X)["z"])(
        random.split(random.PRNGKey(42), M))
    assert zs.shape == (M, N) and jnp.issubdtype(zs.dtype, jnp.integer)
    emp = jnp.stack([(zs == k).mean(0) for k in range(K)], -1)
    assert float(jnp.max(jnp.abs(emp - exact))) < 0.06


def test_infer_discrete_hmm_matches_forward_backward():
    pinned = substitute(hmm, data={"theta": THETA, "phi": PHI})
    # exact smoothing marginals by forward-backward (init state = 0)
    la = jnp.log(THETA[0]) + jnp.log(PHI[:, W[0]])
    alphas = [la]
    for t in range(1, T):
        la = (jax.nn.logsumexp(la[:, None] + jnp.log(THETA), axis=0)
              + jnp.log(PHI[:, W[t]]))
        alphas.append(la)
    lb = jnp.zeros(KH)
    betas = [lb]
    for t in range(T - 1, 0, -1):
        lb = jax.nn.logsumexp(
            jnp.log(THETA) + jnp.log(PHI[:, W[t]])[None, :] + lb[None, :],
            axis=1)
        betas.append(lb)
    exact = jnp.stack([jax.nn.softmax(a + b)
                       for a, b in zip(alphas, betas[::-1])])
    M = 3000
    zs = jax.vmap(lambda k: infer_discrete(pinned, k)(W)["chain"])(
        random.split(random.PRNGKey(7), M))
    assert zs.shape == (M, T) and jnp.issubdtype(zs.dtype, jnp.integer)
    emp = jnp.stack([(zs == k).mean(0) for k in range(KH)], -1)
    assert float(jnp.max(jnp.abs(emp - exact))) < 0.06


def test_infer_discrete_warns_without_enum_sites():
    def model():
        pc.sample("x", dist.Normal(0.0, 1.0))

    with pytest.warns(UserWarning, match="no enumerated sites"):
        out = infer_discrete(model, random.PRNGKey(0))()
    assert out == {}


def test_infer_discrete_summary_handles_integer_sites():
    pinned = substitute(gmm, data={"mu": MUS})
    zs = jax.vmap(lambda k: infer_discrete(pinned, k)(X)["z"])(
        random.split(random.PRNGKey(3), 40))
    stats = print_summary({"z": np.asarray(zs)[None],
                           "mu": np.random.default_rng(0).normal(
                               size=(1, 40))})
    assert set(stats["z"]) >= {"mode", "mode_freq", "n_unique"}
    assert "r_hat" in stats["mu"]


# ---------------------------------------------------------------------------
# end-to-end: untouched models through the jit'd NUTS executor
# ---------------------------------------------------------------------------


def test_nuts_gmm_recovers_component_means():
    n, k = 60, 2
    comp = random.bernoulli(random.PRNGKey(1), 0.4, (n,))
    x = jnp.where(comp, 3.0, -3.0) \
        + 0.5 * random.normal(random.PRNGKey(2), (n,))

    def model(x):
        mu = pc.sample(
            "mu", dist.Normal(jnp.zeros(k), 5.0 * jnp.ones(k)).to_event(1))
        with pc.plate("data", x.shape[0]):
            z = pc.sample("z", dist.Categorical(probs=jnp.ones(k) / k))
            pc.sample("obs", dist.Normal(mu[z], 0.5), obs=x)

    mcmc = MCMC(NUTS(model), num_warmup=150, num_samples=150)
    mcmc.run(random.PRNGKey(3), x)
    samples = mcmc.get_samples()
    assert set(samples) == {"mu"}  # the discrete site is marginalized
    mu = np.sort(np.asarray(samples["mu"].mean(0)))
    assert abs(mu[0] + 3.0) < 0.5 and abs(mu[1] - 3.0) < 0.5

    # posterior assignments from the NUTS draws
    pinned = substitute(model, data={"mu": samples["mu"][-1]})
    z = infer_discrete(pinned, random.PRNGKey(4))(x)["z"]
    acc = np.mean(np.asarray(z) == np.asarray(comp.astype(jnp.int32)))
    assert acc > 0.95 or acc < 0.05  # up to label switching


def test_nuts_unsupervised_hmm_runs_jitted():
    k, v, t_len = 3, 8, 30
    theta_true = dist.Dirichlet(jnp.full((k, k), 0.5)).sample(
        rng_key=random.PRNGKey(4))
    phi_true = dist.Dirichlet(jnp.full((k, v), 0.3)).sample(
        rng_key=random.PRNGKey(5))
    z, ws = 0, []
    kk = random.split(random.PRNGKey(6), 2 * t_len)
    for i in range(t_len):
        z = int(dist.Categorical(probs=theta_true[z]).sample(
            rng_key=kk[2 * i]))
        ws.append(int(dist.Categorical(probs=phi_true[z]).sample(
            rng_key=kk[2 * i + 1])))
    w = jnp.array(ws)

    def model(w):
        th = pc.sample("theta",
                       dist.Dirichlet(jnp.full((k, k), 1.0)).to_event(1))
        ph = pc.sample("phi",
                       dist.Dirichlet(jnp.full((k, v), 1.0)).to_event(1))

        def step(z_prev, w_t):
            zt = pc.sample("z", dist.Categorical(probs=th[z_prev]))
            pc.sample("w", dist.Categorical(probs=ph[zt]), obs=w_t)
            return zt

        markov(step, 0, w)

    mcmc = MCMC(NUTS(model), num_warmup=100, num_samples=100)
    mcmc.run(random.PRNGKey(7), w)
    samples = mcmc.get_samples()
    assert set(samples) == {"phi", "theta"}
    assert samples["theta"].shape == (100, k, k)
    extras = mcmc.get_extra_fields()
    assert bool(np.all(np.isfinite(np.asarray(extras["accept_prob"]))))
