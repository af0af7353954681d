"""``markov`` under an enclosing plate: a batch of sequences, each with a
state of its own, eliminated through one contraction per step.

The model is NumPyro's ``hmm_enum.py`` ``model_1``: the ``sequences``
plate around the chain, the ``tones`` plate inside the step, each padded
step masked out by ``t < length``.  The batched marginal and its gradient
are checked against a plain forward algorithm that walks one sequence at
a time over its own length, and against a brute-force sum over paths.
"""
import functools
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

import repro.core as pc
from repro.core import dist
from repro.core.errors import ReproNotImplementedError
from repro.core.handlers import mask, scale
from repro.core.infer import MCMC, NUTS, infer_discrete, log_density, markov
from repro.core.lint import check_time_independence
from repro.kernels import ops

pytestmark = pytest.mark.enum

HIGHEST = jax.lax.Precision.HIGHEST


def hmm(seqs, lengths, probs_x, probs_y):
    """The likelihood of model_1 at given parameters."""
    num_sequences, max_length, data_dim = seqs.shape

    def step(x_prev, inp):
        y_t, t = inp
        with mask(mask=(t < lengths)[:, None]):
            x = pc.sample("x", dist.Categorical(probs_x[x_prev]))
            with pc.plate("tones", data_dim, dim=-1):
                pc.sample("y", dist.Bernoulli(probs_y[x.squeeze(-1)]),
                          obs=y_t)
        return x

    with pc.plate("sequences", num_sequences, dim=-2):
        return markov(step, 0, (jnp.swapaxes(seqs, 0, 1),
                                jnp.arange(max_length)))


def hmm_one(y, probs_x, probs_y):
    """One sequence, no plate around the chain and no mask."""
    def step(x_prev, y_t):
        x = pc.sample("x", dist.Categorical(probs_x[x_prev]))
        pc.sample("y", dist.Bernoulli(probs_y[x]).to_event(1), obs=y_t)
        return x

    markov(step, 0, y)


def problem(S, T, K, D, lengths, seed=0):
    ks = random.split(random.PRNGKey(seed), 3)
    # parameters away from the clip of Bernoulli/Categorical (eps)
    px = random.dirichlet(ks[0], jnp.full((K, K), 2.0))
    py = random.uniform(ks[1], (K, D), minval=0.05, maxval=0.95)
    seqs = (random.uniform(ks[2], (S, T, D)) < 0.4).astype(jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    live = jnp.arange(T) < lengths[:, None]
    return seqs * live[..., None], lengths, px, py


def marginal(seqs, lengths, px, py):
    return log_density(hmm, (seqs, lengths, px, py), {}, {})[0]


def reference(seqs, lengths, px, py):
    """Plain forward algorithm, one sequence at a time over its own
    length: no handlers, no kernels, f32 products at HIGHEST."""
    total = 0.0
    log_px = jnp.log(px)
    for s in range(seqs.shape[0]):
        y = seqs[s, :int(lengths[s])]
        emit = (jnp.matmul(y, jnp.log(py).T, precision=HIGHEST)
                + jnp.matmul(1 - y, jnp.log1p(-py).T, precision=HIGHEST))
        alpha = log_px[0] + emit[0]
        for t in range(1, y.shape[0]):
            alpha = jax.nn.logsumexp(alpha[:, None] + log_px, axis=0) \
                + emit[t]
        total = total + jax.nn.logsumexp(alpha)
    return total


def rel(a, b):
    a, b = np.ravel(np.asarray(a, np.float64)), np.ravel(np.asarray(b))
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# f32 with a few hundred logsumexp steps of O(100)-nat messages: rounding
# stays near 1e-6 relative; a step mishandled (a masked step's zeroed
# factor adds log K = 1.39 nats) is 1e-2 relative here
RTOL = 1e-5


@pytest.mark.parametrize("wrt", ["value", "grad"])
def test_batched_marginal_matches_forward_reference(wrt):
    seqs, lengths, px, py = problem(7, 9, 4, 6, [9, 3, 5, 1, 9, 7, 2])
    if wrt == "value":
        got = marginal(seqs, lengths, px, py)
        want = reference(seqs, lengths, px, py)
    else:
        # through softmax logits: the gradient along the simplex (a
        # Categorical renormalises its row, which moves the gradient off
        # it by a constant per row)
        def on_simplex(f):
            return jax.grad(lambda lx, p: f(seqs, lengths,
                                            jax.nn.softmax(lx), p),
                            argnums=(0, 1))
        got = on_simplex(marginal)(jnp.log(px), py)
        want = on_simplex(reference)(jnp.log(px), py)
        got, want = jnp.concatenate([g.ravel() for g in got]), \
            jnp.concatenate([w.ravel() for w in want])
    assert rel(got, want) <= RTOL


def test_batched_marginal_matches_brute_force_paths():
    S, T, K, D = 3, 5, 3, 2
    seqs, lengths, px, py = problem(S, T, K, D, [5, 3, 1], seed=1)
    p_x, p_y = np.asarray(px, np.float64), np.asarray(py, np.float64)
    y = np.asarray(seqs, np.float64)
    total = 0.0
    for s in range(S):
        L = int(lengths[s])
        acc = -np.inf
        for path in itertools.product(range(K), repeat=L):
            lp, prev = 0.0, 0
            for t, k in enumerate(path):
                lp += np.log(p_x[prev, k]) + np.sum(
                    y[s, t] * np.log(p_y[k]) + (1 - y[s, t])
                    * np.log1p(-p_y[k]))
                prev = k
            acc = np.logaddexp(acc, lp)
        total += acc
    assert rel(marginal(seqs, lengths, px, py), total) <= RTOL


def test_each_row_is_its_sequence_truncated_to_its_length():
    """A padded sequence of length L scores exactly what the unbatched
    chain scores on its first L steps: a masked step leaves the row's
    forward message unchanged."""
    seqs, lengths, px, py = problem(5, 8, 3, 4, [8, 2, 5, 1, 6], seed=2)
    _, tr = log_density(hmm, (seqs, lengths, px, py), {}, {})
    site = tr["markov_marginal"]
    per_row = np.asarray(site["fn"].log_density)
    assert per_row.shape == (5, 1)
    for s in range(5):
        L = int(lengths[s])
        one = log_density(hmm_one, (seqs[s, :L], px, py), {}, {})[0]
        np.testing.assert_allclose(per_row[s, 0], float(one), rtol=RTOL)


def test_outer_scale_weighs_each_row_once():
    seqs, lengths, px, py = problem(4, 6, 3, 3, [6, 4, 2, 5], seed=3)
    half = log_density(scale(hmm, scale=0.5), (seqs, lengths, px, py),
                       {}, {})[0]
    np.testing.assert_allclose(float(half),
                               0.5 * float(marginal(seqs, lengths, px, py)),
                               rtol=RTOL)


def _kernel_calls(jaxpr, out=None):
    """Names of the ``pallas_call``s in ``jaxpr`` and below, in order."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn.params["name"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, out)
    return out


@pytest.mark.parametrize("T", [4, 8, 16])
def test_gradient_is_one_chain_call_each_way_for_all_rows(T):
    """At every T the value and gradient of the batched marginal hold one
    forward call over every step of every row and one backward call: no
    per-step or per-sequence kernel."""
    seqs, lengths, px, py = problem(6, T, 3, 2, [T, 2, 3, T, 1, 2])
    with ops.use_pallas(True, interpret=True):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(marginal, argnums=(2, 3)))(
            seqs, lengths, px, py)
    assert _kernel_calls(jaxpr.jaxpr) == ["enum_contract_chain",
                                          "enum_contract_chain_vjp"]


def test_batched_program_is_time_independent():
    def make(T):
        seqs, lengths, px, py = problem(6, T, 3, 2, [T, 2, 3, T, 1, 2])
        return (lambda a, b: marginal(seqs, lengths, a, b)), (px, py)

    result = check_time_independence(make, sizes=(4, 8))
    assert result.ok and not result.findings


@pytest.mark.parametrize("chains", [1, 3])
def test_kernel_in_interpret_mode_matches_the_reference_path(chains):
    """13 sequences (39 rows under 3 chains): not a multiple of the
    kernel's 8-row tile, so its padding rows are exercised."""
    seqs, lengths, px, py = problem(13, 7, 4, 3,
                                    [7, 1, 2, 3, 4, 5, 6, 7, 6, 5, 4, 3, 2],
                                    seed=4)
    pxs = jnp.stack([px ** (1 + 0.1 * c) / jnp.sum(px ** (1 + 0.1 * c), -1,
                                                   keepdims=True)
                     for c in range(chains)])

    def f(p):
        return jax.vmap(jax.value_and_grad(
            lambda a: marginal(seqs, lengths, a, py)))(p)

    want_v, want_g = f(pxs)
    with ops.use_pallas(True, interpret=True):
        got_v, got_g = f(pxs)
    # the kernel's forward pass computes the reference's formula in the
    # same order; its backward pass is the forward-backward posterior, the
    # reference's is autodiff through the scan: equal to f32 rounding
    np.testing.assert_allclose(got_v, want_v, rtol=1e-6)
    assert rel(got_g, want_g) <= 1e-6


def jsb_chain(rows, seed=0):
    """One chain's factors at JSB's magnitudes: 129 steps, 16 states, each
    step's emission a sum over 51 tones, so ``log Z`` is thousands of
    nats a row; ragged lengths."""
    T, K, D = 129, 16, 51
    ks = random.split(random.PRNGKey(seed), 4)
    y = (random.uniform(ks[0], (rows, T, D)) < 0.3).astype(jnp.float32)
    py = random.uniform(ks[1], (K, D), minval=0.05, maxval=0.95)
    emit = (jnp.einsum("btd,kd->btk", y, jnp.log(py), precision=HIGHEST)
            + jnp.einsum("btd,kd->btk", 1 - y, jnp.log1p(-py),
                         precision=HIGHEST))
    log_px = jnp.log(random.dirichlet(ks[2], jnp.ones((K, K))))
    lengths = random.randint(ks[3], (rows,), 32, T + 1)
    keep = jnp.arange(1, T)[:, None] < lengths[None, :]
    return (log_px[0] + emit[:, 0], jnp.swapaxes(emit, 0, 1)[1:],
            jnp.broadcast_to(log_px, (T - 1, K, K)), keep)


@pytest.mark.parametrize("rows", [5, 300])
def test_chain_kernel_gradient_at_jsb_magnitudes(rows):
    """Value and gradient of the whole-chain kernels (interpret mode) and of
    the reference path against the reference in float64, at the sizes of
    the JSB cell.  300 rows take two grid steps of 256."""
    from repro.kernels import ref
    from repro.kernels.enum_contract import enum_contract_chain
    alpha0, unary, pair, keep = jsb_chain(rows)
    w = jnp.linspace(0.5, 1.5, rows)

    def value_and_grad(chain, *args):
        return jax.value_and_grad(
            lambda a, u, p: jnp.sum(chain(a, u, p, keep) * w.astype(a.dtype)),
            argnums=(0, 1, 2))(*args)

    with jax.enable_x64(True):
        v64, g64 = value_and_grad(ref.enum_contract_chain, *(
            jnp.asarray(np.asarray(x, np.float64))
            for x in (alpha0, unary, pair)))
        v64, g64 = float(v64), [np.asarray(g) for g in g64]
    kernel = value_and_grad(functools.partial(enum_contract_chain,
                                              interpret=True),
                            alpha0, unary, pair)
    plain = value_and_grad(ref.enum_contract_chain, alpha0, unary, pair)
    for v, g in (kernel, plain):
        # f32 keeps about 1e-7 of log Z (tens of thousands of nats here)
        assert abs(float(v) - v64) <= 1e-6 * abs(v64)
        # each posterior is formed from normalised messages, a few
        # emissions from 0, so it keeps f32's digits (about 1e-6 here);
        # from unnormalised ones it lost them (8e-4)
        for got, want in zip(g, g64):
            assert rel(got, want) <= 1e-5


def test_chain_route_depends_on_steps_not_rows():
    from repro.kernels.enum_contract import _row_block, chain_route
    # a grid step takes every row while they fit VMEM, else blocks of them
    assert _row_block(128, 16, 229) == 256
    assert _row_block(128, 16, 300) == _row_block(128, 16, 5000) == 256
    assert chain_route(128, 16, per_row_pair=False) == "whole_chain"
    assert chain_route(128, 16, per_row_pair=True) == "per_step"
    assert chain_route(1999, 32, per_row_pair=False) == "per_step"


def jsb_like(seqs, lengths):
    """model_1 with its priors, at a tiny size."""
    _, _, data_dim = seqs.shape
    K = 2
    probs_x = pc.sample("probs_x", dist.Dirichlet(0.9 * jnp.eye(K) + 0.1)
                        .to_event(1))
    probs_y = pc.sample("probs_y", dist.Beta(0.1, 0.9)
                        .expand([K, data_dim]).to_event(2))
    hmm(seqs, lengths, probs_x, probs_y)


def test_vectorized_nuts_runs_and_reports_its_route(tmp_path):
    import re

    from repro import obs
    seqs, lengths, _, _ = problem(4, 6, 2, 3, [6, 3, 5, 2], seed=5)
    tele = obs.Telemetry(dir=str(tmp_path))
    mcmc = MCMC(NUTS(jsb_like), num_warmup=30, num_samples=30, num_chains=2,
                chain_method="vectorized", progress=False, telemetry=tele)
    mcmc.run(random.PRNGKey(0), seqs, lengths)
    samples = mcmc.get_samples(group_by_chain=True)
    assert samples["probs_x"].shape == (2, 30, 2, 2)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in samples.values())
    with open(tmp_path / "run_manifest.json") as f:
        final = json.load(f)["sessions"][-1]["final"]
    # every chain's sequences go through each contraction together
    assert final["markov_route"] == {"route": "plate_batched",
                                     "path": "reference", "rows": 8,
                                     "steps": 5, "states": 2}
    (prog,) = [fn for key, fn in mcmc._exec_cache.items()
               if key[0] == "sample"]
    text = prog.lower(mcmc.last_state).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    # the elimination runs inside the gradient (its ops read
    # ".../repro.potential/jvp(repro.markov)/...")
    nested = f"{obs.SCOPES.potential}/.*{obs.SCOPES.markov}"
    assert any(re.search(nested, p) for p in paths)


def _posterior_marginals(y, L, px, py):
    """p(x_t = k | y_{:L}) of one sequence, by summing over every path."""
    K = px.shape[0]
    probs = np.zeros((L, K))
    total = 0.0
    for path in itertools.product(range(K), repeat=L):
        p, prev = 1.0, 0
        for t, k in enumerate(path):
            p *= px[prev, k] * np.prod(np.where(y[t] > 0, py[k], 1 - py[k]))
            prev = k
        total += p
        for t, k in enumerate(path):
            probs[t, k] += p
    return probs / total


def test_infer_discrete_samples_each_sequence_from_its_posterior():
    S, T, K, D = 3, 4, 2, 2
    seqs, lengths, px, py = problem(S, T, K, D, [4, 2, 3], seed=6)
    n = 4000
    keys = random.split(random.PRNGKey(7), n)
    draws = jax.vmap(lambda k: infer_discrete(hmm, k)(
        seqs, lengths, px, py)["markov"])(keys)
    assert draws.shape == (n, S, T)
    p_x, p_y = np.asarray(px, np.float64), np.asarray(py, np.float64)
    for s in range(S):
        L = int(lengths[s])
        # padded steps are marked, not sampled
        assert bool(jnp.all(draws[:, s, L:] == -1))
        exact = _posterior_marginals(np.asarray(seqs[s]), L, p_x, p_y)
        freq = np.asarray(jnp.mean(draws[:, s, :L] == 1, axis=0))
        se = np.sqrt(exact[:, 1] * (1 - exact[:, 1]) / n)
        # within 5 standard errors of the exact marginal (Monte Carlo)
        assert np.all(np.abs(freq - exact[:, 1]) <= 5 * se + 1e-3), \
            (s, freq, exact[:, 1])


def test_state_in_a_plate_opened_within_the_step_is_refused():
    """NumPyro's spelling opens the sequences plate inside the step, around
    the state: every sequence would share one state.  It raises RPL014,
    naming the plate, with the fix."""
    seqs, lengths, px, py = problem(3, 4, 2, 2, [4, 2, 3])

    def numpyro_spelling(seqs):
        def step(x_prev, y_t):
            with pc.plate("sequences", seqs.shape[0], dim=-2):
                x = pc.sample("x", dist.Categorical(px[x_prev]))
                with pc.plate("tones", seqs.shape[-1], dim=-1):
                    pc.sample("y", dist.Bernoulli(py[x.squeeze(-1)]),
                              obs=y_t)
            return x

        markov(step, 0, jnp.swapaxes(seqs, 0, 1))

    with pytest.raises(ReproNotImplementedError,
                       match="around the markov call") as e:
        log_density(numpyro_spelling, (seqs,), {}, {})
    assert e.value.code == "RPL014" and e.value.site == "sequences"


def test_nested_markov_is_refused():
    px = jnp.full((2, 2), 0.5)

    def outer(xs):
        def step(x_prev, x_t):
            x = pc.sample("x", dist.Categorical(px[x_prev]))
            markov(lambda c, v: pc.sample("z", dist.Categorical(px[c])),
                   0, jnp.zeros(2), name="inner")
            return x

        markov(step, 0, xs)

    with pytest.raises(ReproNotImplementedError, match="nested") as e:
        log_density(outer, (jnp.zeros(3),), {}, {})
    assert e.value.code == "RPL014"


def test_transition_per_row_matches_the_reference():
    """Each sequence with a transition of its own: the factor that reads the
    previous state varies by row, so the chain runs one contraction per
    step with a matrix per row, on the reference path and the kernel's."""
    S, T, K, D = 5, 7, 3, 4
    seqs, lengths, _, py = problem(S, T, K, D, [7, 2, 5, 1, 6], seed=8)
    pxs = random.dirichlet(random.PRNGKey(9), jnp.full((S, K, K), 2.0))

    def per_row(seqs, lengths, pxs, py):
        def step(x_prev, inp):
            y_t, t = inp
            rows = jnp.arange(seqs.shape[0])[:, None]
            with mask(mask=(t < lengths)[:, None]):
                x = pc.sample("x", dist.Categorical(pxs[rows, x_prev]))
                with pc.plate("tones", D, dim=-1):
                    pc.sample("y", dist.Bernoulli(py[x.squeeze(-1)]),
                              obs=y_t)
            return x

        with pc.plate("sequences", S, dim=-2):
            markov(step, jnp.zeros((S, 1), jnp.int32),
                   (jnp.swapaxes(seqs, 0, 1), jnp.arange(T)))

    want = sum(reference(seqs[s:s + 1], lengths[s:s + 1], pxs[s], py)
               for s in range(S))
    got = log_density(per_row, (seqs, lengths, pxs, py), {}, {})[0]
    assert rel(got, want) <= RTOL
    with ops.use_pallas(True, interpret=True):
        kernel = log_density(per_row, (seqs, lengths, pxs, py), {}, {})[0]
    assert rel(kernel, want) <= RTOL
