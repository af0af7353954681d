"""Static analyzer (ISSUE 6): the defective-model corpus, lint/runtime
error parity, jaxpr hazard rules, op-registry + KernelSetup invariants, the
distribution constraint audit, and the ``validate=`` inference hooks (with
the zero-warm-path-overhead guarantee)."""
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

import repro.core as pc
from repro import optim
from repro.core import dist
from repro.core.dist import constraints
from repro.core.errors import ReproError, ReproValueError, ReproWarning
from repro.core.handlers import (condition, replay, reparam, seed,
                                 substitute, trace)
from repro.core.infer import (MCMC, NUTS, SVI, Trace_ELBO, log_density,
                              markov)
from repro.core.reparam import LocScaleReparam
from repro.kernels.ops import OP_TABLE, OpSpec
from repro.lint import (RULES, analyze, check_parity,
                        check_registry_completeness, check_signatures,
                        check_time_independence, lint_model,
                        verify_kernel_setup)


# ---------------------------------------------------------------------------
# the defective-model corpus: one entry per RPL0xx rule
# ---------------------------------------------------------------------------

class Defect(NamedTuple):
    code: str
    site: Optional[str]      # expected Finding.site (None: no single site)
    expect: str              # fragment that must appear in str(finding)
    build: callable          # () -> (model, args, kwargs, lint_kwargs)


def _dup_site():
    def model():
        pc.sample("w", dist.Normal(0.0, 1.0))
        pc.sample("w", dist.Normal(0.0, 1.0))
    return model, (), {}, {}


def _plate_dim_collision():
    def model():
        with pc.plate("a", 3, dim=-1), pc.plate("b", 4, dim=-1):
            pc.sample("x", dist.Normal(0.0, 1.0))
    return model, (), {}, {}


def _enum_budget_overflow():
    def model(x):
        mu = pc.sample("mu", dist.Normal(jnp.zeros(2), 1.0).to_event(1))
        with pc.plate("data", x.shape[0]):
            z = pc.sample("z", dist.Categorical(probs=jnp.ones(2) / 2),
                          infer={"enumerate": "parallel"})
            pc.sample("obs", dist.Normal(mu[z], 1.0), obs=x)
    return model, (jnp.zeros(5),), {}, {"max_plate_nesting": 0}


def _plate_shape_mismatch():
    def model():
        with pc.plate("data", 5):
            pc.sample("obs", dist.Normal(0.0, 1.0), obs=jnp.zeros(7))
    return model, (), {}, {}


def _obs_outside_support():
    def model():
        pc.sample("x", dist.Beta(2.0, 2.0), obs=jnp.array(1.5))
    return model, (), {}, {}


def _dead_substitute_key():
    def model():
        pc.sample("mu", dist.Normal(0.0, 1.0))
    return substitute(model, data={"mu_typo": 0.3}), (), {}, {}


def _substitute_reparamed_site():
    def model():
        mu = pc.sample("mu", dist.Normal(0.0, 1.0))
        pc.sample("x", dist.Normal(mu, 2.0))
    wrapped = substitute(
        reparam(model, config={"x": LocScaleReparam(0.0)}),
        data={"x": 0.5})
    return wrapped, (), {}, {}


def _enum_model():
    def model():
        z = pc.sample("z", dist.Categorical(probs=jnp.ones(3) / 3),
                      infer={"enumerate": "parallel"})
        pc.sample("obs", dist.Normal(jnp.arange(3.0)[z], 1.0), obs=1.0)
    return model


def _param_on_enumerated_site():
    return _enum_model(), (), {}, {"params": {"z": 1}}


def _unseeded_latent():
    def model():
        pc.sample("mu", dist.Normal(0.0, 1.0))
    return model, (), {}, {"mode": "simulate"}


def _float64_observation():
    def model(y):
        mu = pc.sample("mu", dist.Normal(0.0, 1.0))
        pc.sample("obs", dist.Normal(mu, 1.0), obs=y)
    return model, (np.zeros(4, dtype=np.float64),), {}, {}


def _replay_observed_latent_mismatch():
    def model():
        pc.sample("x", dist.Normal(0.0, 1.0))
    observed_tr = trace(seed(condition(model, data={"x": 0.3}),
                             random.PRNGKey(0))).get_trace()
    return replay(model, observed_tr), (), {}, {}


def _unseeded_subsample():
    def model(x):
        with pc.plate("data", x.shape[0], subsample_size=2):
            xb = pc.subsample(x, event_dim=0)
            pc.sample("obs", dist.Normal(0.0, 1.0), obs=xb)
    return model, (jnp.zeros(6),), {}, {"mode": "simulate"}


def _enumerate_continuous_site():
    def model():
        pc.sample("x", dist.Normal(0.0, 1.0),
                  infer={"enumerate": "parallel"})
        pc.sample("obs", dist.Normal(0.0, 1.0), obs=0.5)
    return model, (), {}, {}


def _markov_state_in_step_plate():
    # the plate opened inside the transition, around the state: every
    # sequence would share one state (the plate belongs around markov)
    def step(carry, x_t):
        with pc.plate("sequences", 4):
            z = pc.sample("z", dist.Categorical(probs=jnp.ones(2) / 2),
                          infer={"enumerate": "parallel"})
            pc.sample("obs", dist.Normal(z.astype(jnp.float32), 1.0),
                      obs=x_t)
        return z

    def model(x):
        markov(step, 0, x)
    return model, (jnp.zeros(3),), {}, {}


def _seed_baked_into_model():
    def model():
        pc.sample("mu", dist.Normal(0.0, 1.0))
        pc.sample("obs", dist.Normal(0.0, 1.0), obs=0.5)
    return seed(model, random.PRNGKey(0)), (), {}, {}


DEFECTS = [
    Defect("RPL001", "w", "'w'", _dup_site),
    Defect("RPL002", "b", "'b'", _plate_dim_collision),
    Defect("RPL003", None, "max_plate_nesting", _enum_budget_overflow),
    Defect("RPL004", "obs", "'obs'", _plate_shape_mismatch),
    Defect("RPL005", "x", "'x'", _obs_outside_support),
    Defect("RPL006", "mu_typo", "'mu_typo'", _dead_substitute_key),
    Defect("RPL007", "x", "'x'", _substitute_reparamed_site),
    Defect("RPL008", "z", "'z'", _param_on_enumerated_site),
    Defect("RPL009", "mu", "'mu'", _unseeded_latent),
    Defect("RPL010", "obs", "'obs'", _float64_observation),
    Defect("RPL011", "x", "'x'", _replay_observed_latent_mismatch),
    Defect("RPL012", None, "subsample", _unseeded_subsample),
    Defect("RPL013", "x", "'x'", _enumerate_continuous_site),
    Defect("RPL014", "sequences", "'sequences'", _markov_state_in_step_plate),
    Defect("RPL015", None, "seed", _seed_baked_into_model),
]


@pytest.mark.parametrize("defect", DEFECTS, ids=[d.code for d in DEFECTS])
def test_defect_corpus_fires_with_site(defect):
    model, args, kwargs, lint_kwargs = defect.build()
    result = lint_model(model, args, kwargs, **lint_kwargs)
    assert defect.code in result.codes(), (
        f"{defect.code} did not fire; findings: {result.findings}")
    finding = next(f for f in result.findings if f.code == defect.code)
    if defect.site is not None:
        assert finding.site == defect.site
    assert defect.expect in str(finding), (
        f"finding does not name the offending site/fix: {finding}")
    assert finding.severity == RULES[defect.code].severity


def test_defect_corpus_spans_all_model_rules():
    """Every RPL0xx rule in the registry has a corpus entry proving the
    linter catches it — the >=12-defect acceptance floor, structurally."""
    covered = {d.code for d in DEFECTS}
    model_rules = {c for c in RULES if c.startswith("RPL0")}
    assert model_rules <= covered
    assert len(DEFECTS) >= 12


# ---------------------------------------------------------------------------
# clean models: no false positives on the repo's own corpus
# ---------------------------------------------------------------------------

def test_clean_model_no_findings():
    def model(x, y=None):
        w = pc.sample("w", dist.Normal(jnp.zeros(3), 1.0).to_event(1))
        with pc.plate("data", x.shape[0]):
            pc.sample("obs", dist.Bernoulli(logits=x @ w), obs=y)
    x = random.normal(random.PRNGKey(0), (20, 3))
    y = (x @ jnp.ones(3) > 0).astype(jnp.float32)
    result = lint_model(model, (x,), {"y": y})
    assert result.ok and not result.findings


def test_examples_and_benchmarks_lint_clean():
    from repro.lint.__main__ import _corpus_entries
    labels = []
    for label, model, args, kwargs in _corpus_entries():
        result = lint_model(model, args, kwargs)
        assert result.ok, f"{label} failed lint:\n{result}"
        labels.append(label)
    assert len(labels) >= 8  # every example + benchmark model was visited


def test_lint_under_eval_shape_is_abstract():
    """ShapeDtypeStruct leaves run the probe under eval_shape: structural
    rules still fire, value rules skip the (traced) data."""
    def dup(x):
        pc.sample("w", dist.Normal(0.0, 1.0))
        pc.sample("w", dist.Normal(0.0, 1.0))
        pc.sample("obs", dist.Normal(0.0, 1.0), obs=x)

    def badobs(x):
        pc.sample("x", dist.Beta(2.0, 2.0), obs=x)

    struct = jax.ShapeDtypeStruct((4,), jnp.float32)
    assert "RPL001" in lint_model(dup, (struct,)).codes()
    # the 1.5 observation is abstract here, so the value rule cannot judge it
    bad = jax.ShapeDtypeStruct((), jnp.float32)
    assert "RPL005" not in lint_model(badobs, (bad,)).codes()
    # ...but with the concrete value the same rule fires
    assert "RPL005" in lint_model(badobs, (jnp.array(1.5),)).codes()


# ---------------------------------------------------------------------------
# lint/runtime parity: same code at lint time and at runtime
# ---------------------------------------------------------------------------

def test_every_lint_only_rule_justifies_itself():
    for code, r in RULES.items():
        if r.twin is None:
            assert r.justification, f"{code} has no runtime twin and no " \
                "justification for staying silent at runtime"
        else:
            assert r.twin in ("error", "warning")


def test_runtime_twin_errors_carry_codes():
    """The runtime raises the *same* coded error the linter reports — and
    stays catchable as the plain builtin the pre-code API raised."""
    model, args, kwargs, _ = _dup_site()
    with pytest.raises(ValueError, match=r"\[RPL001\]") as ei:
        trace(seed(model, random.PRNGKey(0))).get_trace(*args, **kwargs)
    assert isinstance(ei.value, ReproError) and ei.value.code == "RPL001"

    bad_obs_model, *_ = _obs_outside_support()
    with pytest.raises(ValueError, match=r"\[RPL005\]"):
        trace(seed(bad_obs_model, random.PRNGKey(0))).get_trace()

    with pytest.raises(ValueError, match=r"\[RPL008\]"):
        log_density(_enum_model(), (), {}, {"z": 1})

    def latent():
        pc.sample("x", dist.Normal(0.0, 1.0))
    with pytest.raises(ValueError, match=r"\[RPL009\]"):
        trace(latent).get_trace()


def test_substitute_strict_is_the_rpl006_runtime_twin():
    def model():
        pc.sample("mu", dist.Normal(0.0, 1.0))
        pc.sample("obs", dist.Normal(0.0, 1.0), obs=0.5)
    # default: dead keys tolerated (ELBO passes merged param maps around)
    trace(seed(substitute(model, data={"nope": 1.0}),
               random.PRNGKey(0))).get_trace()
    with pytest.raises(ValueError, match=r"\[RPL006\].*'nope'"):
        with substitute(data={"nope": 1.0}, strict=True):
            trace(seed(model, random.PRNGKey(0))).get_trace()


def test_unseeded_subsample_warns_with_code():
    model, args, _, _ = _unseeded_subsample()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trace(model).get_trace(*args)
    assert any(isinstance(w.message, ReproWarning)
               and "[RPL012]" in str(w.message) for w in caught)


# ---------------------------------------------------------------------------
# jaxpr hazard analysis (RPL1xx) — zero-FLOP: trace only, never execute
# ---------------------------------------------------------------------------

def test_analyze_flags_large_baked_constant():
    big = jnp.zeros(400_000)  # 1.6 MB closed over, not passed in

    def fn(x):
        return (x + big).sum()

    result = analyze(fn, jnp.zeros(400_000))
    assert "RPL101" in result.codes()
    # raising the limit clears it; passing the array as an argument also does
    assert "RPL101" not in analyze(fn, jnp.zeros(400_000),
                                   const_bytes_limit=1 << 24).codes()
    assert "RPL101" not in analyze(lambda x, c: (x + c).sum(),
                                   jnp.zeros(400_000),
                                   jnp.zeros(400_000)).codes()


def test_analyze_flags_host_callback():
    def fn(x):
        y = jax.pure_callback(
            lambda v: np.sin(v), jax.ShapeDtypeStruct((4,), jnp.float32), x)
        return y.sum()

    assert "RPL102" in analyze(fn, jnp.zeros(4)).codes()
    assert "RPL102" not in analyze(lambda x: jnp.sin(x).sum(),
                                   jnp.zeros(4)).codes()


def test_analyze_flags_precision_narrowing():
    def fn(x):
        return x.astype(jnp.float16).sum()

    assert "RPL103" in analyze(fn, jnp.zeros(8, jnp.float32)).codes()
    assert "RPL103" not in analyze(lambda x: x.sum(),
                                   jnp.zeros(8, jnp.float32)).codes()


def _markov_log_density_at(T):
    xs = jnp.zeros(T)

    def step(carry, x_t):
        trans = jnp.array([[0.8, 0.2], [0.3, 0.7]])
        z = pc.sample("z", dist.Categorical(probs=trans[carry]),
                      infer={"enumerate": "parallel"})
        pc.sample("x", dist.Normal(z.astype(jnp.float32), 1.0), obs=x_t)
        return z

    def model():
        markov(step, 0, xs)

    def fn(mu0):
        return log_density(model, (), {}, {})[0] + 0.0 * mu0
    return fn, (jnp.zeros(()),)


def test_markov_program_is_time_independent():
    """The ISSUE acceptance proof: the compiled markov HMM density has the
    same jaxpr equation count at T=4 and T=8 (elimination runs inside
    lax.scan, never unrolled)."""
    result = check_time_independence(_markov_log_density_at, sizes=(4, 8))
    assert result.ok and not result.findings


def test_unrolled_chain_is_flagged_time_dependent():
    def make_fn(T):
        xs = jnp.zeros(T)

        def fn(mu):
            lp = jnp.zeros(())
            for t in range(T):  # Python loop: O(T) program size
                lp = lp + dist.Normal(mu, 1.0).log_prob(xs[t])
            return lp
        return fn, (jnp.zeros(()),)

    result = check_time_independence(make_fn, sizes=(4, 8))
    assert "RPL104" in result.codes()
    assert not result.ok


# ---------------------------------------------------------------------------
# RPL2xx: op registry + KernelSetup invariants
# ---------------------------------------------------------------------------

def test_op_registry_is_complete():
    result = check_registry_completeness()
    assert result.ok, f"registry drift:\n{result}"


@pytest.mark.parametrize("spec", OP_TABLE, ids=[s.name for s in OP_TABLE])
def test_op_signatures_match(spec):
    result = check_signatures(spec)
    assert result.ok, f"signature drift for {spec.name}:\n{result}"


@pytest.mark.parametrize("spec", OP_TABLE, ids=[s.name for s in OP_TABLE])
def test_op_parity_interpret_mode(spec):
    result = check_parity(spec)
    assert result.ok, f"pallas/ref disagreement for {spec.name}:\n{result}"


def test_signature_drift_is_caught():
    bogus = OpSpec("rmsnorm", None,
                   ("repro.kernels.leapfrog", "leapfrog_halfstep_ref"),
                   False, 1e-5)
    result = check_signatures(bogus)
    assert "RPL202" in result.codes()


def test_stale_registry_entry_is_caught(monkeypatch):
    import repro.lint_rules.invariants as inv
    stale = OP_TABLE + (OpSpec("no_such_op", None,
                               ("repro.kernels.ref", "rmsnorm"),
                               False, 0.0),)
    monkeypatch.setattr(inv, "OP_TABLE", stale)
    result = check_registry_completeness()
    assert "RPL201" in result.codes()
    assert any(f.site == "no_such_op" for f in result.findings)


def _small_nuts_setup():
    def model(x):
        mu = pc.sample("mu", dist.Normal(0.0, 1.0))
        pc.sample("obs", dist.Normal(mu, 1.0), obs=x)
    x = jnp.array([0.2, -0.1, 0.4])
    return NUTS(model).setup(random.PRNGKey(0), 10, model_args=(x,))


def test_kernel_setup_contract_passes_for_real_setup():
    setup = _small_nuts_setup()
    result = verify_kernel_setup(setup)
    assert result.ok, f"real NUTS setup violates its own contract:\n{result}"


def test_kernel_setup_contract_catches_violations():
    setup = _small_nuts_setup()
    r = verify_kernel_setup(setup._replace(num_warmup=jnp.asarray(10)))
    assert "RPL204" in r.codes() and "num_warmup" in str(r)
    r = verify_kernel_setup(setup._replace(adapt_schedule=[(0, 10)]))
    assert "RPL204" in r.codes() and "adapt_schedule" in str(r)
    r = verify_kernel_setup(setup._replace(sample_fn=None))
    assert "RPL204" in r.codes()
    # cross-chain state leaves must lead with the chain axis
    r = verify_kernel_setup(setup._replace(cross_chain=True),
                            state={"z": jnp.zeros((3, 2))}, num_chains=4)
    assert "RPL204" in r.codes() and "chain axis" in str(r)


def test_kernel_setup_data_axis_drift_is_caught():
    """RPL204 fabricated-drift negatives: either half of the data-sharding
    declaration (setup.data_axis vs the potential's data_shards marker)
    drifting alone must fail loudly."""
    setup = _small_nuts_setup()
    assert setup.data_axis is None and verify_kernel_setup(setup).ok

    # drift 1: axis declared, potential monolithic (no data_shards marker)
    r = verify_kernel_setup(setup._replace(data_axis="data"))
    assert "RPL204" in r.codes() and "data_shards" in str(r)

    # drift 2: axis declared but not a mesh axis name
    r = verify_kernel_setup(setup._replace(data_axis=3))
    assert "RPL204" in r.codes() and "axis name" in str(r)

    # drift 3: shard-aware potential with no axis declaration
    def pot(z):
        return jnp.sum(z * z)
    pot.data_shards = 4
    r = verify_kernel_setup(setup._replace(potential_fn=pot))
    assert "RPL204" in r.codes() and "data_axis is None" in str(r)

    # coherent declaration passes
    r = verify_kernel_setup(setup._replace(potential_fn=pot,
                                           data_axis="data"))
    assert r.ok, f"coherent data_axis declaration flagged:\n{r}"


# ---------------------------------------------------------------------------
# constraint audit: check()/feasible_like() across every distribution
# ---------------------------------------------------------------------------

def _audited_distributions():
    return [
        ("Normal", dist.Normal(0.0, 1.0)),
        ("LogNormal", dist.LogNormal(0.0, 1.0)),
        ("Cauchy", dist.Cauchy(0.0, 1.0)),
        ("StudentT", dist.StudentT(3.0, 0.0, 1.0)),
        ("Gamma", dist.Gamma(2.0, 1.0)),
        ("InverseGamma", dist.InverseGamma(2.0, 1.0)),
        ("Beta", dist.Beta(2.0, 2.0)),
        ("Exponential", dist.Exponential(1.0)),
        ("HalfNormal", dist.HalfNormal(1.0)),
        ("HalfCauchy", dist.HalfCauchy(1.0)),
        ("Dirichlet", dist.Dirichlet(jnp.ones(3))),
        ("MultivariateNormal",
         dist.MultivariateNormal(jnp.zeros(2),
                                 covariance_matrix=jnp.eye(2))),
        ("Delta", dist.Delta(0.5)),
        ("Bernoulli", dist.Bernoulli(probs=0.3)),
        ("Categorical", dist.Categorical(probs=jnp.ones(3) / 3)),
        ("DiscreteUniform", dist.DiscreteUniform(0, 5)),
    ]


@pytest.mark.parametrize("name,d", _audited_distributions(),
                         ids=[n for n, _ in _audited_distributions()])
def test_support_check_and_feasible_like(name, d):
    c = d.support
    proto = jnp.zeros(d.batch_shape + d.event_shape)
    feasible = c.feasible_like(proto)
    assert jnp.shape(feasible) == jnp.shape(proto)
    assert bool(jnp.all(c.check(feasible))), (
        f"{name}: feasible_like produced an infeasible value")
    # check() must be trace-safe: the lint path evaluates it under eval_shape
    out = jax.eval_shape(c.check, jax.ShapeDtypeStruct(proto.shape,
                                                       proto.dtype))
    assert out.dtype == jnp.bool_
    # a sample from the distribution lies in its own support
    s = d.sample(rng_key=random.PRNGKey(0))
    assert bool(jnp.all(c.check(s)))


def test_remaining_constraint_singletons_feasible():
    lc = constraints.lower_cholesky.feasible_like(jnp.zeros((4, 3, 3)))
    assert jnp.shape(lc) == (4, 3, 3)
    assert bool(jnp.all(constraints.lower_cholesky.check(lc)))
    pv = constraints.positive_vector.feasible_like(jnp.zeros(5))
    assert bool(jnp.all(constraints.positive_vector.check(pv)))
    ii = constraints.integer_interval(2, 7).feasible_like(jnp.zeros(3))
    assert bool(jnp.all(constraints.integer_interval(2, 7).check(ii)))
    iv = constraints.interval(-1.0, 3.0).feasible_like(jnp.zeros(()))
    assert float(iv) == 1.0  # midpoint


# ---------------------------------------------------------------------------
# validate= hooks: MCMC / SVI
# ---------------------------------------------------------------------------

def _logreg_setup(trace_counter=None):
    x = random.normal(random.PRNGKey(0), (20, 3))
    y = (x @ jnp.ones(3) > 0).astype(jnp.float32)

    def model(x, y=None):
        if trace_counter is not None:
            trace_counter["n"] += 1
        w = pc.sample("w", dist.Normal(jnp.zeros(3), 1.0).to_event(1))
        with pc.plate("data", x.shape[0]):
            pc.sample("obs", dist.Bernoulli(logits=x @ w), obs=y)
    return model, x, y


def test_mcmc_validate_rejects_defective_model():
    model, *_ = _dup_site()
    x = jnp.zeros(3)
    mcmc = MCMC(NUTS(lambda: model()), num_warmup=5, num_samples=5,
                validate=True)
    with pytest.raises(ValueError, match=r"\[RPL001\]"):
        mcmc.run(random.PRNGKey(0))
    del x


def test_mcmc_validate_passes_clean_model_and_adds_no_recompiles():
    model, x, y = _logreg_setup()
    mcmc = MCMC(NUTS(model), num_warmup=10, num_samples=10, validate=True)
    mcmc.run(random.PRNGKey(0), x, y=y)
    assert mcmc.get_samples()["w"].shape == (10, 3)
    n_compiled = len(mcmc._exec_cache)
    # warm re-run: the cached setup short-circuits validation entirely,
    # and no new executables are built
    mcmc.run(random.PRNGKey(1), x, y=y)
    assert len(mcmc._exec_cache) == n_compiled

    plain = MCMC(NUTS(model), num_warmup=10, num_samples=10)
    plain.run(random.PRNGKey(0), x, y=y)
    assert len(plain._exec_cache) == n_compiled  # same program set


def test_mcmc_validate_is_cold_path_only():
    counter = {"n": 0}
    model, x, y = _logreg_setup(counter)
    mcmc = MCMC(NUTS(model), num_warmup=5, num_samples=5, validate=True)
    mcmc.run(random.PRNGKey(0), x, y=y)
    warm = counter["n"]
    mcmc.run(random.PRNGKey(1), x, y=y)
    assert counter["n"] == warm, (
        "validate=True re-traced the model on the warm path")


def test_svi_validate_rejects_defective_guide():
    model, x, y = _logreg_setup()

    def bad_guide(x, y=None):
        pc.param("loc", jnp.zeros(3))
        pc.sample("w", dist.Normal(jnp.zeros(3), 1.0).to_event(1))
        pc.sample("w", dist.Normal(jnp.zeros(3), 1.0).to_event(1))

    svi = SVI(model, bad_guide, optim.adam(1e-2), Trace_ELBO(),
              validate=True)
    with pytest.raises(ValueError, match=r"\[RPL001\]"):
        svi.init(random.PRNGKey(0), x, y=y)


def test_svi_validate_compiles_once():
    counter = {"n": 0}
    model, x, y = _logreg_setup(counter)

    def guide(x, y=None):
        loc = pc.param("w_loc", jnp.zeros(3))
        scale = pc.param("w_scale", jnp.ones(3))
        pc.sample("w", dist.Normal(loc, jnp.abs(scale) + 1e-3).to_event(1))

    svi = SVI(model, guide, optim.adam(1e-2), Trace_ELBO(), validate=True)
    state = svi.init(random.PRNGKey(0), x, y=y)
    step = jax.jit(svi.update)
    state, _ = step(state, x, y=y)
    state, _ = step(state, x, y=y)
    warm = counter["n"]
    for _ in range(30):
        state, _ = step(state, x, y=y)
    assert counter["n"] == warm, (
        "validate=True forced retraces inside the jitted update")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_reports_defective_target(tmp_path):
    from repro.lint.__main__ import main
    target = tmp_path / "defective.py"
    target.write_text(
        "import repro.core as pc\n"
        "from repro.core import dist\n\n"
        "def model():\n"
        "    pc.sample('w', dist.Normal(0.0, 1.0))\n"
        "    pc.sample('w', dist.Normal(0.0, 1.0))\n")
    assert main([f"{target}:model"]) == 1

    clean = tmp_path / "clean.py"
    clean.write_text(
        "import repro.core as pc\n"
        "from repro.core import dist\n\n"
        "def model():\n"
        "    mu = pc.sample('mu', dist.Normal(0.0, 1.0))\n"
        "    pc.sample('obs', dist.Normal(mu, 1.0), obs=0.5)\n")
    assert main([f"{clean}:model"]) == 0


@pytest.mark.docs
def test_cli_corpus_passes():
    from repro.lint.__main__ import main
    assert main(["--corpus"]) == 0


def test_lint_result_raise_if_errors():
    model, args, kwargs, lint_kwargs = _dup_site()
    result = lint_model(model, args, kwargs, **lint_kwargs)
    with pytest.raises(ReproValueError, match=r"\[RPL001\]"):
        result.raise_if_errors()
    clean = lint_model(lambda: pc.sample("obs", dist.Normal(0.0, 1.0),
                                         obs=0.5))
    assert clean.raise_if_errors() is clean
