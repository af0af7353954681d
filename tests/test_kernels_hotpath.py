"""Roofline hot-path kernels: fused GLM potential, chain-batched leapfrog
megakernel, batched MALA/RWM proposals.

Everything runs in Pallas interpret mode on CPU: the registry-driven parity
sweep (RPL202/RPL203 over the whole OP_TABLE — new ops are picked up
automatically), megakernel-vs-vmapped-halfstep equivalence on the ChEES
path, GLM fused-potential exactness + structural fallback + compile-once
behavior, and the MALA/RWM samplers through the unchanged executor
(posterior sanity, RPL204 contract, bit-identical checkpoint/resume).
"""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, random
from jax.sharding import AbstractMesh, AxisType

import repro.core as pc
from repro.core import dist
from repro.core.infer import MALA, MCMC, NUTS, RWM, mrw_setup, nuts_setup
from repro.core.infer.hmc_util import (
    IntegratorState,
    velocity_verlet,
    velocity_verlet_batch,
)
from repro.core.infer.util import initialize_model_structure
from repro.kernels import ops
from repro.kernels.leapfrog import (
    leapfrog_halfstep,
    leapfrog_halfstep_batch,
    leapfrog_halfstep_batch_ref,
)
from repro.lint_rules.invariants import (
    check_parity,
    check_signatures,
    verify_kernel_setup,
)

pytestmark = pytest.mark.kernels


# ---------------------------------------------------------------------------
# registry-driven parity (RPL202/RPL203): every op, interpret mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", ops.OP_TABLE,
                         ids=[s.name for s in ops.OP_TABLE])
def test_registry_signatures(spec):
    assert check_signatures(spec).findings == []


@pytest.mark.parametrize("spec", ops.OP_TABLE,
                         ids=[s.name for s in ops.OP_TABLE])
def test_registry_parity_interpret(spec):
    assert check_parity(spec, random.PRNGKey(7)).findings == []


def test_dispatch_follows_the_platform(monkeypatch):
    """Ops take their jnp reference on the CPU backend and their Pallas
    kernel on a TPU; ``use_pallas`` overrides either way."""
    z, g, noise = (random.normal(k, (4, 6))
                   for k in random.split(random.PRNGKey(0), 3))

    def routed_to_kernel():
        # a fresh function each time: traces are cached per function, and
        # the route is read while tracing
        jaxpr = jax.make_jaxpr(lambda *a: ops.mala_step(*a))(
            z, g, noise, jnp.ones(6), 0.1)
        return "pallas_call" in str(jaxpr)

    assert jax.default_backend() == "cpu"
    assert not ops.pallas_enabled("mala_step") and not routed_to_kernel()
    with ops.use_pallas(True, interpret=True):
        assert ops.pallas_enabled("mala_step") and routed_to_kernel()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.pallas_enabled("mala_step")
    # kernels the TPU compiler refuses stay on their oracle there
    for op in ("attention", "rmsnorm", "softmax_xent", "ssd_scan"):
        assert not ops.pallas_enabled(op)
        with ops.use_pallas(True):
            assert ops.pallas_enabled(op)
    with ops.use_pallas(False):
        assert not ops.pallas_enabled("mala_step") and not routed_to_kernel()
    # an override may name the ops that take their kernels
    with ops.use_pallas(["glm_potential_grad"], interpret=True):
        assert ops.pallas_enabled("glm_potential_grad")
        assert not ops.pallas_enabled("mala_step") and not routed_to_kernel()


@pytest.mark.parametrize("axis_types,kernel", [
    (None, True),                                   # no mesh in the trace
    ((AxisType.Auto,), False),                      # partitioned program
    ((AxisType.Explicit,), False),
    ((AxisType.Manual,), True),                     # a shard_map body
])
def test_dispatch_keeps_kernels_out_of_partitioned_programs(
        monkeypatch, axis_types, kernel):
    """A Pallas TPU kernel cannot be partitioned: under a tracing mesh of
    several devices the platform route takes the oracle, except along
    manual (``shard_map``) axes; an explicit override still wins."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = (AbstractMesh((4,), ("data",), axis_types=axis_types)
            if axis_types else AbstractMesh((), ()))
    with jax.sharding.use_abstract_mesh(mesh):
        assert ops.pallas_enabled("glm_potential_grad") is kernel
        with ops.use_pallas(True):
            assert ops.pallas_enabled("glm_potential_grad")
    partial = AbstractMesh((2, 2), ("chains", "data"),
                           axis_types=(AxisType.Auto, AxisType.Manual))
    with jax.sharding.use_abstract_mesh(partial):
        assert not ops.pallas_enabled("glm_potential_grad")


# ---------------------------------------------------------------------------
# chain-batched leapfrog megakernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("C,D", [(1, 64), (5, 515), (8, 128), (64, 16)])
def test_megakernel_matches_vmapped_halfstep(C, D):
    """(C, D) megakernel == per-chain vmap(fused halfstep) within 1e-6 —
    the exact replacement made on the ChEES dense path."""
    ks = random.split(random.PRNGKey(0), 4)
    z, r, g = (random.normal(k, (C, D)) for k in ks[:3])
    m_inv = jnp.abs(random.normal(ks[3], (D,))) + 0.5
    eps = jnp.asarray(0.07)
    zv, rv = jax.vmap(lambda zz, rr, gg: ops.leapfrog_halfstep(
        zz, rr, gg, m_inv, eps))(z, r, g)
    for pallas in (False, True):
        with ops.use_pallas(pallas, interpret=True):
            zb, rb = ops.leapfrog_halfstep_batch(z, r, g, m_inv, eps)
        assert float(jnp.max(jnp.abs(zb - zv))) < 1e-6
        assert float(jnp.max(jnp.abs(rb - rv))) < 1e-6


def test_megakernel_full_kick_is_merged_halfkicks():
    """kick=1.0 == two adjacent half-kicks with no drift in between."""
    ks = random.split(random.PRNGKey(1), 4)
    z, r, g = (random.normal(k, (4, 130)) for k in ks[:3])
    m_inv = jnp.abs(random.normal(ks[3], (130,))) + 0.5
    eps = 0.05
    _, r_full = leapfrog_halfstep_batch_ref(z, r, g, m_inv, eps, kick=1.0)
    np.testing.assert_allclose(np.asarray(r_full),
                               np.asarray(r - eps * g), rtol=1e-6)
    z_full, _ = leapfrog_halfstep_batch(z, r, g, m_inv, eps, kick=1.0,
                                        interpret=True)
    z_exp, _ = leapfrog_halfstep_batch_ref(z, r, g, m_inv, eps, kick=1.0)
    assert float(jnp.max(jnp.abs(z_full - z_exp))) < 1e-6


def test_leapfrog_block_kwarg_is_pure_tuning():
    """The (bugfixed) trailing block kwarg changes tiling, not results."""
    ks = random.split(random.PRNGKey(2), 4)
    z, r, g = (random.normal(k, (515,)) for k in ks[:3])
    m_inv = jnp.abs(random.normal(ks[3], (515,))) + 0.5
    z1, r1 = leapfrog_halfstep(z, r, g, m_inv, 0.1, interpret=True)
    z2, r2 = leapfrog_halfstep(z, r, g, m_inv, 0.1, block=128,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    zb1, _ = leapfrog_halfstep_batch(jnp.stack([z] * 3), jnp.stack([r] * 3),
                                     jnp.stack([g] * 3), m_inv, 0.1,
                                     interpret=True)
    zb2, _ = leapfrog_halfstep_batch(jnp.stack([z] * 3), jnp.stack([r] * 3),
                                     jnp.stack([g] * 3), m_inv, 0.1,
                                     block=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(zb1), np.asarray(zb2))


@pytest.mark.parametrize("num_steps", [1, 2, 7])
def test_batched_trajectory_matches_vmapped_verlet(num_steps):
    """velocity_verlet_batch (merged interior kicks) == the old
    fori_loop(vmap(vv_update)) loop: exact leapfrog, same positions and
    momenta up to float reassociation."""
    C, D = 6, 37
    pot = lambda z: 0.5 * jnp.dot(z * jnp.linspace(0.5, 2.0, D), z)  # noqa: E731
    ks = random.split(random.PRNGKey(3), 2)
    z, r = random.normal(ks[0], (C, D)), random.normal(ks[1], (C, D))
    pe, grad = jax.vmap(jax.value_and_grad(pot))(z)
    m_inv = jnp.abs(random.normal(random.PRNGKey(4), (D,))) + 0.5
    eps = jnp.asarray(0.05)
    state = IntegratorState(z, r, pe, grad)

    _, vv_update = velocity_verlet(pot)
    step_all = jax.vmap(lambda s: vv_update(eps, m_inv, s))
    expected = lax.fori_loop(0, num_steps, lambda _, s: step_all(s), state)

    trajectory = velocity_verlet_batch(pot)
    got = jax.jit(lambda s, n: trajectory(eps, m_inv, s, n))(
        state, jnp.asarray(num_steps))
    for a, b in zip(got, expected):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# fused GLM potential
# ---------------------------------------------------------------------------


def _logreg_pair(n=300, d=5):
    ks = random.split(random.PRNGKey(5), 3)
    x = random.normal(ks[0], (n, d))
    w_true = random.normal(ks[1], (d,))
    y = (random.uniform(ks[2], (n,))
         < jax.nn.sigmoid(x @ w_true)).astype(jnp.float32)

    def plain(x, y=None):
        d = x.shape[-1]
        w = pc.sample("w", dist.Normal(jnp.zeros(d),
                                       jnp.ones(d)).to_event(1))
        return pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y)

    def glm(x, y=None):
        d = x.shape[-1]
        w = pc.sample("w", dist.Normal(jnp.zeros(d),
                                       jnp.ones(d)).to_event(1))
        return pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y,
                         infer={"potential": "glm"})

    return plain, glm, x, y


def test_glm_fused_potential_matches_plain():
    """Fused potential == plain potential (value and gradient) everywhere,
    including under jit+vmap — the custom_vjp backward is the kernel's own
    residual product."""
    plain, glm, x, y = _logreg_pair()
    key = random.PRNGKey(0)
    p_plain = initialize_model_structure(key, plain, (x,), {"y": y})[0]
    p_glm = initialize_model_structure(key, glm, (x,), {"y": y})[0]
    zs = random.normal(random.PRNGKey(6), (8, x.shape[1]))
    v1, g1 = jax.jit(jax.vmap(jax.value_and_grad(p_plain)))(zs)
    v2, g2 = jax.jit(jax.vmap(jax.value_and_grad(p_glm)))(zs)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-4)


def test_glm_normal_family_matches_plain():
    ks = random.split(random.PRNGKey(7), 3)
    x = random.normal(ks[0], (200, 4))
    y = x @ random.normal(ks[1], (4,)) + 0.3 * random.normal(ks[2], (200,))

    def plain(x, y=None):
        d = x.shape[-1]
        w = pc.sample("w", dist.Normal(jnp.zeros(d),
                                       jnp.ones(d)).to_event(1))
        return pc.sample("y", dist.Normal(x @ w, 0.3).to_event(1), obs=y)

    def glm(x, y=None):
        d = x.shape[-1]
        w = pc.sample("w", dist.Normal(jnp.zeros(d),
                                       jnp.ones(d)).to_event(1))
        return pc.sample("y", dist.Normal(x @ w, 0.3).to_event(1), obs=y,
                         infer={"potential": "glm"})

    key = random.PRNGKey(0)
    p_plain = initialize_model_structure(key, plain, (x,), {"y": y})[0]
    p_glm = initialize_model_structure(key, glm, (x,), {"y": y})[0]
    z = random.normal(random.PRNGKey(8), (4,))
    v1, g1 = jax.value_and_grad(p_plain)(z)
    v2, g2 = jax.value_and_grad(p_glm)(z)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("family", ["bernoulli_logit", "normal"])
@pytest.mark.parametrize("n", [40, 64, 100, 257])
def test_glm_kernel_reads_x_in_place(family, n):
    """Tiles span all d columns and the last tile runs past row n: interpret
    mode fills what lies beyond with NaN, as undefined memory would be on
    the chip, so an unmasked row would poison the value or the gradient."""
    from repro.kernels import ref
    from repro.kernels.glm_potential import glm_potential_grad
    ks = random.split(random.PRNGKey(n), 4)
    x = random.normal(ks[0], (n, 54))
    w = 0.3 * random.normal(ks[1], (54,))
    y = (random.bernoulli(ks[2], 0.4, (n,)).astype(jnp.float32)
         if family == "bernoulli_logit" else random.normal(ks[2], (n,)))
    off = random.normal(ks[3], (n,))
    got = glm_potential_grad(x, y, w, off, 0.7, family, block_n=64,
                             interpret=True)
    want = ref.glm_potential_grad(x, y, w, off, 0.7, family)
    for a, b in zip(got, want):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-4)


def _glm_slab_data(n, d, family, with_offset, key):
    ks = random.split(key, 3)
    x = random.normal(ks[0], (n, d))
    y = (random.bernoulli(ks[1], 0.4, (n,)).astype(jnp.float32)
         if family == "bernoulli_logit" else random.normal(ks[1], (n,)))
    off = random.normal(ks[2], (n,)) if with_offset else None
    return x, y, off


@pytest.mark.parametrize("family", ["bernoulli_logit", "normal"])
@pytest.mark.parametrize("chains", [1, 4, 64])
@pytest.mark.parametrize("with_offset", [False, True])
def test_glm_slab_kernel_matches_per_chain_ref(family, chains, with_offset):
    """The chain-batched kernel over the design slab gives every chain the
    per-chain oracle's value and gradient.  n = 300 is no multiple of the
    128-lane tile: interpret mode fills the last tile's overrun with NaN,
    as undefined memory would be on the chip."""
    from repro.kernels import ref
    from repro.kernels.glm_potential import glm_potential_grad_slab, glm_slab
    n, d = 300, 54
    x, y, off = _glm_slab_data(n, d, family, with_offset,
                               random.PRNGKey(chains))
    w = 0.3 * random.normal(random.PRNGKey(1), (chains, d))
    got = glm_potential_grad_slab(glm_slab(x, y, off), w, 0.7, family,
                                  block_n=128, interpret=True)
    want = jax.vmap(lambda wc: ref.glm_potential_grad(x, y, wc, off, 0.7,
                                                      family))(w)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-4)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr`` and its sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("family", ["bernoulli_logit", "normal"])
def test_glm_chain_batched_gradient_is_one_kernel_call(family):
    """``vmap(value_and_grad(fused))`` over 4 chains is one kernel call
    whose grid walks the n-tiles only, with no chain axis, and the fused
    potential's route reads batched over 4 chains."""
    n, d = 300, 5
    x, y, _ = _glm_slab_data(n, d, family, False, random.PRNGKey(3))

    def model(x, y=None):
        w = pc.sample("w", dist.Normal(jnp.zeros(d),
                                       jnp.ones(d)).to_event(1))
        fn = (dist.Bernoulli(logits=x @ w) if family == "bernoulli_logit"
              else dist.Normal(x @ w, 0.7).to_event(1))
        return pc.sample("y", fn, obs=y, infer={"potential": "glm"})

    zs = random.normal(random.PRNGKey(4), (4, d))
    with ops.use_pallas(True, interpret=True):
        fused = initialize_model_structure(random.PRNGKey(0), model, (x,),
                                           {"y": y})[0]
        jaxpr = jax.make_jaxpr(jax.vmap(jax.value_and_grad(fused)))(zs)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 1
    assert calls[0].params["grid_mapping"].grid == (1,)   # one n-tile
    assert fused.glm_route == {"route": "batched", "chains": 4}


@pytest.mark.parametrize("w_batched", [False, True])
def test_glm_batched_data_keeps_one_pass_per_chain(w_batched):
    """Data that differs per chain (a batched slab) takes the per-chain
    route: the kernel's own batching, one grid row per chain, with the
    per-chain oracle's numbers."""
    from repro.core.infer.glm import _slab_value_and_grad
    from repro.kernels import ref
    from repro.kernels.glm_potential import glm_slab
    n, d, chains = 300, 6, 3
    data = [_glm_slab_data(n, d, "bernoulli_logit", True, random.PRNGKey(k))
            for k in range(chains)]
    slabs = jnp.stack([glm_slab(*xyo) for xyo in data])
    w = 0.3 * random.normal(random.PRNGKey(9), (chains, 1, d))
    route = {}
    vg = _slab_value_and_grad(None, "bernoulli_logit", route)
    batched = jax.vmap(vg, in_axes=(0, 0 if w_batched else None))
    with ops.use_pallas(True, interpret=True):
        args = (slabs, w if w_batched else w[0])
        calls = _pallas_calls(jax.make_jaxpr(batched)(*args).jaxpr)
        nll, grad = batched(*args)
    assert route == {"route": "per_chain", "chains": chains}
    assert len(calls) == 1
    assert calls[0].params["grid_mapping"].grid[0] == chains
    for c, (x, y, off) in enumerate(data):
        v, g = ref.glm_potential_grad(x, y, w[c if w_batched else 0, 0],
                                      off)
        np.testing.assert_allclose(float(nll[c, 0]), float(v), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grad[c, 0]), np.asarray(g),
                                   rtol=1e-4, atol=1e-4)


def test_glm_nuts_reports_its_route_in_the_manifest(tmp_path):
    """Four vectorized NUTS chains take the chain-batched route, and the
    run manifest says so."""
    import json

    from repro import obs
    _, glm, x, y = _logreg_pair(n=200, d=3)
    tele = obs.Telemetry(dir=str(tmp_path))
    mcmc = MCMC(NUTS(glm), num_warmup=20, num_samples=20, num_chains=4,
                progress=False, telemetry=tele)
    mcmc.run(random.PRNGKey(0), x, y=y)
    with open(tmp_path / "run_manifest.json") as f:
        final = json.load(f)["sessions"][-1]["final"]
    assert final["glm_route"] == {"route": "batched", "chains": 4}


def test_glm_nonaffine_predictor_falls_back_with_warning():
    """A non-affine marked site must warn and keep exact plain semantics —
    the fusion is an optimization, never a silent approximation."""
    ks = random.split(random.PRNGKey(9), 2)
    x = random.normal(ks[0], (100, 3))
    y = (random.uniform(ks[1], (100,)) < 0.5).astype(jnp.float32)

    def nonaffine(x, y=None):
        d = x.shape[-1]
        w = pc.sample("w", dist.Normal(jnp.zeros(d),
                                       jnp.ones(d)).to_event(1))
        return pc.sample("y", dist.Bernoulli(logits=x @ jnp.tanh(w)),
                         obs=y, infer={"potential": "glm"})

    def plain(x, y=None):
        d = x.shape[-1]
        w = pc.sample("w", dist.Normal(jnp.zeros(d),
                                       jnp.ones(d)).to_event(1))
        return pc.sample("y", dist.Bernoulli(logits=x @ jnp.tanh(w)),
                         obs=y)

    with pytest.warns(UserWarning, match="not affine"):
        p_fused = initialize_model_structure(random.PRNGKey(0), nonaffine,
                                             (x,), {"y": y})[0]
    p_plain = initialize_model_structure(random.PRNGKey(0), plain, (x,),
                                         {"y": y})[0]
    z = random.normal(random.PRNGKey(1), (3,))
    np.testing.assert_allclose(float(p_fused(z)), float(p_plain(z)),
                               rtol=1e-6)


def test_glm_kernel_error_propagates(monkeypatch):
    """An error raised by the kernel (or its lowering) is not a structural
    surprise: it propagates instead of warning and quietly running the
    plain potential, which on a TPU would hide the broken kernel."""
    _, glm, x, y = _logreg_pair()

    def broken(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile the kernel")

    monkeypatch.setattr(ops, "glm_potential_grad_slab", broken)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(RuntimeError, match="Mosaic"):
            initialize_model_structure(random.PRNGKey(0), glm, (x,),
                                       {"y": y})


def test_glm_nuts_setup_compile_once_across_arg_shapes():
    """One GLM-potential NUTS setup compiles once per state shape; a second
    setup at a different data shape is an independent cache entry and also
    compiles once (the custom_vjp potential must not retrace per call)."""
    for n in (150, 260):
        _, glm, x, y = _logreg_pair(n=n, d=4)
        setup = nuts_setup(random.PRNGKey(0), 10, model=glm,
                           model_args=(x,), model_kwargs={"y": y})
        n_traces = 0

        def step(state, sample_fn=setup.sample_fn):
            nonlocal n_traces
            n_traces += 1
            return sample_fn(state)

        stepper = jax.jit(step)
        state = setup.init_fn(random.PRNGKey(1))
        s1 = stepper(state)
        s2 = stepper(s1)
        assert n_traces == 1, n
        assert bool(jnp.isfinite(s2.potential_energy))


def test_glm_nuts_posterior_matches_plain_nuts():
    """Statistical acceptance: NUTS on the glm-marked model reproduces the
    plain-model posterior (same data, same seeds)."""
    plain, glm, x, y = _logreg_pair(n=250, d=3)
    means = {}
    for name, model in (("plain", plain), ("glm", glm)):
        mcmc = MCMC(NUTS(model), num_warmup=300, num_samples=300,
                    num_chains=2)
        mcmc.run(random.PRNGKey(2), x, y=y)
        means[name] = np.asarray(mcmc.get_samples()["w"].mean(0))
    np.testing.assert_allclose(means["glm"], means["plain"], atol=0.15)


# ---------------------------------------------------------------------------
# MALA / RWM through the unchanged executor
# ---------------------------------------------------------------------------


def _scalar_model():
    def model():
        pc.sample("x", dist.Normal(1.5, 2.0))
    return model


@pytest.mark.parametrize("kernel_cls", [MALA, RWM],
                         ids=["mala", "rwm"])
def test_mrw_posterior_sanity(kernel_cls):
    mcmc = MCMC(kernel_cls(_scalar_model()), num_warmup=600,
                num_samples=600, num_chains=16)
    mcmc.run(random.PRNGKey(0))
    xs = mcmc.get_samples()["x"]
    assert xs.shape == (16 * 600,)
    assert abs(float(xs.mean()) - 1.5) < 0.15
    assert abs(float(xs.std()) - 2.0) < 0.2


@pytest.mark.parametrize("algo,target", [("MALA", 0.574), ("RWM", 0.234)],
                         ids=["mala", "rwm"])
def test_mrw_adaptation_hits_target_accept(algo, target):
    """Dual averaging controls the cross-chain *harmonic mean* acceptance
    (worst chains dominate) — that statistic, not the arithmetic mean, must
    land at the Roberts–Rosenthal target after warmup."""
    def model():
        pc.sample("v", dist.Normal(jnp.zeros(4), 2.0).to_event(1))

    setup = mrw_setup(random.PRNGKey(0), 500, algo, model=model)
    state = setup.init_fn(random.split(random.PRNGKey(1), 32))
    step = jax.jit(setup.sample_fn)
    hmeans = []
    for t in range(800):
        state = step(state)
        if t >= 500:
            ap = jnp.clip(state.accept_prob, min=1e-10)
            hmeans.append(1.0 / float((1.0 / ap).mean()))
    hmean = float(np.mean(hmeans))
    assert abs(hmean - target) < 0.12, (algo, hmean)


@pytest.mark.parametrize("algo", ["MALA", "RWM"])
def test_mrw_kernel_setup_contract(algo):
    """RPL204: the batch-aware contract, including cross-chain leaves."""
    setup = mrw_setup(random.PRNGKey(0), 20, algo, model=_scalar_model())
    state = setup.init_fn(random.split(random.PRNGKey(1), 4))
    result = verify_kernel_setup(setup, state=state, num_chains=4)
    assert result.findings == []


@pytest.mark.parametrize("kernel_cls", [MALA, RWM], ids=["mala", "rwm"])
def test_mrw_checkpoint_resume_mid_warmup_bit_identical(kernel_cls,
                                                        tmp_path):
    """Kill mid-warmup (pooled adaptation state lives only in the
    checkpoint pytree), resume, and finish bit-identically — same
    acceptance as the ChEES resume test, through the same executor."""
    from repro.distributed import checkpoint as ckpt

    def make():
        return MCMC(kernel_cls(_scalar_model()), num_warmup=60,
                    num_samples=80, num_chains=4)

    ref_run = make()
    ref_run.run(random.PRNGKey(9))
    expected = np.asarray(ref_run.get_samples(group_by_chain=True)["x"])

    ckdir = str(tmp_path / "mrw")
    real_save, calls = ckpt.save, {"n": 0}

    def killing_save(tree, directory, **kw):
        real_save(tree, directory, **kw)
        calls["n"] += 1
        if calls["n"] == 2:   # state at iteration 50 — still in warmup
            raise KeyboardInterrupt

    ckpt.save = killing_save
    try:
        with pytest.raises(KeyboardInterrupt):
            make().run(random.PRNGKey(9), checkpoint_every=25,
                       checkpoint_dir=ckdir)
    finally:
        ckpt.save = real_save

    step = ckpt.latest_step(os.path.join(ckdir, "state"))
    assert step is not None and step < 60, step   # mid-warmup

    resumed = make()
    resumed.run(random.PRNGKey(9), checkpoint_every=25,
                checkpoint_dir=ckdir, resume=True)
    got = np.asarray(resumed.get_samples(group_by_chain=True)["x"])
    np.testing.assert_array_equal(got, expected)
