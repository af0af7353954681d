"""Inference utilities: log densities, transforms to unconstrained space,
model initialization, and vmap-powered predictive utilities (paper Sec 3.2).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from ..dist.transforms import biject_to
from ..handlers import block, seed, substitute, trace


def log_density(model, model_args, model_kwargs, params):
    """Joint log density of ``model`` at ``params`` (constrained space).

    Returns ``(log_joint, trace)``.  This is the *single* density accumulator
    in the system — ``Trace_ELBO``, :func:`potential_energy` (and through it
    HMC/NUTS via :func:`initialize_model_structure`) all reduce to it — so the
    message-protocol contract is honored in exactly one place: per-site
    ``mask`` zeroes elements *before* the multiplicative ``scale`` applies
    (handlers accumulate both; see :mod:`repro.core.handlers`), and only
    ``sample`` sites contribute (``param``/``deterministic``/``plate`` sites
    carry no density).  A subsampled plate therefore yields an unbiased
    minibatch estimate of the full-data log density: each enclosed site is
    scaled by ``size / subsample_size``.

    The accumulator is *enumeration-aware*: a first, inert probe pass detects
    sites marked ``infer={"enumerate": "parallel"}`` (or chains built with
    :func:`~repro.core.infer.enum.markov`) and measures the deepest
    plate/batch dim.  If any are found, the trace is re-run under an
    :class:`~repro.core.infer.enum.enum` handler that broadcasts each such
    site's full support into fresh leftmost dims, and those dims are summed
    out exactly by :func:`~repro.core.infer.enum.contract_enum_factors` —
    the returned ``log_joint`` is the discrete-marginalized joint density,
    still a pure, differentiable function of ``params``.  Models without
    enumeration marks take the plain single-pass path unchanged.
    """
    from .enum import _EnumProbe, _first_available_dim, contract_enum_factors
    from .enum import enum as _enum

    probe = _EnumProbe(model)
    substituted = substitute(probe, data=params)
    tr = trace(substituted).get_trace(*model_args, **model_kwargs)
    if probe.found:
        enum_handler = _enum(model,
                             first_available_dim=_first_available_dim(probe))
        substituted = substitute(enum_handler, data=params)
        tr = trace(substituted).get_trace(*model_args, **model_kwargs)
        return contract_enum_factors(tr), tr
    from .enum import _site_log_prob
    log_joint = jnp.zeros(())
    for site in tr.values():
        if site["type"] != "sample":
            continue
        log_joint = log_joint + jnp.sum(_site_log_prob(site))
    return log_joint, tr


def get_model_transforms(model, model_args=(), model_kwargs=None, rng_key=None):
    """Trace the model once to discover latent sites and their bijections.

    Wrapped in ``block`` so the exploratory trace never leaks sites into any
    enclosing handler (e.g. when called from a guide that is itself being
    traced).
    """
    model_kwargs = model_kwargs or {}
    key = rng_key if rng_key is not None else jax.random.PRNGKey(0)
    with block():
        tr = trace(seed(model, key)).get_trace(*model_args, **model_kwargs)
    transforms, latent_shapes = {}, {}
    for name, site in tr.items():
        if site["type"] == "sample" and not site["is_observed"]:
            fn = site["fn"]
            if (site["infer"].get("enumerate") == "parallel"
                    or getattr(fn, "has_enumerate_support", False)):
                # enumerable discrete latent: no bijection to R^n — the
                # enum-aware log_density marginalizes it instead, so it is
                # simply not part of the continuous latent vector
                continue
            support = fn.support
            transforms[name] = biject_to(support)
            latent_shapes[name] = jnp.shape(site["value"])
    return transforms, tr


def transform_fn(transforms, params, invert=False):
    return {
        k: transforms[k].inv(v) if invert else transforms[k](v)
        for k, v in params.items()
    }


def constrain_fn(model, model_args, model_kwargs, transforms, params_uncon):
    return transform_fn(transforms, params_uncon)


def potential_energy(model, model_args, model_kwargs, transforms, params_uncon):
    """-log p(constrained(z)) - log|det J(z)| on unconstrained space."""
    return _potential_and_trace(model, model_args, model_kwargs, transforms,
                                params_uncon)[0]


def _potential_and_trace(model, model_args, model_kwargs, transforms,
                         params_uncon):
    params_con = {}
    log_det = jnp.zeros(())
    for name, t in transforms.items():
        u = params_uncon[name]
        x = t(u)
        params_con[name] = x
        ladj = t.log_abs_det_jacobian(u, x)
        log_det = log_det + jnp.sum(ladj)
    log_joint, tr = log_density(model, model_args, model_kwargs, params_con)
    return -(log_joint + log_det), tr


def initialize_model_structure(rng_key, model, model_args=(),
                               model_kwargs=None, data_shards=None):
    """One-time Python-level work: trace the model, build the flat-space
    closures.  No initial-point search — that part is pure and per-chain
    (:func:`find_valid_initial_params`), so a multi-chain driver runs this
    once and ``vmap``s the search over chain keys.

    Returns ``(potential_fn_flat, unravel_fn, transforms, constrain,
    model_trace, flat_prototype)``.

    Models with enumerable discrete latents need no special treatment from
    the caller: the model is wrapped in
    :func:`~repro.core.infer.enum.config_enumerate` (inert otherwise), those
    sites are excluded from the continuous latent vector, and every
    potential-energy evaluation marginalizes them through the enum-aware
    :func:`log_density` — so the existing jit-compiled NUTS executor runs
    mixture/HMM models with untouched model code.
    """
    from .enum import config_enumerate
    model_kwargs = model_kwargs or {}
    model = config_enumerate(model)
    transforms, tr = get_model_transforms(model, model_args, model_kwargs,
                                          rng_key)
    if not transforms:
        raise ValueError("model has no continuous latent sample sites")

    # prototype unconstrained pytree (used for ravel/unravel structure)
    proto = {}
    for name, t in transforms.items():
        value = tr[name]["value"]
        proto[name] = t.inv(value)
    flat_proto, unravel_fn = ravel_pytree(proto)

    markov_route = {}

    def potential_flat(zflat):
        u, tr = _potential_and_trace(model, model_args, model_kwargs,
                                     transforms, unravel_fn(zflat))
        for site in tr.values():
            markov_route.update((site.get("infer") or {})
                                .get("markov_route", {}))
        return u

    # the route a markov elimination took, which its marginal site carries
    # (enum.markov), read while the programs are traced; the executor
    # reports it
    potential_flat.markov_route = markov_route

    def constrain(zflat):
        return transform_fn(transforms, unravel_fn(zflat))

    # Opt-in fused GLM likelihood (infer={"potential": "glm"} on an observed
    # site): one kernel pass serves potential value AND gradient.  Verified
    # structurally at setup; any surprise falls back to the plain closure.
    # ``data_shards=S`` additionally requests the data-shard-aware fold
    # structure on the fused likelihood (see glm._make_sharded_nll); the
    # returned potential then carries a ``data_shards`` attribute the setup
    # layer turns into KernelSetup.data_axis.
    from .glm import maybe_fuse_glm_potential
    fused = maybe_fuse_glm_potential(model, model_args, model_kwargs,
                                     transforms, unravel_fn, flat_proto, tr,
                                     potential_flat, data_shards=data_shards)
    if fused is not None:
        potential_flat = fused

    return potential_flat, unravel_fn, transforms, constrain, tr, flat_proto


def find_valid_initial_params(rng_key, potential_fn, prototype, *,
                              init_strategy="uniform", radius=2.0,
                              max_tries=100, model=None, model_args=(),
                              model_kwargs=None, transforms=None):
    """Pure rejection search for a flat unconstrained init with finite
    potential and gradient.  Jit/vmap-safe: a batch of chains searches
    independently under one ``vmap``.

    Returns ``(z, potential, grad)``.
    """
    model_kwargs = model_kwargs or {}

    def _try(key):
        if init_strategy == "uniform":
            z = jax.random.uniform(key, jnp.shape(prototype), minval=-radius,
                                   maxval=radius)
        elif init_strategy == "prior":
            sub_tr = trace(seed(model, key)).get_trace(*model_args,
                                                       **model_kwargs)
            z = ravel_pytree({n: transforms[n].inv(sub_tr[n]["value"])
                              for n in transforms})[0]
        else:
            raise ValueError(f"unknown init strategy {init_strategy}")
        pe, grad = jax.value_and_grad(potential_fn)(z)
        ok = jnp.isfinite(pe) & jnp.all(jnp.isfinite(grad))
        return z, pe, grad, ok

    def cond_fn(state):
        i, _, _, _, ok, _ = state
        return (~ok) & (i < max_tries)

    def body_fn(state):
        i, _, _, _, _, key = state
        key, sub = jax.random.split(key)
        z, pe, grad, ok = _try(sub)
        return i + 1, z, pe, grad, ok, key

    key0, sub0 = jax.random.split(rng_key)
    z0, pe0, grad0, ok0 = _try(sub0)
    _, z, pe, grad, ok, _ = jax.lax.while_loop(
        cond_fn, body_fn,
        (jnp.zeros((), jnp.int32), z0, pe0, grad0, ok0, key0))
    return z, pe, grad


def initialize_model(rng_key, model, model_args=(), model_kwargs=None,
                     init_strategy="uniform", radius=2.0, max_tries=100):
    """Find valid initial unconstrained parameters with finite potential.

    Returns ``(init_params_flat, potential_fn_flat, unravel_fn, transforms,
    constrain, model_trace)``; everything downstream (integrator, NUTS tree)
    works on a single flat vector so mass-matrix algebra and the U-turn
    checkpointing arrays are simple ``(D,)``/``(depth, D)`` buffers.

    Compatibility wrapper over :func:`initialize_model_structure` (trace
    once) + :func:`find_valid_initial_params` (pure per-chain search).
    """
    (potential_flat, unravel_fn, transforms, constrain, tr,
     flat_proto) = initialize_model_structure(rng_key, model, model_args,
                                              model_kwargs)
    z, _, _ = find_valid_initial_params(
        rng_key, potential_flat, flat_proto, init_strategy=init_strategy,
        radius=radius, max_tries=max_tries, model=model,
        model_args=model_args, model_kwargs=model_kwargs,
        transforms=transforms)
    return z, potential_flat, unravel_fn, transforms, constrain, tr


# ---------------------------------------------------------------------------
# vmap-based predictive utilities (paper Fig. 1 / Listing 1)
# ---------------------------------------------------------------------------

class Predictive:
    """Vectorized prior/posterior predictive sampling.

    Composes the paper's three handlers per draw — ``seed`` (fresh key),
    ``substitute`` (pin latents to one posterior draw), ``trace`` (collect
    every site) — and batches the whole composition over posterior draws with
    ``vmap``, so models never carry manual batch dimensions:

    - *prior predictive*: ``Predictive(model, num_samples=N)`` — nothing is
      substituted, every site is a fresh draw.
    - *posterior predictive*: ``Predictive(model, posterior_samples=samples)``
      — latents are pinned per-draw, remaining (observed-site) distributions
      are sampled.

    ``posterior_samples`` leaves are ``(num_samples, ...)`` arrays
    (``batch_ndims=1``, e.g. ``MCMC.get_samples()``) or ``(num_chains,
    num_samples, ...)`` (``batch_ndims=2``, chain-grouped — outputs keep the
    chain axis).  ``return_sites`` restricts the output (deterministic sites,
    e.g. reparameterized originals, are legal targets); by default all sample
    and deterministic sites not substituted are returned.  ``parallel=False``
    falls back to a Python loop for models that cannot be vmapped.
    """

    def __init__(self, model, posterior_samples: Optional[Dict] = None,
                 num_samples: Optional[int] = None, return_sites=None,
                 parallel: bool = True, batch_ndims: int = 1):
        if batch_ndims not in (1, 2):
            raise ValueError(f"batch_ndims must be 1 or 2, got {batch_ndims}")
        self.model = model
        self.posterior_samples = posterior_samples or {}
        self.batch_ndims = batch_ndims
        self._batch_shape = None
        if self.posterior_samples:
            if num_samples is not None:
                raise ValueError(
                    "num_samples is determined by posterior_samples; passing "
                    "both is ambiguous")
            shapes = {jnp.shape(v)[:batch_ndims]
                      for v in self.posterior_samples.values()}
            if len(shapes) != 1:
                raise ValueError(
                    f"inconsistent posterior sample batch shapes: {shapes}")
            self._batch_shape = shapes.pop()
            num_samples = math.prod(self._batch_shape)
        elif num_samples is None:
            raise ValueError("need posterior_samples or num_samples")
        self.num_samples = num_samples
        self.return_sites = return_sites
        self.parallel = parallel

    def __call__(self, rng_key, *args, **kwargs):
        # flatten chain-grouped draws to one vmapped batch axis
        flat_samples = self.posterior_samples
        if self._batch_shape is not None and self.batch_ndims == 2:
            flat_samples = jax.tree_util.tree_map(
                lambda v: v.reshape((self.num_samples,)
                                    + v.shape[self.batch_ndims:]),
                flat_samples)

        def single(key, samples):
            m = substitute(seed(self.model, key), data=samples)
            tr = trace(m).get_trace(*args, **kwargs)
            if self.return_sites is not None:
                missing = [n for n in self.return_sites if n not in tr]
                if missing:
                    raise ValueError(
                        f"return_sites {missing} not found in model trace "
                        f"(available: {list(tr)})")
                sites = self.return_sites
            else:
                sites = [
                    n for n, s in tr.items()
                    if s["type"] in ("sample", "deterministic")
                    and n not in samples
                ]
            return {n: tr[n]["value"] for n in sites}

        keys = jax.random.split(rng_key, self.num_samples)
        if self.parallel:
            out = jax.vmap(single)(keys, flat_samples)
        else:
            outs = [single(k, jax.tree_util.tree_map(lambda v: v[i],
                                                     flat_samples))
                    for i, k in enumerate(keys)]
            out = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *outs)
        if self._batch_shape is not None and self.batch_ndims == 2:
            out = jax.tree_util.tree_map(
                lambda v: v.reshape(self._batch_shape + v.shape[1:]), out)
        return out


def log_likelihood(model, posterior_samples, *args, **kwargs):
    """Per-sample log likelihood of observed sites, vectorized with vmap.

    Models with enumerable discrete latents need those latents *pinned*:
    NUTS marginalizes them, so they are absent from ``get_samples()`` — pass
    :func:`~repro.core.infer.enum.infer_discrete` draws alongside the
    continuous ones (``{**samples, **discrete_samples}``).  A per-site
    marginalized likelihood is not well-defined once a discrete latent
    couples several sites, so an unpinned enumerable latent raises instead
    of crashing mid-trace.
    """
    def single(samples):
        from .enum import RequirePinnedDiscrete

        m = substitute(model, data=samples)
        with RequirePinnedDiscrete(what="log_likelihood"):
            tr = trace(m).get_trace(*args, **kwargs)
        return {
            name: site["fn"].log_prob(site["value"])
            for name, site in tr.items()
            if site["type"] == "sample" and site["is_observed"]
        }

    return jax.vmap(single)(posterior_samples)
