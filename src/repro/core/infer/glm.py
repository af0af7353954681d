"""Opt-in fused GLM potential: route a model's dominant likelihood term
through the single-pass ``ops.glm_potential_grad_slab`` kernel.

A model opts in by marking its observed site::

    pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y,
              infer={"potential": "glm"})

At setup time (:func:`~repro.core.infer.util.initialize_model_structure`,
one-time Python-level work) the site's linear predictor is extracted by
differentiating the traced predictor at zero — ``offset = predictor(0)``,
``X = jacfwd(predictor)(0)`` — and *verified* affine at two random probes;
the fused potential is then

    potential(z) = potential_energy(block(model, hide=[site]), z) + nll(z)

i.e. the exact prior + transform log-det through the normal machinery and
the likelihood through the fused kernel, wrapped in ``jax.custom_vjp`` so
the backward pass is the O(d) residual product the kernel already computed
— instead of XLA's n-vector reverse chains.  X, y and the offset are laid
out once as a lane-dense design slab, and under a chain ``vmap`` one kernel
call serves every chain (:func:`_slab_value_and_grad`).  Any structural
surprise (non-affine or untraceable predictor, probs-parametrized Bernoulli,
non-constant Normal scale, site-level scale/mask, enumeration marks) falls
back to the plain potential with a warning: the fusion is an optimization,
never a semantics change.  An error raised by the kernel itself (or by its
lowering) is not a structural surprise and propagates.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from ...kernels import ops
from ..handlers import block, seed, substitute, trace


def _unwrap(fn):
    while hasattr(fn, "base_dist"):
        fn = fn.base_dist
    return fn


def _fallback(name, reason):
    warnings.warn(
        f"site '{name}' requested infer={{'potential': 'glm'}} but {reason}"
        "; falling back to the plain potential.", stacklevel=3)
    return None


def _make_sharded_nll(x, y, offset, scale, family, data_shards):
    """The data-shard-aware likelihood term: S static per-shard partials
    combined with the ``hmc_util.chain_sum`` pairwise-tree fold.

    The fold structure (``S = data_shards``) is baked in at setup time and
    is identical in every chain method — what varies per compiled program
    is only *where* the partials evaluate.  Without an active inference
    mesh the S per-shard (value, grad) pairs are computed locally and
    folded; with one (``distributed.sharding.use_inference_mesh``, entered
    by the executor at trace time), each device computes its ``S / Sd``
    local partials under ``shard_map``, ``all_gather``s the stacked rows in
    shard order, and runs the *same* fold — slices and elementwise adds
    only, so the result is bit-identical under every data-axis layout.

    Gradients are wrapped in ``jax.custom_vjp`` with the backward pass
    ``ct * folded_grad``: the per-shard kernel already produces the shard
    gradient in its single pass, and folding those rows explicitly keeps
    the gradient on the same bit-deterministic path — reverse-mode AD
    *through* a ``shard_map``/``all_gather`` combine re-associates the
    accumulation and breaks bit-identity.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ...kernels.glm_potential import glm_potential_partials
    from .hmc_util import chain_sum
    S = int(data_shards)

    def _value_and_grad(zflat):
        from repro.distributed.sharding import active_data_mesh
        active = active_data_mesh()
        if active is not None:
            mesh, axis = active
            sd = mesh.shape[axis]
            if S % sd != 0:
                from ..errors import ReproValueError
                raise ReproValueError(
                    f"potential has data_shards={S} but the active mesh "
                    f"data axis has {sd} devices; the shard structure must "
                    "split evenly across the mesh (pick data_shards as a "
                    "multiple of the data-axis size).", code="RPL303")

            def body(x_loc, y_loc, off_loc, z):
                lv, lg = lax.optimization_barrier(glm_potential_partials(
                    x_loc, y_loc, z, off_loc, scale, family,
                    data_shards=S // sd))
                # tiled gather preserves device (= shard) order, so the
                # stacked rows match the local path's reshape order exactly
                av = lax.all_gather(lv, axis, axis=0, tiled=True)
                ag = lax.all_gather(lg, axis, axis=0, tiled=True)
                return chain_sum(av), chain_sum(ag)

            out = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(axis), P()),
                out_specs=(P(), P()), check_vma=False)(x, y, offset, zflat)
        else:
            vals, grads = lax.optimization_barrier(glm_potential_partials(
                x, y, zflat, offset, scale, family, data_shards=S))
            out = chain_sum(vals), chain_sum(grads)
        # identical fusion boundary in both branches: the shard_map edge
        # already stops XLA from fusing (e.g. FMA-contracting) the fold's
        # final add into downstream consumers, so the local path must stop
        # it too or the two graphs round differently at the seam
        return lax.optimization_barrier(out)

    @jax.custom_vjp
    def nll(zflat):
        return _value_and_grad(zflat)[0]

    def nll_fwd(zflat):
        val, grad = _value_and_grad(zflat)
        return val, grad

    def nll_bwd(grad, ct):
        return (ct * grad,)

    nll.defvjp(nll_fwd, nll_bwd)
    return nll


def _slab_value_and_grad(scale, family, route):
    """``(slab, w) -> (nll, grad)`` over C coefficient rows, whose
    ``vmap`` is one kernel call for every chain of the batch.

    Under a ``vmap`` of the coefficients with the slab shared, the rule of
    this ``jax.custom_vmap`` hands ``ops.glm_potential_grad_slab`` all the
    rows at once, so the kernel's grid walks the n-tiles only and each slab
    tile is read from HBM once for all chains.  A batched slab (data that
    differs per chain) keeps one pass per chain, through the op's own
    batching.  The rule records the route it took, and over how many
    chains, in ``route`` while the program is traced: nothing is counted
    per step.
    """
    from jax.custom_batching import custom_vmap

    @custom_vmap
    def value_and_grad(slab, w):
        return ops.glm_potential_grad_slab(slab, w, scale, family)

    @value_and_grad.def_vmap
    def _batched(axis_size, in_batched, slab, w):
        slab_batched, w_batched = in_batched
        if slab_batched:
            route.update(route="per_chain", chains=axis_size)
            out = jax.vmap(value_and_grad.fun,
                           in_axes=(0, 0 if w_batched else None))(slab, w)
            return out, (True, True)
        # the slab is shared, so the rule runs because w is batched;
        # recorded before the call: under nested vmaps the outermost rule,
        # which sees every chain, runs inside this call and writes last
        route.update(route="batched", chains=axis_size * w.shape[1])
        nll, grad = value_and_grad(slab, w.reshape(-1, w.shape[-1]))
        return (nll.reshape(w.shape[:2]), grad.reshape(w.shape)), (True, True)

    return value_and_grad


def _make_slab_nll(slab, scale, family, route):
    """The likelihood term over the design slab (``glm_slab``: the
    design matrix transposed, y and the offset in its padding rows), built
    once at setup and the only copy of the data the term keeps.  Its
    gradient is wrapped in ``jax.custom_vjp`` with the backward pass
    ``ct * grad``: the kernel produces the gradient in the same pass."""
    value_and_grad = _slab_value_and_grad(scale, family, route)

    def _value_and_grad(zflat):
        nll, grad = value_and_grad(slab, zflat[None])
        return nll[0], grad[0]

    @jax.custom_vjp
    def nll(zflat):
        return _value_and_grad(zflat)[0]

    def nll_bwd(grad, ct):
        return (ct * grad,)

    nll.defvjp(_value_and_grad, nll_bwd)
    return nll


def maybe_fuse_glm_potential(model, model_args, model_kwargs, transforms,
                             unravel_fn, flat_proto, model_trace,
                             potential_flat, data_shards=None):
    """Return a fused flat potential function, or None to keep the plain
    one.  ``model`` is the (config_enumerate-wrapped) model whose trace is
    ``model_trace``; verification runs on concrete arrays at setup time.

    ``data_shards=S`` additionally gives the likelihood term a static
    S-shard fold structure (see :func:`_make_sharded_nll`) and marks the
    returned potential with ``potential.data_shards = S`` so the executor
    and RPL204 can see it is shard-aware.

    Extraction and verification run their matmuls in full f32: a TPU's
    default precision multiplies f32 in one bf16 pass, which would read
    the design matrix back rounded and fail the affinity probe.  The
    returned potential is traced later, at the caller's precision."""
    with jax.default_matmul_precision("highest"):
        return _fuse_glm_potential(model, model_args, model_kwargs,
                                   transforms, unravel_fn, flat_proto,
                                   model_trace, potential_flat, data_shards)


def _fuse_glm_potential(model, model_args, model_kwargs, transforms,
                        unravel_fn, flat_proto, model_trace, potential_flat,
                        data_shards):
    marked = [name for name, site in model_trace.items()
              if site["type"] == "sample" and site["is_observed"]
              and site["infer"].get("potential") == "glm"]
    if not marked:
        return None
    if len(marked) > 1:
        return _fallback(marked[0], f"{len(marked)} sites are marked "
                         "(only a single GLM likelihood can be fused)")
    name = marked[0]
    site = model_trace[name]
    if site["scale"] is not None or site["mask"] is not None:
        return _fallback(name, "the site carries a scale/mask modifier "
                         "(subsampled plate or mask handler)")
    if any(s["infer"].get("enumerate") == "parallel"
           for s in model_trace.values() if s["type"] == "sample"):
        return _fallback(name, "the model has enumerated discrete latents")
    fn = _unwrap(site["fn"])
    kind = type(fn).__name__
    if kind == "Bernoulli":
        if fn.logits is None:
            return _fallback(name, "the Bernoulli is probs-parametrized "
                             "(fusion needs the logits parametrization)")
        family, read = "bernoulli_logit", lambda d: _unwrap(d).logits
    elif kind == "Normal":
        family, read = "normal", lambda d: _unwrap(d).loc
    else:
        return _fallback(name, f"its distribution is {kind} (supported: "
                         "Bernoulli(logits=...), Normal)")
    y = jnp.asarray(site["value"])
    if y.ndim != 1:
        return _fallback(name, f"observations have shape {y.shape} "
                         "(fusion expects a flat (n,) vector)")

    model_kwargs = model_kwargs or {}
    key = jax.random.PRNGKey(0)

    def predictor(zflat):
        uncon = unravel_fn(zflat)
        params = {n: t(uncon[n]) for n, t in transforms.items()}
        with block():
            tr = trace(substitute(seed(model, key), data=params)) \
                .get_trace(*model_args, **model_kwargs)
        return read(tr[name]["fn"]).astype(jnp.float32), tr[name]["fn"]

    try:
        zeros = jnp.zeros_like(flat_proto)
        offset, fn0 = predictor(zeros)
        x = jax.jacfwd(lambda z: predictor(z)[0])(zeros)   # (n, D)
        scale = None
        if family == "normal":
            s = jnp.asarray(_unwrap(fn0).scale)
            if s.size > 1 and not bool(jnp.all(s == s.reshape(-1)[0])):
                return _fallback(name, "the Normal scale varies across "
                                 "observations (kernel takes one scalar)")
            scale = s.reshape(-1)[0]
        # verify affinity (and scale constancy) at two random probes
        for k in jax.random.split(jax.random.PRNGKey(1), 2):
            z = jax.random.normal(k, flat_proto.shape) * 0.5
            pred, fnz = predictor(z)
            lin = x @ z + offset
            tol = 1e-4 * (1.0 + float(jnp.max(jnp.abs(lin))))
            if not bool(jnp.all(jnp.abs(pred - lin) <= tol)):
                return _fallback(name, "its predictor is not affine in the "
                                 "unconstrained latents")
            if family == "normal":
                sz = jnp.asarray(_unwrap(fnz).scale)
                if not bool(jnp.all(sz == s)):
                    return _fallback(name, "the Normal scale depends on "
                                     "the latents")
    except (jax.errors.JAXTypeError, jax.errors.JAXIndexError) as e:
        # the predictor is not a traceable function of the latents (Python
        # control flow on a traced value, a leaked tracer, ...)
        return _fallback(name, f"predictor extraction failed "
                         f"({type(e).__name__}: {e})")

    if data_shards is not None:
        S = int(data_shards)
        if S < 1:
            return _fallback(name, f"data_shards={data_shards} is not a "
                             "positive shard count")
        if y.shape[0] % S != 0:
            return _fallback(name, f"n={y.shape[0]} observations do not "
                             f"split into data_shards={S} equal shards")
        nll = _make_sharded_nll(x, y, offset, scale, family, S)
    else:
        from ...kernels.glm_potential import glm_slab
        route = {}
        nll = _make_slab_nll(glm_slab(x, y, offset), scale, family, route)

    from .util import potential_energy
    prior_model = block(model, hide=[name])

    def fused_potential(zflat):
        prior = potential_energy(prior_model, model_args, model_kwargs,
                                 transforms, unravel_fn(zflat))
        return prior + nll(zflat)

    # end-to-end verification: fused == plain at a probe point.  An error
    # raised here comes from the kernel or its lowering and propagates.
    zp = jax.random.normal(jax.random.PRNGKey(2), flat_proto.shape) * 0.5
    a, b = fused_potential(zp), potential_flat(zp)
    if not bool(jnp.abs(a - b) <= 1e-4 * (1.0 + jnp.abs(b))):
        return _fallback(name, f"fused potential mismatch ({a} vs {b})")
    if data_shards is not None:
        # marker the setup layer / RPL204 use to tell shard-aware potentials
        # from monolithic ones (see kernel_api.KernelSetup.data_axis)
        fused_potential.data_shards = int(data_shards)
    else:
        # which route the chain-batched gradient took (_slab_value_and_grad);
        # the executor reports it in the run manifest
        fused_potential.glm_route = route
    return fused_potential
