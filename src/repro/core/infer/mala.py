"""Batched MALA and random-walk Metropolis through the unified executor.

The cheap high-volume scenario class: when a posterior is well-conditioned
(or the budget is thousands of chains rather than long trajectories),
one-gradient-per-draw Langevin proposals — or zero-gradient random-walk
proposals — beat HMC on raw draws/sec.  Both samplers here implement the
batch-aware :class:`~repro.core.infer.kernel_api.KernelSetup` contract
(``cross_chain=True``): the whole (C, D) ensemble moves through the
chain-batched :func:`repro.kernels.ops.mala_step` proposal kernel in one
pass, and warmup adaptation pools across chains exactly like ChEES —
one dual-averaging run on the cross-chain harmonic-mean acceptance
probability and one pooled Welford estimator feeding the shared diagonal
preconditioner.  The unchanged executor supplies chunked ``lax.scan``,
``chain_method="parallel"`` sharding and bit-identical checkpoint/resume.

MALA proposal (preconditioner ``M^{-1}`` diagonal, step ``eps``):

    z' = z - eps * M^{-1} grad U(z) + sqrt(2 eps M^{-1}) xi

with the exact Metropolis-Hastings correction (the forward density comes
free from the drawn ``xi``; the reverse one re-uses the gradient at ``z'``
that the next iteration needs anyway).  RWM drops the drift term — the
proposal is symmetric, so the correction reduces to the potential
difference.  Optimal acceptance targets differ: 0.574 for MALA and 0.234
for RWM (Roberts & Rosenthal), and divergence means a non-finite proposal
potential (always rejected).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax, random

from ...kernels import ops
from ...obs.scopes import SCOPES, scoped
from .hmc_util import (
    DAState,
    WelfordState,
    build_adaptation_schedule,
    chain_mean,
    chain_vmap,
    dual_averaging_init,
    dual_averaging_update,
    shared_draw,
    welford_batch,
    welford_combine,
    welford_covariance,
    welford_init,
    window_predicates,
)
from .kernel_api import KernelSetup
from .util import find_valid_initial_params

# optimal acceptance rates (Roberts & Rosenthal): MALA scales like d^{-1/3}
# at 0.574, random-walk like d^{-1} at 0.234
DEFAULT_TARGET_ACCEPT = {"MALA": 0.574, "RWM": 0.234}


class MRWAdaptState(NamedTuple):
    """Shared (cross-chain, unbatched) adaptation state."""
    step_size: jnp.ndarray            # scalar, shared by every chain
    inverse_mass_matrix: jnp.ndarray  # (D,) diagonal preconditioner, shared
    da_state: DAState                 # dual averaging on mean accept prob
    welford: WelfordState             # pooled (D,) estimator over all chains


class MRWState(NamedTuple):
    """Full ensemble state: per-chain leaves lead with the chain axis C,
    ``adapt_state``/``i``/``rng_key`` are shared.  ``z_grad`` is the drift
    gradient for MALA and stays all-zeros for RWM (one pytree shape serves
    both, so checkpoint/resume and the executor treat them identically)."""
    i: jnp.ndarray                    # scalar iteration counter
    z: jnp.ndarray                    # (C, D) flat unconstrained positions
    potential_energy: jnp.ndarray     # (C,)
    z_grad: jnp.ndarray               # (C, D)
    accept_prob: jnp.ndarray          # (C,)
    mean_accept_prob: jnp.ndarray     # (C,) running post-warmup mean
    diverging: jnp.ndarray            # (C,) bool
    adapt_state: MRWAdaptState
    rng_key: jnp.ndarray              # one shared key, split per iteration


def _make_init_fn(potential_fn, dim, *, z_fixed, step_size0, init_strategy,
                  model, model_args, model_kwargs, transforms):
    """Batch init: per-chain position search (vmapped), then the shared
    scalars — initial step size as given (dual averaging owns it from the
    first warmup iteration), unit preconditioner."""

    def one_chain(key):
        init_key, _ = random.split(key)
        if z_fixed is not None:
            z = z_fixed
            pe, grad = jax.value_and_grad(potential_fn)(z)
            return z, pe, grad
        return find_valid_initial_params(
            init_key, potential_fn, jnp.zeros((dim,)),
            init_strategy=init_strategy, model=model, model_args=model_args,
            model_kwargs=model_kwargs, transforms=transforms)

    def init_fn(keys):
        z, pe, grad = chain_vmap(one_chain)(keys)
        num_chains = z.shape[0]
        _, shared = random.split(keys[0])
        step_size = jnp.asarray(step_size0, jnp.float32)
        adapt = MRWAdaptState(
            step_size=step_size, inverse_mass_matrix=jnp.ones(dim),
            da_state=dual_averaging_init(jnp.log(step_size)),
            welford=welford_init(dim))
        return MRWState(
            i=jnp.zeros((), jnp.int32), z=z, potential_energy=pe,
            z_grad=grad,
            accept_prob=jnp.zeros((num_chains,)),
            mean_accept_prob=jnp.zeros((num_chains,)),
            diverging=jnp.zeros((num_chains,), bool),
            adapt_state=adapt, rng_key=shared)

    return init_fn


def _make_sample_fn(potential_fn, num_warmup, schedule, algo, *,
                    adapt_step_size, adapt_mass_matrix, target_accept_prob):
    """Pure ensemble transition ``MRWState -> MRWState``."""
    in_middle_window, window_end_is_middle = window_predicates(schedule)
    pe_and_grad = scoped(SCOPES.potential)(
        chain_vmap(jax.value_and_grad(potential_fn)))
    use_grad = algo == "MALA"

    @scoped(SCOPES.adapt)
    def adapt_update(adapt: MRWAdaptState, t, z_next,
                     accept_prob) -> MRWAdaptState:
        # one dual-averaging run on the cross-chain *harmonic* mean accept
        # prob (worst chains dominate), exactly as on the ChEES path
        if adapt_step_size:
            hmean = 1.0 / chain_mean(1.0 / jnp.clip(accept_prob, min=1e-10))
            da = dual_averaging_update(adapt.da_state,
                                       target_accept_prob - hmean)
            step_size = jnp.exp(da.x)
        else:
            da, step_size = adapt.da_state, adapt.step_size

        def freeze_final(step_size):
            if adapt_step_size:
                return jnp.where(t == (num_warmup - 1), jnp.exp(da.x_avg),
                                 step_size)
            return step_size

        if not adapt_mass_matrix:
            return MRWAdaptState(freeze_final(step_size),
                                 adapt.inverse_mass_matrix, da,
                                 adapt.welford)
        in_mid = in_middle_window(t)
        wf_new = welford_combine(adapt.welford, welford_batch(z_next))
        wf = jax.tree_util.tree_map(
            lambda new, old: jnp.where(in_mid, new, old), wf_new,
            adapt.welford)
        at_end = window_end_is_middle(t)

        def refresh(_):
            imm = welford_covariance(wf)
            wf_reset = jax.tree_util.tree_map(jnp.zeros_like, wf)
            if adapt_step_size:
                ss = jnp.exp(da.x_avg)
                da_new = dual_averaging_init(jnp.log(ss))
            else:
                ss, da_new = step_size, da
            return imm, wf_reset, da_new, ss

        def keep(_):
            return adapt.inverse_mass_matrix, wf, da, step_size

        imm, wf, da, step_size = lax.cond(at_end, refresh, keep, None)
        return MRWAdaptState(freeze_final(step_size), imm, da, wf)

    def sample_fn(state: MRWState) -> MRWState:
        num_chains = state.z.shape[0]
        rng_key, key_noise, key_acc = random.split(state.rng_key, 3)
        acc_keys = random.split(key_acc, num_chains)
        adapt = state.adapt_state
        minv, eps = adapt.inverse_mass_matrix, adapt.step_size

        noise = shared_draw(random.normal(key_noise, state.z.shape))
        z_new = ops.mala_step(state.z, state.z_grad if use_grad else None,
                              noise, minv, eps)
        pe_new, grad_new = pe_and_grad(z_new)
        log_accept = state.potential_energy - pe_new
        if use_grad:
            # forward density from the drawn noise; reverse one re-uses the
            # gradient at z' that the accepted next iteration needs anyway:
            #   xi_rev = (z - z' + eps*minv*grad') / sqrt(2*eps*minv)
            logq_fwd = -0.5 * jnp.sum(noise * noise, -1)
            diff = state.z - z_new + eps * minv * grad_new
            logq_rev = -0.25 / eps * jnp.sum(diff * diff / minv, -1)
            log_accept = log_accept + logq_rev - logq_fwd
        diverging = ~jnp.isfinite(pe_new)
        log_accept = jnp.where(diverging, -jnp.inf, log_accept)
        accept_prob = jnp.clip(jnp.exp(log_accept), max=1.0)
        accept = shared_draw(jax.vmap(random.uniform)(acc_keys)) \
            < accept_prob
        acc2 = accept[:, None]
        z = jnp.where(acc2, z_new, state.z)
        pe = jnp.where(accept, pe_new, state.potential_energy)
        grad = jnp.where(acc2, grad_new, state.z_grad) if use_grad \
            else state.z_grad

        t = state.i
        in_warmup = t < num_warmup
        new_adapt = lax.cond(
            in_warmup,
            lambda _: adapt_update(adapt, t, z, accept_prob),
            lambda _: adapt, None)
        i = t + 1
        n_post = jnp.maximum(i - num_warmup, 1)
        mean_ap = jnp.where(
            in_warmup, accept_prob,
            state.mean_accept_prob + (accept_prob - state.mean_accept_prob)
            / n_post)
        return MRWState(i, z, pe, grad, accept_prob, mean_ap, diverging,
                        new_adapt, rng_key)

    return sample_fn


def _collect_fn(state: MRWState):
    """Per-draw outputs; shared scalars broadcast over the chain axis so
    every collected leaf leads with (C,) like the per-chain kernels."""
    num_chains = state.z.shape[0]
    return {
        "z": state.z,
        "potential_energy": state.potential_energy,
        "num_steps": jnp.ones((num_chains,), jnp.int32),
        "accept_prob": state.accept_prob,
        "diverging": state.diverging,
        "step_size": jnp.broadcast_to(state.adapt_state.step_size,
                                      (num_chains,)),
    }


def _metrics_fn(state: MRWState):
    """Metrics stream under the cross-chain contract: the pooled step size
    and preconditioner trace stay scalars (one value per draw — that is
    what the ensemble actually adapts), per-chain diagnostics are (C,)."""
    adapt = state.adapt_state
    return {
        "step_size": adapt.step_size,                       # scalar, pooled
        "mass_trace": jnp.sum(adapt.inverse_mass_matrix),   # scalar, pooled
        "accept_prob": state.accept_prob,                   # (C,)
        "diverging": state.diverging,                       # (C,)
        "potential_energy": state.potential_energy,         # (C,)
    }


def mrw_setup(rng_key, num_warmup, algo, *, model=None, potential_fn=None,
              init_params=None, model_args=(), model_kwargs=None,
              step_size=0.1, adapt_step_size=True, adapt_mass_matrix=True,
              target_accept_prob=None,
              init_strategy="uniform", data_shards=None) -> KernelSetup:
    """Build the static batch-aware :class:`KernelSetup` for MALA or RWM.

    Same model-tracing preamble as :func:`~repro.core.infer.hmc.hmc_setup`;
    ``cross_chain=True`` so the unified executor drives the whole
    ``(num_chains, ...)`` ensemble without an outer ``vmap``.
    """
    from .hmc import flat_model_ingredients, resolve_data_axis
    if algo not in ("MALA", "RWM"):
        raise ValueError(f"algo must be 'MALA' or 'RWM', got {algo!r}")
    if target_accept_prob is None:
        target_accept_prob = DEFAULT_TARGET_ACCEPT[algo]
    model_kwargs = model_kwargs or {}
    (potential_flat, unravel, constrain, transforms, dim,
     z_fixed) = flat_model_ingredients(
        rng_key, model=model, potential_fn=potential_fn,
        init_params=init_params, model_args=model_args,
        model_kwargs=model_kwargs, data_shards=data_shards)
    data_axis = resolve_data_axis(potential_flat, data_shards)

    schedule = build_adaptation_schedule(num_warmup)
    init_fn = _make_init_fn(
        potential_flat, dim, z_fixed=z_fixed, step_size0=step_size,
        init_strategy=init_strategy, model=model, model_args=model_args,
        model_kwargs=model_kwargs, transforms=transforms)
    sample_fn = _make_sample_fn(
        potential_flat, num_warmup, schedule, algo,
        adapt_step_size=adapt_step_size,
        adapt_mass_matrix=adapt_mass_matrix,
        target_accept_prob=target_accept_prob)
    return KernelSetup(
        init_fn=init_fn, sample_fn=sample_fn, collect_fn=_collect_fn,
        potential_fn=potential_flat, unravel_fn=unravel,
        constrain_fn=constrain, num_warmup=int(num_warmup), algo=algo,
        adapt_schedule=tuple((int(s), int(e)) for (s, e) in schedule),
        cross_chain=True, data_axis=data_axis, metrics_fn=_metrics_fn)


class _MRWKernel:
    """Shared class shim over :func:`mrw_setup` (``SamplerKernel`` API)."""

    _algo = ""

    def __init__(self, model=None, potential_fn=None, step_size=0.1,
                 adapt_step_size=True, adapt_mass_matrix=True,
                 target_accept_prob=None, init_strategy="uniform",
                 data_shards=None):
        self.model = model
        self.potential_fn = potential_fn
        self._step_size = step_size
        self._adapt_step_size = adapt_step_size
        self._adapt_mass_matrix = adapt_mass_matrix
        self._target = target_accept_prob
        self._init_strategy = init_strategy
        self._data_shards = data_shards
        self._setup: Optional[KernelSetup] = None

    def setup(self, rng_key, num_warmup, init_params=None, model_args=(),
              model_kwargs=None) -> KernelSetup:
        setup = mrw_setup(
            rng_key, num_warmup, self._algo, model=self.model,
            potential_fn=self.potential_fn if self.model is None else None,
            init_params=init_params, model_args=model_args,
            model_kwargs=model_kwargs, step_size=self._step_size,
            adapt_step_size=self._adapt_step_size,
            adapt_mass_matrix=self._adapt_mass_matrix,
            target_accept_prob=self._target,
            init_strategy=self._init_strategy,
            data_shards=self._data_shards)
        self._setup = setup
        return setup

    def init(self, rng_key, num_warmup, init_params=None, model_args=(),
             model_kwargs=None, num_chains=1):
        """Build the setup and initialize a ``num_chains``-wide ensemble."""
        setup = self.setup(rng_key, num_warmup, init_params=init_params,
                           model_args=model_args, model_kwargs=model_kwargs)
        return setup.init_fn(random.split(rng_key, num_chains))


class MALA(_MRWKernel):
    """Metropolis-adjusted Langevin ensemble kernel (batch-aware).

    Drop-in for ``NUTS``/``ChEES`` in :class:`~repro.core.infer.mcmc.MCMC`
    with a batched ``chain_method``: one gradient per draw, all chains
    stepped by one (C, D) proposal kernel, warmup pooled across chains.
    """

    _algo = "MALA"


class RWM(_MRWKernel):
    """Random-walk Metropolis ensemble kernel (batch-aware).

    Zero gradients per draw — the cheapest possible transition, for
    well-conditioned posteriors at very high chain counts.  Same pooled
    cross-chain warmup and executor contract as :class:`MALA`.
    """

    _algo = "RWM"
