"""HMC and NUTS as pure functional sampler kernels.

The functional core is :func:`hmc_setup`: it performs the one-time
Python-level work (tracing the model, building the flat-space potential and
the Stan-style windowed adaptation schedule) and returns a static
:class:`~repro.core.infer.kernel_api.KernelSetup` whose ``init_fn`` /
``sample_fn`` are *pure* — a whole chain (warmup adaptation included)
compiles to a single XLA program (``lax.scan`` over ``sample_fn``), and a
batch of chains is just ``vmap`` over ``init_fn``/``sample_fn``.  This is
the end-to-end-JIT property the paper demonstrates (Sec. 3.1), now with the
state/closure split BlackJAX showed unlocks composition at scale.

The classic class-based API (``HMC``/``NUTS`` with ``.init(state)`` /
``.sample(state)``) survives as a thin wrapper over the functional core —
see ``docs/inference.md`` for the migration note.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ...obs.scopes import SCOPES, scoped
from .hmc_util import (
    DAState,
    IntegratorState,
    WelfordState,
    build_adaptation_schedule,
    build_tree,
    chain_vmap,
    dual_averaging_init,
    dual_averaging_update,
    find_reasonable_step_size,
    kinetic_energy,
    momentum_sample,
    velocity_verlet,
    welford_covariance,
    welford_init,
    welford_pool,
    welford_update,
    window_predicates,
)
from .kernel_api import KernelSetup
from .util import (
    find_valid_initial_params,
    initialize_model_structure,
)


class AdaptState(NamedTuple):
    step_size: jnp.ndarray
    inverse_mass_matrix: jnp.ndarray
    da_state: DAState
    welford: WelfordState
    window_idx: jnp.ndarray


class HMCState(NamedTuple):
    i: jnp.ndarray
    z: jnp.ndarray                  # flat unconstrained position
    potential_energy: jnp.ndarray
    z_grad: jnp.ndarray
    energy: jnp.ndarray
    num_steps: jnp.ndarray          # leapfrog steps this iteration
    accept_prob: jnp.ndarray
    mean_accept_prob: jnp.ndarray
    diverging: jnp.ndarray
    adapt_state: AdaptState
    rng_key: jnp.ndarray


# ---------------------------------------------------------------------------
# pure closures
# ---------------------------------------------------------------------------

def _make_init_fn(potential_fn, dim, num_warmup, *, z_fixed, adapt_step_size,
                  dense_mass, step_size0, init_strategy, model, model_args,
                  model_kwargs, transforms):
    """Pure per-chain state init: initial-point search (unless ``z_fixed``),
    reasonable-step-size search, adaptation bootstrap.  Vmappable."""

    def init_fn(rng_key):
        rng_key, init_key, ss_key = jax.random.split(rng_key, 3)
        if z_fixed is not None:
            z = z_fixed
            pe, grad = jax.value_and_grad(potential_fn)(z)
        else:
            z, pe, grad = find_valid_initial_params(
                init_key, potential_fn, jnp.zeros((dim,)),
                init_strategy=init_strategy, model=model,
                model_args=model_args, model_kwargs=model_kwargs,
                transforms=transforms)

        imm = (jnp.ones(dim) if not dense_mass else jnp.eye(dim))
        if adapt_step_size:
            step_size = find_reasonable_step_size(
                potential_fn, imm, z, pe, grad, ss_key,
                init_step_size=step_size0)
        else:
            step_size = jnp.asarray(step_size0, jnp.float32)

        da = dual_averaging_init(jnp.log(step_size))
        wf = welford_init(dim, diagonal=not dense_mass)
        adapt = AdaptState(step_size, imm, da, wf, jnp.zeros((), jnp.int32))
        return HMCState(
            i=jnp.zeros((), jnp.int32), z=z, potential_energy=pe,
            z_grad=grad, energy=pe, num_steps=jnp.zeros((), jnp.int32),
            accept_prob=jnp.zeros(()), mean_accept_prob=jnp.zeros(()),
            diverging=jnp.zeros((), bool), adapt_state=adapt,
            rng_key=rng_key)

    return init_fn


def _make_sample_fn(potential_fn, num_warmup, schedule, *, algo,
                    trajectory_length, adapt_step_size, adapt_mass_matrix,
                    dense_mass, target_accept_prob, max_tree_depth,
                    pooled_mass=False):
    """Pure transition ``HMCState -> HMCState`` with every static ingredient
    (closures, schedule tables) captured here, never read off an object.

    ``pooled_mass=True`` defers the mass-matrix refresh: the per-chain
    Welford accumulator still collects draws inside middle windows and dual
    averaging still restarts at window ends, but the inverse mass matrix is
    left untouched (and the accumulator is not reset) so a batch-aware
    wrapper can pool the accumulators *across* chains at the window boundary
    — see :func:`hmc_setup` with ``cross_chain_adapt=True``.
    """
    in_middle_window, window_end_is_middle = window_predicates(schedule)

    @scoped(SCOPES.adapt)
    def adapt_update(state: HMCState, accept_prob) -> AdaptState:
        adapt = state.adapt_state
        t = state.i
        # 1) dual averaging on log step size
        if adapt_step_size:
            da = dual_averaging_update(adapt.da_state,
                                       target_accept_prob - accept_prob)
            step_size = jnp.exp(da.x)
        else:
            da, step_size = adapt.da_state, adapt.step_size
        if not adapt_mass_matrix:
            if adapt_step_size:
                # same end-of-warmup freeze as the mass-adapting path:
                # sampling runs on the averaged DA iterate, not the last
                # noisy update
                step_size = jnp.where(t == (num_warmup - 1),
                                      jnp.exp(da.x_avg), step_size)
            return AdaptState(step_size, adapt.inverse_mass_matrix, da,
                              adapt.welford, adapt.window_idx)
        # 2) welford accumulation inside middle windows
        in_mid = in_middle_window(t)
        wf = jax.tree_util.tree_map(
            lambda new, old: jnp.where(in_mid, new, old),
            welford_update(adapt.welford, state.z), adapt.welford)
        # 3) at the end of a middle window: refresh the mass matrix,
        #    reset welford, restart dual averaging from the averaged iterate
        at_end = window_end_is_middle(t)

        def refresh(_):
            if pooled_mass:
                # cross-chain mode: the batch wrapper pools the per-chain
                # accumulators and swaps in the shared estimate right after
                # this step; here only dual averaging restarts
                imm, wf_new = adapt.inverse_mass_matrix, wf
            else:
                imm = welford_covariance(wf)
                wf_new = welford_init(state.z.shape[0],
                                      diagonal=not dense_mass)
            if adapt_step_size:
                ss = jnp.exp(da.x_avg)
                da_new = dual_averaging_init(jnp.log(ss))
            else:
                ss, da_new = step_size, da
            return imm, wf_new, da_new, ss

        def keep(_):
            return adapt.inverse_mass_matrix, wf, da, step_size

        imm, wf, da, step_size = lax.cond(at_end, refresh, keep, None)
        # final step of warmup: freeze averaged step size
        if adapt_step_size:
            is_last = t == (num_warmup - 1)
            step_size = jnp.where(is_last, jnp.exp(da.x_avg), step_size)
        return AdaptState(step_size, imm, da, wf,
                          adapt.window_idx + at_end.astype(jnp.int32))

    def num_leapfrog(step_size):
        return jnp.clip(
            jnp.ceil(trajectory_length / step_size).astype(jnp.int32),
            1, 1024)

    def sample_fn(state: HMCState) -> HMCState:
        rng_key, key_mom, key_tr, key_accept = jax.random.split(
            state.rng_key, 4)
        adapt = state.adapt_state
        imm, step_size = adapt.inverse_mass_matrix, adapt.step_size
        r = momentum_sample(key_mom, imm, state.z.dtype)
        energy_cur = state.potential_energy + kinetic_energy(imm, r)
        _, vv_update = velocity_verlet(potential_fn)

        if algo == "NUTS":
            tree = build_tree(vv_update, imm, step_size, key_tr,
                              IntegratorState(state.z, r,
                                              state.potential_energy,
                                              state.z_grad),
                              max_tree_depth=max_tree_depth)
            accept_prob = tree.sum_accept_probs / jnp.maximum(
                tree.num_proposals, 1)
            z, pe, grad = tree.z_proposal, tree.z_proposal_pe, \
                tree.z_proposal_grad
            energy = tree.z_proposal_energy
            num_steps = tree.num_proposals
            diverging = tree.diverging
        else:
            n_steps = num_leapfrog(step_size)

            def body(i, s):
                return vv_update(step_size, imm, s)

            nxt = lax.fori_loop(
                0, n_steps, body,
                IntegratorState(state.z, r, state.potential_energy,
                                state.z_grad))
            energy_new = nxt.potential_energy + kinetic_energy(imm, nxt.r)
            delta = jnp.where(jnp.isnan(energy_new), jnp.inf,
                              energy_new - energy_cur)
            accept_prob = jnp.clip(jnp.exp(-delta), max=1.0)
            accept = jax.random.uniform(key_accept) < accept_prob
            z, pe, grad, energy = jax.tree_util.tree_map(
                lambda a, b: jnp.where(accept, a, b),
                (nxt.z, nxt.potential_energy, nxt.z_grad, energy_new),
                (state.z, state.potential_energy, state.z_grad, energy_cur))
            num_steps = n_steps
            diverging = delta > 1000.0

        in_warmup = state.i < num_warmup
        new_adapt = lax.cond(in_warmup,
                             lambda _: adapt_update(state, accept_prob),
                             lambda _: adapt, None)
        i = state.i + 1
        # running mean accept prob over the post-warmup phase
        n_post = jnp.maximum(i - num_warmup, 1)
        mean_ap = jnp.where(
            in_warmup, accept_prob,
            state.mean_accept_prob + (accept_prob - state.mean_accept_prob)
            / n_post)
        return HMCState(i, z, pe, grad, energy, num_steps, accept_prob,
                        mean_ap, diverging, new_adapt, rng_key)

    return sample_fn


def _collect_fn(state: HMCState):
    """Per-draw outputs the executor records during the sampling phase.
    ``energy`` (the Hamiltonian at the accepted proposal) rides along so
    divergence forensics can record the blow-up magnitude per divergent
    transition without re-evaluating anything (``repro.obs.divergences``).
    """
    return {
        "z": state.z,
        "potential_energy": state.potential_energy,
        "energy": state.energy,
        "num_steps": state.num_steps,
        "accept_prob": state.accept_prob,
        "diverging": state.diverging,
        "step_size": state.adapt_state.step_size,
    }


def _metrics_fn(state: HMCState):
    """Metrics stream (``KernelSetup.metrics_fn``): all scalars, per the
    per-chain contract — the executor's vmap adds the chain axis and the
    chunk scan the draw axis.  Reads state only (never the rng key), so it
    can ride the collect path without perturbing the sample stream.
    ``num_steps`` is the trajectory's leapfrog count (2^depth-ish for NUTS —
    the tree-depth signal); ``mass_trace`` tracks the adapted (inverse)
    mass matrix through warmup windows."""
    imm = state.adapt_state.inverse_mass_matrix
    mass_trace = jnp.trace(imm) if imm.ndim == 2 else jnp.sum(imm)
    return {
        "step_size": state.adapt_state.step_size,
        "accept_prob": state.accept_prob,
        "diverging": state.diverging,
        "num_steps": state.num_steps,
        "energy": state.energy,
        "mass_trace": mass_trace,
    }


def flat_model_ingredients(rng_key, *, model=None, potential_fn=None,
                           init_params=None, model_args=(),
                           model_kwargs=None, data_shards=None):
    """One-time Python-level work shared by every gradient-based kernel:
    trace the model (or accept a raw ``potential_fn``) and return
    ``(potential_flat, unravel, constrain, transforms, dim, z_fixed)``
    operating on the flat unconstrained vector.

    ``data_shards=S`` requests a shard-aware potential (S-shard static fold;
    see :mod:`repro.core.infer.glm`) — only honoured in model mode for a
    model whose likelihood fuses; the setup layer raises RPL302 when the
    request cannot be satisfied."""
    model_kwargs = model_kwargs or {}
    transforms = None
    if model is not None:
        (potential_flat, unravel, transforms, constrain, tr,
         flat_proto) = initialize_model_structure(rng_key, model, model_args,
                                                  model_kwargs,
                                                  data_shards=data_shards)
        dim = flat_proto.shape[0]
        z_fixed = None
        if init_params is not None:
            from jax.flatten_util import ravel_pytree
            z_fixed = ravel_pytree({k: transforms[k].inv(v)
                                    for k, v in init_params.items()})[0]
    else:
        if potential_fn is None:
            raise ValueError("need a model or a potential_fn")
        if init_params is None:
            raise ValueError("potential_fn mode requires init_params")
        from jax.flatten_util import ravel_pytree
        z_fixed, unravel = ravel_pytree(init_params)
        potential_flat, constrain = potential_fn, unravel
        dim = z_fixed.shape[0]
    return potential_flat, unravel, constrain, transforms, dim, z_fixed


def resolve_data_axis(potential_flat, data_shards):
    """``KernelSetup.data_axis`` for a potential built with ``data_shards``.

    ``data_shards=None`` -> ``None`` (monolithic potential).  Otherwise the
    potential MUST carry the shard-aware fold marker set by
    ``glm.maybe_fuse_glm_potential`` — a raw ``potential_fn`` or a model
    whose likelihood fell back to the plain path has no per-shard structure,
    and silently annotating it would let the executor activate a data mesh
    under a potential that evaluates every row on every device (or worse,
    double-counts the likelihood).  Raises RPL302 instead.
    """
    if data_shards is None:
        return None
    marker = getattr(potential_flat, "data_shards", None)
    if marker is None:
        from ..errors import ReproValueError
        raise ReproValueError(
            f"data_shards={data_shards} was requested but no shard-aware "
            "potential was built: the model's likelihood did not fuse "
            "(watch for the fallback warning), or a raw potential_fn was "
            "passed.  Data-sharded inference needs the fused GLM potential "
            "(mark the observed site with infer={'potential': 'glm'}).",
            code="RPL302")
    if int(marker) != int(data_shards):
        from ..errors import ReproValueError
        raise ReproValueError(
            f"potential carries data_shards={marker} but the kernel was "
            f"asked for data_shards={data_shards}.", code="RPL302")
    from ...distributed.sharding import DATA_AXIS
    return DATA_AXIS


def hmc_setup(rng_key, num_warmup, *, model=None, potential_fn=None,
              init_params=None, model_args=(), model_kwargs=None,
              algo="HMC", step_size=1.0, trajectory_length=2 * jnp.pi,
              adapt_step_size=True, adapt_mass_matrix=True, dense_mass=False,
              target_accept_prob=0.8, max_tree_depth=10,
              init_strategy="uniform",
              cross_chain_adapt=False, data_shards=None) -> KernelSetup:
    """Build the static :class:`KernelSetup` for HMC (``algo="HMC"``) or
    NUTS (``algo="NUTS"``).

    This is the only impure-ish step (it traces ``model`` once to discover
    latent sites); everything it returns is a pure closure over the results.
    ``rng_key`` only seeds the structure-discovery trace — per-chain
    randomness comes from the key passed to ``init_fn``.

    ``cross_chain_adapt=True`` opts the warmup into the batch-aware kernel
    contract (``KernelSetup.cross_chain``): the transition itself stays
    per-chain (vmapped inside the returned ``sample_fn``), but at every
    middle-window boundary the per-chain Welford accumulators are pooled
    (:func:`~repro.core.infer.hmc_util.welford_pool`) and the resulting
    shared mass-matrix estimate — C chains × window draws instead of one
    chain's worth — is broadcast back into every chain.  Step-size dual
    averaging remains per-chain.
    """
    model_kwargs = model_kwargs or {}
    (potential_flat, unravel, constrain, transforms, dim,
     z_fixed) = flat_model_ingredients(
        rng_key, model=model, potential_fn=potential_fn,
        init_params=init_params, model_args=model_args,
        model_kwargs=model_kwargs, data_shards=data_shards)
    data_axis = resolve_data_axis(potential_flat, data_shards)

    schedule = build_adaptation_schedule(num_warmup)
    init_fn = _make_init_fn(
        potential_flat, dim, num_warmup, z_fixed=z_fixed,
        adapt_step_size=adapt_step_size, dense_mass=dense_mass,
        step_size0=step_size, init_strategy=init_strategy, model=model,
        model_args=model_args, model_kwargs=model_kwargs,
        transforms=transforms)
    sample_fn = _make_sample_fn(
        potential_flat, num_warmup, schedule, algo=algo,
        trajectory_length=trajectory_length, adapt_step_size=adapt_step_size,
        adapt_mass_matrix=adapt_mass_matrix, dense_mass=dense_mass,
        target_accept_prob=target_accept_prob,
        max_tree_depth=max_tree_depth,
        pooled_mass=cross_chain_adapt and adapt_mass_matrix)
    if cross_chain_adapt:
        init_fn, sample_fn = _cross_chain_wrap(
            init_fn, sample_fn, schedule, num_warmup,
            pool_mass=adapt_mass_matrix)
    # cross-chain-adapted HMC drives the *batched* state, so the metrics fn
    # is vmapped the same way the transition is: every leaf comes out (C,),
    # which is the valid per-chain shape under the cross_chain contract
    metrics_fn = (chain_vmap(_metrics_fn) if cross_chain_adapt
                  else _metrics_fn)
    return KernelSetup(
        init_fn=init_fn, sample_fn=sample_fn, collect_fn=_collect_fn,
        potential_fn=potential_flat, unravel_fn=unravel,
        constrain_fn=constrain, num_warmup=int(num_warmup), algo=algo,
        adapt_schedule=tuple((int(s), int(e)) for (s, e) in schedule),
        cross_chain=cross_chain_adapt, data_axis=data_axis,
        metrics_fn=metrics_fn)


def _cross_chain_wrap(chain_init_fn, chain_sample_fn, schedule, num_warmup,
                      *, pool_mass):
    """Lift a per-chain HMC/NUTS kernel to the batch-aware contract with
    pooled cross-chain mass adaptation.

    The wrapped ``sample_fn`` runs the vmapped per-chain transition (whose
    ``pooled_mass=True`` adaptation accumulates but never refreshes), then —
    at middle-window ends, detectable outside the vmap because every chain
    shares the same iteration counter — pools the per-chain Welford states,
    broadcasts the shared covariance into each chain's inverse mass matrix,
    and resets the accumulators.
    """
    _, window_end_is_middle = window_predicates(schedule)

    def init_fn(keys):
        return chain_vmap(chain_init_fn)(keys)

    def sample_fn(states: HMCState) -> HMCState:
        states = chain_vmap(chain_sample_fn)(states)
        if not pool_mass:
            return states
        # iteration just completed (i was incremented by the transition)
        t = states.i[0] - 1
        at_end = window_end_is_middle(t) & (t < num_warmup)

        @scoped(SCOPES.adapt)
        def refresh(states):
            adapt = states.adapt_state
            pooled = welford_pool(adapt.welford)
            imm = welford_covariance(pooled)
            num_chains = states.i.shape[0]
            imm_b = jnp.broadcast_to(imm, (num_chains,) + imm.shape)
            wf_reset = jax.tree_util.tree_map(jnp.zeros_like, adapt.welford)
            return states._replace(adapt_state=adapt._replace(
                inverse_mass_matrix=imm_b, welford=wf_reset))

        return lax.cond(at_end, refresh, lambda s: s, states)

    return init_fn, sample_fn


def nuts_setup(rng_key, num_warmup, **kwargs) -> KernelSetup:
    """:func:`hmc_setup` with the iterative No-U-Turn transition."""
    kwargs.pop("algo", None)
    kwargs.pop("trajectory_length", None)
    return hmc_setup(rng_key, num_warmup, algo="NUTS", **kwargs)


def hmc_init(rng_key, num_warmup, **kwargs):
    """Functional entry point: ``-> (HMCState, KernelSetup)``."""
    setup = hmc_setup(rng_key, num_warmup, **kwargs)
    return setup.init_fn(rng_key), setup


def nuts_init(rng_key, num_warmup, **kwargs):
    """Functional entry point: ``-> (HMCState, KernelSetup)``."""
    setup = nuts_setup(rng_key, num_warmup, **kwargs)
    return setup.init_fn(rng_key), setup


# ---------------------------------------------------------------------------
# class-based API: thin wrappers over the functional core
# ---------------------------------------------------------------------------

class HMC:
    """Vanilla HMC with fixed/jittered trajectory length.

    Thin wrapper: ``init`` builds a :class:`KernelSetup` (stored for the
    legacy single-argument ``sample``) and returns the initial state;
    ``setup`` exposes the pure functional core directly.
    """

    def __init__(self, model=None, potential_fn=None, step_size=1.0,
                 trajectory_length=2 * jnp.pi, adapt_step_size=True,
                 adapt_mass_matrix=True, dense_mass=False,
                 target_accept_prob=0.8, init_strategy="uniform",
                 cross_chain_adapt=False, data_shards=None):
        self.model = model
        self.potential_fn = potential_fn
        self._step_size = step_size
        self._trajectory_length = trajectory_length
        self._adapt_step_size = adapt_step_size
        self._adapt_mass_matrix = adapt_mass_matrix
        self._dense_mass = dense_mass
        self._target = target_accept_prob
        self._init_strategy = init_strategy
        self._cross_chain_adapt = cross_chain_adapt
        self._data_shards = data_shards
        self._algo = "HMC"
        self._max_tree_depth = 10
        self._setup: Optional[KernelSetup] = None

    # -- functional core -----------------------------------------------------
    def setup(self, rng_key, num_warmup, init_params=None, model_args=(),
              model_kwargs=None) -> KernelSetup:
        """Build the static setup for this kernel's configuration."""
        return hmc_setup(
            rng_key, num_warmup, model=self.model,
            potential_fn=self.potential_fn if self.model is None else None,
            init_params=init_params, model_args=model_args,
            model_kwargs=model_kwargs, algo=self._algo,
            step_size=self._step_size,
            trajectory_length=self._trajectory_length,
            adapt_step_size=self._adapt_step_size,
            adapt_mass_matrix=self._adapt_mass_matrix,
            dense_mass=self._dense_mass,
            target_accept_prob=self._target,
            max_tree_depth=self._max_tree_depth,
            init_strategy=self._init_strategy,
            cross_chain_adapt=self._cross_chain_adapt,
            data_shards=self._data_shards)

    # -- legacy API ----------------------------------------------------------
    def init(self, rng_key, num_warmup, init_params=None, model_args=(),
             model_kwargs=None):
        setup = self.setup(rng_key, num_warmup, init_params=init_params,
                           model_args=model_args, model_kwargs=model_kwargs)
        self._bind_setup(setup)
        return setup.init_fn(rng_key)

    def sample(self, state: HMCState) -> HMCState:
        if self._setup is None:
            raise RuntimeError(
                "call init() before the legacy one-argument sample(); for "
                "the functional path use kernel_api.sample(setup, state) "
                "with the setup returned by setup()")
        return self._setup.sample_fn(state)

    def _bind_setup(self, setup: KernelSetup):
        self._setup = setup
        # legacy attribute surface (read by older callers / tests)
        if self.model is not None:
            self.potential_fn = setup.potential_fn
        self._unravel_fn = setup.unravel_fn
        self._constrain_fn = setup.constrain_fn
        self._num_warmup = setup.num_warmup
        self._schedule = list(setup.adapt_schedule)

    # convenience: map flat unconstrained vector to constrained dict
    def constrain(self, z):
        return self._constrain_fn(z)


class NUTS(HMC):
    """No-U-Turn Sampler with the paper's iterative, fully-jittable tree."""

    def __init__(self, model=None, potential_fn=None, step_size=1.0,
                 adapt_step_size=True, adapt_mass_matrix=True,
                 dense_mass=False, target_accept_prob=0.8,
                 max_tree_depth=10, init_strategy="uniform",
                 cross_chain_adapt=False, data_shards=None):
        super().__init__(model=model, potential_fn=potential_fn,
                         step_size=step_size, adapt_step_size=adapt_step_size,
                         adapt_mass_matrix=adapt_mass_matrix,
                         dense_mass=dense_mass,
                         target_accept_prob=target_accept_prob,
                         init_strategy=init_strategy,
                         cross_chain_adapt=cross_chain_adapt,
                         data_shards=data_shards)
        self._algo = "NUTS"
        self._max_tree_depth = max_tree_depth
