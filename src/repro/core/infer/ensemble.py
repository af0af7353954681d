"""Cross-chain ensemble inference: ChEES-HMC with lockstep trajectories.

The vmapped NUTS executor (paper Sec 3.2) pays a hidden tax in the
many-chain regime: every chain adapts alone (so warmup statistics never
benefit from the batch) and the per-chain U-turn ``while_loop``s run in
masked lockstep under ``vmap`` — each integrator step executes full tree
bookkeeping for *every* chain until the deepest tree finishes, so the batch
is as slow as its raggedest member.

ChEES-HMC (Hoffman, Radul & Sountsov, 2021), the cross-chain adaptive
sampler BlackJAX popularized, turns the chain axis from a liability into
the signal:

- **Lockstep trajectories** — every chain runs the *same* number of
  leapfrog steps per iteration.  The trajectory loop is one batch-uniform
  loop whose body is the dense, vmapped fused leapfrog
  (:func:`repro.kernels.ops.leapfrog_halfstep` through
  :func:`~repro.core.infer.hmc_util.velocity_verlet`); there is no
  per-chain raggedness and no tree bookkeeping, so device utilization is
  the integrator itself.
- **Halton jitter** — the shared trajectory length is multiplied by a
  quasi-random van-der-Corput factor in (0, 1) each iteration, restoring
  the ergodicity that a fixed length would lose (periodic orbits) while
  keeping all chains in lockstep (the jitter is per-iteration, not
  per-chain).
- **ChEES criterion** — the trajectory length is *learned*: Adam ascends
  the Change-in-the-Estimator-of-the-Expected-Square criterion
  ``E[(||z' - E z'||^2 - ||z - E z||^2)^2]`` whose gradient w.r.t. the
  trajectory length has the per-chain Monte-Carlo estimate
  ``h * (||z'c||^2 - ||zc||^2) * <z'c, v'>`` (``z'c``/``zc`` centered
  proposal/initial positions, ``v'`` the final velocity), Rao-
  Blackwellized by weighting each chain with its acceptance probability.
  More chains = lower-variance gradient = faster, stabler adaptation.
- **Cross-chain step size** — one dual-averaging run on the cross-chain
  mean acceptance probability (the *harmonic* mean, so the worst chains
  dominate and a batch-killing step size is corrected immediately;
  target 0.651, the known optimum for jittered-HMC) instead of C
  independent ones.
- **Pooled mass matrix** — a single Welford estimator folds in the whole
  chain-batch every middle-window iteration
  (:func:`~repro.core.infer.hmc_util.welford_batch` +
  :func:`~repro.core.infer.hmc_util.welford_combine`), so C chains × n
  draws feed one estimate.

The kernel implements the batch-aware contract
(:class:`~repro.core.infer.kernel_api.KernelSetup` with
``cross_chain=True``): ``init_fn`` consumes the full ``(num_chains,)`` key
array, ``sample_fn`` maps the whole ensemble state, and the unified
executor in :mod:`repro.core.infer.mcmc` drives it without the outer
per-chain ``vmap`` — chunked ``lax.scan``, ``chain_method="parallel"``
sharding and checkpoint/resume all work unchanged because the ensemble
adaptation state is just one more pytree in the chain state.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax, random

from ...obs.scopes import SCOPES, scoped
from .hmc_util import (
    DAState,
    IntegratorState,
    WelfordState,
    build_adaptation_schedule,
    chain_mean,
    chain_sum,
    chain_vmap,
    dual_averaging_init,
    dual_averaging_update,
    find_reasonable_step_size,
    kinetic_energy,
    momentum_sample,
    shared_draw,
    velocity,
    velocity_verlet,
    velocity_verlet_batch,
    welford_batch,
    welford_combine,
    welford_covariance,
    welford_init,
    window_predicates,
)
from .kernel_api import KernelSetup
from .util import find_valid_initial_params

# optimal acceptance rate for jittered-HMC (Hoffman et al. 2021), lower than
# NUTS's 0.8 because fixed-length trajectories tolerate coarser steps
DEFAULT_TARGET_ACCEPT = 0.651


class AdamState(NamedTuple):
    m: jnp.ndarray
    v: jnp.ndarray
    t: jnp.ndarray


def adam_init():
    return AdamState(jnp.zeros(()), jnp.zeros(()), jnp.zeros((), jnp.int32))


def adam_step(state: AdamState, grad, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam *ascent* step on a scalar; returns ``(delta, new_state)``."""
    t = state.t + 1
    m = b1 * state.m + (1 - b1) * grad
    v = b2 * state.v + (1 - b2) * grad * grad
    tf = t.astype(jnp.float32)
    m_hat = m / (1 - b1 ** tf)
    v_hat = v / (1 - b2 ** tf)
    return lr * m_hat / (jnp.sqrt(v_hat) + eps), AdamState(m, v, t)


def halton(t, bits=16):
    """Base-2 van der Corput radical inverse of ``t + 1`` — the standard
    quasi-random jitter sequence for ChEES trajectories.  Jittable, branch
    free, period ``2**bits``."""
    t = (t + 1).astype(jnp.uint32)
    out = jnp.zeros((), jnp.float32)
    for b in range(bits):
        out = out + ((t >> b) & 1).astype(jnp.float32) * (0.5 ** (b + 1))
    return out


class ChEESAdaptState(NamedTuple):
    """Shared (cross-chain, unbatched) adaptation state."""
    step_size: jnp.ndarray            # scalar, shared by every chain
    inverse_mass_matrix: jnp.ndarray  # (D,) diagonal, shared
    da_state: DAState                 # dual averaging on mean accept prob
    log_traj: jnp.ndarray             # log trajectory length (pre-jitter)
    adam_state: AdamState             # Adam moments for the ChEES ascent
    welford: WelfordState             # pooled (D,) estimator over all chains


class ChEESState(NamedTuple):
    """Full ensemble state: per-chain leaves lead with the chain axis C,
    everything in ``adapt_state`` plus ``i``/``rng_key`` is shared."""
    i: jnp.ndarray                    # scalar iteration counter
    z: jnp.ndarray                    # (C, D) flat unconstrained positions
    potential_energy: jnp.ndarray     # (C,)
    z_grad: jnp.ndarray               # (C, D)
    energy: jnp.ndarray               # (C,)
    num_steps: jnp.ndarray            # scalar — identical for all chains
    accept_prob: jnp.ndarray          # (C,)
    mean_accept_prob: jnp.ndarray     # (C,) running post-warmup mean
    diverging: jnp.ndarray            # (C,) bool
    adapt_state: ChEESAdaptState
    rng_key: jnp.ndarray              # one shared key, split per iteration


def _make_init_fn(potential_fn, dim, *, z_fixed, adapt_step_size, step_size0,
                  init_strategy, model, model_args, model_kwargs, transforms):
    """Batch init: per-chain position search (vmapped), then the shared
    scalars — one reasonable-step-size search seeded from chain 0, unit
    mass, trajectory length starting at 1.0 (the ChEES ascent owns it from
    there)."""

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
        shared, ss_key = random.split(shared)
        imm = jnp.ones(dim)
        if adapt_step_size:
            step_size = find_reasonable_step_size(
                potential_fn, imm, z[0], pe[0], grad[0], ss_key,
                init_step_size=step_size0)
        else:
            step_size = jnp.asarray(step_size0, jnp.float32)
        # trajectory starts at 1.0 — the natural scale once the pooled mass
        # matrix normalizes the geometry — and the ChEES ascent takes it
        # from there; starting from one leapfrog (= step size) wastes half
        # the warmup just climbing out
        adapt = ChEESAdaptState(
            step_size=step_size, inverse_mass_matrix=imm,
            da_state=dual_averaging_init(jnp.log(step_size)),
            log_traj=jnp.zeros(()), adam_state=adam_init(),
            welford=welford_init(dim))
        return ChEESState(
            i=jnp.zeros((), jnp.int32), z=z, potential_energy=pe,
            z_grad=grad, energy=pe,
            num_steps=jnp.zeros((), jnp.int32),
            accept_prob=jnp.zeros((num_chains,)),
            mean_accept_prob=jnp.zeros((num_chains,)),
            diverging=jnp.zeros((num_chains,), bool),
            adapt_state=adapt, rng_key=shared)

    return init_fn


def _make_sample_fn(potential_fn, num_warmup, schedule, *, adapt_step_size,
                    adapt_mass_matrix, adapt_trajectory, target_accept_prob,
                    learning_rate, max_num_steps, max_delta_energy=1000.0):
    """Pure ensemble transition ``ChEESState -> ChEESState``."""
    in_middle_window, window_end_is_middle = window_predicates(schedule)
    _, vv_update = velocity_verlet(potential_fn)
    vv_trajectory = velocity_verlet_batch(potential_fn)
    # static trajectory-length bounds: wide enough to be inert for any sane
    # posterior; tying them to the (oscillating) step size would let dual-
    # averaging transients yank the learned trajectory around via the clip
    log_traj_lo, log_traj_hi = jnp.log(1e-3), jnp.log(1e3)

    def integrate(step_size, imm, istate, num_steps):
        """One batch-uniform loop: every chain advances the same number of
        leapfrog steps.  The diagonal-mass path (always, for ChEES) walks
        the whole (C, D) ensemble through the chain-batched megakernel
        trajectory — merged interior kicks, no per-chain vmap layout churn;
        a dense mass matrix would fall back to the vmapped scalar step."""
        if imm.ndim == 1:
            return vv_trajectory(step_size, imm, istate, num_steps)
        step_all = chain_vmap(lambda s: vv_update(step_size, imm, s))
        return lax.fori_loop(0, num_steps, lambda _, s: step_all(s), istate)

    def chees_gradient(h, z0, z1, v1, weights):
        """Rao-Blackwellized MC estimate of d ChEES / d log-trajectory.

        ``z0``/``z1`` (C, D) initial/proposed positions, ``v1`` final
        velocities, ``weights`` per-chain acceptance probs (0 for divergent
        chains).  All reductions run over the (possibly sharded) chain axis.

        Divergent proposals carry zero weight *and* non-finite coordinates,
        so they are zeroed before any arithmetic — ``0 * inf`` would
        otherwise poison the whole estimate (and, through Adam's moments,
        every later iteration).
        """
        keep = (weights > 0)[:, None]
        z1 = jnp.where(keep, z1, 0.0)
        v1 = jnp.where(keep, v1, 0.0)
        w_sum = jnp.maximum(chain_sum(weights), 1e-10)
        w = weights[:, None]
        z0c = z0 - chain_sum(w * z0) / w_sum
        z1c = jnp.where(keep, z1 - chain_sum(w * z1) / w_sum, 0.0)
        per_chain = h * (jnp.sum(z1c * z1c, -1) - jnp.sum(z0c * z0c, -1)) \
            * jnp.sum(z1c * v1, -1)
        grad = chain_sum(weights * per_chain) / w_sum
        # every chain divergent (warmup's first steps): no information
        return jnp.where(jnp.isfinite(grad), grad, 0.0)

    @scoped(SCOPES.adapt)
    def adapt_update(adapt: ChEESAdaptState, t, z0, z1, v1, z_next,
                     accept_prob, diverging, h) -> ChEESAdaptState:
        # 1) one dual-averaging run on the cross-chain *harmonic* mean
        #    accept prob: dominated by the worst chains, so a step size that
        #    kills part of the batch is pushed down immediately instead of
        #    being averaged away by the chains that still accept
        if adapt_step_size:
            hmean = 1.0 / chain_mean(1.0 / jnp.clip(accept_prob, min=1e-10))
            da = dual_averaging_update(adapt.da_state,
                                       target_accept_prob - hmean)
            step_size = jnp.exp(da.x)
        else:
            da, step_size = adapt.da_state, adapt.step_size
        # 2) ChEES ascent on log trajectory length (divergent chains carry
        #    zero weight; leapfrog count is capped at max_num_steps)
        if adapt_trajectory:
            weights = jnp.where(diverging, 0.0, accept_prob)
            grad = chees_gradient(h, z0, z1, v1, weights)
            delta, adam = adam_step(adapt.adam_state, grad, learning_rate)
            log_traj = jnp.clip(adapt.log_traj + delta, log_traj_lo,
                                log_traj_hi)
        else:
            log_traj, adam = adapt.log_traj, adapt.adam_state

        def freeze_final(step_size):
            # last warmup step: sampling runs on the *averaged* DA iterate,
            # not wherever the last noisy update happened to land
            if adapt_step_size:
                return jnp.where(t == (num_warmup - 1), jnp.exp(da.x_avg),
                                 step_size)
            return step_size

        if not adapt_mass_matrix:
            return ChEESAdaptState(freeze_final(step_size),
                                   adapt.inverse_mass_matrix, da,
                                   log_traj, adam, adapt.welford)
        # 3) pooled Welford: fold the whole chain-batch in at once
        in_mid = in_middle_window(t)
        wf_new = welford_combine(adapt.welford, welford_batch(z_next))
        wf = jax.tree_util.tree_map(
            lambda new, old: jnp.where(in_mid, new, old), wf_new,
            adapt.welford)
        # 4) at middle-window ends: refresh the shared mass matrix from the
        #    pooled estimate, reset the estimator, restart dual averaging
        at_end = window_end_is_middle(t)

        def refresh(_):
            imm = welford_covariance(wf)
            wf_reset = jax.tree_util.tree_map(jnp.zeros_like, wf)
            if adapt_step_size:
                ss = jnp.exp(da.x_avg)
                da_new = dual_averaging_init(jnp.log(ss))
            else:
                ss, da_new = step_size, da
            # the refreshed metric rescales the dynamics: restart the
            # trajectory optimizer too, so stale Adam moments from the old
            # geometry don't fight the new gradient signal
            return imm, wf_reset, da_new, ss, adam_init()

        def keep(_):
            return adapt.inverse_mass_matrix, wf, da, step_size, adam

        imm, wf, da, step_size, adam = lax.cond(at_end, refresh, keep, None)
        return ChEESAdaptState(freeze_final(step_size), imm, da, log_traj,
                               adam, wf)

    def sample_fn(state: ChEESState) -> ChEESState:
        num_chains = state.z.shape[0]
        rng_key, key_mom, key_acc = random.split(state.rng_key, 3)
        mom_keys = random.split(key_mom, num_chains)
        acc_keys = random.split(key_acc, num_chains)
        adapt = state.adapt_state
        imm, step_size = adapt.inverse_mass_matrix, adapt.step_size

        # shared jittered trajectory: same leapfrog count for every chain
        h = halton(state.i)
        num_steps = jnp.clip(
            jnp.ceil(h * jnp.exp(adapt.log_traj) / step_size)
            .astype(jnp.int32), 1, max_num_steps)

        r = shared_draw(
            jax.vmap(lambda k: momentum_sample(k, imm, state.z.dtype))(
                mom_keys))
        energy_cur = state.potential_energy \
            + jax.vmap(lambda rr: kinetic_energy(imm, rr))(r)
        end = integrate(step_size, imm,
                        IntegratorState(state.z, r, state.potential_energy,
                                        state.z_grad),
                        num_steps)
        energy_new = end.potential_energy \
            + jax.vmap(lambda rr: kinetic_energy(imm, rr))(end.r)
        delta = jnp.where(jnp.isnan(energy_new), jnp.inf,
                          energy_new - energy_cur)
        accept_prob = jnp.clip(jnp.exp(-delta), max=1.0)
        diverging = delta > max_delta_energy
        accept = shared_draw(jax.vmap(random.uniform)(acc_keys)) \
            < accept_prob
        acc2 = accept[:, None]
        z = jnp.where(acc2, end.z, state.z)
        pe = jnp.where(accept, end.potential_energy, state.potential_energy)
        grad = jnp.where(acc2, end.z_grad, state.z_grad)
        energy = jnp.where(accept, energy_new, energy_cur)

        v_end = jax.vmap(lambda rr: velocity(imm, rr))(end.r)
        t = state.i
        in_warmup = t < num_warmup
        new_adapt = lax.cond(
            in_warmup,
            lambda _: adapt_update(adapt, t, state.z, end.z, v_end, z,
                                   accept_prob, diverging, h),
            lambda _: adapt, None)
        i = t + 1
        n_post = jnp.maximum(i - num_warmup, 1)
        mean_ap = jnp.where(
            in_warmup, accept_prob,
            state.mean_accept_prob + (accept_prob - state.mean_accept_prob)
            / n_post)
        return ChEESState(i, z, pe, grad, energy, num_steps, accept_prob,
                          mean_ap, diverging, new_adapt, rng_key)

    return sample_fn


def _collect_fn(state: ChEESState):
    """Per-draw outputs; shared scalars broadcast over the chain axis so
    every collected leaf leads with (C,) like the per-chain kernels."""
    num_chains = state.z.shape[0]
    return {
        "z": state.z,
        "potential_energy": state.potential_energy,
        # per-chain Hamiltonian at the accepted proposal: what divergence
        # forensics records per divergent transition (repro.obs.divergences)
        "energy": state.energy,
        "num_steps": jnp.broadcast_to(state.num_steps, (num_chains,)),
        "accept_prob": state.accept_prob,
        "diverging": state.diverging,
        "step_size": jnp.broadcast_to(state.adapt_state.step_size,
                                      (num_chains,)),
        "trajectory_length": jnp.broadcast_to(
            jnp.exp(state.adapt_state.log_traj), (num_chains,)),
    }


def _metrics_fn(state: ChEESState):
    """Metrics stream under the cross-chain contract: pooled ensemble
    quantities stay scalars (the executor records them once per draw, not
    per chain), per-chain quantities are ``(C,)``.  Unlike ``_collect_fn``
    there is no broadcasting — the stream records what the ensemble
    actually adapts: one shared step size, one trajectory length, one
    pooled mass-matrix trace."""
    adapt = state.adapt_state
    return {
        "step_size": adapt.step_size,                        # scalar, pooled
        "trajectory_length": jnp.exp(adapt.log_traj),        # scalar, pooled
        "num_steps": state.num_steps,                        # scalar, shared
        "mass_trace": jnp.sum(adapt.inverse_mass_matrix),    # scalar, pooled
        "accept_prob": state.accept_prob,                    # (C,)
        "diverging": state.diverging,                        # (C,)
        "energy": state.energy,                              # (C,)
    }


def chees_setup(rng_key, num_warmup, *, model=None, potential_fn=None,
                init_params=None, model_args=(), model_kwargs=None,
                step_size=1.0, adapt_step_size=True, adapt_mass_matrix=True,
                adapt_trajectory=True,
                target_accept_prob=DEFAULT_TARGET_ACCEPT,
                learning_rate=0.05, max_num_steps=256,
                init_strategy="uniform", data_shards=None) -> KernelSetup:
    """Build the static batch-aware :class:`KernelSetup` for ChEES-HMC.

    Same model-tracing preamble as :func:`~repro.core.infer.hmc.hmc_setup`;
    the returned setup has ``cross_chain=True`` so the unified executor
    drives ``init_fn``/``sample_fn`` over the whole ``(num_chains, ...)``
    batch without an outer ``vmap``.
    """
    from .hmc import flat_model_ingredients, resolve_data_axis
    model_kwargs = model_kwargs or {}
    (potential_flat, unravel, constrain, transforms, dim,
     z_fixed) = flat_model_ingredients(
        rng_key, model=model, potential_fn=potential_fn,
        init_params=init_params, model_args=model_args,
        model_kwargs=model_kwargs, data_shards=data_shards)
    data_axis = resolve_data_axis(potential_flat, data_shards)

    schedule = build_adaptation_schedule(num_warmup)
    init_fn = _make_init_fn(
        potential_flat, dim, z_fixed=z_fixed,
        adapt_step_size=adapt_step_size, step_size0=step_size,
        init_strategy=init_strategy, model=model, model_args=model_args,
        model_kwargs=model_kwargs, transforms=transforms)
    sample_fn = _make_sample_fn(
        potential_flat, num_warmup, schedule,
        adapt_step_size=adapt_step_size,
        adapt_mass_matrix=adapt_mass_matrix,
        adapt_trajectory=adapt_trajectory,
        target_accept_prob=target_accept_prob,
        learning_rate=learning_rate, max_num_steps=max_num_steps)
    return KernelSetup(
        init_fn=init_fn, sample_fn=sample_fn, collect_fn=_collect_fn,
        potential_fn=potential_flat, unravel_fn=unravel,
        constrain_fn=constrain, num_warmup=int(num_warmup), algo="ChEES",
        adapt_schedule=tuple((int(s), int(e)) for (s, e) in schedule),
        cross_chain=True, data_axis=data_axis, metrics_fn=_metrics_fn)


def chees_init(rng_key, num_warmup, num_chains, **kwargs):
    """Functional entry point: ``-> (ChEESState, KernelSetup)``."""
    setup = chees_setup(rng_key, num_warmup, **kwargs)
    return setup.init_fn(random.split(rng_key, num_chains)), setup


class ChEES:
    """ChEES-HMC ensemble kernel (batch-aware ``SamplerKernel``).

    Drop-in for ``NUTS`` in :class:`~repro.core.infer.mcmc.MCMC` — pass more
    chains and the warmup pools its statistics across them while every
    trajectory runs in lockstep.  Requires a batched ``chain_method``
    (``"vectorized"`` or ``"parallel"``); cross-chain adaptation is
    meaningless one chain at a time, though ``num_chains=1`` itself is fine.
    """

    def __init__(self, model=None, potential_fn=None, step_size=1.0,
                 adapt_step_size=True, adapt_mass_matrix=True,
                 adapt_trajectory=True,
                 target_accept_prob=DEFAULT_TARGET_ACCEPT,
                 learning_rate=0.05, max_num_steps=256,
                 init_strategy="uniform", data_shards=None):
        self.model = model
        self.potential_fn = potential_fn
        self._step_size = step_size
        self._adapt_step_size = adapt_step_size
        self._adapt_mass_matrix = adapt_mass_matrix
        self._adapt_trajectory = adapt_trajectory
        self._target = target_accept_prob
        self._learning_rate = learning_rate
        self._max_num_steps = max_num_steps
        self._init_strategy = init_strategy
        self._data_shards = data_shards
        self._setup: Optional[KernelSetup] = None

    def setup(self, rng_key, num_warmup, init_params=None, model_args=(),
              model_kwargs=None) -> KernelSetup:
        setup = chees_setup(
            rng_key, num_warmup, model=self.model,
            potential_fn=self.potential_fn if self.model is None else None,
            init_params=init_params, model_args=model_args,
            model_kwargs=model_kwargs, step_size=self._step_size,
            adapt_step_size=self._adapt_step_size,
            adapt_mass_matrix=self._adapt_mass_matrix,
            adapt_trajectory=self._adapt_trajectory,
            target_accept_prob=self._target,
            learning_rate=self._learning_rate,
            max_num_steps=self._max_num_steps,
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
