"""HMC/NUTS numerical core.

The centerpiece is :func:`iterative_build_subtree` — the paper's Algorithm 2:
an *iterative* reformulation of the recursive BuildTree procedure that keeps
the O(log N) memory profile (via bit-count-indexed momentum checkpoints) while
being expressible with ``lax.while_loop``, so one entire NUTS trajectory —
LeapFrog gradients included — JIT-compiles end-to-end under XLA.

Everything operates on *flat* (D,) position/momentum vectors; callers ravel
their latent pytrees once at the kernel boundary.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ...obs.scopes import SCOPES, scoped


# ---------------------------------------------------------------------------
# integrator
# ---------------------------------------------------------------------------

class IntegratorState(NamedTuple):
    z: jnp.ndarray          # position, flat (D,)
    r: jnp.ndarray          # momentum, flat (D,)
    potential_energy: jnp.ndarray
    z_grad: jnp.ndarray     # dU/dz, flat (D,)


def velocity(inverse_mass_matrix, r):
    if inverse_mass_matrix.ndim == 1:
        return inverse_mass_matrix * r
    return inverse_mass_matrix @ r


def kinetic_energy(inverse_mass_matrix, r):
    return 0.5 * jnp.dot(r, velocity(inverse_mass_matrix, r))


def momentum_sample(rng_key, inverse_mass_matrix, dtype=jnp.float32):
    """Draw r ~ N(0, M) where M = imm^{-1}."""
    d = inverse_mass_matrix.shape[-1]
    eps = jax.random.normal(rng_key, (d,), dtype)
    if inverse_mass_matrix.ndim == 1:
        return eps / jnp.sqrt(inverse_mass_matrix)
    # imm = L L^T  =>  M = L^{-T} L^{-1},  r = L^{-T} eps  ~  N(0, M)
    L = jnp.linalg.cholesky(inverse_mass_matrix)
    return jax.scipy.linalg.solve_triangular(L, eps, lower=True, trans=1)


def velocity_verlet(potential_fn: Callable, kinetic_grad=velocity):
    """Single leapfrog (velocity Verlet) step closure.

    The diagonal-mass path routes the memory-bound half of the step —
    momentum half-kick + position drift — through the fused
    :func:`repro.kernels.ops.leapfrog_halfstep` (one HBM pass under Pallas;
    a bit-identical jnp reference elsewhere).  Dense mass matrices and
    custom ``kinetic_grad`` closures fall back to the two-pass form.
    """
    pe_and_grad = scoped(SCOPES.potential)(jax.value_and_grad(potential_fn))
    fuse_ok = kinetic_grad is velocity

    def init(z):
        pe, grad = pe_and_grad(z)
        return pe, grad

    @scoped(SCOPES.integrator)
    def update(step_size, inverse_mass_matrix, state: IntegratorState):
        z, r, _, z_grad = state
        if fuse_ok and inverse_mass_matrix.ndim == 1:
            from repro.kernels import ops
            z, r = ops.leapfrog_halfstep(z, r, z_grad, inverse_mass_matrix,
                                         step_size)
        else:
            r = r - 0.5 * step_size * z_grad
            z = z + step_size * kinetic_grad(inverse_mass_matrix, r)
        pe, z_grad = pe_and_grad(z)
        r = r - 0.5 * step_size * z_grad
        return IntegratorState(z, r, pe, z_grad)

    return init, update


def velocity_verlet_batch(potential_fn):
    """Chain-batched leapfrog trajectory over a (C, D) ensemble with merged
    interior kicks (diagonal mass only).

    A length-L leapfrog trajectory applies the kicks
    ``(eps/2) g_0, eps g_1, ..., eps g_{L-1}, (eps/2) g_L`` — the two
    adjacent half-kicks between interior steps are mathematically one full
    kick, so fusing them saves one (C, D) memory pass per interior step on
    top of what the chain-batched :func:`repro.kernels.ops.
    leapfrog_halfstep_batch` megakernel already saves over per-chain
    ``vmap``.  Exact leapfrog: same positions, same L gradient evaluations.

    Returns ``trajectory(step_size, inverse_mass_matrix, state, num_steps)``
    mapping a (C,)-batched :class:`IntegratorState` through ``num_steps``
    (traced, >= 1) leapfrog steps.
    """
    from repro.kernels import ops

    pe_and_grad = scoped(SCOPES.potential)(
        chain_vmap(jax.value_and_grad(potential_fn)))

    @scoped(SCOPES.integrator)
    def trajectory(step_size, inverse_mass_matrix, state: IntegratorState,
                   num_steps):
        def kick_drift(s, kick):
            z, r = ops.leapfrog_halfstep_batch(s.z, s.r, s.z_grad,
                                               inverse_mass_matrix,
                                               step_size, kick)
            pe, z_grad = pe_and_grad(z)
            return IntegratorState(z, r, pe, z_grad)

        s = kick_drift(state, 0.5)                  # opening half-kick
        s = lax.fori_loop(0, num_steps - 1,
                          lambda _, st: kick_drift(st, 1.0), s)
        r = s.r - 0.5 * step_size * s.z_grad        # closing half-kick
        return IntegratorState(s.z, r, s.potential_energy, s.z_grad)

    return trajectory


# ---------------------------------------------------------------------------
# dual averaging (Nesterov 2009 / Hoffman & Gelman 2014)
# ---------------------------------------------------------------------------

class DAState(NamedTuple):
    x: jnp.ndarray       # log step size
    x_avg: jnp.ndarray   # averaged iterate
    g_avg: jnp.ndarray   # averaged gradient (target - accept)
    t: jnp.ndarray
    prox_center: jnp.ndarray


def dual_averaging_init(x0):
    x0 = jnp.asarray(x0, jnp.float32)
    return DAState(x0, jnp.zeros_like(x0), jnp.zeros_like(x0),
                   jnp.zeros((), jnp.int32), x0 + jnp.log(10.0))


def dual_averaging_update(state: DAState, g, t0=10, kappa=0.75, gamma=0.05):
    x, x_avg, g_avg, t, prox_center = state
    t = t + 1
    tf = t.astype(jnp.float32)
    g_avg = (1 - 1 / (tf + t0)) * g_avg + g / (tf + t0)
    x = prox_center - jnp.sqrt(tf) / gamma * g_avg
    weight = tf ** (-kappa)
    x_avg = (1 - weight) * x_avg + weight * x
    return DAState(x, x_avg, g_avg, t, prox_center)


# ---------------------------------------------------------------------------
# Welford online (co)variance
# ---------------------------------------------------------------------------

class WelfordState(NamedTuple):
    mean: jnp.ndarray
    m2: jnp.ndarray
    n: jnp.ndarray


def welford_init(size, diagonal=True):
    mean = jnp.zeros(size)
    m2 = jnp.zeros(size) if diagonal else jnp.zeros((size, size))
    return WelfordState(mean, m2, jnp.zeros((), jnp.int32))


def welford_update(state: WelfordState, x):
    mean, m2, n = state
    n = n + 1
    delta_pre = x - mean
    mean = mean + delta_pre / n
    delta_post = x - mean
    if m2.ndim == 1:
        m2 = m2 + delta_pre * delta_post
    else:
        m2 = m2 + jnp.outer(delta_post, delta_pre)
    return WelfordState(mean, m2, n)


def chain_vmap(f):
    """``jax.vmap`` over the leading chain axis, inference-mesh-aware.

    When the executor has activated a 2-D ``("chains", "data")`` mesh
    (:func:`repro.distributed.sharding.use_inference_mesh`, read at trace
    time), the vmap carries ``spmd_axis_name="chains"`` so the batch
    dimension stays *sharded* over the chain axis through any ``shard_map``
    inside ``f`` — without it, GSPMD treats the batched dim as replicated
    at the shard_map boundary, gathers the chains, and the resulting
    resharding seam perturbs fusion enough to break bit-identity with the
    unsharded layouts.  With no active mesh this is exactly ``jax.vmap``.

    The mesh decision is deferred to call (= trace) time, so closures built
    at setup time stay mesh-agnostic.
    """
    def batched(*args):
        from repro.distributed.sharding import CHAIN_AXIS, active_data_mesh
        active = active_data_mesh()
        if active is not None and CHAIN_AXIS in active[0].axis_names:
            return jax.vmap(f, spmd_axis_name=CHAIN_AXIS)(*args)
        return jax.vmap(f)(*args)

    return batched


def shared_draw(x):
    """Pin a shared-key ensemble RNG draw to the replicated layout.

    Cross-chain kernels draw chain-batched randomness from one shared key —
    ``random.normal(key, (C, D))`` or a ``vmap`` over ``random.split(key,
    C)``.  The installed jax lowers threefry partitionably
    (``jax_threefry_partitionable`` is on by default): each value's counter
    depends only on its own index, so a draw sharded over the inference mesh
    yields the same bits as the unsharded one.  The replication constraint
    keeps that true by construction rather than by the lowering: every
    device computes the whole (tiny, O(C·D)) draw exactly as the
    single-device graph does, and downstream consumers re-slice it.  (Under
    the older non-partitionable lowering, GSPMD's counter rewrite produced
    a different random stream on a 2-D mesh.)

    The trailing ``optimization_barrier`` fires in *every* graph (mesh or
    not): the replication constraint is itself a fusion boundary, so the
    unsharded graphs need the same boundary or the draw's consumers fuse
    (FMA-contract) differently and drift at ULP level.
    """
    from repro.distributed.sharding import active_data_mesh
    active = active_data_mesh()
    if active is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(active[0], PartitionSpec()))
    return jax.lax.optimization_barrier(x)


def chain_sum(x):
    """Bit-deterministic sum over the leading (chain) axis.

    ``jnp.sum`` over an axis that ``chain_method="parallel"`` shards across
    devices lowers to per-shard partial sums plus an all-reduce — a
    *different floating-point association* than the single-device row sum,
    so pooled cross-chain statistics would drift between chain methods.
    This fixed pairwise-tree fold bakes the association into the graph
    (slices + elementwise adds only), making the result bit-identical for
    every device layout.  Chain counts are small, so the O(log C) fold is
    noise next to the leapfrog work it summarizes.
    """
    while x.shape[0] > 1:
        n = x.shape[0]
        half = n // 2
        folded = x[:half] + x[half:2 * half]
        if n % 2:
            folded = jnp.concatenate([folded, x[2 * half:]], axis=0)
        x = folded
    return x[0]


def chain_mean(x):
    """Bit-deterministic mean over the leading (chain) axis."""
    return chain_sum(x) / x.shape[0]


def welford_combine(a: WelfordState, b: WelfordState) -> WelfordState:
    """Exact merge of two Welford accumulators (Chan et al. 1979).

    Either side may be empty (``n == 0``).
    """
    n_a = a.n.astype(a.mean.dtype)
    n_b = b.n.astype(b.mean.dtype)
    n = n_a + n_b
    n_safe = jnp.maximum(n, 1.0)
    delta = b.mean - a.mean
    mean = a.mean + delta * (n_b / n_safe)
    if a.m2.ndim == a.mean.ndim:          # diagonal accumulator
        cross = delta * delta
    else:                                  # dense accumulator
        cross = jnp.outer(delta, delta)
    m2 = a.m2 + b.m2 + cross * (n_a * n_b / n_safe)
    return WelfordState(mean, m2, a.n + b.n)


def welford_batch(x, diagonal=True) -> WelfordState:
    """Welford accumulator equivalent to folding in every row of ``x``
    (shape ``(batch, dim)``) — one vectorized pass, no per-row loop.

    Combined with :func:`welford_combine` this pools a whole chain-batch of
    draws into a shared cross-chain estimator in O(dim) reductions per
    iteration.  Reductions over the batch axis use :func:`chain_sum`, so the
    estimate is bit-identical whether the axis is sharded or not.
    """
    n = x.shape[0]
    mean = chain_mean(x)
    centered = x - mean
    if diagonal:
        m2 = chain_sum(centered * centered)
    else:
        m2 = chain_sum(centered[:, :, None] * centered[:, None, :])
    return WelfordState(mean, m2, jnp.asarray(n, jnp.int32))


def welford_pool(states: WelfordState) -> WelfordState:
    """Pool a chain-batch of Welford accumulators (leaves lead with the
    chain axis) into one: the exact accumulator that would result from
    folding every chain's draws into a single estimator.

    This is the cross-chain mass-matrix pooling step: C chains × n draws
    each become one (C·n)-draw estimate, so warmup variance shrinks with the
    chain count instead of each chain re-learning the scale alone.  All
    chain-axis reductions go through :func:`chain_sum` so the pooled
    estimate is bit-identical between ``chain_method="vectorized"`` and
    ``"parallel"``.
    """
    n_c = states.n.astype(states.mean.dtype)            # (C,)
    n = chain_sum(n_c)
    n_safe = jnp.maximum(n, 1.0)
    nb = n_c.reshape((-1,) + (1,) * (states.mean.ndim - 1))
    mean = chain_sum(nb * states.mean) / n_safe
    delta = states.mean - mean                          # (C, dim)
    if states.m2.ndim == states.mean.ndim:              # diagonal
        m2 = chain_sum(states.m2) + chain_sum(nb * delta * delta)
    else:                                               # dense
        m2 = chain_sum(states.m2) + chain_sum(
            n_c[:, None, None] * delta[:, :, None] * delta[:, None, :])
    return WelfordState(mean, m2, chain_sum(states.n))


def welford_covariance(state: WelfordState, regularize=True):
    mean, m2, n = state
    nf = jnp.maximum(n, 2).astype(m2.dtype)
    cov = m2 / (nf - 1)
    if regularize:  # Stan's shrinkage toward identity
        scaled = (nf / (nf + 5.0)) * cov
        shrink = 1e-3 * (5.0 / (nf + 5.0))
        if cov.ndim == 1:
            cov = scaled + shrink
        else:
            cov = scaled + shrink * jnp.eye(cov.shape[0], dtype=cov.dtype)
    return cov


# ---------------------------------------------------------------------------
# step-size search
# ---------------------------------------------------------------------------

def find_reasonable_step_size(potential_fn, inverse_mass_matrix, z, pe, z_grad,
                              rng_key, init_step_size=1.0, target=0.8,
                              max_iters=64):
    """Double/halve the step size until the one-step accept prob crosses
    ``target`` from the chosen direction (jittable while_loop)."""
    _, vv_update = velocity_verlet(potential_fn)

    def accept_log_prob(step_size, r):
        energy_cur = pe + kinetic_energy(inverse_mass_matrix, r)
        nxt = vv_update(step_size, inverse_mass_matrix,
                        IntegratorState(z, r, pe, z_grad))
        energy_new = nxt.potential_energy + kinetic_energy(
            inverse_mass_matrix, nxt.r)
        # NaN energies must count as rejections, not propagate through sign()
        delta = jnp.where(jnp.isfinite(energy_new), energy_cur - energy_new,
                          -jnp.inf)
        return jnp.minimum(delta, 0.0)

    log_target = jnp.log(target)
    r0 = momentum_sample(rng_key, inverse_mass_matrix, z.dtype)
    alp0 = accept_log_prob(jnp.asarray(init_step_size), r0)
    direction = jnp.where(alp0 > log_target, 1.0, -1.0)

    def cond_fn(val):
        i, ss, alp = val
        crossed = jnp.where(direction > 0, alp <= log_target, alp > log_target)
        return (~crossed) & (i < max_iters) & (ss > 1e-10) & (ss < 1e10)

    def body_fn(val):
        i, ss, _ = val
        ss = ss * 2.0 ** direction
        return i + 1, ss, accept_log_prob(ss, r0)

    _, step_size, _ = lax.while_loop(
        cond_fn, body_fn, (jnp.zeros((), jnp.int32),
                           jnp.asarray(init_step_size, jnp.float32), alp0))
    # we stop one step *past* the crossing in the shrinking direction; that is
    # the conservative (stable) side, keep it.
    return step_size


# ---------------------------------------------------------------------------
# adaptation schedule (Stan-style windows)
# ---------------------------------------------------------------------------

def build_adaptation_schedule(num_steps):
    """Returns a list of (start, end) inclusive windows. First and last are
    fast (step-size only) buffers; middle windows adapt the mass matrix with
    doubling lengths."""
    if num_steps < 20:
        return [(0, num_steps - 1)] if num_steps > 0 else []
    init_buffer, term_buffer, base_window = 75, 50, 25
    if init_buffer + base_window + term_buffer > num_steps:
        init_buffer = int(0.15 * num_steps)
        term_buffer = int(0.1 * num_steps)
        base_window = num_steps - init_buffer - term_buffer
    schedule = [(0, init_buffer - 1)]
    end = num_steps - term_buffer - 1
    start, size = init_buffer, base_window
    while start + size - 1 < end:
        nxt = start + size
        if nxt + 2 * size - 1 > end:  # absorb remainder into this window
            schedule.append((start, end))
            start = end + 1
            break
        schedule.append((start, nxt - 1))
        start, size = nxt, 2 * size
    if start <= end:
        schedule.append((start, end))
    schedule.append((num_steps - term_buffer, num_steps - 1))
    return schedule


def window_predicates(schedule):
    """Jittable predicates over a Stan-style window schedule.

    Returns ``(in_middle_window, window_end_is_middle)``: scalar-int ->
    scalar-bool closures over static window tables, shared by the per-chain
    HMC/NUTS adaptation and the cross-chain ensemble kernels so both agree
    on exactly which warmup iterations accumulate / refresh the mass matrix.
    """
    window_starts = jnp.asarray([s for (s, _) in schedule] or [0], jnp.int32)
    window_ends = jnp.asarray([e for (_, e) in schedule] or [0], jnp.int32)
    has_middle = len(schedule) > 2
    is_middle = jnp.asarray(
        [1 if 0 < i < len(schedule) - 1 else 0
         for i in range(len(schedule))] or [0], jnp.int32).astype(bool)

    def in_middle_window(t):
        if not has_middle:
            return jnp.zeros((), bool)
        return ((t >= window_starts) & (t <= window_ends) & is_middle).any()

    def window_end_is_middle(t):
        if not has_middle:
            return jnp.zeros((), bool)
        return ((t == window_ends) & is_middle).any()

    return in_middle_window, window_end_is_middle


# ---------------------------------------------------------------------------
# iterative NUTS tree building (paper Algorithm 2)
# ---------------------------------------------------------------------------

class TreeState(NamedTuple):
    z_left: jnp.ndarray
    r_left: jnp.ndarray
    z_left_grad: jnp.ndarray
    z_right: jnp.ndarray
    r_right: jnp.ndarray
    z_right_grad: jnp.ndarray
    z_proposal: jnp.ndarray
    z_proposal_pe: jnp.ndarray
    z_proposal_grad: jnp.ndarray
    z_proposal_energy: jnp.ndarray
    depth: jnp.ndarray
    weight: jnp.ndarray        # log sum of exp(-energy) over leaves
    r_sum: jnp.ndarray         # sum of momenta over all leaves
    turning: jnp.ndarray
    diverging: jnp.ndarray
    sum_accept_probs: jnp.ndarray
    num_proposals: jnp.ndarray


def _bit_count(n):
    """popcount for int32 scalars (jittable, branch-free)."""
    n = n.astype(jnp.uint32)
    n = n - ((n >> 1) & 0x55555555)
    n = (n & 0x33333333) + ((n >> 2) & 0x33333333)
    n = (n + (n >> 4)) & 0x0F0F0F0F
    return ((n * 0x01010101) >> 24).astype(jnp.int32)


def _trailing_ones(n):
    """Number of contiguous low-order 1 bits; e.g. 11=(1011) -> 2."""
    # n ^ (n+1) has (t+1) low bits set where t = trailing ones
    return _bit_count(n ^ (n + 1)) - 1


def _leaf_idx_to_ckpt_idxs(n):
    """For odd leaf ``n``, the checkpoint index range [idx_min, idx_max]
    holding the left endpoints of every balanced subtree whose rightmost
    node is ``n`` (trailing-1s masking; paper App. A)."""
    idx_max = _bit_count(n - 1)
    idx_min = idx_max - _trailing_ones(n)  # = idx_max - l + 1
    return idx_min + 1, idx_max


def _is_turning(inverse_mass_matrix, r_left, r_right, r_sum):
    """Generalized U-turn criterion (Betancourt) on momentum sums."""
    v_left = velocity(inverse_mass_matrix, r_left)
    v_right = velocity(inverse_mass_matrix, r_right)
    r_mid = r_sum - 0.5 * (r_left + r_right)
    return (jnp.dot(v_left, r_mid) <= 0) | (jnp.dot(v_right, r_mid) <= 0)


def _is_iterative_turning(inverse_mass_matrix, r, r_sum, r_ckpts, r_sum_ckpts,
                          idx_min, idx_max):
    """Scan checkpoints idx_max..idx_min checking the U-turn condition of
    each balanced subtree ending at the current (odd) leaf."""

    def cond_fn(val):
        i, turning = val
        return (i >= idx_min) & ~turning

    def body_fn(val):
        i, _ = val
        subtree_r_sum = r_sum - r_sum_ckpts[i] + r_ckpts[i]
        turning = _is_turning(inverse_mass_matrix, r_ckpts[i], r, subtree_r_sum)
        return i - 1, turning

    _, turning = lax.while_loop(cond_fn, body_fn,
                                (idx_max, jnp.zeros((), bool)))
    return turning


def _leaf_tree(state: IntegratorState, energy, ref_energy, max_delta_energy,
               depth_dtype=jnp.int32):
    """A single-leaf tree with multinomial weight exp(-energy)."""
    delta = energy - ref_energy
    delta = jnp.where(jnp.isnan(delta), jnp.inf, delta)
    diverging = delta > max_delta_energy
    accept_prob = jnp.clip(jnp.exp(-delta), max=1.0)
    return TreeState(
        z_left=state.z, r_left=state.r, z_left_grad=state.z_grad,
        z_right=state.z, r_right=state.r, z_right_grad=state.z_grad,
        z_proposal=state.z, z_proposal_pe=state.potential_energy,
        z_proposal_grad=state.z_grad, z_proposal_energy=energy,
        depth=jnp.zeros((), depth_dtype),
        weight=-delta,           # log weight relative to ref energy
        r_sum=state.r,
        turning=jnp.zeros((), bool),
        diverging=diverging,
        sum_accept_probs=accept_prob,
        num_proposals=jnp.ones((), jnp.int32),
    )


def _combine_tree(rng_key, inverse_mass_matrix, current: TreeState,
                  new: TreeState, going_right, biased: bool):
    """Merge ``new`` (grown in direction ``going_right``) into ``current``.

    ``biased=True`` is the tree-level biased-progressive transition used when
    merging the doubled half; ``biased=False`` is the within-subtree
    multinomial update.
    """
    # orientation
    z_left, r_left, z_left_grad = jax.tree_util.tree_map(
        lambda a, b: jnp.where(going_right, a, b),
        (current.z_left, current.r_left, current.z_left_grad),
        (new.z_left, new.r_left, new.z_left_grad))
    z_right, r_right, z_right_grad = jax.tree_util.tree_map(
        lambda a, b: jnp.where(going_right, a, b),
        (new.z_right, new.r_right, new.z_right_grad),
        (current.z_right, current.r_right, current.z_right_grad))

    total_weight = jnp.logaddexp(current.weight, new.weight)
    if biased:
        transition_lp = jnp.minimum(new.weight - current.weight, 0.0)
        transition_lp = jnp.where(new.turning | new.diverging, -jnp.inf,
                                  transition_lp)
    else:
        transition_lp = new.weight - total_weight
    take_new = jnp.log(jax.random.uniform(rng_key)) < transition_lp

    z_prop, z_prop_pe, z_prop_grad, z_prop_energy = jax.tree_util.tree_map(
        lambda a, b: jnp.where(take_new, a, b),
        (new.z_proposal, new.z_proposal_pe, new.z_proposal_grad,
         new.z_proposal_energy),
        (current.z_proposal, current.z_proposal_pe, current.z_proposal_grad,
         current.z_proposal_energy))

    r_sum = current.r_sum + new.r_sum
    turning = current.turning | new.turning
    if biased:
        # after doubling, check the U-turn condition across the merged tree
        turning = turning | _is_turning(inverse_mass_matrix, r_left, r_right,
                                        r_sum)
    return TreeState(
        z_left=z_left, r_left=r_left, z_left_grad=z_left_grad,
        z_right=z_right, r_right=r_right, z_right_grad=z_right_grad,
        z_proposal=z_prop, z_proposal_pe=z_prop_pe,
        z_proposal_grad=z_prop_grad, z_proposal_energy=z_prop_energy,
        depth=current.depth + 1 if biased else current.depth,
        weight=total_weight, r_sum=r_sum, turning=turning,
        diverging=current.diverging | new.diverging,
        sum_accept_probs=current.sum_accept_probs + new.sum_accept_probs,
        num_proposals=current.num_proposals + new.num_proposals,
    )


def iterative_build_subtree(vv_update, inverse_mass_matrix, step_size,
                            going_right, rng_key, initial: TreeState,
                            depth, max_depth, ref_energy, max_delta_energy):
    """Paper Algorithm 2: grow a balanced subtree of up to 2**depth leaves by
    running the LeapFrog integrator iteratively, storing only O(max_depth)
    momentum checkpoints for U-turn checks.

    Returns a TreeState for the subtree (not yet merged with ``initial``).
    """
    d = initial.z_left.shape[0]
    dtype = initial.r_sum.dtype
    # integrate backwards in time when growing the tree leftwards
    step_size = jnp.where(going_right, step_size, -step_size)

    # momentum / momentum-prefix-sum checkpoints: indices 0..max_depth-1
    r_ckpts = jnp.zeros((max_depth, d), dtype)
    r_sum_ckpts = jnp.zeros((max_depth, d), dtype)

    z0, r0, g0 = lax.cond(
        going_right,
        lambda t: (t.z_right, t.r_right, t.z_right_grad),
        lambda t: (t.z_left, t.r_left, t.z_left_grad),
        initial)
    # pe at the edge is recomputed by the first vv step; value unused
    basestate = IntegratorState(z0, r0, initial.z_proposal_pe, g0)

    num_leaves = jnp.asarray(2, jnp.int32) ** depth

    def cond_fn(val):
        tree, leaf_idx, _, _, _, _ = val
        return (leaf_idx < num_leaves) & ~tree.turning & ~tree.diverging

    def body_fn(val):
        tree, leaf_idx, edge, r_ckpts, r_sum_ckpts, key = val
        key, transition_key = jax.random.split(key)
        nxt = vv_update(step_size, inverse_mass_matrix, edge)
        energy = nxt.potential_energy + kinetic_energy(inverse_mass_matrix,
                                                       nxt.r)
        leaf = _leaf_tree(nxt, energy, ref_energy, max_delta_energy)
        new_tree = lax.cond(
            leaf_idx == 0,
            lambda ops: ops[2],
            lambda ops: _combine_tree(ops[0], inverse_mass_matrix, ops[1],
                                      ops[2], going_right, biased=False),
            (transition_key, tree, leaf))

        # checkpoint bookkeeping (paper App. A) -------------------------
        is_even = (leaf_idx % 2) == 0
        ckpt_i = _bit_count(leaf_idx)
        # r_sum over leaves of THIS subtree only, through current leaf
        r_sum_through = new_tree.r_sum
        r_ckpts = jnp.where(is_even, r_ckpts.at[ckpt_i].set(nxt.r), r_ckpts)
        r_sum_ckpts = jnp.where(is_even,
                                r_sum_ckpts.at[ckpt_i].set(r_sum_through),
                                r_sum_ckpts)

        idx_min, idx_max = _leaf_idx_to_ckpt_idxs(leaf_idx)
        turning = lax.cond(
            is_even | new_tree.turning | new_tree.diverging,
            lambda _: new_tree.turning,
            lambda _: _is_iterative_turning(
                inverse_mass_matrix, nxt.r, r_sum_through, r_ckpts,
                r_sum_ckpts, idx_min, idx_max),
            None)
        new_tree = new_tree._replace(turning=turning)
        return new_tree, leaf_idx + 1, nxt, r_ckpts, r_sum_ckpts, key

    # first leaf: one vv step from the edge
    key0, key_rest = jax.random.split(rng_key)
    first = vv_update(step_size, inverse_mass_matrix, basestate)
    energy0 = first.potential_energy + kinetic_energy(inverse_mass_matrix,
                                                      first.r)
    tree0 = _leaf_tree(first, energy0, ref_energy, max_delta_energy)
    r_ckpts = r_ckpts.at[0].set(first.r)
    r_sum_ckpts = r_sum_ckpts.at[0].set(first.r)

    tree, _, _, _, _, _ = lax.while_loop(
        cond_fn, body_fn,
        (tree0, jnp.ones((), jnp.int32), first, r_ckpts, r_sum_ckpts,
         key_rest))
    # left/right ends were already oriented inside _combine_tree
    return tree


@scoped(SCOPES.tree)
def build_tree(vv_update, inverse_mass_matrix, step_size, rng_key,
               initial_state: IntegratorState, max_tree_depth=10,
               max_delta_energy=1000.0):
    """One full NUTS trajectory: repeated doubling with iterative subtrees.

    Fully jittable — this is the paper's headline capability.
    """
    energy0 = initial_state.potential_energy + kinetic_energy(
        inverse_mass_matrix, initial_state.r)
    tree = _leaf_tree(initial_state, energy0, energy0, max_delta_energy)
    # the root is not a proposal; don't let it bias the accept-prob statistic
    tree = tree._replace(sum_accept_probs=jnp.zeros(()),
                         num_proposals=jnp.zeros((), jnp.int32))

    def cond_fn(val):
        tree, key = val
        return (tree.depth < max_tree_depth) & ~tree.turning & ~tree.diverging

    def body_fn(val):
        tree, key = val
        key, dir_key, subtree_key, transition_key = jax.random.split(key, 4)
        going_right = jax.random.bernoulli(dir_key)
        subtree = iterative_build_subtree(
            vv_update, inverse_mass_matrix, step_size, going_right,
            subtree_key, tree, tree.depth, max_tree_depth, energy0,
            max_delta_energy)
        tree = _combine_tree(transition_key, inverse_mass_matrix, tree,
                             subtree, going_right, biased=True)
        return tree, key

    tree, _ = lax.while_loop(cond_fn, body_fn, (tree, rng_key))
    return tree
