"""Fused logsumexp contraction for discrete-latent chain elimination.

The enumeration subsystem's hot loop (``repro.core.infer.enum.markov``) runs
``out[..., j] = logsumexp_i(log_alpha[..., i] + log_mat[..., i, j])`` once per
time step inside ``lax.scan`` — the O(K^2) inner body of the O(T*K^2) forward
algorithm.  Unfused, XLA materializes the (K, K) broadcast sum, the max, the
exp and the log as separate HBM round-trips; this kernel does the whole
contraction in one VMEM pass per tile of batch rows.

The formula is written identically to :func:`repro.kernels.ref.enum_contract`
(max, strictly left-to-right exp-sum over the contracted axis, log,
fully-masked columns pinned to -inf), so the kernel is bit-identical to the
ref path in interpret mode — the same contract ``leapfrog_halfstep`` keeps.

Layout.  The TPU compiler tiles the last two dims of every block in (8, 128)
sublane x lane units.  The contracted axis therefore leads ``log_mat``
(``(Ki, B, K)``) and is unrolled in the body, while the batch rows go on the
sublanes in tiles of 8 and the output states on the lanes; the batch is
padded to a multiple of 8 and the states to a multiple of 128, and both pads
are sliced off the result.

Gradients.  A ``pallas_call`` has no reverse-mode rule, and NUTS
differentiates the marginal through every step of the scan, so the kernel is
wrapped in ``jax.custom_vjp`` whose backward pass is the VJP of the ref
oracle at the same inputs (the softmax-weighted cotangents).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

SUBLANE = 8    # f32 min tile rows: batch rows per grid step
LANE = 128     # lane width: output states padded to a multiple of this


def _kernel(alpha_ref, mat_ref, out_ref, *, compute_dtype):
    alpha = alpha_ref[...].astype(compute_dtype)        # (SUBLANE, Ki)
    # x_i = alpha[:, i] + mat[i] over the contracted states, each (SUBLANE, Kp)
    xs = [alpha[:, i:i + 1] + mat_ref[i].astype(compute_dtype)
          for i in range(mat_ref.shape[0])]
    out_ref[...] = _logsumexp(xs).astype(out_ref.dtype)


def _logsumexp(xs):
    """``logsumexp`` over the list ``xs``: max, then the exp-sum strictly
    in list order (the order ``ref.enum_contract`` pins), then log, with a
    column that is -inf throughout pinned to -inf."""
    m = xs[0]
    for x in xs[1:]:
        m = jnp.maximum(m, x)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    s = jnp.exp(xs[0] - m_safe)
    for x in xs[1:]:
        s = s + jnp.exp(x - m_safe)
    return jnp.where(jnp.isfinite(m), jnp.log(s) + m_safe,
                     -jnp.array(jnp.inf, m.dtype))


def _pad_to(n, mult):
    return n + (-n) % mult


def enum_contract(log_alpha, log_mat, *, interpret=False):
    """``(..., Ki) x (..., Ki, K) -> (..., K)`` logsumexp contraction."""
    Ki = log_mat.shape[-2]
    if log_alpha.shape[-1] != Ki:
        raise ValueError(
            f"enum_contract: log_alpha has {log_alpha.shape[-1]} states, "
            f"log_mat contracts over {Ki}")
    return _contract(log_alpha, log_mat, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _contract(log_alpha, log_mat, interpret):
    Ki, K = log_mat.shape[-2:]
    batch = jnp.broadcast_shapes(log_alpha.shape[:-1], log_mat.shape[:-2])
    out_dtype = jnp.result_type(log_alpha.dtype, log_mat.dtype)
    alpha = jnp.broadcast_to(log_alpha, batch + (Ki,)).astype(out_dtype)
    mat = jnp.broadcast_to(log_mat, batch + (Ki, K)).astype(out_dtype)
    B = math.prod(batch)
    bp, kp = _pad_to(B, SUBLANE), _pad_to(K, LANE)
    alpha = jnp.pad(alpha.reshape(B, Ki), ((0, bp - B), (0, 0)))
    mat = jnp.pad(jnp.moveaxis(mat.reshape(B, Ki, K), 1, 0),
                  ((0, 0), (0, bp - B), (0, kp - K)),
                  constant_values=-jnp.inf)

    compute_dtype = jnp.promote_types(out_dtype, jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, compute_dtype=compute_dtype),
        grid=(bp // SUBLANE,),
        in_specs=[pl.BlockSpec((SUBLANE, Ki), lambda b: (b, 0)),
                  pl.BlockSpec((Ki, SUBLANE, kp), lambda b: (0, b, 0))],
        out_specs=pl.BlockSpec((SUBLANE, kp), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, kp), out_dtype),
        interpret=interpret, name="enum_contract",
    )(alpha, mat)
    return out[:B, :K].reshape(batch + (K,))


def _contract_fwd(log_alpha, log_mat, interpret):
    return _contract(log_alpha, log_mat, interpret), (log_alpha, log_mat)


def _contract_bwd(interpret, res, ct):
    return jax.vjp(ref.enum_contract, *res)[1](ct)


_contract.defvjp(_contract_fwd, _contract_bwd)


# ---------------------------------------------------------------------------
# the whole chain: every step of a block of rows in one grid step
# ---------------------------------------------------------------------------
#
# A chain's forward algorithm is T - 1 dependent contractions.  One kernel
# call per step pays a launch and the loop's own operations at each step,
# and that fixed cost, not the contraction, sets the time of a chain of
# a few hundred small steps.  The two kernels below run the recursion
# inside one call each, the rows on the lanes (``(K, B)`` messages) and a
# block's factors of every step in VMEM.  A grid step takes as many rows
# as its blocks fit ``CHAIN_VMEM`` (all of them where they fit, since
# each step's latency, not its width, sets the time), so VMEM does not
# grow with the rows.  Both kernels keep every message's largest entry at
# 0, as ``ref.chain_scan`` does:
#
# - ``enum_contract_chain``, forward: alpha~_t = logsumexp_i(alpha_{t-1}[i]
#   + pair_t[i, :]) + unary_t, m_t = max(alpha~_t), alpha_t = alpha~_t -
#   m_t, and log Z = sum of the m_t + logsumexp(alpha_T);
# - ``enum_contract_chain_vjp``, backward: beta~_{t-1} = logsumexp_j(
#   pair_t[:, j] + unary_t[j] + beta_t[j]), beta_{t-1} = beta~_{t-1} -
#   max(beta~_{t-1}), beta = 0 after the last step.
#
# A row that does not take a step holds its message (and m_t = 0).  The
# gradient is the posterior of each state, softmax_k(alpha_t + beta_t),
# and of each pair of states, exp(alpha_{t-1}[i] + pair_t[i, j] +
# unary_t[j] + beta_t[j] - m_t - n_t) with n_t = logsumexp(alpha_t +
# beta_t), summed by XLA over all steps at once.  Every exponent is then a
# difference of numbers a few emissions from 0: unnormalised messages
# reach log Z itself (thousands of nats for a long chain), where f32 keeps
# only about 1e-4 of a nat and the posterior loses its digits.

CHAIN_VMEM = 12 * 2 ** 20   # most bytes a grid step's blocks may hold


def _chain_vmem_bytes(steps, K, lanes):
    """VMEM of the larger (forward) kernel's blocks for ``lanes`` rows,
    double-buffered: the (8, 128) tiling pads the states to 8 sublanes and
    each step's (K, K) factor to 128 lanes."""
    k8 = _pad_to(K, SUBLANE)
    messages = (2 * steps + 2) * k8 * lanes     # alpha0, unary, alphas
    pair = steps * k8 * _pad_to(K, LANE)
    rows = 3 * _pad_to(steps + 1, SUBLANE) * lanes  # keep, m, log Z
    return 2 * 4 * (messages + pair + rows)


def _row_block(steps, K, B):
    """Rows a grid step takes: every row, padded to whole ``LANE`` blocks,
    or the most whole blocks that fit ``CHAIN_VMEM``."""
    lanes = _pad_to(B, LANE)
    while lanes > LANE and _chain_vmem_bytes(steps, K, lanes) > CHAIN_VMEM:
        lanes -= LANE
    return lanes


def chain_route(steps, K, per_row_pair):
    """Which path :func:`enum_contract_chain` takes: ``"whole_chain"``, the
    two kernels above, or ``"per_step"``, one ``enum_contract`` call a
    step (a transition per row, or a chain whose steps do not fit
    ``CHAIN_VMEM`` even for one block of ``LANE`` rows)."""
    if per_row_pair or _chain_vmem_bytes(steps, K, LANE) > CHAIN_VMEM:
        return "per_step"
    return "whole_chain"


def _lse_rows(x):
    """``logsumexp`` over the states (sublanes) of a (K, B) block, in the
    order ``ref.logsumexp_states`` pins: (1, B)."""
    return _logsumexp([x[k:k + 1, :] for k in range(x.shape[0])])


def _shifted(x):
    """``x`` less its largest state, and that largest state (exact, so in
    any order): (K, B), (1, B)."""
    m = jnp.max(x, axis=0, keepdims=True)
    return x - jnp.where(jnp.isfinite(m), m, 0.0), m


def _chain_forward_kernel(alpha0_ref, unary_ref, pair_t_ref, keep_ref,
                          out_ref, m_ref, log_z_ref):
    K = alpha0_ref.shape[0]
    alpha, log_z = _shifted(alpha0_ref[...])
    out_ref[0] = alpha
    m_ref[pl.ds(0, 1), :] = log_z

    def step(t, carry):                         # alpha: (K, B)
        alpha, log_z = carry
        pt = pair_t_ref[t - 1]                  # (K_to, K_from)
        xs = [alpha[i:i + 1, :] + pt[:, i:i + 1] for i in range(K)]
        new, m = _shifted(_logsumexp(xs) + unary_ref[t - 1])
        keep = keep_ref[pl.ds(t - 1, 1), :] > 0
        alpha = jnp.where(keep, new, alpha)
        out_ref[t] = alpha
        m_ref[pl.ds(t, 1), :] = jnp.where(keep, m, 0.0)
        return alpha, jnp.where(keep, log_z + m, log_z)

    alpha, log_z = jax.lax.fori_loop(1, out_ref.shape[0], step,
                                     (alpha, log_z))
    log_z_ref[...] = log_z + _lse_rows(alpha)


def _chain_backward_kernel(unary_ref, pair_ref, keep_ref, out_ref):
    T, K = out_ref.shape[0], pair_ref.shape[1]
    beta = jnp.zeros(out_ref.shape[1:], out_ref.dtype)
    out_ref[T - 1] = beta

    def step(r, beta):                          # beta_t -> beta_{t-1}
        t = T - 1 - r
        p = pair_ref[t - 1]                     # (K_from, K_to)
        v = unary_ref[t - 1] + beta             # (K_to, B)
        xs = [v[j:j + 1, :] + p[:, j:j + 1] for j in range(K)]
        new, _ = _shifted(_logsumexp(xs))
        beta = jnp.where(keep_ref[pl.ds(t - 1, 1), :] > 0, new, beta)
        out_ref[t - 1] = beta
        return beta

    jax.lax.fori_loop(0, T - 1, step, beta)


def _on_lanes(shape, lanes):
    """A block of ``lanes`` rows (the last axis) of an array of ``shape``."""
    block = shape[:-1] + (lanes,)
    return pl.BlockSpec(block, lambda b: (0,) * (len(shape) - 1) + (b,))


def _shared(shape):
    """The whole array, the same block at every grid step."""
    return pl.BlockSpec(shape, lambda b: (0,) * len(shape))


def enum_contract_chain(alpha0, unary, pair, keep, *, interpret=False):
    """``log Z`` (B,) of every row of a chain (``ref.enum_contract_chain``).

    A chain whose rows share each step's ``pair`` (T-1, K, K) runs in the
    two whole-chain kernels while a block of 128 rows' steps fits
    ``CHAIN_VMEM``; a ``pair`` per row (T-1, B, K, K), or a longer chain,
    runs one ``enum_contract`` call per step (:func:`chain_route`)."""
    steps, B, K = unary.shape
    if steps == 0 or chain_route(steps, K, pair.ndim != 3) == "per_step":
        return ref.chain_scan(
            functools.partial(enum_contract, interpret=interpret),
            alpha0, unary, pair, keep)
    return _chain(alpha0, unary, pair, keep, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chain(alpha0, unary, pair, keep, interpret):
    return _chain_fwd(alpha0, unary, pair, keep, interpret)[0]


def _chain_fwd(alpha0, unary, pair, keep, interpret):
    steps, B, K = unary.shape
    out_dtype = jnp.result_type(alpha0.dtype, unary.dtype, pair.dtype)
    dtype = jnp.promote_types(out_dtype, jnp.float32)
    lanes = _row_block(steps, K, B)
    bp = _pad_to(B, lanes)
    # rows on the lanes, padded to whole blocks: a padding row takes no
    # step and is sliced off
    a0 = jnp.pad(alpha0.T.astype(dtype), ((0, 0), (0, bp - B)))  # (K, Bp)
    u = jnp.pad(jnp.swapaxes(unary, 1, 2).astype(dtype),
                ((0, 0), (0, 0), (0, bp - B)))                # (T-1, K, Bp)
    kf = jnp.pad(keep.astype(dtype), ((0, 0), (0, bp - B)))   # (T-1, Bp)
    p = pair.astype(dtype)                            # (T-1, K_from, K_to)
    alphas, m, log_z = pl.pallas_call(
        _chain_forward_kernel, grid=(bp // lanes,),
        in_specs=[_on_lanes(a0.shape, lanes), _on_lanes(u.shape, lanes),
                  _shared(p.shape), _on_lanes(kf.shape, lanes)],
        out_specs=[_on_lanes((steps + 1, K, bp), lanes),
                   _on_lanes((steps + 1, bp), lanes),
                   _on_lanes((1, bp), lanes)],
        out_shape=[jax.ShapeDtypeStruct((steps + 1, K, bp), dtype),
                   jax.ShapeDtypeStruct((steps + 1, bp), dtype),
                   jax.ShapeDtypeStruct((1, bp), dtype)],
        interpret=interpret, name="enum_contract_chain",
    )(a0, u, jnp.swapaxes(p, 1, 2), kf)
    out = log_z[0, :B].astype(out_dtype)
    # the inputs' dtypes ride along as empty arrays (residuals are arrays)
    dtypes = tuple(jnp.zeros((0,), x.dtype) for x in (alpha0, unary, pair))
    return out, (alphas, m, u, p, kf, log_z[0], dtypes)


def _chain_bwd(interpret, res, ct):
    alphas, m, u, p, kf, log_z, dtypes = res
    a_dt, u_dt, p_dt = (x.dtype for x in dtypes)
    steps, K, bp = u.shape
    B = ct.shape[0]
    lanes = _row_block(steps, K, B)
    betas = pl.pallas_call(
        _chain_backward_kernel, grid=(bp // lanes,),
        in_specs=[_on_lanes(u.shape, lanes), _shared(p.shape),
                  _on_lanes(kf.shape, lanes)],
        out_specs=_on_lanes((steps + 1, K, bp), lanes),
        out_shape=jax.ShapeDtypeStruct((steps + 1, K, bp), u.dtype),
        interpret=interpret, name="enum_contract_chain_vjp",
    )(u, p, kf)
    # a row with log Z = -inf has no posterior and no gradient
    w = jnp.pad(ct.astype(u.dtype), (0, bp - B))
    w = jnp.where(jnp.isfinite(log_z), w, 0.0)                      # (Bp,)
    s = alphas + betas                                              # (T,K,Bp)
    n = jax.nn.logsumexp(s, axis=1)                                 # (T, Bp)
    n = jnp.where(jnp.isfinite(n), n, 0.0)
    gamma = jnp.exp(s - n[:, None, :]) * w
    d_alpha0 = gamma[0, :, :B].T.astype(a_dt)
    d_unary = jnp.swapaxes(gamma[1:] * kf[:, None, :], 1, 2)[:, :B]
    norm = jnp.where(jnp.isfinite(m[1:]), m[1:], 0.0) + n[1:]     # (T-1, Bp)
    x = (alphas[:-1, :, None, :] + p[..., None]
         + (u + betas[1:])[:, None, :, :] - norm[:, None, None, :])
    xi = jnp.exp(jnp.where(kf[:, None, None, :] > 0, x, -jnp.inf))
    d_pair = jnp.sum(xi * w, axis=-1).astype(p_dt)              # (T-1,K,K)
    return d_alpha0, d_unary.astype(u_dt), d_pair, None


_chain.defvjp(_chain_fwd, _chain_bwd)
