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
    m = xs[0]
    for x in xs[1:]:
        m = jnp.maximum(m, x)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    # left-to-right sequential sum: pinned order matches the ref oracle
    s = jnp.exp(xs[0] - m_safe)
    for x in xs[1:]:
        s = s + jnp.exp(x - m_safe)
    out = jnp.where(jnp.isfinite(m), jnp.log(s) + m_safe,
                    -jnp.array(jnp.inf, compute_dtype))
    out_ref[...] = out.astype(out_ref.dtype)


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
