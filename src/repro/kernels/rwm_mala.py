"""Batched MALA / random-walk Metropolis proposal kernel.

MALA's proposal is pure elementwise traffic over the (C, D) chain ensemble:

    z' = z - eps * m_inv * grad + sqrt(2 * eps * m_inv) * noise

i.e. three reads + one write per element with two broadcast scalars/rows —
exactly the memory-bound shape the leapfrog megakernel already exploits.
One kernel walks all C chains x D dims with eps broadcast from a scalar
operand and the diagonal preconditioner ``m_inv`` from a (1, D) row.
``grad=None`` drops the drift term (the symmetric random-walk proposal);
the gradient operand is then omitted entirely, not zero-filled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 4096
_SUBLANE = 8
_LANE = 128


def _kernel(eps_ref, z_ref, *rest, has_grad, compute_dtype):
    if has_grad:
        g_ref, noise_ref, minv_ref, out_ref = rest
    else:
        g_ref, (noise_ref, minv_ref, out_ref) = None, rest
    eps = eps_ref[0].astype(compute_dtype)
    z = z_ref[...].astype(compute_dtype)
    minv = minv_ref[...].astype(compute_dtype)               # (1, bd) row
    sig = jnp.sqrt(2.0 * eps * minv)
    out = z + sig * noise_ref[...].astype(compute_dtype)
    if has_grad:
        out = out - eps * minv * g_ref[...].astype(compute_dtype)
    out_ref[...] = out.astype(out_ref.dtype)


def mala_step(z, grad, noise, m_inv, eps, *, block=BLOCK, interpret=False):
    """(C, D)-batched Langevin proposal; ``grad=None`` -> random walk.

    ``m_inv`` is the shared (D,) diagonal preconditioner, ``eps`` a scalar,
    ``noise`` standard normal draws.  ``block`` is the D-tile size —
    tuning only, trailing-defaulted (RPL202).
    """
    C, D = z.shape
    bd = min(block, D)
    bd += (-bd) % _LANE
    cpad = (-C) % _SUBLANE
    dpad = (-D) % bd
    has_grad = grad is not None
    if cpad or dpad:
        z = jnp.pad(z, ((0, cpad), (0, dpad)))
        noise = jnp.pad(noise, ((0, cpad), (0, dpad)))
        if has_grad:
            grad = jnp.pad(grad, ((0, cpad), (0, dpad)))
    m_inv = jnp.pad(m_inv, (0, dpad)).reshape(1, -1)
    cp, dp = z.shape
    compute_dtype = jnp.promote_types(z.dtype, jnp.float32)
    eps = jnp.asarray(eps, compute_dtype).reshape(1)
    ens_spec = pl.BlockSpec((cp, bd), lambda i: (0, i))
    operands = ([eps, z] + ([grad] if has_grad else [])
                + [noise, m_inv])
    out = pl.pallas_call(
        functools.partial(_kernel, has_grad=has_grad,
                          compute_dtype=compute_dtype),
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((1,), lambda i: (0,))]
        + [ens_spec] * (3 if has_grad else 2)
        + [pl.BlockSpec((1, bd), lambda i: (0, i))],
        out_specs=ens_spec,
        out_shape=jax.ShapeDtypeStruct((cp, dp), z.dtype),
        interpret=interpret, name="mala_step",
    )(*operands)
    return out[:C, :D]
