"""Fused leapfrog half-step for HMC/NUTS (the paper's compute hot-spot).

One HBM pass computes the momentum half-step and the position full-step
together:  r' = r - (eps/2) * g ;  z' = z + eps * (r' * m_inv)  — the purely
memory-bound half of the integrator (the other half is the potential-energy
gradient, which is the model's own compute).  For the million-dimensional
latent spaces of SKIM-scale models this halves integrator memory traffic
vs. two separate axpy passes.

The sign convention matches ``hmc_util.velocity_verlet`` exactly (``g`` is
the gradient of the *potential*), so the kernel drops into the integrator
with no extra negation pass.  ``eps`` is a traced operand — NUTS flips its
sign when growing the trajectory leftwards and adaptation rescales it every
warmup step — so it is shipped as a tiny (1, 1) array rather than baked into
the kernel at trace time.

The TPU compiler tiles the last two dims of every block in (8, 128) units,
and ``vmap`` over chains (the executor's batching) adds a leading block dim.
So the (D,) vectors travel as (rows, 128) slabs and ``eps`` as (1, 1): under
``vmap`` each chain's blocks keep whole-array or tile-aligned last two dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 4096
_SUBLANE = 8
_LANE = 128


def _kernel(eps_ref, z_ref, r_ref, g_ref, minv_ref, znew_ref, rnew_ref, *,
            compute_dtype):
    eps = eps_ref[0, 0].astype(compute_dtype)
    r = r_ref[...].astype(compute_dtype)
    g = g_ref[...].astype(compute_dtype)
    z = z_ref[...].astype(compute_dtype)
    r_new = r - 0.5 * eps * g
    z_new = z + eps * (r_new * minv_ref[...].astype(compute_dtype))
    rnew_ref[...] = r_new.astype(rnew_ref.dtype)
    znew_ref[...] = z_new.astype(znew_ref.dtype)


def leapfrog_halfstep(z, r, grad, m_inv, eps, *, block=BLOCK,
                      interpret=False):
    """(z, r, grad, m_inv) flat vectors of dim D -> (z', r').

    ``block`` is the D-tile size — a tuning knob, trailing-defaulted so the
    kernel stays a drop-in replacement for the ref oracle (RPL202).
    """
    D = z.shape[0]
    rows = -(-D // _LANE)
    # one block of every row when it fits, else sublane-aligned row tiles
    br = rows if rows * _LANE <= block else max(
        _SUBLANE, block // _LANE // _SUBLANE * _SUBLANE)
    rows += (-rows) % br
    pad = rows * _LANE - D
    z, r, grad, m_inv = (jnp.pad(a, (0, pad)).reshape(rows, _LANE)
                         for a in (z, r, grad, m_inv))
    # accumulate low-precision inputs in f32, but never truncate f64 chains
    compute_dtype = jnp.promote_types(z.dtype, jnp.float32)
    eps = jnp.asarray(eps, compute_dtype).reshape(1, 1)
    tile = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    zf, rf = pl.pallas_call(
        functools.partial(_kernel, compute_dtype=compute_dtype),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0))] + [tile] * 4,
        out_specs=[tile] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), z.dtype),
                   jax.ShapeDtypeStruct((rows, _LANE), r.dtype)],
        interpret=interpret, name="leapfrog_halfstep",
    )(eps, z, r, grad, m_inv)
    return zf.reshape(-1)[:D], rf.reshape(-1)[:D]


def leapfrog_halfstep_ref(z, r, grad, m_inv, eps):
    r_new = r - 0.5 * eps * grad
    return z + eps * (r_new * m_inv), r_new


# --------------------------------------------------------------------------
# Chain-batched megakernel: one kernel walks all C chains × D dims.
#
# The ChEES dense path steps every chain in lockstep; ``vmap(halfstep)``
# would re-tile per chain and churn layouts.  Here the whole (C, D) ensemble
# is one blocked array and eps / m_inv broadcast from a tiny scalar operand
# and a (1, D) row.  ``kick`` generalises the half-step: 0.5 gives the
# classic half-kick, 1.0 the merged full kick used between interior steps of
# a trajectory (two adjacent half-kicks fused into one HBM pass).
# --------------------------------------------------------------------------

def _batch_kernel(s_ref, z_ref, r_ref, g_ref, minv_ref, znew_ref, rnew_ref,
                  *, compute_dtype):
    eps = s_ref[0].astype(compute_dtype)
    kick = s_ref[1].astype(compute_dtype)
    r = r_ref[...].astype(compute_dtype)
    g = g_ref[...].astype(compute_dtype)
    z = z_ref[...].astype(compute_dtype)
    minv = minv_ref[...].astype(compute_dtype)  # (1, bd) row, broadcasts
    r_new = r - (kick * eps) * g
    z_new = z + eps * (r_new * minv)
    rnew_ref[...] = r_new.astype(rnew_ref.dtype)
    znew_ref[...] = z_new.astype(znew_ref.dtype)


def leapfrog_halfstep_batch(z, r, grad, m_inv, eps, kick=0.5, *, block=BLOCK,
                            interpret=False):
    """(C, D)-batched leapfrog kick+drift: r' = r - kick*eps*g ;
    z' = z + eps*(r'*m_inv).  ``m_inv`` is the shared (D,) diagonal mass;
    ``eps``/``kick`` are scalars broadcast to every chain."""
    C, D = z.shape
    bd = min(block, D)
    bd += (-bd) % _LANE                      # lane-align the D tile
    cpad = (-C) % _SUBLANE
    dpad = (-D) % bd
    if cpad or dpad:
        z, r, grad = (jnp.pad(a, ((0, cpad), (0, dpad)))
                      for a in (z, r, grad))
    m_inv = jnp.pad(m_inv, (0, dpad)).reshape(1, -1)
    cp, dp = z.shape
    compute_dtype = jnp.promote_types(z.dtype, jnp.float32)
    scalars = jnp.stack([jnp.asarray(eps, compute_dtype),
                         jnp.asarray(kick, compute_dtype)])
    zf, rf = pl.pallas_call(
        functools.partial(_batch_kernel, compute_dtype=compute_dtype),
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((2,), lambda i: (0,))]
        + [pl.BlockSpec((cp, bd), lambda i: (0, i))] * 3
        + [pl.BlockSpec((1, bd), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((cp, bd), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((cp, dp), z.dtype),
                   jax.ShapeDtypeStruct((cp, dp), r.dtype)],
        interpret=interpret, name="leapfrog_halfstep_batch",
    )(scalars, z, r, grad, m_inv)
    return zf[:C, :D], rf[:C, :D]


def leapfrog_halfstep_batch_ref(z, r, grad, m_inv, eps, kick=0.5):
    r_new = r - kick * eps * grad
    return z + eps * (r_new * m_inv), r_new
