"""The comparison that decides ``correct``.

After the window, the plain reference recomputes the potential and its
gradient at every draw the timed fits kept, and at each chain's final
state.  From those:

- ``pe_gap``: the widest gap, in nats, between the potential energy the
  program recorded with each kept draw and the reference's potential at
  that draw.  It covers the density (the fused GLM kernel) and ties each
  stored draw to the value the sampler accepted it with, so a draw altered
  after it was produced shows here.
- ``grad_gap``: the widest relative gap ``|g - g_ref| / |g_ref|`` between
  the gradient the integrator carried in each chain's final state and the
  reference's gradient there.
- ``virial_z``: whether the draws follow the posterior.  For draws from
  ``exp(-U)`` over D coordinates, integration by parts gives
  ``E[(z - c) . grad U] / D = 1`` for any fixed ``c`` (the fit's mean
  draw here), with no knowledge of the posterior beyond ``grad U``.  The
  number is the distance of the draws' mean from 1 in Monte Carlo
  standard errors, the error taken from the series' own effective sample
  size.  A tree that picks its draw with the wrong weights, or momenta of
  the wrong size, samples a hotter or colder distribution and moves it
  where the density is right.
- ``failed_fits``: fits that raised, returned a non-finite draw, or left a
  chain where it was for all its kept draws (a transition that returns its
  state unchanged).  Its limit is 0.

The control puts the reference computed one precision lower (bfloat16) in
the program's place and reads the same numbers against the f32 reference.
"""
from __future__ import annotations

import numpy as np

from .ess import effective_sample_size

NAMES = ("pe_gap", "grad_gap", "virial_z")


def fit_failure(rec):
    """Why a fit's answer cannot be used, or None."""
    if rec.error is not None:
        return rec.error
    z = np.asarray(rec.z)
    if not np.all(np.isfinite(z)):
        return "non-finite draws"
    if not np.all(np.isfinite(np.asarray(rec.pe))):
        return "non-finite potential energy"
    frozen = np.all(z == z[:, :1], axis=(1, 2))
    if np.any(frozen):
        return f"chains {np.flatnonzero(frozen).tolist()} never moved"
    return None


def _widest(gaps):
    """The largest gap; one that is not a number counts as infinite."""
    gaps = np.asarray(gaps, np.float64)
    return float(np.max(np.where(np.isnan(gaps), np.inf, gaps)))


def virial_z(z, g):
    """Distance of the virial mean from 1, in standard errors, of draws
    ``z`` (chains, draws, D) with the reference's ``grad U`` at them, ``g``
    of the same shape."""
    v = np.mean((z - z.mean(axis=(0, 1))) * g, axis=-1)     # (chains, draws)
    se = v.std() / np.sqrt(effective_sample_size(v))
    return _widest(np.abs(v.mean() - 1.0) / se if se > 0 else np.nan)


def gaps(reference, inputs, fits, control=False):
    """The numbers of ``NAMES`` over ``fits``, the widest of each: of the
    program against the reference, or with ``control`` of the bfloat16
    reference in the program's place."""
    out = dict.fromkeys(NAMES, 0.0)
    for rec in fits:
        z = np.asarray(rec.z, np.float64)
        flat = z.reshape(-1, z.shape[-1])
        u_ref, g_ref = reference.potential_and_grad(inputs, flat)
        last_z = np.asarray(rec.last_z)
        _, gl_ref = reference.potential_and_grad(inputs, last_z)
        if control:
            u, g = reference.potential_and_grad(inputs, flat, "bfloat16")
            _, gl = reference.potential_and_grad(inputs, last_z, "bfloat16")
        else:
            u, g, gl = np.asarray(rec.pe).reshape(-1), g_ref, rec.last_grad
        gl = np.asarray(gl, np.float64)
        rel = np.linalg.norm(gl - gl_ref, axis=-1) / np.linalg.norm(gl_ref,
                                                                    axis=-1)
        fit = {"pe_gap": _widest(np.abs(np.asarray(u, np.float64) - u_ref)),
               "grad_gap": _widest(rel),
               "virial_z": virial_z(z, g.reshape(z.shape))}
        out = {k: _widest([out[k], fit[k]]) for k in NAMES}
    return out


def judge(numbers, limits):
    """``(correct, rows)``: each number beside its limit.  A number that is
    not finite fails."""
    rows = {}
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok &= good
        rows[name] = {"value": value if np.isfinite(value) else str(value),
                      "limit": limit}
    return ok, rows
