"""jit'd dispatch wrappers: one call site for Pallas kernels and jnp oracles.

The route is chosen by the platform at trace time: on a TPU every op that
has a kernel runs its Pallas TPU kernel; everywhere else (the CPU backend,
where Pallas TPU lowering is unavailable) it runs the pure-jnp reference in
:mod:`repro.kernels.ref`.  ``use_pallas(enable, interpret)`` overrides the
choice inside a ``with`` block: ``use_pallas(True, interpret=True)`` is how
the test-suite executes kernel bodies on CPU against the oracles, and
``use_pallas(False)`` runs the oracles on a TPU.

A program that XLA partitions over several devices cannot hold a Pallas TPU
kernel (the compiler cannot split it), so the platform route reads the
mesh of the tracing context: under a mesh of more than one device the ops
take their oracles, except inside a ``shard_map`` body, where every device
runs its own block and the kernels are legal.  A partitioned program
declares its mesh while it is traced (``jax.set_mesh`` or
``jax.sharding.use_abstract_mesh``); the MCMC executor does so for
``chain_method="parallel"``.
"""
from __future__ import annotations

import math
import os
from contextlib import contextmanager
from typing import NamedTuple, Optional, Tuple

import jax

from . import ref

# "pallas": None follows the platform; True/False or a set of op names is a
# use_pallas override
_STATE = {"pallas": None,
          "interpret": False,
          "ssd_inline": os.environ.get("REPRO_SSD_INLINE", "0") == "1"}


class OpSpec(NamedTuple):
    """Declarative registry entry for one dispatched op (consumed by
    :mod:`repro.lint_rules.invariants` and its registry-driven tests).

    ``pallas``/``ref`` are ``(module, attr)`` import paths; ``pallas`` is
    ``None`` for ref-only ops (no kernel exists yet — decode paths).
    ``bit_identical`` ops must agree with their oracle bit-for-bit in
    interpret mode (the enum-contract contract: enumeration results feed
    exact marginalization); others must agree to ``tol`` max-abs error.
    """

    name: str
    pallas: Optional[Tuple[str, str]]
    ref: Tuple[str, str]
    bit_identical: bool
    tol: float


# Every public op this module dispatches, exactly once.  The invariant
# checker (RPL201) asserts this table and the module's public callables
# stay in bijection (minus the _CONTROL context managers below), so a new
# kernel cannot land without a ref oracle and a parity bound.
OP_TABLE = (
    OpSpec("attention", ("repro.kernels.flash_attention", "flash_attention"),
           ("repro.kernels.ref", "attention"), False, 2e-4),
    OpSpec("decode_attention", None,
           ("repro.kernels.ref", "decode_attention"), False, 0.0),
    OpSpec("mla_absorbed_decode", None,
           ("repro.kernels.ref", "mla_absorbed_decode"), False, 0.0),
    OpSpec("leapfrog_halfstep", ("repro.kernels.leapfrog",
                                 "leapfrog_halfstep"),
           ("repro.kernels.leapfrog", "leapfrog_halfstep_ref"), False, 1e-6),
    OpSpec("leapfrog_halfstep_batch", ("repro.kernels.leapfrog",
                                       "leapfrog_halfstep_batch"),
           ("repro.kernels.leapfrog", "leapfrog_halfstep_batch_ref"),
           False, 1e-6),
    OpSpec("glm_potential_grad", ("repro.kernels.glm_potential",
                                  "glm_potential_grad"),
           ("repro.kernels.ref", "glm_potential_grad"), False, 5e-3),
    OpSpec("glm_potential_grad_slab", ("repro.kernels.glm_potential",
                                       "glm_potential_grad_slab"),
           ("repro.kernels.ref", "glm_potential_grad_slab"), False, 5e-3),
    OpSpec("mala_step", ("repro.kernels.rwm_mala", "mala_step"),
           ("repro.kernels.ref", "mala_step"), False, 1e-6),
    OpSpec("enum_contract", ("repro.kernels.enum_contract", "enum_contract"),
           ("repro.kernels.ref", "enum_contract"), True, 0.0),
    OpSpec("enum_contract_chain", ("repro.kernels.enum_contract",
                                   "enum_contract_chain"),
           ("repro.kernels.ref", "enum_contract_chain"), True, 0.0),
    OpSpec("rmsnorm", ("repro.kernels.rmsnorm", "rmsnorm"),
           ("repro.kernels.ref", "rmsnorm"), False, 2e-5),
    OpSpec("softmax_xent", ("repro.kernels.softmax_xent", "softmax_xent"),
           ("repro.kernels.ref", "softmax_xent"), False, 1e-4),
    OpSpec("ssd_scan", ("repro.kernels.ssd_scan", "ssd_scan"),
           ("repro.kernels.ref", "ssd_scan"), False, 1e-4),
    OpSpec("ssd_decode_step", None,
           ("repro.kernels.ref", "ssd_decode_step"), False, 0.0),
)

# public callables that are dispatch *controls*, not ops
_CONTROL = frozenset({"use_pallas", "pallas_enabled", "ssd_inline"})


@contextmanager
def use_pallas(enable=True, interpret=False):
    """Override the platform route inside the block: ``True`` runs every
    op's kernel, ``False`` every oracle, and a collection of op names runs
    those ops' kernels and the oracles of the rest."""
    if not isinstance(enable, bool):
        enable = frozenset(enable)
    old = dict(_STATE)
    _STATE.update(pallas=enable, interpret=interpret)
    try:
        yield
    finally:
        _STATE.update(old)


# Ops whose kernels the TPU compiler refuses (blocks that are not (8, 128)
# tile-aligned, an operand layout Mosaic rejects) or that were never
# compiled for the chip (rmsnorm: a row count that is not a multiple of its
# block, a misaligned backward block): the platform route keeps them on
# their oracle until the kernels are fixed, and only an explicit
# use_pallas(True) runs them.
_REFUSED_ON_TPU = frozenset({"attention", "rmsnorm", "softmax_xent",
                             "ssd_scan"})


def _partitioned():
    """Whether the trace belongs to a program XLA partitions over several
    devices: the tracing context's mesh spans more than one device along
    axes that are not manual (a ``shard_map`` body's axes are)."""
    mesh = jax.sharding.get_abstract_mesh()
    return math.prod(size for name, size in mesh.shape.items()
                     if name not in mesh.manual_axes) > 1


def pallas_enabled(op):
    """Whether ``op``, traced now, dispatches to its Pallas kernel."""
    enable = _STATE["pallas"]
    if enable is None:
        return (jax.default_backend() == "tpu" and op not in _REFUSED_ON_TPU
                and not _partitioned())
    if isinstance(enable, bool):
        return enable
    return op in enable


# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal=True, scale=None, window=0):
    if pallas_enabled("attention"):
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window, interpret=_STATE["interpret"])
    return ref.attention(q, k, v, causal=causal, scale=scale, window=window)


def decode_attention(q, k, v, mask, *, scale=None):
    return ref.decode_attention(q, k, v, mask, scale=scale)


def mla_absorbed_decode(q_nope, q_rope, c_kv, k_rope, wk, wv, mask, *, scale):
    return ref.mla_absorbed_decode(q_nope, q_rope, c_kv, k_rope, wk, wv,
                                   mask, scale=scale)


def leapfrog_halfstep(z, r, grad, m_inv, eps):
    """Fused momentum half-step + position full-step of velocity Verlet
    (diagonal mass).  One HBM pass under Pallas; jnp reference otherwise."""
    if pallas_enabled("leapfrog_halfstep"):
        from .leapfrog import leapfrog_halfstep as _k
        return _k(z, r, grad, m_inv, eps, interpret=_STATE["interpret"])
    from .leapfrog import leapfrog_halfstep_ref
    return leapfrog_halfstep_ref(z, r, grad, m_inv, eps)


def leapfrog_halfstep_batch(z, r, grad, m_inv, eps, kick=0.5):
    """Chain-batched leapfrog kick+drift over a (C, D) ensemble (the ChEES
    lockstep path).  ``kick=0.5`` is the classic half-kick; ``kick=1.0``
    fuses the two adjacent half-kicks between interior trajectory steps.
    One (C, D)-blocked HBM pass under Pallas; jnp reference otherwise."""
    if pallas_enabled("leapfrog_halfstep_batch"):
        from .leapfrog import leapfrog_halfstep_batch as _k
        return _k(z, r, grad, m_inv, eps, kick,
                  interpret=_STATE["interpret"])
    from .leapfrog import leapfrog_halfstep_batch_ref
    return leapfrog_halfstep_batch_ref(z, r, grad, m_inv, eps, kick)


def glm_potential_grad(x, y, w, offset=None, scale=None,
                       family="bernoulli_logit"):
    """Fused GLM negative log-likelihood + gradient wrt ``w`` in one pass
    over the (n, d) design matrix (each shard of the data-sharded fused
    potential).  Under Pallas one HBM read of ``x`` serves value AND
    grad."""
    if pallas_enabled("glm_potential_grad"):
        from .glm_potential import glm_potential_grad as _k
        return _k(x, y, w, offset, scale, family,
                  interpret=_STATE["interpret"])
    return ref.glm_potential_grad(x, y, w, offset, scale, family)


def glm_potential_grad_slab(slab, w, scale=None, family="bernoulli_logit"):
    """``glm_potential_grad`` of C coefficient rows ``w`` (C, d) at once,
    over the design slab (``glm_potential.glm_slab``: the design matrix
    transposed, the observations and offset in its padding rows).  Under
    Pallas one HBM read of each slab tile serves every row's value AND
    grad."""
    if pallas_enabled("glm_potential_grad_slab"):
        from .glm_potential import glm_potential_grad_slab as _k
        return _k(slab, w, scale, family, interpret=_STATE["interpret"])
    return ref.glm_potential_grad_slab(slab, w, scale, family)


def mala_step(z, grad, noise, m_inv, eps):
    """Batched Langevin proposal over a (C, D) ensemble; ``grad=None``
    gives the symmetric random-walk proposal.  One (C, D)-blocked HBM
    pass under Pallas; jnp reference otherwise."""
    if pallas_enabled("mala_step"):
        from .rwm_mala import mala_step as _k
        return _k(z, grad, noise, m_inv, eps, interpret=_STATE["interpret"])
    return ref.mala_step(z, grad, noise, m_inv, eps)


def enum_contract(log_alpha, log_mat):
    """Logsumexp chain-elimination step of discrete enumeration:
    ``out[..., j] = logsumexp_i(log_alpha[..., i] + log_mat[..., i, j])``.
    One VMEM pass under Pallas; stabilized jnp reference otherwise."""
    if pallas_enabled("enum_contract"):
        from .enum_contract import enum_contract as _k
        return _k(log_alpha, log_mat, interpret=_STATE["interpret"])
    return ref.enum_contract(log_alpha, log_mat)


def enum_contract_chain(alpha0, unary, pair, keep):
    """Log partition of every row of a batched chain (the forward
    algorithm of a plate-batched ``markov``): the whole chain in one
    kernel call and its gradient in one more under Pallas; one
    ``enum_contract`` per step otherwise."""
    if pallas_enabled("enum_contract_chain"):
        from .enum_contract import enum_contract_chain as _k
        return _k(alpha0, unary, pair, keep, interpret=_STATE["interpret"])
    return ref.enum_contract_chain(alpha0, unary, pair, keep)


def rmsnorm(x, weight, eps=1e-6):
    if pallas_enabled("rmsnorm"):
        from .rmsnorm import rmsnorm as _k
        return _k(x, weight, eps=eps, interpret=_STATE["interpret"])
    return ref.rmsnorm(x, weight, eps=eps)


def softmax_xent(x, w_unembed, labels, *, z_loss_weight=0.0):
    if pallas_enabled("softmax_xent"):
        from .softmax_xent import softmax_xent as _k
        return _k(x, w_unembed, labels, z_loss_weight=z_loss_weight,
                  interpret=_STATE["interpret"])
    return ref.softmax_xent(x, w_unembed, labels, z_loss_weight=z_loss_weight)


def ssd_scan(x, dt, A, B, C, *, chunk, D=None, h0=None):
    if pallas_enabled("ssd_scan"):
        from .ssd_scan import ssd_scan as _k
        return _k(x, dt, A, B, C, chunk=chunk, D=D, h0=h0,
                  interpret=_STATE["interpret"])
    if _STATE["ssd_inline"]:
        return ref.ssd_scan_inline(x, dt, A, B, C, chunk=chunk, D=D, h0=h0)
    return ref.ssd_scan(x, dt, A, B, C, chunk=chunk, D=D, h0=h0)


@contextmanager
def ssd_inline(enable=True):
    old = _STATE["ssd_inline"]
    _STATE["ssd_inline"] = enable
    try:
        yield
    finally:
        _STATE["ssd_inline"] = old


def ssd_decode_step(state, x, dt, A, B, C, *, D=None):
    return ref.ssd_decode_step(state, x, dt, A, B, C, D=D)
