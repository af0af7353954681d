"""Fused GLM potential + gradient (the logreg / CoverType hot path).

The paper's logistic-regression benchmark spends its whole budget in the
potential and its VJP: XLA emits one pass over the (n, d) design matrix for
the forward log-density and a second (plus an n-vector residual chain) for
the backward.  Both reductions consume the *same* residual against the same
``x``, so one HBM read of the design matrix can serve value AND gradient —
that is what this kernel does.  The grid walks n-tiles; each tile computes
its logits on the MXU, masks the rows past ``n``, and accumulates a scalar
nll and a (1, d) gradient row into the (sequential) grid outputs.

Supported families mirror the model-side detection in
``repro.core.infer.glm``: ``bernoulli_logit`` (exact negation of
``Bernoulli.log_prob``) and ``normal`` (constant noise scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HALF_LOG_2PI = 0.5 * 1.8378770664093453
BLOCK_N = 2048
_F32 = jax.lax.Precision.HIGHEST


def _kernel(scale_ref, x_ref, y_ref, off_ref, w_ref, nll_ref, grad_ref, *,
            family, bn, n):
    i = pl.program_id(0)
    # the last tile may run past row n: what it reads there is undefined,
    # so those rows are zeroed before they reach a sum or a product
    row = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    valid = row < n
    x = jnp.where(valid, x_ref[...].astype(jnp.float32), 0.0)  # (bn, d)
    y = jnp.where(valid, y_ref[...].astype(jnp.float32), 0.0)  # (bn, 1)
    off = jnp.where(valid, off_ref[...].astype(jnp.float32), 0.0)
    w = w_ref[...].astype(jnp.float32)                         # (d, 1)
    # full f32 on the MXU: Mosaic's default may multiply f32 in bf16
    logits = jax.lax.dot(x, w, precision=_F32) + off
    if family == "bernoulli_logit":
        terms = jax.nn.softplus(logits) - y * logits
        resid = jax.nn.sigmoid(logits) - y
    else:  # normal
        s = scale_ref[0, 0].astype(jnp.float32)
        zsc = (logits - y) / s
        terms = 0.5 * zsc * zsc + jnp.log(s) + _HALF_LOG_2PI
        resid = (logits - y) / (s * s)
    terms = jnp.where(valid, terms, 0.0)
    resid = jnp.where(valid, resid, 0.0)
    part_nll = jnp.sum(terms).reshape(1, 1)
    part_grad = jax.lax.dot_general(                           # x^T @ resid
        resid, x, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_F32)                                        # (1, d)

    @pl.when(i == 0)
    def _init():
        nll_ref[...] = jnp.zeros_like(nll_ref)
        grad_ref[...] = jnp.zeros_like(grad_ref)

    nll_ref[...] += part_nll.astype(nll_ref.dtype)
    grad_ref[...] += part_grad.astype(grad_ref.dtype)


def glm_potential_grad(x, y, w, offset=None, scale=None,
                       family="bernoulli_logit", *, block_n=BLOCK_N,
                       interpret=False):
    """x: (n, d)  y: (n,)  w: (d,) -> (nll scalar, grad (d,)) in one pass.

    ``offset`` shifts the linear predictor (None = 0); ``scale`` is the
    Normal noise scale (ignored for bernoulli_logit).  ``block_n`` is the
    n-tile size, a multiple of 8 — tuning only, trailing-defaulted
    (RPL202).  The kernel reads ``x`` where it lies: a tile spans all ``d``
    columns, and the last tile masks the rows past ``n``, so no padded copy
    of the design matrix is made per call.
    """
    if family not in ("bernoulli_logit", "normal"):
        raise ValueError(f"unknown GLM family: {family!r}")
    n, d = x.shape
    bn = n if n <= block_n else block_n
    offset = jnp.zeros((n,), jnp.float32) if offset is None else offset
    scale_arr = jnp.asarray(1.0 if scale is None else scale,
                            jnp.float32).reshape(1, 1)
    nll, grad = pl.pallas_call(
        functools.partial(_kernel, family=family, bn=bn, n=n),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),          # scale
            pl.BlockSpec((bn, d), lambda i: (i, 0)),         # x tile
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),         # y tile
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),         # offset tile
            pl.BlockSpec((d, 1), lambda i: (0, 0)),          # w (full)
        ],
        out_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret, name="glm_potential_grad",
    )(scale_arr, x, y.reshape(-1, 1), offset.reshape(-1, 1), w.reshape(-1, 1))
    return nll[0, 0].astype(w.dtype), grad[0].astype(w.dtype)


def glm_potential_partials(x, y, w, offset=None, scale=None,
                           family="bernoulli_logit", *, data_shards=1):
    """Per-shard partials of the fused GLM potential: split the n rows into
    ``data_shards`` equal shards and run the one-pass kernel on each.

    Returns ``(vals, grads)`` with shapes ``(S,)`` / ``(S, d)`` — row ``i``
    is exactly ``glm_potential_grad`` of shard ``i``.  The loop is unrolled
    so every shard executes the *same* unbatched subgraph: a device holding
    ``k`` of the ``S`` shards under ``shard_map`` emits the identical
    per-shard ops as a device holding all of them, which is what makes
    folding the stacked rows with ``hmc_util.chain_sum`` bit-identical for
    every data-axis layout (see ``repro.core.infer.glm``).
    """
    from . import ops
    n, _ = x.shape
    S = int(data_shards)
    if n % S != 0:
        raise ValueError(
            f"n={n} rows do not split into data_shards={S} equal shards")
    m = n // S
    offset = jnp.zeros((n,), jnp.float32) if offset is None else offset
    xs = x.reshape(S, m, x.shape[1])
    ys = y.reshape(S, m)
    offs = offset.reshape(S, m)
    vals, grads = [], []
    for i in range(S):
        v, g = ops.glm_potential_grad(xs[i], ys[i], w, offs[i], scale,
                                      family)
        vals.append(v)
        grads.append(g)
    return jnp.stack(vals), jnp.stack(grads)
