"""Fused GLM potential + gradient (the logreg / CoverType hot path).

The paper's logistic-regression benchmark spends its whole budget in the
potential and its VJP: XLA emits one pass over the (n, d) design matrix for
the forward log-density and a second (plus an n-vector residual chain) for
the backward.  Both reductions consume the *same* residual against the same
``x``, so one HBM read of the design matrix can serve value AND gradient —
that is what these kernels do.

- ``glm_potential_grad_slab`` serves C chains at once from the design slab
  (``glm_slab``: x transposed, with y and the offset in its sublane padding,
  laid out once at setup).  Its grid walks n-tiles only, so each tile is
  read from HBM once for every chain; the fused potential's chain ``vmap``
  becomes one such call (``repro.core.infer.glm``).
- ``glm_potential_grad`` serves one chain from an (n, d) design matrix, as
  the data-sharded potential calls it on each shard.  The grid walks
  n-tiles; each tile computes its logits on the MXU, masks the rows past
  ``n``, and accumulates a scalar nll and a (1, d) gradient row into the
  (sequential) grid outputs.

Supported families mirror the model-side detection in
``repro.core.infer.glm``: ``bernoulli_logit`` (exact negation of
``Bernoulli.log_prob``) and ``normal`` (constant noise scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HALF_LOG_2PI = 0.5 * 1.8378770664093453
BLOCK_N = 2048
BLOCK_N_SLAB = 8192
_LANES = 128
_TILE_VALUES = 1 << 18
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


def glm_slab(x, y, offset=None):
    """The design slab the chain-batched kernel reads: ``x`` transposed so
    the n rows lie along the lanes, with ``y`` and the offset in the
    sublane padding.

    Shape ``(round_up(d + 2, 8), n)`` f32: rows ``[0, d)`` hold ``x.T``,
    row ``d`` holds ``y``, row ``d + 1`` the offset (zeros without one),
    and the rest are zeros.  Built once, at setup; a (d,) coefficient
    vector extended with 0 at row ``d`` and 1 at row ``d + 1``
    (:func:`_extend`) turns the logits into one product with the slab.
    """
    n, d = x.shape
    rows = -(-(d + 2) // 8) * 8
    offset = jnp.zeros((n,), jnp.float32) if offset is None else offset
    return jnp.concatenate([
        x.T.astype(jnp.float32), y.astype(jnp.float32)[None],
        offset.astype(jnp.float32)[None],
        jnp.zeros((rows - d - 2, n), jnp.float32)])


def _extend(w, rows):
    """(C, d) coefficients -> (C, rows): 0 against y, 1 against the
    offset, 0 against the padding."""
    c, d = w.shape
    return jnp.concatenate([
        w.astype(jnp.float32), jnp.zeros((c, 1), jnp.float32),
        jnp.ones((c, 1), jnp.float32),
        jnp.zeros((c, rows - d - 2), jnp.float32)], axis=1)


def _fold_lanes(a):
    """(r, k * 128) -> (r, 128): the sum of the 128-lane column groups
    (whole vregs, so no data moves between lanes)."""
    out = a[:, :_LANES]
    for j in range(1, a.shape[1] // _LANES):
        out = out + a[:, j * _LANES:(j + 1) * _LANES]
    return out


def _slab_kernel(scale_ref, s_ref, w_ref, nll_ref, grad_ref, nll_acc, *,
                 family, d, bn, n):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        nll_acc[...] = jnp.zeros_like(nll_acc)
        grad_ref[...] = jnp.zeros_like(grad_ref)

    # the last tile may run past column n: what it reads there is
    # undefined, so those columns are zeroed before any product
    col = i * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    valid = col < n
    tile = jnp.where(valid, s_ref[...], 0.0)                  # (rows, bn)
    y = tile[d:d + 1, :]                                       # (1, bn)
    # full f32 on the MXU: Mosaic's default may multiply f32 in bf16
    logits = jax.lax.dot_general(                              # (C, bn)
        w_ref[...], tile, (((1,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)
    if family == "bernoulli_logit":
        # jax.nn's forms: on a TPU v5e a softplus and sigmoid sharing
        # exp(-|l|) and a division left the gradient 3x farther from a
        # float64 one near the posterior
        terms = jax.nn.softplus(logits) - y * logits
        resid = jax.nn.sigmoid(logits) - y
    else:  # normal
        s = scale_ref[0, 0]
        zsc = (logits - y) / s
        terms = 0.5 * zsc * zsc + jnp.log(s) + _HALF_LOG_2PI
        resid = (logits - y) / (s * s)
    # per-lane partial sums, reduced once at the last tile
    nll_acc[...] += _fold_lanes(jnp.where(valid, terms, 0.0))
    grad_ref[...] += jax.lax.dot_general(                      # (C, rows)
        resid, tile, (((1,), (1,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        nll_ref[...] = jnp.sum(nll_acc[...], axis=1, keepdims=True)


def glm_potential_grad_slab(slab, w, scale=None, family="bernoulli_logit",
                            *, block_n=BLOCK_N_SLAB, interpret=False):
    """slab: (rows, n) from :func:`glm_slab`  w: (C, d) -> (nll (C,),
    grad (C, d)): the GLM value and gradient of every chain in one pass.

    The grid walks n-tiles only, so each slab tile is read from HBM once
    and serves all C chains.  A tile's logits lie as (C, tile), n on the
    lanes, so the epilogue runs on dense vregs.  Both products run on the
    MXU in exact f32 (``HIGHEST``): on a TPU v5e at the CoverType size this
    beat f32 multiply-adds on the VPU at every chain count tried, 4 to 64.
    ``nll`` gathers per-lane partial sums across the tiles, reduced once
    at the last tile.  ``block_n`` is the widest n-tile, a multiple of
    128 -- tuning only, trailing-defaulted (RPL202); many chains take a
    narrower one, so that a tile's (C, tile) values stay within VMEM.
    """
    if family not in ("bernoulli_logit", "normal"):
        raise ValueError(f"unknown GLM family: {family!r}")
    rows, n = slab.shape
    chains, d = w.shape
    # whole 128-lane groups (the columns past n are masked), at most
    # _TILE_VALUES per (C, tile) value
    bn = min(block_n, pl.cdiv(n, _LANES) * _LANES,
             max(_LANES, _TILE_VALUES // chains // _LANES * _LANES))
    scale_arr = jnp.asarray(1.0 if scale is None else scale,
                            jnp.float32).reshape(1, 1)
    nll, grad = pl.pallas_call(
        functools.partial(_slab_kernel, family=family, d=d, bn=bn, n=n),
        grid=(pl.cdiv(n, bn),),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),      # scale
                  pl.BlockSpec((rows, bn), lambda i: (0, i)),  # slab tile
                  pl.BlockSpec((chains, rows), lambda i: (0, 0))],  # w
        out_specs=[pl.BlockSpec((chains, 1), lambda i: (0, 0)),
                   pl.BlockSpec((chains, rows), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((chains, 1), jnp.float32),
                   jax.ShapeDtypeStruct((chains, rows), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((chains, _LANES), jnp.float32)],
        interpret=interpret, name="glm_potential_grad",
    )(scale_arr, slab, _extend(w, rows))
    return nll[:, 0].astype(w.dtype), grad[:, :d].astype(w.dtype)


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
