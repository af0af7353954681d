"""Plain reference of jsb_hmm, in jax.numpy, independent of the program.

Potential (negative log density, in the unconstrained coordinates NUTS
moves) of NumPyro's ``hmm_enum.py`` ``model_1``:

    probs_x[k] = stick_breaking(u_x[k])      ~ Dirichlet(transition_prior[k])
    probs_y    = sigmoid(u_y)                ~ Beta(a, b), each entry
    x_0 ~ Cat(probs_x[0]),  x_t ~ Cat(probs_x[x_{t-1}]),
    y_{t,d} ~ Bernoulli(probs_y[x_t, d]),    t < L_s, for each sequence s

    U(u) = -[log p(probs) + log|J(u)| + sum_s log sum_x p(y_s, x)]

with NumPyro's conventions for numbers at the ends of [0, 1]: a sigmoid
(and each stick) is held in ``[tiny, 1 - eps]`` of float32 and every
simplex entry at or above ``tiny``, so a Beta or Dirichlet with a
concentration below 1 never reads log(0); a probability a Bernoulli or
Categorical scores is held in ``[eps, 1 - eps]`` (the Categorical's row
then renormalised).  The log Jacobians are those of the unclipped maps.

The hidden path is summed by the scaled forward algorithm, in
probabilities, each sequence over its own length (a step past it holds
the message).  ``z`` is flattened as the
program flattens it: ``u_x`` (K, K - 1) then ``u_y`` (K, D), row-major.
The reference runs on the host's CPU in float64, away from the device
under test, for many draws at once, in blocks of draws and of sequences
whose partial sums are added on the host.  The clips are those of the
configuration's float32 (NumPyro's, at the precision the model is run
in).  ``dtype="bfloat16"`` computes everything in bfloat16, clips
included: the control, one precision below the configuration's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import gammaln

DRAWS = 32         # draws per block
SEQUENCES = 32     # sequences per block
CLIPS = jnp.float32  # the precision whose clips the model is run with


def _unpack(z, k, d):
    ux = z[..., :k * (k - 1)].reshape(z.shape[:-1] + (k, k - 1))
    uy = z[..., k * (k - 1):].reshape(z.shape[:-1] + (k, d))
    return ux, uy


def _finfo(dtype):
    """The clips of the configuration's precision, or of a coarser one."""
    return jnp.finfo(dtype if jnp.finfo(dtype).bits < 32 else CLIPS)


def _held(p):
    """A probability as the model holds it: the nearest number of the
    configuration's float32 (the bfloat16 control is coarser already).
    Near 1, f32 spaces its numbers 6e-8 apart, so ``1 - p`` of p = 1 - 6e-6
    moves by 1 % from one to the next, and the model's ``log(1 - p)``,
    reused at every step, is that of the f32 number."""
    if jnp.finfo(p.dtype).bits <= 32:
        return p
    return p.astype(CLIPS).astype(p.dtype)


def _unit(v, held=False):
    finfo = _finfo(v.dtype)
    s = jax.nn.sigmoid(v)
    return jnp.clip(_held(s) if held else s, finfo.tiny, 1 - finfo.eps)


def _stick_breaking(u):
    """R^{K-1} -> the K-simplex, ``s_k = sigmoid(u_k - log(K - 1 - k))``,
    ``p_k = s_k prod_{i<k} (1 - s_i)``; and log|J|."""
    km1 = u.shape[-1]
    v = u - jnp.log(km1 - jnp.arange(km1, dtype=u.dtype))
    s = _unit(v)
    rest = jnp.cumprod(1 - s, axis=-1)
    before = jnp.concatenate([jnp.ones_like(rest[..., :1]), rest[..., :-1]],
                             axis=-1)
    p = jnp.concatenate([s * before, rest[..., -1:]], axis=-1)
    p = jnp.maximum(p, _finfo(p.dtype).tiny)
    log_1ms = jax.nn.log_sigmoid(-v)
    log_before = jnp.concatenate([jnp.zeros_like(v[..., :1]),
                                  jnp.cumsum(log_1ms, axis=-1)[..., :-1]],
                                 axis=-1)
    log_jac = jnp.sum(jax.nn.log_sigmoid(v) + log_1ms + log_before, axis=-1)
    return p, log_jac


def _scored(p):
    eps = _finfo(p.dtype).eps
    return jnp.clip(p, eps, 1 - eps)


def _prior(ux, uy, conc, beta):
    """log p(probs) + log|J| of one draw; and the log tables the
    likelihood scores: log probs_x (K, K), log probs_y, log(1 - probs_y)."""
    px, jac_x = _stick_breaking(ux)                               # (K, K)
    px = _held(px)
    py = _unit(uy, held=True)                                     # (K, D)
    a, b = beta[0], beta[1]
    lp = jnp.sum(gammaln(conc.sum(-1)) - gammaln(conc).sum(-1)
                 + ((conc - 1) * jnp.log(px)).sum(-1) + jac_x)
    lp += jnp.sum((a - 1) * jnp.log(py) + (b - 1) * jnp.log1p(-py)
                  + gammaln(a + b) - gammaln(a) - gammaln(b)
                  + jax.nn.log_sigmoid(uy) + jax.nn.log_sigmoid(-uy))
    qx, qy = _scored(px), _scored(py)
    log_qx = jnp.log(qx) - jnp.log(qx.sum(-1, keepdims=True))
    return lp, log_qx, jnp.log(qy), jnp.log1p(-qy)


def _log_likelihood(log_px, log_py, log_1my, y, lengths):
    """sum over the block's sequences of log p(y_s): the scaled forward
    algorithm, in probabilities, each step's message rescaled to sum to 1
    and the scales' logs summed."""
    emit = (jnp.einsum("std,kd->stk", y, log_py - log_1my)
            + jnp.sum(log_1my, axis=-1))                         # (S, T, K)
    top = jnp.max(emit, axis=-1)                                 # (S, T)
    e = jnp.exp(emit - top[..., None])
    px = jnp.exp(log_px)

    def rescaled(a):
        c = jnp.sum(a, axis=-1)
        return a / c[:, None], jnp.log(c)

    alpha, log_c = rescaled(px[0] * e[:, 0])

    def step(carry, inp):
        alpha, log_z = carry
        e_t, top_t, t = inp
        new, log_c = rescaled((alpha @ px) * e_t)
        live = t < lengths
        return (jnp.where(live[:, None], new, alpha),
                jnp.where(live, log_z + log_c + top_t, log_z)), None

    T = y.shape[1]
    (_, log_z), _ = jax.lax.scan(
        step, (alpha, log_c + top[:, 0]),
        (jnp.swapaxes(e, 0, 1)[1:], top.T[1:], jnp.arange(1, T)))
    return log_z                                                 # (S,)


@functools.partial(jax.jit, static_argnames=("with_prior", "dtype"))
def _block(z, y, lengths, live, conc, beta, with_prior, dtype):
    """U partials (m,) and grad partials (m, D) over a block of draws and
    sequences; ``live`` zeroes the padding sequences of a last block."""
    dt = jnp.dtype(dtype)
    k, d = conc.shape[0], y.shape[-1]

    def u(zz):
        ux, uy = _unpack(zz.astype(dt), k, d)
        lp, log_px, log_py, log_1my = _prior(ux, uy, conc.astype(dt),
                                             beta.astype(dt))
        ll = _log_likelihood(log_px, log_py, log_1my, y.astype(dt), lengths)
        out = -jnp.sum(jnp.where(live, ll, 0))
        return out - lp if with_prior else out

    val, grad = jax.vmap(jax.value_and_grad(u))(z)
    return val.astype(jnp.float64), grad.astype(jnp.float64)


def _pad(a, n, axis=0):
    extra = n - a.shape[axis]
    if extra == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, extra)
    return np.pad(a, widths)


def potential_and_grad(inputs, z, dtype="float64"):
    """U and grad U at each row of ``z`` (m, D), as float64 numpy arrays,
    computed on the host's CPU in ``dtype``."""
    host = {k: np.asarray(v) for k, v in inputs.items()}
    y, lengths = host["sequences"].astype(np.float64), host["lengths"]
    conc = host["transition_prior"].astype(np.float64)
    beta = host["emission_prior"].astype(np.float64)
    z = np.asarray(z, np.float32).astype(np.float64)
    m, s = z.shape[0], y.shape[0]
    u = np.zeros(m)
    grad = np.zeros(z.shape, np.float64)
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        for d0 in range(0, m, DRAWS):
            zb = _pad(z[d0:d0 + DRAWS], DRAWS)
            for s0 in range(0, s, SEQUENCES):
                n = min(SEQUENCES, s - s0)
                part_u, part_g = _block(
                    zb, _pad(y[s0:s0 + n], SEQUENCES),
                    _pad(lengths[s0:s0 + n], SEQUENCES),
                    np.arange(SEQUENCES) < n, conc, beta,
                    with_prior=s0 == 0, dtype=dtype)
                rows = min(DRAWS, m - d0)
                u[d0:d0 + rows] += np.asarray(part_u)[:rows]
                grad[d0:d0 + rows] += np.asarray(part_g)[:rows]
    return u, grad
