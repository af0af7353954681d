"""Pure-jnp reference oracles for every Pallas kernel.

These are the semantics; kernels in this package must match them to
float tolerance (tests sweep shapes/dtypes in ``interpret=True`` mode).
They are also the default execution path on CPU and for the dry-run
(Pallas TPU lowering is unavailable on the CPU backend).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# attention (GQA-aware; covers MHA/MQA/MLA-shaped q/k/v)
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal=True, scale=None, window=0):
    """q: (B,S,H,dq)  k: (B,S,K,dq)  v: (B,S,K,dv)  with H % K == 0.

    Returns (B,S,H,dv). Softmax in fp32. ``window`` > 0 gives sliding-window
    (local) attention over the last ``window`` positions.
    """
    B, S, H, dq = q.shape
    K = k.shape[2]
    G = H // K
    scale = (dq ** -0.5) if scale is None else scale
    qg = q.reshape(B, S, K, G, dq)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        mask = j <= i
        if window:
            mask = mask & (j > i - window)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def decode_attention(q, k, v, mask, *, scale=None):
    """Single-query attention against a full cache.

    q: (B,1,H,dq)  k: (B,S,K,dq)  v: (B,S,K,dv)  mask: (1|B, S) bool.
    Returns (B,1,H,dv).
    """
    B, _, H, dq = q.shape
    K = k.shape[2]
    G = H // K
    scale = (dq ** -0.5) if scale is None else scale
    qg = q.reshape(B, K, G, dq)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, v.shape[-1]).astype(q.dtype)


def mla_absorbed_decode(q_nope, q_rope, c_kv, k_rope, wk, wv, mask, *, scale):
    """Absorbed-matmul MLA decode (DeepSeek-V3 trick): never expand k/v.

    q_nope: (B,1,H,dn)  q_rope: (B,1,H,dr)  c_kv: (B,S,r)  k_rope: (B,S,dr)
    wk: (H,dn,r) k-expansion  wv: (H,r,dv) v-expansion.  Returns (B,1,H,dv).
    """
    ql = jnp.einsum("bqhn,hnr->bqhr", q_nope.astype(jnp.float32),
                    wk.astype(jnp.float32))
    s_lat = jnp.einsum("bqhr,bsr->bhqs", ql, c_kv.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                        k_rope.astype(jnp.float32))
    scores = (s_lat + s_rope) * scale
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", p, c_kv.astype(jnp.float32))
    out = jnp.einsum("bqhr,hrv->bqhv", o_lat, wv.astype(jnp.float32))
    return out.astype(q_nope.dtype)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

def rmsnorm(x, weight, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise softmax cross-entropy over a large vocab
# ---------------------------------------------------------------------------

def softmax_xent(x, w_unembed, labels, *, z_loss_weight=0.0):
    """x: (T,d)  w_unembed: (d,V)  labels: (T,) int32.

    Returns (ce (T,), z_loss (T,)) in fp32 without keeping (T,V) fp32 logits
    live (the Pallas kernel streams vocab blocks through VMEM).
    """
    logits = (x.astype(jnp.float32) @ w_unembed.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = lse - ll
    zl = z_loss_weight * lse ** 2 if z_loss_weight else jnp.zeros_like(ce)
    return ce, zl


# ---------------------------------------------------------------------------
# Mamba-2 SSD chunked scan
# ---------------------------------------------------------------------------

def ssd_scan_inline(x, dt, A, B, C, *, chunk, D=None, h0=None):
    """SSD with the entering-state contribution computed INSIDE the chunk
    scan (what the Pallas kernel does): the (nc, b, h, p, n) stacked-states
    buffer never round-trips through HBM.  Same math as :func:`ssd_scan`
    (§Perf mamba2 hillclimb — identical outputs, lower memory traffic)."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0
    nc = l // chunk
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)

    xc = x.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Bc = Bh.reshape(b, nc, chunk, h, n).astype(jnp.float32)
    Cc = Ch.reshape(b, nc, chunk, h, n).astype(jnp.float32)
    Af = A.astype(jnp.float32)
    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]

    def body(state, inp):
        xk, dtk, Bk, Ck = inp                          # (b, c, h, ...)
        dA = dtk * Af
        cum = jnp.cumsum(dA, axis=1)
        seg = cum[:, :, None, :] - cum[:, None, :, :]
        L = jnp.where(causal[None, :, :, None], jnp.exp(seg), 0.0)
        scores = jnp.einsum("bihn,bjhn->bijh", Ck, Bk) * L
        y = jnp.einsum("bijh,bjh,bjhp->bihp", scores, dtk, xk)
        y += jnp.einsum("bchn,bhpn,bch->bchp", Ck, state, jnp.exp(cum))
        dec = jnp.exp(cum[:, -1:, :] - cum)
        upd = jnp.einsum("bch,bch,bchn,bchp->bhpn", dtk, dec, Bk, xk)
        state = state * jnp.exp(cum[:, -1, :])[..., None, None] + upd
        return state, y

    init = (jnp.zeros((b, h, p, n), jnp.float32) if h0 is None
            else h0.astype(jnp.float32))
    final, ys = jax.lax.scan(
        body, init,
        (xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
         Bc.transpose(1, 0, 2, 3, 4), Cc.transpose(1, 0, 2, 3, 4)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, l, h, p)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] \
            * x.astype(jnp.float32)
    return y.astype(x.dtype), final


def ssd_scan(x, dt, A, B, C, *, chunk, D=None, h0=None):
    """State-space-duality forward (Mamba-2, arXiv:2405.21060 Alg 1).

    x:  (b, l, h, p)  inputs per head
    dt: (b, l, h)     softplus'd step sizes (>=0)
    A:  (h,)          negative decay rates (A < 0)
    B:  (b, l, g, n)  input projections (g groups broadcast over heads)
    C:  (b, l, g, n)  output projections
    Returns (y (b,l,h,p), final_state (b,h,p,n)).
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0
    nc = l // chunk
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)                  # (b,l,h,n)
    Ch = jnp.repeat(C, rep, axis=2)

    xc = x.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Bc = Bh.reshape(b, nc, chunk, h, n).astype(jnp.float32)
    Cc = Ch.reshape(b, nc, chunk, h, n).astype(jnp.float32)

    dA = dtc * A.astype(jnp.float32)                 # (b,nc,c,h) log-decay <= 0
    cum = jnp.cumsum(dA, axis=2)                     # within-chunk cumulative

    # --- intra-chunk (quadratic in `chunk`, MXU-shaped) -------------------
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (b,nc,ci,cj,h)
    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("bzihn,bzjhn->bzijh", Cc, Bc) * L
    y_diag = jnp.einsum("bzijh,bzjh,bzjhp->bzihp", scores, dtc, xc)

    # --- chunk states + inter-chunk recurrence ----------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)       # (b,nc,c,h)
    states = jnp.einsum("bzch,bzch,bzchn,bzchp->bzhpn",
                        dtc, decay_to_end, Bc, xc)        # (b,nc,h,p,n)
    chunk_decay = jnp.exp(cum[:, :, -1, :])               # (b,nc,h)

    def step(carry, inp):
        s_prev = carry
        s_chunk, dec = inp
        s_new = s_prev * dec[..., None, None] + s_chunk
        return s_new, s_prev

    init = (jnp.zeros((b, h, p, n), jnp.float32) if h0 is None
            else h0.astype(jnp.float32))
    final, prev_states = jax.lax.scan(
        step, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)    # (b,nc,h,p,n)

    # --- contribution of entering state to each position ------------------
    state_decay = jnp.exp(cum)                            # (b,nc,c,h)
    y_off = jnp.einsum("bzchn,bzhpn,bzch->bzchp", Cc, prev_states, state_decay)

    y = (y_diag + y_off).reshape(b, l, h, p)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), final


def ssd_decode_step(state, x, dt, A, B, C, *, D=None):
    """One-token SSD update. state: (b,h,p,n); x: (b,h,p); dt: (b,h);
    B,C: (b,g,n). Returns (y (b,h,p), new_state)."""
    b, h, p = x.shape
    g = B.shape[1]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32))             # (b,h)
    new = state * dA[..., None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dtf, xf, Bh)
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x.dtype), new


# ---------------------------------------------------------------------------
# fused GLM potential + gradient (logreg / CoverType hot path)
# ---------------------------------------------------------------------------

_HALF_LOG_2PI = 0.5 * 1.8378770664093453


def glm_potential_grad(x, y, w, offset=None, scale=None,
                       family="bernoulli_logit"):
    """Negative log-likelihood of a GLM and its gradient wrt ``w``, fused.

    x: (n, d) design matrix  y: (n,) observations  w: (d,) coefficients.
    ``offset`` (n,) shifts the linear predictor; ``scale`` is the Normal
    noise scale (ignored for bernoulli_logit).  Returns ``(nll, grad)``
    with ``nll`` scalar and ``grad`` of shape (d,).

    bernoulli_logit:  nll_i = softplus(l_i) - y_i * l_i
                      (the exact negation of ``Bernoulli.log_prob``)
    normal:           nll_i = 0.5*((l_i-y_i)/scale)^2 + log(scale)
                              + 0.5*log(2*pi)

    The gradient shares the single pass over ``x``: both reduce the same
    residual vector against the design matrix, which is what the Pallas
    kernel exploits (one HBM read of x serves value AND grad).
    """
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    logits = xf @ w.astype(jnp.float32)
    if offset is not None:
        logits = logits + offset.astype(jnp.float32)
    if family == "bernoulli_logit":
        nll = jnp.sum(jax.nn.softplus(logits) - yf * logits)
        resid = jax.nn.sigmoid(logits) - yf
    elif family == "normal":
        s = jnp.asarray(scale, jnp.float32)
        zscore = (logits - yf) / s
        nll = jnp.sum(0.5 * zscore * zscore + jnp.log(s) + _HALF_LOG_2PI)
        resid = (logits - yf) / (s * s)
    else:
        raise ValueError(f"unknown GLM family: {family!r}")
    grad = resid @ xf
    return nll.astype(w.dtype), grad.astype(w.dtype)


def glm_potential_grad_slab(slab, w, scale=None, family="bernoulli_logit"):
    """``glm_potential_grad`` for C coefficient rows at once, over the
    design slab (``repro.kernels.glm_potential.glm_slab``).

    slab: (rows, n), rows ``[0, d)`` the design matrix transposed, row
    ``d`` the observations, row ``d + 1`` the offset, the rest zeros.
    w: (C, d).  Returns ``(nll (C,), grad (C, d))``: row ``c`` is
    ``glm_potential_grad(x, y, w[c], offset, scale, family)``.
    """
    rows, _ = slab.shape
    c, d = w.shape
    sf = slab.astype(jnp.float32)
    # 0 against y, 1 against the offset, 0 against the padding
    we = jnp.concatenate([w.astype(jnp.float32), jnp.zeros((c, 1)),
                          jnp.ones((c, 1)), jnp.zeros((c, rows - d - 2))],
                         axis=1)
    logits = we @ sf
    yf = sf[d]
    if family == "bernoulli_logit":
        nll = jnp.sum(jax.nn.softplus(logits) - yf * logits, axis=1)
        resid = jax.nn.sigmoid(logits) - yf
    elif family == "normal":
        s = jnp.asarray(scale, jnp.float32)
        zscore = (logits - yf) / s
        nll = jnp.sum(0.5 * zscore * zscore + jnp.log(s) + _HALF_LOG_2PI,
                      axis=1)
        resid = (logits - yf) / (s * s)
    else:
        raise ValueError(f"unknown GLM family: {family!r}")
    grad = (resid @ sf.T)[:, :d]
    return nll.astype(w.dtype), grad.astype(w.dtype)


# ---------------------------------------------------------------------------
# batched MALA / random-walk Metropolis proposal
# ---------------------------------------------------------------------------

def mala_step(z, grad, noise, m_inv, eps):
    """Langevin (or random-walk) proposal for a (C, D) chain ensemble.

    z' = z - eps * m_inv * grad + sqrt(2 * eps * m_inv) * noise

    ``grad=None`` drops the drift term, giving the symmetric random-walk
    proposal with the same preconditioner.  ``m_inv`` is the shared (D,)
    diagonal preconditioner, ``eps`` a scalar, ``noise`` standard normal.
    """
    zf = z.astype(jnp.float32)
    minv = m_inv.astype(jnp.float32)
    epsf = jnp.asarray(eps, jnp.float32)
    sig = jnp.sqrt(2.0 * epsf * minv)
    out = zf + sig * noise.astype(jnp.float32)
    if grad is not None:
        out = out - epsf * minv * grad.astype(jnp.float32)
    return out.astype(z.dtype)


def enum_contract(log_alpha, log_mat):
    """Stabilized logsumexp contraction of the enumeration forward pass:
    ``out[..., j] = logsumexp_i(log_alpha[..., i] + log_mat[..., i, j])``.

    This is one step of chain elimination (``markov``): ``log_alpha`` is the
    forward message over the previous state, ``log_mat`` the per-step factor
    ``log p(z_t=j | z_{t-1}=i) + log p(obs_t | z_t=j)``.  Written as the
    exact formula the Pallas kernel computes (max, strictly left-to-right
    exp-sum over the shared axis, log, with fully-masked columns pinned to
    -inf) so the two paths stay bit-identical in interpret mode: ``jnp.sum``
    would let XLA re-associate the reduction differently for the kernel's
    lane-padded layout, while a sequential sum is order-pinned and the
    kernel's padding rows only append exact ``+0.0`` terms.
    """
    x = log_alpha[..., :, None] + log_mat
    m = jnp.max(x, axis=-2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(x - m_safe[..., None, :])
    s = e[..., 0, :]
    for i in range(1, e.shape[-2]):
        s = s + e[..., i, :]
    return jnp.where(jnp.isfinite(m), jnp.log(s) + m_safe, -jnp.inf)


def enum_contract_chain(alpha0, unary, pair, keep):
    """Log partition of every row of a batched chain: the forward algorithm
    over T - 1 steps, one :func:`enum_contract` per step for all rows.

    ``alpha0`` (B, K) is each row's first message, ``unary`` (T-1, B, K)
    the factors of each step that do not read the previous state,
    ``pair`` (T-1, K, K) those that do (one matrix for every row, or
    (T-1, B, K, K)), ``keep`` (T-1, B) whether the row takes the step: a
    row that does not holds its message.  Returns ``log Z`` (B,).
    """
    return chain_scan(enum_contract, alpha0, unary, pair, keep)


def logsumexp_states(x):
    """``logsumexp`` over the last axis in the order :func:`enum_contract`
    pins (max, exp-sum left to right, log; an axis that is -inf
    throughout gives -inf)."""
    m = jnp.max(x, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    s = jnp.exp(x[..., 0] - m_safe)
    for k in range(1, x.shape[-1]):
        s = s + jnp.exp(x[..., k] - m_safe)
    return jnp.where(jnp.isfinite(m), jnp.log(s) + m_safe, -jnp.inf)


def chain_scan(contract, alpha0, unary, pair, keep):
    """:func:`enum_contract_chain` with ``contract`` as each step's
    contraction.  Each message is shifted so that its largest state is 0,
    and the shifts are summed into ``log Z`` one step at a time: a message
    of a long chain then stays within a few emissions of 0 instead of
    growing to ``log Z`` itself, where f32 has too few digits for the
    posterior that the gradient is made of."""
    def shifted(x):
        m = jnp.max(x, axis=-1)
        return x - jnp.where(jnp.isfinite(m), m, 0.0)[..., None], m

    def step(carry, inp):
        alpha, log_z = carry
        u, p, k = inp
        new, m = shifted(contract(alpha, p) + u)
        return (jnp.where(k[:, None], new, alpha),
                jnp.where(k, log_z + m, log_z)), None

    (alpha, log_z), _ = jax.lax.scan(step, shifted(alpha0),
                                     (unary, pair, keep))
    return log_z + logsumexp_states(alpha)
