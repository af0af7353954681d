"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import pytest
from jax import random

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.leapfrog import leapfrog_halfstep, leapfrog_halfstep_ref
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.softmax_xent import softmax_xent
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dt):
    return TOL[dt]


@pytest.mark.parametrize("S,H,K,dq,dv,causal,dtype", [
    (128, 4, 4, 64, 64, True, jnp.float32),     # MHA
    (256, 4, 2, 64, 64, True, jnp.float32),     # GQA
    (128, 4, 1, 64, 64, True, jnp.float32),     # MQA
    (128, 4, 4, 96, 64, True, jnp.float32),     # MLA-shaped dq != dv
    (128, 2, 2, 64, 64, False, jnp.float32),    # bidirectional
    (256, 4, 2, 64, 64, True, jnp.bfloat16),    # bf16
])
def test_flash_attention_sweep(S, H, K, dq, dv, causal, dtype):
    B = 2
    ks = random.split(random.PRNGKey(0), 3)
    q = random.normal(ks[0], (B, S, H, dq), dtype)
    k = random.normal(ks[1], (B, S, K, dq), dtype)
    v = random.normal(ks[2], (B, S, K, dv), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    exp = ref.attention(q, k, v, causal=causal)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)))
    assert float(err) < _tol(dtype) * 10, float(err)


def test_flash_attention_grads():
    B, S, H, K, d = 1, 128, 2, 1, 32
    ks = random.split(random.PRNGKey(1), 4)
    q = random.normal(ks[0], (B, S, H, d))
    k = random.normal(ks[1], (B, S, K, d))
    v = random.normal(ks[2], (B, S, K, d))
    do = random.normal(ks[3], (B, S, H, d))

    def loss(f):
        return lambda q, k, v: (f(q, k, v) * do).sum()
    g1 = jax.grad(loss(lambda *a: flash_attention(*a, causal=True,
                                                  interpret=True)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda *a: ref.attention(*a, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


@pytest.mark.parametrize("shape,dtype", [
    ((4, 64, 128), jnp.float32),
    ((2, 256, 512), jnp.float32),
    ((8, 128), jnp.bfloat16),
])
def test_rmsnorm_sweep(shape, dtype):
    x = random.normal(random.PRNGKey(0), shape, dtype)
    w = (random.normal(random.PRNGKey(1), shape[-1:]) * 0.1 + 1.0)
    out = rmsnorm(x, w, 1e-6, True)
    exp = ref.rmsnorm(x, w)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)))
    assert float(err) < _tol(dtype), float(err)
    g1 = jax.grad(lambda x, w: (rmsnorm(x, w, 1e-6, True).astype(
        jnp.float32) ** 2).sum(), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: (ref.rmsnorm(x, w).astype(
        jnp.float32) ** 2).sum(), argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        err = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
        assert float(err) < _tol(dtype) * 200


@pytest.mark.parametrize("T,d,V,zlw", [
    (128, 64, 512, 0.0),
    (256, 32, 1024, 1e-4),
    (128, 64, 2048, 1e-4),
])
def test_softmax_xent_sweep(T, d, V, zlw):
    x = random.normal(random.PRNGKey(0), (T, d)) * 0.5
    w = random.normal(random.PRNGKey(1), (d, V)) * 0.5
    lbl = random.randint(random.PRNGKey(2), (T,), 0, V)
    ce, zl = softmax_xent(x, w, lbl, zlw, True)
    cer, zlr = ref.softmax_xent(x, w, lbl, z_loss_weight=zlw)
    assert float(jnp.max(jnp.abs(ce - cer))) < 1e-4
    assert float(jnp.max(jnp.abs(zl - zlr))) < 1e-4
    g1 = jax.grad(lambda x: softmax_xent(x, w, lbl, zlw, True)[0].sum())(x)
    g2 = jax.grad(lambda x: ref.softmax_xent(
        x, w, lbl, z_loss_weight=zlw)[0].sum())(x)
    assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-4


@pytest.mark.parametrize("l,h,p,n,chunk", [
    (128, 2, 16, 32, 32),
    (256, 4, 16, 32, 64),
    (64, 2, 32, 16, 64),   # chunk == l/1
])
def test_ssd_scan_sweep(l, h, p, n, chunk):
    b, g = 2, 1
    ks = random.split(random.PRNGKey(0), 5)
    x = random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(random.normal(ks[2], (h,)))
    B = random.normal(ks[3], (b, l, g, n)) * 0.3
    C = random.normal(ks[4], (b, l, g, n)) * 0.3
    D = jnp.ones((h,))
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk, D=D, interpret=True)
    yr, sr = ref.ssd_scan(x, dt, A, B, C, chunk=chunk, D=D)
    assert float(jnp.max(jnp.abs(y - yr))) < 1e-4
    assert float(jnp.max(jnp.abs(st - sr))) < 1e-4


def test_ssd_inline_matches_stacked():
    """ref.ssd_scan_inline (fused state contribution) == ref.ssd_scan,
    values and grads (the §Perf mamba2 variant must be semantics-free)."""
    b, l, h, p, g, n = 2, 256, 4, 16, 1, 32
    ks = random.split(random.PRNGKey(0), 5)
    x = random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(random.normal(ks[2], (h,)))
    B = random.normal(ks[3], (b, l, g, n)) * 0.3
    C = random.normal(ks[4], (b, l, g, n)) * 0.3
    D = jnp.ones((h,))
    y1, s1 = ref.ssd_scan(x, dt, A, B, C, chunk=64, D=D)
    y2, s2 = ref.ssd_scan_inline(x, dt, A, B, C, chunk=64, D=D)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-5
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-5
    g1 = jax.grad(lambda x: ref.ssd_scan(x, dt, A, B, C, chunk=64,
                                         D=D)[0].sum())(x)
    g2 = jax.grad(lambda x: ref.ssd_scan_inline(x, dt, A, B, C, chunk=64,
                                                D=D)[0].sum())(x)
    assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-5


def test_ssd_decode_consistency():
    """Sequential one-token SSD decode == chunked scan over the sequence."""
    b, l, h, p, g, n = 1, 32, 2, 16, 1, 16
    ks = random.split(random.PRNGKey(0), 5)
    x = random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(random.normal(ks[2], (h,)))
    B = random.normal(ks[3], (b, l, g, n)) * 0.3
    C = random.normal(ks[4], (b, l, g, n)) * 0.3
    y_scan, st_scan = ref.ssd_scan(x, dt, A, B, C, chunk=16)
    st = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        y, st = ref.ssd_decode_step(st, x[:, t], dt[:, t], A, B[:, t],
                                    C[:, t])
        ys.append(y)
    y_dec = jnp.stack(ys, 1)
    assert float(jnp.max(jnp.abs(y_dec - y_scan))) < 1e-4
    assert float(jnp.max(jnp.abs(st - st_scan))) < 1e-4


def test_leapfrog_fused():
    D = 12345   # non-multiple of block: exercises padding
    ks = random.split(random.PRNGKey(0), 4)
    z, r, g = (random.normal(k, (D,)) for k in ks[:3])
    mi = jnp.abs(random.normal(ks[3], (D,))) + 0.5
    z1, r1 = leapfrog_halfstep(z, r, g, mi, 0.1, interpret=True)
    z2, r2 = leapfrog_halfstep_ref(z, r, g, mi, 0.1)
    assert float(jnp.max(jnp.abs(z1 - z2))) < 1e-6
    assert float(jnp.max(jnp.abs(r1 - r2))) < 1e-6


def test_leapfrog_fused_inside_velocity_verlet():
    """Parity of the Pallas halfstep (interpret mode) vs the jnp reference
    *as wired inside* velocity_verlet — the integrator the NUTS tree runs,
    not the kernel in isolation."""
    from repro.core.infer.hmc_util import IntegratorState, velocity_verlet
    from repro.kernels import ops

    D = 513  # non-multiple of block: exercises padding inside the verlet
    A = random.normal(random.PRNGKey(0), (D, D)) * 0.1
    prec = A @ A.T / D + jnp.eye(D)
    pot = lambda z: 0.5 * jnp.dot(z, prec @ z)  # noqa: E731
    _, vv_update = velocity_verlet(pot)

    ks = random.split(random.PRNGKey(1), 3)
    z, r = random.normal(ks[0], (D,)), random.normal(ks[1], (D,))
    m_inv = jnp.abs(random.normal(ks[2], (D,))) + 0.5
    pe, grad = jax.value_and_grad(pot)(z)
    state = IntegratorState(z, r, pe, grad)

    import numpy as np
    for eps in (0.05, -0.05):   # negative: NUTS growing the tree leftwards
        ref_out = vv_update(jnp.asarray(eps), m_inv, state)
        with ops.use_pallas(True, interpret=True):
            pl_out = vv_update(jnp.asarray(eps), m_inv, state)
        for a, b in zip(pl_out, ref_out):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-5)


def test_leapfrog_fused_jit_vmap_compile_once():
    """jit(vmap(verlet-with-fused-kernel)) over a batch of chains traces
    once and matches the reference batch."""
    from repro.core.infer.hmc_util import IntegratorState, velocity_verlet
    from repro.kernels import ops

    B, D = 8, 256
    pot = lambda z: 0.5 * jnp.dot(z, z)  # noqa: E731
    _, vv_update = velocity_verlet(pot)
    ks = random.split(random.PRNGKey(2), 2)
    zb, rb = random.normal(ks[0], (B, D)), random.normal(ks[1], (B, D))
    m_inv = jnp.ones(D)
    peb, gradb = jax.vmap(jax.value_and_grad(pot))(zb)

    n_traces = 0

    def step(z, r, pe, g):
        nonlocal n_traces
        n_traces += 1
        return vv_update(jnp.asarray(0.1), m_inv,
                         IntegratorState(z, r, pe, g))

    with ops.use_pallas(True, interpret=True):
        batched = jax.jit(jax.vmap(step))
        out1 = batched(zb, rb, peb, gradb)
        out2 = batched(zb + 0, rb + 0, peb + 0, gradb + 0)
    assert n_traces == 1
    exp = jax.vmap(lambda z, r, pe, g: vv_update(
        jnp.asarray(0.1), m_inv, IntegratorState(z, r, pe, g)))(
        zb, rb, peb, gradb)
    for a, b in zip(out1, exp):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5
    assert float(jnp.max(jnp.abs(out1.z - out2.z))) == 0.0


def test_mla_absorbed_decode_matches_expanded():
    """The absorbed-matmul MLA decode == naive expand-then-attend."""
    B, S, H, dn, dr, r, dv = 2, 16, 4, 16, 8, 32, 16
    ks = random.split(random.PRNGKey(0), 6)
    q_nope = random.normal(ks[0], (B, 1, H, dn))
    q_rope = random.normal(ks[1], (B, 1, H, dr))
    c_kv = random.normal(ks[2], (B, S, r))
    k_rope = random.normal(ks[3], (B, S, dr))
    wk = random.normal(ks[4], (H, dn, r)) * 0.3
    wv = random.normal(ks[5], (H, r, dv)) * 0.3
    mask = jnp.arange(S)[None, :] <= 10
    scale = (dn + dr) ** -0.5
    out = ref.mla_absorbed_decode(q_nope, q_rope, c_kv, k_rope, wk, wv,
                                  mask, scale=scale)
    # naive: expand k/v per position then standard decode attention
    k_nope = jnp.einsum("bsr,hnr->bshn", c_kv, wk)
    v = jnp.einsum("bsr,hrv->bshv", c_kv, wv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    exp = ref.decode_attention(q, k, v, mask, scale=scale)
    assert float(jnp.max(jnp.abs(out - exp))) < 1e-4


# ---------------------------------------------------------------------------
# enum_contract: logsumexp chain-elimination kernel vs ref oracle
# ---------------------------------------------------------------------------

@pytest.mark.enum
@pytest.mark.parametrize("batch,Ki,K", [
    ((), 2, 2), ((), 3, 3), ((), 16, 16), ((), 128, 128), ((), 7, 13),
    ((), 257, 5), ((4,), 8, 8), ((2, 3), 5, 5),
])
def test_enum_contract_bit_parity(batch, Ki, K):
    from repro.kernels.enum_contract import enum_contract
    ks = random.split(random.PRNGKey(0), 2)
    a = random.normal(ks[0], batch + (Ki,))
    m = random.normal(ks[1], batch + (Ki, K))
    out = enum_contract(a, m, interpret=True)
    exp = ref.enum_contract(a, m)
    assert out.shape == exp.shape == batch + (K,)
    assert jnp.array_equal(out, exp), "kernel must be bit-identical to ref"


@pytest.mark.enum
@pytest.mark.parametrize("K", [2, 3, 8, 25])
@pytest.mark.parametrize("batch", [(1,), (5,), (13,), (3, 7), (120,)])
def test_enum_contract_batch_tiling_bit_parity(batch, K):
    """The batch rows ride the sublanes in tiles of 8: batches that are not
    a multiple of 8 are padded and sliced off without touching a bit."""
    from repro.kernels.enum_contract import enum_contract
    ks = random.split(random.PRNGKey(K), 2)
    a = random.normal(ks[0], batch + (K,))
    m = random.normal(ks[1], batch + (K, K)).at[..., 0].set(-jnp.inf)
    out = enum_contract(a, m, interpret=True)
    assert jnp.array_equal(out, ref.enum_contract(a, m))


@pytest.mark.enum
def test_enum_contract_kernel_gradient_is_ref_gradient():
    """NUTS differentiates the marginal through every kernel call; the
    kernel's custom VJP is the oracle's, so the gradients agree exactly."""
    from repro.kernels.enum_contract import enum_contract
    a = random.normal(random.PRNGKey(6), (4, 5))
    m = random.normal(random.PRNGKey(7), (4, 5, 5))

    def loss(fn):
        return jax.grad(lambda aa, mm: fn(aa, mm).sum(), argnums=(0, 1))(a, m)

    got = loss(lambda aa, mm: enum_contract(aa, mm, interpret=True))
    for g, e in zip(got, loss(ref.enum_contract)):
        assert jnp.array_equal(g, e)


@pytest.mark.enum
def test_enum_contract_masked_columns_and_rows():
    from repro.kernels.enum_contract import enum_contract
    a = jnp.array([0.3, -jnp.inf, 1.2])
    m = random.normal(random.PRNGKey(1), (3, 4)).at[:, 2].set(-jnp.inf)
    out = enum_contract(a, m, interpret=True)
    exp = ref.enum_contract(a, m)
    assert jnp.array_equal(out, exp)
    assert bool(jnp.isneginf(out[2]))  # fully-masked column pins to -inf
    # matches a plain stabilized logsumexp on the finite columns
    lse = jax.nn.logsumexp(a[:, None] + m, axis=0)
    finite = jnp.isfinite(lse)
    assert jnp.allclose(out[finite], lse[finite], atol=1e-6)


@pytest.mark.enum
def test_enum_contract_ref_is_correct_and_differentiable():
    a = random.normal(random.PRNGKey(2), (6,))
    m = random.normal(random.PRNGKey(3), (6, 9))
    exp = jax.nn.logsumexp(a[:, None] + m, axis=0)
    assert jnp.allclose(ref.enum_contract(a, m), exp, atol=1e-6)
    g = jax.grad(lambda aa: ref.enum_contract(aa, m).sum())(a)
    assert bool(jnp.all(jnp.isfinite(g)))
    # softmax-weight structure of the gradient: rows sum to #columns
    assert abs(float(g.sum()) - m.shape[1]) < 1e-4


@pytest.mark.enum
def test_enum_contract_ops_dispatch():
    from repro.kernels import ops
    a = random.normal(random.PRNGKey(4), (5,))
    m = random.normal(random.PRNGKey(5), (5, 5))
    base = ops.enum_contract(a, m)  # default: ref path
    with ops.use_pallas(True, interpret=True):
        fused = ops.enum_contract(a, m)
    assert jnp.array_equal(base, fused)
