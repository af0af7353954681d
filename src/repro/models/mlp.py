"""MLP and Mixture-of-Experts layers.

MoE uses expert parallelism with explicit ``all_to_all`` dispatch inside
``shard_map`` (TPU-native EP: tokens travel over ICI to the devices owning
their experts; experts never move).  Dispatch is scatter-based — no GShard
one-hot einsum — so HLO FLOPs stay proportional to *active* compute.

Single-device (smoke tests) runs the identical code path with ep_degree=1
and no collectives.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.primitives import param
from repro.models import common
from repro.models.common import normal_init, zeros_init
from repro.models.config import ModelConfig


def _p(name, shape, sharding, dtype, init=None):
    return param(name, shape=shape, init_fn=init or normal_init(0.02),
                 dtype=dtype, sharding=sharding)


def _stk(stacked, shape, sharding):
    if stacked:
        return (stacked,) + shape, ("layers",) + sharding
    return shape, sharding


# ---------------------------------------------------------------------------
# dense gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_params(cfg: ModelConfig, prefix: str, stacked: int = 0,
               d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.jnp_dtype
    w = {}
    shape, shard = _stk(stacked, (d, f), ("embed", "mlp"))
    w["wg"] = _p(f"{prefix}.wg", shape, shard, dt)
    w["wu"] = _p(f"{prefix}.wu", shape, shard, dt)
    shape, shard = _stk(stacked, (f, d), ("mlp", "embed"))
    w["wd"] = _p(f"{prefix}.wd", shape, shard, dt)
    return w


def mlp_apply(cfg: ModelConfig, w, x):
    act = common.geglu if cfg.mlp_act == "geglu" else common.swiglu
    g = jnp.einsum("bsd,df->bsf", x, w["wg"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, w["wu"].astype(x.dtype))
    h = act(g, u)
    return jnp.einsum("bsf,fd->bsd", h, w["wd"].astype(x.dtype))


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def padded_experts(cfg: ModelConfig, ep_degree: int) -> int:
    """Experts padded up to a multiple of the EP degree (phantom experts get
    -inf router logits and are never selected; see DESIGN.md)."""
    e = cfg.num_experts
    return int(math.ceil(e / ep_degree) * ep_degree)


def moe_params(cfg: ModelConfig, prefix: str, stacked: int = 0,
               ep_degree: int = 1):
    d, f = cfg.d_model, cfg.moe_d_ff
    e_pad = padded_experts(cfg, ep_degree)
    dt = cfg.jnp_dtype
    w = {}
    shape, shard = _stk(stacked, (d, e_pad), ("embed", None))
    w["router"] = _p(f"{prefix}.router", shape, shard, jnp.float32,
                     init=normal_init(0.006))
    if cfg.router_type == "sigmoid":
        # DeepSeek-V3 aux-free balancing bias: NOT trained by gradients —
        # updated from load statistics in train_step (see launch/train.py).
        shape, shard = _stk(stacked, (e_pad,), (None,))
        w["router_bias"] = _p(f"{prefix}.router_bias", shape, shard,
                              jnp.float32, init=zeros_init())
    for n, io in (("wg", (d, f)), ("wu", (d, f)), ("wd", (f, d))):
        shape, shard = _stk(stacked, (e_pad,) + io,
                            ("expert",) + ((None, "expert_inner")
                                           if n != "wd"
                                           else ("expert_inner", None)))
        w[n] = _p(f"{prefix}.{n}", shape, shard, dt)
    if cfg.num_shared_experts:
        w["shared"] = mlp_params(
            cfg, f"{prefix}.shared", stacked,
            d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    return w


def _route(cfg: ModelConfig, logits, bias):
    """Top-k routing. Returns (ids (T,k), weights (T,k), probs (T,E))."""
    e = cfg.num_experts
    k = cfg.num_experts_per_tok
    e_pad = logits.shape[-1]
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(jnp.arange(e_pad) < e, logits, neg)  # mask phantoms
    if cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = jnp.where(jnp.arange(e_pad) < e, scores + bias, neg)
        _, ids = jax.lax.top_k(sel, k)
        wts = jnp.take_along_axis(scores, ids, axis=-1)
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
        probs = scores
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        wts, ids = jax.lax.top_k(probs, k)
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    return ids, wts, probs


def _moe_local(cfg: ModelConfig, wg, wu, wd, x, logits, bias,
               ep_axes=(), inner_axis=None, all_axes=(),
               capacity_factor=1.25):
    """Per-device MoE body. Shapes are LOCAL (inside shard_map) or global
    (single device).  x: (T, d); logits: (T, E_pad); w*: (E_loc, d|f, f|d).

    Returns (y (T,d), load (E_pad,) fraction of assignments per expert).
    """
    T, d = x.shape
    e_pad = logits.shape[-1]
    k = cfg.num_experts_per_tok
    ep = 1
    for a in ep_axes:
        ep *= jax.lax.axis_size(a)
    e_loc = e_pad // ep

    ids, wts, _ = _route(cfg, logits.astype(jnp.float32), bias)
    a_ids = ids.reshape(-1)                              # (A,) expert per slot
    a_wts = wts.reshape(-1)
    a_tok = jnp.repeat(jnp.arange(T), k)

    # position of each assignment within its expert's capacity bucket
    oh = (a_ids[:, None] == jnp.arange(e_pad)[None, :]).astype(jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - 1)
    pos = jnp.sum(pos * oh, axis=1)                      # (A,)
    load = oh.sum(0).astype(jnp.float32) / max(T * k, 1)

    cap = max(1, math.ceil(T * k / cfg.num_experts * capacity_factor))
    keep = pos < cap
    slot = jnp.where(keep, a_ids * cap + pos, e_pad * cap)  # OOB -> dropped

    send = jnp.zeros((e_pad * cap, d), x.dtype)
    send = send.at[slot].set(x[a_tok], mode="drop")

    if ep > 1:
        send = send.reshape(ep, e_loc * cap, d)
        recv = jax.lax.all_to_all(send, ep_axes, split_axis=0, concat_axis=0,
                                  tiled=True)           # (ep, e_loc*cap, d)
        recv = recv.reshape(ep, e_loc, cap, d).transpose(1, 0, 2, 3)
        h_in = recv.reshape(e_loc, ep * cap, d)
    else:
        h_in = send.reshape(e_loc, cap, d)

    if inner_axis is not None:  # expert weights FSDP-sharded on the f dim
        wg = jax.lax.all_gather(wg, inner_axis, axis=2, tiled=True)
        wu = jax.lax.all_gather(wu, inner_axis, axis=2, tiled=True)
        wd = jax.lax.all_gather(wd, inner_axis, axis=1, tiled=True)

    act = common.geglu if cfg.mlp_act == "geglu" else common.swiglu
    g = jnp.einsum("ecd,edf->ecf", h_in, wg.astype(h_in.dtype))
    u = jnp.einsum("ecd,edf->ecf", h_in, wu.astype(h_in.dtype))
    h_out = jnp.einsum("ecf,efd->ecd", act(g, u), wd.astype(h_in.dtype))

    if ep > 1:
        back = h_out.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
        back = back.reshape(ep, e_loc * cap, d)
        back = jax.lax.all_to_all(back, ep_axes, split_axis=0, concat_axis=0,
                                  tiled=True)
        back = back.reshape(e_pad * cap, d)
    else:
        back = h_out.reshape(e_pad * cap, d)

    out = jnp.take(back, jnp.where(keep, slot, 0), axis=0)
    out = out * keep[:, None].astype(out.dtype)
    out = out * a_wts[:, None].astype(out.dtype)
    y = jnp.zeros((T, d), jnp.float32).at[a_tok].add(out.astype(jnp.float32))
    if all_axes:
        load = jax.lax.pmean(load, all_axes)    # global expert load fractions
    return y.astype(x.dtype), load


def moe_apply(cfg: ModelConfig, w, x, *, capacity_factor=None):
    """x: (B, S, d) -> (y, aux) where aux = {"load": (E_pad,), "aux_loss": ()}.

    Distributed when a sharding context is active (see common.sharding_ctx):
    the dispatch/combine runs inside shard_map over the EP axes.  Decode
    (S == 1) stays in GSPMD — token counts are tiny and the grouped matmul
    shards over the expert dim without manual collectives.
    """
    B, S, d = x.shape
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    mesh = common.current_mesh()
    rules = common.current_rules()
    xt = x.reshape(B * S, d)
    logits = (xt.astype(jnp.float32) @ w["router"].astype(jnp.float32))
    bias = w.get("router_bias", jnp.zeros((logits.shape[-1],), jnp.float32))
    bias = jax.lax.stop_gradient(bias)

    if mesh is None or rules is None or S == 1:
        y, load = _moe_local(cfg, w["wg"], w["wu"], w["wd"], xt, logits, bias,
                             capacity_factor=capacity_factor)
    else:
        ep_axes = rules.get("expert") or ()
        if isinstance(ep_axes, str):
            ep_axes = (ep_axes,)
        inner = rules.get("expert_inner")
        # tokens are sharded over (DP axes + sequence axis): flattening
        # (B, S) -> T keeps the layout (batch-major) so the reshape is local
        dp = rules.get("batch") or ()
        dp = (dp,) if isinstance(dp, str) else tuple(dp)
        sq = rules.get("seq") or ()
        sq = (sq,) if isinstance(sq, str) else tuple(sq)
        tok_axes = dp + sq
        tok_spec = P(tok_axes, None)
        xt = common.constrain_spec(xt, tok_spec)
        logits = common.constrain_spec(logits, tok_spec)
        w_spec = P(ep_axes, None, inner)
        wd_spec = P(ep_axes, inner, None)
        body = partial(_moe_local, cfg, ep_axes=ep_axes, inner_axis=inner,
                       all_axes=tok_axes, capacity_factor=capacity_factor)
        in_specs = (w_spec, w_spec, wd_spec, tok_spec, tok_spec, P())
        out_specs = (tok_spec, P())
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        y, load = fn(w["wg"], w["wu"], w["wd"], xt, logits, bias)

    y = y.reshape(B, S, d)
    if cfg.num_shared_experts:
        y = y + mlp_apply(cfg, w["shared"], x)

    # switch-style load-balance loss on the softmax/sigmoid probabilities
    e = cfg.num_experts
    probs = (jax.nn.sigmoid(logits) if cfg.router_type == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    p_mean = probs[:, :e].mean(0)
    p_mean = p_mean / (p_mean.sum() + 1e-20)
    aux_loss = e * jnp.sum(load[:e] * p_mean)
    return y, {"load": load, "aux_loss": aux_loss}
