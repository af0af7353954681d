"""RPL2xx — machine-checked kernel/handler invariants.

Two contracts, both declarative:

- The **op registry**: :data:`repro.kernels.ops.OP_TABLE` must stay in
  bijection with the public ops that module dispatches (RPL201), every
  Pallas kernel must share its ref oracle's signature (RPL202, parameter
  *names in order*; the trailing ``interpret`` flag is dispatch plumbing and
  is stripped before comparison), and running each registered pair in
  interpret mode must agree — bit-identically where the table says so
  (RPL203).  ``tests/test_lint.py`` drives these per-op, replacing
  hand-enumerated parity lists.
- The **KernelSetup field contract** (RPL204): hashability (the executor
  jit-caches on setup identity), integer ``num_warmup``, a Stan-style
  ``adapt_schedule`` of int pairs, callable closures, — for
  ``cross_chain`` kernels — ensemble state leaves leading with the chain
  axis, and a coherent ``data_axis`` declaration: a setup that names a mesh
  data axis must close over a shard-aware potential (one carrying the
  ``data_shards`` fold marker), and vice versa — either half drifting alone
  means the executor silently runs monolithic potentials on a sharded mesh
  (or never activates the mesh at all).
"""
from __future__ import annotations

import importlib
import inspect

import jax
import jax.numpy as jnp
from jax import random

from ..kernels import ops
from ..kernels.ops import _CONTROL, OP_TABLE
from . import ERROR


def _mk(code, site, message):
    from ..core.lint import Finding
    return Finding(code, ERROR, site, message)


def _result(findings):
    from ..core.lint import LintResult
    return LintResult(findings)


def _load(path):
    module, attr = path
    return getattr(importlib.import_module(module), attr)


def _param_names(fn):
    names = [p.name for p in inspect.signature(fn).parameters.values()]
    if names and names[-1] == "interpret":
        names = names[:-1]
    return names


def _sample_inputs(name, key):
    """Small concrete inputs exercising each registered op's full signature
    (shapes follow the kernel block constraints the sweep tests use)."""
    ks = random.split(key, 8)
    if name == "attention":
        b, s, h, kh, d = 1, 128, 2, 1, 64
        return (random.normal(ks[0], (b, s, h, d)),
                random.normal(ks[1], (b, s, kh, d)),
                random.normal(ks[2], (b, s, kh, d))), {"causal": True}
    if name == "leapfrog_halfstep":
        d = 515  # non-multiple of the kernel block: exercises padding
        z, r, g = (random.normal(k, (d,)) for k in ks[:3])
        m_inv = jnp.abs(random.normal(ks[3], (d,))) + 0.5
        return (z, r, g, m_inv, 0.1), {}
    if name == "leapfrog_halfstep_batch":
        c, d = 5, 515  # non-multiples of sublane/block: exercises padding
        z, r, g = (random.normal(k, (c, d)) for k in ks[:3])
        m_inv = jnp.abs(random.normal(ks[3], (d,))) + 0.5
        return (z, r, g, m_inv, 0.1, 1.0), {}
    if name == "glm_potential_grad":
        n, d = 300, 7  # n spans >1 block row-group; d exercises lane padding
        x = random.normal(ks[0], (n, d))
        w = random.normal(ks[1], (d,)) * 0.3
        y = (random.uniform(ks[2], (n,)) < 0.5).astype(jnp.float32)
        offset = random.normal(ks[3], (n,)) * 0.1
        return (x, y, w, offset), {"family": "bernoulli_logit"}
    if name == "glm_potential_grad_slab":
        from ..kernels.glm_potential import glm_slab
        n, c, d = 300, 3, 7  # n not a multiple of the 128-lane tile
        x = random.normal(ks[0], (n, d))
        w = random.normal(ks[1], (c, d)) * 0.3
        y = (random.uniform(ks[2], (n,)) < 0.5).astype(jnp.float32)
        offset = random.normal(ks[3], (n,)) * 0.1
        return (glm_slab(x, y, offset), w), {"family": "bernoulli_logit"}
    if name == "mala_step":
        c, d = 5, 515
        z, g, noise = (random.normal(k, (c, d)) for k in ks[:3])
        m_inv = jnp.abs(random.normal(ks[3], (d,))) + 0.5
        return (z, g, noise, m_inv, 0.05), {}
    if name == "enum_contract":
        return (random.normal(ks[0], (7,)),
                random.normal(ks[1], (7, 5))), {}
    if name == "enum_contract_chain":
        steps, b, k = 6, 11, 5
        keep = random.uniform(ks[3], (steps, b)) < 0.8
        return (random.normal(ks[0], (b, k)),
                random.normal(ks[1], (steps, b, k)),
                jax.nn.log_softmax(random.normal(ks[2], (steps, k, k))),
                keep), {}
    if name == "rmsnorm":
        x = random.normal(ks[0], (4, 64, 128))
        w = random.normal(ks[1], (128,)) * 0.1 + 1.0
        return (x, w), {}
    if name == "softmax_xent":
        t, d, v = 128, 32, 512
        return (random.normal(ks[0], (t, d)) * 0.5,
                random.normal(ks[1], (d, v)) * 0.5,
                random.randint(ks[2], (t,), 0, v)), {"z_loss_weight": 1e-4}
    if name == "ssd_scan":
        b, length, h, p, g, n = 1, 64, 2, 16, 1, 16
        x = random.normal(ks[0], (b, length, h, p)) * 0.5
        dt = jax.nn.softplus(random.normal(ks[1], (b, length, h)))
        a = -jnp.exp(random.normal(ks[2], (h,)))
        bb = random.normal(ks[3], (b, length, g, n)) * 0.3
        c = random.normal(ks[4], (b, length, g, n)) * 0.3
        return (x, dt, a, bb, c), {"chunk": 32, "D": jnp.ones((h,))}
    return None  # ref-only op: nothing to run parity against


def check_registry_completeness():
    """RPL201: OP_TABLE <-> public ops bijection, all entries importable."""
    findings = []
    table = {spec.name: spec for spec in OP_TABLE}
    public = {n for n, f in inspect.getmembers(ops, inspect.isfunction)
              if not n.startswith("_") and f.__module__ == ops.__name__}
    public -= set(_CONTROL)
    for name in sorted(public - set(table)):
        findings.append(_mk("RPL201", name,
                            f"op '{name}' is dispatched by kernels/ops.py "
                            "but has no OP_TABLE entry: register its Pallas "
                            "kernel (or None) and its ref oracle."))
    for name in sorted(set(table) - public):
        findings.append(_mk("RPL201", name,
                            f"OP_TABLE entry '{name}' matches no public op "
                            "in kernels/ops.py: remove the stale entry or "
                            "restore the op."))
    for spec in OP_TABLE:
        for label, path in (("ref", spec.ref), ("pallas", spec.pallas)):
            if path is None:
                continue
            try:
                _load(path)
            except Exception as e:  # noqa: BLE001 — report, don't crash
                findings.append(_mk(
                    "RPL201", spec.name,
                    f"op '{spec.name}': {label} entry {path} does not "
                    f"import ({type(e).__name__}: {e})."))
    return _result(findings)


def check_signatures(spec):
    """RPL202 for one op: Pallas kernel, ref oracle, and the dispatch
    wrapper must agree on parameter names in order (``interpret`` excluded;
    positional-vs-keyword kind is a style choice and is ignored).  A kernel
    may declare *extra trailing* parameters beyond the ref signature —
    block-size tuning knobs — but every extra must carry a default, so the
    kernel stays a drop-in replacement when called with ref arguments."""
    findings = []
    ref_fn = _load(spec.ref)
    ref_names = _param_names(ref_fn)
    candidates = [("dispatch wrapper", getattr(ops, spec.name, None))]
    if spec.pallas is not None:
        candidates.append(("pallas kernel", _load(spec.pallas)))
    for label, fn in candidates:
        if fn is None:
            continue
        names = _param_names(fn)
        if names[:len(ref_names)] != ref_names:
            findings.append(_mk(
                "RPL202", spec.name,
                f"op '{spec.name}': {label} signature {names} does not "
                f"match the ref oracle signature {ref_names} — the two "
                "paths must be drop-in interchangeable."))
            continue
        params = inspect.signature(fn).parameters
        for extra in names[len(ref_names):]:
            if params[extra].default is inspect.Parameter.empty:
                findings.append(_mk(
                    "RPL202", spec.name,
                    f"op '{spec.name}': {label} extra parameter '{extra}' "
                    "has no default — tuning knobs beyond the ref oracle "
                    "signature must be optional."))
    return _result(findings)


def check_parity(spec, rng_key=None):
    """RPL203 for one op: run the dispatch wrapper on both paths (Pallas
    interpret mode vs ref) on sample inputs and compare outputs."""
    findings = []
    if spec.pallas is None:
        return _result(findings)
    if rng_key is None:
        rng_key = random.PRNGKey(0)
    inputs = _sample_inputs(spec.name, rng_key)
    if inputs is None:
        findings.append(_mk(
            "RPL203", spec.name,
            f"op '{spec.name}' has a Pallas kernel but no sample-input "
            "factory: add one to lint_rules.invariants._sample_inputs so "
            "parity is actually executed."))
        return _result(findings)
    args, kwargs = inputs
    wrapper = getattr(ops, spec.name)
    with ops.use_pallas(True, interpret=True):
        out_pallas = wrapper(*args, **kwargs)
    with ops.use_pallas(False):
        out_ref = wrapper(*args, **kwargs)
    pallas_leaves = jax.tree_util.tree_leaves(out_pallas)
    ref_leaves = jax.tree_util.tree_leaves(out_ref)
    for i, (a, b) in enumerate(zip(pallas_leaves, ref_leaves)):
        if jnp.shape(a) != jnp.shape(b):
            findings.append(_mk(
                "RPL203", spec.name,
                f"op '{spec.name}' output {i}: Pallas shape {jnp.shape(a)} "
                f"!= ref shape {jnp.shape(b)}."))
            continue
        if spec.bit_identical:
            if not bool(jnp.array_equal(a, b)):
                findings.append(_mk(
                    "RPL203", spec.name,
                    f"op '{spec.name}' output {i}: kernel is declared "
                    "bit-identical to its ref oracle but differs."))
        else:
            err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            if not err < spec.tol:
                findings.append(_mk(
                    "RPL203", spec.name,
                    f"op '{spec.name}' output {i}: max abs error {err} "
                    f"exceeds the registered tolerance {spec.tol}."))
    return _result(findings)


def verify_registry(rng_key=None, parity: bool = True):
    """All RPL201/202/203 checks over the whole table in one pass."""
    findings = list(check_registry_completeness().findings)
    for spec in OP_TABLE:
        try:
            findings.extend(check_signatures(spec).findings)
        except Exception:  # unresolvable entries already reported as RPL201
            continue
        if parity:
            findings.extend(check_parity(spec, rng_key).findings)
    return _result(findings)


_SETUP_CALLABLES = ("init_fn", "sample_fn", "collect_fn", "potential_fn",
                    "unravel_fn", "constrain_fn")


def verify_kernel_setup(setup, state=None, num_chains=None):
    """RPL204: the KernelSetup field contract.

    ``state``/``num_chains`` optionally verify the cross-chain leaf
    contract: matrix-shaped ensemble state leaves must lead with the chain
    axis (scalars and vectors are shared pooled adaptation state).
    """
    findings = []

    def bad(msg):
        findings.append(_mk("RPL204", getattr(setup, "algo", None), msg))

    try:
        hash(setup)
    except TypeError as e:
        bad(f"KernelSetup is not hashable ({e}): it cannot be a jit "
            "static argument, so the executor cache cannot key on it. "
            "Keep every field a function, int, str, or nested tuple.")
    for field in _SETUP_CALLABLES:
        if not callable(getattr(setup, field, None)):
            bad(f"KernelSetup.{field} is not callable.")
    if not isinstance(getattr(setup, "num_warmup", None), int):
        bad(f"KernelSetup.num_warmup must be a Python int, got "
            f"{type(getattr(setup, 'num_warmup', None)).__name__} — traced "
            "or array-valued warmup lengths break the static schedule.")
    sched = getattr(setup, "adapt_schedule", None)
    ok_sched = isinstance(sched, tuple) and all(
        isinstance(w, tuple) and len(w) == 2
        and all(isinstance(x, int) for x in w) for w in sched)
    if not ok_sched:
        bad("KernelSetup.adapt_schedule must be a tuple of (start, end) "
            f"int pairs, got {sched!r}.")
    if not isinstance(getattr(setup, "cross_chain", None), bool):
        bad("KernelSetup.cross_chain must be a bool.")
    data_axis = getattr(setup, "data_axis", None)
    pot = getattr(setup, "potential_fn", None)
    shards = getattr(pot, "data_shards", None)
    if data_axis is not None:
        if not isinstance(data_axis, str):
            bad(f"KernelSetup.data_axis must be None or a mesh axis name "
                f"(str), got {type(data_axis).__name__} — the executor "
                "matches it against Mesh.axis_names.")
        elif not (isinstance(shards, int) and shards >= 1):
            bad(f"KernelSetup.data_axis={data_axis!r} declares a data-"
                "sharded potential, but potential_fn carries no "
                f"data_shards marker (found {shards!r}) — the executor "
                "would enter the mesh and evaluate a monolithic potential "
                "with no shard_map, silently losing data parallelism and "
                "the resharding bit-identity guarantee. Route the "
                "potential through maybe_fuse_glm_potential(data_shards=S) "
                "or drop the axis declaration.")
    elif isinstance(shards, int) and shards >= 1:
        bad(f"potential_fn is shard-aware (data_shards={shards}) but "
            "KernelSetup.data_axis is None — the executor never activates "
            "the inference mesh, so every shard evaluates locally and the "
            "declared fold parallelism is dead. Pass the axis through "
            "resolve_data_axis into the setup.")
    if getattr(setup, "cross_chain", False) and state is not None \
            and num_chains is not None:
        # Shared pooled state (iteration counter, rng key, step size, the
        # (D,) mass diagonal / Welford moments) is scalar- or vector-shaped
        # by construction; anything matrix-shaped is per-chain and must
        # lead with the chain axis.
        for i, leaf in enumerate(jax.tree_util.tree_leaves(state)):
            shape = jnp.shape(leaf)
            if len(shape) >= 2 and shape[0] != num_chains:
                bad(f"cross_chain state leaf {i} has shape {shape}; "
                    f"matrix-shaped ensemble leaves must lead with the "
                    f"chain axis ({num_chains},).")
    return _result(findings)


__all__ = [
    "check_parity",
    "check_registry_completeness",
    "check_signatures",
    "verify_kernel_setup",
    "verify_registry",
]
