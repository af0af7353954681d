"""Static analysis for models and compiled programs (``RPL###`` rules).

Three layers, one code vocabulary (see :mod:`repro.lint_rules` for the rule
registry and :mod:`repro.core.errors` for the runtime twins):

1. :func:`lint_model` — the abstract model linter.  The model is traced
   *once* with the same inert probe the enum-aware ``log_density`` uses, and
   every coded runtime error/warning raised during that probe trace becomes
   a finding with the same ``RPL`` code the runtime would raise —
   lint/runtime parity for the whole error family is structural, not
   maintained by hand.  Post-trace rules cover the defects the runtime
   tolerates (silent downcasts, unmatched handler keys, baked seed
   handlers).  Pass ``jax.ShapeDtypeStruct`` leaves in
   ``model_args``/``model_kwargs`` to run the whole trace under
   ``jax.eval_shape`` — zero FLOPs on the data (value rules like the
   observed-support check skip traced values; with concrete inputs they
   check the real data).
2. :func:`analyze` — the jaxpr hazard analyzer: recompile hazards (large
   constants baked into the program), host callbacks on the hot path, and
   precision-losing dtype conversions.  :func:`check_time_independence`
   asserts the PR-4 invariant that ``markov`` programs have T-independent
   equation counts.
3. The kernel/handler invariant registry lives in
   :mod:`repro.lint_rules.invariants` (re-exported here) and is driven by
   the declarative op table in :mod:`repro.kernels.ops`.

CLI: ``python -m repro.lint <module:model>`` (see :mod:`repro.lint`).
Inference hooks: ``MCMC(..., validate=True)`` / ``SVI(..., validate=True)``
run :func:`lint_model` once per (pre-compile) setup and raise on errors.
"""
from __future__ import annotations

import warnings
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..lint_rules import ERROR, RULES, WARN
from .errors import ReproError, ReproValueError, warning_code
from .handlers import Messenger, condition, do, seed, substitute, trace
from .infer.enum import _EnumProbe, _first_available_dim, config_enumerate
from .infer.enum import enum as _enum


class Finding(NamedTuple):
    """One lint result: a rule code, its severity, the offending site (when
    one can be named), and the full actionable message."""

    code: str
    severity: str
    site: Optional[str]
    message: str

    def __str__(self):
        where = f" (site '{self.site}')" if self.site else ""
        return f"{self.severity.upper():5s} {self.code}{where}: {self.message}"


class LintResult:
    """Findings of one lint pass.  Falsy-clean: ``result.ok`` is True when
    no *error*-severity finding exists (warnings don't fail a model)."""

    def __init__(self, findings):
        self.findings = list(findings)

    @property
    def errors(self):
        return [f for f in self.findings if f.severity == ERROR]

    @property
    def warnings(self):
        return [f for f in self.findings if f.severity == WARN]

    @property
    def ok(self) -> bool:
        return not self.errors

    def codes(self):
        return {f.code for f in self.findings}

    def __str__(self):
        if not self.findings:
            return "ok: no findings"
        return "\n".join(str(f) for f in self.findings)

    def __repr__(self):
        return (f"LintResult(errors={len(self.errors)}, "
                f"warnings={len(self.warnings)})")

    def raise_if_errors(self):
        errs = self.errors
        if errs:
            raise ReproValueError(
                f"model failed lint with {len(errs)} error(s):\n"
                + "\n".join(str(f) for f in errs),
                code=errs[0].code, site=errs[0].site)
        return self


def _mk_finding(code, severity, site, message):
    # unknown codes stay visible rather than crashing the linter itself
    if code not in RULES:
        return Finding(code, severity, site, message)
    text = message
    prefix = f"[{code}] "
    if text.startswith(prefix):
        text = text[len(prefix):]
    return Finding(code, severity, site, text)


def _dedupe(findings):
    seen, out = set(), []
    for f in findings:
        key = (f.code, f.site)
        if key in seen:
            continue
        seen.add(key)
        out.append(f)
    return out


def _handler_chain(model):
    """The Messenger instances baked into the model callable, outermost
    first (``substitute(seed(model, key), data=...)`` -> [substitute, seed])."""
    chain = []
    m = model
    while isinstance(m, Messenger) and m.fn is not None:
        chain.append(m)
        m = m.fn
    return chain


def _finding_from_error(e: ReproError) -> Finding:
    code = e.code or "RPL000"
    sev = RULES[code].severity if code in RULES else ERROR
    return _mk_finding(code, sev, getattr(e, "site", None), str(e))


_X64 = "float64"


def _check_downcast(tr, findings):
    """RPL010: a float64 numpy array observed/substituted into the model is
    silently truncated to float32 the moment it meets a jnp op (x64 off)."""
    if jax.config.jax_enable_x64:
        return
    for name, site in tr.items():
        v = site.get("value")
        if isinstance(v, np.ndarray) and v.dtype == np.float64:
            findings.append(_mk_finding(
                "RPL010", WARN, name,
                f"site '{name}' carries a float64 numpy value while JAX x64 "
                "is disabled: it will be silently downcast to float32 inside "
                "the compiled program. Cast the data to float32 explicitly, "
                "or enable jax_enable_x64."))


def _check_unmatched_handlers(chain, findings):
    """RPL006 (lint side): after the probe trace, any substitute/condition/
    do data key that matched no site is a dead key — a typo'd name or a
    site the handler cannot see."""
    for h in chain:
        if not isinstance(h, (substitute, condition, do)):
            continue
        data = getattr(h, "data", None)
        if not isinstance(data, dict):
            continue
        for name in sorted(set(data) - h._seen):
            findings.append(_mk_finding(
                "RPL006", ERROR, name,
                f"{type(h).__name__} data key '{name}' matched no site in "
                "the model execution: check the name against "
                "trace(model).get_trace() (sites under `scope` carry a "
                "'prefix/' and blocked sites are invisible to outer "
                "handlers)."))


def _check_baked_handlers(chain, findings):
    """RPL015: a ``seed`` handler baked into the model callable captures its
    key at trace time — under ``jit`` every call replays the same
    randomness (docs/handlers.md global rule: create handler state inside
    the traced function)."""
    for h in chain:
        if isinstance(h, seed):
            findings.append(_mk_finding(
                "RPL015", WARN, None,
                "a `seed` handler is baked into the model callable: under "
                "`jit` its captured key is a trace-time constant and every "
                "call replays the same randomness. Pass the bare model and "
                "seed it inside the traced function (docs/handlers.md)."))


def lint_model(model, model_args: Tuple = (), model_kwargs: Optional[dict]
               = None, *, mode: str = "density",
               max_plate_nesting: Optional[int] = None,
               params: Optional[dict] = None) -> LintResult:
    """Lint ``model`` by tracing it once with the inert enum probe.

    ``mode="density"`` (default) checks the model as inference evaluates it:
    seeded, enumerable discrete latents auto-marked — exactly the
    ``log_density`` path ``MCMC``/``SVI`` compile.  ``mode="simulate"``
    checks it as a *bare simulation* (no implicit seeding), which is how the
    unseeded-latent rule (RPL009) and the unseeded-subsample rule (RPL012)
    become reachable.

    ``max_plate_nesting`` cross-checks the enumeration dim budget the caller
    intends to compile with (RPL003).  ``params`` are substituted *outside*
    the enumeration machinery — the exact handler geometry of
    ``log_density(model, args, kwargs, params)`` — so a param targeting an
    enumerated site surfaces as RPL008 and a dead param key as RPL006.
    Leaves of ``model_args`` / ``model_kwargs`` may be
    ``jax.ShapeDtypeStruct`` — the trace then runs under ``jax.eval_shape``
    (zero FLOPs on the data, value rules skip traced values); with concrete
    inputs the probe runs eagerly and value rules (RPL005/RPL010) check the
    real data.
    """
    if mode not in ("density", "simulate"):
        raise ValueError(f"unknown lint mode {mode!r}")
    model_kwargs = dict(model_kwargs or {})
    findings: list = []

    leaves, treedef = jax.tree_util.tree_flatten(
        (tuple(model_args), model_kwargs),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    struct_ix = [i for i, leaf in enumerate(leaves)
                 if isinstance(leaf, jax.ShapeDtypeStruct)]

    def run(*abstract):
        filled = list(leaves)
        for i, a in zip(struct_ix, abstract):
            filled[i] = a
        args, kwargs = jax.tree_util.tree_unflatten(treedef, filled)
        _run_model_rules(model, args, kwargs, mode, max_plate_nesting,
                         params, findings)
        return 0

    try:
        if struct_ix:
            # abstract pass: ShapeDtypeStruct leaves become tracers, so the
            # trace costs zero FLOPs on the data (value rules skip tracers)
            jax.eval_shape(run, *[leaves[i] for i in struct_ix])
        else:
            # concrete pass: one eager Python-level probe trace (the same
            # work any pre-inference trace does) — value rules fully active
            run()
    except ReproError as e:
        findings.append(_finding_from_error(e))
    except Exception as e:  # noqa: BLE001 — any trace crash is a finding
        findings.append(Finding(
            "RPL000", ERROR, None,
            f"model failed to trace: {type(e).__name__}: {e}"))
    return LintResult(_dedupe(findings))


def _run_model_rules(model, args, kwargs, mode, max_plate_nesting, params,
                     findings):
    chain = _handler_chain(model)
    _check_baked_handlers(chain, findings)

    def _with_params(runner):
        # params apply outside the enum machinery, exactly as log_density
        # substitutes them — RPL008 geometry is preserved
        return substitute(runner, data=params) if params is not None \
            else runner

    marked = config_enumerate(model)
    runner = seed(marked, jax.random.PRNGKey(0)) if mode == "density" \
        else marked
    probe = _EnumProbe(runner)
    param_sub = _with_params(probe)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        tr = trace(param_sub).get_trace(*args, **kwargs)
    for w in caught:
        code = warning_code(w.message)
        if code:
            sev = RULES[code].severity if code in RULES else WARN
            findings.append(_mk_finding(code, sev, None, str(w.message)))

    if probe.found:
        if max_plate_nesting is not None \
                and probe.max_plate_nesting > max_plate_nesting:
            findings.append(_mk_finding(
                "RPL003", ERROR, None,
                f"the model uses {probe.max_plate_nesting} plate/batch "
                f"dim(s) but max_plate_nesting={max_plate_nesting}: "
                "enumeration dims would land on plate dims and corrupt the "
                f"marginal. Pass max_plate_nesting="
                f"{probe.max_plate_nesting} (or more)."))
        else:
            # re-trace under a real enum handler at the caller's budget so
            # allocator collisions (RPL003) surface exactly as the compiled
            # log_density would hit them
            fad = _first_available_dim(probe, max_plate_nesting)
            runner2 = seed(config_enumerate(model), jax.random.PRNGKey(0)) \
                if mode == "density" else config_enumerate(model)
            try:
                trace(_with_params(_enum(
                    runner2, first_available_dim=fad))).get_trace(
                    *args, **kwargs)
            except ReproError as e:
                findings.append(_finding_from_error(e))

    extra = [param_sub] if params is not None else []
    _check_unmatched_handlers(chain + extra, findings)
    _check_downcast(tr, findings)


# ---------------------------------------------------------------------------
# layer 2: jaxpr hazard analysis
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from _iter_eqns(sub)


def _sub_jaxprs(param):
    """Jaxprs nested inside an eqn param (scan/cond/pjit bodies)."""
    vals = param if isinstance(param, (list, tuple)) else [param]
    out = []
    for v in vals:
        inner = getattr(v, "jaxpr", None)
        if inner is not None and hasattr(inner, "eqns"):
            out.append(inner)
        elif hasattr(v, "eqns"):
            out.append(v)
    return out


def count_eqns(closed_jaxpr) -> int:
    """Total equation count of a closed jaxpr, including nested bodies."""
    return sum(1 for _ in _iter_eqns(closed_jaxpr.jaxpr))


def analyze(fn: Callable, *args, const_bytes_limit: int = 1 << 20,
            **kwargs) -> LintResult:
    """Inspect the closed jaxpr of ``fn(*args, **kwargs)`` for hazards.

    - RPL101: a constant larger than ``const_bytes_limit`` baked into the
      program (a closed-over array: copied into every executable, re-hashed
      every dispatch, and a recompile when it changes identity).
    - RPL102: host callbacks on the hot path (``pure_callback``/
      ``io_callback``/``debug_callback`` force a device→host sync per call).
      Callbacks whose target function is marked with
      :func:`repro.obs.sanction` are skipped — the telemetry subsystem's
      chunk-boundary drain is the one sanctioned host transfer (it rides a
      sync the executor pays anyway for progress/checkpoints).
    - RPL103: precision-losing float conversions inside the program.

    Zero FLOPs: the program is traced, never executed.
    """
    from ..obs import is_sanctioned
    findings = []
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    for c in closed.consts:
        nbytes = getattr(c, "nbytes", 0)
        if nbytes > const_bytes_limit:
            findings.append(_mk_finding(
                "RPL101", WARN, None,
                f"a {nbytes}-byte constant (shape "
                f"{getattr(c, 'shape', '?')}, dtype "
                f"{getattr(c, 'dtype', '?')}) is baked into the jaxpr: pass "
                "it as an argument (donate or close over device arrays "
                "deliberately) to avoid per-compile copies and recompiles "
                "on identity change."))
    for eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if "callback" in name:
            if any(is_sanctioned(v) for v in eqn.params.values()):
                continue
            findings.append(_mk_finding(
                "RPL102", WARN, None,
                f"host callback primitive '{name}' inside the program: each "
                "call synchronizes device→host. Keep callbacks out of "
                "sampling/density hot paths (or guard them behind debug "
                "flags)."))
        elif name == "convert_element_type":
            old = eqn.invars[0].aval.dtype
            new = eqn.params.get("new_dtype")
            if (new is not None
                    and jnp.issubdtype(old, jnp.floating)
                    and jnp.issubdtype(new, jnp.floating)
                    and jnp.dtype(new).itemsize < jnp.dtype(old).itemsize):
                findings.append(_mk_finding(
                    "RPL103", WARN, None,
                    f"precision-losing conversion {jnp.dtype(old).name} -> "
                    f"{jnp.dtype(new).name} inside the program: if this is "
                    "not an intentional mixed-precision cast, an f64/f32 "
                    "input is being silently narrowed."))
    return LintResult(_dedupe(findings))


def check_time_independence(make_fn: Callable, sizes: Tuple[int, ...]
                            = (4, 8)) -> LintResult:
    """RPL104: assert a chain program's jaxpr size does not grow with T.

    ``make_fn(T) -> (fn, args)`` builds the program at one time-axis length.
    ``markov`` elimination runs inside ``lax.scan``, so the traced program
    must have the *same* equation count at every T — growth means the chain
    got unrolled (O(T) code size, O(T) compile time).  A chain batched by
    plates around ``markov`` (RPL014 refuses a state inside a plate opened
    within the transition) still runs one contraction per step over all
    its rows.
    """
    counts = {}
    for t in sizes:
        fn, args = make_fn(t)
        counts[t] = count_eqns(jax.make_jaxpr(fn)(*args))
    findings = []
    if len(set(counts.values())) != 1:
        findings.append(_mk_finding(
            "RPL104", ERROR, None,
            f"program size grows with the time axis: eqn counts {counts}. "
            "markov chains must eliminate inside lax.scan (T-independent "
            "program, O(T*K^2) runtime) — check for Python loops over "
            "time steps."))
    return LintResult(findings)


__all__ = [
    "Finding",
    "LintResult",
    "analyze",
    "check_time_independence",
    "count_eqns",
    "lint_model",
]
