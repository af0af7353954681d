"""Rule registry for the ``repro`` static analyzer.

Three rule families, one code vocabulary (shared with the runtime via
:mod:`repro.core.errors`):

- ``RPL0xx`` — abstract model rules (:mod:`repro.lint_rules.model_rules`),
  found by tracing the model once under ``jax.eval_shape``;
- ``RPL1xx`` — jaxpr hazard rules (:mod:`repro.lint_rules.jaxpr_rules`),
  found by inspecting a compiled program's closed jaxpr;
- ``RPL2xx`` — kernel/handler invariants (:mod:`repro.lint_rules.invariants`),
  checked against the declarative op table in :mod:`repro.kernels.ops` and
  the :class:`~repro.core.infer.kernel_api.KernelSetup` field contract;
- ``RPL4xx`` — observability rules (:mod:`repro.lint_rules.obs_rules`):
  the ``KernelSetup.metrics_fn`` stream contract (shape discipline, no
  PRNG dependence) backing ``repro.obs``.

Each :class:`Rule` declares its *runtime twin*: the coded error or warning
the runtime raises for the same defect.  ``twin="error"``/``"warning"``
means the runtime raises/warns with the same ``RPL`` code (the error-parity
test in ``tests/test_lint.py`` enforces this); ``twin=None`` requires a
``justification`` explaining why the defect is silent at runtime.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

ERROR = "error"
WARN = "warn"


class Rule(NamedTuple):
    code: str
    title: str
    severity: str                       # default severity of findings
    twin: Optional[str]                 # "error" | "warning" | None
    justification: str = ""             # required when twin is None


RULES = {r.code: r for r in [
    # -- RPL0xx: abstract model rules --------------------------------------
    Rule("RPL001", "duplicate site name in one trace", ERROR, "error"),
    Rule("RPL002", "plate dim collision with an enclosing plate", ERROR,
         "error"),
    Rule("RPL003", "enumeration dim budget overflow vs max_plate_nesting",
         ERROR, "error"),
    Rule("RPL004", "sample/obs shape does not broadcast against its plate "
         "frame", ERROR, "error"),
    Rule("RPL005", "observed value outside the site's constraint support",
         ERROR, "error"),
    Rule("RPL006", "substitute/condition/do targets a nonexistent site",
         ERROR, "error"),
    Rule("RPL007", "handler targets a reparam-rewritten deterministic site",
         ERROR, "error"),
    Rule("RPL008", "handler targets an enumerated site", ERROR, "error"),
    Rule("RPL009", "unseeded latent sample reachable under jit", ERROR,
         "error"),
    Rule("RPL010", "float64 value entering an f32 chain (silent downcast)",
         WARN, None,
         "JAX downcasts float64 inputs silently when x64 is disabled — by "
         "design there is no runtime error site to attach a code to"),
    Rule("RPL011", "replay of a site recorded as observed but latent here",
         ERROR, "error"),
    Rule("RPL012", "subsampled plate traced without an rng key "
         "(deterministic arange fallback)", WARN, "warning"),
    Rule("RPL013", "enumerate mark on a non-enumerable (continuous) site",
         ERROR, "error"),
    Rule("RPL014", "markov state site inside a plate opened within the "
         "transition (open the plate around markov), or nested markov",
         ERROR, "error"),
    Rule("RPL015", "handler state baked into the model callable "
         "(seed key captured at trace time)", WARN, None,
         "a seed handler in the model chain re-splits its captured key per "
         "call eagerly, but under jit the key is baked at trace time and "
         "every call replays the same randomness — the runtime cannot "
         "distinguish that from intended reuse (docs/handlers.md, global "
         "rule: handler state must be created inside the traced function)"),
    # -- RPL1xx: jaxpr hazard rules ----------------------------------------
    Rule("RPL101", "large constant baked into the jaxpr (recompile/memory "
         "hazard)", WARN, None,
         "baked constants are valid programs; only the analyzer can see "
         "the closure boundary"),
    Rule("RPL102", "host callback on the hot path", WARN, None,
         "callbacks are legal ops; hotness is a property of the call site"),
    Rule("RPL103", "precision-losing dtype conversion inside the program",
         WARN, None,
         "dtype conversions are silent by design in XLA programs"),
    Rule("RPL104", "program size grows with the time axis (markov "
         "elimination must be T-independent)", ERROR, None,
         "eqn-count growth is only observable by comparing jaxprs at two "
         "sizes — there is no single-run runtime signal"),
    # -- RPL2xx: kernel/handler invariants ---------------------------------
    Rule("RPL201", "op missing its Pallas or ref registry entry", ERROR,
         None, "registry completeness is a repo invariant, not a runtime "
         "event"),
    Rule("RPL202", "Pallas/ref signature mismatch for a registered op",
         ERROR, None, "signatures are static properties of the source"),
    Rule("RPL203", "Pallas kernel (interpret mode) disagrees with its ref "
         "oracle", ERROR, None, "parity is verified by execution in the "
         "registry harness, not raised by the dispatch layer"),
    Rule("RPL204", "KernelSetup field contract violation", ERROR, None,
         "the contract is checked by the registry harness; jit itself "
         "fails later with an unhashability error that carries no code"),
    # -- RPL4xx: observability/metrics-stream rules ------------------------
    # (lint side in repro.lint_rules.obs_rules; the runtime twin is the
    # executor's eager pre-compile check, MCMC._check_metrics_contract)
    Rule("RPL401", "metrics_fn leaf violates the shape contract (scalar "
         "per-chain; scalar or (num_chains,) cross-chain)", ERROR, "error"),
    Rule("RPL402", "metrics_fn output depends on the state's rng key "
         "(metrics must observe the chain, never consume randomness)",
         ERROR, "error"),
    Rule("RPL403", "Converged stopping rule unsatisfiable for the run "
         "geometry (min_ess above the draw budget, max_rhat below 1, or a "
         "batch size the budget can never fill)", ERROR, "error"),
]}


def rule(code: str) -> Rule:
    return RULES[code]


__all__ = ["ERROR", "WARN", "RULES", "Rule", "rule"]
