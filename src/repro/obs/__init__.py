"""``repro.obs`` — in-program telemetry for the unified MCMC executor.

The hot loop of this repo is a compiled ``lax.scan`` chunk that never
touches the host (the paper's whole pitch), which makes runtime visibility
a design problem: a callback in the sampling loop would force a device→host
sync per iteration (our own lint rule RPL102 exists to flag exactly that),
and Python-side counters can't see inside a compiled program at all.  The
telemetry layer therefore follows the same rule as the samplers themselves
(BlackJAX-style, arXiv 2402.10797): **metrics are state**, computed by a
pure ``metrics_fn(state) -> dict[str, scalar]`` declared on the
:class:`~repro.core.infer.kernel_api.KernelSetup`, folded into the chunked
scan's *collect* path (never the carry that feeds the next transition), and
drained host-side once per compiled chunk — the one sync a progress line or
checkpoint write already pays.  Sample streams are bit-identical with
metrics on or off, and enabling them compiles one additional program per
(setup, chunk length) instead of recompiling anything that already ran.

Public surface:

- :class:`~repro.obs.telemetry.Telemetry` — the facade ``MCMC`` consumes:
  metrics buffering, phase spans (each also a ``jax.profiler``
  annotation), counters, event sinks, run manifests.
- :data:`~repro.obs.scopes.SCOPES` — the fixed ``jax.named_scope`` names
  of the sampler's layers (potential, integrator, tree, adapt) inside the
  compiled programs, which a profiler trace's operations carry.
- :class:`~repro.obs.sinks.JsonlSink` / ``MemorySink`` — event writers;
  every event validates against ``event_schema.json``
  (``python -m repro.obs.validate events.jsonl run_manifest.json``).
- :mod:`~repro.obs.manifest` — per-run manifest (git rev, jax versions,
  device topology, mesh shape, kernel setup hash, chunk schedule, final
  diagnostics) written next to the checkpoint dirs; elastic resumes append
  a new session to the same record.
- :class:`~repro.obs.report.LiveReporter` — the chunk-boundary progress
  reporter (divergence deltas, step-size/accept summaries, streaming
  R-hat/ESS of a gated run, ETA).
- :mod:`~repro.obs.monitor` — streaming split R-hat / batch-means ESS
  accumulators and the :class:`Converged` stopping rule behind
  ``MCMC.run(..., until=...)`` (convergence-gated runs).
- :mod:`~repro.obs.divergences` — the divergent-transition ring buffer and
  ``python -m repro.obs.divergences <run_dir>`` localization CLI.
- :mod:`~repro.obs.compare` — the cross-run regression gate
  (``python -m repro.obs.compare <current> <baseline>``), diffing bench
  summaries and run manifests with per-metric thresholds.
- :func:`sanction` — marks a host callback as an executor-sanctioned
  chunk-boundary drain so the RPL102 hazard rule does not fire on it.

See ``docs/observability.md`` for the full contract.
"""
from .divergences import DivergenceRing
from .manifest import MANIFEST_NAME, RunManifest, collect_environment
from .metrics import MetricsBuffer, metrics_struct, validate_metrics_struct
from .monitor import Converged, ConvergenceMonitor, StreamingDiagnostics
from .report import LiveReporter
from .scopes import SCOPES
from .sinks import JsonlSink, MemorySink, NullSink
from .spans import SpanRecord
from .telemetry import Telemetry


def sanction(fn):
    """Mark ``fn`` as an executor-sanctioned chunk-boundary host drain.

    The jaxpr hazard rule RPL102 flags *any* host callback inside a
    compiled program, because on the sampling hot path each call is a
    device→host sync per iteration.  The telemetry design never needs one —
    metrics ride the collect path and are drained between chunk programs —
    but a callback that fires once per compiled *chunk* (not per iteration)
    is the same cost the executor's own drain already pays, and is a
    legitimate escape hatch (e.g. streaming chunk summaries from inside a
    larger jitted driver).  Decorating such a callback with ``sanction``
    records that intent on the function object, and
    :func:`repro.core.lint.analyze` skips RPL102 for it.
    """
    fn._repro_obs_sanctioned = True
    return fn


def is_sanctioned(fn) -> bool:
    """True iff ``fn`` (or a callable it wraps) passed through
    :func:`sanction`.  Unwraps the layers JAX's callback primitives add:
    ``_FlatCallback.callback_func`` (pure/io callbacks), functools wrappers,
    and closure cells (``jax.debug.callback``'s ``_flat_callback``)."""
    seen = set()

    def walk(obj, depth=0):
        if obj is None or id(obj) in seen or depth > 4:
            return False
        seen.add(id(obj))
        if getattr(obj, "_repro_obs_sanctioned", False):
            return True
        for attr in ("callback_func", "func", "fn", "__wrapped__"):
            if walk(getattr(obj, attr, None), depth + 1):
                return True
        cells = getattr(obj, "__closure__", None) or ()
        for cell in cells:
            try:
                inner = cell.cell_contents
            except ValueError:
                continue
            if callable(inner) and walk(inner, depth + 1):
                return True
        return False

    return walk(fn)


__all__ = [
    "Converged",
    "ConvergenceMonitor",
    "DivergenceRing",
    "JsonlSink",
    "LiveReporter",
    "MANIFEST_NAME",
    "MemorySink",
    "MetricsBuffer",
    "NullSink",
    "RunManifest",
    "SCOPES",
    "SpanRecord",
    "StreamingDiagnostics",
    "Telemetry",
    "collect_environment",
    "is_sanctioned",
    "metrics_struct",
    "sanction",
    "validate_metrics_struct",
]
