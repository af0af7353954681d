"""Phase spans: wall-clock timing of the executor's host-side phases.

A span brackets one phase of an ``MCMC.run`` — setup (model trace + lint),
resume-restore, each compiled warmup/sample chunk, the host work at each
chunk boundary (``chunk_drain``), each checkpoint write —
entirely *outside* the compiled programs: the span clock starts before the
chunk program is invoked and stops after its outputs are used host-side, so
the first span over a fresh ``(setup, length)`` pair includes that
program's compile time and later spans over the same program measure pure
device execution.  That asymmetry is the compile-visibility story: the
``_exec_cache`` hit/miss counters say *whether* a chunk compiled, the span
pair says *what it cost*, and no jitted callable is ever wrapped (wrapping
would poison ``jax.eval_shape`` calls on the same programs with bogus
timings).

Each span is also a ``jax.profiler.TraceAnnotation``, so a profiler
trace taken around a run shows the phases beside the device's operations —
see :meth:`repro.obs.telemetry.Telemetry.span`.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Optional


class SpanRecord(NamedTuple):
    """One closed span: name, wall-clock seconds, and static attributes
    (chunk range, phase, cold/warm program, checkpoint step, ...)."""

    name: str
    start_unix: float
    duration_s: float
    attrs: tuple  # sorted (key, value) pairs — hashable, JSON-friendly

    def attr(self, key, default=None):
        for k, v in self.attrs:
            if k == key:
                return v
        return default

    def to_event(self) -> dict:
        event = {"span": self.name, "start_unix": self.start_unix,
                 "duration_s": self.duration_s}
        event.update(dict(self.attrs))
        return event


class SpanClock:
    """Open span being timed; closed by the ``Telemetry.span`` context
    manager into a :class:`SpanRecord`."""

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = dict(attrs)
        self.start_unix = time.time()
        self._t0 = time.monotonic()

    def close(self, extra_attrs: Optional[dict] = None) -> SpanRecord:
        if extra_attrs:
            self.attrs.update(extra_attrs)
        return SpanRecord(
            name=self.name, start_unix=self.start_unix,
            duration_s=time.monotonic() - self._t0,
            attrs=tuple(sorted(self.attrs.items())))
