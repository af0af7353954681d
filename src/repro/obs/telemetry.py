"""``Telemetry`` — the one object ``MCMC`` consumes.

Bundles the four telemetry concerns so the executor stays small: the
metrics stream buffer (chunk-boundary drains of ``metrics_fn`` outputs),
phase spans (also written into any active ``jax.profiler`` trace), event
sinks (JSONL), and the per-run manifest.  Construction is cheap and
declarative; all I/O is lazy until :meth:`begin_run` resolves where
artifacts go (``dir=...`` here, else next to the run's
``checkpoint_dir``, else memory only).

Example::

    from repro import obs
    tele = obs.Telemetry(dir="runs/exp1")       # events.jsonl + manifest
    mcmc = MCMC(kernel, 500, 1000, num_chains=4, telemetry=tele)
    mcmc.run(key, data)
    tele.buffer.series()["accept_prob"]          # (chains, draws)
    [s.name for s in tele.spans]                 # phase timings

The invariants the rest of the repo holds this object to:

- enabling it never changes the sample stream (metrics ride the scan's
  collect outputs, never the carry — bit-identity is tested);
- it never adds a host sync beyond the one-per-chunk drain;
- it never calls ``repro.distributed.checkpoint.save`` (kill-point
  semantics of the preemption tests stay fixed).
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax

from .divergences import DivergenceRing
from .manifest import MANIFEST_NAME, RunManifest
from .metrics import MetricsBuffer
from .report import LiveReporter
from .sinks import JsonlSink, MemorySink, NullSink, stamp
from .spans import SpanClock


class Telemetry:
    def __init__(self, *, metrics: bool = True, dir: Optional[str] = None,
                 sink=None, events: bool = True, manifest: bool = True,
                 reporter: Optional[LiveReporter] = None,
                 forensics: bool = True, forensics_capacity: int = 256):
        self.metrics = bool(metrics)
        self.dir = str(dir) if dir is not None else None
        self._sink_arg = sink
        self._events = bool(events)
        self._manifest_enabled = bool(manifest)
        self.reporter = reporter if reporter is not None else LiveReporter()
        # divergence forensics: a bounded ring of divergent-transition
        # records the executor feeds at the chunk drain (positions fetched
        # only for divergent draws — a clean run pays nothing), written to
        # divergences.json at finish_run for `python -m
        # repro.obs.divergences <run_dir>`
        self._forensics_enabled = bool(forensics)
        self._forensics_capacity = int(forensics_capacity)
        self.forensics: Optional[DivergenceRing] = None
        self.buffer = MetricsBuffer()
        self.sink = sink if sink is not None else NullSink()
        self.manifest: Optional[RunManifest] = None
        self.spans = []
        self.counters = {}
        self._artifact_dir = None

    # -- run lifecycle ------------------------------------------------------
    def begin_run(self, run_config: dict, *, default_dir=None,
                  resume: bool = False) -> None:
        """Reset per-run state and open artifacts.  Artifacts land in
        ``self.dir`` when set, else next to ``default_dir`` (the run's
        checkpoint_dir), else stay in memory (``MemorySink``).

        ``run_config`` may be provisional (the executor calls this before
        building the kernel setup, so early spans have a live sink);
        :meth:`commit_run_config` fills in the setup-derived fields and
        emits the ``run_started`` event."""
        base = self.dir if self.dir is not None else default_dir
        self._artifact_dir = base
        self.buffer.clear()
        self.spans = []
        self.counters = {}
        self.forensics = (DivergenceRing(self._forensics_capacity)
                          if self._forensics_enabled else None)
        self._run_config = dict(run_config)
        self._resume = bool(resume)
        if self._sink_arg is not None:
            self.sink = self._sink_arg
        elif not self._events:
            self.sink = NullSink()
        elif base is not None:
            self.sink = JsonlSink(os.path.join(base, "events.jsonl"))
        else:
            self.sink = MemorySink()
        if self._manifest_enabled and base is not None:
            self.manifest = RunManifest(os.path.join(base, MANIFEST_NAME))
            self.manifest.begin_session(run_config=self._run_config,
                                        resume=resume)
        else:
            self.manifest = None

    def commit_run_config(self, **updates) -> None:
        """Finalize the run record once the kernel setup exists (algo,
        setup hash) and announce the run on the event stream."""
        self._run_config.update(updates)
        if self.manifest is not None:
            self.manifest.data["run"].update(updates)
            self.manifest.flush()
        self.event("run_started", resume=self._resume, **self._run_config)

    def set_resumed_at(self, done: int) -> None:
        """Record the iteration a resumed session restarted from (known
        only after the checkpoint restore)."""
        if self.manifest is not None:
            self.manifest.session()["resumed_at_iteration"] = int(done)
            self.manifest.flush()

    def finish_run(self, final: dict) -> None:
        self.event("run_finished", **final)
        if self.manifest is not None:
            self.manifest.finish_session(counters=dict(self.counters),
                                         final=final)
        if self.forensics is not None and self._artifact_dir is not None:
            # plain atomic JSON like the manifest — never checkpoint.save,
            # so the preemption kill-point indices stay fixed
            self.forensics.write(self._artifact_dir)
        self.sink.close()

    # -- events / counters --------------------------------------------------
    def event(self, kind: str, **payload) -> None:
        self.sink.emit(stamp(kind, payload))

    def counter(self, name: str, inc: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(inc)

    # -- spans --------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time one host-side phase.  Yields a mutable attr dict the body
        may extend (e.g. marking a chunk cold after the compile-cache miss
        is known).  The span is also a ``jax.profiler.TraceAnnotation``:
        inside an active profiler trace it is a host event of the same
        name, on the clock of the device's operations (with no trace
        active it costs about a microsecond of host time)."""
        clock = SpanClock(name, attrs)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield clock.attrs
        finally:
            record = clock.close()
            self.spans.append(record)
            self.sink.emit(stamp("span", record.to_event()))
            if self.manifest is not None:
                self.manifest.record_span(record)

    # -- chunk boundary -----------------------------------------------------
    def drain_chunk(self, phase: str, start: int, end: int, metrics_tree):
        """The sanctioned once-per-compiled-chunk host drain: transfer the
        chunk's stacked metrics, buffer them, emit the chunk event.
        Returns the host tree (for the live reporter) or None."""
        host = None
        if metrics_tree is not None:
            host = self.buffer.add_chunk(phase, start, end, metrics_tree)
        if self.manifest is not None:
            self.manifest.record_chunk(start, end, phase)
        payload = {"phase": phase, "start": start, "end": end}
        if host is not None:
            payload["metrics"] = {
                k: {"mean": float(v.mean()),
                    "last": float(v[..., -1].mean())}
                for k, v in host.items()}
        self.event("chunk", **payload)
        return host

    def record_divergences(self, total: int) -> None:
        if self.manifest is not None:
            self.manifest.set_divergences(total)
