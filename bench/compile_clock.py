"""JAX's own trace, lowering and backend-compile spans (a persistent-cache
fetch is inside the backend span), summed as a union so nested spans count
once."""
from __future__ import annotations

STAGES = {"trace": "/jax/core/compile/jaxpr_trace_duration",
          "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "backend": "/jax/core/compile/backend_compile_duration"}


class CompileClock:
    def __init__(self):
        import jax
        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event in STAGES.values():
            self.spans.append((event, start, end))

    def seconds(self, t0, t1):
        total, reach = 0.0, t0
        for s, e in sorted((s, e) for _, s, e in self.spans):
            s, e = max(s, reach), min(e, t1)
            if e > s:
                total += e - s
                reach = e
        return total

    def count(self, t0, t1):
        """Backend compiles (or cache fetches) that began inside [t0, t1]."""
        return sum(1 for ev, s, _ in self.spans
                   if ev == STAGES["backend"] and t0 <= s <= t1)
