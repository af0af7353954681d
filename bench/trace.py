"""Reduce a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler writes: the operations
of each TPU device (the ``XLA Ops`` line of its ``/device:TPU:<i>`` plane)
and the host's annotated spans (``/host:CPU``).  ``Trace`` then answers, for
the window that one host annotation spans:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any;
- ``kernel(name)``: device seconds and calls of the operations whose name
  contains ``name`` (a Pallas kernel's operation carries its name);
- ``top_ops``: device seconds by operation name;
- ``idle_gaps``: the idle device time inside the window, by what the host
  was doing: the host spans that cover the middle of each gap.

An event of the ``XLA Ops`` line is named by its HLO instruction text; the
name kept is the instruction's name (``%while.12 = ...`` gives
``while.12``).  A control-flow operation (``while``, ``conditional``,
``call``) spans the operations it runs: it counts towards busy time, but
not among the top operations, which would count its body twice.

When the device's trace buffers fill, the profiler drops the rest of the
window and marks the drop with a ``Trace Buffers Dropped`` event; the
window then ends where the drop begins, and ``complete`` is False.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
_DROPPED = "Trace Buffers Dropped"
_CONTROL_FLOW = ("while", "conditional", "call")


def op_name(event_name):
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def profile_options():
    """Profiler options of a traced run: device and annotated host spans,
    no Python function tracing (it would slow the host it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def xplane_file(log_dir):
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path, window):
    """``Trace`` of the file at ``path`` over the host span named
    ``window``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, drops = {}, [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
                else:
                    drops.extend(e.start_ns for e in line.events
                                 if e.name == _DROPPED)
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                             line.name) for e in line.events)
    return Trace(devices, host, window, min(drops) if drops else None)


def merge(intervals):
    """Union of ``(start, end)`` intervals, as disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Trace:
    def __init__(self, devices, host, window, dropped_at=None):
        spans = [h for h in host if h[0] == window]
        if len(spans) != 1:
            raise RuntimeError(f"expected one host span {window!r} in the "
                               f"trace, found {len(spans)}")
        _, self.t0, self.t1, self._line = spans[0]
        self.span_s = (self.t1 - self.t0) * 1e-9
        self.complete = dropped_at is None or dropped_at >= self.t1
        if not self.complete:
            self.t1 = max(self.t0, dropped_at)
        clip = self._clip
        self.devices = {name: [(n, *clip(s, e)) for n, s, e in ops
                               if clip(s, e)[1] > clip(s, e)[0]]
                        for name, ops in devices.items()}
        self.devices = {k: v for k, v in self.devices.items() if v}
        # the host spans on the window's thread, outermost first
        self.host = sorted((h for h in host if h[3] == self._line
                            and h[0] != window and h[2] > self.t0
                            and h[1] < self.t1),
                           key=lambda h: (h[1], -h[2]))

    def _clip(self, s, e):
        return max(s, self.t0), min(e, self.t1)

    @property
    def window_s(self):
        return (self.t1 - self.t0) * 1e-9

    def _busy(self, ops):
        return merge((s, e) for _, s, e in ops)

    @property
    def busy_s(self):
        if not self.devices:
            return 0.0
        total = sum(e - s for ops in self.devices.values()
                    for s, e in self._busy(ops))
        return total * 1e-9 / len(self.devices)

    @property
    def num_ops(self):
        return sum(len(ops) for ops in self.devices.values())

    def kernel(self, name):
        """(device seconds, calls) of the operations named after ``name``,
        summed over devices."""
        hits = [e - s for ops in self.devices.values() for n, s, e in ops
                if name in n]
        return sum(hits) * 1e-9, len(hits)

    def top_ops(self, k=10):
        total = defaultdict(int)
        for ops in self.devices.values():
            for n, s, e in ops:
                if not n.startswith(_CONTROL_FLOW):
                    total[n] += e - s
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9] for n, t in top]

    def idle_gaps(self, k=10):
        """Idle seconds inside the window, summed by the host spans that
        cover each gap's middle (the innermost two, outer first), largest
        first; device 0's gaps where several devices ran."""
        if not self.devices:
            return [["(no device ops)", self.window_s]]
        busy = self._busy(self.devices[sorted(self.devices)[0]])
        edges = [self.t0] + [t for iv in busy for t in iv] + [self.t1]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        total = defaultdict(int)
        active, nxt = [], 0
        for s, e in gaps:                      # in time order: one sweep
            mid = (s + e) // 2
            while nxt < len(self.host) and self.host[nxt][1] <= mid:
                active.append(self.host[nxt])
                nxt += 1
            active = [h for h in active if h[2] > mid]
            label = "/".join(h[0] for h in active[-2:]) or "(no host span)"
            total[label] += e - s
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9] for n, t in top]
