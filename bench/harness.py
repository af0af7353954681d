"""One run of one cell: set-up, a window of back-to-back fits, the checks.

The unit of work is one fit: ``MCMC.run(key_i, *data)`` on one ``MCMC``
instance, warmup and sampling both, with the key folded from the seed and
the fit's index.  Set-up makes the data on the device, then runs one fit of
the cell's own shapes, which compiles or fetches from the persistent cache
every program the window drives.  The window then starts fits until the
mean fit time so far says the next would end past ``seconds``; it runs from
the first timed fit's start to the last one's end and holds whole fits.

With ``trace`` the window is one fit under the profiler, and the run
reports the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import tempfile
import time
import traceback

import numpy as np

from . import checks, spec, trace as trace_mod
from .compile_clock import CompileClock

FIT_SPAN = "bench_fit"


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def device_info(chips):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU found (platform {dev.platform!r}); this "
                     "benchmark measures the chip and has no CPU fallback")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips], {"platform": dev.platform,
                             "kind": dev.device_kind, "count": len(devices)}


def seed_keys(seed):
    """Keys of one run, all folded from ``seed`` (any non-negative int
    below 2**64)."""
    from jax import random
    base = random.fold_in(random.fold_in(random.PRNGKey(0), seed >> 32),
                          seed & 0xFFFFFFFF)
    return {"data": random.fold_in(base, 0), "warm": random.fold_in(base, 1),
            "fits": random.fold_in(base, 2)}


def _annotated_telemetry():
    """Telemetry whose spans also annotate the profiler's trace, so a gap
    on the device can be put down to the executor phase around it."""
    import jax

    from repro import obs

    class Annotated(obs.Telemetry):
        @contextlib.contextmanager
        def span(self, name, **attrs):
            with jax.profiler.TraceAnnotation(name), \
                    super().span(name, **attrs) as a:
                yield a

    return Annotated(metrics=True)


def build_mcmc(cell):
    from repro.core.infer import MCMC, NUTS
    t = cell.traffic
    if t["kernel"] != "NUTS":
        raise ValueError(f"unknown kernel {t['kernel']!r}")
    kernel = NUTS(cell.model.model, max_tree_depth=t["max_tree_depth"],
                  target_accept_prob=t["target_accept_prob"])
    return MCMC(kernel, num_warmup=t["num_warmup"],
                num_samples=t["num_samples"], num_chains=t["num_chains"],
                chain_method=t["chain_method"],
                telemetry=_annotated_telemetry())


class Fit:
    """What one fit produced and what it cost."""

    error = None

    def __init__(self, mcmc, key, model_args):
        import jax
        t0 = time.time()
        try:
            mcmc.run(key, *model_args)
            self.samples = mcmc.get_samples(group_by_chain=True)
            jax.block_until_ready(self.samples)
        except Exception:  # a fit that raises is counted, not fatal
            self.error = traceback.format_exc(limit=3)
            log(f"fit raised:\n{self.error}")
        self.seconds = time.time() - t0
        tele = mcmc.telemetry
        self.cache_miss = tele.counters.get("exec_cache_miss", 0)
        if self.error is not None:
            self.grads = 0
            return
        self.steps = {ph: tele.buffer.series(ph)["num_steps"]
                      for ph in ("warmup", "sample")}
        self.grads = int(sum(int(np.sum(s)) for s in self.steps.values()))
        collected = mcmc._collected
        self.z, self.pe = collected["z"], collected["potential_energy"]
        self.last_z = mcmc.last_state.z
        self.last_grad = mcmc.last_state.z_grad

    def fetch(self):
        """Bring the fit's arrays to the host."""
        import jax
        if self.error is None:
            (self.samples, self.z, self.pe, self.last_z,
             self.last_grad) = jax.device_get(
                (self.samples, self.z, self.pe, self.last_z, self.last_grad))
        self.failure = checks.fit_failure(self)
        return self


class LayerRun:
    """What a per-layer metric's ``read(run)`` is given: the traced fits,
    the reduced trace, the cell and the chip's peaks.

    ``useful_grads`` counts the gradients the chains needed over the whole
    traced fit; ``window_grads`` is the same where the trace holds the
    whole fit, and None where the profiler dropped its end, since the
    gradients inside a cut trace are not counted.
    """

    def __init__(self, cell, trace, fits, peak):
        self.cell, self.trace, self.fits, self.peak = cell, trace, fits, peak
        self.useful_grads = sum(f.grads for f in fits)
        self.window_grads = self.useful_grads if trace.complete else None


def check_kernels(cell, mcmc):
    """Refuse a run whose compiled sample chunk lacks one of the cell's
    Pallas kernels (each TPU custom call is named after its kernel)."""
    names = set()
    for key, fn in mcmc._exec_cache.items():
        if key[0] == "sample":
            text = fn.lower(mcmc.last_state).compile().as_text()
            names |= {line.split(" = ")[0].strip().lstrip("%")
                      for line in text.splitlines()
                      if 'custom_call_target="tpu_custom_call"' in line}
    missing = [k for k in cell.model.KERNELS
               if not any(k in n for n in names)]
    if missing:
        raise RuntimeError(f"kernels {missing} are not in the compiled "
                           f"sample chunk (it runs {sorted(names)})")


def memory_peak(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def timed_fits(mcmc, keys, model_args, t0, seconds):
    """Fits from ``t0`` until the mean so far says the next would end past
    ``seconds``; the fits and the time the last one ended."""
    import jax
    fits = []
    while True:
        fits.append(Fit(mcmc, jax.random.fold_in(keys["fits"], len(fits)),
                        model_args))
        elapsed = time.time() - t0
        if elapsed + elapsed / len(fits) > seconds:
            return fits, time.time()


def traced_fit(mcmc, keys, model_args):
    """One fit under the profiler: the fit, the time it ended, and its
    reduced trace."""
    import jax
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(
            log_dir, profiler_options=trace_mod.profile_options())
        try:
            with jax.profiler.TraceAnnotation(FIT_SPAN):
                fit = Fit(mcmc, jax.random.fold_in(keys["fits"], 0),
                          model_args)
            t1 = time.time()
        finally:
            jax.profiler.stop_trace()
        tr = trace_mod.load(trace_mod.xplane_file(log_dir), FIT_SPAN)
    log(f"trace written and read in {time.time() - t1:.1f} s")
    return [fit], t1, tr


def run_cell(cell, seed, seconds, traced, *, t_start):
    """The result line of one run, as a dict."""
    import warnings

    import jax

    # a fused potential that falls back would take the kernel off the path
    warnings.filterwarnings("error", message=".*falling back to the plain")
    devices, device = device_info(cell.chips)
    try:
        peak = spec.peaks(device["kind"])
    except KeyError as e:
        raise NoChip(e.args[0]) from None
    clock = CompileClock()
    keys = seed_keys(seed)
    model_args, inputs = cell.model.make_data(keys["data"], cell.config)
    jax.block_until_ready(model_args)
    mcmc = build_mcmc(cell)
    warm = Fit(mcmc, keys["warm"], model_args)
    if warm.error is not None:
        raise RuntimeError("the warm-up fit raised")
    check_kernels(cell, mcmc)
    t0 = time.time()
    setup_s = t0 - t_start
    log(f"set-up {setup_s:.2f} s (compile {clock.seconds(t_start, t0):.2f} "
        f"s, warm-up fit {warm.seconds:.2f} s)")

    if traced:
        fits, t1, tr = traced_fit(mcmc, keys, model_args)
    else:
        fits, t1 = timed_fits(mcmc, keys, model_args, t0, seconds)
        tr = None
    window_s = t1 - t0
    misses = sum(f.cache_miss for f in fits)
    compiles = clock.count(t0, t1)
    log(f"window {window_s:.3f} s: {len(fits)} fits, exec_cache_miss "
        f"{misses}, backend compiles {compiles}")
    memory_peak_bytes = memory_peak(devices)

    # the program's state goes before the reference runs
    del mcmc, warm
    gc.collect()
    for f in fits:
        f.fetch()
    failed = [f for f in fits if f.failure is not None]
    for f in failed:
        log(f"failed fit: {f.failure.splitlines()[-1]}")
    sound = [f for f in fits if f.failure is None]
    numbers = (checks.gaps(cell.reference, inputs, sound) if sound
               else dict.fromkeys(checks.NAMES, float("nan")))
    numbers["failed_fits"] = len(failed)
    correct, rows = checks.judge(numbers, dict(cell.limits, failed_fits=0))

    device["memory_peak_bytes"] = memory_peak_bytes
    result = {"correct": bool(correct), "attempted": len(fits),
              "failed": len(failed)}
    if traced:
        run = LayerRun(cell, tr, fits, peak)
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
        log(f"trace: {tr.num_ops} device ops, busy {tr.busy_s:.3f} of "
            f"{tr.window_s:.3f} s, the fit spans {tr.span_s:.3f} s"
            + ("" if tr.complete else " (buffers dropped after the window)"))
    else:
        log("fits: " + ", ".join(f"{f.seconds:.3f} s {f.grads} grads"
                                 for f in fits))
        values = {
            "grad_evals_per_s": sum(f.grads for f in fits) / window_s,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = rows
    for name, row in rows.items():
        log(f"check {name} {row['value']} limit {row['limit']}")
    return result
