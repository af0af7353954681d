"""Readings that the correctness limits are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 ... \
        [--faults <fault> ... --fault-seeds 4 5 6 ...] [--telemetry-cost]

For each seed, in one process: the cell's data from the seed, one fit
through the same path as the benchmark's window (``harness.Fit``), then the
numbers ``correct`` compares, for the program and for the control (the
reference one precision lower, in the program's place), and each fit's ESS.
Then, for each fault of ``bench/faults.py`` named, the same numbers of a
fit with that fault planted in the program, on each of ``--fault-seeds``.
One JSON line per fit on standard output.  ``--telemetry-cost`` then
traces one fit of the last seed's data with telemetry on and one with it
off, on the same key, and prints their fit seconds and device busy time.
The benchmark's own runs do not run this.
"""
import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fit_readings(cell, seed, control=True):
    import jax
    import numpy as np

    from bench import checks, harness
    from bench.ess import effective_sample_size
    keys = harness.seed_keys(seed)
    model_args, inputs = cell.model.make_data(keys["data"], cell.config)
    mcmc = harness.build_mcmc(cell)
    fit = harness.Fit(mcmc, jax.random.fold_in(keys["fits"], 0), model_args)
    fit.fetch()
    row = {"seed": seed, "grads": fit.grads, "failure": fit.failure}
    if fit.failure is None:
        ess = np.concatenate([np.ravel(effective_sample_size(v))
                              for v in fit.samples.values()])
        row.update(min_ess=float(ess.min()), median_ess=float(np.median(ess)),
                   mean_ess=float(ess.mean()),
                   program=checks.gaps(cell.reference, inputs, [fit]))
        if control:
            row["control"] = checks.gaps(cell.reference, inputs, [fit],
                                         control=True)
    return row, mcmc, model_args, keys


def telemetry_cost(mcmc, model_args, keys):
    import jax

    from bench import harness, trace as trace_mod
    key = jax.random.fold_in(keys["fits"], 1)
    out = {}
    tele = mcmc.telemetry
    for label in ("on", "off", "on_again"):
        mcmc.telemetry = tele if label != "off" else None
        mcmc.run(key, *model_args)  # compiles this setting's programs
        jax.block_until_ready(mcmc.get_samples())
        with tempfile.TemporaryDirectory() as log_dir:
            jax.profiler.start_trace(
                log_dir, profiler_options=trace_mod.profile_options())
            t0 = time.time()
            with jax.profiler.TraceAnnotation(harness.FIT_SPAN):
                mcmc.run(key, *model_args)
                jax.block_until_ready(mcmc.get_samples())
            seconds = time.time() - t0
            jax.profiler.stop_trace()
            tr = trace_mod.load(trace_mod.xplane_file(log_dir),
                                harness.FIT_SPAN)
        out[label] = {"fit_s": seconds, "busy_s": tr.busy_s,
                      "window_s": tr.window_s, "complete": tr.complete}
    mcmc.telemetry = tele
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--telemetry-cost", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.run import configure_jax
    configure_jax()
    import warnings

    from bench import faults, harness, spec
    warnings.filterwarnings("error", message=".*falling back to the plain")
    cell = spec.Cell(args.workload)
    harness.device_info(cell.chips)
    for seed in args.seeds:
        t0 = time.time()
        row, mcmc, model_args, keys = fit_readings(cell, seed)
        row["seconds"] = time.time() - t0
        print(json.dumps(row), flush=True)
    if args.telemetry_cost:
        print(json.dumps({"telemetry_cost": telemetry_cost(mcmc, model_args,
                                                           keys)}), flush=True)
    for fault in args.faults:
        with faults.FAULTS[fault]():
            for seed in args.fault_seeds:
                t0 = time.time()
                row = fit_readings(cell, seed, control=False)[0]
                row.update(fault=fault, seconds=time.time() - t0)
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
