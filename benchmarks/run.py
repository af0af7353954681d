"""Run every benchmark (one per paper table/figure).

``python -m benchmarks.run``          — full paper-spec settings
``python -m benchmarks.run --quick``  — reduced step counts (CI / smoke)

Compiled programs persist in ``JAX_COMPILATION_CACHE_DIR`` when it is set,
else in ``.jax_cache/`` at the repository root.
"""
import json
import os
import sys
import time

RESULTS = "benchmarks/results"


def _previous_headlines():
    """Headline metrics of the last recorded run, carried forward into the
    new summary so each bench_summary.json shows before/after per PR."""
    path = os.path.join(RESULTS, "bench_summary.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            prev = json.load(f)
    except Exception:
        return None
    keep = {}
    for k in ("hmm", "logreg", "skim"):
        if isinstance(prev.get(k), dict):
            keep[k] = {m: prev[k][m]
                       for m in ("ms_per_leapfrog", "ms_per_eff_sample",
                                 "wall_s")
                       if m in prev[k]}
    for k in ("multichain", "svi_minibatch", "enum_hmm", "chees",
              "sharded_potential"):
        if isinstance(prev.get(k), dict):
            keep[k] = {"rows": prev[k].get("rows")}
            if "ess_per_sec_ratio_at_max_chains" in prev[k]:
                keep[k]["ess_per_sec_ratio_at_max_chains"] = \
                    prev[k]["ess_per_sec_ratio_at_max_chains"]
    if isinstance(prev.get("kernels"), dict):
        kern = prev["kernels"]
        keep["kernels"] = {
            "ops": kern.get("ops"),
            "copy_bandwidth_gbs": kern.get("copy_bandwidth_gbs"),
            "nuts_glm_ms_per_leapfrog_speedup":
                (kern.get("nuts_glm") or {}).get("ms_per_leapfrog_speedup"),
            "chees_64_warm_wall_s":
                (kern.get("chees_64_chains") or {}).get("wall_s"),
        }
    return keep or None


def _lint_bench():
    """Static-analysis overhead on the logreg model: the full lint pass is
    pure tracing (zero FLOPs), so its wall time is the entire cost a user
    pays for ``MCMC(..., validate=True)`` — once, on the cold path."""
    from benchmarks.models import covtype_data, logreg_model
    from repro.lint import lint_model

    data = covtype_data(n=5000)
    t0 = time.time()
    result = lint_model(logreg_model, (data["x"],), {"y": data["y"]})
    lint_ms = (time.time() - t0) * 1e3
    rec = {"benchmark": "lint_logreg", "n": 5000, "lint_ms": lint_ms,
           "ok": result.ok, "codes": sorted(result.codes())}
    print(f"lint_model(logreg, n=5000): {lint_ms:.1f} ms, "
          f"ok={result.ok}", flush=True)
    return rec


def main():
    quick = "--quick" in sys.argv or os.environ.get("BENCH_QUICK") == "1"
    os.makedirs(RESULTS, exist_ok=True)
    t0 = time.time()
    out = {}
    previous = _previous_headlines()

    from benchmarks.harness import use_compile_cache
    use_compile_cache()
    # every summary records where it was measured (the trajectory in
    # BENCH_<n>.json is only comparable within one environment)
    from repro.obs import collect_environment
    out["environment"] = collect_environment()

    from benchmarks import (chees, enum_hmm, hmm, logreg, multichain,
                            obs_overhead, skim, svi_minibatch)
    from benchmarks import kernels_bench, sharded_potential

    sections = [
        ("hmm", "Table 2a — HMM (time per leapfrog step)", hmm.main),
        ("enum_hmm", "Enum HMM — fully latent states, ms/leapfrog vs K "
         "(markov + enum_contract)", enum_hmm.main),
        ("logreg", "Table 2a — logistic regression / CoverType-shaped",
         logreg.main),
        ("multichain", "Multi-chain throughput (chains × samples/sec, vmap "
         "executor)", multichain.main),
        ("chees", "ChEES-HMC vs NUTS (samples/sec + ESS/sec vs chain "
         "count)", chees.main),
        ("svi_minibatch", "Minibatch SVI (steps/sec vs subsample size, one "
         "compiled step)", svi_minibatch.main),
        ("skim", "Fig 2b — SKIM time per effective sample vs p", skim.main),
        ("kernels", "Hot-path kernels — per-op ms + roofline fraction, GLM "
         "fused vs plain, ChEES 64-chain warm wall", kernels_bench.main),
        ("sharded_potential", "Data-sharded GLM potential — ms/eval vs mesh "
         "data-axis size (8 virtual devices, chains x data mesh)",
         sharded_potential.main),
        ("obs_overhead", "Telemetry overhead — logreg quick warm wall, "
         "metrics off vs on vs convergence-gated (budget < 3%)",
         obs_overhead.main),
        ("lint", "Static analyzer — lint_ms on logreg (cost of "
         "validate=True)", lambda quick: _lint_bench()),
    ]
    for key, title, fn in sections:
        print("=" * 70)
        print(title)
        print("=" * 70, flush=True)
        out[key] = fn(quick=quick)

    out["total_wall_s"] = time.time() - t0
    if previous is not None:
        out["previous"] = previous
    with open(os.path.join(RESULTS, "bench_summary.json"), "w") as f:
        json.dump(out, f, indent=1)
    # per-PR snapshot: bench_summary.json is overwritten every run, the
    # BENCH_<n>.json files accumulate the trajectory
    with open(os.path.join(RESULTS, "BENCH_10.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(f"\nall benchmarks done in {out['total_wall_s']:.0f}s; summary in "
          f"{RESULTS}/bench_summary.json (snapshot: BENCH_10.json)")


if __name__ == "__main__":
    main()
