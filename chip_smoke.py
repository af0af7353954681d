"""Smoke run of the inference path on a TPU: a bring-up check, not a benchmark.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the (chains x data) mesh on four chips

One chip runs these phases in one process, through the entry points a user
calls (``MCMC`` with ``NUTS``/``ChEES``/``MALA``, the chunked executor,
``markov`` enumeration), on the paper's CoverType-shaped logistic regression
at full width (581,012 x 54, generated from a seed):

1. ``device``  — the platform must be ``tpu``; there is no CPU fallback.
2. ``nuts``    — 4 chains, adaptive warmup, telemetry on, a preemption after
   the first sampling chunk's checkpoint and a ``resume=True`` run; the fused
   GLM potential and the Pallas kernels must be in the compiled chunk.
3. ``ensemble``— ChEES and MALA at 64 chains on the same data.
4. ``enum_hmm``— an enumerated HMM (K=8, T=120) under NUTS, so ``markov``
   runs the ``enum_contract`` kernel.

``--chips 4`` runs only NUTS and MALA (one phase each) on the 2x2
``(chains, data)`` mesh with ``data_shards=4`` and compares them with the
one-device vectorized run of the same seed.  The TPU compiler cannot
partition a Pallas kernel, so in the partitioned program only the GLM
potential, which runs inside a ``shard_map`` body, takes its kernel and the
other ops take their jnp references; the one-device run it is compared with
takes the same routes.

Each phase prints one ``[smoke]`` line with its compile seconds (tracing,
lowering and XLA compilation or a persistent-cache fetch), the rest of its
wall time, and its checks.  A failed check or phase exits non-zero.  The
last line of a passing run is ``{"ok": true, "device": {...}}``.

The persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, and at ``.jax_cache/`` in this checkout when it is not set.  The data
is passed to the compiled programs as an argument, not compiled into them
(``JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS=1``), so a second run finds them there.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# Full-size settings: paper width for the data, few hundred draws per run.
FULL = {
    "n": 581_012, "d": 54,
    # NUTS: 4 warmup chunks + 2 sampling chunks of `every` iterations
    "nuts_chains": 4, "nuts_warmup": 200, "nuts_samples": 100, "every": 50,
    "ens_chains": 64, "chees_warmup": 200, "chees_samples": 100,
    "chees_max_steps": 16, "mala_warmup": 400, "mala_samples": 200,
    "hmm_k": 8, "hmm_t": 120, "hmm_chains": 2, "hmm_warmup": 100,
    "hmm_samples": 100,
    # four-chip comparison
    "mesh_chains": 8, "mesh_warmup": 150, "mesh_samples": 100,
    "mesh_mala_warmup": 300, "mesh_mala_samples": 200,
}
SEED = 0
# posterior means may differ from a reference by at most Z standard errors
# (max over 54 coordinates of a standard normal stays far below 5)
Z = 5.0

_COMPILE_STAGES = {"trace": "/jax/core/compile/jaxpr_trace_duration",
                   "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "backend": "/jax/core/compile/backend_compile_duration"}


class PhaseFailed(Exception):
    pass


class CompileClock:
    """JAX's trace, lowering and backend-compile spans (a persistent-cache
    fetch is inside the backend span); unions, so nested spans count once."""

    def __init__(self):
        import jax
        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event in _COMPILE_STAGES.values():
            self.spans.append((event, start, end))

    def seconds(self, t0, t1, stage=None):
        total, reach = 0.0, t0
        for s, e in sorted((s, e) for ev, s, e in self.spans
                           if stage is None or ev == _COMPILE_STAGES[stage]):
            s, e = max(s, reach), min(e, t1)
            if e > s:
                total += e - s
                reach = e
        return total


class Checks:
    def __init__(self):
        self.rows = []

    def __call__(self, name, ok, detail=""):
        self.rows.append((name, bool(ok), detail))

    def report(self):
        for name, ok, detail in self.rows:
            print(f"  {'pass' if ok else 'FAIL'} {name}"
                  + (f": {detail}" if detail else ""), flush=True)
        return sum(ok for _, ok, _ in self.rows), len(self.rows)


def run_phase(name, fn, clock):
    checks = Checks()
    t0 = time.time()
    fn(checks)
    t1 = time.time()
    compile_s = clock.seconds(t0, t1)
    stages = ", ".join(f"{stage} {clock.seconds(t0, t1, stage):.1f}"
                       for stage in _COMPILE_STAGES)
    print(f"[smoke] {name}: compile_s={compile_s:.1f} ({stages}) "
          f"run_s={t1 - t0 - compile_s:.1f}", flush=True)
    passed, total = checks.report()
    print(f"[smoke] {name}: checks {passed}/{total} passed", flush=True)
    if passed != total:
        raise PhaseFailed(name)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _moments(draws):
    """Per-coordinate posterior mean, sd and Monte Carlo standard error of
    ``draws`` shaped (chains, draws, d)."""
    import numpy as np

    from repro.core.infer import effective_sample_size
    draws = np.asarray(draws, np.float64)
    flat = draws.reshape(-1, draws.shape[-1])
    sd = flat.std(0)
    ess = effective_sample_size(draws)
    return flat.mean(0), sd, sd / np.sqrt(ess)


def _agree(checks, name, a, b):
    """Means of two runs agree within Z combined Monte Carlo errors."""
    import numpy as np
    (ma, _, ea), (mb, _, eb) = a, b
    z = np.abs(ma - mb) / np.sqrt(ea ** 2 + eb ** 2)
    checks(name, np.all(z <= Z), f"max |diff|/mcse = {z.max():.2f} "
           f"(limit {Z}), max |diff| = {np.abs(ma - mb).max():.2e}")


def _compiled_text(mcmc, kind):
    """HLO of the executor's compiled ``kind`` chunk program."""
    fns = [fn for key, fn in mcmc._exec_cache.items() if key[0] == kind]
    return fns[0].lower(mcmc.last_state).compile().as_text()


def _kernel_calls(text):
    """Instruction names of the Pallas TPU kernels in compiled HLO; each is
    named after its kernel, with a ``vmap_`` prefix when batched."""
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _has_kernels(checks, name, text, kernels):
    calls = _kernel_calls(text)
    found = {k: any(k in c for c in calls) for k in kernels}
    checks(name, all(found.values()),
           ", ".join(f"{k}={'yes' if v else 'no'}" for k, v in found.items()))


@contextlib.contextmanager
def preempt_after(n_saves):
    """Raise KeyboardInterrupt right after the ``n_saves``-th checkpoint
    write, as a preemption landing there would."""
    from repro.distributed import checkpoint as ckpt
    real, calls = ckpt.save, {"n": 0}

    def save(tree, directory, **kw):
        real(tree, directory, **kw)
        calls["n"] += 1
        if calls["n"] == n_saves:
            raise KeyboardInterrupt(f"preempted after save #{n_saves}")

    ckpt.save = save
    try:
        yield
    finally:
        ckpt.save = real


def _logreg_data(size):
    from jax import random

    from benchmarks.models import covtype_data
    return covtype_data(random.PRNGKey(SEED), n=size["n"], d=size["d"])


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_nuts(size, data, state, checks):
    import jax
    import numpy as np
    from jax import random

    from benchmarks.models import logreg_model_glm
    from repro import obs
    from repro.core.infer import MCMC, NUTS
    from repro.kernels import ops

    W, S, E = size["nuts_warmup"], size["nuts_samples"], size["every"]
    C = size["nuts_chains"]
    x, y = data["x"], data["y"]
    key = random.PRNGKey(SEED + 1)
    mcmc = MCMC(NUTS(logreg_model_glm), num_warmup=W, num_samples=S,
                num_chains=C, chain_method="vectorized")
    with tempfile.TemporaryDirectory() as tmp:
        # uninterrupted, checkpointed run with telemetry
        tele = mcmc.telemetry = obs.Telemetry(dir=os.path.join(tmp, "tele"))
        mcmc.run(key, x, y, checkpoint_every=E,
                 checkpoint_dir=os.path.join(tmp, "full"))
        ref = np.asarray(mcmc.get_samples(group_by_chain=True)["w"])
        extra = mcmc.get_extra_fields(group_by_chain=True)
        text = _compiled_text(mcmc, "sample")
        # W/E warmup state writes, then (samples, state) per sampling chunk:
        # stop right after the first sampling chunk is checkpointed
        ck = os.path.join(tmp, "preempted")
        mcmc.telemetry = obs.Telemetry(dir=os.path.join(tmp, "tele_kill"))
        try:
            with preempt_after(W // E + 2):
                mcmc.run(key, x, y, checkpoint_every=E, checkpoint_dir=ck)
            preempted = False
        except KeyboardInterrupt:
            preempted = True
        mcmc.telemetry = obs.Telemetry(dir=os.path.join(tmp, "tele_resume"))
        mcmc.run(key, x, y, checkpoint_every=E, checkpoint_dir=ck,
                 resume=True)
        resumed = np.asarray(mcmc.get_samples(group_by_chain=True)["w"])

    series = tele.buffer.series("sample")
    checks("telemetry", series["accept_prob"].shape == (C, S)
           and any(s.name == "sample_chunk" for s in tele.spans),
           f"accept_prob series {series['accept_prob'].shape}, "
           f"{len(tele.spans)} spans")
    _has_kernels(checks, "pallas kernels in the compiled chunk", text,
                 ("glm_potential_grad", "leapfrog_halfstep"))
    div = int(np.sum(np.asarray(extra["diverging"])))
    checks("no divergences", div == 0, f"{div} divergent draws")
    checks("finite draws", np.all(np.isfinite(ref)), f"shape {ref.shape}")
    mom = _moments(ref)
    mean, sd, mcse = mom
    true_w = np.asarray(data["true_w"])
    z = np.abs(mean - true_w) / np.sqrt(sd ** 2 + mcse ** 2)
    checks("posterior mean vs true_w", np.all(z <= Z),
           f"max |mean - true_w| / sd = {z.max():.2f} (limit {Z})")
    checks("preempted then resumed", preempted
           and np.array_equal(resumed, ref),
           "resume after the first sampling chunk is array_equal"
           if preempted else "the preemption never fired")

    # the same run with every op on its jnp reference, in full f32 (a TPU's
    # default precision would multiply f32 in one bf16 pass)
    with ops.use_pallas(False), jax.default_matmul_precision("highest"):
        plain = MCMC(NUTS(logreg_model_glm), num_warmup=W, num_samples=S,
                     num_chains=C, chain_method="vectorized")
        plain.run(key, x, y)
        ref_text = _compiled_text(plain, "sample")
    checks("reference run has no kernels", not _kernel_calls(ref_text))
    _agree(checks, "fused vs reference posterior means", mom,
           _moments(plain.get_samples(group_by_chain=True)["w"]))
    state["nuts"] = mom


def phase_ensemble(size, data, state, checks):
    import numpy as np
    from jax import random

    from benchmarks.models import logreg_model_glm
    from repro.core.infer import MALA, MCMC, ChEES

    runs = (
        ("chees", ChEES(logreg_model_glm,
                        max_num_steps=size["chees_max_steps"]),
         size["chees_warmup"], size["chees_samples"],
         ("leapfrog_halfstep_batch", "glm_potential_grad")),
        ("mala", MALA(logreg_model_glm), size["mala_warmup"],
         size["mala_samples"], ("mala_step", "glm_potential_grad")),
    )
    for name, kernel, W, S, kernels in runs:
        mcmc = MCMC(kernel, num_warmup=W, num_samples=S,
                    num_chains=size["ens_chains"], chain_method="vectorized")
        mcmc.run(random.PRNGKey(SEED + 2), data["x"], data["y"])
        w = np.asarray(mcmc.get_samples(group_by_chain=True)["w"])
        checks(f"{name} finite draws", np.all(np.isfinite(w)),
               f"shape {w.shape}")
        _has_kernels(checks, f"{name} pallas kernels in the compiled chunk",
                     _compiled_text(mcmc, "sample"), kernels)
        _agree(checks, f"{name} vs NUTS posterior means", _moments(w),
               state["nuts"])


def phase_enum_hmm(size, checks):
    import jax
    import numpy as np
    from jax import random

    from benchmarks.models import enum_hmm_data, enum_hmm_model
    from repro.core.infer import MCMC, NUTS, initialize_model_structure
    from repro.kernels import ops

    data = enum_hmm_data(size["hmm_k"], random.PRNGKey(SEED + 3),
                         T=size["hmm_t"])
    mcmc = MCMC(NUTS(enum_hmm_model, max_tree_depth=8),
                num_warmup=size["hmm_warmup"],
                num_samples=size["hmm_samples"],
                num_chains=size["hmm_chains"], chain_method="vectorized")
    mcmc.run(random.PRNGKey(SEED + 4), data)
    theta = np.asarray(mcmc.get_samples()["theta"])
    checks("finite draws", np.all(np.isfinite(theta)), f"shape {theta.shape}")
    _has_kernels(checks, "enum_contract in the compiled chunk",
                 _compiled_text(mcmc, "sample"), ("enum_contract",))

    # the marginal log-density (the NUTS potential) on the chip, kernel vs
    # jnp reference, at the final chain states and random points
    potential = initialize_model_structure(random.PRNGKey(0), enum_hmm_model,
                                           (data,))[0]
    zs = np.asarray(mcmc.last_state.z)
    zs = np.concatenate([zs, np.asarray(random.normal(
        random.PRNGKey(5), (8, zs.shape[-1])))])
    # a fresh jit per route: the route is read while tracing
    fused_fn = jax.jit(jax.vmap(potential))
    _has_kernels(checks, "enum_contract in the compiled marginal",
                 fused_fn.lower(zs).compile().as_text(), ("enum_contract",))
    fused = np.asarray(fused_fn(zs))
    with ops.use_pallas(False):
        plain_fn = jax.jit(jax.vmap(potential))
        checks("reference marginal has no kernels",
               not _kernel_calls(plain_fn.lower(zs).compile().as_text()))
        plain = np.asarray(plain_fn(zs))
    checks("marginal log-density == jnp reference",
           np.array_equal(fused, plain),
           f"max |diff| = {np.abs(fused - plain).max():.3e} over "
           f"{len(zs)} points")


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def phase_mesh(size, data, kernel, checks):
    import numpy as np
    from jax import random

    from benchmarks.models import logreg_model_glm
    from repro.core.infer import MALA, MCMC, NUTS
    from repro.kernels import ops

    kernel_cls, W, S = {
        "nuts": (NUTS, size["mesh_warmup"], size["mesh_samples"]),
        "mala": (MALA, size["mesh_mala_warmup"], size["mesh_mala_samples"]),
    }[kernel]

    def run(method, **extra):
        mcmc = MCMC(kernel_cls(logreg_model_glm, data_shards=4),
                    num_warmup=W, num_samples=S, num_chains=size["mesh_chains"],
                    chain_method=method, **extra)
        mcmc.run(random.PRNGKey(SEED + 6), data["x"], data["y"])
        return mcmc, np.asarray(mcmc.get_samples(group_by_chain=True)["w"])

    # in the partitioned program only the GLM potential's shard_map body may
    # hold a Pallas kernel; the one-device run it must equal takes the same
    # routes
    mesh_run, b = run("parallel", mesh_shape=(2, 2))
    with ops.use_pallas({"glm_potential_grad"}):
        _, a = run("vectorized")
    devices = mesh_run.last_state.z.sharding.device_set
    checks("chains placed on 4 devices", len(devices) == 4,
           f"{len(devices)} devices hold the chain state")
    text = _compiled_text(mesh_run, "sample")
    checks("sharded chunk gathers over the data axis", "all-gather" in text)
    calls = _kernel_calls(text)
    checks("partitioned chunk runs only the GLM kernel, in its shard_map",
           calls and all("glm_potential_grad" in c for c in calls),
           f"kernels: {sorted(set(calls))}")
    checks("finite draws", np.all(np.isfinite(b)))
    checks("(2,2) mesh array_equal to one device", np.array_equal(a, b),
           f"max |diff| = {np.abs(a - b).max():.3e}, "
           f"{int(np.sum(a != b))} of {a.size} values differ")


# ---------------------------------------------------------------------------

def run(size, chips, clock):
    """Every phase for ``chips``; raises PhaseFailed on a failed check."""
    data = _logreg_data(size)
    if chips == 4:
        for kernel in ("nuts", "mala"):
            run_phase(f"mesh_2x2_{kernel}",
                      lambda c, k=kernel: phase_mesh(size, data, k, c), clock)
        return
    state = {}
    run_phase("nuts", lambda c: phase_nuts(size, data, state, c), clock)
    run_phase("ensemble", lambda c: phase_ensemble(size, data, state, c),
              clock)
    run_phase("enum_hmm", lambda c: phase_enum_hmm(size, c), clock)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # read by JAX when it is imported: arrays a program closes over (the
    # design matrix) become arguments of its executable instead of
    # constants compiled into it, so each chunk program compiles without
    # the data and is small enough for the persistent cache
    os.environ.setdefault("JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS", "1")
    import jax

    from benchmarks.harness import use_compile_cache
    cache = use_compile_cache()
    # a fused potential that falls back would hide the kernel from the chip
    warnings.filterwarnings("error", message=".*falling back to the plain")
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"[smoke] device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    if dev.platform != "tpu":
        print("[smoke] device: no TPU found; this smoke run needs one",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[smoke] device: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    t0 = time.time()
    try:
        run(FULL, args.chips, CompileClock())
    except PhaseFailed as e:
        print(f"[smoke] phase {e} failed", file=sys.stderr)
        return 1
    print(f"[smoke] all phases passed in {time.time() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
