"""The telemetry subsystem (docs/observability.md).

The load-bearing invariant first: attaching ``obs.Telemetry`` must not
change a single bit of the sample stream — metrics ride the chunked scan's
collect outputs, never its carry — and must not recompile any metrics-off
program.  Then the artifact layer (JSONL events + run manifest validated
against their checked-in schemas, manifest append-on-resume, divergence
counter continuity across kill/resume), the live reporter's line contract,
and the RPL401/RPL402/RPL102 lint rules the metrics contract rides on.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

MCMC_WARMUP, MCMC_SAMPLES, MCMC_EVERY = 24, 36, 20


def _kernels():
    from repro.core.infer import MALA, NUTS, ChEES
    return {"NUTS": NUTS, "ChEES": ChEES, "MALA": MALA}


def _logreg():
    import jax.numpy as jnp
    from jax import random

    import repro.core as pc
    from repro.core import dist

    x = random.normal(random.PRNGKey(0), (80, 3))
    y = (x @ jnp.ones(3) > 0).astype(jnp.float32)

    def model(x, y=None):
        m = pc.sample("m", dist.Normal(0.0, jnp.ones(3)).to_event(1))
        b = pc.sample("b", dist.Normal(0.0, 1.0))
        return pc.sample("y", dist.Bernoulli(logits=x @ m + b), obs=y)

    return model, (x,), {"y": y}


def _funnel_mcmc(kernel_cls, **kw):
    import jax.numpy as jnp

    import repro.core as pc
    from repro.core import dist
    from repro.core.infer import MCMC

    def funnel():
        v = pc.sample("v", dist.Normal(0.0, 3.0))
        pc.sample("x", dist.Normal(0.0, jnp.exp(0.5 * v)))

    return MCMC(kernel_cls(funnel), num_warmup=MCMC_WARMUP,
                num_samples=MCMC_SAMPLES, num_chains=4, progress=False, **kw)


# ---------------------------------------------------------------------------
# bit-identity + zero recompiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_kernels()))
def test_samples_bit_identical_metrics_on_vs_off(name, tmp_path):
    from jax import random

    from repro import obs
    from repro.core.infer import MCMC

    model, args, kwargs = _logreg()
    kernel_cls = _kernels()[name]

    plain = MCMC(kernel_cls(model), num_warmup=40, num_samples=40,
                 num_chains=4, progress=False)
    plain.run(random.PRNGKey(1), *args, **kwargs)
    ref = plain.get_samples(group_by_chain=True)

    tele = obs.Telemetry(dir=str(tmp_path))
    inst = MCMC(kernel_cls(model), num_warmup=40, num_samples=40,
                num_chains=4, progress=False, telemetry=tele)
    inst.run(random.PRNGKey(1), *args, **kwargs)
    got = inst.get_samples(group_by_chain=True)

    for site in ref:
        np.testing.assert_array_equal(
            np.asarray(got[site]), np.asarray(ref[site]),
            err_msg=f"{name}: telemetry changed the sample stream at "
            f"site {site!r}")

    # the metrics streams came along: (chains, draws) per-chain series
    series = tele.buffer.series("sample")
    assert {"step_size", "accept_prob", "diverging"} <= set(series)
    assert series["accept_prob"].shape == (4, 40)
    assert tele.buffer.num_draws("sample") == 40

    # artifacts validate against the checked-in schemas
    from repro.obs.validate import validate_events, validate_manifest
    assert validate_events(str(tmp_path / "events.jsonl")) == []
    assert validate_manifest(str(tmp_path / "run_manifest.json")) == []

    # the span trace covers every phase
    span_names = {s.name for s in tele.spans}
    assert {"setup", "init", "warmup_chunk", "sample_chunk"} <= span_names


def test_zero_warm_path_recompiles(tmp_path):
    from jax import random

    from repro import obs
    from repro.core.infer import MCMC, NUTS

    model, args, kwargs = _logreg()
    tele = obs.Telemetry(dir=str(tmp_path))
    mcmc = MCMC(NUTS(model), num_warmup=40, num_samples=40, num_chains=4,
                progress=False, telemetry=tele)
    mcmc.run(random.PRNGKey(1), *args, **kwargs)
    cold_misses = tele.counters["exec_cache_miss"]
    assert cold_misses > 0
    # every chunk span after the first per (phase, length) shape ran a
    # cached program
    cold_spans = [s for s in tele.spans
                  if s.name.endswith("_chunk") and s.attr("program_cold")]
    assert len(cold_spans) == cold_misses - 1  # +1 miss is the init program

    # second run of the same object: everything hits the warm cache
    mcmc.run(random.PRNGKey(2), *args, **kwargs)
    assert tele.counters.get("exec_cache_miss", 0) == 0, (
        "warm-path rerun recompiled a chunk program")
    assert tele.counters["exec_cache_hit"] > 0


def test_enabling_metrics_keeps_plain_programs_cached(tmp_path):
    """Flipping telemetry on compiles *new* cache entries; the metrics-off
    programs stay resident and are reused verbatim when telemetry is
    detached again."""
    from jax import random

    from repro import obs
    from repro.core.infer import MCMC, NUTS

    model, args, kwargs = _logreg()
    mcmc = MCMC(NUTS(model), num_warmup=40, num_samples=40, num_chains=4,
                progress=False)
    mcmc.run(random.PRNGKey(1), *args, **kwargs)
    plain_keys = set(mcmc._exec_cache)
    assert all(k[-1] is False for k in plain_keys)

    tele = obs.Telemetry(dir=str(tmp_path))
    mcmc.telemetry = tele
    mcmc.run(random.PRNGKey(1), *args, **kwargs)
    assert plain_keys <= set(mcmc._exec_cache)
    new_keys = set(mcmc._exec_cache) - plain_keys
    assert new_keys and all(k[-1] is True for k in new_keys)

    mcmc.telemetry = None
    mcmc.run(random.PRNGKey(1), *args, **kwargs)
    assert set(mcmc._exec_cache) == plain_keys | new_keys


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax import random
import repro.core as pc
from repro import obs
from repro.core import dist
from repro.core.infer import MCMC, NUTS
from repro.core.infer.ensemble import ChEES
from repro.core.infer.mala import MALA

kern = {"nuts": NUTS, "chees": ChEES, "mala": MALA}[os.environ["OBS_KERNEL"]]

n, d = 256, 4
x = random.normal(random.PRNGKey(0), (n, d))
y = (random.uniform(random.PRNGKey(1), (n,))
     < jax.nn.sigmoid(x @ jnp.linspace(-1.0, 1.0, d))).astype(jnp.float32)

def model(x, y):
    w = pc.sample("w", dist.Normal(jnp.zeros(d), 1.0).to_event(1))
    pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y,
              infer={"potential": "glm"})

def run(mesh_shape, tele):
    m = MCMC(kern(model, data_shards=2), num_warmup=24, num_samples=24,
             num_chains=4, chain_method="parallel", mesh_shape=mesh_shape,
             progress=False, telemetry=tele)
    m.run(random.PRNGKey(7), x, y)
    return np.asarray(m.get_samples()["w"], np.float32).tobytes().hex()

out = {"n_devices": len(jax.devices())}
for label, mesh in [("mesh_1d", None), ("mesh_2x2", (2, 2))]:
    tele = obs.Telemetry()
    out[label + "_off"] = run(mesh, None)
    out[label + "_on"] = run(mesh, tele)
    series = tele.buffer.series("sample")
    out[label + "_metrics"] = sorted(series)
    out[label + "_accept_shape"] = list(np.shape(series["accept_prob"]))
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.parametrize("kernel", ["nuts", "chees", "mala"])
def test_mcmc_mesh_samples_bit_identical_metrics_on_vs_off(kernel):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"),
               OBS_KERNEL=kernel)
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["n_devices"] == 4
    for label in ("mesh_1d", "mesh_2x2"):
        assert got[label + "_on"] == got[label + "_off"], (
            f"{kernel}/{label}: telemetry changed the sample stream")
        assert "accept_prob" in got[label + "_metrics"]
        assert got[label + "_accept_shape"] == [4, 24]


# ---------------------------------------------------------------------------
# manifest append-on-resume + divergence continuity
# ---------------------------------------------------------------------------

def _run_killed(mcmc, ckdir, kill_at, seed=11):
    """Run with checkpointing; raise KeyboardInterrupt right after ckpt
    save call #``kill_at`` (the preemption-test pattern)."""
    from jax import random

    from repro.distributed import checkpoint as ckpt
    real_save, calls = ckpt.save, {"n": 0}

    def wrapped_save(tree, directory, **kw):
        real_save(tree, directory, **kw)
        calls["n"] += 1
        if calls["n"] == kill_at:
            raise KeyboardInterrupt(f"preempted after save #{kill_at}")

    ckpt.save = wrapped_save
    try:
        with pytest.raises(KeyboardInterrupt):
            mcmc.run(random.PRNGKey(seed), checkpoint_every=MCMC_EVERY,
                     checkpoint_dir=ckdir)
    finally:
        ckpt.save = real_save
    return calls["n"]


def test_manifest_appends_on_resume_and_divergences_survive(tmp_path):
    from jax import random

    from repro import obs
    from repro.core.infer import NUTS
    from repro.obs.manifest import RunManifest
    from repro.obs.validate import validate_events, validate_manifest

    # uninterrupted reference (funnel: divergences guaranteed nonzero)
    ref = _funnel_mcmc(NUTS, telemetry=None)
    ref.run(random.PRNGKey(11), checkpoint_every=MCMC_EVERY,
            checkpoint_dir=str(tmp_path / "ref"))
    expected = np.asarray(ref.get_samples(group_by_chain=True)["x"])
    total_div = ref._divergences
    assert total_div > 0, "funnel run produced no divergences; weak test"

    # kill after save #3 (between a sampling chunk's samples and state
    # writes), then resume with a fresh process-equivalent MCMC + Telemetry
    ckdir = str(tmp_path / "kill")
    _run_killed(_funnel_mcmc(NUTS, telemetry=obs.Telemetry()), ckdir,
                kill_at=3)

    resumed = _funnel_mcmc(NUTS, telemetry=obs.Telemetry())
    resumed.run(random.PRNGKey(11), checkpoint_every=MCMC_EVERY,
                checkpoint_dir=ckdir, resume=True)
    np.testing.assert_array_equal(
        np.asarray(resumed.get_samples(group_by_chain=True)["x"]), expected)
    assert resumed._divergences == total_div, (
        "cumulative divergence counter did not survive kill/resume")

    # the manifest (written next to the checkpoints) accumulated both
    # sessions of the same run record
    mpath = os.path.join(ckdir, obs.MANIFEST_NAME)
    assert validate_manifest(mpath) == []
    assert validate_events(os.path.join(ckdir, "events.jsonl")) == []
    man = RunManifest.peek(mpath).data
    assert len(man["sessions"]) == 2
    first, second = man["sessions"]
    assert first["resume"] is False and first["final"] is None
    assert second["resume"] is True
    assert second["resumed_at_iteration"] == MCMC_WARMUP
    assert second["final"]["divergences"] == total_div
    assert man["divergences"] == total_div


def test_divergence_counter_restored_without_telemetry(tmp_path):
    """The satellite fix in isolation: resume=True restores the cumulative
    counter from the checkpoint extra even with no telemetry attached."""
    from jax import random

    from repro.core.infer import NUTS

    ref = _funnel_mcmc(NUTS)
    ref.run(random.PRNGKey(11), checkpoint_every=MCMC_EVERY,
            checkpoint_dir=str(tmp_path / "ref"))
    assert ref._divergences > 0

    ckdir = str(tmp_path / "kill")
    _run_killed(_funnel_mcmc(NUTS), ckdir, kill_at=4)
    resumed = _funnel_mcmc(NUTS)
    resumed.run(random.PRNGKey(11), checkpoint_every=MCMC_EVERY,
                checkpoint_dir=ckdir, resume=True)
    assert resumed._divergences == ref._divergences


def test_telemetry_never_calls_checkpoint_save(tmp_path):
    """Kill-point semantics of the preemption sweep stay fixed: a
    telemetry-on checkpointed run performs exactly the same six
    ``checkpoint.save`` calls as a plain one (manifest/events go through
    plain json)."""
    from jax import random

    from repro import obs
    from repro.core.infer import NUTS
    from repro.distributed import checkpoint as ckpt

    real_save, calls = ckpt.save, {"n": 0}

    def counting_save(tree, directory, **kw):
        calls["n"] += 1
        real_save(tree, directory, **kw)

    ckpt.save = counting_save
    try:
        mcmc = _funnel_mcmc(NUTS, telemetry=obs.Telemetry())
        mcmc.run(random.PRNGKey(11), checkpoint_every=MCMC_EVERY,
                 checkpoint_dir=str(tmp_path))
    finally:
        ckpt.save = real_save
    assert calls["n"] == 6


# ---------------------------------------------------------------------------
# reporter + guardrails
# ---------------------------------------------------------------------------

def test_reporter_line_contract():
    from repro.obs.report import LiveReporter

    lines = []
    rep = LiveReporter(print_fn=lines.append)
    rep.start(total=120)
    rep.chunk(done=40, total=120, phase="warmup", num_chains=4,
              divergences=0)
    rep.chunk(done=80, total=120, phase="sample", num_chains=4,
              divergences=3, delta_div=3,
              metrics={"step_size": np.full((4, 40), 0.5),
                       "accept_prob": np.full((4, 40), 0.87)})
    assert lines[0].startswith(
        "[MCMC] 40/120 iterations (warmup) | chains: 4 | divergences: 0")
    assert lines[1].startswith(
        "[MCMC] 80/120 iterations (sample) | chains: 4 | divergences: 3")
    assert "+3 div" in lines[1]
    assert "step: 0.5" in lines[1]
    assert "accept: 0.87" in lines[1]
    assert "eta:" in lines[1]


def test_sequential_chain_method_rejects_telemetry():
    from jax import random

    from repro import obs
    from repro.core.infer import MCMC, NUTS

    model, args, kwargs = _logreg()
    mcmc = MCMC(NUTS(model), num_warmup=10, num_samples=10, num_chains=2,
                chain_method="sequential", progress=False,
                telemetry=obs.Telemetry())
    with pytest.raises(ValueError, match="batched chain_method"):
        mcmc.run(random.PRNGKey(0), *args, **kwargs)


def _host_events(log_dir):
    """Names of the host events in the one profiler trace under
    ``log_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return [e.name for line in plane.lines for e in line.events]


def test_spans_are_profiler_annotations(tmp_path):
    import jax
    from jax import random

    from repro import obs
    from repro.core.infer import MCMC, NUTS

    model, args, kwargs = _logreg()
    tele = obs.Telemetry(dir=str(tmp_path / "run"))
    mcmc = MCMC(NUTS(model), num_warmup=20, num_samples=20, num_chains=2,
                progress=False, telemetry=tele)
    log_dir = str(tmp_path / "prof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # annotations only, not every call
    with jax.profiler.trace(log_dir, profiler_options=opts):
        mcmc.run(random.PRNGKey(0), *args, checkpoint_every=10, **kwargs)
    names = _host_events(log_dir)
    # one host event per span, on the profiler's clock
    assert names.count("setup") == names.count("init") == 1
    assert names.count("warmup_chunk") == names.count("sample_chunk") == 2
    assert names.count("chunk_drain") == 4
    assert sorted(s.name for s in tele.spans) == sorted(
        n for n in names if n in {s.name for s in tele.spans})
    drains = [s for s in tele.spans if s.name == "chunk_drain"]
    assert [(s.attr("phase"), s.attr("start"), s.attr("end"))
            for s in drains] == [("warmup", 0, 10), ("warmup", 10, 20),
                                 ("sample", 20, 30), ("sample", 30, 40)]


def test_sample_chunk_carries_the_layer_scopes():
    import re

    from jax import random

    from repro import obs
    from repro.core.infer import MCMC, NUTS

    model, args, kwargs = _logreg()
    mcmc = MCMC(NUTS(model), num_warmup=5, num_samples=5, num_chains=2,
                progress=False)
    mcmc.run(random.PRNGKey(0), *args, **kwargs)
    (prog,) = [fn for key, fn in mcmc._exec_cache.items()
               if key[0] == "sample"]
    text = prog.lower(mcmc.last_state).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    # every scope of the NUTS hot path (the model holds no markov chain)
    for scope in (obs.SCOPES.potential, obs.SCOPES.integrator,
                  obs.SCOPES.tree, obs.SCOPES.adapt):
        assert any(scope in p for p in paths), scope
    # the gradient runs inside the leapfrog step, inside the NUTS tree
    nested = f"{obs.SCOPES.tree}/.*{obs.SCOPES.integrator}/" \
        f"{obs.SCOPES.potential}/"
    assert any(re.search(nested, p) for p in paths)


# ---------------------------------------------------------------------------
# lint rules: RPL401 / RPL402 / sanctioned RPL102
# ---------------------------------------------------------------------------

def _nuts_setup():
    from jax import random

    from repro.core.infer import hmc_setup

    model, args, kwargs = _logreg()
    return hmc_setup(random.PRNGKey(0), 10, algo="NUTS", model=model,
                     model_args=args, model_kwargs=kwargs)


def test_builtin_metrics_fns_pass_the_contract():
    from jax import random

    from repro.core.infer import chees_setup, hmc_setup, mrw_setup
    from repro.lint import verify_metrics_fn

    model, args, kwargs = _logreg()
    common = dict(model=model, model_args=args, model_kwargs=kwargs)
    key = random.PRNGKey(0)
    for setup in (hmc_setup(key, 10, algo="NUTS", **common),
                  hmc_setup(key, 10, algo="NUTS", cross_chain_adapt=True,
                            **common),
                  chees_setup(key, 10, **common),
                  mrw_setup(key, 10, "MALA", **common)):
        assert setup.metrics_fn is not None
        assert verify_metrics_fn(setup, num_chains=4).ok


def test_rpl401_fires_on_non_scalar_metric_leaf():
    setup = _nuts_setup()
    bad = setup._replace(metrics_fn=lambda st: {"z": st.z})
    from repro.lint import verify_metrics_fn
    result = verify_metrics_fn(bad, num_chains=4)
    assert [f.code for f in result.findings] == ["RPL401"]
    with pytest.raises(Exception, match="RPL401"):
        result.raise_if_errors()


def test_rpl402_fires_on_rng_dependent_metric():
    import jax.numpy as jnp

    setup = _nuts_setup()
    bad = setup._replace(metrics_fn=lambda st: {
        "key_leak": st.rng_key.sum().astype(jnp.float32),
        "step_size": st.adapt_state.step_size})
    from repro.lint import verify_metrics_fn
    result = verify_metrics_fn(bad, num_chains=4)
    assert [(f.code, f.site) for f in result.findings] \
        == [("RPL402", "key_leak")]


def test_executor_rejects_contract_violating_metrics_fn(tmp_path):
    """The runtime twin: MCMC refuses to compile a metrics_fn the lint
    rules reject (eagerly, before any chunk program is built)."""
    from jax import random

    from repro import obs
    from repro.core.infer import MCMC, NUTS

    model, args, kwargs = _logreg()
    mcmc = MCMC(NUTS(model), num_warmup=10, num_samples=10, num_chains=2,
                progress=False, telemetry=obs.Telemetry())
    setup = mcmc._get_setup(random.PRNGKey(0), None, args, kwargs)
    bad = setup._replace(metrics_fn=lambda st: {"z": st.z})
    bundle, warmup, _ = mcmc._setup_cache
    mcmc._setup_cache = (bundle, warmup, bad)
    with pytest.raises(Exception, match="RPL401"):
        mcmc.run(random.PRNGKey(0), *args, **kwargs)


def test_rpl102_skips_sanctioned_callbacks():
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.lint import analyze

    def drain(x):
        return None

    def prog(x):
        jax.debug.callback(drain, x)
        return x * 2

    assert "RPL102" in [f.code for f in analyze(prog, jnp.ones(3)).findings]
    obs.sanction(drain)
    assert "RPL102" not in [f.code
                            for f in analyze(prog, jnp.ones(3)).findings]


def test_schema_validator_cli(tmp_path):
    """``python -m repro.obs.validate`` is what the CI obs-smoke job runs."""
    from jax import random

    from repro import obs
    from repro.core.infer import MCMC, NUTS

    model, args, kwargs = _logreg()
    tele = obs.Telemetry(dir=str(tmp_path))
    MCMC(NUTS(model), num_warmup=10, num_samples=10, num_chains=2,
         progress=False, telemetry=tele).run(random.PRNGKey(0), *args,
                                             **kwargs)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    for artifact in ("events.jsonl", obs.MANIFEST_NAME):
        out = subprocess.run(
            [sys.executable, "-m", "repro.obs.validate",
             str(tmp_path / artifact)],
            env=env, capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stdout + out.stderr

    # and it rejects garbage
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "span", "t_unix": 0}\n')
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs.validate", str(bad)],
        env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode == 1
