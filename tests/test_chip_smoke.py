"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases run end to end at a tiny size with the kernels in interpret mode.

The kernel-presence checks read the instruction names of compiled TPU
kernels, which only a chip produces; here they read the kernel names that
interpret mode leaves in the program instead.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(chip_smoke.FULL, n=2048, nuts_warmup=20, nuts_samples=20,
            every=10, ens_chains=16, chees_warmup=40, chees_samples=20,
            mala_warmup=200, mala_samples=100, hmm_k=3, hmm_t=20,
            hmm_warmup=20, hmm_samples=20)


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def _cache_run(code, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(env, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.join(REPO, "src"), REPO]))
    out = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.harness import use_compile_cache\n"
         "print(use_compile_cache())\n" + code],
        cwd=REPO, env=base, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.splitlines()[0]


def test_compile_cache_defaults_to_a_fixed_directory_of_the_checkout():
    assert _cache_run("") == os.path.join(REPO, ".jax_cache")


def test_compile_cache_is_written_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    placed = _cache_run("import jax\njax.jit(lambda x: x * 2 + 1)(1.0)",
                        JAX_COMPILATION_CACHE_DIR=str(cache),
                        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert placed == str(cache)
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_chip_smoke_phases_rehearse_in_interpret_mode(monkeypatch, capsys):
    from repro.kernels import ops

    def names_in_program(checks, name, text, kernels):
        checks(name, all(k in text for k in kernels))

    monkeypatch.setattr(chip_smoke, "_has_kernels", names_in_program)
    with ops.use_pallas(True, interpret=True):
        chip_smoke.run(TINY, 1, chip_smoke.CompileClock())
    lines = capsys.readouterr().out.splitlines()
    for phase in ("nuts", "ensemble", "enum_hmm"):
        done = [ln for ln in lines
                if ln.startswith(f"[smoke] {phase}: checks")]
        assert done, phase
        passed, total = done[0].split()[3].split("/")
        assert passed == total and int(total) > 0, done[0]

