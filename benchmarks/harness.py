"""Shared benchmark harness: time-per-leapfrog-step and time-per-effective-
sample, the paper's two metrics (Table 2a, Fig 2b)."""
from __future__ import annotations

import os
import time

import jax
import numpy as np
from jax import random

from repro.core.infer import MCMC, NUTS, effective_sample_size

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")


def use_compile_cache():
    """Persist compiled programs across processes: in
    ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself), else
    in a fixed directory of this checkout — a fixed path, because the path
    is part of what a later run must find again.  Returns the directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def run_nuts(model, model_args=(), model_kwargs=None, *, num_warmup,
             num_samples, rng_seed=0, step_size=None, adapt=True,
             max_tree_depth=10):
    kw = model_kwargs or {}
    kernel_kwargs = dict(max_tree_depth=max_tree_depth)
    if step_size is not None:
        kernel_kwargs.update(step_size=step_size, adapt_step_size=adapt,
                             adapt_mass_matrix=adapt)
    kernel = NUTS(model, **kernel_kwargs)
    mcmc = MCMC(kernel, num_warmup=num_warmup, num_samples=num_samples)

    t0 = time.time()
    mcmc.run(random.PRNGKey(rng_seed), *model_args, **kw)
    jax.block_until_ready(mcmc.get_samples())
    cold = time.time() - t0
    # warm run: the whole chain is ONE cached XLA program (paper Sec 3.1) —
    # re-running with a new seed measures device time, no trace/compile
    t1 = time.time()
    mcmc.run(random.PRNGKey(rng_seed + 1), *model_args, **kw)
    jax.block_until_ready(mcmc.get_samples())
    wall = time.time() - t1
    # stable run: REPEAT the warm seed.  The first warm chunk still pays
    # one-off allocator/first-touch costs, and a fresh seed draws different
    # trajectories (different leapfrog counts), so wall_s alone makes
    # ms/leapfrog noisy across runs.  Same seed -> same program, same rng,
    # same trajectories as the run whose extras are read below.
    t2 = time.time()
    mcmc.run(random.PRNGKey(rng_seed + 1), *model_args, **kw)
    jax.block_until_ready(mcmc.get_samples())
    warm_wall = time.time() - t2

    extras = mcmc.get_extra_fields()
    n_leapfrog = int(np.sum(np.asarray(extras["num_steps"])))
    # warmup leapfrogs aren't collected; estimate with the sampling mean
    mean_steps = n_leapfrog / max(num_samples, 1)
    total_lf = n_leapfrog + mean_steps * num_warmup
    samples = mcmc.get_samples(group_by_chain=True)
    ess = {k: float(np.min(effective_sample_size(v)))
           for k, v in samples.items() if v.ndim >= 2}
    min_ess = min(ess.values()) if ess else float("nan")
    return {
        "wall_s": wall,
        "warm_wall_s": warm_wall,
        "compile_s": cold - wall,
        "num_leapfrog": int(total_lf),
        "ms_per_leapfrog": 1e3 * warm_wall / max(total_lf, 1),
        "min_ess": min_ess,
        "ms_per_eff_sample": 1e3 * warm_wall / max(min_ess, 1e-9),
        "mean_accept": float(np.mean(np.asarray(extras["accept_prob"]))),
        "divergences": int(np.sum(np.asarray(extras["diverging"]))),
    }
