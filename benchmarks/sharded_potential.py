"""Data-sharded GLM potential scaling: ms per chain-batched
potential+gradient evaluation (the leapfrog-dominant cost) vs the mesh
data-axis size, at n in {20k, 200k} (docs/distributed.md).

On a TPU the timing runs in this process over the chips present: a child
process could not reach a chip the parent already holds.  Elsewhere it runs
in a child with 8 virtual CPU devices so the ``(1, sd)`` meshes are real
even when this process already initialized jax on one device.  Virtual
devices share the same cores, so absolute speedups there understate real
multi-chip scaling — the recorded trajectory is what matters (a layout that
stops compiling, or a fold that starts re-evaluating every row on every
device, shows up as a step change here).
"""
import json
import os
import subprocess
import sys

VIRTUAL_DEVICES = 8


def measure(cfg):
    """Rows of ms/eval per layout, over every data-axis size up to 8 that
    the visible devices can hold."""
    import time

    import jax
    import jax.numpy as jnp
    from jax import random
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.infer.glm import _make_sharded_nll
    from repro.core.infer.hmc_util import chain_vmap
    from repro.distributed.sharding import use_inference_mesh
    from repro.launch.mesh import make_inference_mesh

    d, C, S = 8, 8, 8          # latent dim, chains, static fold shards
    rows = []
    for n in cfg["ns"]:
        x = random.normal(random.PRNGKey(0), (n, d))
        y = (random.uniform(random.PRNGKey(1), (n,)) < 0.5).astype(
            jnp.float32)
        nll = _make_sharded_nll(x, y, jnp.zeros(n), None, "bernoulli_logit",
                                S)
        z = random.normal(random.PRNGKey(2), (C, d)) * 0.1

        def timed(f, zz):
            out = f(zz)
            jax.block_until_ready(out)          # compile + first touch
            reps, best = cfg["reps"], float("inf")
            for _ in range(3):                  # best-of-3 batches of reps
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = f(zz)
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t0) / reps)
            return 1e3 * best

        base = jax.jit(lambda zz: jax.vmap(jax.value_and_grad(nll))(zz))
        rows.append({"n": n, "layout": "local",
                     "ms_per_eval": timed(base, z)})
        for sd in (1, 2, 4, 8):
            if sd > len(jax.devices()):
                break
            mesh = make_inference_mesh(C, (1, sd))

            def sharded(zz, mesh=mesh):
                with use_inference_mesh(mesh, "data"):
                    return chain_vmap(jax.value_and_grad(nll))(zz)

            zs = jax.device_put(z, NamedSharding(mesh, P("chains")))
            rows.append({"n": n, "layout": f"(1,{sd})",
                         "ms_per_eval": timed(jax.jit(sharded), zs)})
    return {"rows": rows, "n_devices": len(jax.devices()),
            "platform": jax.default_backend()}


_CHILD = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
from benchmarks.sharded_potential import measure
print(json.dumps(measure(json.loads(sys.argv[1]))))
"""


def main(quick=False):
    import jax

    # n=200k stays in quick mode: a potential eval is milliseconds, so the
    # headline scaling row costs a few compiles, not a long chain
    cfg = {"ns": [20_000, 200_000], "reps": 10 if quick else 30}
    if jax.default_backend() == "tpu":
        got = measure(cfg)
    else:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            p for p in [os.path.join(root, "src"), root,
                        os.environ.get("PYTHONPATH", "")] if p))
        out = subprocess.run(
            [sys.executable, "-c", _CHILD.format(n=VIRTUAL_DEVICES),
             json.dumps(cfg)],
            env=env, capture_output=True, text=True, timeout=1800)
        if out.returncode != 0:
            print(f"[sharded_potential failed]\n{out.stderr[-2000:]}")
            return {"benchmark": "sharded_potential", "error":
                    out.stderr.strip().splitlines()[-1][:300] if out.stderr
                    else "subprocess failed"}
        got = json.loads(out.stdout.strip().splitlines()[-1])
    rec = {"benchmark": "sharded_potential", "n_devices": got["n_devices"],
           "platform": got["platform"], "data_shards": 8, "num_chains": 8,
           "rows": got["rows"]}
    for row in got["rows"]:
        print(f"n={row['n']:>7}  {row['layout']:>6}  "
              f"{row['ms_per_eval']:8.3f} ms/eval")
    return rec


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
