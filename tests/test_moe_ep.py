"""MoE expert parallelism: shard_map all_to_all dispatch == single-device
reference, including gradients (runs in a subprocess with 8 virtual
devices so the main pytest process keeps its real device count)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax, jax.numpy as jnp
from jax import random
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import LM, reduced
from repro.models.common import sharding_ctx
from repro.distributed.sharding import make_rules, param_shardings

results = {}
for arch, overrides in [
    ("deepseek-v3-671b", dict(num_experts=8, num_experts_per_tok=2,
                              mtp=False)),
    ("jamba-v0.1-52b", dict(num_experts=8, num_experts_per_tok=2)),
]:
    cfg = reduced(get_config(arch), **overrides)
    cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # no drops
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    rules = make_rules(cfg, mesh)
    lm = LM(cfg, remat="none")
    B, S = 4, 32
    batch = {"tokens": random.randint(random.PRNGKey(1), (B, S), 3,
                                      cfg.vocab_size),
             "labels": random.randint(random.PRNGKey(2), (B, S), 3,
                                      cfg.vocab_size)}
    w_ref = lm.init(random.PRNGKey(0))
    loss_ref, g_ref = jax.value_and_grad(
        lambda w: lm.forward(w, batch)[0])(w_ref)
    with sharding_ctx(mesh, rules):
        shapes, spec = lm.abstract_params()
        shardings = param_shardings(spec, rules, mesh, shapes=shapes)
        w = jax.tree.map(jax.device_put, w_ref, shardings)
        bsh = NamedSharding(mesh, P(("pod", "data"), None))
        batch_d = {k: jax.device_put(v, bsh) for k, v in batch.items()}
        loss_d, g_d = jax.jit(jax.value_and_grad(
            lambda w, b: lm.forward(w, b)[0]))(w, batch_d)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        g_ref, g_d)
    results[arch] = {"loss_ref": float(loss_ref), "loss_dist": float(loss_d),
                     "max_grad_err": max(jax.tree.leaves(errs))}
print(json.dumps(results))
"""


@pytest.mark.slow
def test_moe_ep_parity_8dev():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    for arch, r in results.items():
        assert abs(r["loss_ref"] - r["loss_dist"]) < 2e-5, (arch, r)
        assert r["max_grad_err"] < 2e-3, (arch, r)
