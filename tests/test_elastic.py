"""Elastic restart: a checkpoint written under one mesh restores onto a
different mesh shape (subprocess with 8 virtual devices).  Below that, the
MCMC elastic-resume matrix: an inference run checkpointed on 4 devices
(2x2 mesh) is preempted and resumed on 1, 2, and 8 devices — every
continuation must be bit-identical to the single-device vectorized
reference, and an indivisible chain/mesh combination must fail loudly
with RPL301 (docs/distributed.md)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.distributed import checkpoint as ckpt

d = tempfile.mkdtemp()
# "train" on mesh A: (data=4, model=2)
mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(AxisType.Auto,) * 2)
w = {"emb": jnp.arange(64.0).reshape(8, 8),
     "scale": jnp.ones(8)}
sh_a = {"emb": NamedSharding(mesh_a, P("data", "model")),
        "scale": NamedSharding(mesh_a, P("model"))}
w_a = jax.tree.map(jax.device_put, w, sh_a)
ckpt.save(w_a, d + "/ck", step=42, extra={"cursor": 7})

# elastic restart on mesh B: (data=2, model=4) — different dp degree
mesh_b = jax.make_mesh((2, 4), ("data", "model"),
                       axis_types=(AxisType.Auto,) * 2)
sh_b = {"emb": NamedSharding(mesh_b, P("data", "model")),
        "scale": NamedSharding(mesh_b, P("model"))}
w_b, step, extra = ckpt.restore(w, d + "/ck", shardings=sh_b)
assert step == 42 and extra["cursor"] == 7
np.testing.assert_array_equal(np.asarray(w_b["emb"]), np.asarray(w["emb"]))
assert w_b["emb"].sharding.mesh.shape["data"] == 2   # re-sharded
# and the restored array is usable in computation on the new mesh
out = jax.jit(lambda a: (a @ a.T).sum())(w_b["emb"])
assert np.isfinite(float(out))
print(json.dumps({"ok": True}))
"""


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]


# ---------------------------------------------------------------------------
# MCMC elastic-resume matrix
#
# Save on 4 devices with a (2, 2) chains-x-data mesh, preempt between a
# sampling chunk's samples write and its state write (the orphaned-chunk
# case), then resume on 1, 2, and 8 devices with (1,1) / (2,1) / (4,2)
# meshes.  Arrays are checkpointed in logical (unsharded) layout, so each
# resume re-places the state under its own mesh; the continuation must be
# bit-identical to the single-device vectorized reference.  The chain
# widths stay >= 2 chains per device in every layout — at width 1 XLA's
# scalar-width fusion drifts at ULP level (docs/distributed.md).
# ---------------------------------------------------------------------------

MCMC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ["ELASTIC_DEVICES"])
import json
import jax, jax.numpy as jnp
import numpy as np
from jax import random
import repro.core as pc
from repro.core import dist
from repro.core.infer import MCMC, NUTS
from repro.core.infer.mala import MALA

mode = os.environ["ELASTIC_MODE"]
mesh = os.environ["ELASTIC_MESH"]
ckdir = os.environ.get("ELASTIC_CKDIR", "")
kern = {"nuts": NUTS, "mala": MALA}[os.environ["ELASTIC_KERNEL"]]

n, d = 128, 4
x = random.normal(random.PRNGKey(0), (n, d))
w_true = jnp.linspace(-1.0, 1.0, d)
y = (random.uniform(random.PRNGKey(1), (n,))
     < jax.nn.sigmoid(x @ w_true)).astype(jnp.float32)

def model(x, y):
    w = pc.sample("w", dist.Normal(jnp.zeros(d), 1.0).to_event(1))
    pc.sample("y", dist.Bernoulli(logits=x @ w), obs=y,
              infer={"potential": "glm"})

def make():
    if mesh == "vectorized":
        return MCMC(kern(model, data_shards=4), num_warmup=24,
                    num_samples=36, num_chains=8, chain_method="vectorized")
    shape = tuple(int(v) for v in mesh.split(","))
    return MCMC(kern(model, data_shards=4), num_warmup=24, num_samples=36,
                num_chains=8, chain_method="parallel", mesh_shape=shape)

def sample_hex(m):
    return np.asarray(m.get_samples()["w"], np.float32).tobytes().hex()

if mode == "ref":
    m = make()
    m.run(random.PRNGKey(7), x, y)
    print(json.dumps({"hex": sample_hex(m)}))
elif mode == "kill":
    from repro.distributed import checkpoint as ckpt
    real, calls = ckpt.save, {"n": 0}
    def killing(tree, directory, **kw):
        real(tree, directory, **kw)
        calls["n"] += 1
        if calls["n"] == 3:   # after the samples chunk, before the state
            raise KeyboardInterrupt
    ckpt.save = killing
    try:
        make().run(random.PRNGKey(7), x, y, checkpoint_every=20,
                   checkpoint_dir=ckdir)
        raise SystemExit("kill never fired")
    except KeyboardInterrupt:
        pass
    print(json.dumps({"killed_after": calls["n"],
                      "state_step": ckpt.latest_step(ckdir + "/state")}))
elif mode == "resume":
    m = make()
    m.run(random.PRNGKey(7), x, y, checkpoint_every=20, checkpoint_dir=ckdir,
          resume=True)
    print(json.dumps({"hex": sample_hex(m),
                      "n_devices": len(jax.devices())}))
elif mode == "negative":
    try:
        make().run(random.PRNGKey(7), x, y, checkpoint_dir=ckdir,
                   resume=True)
        print(json.dumps({"error": None}))
    except Exception as e:
        print(json.dumps({"error": f"{type(e).__name__}: {e}"[:400]}))
"""


def _run_elastic(tmp_path, *, mode, devices, mesh, kernel, ckdir=""):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"),
               ELASTIC_MODE=mode, ELASTIC_DEVICES=str(devices),
               ELASTIC_MESH=mesh, ELASTIC_KERNEL=kernel,
               ELASTIC_CKDIR=ckdir)
    out = subprocess.run([sys.executable, "-c", MCMC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (
        f"{mode}/{kernel}/{mesh} on {devices} devices failed:\n"
        + out.stderr[-3000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


# resume targets: (devices, mesh) — chain widths 8, 4, 2; data axis 1, 1, 2
RESUME_MATRIX = [(1, "1,1"), (2, "2,1"), (8, "4,2")]


@pytest.mark.slow
@pytest.mark.parametrize("kernel", ["nuts", "mala"])
def test_mcmc_elastic_resume_matrix(kernel, tmp_path):
    ref = _run_elastic(tmp_path, mode="ref", devices=1, mesh="vectorized",
                       kernel=kernel)

    saved = str(tmp_path / f"{kernel}-save")
    kill = _run_elastic(tmp_path, mode="kill", devices=4, mesh="2,2",
                        kernel=kernel, ckdir=saved)
    # preempted between the samples write and the state write: the state
    # manifest is still at warmup end, the samples chunk is orphaned
    assert kill["killed_after"] == 3 and kill["state_step"] == 24, kill

    for devices, mesh in RESUME_MATRIX:
        # each resume completes its checkpoint dir, so every target gets a
        # fresh copy of the preempted state
        ckdir = str(tmp_path / f"{kernel}-resume-{devices}")
        shutil.copytree(saved, ckdir)
        got = _run_elastic(tmp_path, mode="resume", devices=devices,
                           mesh=mesh, kernel=kernel, ckdir=ckdir)
        assert got["n_devices"] == devices, got
        assert got["hex"] == ref["hex"], (
            f"{kernel}: resume on {devices} devices (mesh {mesh}) diverged "
            "from the vectorized reference")


@pytest.mark.slow
def test_mcmc_elastic_resume_indivisible_chains_raises_rpl301(tmp_path):
    saved = str(tmp_path / "neg-save")
    _run_elastic(tmp_path, mode="kill", devices=4, mesh="2,2",
                 kernel="nuts", ckdir=saved)
    got = _run_elastic(tmp_path, mode="negative", devices=8, mesh="3,2",
                       kernel="nuts", ckdir=saved)
    assert got["error"] is not None and "RPL301" in got["error"], got
