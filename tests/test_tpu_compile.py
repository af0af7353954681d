"""The inference kernels compile for a TPU v5e chip at the widths they run at.

Each test lowers one Pallas kernel (not its jnp reference) for a described,
not attached, v5e chip and asserts the compiled program holds the kernel as a
``tpu_custom_call``.  This catches what interpret mode cannot: block shapes
the Mosaic compiler refuses, VMEM overuse, and kernels that fail to lower.
The topology is described inside a module fixture, so importing this file
never loads libtpu, and every worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.enum_contract import enum_contract
from repro.kernels.glm_potential import glm_potential_grad
from repro.kernels.leapfrog import leapfrog_halfstep, leapfrog_halfstep_batch
from repro.kernels.rwm_mala import mala_step

N, D = 581_012, 54      # CoverType width (paper Table 2a)
CHAINS = 4              # NUTS chains
ENSEMBLE = 64           # ChEES / MALA chains


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_kernel(fn, *args, name):
    text = jax.jit(fn).lower(*args).compile().as_text()
    # a kernel's instruction is named after it (with a vmap_ prefix when
    # batched): "%vmap_glm_potential_grad_.1 = ... custom-call(...)"
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any(name in call for call in calls), (name, calls)


def test_glm_potential_grad_compiles(one_chip):
    _assert_kernel(glm_potential_grad, _spec(one_chip, N, D),
                   _spec(one_chip, N), _spec(one_chip, D),
                   name="glm_potential_grad")


def test_glm_potential_grad_vmapped_over_chains_compiles(one_chip):
    def chains(x, y, w):
        return jax.vmap(lambda wc: glm_potential_grad(x, y, wc))(w)

    _assert_kernel(chains, _spec(one_chip, N, D), _spec(one_chip, N),
                   _spec(one_chip, CHAINS, D), name="glm_potential_grad")


def test_leapfrog_halfstep_compiles(one_chip):
    vec = _spec(one_chip, D)
    _assert_kernel(leapfrog_halfstep, vec, vec, vec, vec, _spec(one_chip),
                   name="leapfrog_halfstep")


def test_leapfrog_halfstep_vmapped_over_chains_compiles(one_chip):
    # how NUTS calls it: per-chain step size and mass under the chain vmap
    ens = _spec(one_chip, CHAINS, D)
    _assert_kernel(jax.vmap(leapfrog_halfstep), ens, ens, ens, ens,
                   _spec(one_chip, CHAINS), name="leapfrog_halfstep")


def test_leapfrog_halfstep_batch_compiles(one_chip):
    ens = _spec(one_chip, ENSEMBLE, D)
    _assert_kernel(leapfrog_halfstep_batch, ens, ens, ens, _spec(one_chip, D),
                   _spec(one_chip), name="leapfrog_halfstep_batch")


def test_mala_step_compiles(one_chip):
    ens = _spec(one_chip, ENSEMBLE, D)
    _assert_kernel(mala_step, ens, ens, ens, _spec(one_chip, D),
                   _spec(one_chip), name="mala_step")


def test_enum_contract_compiles(one_chip):
    K, T = 8, 120
    _assert_kernel(enum_contract, _spec(one_chip, T, K),
                   _spec(one_chip, T, K, K), name="enum_contract")
