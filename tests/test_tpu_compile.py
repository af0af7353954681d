"""The inference kernels compile for a TPU v5e chip at the widths they run at.

Each test lowers one Pallas kernel (not its jnp reference) for a described,
not attached, v5e chip and asserts the compiled program holds the kernel as a
``tpu_custom_call``.  This catches what interpret mode cannot: block shapes
the Mosaic compiler refuses, VMEM overuse, and kernels that fail to lower.
The topology is described inside a module fixture, so importing this file
never loads libtpu, and every worker collects the same tests.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.infer.glm import _make_slab_nll
from repro.kernels import ops
from repro.kernels.enum_contract import enum_contract, enum_contract_chain
from repro.kernels.glm_potential import (
    glm_potential_grad,
    glm_potential_grad_slab,
)
from repro.kernels.leapfrog import leapfrog_halfstep, leapfrog_halfstep_batch
from repro.kernels.rwm_mala import mala_step

N, D = 581_012, 54      # CoverType width (paper Table 2a)
ROWS = 56               # the design slab's rows: round_up(D + 2, 8)
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


@pytest.mark.parametrize("chains", [CHAINS, ENSEMBLE])
def test_glm_potential_grad_slab_compiles(one_chip, chains):
    _assert_kernel(glm_potential_grad_slab, _spec(one_chip, ROWS, N),
                   _spec(one_chip, chains, D), name="glm_potential_grad")


def _elements(line):
    """The most elements of any array shape written in an HLO line."""
    return max((math.prod(int(n) for n in dims.split(",") if n)
                for dims in re.findall(r"[a-z]+\d*\[([\d,]*)\]", line)),
               default=0)


@pytest.mark.parametrize("chains", [CHAINS, ENSEMBLE])
def test_glm_fused_gradient_reads_the_slab_in_place(one_chip, chains):
    """The fused likelihood's gradient, vmapped over chains as the executor
    runs it, compiles to one kernel call that takes the slab where it
    lies: no other instruction touches an array of n or more elements (a
    reshape, copy, pad or transpose of the data on every call)."""
    route = {}

    def over_chains(slab, w):
        nll = _make_slab_nll(slab, None, "bernoulli_logit", route)
        return jax.vmap(jax.value_and_grad(nll))(w)

    with ops.use_pallas(True):
        text = jax.jit(over_chains).lower(
            _spec(one_chip, ROWS, N),
            _spec(one_chip, chains, D)).compile().as_text()
    lines = [line.strip() for line in text.splitlines()
             if re.match(r"\s*(ROOT )?%\S+ = ", line)]
    kernels = [line for line in lines
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and "glm_potential_grad" in kernels[0]
    big = [line[:160] for line in lines
           if line not in kernels and " parameter(" not in line
           and _elements(line) >= N]
    assert not big, big
    assert route == {"route": "batched", "chains": chains}


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


def test_enum_contract_plate_batched_compiles(one_chip):
    """One step of the plate-batched markov elimination of the JSB HMM:
    4 chains x 229 sequences = 916 rows of 16 states, under the chain
    vmap and differentiated through the kernel's custom_vjp."""
    chains, sequences, K = 4, 229, 16

    def value_and_grads(alpha, mat):
        def one_chain(a, m):
            return jnp.sum(enum_contract(a, m))
        return jax.vmap(jax.value_and_grad(one_chain, argnums=(0, 1)))(
            alpha, mat)

    _assert_kernel(value_and_grads, _spec(one_chip, chains, sequences, K),
                   _spec(one_chip, chains, sequences, K, K),
                   name="enum_contract")


@pytest.mark.parametrize("sequences", [229, 5000])
def test_enum_contract_chain_compiles(one_chip, sequences):
    """The JSB HMM's whole chain, value and gradient: 4 chains, each 229
    sequences over 128 steps of 16 states, under the chain vmap; the
    forward and backward kernels each hold a block of at most 256 rows'
    steps in VMEM, so a corpus of 5,000 sequences compiles too."""
    chains, steps, K = 4, 128, 16

    def value_and_grads(alpha0, unary, pair, keep):
        def one_chain(a, u, p):
            return jnp.sum(enum_contract_chain(a, u, p, keep))
        return jax.vmap(jax.value_and_grad(one_chain, argnums=(0, 1, 2)))(
            alpha0, unary, pair)

    keep = jax.ShapeDtypeStruct((steps, sequences), jnp.bool_,
                                sharding=one_chip)
    text = jax.jit(value_and_grads).lower(
        _spec(one_chip, chains, sequences, K),
        _spec(one_chip, chains, steps, sequences, K),
        _spec(one_chip, chains, steps, K, K), keep).compile().as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any("enum_contract_chain_vjp" in c for c in calls), calls
    assert any("enum_contract_chain" in c and "vjp" not in c
               for c in calls), calls
