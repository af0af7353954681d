"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run pins the virtual device count before jax's
first init; see dryrun.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis rides
    the DCN and carries only data-parallel gradient reductions."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=("data", "model")):
    """A mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n // 2, 2) if n % 2 == 0 and n > 1 else (n, 1)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_inference_mesh(num_chains, mesh_shape=None, *, devices=None):
    """Mesh for the MCMC executor (``chain_method="parallel"``).

    ``mesh_shape=None`` builds the legacy 1-D ``("chains",)`` mesh over the
    largest device count dividing ``num_chains`` — chains spread, the
    potential evaluates locally per device.  ``mesh_shape=(Sc, Sd)`` builds
    the 2-D ``("chains", "data")`` mesh: the chain axis stays GSPMD-sharded
    (same compiled graph as the 1-D and single-device layouts — the
    bit-identity invariant), while a data-shard-aware potential evaluates
    its per-shard partials under ``shard_map`` over the ``data`` axis.

    Raises :class:`~repro.core.errors.ReproValueError` RPL301 when the
    requested shape does not fit: chain count not divisible by the chain
    axis (every device must own the same number of whole chains, or the
    resumed sample streams could not be bit-identical), or more mesh slots
    than devices.
    """
    from repro.core.errors import ReproValueError
    devices = list(devices) if devices is not None else jax.devices()
    if mesh_shape is None:
        use = max(d for d in range(1, len(devices) + 1)
                  if num_chains % d == 0)
        return jax.make_mesh((use,), ("chains",), devices=devices[:use],
                             axis_types=(AxisType.Auto,))
    chains_ax, data_ax = (int(v) for v in mesh_shape)
    if chains_ax < 1 or data_ax < 1:
        raise ReproValueError(
            f"mesh_shape={mesh_shape} is not a valid (chains, data) shape",
            code="RPL301")
    if num_chains % chains_ax != 0:
        raise ReproValueError(
            f"num_chains={num_chains} is not divisible by the mesh chain "
            f"axis ({chains_ax}): every device must own the same number of "
            "whole chains for sample streams to stay bit-identical across "
            "layouts. Pick a chain axis that divides the chain count.",
            code="RPL301")
    need = chains_ax * data_ax
    if need > len(devices):
        raise ReproValueError(
            f"mesh_shape={mesh_shape} needs {need} devices but only "
            f"{len(devices)} are visible.", code="RPL301")
    return jax.make_mesh((chains_ax, data_ax), ("chains", "data"),
                         devices=devices[:need],
                         axis_types=(AxisType.Auto,) * 2)
