"""Named scopes: the sampler's layers, named inside the compiled programs.

The executor compiles a whole chunk of draws into one XLA program, so a
profiler trace shows only XLA's operations (``fusion.12``,
``reshape.562``, a Pallas kernel's call).  Each layer of the hot path runs
under a ``jax.named_scope`` of one fixed name, which JAX writes into every
operation's ``op_name`` metadata (``jit(prog)/.../repro.tree/.../
repro.integrator/repro.potential/...``).  A trace reducer credits each
device operation to the innermost ``repro.*`` scope of that path.

Scopes are metadata only: the compiled instructions, and so the draws,
are the same with them as without.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax


class Scopes(NamedTuple):
    potential: str = "repro.potential"    # value and gradient of U
    integrator: str = "repro.integrator"  # leapfrog kicks and drift
    tree: str = "repro.tree"              # NUTS trajectory building
    adapt: str = "repro.adapt"            # warmup step-size / mass adaptation
    markov: str = "repro.markov"          # discrete chain elimination


SCOPES = Scopes()


def scoped(name):
    """Decorator: trace the function under the named scope ``name``.

    Each call enters a fresh ``jax.named_scope``: one instance used as a
    decorator keeps its state on itself, which a nested or concurrent
    trace of the same function would overwrite."""
    def wrap(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapped

    return wrap
