"""Discrete-latent enumeration: exact marginalization by effect handlers.

NUTS only moves continuous latents; what makes the modeling language general
is summing discrete latents out *exactly*, implemented — as in Pyro — purely
with handlers and broadcasting:

- The :class:`enum` handler substitutes, for every latent sample site marked
  ``infer={"enumerate": "parallel"}``, the distribution's full support
  broadcast into a fresh *leftmost* batch dim from a plate-aware allocator
  (enumeration dims live at ``dim <= first_available_dim``, strictly to the
  left of every plate/batch dim, so they never collide).
- :func:`contract_enum_factors` is the enum-aware density contraction used by
  the unified :func:`repro.core.infer.util.log_density`: per-site ``mask``
  (then ``scale``) apply as usual, after which the enumeration dims are summed
  out by variable elimination in log space — plate dims stay independent
  products, exactly as without enumeration.
- :func:`markov` is the sequential counterpart for chain-structured models:
  it eliminates the state along the time axis at O(T·K²) — instead of the
  O(K^T) a parallel dim per step would cost — for every row of the plates
  around it at once, through :func:`repro.kernels.ops.enum_contract_chain`
  (the whole forward algorithm in one Pallas call and its gradient in one
  more; a ``lax.scan`` of :func:`repro.kernels.ops.enum_contract` steps on
  the reference path).
- :func:`infer_discrete` recovers the *posterior* of the marginalized sites
  given continuous draws: forward-filter/backward-sample for ``markov``
  chains, exact sequential conditioning on the joint enumeration tensor for
  parallel sites.

``initialize_model_structure`` auto-marks enumerable discrete latents (via
:func:`config_enumerate`), so a model with a latent ``Categorical`` flows
through the jit-compiled NUTS executor untouched — the flat vector NUTS moves
contains only the continuous latents, and every potential-energy evaluation
marginalizes the discrete ones.  See ``docs/enumeration.md``.
"""
from __future__ import annotations

import math
import warnings
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, random

from ...obs.scopes import SCOPES
from .. import dist as _dist
from .. import primitives
from ..errors import ReproNotImplementedError, ReproValueError
from ..handlers import Messenger, block, infer_config, scope, seed, trace
from ..primitives import deterministic as _deterministic
from ..primitives import plate as _plate
from ..primitives import sample as _sample

_NOT_ENUMERABLE_ERR = (
    "cannot enumerate site '{name}': {fn} has no enumerate_support (only "
    "finite-support discrete distributions can be enumerated — a continuous "
    "site cannot). Remove infer={{'enumerate': 'parallel'}} from the site, "
    "or observe/substitute it.")


def _is_enumerable_latent(msg: dict) -> bool:
    return (msg["type"] == "sample" and not msg["is_observed"]
            and msg["value"] is None
            and getattr(msg["fn"], "has_enumerate_support", False))


def _auto_parallel(msg: dict) -> bool:
    """Unmarked enumerable latent with no rng key in reach: nothing but
    enumeration can value it (an unseeded density evaluation would crash on
    the draw), so ``log_density`` auto-detects it.  Seeded traces keep their
    draw semantics — the mark stays opt-in there."""
    return (_is_enumerable_latent(msg)
            and msg["infer"].get("enumerate") is None
            and msg["kwargs"].get("rng_key") is None)


def config_enumerate(fn=None):
    """Mark every enumerable discrete latent site for parallel enumeration.

    Thin :class:`~repro.core.handlers.infer_config` wrapper setting
    ``infer={"enumerate": "parallel"}`` on latent sample sites whose
    distribution ``has_enumerate_support`` (sites that already carry an
    ``enumerate`` entry are left alone).  The mark is inert outside density
    evaluation: a seeded simulation still draws the site normally.
    """
    def _cfg(msg):
        if _is_enumerable_latent(msg) and "enumerate" not in msg["infer"]:
            return {"enumerate": "parallel"}
        return {}

    return infer_config(fn, config_fn=_cfg)


class _EnumProbe(Messenger):
    """Pass-1 detector for the enum-aware ``log_density``.

    Inert for models without enumeration: it only *measures* — the deepest
    plate/batch dim of any sample site (the plate-aware allocator's budget)
    and whether any site requests enumeration.  Marked sites get a cheap
    probe value (the lowest support element, broadcast-ready) so the trace
    completes without an rng key; the probe trace is discarded whenever
    enumeration is detected and a real :class:`enum` pass follows.
    """

    def __enter__(self):
        self.found = False
        self.max_plate_nesting = 0
        self.min_marked_dim = 0  # most negative dim pre-allocated by an
        #                          inner (user-managed) enum handler
        return super().__enter__()

    def process_message(self, msg: dict) -> None:
        if msg["type"] != "sample":
            return
        fn = msg["fn"]
        nd = len(getattr(fn, "batch_shape", ()))
        for frame in msg["cond_indep_stack"]:
            nd = max(nd, -frame.dim)
        if msg["value"] is not None:
            nd = max(nd, jnp.ndim(msg["value"]) - getattr(fn, "event_dim", 0))
        self.max_plate_nesting = max(self.max_plate_nesting, nd)
        d = msg["infer"].get("_enumerate_dim")
        if d is not None:  # an inner enum handler already enumerated it
            self.found = True
            self.min_marked_dim = min(self.min_marked_dim, d)
            return
        if _auto_parallel(msg):
            msg["infer"]["enumerate"] = "parallel"
        if (msg["infer"].get("enumerate") == "parallel"
                and not msg["is_observed"] and msg["value"] is None):
            self.found = True
            if not getattr(fn, "has_enumerate_support", False):
                raise ReproValueError(_NOT_ENUMERABLE_ERR.format(
                    name=msg["name"], fn=type(fn).__name__),
                    code="RPL013", site=msg["name"])
            msg["value"] = fn.enumerate_support(expand=False)[0]
            msg["infer"]["_enum_probe"] = True


def _first_available_dim(probe: _EnumProbe, max_plate_nesting=None) -> int:
    mpn = (probe.max_plate_nesting if max_plate_nesting is None
           else max_plate_nesting)
    return min(-int(mpn) - 1, probe.min_marked_dim - 1)


class enum(Messenger):
    """Parallel-enumeration handler.

    Effect: ``process_message`` — for latent sample sites marked
    ``infer={"enumerate": "parallel"}``, replaces the would-be draw with the
    distribution's full support stacked into a fresh leftmost dim allocated
    from ``first_available_dim`` downwards (``first_available_dim`` must be
    ``-(max_plate_nesting + 1)`` or deeper, so enumeration dims sit strictly
    left of every plate/batch dim).  The allocated dim and support size are
    recorded in ``msg["infer"]["_enumerate_dim"] / ["_enum_total"]`` — the
    breadcrumbs :func:`contract_enum_factors` eliminates by, and that make
    an outer ``substitute``/``condition``/``do`` on the site fail loudly
    instead of silently overwriting the enumeration.

    ``mode="sample"`` (used by :func:`infer_discrete`) additionally carries an
    rng key; :func:`markov` then backward-samples its chain into ``.samples``
    instead of emitting a marginal factor.
    """

    def __init__(self, fn=None, first_available_dim=None, *,
                 mode: str = "marginal", rng_key=None, strict: bool = False,
                 extra_dims: Optional[dict] = None):
        super().__init__(fn)
        if first_available_dim is None or first_available_dim >= 0:
            raise ValueError(
                "enum requires a negative first_available_dim — use "
                "-(max_plate_nesting + 1), counting every plate/batch dim "
                f"of the model; got {first_available_dim}")
        if mode not in ("marginal", "sample"):
            raise ValueError(f"unknown enum mode {mode!r}")
        if mode == "sample" and rng_key is None:
            raise ValueError("enum(mode='sample') requires an rng_key")
        self.first_available_dim = int(first_available_dim)
        self.mode = mode
        self.rng_key = rng_key
        self.strict = strict          # markov-internal: no stray latents
        self._markov_local = False    # set on markov's per-step instances
        # enumeration dims owned by an enclosing allocator (markov hands its
        # local per-step handler the chain's `prev` dim this way) — batch
        # extents at these dims are legitimate, not collisions
        self._extra_dims = dict(extra_dims or {})
        self.samples: dict = {}
        self._next = self.first_available_dim
        self._alloc: OrderedDict = OrderedDict()

    def __enter__(self):
        self._next = self.first_available_dim
        self._alloc = OrderedDict()
        self.samples = {}
        return super().__enter__()

    def allocate(self, size: int, name: str) -> int:
        dim = self._next
        self._next -= 1
        self._alloc[name] = (dim, int(size))
        return dim

    def fresh_key(self):
        self.rng_key, sub = random.split(self.rng_key)
        return sub

    def process_message(self, msg: dict) -> None:
        if msg["type"] != "sample":
            return
        if msg["value"] is not None or msg["is_observed"]:
            return
        strategy = msg["infer"].get("enumerate")
        if strategy is None and _auto_parallel(msg):
            strategy = "parallel"
        if strategy is None:
            if self.strict and not getattr(msg["fn"], "has_enumerate_support",
                                           False):
                raise RuntimeError(
                    f"latent site '{msg['name']}' inside a markov transition "
                    "is neither observed nor enumerable; sample continuous "
                    "latents outside the transition function")
            return
        if strategy != "parallel":
            raise ValueError(
                f"unknown enumerate strategy {strategy!r} for site "
                f"'{msg['name']}' (only 'parallel' is supported)")
        fn = msg["fn"]
        if not getattr(fn, "has_enumerate_support", False):
            raise ReproValueError(_NOT_ENUMERABLE_ERR.format(
                name=msg["name"], fn=type(fn).__name__),
                code="RPL013", site=msg["name"])
        if tuple(msg["kwargs"].get("sample_shape") or ()) != ():
            raise NotImplementedError(
                f"site '{msg['name']}': sample_shape does not compose with "
                "enumeration; use a plate instead")
        for frame in msg["cond_indep_stack"]:
            if frame.dim <= self.first_available_dim:
                raise ReproValueError(
                    f"plate '{frame.name}' occupies dim {frame.dim}, which "
                    f"collides with the enumeration dims (first_available_dim"
                    f"={self.first_available_dim}); pass a deeper "
                    "first_available_dim / max_plate_nesting",
                    code="RPL003", site=frame.name)
        # batch dims reaching into the enumeration region are fine exactly
        # when they *are* enumeration dims (the site's parameters depend on
        # another enumerated value); anything else is a plate-budget bug
        known = dict(self._extra_dims)
        known.update({dim: size for dim, size in self._alloc.values()})
        batch_shape = tuple(fn.batch_shape)
        for d in range(-len(batch_shape), self.first_available_dim + 1):
            if batch_shape[d] != 1 and known.get(d) != batch_shape[d]:
                raise ReproValueError(
                    f"site '{msg['name']}' has batch extent {batch_shape[d]} "
                    f"at dim {d}, inside the enumeration region "
                    f"(first_available_dim={self.first_available_dim}) but "
                    "matching no enumerated site — deepen "
                    "first_available_dim / max_plate_nesting",
                    code="RPL003", site=msg["name"])
        support = fn.enumerate_support(expand=False)
        size = support.shape[0]
        dim = self.allocate(size, msg["name"])
        msg["value"] = support.reshape((size,) + (1,) * (-dim - 1))
        msg["infer"]["_enumerate_dim"] = dim
        msg["infer"]["_enum_total"] = size


def _site_log_prob(site: dict):
    """Per-site log factor with the message-protocol contract applied:
    mask zeroes elements before the multiplicative scale.

    For an *enumerated* site, a masked-out element's factor is the
    normalized uniform ``-log K`` rather than 0: the later ``logsumexp``
    over its K enumerated values then contributes exactly 0 — the site
    drops out of the density, matching the non-enumerated mask contract
    (0-valued masked elements would each leak ``+log K`` into the
    marginal)."""
    lp = site["fn"].log_prob(site["value"])
    if site["mask"] is not None:
        d = site["infer"].get("_enumerate_dim")
        fill = -jnp.log(float(site["infer"]["_enum_total"])) \
            if d is not None else 0.0
        lp = jnp.where(site["mask"], lp, fill)
    if site["scale"] is not None:
        lp = lp * site["scale"]
    return lp


def _owns_plate(site_batch, p: int) -> bool:
    """Does the enumerated site with (plate-expanded) batch shape
    ``site_batch`` range over plate dim ``p``?"""
    return len(site_batch) >= -p and site_batch[p] != 1


def _reduce_foreign_plates(f, ds, d: int, alloc, boundary: int):
    """Sum out of factor ``f`` every plate dim that the enumerated variable
    ``d`` does *not* range over (and that no other enumeration dim still
    pending in ``ds`` owns) — log factors multiply independently across such
    plates, so they reduce by a plain sum *before* the logsumexp over ``d``.
    A plate dim ``d`` ranges over but ``f`` is constant across means the
    enumerated value escaped its plate: that joint is not representable with
    one enumeration dim, so fail loudly."""
    _, site_batch = alloc[d]
    sum_axes = []
    for p in range(boundary + 1, 0):
        if jnp.ndim(f) < -p:
            continue
        if _owns_plate(site_batch, p):
            if f.shape[p] == 1:
                raise NotImplementedError(
                    f"enumerated site at dim {d} is used outside its plate "
                    f"(a factor is constant across plate dim {p}); move the "
                    "dependent site inside the plate")
            continue
        if f.shape[p] != 1 and not any(
                d2 != d and _owns_plate(alloc[d2][1], p) for d2 in ds):
            sum_axes.append(p)
    if sum_axes:
        f = jnp.sum(f, axis=tuple(sum_axes), keepdims=True)
    return f


def _eliminate(factors, alloc, dims):
    """Variable elimination of ``dims`` (most-negative first) over the factor
    pool.  Returns ``(remaining_factors, const)`` where ``const`` accumulates
    the fully-contracted scalars.  Because elimination proceeds leftmost-dim
    first, removing an axis never shifts the (right-counted) positions of the
    dims still pending."""
    const = jnp.zeros(())
    factors = list(factors)
    for d in sorted(dims):
        group = [fd for fd in factors if d in fd[1]]
        if not group:
            continue
        factors = [fd for fd in factors if d not in fd[1]]
        boundary = max(alloc)
        f, ds = None, set()
        for g, gds in group:
            g = _reduce_foreign_plates(g, gds, d, alloc, boundary)
            f = g if f is None else f + g
            ds |= gds
        f = jax.nn.logsumexp(f, axis=d)
        ds.discard(d)
        if ds:
            factors.append((f, frozenset(ds)))
        else:
            const = const + jnp.sum(f)
    return factors, const


def _collect_enum_factors(tr):
    """Split a trace's sample sites into (alloc, enum factors, plain
    log-density sum).  ``alloc`` maps each enumeration dim to ``(support
    size, site batch shape)`` — the batch shape (plate-expanded) is what
    tells elimination which plate dims the enumerated variable ranges over.
    """
    alloc = {}
    for site in tr.values():
        if site["type"] != "sample":
            continue
        d = site["infer"].get("_enumerate_dim")
        if d is not None:
            alloc[d] = (site["infer"]["_enum_total"],
                        tuple(site["fn"].batch_shape))

    log_plain = jnp.zeros(())
    factors = []
    for site in tr.values():
        if site["type"] != "sample":
            continue
        lp = _site_log_prob(site)
        dims = set()
        for d, (size, _) in alloc.items():
            if jnp.ndim(lp) >= -d and lp.shape[d] != 1:
                if lp.shape[d] != size:
                    raise ValueError(
                        f"site '{site['name']}': log factor extent "
                        f"{lp.shape[d]} at enumeration dim {d} does not "
                        f"match the enumerated support size {size}")
                dims.add(d)
        if dims:
            factors.append((lp, frozenset(dims)))
        else:
            log_plain = log_plain + jnp.sum(lp)
    return alloc, factors, log_plain


def contract_enum_factors(tr):
    """Sum out every enumeration dim of a traced model by variable
    elimination, returning the scalar joint log density.

    Sites whose log factor mentions no enumeration dim accumulate directly
    (plate dims are independent products — a plain sum, as in the non-enum
    path).  Factors that do are eliminated one dim at a time, most-negative
    (latest-allocated, i.e. deepest in the program) first: each factor first
    sums out the plate dims the variable does not range over (independent
    products), then the group is broadcast-added and ``logsumexp``-contracted
    over the dim, and the resulting message re-enters the factor pool.
    """
    alloc, factors, log_joint = _collect_enum_factors(tr)
    leftover, const = _eliminate(factors, alloc, set(alloc))
    assert not leftover
    return log_joint + const


# ---------------------------------------------------------------------------
# markov: sequential elimination along a chain
# ---------------------------------------------------------------------------

class RequirePinnedDiscrete(Messenger):
    """Guard for utilities that score models without enumerating
    (``log_likelihood``): an enumerable discrete latent that nothing pinned
    and no rng key can reach would crash mid-trace — raise a diagnosis
    instead."""

    def __init__(self, fn=None, what: str = "this utility"):
        super().__init__(fn)
        self.what = what

    def process_message(self, msg: dict) -> None:
        if _is_enumerable_latent(msg) \
                and msg["kwargs"].get("rng_key") is None:
            raise NotImplementedError(
                f"{self.what}: discrete latent site '{msg['name']}' is "
                "marginalized by inference and absent from the posterior "
                "samples; pin it by including infer_discrete draws in "
                "posterior_samples")


class _RequireEnumerable(Messenger):
    """Guard for markov transition bodies: any latent site that cannot be
    enumerated has no business inside the per-step factor computation."""

    def process_message(self, msg: dict) -> None:
        if (msg["type"] == "sample" and not msg["is_observed"]
                and msg["value"] is None
                and not getattr(msg["fn"], "has_enumerate_support", False)):
            raise RuntimeError(
                f"latent site '{msg['name']}' inside a markov transition "
                "is neither observed nor enumerable; sample continuous "
                "latents outside the transition function")


def _find_enum_state():
    """Innermost enum-machinery handler on the stack (enum beats probe)."""
    for handler in reversed(primitives.stack()):
        if isinstance(handler, (enum, _EnumProbe)):
            return handler
    return None


def _enclosing_plates():
    """The plates open around a ``markov`` call, outermost first."""
    return [h for h in primitives.stack()
            if isinstance(h, _plate) and h._frame is not None]


class _StateOnRows(Messenger):
    """RPL014 for a state site inside a plate opened within the transition:
    summing that plate before the elimination would make all its elements
    share one state."""

    def __init__(self, row_dims, chain: str):
        super().__init__()
        self.row_dims = frozenset(row_dims)
        self.chain = chain

    def process_message(self, msg: dict) -> None:
        if not (msg["type"] == "sample" and not msg["is_observed"]
                and msg["value"] is None
                and getattr(msg["fn"], "has_enumerate_support", False)):
            return
        for frame in msg["cond_indep_stack"]:
            if frame.dim not in self.row_dims:
                raise ReproNotImplementedError(
                    f"markov '{self.chain}': state site '{msg['name']}' lies "
                    f"inside plate '{frame.name}', opened within the "
                    "transition, where all its elements would share one "
                    "state; open the plate around the markov call instead "
                    "(one chain per element) and keep only observed sites "
                    "in plates opened inside the transition",
                    code="RPL014", site=frame.name)


def _step_factors(tr, plate_budget: int, dims, rows, K: int, state: str):
    """Collapse one markov step's local trace into ``(unary, pair, keep)``.

    ``dims`` are the chain's enumeration dims (ascending: prev before
    cur), ``rows`` the ``(dim, size)`` of the plates around the call: each
    of their elements is a row, B in all (1 without any).  Plate dims
    opened within the step are summed, conditionally independent given
    the state; any other enumeration dim leaking in (a transition
    depending on a separately enumerated site) is a loud error.

    - ``unary`` (B, K): the sites that do not read the previous state
      (the emissions), each with its own mask;
    - ``pair``: the sites that do (the transition), ``(K, K)`` when no
      row varies them, else ``(B, K, K)``; None at the first step;
    - ``keep`` (B,): the state site's mask, all True without one.  From
      the second step on, a row whose state is masked leaves the step out
      (the caller holds its forward message), so the state's own factor
      is taken unmasked; at the first step a masked state scores the
      normalized ``-log K``, which sums to 0.
    """
    R = -min(dims)
    row_axes = [R + d for d, _ in rows]
    enum_axes = [R + d for d in dims]
    inner = [R + p for p in range(-plate_budget, 0)
             if R + p not in row_axes]
    B = math.prod(n for _, n in rows)

    def on_rows(x, axes, sizes):
        """``x`` broadcast to ``sizes`` at ``axes`` and moved there, in
        that order, all other axes dropped (they have extent 1)."""
        shape = [1] * R
        for a, n in zip(axes, sizes):
            shape[a] = n
        x = jnp.broadcast_to(jnp.reshape(x, (1,) * (R - jnp.ndim(x))
                                         + jnp.shape(x)), shape)
        x = jnp.transpose(x, list(axes)
                          + [a for a in range(R) if a not in axes])
        return x.reshape(tuple(sizes))

    unary = pair = jnp.zeros((1,) * R)
    keep = None
    for site in tr.values():
        if site["type"] != "sample":
            continue
        if site["name"] == state:
            keep = site["mask"]
        if site["name"] == state and len(dims) == 2:
            lp = site["fn"].log_prob(site["value"])
            if site["scale"] is not None:
                lp = lp * site["scale"]
        else:
            lp = _site_log_prob(site)
        if jnp.ndim(lp) > R:
            raise NotImplementedError(
                f"markov: the factor of site '{site['name']}' depends on "
                f"enumeration dim {-jnp.ndim(lp)} outside the chain; markov "
                "transitions may only depend on the previous state")
        lp = jnp.reshape(lp, (1,) * (R - jnp.ndim(lp)) + jnp.shape(lp))
        summed = tuple(a for a in inner if lp.shape[a] != 1)
        if summed:
            lp = jnp.sum(lp, axis=summed, keepdims=True)
        for ax in range(R):
            if lp.shape[ax] != 1 and ax not in enum_axes \
                    and ax not in row_axes:
                raise NotImplementedError(
                    f"markov: the factor of site '{site['name']}' depends on "
                    f"enumeration dim {ax - R} outside the chain; markov "
                    "transitions may only depend on the previous state")
        if len(dims) == 2 and lp.shape[enum_axes[0]] != 1:
            pair = pair + lp
        else:
            unary = unary + lp
    sizes = [n for _, n in rows]
    unary = on_rows(unary, row_axes + enum_axes[-1:], sizes + [K])
    unary = unary.reshape(B, K)
    keep = (jnp.ones((B,), bool) if keep is None
            else on_rows(keep, row_axes, sizes).reshape(B))
    if len(dims) == 1:
        return unary, None, keep
    if any(pair.shape[a] != 1 for a in row_axes):
        pair = on_rows(pair, row_axes + enum_axes, sizes + [K, K])
        pair = pair.reshape(B, K, K)
    else:
        pair = on_rows(pair, enum_axes, [K, K])
    return unary, pair, keep


def markov(fn, init, xs, *, name: str = "markov"):
    """Chain-structured sequential enumeration combinator.

    ``fn(carry, x) -> carry`` is one transition: it must contain exactly one
    enumerable latent sample site (the state, whose value it returns as the
    new carry); every other site inside must be observed.  ``xs`` is a pytree
    of arrays with a leading time axis of length T.

    Plates open around the call batch the chain: each of their elements (a
    row, e.g. one sequence of a corpus) has a state of its own, and all
    rows are eliminated together.  Plates opened inside ``fn`` may hold
    observed sites only (they are summed given the state); a state inside
    one raises ``RPL014``.  Where the state site's
    mask is False (e.g. ``t >= length`` of a padded sequence), that row
    leaves the step out: its state holds and its forward message passes
    unchanged, so a padded row scores exactly its unpadded length.

    Semantics depend on context:

    - plain simulation (``seed``/``trace``, no enumeration active): runs the
      transition T times under per-step :class:`~repro.core.handlers.scope`
      prefixes (``{name}/{t}/...``) and returns the stacked carries ``(T,
      ...)``;
    - enum-aware ``log_density``: computes per-step factors for all steps
      and rows at once (one ``vmap`` over time), split into those that read
      the previous state (``log p(z_t | z_{t-1})``, one (K, K) matrix for
      all rows where no row varies it) and those that do not (``log
      p(obs_t | z_t)``, (B, K)), eliminates the state along the time axis
      with :func:`repro.kernels.ops.enum_contract_chain` — the forward
      algorithm of every row in one call, O(T·B·K²), T-independent
      program, under the ``repro.markov`` scope — and contributes each
      row's marginal likelihood through one ``{name}_marginal`` factor
      site shaped like the enclosing plates (their ``scale``/``mask`` and
      outer handlers apply to it).  Returns ``None``: the carry must not be consumed
      downstream under marginalization;
    - :func:`infer_discrete`: forward-filters, backward-samples every row's
      state path, records it as a ``deterministic`` site named ``{name}``,
      and returns the sampled states, ``(T,)`` or ``(*plate sizes, T)``
      with masked steps marked ``-1`` (so downstream code runs on
      concrete draws).
    """
    leaves = jax.tree_util.tree_leaves(xs)
    if not leaves:
        raise ValueError("markov requires xs with at least one array leaf")
    T = jnp.shape(leaves[0])[0]
    if T == 0:
        raise ValueError("markov requires a non-empty time axis")

    handler = _find_enum_state()

    if handler is None:  # plain simulation
        carries = []
        carry = init
        for t in range(T):
            x_t = jax.tree_util.tree_map(lambda a: a[t], xs)
            with scope(prefix=f"{name}/{t}"):
                carry = fn(carry, x_t)
            carries.append(carry)
        return jax.tree_util.tree_map(lambda *v: jnp.stack(v), *carries)

    if getattr(handler, "_markov_local", False):
        raise ReproNotImplementedError(
            f"markov '{name}' is nested in another markov transition; "
            "nested chains are not supported (eliminate one chain whose "
            "state is the product of both)", code="RPL014", site=name)
    plates = _enclosing_plates()
    rows = tuple(sorted((p._frame.dim, p._frame.size) for p in plates))
    row_dims = [d for d, _ in rows]
    x0 = jax.tree_util.tree_map(lambda a: a[0], xs)

    if isinstance(handler, _EnumProbe):
        # measurement pass: run one step so within-step plates and the state
        # site are counted, then hand back a carry of the right structure
        handler.found = True
        with scope(prefix=f"{name}/probe"), config_enumerate(), \
                _RequireEnumerable(), _StateOnRows(row_dims, name):
            carry = fn(init, x0)
        return jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(jnp.asarray(v),
                                       (T,) + jnp.shape(v)), carry)

    plate_budget = -handler.first_available_dim - 1

    with jax.named_scope(SCOPES.markov):
        # --- step 0: discover the state site and its support --------------
        e0 = enum(first_available_dim=handler._next, strict=True)
        e0._markov_local = True
        with block(), trace() as tr0, e0, config_enumerate(), \
                _StateOnRows(row_dims, name):
            fn(init, x0)
        if len(e0._alloc) != 1:
            raise ValueError(
                f"markov '{name}': the transition must contain exactly one "
                "enumerable latent state site, found "
                f"{list(e0._alloc) or 'none'}")
        state_name, (d0, K) = next(iter(e0._alloc.items()))
        d_cur = handler.allocate(K, f"_markov/{name}/cur")
        assert d_cur == d0
        d_prev = handler.allocate(K, f"_markov/{name}/prev")
        support = tr0[state_name]["fn"].enumerate_support(expand=False)
        support_flat = support.reshape(-1)
        alpha0, _, keep0 = _step_factors(tr0, plate_budget, (d_cur,), rows,
                                         K, state_name)          # (B, K)

        # --- steps 1..T-1: factors, vectorized over time ------------------
        prev_value = support_flat.reshape((K,) + (1,) * (-d_prev - 1))
        e1 = enum(first_available_dim=d_cur, strict=True,
                  extra_dims={d_prev: K})
        e1._markov_local = True

        def step_factors(x_t):
            with block(), trace() as tr, e1, config_enumerate(), \
                    _StateOnRows(row_dims, name):
                fn(prev_value, x_t)
            (nm, (d, k)), = e1._alloc.items()
            if (d, k) != (d_cur, K) or nm != state_name:
                raise ValueError(
                    f"markov '{name}': transition structure changed "
                    f"between steps (state site '{state_name}' with {K} "
                    f"states became '{nm}' with {k})")
            return _step_factors(tr, plate_budget, (d_prev, d_cur), rows,
                                 K, state_name)

        # (T-1, B, K), (T-1, K, K) or (T-1, B, K, K), (T-1, B)
        steps = jax.vmap(step_factors)(
            jax.tree_util.tree_map(lambda a: a[1:], xs))

        from repro.kernels import ops

        if handler.mode == "marginal":
            B, pair = alpha0.shape[0], steps[1]
            if not ops.pallas_enabled("enum_contract_chain"):
                path = "reference"
            else:
                from repro.kernels.enum_contract import chain_route
                path = chain_route(T - 1, K, pair.ndim == 4)
            route = {"route": "plate_batched", "path": path, "rows": B,
                     "steps": T - 1, "states": K}

            # the forward algorithm of every row: alpha_t = logsumexp_i(
            # alpha_{t-1}[i] + pair_t[i, :]) + unary_t, a masked row holding
            per_row = ops.enum_contract_chain(alpha0, *steps)   # (B,)
        else:
            # --- mode == "sample": forward filter, backward sample --------
            def fwd(alpha, step):
                unary, pair, keep = step
                new = ops.enum_contract(alpha, pair) + unary
                new = jnp.where(keep[:, None], new, alpha)
                return new, new

            _, tail = lax.scan(fwd, alpha0, steps)
            alphas = jnp.concatenate([alpha0[None], tail], axis=0)  # (T,B,K)
            key_last, key_rest = random.split(handler.fresh_key())
            z_last = random.categorical(key_last, alphas[-1])       # (B,)
            _, pairs, keeps = steps

            def back(z_next, inp):
                # p(z_t | z_{t+1}) ~ alpha_t(z_t) pair_{t+1}(z_t, z_{t+1});
                # a held step (masked t+1) keeps the state
                alpha_t, pair_next, keep_next, k = inp
                col = (pair_next[:, z_next].T if pair_next.ndim == 2 else
                       jnp.take_along_axis(pair_next, z_next[:, None, None],
                                           axis=-1)[..., 0])         # (B, K)
                z = random.categorical(k, alpha_t + col)
                z = jnp.where(keep_next, z, z_next)
                return z, z

            _, zs = lax.scan(back, z_last,
                             (alphas[:-1], pairs, keeps,
                              random.split(key_rest, T - 1)), reverse=True)
            idx = jnp.concatenate([zs, z_last[None]], axis=0)       # (T, B)
            kept = jnp.concatenate([keep0[None], keeps], axis=0)
            states = jnp.where(kept, support_flat[idx],
                               jnp.asarray(-1, support_flat.dtype)).T

    row_sizes = tuple(n for _, n in rows)
    if handler.mode == "marginal":
        # one factor per row, at the enclosing plates' dims
        at_plates = [1] * (-row_dims[0] if rows else 0)
        for d, n in rows:
            at_plates[d] = n
        # the site carries the route the elimination took, fixed at trace
        # time (rows per chain); the executor reports it
        _sample(f"{name}_marginal",
                _dist.Delta(jnp.zeros(at_plates),
                            log_density=per_row.reshape(at_plates)),
                obs=jnp.zeros(at_plates), infer={"markov_route": route})
        return None
    states = states.reshape(row_sizes + (T,))
    _deterministic(name, states)
    handler.samples[name] = states
    return states


# ---------------------------------------------------------------------------
# infer_discrete: posterior of the marginalized sites
# ---------------------------------------------------------------------------

def _condition_factor(f, d: int, idx):
    """Index factor ``f`` at enumeration dim ``d`` by ``idx`` (the sampled
    per-plate-element state indices, right-aligned to the plate region)."""
    axis = jnp.ndim(f) + d
    want = jnp.ndim(f) - 1
    ie = jnp.reshape(idx, (1,) * max(0, want - jnp.ndim(idx))
                     + jnp.shape(idx)[max(0, jnp.ndim(idx) - want):])
    ie = jnp.expand_dims(ie, axis)
    ie = jnp.broadcast_to(ie, f.shape[:axis] + (1,) + f.shape[axis + 1:])
    return jnp.take_along_axis(f, ie, axis=axis)


def _sample_parallel_sites(tr, handler: enum, rng_key):
    """Exact sequential sampling of the parallel-enumerated sites: for each
    site (in allocation order), eliminate every *other* pending enumeration
    dim from a working copy of the factor pool, reduce foreign plates, and
    sample from the resulting per-plate-element conditional; then condition
    the pool on the draw (chain rule — exact joint posterior)."""
    sites = [(nm, dim, size) for nm, (dim, size) in handler._alloc.items()
             if nm in tr and tr[nm]["infer"].get("_enumerate_dim") == dim]
    if not sites:
        return {}

    alloc, factors, _ = _collect_enum_factors(tr)
    pending = {dim for _, dim, _ in sites}
    out = {}
    for nm, dim, size in sites:
        work, _ = _eliminate(factors, alloc, pending - {dim})
        boundary = max(alloc)
        f = None
        for g, gds in work:
            if dim not in gds:
                continue  # constant w.r.t. this site: normalization only
            g = _reduce_foreign_plates(g, {dim}, dim, alloc, boundary)
            f = g if f is None else f + g
        logits = jnp.moveaxis(f, jnp.ndim(f) + dim, -1)
        rng_key, sub = random.split(rng_key)
        idx = random.categorical(sub, logits)     # (..mine plates..,)
        factors = [(_condition_factor(g, dim, idx) if dim in gds else g,
                    gds - {dim}) for g, gds in factors]
        pending.discard(dim)
        # the recorded draw has the site's plate-region shape: batch extents
        # in the enumeration region come from *upstream* enumerated values
        # (parameters indexed by another enumerated site) and are not part
        # of a single draw
        width = -max(alloc) - 1
        site_batch = tuple(tr[nm]["fn"].batch_shape)
        target = site_batch[len(site_batch) - width:] if width else ()
        while target and target[0] == 1:
            target = target[1:]
        support_flat = tr[nm]["fn"].enumerate_support(expand=False).reshape(-1)
        out[nm] = support_flat[idx].reshape(target)
    return out


def infer_discrete(model, rng_key, *, max_plate_nesting: Optional[int] = None):
    """Sample the marginalized discrete latents from their exact posterior.

    Given a model whose continuous latents are pinned (compose with
    ``substitute(model, data=continuous_draw)``), returns a callable
    ``run(*model_args, **model_kwargs) -> {site: integer draws}``:
    parallel-enumerated sites are sampled by exact conditioning on the joint
    enumeration tensor, :func:`markov` chains by forward-filter /
    backward-sample.  Vectorize over posterior draws with ``jax.vmap`` over
    ``(draw, key)`` pairs.  Stray unpinned latent sites are seeded from
    ``rng_key`` (prior draws), mirroring ``Predictive``.
    """
    if rng_key is None:
        raise ValueError("infer_discrete requires an rng_key")

    def run(*args, **kwargs):
        k_seed, k_disc = random.split(rng_key)
        # auto-mark enumerable discrete latents, mirroring
        # initialize_model_structure: untouched model code just works
        marked = config_enumerate(model)
        probe = _EnumProbe(seed(marked, k_seed))
        trace(probe).get_trace(*args, **kwargs)
        if not probe.found:
            warnings.warn(
                "infer_discrete: the model has no enumerated sites (mark "
                "discrete latents with infer={'enumerate': 'parallel'} or "
                "wrap the model in config_enumerate)", stacklevel=2)
            return {}
        fad = _first_available_dim(probe, max_plate_nesting)
        handler = enum(seed(marked, k_seed), first_available_dim=fad,
                       mode="sample", rng_key=k_disc)
        with handler:
            tr = trace(handler.fn).get_trace(*args, **kwargs)
        samples = dict(handler.samples)
        samples.update(_sample_parallel_sites(tr, handler,
                                              handler.fresh_key()))
        return samples

    return run


__all__ = [
    "config_enumerate",
    "contract_enum_factors",
    "enum",
    "infer_discrete",
    "markov",
]
