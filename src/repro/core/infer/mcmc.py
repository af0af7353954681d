"""MCMC driver: one chunked multi-chain executor for every chain method.

Chains are always a batch: ``init_fn``/``sample_fn`` from the kernel's
:class:`~repro.core.infer.kernel_api.KernelSetup` are pure, so the executor
``vmap``s them over a leading ``(chains,)`` axis and runs the whole batch in
``ceil(T / checkpoint_every)`` compiled ``lax.scan`` chunks:

- ``vectorized`` — the batched program on one device (paper Sec 3.2);
- ``parallel``  — the *same* program with the chain axis sharded over a
  1-D ``chains`` mesh: thousands of chains spread over a pod with zero
  change to kernel code.  ``mesh_shape=(Sc, Sd)`` upgrades it to the 2-D
  ``("chains", "data")`` mesh: chains stay GSPMD-sharded on the first axis
  while a shard-aware potential (``KernelSetup.data_axis``, see
  ``docs/distributed.md``) evaluates its per-shard partial likelihoods
  under ``shard_map`` over the second — sample streams stay bit-identical
  across all three layouts because the fold structure is static;
- ``sequential`` — the same compiled batch-size-1 program invoked per
  chain (bounded memory), results stacked host-side.

Batch-aware kernels (``KernelSetup.cross_chain``, e.g. the ChEES-HMC
ensemble in :mod:`repro.core.infer.ensemble`) skip the executor's outer
``vmap``: their ``sample_fn`` maps the whole ensemble state, so cross-chain
reductions (pooled mass matrices, ensemble step-size adaptation) live
inside the kernel and become all-reduces over the ``chains`` mesh under
``chain_method="parallel"``.  Chunking, sharding and checkpoint/resume are
identical — ensemble adaptation state is just one more pytree in the
checkpoint.

Fault tolerance: ``run(..., checkpoint_every=k, checkpoint_dir=d)`` persists
the full chain state (``d/state``, overwritten) plus each completed chunk of
collected draws (``d/samples_<start>_<end>``, written once — total I/O stays
linear in chain length) through ``repro.distributed.checkpoint.save``, and
``run(..., resume=True)`` restores from ``latest_step`` and continues to
bit-identical final samples — chunk boundaries are a pure function of the
iteration count, so a resumed run replays the exact op sequence of an
uninterrupted one.
"""
from __future__ import annotations

import json
import os
import re
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, random

from .diagnostics import print_summary
from .hmc import HMC, HMCState  # noqa: F401  (re-exported legacy surface)
from .hmc_util import chain_vmap
from .kernel_api import KernelSetup

_SAMPLES_DIR_RE = re.compile(r"^samples_(\d+)_(\d+)$")


def _tree_concat(parts, axis=1):
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=axis), *parts)


def _metrics_chain_first(met):
    """Cross-chain metrics leave the scan as ``(draws,)`` pooled scalars or
    ``(draws, C)`` per-chain vectors; put the chain axis first on the
    latter so buffered per-chain series are ``(C, draws)`` like the collect
    path, while pooled series stay ``(draws,)``."""
    return jax.tree_util.tree_map(
        lambda x: jnp.swapaxes(x, 0, 1) if x.ndim >= 2 else x, met)


def _same_args(old, new):
    """True iff two (args, kwargs, init_params) bundles are structurally
    identical with every array leaf being the *same object* — the executor's
    closures capture argument values, so value identity (not just shape) is
    the safe cache condition."""
    old_leaves, old_def = jax.tree_util.tree_flatten(old)
    new_leaves, new_def = jax.tree_util.tree_flatten(new)
    if old_def != new_def or len(old_leaves) != len(new_leaves):
        return False
    for a, b in zip(old_leaves, new_leaves):
        if hasattr(a, "shape") or hasattr(b, "shape"):
            if a is not b:
                return False
        elif a != b:
            return False
    return True


class MCMC:
    def __init__(self, kernel, num_warmup: int, num_samples: int,
                 num_chains: int = 1, thinning: int = 1,
                 chain_method: str = "vectorized", progress: bool = False,
                 collect_fields=("z",), jit_model_args: bool = False,
                 validate: bool = False, mesh_shape=None, telemetry=None):
        self.kernel = kernel
        # telemetry=obs.Telemetry(...) streams kernel metrics (step size,
        # accept prob, divergences, ...) off-device at chunk boundaries,
        # times the executor's phases, and writes JSONL events + a run
        # manifest — without touching the sample stream (bit-identity with
        # telemetry on vs. off is tested) and without extra host syncs
        # beyond the one drain per compiled chunk (docs/observability.md)
        self.telemetry = telemetry
        # validate=True lints the kernel's model once per fresh setup (a
        # pure Python pre-compile pass; the warm sampling path is untouched)
        self.validate = bool(validate)
        self.num_warmup = int(num_warmup)
        self.num_samples = int(num_samples)
        self.num_chains = int(num_chains)
        self.thinning = int(thinning)
        if chain_method not in ("vectorized", "sequential", "parallel"):
            raise ValueError(f"unknown chain_method {chain_method}")
        self.chain_method = chain_method
        # 2-D (chains, data) inference mesh for chain_method="parallel":
        # chains stay GSPMD-sharded on the first axis (same compiled graph
        # as vectorized/1-D — the bit-identity invariant), a shard-aware
        # potential (KernelSetup.data_axis) evaluates data-parallel over the
        # second.  None keeps the legacy 1-D chains-only mesh.
        if mesh_shape is not None:
            if chain_method != "parallel":
                raise ValueError(
                    "mesh_shape is only meaningful with "
                    "chain_method='parallel'")
            mesh_shape = tuple(int(v) for v in mesh_shape)
            if len(mesh_shape) != 2:
                raise ValueError(
                    f"mesh_shape must be a (chains, data) pair, got "
                    f"{mesh_shape}")
        self.mesh_shape = mesh_shape
        self._mesh = None          # lazily built inference mesh
        self.progress = bool(progress)
        self._divergences = 0   # cumulative, reported by progress lines
        # convergence gating (run(..., until=Converged(...))): the monitor
        # folds drained sample chunks into streaming R-hat/ESS accumulators
        # and the chunk loop stops when the thresholds hold — see
        # repro.obs.monitor and docs/observability.md
        self.monitor = None     # per-run ConvergenceMonitor (or None)
        self._until = None
        self._reporter = None   # lazily-built default chunk reporter
        self._metrics_ok = set()  # setups whose metrics_fn passed RPL401/402
        self.collect_fields = collect_fields
        self._samples = None
        self._collected = None
        self._last_state = None
        self._setup_cache = None   # (args-bundle, num_warmup, KernelSetup)
        # compiled executors, keyed on (kind, setup, length).  Instance-level
        # (not a module-level jit) so dropping the MCMC object frees the
        # executables AND the datasets captured by the setup closures; keying
        # on the setup means reuse across models/arg-shapes can never replay
        # a stale executable — a different model or shape is a new setup.
        self._exec_cache = {}

    # -- compiled chunk programs ----------------------------------------------
    def _exec(self, kind, setup: KernelSetup, length=None, metrics=False):
        """Compiled chunk program for ``setup``.

        Per-chain kernels get the executor's batching (``vmap`` over the
        leading chain axis); batch-aware kernels (``setup.cross_chain``) are
        driven whole — their ``sample_fn`` already maps the full ensemble
        state, so the chunk is a plain ``lax.scan`` and cross-chain
        reductions inside the kernel stay visible to XLA (they become
        all-reduces under ``chain_method="parallel"``).  Collected draws come
        out as ``(chains, draws, ...)`` either way.

        ``metrics=True`` additionally threads ``setup.metrics_fn`` through
        the scan's *outputs* (never the carry — the transition chain is the
        identical op sequence, which is why the sample stream stays
        bit-identical): warmup chunks then return ``(state, metrics)``
        instead of ``state`` and sample chunks ``(state, (collect,
        metrics))``.  The flag is part of the cache key, so metrics-off
        programs are byte-for-byte the pre-telemetry ones and flipping
        telemetry on compiles *new* entries instead of recompiling any
        existing setup's warm path.
        """
        metrics = bool(metrics) and setup.metrics_fn is not None \
            and kind != "init"
        key = (kind, setup, length, self.mesh_shape, metrics)
        fn = self._exec_cache.get(key)
        tele = self.telemetry
        if fn is not None:
            if tele is not None:
                tele.counter("exec_cache_hit")
            return fn
        if tele is not None:
            tele.counter("exec_cache_miss")
        if kind == "init":
            if setup.cross_chain:
                prog = setup.init_fn
            else:
                prog = lambda keys: chain_vmap(setup.init_fn)(keys)  # noqa: E731
        elif kind == "warmup" and not metrics:
            def warm_scan(state):
                return lax.scan(lambda s, _: (setup.sample_fn(s), None),
                                state, None, length=length)[0]

            if setup.cross_chain:
                prog = warm_scan
            else:
                prog = lambda states: chain_vmap(warm_scan)(states)  # noqa: E731
        elif kind == "warmup":
            def warm_scan_m(state):
                def body(s, _):
                    s = setup.sample_fn(s)
                    return s, setup.metrics_fn(s)

                return lax.scan(body, state, None, length=length)

            if setup.cross_chain:
                def whole_warm(state):
                    state, met = warm_scan_m(state)
                    return state, _metrics_chain_first(met)

                prog = whole_warm
            else:
                prog = lambda states: chain_vmap(warm_scan_m)(states)  # noqa: E731
        elif kind == "sample" and not metrics:
            def body(s, _):
                s = setup.sample_fn(s)
                return s, setup.collect_fn(s)

            if setup.cross_chain:
                def whole(state):
                    state, out = lax.scan(body, state, None, length=length)
                    # scan stacks draws leftmost; put the chain axis first
                    out = jax.tree_util.tree_map(
                        lambda x: jnp.swapaxes(x, 0, 1), out)
                    return state, out

                prog = whole
            else:
                def one_sample(state):
                    return lax.scan(body, state, None, length=length)

                prog = lambda states: chain_vmap(one_sample)(states)  # noqa: E731
        elif kind == "sample":
            def body_m(s, _):
                s = setup.sample_fn(s)
                return s, (setup.collect_fn(s), setup.metrics_fn(s))

            if setup.cross_chain:
                def whole_m(state):
                    state, (out, met) = lax.scan(body_m, state, None,
                                                 length=length)
                    out = jax.tree_util.tree_map(
                        lambda x: jnp.swapaxes(x, 0, 1), out)
                    return state, (out, _metrics_chain_first(met))

                prog = whole_m
            else:
                def one_sample_m(state):
                    return lax.scan(body_m, state, None, length=length)

                prog = lambda states: chain_vmap(one_sample_m)(states)  # noqa: E731
        else:
            raise ValueError(kind)
        fn = jax.jit(self._with_mesh(setup, prog))
        self._exec_cache[key] = fn
        return fn

    def _with_mesh(self, setup, prog):
        """Activate the inference mesh for ``prog``'s trace when the kernel
        declares a data-shardable potential under ``chain_method="parallel"``.

        The ``with`` runs at trace time (inside the jitted callable), so the
        potential closure reads the mesh via
        ``repro.distributed.sharding.active_data_mesh`` while the program is
        being traced — the compiled executable is mesh-specialized but the
        KernelSetup stays mesh-agnostic and hashable.  Every parallel program
        is also traced under the mesh as JAX's abstract mesh: the kernel
        dispatch reads it, and keeps Pallas kernels (which the TPU compiler
        cannot partition) out of the partitioned program except inside the
        potential's ``shard_map`` body.
        """
        if self.chain_method != "parallel":
            return prog
        mesh = self._inference_mesh()
        partitioned = prog

        def prog(*args):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return partitioned(*args)

        if setup.data_axis is None or setup.data_axis not in mesh.axis_names:
            return prog  # legacy 1-D chains mesh: potential folds locally
        from repro.distributed.sharding import use_inference_mesh

        def with_mesh(*args):
            with use_inference_mesh(mesh, setup.data_axis):
                return prog(*args)

        return with_mesh

    def _span(self, name, **attrs):
        """Telemetry phase span, or an inert context when telemetry is off
        (yields a mutable attr dict either way)."""
        if self.telemetry is None:
            import contextlib
            return contextlib.nullcontext(dict(attrs))
        return self.telemetry.span(name, **attrs)

    # -- setup ---------------------------------------------------------------
    def _get_setup(self, rng_key, init_params, model_args,
                   model_kwargs) -> KernelSetup:
        bundle = (model_args, model_kwargs, init_params)
        if self._setup_cache is not None:
            old_bundle, old_warmup, old_setup = self._setup_cache
            if old_warmup == self.num_warmup and _same_args(old_bundle,
                                                            bundle):
                return old_setup
            # evict the replaced setup's executors: they pin compiled
            # programs plus the dataset captured by its closures
            self._exec_cache = {k: v for k, v in self._exec_cache.items()
                                if k[1] is not old_setup}
        with self._span("setup", validate=self.validate):
            if self.validate:
                self._validate_model(model_args, model_kwargs)
            setup = self.kernel.setup(rng_key, self.num_warmup,
                                      init_params=init_params,
                                      model_args=model_args,
                                      model_kwargs=model_kwargs)
        self._setup_cache = (bundle, self.num_warmup, setup)
        return setup

    def _check_metrics_contract(self, setup):
        """Eager pre-compile enforcement of the metrics-stream contract,
        once per setup: RPL401 (non-scalar/wrong-shape metric leaves would
        broadcast garbage into the buffered series) and RPL402 (a
        metrics_fn whose outputs depend on the state's rng key).  Pure
        tracing — ``jax.eval_shape``/``make_jaxpr`` only, zero FLOPs —
        and the same codes the lint rules in
        :mod:`repro.lint_rules.obs_rules` report statically."""
        if setup.metrics_fn is None or setup in self._metrics_ok:
            return
        from repro.lint_rules.obs_rules import verify_metrics_fn
        verify_metrics_fn(setup,
                          num_chains=self.num_chains).raise_if_errors()
        self._metrics_ok.add(setup)

    def _validate_model(self, model_args, model_kwargs):
        """Lint the kernel's model before building a fresh setup: errors
        raise with their ``RPL`` code, warnings surface as warnings.  Runs
        only on the cold path (a cached setup skips it entirely), so
        ``validate=True`` never touches the compiled sampling loop."""
        model = getattr(self.kernel, "model", None)
        if model is None:
            return  # potential_fn-only kernels have no model to lint
        from ..lint import lint_model
        result = lint_model(model, model_args, model_kwargs)
        for finding in result.warnings:
            warnings.warn(str(finding), stacklevel=3)
        result.raise_if_errors()

    def _inference_mesh(self):
        """The (cached) device mesh for ``chain_method="parallel"``:
        legacy 1-D ``("chains",)`` when ``mesh_shape`` is None, the 2-D
        ``("chains", "data")`` mesh otherwise (RPL301 if it doesn't fit —
        see :func:`repro.launch.mesh.make_inference_mesh`)."""
        if self._mesh is None:
            from repro.launch.mesh import make_inference_mesh
            self._mesh = make_inference_mesh(self.num_chains,
                                             self.mesh_shape)
        return self._mesh

    def _chains_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self._inference_mesh(),
                             PartitionSpec("chains"))

    def _shard_tree(self, tree):
        """Device-put a state/collected pytree for ``chain_method="parallel"``:
        leaves with a leading chain axis are sharded over the ``chains`` mesh,
        everything else (shared ensemble adaptation state, counters, the
        shared rng key of a cross-chain kernel) is replicated."""
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = self._chains_sharding()
        replicated = NamedSharding(sharding.mesh, PartitionSpec())

        def put(x):
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == self.num_chains:
                return jax.device_put(x, sharding)
            return jax.device_put(x, replicated)

        return jax.tree_util.tree_map(put, tree)

    # -- checkpoint/resume ----------------------------------------------------
    # Layout under checkpoint_dir:
    #   state/                     latest chain state, overwritten per chunk
    #   samples_<start>_<end>/     one immutable dir per completed sampling
    #                              chunk (iteration range, end-exclusive) —
    #                              append-only, so checkpoint I/O is linear
    #                              in chain length, not quadratic.
    # The state manifest's step advances only after the chunk's samples are
    # on disk; an orphaned samples dir from a crash between the two writes is
    # deterministically rewritten (same rng path) after resume.

    def _save_checkpoint(self, directory, states, done, chunk=None,
                         chunk_range=None):
        import shutil

        from repro.distributed import checkpoint as ckpt
        os.makedirs(directory, exist_ok=True)
        if chunk is not None:
            start, end = chunk_range
            # drop orphaned chunks at/after this start (abandoned futures
            # from a crash or a resume with a different checkpoint_every) —
            # keeps on-disk chunks non-overlapping and contiguous, so a
            # finished checkpoint is always restorable
            for name in os.listdir(directory):
                m = _SAMPLES_DIR_RE.match(name)
                if m and int(m.group(1)) >= start:
                    shutil.rmtree(os.path.join(directory, name))
            ckpt.save(chunk,
                      os.path.join(directory, f"samples_{start:06d}_{end:06d}"),
                      step=end)
        # mesh provenance is diagnostic only: arrays are saved in logical
        # (unsharded) layout, so restore is mesh-agnostic — an elastic
        # resume onto a different device count/mesh never consults these.
        # "divergences" persists the cumulative counter so a resumed run
        # continues it instead of resetting to 0 mid-run; "monitor" does the
        # same for the convergence accumulators of a gated run (sufficient
        # statistics only, a few (chains, dims) rows per completed batch),
        # so a resumed gated run re-hydrates them and reaches the identical
        # stopping iteration.
        extra = {"num_warmup": self.num_warmup,
                 "num_samples": self.num_samples,
                 "num_chains": self.num_chains,
                 "chain_method": self.chain_method,
                 "mesh_shape": (list(self.mesh_shape)
                                if self.mesh_shape else None),
                 "num_devices": len(jax.devices()),
                 "divergences": int(self._divergences)}
        if self.monitor is not None:
            extra["monitor"] = self.monitor.state_dict()
        ckpt.save({"chain_state": states}, os.path.join(directory, "state"),
                  step=done, extra=extra)

    def _restore_checkpoint(self, directory, setup, keys):
        """Returns (states, collected_or_None, done, extra) or None if no
        checkpoint exists yet."""
        from repro.distributed import checkpoint as ckpt
        state_dir = os.path.join(directory, "state")
        done = ckpt.latest_step(state_dir)
        if done is None:
            return None
        with open(os.path.join(state_dir, "manifest.json")) as f:
            extra = json.load(f)["extra"]
        for field in ("num_warmup", "num_samples", "num_chains"):
            if extra.get(field) != getattr(self, field):
                raise ValueError(
                    f"checkpoint at {directory} was written by a run with "
                    f"{field}={extra.get(field)}, this MCMC has "
                    f"{getattr(self, field)}")

        # abstract-trace the same compiled programs the executor runs, so
        # the skeleton matches per-chain and cross-chain kernels alike
        state_skel = jax.eval_shape(self._exec("init", setup), keys)
        tree, _, _ = ckpt.restore({"chain_state": state_skel}, state_dir)
        states = tree["chain_state"]

        # collected draws: restore every completed chunk up to `done`
        ranges = []
        for name in os.listdir(directory):
            m = _SAMPLES_DIR_RE.match(name)
            if m and int(m.group(2)) <= done:
                ranges.append((int(m.group(1)), int(m.group(2))))
        ranges.sort()
        expected_start = self.num_warmup
        parts, skel_cache = [], {}
        for start, end in ranges:
            if start != expected_start:
                raise ValueError(
                    f"checkpoint at {directory} is missing the sample chunk "
                    f"starting at iteration {expected_start}")
            length = end - start
            skel = skel_cache.get(length)
            if skel is None:
                # abstract-trace the chunk once per distinct length (at most
                # two: full chunk + remainder), not once per chunk dir
                skel = jax.eval_shape(self._exec("sample", setup, length),
                                      state_skel)[1]
                skel_cache[length] = skel
            part, _, _ = ckpt.restore(
                skel, os.path.join(directory, f"samples_{start:06d}_{end:06d}"))
            parts.append(part)
            expected_start = end
        if expected_start != max(done, self.num_warmup):
            raise ValueError(
                f"checkpoint at {directory} is missing sample chunks "
                f"covering iterations {expected_start}..{done}")
        collected = _tree_concat(parts) if parts else None
        return states, collected, done, extra

    # -- the executor ---------------------------------------------------------
    def _advance(self, setup, states, collected, done, *, checkpoint_every,
                 checkpoint_dir):
        """Advance a batch of chains from iteration ``done`` to the end in
        compiled chunks, checkpointing after each chunk.  Chunk boundaries
        depend only on (num_warmup, num_samples, checkpoint_every, done),
        so a resumed run replays the identical op sequence.

        Telemetry rides the chunk boundary: metrics stacked by the chunk
        program come off-device in one drain, spans time each chunk (the
        first span over a fresh program includes its compile) and the host
        work after it (``chunk_drain``), and the live reporter prints once
        per chunk.  None of it touches the carry, the collect path, or the
        checkpoint layout — ``self.telemetry = None`` runs the
        byte-identical pre-telemetry programs.
        """
        total = self.num_warmup + self._target_samples()
        # a convergence-gated run needs chunk boundaries to check at; an
        # explicit checkpoint_every wins (resume boundaries stay a pure
        # function of the geometry), else the gate cadence sets the chunk
        if checkpoint_every:
            chunk = int(checkpoint_every)
        elif self.monitor is not None:
            chunk = int(self.monitor.until.check_every)
        else:
            chunk = total
        tele = self.telemetry
        want_metrics = (tele is not None and tele.metrics
                        and setup.metrics_fn is not None)
        forens = getattr(tele, "forensics", None)
        # the cumulative divergence counter is maintained whenever anything
        # consumes it: progress lines, telemetry, or the checkpoint extra
        # (which is how it survives a kill/resume)
        count_div = (self.progress or tele is not None
                     or checkpoint_dir is not None)
        while done < total:
            # a resumed gated run whose previous session already reached its
            # stopping decision (killed between the decisive chunk's state
            # write and process exit) must not draw past it: the decision is
            # rehydrated from the checkpoint extra with the accumulators
            if (self.monitor is not None and self.monitor.decision is not None
                    and self.monitor.decision.get("reason") == "converged"):
                break
            out = met = None
            if done < self.num_warmup:
                phase = "warmup"
                n = min(chunk, self.num_warmup - done)
            else:
                phase = "sample"
                n = min(chunk, total - done)
            miss0 = tele.counters.get("exec_cache_miss", 0) \
                if tele is not None else 0
            prog = self._exec(phase, setup, n, metrics=want_metrics)
            cold = (tele is not None
                    and tele.counters.get("exec_cache_miss", 0) > miss0)
            with self._span(f"{phase}_chunk", phase=phase, start=done,
                            end=done + n, program_cold=cold):
                if phase == "warmup":
                    if want_metrics:
                        states, met = prog(states)
                    else:
                        states = prog(states)
                else:
                    if want_metrics:
                        states, (out, met) = prog(states)
                    else:
                        states, out = prog(states)
                    collected = out if collected is None else _tree_concat(
                        [collected, out])
                if tele is not None:
                    # close the span on finished device work, not dispatch
                    jax.block_until_ready(states)
            start, done = done, done + n
            # the host's work at the chunk boundary: drain, divergence
            # count and forensics, convergence fold, progress line
            with self._span("chunk_drain", phase=phase, start=start,
                            end=done):
                host_met = tele.drain_chunk(phase, start, done, met) \
                    if tele is not None else None
                delta_div = 0
                if count_div and out is not None and "diverging" in out:
                    if forens is not None:
                        # the mask fetch is the same chunk-boundary sync
                        # the plain counter pays; positions are fetched
                        # only for chunks with a divergent draw (see
                        # obs/divergences.py)
                        mask = jax.device_get(out["diverging"])
                        delta_div = int(np.sum(mask))
                        if delta_div:
                            forens.fold(start, out, mask, phase=phase)
                    else:
                        delta_div = int(jnp.sum(out["diverging"]))
                    self._divergences += delta_div
                    if tele is not None:
                        tele.record_divergences(self._divergences)
                # convergence gate: fold the drained chunk's positions into
                # the streaming accumulators and stop between chunks once
                # the thresholds hold.  Reads only the chunk's collect
                # outputs — never the carry — so the draws taken are
                # bit-identical with monitoring on or off; the one host
                # fetch rides the chunk boundary the drain/progress/
                # checkpoint already sync on.
                stop = False
                if self.monitor is not None and out is not None:
                    self.monitor.fold(jax.device_get(out["z"]))
                    stop = self.monitor.check(done - self.num_warmup)
                if self.progress:
                    self._reporter.chunk(
                        done=done, total=total, phase=phase,
                        num_chains=self.num_chains,
                        divergences=self._divergences, delta_div=delta_div,
                        metrics=host_met if host_met is not None else out,
                        convergence=(self.monitor.history[-1]
                                     if self.monitor is not None
                                     and self.monitor.history else None))
            if checkpoint_dir is not None:
                with self._span("checkpoint_write", step=done):
                    self._save_checkpoint(
                        checkpoint_dir, states, done, chunk=out,
                        chunk_range=((done - n, done)
                                     if out is not None else None))
            if stop:
                break
        return states, collected

    def _target_samples(self) -> int:
        """Post-warmup draw budget: ``until.max_samples`` when a gated run
        sets one (it may exceed ``num_samples`` — slow convergence is
        allowed to draw longer), else ``num_samples``."""
        if self._until is not None and self._until.max_samples is not None:
            return int(self._until.max_samples)
        return self.num_samples

    # -- public API ----------------------------------------------------------
    def run(self, rng_key, *model_args, init_params=None,
            checkpoint_every: Optional[int] = None,
            checkpoint_dir: Optional[str] = None, resume: bool = False,
            until=None, **model_kwargs):
        if resume and checkpoint_dir is None:
            raise ValueError("resume=True requires checkpoint_dir")
        self._until = until
        if until is not None:
            from repro.obs.monitor import Converged, ConvergenceMonitor
            if not isinstance(until, Converged):
                raise TypeError(
                    f"until must be an obs.Converged spec, got "
                    f"{type(until).__name__}")
            if self.chain_method == "sequential":
                raise ValueError(
                    "convergence gating requires a batched chain_method "
                    "('vectorized' or 'parallel'): sequential runs finish "
                    "one chain before the next starts, so cross-chain "
                    "R-hat cannot be streamed mid-run")
            # eager RPL403: an unsatisfiable stopping rule silently
            # degenerates into a fixed-length run that looks gated — reject
            # it before anything compiles (lint twin:
            # repro.lint_rules.obs_rules.verify_until)
            from repro.lint_rules.obs_rules import verify_until
            verify_until(until, num_samples=self.num_samples,
                         num_chains=self.num_chains).raise_if_errors()
            self.monitor = ConvergenceMonitor(until)
        else:
            self.monitor = None
        tele = self.telemetry
        if tele is not None and self.chain_method == "sequential":
            raise ValueError(
                "telemetry requires a batched chain_method ('vectorized' "
                "or 'parallel'): sequential runs re-enter the executor per "
                "chain, so there is no single chunk stream to instrument")
        if tele is not None:
            # open the sink/manifest before any span can fire; the
            # setup-derived fields land via commit_run_config below
            tele.begin_run(
                {"algo": type(self.kernel).__name__,
                 "kernel_setup_hash": "",
                 "num_warmup": self.num_warmup,
                 "num_samples": self.num_samples,
                 "num_chains": self.num_chains,
                 "chain_method": self.chain_method,
                 "mesh_shape": (list(self.mesh_shape) if self.mesh_shape
                                else None),
                 "thinning": self.thinning,
                 "until": (None if until is None else
                           {"max_rhat": until.max_rhat,
                            "min_ess": until.min_ess,
                            "max_samples": until.max_samples,
                            "check_every": until.check_every,
                            "batch_size": until.batch_size})},
                default_dir=checkpoint_dir, resume=resume)
        setup = self._get_setup(rng_key, init_params, model_args,
                                model_kwargs)
        if tele is not None:
            if tele.metrics and setup.metrics_fn is not None:
                self._check_metrics_contract(setup)
            tele.commit_run_config(
                algo=setup.algo,
                kernel_setup_hash=f"{hash(setup) & ((1 << 64) - 1):016x}")
        if self.chain_method == "parallel" and setup.data_axis is not None:
            # eager shard/mesh fit check — the same condition would raise
            # RPL303 mid-trace, this surfaces it before any compilation
            mesh = self._inference_mesh()
            shards = getattr(setup.potential_fn, "data_shards", None)
            if (setup.data_axis in mesh.axis_names and shards is not None
                    and shards % mesh.shape[setup.data_axis] != 0):
                from ..errors import ReproValueError
                raise ReproValueError(
                    f"potential has data_shards={shards} but the mesh data "
                    f"axis has {mesh.shape[setup.data_axis]} devices; pick "
                    "data_shards as a multiple of the data-axis size.",
                    code="RPL303")
        keys = random.split(rng_key, self.num_chains)
        self._divergences = 0
        if self.progress:
            if tele is not None:
                self._reporter = tele.reporter
            elif self._reporter is None:
                from repro.obs.report import LiveReporter
                self._reporter = LiveReporter()
            self._reporter.start(self.num_warmup + self.num_samples)

        if setup.cross_chain and self.chain_method == "sequential":
            raise ValueError(
                f"kernel {setup.algo!r} adapts across the chain batch; "
                "chain_method='sequential' would run each chain alone — "
                "use 'vectorized' or 'parallel'")
        if self.chain_method == "sequential":
            if checkpoint_every or checkpoint_dir:
                raise ValueError(
                    "checkpointing requires a batched chain_method "
                    "('vectorized' or 'parallel')")
            per_chain = []
            for k in keys:
                st = self._exec("init", setup)(k[None])
                st, out = self._advance(setup, st, None, 0,
                                        checkpoint_every=None,
                                        checkpoint_dir=None)
                per_chain.append((st, out))
            states = _tree_concat([s for s, _ in per_chain], axis=0)
            collected = _tree_concat([o for _, o in per_chain], axis=0)
        else:
            if self.chain_method == "parallel":
                keys = jax.device_put(keys, self._chains_sharding())

            restored = None
            if resume:
                with self._span("resume_restore"):
                    restored = self._restore_checkpoint(checkpoint_dir,
                                                        setup, keys)
            if restored is not None:
                states, collected, done, ck_extra = restored
                # continue the cumulative divergence counter across the
                # resume: the checkpoint extra persists it exactly; a
                # pre-telemetry checkpoint without the field falls back to
                # recounting the restored chunks
                prev_div = ck_extra.get("divergences")
                if prev_div is not None:
                    self._divergences = int(prev_div)
                elif collected is not None and "diverging" in collected:
                    self._divergences = int(jnp.sum(collected["diverging"]))
                # re-hydrate the convergence accumulators the same way the
                # divergence counter comes back: from the checkpoint extra
                # when the killed run was gated, else (a checkpoint from an
                # ungated run now resumed with until=) by re-folding the
                # restored draws — both land on the same accumulator state,
                # because folds depend only on the draw stream, not on how
                # it was chunked
                if self.monitor is not None:
                    mon_state = ck_extra.get("monitor")
                    if mon_state is not None:
                        self.monitor.load_state_dict(mon_state)
                    elif collected is not None:
                        self.monitor.fold(jax.device_get(collected["z"]))
                if tele is not None:
                    tele.set_resumed_at(done)
                    tele.record_divergences(self._divergences)
                if self.chain_method == "parallel":
                    states = self._shard_tree(states)
                    if collected is not None:
                        collected = self._shard_tree(collected)
            else:
                with self._span("init"):
                    states = self._exec("init", setup)(keys)
                    collected, done = None, 0

            states, collected = self._advance(
                setup, states, collected, done,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir)

        self._last_state = states
        self._collected = collected
        # constrained-space samples keyed by site name
        z = collected["z"]  # (chains, samples, D)
        drawn = int(z.shape[1])
        if self.monitor is not None and self.monitor.decision is None:
            # the gate never fired: the draw budget ran out unconverged
            self.monitor.exhausted(drawn)
        self._samples = jax.vmap(jax.vmap(setup.constrain_fn))(z)
        if not isinstance(self._samples, dict):
            self._samples = {"z": self._samples}
        if tele is not None:
            tele.record_divergences(self._divergences)
            forens = getattr(tele, "forensics", None)
            if forens is not None and forens.total > 0:
                # localization baseline: one host fetch of the collected
                # positions, paid only by runs that actually diverged
                forens.set_baseline(jax.device_get(z))
            final = {"done": self.num_warmup + drawn,
                     "divergences": int(self._divergences)}
            if self.monitor is not None:
                final["convergence"] = self.monitor.decision
            if tele.metrics and setup.metrics_fn is not None:
                final["metrics"] = tele.buffer.summary("sample")
            # the fused GLM gradient's route (glm._slab_value_and_grad) and
            # a markov elimination's (enum.markov), fixed when the chunk
            # programs were traced
            route = getattr(setup.potential_fn, "glm_route", None)
            if route:
                final["glm_route"] = dict(route)
            route = getattr(setup.potential_fn, "markov_route", None)
            if route:
                # vectorized chains put all their rows through each call
                final["markov_route"] = dict(route, rows=route["rows"] * (
                    self.num_chains if self.chain_method == "vectorized"
                    else 1))
            tele.finish_run(final)
        return self

    def get_samples(self, group_by_chain: bool = False):
        samples = self._samples
        if self.thinning > 1:
            samples = jax.tree_util.tree_map(
                lambda x: x[:, ::self.thinning], samples)
        if group_by_chain:
            return samples
        return jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), samples)

    def get_extra_fields(self, group_by_chain: bool = False):
        extra = {k: v for k, v in self._collected.items() if k != "z"}
        # keep extras aligned with get_samples: same thinning slice
        if self.thinning > 1:
            extra = jax.tree_util.tree_map(
                lambda x: x[:, ::self.thinning], extra)
        if group_by_chain:
            return extra
        return jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), extra)

    @property
    def last_state(self):
        return self._last_state

    def print_summary(self):
        return print_summary(self.get_samples(group_by_chain=True))
