"""Executor: lowers a whole program block to ONE jitted XLA computation.

This is the central idiomatic departure from the reference. The reference's
Executor is a per-op interpreter — it walks the block and dispatches a device
kernel per op (/root/reference/paddle/framework/executor.cc:73-129, hot loop
at :112-125), paying a host->device boundary per op. Here the entire block is
traced into a single pure JAX function and compiled once per (program,
shapes) signature; XLA fuses across op boundaries, keeps intermediates in
registers/VMEM, and overlaps collectives with compute. Feed variables become
function inputs; persistable state (parameters, optimizer accumulators) is
threaded functionally and donated so XLA can update buffers in place —
replacing the reference's in-place Scope mutation.

Run semantics match fluid's ``Executor.run`` feed/fetch contract
(/root/reference/python/paddle/v2/fluid/executor.py:112-168): only
persistable variables survive a run in the scope; intermediates must be
fetched.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import backward
from . import program as prog_mod
from .enforce import EnforceError, op_error
from .program import Program, RNG_VAR
from .registry import get_op, op_uses_rng
from .selected_rows import SelectedRows, densify
from .scope import Scope, global_scope
from .. import trace

logger = logging.getLogger("paddle_tpu")

# Sharding-invariant RNG: with the legacy (non-partitionable) threefry,
# XLA partitions a random op whose output lands sharded (GSPMD
# out_shardings — e.g. a vocab-sharded embedding table's uniform init)
# and produces DIFFERENT bits than the single-device run of the same
# program+seed. The partitionable implementation is invariant to
# sharding, which is the whole reproducibility contract of the one
# sharding plane: dp/tp runs must match their single-device reference.
jax.config.update("jax_threefry_partitionable", True)


class TPUPlace:
    """Device handle, analogue of platform::Place (place.h:53)."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def device(self):
        return jax.devices()[self.device_id]

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


class CPUPlace(TPUPlace):
    def device(self):
        cpus = [d for d in jax.devices() if d.platform == "cpu"]
        if cpus:
            return cpus[self.device_id]
        return jax.devices()[self.device_id]

    def __repr__(self):
        return f"CPUPlace({self.device_id})"


def _nonfinite_counts(value) -> Optional[Tuple[int, int]]:
    """(n_nan, n_inf) for float arrays, None for non-float / all-finite."""
    if isinstance(value, SelectedRows):
        value = value.values
    arr = np.asarray(value)
    if not np.issubdtype(arr.dtype, np.floating):
        return None
    n_nan = int(np.isnan(arr).sum())
    n_inf = int(np.isinf(arr).sum())
    return (n_nan, n_inf) if n_nan or n_inf else None


def _raise_nonfinite(name: str, n_nan: int, n_inf: int) -> None:
    raise FloatingPointError(
        f"variable {name!r} contains NaN/Inf "
        f"({n_nan} NaN, {n_inf} Inf); re-run with trace_level=2 "
        f"(or --trace_level=2) to locate the producing op")


def _check_nan_inf(name: str, value) -> None:
    bad = _nonfinite_counts(value)
    if bad is not None:
        _raise_nonfinite(name, bad[0], bad[1])


# On-device (n_nan, n_inf) reduction for the deferred check_nan_inf scan:
# written-back state is donated to the NEXT run_async dispatch, so the
# RunHandle must not hold the raw state arrays — it holds these two
# scalars per state instead (cheap, not donated, safe to read any time).
_nonfinite_count_kernel = jax.jit(
    lambda a: jnp.stack([jnp.isnan(a).sum(), jnp.isinf(a).sum()]))


def _device_nonfinite_counts(value):
    """Dispatch the non-finite count for a device array without any host
    sync; returns None for non-float values (nothing to check)."""
    if isinstance(value, SelectedRows):
        value = value.values
    if not np.issubdtype(np.dtype(value.dtype), np.floating):
        return None
    return _nonfinite_count_kernel(value)


def _value_stats(value) -> dict:
    """JSON-safe per-output stats for the interpret-mode op spans."""
    if isinstance(value, SelectedRows):
        value = value.values
    arr = np.asarray(value)
    out = {"shape": list(arr.shape), "dtype": str(arr.dtype)}
    if arr.size and np.issubdtype(arr.dtype, np.floating):
        finite = arr[np.isfinite(arr)]
        out["nonfinite"] = int(arr.size - finite.size)
        if finite.size:
            out["mean"] = float(finite.mean())
            out["absmax"] = float(np.abs(finite).max())
    return out


_cache_enabled = False


def _pc_enabled() -> bool:
    """Is a persistent (on-disk) compilation cache active? Covers the
    wiring below, ``$JAX_COMPILATION_CACHE_DIR`` and a jax config set by
    the embedding application."""
    return _cache_enabled or bool(jax.config.jax_compilation_cache_dir)


def reset_compilation_cache() -> None:
    """Unwire the persistent compilation cache (tests / re-pointing the
    dir mid-process): the next compile re-resolves
    ``xla_env.compilation_cache_dir()`` and re-initialises the cache
    there. A directory placed by ``$JAX_COMPILATION_CACHE_DIR`` is never
    cleared — the environment owns it."""
    global _cache_enabled
    import os

    from jax._src.compilation_cache import reset_cache

    from ..xla_env import CACHE_DIR_ENV

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", None)
    reset_cache()
    _cache_enabled = False


# ---------------------------------------------------------------------------
# Compile-source classification: fresh XLA compile vs persistent-cache
# (disk) restore vs in-process hit. jax announces disk restores through its
# monitoring plane; the events fire synchronously on the compiling thread,
# so a thread-local window around each .lower().compile() attributes them
# correctly even when manifest replay compiles on a thread pool.
# ---------------------------------------------------------------------------
_pc_local = threading.local()
_pc_listener_on = False
_PC_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_jax_compile_event(event, **_kw) -> None:
    window = getattr(_pc_local, "window", None)
    if window is not None and event == _PC_HIT_EVENT:
        window["persistent_hits"] += 1


def _ensure_cache_listener() -> None:
    global _pc_listener_on
    if _pc_listener_on:
        return
    _pc_listener_on = True
    from jax._src import monitoring

    monitoring.register_event_listener(_on_jax_compile_event)


@contextlib.contextmanager
def _compile_window():
    prev = getattr(_pc_local, "window", None)
    window = {"persistent_hits": 0}
    _pc_local.window = window
    try:
        yield window
    finally:
        _pc_local.window = prev


def _maybe_enable_compilation_cache() -> None:
    """Wire jax's persistent compilation cache (once per process) at the
    directory ``xla_env.compilation_cache_dir()`` resolves: repeat runs of
    the same program skip the first-compile latency entirely — the
    whole-block-compile design's answer to the reference's kernel warmup
    costs. Runs before the first compile (not at Executor construction),
    because the TPU default needs the backend's platform."""
    global _cache_enabled
    if _cache_enabled:
        return
    import os

    from ..xla_env import CACHE_DIR_ENV, compilation_cache_dir

    d = compilation_cache_dir()
    if not d:
        return
    # cache every compile, however small/fast: a startup block that
    # recompiles on the warm run breaks the zero-fresh-compile boot
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not os.environ.get(CACHE_DIR_ENV):
        # jax read the env var itself at import; only the flag / TPU
        # default route sets the directory here
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
        # jax initialises the persistent cache once, on the first
        # compile: if anything compiled before this point the dir change
        # would silently not take — drop the initialised cache so the
        # next compile re-inits at ``d``
        from jax._src.compilation_cache import reset_cache

        reset_cache()
    _cache_enabled = True


class _Compiled:
    """A compiled (program-block, signature) record.

    ``fn`` is the jitted callable; ``aot`` is its eagerly-compiled XLA
    executable (``.lower().compile()``), built under a classification
    window so ``source`` says whether it was a fresh compile or a
    persistent-cache (disk) restore. Every execution goes through
    ``aot``: an executable that rejects its arguments (aval, device or
    sharding drift) raises — there is no quiet jit re-dispatch."""

    __slots__ = ("fn", "raw_fn", "feed_names", "ro_state_names",
                 "rw_state_names", "out_state_names", "uses_rng",
                 "feed_shardings", "ro_shardings", "rw_shardings",
                 "paired_vjp_ops", "aot", "source")

    def __init__(self, fn, raw_fn, feed_names, ro_state_names, rw_state_names,
                 out_state_names, uses_rng, feed_shardings=None,
                 ro_shardings=None, rw_shardings=None, paired_vjp_ops=0):
        self.fn = fn
        self.raw_fn = raw_fn
        self.feed_names = feed_names
        self.ro_state_names = ro_state_names
        self.rw_state_names = rw_state_names
        self.out_state_names = out_state_names
        self.uses_rng = uses_rng
        self.feed_shardings = feed_shardings
        self.ro_shardings = ro_shardings
        self.rw_shardings = rw_shardings
        self.paired_vjp_ops = paired_vjp_ops
        self.aot = None
        self.source = None


class RunHandle:
    """Deferred result of :meth:`Executor.run_async`.

    Holds the fetched values as device arrays (jax's async dispatch means
    the computation may still be in flight) plus per-state non-finite
    COUNT scalars for deferred ``check_nan_inf`` — never the written-back
    state arrays themselves, which are donated to the next dispatch and
    deleted on platforms that honor donation. Nothing touches the host
    until :meth:`result` / :meth:`numpy`; the scope write-back already
    happened at dispatch time with device arrays, so consecutive
    dispatches chain on-device without a host round-trip.
    """

    __slots__ = ("fetch_names", "_fetches", "_state_checks", "_check",
                 "_dense", "__weakref__")  # weakref: serving drain registry

    def __init__(self, fetches, fetch_names, state_checks=(),
                 check_nan_inf=False):
        self._fetches = list(fetches)
        self.fetch_names = list(fetch_names)
        self._state_checks = list(state_checks)
        self._check = check_nan_inf
        self._dense = None

    def done(self) -> bool:
        """Non-blocking readiness poll (True for host-resident values)."""
        return all(v.is_ready() for v in self._fetches
                   if isinstance(v, jax.Array))

    def block(self) -> "RunHandle":
        """Wait for device completion without transferring to host."""
        for v in self._fetches:
            if isinstance(v, jax.Array):
                v.block_until_ready()
        return self

    def result(self, return_numpy: bool = True):
        """Resolve the run: blocks on the device values, applies the
        deferred ``check_nan_inf`` scan (fetches AND written-back state,
        the latter via the count scalars computed at dispatch), and
        returns the fetch list — numpy by default, device arrays with
        ``return_numpy=False``."""
        if self._dense is None:
            if self._check:
                for name, counts in self._state_checks:
                    c = np.asarray(counts)
                    if c[0] or c[1]:
                        _raise_nonfinite(name, int(c[0]), int(c[1]))
                for name, val in zip(self.fetch_names, self._fetches):
                    _check_nan_inf(name, val)
            self._dense = [densify(v) for v in self._fetches]
            self._state_checks = []
        if return_numpy:
            return [Executor._fetch_numpy(v) for v in self._dense]
        return list(self._dense)

    def numpy(self):
        return self.result(return_numpy=True)

    def __repr__(self):
        state = "done" if self.done() else "in-flight"
        return f"RunHandle({self.fetch_names}, {state})"


class Executor:
    """Compiles and runs Programs.

    ``check_nan_inf`` mirrors the reference's --check_nan_inf executor flag
    (executor.cc:25,116-124): after each run, fetched values and updated
    state are scanned for non-finite values on the host.
    """

    def __init__(self, place: Optional[TPUPlace] = None,
                 check_nan_inf: Optional[bool] = None, mesh=None, plan=None):
        """``mesh``/``plan`` enable SPMD execution: the whole block is jitted
        with jax.sharding annotations from the parallel.ShardingPlan and XLA
        GSPMD inserts the collectives — the in-graph replacement for the
        reference's pserver / NCCL / MultiGradientMachine paths (SURVEY.md
        §5.8). With a mesh and no plan, a pure data-parallel plan is used.
        """
        from ..flags import FLAGS

        _ensure_cache_listener()
        self.place = place or TPUPlace(0)
        self._device = None  # resolved lazily: see device()
        self.check_nan_inf = (FLAGS.check_nan_inf if check_nan_inf is None
                              else check_nan_inf)
        if mesh is None and plan is not None:
            mesh = plan.mesh  # Executor(plan=...) — the plan carries it
        self.mesh = mesh
        if mesh is not None and plan is None:
            from ..parallel import data_parallel_plan
            plan = data_parallel_plan(
                mesh, data_axis=mesh.axis_names[0])
        self.plan = plan
        self._cache: Dict[Tuple, _Compiled] = {}
        # Compile-cache observability (the serving warm-path contract:
        # after warmup a steady-state server shows hits only). Counts
        # in-process (program, signature) cache lookups; misses further
        # classify into persistent_hits (executable restored from the
        # persistent cache directory) vs fresh_compiles (paid XLA
        # compile) — the cold-vs-warm boot dimension.
        self.cache_hits = 0
        self.cache_misses = 0
        self.persistent_hits = 0
        self.fresh_compiles = 0
        # cumulative seconds inside ``.lower().compile()``, split by
        # source — the goodput plane's fresh_compile bucket deltas
        # fresh_compile_seconds around each run to re-attribute compile
        # wall out of device_compute
        self.compile_seconds = 0.0
        self.fresh_compile_seconds = 0.0
        from .manifest import SignatureManifest

        # every compiled signature is recorded here; engines/trainer
        # persist it next to the artifact for AOT replay on the next boot
        self.manifest = SignatureManifest()

    def cache_stats(self) -> Dict[str, int]:
        """{'hits', 'misses', 'entries', 'persistent_hits',
        'fresh_compiles', 'paired_vjp_ops'} of the (program, shapes) ->
        compiled-executable cache. ``misses`` split into disk restores
        (persistent_hits) and real compiles (fresh_compiles); a
        manifest+cache-warm boot shows fresh_compiles == 0.
        ``paired_vjp_ops`` counts, over the cached blocks, the
        loop-bearing forward ops traced once for forward and backward
        (backward.traced_once)."""
        return {"hits": self.cache_hits, "misses": self.cache_misses,
                "entries": len(self._cache),
                "persistent_hits": self.persistent_hits,
                "fresh_compiles": self.fresh_compiles,
                "paired_vjp_ops": sum(c.paired_vjp_ops
                                      for c in self._cache.values())}

    def device(self):
        """The device this executor computes on when no mesh is in play
        (``place.device()``, resolved once)."""
        if self._device is None:
            self._device = self.place.device()
        return self._device

    def device_ctx(self, program: Optional[Program] = None):
        """Context under which single-device work compiles, executes and
        allocates: ``jax.default_device(self.device())`` — uncommitted
        inputs (numpy feeds, fresh ``jnp`` state) land on THIS executor's
        device, so ``Executor(TPUPlace(i))`` computes on chip i. Mesh runs
        (the executor's own mesh, or ``program``'s sharding plan) place by
        sharding instead."""
        mesh = self.mesh if program is None \
            else self._mesh_plan_for(program)[0]
        if mesh is not None:
            return contextlib.nullcontext()
        return jax.default_device(self.device())

    # ------------------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        trace_level: Optional[int] = None,
    ):
        """``trace_level`` overrides the global trace level for this run:
        at >= 2 the block is NOT compiled — it executes op-by-op through
        the un-jitted kernel dispatch (``_run_interpreted``), recording a
        span per op with host time and output stats and naming the exact
        op/output var on NaN/Inf. None inherits ``trace.active_level()``
        (seeded from --trace_level)."""
        program = program or prog_mod.default_main_program()
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        scope = scope or global_scope()

        fetch_names = [f.name if hasattr(f, "name") else str(f) for f in fetch_list]
        block = program.global_block

        level = trace.active_level() if trace_level is None else trace_level
        interpret = level >= 2 and self._mesh_plan_for(program)[0] is None
        feed_vals, key, compiled = self._feed_and_lookup(
            program, feed, fetch_names, scope, interpret)
        if interpret:
            with self.device_ctx(program):
                return self._run_interpreted(program, feed_vals,
                                             fetch_names, scope,
                                             return_numpy)
        cache_hit = compiled is not None
        if compiled is None:
            self.cache_misses += 1
            with trace.span("executor/compile", cache="miss",
                            key=f"{hash(key) & 0xffffffff:08x}",
                            ops=len(block.ops), feeds=len(feed_vals),
                            fetches=len(fetch_names)) as csp:
                compiled = self._compile(program, feed_vals, fetch_names,
                                         scope)
                self._finish_compile(compiled, feed_vals, scope, program,
                                     csp)
            self._cache[key] = compiled
            self._record_signature(program, feed_vals, fetch_names)
        else:
            self.cache_hits += 1
        with trace.span("executor/run",
                        cache="hit" if cache_hit else "miss",
                        key=f"{hash(key) & 0xffffffff:08x}",
                        ops=len(block.ops)):
            return self._run_compiled(compiled, feed_vals, fetch_names,
                                      scope, program, return_numpy)

    # ------------------------------------------------------------------
    def run_async(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        trace_level: Optional[int] = None,
    ) -> RunHandle:
        """Dispatch a run WITHOUT any host synchronisation and return a
        :class:`RunHandle` of device arrays.

        jax's async dispatch does the overlap: the call returns as soon as
        the computation is enqueued; updated persistable state lands back
        in the scope as (possibly still in-flight) device arrays, so the
        next ``run_async`` chains on-device. ``check_nan_inf`` scans are
        deferred to ``handle.result()`` — the only point that touches the
        host. At trace level >= 2 the per-op interpret path runs eagerly
        and the handle comes back already resolved.
        """
        program = program or prog_mod.default_main_program()
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        scope = scope or global_scope()
        fetch_names = [f.name if hasattr(f, "name") else str(f)
                       for f in fetch_list]
        block = program.global_block

        level = trace.active_level() if trace_level is None else trace_level
        interpret = level >= 2 and self._mesh_plan_for(program)[0] is None
        feed_vals, key, compiled = self._feed_and_lookup(
            program, feed, fetch_names, scope, interpret)
        if interpret:
            with self.device_ctx(program):
                outs = self._run_interpreted(program, feed_vals,
                                             fetch_names, scope,
                                             return_numpy=False)
            return RunHandle(outs, fetch_names,
                             check_nan_inf=self.check_nan_inf)
        cache_hit = compiled is not None
        if compiled is None:
            self.cache_misses += 1
            with trace.span("executor/compile", cache="miss",
                            key=f"{hash(key) & 0xffffffff:08x}",
                            ops=len(block.ops), feeds=len(feed_vals),
                            fetches=len(fetch_names)) as csp:
                compiled = self._compile(program, feed_vals, fetch_names,
                                         scope)
                self._finish_compile(compiled, feed_vals, scope, program,
                                     csp)
            self._cache[key] = compiled
            self._record_signature(program, feed_vals, fetch_names)
        else:
            self.cache_hits += 1
        with trace.span("executor/dispatch",
                        cache="hit" if cache_hit else "miss",
                        key=f"{hash(key) & 0xffffffff:08x}",
                        ops=len(block.ops)):
            fetches, new_states, new_rng = self._call_compiled(
                compiled, feed_vals, scope, program)
            # Write-back of donated state WITHOUT materializing on host:
            # the scope holds the in-flight device arrays directly.
            if new_rng is not None:
                scope.set(RNG_VAR, new_rng)
            checks = []
            for name, val in zip(compiled.out_state_names, new_states):
                scope.set(name, val)
                if self.check_nan_inf:
                    # count non-finites on device NOW, while the array is
                    # still ours: a later dispatch donates it, so the
                    # handle may only keep these scalars
                    counts = _device_nonfinite_counts(val)
                    if counts is not None:
                        checks.append((name, counts))
        return RunHandle(fetches, fetch_names, state_checks=checks,
                         check_nan_inf=self.check_nan_inf)

    def _feed_and_lookup(self, program: Program, feed, fetch_names,
                         scope: Scope, interpret: bool):
        """``(feed_vals, key, compiled)`` of one call under ONE
        ``executor/feed`` span: the executor's host work before it
        touches the device. ``compiled`` is None on a miss; an
        interpreted run (``interpret``) needs the feeds alone."""
        with trace.span("executor/feed"):
            feed_vals = self._normalize_feeds(program.global_block, feed)
            if interpret:
                return feed_vals, None, None
            key = self._cache_key(program, feed_vals, fetch_names, scope)
            return feed_vals, key, self._cache.get(key)

    def _call_compiled(self, compiled: "_Compiled", feed_vals,
                       scope: Scope, program: Program):
        """Invoke the compiled executable (pure dispatch, no scope
        writes). Returns ``(fetches, new_states, new_rng_or_None)``.
        One ``executor/launch`` span: the state lookups, the
        host-to-device copy of the host feeds and the enqueue, as far as
        the call blocks for them."""
        with trace.span("executor/launch"):
            out = self._launch(compiled, feed_vals, scope, program)
        return self._unpack(compiled, out)

    def _launch(self, compiled: "_Compiled", feed_vals, scope: Scope,
                program: Program):
        feed_args = [feed_vals[n] for n in compiled.feed_names]
        ro_args = [scope.get(n) for n in compiled.ro_state_names]
        rw_args = [scope.get(n) for n in compiled.rw_state_names]
        if compiled.feed_shardings is not None:
            # device_put is a no-op when the array already has the target
            # sharding; otherwise it reshards (e.g. state initialised by a
            # single-device startup run). On a multi-process mesh (DCN
            # plane, parallel/multihost.py) host data destined for
            # non-addressable devices goes through make_array_from_callback
            # — every process provides the full array and keeps only its
            # local shards, the analogue of each reference trainer feeding
            # its slice of the global batch.
            with trace.span("executor/shard_feed", feeds=len(feed_args)):
                feed_args = [self._put(a, s) for a, s in
                             zip(feed_args, compiled.feed_shardings)]
            ro_args = [self._put(a, s)
                       for a, s in zip(ro_args, compiled.ro_shardings)]
            rw_args = [self._put(a, s)
                       for a, s in zip(rw_args, compiled.rw_shardings)]
        with self.device_ctx(program):
            rng = self._rng_state(program, scope) if compiled.uses_rng \
                else None
            if compiled.aot is None:
                # entry compiled lazily (as_function path)
                self._finish_compile(compiled, feed_vals, scope, program)
            tail = (rng,) if rng is not None else ()
            return compiled.aot(feed_args, ro_args, rw_args, *tail)

    @staticmethod
    def _unpack(compiled: "_Compiled", out):
        if compiled.uses_rng:
            fetches, new_states, new_rng = out
            return fetches, new_states, new_rng
        fetches, new_states = out
        return fetches, new_states, None

    # -- cold-start plane: AOT compile + source classification ------------
    @staticmethod
    def _aval_like(x):
        """Shape/dtype skeleton for AOT lowering (no data touched)."""
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x  # SelectedRows etc.: lower from the concrete value

    def _aval_args(self, compiled: "_Compiled", feed_vals, scope: Scope,
                   program: Program):
        feed_args = [self._aval_like(feed_vals[n])
                     for n in compiled.feed_names]
        ro_args = [self._aval_like(scope.get(n))
                   for n in compiled.ro_state_names]
        rw_args = [self._aval_like(scope.get(n))
                   for n in compiled.rw_state_names]
        args = (feed_args, ro_args, rw_args)
        if compiled.uses_rng:
            args = args + (self._aval_like(
                self._rng_state(program, scope)),)
        return args

    def _aot_compile(self, jitted, args) -> Tuple[Any, Dict[str, Any]]:
        """``.lower()`` then ``.compile()`` under a classification window;
        bumps the source counters and returns the executable with what
        the ``executor/compile`` span carries: ``restored`` (loaded from
        the persistent cache, not compiled), ``lower_s`` (tracing the
        block and lowering it to StableHLO) and ``compile_s`` (XLA's
        compile, or the cache load)."""
        from .. import profiler

        _maybe_enable_compilation_cache()
        t0 = time.perf_counter()
        with _compile_window() as window:
            lowered = jitted.lower(*args)
            t1 = time.perf_counter()
            executable = lowered.compile()
        t2 = time.perf_counter()
        dt = t2 - t0
        self.compile_seconds += dt
        restored = window["persistent_hits"] > 0
        if restored:
            self.persistent_hits += 1
            profiler.global_stat.add_count(
                "executor/compile_cache/persistent_hit", 1)
        else:
            self.fresh_compiles += 1
            self.fresh_compile_seconds += dt
            profiler.global_stat.add_count(
                "executor/compile_cache/fresh_compile", 1)
            profiler.global_stat.add("executor/fresh_compile", dt)
        return executable, {"restored": restored, "lower_s": t1 - t0,
                            "compile_s": t2 - t1}

    def _finish_compile(self, compiled: "_Compiled", feed_vals,
                        scope: Scope, program: Program, span=None) -> None:
        """Compile the entry's executable NOW (ahead of execution) and
        classify its source. A compile failure propagates: the jit
        dispatch it used to degrade to would hit the same compiler."""
        if compiled.aot is not None:
            return
        if compiled.feed_shardings is None:
            self._check_state_device(compiled, scope)
        with self.device_ctx(program):
            args = self._aval_args(compiled, feed_vals, scope, program)
            compiled.aot, how = self._aot_compile(compiled.fn, args)
        compiled.source = "persistent" if how["restored"] else "fresh"
        if span is not None:
            span.set_attrs(source=compiled.source,
                           paired_vjp_ops=compiled.paired_vjp_ops, **how)

    def _check_state_device(self, compiled: "_Compiled",
                            scope: Scope) -> None:
        """Single-device entries compile for ``self.device()``; state
        committed elsewhere would be rejected by the executable (or, if
        uncommitted, silently copied across on every call — read-only
        state is never written back). Checked once per entry, here."""
        want = {self.device()}
        for name in compiled.ro_state_names + compiled.rw_state_names:
            val = scope.get(name)
            if isinstance(val, jax.Array) and val.devices() != want:
                raise ValueError(
                    f"state variable {name!r} lives on "
                    f"{sorted(str(d) for d in val.devices())} but this "
                    f"executor ({self.place!r}) computes on "
                    f"{self.device()}; load or device_put it there (or run "
                    f"under the mesh/plan that sharded it)")

    def _record_signature(self, program: Program, feed_vals,
                          fetch_names) -> None:
        from . import manifest as manifest_mod

        feeds = [(n, tuple(int(d) for d in v.shape), str(np.dtype(v.dtype)))
                 for n, v in feed_vals.items()
                 if hasattr(v, "shape") and hasattr(v, "dtype")]
        self.manifest.record(manifest_mod.program_digest(program), feeds,
                             list(fetch_names))

    def warm_signature(self, program: Program, feeds: Dict[str, tuple],
                       fetch_names: Sequence[str],
                       scope: Optional[Scope] = None) -> bool:
        """AOT-compile one (program, feed-signature) into the in-process
        cache WITHOUT executing anything: ``.lower().compile()`` of the
        whole block from shape/dtype skeletons. ``feeds`` maps feed name
        -> (shape, dtype). Returns True when a new executable was
        compiled, False when the signature was already warm. This is the
        boot path behind manifest replay (core.manifest.replay /
        engine.warm_start / SGD.train resume): with a persistent cache
        the compile is a disk restore, and the first real request/step is
        a pure in-process hit."""
        import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 dtype names

        program = program or prog_mod.default_main_program()
        scope = scope or global_scope()
        block = program.global_block
        feed_vals = {
            name: jax.ShapeDtypeStruct(tuple(int(d) for d in shape),
                                       np.dtype(dtype))
            for name, (shape, dtype) in feeds.items()}
        fetch_names = list(fetch_names)
        if any(op_uses_rng(get_op(op.type), op.attrs) for op in block.ops):
            # seed the scope RNG plane BEFORE keying, so the scope key set
            # matches live traffic (the GenerationEngine.warmup contract)
            with self.device_ctx(program):
                self._rng_state(program, scope)
        key = self._cache_key(program, feed_vals, fetch_names, scope)
        compiled = self._cache.get(key)
        if compiled is not None:
            self._finish_compile(compiled, feed_vals, scope, program)
            return False
        self.cache_misses += 1
        with trace.span("executor/compile", cache="miss", mode="aot_warm",
                        key=f"{hash(key) & 0xffffffff:08x}",
                        ops=len(block.ops), feeds=len(feed_vals),
                        fetches=len(fetch_names)) as csp:
            compiled = self._compile(program, feed_vals, fetch_names, scope)
            self._finish_compile(compiled, feed_vals, scope, program, csp)
        self._cache[key] = compiled
        self._record_signature(program, feed_vals, fetch_names)
        return True

    def _run_compiled(self, compiled: "_Compiled", feed_vals, fetch_names,
                      scope: Scope, program: Program, return_numpy: bool):
        fetches, new_states, new_rng = self._call_compiled(
            compiled, feed_vals, scope, program)
        if new_rng is not None:
            scope.set(RNG_VAR, new_rng)
        for name, val in zip(compiled.out_state_names, new_states):
            if self.check_nan_inf:
                _check_nan_inf(name, val)
            scope.set(name, val)
        if self.check_nan_inf:
            for name, val in zip(fetch_names, fetches):
                _check_nan_inf(name, val)
        if not return_numpy:
            return list(fetches)
        # the wait for the device and the copy back
        with trace.span("executor/fetch"):
            return [self._fetch_numpy(densify(v)) for v in fetches]

    def _call_op(self, op, opdef, ins, env, rng, vjp_pairs,
                 program: Program, scope: Scope):
        """One op of a block, traced or eager -> (outs, rng). A forward op
        paired with a grad op in this block (``vjp_pairs``) runs once under
        ``jax.vjp`` and the grad op applies the closure kept in ``env``."""
        pair = vjp_pairs.get(op.attrs.get(backward.VJP_KEY_ATTR))
        if pair is not None:
            if op.type == "grad":
                return backward.grad_kept(op.attrs, ins, env), rng
            return backward.traced_once(op, ins, pair, env), rng
        if opdef.special:
            return opdef.fn(op.attrs, ins, executor=self, env=env, op=op,
                            program=program, scope=scope), rng
        if op_uses_rng(opdef, op.attrs):
            rng, sub = jax.random.split(rng)
            return opdef.fn(op.attrs, ins, rng=sub), rng
        if callable(opdef.needs_rng):
            return opdef.fn(op.attrs, ins, rng=None), rng
        return opdef.fn(op.attrs, ins), rng

    # ------------------------------------------------------------------
    def _run_interpreted(self, program: Program, feed_vals, fetch_names,
                         scope: Scope, return_numpy: bool = True):
        """Per-op debug execution (trace_level=2): walk the block and
        dispatch each kernel eagerly through the registry — the
        reference's per-op interpreter loop (executor.cc:112-125),
        deliberately revived for observability. Each op records a span
        with host wall time and output stats, and a non-finite output
        raises immediately naming the exact op, its callsite, and the
        output variable — upgrading --check_nan_inf's "a variable is
        bad" to a located diagnosis. Orders of magnitude slower than the
        compiled path; never use it for serving traffic."""
        block = program.global_block
        ops = list(block.ops)
        vjp_pairs = backward.vjp_pairs(ops)
        env: Dict[str, Any] = dict(feed_vals)
        state_read: set = set()
        rng = None
        uses_rng = any(op_uses_rng(get_op(op.type), op.attrs) for op in ops)
        if uses_rng:
            rng = self._rng_state(program, scope)
        with trace.span("executor/interpret", ops=len(ops),
                        feeds=len(feed_vals), fetches=len(fetch_names),
                        paired_vjp_ops=len(vjp_pairs)):
            for op_index, op in enumerate(ops):
                opdef = get_op(op.type)
                ins = {}
                for slot, names in op.inputs.items():
                    if not names:
                        continue
                    vals = []
                    for name in names:
                        if name in env:
                            vals.append(env[name])
                        elif scope.has(name):
                            state_read.add(name)
                            env[name] = scope.get(name)
                            vals.append(env[name])
                        else:
                            raise RuntimeError(
                                f"op {op.type!r} input {slot}={name!r} is "
                                f"neither a feed, produced by a prior op, "
                                f"nor present in the scope. Did you forget "
                                f"to run the startup program? "
                                f"(paddle_tpu.analysis.check_program / "
                                f"tools/proglint.py locate dangling "
                                f"inputs statically)")
                    ins[slot] = vals
                t0 = time.perf_counter()
                try:
                    outs, rng = self._call_op(op, opdef, ins, env, rng,
                                              vjp_pairs, program, scope)
                except EnforceError:
                    raise
                except Exception as exc:
                    raise op_error(op, op_index, ins, exc) from exc
                produced = []
                if outs:
                    for slot, names in op.outputs.items():
                        if slot not in outs:
                            continue
                        for name, val in zip(names, outs[slot]):
                            env[name] = val
                            produced.append((slot, name, val))
                # host time includes device completion: the stats readback
                # below blocks on the outputs, so the span closes after
                # the op's device work — per-op device-inclusive timing.
                stats = {name: _value_stats(val)
                         for _, name, val in produced}
                t1 = time.perf_counter()
                trace.record(
                    f"op/{op.type}", t0, t1,
                    parent=trace.current_span(), op_index=op_index,
                    callsite=op.attrs.get("_callsite"), outputs=stats)
                for slot, name, val in produced:
                    bad = _nonfinite_counts(val)
                    if bad is None:
                        continue
                    # NaN is never legitimate; Inf can be (top-k/beam
                    # masking emits -inf by design), so Inf-only outputs
                    # raise only under the strict --check_nan_inf mode.
                    if bad[0] == 0 and not self.check_nan_inf:
                        continue
                    site = op.attrs.get("_callsite")
                    raise FloatingPointError(
                        f"op #{op_index} {op.type!r}"
                        + (f" (created at {site})" if site else "")
                        + f" produced NaN/Inf in output {slot}="
                        f"{name!r}: {bad[0]} NaN, {bad[1]} Inf "
                        f"(inputs: "
                        + ", ".join(f"{s}={list(n)}" for s, n in
                                    op.inputs.items() if n)
                        + ")")
            # write-back contract matches the compiled path: persistable
            # outputs and state read from the scope land back in the scope
            for op in ops:
                for name in op.output_names():
                    if name not in env:
                        continue
                    is_persist = (block.has_var(name)
                                  and block.var(name).persistable)
                    if is_persist or name in state_read:
                        scope.set(name, env[name])
            if uses_rng:
                scope.set(RNG_VAR, rng)
            fetches = []
            for name in fetch_names:
                if name in env:
                    fetches.append(env[name])
                elif scope.has(name):
                    fetches.append(scope.get(name))
                else:
                    raise RuntimeError(
                        f"fetch variable {name!r} is never produced")
        if return_numpy:
            return [self._fetch_numpy(densify(v)) for v in fetches]
        return list(fetches)

    @staticmethod
    def _fetch_numpy(v):
        """np.asarray that also handles multi-process global arrays whose
        local shards cover the full value (replicated or intra-process
        sharded axes — the fetch contract on the DCN plane)."""
        if not isinstance(v, jax.Array) or v.is_fully_addressable:
            return np.asarray(v)
        out = np.zeros(v.shape, v.dtype)
        seen = np.zeros(v.shape, bool)
        for sh in v.addressable_shards:
            out[sh.index] = np.asarray(sh.data)
            seen[sh.index] = True
        if not seen.all():
            raise ValueError(
                "fetched value is not fully recoverable on this process; "
                "fetch replicated values or gather explicitly")
        return out

    # ------------------------------------------------------------------
    def as_function(self, program: Program, feed: Dict[str, Any],
                    fetch_list: Sequence, scope: Optional[Scope] = None):
        """Export a program block as a pure jittable function.

        Returns ``(fn, example_args)`` where ``fn(feed_args, ro_state,
        rw_state[, rng])`` is the untraced closure over the block (suitable
        for jax.jit / embedding in larger JAX programs) and ``example_args``
        are concrete arrays drawn from ``feed`` and the scope.
        """
        scope = scope or global_scope()
        feed_vals = self._normalize_feeds(program.global_block, feed)
        fetch_names = [f.name if hasattr(f, "name") else str(f)
                       for f in fetch_list]
        key = self._cache_key(program, feed_vals, fetch_names, scope)
        compiled = self._cache.get(key)
        if compiled is None:
            self.cache_misses += 1
            compiled = self._compile(program, feed_vals, fetch_names, scope)
            self._cache[key] = compiled
            self._record_signature(program, feed_vals, fetch_names)
        else:
            self.cache_hits += 1
        args = (
            [feed_vals[n] for n in compiled.feed_names],
            [scope.get(n) for n in compiled.ro_state_names],
            [scope.get(n) for n in compiled.rw_state_names],
        )
        if compiled.uses_rng:
            args = args + (self._rng_state(program, scope),)
        return compiled.raw_fn, args

    # ------------------------------------------------------------------
    @staticmethod
    def _put(a, sharding):
        if isinstance(a, jax.Array):
            # device_put reshards device arrays, including global->global
            # on a multi-process mesh (no-op when already right).
            return jax.device_put(a, sharding)
        if sharding.is_fully_addressable:
            return jax.device_put(a, sharding)
        arr = np.asarray(a)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    # ------------------------------------------------------------------
    @staticmethod
    def _normalize_feeds(block, feed: Dict[str, Any]) -> Dict[str, Any]:
        """Normalise feeds to device-dtype arrays. Feeds that are already
        device-resident jax.Arrays of the right dtype pass through without a
        host round-trip (on-device input pipelines depend on this)."""
        feed_vals = {}
        for name, value in feed.items():
            dtype = block.var(name).dtype if block.has_var(name) else None
            if isinstance(value, jax.Array) and (
                    dtype is None or value.dtype == dtype):
                feed_vals[name] = value
            else:
                feed_vals[name] = np.asarray(value, dtype=dtype)
        return feed_vals

    def _cache_key(self, program: Program, feed_vals, fetch_names,
                   scope: Scope) -> Tuple:
        from ..ops import common as ops_common

        feed_sig = tuple(sorted((n, v.shape, str(v.dtype))
                                for n, v in feed_vals.items()))
        # The data-flow classification depends on which names exist in the
        # scope (state inputs), so the set of scope keys is part of the key —
        # as are the global dtype policies (AMP / MXU precision) and the
        # mesh/plan, all of which change the traced computation. The key
        # set is memoized inside the Scope per key-set version: a training
        # step rewrites existing names, which does not bump the version,
        # so the steady-state path hashes a cached frozenset instead of
        # rebuilding an O(#params) set every run.
        scope_keys = scope.key_set() if hasattr(scope, "key_set") \
            else frozenset(self._all_scope_keys(scope))
        return (id(program), program.version, feed_sig, tuple(fetch_names),
                id(scope), scope_keys, ops_common.amp_enabled(),
                ops_common.mxu_precision(),
                self._sharding_key(program))

    # ------------------------------------------------------------------
    def _mesh_plan_for(self, program: Program):
        """(mesh, plan) for one program: the executor's own mesh/plan
        wins; otherwise a ShardProgram-annotated program
        (``program.sharding_plan`` over a real device mesh) makes ANY
        executor lower it sharded — the one-sharding-plane contract."""
        if self.mesh is not None:
            return self.mesh, self.plan
        plan = getattr(program, "sharding_plan", None)
        if plan is not None and getattr(plan.mesh, "devices", None) \
                is not None:
            return plan.mesh, plan
        return None, None

    def _sharding_key(self, program: Program):
        """Content key of the (mesh, plan) pair: mesh axes + device ids
        + the plan's rule digest. Two equivalent plans built
        independently (a fresh ``megatron_plan(mesh)`` per boot/request)
        key identically, so serving steady state stays at zero
        recompiles — ``id(plan)`` would thrash the cache."""
        mesh, plan = self._mesh_plan_for(program)
        if mesh is None:
            return None
        return (tuple(mesh.axis_names),
                tuple(int(s) for s in mesh.devices.shape),
                tuple(int(d.id) for d in mesh.devices.flat),
                plan.digest() if plan is not None else None)

    @staticmethod
    def _all_scope_keys(scope: Scope):
        s = scope
        while s is not None:
            yield from s.keys()
            s = s.parent

    def _rng_state(self, program: Program, scope: Scope):
        if not scope.has(RNG_VAR):
            from ..flags import FLAGS

            seed = (program.random_seed if program.random_seed is not None
                    else FLAGS.seed)
            scope.set(RNG_VAR, jax.random.PRNGKey(seed))
        return scope.get(RNG_VAR)

    def _compile(self, program: Program, feed_vals, fetch_names, scope: Scope) -> _Compiled:
        block = program.global_block
        feed_names = sorted(feed_vals)

        # Classify data flow: which op inputs come from the scope (state) and
        # which persistables get (re)written and must flow back out.
        produced = set(feed_names)
        state_names: List[str] = []
        state_set = set()
        written_persist: List[str] = []
        written_set = set()
        uses_rng = False
        for op in block.ops:
            opdef = get_op(op.type)
            if op_uses_rng(opdef, op.attrs):
                uses_rng = True
            for slot, names in op.inputs.items():
                for name in names:
                    if name in produced or name in state_set:
                        continue
                    if scope.has(name):
                        state_set.add(name)
                        state_names.append(name)
                    else:
                        raise RuntimeError(
                            f"op {op.type!r} input {slot}={name!r} is neither a feed, "
                            f"produced by a prior op, nor present in the scope. "
                            f"Did you forget to run the startup program? "
                            f"(paddle_tpu.analysis.check_program / "
                            f"tools/proglint.py locate dangling inputs "
                            f"statically)"
                        )
            for name in op.output_names():
                produced.add(name)
                is_persistable = block.has_var(name) and block.var(name).persistable
                if (is_persistable or name in state_set) and name not in written_set:
                    written_set.add(name)
                    written_persist.append(name)
        for name in fetch_names:
            if name not in produced and not scope.has(name):
                raise RuntimeError(f"fetch variable {name!r} is never produced")
        # Fetches resident only in the scope become state inputs.
        for name in fetch_names:
            if name not in produced and name not in state_set:
                state_set.add(name)
                state_names.append(name)

        # Split state inputs: written-back ones are donated to XLA (in-place
        # buffer update); read-only ones must NOT be donated or the arrays
        # still referenced by the scope would be invalidated.
        rw_state = [n for n in state_names if n in written_set]
        ro_state = [n for n in state_names if n not in written_set]

        ops = list(block.ops)
        vjp_pairs = backward.vjp_pairs(ops)
        mesh, plan = self._mesh_plan_for(program)

        def run_traced(feed_args, ro_args, rw_args, rng=None):
            from ..parallel.context import mesh_context

            with mesh_context(mesh, plan.data_axis if plan else None):
                return _run_body(feed_args, ro_args, rw_args, rng)

        def _run_body(feed_args, ro_args, rw_args, rng=None):
            env: Dict[str, jax.Array] = {}
            env.update(zip(feed_names, feed_args))
            env.update(zip(ro_state, ro_args))
            env.update(zip(rw_state, rw_args))
            for op_index, op in enumerate(ops):
                opdef = get_op(op.type)
                ins = {
                    slot: [env[n] for n in names]
                    for slot, names in op.inputs.items()
                    if names
                }
                try:
                    outs, rng = self._call_op(op, opdef, ins, env, rng,
                                              vjp_pairs, program, scope)
                except EnforceError:
                    raise  # already carries op context (nested blocks)
                except Exception as exc:
                    # CustomStackTrace analogue: report the failing op, its
                    # input signature, and the user line that created it.
                    raise op_error(op, op_index, ins, exc) from exc
                if outs:
                    for slot, names in op.outputs.items():
                        if slot not in outs:
                            continue
                        vals = outs[slot]
                        for name, val in zip(names, vals):
                            env[name] = val
            fetches = [env[n] for n in fetch_names]
            new_states = [env[n] for n in written_persist]
            if rng is None:
                return fetches, new_states
            return fetches, new_states, rng

        feed_sh = ro_sh = rw_sh = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.plan import spec_axes

            mesh_axes = set(mesh.axis_names)

            def _shape_of(name):
                v = block.var(name) if block.has_var(name) else None
                if v is not None and v.shape is not None:
                    return tuple(v.shape)
                val = scope.get(name) if scope.has(name) \
                    else feed_vals.get(name)
                try:
                    return tuple(np.shape(val))
                except Exception:  # SelectedRows-class pytrees
                    return None

            def _annotated(name):
                # a ShardProgram annotation wins over the plan rules —
                # but only when every axis it names exists on THIS mesh
                # (stale annotations from another plan never leak in)
                v = block.var(name) if block.has_var(name) else None
                sp = getattr(v, "sharding", None) if v is not None else None
                if sp is not None and all(ax in mesh_axes
                                          for ax in spec_axes(sp)):
                    return NamedSharding(mesh, sp)
                return None

            def _feed_sharding(name):
                sp = _annotated(name)
                if sp is not None:
                    return sp
                shape = _shape_of(name)
                return plan.feed_sharding(
                    name, len(shape) if shape is not None else 0)

            def _state_sharding(name):
                sp = _annotated(name)
                if sp is not None:
                    return sp
                shape = _shape_of(name)
                ndim = len(shape) if shape is not None else 0
                if shape is not None and any(int(d) < 0 for d in shape):
                    shape = None  # symbolic batch: no divisibility check
                return plan.state_sharding(name, ndim, shape=shape)

            feed_sh = [_feed_sharding(n) for n in feed_names]
            ro_sh = [_state_sharding(n) for n in ro_state]
            rw_sh = [_state_sharding(n) for n in rw_state]
            replicated = NamedSharding(mesh, PartitionSpec())
            in_shardings = (feed_sh, ro_sh, rw_sh)
            # written-back state must LAND with the plan's shardings (not
            # whatever GSPMD propagates — e.g. a ZeRO-sharded accumulator
            # feeding a momentum update would otherwise leak its dp
            # sharding into the updated parameter); fetches stay
            # unconstrained (None = compiler's choice)
            ws_sh = [_state_sharding(n) for n in written_persist]
            out_shardings = ([None] * len(fetch_names), ws_sh)
            if uses_rng:
                in_shardings = in_shardings + (replicated,)
                out_shardings = out_shardings + (replicated,)

            jitted = jax.jit(run_traced, donate_argnums=(2,),
                             in_shardings=in_shardings,
                             out_shardings=out_shardings)
        else:
            jitted = jax.jit(run_traced, donate_argnums=(2,))
        logger.debug(
            "compiled block: %d ops, %d feeds, %d state vars, %d outputs",
            len(ops), len(feed_names), len(state_names), len(fetch_names),
        )
        return _Compiled(jitted, run_traced, feed_names, ro_state, rw_state,
                         written_persist, uses_rng, feed_sh, ro_sh, rw_sh,
                         paired_vjp_ops=len(vjp_pairs))

    def close(self):
        self._cache.clear()
