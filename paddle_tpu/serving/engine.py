"""InferenceEngine: bucketed one-shot inference over a saved model.

The serving half of the whole-block-compile design: the executor compiles
one XLA computation per (program, feed-shape) signature, so a server that
pads every batch to a small set of batch-size (and optional seq-len)
buckets hits the compile cache on EVERY request after warmup — the
reference's per-op interpreter had per-op dispatch cost but no compile
cliff; here the cliff is real and bucketing is the contract that removes
it from the serving path.

Replica dispatch rides :mod:`paddle_tpu.parallel`: pass a ``mesh`` (e.g.
``make_mesh({"dp": n_local_devices})``) and every padded batch is sharded
across the devices by the data-parallel plan — XLA splits the batch, runs
the same weights per device, and the fetch gathers rows back. Without a
mesh, ``place`` pins the engine to one local device so several engines
can serve side by side (one replica per device, each with its own warm
cache).
"""
from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import trace
from ..core.executor import Executor, TPUPlace
from ..core.scope import Scope
from .errors import BadRequestError, EngineClosedError
from .metrics import MetricsRegistry

DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)


class PendingInference:
    """Deferred result of :meth:`InferenceEngine.run_async`: one or more
    in-flight padded chunk dispatches (core.executor.RunHandle). The
    engine's batch metrics (execute latency, occupancy) are observed at
    resolve time, covering dispatch->completion of the whole request."""

    def __init__(self, engine: "InferenceEngine", parts):
        self._engine = engine
        self._parts = parts  # [(RunHandle, bucket, rows, t0), ...]
        self._part_outs: List = [None] * len(parts)
        self._result = None

    def done(self) -> bool:
        return all(h.done() for h, _, _, _ in self._parts)

    def result(self) -> List[np.ndarray]:
        """Block until every chunk completes; returns the fetch list
        sliced back to the true batch. Each chunk resolves exactly once —
        a retry after one chunk's failure re-resolves only the failed
        chunks, so the batch metrics observe each chunk once."""
        if self._result is None:
            for i, (h, bucket, n, t0) in enumerate(self._parts):
                if self._part_outs[i] is None:
                    self._part_outs[i] = self._engine._resolve_padded(
                        h, bucket, n, t0)
            outs = self._part_outs
            if len(outs) == 1:
                self._result = outs[0]
            else:
                self._result = [
                    np.concatenate([o[i] for o in outs], axis=0)
                    for i in range(len(self._engine.fetch_names))]
        return self._result


def _round_buckets(buckets: Sequence[int], multiple: int) -> List[int]:
    """Round every bucket up to ``multiple`` (mesh data-parallel needs
    per-device batch divisibility) and dedup, keeping order."""
    return sorted({max(multiple, -(-int(b) // multiple) * multiple)
                   for b in buckets})


def load_param_arrays(source) -> Dict[str, object]:
    """``name -> array`` from any weight source a rolling update can
    publish: a resilience checkpoint directory (the trainer's
    ``CheckpointConfig`` output), a ``save_inference_model`` directory,
    a Scope, or a plain dict of arrays."""
    import os

    from ..core.scope import Scope

    if isinstance(source, dict):
        return dict(source)
    if isinstance(source, Scope):
        return {k: source.get(k) for k in source.keys()}
    dirname = str(source)
    from .. import checkpoint as ckpt_mod

    if os.path.exists(os.path.join(dirname, ckpt_mod.META_NAME)):
        staging = Scope()
        ckpt_mod.load_checkpoint(dirname, scope=staging)
        return {k: staging.get(k) for k in staging.keys()}
    if os.path.exists(os.path.join(dirname, "params", "MANIFEST.json")):
        from ..io import _load_saved_params

        staging = _load_saved_params(dirname)
        return {k: staging.get(k) for k in staging.keys()}
    raise ValueError(
        f"{dirname!r} is neither a checkpoint directory "
        f"({ckpt_mod.META_NAME}) nor a saved inference model "
        f"(params/MANIFEST.json)")


def swap_scope_params(scope, source, *, skip=(), strict: bool = True,
                      device_ctx=None, metrics=None) -> Dict[str, int]:
    """Hot-swap parameter values in a live serving scope.

    Every value whose name exists in both ``source`` and ``scope`` is
    replaced, but ONLY when shape and dtype match exactly — the compile
    caches key on the scope's key set and the program signatures, so a
    same-shape swap costs zero recompiles, and a mismatch (which WOULD
    silently retrace every warm executable) raises instead of degrading
    (``strict=False`` skips mismatches). Donation-safe: old arrays stay
    alive for any outstanding RunHandle that captured them at dispatch;
    new values are fresh device buffers.

    Returns counters: swapped / skipped (not in scope, or in ``skip``) /
    mismatched (strict=False only) / kept (scope keys the source lacks).
    """
    import contextlib

    from ..core.program import RNG_VAR

    skip = set(skip) | {RNG_VAR}
    new = load_param_arrays(source)
    scope_keys = set(scope.keys())
    staged = []
    stats = {"swapped": 0, "skipped": 0, "mismatched": 0, "kept": 0}
    for name in sorted(new):
        if name in skip or name not in scope_keys:
            stats["skipped"] += 1
            continue
        old = scope.get(name)
        arr = new[name]
        old_sig = (tuple(np.shape(old)), str(getattr(old, "dtype", "?")))
        new_sig = (tuple(np.shape(arr)), str(getattr(arr, "dtype", "?")))
        if old_sig != new_sig:
            if strict:
                raise ValueError(
                    f"swap_params: {name!r} is {new_sig} in the source "
                    f"but {old_sig} live — a mismatched swap would "
                    f"retrace every warm executable; publish a "
                    f"same-architecture checkpoint (or pass "
                    f"strict=False to skip)")
            stats["mismatched"] += 1
            continue
        staged.append((name, arr))
    if not staged and strict:
        raise ValueError(
            "swap_params: the source shares no parameter names with the "
            f"live scope (source has {sorted(new)[:5]}..., scope has "
            f"{sorted(scope_keys)[:5]}...) — wrong artifact? (pass "
            "strict=False to no-op)")
    stats["kept"] = len(scope_keys - skip - {n for n, _ in staged})
    # stage fully, then install: a half-applied swap (mid-list error)
    # must not leave the scope serving a chimera of old and new weights
    import jax

    with (device_ctx() if device_ctx is not None
          else contextlib.nullcontext()):
        staged = [(name, jax.device_put(np.asarray(arr)))
                  for name, arr in staged]
    for name, arr in staged:
        scope.set(name, arr)
    stats["swapped"] = len(staged)
    if metrics is not None:
        metrics.inc("param_swaps")
        metrics.set_gauge("param_swap/last_swapped", stats["swapped"])
    return stats


class InferenceEngine:
    """Loads a saved inference model and serves padded-bucket batches.

    Construct from a ``save_inference_model`` directory (``model_dir``)
    or from an already-built (program, feed_names, fetch_names, scope).
    """

    def __init__(self, model_dir: Optional[str] = None, *,
                 program=None, feed_names=None, fetch_names=None,
                 scope: Optional[Scope] = None,
                 batch_buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS,
                 seq_buckets: Optional[Sequence[int]] = None,
                 mesh=None, plan=None, place=None,
                 metrics: Optional[MetricsRegistry] = None,
                 transpile: Optional[bool] = None,
                 mem_budget: Optional[float] = None):
        self.metrics = metrics or MetricsRegistry()
        self.scope = scope or Scope()
        self.model_dir = model_dir  # manifest home (save_manifest/warm_start)
        if mesh is None and plan is not None:
            mesh = plan.mesh  # InferenceEngine(plan=...) — plan carries it
        self.mesh = mesh
        if mesh is not None and plan is None:
            from ..parallel import data_parallel_plan
            plan = data_parallel_plan(mesh, data_axis=mesh.axis_names[0])
        self.executor = Executor(place or TPUPlace(0), mesh=mesh, plan=plan)
        if model_dir is not None:
            from ..io import load_inference_model
            program, feed_names, fetch_names = load_inference_model(
                model_dir, self.executor, scope=self.scope)
        if program is None or not feed_names or not fetch_names:
            raise ValueError("need model_dir or (program, feed_names, "
                             "fetch_names)")
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        # Transpile before warmup (default only for models we own the copy
        # of, i.e. loaded from disk): the inference pipeline re-runs — a
        # no-op on already-transpiled artifacts, the full rewrite set on
        # raw ones — and its per-pass stats land in the MetricsRegistry.
        if transpile is None:
            transpile = model_dir is not None
        if transpile:
            from ..transpiler import inference_pipeline

            pm = inference_pipeline()
            self.program = pm.run(self.program.clone(), self.feed_names,
                                  self.fetch_names, scope=self.scope,
                                  preserve_state_writes=True)
            for k, v in pm.metrics_dict().items():
                self.metrics.set_gauge(k, v)
        if plan is not None:
            # one sharding plane: annotate the served program's vars with
            # the plan's PartitionSpecs (ShardProgram pass) so lowering,
            # verification, and the memory analysis all read the same
            # per-var specs the executor jits with
            from ..transpiler import shard_program

            shard_program(self.program, plan, self.feed_names,
                          self.fetch_names, scope=self.scope)
        from ..flags import FLAGS

        if FLAGS.verify_program:
            # verify the program actually served (transpiled or raw)
            # before warmup compiles it — a corrupted artifact fails here
            # with op/slot context instead of mid-warmup
            from .. import analysis

            analysis.check_program(self.program, self.feed_names,
                                   self.fetch_names, scope=self.scope,
                                   annotate=False)
        if mesh is not None:
            dp = int(np.prod(mesh.devices.shape))
            batch_buckets = _round_buckets(batch_buckets, dp)
        self.batch_buckets = sorted(set(int(b) for b in batch_buckets))
        self.seq_buckets = (sorted(set(int(s) for s in seq_buckets))
                            if seq_buckets else None)
        if mem_budget is not None:
            # build-time gate at the WORST bucket (largest batch the
            # warmup will compile): a model that cannot fit raises a
            # located MemoryBudgetError here, before any compile/OOM
            from .. import analysis

            mem = analysis.check_memory_budget(
                self.program, self.feed_names, self.fetch_names,
                mem_budget, scope=self.scope,
                batch_size=self.batch_buckets[-1],
                what=f"InferenceEngine (bucket "
                     f"{self.batch_buckets[-1]})", plan=plan)
            self.metrics.set_gauge("mem/static_peak_bytes",
                                   mem.peak_bytes)
            self.metrics.set_gauge("mem/resident_bytes",
                                   mem.resident_bytes)
        # graceful-drain state: admissions stop at close(). Synchronous
        # runs in other threads are counted; async dispatches register
        # their RunHandles so close(drain=True) can block on DEVICE
        # completion (never on host-side result(), which only the caller
        # may trigger — waiting for it here would deadlock the closer).
        self._closed = False
        self._released = False
        self._inflight = 0
        self._inflight_cond = threading.Condition()
        self._outstanding: "weakref.WeakSet" = weakref.WeakSet()
        # flight recorder: live engine state rides every crash/SIGUSR1/
        # admin dump (weak registration — never keeps the engine alive)
        from ..trace import flight as trace_flight

        trace_flight.get_recorder().add_source(type(self).__name__,
                                               self.flight_state)

    # ------------------------------------------------------------------
    def flight_state(self) -> dict:
        """Live state for the flight recorder bundle."""
        return {
            "engine": type(self).__name__,
            "closed": self._closed,
            "inflight": self._inflight,
            "batch_buckets": list(self.batch_buckets),
            "feed_names": list(self.feed_names),
            "cache_stats": dict(self.cache_stats()),
        }

    # ------------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    def _feed_template(self, name: str):
        block = self.program.global_block
        if not block.has_var(name):
            return None, None
        v = block.var(name)
        return list(v.shape or []), v.dtype

    # ------------------------------------------------------------------
    def _validated_arrays(self, feed: Dict[str, np.ndarray]):
        missing = [n for n in self.feed_names if n not in feed]
        if missing:
            raise BadRequestError(f"missing feeds: {missing}")
        arrays = {n: np.asarray(feed[n]) for n in self.feed_names}
        ns = {n: a.shape[0] for n, a in arrays.items()}
        if len(set(ns.values())) != 1:
            raise BadRequestError(f"inconsistent batch sizes: {ns}")
        n = next(iter(ns.values()))
        if n == 0:
            raise BadRequestError("empty batch")
        return arrays, n

    def _admit(self):
        if self._closed:
            raise EngineClosedError(
                "engine is closed (draining or released); no new batches")

    def _track(self, delta: int) -> None:
        with self._inflight_cond:
            self._inflight += delta
            if delta < 0:
                self._inflight_cond.notify_all()

    def run(self, feed: Dict[str, np.ndarray]) -> List[np.ndarray]:
        """Execute one user batch: pads the leading dim to the nearest
        bucket (chunking batches beyond the largest), runs the compiled
        program, and returns the fetches sliced back to the true batch.
        Assumes every feed and fetch carries the batch on axis 0 — the
        save_inference_model feed contract."""
        self._admit()
        arrays, n = self._validated_arrays(feed)
        outs: List[List[np.ndarray]] = []
        start = 0
        while start < n:
            chunk = min(n - start, self.batch_buckets[-1])
            outs.append(self._run_padded(
                {k: a[start:start + chunk] for k, a in arrays.items()},
                chunk))
            start += chunk
        if len(outs) == 1:
            return outs[0]
        return [np.concatenate([o[i] for o in outs], axis=0)
                for i in range(len(self.fetch_names))]

    def run_async(self, feed: Dict[str, np.ndarray]) -> PendingInference:
        """Non-blocking :meth:`run`: dispatches every padded chunk via
        ``Executor.run_async`` and returns a :class:`PendingInference`
        handle. The batcher uses this to pipeline consecutive buckets —
        bucket k+1's padding/stacking and dispatch overlap bucket k's
        device execution — and ``serve_step`` resolves in dispatch
        order."""
        self._admit()
        arrays, n = self._validated_arrays(feed)
        parts = []
        start = 0
        while start < n:
            chunk = min(n - start, self.batch_buckets[-1])
            parts.append(self._dispatch_padded(
                {k: a[start:start + chunk] for k, a in arrays.items()},
                chunk))
            start += chunk
        return PendingInference(self, parts)

    def _pad_feed(self, arrays: Dict[str, np.ndarray], n: int):
        bucket = self.bucket_for(n)
        pad = bucket - n
        fed = {}
        for name, a in arrays.items():
            if pad:
                # replicate the last row: numerically safe for any model
                # (an all-zeros row can hit log/div landmines)
                a = np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
            fed[name] = a
        return fed, bucket

    def _dispatch_padded(self, arrays: Dict[str, np.ndarray], n: int):
        fed, bucket = self._pad_feed(arrays, n)
        t0 = time.perf_counter()
        with trace.span("serving/dispatch_batch", bucket=bucket, rows=n):
            handle = self.executor.run_async(
                self.program, feed=fed, fetch_list=self.fetch_names,
                scope=self.scope)
        self._outstanding.add(handle)
        return handle, bucket, n, t0

    def _resolve_padded(self, handle, bucket: int, n: int, t0: float):
        with trace.span("serving/resolve_batch", bucket=bucket, rows=n):
            res = handle.result()
        self.metrics.observe_latency(
            time.perf_counter() - t0, name="batch_execute")
        self.metrics.inc("batches_executed")
        self.metrics.set_gauge("batch_occupancy", n / bucket)
        return [np.asarray(r)[:n] for r in res]

    def _run_padded(self, arrays: Dict[str, np.ndarray], n: int):
        fed, bucket = self._pad_feed(arrays, n)
        t0 = time.perf_counter()
        self._track(+1)
        try:
            with trace.span("serving/infer_batch", bucket=bucket, rows=n):
                res = self.executor.run(self.program, feed=fed,
                                        fetch_list=self.fetch_names,
                                        scope=self.scope)
        finally:
            self._track(-1)
        self.metrics.observe_latency(
            time.perf_counter() - t0, name="batch_execute")
        self.metrics.inc("batches_executed")
        self.metrics.set_gauge("batch_occupancy", n / bucket)
        return [np.asarray(r)[:n] for r in res]

    # ------------------------------------------------------------------
    def warmup(self) -> int:
        """Compile every configured bucket shape up front with dummy
        feeds so live traffic never pays a compile. Returns the number
        of (batch, seq) combinations warmed; feeds with a dynamic
        non-batch dim need ``seq_buckets`` configured or they are
        skipped (and counted in the 'warmup_skipped' metric)."""
        combos = 0
        seqs = self.seq_buckets or [None]
        for b in self.batch_buckets:
            for s in seqs:
                feed = {}
                ok = True
                for name in self.feed_names:
                    shape, dtype = self._feed_template(name)
                    if shape is None:
                        ok = False
                        break
                    dims = [b]
                    for d in shape[1:]:
                        if d in (-1, None):
                            if s is None:
                                ok = False
                                break
                            dims.append(s)
                        else:
                            dims.append(int(d))
                    if not ok:
                        break
                    feed[name] = np.zeros(dims, dtype=dtype)
                if not ok:
                    self.metrics.inc("warmup_skipped")
                    continue
                self.executor.run(self.program, feed=feed,
                                  fetch_list=self.fetch_names,
                                  scope=self.scope)
                combos += 1
        self.metrics.inc("warmup_compiles", combos)
        self.save_manifest()
        return combos

    # -- cold-start plane ----------------------------------------------
    def save_manifest(self, dirname: Optional[str] = None) -> Optional[str]:
        """Persist the executor's recorded compile signatures next to the
        saved model (``warmup_manifest.json``) so the next replica can
        AOT-replay them (:meth:`warm_from_manifest`) instead of paying
        fresh compiles. No-op (returns None) without a model directory or
        before anything compiled."""
        dirname = dirname or self.model_dir
        if dirname is None or len(self.executor.manifest) == 0:
            return None
        try:
            return self.executor.manifest.save(dirname)
        except OSError:  # read-only artifact volume: serving still works
            return None

    def warm_from_manifest(self,
                           dirname: Optional[str] = None) -> Optional[int]:
        """AOT-replay a saved warmup manifest: ``.lower().compile()`` of
        every recorded signature of this engine's program, concurrently,
        WITHOUT executing anything. Returns the number of signatures now
        warm, or None when no manifest exists (caller falls back to the
        execute-based :meth:`warmup`). With ``--compilation_cache_dir``
        the compiles are disk restores and the first request is a pure
        in-process cache hit."""
        from ..core import manifest as manifest_mod

        dirname = dirname or self.model_dir
        if dirname is None:
            return None
        manifest = manifest_mod.try_load(dirname)
        if manifest is None:
            return None
        stats = manifest_mod.replay(
            self.executor, [self.program], scope=self.scope,
            manifest=manifest)
        self.metrics.inc("warmup_replayed", stats["compiled"])
        if stats["skipped"]:
            self.metrics.inc("warmup_manifest_skipped", stats["skipped"])
        return stats["compiled"] + stats["already"]

    def warm_start(self) -> int:
        """Boot path: manifest replay when available (AOT, concurrent, no
        execution), else execute-based :meth:`warmup`; either way a fresh
        manifest lands next to the model so the NEXT replica boots warm.
        A stale/foreign manifest degrades into ``warmup()`` instead of
        failing the boot."""
        import warnings as warnings_mod

        from ..core.manifest import ManifestError

        warmed = None
        try:
            warmed = self.warm_from_manifest()
        except ManifestError as exc:
            warnings_mod.warn(f"ignoring warmup manifest: {exc}",
                              RuntimeWarning, stacklevel=2)
        if warmed is None:
            warmed = self.warmup()
        self.save_manifest()
        return warmed

    def cache_stats(self) -> dict:
        return self.executor.cache_stats()

    def swap_params(self, source, *, strict: bool = True) -> Dict[str, int]:
        """Zero-recompile param hot-swap (the rolling-update payload
        step): replace this engine's weights in place from ``source`` (a
        trainer checkpoint dir, a saved-model dir, a Scope, or a dict).
        Shapes/dtypes must match the live values — the compile cache
        keys on the scope's key set, so a same-signature swap keeps
        every warm executable. Outstanding async dispatches keep the old
        arrays alive until they resolve (donation-safe)."""
        return swap_scope_params(self.scope, source, strict=strict,
                                 device_ctx=self.executor.device_ctx,
                                 metrics=self.metrics)

    # ------------------------------------------------------------------
    @property
    def state(self) -> str:
        """``ready`` | ``draining`` (closed, in-flight work finishing) |
        ``closed`` — the /healthz vocabulary."""
        if not self._closed:
            return "ready"
        return "closed" if self._released else "draining"

    def close(self, drain: bool = True,
              timeout: Optional[float] = 30.0) -> None:
        """Graceful release: stop admissions (``run``/``run_async``/
        ``serve_step`` raise :class:`EngineClosedError` from now on),
        then — with ``drain`` — wait for every in-flight batch before
        releasing the compile cache: synchronous runs on other threads
        finish, and async dispatches complete ON DEVICE (their callers
        can still ``result()`` afterwards — the fetched arrays outlive
        the engine). Idempotent."""
        with self._inflight_cond:
            self._closed = True
        if drain:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            with self._inflight_cond:
                while self._inflight > 0:  # sync runs in other threads
                    remaining = (None if deadline is None
                                 else deadline - time.monotonic())
                    if remaining is not None and remaining <= 0:
                        break  # bounded wait
                    self._inflight_cond.wait(remaining)
            for handle in list(self._outstanding):
                try:
                    handle.block()  # device completion, no host fetch
                except Exception:  # noqa: BLE001 - failed batch: done too
                    pass
        self._released = True
        self.executor.close()

    # ------------------------------------------------------------------
    # Server-driver interface
    # ------------------------------------------------------------------
    def serve_step(self, batcher, idle_wait_s: Optional[float] = None) -> bool:
        """Pull one batch from the batcher and execute it. Request
        payloads are per-row feed dicts (no batch dim); rows with
        identical shapes coalesce into one padded run. Shape groups are
        dispatched non-blocking (``run_async``) before any is resolved,
        so consecutive buckets pipeline: group k+1's stacking/padding and
        dispatch overlap group k's device execution. Returns True when
        work was done."""
        self._admit()
        reqs = batcher.next_batch(wait_s=idle_wait_s)
        if not reqs:
            return False
        groups: Dict[tuple, list] = {}
        for req in reqs:
            try:
                rows = {n: np.asarray(req.payload[n])
                        for n in self.feed_names}
            except (KeyError, TypeError) as exc:
                req.end_trace(status="bad_request")
                req.future.set_exception(BadRequestError(
                    f"payload must be a dict with feeds "
                    f"{self.feed_names}: {exc}"))
                continue
            sig = tuple((n, rows[n].shape) for n in self.feed_names)
            groups.setdefault(sig, []).append((req, rows))

        def fail(members, t0, exc):
            t1 = time.perf_counter()
            for req, _ in members:
                if req.span is not None:  # keep sampling decisions
                    trace.record("serving/execute", t0, t1,
                                 parent=req.span, batch=len(members),
                                 error=repr(exc)[:200])
                req.end_trace(status="error", error=repr(exc)[:200])
                req.future.set_exception(exc)

        dispatched = []
        for _, members in groups.items():
            feed = {n: np.stack([rows[n] for _, rows in members])
                    for n in self.feed_names}
            t0 = time.perf_counter()
            try:
                pending = self.run_async(feed)
            except Exception as exc:  # engine failure fails the batch
                fail(members, t0, exc)
                continue
            dispatched.append((members, t0, pending))
        for members, t0, pending in dispatched:
            try:
                fetched = pending.result()
            except Exception as exc:
                fail(members, t0, exc)
                continue
            t1 = time.perf_counter()
            now = time.monotonic()
            for i, (req, _) in enumerate(members):
                # attribute the shared batch execution to each rider
                # (skipped for unsampled requests: a root 'execute' span
                # would defeat the per-request sampling decision)
                if req.span is not None:
                    trace.record("serving/execute", t0, t1,
                                 parent=req.span, batch=len(members),
                                 row=i)
                req.future.set_result([f[i] for f in fetched])
                req.end_trace(status="ok",
                              latency_s=round(now - req.enqueue_t, 6))
                self.metrics.inc("completed")
                self.metrics.observe_latency(now - req.enqueue_t)
        return True
