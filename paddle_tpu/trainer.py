"""Event-driven training loop, parity with the v2 SGD trainer
(/root/reference/python/paddle/v2/trainer.py:24,124-202) on top of the
whole-block XLA executor.

Differences from the reference, all TPU-motivated:
- No parameter/updater objects: the optimizer appends its update ops into
  the program (fluid-style) and the whole step — forward, backward,
  update — is one compiled XLA computation per batch signature.
- Distribution is an argument (mesh + ShardingPlan), not a different
  updater class: the same loop runs single-chip or SPMD over a slice.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import numpy as np

from . import event as evt
from . import io as io_mod
from . import profiler
from .core.executor import Executor, TPUPlace
from .core.program import (Program, Variable, default_main_program,
                           default_startup_program)
from .core.scope import Scope, global_scope
from .data_feeder import DataFeeder


#: sets in the feed ring at most, whatever ``async_depth``: a set holds a
#: batch's host bytes, and until its turn a batch's feed on the device
#: (154 MB each in ``resnet50-train``), and a transfer (38 ms there) is
#: long over when its set comes round two or three steps later
_RING_SETS = 3


class _FeedRing:
    """The host buffers that the async feed stage stacks dense columns
    into: ``size`` sets (``DataFeeder.feed``'s ``out`` dicts), lent oldest
    first.

    **The recycle rule.** ``jax.device_put`` returns before its transfer
    has read the host array, and on some backends the device array may BE
    the host array: the CPU client takes a 64-byte-aligned host buffer
    zero-copy and aliases it for the array's whole life. So a set is
    written again only when every device array that was made from it has
    completed its transfer (``is_ready``) AND does not alias the host
    array. A set that fails either test when its turn comes is never
    written again: the ring forgets it and lends an empty one, which the
    feeder fills with fresh arrays. The stage therefore never WAITS for a
    transfer (no span would name such a wait); what a late transfer costs
    is a fresh allocation, and ``trainer/feed_stack`` shows it as
    ``reused=False`` beside a longer span. That a device array has been
    consumed, or dropped by everyone else, proves neither test (a step is
    dispatched before its feed has arrived), so the ring holds the arrays
    themselves until it has asked them: a batch's feed stays alive on the
    device until its set comes round, at most ``size`` batches later."""

    def __init__(self, size: int):
        self._size = size
        self._lent = []     # (set, [(host array, device array)])

    def take(self) -> Dict[str, np.ndarray]:
        """A set that is safe to write; never waits."""
        if len(self._lent) < self._size:
            return {}
        bufs, made = self._lent.pop(0)
        for host, arr in made:
            if (not arr.is_ready()
                    or arr.unsafe_buffer_pointer() == host.ctypes.data):
                return {}
        return bufs

    def lend(self, bufs: Dict[str, np.ndarray], made) -> None:
        """``made``: (host array of ``bufs``, device array put from it)
        pairs; the set comes back through :meth:`take`."""
        self._lent.append((bufs, made))


class SGD:
    """``SGD(cost, optimizer, feed_list).train(reader, ...)``.

    ``metrics`` maps display names to program variables (e.g. the output of
    layers.accuracy) fetched and averaged alongside the cost — the analogue
    of the reference's in-loop Evaluators (TrainerInternal.cpp:140-153).
    """

    def __init__(self, cost: Variable, optimizer, feed_list: Sequence[Variable],
                 place: Optional[TPUPlace] = None, mesh=None, plan=None,
                 metrics: Optional[Dict[str, Variable]] = None,
                 scope: Optional[Scope] = None,
                 check_nan_inf: Optional[bool] = None,
                 transpile: bool = False,
                 pad_to_multiple: Optional[int] = None):
        self.cost = cost
        self.metrics = dict(metrics or {})
        self.main_program: Program = cost.block.program
        self.startup_program = default_startup_program()
        # Inference/test clone is taken BEFORE optimizer ops are appended
        # and flips is_test (fluid's Program.clone(for_test=True)).
        self.test_program = self.main_program.clone(for_test=True)
        if transpile:
            # Training rewrites must land BEFORE minimize appends the
            # backward: grad ops reference the op list they were derived
            # from, and the fused replacements carry their own grad_fns.
            # Per-pass wall time / op deltas go to the profiler StatSet
            # (profiler.print_all_status shows them next to step timers).
            from .transpiler import training_pipeline, prune_pipeline

            feeds = [v.name for v in feed_list]
            fetches = [cost.name] + [v.name for v in self.metrics.values()]
            training_pipeline().run(self.main_program, feeds, fetches,
                                    scope=scope or global_scope())
            prune_pipeline().run(self.test_program, feeds, fetches)
        optimizer.minimize(cost, startup_program=self.startup_program)
        from .flags import FLAGS

        if FLAGS.verify_program:
            # static backstop before the first compile: structural verify
            # + whole-program shape/dtype inference over the FULL step
            # program (forward, backward, optimizer updates) — a broken
            # layer/rewrite fails here naming op/callsite/slot, not as a
            # JAX trace error inside jit
            from . import analysis

            feeds = [v.name for v in feed_list]
            fetches = [cost.name] + [v.name for v in self.metrics.values()]
            analysis.check_program(self.main_program, feeds, fetches,
                                   scope=scope or global_scope())
            analysis.check_program(self.startup_program)
        # pad_to_multiple: bucket ragged columns (data_feeder.py) so varlen
        # training pads to a bounded set of compile signatures.
        self.feeder = DataFeeder(feed_list, pad_to_multiple=pad_to_multiple)
        self._feed_names = [v.name for v in feed_list]
        self.scope = scope or global_scope()
        if mesh is None and plan is not None:
            mesh = plan.mesh
        self.exe = Executor(place or TPUPlace(0), check_nan_inf=check_nan_inf,
                            mesh=mesh, plan=plan)
        self._initialized = False
        if plan is not None:
            self._apply_plan(plan)

    # ------------------------------------------------------------------
    def _apply_plan(self, plan):
        """One sharding plane: run the ShardProgram pass over the step,
        test, and startup programs (every var annotated with its
        plan-resolved PartitionSpec; located ShardingPlanError on a rule
        set that cannot fit) and point the executor at the plan's mesh —
        parameters then INITIALIZE sharded (the startup run lands each
        shard on its device; no replicated staging copy) and every step
        lowers through ``jax.jit(in_shardings/out_shardings,
        donate_argnums)`` with GSPMD inserting the collectives."""
        from .transpiler import shard_program

        fetches = [self.cost.name] + [v.name for v in
                                      self.metrics.values()]
        for prog in (self.main_program, self.test_program,
                     self.startup_program):
            shard_program(prog, plan, self._feed_names, fetches,
                          scope=self.scope)
        self.exe.mesh = plan.mesh
        self.exe.plan = plan

    def _init_params(self):
        if not self._initialized:
            self.exe.run(self.startup_program, scope=self.scope)
            self._initialized = True

    def _fetch_list(self):
        return [self.cost] + list(self.metrics.values())

    def _split(self, fetched):
        cost = float(np.asarray(fetched[0]))
        names = list(self.metrics.keys())
        vals = {n: float(np.mean(np.asarray(v)))
                for n, v in zip(names, fetched[1:])}
        return cost, vals

    # ------------------------------------------------------------------
    def train(self, reader: Callable, num_passes: int = 1,
              event_handler: Optional[Callable] = None,
              test_reader: Optional[Callable] = None,
              run_log=None, async_depth: int = 1,
              checkpoint=None, mem_budget: Optional[float] = None,
              plan=None, goodput=None):
        """Run ``num_passes`` over ``reader`` (a batched reader: yields
        minibatches of rows ordered like ``feed_list``).

        Without an ``event_handler``, batch cost is logged every
        ``--log_period`` batches (flags.py), the reference trainer's
        default output (TrainerInternal.cpp log_period path).

        ``run_log`` (a :class:`paddle_tpu.trace.RunLog` or any event
        callable) receives every event IN ADDITION to ``event_handler``:
        per-iteration cost/metrics/examples-per-sec land in its JSONL
        journal and the global StatSet is dumped at EndPass — the
        Trainer.cpp:449 stat dump, machine-readable.

        ``checkpoint`` (a :class:`paddle_tpu.resilience.CheckpointConfig`)
        makes the run preemption-safe: the scope (params, optimizer
        slots, RNG stream) plus the training position is checkpointed
        every ``every_n_steps`` completed steps (serialized off the
        critical path with ``background=True``), SIGTERM/SIGINT drains
        in-flight work, writes a final checkpoint and exits after
        ``EndPass(interrupted=True)``, and the next ``train`` call
        auto-resumes from the latest intact checkpoint — skipping the
        already-consumed batches of the interrupted pass (unless the
        reader is master-backed, whose task queue already tracks
        consumption) so the end state is bit-identical to an
        uninterrupted run.

        ``mem_budget`` (bytes) gates the step program on the static
        peak-HBM estimate (paddle_tpu.analysis.memory): at the first
        batch — when the batch size is known but BEFORE the first
        compile — the whole step program (forward, backward, optimizer)
        is analyzed against the budget, and a
        :class:`~paddle_tpu.analysis.MemoryBudgetError` naming the peak
        live set and the remat advisor's suggestions is raised instead
        of letting XLA OOM at compile or first run.

        ``plan`` (a :class:`paddle_tpu.parallel.ShardingPlan`) turns the
        run SPMD over the plan's mesh: the ShardProgram pass annotates
        every program var with its PartitionSpec, parameters initialize
        sharded, and the whole step lowers through one
        ``jax.jit(in_shardings/out_shardings, donate_argnums)`` — dp, tp
        (and sp/ep through the mesh-aware op kernels) compose on ONE
        mesh. Equivalent to constructing ``SGD(..., plan=plan)``; must
        be given before the first step initializes parameters.

        ``async_depth`` > 1 pipelines the loop: batch stacking +
        host->device transfer run on a background thread
        (reader.device_prefetch machinery), each step is dispatched with
        ``Executor.run_async`` while up to ``async_depth`` prior steps
        are still in flight, and cost/metrics resolve with that lag —
        ``EndIteration`` fires (in batch order) when a step's fetches
        RESOLVE, with a full drain before ``EndPass``, so a
        ``BeginIteration`` for step k+1 can precede step k's
        ``EndIteration``. Numerics are unchanged: the same programs run
        in the same order on the same device state (async-vs-sync parity
        is pinned bitwise by tests/test_async_training.py). The default
        ``async_depth=1`` is the fully synchronous reference loop.

        ``goodput`` controls the training observatory
        (:class:`paddle_tpu.trace.GoodputMeter`): the default ``None``
        creates a fresh meter so every second of the run decomposes into
        the goodput/badput buckets and ``EndIteration`` events carry
        host/device walls + live MFU; pass an existing meter to share
        accounting (the elastic ``StreamingTrainer`` does), or ``False``
        to run the bare uninstrumented loop (the bench A/B off-leg).
        The active meter is exposed as ``self.goodput``."""
        user_handler = event_handler or _default_log_handler()
        if run_log is not None:
            def event_handler(e, _h=user_handler, _r=run_log):
                _h(e)
                _r(e)
        else:
            event_handler = user_handler
        if plan is not None:
            # a mid-life plan swap is legal: params already initialized
            # under the previous layout are resharded by the executor's
            # device_put at the next step
            self._apply_plan(plan)
        self._init_params()
        self._mem_budget = mem_budget
        self._mem_checked = False
        from .trace.goodput import GoodputMeter

        if goodput is False:
            meter = None
        elif goodput is None or goodput is True:
            meter = GoodputMeter()
        else:
            meter = goodput
        self.goodput = meter
        self._flops_priced = meter is None
        rs = None
        from .flags import FLAGS
        from .resilience import TrainResilience, faults
        if (checkpoint is not None or FLAGS.fault_plan
                or faults.active_plan() is not None):
            # reshard-on-restore: the executor's plan rides into the
            # restore, so a checkpoint saved under a different mesh/plan
            # re-places bitwise through THIS plan's PartitionSpecs
            rs = TrainResilience(checkpoint, scope=self.scope,
                                 plan=self.exe.plan)
            rs.resume()  # restores scope + position from the latest ckpt
            if checkpoint is not None and getattr(checkpoint, "dirname",
                                                  None):
                # cold-start replay: AOT-compile the step signatures the
                # previous run recorded next to its checkpoints, BEFORE
                # the first batch — with --compilation_cache_dir these
                # are disk restores and resume pays zero fresh compiles
                self._replay_manifest(checkpoint.dirname)
        import contextlib

        from .trace.flight import get_recorder

        # live trainer state rides every flight bundle (position,
        # goodput snapshot, recent step walls); WeakMethod-held so a
        # dropped SGD never pins memory
        from collections import deque

        self._flight_pos = {"pass_id": None, "batch_id": None}
        self._step_walls = deque(maxlen=32)
        recorder = get_recorder()
        recorder.add_source("trainer", self._flight_state)
        ctx = rs.signal_context() if rs is not None \
            else contextlib.nullcontext()
        try:
            self._train_passes(ctx, rs, reader, num_passes, event_handler,
                               test_reader, async_depth, meter)
        except BaseException as exc:
            if rs is not None:
                # join (never mask) an in-flight background save so no
                # thread keeps mutating the ckpt dir after the crash
                rs.abort()
            # black box for the postmortem: throttled bundle capturing
            # the exact position/goodput state at the failure
            recorder.auto_dump("trainer_error", error=exc)
            raise
        if rs is not None:
            rs.finalize()
            if checkpoint is not None and getattr(checkpoint, "dirname",
                                                  None):
                self._save_manifest(checkpoint.dirname)

    def _replay_manifest(self, dirname: str):
        """Resume-time warmup: AOT-replay the signature manifest saved
        next to the checkpoints (see core.manifest). A missing manifest
        is a normal first boot; a version-rejected one warns and falls
        back to compile-on-first-step — resume must never die on a
        warmup artifact."""
        import warnings

        from . import trace
        from .core import manifest as manifest_mod

        try:
            manifest = manifest_mod.try_load(dirname)
        except manifest_mod.ManifestError as exc:
            warnings.warn(f"ignoring warmup manifest: {exc}",
                          RuntimeWarning, stacklevel=2)
            return None
        if manifest is None:
            return None
        with trace.span("trainer/manifest_replay", dirname=dirname) as sp:
            stats = manifest_mod.replay(
                self.exe, [self.main_program, self.test_program],
                scope=self.scope, manifest=manifest)
            if sp is not None:
                sp.set_attrs(**stats)
        self._last_replay = stats
        return stats

    def _save_manifest(self, dirname: str) -> None:
        """Persist the compile signatures of this run next to the
        checkpoints so the next resume replays them."""
        if len(self.exe.manifest) == 0:
            return
        try:
            self.exe.manifest.save(dirname)
        except OSError:
            pass  # checkpoint volume gone: the run itself still succeeded

    def _train_passes(self, ctx, rs, reader, num_passes, event_handler,
                      test_reader, async_depth, meter=None):
        import time as time_mod

        with ctx:
            if meter is not None:
                # the residual anchor carries ACROSS passes: event
                # dispatch, reader setup, and the EndPass->BeginPass gap
                # all belong to the decomposition, not just the step loop
                t_anchor = time_mod.perf_counter()
                acc0 = meter.total_seconds()
            for pass_id in range(rs.start_pass if rs else 0, num_passes):
                event_handler(evt.BeginPass(pass_id))
                skip_n = rs.skip_for_pass(pass_id, reader) if rs else 0
                if async_depth > 1:
                    pass_costs, pass_metrics = self._run_pass_async(
                        pass_id, reader, event_handler, int(async_depth),
                        rs=rs, skip_n=skip_n, meter=meter)
                else:
                    pass_costs, pass_metrics = self._run_pass_sync(
                        pass_id, reader, event_handler, rs=rs,
                        skip_n=skip_n, meter=meter)
                if meter is not None:
                    # the residual (event handlers, splits, loop
                    # bookkeeping) closes the decomposition: bucket
                    # seconds sum to the measured pass wall
                    wall = time_mod.perf_counter() - t_anchor
                    meter.account("host_dispatch",
                                  wall - (meter.total_seconds() - acc0))
                    meter.publish_stats(profiler.global_stat)
                    t_anchor = time_mod.perf_counter()
                    acc0 = meter.total_seconds()
                summary = _mean_metrics(pass_metrics)
                summary["cost"] = float(np.mean(pass_costs)) \
                    if pass_costs else 0.0
                if rs is not None and rs.interrupted:
                    # graceful preemption: the final checkpoint is
                    # already on disk (commit with wait=True); no test
                    # pass on the way out
                    event_handler(evt.EndPass(pass_id, metrics=summary,
                                              interrupted=True))
                    break
                if test_reader is not None:
                    result = self.test(test_reader)
                    event_handler(evt.EndPass(pass_id, metrics=summary))
                    event_handler(result)
                else:
                    event_handler(evt.EndPass(pass_id, metrics=summary))

    def _maybe_check_mem_budget(self, feed):
        """One-shot build-time budget gate, run at the first batch (batch
        size now known) BEFORE the first compile/dispatch."""
        if getattr(self, "_mem_budget", None) is None or self._mem_checked:
            return
        self._mem_checked = True
        from . import analysis

        batch = 1
        for v in feed.values():
            shape = getattr(v, "shape", None)
            if shape:
                batch = int(shape[0])
                break
        fetches = [self.cost.name] + [v.name for v in
                                      self.metrics.values()]
        analysis.check_memory_budget(
            self.main_program, list(feed), fetches, self._mem_budget,
            scope=self.scope, batch_size=batch,
            what="SGD.train step program", plan=self.exe.plan)

    def _maybe_price_flops(self, feed, meter):
        """One-shot MFU numerator: price the step program through the
        calibrated cost model at the first batch (batch size now known).
        Unpriceable programs simply leave MFU off."""
        if meter is None or getattr(self, "_flops_priced", True):
            return
        self._flops_priced = True
        from .trace.goodput import program_flops

        batch = 1
        for v in feed.values():
            shape = getattr(v, "shape", None)
            if shape:
                batch = int(shape[0])
                break
        # the static analysis costs ~10ms — cache per batch size so
        # repeated train() calls on one trainer price it once
        cached = getattr(self, "_flops_cache", None)
        if cached is not None and cached[0] == batch:
            meter.set_program_flops(cached[1])
            return
        fetches = [self.cost.name] + [v.name for v in
                                      self.metrics.values()]
        flops = program_flops(
            self.main_program, self._feed_names, fetches,
            scope=self.scope, batch_size=batch, plan=self.exe.plan)
        self._flops_cache = (batch, flops)
        meter.set_program_flops(flops)

    def _flight_state(self):
        """Live-state source for the flight recorder: where the run is,
        its goodput waterfall, and the last-N step walls."""
        meter = getattr(self, "goodput", None)
        return {
            "position": dict(getattr(self, "_flight_pos", {}) or {}),
            "goodput": meter.snapshot() if meter is not None else None,
            "recent_step_walls_s": [
                round(w, 6) for w in getattr(self, "_step_walls", [])],
        }

    def _run_pass_sync(self, pass_id, reader, event_handler, rs=None,
                       skip_n=0, meter=None):
        import time as time_mod

        from . import trace

        m = meter
        perf = time_mod.perf_counter
        exe = self.exe
        pass_costs, pass_metrics = [], []
        it = enumerate(reader())
        while True:
            # the reader pull is the data-wait bucket; a master-backed
            # reader (StreamingTrainer) accounts its queue idle +
            # rollback time into the shared meter DURING next(), so
            # those inner seconds are re-attributed out of data_wait
            if m is not None:
                inner0 = (m.bucket_seconds("master_wait")
                          + m.bucket_seconds("recovery_rollback"))
                t_read0 = perf()
            try:
                batch_id, batch = next(it)
                while batch_id < skip_n:
                    # consumed before the interrupt (resume replay)
                    batch_id, batch = next(it)
            except StopIteration:
                if m is not None:
                    inner = (m.bucket_seconds("master_wait")
                             + m.bucket_seconds("recovery_rollback")
                             - inner0)
                    m.account("data_wait", perf() - t_read0 - inner)
                break
            if m is not None:
                inner = (m.bucket_seconds("master_wait")
                         + m.bucket_seconds("recovery_rollback")
                         - inner0)
                m.account("data_wait", perf() - t_read0 - inner)
                t_step0 = perf()
                self._flight_pos["pass_id"] = pass_id
                self._flight_pos["batch_id"] = batch_id
            if rs is not None:
                # transient-fault retries (backoff included) are
                # recovery, not compute
                if m is not None:
                    with m.measure("recovery_rollback"):
                        rs.before_step()
                else:
                    rs.before_step()
            event_handler(evt.BeginIteration(pass_id, batch_id))
            # REGISTER_TIMER("TrainBatch") parity: the step timer
            # accumulates in the global StatSet, which RunLog dumps
            # (and print_all_status prints) at pass end
            device_dt = step_mfu = None
            with trace.span("trainer/iteration", pass_id=pass_id,
                            batch_id=batch_id) as sp, \
                    profiler.timer("trainer/step"):
                if m is not None:
                    t_feed0 = perf()
                feed = self.feeder.feed(batch)
                if m is not None:
                    m.account("data_wait", perf() - t_feed0)
                self._maybe_check_mem_budget(feed)
                self._maybe_price_flops(feed, m)
                if m is not None:
                    fc0 = exe.fresh_compile_seconds
                    t_run0 = perf()
                fetched = exe.run(self.main_program, feed=feed,
                                  fetch_list=self._fetch_list(),
                                  scope=self.scope)
                if m is not None:
                    run_dt = perf() - t_run0
                    fc_dt = min(exe.fresh_compile_seconds - fc0, run_dt)
                    device_dt = run_dt - fc_dt
                    m.account("fresh_compile", fc_dt)
                    m.account("device_compute", device_dt)
                    step_mfu = m.note_step(device_dt)
                cost, mvals = self._split(fetched)
                if sp is not None:
                    sp.set_attr("cost", cost)
            pass_costs.append(cost)
            pass_metrics.append(mvals)
            try:
                bs = len(batch)
            except TypeError:
                bs = None
            host_dt = None
            if m is not None:
                step_wall = perf() - t_step0
                host_dt = max(0.0, step_wall - (device_dt or 0.0))
                self._step_walls.append(step_wall)
            event_handler(evt.EndIteration(pass_id, batch_id, cost,
                                           mvals, batch_size=bs,
                                           host_wall_s=host_dt,
                                           device_wall_s=device_dt,
                                           mfu=step_mfu))
            if rs is not None:
                # a due/periodic save stalls the loop right here
                if m is not None:
                    with m.measure("checkpoint_stall"):
                        stop = rs.after_step(pass_id, batch_id, bs)
                else:
                    stop = rs.after_step(pass_id, batch_id, bs)
                if stop:
                    break  # graceful interrupt: checkpoint written
        return pass_costs, pass_metrics

    def _run_pass_async(self, pass_id, reader, event_handler, depth,
                        rs=None, skip_n=0, meter=None):
        """The overlapped pipeline: a background feeder stage keeps
        device-resident batches ready, the dispatch loop enqueues step
        k+1 while step k executes (bounded at ``depth`` in flight), and
        the oldest step resolves — one host sync — only when the window
        is full. Iteration spans split into ``trainer/dispatch`` and
        ``trainer/resolve`` phases carrying a ``queue_depth`` attr, so
        tools/trace_summary.py --pipeline shows host gap vs device
        time; the feed thread's ``trainer/feed_stack`` (rows -> one host
        array per feed; attrs ``bytes`` written by the feeder's dense
        row copies, ``fast_cols`` of ``cols`` columns that took them,
        ``reused``: into buffers the ring already held) and
        ``trainer/feed_put`` (the ``device_put`` calls) say what a
        ``data_wait`` waited for.

        The feed stage stacks into a :class:`_FeedRing` that this pass
        owns: ``depth`` buffer sets, ``_RING_SETS`` at most (its bytes
        are the dense columns'; the recycle rule is stated there). A
        fresh 154 MB array a step costs ten times more in page faults
        than its copy does."""
        import time as time_mod
        from collections import deque

        import jax

        from . import trace
        from .reader.decorator import background_stage

        feeder = self.feeder
        dev = None if self.exe.mesh is not None \
            else self.exe.place.device()
        # mesh runs keep fresh arrays: the executor shards the feed itself
        # on the dispatch thread (``executor/shard_feed``), so this stage
        # never sees the device arrays whose transfers gate a reuse
        ring = None if dev is None else _FeedRing(min(depth, _RING_SETS))

        def feed_source():
            for batch_id, batch in enumerate(reader()):
                if batch_id < skip_n:
                    continue  # consumed before the interrupt (resume)
                try:
                    bs = len(batch)
                except TypeError:
                    bs = None
                bufs = ring.take() if ring is not None else {}
                lent = dict(bufs)
                with trace.span("trainer/feed_stack",
                                batch_id=batch_id) as sp:
                    feed = feeder.feed(batch, out=bufs)
                    if sp is not None:
                        # a feed that IS its entry of the set was copied
                        # row by row: into the lent array, or a fresh one
                        fast = [k for k, v in feed.items()
                                if v is bufs.get(k)]
                        sp.set_attrs(
                            bytes=sum(feed[k].nbytes for k in fast),
                            cols=len(feeder.feed_vars),
                            fast_cols=len(fast),
                            reused=bool(fast) and all(
                                feed[k] is lent.get(k) for k in fast))
                yield batch_id, bs, feed, bufs

        def to_device(item):
            batch_id, bs, feed, bufs = item
            if dev is None:  # mesh runs: the executor shards feeds itself
                return batch_id, bs, feed
            # the span times the host call: the transfer is the device
            # trace's, and nothing here waits for it
            with trace.span("trainer/feed_put", batch_id=batch_id):
                put = {k: (jax.device_put(v, dev)
                           if not isinstance(v, jax.Array) else v)
                       for k, v in feed.items()}
            ring.lend(bufs, [(v, put[k]) for k, v in feed.items()
                             if v is bufs.get(k)])
            return batch_id, bs, put

        m = meter
        perf = time_mod.perf_counter
        exe = self.exe
        pending = deque()  # (batch_id, batch_size, RunHandle, host_wall)
        pass_costs, pass_metrics = [], []
        # device wall per step on the overlapped path = the
        # resolve-ordered interval (EndIteration k-1 -> EndIteration k):
        # with the window full the device is the bottleneck, so that
        # interval IS the step's device time — the MFU denominator and
        # the runlog's examples/sec base
        last_resolve = [None]

        def resolve_oldest():
            batch_id, bs, handle, host_dt = pending.popleft()
            if m is not None:
                t0 = perf()
            with trace.span("trainer/resolve", pass_id=pass_id,
                            batch_id=batch_id,
                            queue_depth=len(pending) + 1) as sp, \
                    profiler.timer("trainer/resolve"):
                cost, mvals = self._split(handle.result())
                if sp is not None:
                    sp.set_attr("cost", cost)
            device_dt = step_mfu = None
            if m is not None:
                now = perf()
                # host blocked on device results: the goodput numerator
                m.account("device_compute", now - t0)
                if last_resolve[0] is not None:
                    device_dt = now - last_resolve[0]
                    step_mfu = m.note_step(device_dt)
                    self._step_walls.append(device_dt)
                last_resolve[0] = now
            pass_costs.append(cost)
            pass_metrics.append(mvals)
            event_handler(evt.EndIteration(pass_id, batch_id, cost,
                                           mvals, batch_size=bs,
                                           host_wall_s=host_dt,
                                           device_wall_s=device_dt,
                                           mfu=step_mfu))
            if rs is not None:
                # defer: a snapshot here would race the in-flight window
                # (donated state) — the dispatch loop drains, then
                # commits at the safe point
                rs.after_step(pass_id, batch_id, bs, defer=True)

        stream = background_stage(feed_source, depth=depth,
                                  transform=to_device)
        stopped = False
        try:
            sit = iter(stream())
            while True:
                # blocked on the background feed stage = data wait
                if m is not None:
                    t_read0 = perf()
                try:
                    batch_id, bs, feed = next(sit)
                except StopIteration:
                    if m is not None:
                        m.account("data_wait", perf() - t_read0)
                    break
                if m is not None:
                    m.account("data_wait", perf() - t_read0)
                    self._flight_pos["pass_id"] = pass_id
                    self._flight_pos["batch_id"] = batch_id
                if rs is not None:
                    if m is not None:
                        with m.measure("recovery_rollback"):
                            rs.before_step()
                    else:
                        rs.before_step()
                self._maybe_check_mem_budget(feed)
                self._maybe_price_flops(feed, m)
                event_handler(evt.BeginIteration(pass_id, batch_id))
                host_dt = None
                if m is not None:
                    fc0 = exe.fresh_compile_seconds
                    t_disp0 = perf()
                with trace.span("trainer/dispatch", pass_id=pass_id,
                                batch_id=batch_id,
                                queue_depth=len(pending)), \
                        profiler.timer("trainer/dispatch"):
                    handle = exe.run_async(self.main_program, feed=feed,
                                           fetch_list=self._fetch_list(),
                                           scope=self.scope)
                if m is not None:
                    host_dt = perf() - t_disp0
                    fc_dt = min(exe.fresh_compile_seconds - fc0, host_dt)
                    m.account("fresh_compile", fc_dt)
                    m.account("host_dispatch", host_dt - fc_dt)
                pending.append((batch_id, bs, handle, host_dt))
                while len(pending) >= depth:
                    resolve_oldest()
                if rs is not None and rs.pause_requested:
                    # checkpoint due / shutdown: drain the whole window so
                    # resolved == dispatched == scope state, then save
                    while pending:
                        resolve_oldest()
                    if m is not None:
                        with m.measure("checkpoint_stall"):
                            stop = rs.commit(pass_id)
                    else:
                        stop = rs.commit(pass_id)
                    if stop:
                        stopped = True
                        break
            while pending:  # drain: every EndIteration precedes EndPass
                resolve_oldest()
            if not stopped and rs is not None and rs.pause_requested:
                if m is not None:
                    with m.measure("checkpoint_stall"):
                        rs.commit(pass_id)
                else:
                    rs.commit(pass_id)
        except BaseException:
            # In-flight steps' state writes have already landed in the
            # scope; drain their handles (costs/metrics + EndIteration
            # per step) so the event stream stays consistent with the
            # scope before propagating. If the drain itself keeps
            # failing (e.g. the handler raises), at least block the
            # remaining handles instead of abandoning them mid-flight.
            while pending:
                try:
                    resolve_oldest()
                except BaseException:
                    for _, _, h, _ in pending:
                        try:
                            h.block()
                        except Exception:
                            pass
                    pending.clear()
            raise
        return pass_costs, pass_metrics

    def test(self, reader: Callable) -> "evt.TestResult":
        self._init_params()
        costs, metrics = [], []
        for batch in reader():
            feed = self.feeder.feed(batch)
            fetched = self.exe.run(self.test_program, feed=feed,
                                   fetch_list=self._fetch_list(),
                                   scope=self.scope)
            cost, mvals = self._split(fetched)
            costs.append(cost)
            metrics.append(mvals)
        return evt.TestResult(float(np.mean(costs)) if costs else 0.0,
                              _mean_metrics(metrics))

    # ------------------------------------------------------------------
    def save_params(self, dirname: str):
        io_mod.save_params(self.exe, dirname, self.main_program,
                           scope=self.scope)

    def load_params(self, dirname: str):
        self._init_params()
        io_mod.load_params(self.exe, dirname, self.main_program,
                           scope=self.scope)


def _default_log_handler():
    from .flags import FLAGS

    period = max(int(FLAGS.log_period), 1)

    def handler(e):
        if isinstance(e, evt.EndIteration) and e.batch_id % period == 0:
            extra = "".join(f" {k}={v:.4f}" for k, v in
                            (e.metrics or {}).items())
            print(f"pass {e.pass_id} batch {e.batch_id} "
                  f"cost={e.cost:.6f}{extra}", flush=True)
        elif isinstance(e, evt.EndPass):
            print(f"pass {e.pass_id} done: "
                  + " ".join(f"{k}={v:.6f}" for k, v in
                             (e.metrics or {}).items()), flush=True)

    return handler


def _mean_metrics(per_batch):
    out: Dict[str, float] = {}
    if per_batch:
        for key in per_batch[0]:
            out[key] = float(np.mean([m[key] for m in per_batch]))
    return out
