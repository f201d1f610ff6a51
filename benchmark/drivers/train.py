"""Driver ``train``: a family's training program behind ``trainer.SGD``,
fed by a reader that makes each batch on the host, for a fixed time.

The run, on one thread (the trainer's feed stage is the program's own):
startup -> the plain reference's loss on the first batch and the initial
weights (at ``BeginPass``: after the startup run, before the first step
donates the weights) -> ``warmup_steps`` steps (the first compiles or
loads the one step executable) -> the window opens at the last warm-up
step's ``EndIteration`` -> the reader stops handing out batches
``seconds`` later -> the window closes at the last step's
``EndIteration``, a blocking fetch of its loss. The step time behind
``train_mfu`` and ``train_<item>_per_s`` is the mean time between two
``EndIteration`` events of the window with the slowest and the fastest 2%
left out (``TRIM``): a rare host stall of a second or two — 2 of 12 runs
had one (my chip runs, PR 22) — would otherwise swing a run by 2-4%, and
a late fetch is followed by early ones, which go with it; anything that
slows more than one step in fifty still counts. The whole-window and
median-step figures go on the notes line. A traced run switches the program's own tracer on for
the whole window and puts the profiler round ``trace_steps`` steps three
quarters of the way through it (``TRACE_AFTER``); ``data_wait`` is read
from the window's opening to that point, before the profiler slows the
host.

Mix parameters: ``batch``, ``async_depth``, ``warmup_steps``,
``trace_steps``, ``optimizer``, ``loss_tol``, ``loss_must_fall``, an
optional ``plan`` (``{"kind": "data_parallel", "axes": {"dp": 4}}``), and
whatever the family's ``batches`` reads.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import harness

#: share of the window's steps left out at EACH end of the sorted step
#: times before their mean is taken
TRIM = 0.02
#: a traced run starts the profiler this far into the window
TRACE_AFTER = 0.75


def _plan(mix: dict, devices):
    spec = mix.get("plan")
    if not spec:
        return None
    from paddle_tpu import parallel

    mesh = parallel.make_mesh(spec["axes"], devices=list(devices))
    return getattr(parallel, spec["kind"] + "_plan")(mesh)


def run(cell, seed: int, seconds: float, traced: bool, devices,
        t0: float) -> harness.Measured:
    import paddle_tpu as pt

    mix, config, family = cell.mix, cell.config, cell.family
    pt.set_amp(config["amp"] == "bfloat16")
    prog = family.build_train(config, mix, seed, _plan(mix, devices))
    sgd = prog.sgd
    log = harness.SpanLog()
    spans = harness.ProgramSpans() if traced else None
    slice_ = harness.TraceSlice(cell.name) if traced else None
    warmup, trace_steps = mix["warmup_steps"], mix["trace_steps"]

    first = {}
    ends = []                    # (monotonic at EndIteration, loss)
    mark = {"open": None, "deadline": None, "at_open": None}   # + slice

    def counters_now():
        return {"fresh_compiles": sgd.exe.cache_stats()["fresh_compiles"],
                "data_wait_s": sgd.goodput.bucket_seconds("data_wait")}

    stream = family.batches(config, mix, seed)

    def reader():
        yield first["batch"]
        while mark["deadline"] is None or time.monotonic() < mark["deadline"]:
            with log.span("bench/reader_next"):
                batch = next(stream)
            yield batch

    def on_event(e):
        if isinstance(e, pt.event.BeginPass):
            # BeginPass comes after the startup run and before the reader
            # is started: the reference sees the INITIAL weights, which
            # the first step donates
            with log.span("bench/reference"):
                first["batch"] = next(stream)
                first["ref_loss"] = family.reference_loss(
                    config, family.weights_of(prog.main, prog.scope),
                    sgd.feeder.feed(first["batch"]))
        elif isinstance(e, pt.event.EndIteration):
            now = time.monotonic()
            ends.append((now, float(e.cost)))
            n = len(ends)
            if n == warmup:
                mark.update(open=now, deadline=now + seconds,
                            at_open=counters_now())
            if not traced or mark["open"] is None or slice_.stopped:
                return
            if not slice_.started:
                if now >= mark["open"] + TRACE_AFTER * seconds:
                    # what the host waited for is read up to HERE: the
                    # profiler slows a host-bound loop severalfold
                    mark.update(at_slice=counters_now(), slice_t=now,
                                slice_stop=n + trace_steps)
                    slice_.start()
            elif n >= mark["slice_stop"]:
                slice_.stop()

    with log.span("bench/train"):
        sgd.train(reader, num_passes=1, event_handler=on_event,
                  async_depth=mix["async_depth"])
    if traced:
        slice_.stop()       # the window may have ended inside the slice
    if mark["open"] is None:
        raise RuntimeError(f"{cell.name}: the run ended inside warm-up")
    at_close = counters_now()
    t_open, t_close = mark["open"], ends[-1][0]
    losses = np.asarray([c for _, c in ends])
    steps = len(ends) - warmup
    elapsed = t_close - t_open
    per_step = family.items_per_step(mix)
    flops_item = family.flops_per_item(config, mix)
    peak = cell.chips * cell.peaks["bf16_flops_per_s"]

    def mfu_at(step_seconds: float) -> float:
        return per_step / step_seconds * flops_item / peak * 100.0

    intervals = np.diff([t for t, _ in ends[warmup - 1:]])
    k = int(TRIM * len(intervals))
    step_s = float(np.mean(np.sort(intervals)[k:len(intervals) - k]))
    items = steps * per_step

    finite = bool(np.all(np.isfinite(losses)))
    close = abs(losses[0] - first["ref_loss"]) <= mix["loss_tol"]
    fell = (not mix["loss_must_fall"]
            or float(np.mean(losses[-10:])) < float(losses[0]))
    # a traced run reads the host's waiting before the profiler started
    until = mark.get("at_slice", at_close)
    waited = until["data_wait_s"] - mark["at_open"]["data_wait_s"]
    waited_in = mark.get("slice_t", t_close) - t_open
    return harness.Measured(
        correct=finite and close and fell and steps > 0,
        attempted=steps, failed=0 if finite else steps,
        setup_s=t_open - t0,
        end_to_end={"train_mfu": mfu_at(step_s),
                    f"train_{family.ITEM}_per_s": per_step / step_s},
        counters={
            "window_s": elapsed, "steps": steps,
            "window_fresh_compiles": (at_close["fresh_compiles"]
                                      - mark["at_open"]["fresh_compiles"]),
            "data_wait_s": waited, "data_wait_window_s": waited_in,
        },
        spans=log.spans + (spans.collect() if spans else []),
        executors=[sgd.exe],
        xplane=slice_.xplane if slice_ else None,
        notes={
            "steps": steps, "window_s": elapsed,
            f"{family.ITEM}_per_s_whole_window": items / elapsed,
            "mfu_whole_window": mfu_at(elapsed / steps),
            "mfu_median_step": mfu_at(float(np.median(intervals))),
            "step_ms": {q: float(np.percentile(intervals, q) * 1e3)
                        for q in (50, 90, 99, 100)},
            "data_wait_pct_whole_window": 100.0 * (
                at_close["data_wait_s"]
                - mark["at_open"]["data_wait_s"]) / elapsed,
            "flops_per_item": flops_item,
            "first_loss": float(losses[0]),
            "reference_first_loss": first["ref_loss"],
            "loss_tol": mix["loss_tol"],
            "last10_mean_loss": float(np.mean(losses[-10:])),
            "all_finite": finite, "loss_fell": fell,
            "cache": sgd.exe.cache_stats(),
            "compile_seconds": sgd.exe.compile_seconds,
        })
