"""Headline benchmark: ResNet-50 training throughput (images/sec) on one chip.

Baseline: the reference's best published in-tree ResNet-50 training number,
84.08 img/s (MKL-DNN, 2S Xeon Gold 6148 — /root/reference/benchmark/
IntelOptimizedPaddle.md:43-45; its GPU benchmark table has no ResNet entry).
BASELINE.json's north star is images/sec/chip + MFU, so MFU vs the chip's
peak is reported alongside.

One process, which must find a TPU: ``python bench.py`` exits non-zero
without measuring anything when ``jax.devices()[0].platform`` is not
``"tpu"`` — a number from a CPU run is never written under a device
metric's name. The record names the ``platform`` and ``device_kind`` it
ran on, resolves the chip's peak from ``device_kind`` through
``analysis.costmodel.DEVICE_PEAKS`` (an unknown kind is an error), and the
exit code is non-zero if any row raised. Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "extra"}.

The ``bench_*`` functions are the pre-round ad-hoc sweep (ROADMAP S1
replaces them with cells); tests/test_bench_paths.py runs each at toy
size on the CPU mesh as a crash guard only.
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_PER_SEC = 84.08

# Per-image training FLOPs for ResNet-50 @224. The commonly quoted
# "4.1 GFLOPs" is actually GMACs; MFU accounting (and XLA's own
# cost_analysis, which reports 23.9 GFLOP/img for this train step) uses
# 2 FLOPs per MAC: ~8.2 GFLOP forward, x3 for fwd+bwd.
RESNET50_TRAIN_FLOPS_224 = 3 * 2 * 4.09e9


def _peak_flops(device_kind):
    """Dense bf16 peak FLOP/s of ``device_kind``; KeyError when the one
    peaks table does not know the device."""
    from paddle_tpu.analysis.costmodel import device_peaks

    return device_peaks(device_kind)[0]


LSTM_BASELINE_MS = 184.0  # 2xLSTM text classification, bs64 hidden512,
#                           1x K40m (/root/reference/benchmark/README.md:119)


def _time_train_steps(jax, pt, main_prog, startup, loss, feed_np,
                      warmup=3, steps=20):
    """Shared measurement scaffold for the secondary metrics: init, move
    the synthetic batch on-device, warm up, then time ``steps`` async
    dispatches closed by one blocking fetch. Returns seconds/step."""
    import numpy as np

    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    feed = {k: jax.device_put(v) for k, v in feed_np.items()}
    for _ in range(warmup):
        exe.run(main_prog, feed=feed, fetch_list=[loss], scope=scope)
    t0 = time.perf_counter()
    for _ in range(steps):
        out, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                       scope=scope, return_numpy=False)
    np.asarray(out)
    return (time.perf_counter() - t0) / steps


def bench_lstm_step(jax, pt, layers):
    """Secondary metric: stacked-LSTM text-classification train step
    (reference benchmark/paddle/rnn/rnn.py config: bs64, hidden 512),
    ms/batch. Exercises the scan-based recurrent path the way the
    reference's RNN benchmark exercises its fused CUDA cells."""
    import numpy as np

    batch, seqlen, hidden, vocab = 64, 100, 512, 10000
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        words = layers.data("words", shape=[seqlen], dtype="int64")
        label = layers.data("label", shape=[1], dtype="int64")
        emb = layers.embedding(words, size=[vocab, hidden])
        # dynamic_lstm takes the pre-projected [b, T, 4*hidden] input
        # (reference rnn.py: fc + lstmemory per layer)
        x1 = layers.fc(emb, size=4 * hidden, num_flatten_dims=2,
                       bias_attr=False)
        h1, _ = layers.dynamic_lstm(x1, 4 * hidden)
        x2 = layers.fc(h1, size=4 * hidden, num_flatten_dims=2,
                       bias_attr=False)
        h2, _ = layers.dynamic_lstm(x2, 4 * hidden)
        pooled = layers.sequence_pool(h2, "max")
        logits = layers.fc(pooled, size=2)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(
            loss, startup_program=startup)
    rng = np.random.RandomState(0)
    feed = {
        "words": rng.randint(0, vocab, size=(batch, seqlen)).astype("int64"),
        "label": rng.randint(0, 2, size=(batch, 1)).astype("int64"),
    }
    return _time_train_steps(jax, pt, main_prog, startup, loss, feed) * 1e3


def transformer_train_flops(bs, T, d, n_layers, vocab, d_ff=None):
    """Analytic model FLOPs per train step, 2 FLOPs/MAC, fwd x3 for
    fwd+bwd. Counts the in-kernel flash-attention contractions (invisible
    to XLA cost_analysis) at their CAUSAL cost (half the T^2 square)."""
    d_ff = d_ff or 4 * d
    dense = n_layers * (
        2 * bs * T * d * (4 * d)        # fused qkv + out proj
        + 2 * bs * T * d * (2 * d_ff))  # ffn in + out
    attn = n_layers * 2 * bs * T * T * d  # QK^T + PV, causal half
    head = 2 * bs * T * d * vocab
    return 3 * (dense + attn + head)


def bench_transformer_step(jax, pt, layers, models,
                           bs=8, T=2048, vocab=16384, d=1024, L=8, H=8,
                           steps=10, fused_head=False):
    """Secondary metric: GPT-style LM train step in tokens/sec AND MFU —
    the compute-dense path where the >=50% MFU target lives (flash
    attention fwd+bwd in Pallas, fused qkv, fused matmul backward;
    PERF.md). d_head=128 (d1024 / 8 heads): the MXU-native head width.
    No reference baseline exists (the reference predates Transformers).
    Size parameters exist so the CPU test tier can smoke the build/measure
    path at toy shapes (tests/test_bench_paths.py)."""
    import numpy as np
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        if fused_head:
            # chunked head+loss: the [tokens, vocab] logits never
            # materialize (layers.fused_head_cross_entropy)
            h = models.transformer_lm(ids, vocab_size=vocab, d_model=d,
                                      n_layers=L, num_heads=H, max_len=T,
                                      include_head=False)
            loss = layers.mean(layers.fused_head_cross_entropy(
                h, layers.reshape(tgt, shape=[-1, T, 1]),
                num_classes=vocab))
        else:
            logits = models.transformer_lm(ids, vocab_size=vocab,
                                           d_model=d, n_layers=L,
                                           num_heads=H, max_len=T)
            loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.reshape(logits, shape=[-1, vocab]),
                layers.reshape(tgt, shape=[-1, 1])))
        pt.optimizer.AdamOptimizer(learning_rate=1e-4).minimize(
            loss, startup_program=startup)
    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(0, vocab, size=(bs, T)).astype("int64"),
            "tgt": rng.randint(0, vocab, size=(bs, T)).astype("int64")}
    sec = _time_train_steps(jax, pt, main_prog, startup, loss, feed,
                            steps=steps)
    flops = transformer_train_flops(bs, T, d, L, vocab)
    return bs * T / sec, flops / sec


def bench_decode(jax, pt, layers, models, bs=8, Tp=1024, N=128,
                 vocab=16384, d=1024, L=8, H=8, steps=3):
    """Serving metric: KV-cache greedy decode throughput (generated
    tokens/sec) on the stacked transformer — the O(T)/token path
    (ops/pipeline_ops.transformer_stack_generate). No reference analogue
    (the reference predates autoregressive serving)."""
    import numpy as np

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        prompt = layers.data("prompt", shape=[Tp], dtype="int64")
        out_ids = models.transformer_lm_generate(
            prompt, vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
            max_len=Tp + N, max_new_tokens=N)
    rng = np.random.RandomState(0)
    feed = {"prompt": rng.randint(0, vocab, (bs, Tp)).astype("int64")}
    sec = _time_train_steps(jax, pt, prog, startup, out_ids, feed,
                            warmup=1, steps=steps)
    return {"tokens_per_sec": round(bs * N / sec),
            "config": f"bs{bs} prefill{Tp} decode{N} d{d} L{L}"}


def bench_lstm_varlen(jax, pt, layers, batch=64, hidden=512, vocab=10000,
                      mean_len=80, cap=200, steps=20):
    """Variable-length 2xLSTM text classification (the reference RNN
    benchmark's real semantics — /root/reference/benchmark/paddle/rnn/
    rnn.py runs ragged IMDB batches, not fixed-T synthetic ones). Batches
    are padded to the per-batch max; the LoD masking freezes finished rows.
    Reports true-token throughput and the padded-FLOP waste the dense+mask
    design pays for ragged data."""
    import numpy as np
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        words = layers.data("words", shape=[1], dtype="int64", lod_level=1)
        label = layers.data("label", shape=[1], dtype="int64")
        emb = layers.embedding(words, size=[vocab, hidden])
        emb.seq_len = words.seq_len
        x1 = layers.fc(emb, size=4 * hidden, num_flatten_dims=2,
                       bias_attr=False)
        x1.seq_len = words.seq_len
        h1, _ = layers.dynamic_lstm(x1, 4 * hidden)
        x2 = layers.fc(h1, size=4 * hidden, num_flatten_dims=2,
                       bias_attr=False)
        x2.seq_len = words.seq_len
        h2, _ = layers.dynamic_lstm(x2, 4 * hidden)
        pooled = layers.sequence_pool(h2, "max")
        logits = layers.fc(pooled, size=2)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(
            loss, startup_program=startup)

    # IMDB-like ragged lengths (geometric-ish spread, capped);
    # bucketed into one padded batch per step like the reference reader.
    rng = np.random.RandomState(0)
    lengths = np.clip(rng.geometric(1.0 / mean_len, size=batch), 8,
                      cap).astype(np.int32)
    T = int(lengths.max())
    ids = rng.randint(0, vocab, size=(batch, T)).astype("int64")
    feed_np = {
        "words": ids, "words@len": lengths,
        "label": rng.randint(0, 2, size=(batch, 1)).astype("int64"),
    }
    sec = _time_train_steps(jax, pt, main_prog, startup, loss, feed_np,
                            steps=steps)
    true_tokens = int(lengths.sum())
    return {
        "tokens_per_sec": round(true_tokens / sec),
        "ms_per_batch": round(sec * 1e3, 2),
        "max_len": T,
        "padded_flop_waste": round(1.0 - true_tokens / (batch * T), 3),
    }


# Reference 1x K40m training numbers (/root/reference/benchmark/README.md:
# 37, 50; VGG has no GPU row so its CPU MKL-DNN number is used,
# IntelOptimizedPaddle.md:35).
IMAGE_MODEL_BASELINES = {
    "alexnet": 128 / 0.334,     # 334 ms/batch bs128 -> 383 img/s
    "googlenet": 128 / 1.149,   # 1149 ms/batch bs128 -> 111 img/s
    "vgg16": 30.4,              # img/s, CPU MKL-DNN
}

# Reference bs16 MKL-DNN inference numbers
# (/root/reference/benchmark/IntelOptimizedPaddle.md:77,85,94).
INFER_BASELINES = {"vgg19": 96.75, "resnet50": 217.69, "googlenet": 600.94}


def bench_inference(jax, pt, layers, models, name, batch=16, hw=224,
                    steps=30):
    """bs16 inference img/s through the deployment path: build with
    is_test semantics, save_inference_model, load it back, serve. The
    reference benchmarks exactly this surface (paddle/benchmark
    IntelOptimizedPaddle.md "Infer Speed")."""
    import shutil
    import tempfile

    import numpy as np

    build = {
        "resnet50": lambda img: models.resnet_imagenet(
            img, num_classes=1000, depth=50),
        "googlenet": lambda img: models.googlenet(img, num_classes=1000),
        "vgg19": lambda img: models.vgg(img, num_classes=1000, depth=19),
    }[name]
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        images = layers.data("images", shape=[hw, hw, 3])
        logits = build(images)
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    tmp = tempfile.mkdtemp(prefix=f"bench_infer_{name}_")
    try:
        pt.io.save_inference_model(tmp, ["images"], [logits], exe,
                                   main_program=main_prog, scope=scope)
        prog, feeds, fetches = pt.io.load_inference_model(tmp, exe,
                                                          scope=scope)
        rng = np.random.RandomState(0)
        img = jax.device_put(rng.rand(batch, hw, hw, 3).astype("float32"))
        for _ in range(3):
            exe.run(prog, feed={feeds[0]: img}, fetch_list=fetches,
                    scope=scope)
        t0 = time.perf_counter()
        for _ in range(steps):
            out, = exe.run(prog, feed={feeds[0]: img}, fetch_list=fetches,
                           scope=scope, return_numpy=False)
        np.asarray(out)
        sec = (time.perf_counter() - t0) / steps
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {"img_per_sec": round(batch / sec, 1),
            "ms_per_batch": round(sec * 1e3, 3),
            "vs_baseline": round(batch / sec / INFER_BASELINES[name], 1)}


def bench_transpiler(jax, pt, layers, models, name="resnet50", batch=16,
                     hw=224, steps=30, epilogue=True):
    """Transpiled-vs-raw inference through the deployment path: op count,
    compile wall-time, and steady-state latency for the pruned-only
    program vs the same program through transpiler.inference_pipeline()
    (dropout→scale, constant folding, fused-kernel rewrites, BN folding).
    ``epilogue=True`` forces the conv1x1_bn_act fusion on (the
    deployment-tuned path) regardless of --fused_conv_epilogue. The
    transpiler's own wall time is reported separately — it is paid once
    per deployment, not per request."""
    import numpy as np

    build = {
        "resnet50": lambda img: models.resnet_imagenet(
            img, num_classes=1000, depth=50, is_test=True),
        "vgg19": lambda img: models.vgg(img, num_classes=1000, depth=19,
                                        is_test=True),
    }[name]
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        images = layers.data("images", shape=[hw, hw, 3])
        logits = build(images)
    scope = pt.Scope()
    pt.Executor(pt.TPUPlace()).run(startup, scope=scope)
    rng = np.random.RandomState(0)
    img = jax.device_put(rng.rand(batch, hw, hw, 3).astype("float32"))

    raw = pt.io.prune_program(main_prog, ["images"], [logits.name])
    opt_scope = pt.Scope(parent=scope)
    pm = pt.transpiler.inference_pipeline(epilogue=epilogue or None)
    t0 = time.perf_counter()
    opt = pm.run(main_prog.clone(), ["images"], [logits.name],
                 scope=opt_scope)
    transpile_ms = (time.perf_counter() - t0) * 1e3

    def measure(prog, run_scope):
        exe = pt.Executor(pt.TPUPlace())
        t0 = time.perf_counter()
        exe.run(prog, feed={"images": img}, fetch_list=[logits.name],
                scope=run_scope)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps):
            o, = exe.run(prog, feed={"images": img},
                         fetch_list=[logits.name], scope=run_scope,
                         return_numpy=False)
        np.asarray(o)
        return compile_s, (time.perf_counter() - t0) / steps

    raw_compile, raw_step = measure(raw, scope)
    opt_compile, opt_step = measure(opt, opt_scope)
    return {
        "raw_ops": len(raw.global_block.ops),
        "transpiled_ops": len(opt.global_block.ops),
        "transpile_ms": round(transpile_ms, 1),
        "raw_compile_s": round(raw_compile, 3),
        "transpiled_compile_s": round(opt_compile, 3),
        "raw_ms_per_batch": round(raw_step * 1e3, 3),
        "transpiled_ms_per_batch": round(opt_step * 1e3, 3),
        "pass_stats": pm.stats(),
    }


def bench_trace_overhead(jax, pt, layers, models, name="resnet50",
                         batch=8, hw=64, steps=30, warmup=3):
    """Level-1 span-tracing overhead on the bucket-padded serving path:
    the same InferenceEngine batch measured untraced, then with
    trace.enable(level=1) (executor run spans + serving batch spans —
    what a traced production server pays per request). Reported as
    ms/batch both ways plus the relative overhead; PERF.md records the
    number and pins the <5% budget."""
    import numpy as np

    from paddle_tpu import trace
    from paddle_tpu.serving import InferenceEngine

    build = {
        "resnet50": lambda img: models.resnet_imagenet(
            img, num_classes=1000, depth=50, is_test=True),
        "vgg19": lambda img: models.vgg(img, num_classes=1000, depth=19,
                                        is_test=True),
    }[name]
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        images = layers.data("images", shape=[hw, hw, 3])
        logits = build(images)
    scope = pt.Scope()
    pt.Executor(pt.TPUPlace()).run(startup, scope=scope)
    eng = InferenceEngine(program=main_prog, feed_names=["images"],
                          fetch_names=[logits.name], scope=scope,
                          batch_buckets=[batch], transpile=False)
    rng = np.random.RandomState(0)
    feed = {"images": rng.rand(batch, hw, hw, 3).astype("float32")}

    def measure():
        t0 = time.perf_counter()
        for _ in range(steps):
            eng.run(feed)
        return (time.perf_counter() - t0) / steps

    # Interleaved A/B rounds with medians: host clock drift between two
    # long back-to-back phases would otherwise swamp the µs-scale span
    # cost being measured.
    tracer = trace.get_tracer()
    prev_level = tracer.level
    rounds = 3
    untraced_s, traced_s = [], []
    try:
        for _ in range(warmup):
            eng.run(feed)
        n_spans = 0
        for _ in range(rounds):
            trace.disable()
            untraced_s.append(measure())
            trace.enable(level=1)
            tracer.clear()
            traced_s.append(measure())
            n_spans = len(tracer)
    finally:
        tracer.configure(level=prev_level)
    untraced = sorted(untraced_s)[rounds // 2]
    traced = sorted(traced_s)[rounds // 2]
    overhead_pct = (traced - untraced) / untraced * 100.0
    return {
        "untraced_ms_per_batch": round(untraced * 1e3, 3),
        "traced_ms_per_batch": round(traced * 1e3, 3),
        "overhead_pct": round(overhead_pct, 2),
        "spans_recorded": n_spans,
    }


def bench_train_pipeline(jax, pt, layers, batch=256, dim=1024, depth=4,
                         steps=30, warmup=5, rounds=3):
    """Sync vs async trainer-loop A/B: the same SGD model trained through
    ``train(async_depth=1)`` and ``train(async_depth=N)``, interleaved
    rounds with medians (same drift defense as bench_trace_overhead).
    Reports ms/step for both loops plus the host gap — dispatch-to-
    dispatch wall time minus the pure-device step time (measured with a
    device-resident feed, async dispatch, one closing fetch). The sync
    loop pays batch stacking + a blocking fetch + numpy readback on every
    step's critical path; the async loop hides them behind the device,
    which is the tentpole contract (PERF.md 'overlapped training
    pipeline')."""
    import numpy as np

    from paddle_tpu.trainer import SGD

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        x = layers.data("x", shape=[dim])
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.fc(x, size=dim, act="relu")
        h = layers.fc(h, size=dim, act="relu")
        logits = layers.fc(h, size=10)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        trainer = SGD(cost=loss,
                      optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.1),
                      feed_list=[x, y], place=pt.TPUPlace(),
                      scope=pt.Scope())
    rng = np.random.RandomState(0)
    xs = rng.rand(batch, dim).astype("float32")
    ys = rng.randint(0, 10, size=(batch, 1)).astype("int64")
    rows = [(xs[i], ys[i]) for i in range(batch)]

    def reader():
        for _ in range(steps):
            yield rows

    trainer._init_params()
    quiet = lambda e: None  # noqa: E731 - no log spam in the bench

    def measure(async_depth):
        t0 = time.perf_counter()
        trainer.train(reader, num_passes=1, event_handler=quiet,
                      async_depth=async_depth)
        return (time.perf_counter() - t0) / steps

    # Pure-device step time: device-resident feed, async dispatch, one
    # blocking fetch closing the window (the bench harness idiom) — the
    # subtrahend for the host-gap numbers.
    feed_dev = {"x": jax.device_put(xs), "y": jax.device_put(ys)}
    for _ in range(warmup):
        trainer.exe.run(main_prog, feed=feed_dev, fetch_list=[loss],
                        scope=trainer.scope)
    t0 = time.perf_counter()
    for _ in range(steps):
        out, = trainer.exe.run(main_prog, feed=feed_dev, fetch_list=[loss],
                               scope=trainer.scope, return_numpy=False)
    np.asarray(out)
    device_s = (time.perf_counter() - t0) / steps

    measure(1)          # warm both loop paths (compiles already cached)
    measure(depth)
    sync_s, async_s = [], []
    for _ in range(rounds):
        sync_s.append(measure(1))
        async_s.append(measure(depth))
    sync = sorted(sync_s)[rounds // 2]
    asynd = sorted(async_s)[rounds // 2]
    return {
        "sync_ms_per_step": round(sync * 1e3, 3),
        "async_ms_per_step": round(asynd * 1e3, 3),
        "device_ms_per_step": round(device_s * 1e3, 3),
        "host_gap_sync_ms": round((sync - device_s) * 1e3, 3),
        "host_gap_async_ms": round((asynd - device_s) * 1e3, 3),
        "async_depth": depth,
        "speedup_pct": round((sync - asynd) / sync * 100.0, 2),
    }


def bench_goodput(jax, pt, layers, batch=256, dim=1024, depth=3,
                  steps=30, warmup=5, rounds=3):
    """Goodput-accounting overhead A/B: the same SGD model trained
    through ``train(async_depth=3)`` with the GoodputMeter off
    (``goodput=False``, the bare loop) and on (a fresh meter per pass —
    bucket timers + per-step MFU on the dispatch/resolve path),
    interleaved rounds with medians (the drift defense the other
    trainer benches use). The observability contract is
    overhead_pct < 1% — attribution must be free enough to leave on in
    production."""
    import numpy as np

    from paddle_tpu.trainer import SGD

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        x = layers.data("x", shape=[dim])
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.fc(x, size=dim, act="relu")
        h = layers.fc(h, size=dim, act="relu")
        logits = layers.fc(h, size=10)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        trainer = SGD(cost=loss,
                      optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.1),
                      feed_list=[x, y], place=pt.TPUPlace(),
                      scope=pt.Scope())
    rng = np.random.RandomState(0)
    xs = rng.rand(batch, dim).astype("float32")
    ys = rng.randint(0, 10, size=(batch, 1)).astype("int64")
    rows = [(xs[i], ys[i]) for i in range(batch)]

    def reader():
        for _ in range(steps):
            yield rows

    trainer._init_params()
    quiet = lambda e: None  # noqa: E731 - no log spam in the bench

    def measure(goodput):
        t0 = time.perf_counter()
        trainer.train(reader, num_passes=1, event_handler=quiet,
                      async_depth=depth, goodput=goodput)
        return (time.perf_counter() - t0) / steps

    measure(False)      # warm both paths (compiles already cached)
    measure(None)
    off_s, on_s = [], []
    for _ in range(rounds):
        off_s.append(measure(False))
        on_s.append(measure(None))
    off = sorted(off_s)[rounds // 2]
    on = sorted(on_s)[rounds // 2]
    snap = trainer.goodput.snapshot() if trainer.goodput else {}

    # Direct per-step meter cost: the exact op sequence one async step
    # performs (timed region per dispatch + resolve, bucket accounts,
    # MFU update, wall deque), microbenched in a tight loop. Immune to
    # the scheduler noise that can swamp the A/B on a busy host — the
    # honest numerator for the <1% always-on budget.
    from collections import deque

    from paddle_tpu.trace import GoodputMeter
    probe = GoodputMeter()
    probe.set_program_flops(1e9)
    walls = deque(maxlen=32)
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        t_d = time.perf_counter()           # dispatch: data-wait probe
        probe.account("data_wait", time.perf_counter() - t_d)
        with probe.measure("recovery_rollback"):
            pass
        t_r = time.perf_counter()           # dispatch wall split
        probe.account("fresh_compile", 0.0)
        probe.account("host_dispatch", time.perf_counter() - t_r)
        t_v = time.perf_counter()           # resolve
        probe.account("device_compute", time.perf_counter() - t_v)
        probe.note_step(1e-3)
        walls.append(1e-3)
    meter_us = (time.perf_counter() - t0) / n * 1e6
    return {
        "off_ms_per_step": round(off * 1e3, 3),
        "on_ms_per_step": round(on * 1e3, 3),
        "overhead_pct": round((on - off) / off * 100.0, 2),
        "meter_us_per_step": round(meter_us, 2),
        "meter_overhead_pct": round(meter_us / (off * 1e6) * 100.0, 3),
        "async_depth": depth,
        "goodput_fraction": snap.get("goodput"),
        "buckets_attributed": sum(
            1 for v in (snap.get("buckets") or {}).values() if v > 0),
    }


def bench_checkpoint(jax, pt, layers, batch=64, dim=512, steps=24, every=4,
                     rounds=3):
    """Checkpoint-stall A/B: the same SGD model trained with no
    checkpointing, with synchronous checkpointing (snapshot + npz write +
    md5 on the step critical path), and with background checkpointing
    (only the device->host snapshot stalls; serialization runs on the
    writer thread). Interleaved rounds with medians (the drift defense
    the other trainer benches use). The resilience contract is
    background_overhead_pct << sync_overhead_pct — preemption-safety
    priced in host-copy time, not disk time."""
    import shutil
    import tempfile

    import numpy as np

    from paddle_tpu.resilience import CheckpointConfig
    from paddle_tpu.trainer import SGD

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        x = layers.data("x", shape=[dim])
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.fc(x, size=dim, act="relu")
        h = layers.fc(h, size=dim, act="relu")
        logits = layers.fc(h, size=10)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        trainer = SGD(cost=loss,
                      optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.1),
                      feed_list=[x, y], place=pt.TPUPlace(),
                      scope=pt.Scope())
    rng = np.random.RandomState(0)
    xs = rng.rand(batch, dim).astype("float32")
    ys = rng.randint(0, 10, size=(batch, 1)).astype("int64")
    rows = [(xs[i], ys[i]) for i in range(batch)]

    def reader():
        for _ in range(steps):
            yield rows

    trainer._init_params()
    quiet = lambda e: None  # noqa: E731 - no log spam in the bench
    workdir = tempfile.mkdtemp(prefix="ptckpt_")

    from paddle_tpu import profiler as prof

    def _stall_total_s():
        d = prof.global_stat.as_dict(prefix="ckpt/stall")
        return d.get("ckpt/stall", {}).get("total_ms", 0.0) / 1e3

    def measure(background):
        ckpt = None
        if background is not None:
            # resume=False: each round trains from its live scope, never
            # from the previous round's files; save_final off so only the
            # periodic cadence is priced
            ckpt = CheckpointConfig(
                os.path.join(workdir, f"bg{int(background)}"),
                every_n_steps=every, keep=2, background=background,
                resume=False, save_final=False,
                install_signal_handlers=False)
        stall0 = _stall_total_s()
        t0 = time.perf_counter()
        trainer.train(reader, num_passes=1, event_handler=quiet,
                      checkpoint=ckpt)
        wall = (time.perf_counter() - t0) / steps
        return wall, (_stall_total_s() - stall0) / steps

    try:
        for m in (None, False, True):  # warm compiles + first-write paths
            measure(m)
        base_s, sync_s, bg_s = [], [], []
        for _ in range(rounds):
            base_s.append(measure(None))
            sync_s.append(measure(False))
            bg_s.append(measure(True))
        med = lambda xs, i: sorted(x[i] for x in xs)[rounds // 2]  # noqa: E731
        base = med(base_s, 0)
        sync, sync_stall = med(sync_s, 0), med(sync_s, 1)
        bg, bg_stall = med(bg_s, 0), med(bg_s, 1)
        ckpt_bytes = 0
        for dirpath, _, files in os.walk(workdir):
            ckpt_bytes = max([ckpt_bytes] + [
                os.path.getsize(os.path.join(dirpath, f))
                for f in files if f.endswith(".npz")])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    # Two planes: *_overhead_pct is end-to-end wall per step (where the
    # background write shares the step loop's core, wall cannot improve
    # — total work is conserved); *_stall_pct is the time the
    # STEP LOOP was blocked inside the save path (snapshot only, for
    # background) — the step-latency cost on a host with spare cores,
    # and the resilience acceptance metric (<10% background stall).
    return {
        "base_ms_per_step": round(base * 1e3, 3),
        "sync_ms_per_step": round(sync * 1e3, 3),
        "background_ms_per_step": round(bg * 1e3, 3),
        "sync_overhead_pct": round((sync - base) / base * 100.0, 2),
        "background_overhead_pct": round((bg - base) / base * 100.0, 2),
        "sync_stall_ms_per_step": round(sync_stall * 1e3, 3),
        "background_stall_ms_per_step": round(bg_stall * 1e3, 3),
        "sync_stall_pct": round(sync_stall / base * 100.0, 2),
        "background_stall_pct": round(bg_stall / base * 100.0, 2),
        "every_n_steps": every,
        "ckpt_bytes": int(ckpt_bytes),
    }


def bench_memplan(jax, pt, layers, models, batch=8, hw=32):
    """Static memory/roofline estimator vs XLA ground truth: for the
    resnet50 and transformer train-step programs, measure (a) the
    analyzer's wall time (it must stay a build-time cost, not a compile-
    scale one) and (b) estimated HBM bytes vs the compiled computation's
    ``cost_analysis()['bytes accessed']`` — the drift metric that keeps
    the cost model honest release over release (PERF.md pins the
    ResNet-50 bs256 figure at 78.4 GB)."""
    import numpy as np

    from paddle_tpu import analysis

    def cost_analysis_bytes(exe, prog, feed, fetches, scope):
        fn, args = exe.as_function(prog, feed, fetches, scope=scope)
        compiled = jax.jit(fn).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return float(ca.get("bytes accessed", 0.0))

    def one(name, build):
        prog, startup, loss, feed = build()
        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        exe.run(startup, scope=scope)
        t0 = time.perf_counter()
        mem = analysis.analyze_memory(prog, list(feed), [loss.name],
                                      scope=scope, batch_size=batch)
        est_wall = time.perf_counter() - t0
        actual = cost_analysis_bytes(exe, prog, feed, [loss], scope)
        est = mem.total_hbm_bytes
        return {
            "estimator_ms": round(est_wall * 1e3, 2),
            "ops": len(prog.global_block.ops),
            "est_bytes": round(est),
            "cost_analysis_bytes": round(actual),
            "est_over_actual": (round(est / actual, 3) if actual else None),
            "peak_bytes": round(mem.peak_bytes),
            "est_step_ms": round(mem.estimated_step_seconds() * 1e3, 3),
        }

    rng = np.random.RandomState(0)

    def build_resnet():
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            images = layers.data("images", shape=[hw, hw, 3])
            label = layers.data("label", shape=[1], dtype="int64")
            logits = models.resnet_imagenet(images, num_classes=100,
                                            depth=50)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, label))
            pt.optimizer.MomentumOptimizer(
                learning_rate=0.1, momentum=0.9).minimize(
                loss, startup_program=startup)
        feed = {"images": rng.rand(batch, hw, hw, 3).astype("float32"),
                "label": rng.randint(0, 100, size=(batch, 1))
                .astype("int64")}
        return prog, startup, loss, feed

    def build_transformer():
        T, V = 64, 512
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            ids = layers.data("ids", shape=[T], dtype="int64")
            tgt = layers.data("tgt", shape=[T], dtype="int64")
            logits = models.transformer_lm(
                ids, vocab_size=V, d_model=128, n_layers=2, num_heads=4,
                max_len=T)
            loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.reshape(logits, shape=[-1, V]),
                layers.reshape(tgt, shape=[-1, 1])))
            pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(
                loss, startup_program=startup)
        feed = {"ids": rng.randint(0, V, size=(batch, T)).astype("int64"),
                "tgt": rng.randint(0, V, size=(batch, T)).astype("int64")}
        return prog, startup, loss, feed

    return {"resnet50": one("resnet50", build_resnet),
            "transformer": one("transformer", build_transformer)}


def bench_fleet(jax, pt, layers, n_replicas=3, n_requests=96,
                slow_delay_s=0.06, storm_threads=4):
    """Fleet availability + tail latency under injected chaos, hedging
    A/B. Each leg builds a fresh 3-replica fleet over a small warmed
    classifier, installs a FaultPlan that hard-crashes replica 1 and
    slow-injects replica 2, and storms it; reports availability (ok
    fraction), client P50/P99, and the absorb counters. The hedged leg
    must hold P99 near the healthy baseline while the unhedged leg eats
    the slow replica's delay — the A/B that prices hedging. Host-side
    (router/thread plane)."""
    import threading

    from paddle_tpu.resilience import FaultPlan
    from paddle_tpu.serving import Fleet, InferenceEngine

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        x = layers.data("x", shape=[16])
        out = layers.fc(layers.fc(x, size=32, act="relu"), size=4)
    exe = pt.Executor(pt.CPUPlace())

    def engine():
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        return InferenceEngine(
            program=main_prog, feed_names=["x"], fetch_names=[out.name],
            scope=scope, batch_buckets=(2, 4, 8), place=pt.CPUPlace())

    def leg(hedge):
        plan = (FaultPlan()
                .at(step=1, kind="replica_crash")
                .at(step=2, kind="slow_replica", delay_s=slow_delay_s))
        fleet = Fleet([engine() for _ in range(n_replicas)],
                      hedge=hedge, hedge_delay_ms=20,
                      breaker={"failure_threshold": 2,
                               "recovery_s": 0.5})
        lat, errors = [], []
        lock = threading.Lock()
        rng = np.random.RandomState(0)
        feeds = [rng.rand(16).astype(np.float32)
                 for _ in range(n_requests)]

        def storm(rows):
            for row in rows:
                t0 = time.perf_counter()
                try:
                    fleet.submit({"x": row}, timeout_ms=15_000).result(
                        timeout=20)
                    dt = time.perf_counter() - t0
                    with lock:
                        lat.append(dt)
                except Exception as exc:  # noqa: BLE001 - availability
                    with lock:
                        errors.append(repr(exc)[:100])

        with plan.active(), fleet:
            storm(feeds[:2 * n_replicas])  # warm every replica
            lat.clear()
            t0 = time.perf_counter()
            work = feeds[2 * n_replicas:]
            per = max(1, len(work) // storm_threads)
            threads = [threading.Thread(
                target=storm, args=(work[i * per:(i + 1) * per],))
                for i in range(storm_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            counters = fleet.metrics.snapshot()["counters"]
        lat.sort()

        def pq(q):
            return (lat[min(len(lat) - 1, int(round(q * (len(lat) - 1))))]
                    * 1e3 if lat else None)

        total = len(lat) + len(errors)
        return {
            "availability": round(len(lat) / max(1, total), 4),
            "ok": len(lat), "failed": len(errors),
            "p50_ms": round(pq(0.50), 2), "p99_ms": round(pq(0.99), 2),
            "wall_s": round(wall, 3),
            "hedges": counters.get("hedges", 0),
            "hedge_wins": counters.get("hedge_wins", 0),
            "retries": counters.get("retries", 0),
            "breaker_opens": counters.get("breaker_opens", 0),
            "sheds": counters.get("sheds", 0),
        }

    hedged = leg(hedge=True)
    unhedged = leg(hedge=False)
    return {
        "replicas": n_replicas,
        "requests": n_requests,
        "slow_delay_ms": round(slow_delay_s * 1e3, 1),
        "hedged": hedged,
        "unhedged": unhedged,
        "p99_speedup": (round(unhedged["p99_ms"] / hedged["p99_ms"], 2)
                        if hedged["p99_ms"] else None),
    }


def bench_online(jax, pt, layers, vocab=1_000_000, embed_dim=16, slots=8,
                 batch=128, steps=8, warmup=3, n_replicas=2,
                 storm_threads=3, storm_s=0.15):
    """Online-learning plane witness (ISSUE 13): (a) dense-vs-sparse
    optimizer step time at V=1e6 with a batch touching <=1% of rows,
    plus rows-touched scaling (quarter batch -> sparse step cost falls,
    dense stays flat) and the static-memory evidence that the sparse
    step never materializes a [V, D] gradient; (b) publish-swap latency
    of one rolling weight update under live traffic (zero failed
    requests is part of the record)."""
    import threading

    import numpy as np

    from paddle_tpu import analysis

    def build(is_sparse):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            ids = layers.data("ids", shape=[slots], dtype="int64")
            emb = layers.embedding(ids, size=[vocab, embed_dim],
                                   is_sparse=is_sparse)
            loss = layers.mean(emb)
            pt.optimizer.AdagradOptimizer(learning_rate=0.05).minimize(
                loss, startup_program=startup)
        return main, startup, loss

    rng = np.random.RandomState(0)

    def measure(is_sparse, b):
        main, startup, loss = build(is_sparse)
        feed = {"ids": rng.randint(0, vocab,
                                   size=(b, slots)).astype("int64")}
        sec = _time_train_steps(jax, pt, main, startup, loss, feed,
                                warmup=warmup, steps=steps)
        mem = analysis.analyze_memory(main, ["ids"], [loss.name],
                                      batch_size=b)
        return sec, mem.peak_bytes

    dense_sec, dense_peak = measure(False, batch)
    sparse_sec, sparse_peak = measure(True, batch)
    sparse_quarter_sec, _ = measure(True, max(batch // 4, 1))

    # (b) publish-swap latency under live traffic
    import tempfile

    from paddle_tpu.online import Publisher
    from paddle_tpu.serving import InferenceEngine
    from paddle_tpu.serving.fleet import Fleet

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[8])
        out_v = layers.fc(layers.fc(x, size=32, act="relu"), size=4)

    def engine(seed):
        scope = pt.Scope()
        startup.random_seed = seed
        pt.Executor(pt.TPUPlace()).run(startup, scope=scope)
        return InferenceEngine(program=main, feed_names=["x"],
                               fetch_names=[out_v.name], scope=scope,
                               batch_buckets=(4,), place=pt.CPUPlace())

    ckdir = tempfile.mkdtemp(prefix="bench-online-ck")
    src_scope = pt.Scope()
    startup.random_seed = 99
    pt.Executor(pt.TPUPlace()).run(startup, scope=src_scope)
    pt.checkpoint.save_checkpoint(ckdir, scope=src_scope, step=1)

    engines = [engine(s) for s in range(n_replicas)]
    fleet = Fleet(engines, hedge=False)
    pub = Publisher(fleet, ckdir)
    stop, failed, served = threading.Event(), [], [0]

    def storm():
        while not stop.is_set():
            try:
                fleet.submit({"x": np.random.rand(8).astype(np.float32)},
                             timeout_ms=10_000).result(timeout=15)
                served[0] += 1
            except Exception as exc:  # noqa: BLE001 - the record
                failed.append(repr(exc))

    with fleet:
        for eng in engines:
            eng.run({"x": np.ones((1, 8), np.float32)})
        threads = [threading.Thread(target=storm)
                   for _ in range(storm_threads)]
        for t in threads:
            t.start()
        time.sleep(storm_s)
        published = pub.poll_once()
        time.sleep(storm_s)
        stop.set()
        for t in threads:
            t.join()

    return {
        "vocab": vocab,
        "rows_touched_fraction": round(batch * slots / vocab, 5),
        "dense_step_ms": round(dense_sec * 1e3, 3),
        "sparse_step_ms": round(sparse_sec * 1e3, 3),
        "sparse_speedup": round(dense_sec / sparse_sec, 2),
        "sparse_quarter_batch_ms": round(sparse_quarter_sec * 1e3, 3),
        "dense_peak_mb": round(dense_peak / 1e6, 2),
        "sparse_peak_mb": round(sparse_peak / 1e6, 2),
        "publish_generation": published,
        "publish_swap_s": (round(pub.last_publish_s, 4)
                           if pub.last_publish_s else None),
        "storm_served": served[0],
        "storm_failed": len(failed),
    }


def bench_elastic(jax, pt, layers, n_tasks=4, records_per_task=32,
                  batch=16):
    """Elastic-training chaos witness (ISSUE 15): a 3-trainer relay over
    one master queue — T1 is fenced mid-run as a zombie (its last acks
    rejected by token), T2 hard-crashes holding a claim, T3 (T2's
    reincarnation) rejoins and drains the pass — priced as recovery
    wall time (fence -> successor's first trained step) and steps
    retrained, with the exactly-once check (every task acked once, zero
    discarded, final params bitwise vs an uninterrupted single-trainer
    run) part of the record. Host/control-plane bench."""
    import re
    import tempfile

    import numpy as np

    from paddle_tpu import dataset
    from paddle_tpu.master import MasterServer
    from paddle_tpu.online import StreamingTrainer
    from paddle_tpu.resilience import (CheckpointConfig, FaultPlan,
                                       SimulatedCrash)

    VOCAB = 128
    SLOTS = dataset.ctr.SLOTS
    DD = dataset.ctr.DENSE_DIM

    def build(seed=7):
        main, startup = pt.Program(), pt.Program()
        startup.random_seed = seed
        with pt.program_guard(main, startup):
            ids = layers.data("ids", shape=[SLOTS], dtype="int64")
            dense = layers.data("dense", shape=[DD])
            label = layers.data("label", shape=[1])
            logit = pt.models.wide_deep(ids, dense, vocab_size=VOCAB,
                                        embed_dim=4, hidden_sizes=(8,))
            loss, _ = pt.models.wide_deep_loss(logit, label)
            sgd = pt.trainer.SGD(
                loss, pt.optimizer.SGDOptimizer(learning_rate=0.05),
                [ids, dense, label], scope=pt.Scope())
        return sgd

    descs = dataset.ctr.task_descs(n_tasks,
                                   records_per_shard=records_per_task,
                                   vocab=VOCAB)
    every = max(records_per_task // batch, 1)  # generation per task

    def stream(addr, ck, bundle, trainer_id, fault=None, first_step=None):
        st = StreamingTrainer(
            bundle, addr, dataset.ctr.task_reader, task_descs=descs,
            batch_size=batch,
            checkpoint=CheckpointConfig(ck, every_n_steps=every,
                                        background=False),
            max_passes=1, trainer_id=trainer_id, rejoin=False,
            install_signal_handlers=False)
        handler = None
        if first_step is not None:
            def handler(e, _seen=[False]):  # noqa: B006 - latch
                if not _seen[0] and isinstance(e, pt.event.EndIteration):
                    _seen[0] = True
                    first_step.append(time.perf_counter())
        crashed = False
        ctx = fault.active() if fault is not None else None
        try:
            if ctx is not None:
                ctx.__enter__()
            try:
                st.run(event_handler=handler)
            except SimulatedCrash:
                crashed = True
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
        return st, crashed

    # uninterrupted single-trainer baseline
    srv_u = MasterServer(timeout_s=30, port=0)
    addr_u = srv_u.start()
    bu = build()
    t0 = time.perf_counter()
    st_u, _ = stream(addr_u, tempfile.mkdtemp(prefix="el-u"), bu, "solo")
    base_wall = time.perf_counter() - t0
    srv_u.stop()

    # the chaos relay
    srv = MasterServer(timeout_s=30, port=0)
    addr = srv.start()
    ck = tempfile.mkdtemp(prefix="el-c")
    b = build()
    st1, _ = stream(addr, ck, b, "host-a",
                    fault=FaultPlan().at(step=2, kind="zombie_ack"))
    st2, crashed = stream(addr, ck, b, "host-b",
                          fault=FaultPlan().at(step=2,
                                               kind="trainer_crash"))
    crash_t = time.perf_counter()
    first = []
    # recovery: crash -> the reincarnation's first trained step (fence
    # of the dead lease + front-requeue + checkpoint restore + resume)
    st3, _ = stream(addr, ck, b, "host-b", first_step=first)
    counts = st3.state()["queue"]
    srv.stop()

    def okeys(scope):
        def key(name):
            m = re.search(r"_(\d+)$", name)
            return (0, int(m.group(1))) if m else (1, name)
        return sorted(scope.keys(), key=key)

    bitwise = all(
        np.array_equal(np.asarray(bu.scope.get(a)),
                       np.asarray(b.scope.get(bk)))
        for a, bk in zip(okeys(bu.scope), okeys(b.scope)))
    relay_steps = st1.steps + st2.steps + st3.steps
    acks = (st1.tasks_finished + st2.tasks_finished + st3.tasks_finished)
    return {
        "tasks": n_tasks,
        "recovery_s": round(first[0] - crash_t, 4) if first else None,
        "steps_lost": relay_steps - st_u.steps,
        "acks_exactly_once": acks == n_tasks,
        "zombie_acks_rejected": counts["zombie_acks_rejected"],
        "lease_expired_total": counts["lease_expired_total"],
        "discarded": counts["discarded"],
        "bitwise_vs_uninterrupted": bool(bitwise),
        "uninterrupted_wall_s": round(base_wall, 3),
    }


def bench_feedback_loop(jax, pt, layers, vocab=512, n_requests=192,
                        batch=32, storm_threads=2):
    """Feedback-loop witness (PR 17): (a) serving-side impression-hook
    overhead — the hot path is one bounded-deque append per completed
    request, priced directly against the request's own service time
    (<1% is the acceptance pin) and cross-checked with an attached-vs-
    detached request storm A/B; (b) loop freshness under storm — wall
    time from the first served impression to the trained generation
    PUBLISHED back into the same live fleet, with the zero-failed-
    requests count part of the record; (c) the capacity-bounded a2a
    embedding exchange: modeled interconnect bytes vs the gather path
    (cut ~= n_shards; bitwise parity is pinned on the CPU mesh in
    tests/test_feedback.py). Host/control-plane bench."""
    import tempfile
    import threading

    import numpy as np

    from paddle_tpu import io
    from paddle_tpu.dataset import ctr
    from paddle_tpu.feedback import (Compactor, FeedbackHook,
                                     ImpressionLog, OutcomeJoiner,
                                     task_reader)
    from paddle_tpu.master import MasterClient, MasterServer
    from paddle_tpu.online import Publisher, StreamingTrainer
    from paddle_tpu.parallel.sharded_embedding import exchange_bytes
    from paddle_tpu.resilience import CheckpointConfig
    from paddle_tpu.serving import InferenceEngine
    from paddle_tpu.serving.fleet import Fleet

    main, startup = pt.Program(), pt.Program()
    startup.random_seed = 11
    with pt.program_guard(main, startup):
        ids_v = layers.data("ids", shape=[ctr.SLOTS], dtype="int64")
        dense_v = layers.data("dense", shape=[ctr.DENSE_DIM])
        label_v = layers.data("label", shape=[1])
        logit = pt.models.wide_deep(ids_v, dense_v, vocab_size=vocab,
                                    embed_dim=4, hidden_sizes=(8,))
        loss, prob = pt.models.wide_deep_loss(logit, label_v)
        sgd = pt.trainer.SGD(
            loss, pt.optimizer.AdagradOptimizer(learning_rate=0.05),
            [ids_v, dense_v, label_v], scope=pt.Scope())
    serve_prog = io.prune_program(main, ["ids", "dense"], [prob.name])

    def engine(seed):
        scope = pt.Scope()
        startup.random_seed = seed
        pt.Executor(pt.TPUPlace()).run(startup, scope=scope)
        return InferenceEngine(program=serve_prog,
                               feed_names=["ids", "dense"],
                               fetch_names=[prob.name], scope=scope,
                               batch_buckets=(4,), place=pt.CPUPlace())

    workdir = tempfile.mkdtemp(prefix="bench-feedback")
    log_dir = os.path.join(workdir, "impressions")
    joined_dir = os.path.join(workdir, "joined")
    ckdir = os.path.join(workdir, "ck")
    rng = np.random.RandomState(0)
    ids_all, dense_all, label_all = ctr._impressions(rng, n_requests,
                                                     vocab)

    def storm_rows(fleet, n, collect=None):
        failed = []

        def worker(tid):
            for i in range(tid, n, storm_threads):
                try:
                    fut = fleet.submit({"ids": ids_all[i],
                                        "dense": dense_all[i]},
                                       timeout_ms=20_000)
                    fut.result(timeout=30)
                    if collect is not None:
                        collect.append((fut.request_id, i))
                except Exception as exc:  # noqa: BLE001 - the record
                    failed.append(repr(exc))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(storm_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, failed

    engines = [engine(3), engine(4)]
    fleet = Fleet(engines, hedge=False)
    log = ImpressionLog(log_dir, segment_records=64, flush_s=0.005)
    joiner = OutcomeJoiner(log_dir, joined_dir, window_s=0.05,
                           park_ttl_s=30.0, segment_records=64)
    hook = FeedbackHook(log, joiner=joiner)

    srv = MasterServer(timeout_s=30, port=0)
    addr = srv.start()
    with fleet:
        for eng in engines:
            eng.run({"ids": np.zeros((1, ctr.SLOTS), np.int64),
                     "dense": np.ones((1, ctr.DENSE_DIM), np.float32)})
        # (a) hook overhead: detached baseline vs attached storm, plus
        # the direct hot-path price of on_served itself
        plain_s, f0 = storm_rows(fleet, n_requests)
        fleet.attach_feedback(hook)
        t_loop0 = time.time()
        served = []
        hooked_s, f1 = storm_rows(fleet, n_requests, collect=served)
        row = {"ids": ids_all[0], "dense": dense_all[0]}
        res = [np.zeros((1, 1), np.float32)]
        scratch = ImpressionLog(os.path.join(workdir, "scratch"),
                                segment_records=4096, flush_s=60.0)
        scratch_hook = FeedbackHook(scratch)
        reps, t0 = 2000, time.perf_counter()
        for i in range(reps):
            scratch_hook.on_served(f"bench-{i}", row, res)
        hook_us = (time.perf_counter() - t0) / reps * 1e6
        scratch.close()
        req_ms_plain = plain_s / n_requests * 1e3
        req_ms_hooked = hooked_s / n_requests * 1e3

        # (b) the loop closes under storm: join -> feed -> train ->
        # publish, while background traffic keeps hitting the fleet
        log.seal()
        for rid, i in served:
            if label_all[i, 0] > 0.5:
                joiner.post_outcome(rid, 1.0)
        joiner.poll_once()
        time.sleep(0.1)
        joiner.poll_once()
        joiner.seal()
        stop = threading.Event()
        bg_failed, bg_served = [], [0]

        def bg_storm():
            while not stop.is_set():
                try:
                    fleet.submit({"ids": ids_all[0],
                                  "dense": dense_all[0]},
                                 timeout_ms=10_000).result(timeout=15)
                    bg_served[0] += 1
                except Exception as exc:  # noqa: BLE001 - the record
                    bg_failed.append(repr(exc))

        bg = [threading.Thread(target=bg_storm)
              for _ in range(storm_threads)]
        for t in bg:
            t.start()
        client = MasterClient(addr)
        comp = Compactor(joined_dir)
        descs = comp.enqueue(client)
        st = StreamingTrainer(
            sgd, addr, task_reader, task_descs=None, batch_size=batch,
            checkpoint=CheckpointConfig(ckdir, every_n_steps=8,
                                        background=False),
            max_passes=1)
        stats = st.run()
        pub = Publisher(fleet, ckdir)
        published = pub.poll_once()
        freshness_s = time.time() - t_loop0
        stop.set()
        for t in bg:
            t.join()
        client.close()
    log.close()
    srv.stop()

    # (c) capacity-bounded a2a vs gather: modeled exchange bytes for a
    # merged 4096-row stream of D=16 float32 values over 8 vocab shards
    n, nmp, width = 4096, 8, 4 + 16 * 4   # id lane + value lanes
    bw = exchange_bytes(n, nmp, width, capacity_factor=1.0)
    bw2 = exchange_bytes(n, nmp, width, capacity_factor=2.0)
    js = joiner.stats()
    return {
        "hook_on_served_us": round(hook_us, 2),
        "request_ms_detached": round(req_ms_plain, 3),
        "request_ms_attached": round(req_ms_hooked, 3),
        # the pin: the hot-path append is <1% of the request's own
        # service time (the storm A/B is the noisy cross-check)
        "hook_overhead_pct": round(
            hook_us / 1e3 / req_ms_plain * 100, 3),
        "storm_ab_delta_pct": round(
            (hooked_s - plain_s) / plain_s * 100, 2),
        "storm_failed": len(f0) + len(f1) + len(bg_failed),
        "loop_examples": js["joined"] + js["expired_negatives"],
        "loop_joined": js["joined"],
        "loop_expired_negatives": js["expired_negatives"],
        "segments_fed": len(descs),
        "trained_steps": stats["steps"],
        "published_generation": published,
        "freshness_s": round(freshness_s, 3),
        "bg_served_during_train": bg_served[0],
        "a2a_gather_bytes": bw["gather"],
        "a2a_bytes_cap1": bw["a2a"],
        "a2a_bytes_cap2": bw2["a2a"],
        "a2a_cut_x": round(bw["gather"] / bw["a2a"], 2),
    }


def bench_decode_platform(jax, pt, layers, models, tmax=512, page_size=16,
                          slots=8, prompt_len=24, max_new=16,
                          n_requests=16, d=32, L=2, H=4, vocab=128,
                          beam_k=4, beam_new=12):
    """Decode-platform A/Bs on the paged engine.

    (a) **Sampled-vs-greedy overhead**: the same workload served all-
    greedy vs a mixed batch (greedy + temperature + top-p + top-k rows)
    through the per-request sampling plane — the delta prices the
    per-row sort/filter/categorical inside the one compiled step (and
    pins that the mix adds ZERO fresh compiles).
    (b) **Beam-K page bytes**: beam search as refcounted paged forks vs
    the dense K-copy baseline (K independent sequences of the same
    horizon) — forked beams share the prompt's pages, so the pool
    high-water is sub-linear in K.
    """
    from paddle_tpu.decoding import SamplingParams
    from paddle_tpu.serving import GenerationEngine, LMSpec

    spec = LMSpec(vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
                  max_len=tmax)

    def lm_scope(seed=7):
        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            p = layers.data("p_init", shape=[8], dtype="int64")
            models.transformer_lm_generate(
                p, vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
                max_len=tmax, max_new_tokens=1)
        startup.random_seed = seed
        exe.run(startup, scope=scope)
        return scope

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, (prompt_len,)).astype("int64")
               for _ in range(n_requests)]
    policies = [None,
                SamplingParams(temperature=0.8, seed=11),
                SamplingParams(temperature=1.0, top_p=0.9, seed=12),
                SamplingParams(temperature=0.7, top_k=16, seed=13)]
    mixed = [policies[i % len(policies)] for i in range(n_requests)]

    def serve(sampling):
        eng = GenerationEngine(spec, lm_scope(), slots=slots,
                               max_seq_len=tmax, page_size=page_size,
                               prefix_sharing=False,
                               prompt_buckets=(prompt_len,))
        eng.warmup()
        misses0 = eng.cache_stats()["misses"]
        t0 = time.perf_counter()
        outs = eng.generate_all(prompts, max_new_tokens=max_new,
                                sampling=sampling)
        wall = time.perf_counter() - t0
        toks = sum(len(o) for o in outs) - n_requests * prompt_len
        return {"wall_s": round(wall, 3),
                "ms_per_token": round(1e3 * wall / toks, 3),
                "fresh_compiles": eng.cache_stats()["misses"] - misses0}

    greedy_leg = serve(None)
    mixed_leg = serve(mixed)

    # beam forks vs the dense K-copy baseline (pool high-water)
    prompt = prompts[0]
    entries = -(-(prompt_len + beam_new) // page_size)
    dense_copy_pages = beam_k * entries  # K independent full copies
    eng = GenerationEngine(spec, lm_scope(), slots=beam_k + 1,
                           max_seq_len=tmax, page_size=page_size,
                           beam_width=beam_k, prefix_sharing=False,
                           prompt_buckets=(prompt_len,))
    hwm = [0]
    orig = eng._gauges

    def gauged():
        orig()
        hwm[0] = max(hwm[0], eng.pool.pages_in_use())
    eng._gauges = gauged
    t0 = time.perf_counter()
    ids, scores = eng.generate_beam(prompt, beam_size=beam_k,
                                    max_new_tokens=beam_new)
    beam_wall = time.perf_counter() - t0
    beam_leg = {
        "beam_size": beam_k, "max_new": beam_new,
        "wall_s": round(beam_wall, 3),
        "pages_hwm": hwm[0],
        "dense_copy_pages": dense_copy_pages,
        "page_bytes_ratio": round(hwm[0] / dense_copy_pages, 3),
        "forks": eng.metrics.counter("beam_forks"),
        "cow_copies": eng.metrics.counter("kv_cow_copies"),
    }
    return {
        "config": {"tmax": tmax, "page_size": page_size, "slots": slots,
                   "prompt_len": prompt_len, "max_new": max_new,
                   "n_requests": n_requests,
                   "model": f"d{d} L{L} h{H} V{vocab}"},
        "greedy": greedy_leg,
        "mixed_sampling": mixed_leg,
        "sampling_overhead": round(
            mixed_leg["ms_per_token"] / max(1e-9,
                                            greedy_leg["ms_per_token"])
            - 1.0, 3),
        "beam": beam_leg,
    }


def _sharding_measure(jax, pt, layers, batch=64, dim=256, steps=12,
                      rounds=3, warmup=2):
    """The one-sharding-plane A/B, run on whatever devices this process
    owns: single-device vs dp=N vs dp(N/2) x mp2, interleaved rounds with
    medians (the drift defense every bench here uses). Per leg: step
    wall, per-device parameter bytes (live shard sizes), the static
    per-device peak-HBM + collective-bytes estimate
    (analysis.analyze_memory(plan=...)), steady-state fresh compiles
    (must be 0 after warmup — the plan-digest cache-key contract), and
    the final loss for cross-leg parity."""
    import numpy as np

    from paddle_tpu import analysis
    from paddle_tpu.parallel import (data_parallel_plan, make_mesh,
                                     megatron_plan)

    n = len(jax.devices())
    plans = [("single", None)]
    if n >= 2:
        plans.append((f"dp{n}", data_parallel_plan(make_mesh({"dp": n}))))
    if n >= 4:
        plans.append((f"dp{n // 2}xmp2",
                      megatron_plan(make_mesh({"dp": n // 2, "mp": 2}))))

    rng = np.random.RandomState(0)
    xs = rng.rand(batch, dim).astype("float32")
    ys = rng.randint(0, 16, size=(batch, 1)).astype("int64")

    legs = []
    for tag, plan in plans:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", shape=[dim])
            y = layers.data("y", shape=[1], dtype="int64")
            h = layers.fc(x, size=dim, act="relu")
            h = layers.fc(h, size=dim, act="relu")
            logits = layers.fc(h, size=16)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, y))
            pt.optimizer.MomentumOptimizer(
                learning_rate=0.05, momentum=0.9).minimize(
                loss, startup_program=startup)
        scope = pt.Scope()
        if plan is None:
            exe = pt.Executor(pt.TPUPlace())
        else:
            from paddle_tpu.transpiler import shard_program

            shard_program(main, plan, ["x", "y"], [loss.name],
                          scope=scope)
            exe = pt.Executor(plan=plan)
        exe.run(startup, scope=scope)
        legs.append({"tag": tag, "plan": plan, "exe": exe, "scope": scope,
                     "main": main, "loss": loss, "walls": []})

    def step_leg(leg):
        out, = leg["exe"].run(leg["main"], feed={"x": xs, "y": ys},
                              fetch_list=[leg["loss"]], scope=leg["scope"],
                              return_numpy=False)
        return out

    for leg in legs:
        for _ in range(warmup):
            out = step_leg(leg)
        np.asarray(out)
        leg["warm_fresh"] = leg["exe"].fresh_compiles

    for _ in range(rounds):  # interleaved: drift hits every leg equally
        for leg in legs:
            t0 = time.perf_counter()
            for _ in range(steps):
                out = step_leg(leg)
            np.asarray(out)
            leg["walls"].append((time.perf_counter() - t0) / steps)

    def per_device_param_bytes(scope):
        total = 0.0
        for k in scope.keys():
            v = scope.get(k)
            if isinstance(v, jax.Array) and v.addressable_shards:
                sh = v.addressable_shards[0].data
                total += float(np.prod(sh.shape) or 1) * v.dtype.itemsize
        return total

    report = {}
    final_losses = {}
    for leg in legs:
        tag, plan = leg["tag"], leg["plan"]
        final = float(np.asarray(step_leg(leg)))
        final_losses[tag] = final
        row = {
            "ms_per_step": round(sorted(leg["walls"])[rounds // 2] * 1e3,
                                 3),
            "per_device_param_bytes": round(
                per_device_param_bytes(leg["scope"])),
            "steady_state_fresh_compiles":
                leg["exe"].fresh_compiles - leg["warm_fresh"],
            "final_loss": final,
        }
        mem = analysis.analyze_memory(
            leg["main"], ["x", "y"], [leg["loss"].name],
            scope=leg["scope"], batch_size=batch, plan=plan)
        row["static_peak_bytes"] = round(mem.peak_bytes)
        if plan is not None:
            row["mesh"] = plan.mesh_axes()
            row["collective_bytes_est"] = round(mem.collective_bytes)
        report[tag] = row
    single = final_losses.get("single")
    report["loss_parity_max_abs"] = (
        max(abs(v - single) for v in final_losses.values())
        if single is not None else None)
    report["config"] = {"batch": batch, "dim": dim, "steps": steps,
                        "devices": n}
    return report


def _lm_serving_scope(pt, layers, models, vocab, d, L, H, tmax, seed=7):
    """Initialized LM weights for the serving benches (one startup run
    per seed; callers copy into fresh scopes as needed)."""
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        p = layers.data("p_init", shape=[8], dtype="int64")
        models.transformer_lm_generate(
            p, vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
            max_len=tmax, max_new_tokens=1)
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    return scope


def bench_multi_tenant(jax, pt, layers, models, vocab=32, d=16, L=2, H=2,
                       tmax=64, slots=4, page_size=8, n_replicas=2,
                       jobs_per_thread=8, storm_threads=3):
    """Multi-tenant serving witness: two resident models ('ranker'
    greedy, 'chat' seeded-sampled) on one N-replica fleet behind one
    /v1 surface, under a mixed concurrent storm — per-tenant
    availability and latency, ZERO steady-state fresh compiles — then
    an independent tenant roll (a tenant-scoped Publisher publishing a
    new generation for 'ranker' WHILE 'chat' keeps serving): roll wall
    time and zero failed requests either side. Host/admission plane."""
    import tempfile
    import threading

    from paddle_tpu import checkpoint as ckpt
    from paddle_tpu.decoding import SamplingParams
    from paddle_tpu.online import Publisher
    from paddle_tpu.serving import Fleet, GenerationEngine, LMSpec
    from paddle_tpu.serving.tenancy import ModelRegistry, MultiTenantServer

    spec = LMSpec(vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
                  max_len=tmax)
    weights = {}

    def scope_for(seed):
        if seed not in weights:
            s = _lm_serving_scope(pt, layers, models, vocab, d, L, H,
                                  tmax, seed=seed)
            weights[seed] = {n: s.get(n) for n in s.keys()}
        scope = pt.Scope()
        for n, v in weights[seed].items():
            scope.set(n, v)
        return scope

    def engine(seed):
        eng = GenerationEngine(spec, scope_for(seed), slots=slots,
                               page_size=page_size, prompt_buckets=(8,),
                               prefill_batch_buckets=(1, 2, 4))
        eng.warmup()
        return eng

    servers = []
    for _ in range(n_replicas):
        reg = ModelRegistry()
        reg.register("ranker", [engine(7)])
        reg.register("chat", [engine(13)],
                     sampling=SamplingParams(temperature=0.7, top_k=8,
                                             seed=5))
        srv = MultiTenantServer(reg)
        srv.start()
        servers.append(srv)
    fleet = Fleet(servers, hedge=False, default_timeout_ms=60_000)

    def fresh_compiles():
        return sum(e.cache_stats()["misses"]
                   for srv in servers for t in srv.registry
                   for e in t.engines)

    lock = threading.Lock()
    lat = {"ranker": [], "chat": []}
    errors = []

    def storm(model, n, seed):
        rng = np.random.RandomState(seed)
        for _ in range(n):
            prompt = rng.randint(1, vocab, (3,)).tolist()
            t0 = time.perf_counter()
            try:
                fleet.submit({"prompt": prompt}, model=model,
                             max_new_tokens=6).result(timeout=60)
                with lock:
                    lat[model].append(time.perf_counter() - t0)
            except Exception as exc:  # noqa: BLE001 - availability
                with lock:
                    errors.append(repr(exc)[:100])

    def run_storm():
        threads = [threading.Thread(
            target=storm, args=(["ranker", "chat"][i % 2],
                                jobs_per_thread, 100 + i))
            for i in range(storm_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    def pq(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        return round(
            xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))] * 1e3, 2)

    with fleet:
        storm("ranker", 2, 0)   # touch every replica once
        storm("chat", 2, 1)
        for m in lat:
            lat[m].clear()
        misses0 = fresh_compiles()
        wall = run_storm()
        storm_compiles = fresh_compiles() - misses0

        # independent tenant roll under live traffic on the OTHER tenant
        with tempfile.TemporaryDirectory() as ck:
            ckpt.save_checkpoint(ck, scope=scope_for(99), step=5)
            pub = Publisher(fleet, ck, verify=False, pin=False,
                            tenant="ranker")
            chat_jobs = threading.Thread(
                target=storm, args=("chat", 2 * jobs_per_thread, 200))
            chat_jobs.start()
            t0 = time.perf_counter()
            rolled = pub.poll_once()
            roll_wall = time.perf_counter() - t0
            chat_jobs.join()
        snap = fleet.metrics.snapshot().get("labeled", {})
    total = sum(len(v) for v in lat.values())
    return {
        "replicas": n_replicas, "tenants": 2,
        "storm_wall_s": round(wall, 3),
        "failed": len(errors),
        "fresh_compiles_storm": storm_compiles,
        "ranker": {"ok": len(lat["ranker"]), "p50_ms": pq(lat["ranker"], 0.5),
                   "p99_ms": pq(lat["ranker"], 0.99)},
        "chat": {"ok": len(lat["chat"]), "p50_ms": pq(lat["chat"], 0.5),
                 "p99_ms": pq(lat["chat"], 0.99)},
        "roll": {"published_step": rolled,
                 "wall_s": round(roll_wall, 3),
                 "weights_version_ranker": snap.get(
                     "weights_version", {}).get('{tenant="ranker"}'),
                 "weights_version_chat": snap.get(
                     "weights_version", {}).get('{tenant="chat"}', 0.0)},
        "availability": round(total / max(1, total + len(errors)), 4),
    }


def bench_disagg(jax, pt, layers, models, vocab=64, d=32, L=2, H=4,
                 tmax=256, page_size=16, slots=6,
                 n_long=8, n_short=16, long_len=96, short_len=8,
                 long_new=4, short_new=32, slo_factor=3.0):
    """Prefill/decode disaggregation A/B at EQUAL engine count: a
    unified 2-engine pool vs a 1 prefill + 1 decode split
    (``DisaggEngine``) serving the same interference workload — long
    prompts (prefill-heavy) storming alongside short decode-heavy
    requests. Judged on goodput, not QPS: the SLO budget is
    ``slo_factor`` x each class's unloaded latency, and the metric is
    the SLO-good fraction of the decode-heavy class (the one a prefill
    burst stalls in a unified pool) plus decode TPOT p95. Byte-identity
    of the handoff and zero prefill recompute are asserted in-bench.
    Host/cache-migration plane."""
    import threading

    from paddle_tpu.serving import (DisaggEngine, GenerationEngine,
                                    LMSpec, Server)
    from paddle_tpu.serving.batcher import Request

    spec = LMSpec(vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
                  max_len=tmax)
    base = _lm_serving_scope(pt, layers, models, vocab, d, L, H, tmax)
    weights = {n: base.get(n) for n in base.keys()}

    def scope():
        s = pt.Scope()
        for n, v in weights.items():
            s.set(n, v)
        return s

    kw = dict(slots=slots, page_size=page_size,
              prompt_buckets=(short_len, long_len),
              prefill_batch_buckets=(1, 2, 4))

    rng = np.random.RandomState(0)
    longs = [rng.randint(1, vocab, (long_len,)).astype("int64")
             for _ in range(n_long)]
    shorts = [rng.randint(1, vocab, (short_len,)).astype("int64")
              for _ in range(n_short)]

    # -- correctness gate: handoff byte-identical, zero prefill recompute
    uni_ref = GenerationEngine(spec, scope(), **kw)
    want = uni_ref.generate_all([p.tolist() for p in shorts[:4]],
                                max_new_tokens=short_new)
    dis_ref = DisaggEngine.build(spec, prefill_replicas=1,
                                 decode_replicas=1, scope=scope(), **kw)
    reqs = [Request({"prompt": p.tolist()},
                    {"max_new_tokens": short_new}, None)
            for p in shorts[:4]]
    dis_ref._drive(reqs)
    byte_identical = all(
        np.array_equal(np.asarray(r.future.result(timeout=0)), w)
        for r, w in zip(reqs, want))
    decode_counters = dis_ref.decode.engines[0].metrics.snapshot()[
        "counters"]
    zero_prefill_recompute = decode_counters.get("prefills", 0) == 0 \
        and decode_counters.get("kv_handoffs_in", 0) == len(reqs)

    # SLO calibration: each class's unloaded steady-state latency on ONE
    # warmed unified engine; the budget (slo_factor x quiet) is shared
    # by both legs so the good-fraction comparison is apples-to-apples
    uni_ref.warmup()
    quiet = {}
    for cls, p, n in (("short", shorts[0], short_new),
                      ("long", longs[0], long_new)):
        t0 = time.perf_counter()
        uni_ref.generate_all([p.tolist()], max_new_tokens=n)
        quiet[cls] = time.perf_counter() - t0
    budget = {c: slo_factor * q for c, q in quiet.items()}

    # -- the A/B legs -----------------------------------------------------
    def leg(split):
        if split:
            eng = DisaggEngine.build(spec, prefill_replicas=1,
                                     decode_replicas=1, scope=scope(),
                                     **kw)
            engines = eng.engines
            served = [eng]
        else:
            engines = [GenerationEngine(spec, scope(), **kw)
                       for _ in range(2)]
            served = engines
        for e in engines:
            e.warmup()
        srv = Server(served)
        srv.start()
        lock = threading.Lock()
        lat = {"short": [], "long": []}
        errors = []

        def client(cls, prompts, max_new):
            for p in prompts:
                t0 = time.perf_counter()
                try:
                    srv.submit({"prompt": p.tolist()},
                               max_new_tokens=max_new).result(timeout=120)
                    with lock:
                        lat[cls].append(time.perf_counter() - t0)
                except Exception as exc:  # noqa: BLE001 - availability
                    with lock:
                        errors.append(repr(exc)[:100])

        try:
            # prime the submit path once per class, then storm
            client("short", shorts[:1], short_new)
            client("long", longs[:1], long_new)
            for c in lat:
                lat[c].clear()
            threads = [
                threading.Thread(target=client,
                                 args=("long", longs, long_new)),
                threading.Thread(target=client,
                                 args=("short", shorts[:n_short // 2],
                                       short_new)),
                threading.Thread(target=client,
                                 args=("short", shorts[n_short // 2:],
                                       short_new)),
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
        finally:
            srv.stop()
        tpots = [r["tpot_s"] for e in engines for r in e._recent
                 if r.get("tpot_s")]
        tpots.sort()

        def good(cls):
            return (round(sum(1 for x in lat[cls] if x <= budget[cls])
                          / max(1, len(lat[cls])), 4))

        return {
            "wall_s": round(wall, 3), "failed": len(errors),
            "slo_good_short": good("short"),
            "slo_good_long": good("long"),
            "tpot_p95_ms": (round(
                tpots[int(0.95 * (len(tpots) - 1))] * 1e3, 3)
                if tpots else None),
            "short_p99_ms": (round(sorted(lat["short"])[
                int(0.99 * (len(lat["short"]) - 1))] * 1e3, 2)
                if lat["short"] else None),
        }

    unified = leg(split=False)
    split = leg(split=True)
    return {
        "engines_per_leg": 2,
        "workload": {"long": {"n": n_long, "prompt": long_len,
                              "new": long_new},
                     "short": {"n": n_short, "prompt": short_len,
                               "new": short_new}},
        "handoff_byte_identical": byte_identical,
        "zero_prefill_recompute": zero_prefill_recompute,
        "slo_budget_ms": {c: round(b * 1e3, 2)
                          for c, b in budget.items()},
        "unified": unified,
        "disagg": split,
        "slo_good_short_gain": (round(
            split["slo_good_short"] - unified["slo_good_short"], 4)),
    }


def bench_recovery(jax, pt, layers, models, vocab=32, d=16, L=2, H=2,
                   tmax=64, slots=8, page_size=8, n_requests=8,
                   prompt_len=4, max_new=12, waves=3, kill_after=4):
    """Work-preserving recovery A/B: the same seeded-sampled workload on
    a 2-replica paged fleet, one leg uninterrupted and one leg under a
    kill storm (a fault-plan ``replica_kill`` hard-crashes a replica
    mid-stream EVERY wave; it is revived between waves). The legs are
    interleaved wave-by-wave so machine drift cancels. The record:
    availability under the storm (must be 1.0 — lineage resume turns a
    crash into a retryable, never a failure), bitwise token identity
    against the quiet leg, recovered-token reuse (the killed leg decodes
    STRICTLY FEWER tokens than the quiet leg: crashed streams re-enter
    via chunked prefill, never re-decode), the bounded recovery-prefill
    bill, and added TTFT on the recovered streams (tagged per-request by
    the engine). Host/router plane."""
    from paddle_tpu.decoding import SamplingParams
    from paddle_tpu.resilience import FaultPlan, Retry
    from paddle_tpu.serving import Fleet, GenerationEngine, LMSpec, Server

    spec = LMSpec(vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
                  max_len=tmax)
    base = _lm_serving_scope(pt, layers, models, vocab, d, L, H, tmax)
    weights = {n: base.get(n) for n in base.keys()}

    def scope():
        s = pt.Scope()
        for n, v in weights.items():
            s.set(n, v)
        return s

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, vocab, (prompt_len,)).astype("int64")
               for _ in range(n_requests)]
    sampling = SamplingParams(temperature=0.7, top_k=4, seed=11)

    def build_leg():
        engines = [GenerationEngine(spec, scope(), slots=slots,
                                    page_size=page_size)
                   for _ in range(2)]
        for e in engines:
            e.warmup()
        # patient retries: mid-wave both breakers can be open for a beat
        # (the quarantined kill + the probe window) — the storm outwaits
        # the recovery timer instead of failing fast through it
        fleet = Fleet([Server(e) for e in engines], hedge=False,
                      retry=Retry(max_attempts=8, backoff=0.05,
                                  multiplier=2.0, max_backoff=0.5,
                                  name="fleet"))
        return engines, fleet, {"lat": [], "failed": [], "outs": []}

    def wave(engines, fleet, acc, kill):
        plan = FaultPlan()
        if kill:
            plan.at(kind="replica_kill", after_tokens=kill_after)
        with plan.active():
            t0s, futs = [], []
            for p in prompts:
                t0s.append(time.perf_counter())
                futs.append(fleet.submit({"prompt": p},
                                         max_new_tokens=max_new,
                                         sampling_params=sampling))
            got = []
            for t0, f in zip(t0s, futs):
                try:
                    got.append(np.asarray(f.result(timeout=120)))
                    acc["lat"].append(time.perf_counter() - t0)
                except Exception as exc:  # noqa: BLE001 - availability
                    acc["failed"].append(repr(exc)[:100])
                    got.append(None)
            acc["outs"].append(got)
        for e in engines:
            e.revive()

    quiet = build_leg()
    storm = build_leg()
    try:
        for _ in range(waves):  # interleaved: quiet wave, then storm wave
            wave(*quiet, kill=False)
            wave(*storm, kill=True)

        def close(engines, fleet, acc):
            fc = fleet.metrics.snapshot()["counters"]
            ec = [e.metrics.snapshot()["counters"] for e in engines]
            rows = [r for e in engines for r in e._recent
                    if r.get("ttft_s") is not None]
            lat = sorted(acc["lat"])

            def pq(xs, q):
                return (round(xs[min(len(xs) - 1,
                                     int(round(q * (len(xs) - 1))))]
                              * 1e3, 3) if xs else None)

            total = len(lat) + len(acc["failed"])
            return {
                "availability": round(len(lat) / max(1, total), 4),
                "ok": len(lat), "failed": len(acc["failed"]),
                "p50_ms": pq(lat, 0.50), "p99_ms": pq(lat, 0.99),
                "decode_tokens": sum(c.get("decode_tokens", 0)
                                     for c in ec),
                "replica_kills": sum(c.get("replica_kills", 0)
                                     for c in ec),
                "requests_recovered": fc.get("requests_recovered", 0),
                "recovered_tokens": fc.get("recovered_tokens", 0),
                "recovery_prefill_tokens": sum(
                    c.get("recovery_prefill_tokens", 0) for c in ec),
                "ttft_ms": {
                    "fresh": pq(sorted(r["ttft_s"] for r in rows
                                       if not r.get("resumed")), 0.50),
                    "recovered": pq(sorted(r["ttft_s"] for r in rows
                                           if r.get("resumed")), 0.50),
                },
            }

        q = close(*quiet)
        s = close(*storm)
    finally:
        quiet[1].stop()
        storm[1].stop()

    # bitwise identity: every storm wave must match the quiet baseline
    token_exact = all(
        o is not None and w is not None and np.array_equal(o, w)
        for so, qo in zip(storm[2]["outs"], quiet[2]["outs"])
        for o, w in zip(so, qo))
    # bounded prefill bill: a recovered stream re-prefills at most its
    # prompt + everything emitted before the crash — never more
    bill_cap = s["requests_recovered"] * (prompt_len + max_new) \
        if s["requests_recovered"] else 0
    added = (None if s["ttft_ms"]["recovered"] is None
             or q["ttft_ms"]["fresh"] is None
             else round(s["ttft_ms"]["recovered"]
                        - q["ttft_ms"]["fresh"], 3))
    return {
        "waves": waves, "requests_per_wave": n_requests,
        "max_new": max_new, "kill_after_tokens": kill_after,
        "token_exact": token_exact,
        "tokens_reused": max(0, q["decode_tokens"] - s["decode_tokens"]),
        "no_redecode": s["decode_tokens"] < q["decode_tokens"],
        "prefill_bill_bounded": (
            s["recovery_prefill_tokens"] <= bill_cap),
        "added_ttft_recovered_ms": added,
        "quiet": q,
        "storm": s,
    }


def bench_obs_overhead(jax, pt, layers, models, vocab=64, d=128, L=3, H=4,
                       tmax=256, slots=8, page_size=16, n_requests=24,
                       max_new=24, rounds=5):
    """Full observability-plane A/B on the PAGED serving path: the same
    continuous-batching workload served with the plane dark (trace
    level 0, flight recorder off) and with everything on — level-1
    spans, per-request traceparent inject/extract (the fleet's
    propagation cost), request/queue span lifecycle, TTFT/TPOT/
    queue-wait histogram observation, and the flight recorder's
    event + metric-snapshot rings. Interleaved rounds with medians
    (clock-drift defense, same as bench_trace_overhead). The timeline
    bookkeeping itself is always-on by design — what this prices is the
    whole plane a production fleet would actually run. Target: <1%
    added serving latency (PR 3's level-1 budget was <5%, measured
    0.21%)."""
    from paddle_tpu import trace
    from paddle_tpu.serving import GenerationEngine, LMSpec, Request
    from paddle_tpu.trace import flight

    spec = LMSpec(vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
                  max_len=tmax)
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        p = layers.data("p_init", shape=[8], dtype="int64")
        models.transformer_lm_generate(
            p, vocab_size=vocab, d_model=d, n_layers=L, num_heads=H,
            max_len=tmax, max_new_tokens=1)
    startup.random_seed = 7
    exe.run(startup, scope=scope)
    # prefix sharing off: identical prompt sets must cost the same in
    # every round — a prefix hit in round 2 would masquerade as speedup
    eng = GenerationEngine(spec, scope, slots=slots, page_size=page_size,
                           prompt_buckets=(8, 16, 32),
                           prefix_sharing=False)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, (int(rng.randint(4, 25)),))
               .astype("int64") for _ in range(n_requests)]

    def run_leg(traced):
        reqs, roots = [], []
        for p_arr in prompts:
            meta = {"max_new_tokens": max_new}
            if traced:  # the propagation cost: one inject per request,
                # one extract inside begin_trace — what every fleet
                # attempt pays
                root = trace.start_span("fleet/request", detached=True)
                hdr = trace.inject(root)
                if hdr is not None:
                    meta["traceparent"] = hdr
                roots.append(root)
            req = Request({"prompt": p_arr}, meta, None)
            if traced:
                req.begin_trace()
            reqs.append(req)
        t0 = time.perf_counter()
        pending = list(reqs)
        while pending or eng.active or eng._deferred:
            if pending and eng.free_slots and not eng._deferred:
                k = min(len(pending), eng.free_slots)
                eng.admit(pending[:k])
                pending = pending[k:]
            eng._admit_deferred()
            eng.prefill_tick()
            eng.decode_tick()
        wall = time.perf_counter() - t0
        for root in roots:
            root.finish(status="ok")
        toks = sum(len(np.asarray(r.future.result(timeout=1)))
                   for r in reqs) - sum(len(p_) for p_ in prompts)
        return wall, toks

    tracer = trace.get_tracer()
    recorder = flight.get_recorder()
    prev_level = tracer.level
    prev_flight = recorder.enabled
    base_s, full_s, n_spans, toks = [], [], 0, 0
    try:
        trace.disable()
        recorder.enabled = False
        run_leg(False)  # warmup: every compile happens before the A/B
        for _ in range(rounds):
            trace.disable()
            recorder.enabled = False
            w, toks = run_leg(False)
            base_s.append(w)
            trace.enable(level=1)
            recorder.enabled = True
            tracer.clear()
            w, _ = run_leg(True)
            full_s.append(w)
            n_spans = len(tracer)
        bundle = recorder.bundle("bench")  # the dump path works end-to-end
    finally:
        tracer.configure(level=prev_level)
        recorder.enabled = prev_flight
    base = sorted(base_s)[rounds // 2]
    full = sorted(full_s)[rounds // 2]
    hist = eng.metrics.snapshot()["hist"]
    return {
        "baseline_ms_per_token": round(base / max(1, toks) * 1e3, 4),
        "full_plane_ms_per_token": round(full / max(1, toks) * 1e3, 4),
        "overhead_pct": round((full - base) / base * 100.0, 2),
        "spans_recorded": n_spans,
        "requests": n_requests,
        "new_tokens": toks,
        "ttft_p50_ms": hist["ttft"]["p50_ms"],
        "tpot_p50_ms": hist["tpot"]["p50_ms"],
        "flight_bundle_spans": len(bundle["trace"]["spans"]),
        "flight_metric_snapshots": len(bundle["metric_snapshots"]),
    }


def bench_sharding(jax, pt, layers, batch=64, dim=256, steps=12,
                   rounds=3, warmup=2):
    """One-sharding-plane A/B (single vs dp vs dp x tp), measured in this
    process on the devices it already holds: a four-chip host, or a test
    process on the virtual CPU mesh. Fewer than four devices is an
    error, not a reason to spawn a CPU child."""
    if len(jax.devices()) < 4:
        raise RuntimeError(
            f"bench_sharding needs >= 4 devices, found {len(jax.devices())}")
    return _sharding_measure(jax, pt, layers, batch=batch, dim=dim,
                             steps=steps, rounds=rounds, warmup=warmup)


def bench_image_model(jax, pt, layers, models, name, batch=128, hw=224,
                      steps=8):
    """img/s for one zoo model's train step (benchmark/paddle/image/*)."""
    import numpy as np

    build = {"alexnet": lambda img: models.alexnet(img, num_classes=1000),
             "googlenet": lambda img: models.googlenet(img,
                                                       num_classes=1000),
             "vgg16": lambda img: models.vgg(img, num_classes=1000,
                                             depth=16)}[name]
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        images = layers.data("images", shape=[hw, hw, 3])
        label = layers.data("label", shape=[1], dtype="int64")
        logits = build(images)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.MomentumOptimizer(learning_rate=0.01,
                                       momentum=0.9).minimize(
            loss, startup_program=startup)
    rng = np.random.RandomState(0)
    feed = {"images": rng.rand(batch, hw, hw, 3).astype("float32"),
            "label": rng.randint(0, 1000, size=(batch, 1)).astype("int64")}
    sec = _time_train_steps(jax, pt, main_prog, startup, loss, feed,
                            warmup=2, steps=steps)
    return batch / sec


def assemble(rows):
    """Build the single output record from the sweep's rows.

    ``rows`` maps step name -> {"result": ...} or {"error": ...}. Needs an
    "info" row (platform/device_kind/batch/image_size); rows that raised
    emit as null and are listed under ``failed``."""
    info = rows["info"]["result"]
    platform, device_kind = info["platform"], info["device_kind"]
    batch, hw = info["batch"], info["image_size"]
    peak = _peak_flops(device_kind)

    def res(step):
        r = rows.get(step)
        return r.get("result") if r else None

    resnet = res("resnet")
    img_per_sec = resnet["img_per_sec"] if resnet else None
    flops_per_img = RESNET50_TRAIN_FLOPS_224 * (hw / 224.0) ** 2
    achieved_flops = img_per_sec * flops_per_img if resnet else None
    lstm_ms = res("lstm")
    lm = res("transformer")
    lm_tok_s, lm_flops_s = lm if lm else (None, None)
    lm_wide = res("transformer_wide")
    lmw_tok_s, lmw_flops_s = lm_wide if lm_wide else (None, None)
    zoo = {}
    for name in IMAGE_MODEL_BASELINES:
        ips = res("zoo_" + name)
        if ips:
            zoo[name] = {"img_per_sec": round(ips, 1),
                         "vs_baseline": round(
                             ips / IMAGE_MODEL_BASELINES[name], 1)}
    infer_zoo = {n: res("infer_" + n) for n in INFER_BASELINES
                 if res("infer_" + n)}
    extra = {
        "platform": platform,
        "device_kind": device_kind,
        "device_count": info["device_count"],
        "batch": batch,
        "image_size": hw,
        "achieved_tflops": (round(achieved_flops / 1e12, 2)
                            if resnet else None),
        "mfu": round(achieved_flops / peak, 4) if resnet else None,
        "baseline": "84.08 img/s ResNet-50 train, "
                    "IntelOptimizedPaddle.md:43-45",
        "lstm_ms_per_batch": (round(lstm_ms, 2)
                              if lstm_ms is not None else None),
        "lstm_vs_baseline": (round(LSTM_BASELINE_MS / lstm_ms, 2)
                             if lstm_ms else None),
        "lstm_baseline": "184 ms/batch 2xLSTM bs64 hidden512, "
                         "benchmark/README.md:119",
        "transformer_lm_tokens_per_sec": (round(lm_tok_s)
                                          if lm_tok_s else None),
        "transformer_mfu": (round(lm_flops_s / peak, 4)
                            if lm_flops_s else None),
        "transformer_lm_config": ("d1024 L8 h8 (d_head=128) bs8 T2048 "
                                  "V16k bf16; MFU counts in-kernel "
                                  "causal flash FLOPs"),
        "transformer_wide_tokens_per_sec": (round(lmw_tok_s)
                                            if lmw_tok_s else None),
        "transformer_wide_mfu": (round(lmw_flops_s / peak, 4)
                                 if lmw_flops_s else None),
        "transformer_wide_config": ("d2048 L8 h16 (d_head=128) bs8 "
                                    "T2048 V16k bf16 — the >=50% MFU "
                                    "demonstration config"),
        "lstm_varlen": res("lstm_varlen"),
        "decode_kv_cache": res("decode"),
        "trace_overhead": res("trace_overhead"),
        "train_pipeline": res("train_pipeline"),
        "checkpoint": res("checkpoint"),
        "memplan": res("memplan"),
        "fleet": res("fleet"),
        "failed": {s: r["error"] for s, r in rows.items()
                   if "error" in r} or None,
        "image_zoo_train_bs128": zoo or None,
        "infer_bs16": infer_zoo or None,
    }
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_per_sec, 2) if resnet else None,
        "unit": "img/s",
        "vs_baseline": (round(img_per_sec / BASELINE_IMG_PER_SEC, 3)
                        if resnet else None),
        "extra": extra,
    }


def run_rows(plan):
    """Run ``plan`` — (name, fn, args, kwargs) rows — in order. A row
    that raises is recorded as {"error": ...} with its traceback on
    stderr and the sweep goes on; :func:`main` turns any such row into a
    non-zero exit code."""
    import traceback

    rows = {}
    for name, fn, args, kw in plan:
        try:
            rows[name] = {"result": fn(*args, **kw)}
        except Exception as exc:  # noqa: BLE001 - recorded, exit code != 0
            traceback.print_exc()
            rows[name] = {"error": repr(exc)[:300]}
    return rows


def run_bench(jax, dev):
    """The measurement sweep on the TPU this process holds; returns the
    rows dict (see :func:`assemble`)."""
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    batch, hw, warmup, steps = 256, 224, 3, 20
    # bf16 compute / f32 master weights — the TPU-native training dtype.
    pt.set_amp(True)

    def measure_resnet():
        main_prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_prog, startup):
            images = layers.data("images", shape=[hw, hw, 3])
            label = layers.data("label", shape=[1], dtype="int64")
            logits = models.resnet_imagenet(images, num_classes=1000,
                                            depth=50)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, label))
            pt.optimizer.MomentumOptimizer(
                learning_rate=0.1, momentum=0.9).minimize(
                loss, startup_program=startup)

        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        exe.run(startup, scope=scope)

        # Device-resident synthetic batch: the benchmark measures the
        # training step, not host->device input bandwidth (on real systems
        # the input pipeline overlaps transfers).
        rng = np.random.RandomState(0)
        feed = {
            "images": jax.device_put(
                rng.rand(batch, hw, hw, 3).astype("float32")),
            "label": jax.device_put(
                rng.randint(0, 1000, size=(batch, 1)).astype("int64")),
        }
        for _ in range(warmup):
            exe.run(main_prog, feed=feed, fetch_list=[loss], scope=scope)

        # return_numpy=False keeps the loop asynchronous (no per-step host
        # sync draining the pipeline); one blocking fetch closes the timing.
        t0 = time.perf_counter()
        for _ in range(steps):
            o, = exe.run(main_prog, feed=feed, fetch_list=[loss],
                         scope=scope, return_numpy=False)
        o = np.asarray(o)
        elapsed = time.perf_counter() - t0
        assert np.isfinite(o).all()
        return {"img_per_sec": batch * steps / elapsed}

    def row(name, fn, *args, **kw):
        return (name, fn, args, kw)

    # Headline first, then the >=50%-MFU north-star config, then the rest.
    plan = [
        row("resnet", measure_resnet),
        row("transformer_wide", bench_transformer_step, jax, pt, layers,
            models, bs=8, d=2048, H=16),
        row("transformer", bench_transformer_step, jax, pt, layers, models),
        row("decode", bench_decode, jax, pt, layers, models),
        row("lstm", bench_lstm_step, jax, pt, layers),
        row("lstm_varlen", bench_lstm_varlen, jax, pt, layers),
    ]
    plan += [row("zoo_" + name, bench_image_model, jax, pt, layers, models,
                 name) for name in IMAGE_MODEL_BASELINES]
    plan += [row("infer_" + name, bench_inference, jax, pt, layers, models,
                 name) for name in INFER_BASELINES]
    plan += [
        row("transpiler_resnet50", bench_transpiler, jax, pt, layers,
            models, "resnet50"),
        row("trace_overhead", bench_trace_overhead, jax, pt, layers, models),
        row("train_pipeline", bench_train_pipeline, jax, pt, layers),
        row("checkpoint", bench_checkpoint, jax, pt, layers),
        # static estimator vs cost_analysis
        row("memplan", bench_memplan, jax, pt, layers, models, batch=batch,
            hw=hw),
        # host-side planes (router/threads, cache layout, spans, meters,
        # control loops): each is a count-asserting A/B at toy width that
        # ROADMAP D2 moves into tests/ or replaces with a cell
        row("fleet", bench_fleet, jax, pt, layers),
        row("obs_overhead", bench_obs_overhead, jax, pt, layers, models),
        row("goodput_overhead", bench_goodput, jax, pt, layers, batch=batch,
            dim=1024, steps=30),
        row("decode_platform", bench_decode_platform, jax, pt, layers,
            models),
        row("online", bench_online, jax, pt, layers),
        row("multi_tenant", bench_multi_tenant, jax, pt, layers, models),
        row("disagg", bench_disagg, jax, pt, layers, models),
        row("recovery", bench_recovery, jax, pt, layers, models),
        row("elastic", bench_elastic, jax, pt, layers),
        row("feedback_loop", bench_feedback_loop, jax, pt, layers),
    ]
    if len(jax.devices()) >= 4:
        plan.append(row("sharding", bench_sharding, jax, pt, layers))
    rows = run_rows(plan)
    rows["info"] = {"result": {"platform": dev.platform,
                               "device_kind": dev.device_kind,
                               "device_count": len(jax.devices()),
                               "batch": batch, "image_size": hw}}
    return rows


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py: needs a TPU, jax found {dev.platform!r} "
              f"({dev.device_kind}); nothing was measured",
              file=sys.stderr)
        return 1
    rows = run_bench(jax, dev)
    print(json.dumps(assemble(rows)), flush=True)
    failed = sorted(s for s, r in rows.items() if "error" in r)
    if failed:
        print(f"bench.py: rows raised: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
