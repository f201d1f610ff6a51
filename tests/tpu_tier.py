"""Real-chip test tier: ``python tests/tpu_tier.py`` on a machine with a
TPU (through the chip tool), or as the one child test_tpu_tier.py spawns.

The pytest suite itself is pinned to the virtual CPU mesh (conftest.py);
this script owns the chip for its lifetime — a chip belongs to one process
at a time, so everything TPU-side lives in this one process.

Checks mirror the reference's GPU-vs-CPU compare harnesses
(/root/reference/paddle/function/FunctionTest.h Compare2Function,
/root/reference/python/paddle/v2/fluid/tests/op_test.py
check_output_with_place) with the TPU twist: the interesting axis is the
bf16 MXU dtype policy (SURVEY.md §7 "hard parts"), buffer donation, and
async dispatch — things the CPU mesh cannot exercise.

Prints one JSON line per check: {"check": name, "ok": bool, "detail": str}.
Exit code 0 iff every check passed.
"""
import itertools
import json
import os
import sys
import time
import traceback

import numpy as np

# ``python tests/tpu_tier.py`` puts tests/ on sys.path, not the checkout
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


def _executor_pair():
    import paddle_tpu as pt

    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    return exe, scope


@check
def device_is_tpu():
    import jax

    dev = jax.devices()[0]
    assert dev.platform == "tpu", dev
    return f"{dev.platform}:{dev.device_kind}"


@check
def amp_matmul_numerics():
    """bf16 MXU matmul stays within bf16 tolerance of the f32 answer
    (dtype policy: bf16 multiplies, f32 accumulation)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    a = rng.randn(256, 512).astype(np.float32)
    b = rng.randn(512, 256).astype(np.float32)
    ref = a @ b
    got = np.asarray(jax.jit(
        lambda x, y: jnp.dot(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32))(a, b))
    # bf16 input rounding (~2^-8) accumulates ~sqrt(K)-fashion over the
    # K=512 contraction; normalize by the contraction scale, not per-entry.
    scale = np.sqrt(a.shape[1])
    rel = np.abs(got - ref).max() / scale
    assert rel < 2e-2, rel
    return f"scaled err {rel:.2e}"


@check
def amp_conv_numerics():
    """conv2d under AMP on the chip vs the f32 op on the same chip."""
    import paddle_tpu as pt
    from paddle_tpu.core.registry import get_op
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 16, 16, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(3, 3, 8, 16).astype(np.float32) * 0.1)
    conv = get_op("conv2d").fn
    attrs = {"strides": [1, 1], "paddings": [1, 1], "groups": 1,
             "data_format": "NHWC"}
    pt.set_amp(False)
    ref = np.asarray(conv(attrs, {"Input": [x], "Filter": [w]})["Output"][0])
    pt.set_amp(True)
    got = np.asarray(conv(attrs, {"Input": [x], "Filter": [w]})["Output"][0])
    pt.set_amp(False)
    rel = np.abs(got.astype(np.float32) - ref) / np.maximum(np.abs(ref), 1.0)
    assert rel.max() < 3e-2, rel.max()
    return f"max rel err {rel.max():.2e}"


@check
def executor_donation_reuses_buffers():
    """Optimizer-updated params are donated: the updated param reuses the
    old param's device buffer (in-place update, no copy grow)."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[64])
        h = layers.fc(x, size=64, bias_attr=False,
                      param_attr=pt.ParamAttr(name="don_w"))
        loss = layers.mean(h)
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    exe, scope = _executor_pair()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((8, 64), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)  # compile+run
    old = scope.get("don_w")
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    # donate_argnums consumed the old param buffer in place: the donated
    # array is deleted client-side.
    assert old.is_deleted(), "param buffer was copied, not donated"
    assert not scope.get("don_w").is_deleted()
    return "old param buffer consumed by donation"


@check
def flash_attention_matches_reference():
    """Pallas flash kernel vs the jnp soft(max QK)V reference, bf16-level
    tolerance, causal + padded-length masking."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import flash_attention

    rng = np.random.RandomState(2)
    B, H, T, D = 2, 4, 256, 64
    q = rng.randn(B, H, T, D).astype(np.float32) * 0.3
    k = rng.randn(B, H, T, D).astype(np.float32) * 0.3
    v = rng.randn(B, H, T, D).astype(np.float32)
    lengths = np.array([256, 192], np.int32)
    got = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        lengths=jnp.asarray(lengths), causal=True))
    # reference: explicit masked softmax
    scale = 1.0 / np.sqrt(D)
    mask = np.tril(np.ones((T, T), bool))[None, None]
    lmask = (np.arange(T)[None, :] < lengths[:, None])[:, None, None, :]
    s = (q @ np.swapaxes(k, -1, -2)) * scale
    s = np.where(mask & lmask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = p @ v
    err = np.abs(got - ref).max()
    assert err < 2e-2, err
    return f"max abs err {err:.2e}"


@check
def flash_attention_backward_matches_reference():
    """The Pallas flash BACKWARD (dq/dkv kernels recomputing p-tiles from
    the saved logsumexp) vs the jnp reference vjp, causal + padded.
    T=1024 gives multi-block grids (4 q-blocks x 2 k-blocks at the default
    256/512 block sizes), so the causal block-skip bounds and cross-block
    accumulation actually run on hardware."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    reference_attention)

    rng = np.random.RandomState(5)
    B, H, T, D = 2, 4, 1024, 64
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    lengths = jnp.asarray(np.array([1024, 704], np.int32))

    def loss(attn, q, k, v):
        o = attn(q, k, v, lengths=lengths, causal=True)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.jit(jax.grad(lambda q, k, v: loss(flash_attention, q, k, v),
                          argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda q, k, v: loss(reference_attention, q, k, v),
                          argnums=(0, 1, 2)))(q, k, v)
    errs = {}
    for name, a, b in zip("qkv", gf, gr):
        err = float(jnp.abs(a - b).max())
        scale = max(float(jnp.abs(b).max()), 1.0)
        assert err < 2e-2 * scale, (name, err, scale)
        errs[name] = err
    return " ".join(f"d{n}={e:.1e}" for n, e in errs.items())


@check
def lenet_train_step_converges():
    """One real train job on the chip: LeNet on synthetic MNIST digits,
    loss must halve in 30 steps under AMP."""
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    pt.set_amp(True)
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            img = layers.data("img", shape=[28, 28, 1])
            y = layers.data("y", shape=[1], dtype="int64")
            logits = models.lenet5(img, num_classes=10)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, y))
            pt.optimizer.AdamOptimizer(learning_rate=2e-3).minimize(
                loss, startup_program=startup)
        exe, scope = _executor_pair()
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        # synthetic structured digits: class k = bright kth row band
        losses = []
        for _ in range(30):
            yb = rng.randint(0, 10, size=(64, 1)).astype(np.int64)
            xb = rng.rand(64, 28, 28, 1).astype(np.float32) * 0.1
            for r, cls in enumerate(yb[:, 0]):
                xb[r, cls * 2 + 2:cls * 2 + 5, :, 0] += 1.0
            lo, = exe.run(main, feed={"img": xb, "y": yb},
                          fetch_list=[loss], scope=scope)
            losses.append(float(lo))
        assert np.isfinite(losses).all(), losses[-5:]
        assert losses[-1] < 0.5 * losses[0], (losses[0], losses[-1])
        return f"loss {losses[0]:.3f} -> {losses[-1]:.3f}"
    finally:
        pt.set_amp(False)


@check
def async_dispatch_overlaps():
    """The executor must dispatch asynchronously: N cached steps enqueued
    without fetching should return far faster than the device time they
    consume (the async story the profiler's block_on documents)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import layers

    # sized so one step is milliseconds of MXU work (8 x 4096x2048x2048
    # matmuls, ~0.27 TFLOP) against a host dispatch of well under one:
    # a step the device finishes faster than the host can enqueue the
    # next leaves nothing pending to observe (my chip run, PR 21: at
    # 256x512 fifty dispatches took 20.9 ms and the device was done 0.6 ms
    # later)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[2048])
        h = x
        for _ in range(8):
            h = layers.fc(h, size=2048, act="relu")
        loss = layers.mean(h)
    exe, scope = _executor_pair()
    exe.run(startup, scope=scope)
    feed = {"x": jax.device_put(np.ones((4096, 2048), np.float32))}
    out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                   return_numpy=False)
    jax.block_until_ready(out)
    # The async signature: after the dispatch loop RETURNS, real device
    # work must still be pending (block_until_ready waits measurably).
    # Asserting on the dispatch:total ratio is flaky — host contention
    # (e.g. a CPU test suite on the same box) inflates dispatch time —
    # so assert on the residual wait, best of three windows.
    best_wait, best = -1.0, (0.0, 0.0)
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(50):
            out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                           return_numpy=False)
        dispatch = time.perf_counter() - t0
        jax.block_until_ready(out)
        total = time.perf_counter() - t0
        wait = total - dispatch
        if wait > best_wait:
            best_wait, best = wait, (dispatch, total)
        if wait > 0.02:
            break
    dispatch, total = best
    assert total - dispatch > 0.02, (dispatch, total)
    return f"dispatch {dispatch*1e3:.1f} ms, device wait " \
           f"{(total - dispatch)*1e3:.1f} ms after dispatch returned"


@check
def profiler_reports_device_time():
    """record_event(block_on=...) measures device time: a big matmul's
    synced timer must exceed its unsynced (dispatch-only) timer."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import profiler

    a = jnp.ones((4096, 4096), jnp.bfloat16)
    f = jax.jit(lambda x: x @ x)
    f(a).block_until_ready()  # compile
    stats = profiler.StatSet()
    for _ in range(5):
        with profiler.timer("nosync", stat_set=stats):
            r = f(a)
        with profiler.timer("sync", stat_set=stats, sync=False):
            r = f(a)
            jax.block_until_ready(r)
    table = dict((row[0], row) for row in stats.table())
    nosync = table["nosync"][2]  # total ms
    sync = table["sync"][2]
    assert sync > nosync, (sync, nosync)
    return f"sync {sync:.2f} ms > dispatch {nosync:.2f} ms"


@check
def checkgrad_on_chip():
    """The checkgrad job at forced-f32 MXU precision passes on the real
    chip for a matmul+softmax stack (reference --job=checkgrad,
    /root/reference/paddle/trainer/TrainerMain.cpp:54)."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.checkgrad import check_gradients

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[8])
        h = layers.fc(x, size=6, act="tanh")
        logits = layers.fc(h, size=3)
        y = layers.data("y", shape=[1], dtype="int64")
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
    import paddle_tpu as pt

    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(4, 8).astype(np.float32),
            "y": rng.randint(0, 3, size=(4, 1)).astype(np.int64)}
    exe, scope = _executor_pair()
    exe.run(startup, scope=scope)
    # rtol is looser than the CPU harness (1e-2): even at HIGHEST MXU
    # precision the chip's transcendental units (tanh/exp here) are
    # polynomial approximations, which biases the finite-difference probe
    # by ~1% — the bf16/TPU dtype-policy reality SURVEY.md §7 flags.
    # Raises AssertionError on any out-of-tolerance parameter.
    report = check_gradients(main, feed, loss, scope=scope,
                             executor=exe, rtol=5e-2, atol=1e-3)
    return f"{len(report)} params checked"


@check
def int_label_pipeline():
    """int64 host labels survive the feed path (truncated to int32 on
    device by policy) and one_hot/cross_entropy agree with numpy."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        y = layers.data("y", shape=[1], dtype="int64")
        oh = layers.one_hot(y, depth=7)
    exe, scope = _executor_pair()
    exe.run(startup, scope=scope)
    yb = np.array([[0], [3], [6]], np.int64)
    got, = exe.run(main, feed={"y": yb}, fetch_list=[oh], scope=scope)
    np.testing.assert_array_equal(np.asarray(got).reshape(3, 7),
                                  np.eye(7, dtype=np.float32)[yb[:, 0]])
    return "one_hot ok"


@check
def conv_epilogue_matches_unfused():
    """The fused conv1x1+BN+relu(+residual) Pallas path (compiled, real
    chip — not interpret mode) vs the separate-op composition, at a
    ResNet-stage shape, training and inference modes. Batch 32 makes the
    row count (32*14*14 = 49*128) tile; a shape with no tile must raise
    on the chip rather than quietly run the unfused composition."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    def run(fused, is_test):
        pt.flags.FLAGS.fused_conv_epilogue = fused
        try:
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                x = layers.data("x", shape=[14, 14, 256])
                if fused:
                    y = layers.conv1x1_bn_act(
                        x, 512, act="relu", is_test=is_test,
                        residual=layers.conv1x1_bn_act(
                            x, 512, act=None, is_test=is_test))
                else:
                    def cbn(inp):
                        c = layers.conv2d(inp, num_filters=512,
                                          filter_size=1, bias_attr=False,
                                          data_format="NHWC")
                        return layers.batch_norm(c, act=None,
                                                 is_test=is_test,
                                                 data_layout="NHWC")

                    r = cbn(x)
                    y = layers.relu(layers.elementwise_add(cbn(x), r))
                loss = layers.mean(y)
                if not is_test:
                    pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(
                        loss, startup_program=startup)
            main.random_seed = startup.random_seed = 5
            exe, scope = _executor_pair()
            exe.run(startup, scope=scope)
            rng = np.random.RandomState(2)
            feed = {"x": rng.randn(32, 14, 14, 256).astype(np.float32)}
            return [float(np.asarray(
                exe.run(main, feed=feed, fetch_list=[loss],
                        scope=scope)[0])) for _ in range(3)]
        finally:
            pt.flags.FLAGS.fused_conv_epilogue = False

    msgs = []
    for is_test in (False, True):
        a = run(True, is_test)
        b = run(False, is_test)
        for f, p in zip(a, b):
            assert abs(f - p) < 5e-3 * max(abs(p), 1.0), (is_test, a, b)
        msgs.append(f"{'test' if is_test else 'train'}: "
                    f"{a[0]:.5f}~{b[0]:.5f}")
    import jax.numpy as jnp
    from paddle_tpu.kernels import conv_epilogue as ke

    try:  # 8*14*14 = 1568 rows: no 128-row tile divides it
        ke.conv1x1_stats(jnp.zeros((1568, 256), jnp.bfloat16),
                         jnp.zeros((256, 512), jnp.bfloat16))
    except ValueError as exc:
        assert "1568" in str(exc), exc
        msgs.append("untileable shape raises")
    else:
        raise AssertionError("untileable shape ran (a quiet XLA fallback)")
    return "; ".join(msgs)


@check
def flash_attention_d128_matches_reference():
    """d_head=128 (the bench transformer's head width) through the flash
    kernel fwd+bwd vs the jnp reference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    reference_attention)

    rng = np.random.RandomState(11)
    B, H, T, D = 1, 2, 512, 128
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32) * 0.2)
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32) * 0.2)
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))

    def loss(attn, q, k, v):
        o = attn(q, k, v, causal=True)
        return jnp.sum(o * jnp.sin(o))

    got = np.asarray(flash_attention(q, k, v, causal=True))
    ref = np.asarray(reference_attention(q, k, v, None, True, None))
    err_f = np.abs(got - ref).max()
    assert err_f < 2e-2, err_f
    gf = jax.jit(jax.grad(lambda q, k, v: loss(flash_attention, q, k, v),
                          argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(
        lambda q, k, v: loss(reference_attention, q, k, v),
        argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        err = float(jnp.abs(a - b).max())
        scale = max(float(jnp.abs(b).max()), 1.0)
        assert err < 2e-2 * scale, (name, err, scale)
    return f"fwd err {err_f:.1e}"


@check
def flash_attention_packed_matches_reference():
    """``flash_attention_packed`` over [b, T, H * d] rows, fwd+bwd, at the
    three lane-block cases (d_head 32: four heads a block, 64: two, 128:
    one), causal with a ``lengths`` mask and a T the pad to 128 serves,
    vs the jnp reference on the heads-first view; float32 and, under AMP,
    bf16 operands."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.kernels.flash_attention import (flash_attention_packed,
                                                    reference_attention)

    worst = 0.0
    for (H, D), T, amp in itertools.product(
            ((8, 32), (4, 64), (2, 128)), (1024, 200), (False, True)):
        rng = np.random.RandomState(D + T)
        q, k, v, g = (jnp.asarray(rng.randn(2, T, H * D)
                                  .astype(np.float32) * s)
                      for s in (0.5, 0.5, 1.0, 1.0))
        lengths = jnp.asarray([T, T - T // 3], jnp.int32)

        def heads(a):
            return a.reshape(2, T, H, D).transpose(0, 2, 1, 3)

        def ref(q, k, v):
            o = reference_attention(heads(q), heads(k), heads(v), lengths,
                                    True, None)
            return o.transpose(0, 2, 1, 3).reshape(2, T, H * D)

        def run(fn):
            o, vjp = jax.vjp(fn, q, k, v)
            return (o,) + vjp(g)

        pt.set_amp(amp)
        try:
            got = jax.jit(lambda: run(
                lambda q, k, v: flash_attention_packed(
                    q, k, v, H, lengths=lengths, causal=True)))()
        finally:
            pt.set_amp(False)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda: run(ref))()
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            err = float(jnp.abs(a - b).max() / jnp.abs(b).max())
            assert err < 2e-2, (H, D, T, amp, name, err)
            worst = max(worst, err)
    return f"worst rel err {worst:.1e}"


@check
def flash_attention_full_width_shapes():
    """The shapes the full-width LM hands the kernels — (8, 8, 2048, 128)
    and (8, 16, 2048, 64), causal — forward and both backward kernels, in
    bf16 AND f32 (without AMP the stacked block hands the kernels its
    float32 stream, so the dkv kernel's full-T q/dO blocks are 1 MB each
    before double-buffering against the 16 MB scoped-VMEM limit): Mosaic
    must compile them and they must match the jnp reference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    reference_attention)

    def loss(attn, q, k, v):
        o = attn(q, k, v, causal=True).astype(jnp.float32)
        return jnp.sum(o * jnp.sin(o))

    msgs = []
    for B, H, T, D in ((8, 8, 2048, 128), (8, 16, 2048, 64)):
        for dt in (jnp.bfloat16, jnp.float32):
            rng = np.random.RandomState(D)
            q, k, v = (jnp.asarray(rng.randn(B, H, T, D) * s, dt)
                       for s in (0.2, 0.2, 1.0))
            got = flash_attention(q, k, v, causal=True)
            ref = reference_attention(q, k, v, None, True, None)
            err_f = float(jnp.abs(got.astype(jnp.float32)
                                  - ref.astype(jnp.float32)).max())
            assert err_f < 3e-2, (B, H, T, D, dt, err_f)
            gf = jax.jit(jax.grad(
                lambda q, k, v: loss(flash_attention, q, k, v),
                argnums=(0, 1, 2)))(q, k, v)
            gr = jax.jit(jax.grad(
                lambda q, k, v: loss(reference_attention, q, k, v),
                argnums=(0, 1, 2)))(q, k, v)
            worst = 0.0
            for name, a, b in zip("qkv", gf, gr):
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                scale = max(float(jnp.abs(b).max()), 1.0)
                err = float(jnp.abs(a - b).max()) / scale
                assert err < 4e-2, (B, H, T, D, dt, name, err)
                worst = max(worst, err)
            msgs.append(f"h{H}d{D} {jnp.dtype(dt).name}: "
                        f"fwd {err_f:.1e} bwd {worst:.1e}")
    return "; ".join(msgs)


@check
def flash_attention_under_amp_runs_bf16_kernels():
    """What the LM train cells run since PR 41: float32 q / k / v
    ``[8, 16, 1024, 64]`` under AMP go through the three kernels as bf16
    and come back float32; o, dq, dk, dv
    against the float32 reference (AMP off, highest precision) within
    2^-6 of each tensor's largest magnitude, causal + a ragged length."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    reference_attention)

    rng = np.random.RandomState(41)
    B, H, T, D = 8, 16, 1024, 64
    q, k, v, g = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
                  for _ in range(4))
    lengths = jnp.asarray(np.array([T] * 7 + [700], np.int32))

    def out_and_grads(attn):
        out, vjp = jax.vjp(
            lambda q, k, v: attn(q, k, v, lengths=lengths, causal=True),
            q, k, v)
        return (out,) + vjp(g)

    pt.set_amp(True)
    try:
        got = jax.jit(lambda: out_and_grads(flash_attention))()
    finally:
        pt.set_amp(False)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda: out_and_grads(reference_attention))()
    msgs = []
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
        assert a.dtype == jnp.float32, (name, a.dtype)
        err = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        assert err < 2.0 ** -6, (name, err)
        msgs.append(f"{name} {err:.1e}")
    return "rel to max: " + " ".join(msgs)


@check
def flash_attention_gqa_and_ragged_length():
    """The GQA leg (Hkv < H, K/V broadcast by ops/pipeline_ops._expand_kv)
    at a T that is NOT a multiple of 128 (``_pad_to_lanes`` pads to the
    lane width, masks the K padding, slices the Q padding away), forward
    and backward, vs the reference's native grouped einsum."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    reference_attention)
    from paddle_tpu.ops.pipeline_ops import _expand_kv

    rng = np.random.RandomState(23)
    B, H, Hkv, T, D = 2, 8, 2, 200, 64
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32))

    def flash(q, k, v):
        kx, vx = _expand_kv(k, v, H)
        return flash_attention(q, kx, vx, causal=True)

    def ref(q, k, v):
        return reference_attention(q, k, v, None, True, None)

    got, want = flash(q, k, v), ref(q, k, v)
    assert got.shape == want.shape == (B, H, T, D)
    err_f = float(jnp.abs(got - want).max())
    assert err_f < 2e-2, err_f
    gf = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) ** 2),
                          argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                          argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = max(float(jnp.abs(b).max()), 1.0)
        err = float(jnp.abs(a - b).max()) / scale
        assert err < 2e-2, (name, err)
    return f"T={T} Hkv={Hkv}/H={H}: fwd err {err_f:.1e}"


@check
def norm_backward_matches_generic_vjp():
    """The hand-written batch_norm/layer_norm/rms_norm backwards
    (ops/nn_ops.py, the HBM byte cut) vs the generic vjp-of-forward they
    replace — ON CHIP under AMP bf16, through the executor surface. The
    CPU parity tests (tests/test_norm_grads.py) pin f32 math; this pins
    the bf16 MXU dtype policy the sessions bench."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.core.registry import get_op

    prior_amp = pt.amp_enabled()
    saved = {}

    def run(generic):
        if generic:
            for name in ("batch_norm", "layer_norm", "rms_norm"):
                od = get_op(name)
                saved[name] = od.grad_fn
                od.grad_fn = None
        try:
            pt.set_amp(True)
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                x = layers.data("x", shape=[12, 10, 6])
                x.stop_gradient = False
                h = layers.conv2d(x, num_filters=8, filter_size=3,
                                  padding=1, data_format="NHWC",
                                  param_attr=pt.ParamAttr(name="tcw"),
                                  bias_attr=False)
                h = layers.batch_norm(h, data_layout="NHWC", act="relu",
                                      param_attr=pt.ParamAttr(name="tbs"),
                                      bias_attr=pt.ParamAttr(name="tbb"))
                h = layers.reshape(h, shape=[-1, 12 * 10 * 8])
                h = layers.layer_norm(h, begin_norm_axis=1,
                                      param_attr=pt.ParamAttr(name="tls"),
                                      bias_attr=pt.ParamAttr(name="tlb"))
                h = layers.rms_norm(h, begin_norm_axis=1,
                                    param_attr=pt.ParamAttr(name="trs"))
                loss = layers.mean(layers.square(h))
                pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
                    loss, startup_program=startup)
            exe, scope = _executor_pair()
            exe.run(startup, scope=scope)
            rng = np.random.RandomState(13)
            feed = {"x": rng.rand(8, 12, 10, 6).astype("float32")}
            fetch = ["x@GRAD", "tcw@GRAD", "tbs@GRAD", "tbb@GRAD",
                     "tls@GRAD", "tlb@GRAD", "trs@GRAD"]
            outs = exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
            return {n: np.asarray(o, dtype=np.float32)
                    for n, o in zip(fetch, outs)}
        finally:
            for name, g in saved.items():
                get_op(name).grad_fn = g
            saved.clear()
            pt.set_amp(prior_amp)

    custom = run(False)
    generic = run(True)
    worst = 0.0
    for n in custom:
        a, b = custom[n], generic[n]
        scale = max(np.abs(b).max(), 1e-3)
        err = np.abs(a - b).max() / scale
        assert err < 3e-2, (n, err)
        worst = max(worst, err)
    return f"worst rel err {worst:.1e}"


@check
def fused_head_matches_unfused():
    """Chunked fused_head_cross_entropy vs fc + softmax_with_cross_entropy
    ON CHIP under AMP bf16 — loss and both gradients, including a padded
    tail chunk (vocab 100, chunk 32)."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    prior_amp = pt.amp_enabled()
    n, d, vocab, chunk = 64, 32, 100, 32
    rng = np.random.RandomState(17)
    feed = {"x": (rng.randn(n, d) * 0.5).astype("float32"),
            "lab": rng.randint(0, vocab, (n, 1)).astype("int64")}

    def run(fused):
        pt.set_amp(True)
        try:
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                x = layers.data("x", shape=[d])
                x.stop_gradient = False
                lab = layers.data("lab", shape=[1], dtype="int64")
                if fused:
                    loss = layers.fused_head_cross_entropy(
                        x, lab, num_classes=vocab, chunk=chunk,
                        param_attr=pt.ParamAttr(name="fhw"))
                else:
                    logits = layers.fc(x, size=vocab, bias_attr=False,
                                       param_attr=pt.ParamAttr(name="fhw"))
                    loss = layers.softmax_with_cross_entropy(logits, lab)
                m = layers.mean(loss)
                pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
                    m, startup_program=startup)
            exe, scope = _executor_pair()
            exe.run(startup, scope=scope)
            outs = exe.run(main, feed=feed,
                           fetch_list=[m, "x@GRAD", "fhw@GRAD"],
                           scope=scope)
            return [np.asarray(o, dtype=np.float32) for o in outs]
        finally:
            pt.set_amp(prior_amp)

    got = run(True)
    want = run(False)
    worst = 0.0
    for name, a, b in zip(["loss", "dx", "dw"], got, want):
        scale = max(np.abs(b).max(), 1e-3)
        err = np.abs(a - b).max() / scale
        assert err < 3e-2, (name, err)
        worst = max(worst, err)
    return f"worst rel err {worst:.1e}"


@check
def paged_attention_decode_matches_reference():
    """The paged decode kernel at the three serving rows (16 x 64 float32,
    16 x 128 bf16, and 28 query over 4 KV heads of 128 bf16 under a window
    of 100 keys; pages of 64) against a float64 softmax over each row's
    own pages: float32 pages as exact as the gathered XLA reference
    (1e-4), bf16 pages no further off than that reference is. Vacant
    slot, page boundary, full table, shared and permuted pages; every
    page no row holds is NaN, and under a window so is every table entry
    behind the window's first page."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import reference_attention
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    from paddle_tpu.ops.pipeline_ops import _gather_pages

    L, N, ps, P, layer = 2, 40, 64, 8, 1
    rows = [[], [5], [6, 7], [6, 7, 9, 3, 30, 2, 1, 39], [31, 8, 4]]
    lengths = np.array([1, ps, 2 * ps, P * ps, 2 * ps + 17], np.int32)
    table = np.zeros((len(rows), P), np.int32)
    for s, pages in enumerate(rows):
        table[s, :len(pages)] = pages
    held = sorted({0} | {p for r in rows for p in r})
    detail = []
    for dtype, H, Hkv, dh, window in ((jnp.float32, 16, 16, 64, None),
                                      (jnp.bfloat16, 16, 16, 128, None),
                                      (jnp.bfloat16, 28, 4, 128, 100)):
        rng = np.random.RandomState(11)
        G = H // Hkv
        pools = [jnp.asarray(rng.randn(L, N, ps, Hkv * dh), dtype)
                 for _ in range(2)]
        first = np.zeros(len(rows), np.int64)
        walked = table.copy()
        if window is not None:      # entries behind the window: poisoned
            first = np.maximum(lengths - window, 0)
            for s in range(len(rows)):
                walked[s, :first[s] // ps] = 38
        q = jnp.asarray(2 * rng.randn(len(rows), H, dh), dtype)
        poison = np.ones((L, N, 1, 1), bool)
        poison[layer, held] = False
        ck, cv = (jnp.where(jnp.asarray(poison), jnp.nan, a) for a in pools)
        kw = {} if window is None else {"window": window}
        got = np.asarray(paged_attention_decode(
            q, ck, cv, jnp.int32(layer), jnp.asarray(walked),
            jnp.asarray(lengths), **kw).astype(jnp.float32), np.float64)
        xla = reference_attention(
            q[:, :, None], _gather_pages(pools[0], layer, table, Hkv),
            _gather_pages(pools[1], layer, table, Hkv),
            lengths=jnp.asarray(lengths), **kw)
        xla = np.asarray(xla.astype(jnp.float32), np.float64).reshape(
            len(rows), -1)
        q64, k64, v64 = (np.asarray(a.astype(jnp.float32), np.float64)
                         for a in (q, *pools))
        want = np.zeros_like(got)
        for s in range(len(rows)):
            n, n0 = int(lengths[s]), int(first[s])
            k = k64[layer, table[s]].reshape(-1, Hkv, dh)[n0:n]
            v = v64[layer, table[s]].reshape(-1, Hkv, dh)[n0:n]
            k, v = (np.repeat(a, G, axis=1) for a in (k, v))
            sc = np.einsum("hd,jhd->hj", q64[s], k) / np.sqrt(dh)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want[s] = np.einsum("hj,jhd->hd", p, v).reshape(-1)
        err, ref_err = np.abs(got - want).max(), np.abs(xla - want).max()
        tol = 1e-4 if dtype == jnp.float32 else max(
            2 * ref_err, 2.0 ** -8 * np.abs(want).max())
        assert np.isfinite(got).all() and err <= tol, (str(dtype), err, tol)
        detail.append(f"{jnp.dtype(dtype).name} {H}/{Hkv}x{dh} err "
                      f"{err:.2e} (gathered reference {ref_err:.2e})")
    return "; ".join(detail)


@check
def paged_attention_prefill_matches_gathered():
    """The chunk walk (``paged_attention_prefill``) against the gathered
    path (``_gather_pages`` + ``reference_attention``) at the
    ``kexaone-serve-reason`` cell's shapes (64 query over 8 KV heads of 128,
    bf16 pages of 64, chunks of 256, a table 96 wide), one full layer and
    one window layer (128 keys), two chunks of ONE prompt: the first at
    start 0, whole; the second at start 256 with 190 real tokens. The
    kernel's pools hold NaN in every page out of the walk's reach (the
    window layer: every page behind the window too); the bf16 results are
    no further from a float32 softmax than the gathered path's own."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import reference_attention
    from paddle_tpu.kernels.paged_attention import (chunk_pages_in_reach,
                                                    paged_attention_prefill)
    from paddle_tpu.ops.pipeline_ops import _gather_pages

    L, N, ps, P, layer = 2, 64, 64, 96, 1
    H, Hkv, dh, Tc = 64, 8, 128, 256
    rng = np.random.RandomState(7)
    pools = [jnp.asarray(rng.randn(L, N, ps, Hkv * dh), jnp.bfloat16)
             for _ in range(2)]
    pages = rng.permutation(np.arange(1, N))[:7]
    table = np.full((1, P), N + 5, np.int32)    # the tail: out of range
    table[0, :len(pages)] = pages
    detail = []
    for window in (None, 128):
        for start, length in ((0, Tc), (Tc, 190)):
            q = jnp.asarray(2 * rng.randn(1, H, Tc, dh), jnp.bfloat16)
            first, end = (int(a) for a in chunk_pages_in_reach(
                np.int64(start), np.int64(length), ps, window, xp=np))
            poison = np.ones((L, N, 1, 1), bool)
            poison[layer, table[0, first:end]] = False
            ck, cv = (jnp.where(jnp.asarray(poison), jnp.nan, a)
                      for a in pools)
            s0 = jnp.asarray([start], jnp.int32)
            n0 = jnp.asarray([length], jnp.int32)
            walk = jax.jit(lambda q, ck, cv, s0, n0, w=window:
                           paged_attention_prefill(
                               q, ck, cv, jnp.int32(layer),
                               jnp.asarray(table), s0, n0, window=w))
            got = np.asarray(walk(q, ck, cv, s0, n0).astype(jnp.float32))
            t0 = time.perf_counter()
            walk(q, ck, cv, s0, n0).block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3
            seen = jnp.asarray(np.clip(table, 0, N - 1))
            m = dict(causal=True, q_pos0=s0)
            if window is not None:
                m.update(window=window, k_pos0=jnp.zeros((1,), jnp.int32))

            def gathered(q, k, v):
                out = reference_attention(
                    q, _gather_pages(k, layer, seen, Hkv),
                    _gather_pages(v, layer, seen, Hkv), **m)
                return out.transpose(0, 2, 1, 3).reshape(1, Tc, -1)

            want = np.asarray(gathered(q, *pools).astype(jnp.float32))
            truth = np.asarray(gathered(*(
                a.astype(jnp.float32) for a in (q, *pools))))
            err = np.abs(got - truth)[0, :length].max()
            ref_err = np.abs(want - truth)[0, :length].max()
            tol = max(2 * ref_err, 2.0 ** -8 * np.abs(truth).max())
            assert np.isfinite(got).all() and err <= tol, (
                window, start, err, tol)
            assert not got[0, length:].any()    # padding queries: zeros
            detail.append(f"w{window} s{start}: err {err:.1e} (gathered "
                          f"{ref_err:.1e}) {ms:.2f} ms with dispatch")
    return "; ".join(detail)


@check
def paged_mla_prefill_matches_gathered():
    """The latent chunk walk (``paged_mla_prefill``: ONE pool as key and
    value) against the gathered form ``_mla_paged_step`` ran (``ck[l, tbl]``
    + ``reference_attention``, the row's first r columns the value, scale 1
    on the queries) at the two latent cells' shapes, 32 heads, bf16 pages of
    256: ``mistral4-serve-longdoc`` (row 384, latent 256, a table 80 wide:
    a 256-token question chunk and a 64-token tail with 40 real tokens,
    both behind a 16,640-key context, mid-page) and ``ling3-serve-reason``
    (row 640, latent 512, a table 48 wide: a first chunk, whole, and a
    chunk at 3,840 with 190 real tokens). The kernel's pool holds NaN in
    every page out of the walk's reach and the table's tail names a page
    that does not exist; the bf16 results are no further from a float32
    softmax than the gathered form's own."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import reference_attention
    from paddle_tpu.kernels.paged_attention import (chunk_pages_in_reach,
                                                    paged_attention_prefill)

    L, N, ps, H, layer = 2, 96, 256, 32, 1
    rng = np.random.RandomState(9)
    detail = []
    for W, r, P, calls in (
            (384, 256, 80, ((16640 + 37, 256, 256), (16640 + 293, 64, 40))),
            (640, 512, 48, ((0, 256, 256), (3840, 256, 190)))):
        pool = jnp.asarray(rng.randn(L, N, ps, W), jnp.bfloat16)
        for start, Tc, length in calls:
            first, end = (int(a) for a in chunk_pages_in_reach(
                np.int64(start), np.int64(length), ps, None, xp=np))
            table = np.full((1, P), N + 5, np.int32)    # the tail: no page
            table[0, :end] = rng.permutation(np.arange(1, N))[:end]
            q = jnp.asarray(0.3 * rng.randn(1, H, Tc, W), jnp.bfloat16)
            poison = np.ones((L, N, 1, 1), bool)
            poison[layer, table[0, first:end]] = False
            ck = jnp.where(jnp.asarray(poison), jnp.nan, pool)
            s0 = jnp.asarray([start], jnp.int32)
            n0 = jnp.asarray([length], jnp.int32)
            walk = jax.jit(lambda q, ck, s0, n0, table=table, r=r:
                           paged_attention_prefill(
                               q, ck, None, jnp.int32(layer),
                               jnp.asarray(table), s0, n0, sm_scale=1.0,
                               value_width=r))
            got = np.asarray(walk(q, ck, s0, n0).astype(jnp.float32))
            t0 = time.perf_counter()
            walk(q, ck, s0, n0).block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3
            seen = jnp.asarray(np.clip(table, 0, N - 1))

            def gathered(q, ck):
                lat = ck[layer, seen].reshape(1, 1, P * ps, W)
                out = reference_attention(q, lat, lat[..., :r], sm_scale=1.0,
                                          causal=True, q_pos0=s0)
                return out.transpose(0, 2, 1, 3).reshape(1, Tc, -1)

            want = np.asarray(jax.jit(gathered)(q, pool).astype(jnp.float32))
            truth = np.asarray(jax.jit(gathered)(
                q.astype(jnp.float32), pool.astype(jnp.float32)))
            err = np.abs(got - truth)[0, :length].max()
            ref_err = np.abs(want - truth)[0, :length].max()
            tol = max(2 * ref_err, 2.0 ** -8 * np.abs(truth).max())
            assert got.shape == (1, Tc, H * r), got.shape
            assert np.isfinite(got).all() and err <= tol, (
                W, start, err, tol)
            assert not got[0, length:].any()    # padding queries: zeros
            detail.append(f"w{W} s{start}+{length}/{Tc}: err {err:.1e} "
                          f"(gathered {ref_err:.1e}) {ms:.2f} ms")
    return "; ".join(detail)


@check
def paged_mla_masked_walk_matches_the_gather():
    """A sparse latent layer's two walks under its pick as a group mask
    (``_dsa_pick`` + ``group_mask=``) against ``_dsa_attend``'s gather of
    the picked rows, at ``glm53f-serve-longctx``'s shapes cut in heads and
    depth of table (16 heads over a 512-wide row, bf16 pages of 256, groups
    of 4, 2048 picked tokens, an indexer of 8 heads of 128): a 256-query
    chunk at 5,000 (past the pick: 1,250 groups before) with 200 real
    queries, one at 700 (inside it: every group picked), and a tick of rows
    at 9,000 / 1,500 / vacant. Both sides score the same pooled keys, so the
    picks are the same; both round P and the result to bfloat16."""
    import types

    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.ops import pipeline_ops as po

    N, ps, W, H, G, Hi, Di, P = 96, 256, 512, 16, 4, 8, 128, 40
    blk = types.SimpleNamespace(index_pool=G, index_topk=2048, index_heads=Hi,
                                kv_lora_rank=W)
    rng = np.random.RandomState(13)
    ck = jnp.asarray(rng.randn(1, N, ps, W), jnp.bfloat16)
    ci = jnp.asarray(rng.randn(1, N, ps // G, Di), jnp.bfloat16)
    detail = []

    def operands(b, t):
        table = np.stack([rng.permutation(np.arange(1, N))[:P]
                          for _ in range(b)]).astype(np.int32)
        return (jnp.asarray(table),
                jnp.asarray(0.3 * rng.randn(b, H, t, W), jnp.bfloat16),
                jnp.asarray(rng.randn(b, t, Hi, Di), jnp.float32),
                jnp.asarray(rng.randn(b, t, Hi), jnp.float32))

    for start, Tc, real in ((5000, 256, 200), (700, 256, 256)):
        table, q, q_i, w_i = operands(1, Tc)
        s0, n0 = jnp.asarray([start], jnp.int32), jnp.asarray([real], jnp.int32)
        pos = s0[:, None] + jnp.arange(Tc, dtype=jnp.int32)[None, :]

        def walk(q, q_i, w_i):
            picked = po._dsa_pick(blk, q_i, w_i, ci, 0, table, pos)
            o = pa.paged_attention_prefill(
                q, ck, None, 0, table, s0, n0, sm_scale=1.0, value_width=W,
                group_mask=picked, group_rows=G)
            return o.reshape(1, Tc, H, W).transpose(0, 2, 1, 3), picked

        got, picked = jax.jit(walk)(q, q_i, w_i)
        want = jax.jit(lambda q, q_i, w_i: po._dsa_attend(
            blk, q, q_i, w_i, ck, ci, 0, table, pos))(q, q_i, w_i)
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        err = np.abs(got - want)[0, :, :real].max()
        n_picked = np.asarray(picked)[0, :real].sum(-1)
        assert (n_picked == np.minimum((start + np.arange(real)) // G,
                                       2048 // G - 1) + 1).all()
        # (a few bfloat16 ulps of the largest result; a wrong pick reads 0.3+)
        tol = 2.0 ** -6 * np.abs(want).max()
        assert np.isfinite(got).all() and err <= tol, (start, err, tol)
        assert not got[0, :, real:].any()
        detail.append(f"chunk {start}+{real}/{Tc}: err {err:.1e} "
                      f"(tol {tol:.1e})")
    lengths = jnp.asarray([9000, 1500, 0], jnp.int32)
    table, q, q_i, w_i = operands(3, 1)
    pos = (lengths - 1)[:, None]

    def tick(q, q_i, w_i):
        picked = po._dsa_pick(blk, q_i, w_i, ci, 0, table, pos)
        return pa.paged_attention_decode(
            q[:, :, 0], ck, None, 0, table, lengths, sm_scale=1.0,
            name=pa.MLA_KERNEL, group_mask=picked[:, 0],
            group_rows=G).reshape(3, H, W)

    got = np.asarray(jax.jit(tick)(q, q_i, w_i).astype(jnp.float32))
    want = np.asarray(jax.jit(lambda q, q_i, w_i: po._dsa_attend(
        blk, q, q_i, w_i, ck, ci, 0, table, pos))(q, q_i, w_i)[:, :, 0]
        .astype(jnp.float32))
    err = np.abs(got - want)[:2].max()
    tol = 2.0 ** -6 * np.abs(want[:2]).max()
    assert np.isfinite(got).all() and err <= tol, (err, tol)
    assert not got[2].any()
    detail.append(f"tick 9000/1500/vacant: err {err:.1e} (tol {tol:.1e})")
    return "; ".join(detail)


@check
def grouped_matmul_matches_ragged_dot():
    """The grouped-matmul kernel against ``jax.lax.ragged_dot`` on the
    sliced layer, compiled, at two serving shapes: smallthinker's prefill
    unit (1536 rows over 64 experts, 2560 -> 768, layer 5 of 12, uneven
    groups with empty ones) and kexaone's (2048 static rows of which 130
    are owned by 8 held experts, 6144 -> 2048: rows behind the groups are
    visited by nothing). bf16 operands, float32 accumulation on both sides:
    the owned rows agree to the last bits of a K-long float32 sum."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul

    rng = np.random.RandomState(11)
    detail = []
    for m, k, n, layers, groups, layer, owned in (
            (1536, 2560, 768, 12, 64, 5, 1536),
            (2048, 6144, 2048, 3, 8, 2, 130)):
        cut = np.sort(rng.randint(0, owned + 1, groups - 1))
        sizes = np.diff(np.concatenate([[0], cut, [owned]])).astype(np.int32)
        sizes[rng.randint(0, groups, 2)] = 0        # empty groups too
        owned = int(sizes.sum())
        rows = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        w = jnp.asarray(0.05 * rng.randn(layers * groups, k, n), jnp.bfloat16)
        got = jax.jit(lambda r, w, s, l: grouped_matmul(r, w, s, layer=l))(
            rows, w, jnp.asarray(sizes), jnp.int32(layer))
        want = jax.jit(lambda r, w, s: jax.lax.ragged_dot(
            r, w, s, preferred_element_type=jnp.float32))(
                rows, w[layer * groups:(layer + 1) * groups],
                jnp.asarray(sizes))
        got, want = np.asarray(got)[:owned], np.asarray(want)[:owned]
        err = np.abs(got - want).max()
        tol = 1e-5 * np.abs(want).max() * np.sqrt(k)
        assert np.isfinite(got).all() and err <= tol, (m, k, n, err, tol)
        detail.append(f"{m}x{k}->{n}: {int((sizes > 0).sum())}/{groups} "
                      f"groups, {owned} rows, err {err:.1e} (tol {tol:.1e})")
    return "; ".join(detail)


def main():
    failures = 0
    # (names on the command line: those checks alone)
    for fn in [c for c in CHECKS
               if not sys.argv[1:] or c.__name__ in sys.argv[1:]]:
        t0 = time.perf_counter()
        try:
            detail = fn() or ""
            ok = True
        except Exception:
            detail = traceback.format_exc(limit=3).strip().replace("\n", " | ")
            ok = False
            failures += 1
        print(json.dumps({"check": fn.__name__, "ok": ok,
                          "seconds": round(time.perf_counter() - t0, 2),
                          "detail": str(detail)[:400]}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
