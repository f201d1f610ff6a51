"""The hand-written batch_norm/layer_norm backward (ops/nn_ops.py
_batch_norm_grad/_layer_norm_grad — the HBM byte-reduction for ResNet/LM
training, PERF.md) must match the generic vjp-of-forward gradient it
replaced. The generic path is recovered by monkeypatching the op's
grad_fn away before append_backward runs (backward.py consults it at
build time), so both programs differentiate the identical forward."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core.registry import get_op


def _grads(build, monkeypatch, generic, fetch):
    if generic:
        for op_name in ("batch_norm", "layer_norm"):
            monkeypatch.setattr(get_op(op_name), "grad_fn", None)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        loss, feed = build()
        pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
            loss, startup_program=startup)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    names = [n for n in fetch if main.global_block.has_var(n)]
    assert names == fetch
    outs = exe.run(main, feed=feed, fetch_list=names, scope=scope)
    return {n: np.asarray(o) for n, o in zip(names, outs)}


def _bn_net(fmt, is_test=False):
    rng = np.random.RandomState(0)
    shape = [8, 6, 5, 4] if fmt == "NHWC" else [8, 4, 6, 5]
    x = layers.data("x", shape=shape[1:])
    x.stop_gradient = False
    y = layers.batch_norm(x, data_layout=fmt, is_test=is_test,
                          param_attr=pt.ParamAttr(name="bn_s"),
                          bias_attr=pt.ParamAttr(name="bn_b"))
    loss = layers.mean(layers.square(y))
    feed = {"x": rng.randn(*shape).astype("float32")}
    return loss, feed


@pytest.mark.parametrize("fmt", ["NHWC", "NCHW"])
def test_batch_norm_grad_matches_generic_vjp(monkeypatch, fmt):
    fetch = ["x@GRAD", "bn_s@GRAD", "bn_b@GRAD"]
    custom = _grads(lambda: _bn_net(fmt), monkeypatch, False, fetch)
    generic = _grads(lambda: _bn_net(fmt), monkeypatch, True, fetch)
    for n in fetch:
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_batch_norm_inference_grad_matches_generic_vjp(monkeypatch):
    fetch = ["x@GRAD", "bn_s@GRAD", "bn_b@GRAD"]
    custom = _grads(lambda: _bn_net("NHWC", is_test=True),
                    monkeypatch, False, fetch)
    generic = _grads(lambda: _bn_net("NHWC", is_test=True),
                     monkeypatch, True, fetch)
    for n in fetch:
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_batch_norm_inference_running_stat_grads(monkeypatch):
    """is_test batch_norm genuinely depends on its Mean/Variance INPUTS;
    when those are differentiable the custom grad must reproduce the
    generic vjp's nonzero gradients (code-review finding: the first cut
    silently zero-filled them)."""
    def build():
        rng = np.random.RandomState(3)
        x = layers.data("x", shape=[6, 5, 4])
        x.stop_gradient = False
        y = layers.batch_norm(x, data_layout="NHWC", is_test=True,
                              param_attr=pt.ParamAttr(name="bn2_s"),
                              bias_attr=pt.ParamAttr(name="bn2_b"))
        blk = y.block
        # the layer names its running stats <prefix>.mean/.var; mark
        # them differentiable to exercise the Mean/Variance grad path
        for name, var in blk.vars.items():
            if name.endswith(".mean") or name.endswith(".var"):
                var.stop_gradient = False
        loss = layers.mean(layers.square(y))
        feed = {"x": rng.randn(8, 6, 5, 4).astype("float32")}
        return loss, feed

    # find the stat var names from a probe build
    main = pt.Program()
    with pt.program_guard(main, pt.Program()):
        loss, _ = build()
    stats = sorted(n for n in main.global_block.vars
                   if n.endswith(".mean") or n.endswith(".var"))
    assert len(stats) == 2, stats
    fetch = ["x@GRAD"] + [s + "@GRAD" for s in stats]
    custom = _grads(build, monkeypatch, False, fetch)
    generic = _grads(build, monkeypatch, True, fetch)
    for n in fetch:
        assert np.abs(custom[n]).max() > 0, n
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_batch_norm_training_stat_update_grads(monkeypatch):
    """The running-stat UPDATE (mean_out/var_out = momentum*old +
    (1-momentum)*batch_stat) is differentiable w.r.t. x and the old
    stats; a loss touching the updated stats must get the same gradients
    from the custom backward as from the generic vjp (code-review
    finding: the first cut raised NotImplementedError here)."""
    def build():
        rng = np.random.RandomState(6)
        x = layers.data("x", shape=[6, 5, 4])
        x.stop_gradient = False
        y = layers.batch_norm(x, data_layout="NHWC",
                              param_attr=pt.ParamAttr(name="bn3_s"),
                              bias_attr=pt.ParamAttr(name="bn3_b"))
        blk = y.block
        stat_vars = [v for n, v in blk.vars.items()
                     if n.endswith(".mean") or n.endswith(".var")]
        assert len(stat_vars) == 2
        reg = None
        for v in stat_vars:
            v.stop_gradient = False
            term = layers.mean(layers.square(v))
            reg = term if reg is None else \
                layers.elementwise_add(reg, term)
        loss = layers.elementwise_add(layers.mean(layers.square(y)), reg)
        feed = {"x": rng.randn(8, 6, 5, 4).astype("float32")}
        return loss, feed

    fetch = ["x@GRAD", "bn3_s@GRAD", "bn3_b@GRAD"]
    custom = _grads(build, monkeypatch, False, fetch)
    generic = _grads(build, monkeypatch, True, fetch)
    for n in fetch:
        assert np.abs(custom[n]).max() > 0, n
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_batch_norm_saved_stat_grads(monkeypatch):
    """SavedMean/SavedVariance (batch mean / batch inverse std) are plain
    functions of X; a loss touching them must match the generic vjp."""
    def build():
        rng = np.random.RandomState(12)
        x = layers.data("x", shape=[6, 5, 4])
        x.stop_gradient = False
        y = layers.batch_norm(x, data_layout="NHWC",
                              param_attr=pt.ParamAttr(name="bn4_s"),
                              bias_attr=pt.ParamAttr(name="bn4_b"))
        blk = y.block
        bn_op = [op for op in blk.ops if op.type == "batch_norm"][-1]
        loss = layers.mean(layers.square(y))
        for slot in ("SavedMean", "SavedVariance"):
            sv = blk.vars[bn_op.outputs[slot][0]]
            sv.stop_gradient = False
            loss = layers.elementwise_add(
                loss, layers.mean(layers.square(sv)))
        feed = {"x": rng.randn(8, 6, 5, 4).astype("float32")}
        return loss, feed

    fetch = ["x@GRAD", "bn4_s@GRAD", "bn4_b@GRAD"]
    custom = _grads(build, monkeypatch, False, fetch)
    generic = _grads(build, monkeypatch, True, fetch)
    for n in fetch:
        assert np.abs(custom[n]).max() > 0, n
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_batch_norm_stays_recompute_segment_eligible(monkeypatch):
    """grad_fn_is_optimization must keep BN/LN foldable into recompute
    segments: a conv+BN+relu span under recompute_guard still collapses
    to ONE seg_fwd (no shattering at the norm op), and its grads match
    the unguarded build."""
    from paddle_tpu.core.program import recompute_guard

    def build(recompute):
        rng = np.random.RandomState(5)
        x = layers.data("x", shape=[8, 8, 3])
        x.stop_gradient = False
        import contextlib
        ctx = recompute_guard() if recompute else contextlib.nullcontext()
        with ctx:
            h = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                              data_format="NHWC",
                              param_attr=pt.ParamAttr(name="cw"),
                              bias_attr=False)
            h = layers.batch_norm(h, data_layout="NHWC", act="relu",
                                  param_attr=pt.ParamAttr(name="bs"),
                                  bias_attr=pt.ParamAttr(name="bb"))
            h2 = layers.layer_norm(
                layers.reshape(h, shape=[-1, 8 * 8 * 4]),
                begin_norm_axis=1,
                param_attr=pt.ParamAttr(name="ls"),
                bias_attr=pt.ParamAttr(name="lb"))
        loss = layers.mean(layers.square(h2))
        feed = {"x": rng.rand(4, 8, 8, 3).astype("float32")}
        return loss, feed

    fetch = ["x@GRAD", "cw@GRAD", "bs@GRAD", "ls@GRAD"]
    plain = _grads(lambda: build(False), monkeypatch, False, fetch)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        loss, feed = build(True)
        pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
            loss, startup_program=startup)
    seg_ops = [op.type for op in main.global_block.ops
               if op.type in ("seg_fwd", "grad_seg")]
    assert seg_ops.count("seg_fwd") == 1, seg_ops
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    outs = exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
    for n, o in zip(fetch, outs):
        np.testing.assert_allclose(np.asarray(o), plain[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_per_layer_transformer_remat_matches_plain():
    """transformer_lm(remat=True) on the per-layer path: each block
    collapses into one recompute segment and the training trajectory
    matches the unrematerialized build."""
    from paddle_tpu import models

    def build(remat):
        rng = np.random.RandomState(15)
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            ids = layers.data("ids", shape=[12], dtype="int64")
            tgt = layers.data("tgt", shape=[12], dtype="int64")
            logits = models.transformer_lm(
                ids, vocab_size=48, d_model=16, n_layers=2, num_heads=2,
                max_len=12, remat=remat)
            loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.reshape(logits, shape=[-1, 48]),
                layers.reshape(tgt, shape=[-1, 1])))
            pt.optimizer.AdamOptimizer(learning_rate=5e-3).minimize(
                loss, startup_program=startup)
        feed = {"ids": rng.randint(0, 48, (3, 12)).astype("int64"),
                "tgt": rng.randint(0, 48, (3, 12)).astype("int64")}
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        ls = [float(np.asarray(exe.run(main, feed=feed,
                                       fetch_list=[loss],
                                       scope=scope)[0]))
              for _ in range(8)]
        segs = sum(1 for op in main.global_block.ops
                   if op.type == "seg_fwd")
        return ls, segs

    plain, segs0 = build(False)
    remat, segs1 = build(True)
    assert segs0 == 0
    assert segs1 == 2, segs1  # one segment per block
    np.testing.assert_allclose(remat, plain, rtol=2e-5, atol=2e-6)


def test_per_layer_remat_tags_explicit_program():
    """remat=True must tag the EXPLICIT main_program, not the ambient
    default (code-review finding: the guard landed on
    default_main_program and remat silently no-opped)."""
    from paddle_tpu import models

    main, startup = pt.Program(), pt.Program()
    ids = layers.data("ids", shape=[8], dtype="int64",
                      main_program=main)
    logits = models.transformer_lm(ids, vocab_size=16, d_model=8,
                                   n_layers=2, num_heads=1, max_len=8,
                                   remat=True, main_program=main,
                                   startup_program=startup)
    loss = layers.mean(logits, main_program=main,
                       startup_program=startup)
    pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(
        loss, startup_program=startup)
    segs = sum(1 for op in main.global_block.ops if op.type == "seg_fwd")
    assert segs == 2, segs


def _ln_net(begin):
    rng = np.random.RandomState(1)
    shape = [4, 7, 6]
    x = layers.data("x", shape=shape[1:])
    x.stop_gradient = False
    y = layers.layer_norm(x, begin_norm_axis=begin,
                          param_attr=pt.ParamAttr(name="ln_s"),
                          bias_attr=pt.ParamAttr(name="ln_b"))
    loss = layers.mean(layers.square(y))
    feed = {"x": rng.randn(*shape).astype("float32")}
    return loss, feed


def _rms_net(begin, shift):
    rng = np.random.RandomState(2)
    shape = [4, 7, 6]
    x = layers.data("x", shape=shape[1:])
    x.stop_gradient = False
    y = layers.rms_norm(x, begin_norm_axis=begin, shift=shift,
                        param_attr=pt.ParamAttr(name="rm_s"),
                        bias_attr=pt.ParamAttr(name="rm_b"))
    loss = layers.mean(layers.square(y))
    feed = {"x": rng.randn(*shape).astype("float32")}
    return loss, feed


@pytest.mark.parametrize("begin,shift", [(1, False), (2, True)])
def test_rms_norm_grad_matches_generic_vjp(monkeypatch, begin, shift):
    fetch = ["x@GRAD", "rm_s@GRAD"] + (["rm_b@GRAD"] if shift else [])
    def gen(generic):
        if generic:
            monkeypatch.setattr(get_op("rms_norm"), "grad_fn", None)
        return _grads(lambda: _rms_net(begin, shift), monkeypatch, False,
                      fetch)
    custom = gen(False)
    generic = gen(True)
    for n in fetch:
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_rms_norm_forward_numpy_reference():
    rng = np.random.RandomState(4)
    xv = rng.randn(3, 5).astype("float32")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[5])
        y = layers.rms_norm(x, begin_norm_axis=1,
                            param_attr=pt.ParamAttr(name="rms_ref_s"))
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    (out,) = exe.run(main, feed={"x": xv}, fetch_list=[y], scope=scope)
    want = xv / np.sqrt((xv ** 2).mean(axis=1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5,
                               atol=1e-6)


def test_transformer_rms_norm_trains():
    rng = np.random.RandomState(9)
    from paddle_tpu import models
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[16], dtype="int64")
        tgt = layers.data("tgt", shape=[16], dtype="int64")
        logits = models.transformer_lm(ids, vocab_size=64, d_model=32,
                                       n_layers=2, num_heads=2, max_len=16,
                                       norm_type="rms_norm")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, 64]),
            layers.reshape(tgt, shape=[-1, 1])))
        pt.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(
            loss, startup_program=startup)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"ids": rng.randint(0, 64, (4, 16)).astype("int64"),
            "tgt": rng.randint(0, 64, (4, 16)).astype("int64")}
    losses = []
    for _ in range(25):
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv)))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
    # no LayerNorm shift/mean plane anywhere: the rms blocks create only
    # scale parameters
    ln_ops = [op.type for op in main.global_block.ops
              if op.type == "layer_norm"]
    assert not ln_ops


def test_rms_norm_on_the_stacked_path_comes_from_the_spec():
    """``norm_type`` is the per-layer path's keyword; the stacked path
    takes its norm from ``spec=`` (PR 26: the old raise pointed nowhere).
    The keyword still raises, naming the spec, and leaves no orphan
    parameters; the spec builds RMSNorm planes with no bias."""
    from paddle_tpu import models

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[8], dtype="int64")
        with pytest.raises(ValueError, match="spec="):
            models.transformer_lm(ids, vocab_size=32, d_model=16,
                                  n_layers=1, num_heads=1, max_len=8,
                                  norm_type="rms_norm",
                                  pipeline_stack=True)
        assert not main.global_block.all_parameters()
        spec = pt.LMSpec(vocab_size=32, d_model=16, n_layers=1, num_heads=1,
                         max_len=8, norm="rms_norm")
        models.transformer_lm(ids, spec=spec, pipeline_stack=True)
    names = {p.name for p in main.global_block.all_parameters()}
    assert "lm_stack.stack_ln1_s" in names and "final_ln.scale" in names
    assert not {n for n in names if n.endswith(("ln1_b", "ln2_b"))}
    assert "final_ln.bias" not in names
    assert any(op.type == "rms_norm" for op in main.global_block.ops)


def _stat_output_net(kind):
    """A net whose loss touches the norm's auxiliary stat OUTPUTS
    (layer_norm Mean/Variance; rms_norm InvRms) — they are plain
    differentiable functions of X and must match the generic vjp."""
    rng = np.random.RandomState(8)
    shape = [4, 6, 5]
    x = layers.data("x", shape=shape[1:])
    x.stop_gradient = False
    helper_prog = x.block.program
    from paddle_tpu.layers.layer_helper import LayerHelper

    helper = LayerHelper(f"{kind}_stat_net", main_program=helper_prog)
    s = helper.create_parameter(pt.ParamAttr(name=f"{kind}_ss"),
                                shape=[5], dtype="float32")
    if kind == "layer_norm":
        outs, _ = helper.append_op(
            "layer_norm", {"X": [x], "Scale": [s]},
            ["Y", "Mean", "Variance"],
            {"epsilon": 1e-5, "begin_norm_axis": 2})
        stats = [outs["Mean"][0], outs["Variance"][0]]
    else:
        outs, _ = helper.append_op(
            "rms_norm", {"X": [x], "Scale": [s]}, ["Y", "InvRms"],
            {"epsilon": 1e-6, "begin_norm_axis": 2})
        stats = [outs["InvRms"][0]]
    loss = layers.mean(layers.square(outs["Y"][0]))
    for st in stats:
        st.stop_gradient = False
        loss = layers.elementwise_add(loss,
                                      layers.mean(layers.square(st)))
    feed = {"x": rng.randn(*shape).astype("float32")}
    return loss, feed


@pytest.mark.parametrize("kind", ["layer_norm", "rms_norm"])
def test_norm_stat_output_grads_match_generic_vjp(monkeypatch, kind):
    fetch = ["x@GRAD", f"{kind}_ss@GRAD"]
    def gen(generic):
        if generic:
            monkeypatch.setattr(get_op(kind), "grad_fn", None)
        return _grads(lambda: _stat_output_net(kind), monkeypatch, False,
                      fetch)
    custom = gen(False)
    generic = gen(True)
    for n in fetch:
        assert np.abs(custom[n]).max() > 0, n
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)


def test_norm_grads_match_generic_vjp_under_amp(monkeypatch):
    """The custom backward exists FOR the AMP path (bf16 activations, f32
    reduction accumulation): under set_amp(True) both norms must still
    track the generic vjp within bf16 tolerance."""
    def build():
        rng = np.random.RandomState(11)
        x = layers.data("x", shape=[6, 5, 4])
        x.stop_gradient = False
        h = layers.conv2d(x, num_filters=4, filter_size=1,
                          data_format="NHWC",
                          param_attr=pt.ParamAttr(name="amp_cw"),
                          bias_attr=False)
        h = layers.batch_norm(h, data_layout="NHWC", act="relu",
                              param_attr=pt.ParamAttr(name="amp_bs"),
                              bias_attr=pt.ParamAttr(name="amp_bb"))
        h = layers.layer_norm(layers.reshape(h, shape=[-1, 6 * 5 * 4]),
                              begin_norm_axis=1,
                              param_attr=pt.ParamAttr(name="amp_ls"),
                              bias_attr=pt.ParamAttr(name="amp_lb"))
        loss = layers.mean(layers.square(h))
        feed = {"x": rng.rand(8, 6, 5, 4).astype("float32")}
        return loss, feed

    fetch = ["x@GRAD", "amp_cw@GRAD", "amp_bs@GRAD", "amp_bb@GRAD",
             "amp_ls@GRAD", "amp_lb@GRAD"]
    pt.set_amp(True)
    try:
        custom = _grads(build, monkeypatch, False, fetch)
        generic = _grads(build, monkeypatch, True, fetch)
    finally:
        pt.set_amp(False)
    for n in fetch:
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-2,
                                   atol=2e-3, err_msg=n)


@pytest.mark.parametrize("begin", [1, 2])
def test_layer_norm_grad_matches_generic_vjp(monkeypatch, begin):
    fetch = ["x@GRAD", "ln_s@GRAD", "ln_b@GRAD"]
    custom = _grads(lambda: _ln_net(begin), monkeypatch, False, fetch)
    generic = _grads(lambda: _ln_net(begin), monkeypatch, True, fetch)
    for n in fetch:
        np.testing.assert_allclose(custom[n], generic[n], rtol=2e-5,
                                   atol=2e-6, err_msg=n)
