"""A forward op whose kernel holds a loop is traced ONCE for forward and
backward (core/backward.py: ``traced_once`` / ``grad_kept``).

XLA CSEs the forward subexpressions a generic ``grad`` op traces again
only in straight-line code; it does not merge two ``while`` loops. So
``append_backward`` pairs a ``has_loop`` forward op with its grad op and
the executor traces the forward under ``jax.vjp`` once. These tests pin
the structure (loops in the optimized HLO), the counter, that a program
without the grad op runs the plain kernel, bit-for-bit parity with
``jax.value_and_grad`` of the forward-only program, and which registered
ops carry the property.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.core import backward
from paddle_tpu.core.registry import get_op, registered_ops
from paddle_tpu.lm_spec import LMSpec

VOCAB, D, L, H, T, FF, B = 32, 16, 4, 2, 12, 64, 2
STACK = "pipelined_transformer_stack"


def _while_loops(exe, program, feed, fetch, scope):
    """``while`` instructions in the optimized HLO of the whole block."""
    fn, args = exe.as_function(program, feed, fetch, scope=scope)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return len(re.findall(r"= .* while\(", hlo))


def _lm_feed():
    ids = np.random.RandomState(0).randint(0, VOCAB, (B, T)).astype("int64")
    return {"ids": ids, "nxt": np.roll(ids, -1, 1)}


def _lm(remat=True, spec=None, train=True):
    """-> (main, startup, loss, forward-only clone made before minimize)"""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        nxt = layers.data("nxt", shape=[T], dtype="int64")
        kw = dict(spec=spec) if spec is not None else dict(
            vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H, d_ff=FF,
            max_len=T)
        logits = models.transformer_lm(ids, pipeline_stack=True, remat=remat,
                                       **kw)
        aux = None
        if isinstance(logits, tuple):
            logits, aux = logits
        loss = layers.mean(layers.softmax_with_cross_entropy(
            logits, layers.reshape(nxt, shape=[0, T, 1])))
        if aux is not None:
            loss = layers.elementwise_add(
                loss, layers.scale(layers.reshape(aux, shape=[]),
                                   spec.router_aux_loss_coef))
        fwd = main.clone(for_test=True)
        if train:
            pt.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(
                loss, startup_program=startup)
    main.random_seed = startup.random_seed = 13
    return main, startup, loss, fwd


def _moe_spec():
    return LMSpec(vocab_size=VOCAB, d_model=D, n_layers=2, num_heads=H,
                  max_len=T, norm="rms_norm", qk_norm=True, use_rope=True,
                  rope_pairing="half", bias=False, ffn="swiglu_moe",
                  num_experts=4, experts_per_tok=2, d_expert=32,
                  router_aux_loss_coef=0.01)


# --------------------------------------------------------------------------
# structure
# --------------------------------------------------------------------------
@pytest.mark.parametrize("remat", [True, False, "full"])
def test_stacked_train_step_holds_two_loops(remat):
    """One forward scan + one backward scan, whatever the remat policy
    (the parent compiled three: the forward op's scan, the generic grad
    op's own forward scan, and the backward scan)."""
    main, startup, loss, fwd = _lm(remat)
    scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    base = exe.cache_stats()["paired_vjp_ops"]
    assert _while_loops(exe, main, _lm_feed(), [loss], scope) == 2
    assert exe.cache_stats()["paired_vjp_ops"] - base == 1
    # the forward-only clone: one loop, nothing paired
    assert not any(backward.VJP_KEY_ATTR in op.attrs
                   for op in fwd.global_block.ops)
    assert _while_loops(exe, fwd, _lm_feed(), [loss], scope) == 1
    assert exe.cache_stats()["paired_vjp_ops"] - base == 1


def test_compile_span_carries_paired_vjp_ops():
    from paddle_tpu import trace

    main, startup, loss, fwd = _lm(True)
    scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    tracer = trace.get_tracer()
    tracer.clear()
    trace.enable(level=1)
    try:
        exe.run(main, feed=_lm_feed(), fetch_list=[loss], scope=scope)
        exe.run(fwd, feed=_lm_feed(), fetch_list=[loss], scope=scope)
        got = [s.attrs["paired_vjp_ops"] for s in tracer.spans()
               if s.name == "executor/compile"]
    finally:
        trace.disable()
        tracer.clear()
    assert got == [1, 0]


def test_pruned_export_holds_one_loop_no_key_and_runs(tmp_path):
    """save_inference_model of the TRAIN program: the pruned artifact
    carries no pair key, holds the forward's one loop, loads and runs to
    the train program's own logits."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        nxt = layers.data("nxt", shape=[T], dtype="int64")
        logits = models.transformer_lm(
            ids, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            d_ff=FF, max_len=T, pipeline_stack=True, remat=True)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            logits, layers.reshape(nxt, shape=[0, T, 1])))
        pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
            loss, startup_program=startup)
    scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    feed = _lm_feed()
    want, = exe.run(main, feed=feed, fetch_list=[logits], scope=scope)

    pt.io.save_inference_model(str(tmp_path), ["ids"], [logits], exe,
                               main_program=main, scope=scope)
    scope2, exe2 = pt.Scope(), pt.Executor(pt.TPUPlace())
    prog, feed_names, fetches = pt.io.load_inference_model(
        str(tmp_path), exe2, scope=scope2)
    ops = prog.global_block.ops
    assert any(op.type == STACK for op in ops)
    assert not any(backward.VJP_KEY_ATTR in op.attrs for op in ops)
    assert "__vjp_key__" not in (tmp_path / "__model__.json").read_text()
    infer_feed = {"ids": feed["ids"]}
    assert _while_loops(exe2, prog, infer_feed, fetches, scope2) == 1
    assert exe2.cache_stats()["paired_vjp_ops"] == 0
    got, = exe2.run(prog, feed=infer_feed, fetch_list=fetches, scope=scope2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_readers_of_the_block_understand_the_paired_program():
    """The pair is two attrs on op types every reader already knows: the
    checker, the cost model, the memory analyzer and the peak-memory
    scheduler take the train program as before, and the analyzer ends
    the stack's residuals at the grad op that shares its key."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis.memory import _paired_grad_index, analyze_memory
    from paddle_tpu.core.manifest import program_digest
    from paddle_tpu.transpiler.framework import PassContext
    from paddle_tpu.transpiler.schedule import ReducePeakMemory

    main, startup, loss, fwd = _lm(True)
    scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    analysis.check_program(main, ["ids", "nxt"], [loss.name], scope=scope)
    block = main.global_block
    i = next(k for k, op in enumerate(block.ops) if op.type == STACK)
    j = _paired_grad_index(block, i, block.ops[i])
    assert block.ops[j].type == "grad"
    assert block.ops[j].attrs["fwd_type"] == STACK
    mem = analyze_memory(main, ["ids", "nxt"], [loss.name], scope=scope,
                         batch_size=B)
    # the key is private metadata: it does not enter the digest a warmup
    # manifest finds the program by
    before = program_digest(main)
    for op in block.ops:
        if backward.VJP_KEY_ATTR in op.attrs:
            op.attrs = {k: v for k, v in op.attrs.items()
                        if k != backward.VJP_KEY_ATTR}
    main._bump()
    assert program_digest(main) == before
    # ... and with it the analyzer holds the planes the scan stacks for
    # its backward live from the stack to that grad op (without a key it
    # finds no grad op for the stack and drops them, as the parent did)
    keyless = analyze_memory(main, ["ids", "nxt"], [loss.name], scope=scope,
                             batch_size=B)
    held = mem.op_costs[i].residual_bytes
    assert held > 0
    assert mem.live_at_op[i + 1] - keyless.live_at_op[i + 1] == held
    assert mem.live_at_op[j + 1] == keyless.live_at_op[j + 1]
    # a reorder keeps forward before grad (data dependencies), so the
    # scheduled program still pairs
    main2, startup2, loss2, _ = _lm(True)
    ReducePeakMemory(batch_size=B).apply(
        main2, PassContext(["ids", "nxt"], [loss2.name], scope=scope))
    assert len(backward.vjp_pairs(main2.global_block.ops)) == 1


# --------------------------------------------------------------------------
# parity: the paired step against jax.value_and_grad of the forward program
# --------------------------------------------------------------------------
def _value_and_grad_of(fwd, loss, scope, feed, param_names):
    """``jax.value_and_grad`` of the forward-only program's function
    w.r.t. ``param_names``, at the parameters ``scope`` holds now."""
    exe = pt.Executor(pt.TPUPlace())
    fn, (feed_args, ro, rw) = exe.as_function(fwd, feed, [loss], scope=scope)
    assert not rw, "a forward-only program writes no state"
    ro_names = exe._compile(fwd, exe._normalize_feeds(fwd.global_block, feed),
                            [loss.name], scope).ro_state_names
    idx = [ro_names.index(n) for n in param_names]

    def f(params):
        full = list(ro)
        for k, p in zip(idx, params):
            full[k] = p
        return fn(feed_args, full, [])[0][0].reshape(())

    val, grads = jax.jit(jax.value_and_grad(f))(
        [jnp.asarray(ro[k]) for k in idx])
    return np.asarray(val), [np.asarray(g) for g in grads]


def _train_steps(main, startup, loss, feed, steps=3, trace_level=None,
                 exe=None):
    """-> (the losses and then every parameter after ``steps`` steps,
    the scope)"""
    scope = pt.Scope()
    exe = exe or pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    losses = [np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                 scope=scope, trace_level=trace_level)[0])
              for _ in range(steps)]
    names = sorted(p.name for p in main.global_block.all_parameters())
    return losses + [np.asarray(scope.get(n)) for n in names], scope


def _stack_only(remat=True, spec=None):
    """The stack alone between a fed activation and a mean: every op on
    the gradient's path (mean, the stack) goes through the generic
    ``grad`` op, so the step is autodiff's own arithmetic. (The LM's
    cross entropy, layer_norm and embedding take ``grad_custom`` ops
    whose hand-written kernels differ from autodiff by an ulp.)"""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[T, D])
        kw = dict(spec=spec) if spec is not None else dict(
            n_layers=L, num_heads=H, d_ff=FF)
        y = layers.pipelined_transformer_stack(x, remat=remat, **kw)
        aux = None
        if isinstance(y, tuple):
            y, aux = y
        loss = layers.mean(layers.square(y))
        if aux is not None:
            loss = layers.elementwise_add(
                loss, layers.scale(layers.reshape(aux, shape=[]),
                                   spec.router_aux_loss_coef))
        fwd = main.clone(for_test=True)
        pt.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(
            loss, startup_program=startup)
    main.random_seed = startup.random_seed = 13
    feed = {"x": np.random.RandomState(1).randn(B, T, D).astype("float32")}
    return main, startup, loss, fwd, feed


PARITY = {"remat": dict(remat=True), "plain": dict(remat=False),
          "full": dict(remat="full"), "moe": dict(spec=_moe_spec())}


@pytest.mark.parametrize("case", sorted(PARITY))
def test_three_adam_steps_match_value_and_grad_bitwise(case):
    """At each of 3 Adam steps the loss and every parameter's gradient
    equal, bit for bit on the CPU, jax.value_and_grad of the forward-only
    program at the same parameters: tracing the stack once loses no
    mathematics. ``moe`` is the swiglu_moe block (its AuxLoss output comes
    from the same trace). What Adam makes of equal gradients, and the
    eager per-op executor (whose kernels compile one by one, so not to
    the jitted reference's bits), are pinned against the unpaired program
    below."""
    main, startup, loss, fwd, feed = _stack_only(**PARITY[case])
    assert len(backward.vjp_pairs(main.global_block.ops)) == 1
    names = sorted(p.name for p in main.global_block.all_parameters())
    scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    losses = []
    for _ in range(3):
        want_loss, want_grads = _value_and_grad_of(fwd, loss, scope, feed,
                                                   names)
        got = exe.run(main, feed=feed, scope=scope,
                      fetch_list=[loss] + [n + "@GRAD" for n in names])
        np.testing.assert_array_equal(np.asarray(got[0]).reshape(()),
                                      want_loss)
        for n, g, w in zip(names, got[1:], want_grads):
            np.testing.assert_array_equal(np.asarray(g), w, err_msg=n)
        losses.append(float(want_loss))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("case", ["remat", "plain", "full", "moe",
                                  "interpreted"])
def test_lm_train_steps_match_the_unpaired_program_bitwise(case,
                                                           monkeypatch):
    """The whole LM step (embeddings, stack, head, cross entropy, Adam):
    three steps traced once equal three steps of the program built with
    the property off — the parent's program — bit for bit."""
    def run(paired):
        monkeypatch.setattr(get_op(STACK), "has_loop", paired)
        main, startup, loss, _ = (
            _lm(True, spec=_moe_spec()) if case == "moe" else
            _lm({"remat": True, "plain": False, "full": "full",
                 "interpreted": True}[case]))
        assert len(backward.vjp_pairs(main.global_block.ops)) == int(paired)
        level = 2 if case == "interpreted" else None
        return _train_steps(main, startup, loss, _lm_feed(),
                            trace_level=level)[0]

    for a, b in zip(run(True), run(False)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------
# what the layer checkpoint saves (ops/pipeline_ops._STACK_SAVED)
# --------------------------------------------------------------------------
def _count(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr`` and every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, primitive)
    return n


def _stack_grad(remat, spec=None):
    """-> (grad of a scalar of the stack OP w.r.t. its inputs, as a
    function of them; the inputs)"""
    spec = spec or LMSpec(vocab_size=0, d_model=D, n_layers=3, num_heads=H,
                          d_ff=FF)
    rng = np.random.RandomState(3)
    ins = {slot: [jnp.asarray(0.2 * rng.randn(spec.n_layers, *shape)
                              + (key.endswith("_s")), jnp.float32)]
           for slot, key, shape, _ in spec.stack_planes()}
    ins["X"] = [jnp.asarray(rng.randn(B, T, D), jnp.float32)]
    attrs = dict(spec.block.attrs(), causal=True, remat=remat)
    kernel = get_op(STACK).fn

    def loss(ins):
        outs = kernel(attrs, ins)
        return sum(jnp.sum(jnp.square(v[0])) for v in outs.values())

    return jax.grad(loss), ins


@pytest.mark.parametrize("amp", [False, True])
def test_saved_set_spares_the_backward_the_attention_half(amp):
    """The gradient's jaxpr of the stack op over a dense block: with
    nothing rematerialized 20 ``dot_general`` over the scan and the
    attention forward's ``exp`` twice (the forward's, and the one the CPU
    reference's backward differentiates); ``remat=True`` runs ONE product
    more, the FFN's first matmul, and no attention product and no qkv or
    out projection again; ``"full"`` runs five more and the softmax once
    more. ``"dots"``, the set this one replaces, also counted 21, but
    named the kernel's result OUTSIDE the custom VJP: on a chip the flash
    forward, which alone yields logsumexp, still ran twice."""
    pt.set_amp(amp)     # (conftest's autouse fixture puts it back)
    counts = {}
    for remat in (False, True, "full"):
        grad, ins = _stack_grad(remat)
        jaxpr = jax.make_jaxpr(grad)(ins).jaxpr
        counts[remat] = (_count(jaxpr, "dot_general"), _count(jaxpr, "exp"))
    assert counts[False] == (20, 2)
    assert counts[True] == (21, 2)
    assert counts["full"] == (25, 3)


def test_saved_set_of_an_expert_block_is_its_attention_half():
    """A ``swiglu_moe`` block tags nothing in its expert layer: ``True``
    spares the backward the attention forward and the out-projection
    (fewer products than ``"full"``) and recomputes the experts (more
    than with nothing rematerialized)."""
    def dots(remat):
        grad, ins = _stack_grad(remat, _moe_spec())
        jaxpr = jax.make_jaxpr(grad)(ins).jaxpr
        return (_count(jaxpr, "dot_general") + _count(jaxpr, "ragged_dot"),
                _count(jaxpr, "exp"))

    plain, saved, full = dots(False), dots(True), dots("full")
    assert plain[0] < saved[0] < full[0]
    assert saved[1] < full[1]


@pytest.mark.parametrize("amp", [False, True])
@pytest.mark.parametrize("block", ["dense", "moe"])
def test_gradients_agree_across_remat_policies(block, amp):
    """``True`` and ``"full"`` change what is kept, never the
    mathematics: every input's gradient equals the unrematerialized
    one's, bit for bit on the CPU without AMP as in PARITY's cases; under
    AMP to float32's last bits (XLA fuses a recomputed norm or GELU into
    other neighbours than the forward's)."""
    pt.set_amp(amp)     # (conftest's autouse fixture puts it back)
    spec = _moe_spec() if block == "moe" else None
    got = {}
    for remat in (False, True, "full"):
        grad, ins = _stack_grad(remat, spec)
        got[remat] = jax.jit(grad)(ins)
    tol = dict(rtol=1e-5, atol=2e-5) if amp else dict(rtol=0, atol=0)
    for remat in (True, "full"):
        for slot, (g,) in got[remat].items():
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(got[False][slot][0]),
                err_msg=f"{remat} {slot}", **tol)


def test_compile_span_carries_the_saved_planes_bytes():
    """``mem/stack_saved_bytes`` on the ``executor/compile`` span of a
    train step: what the layers' backward holds beside stream and weights.
    ``True``: 5 d a token a layer (q, k, v, the kernel's result, the
    out-projection's) in float32 without AMP; ``"full"``: nothing."""
    from paddle_tpu import trace

    tracer = trace.get_tracer()
    got = {}
    for remat in (True, "full"):
        main, startup, loss, _ = _lm(remat)
        scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
        exe.run(startup, scope=scope)
        tracer.clear()
        trace.enable(level=1)
        try:
            exe.run(main, feed=_lm_feed(), fetch_list=[loss], scope=scope)
            got[remat], = [s.attrs["mem/stack_saved_bytes"]
                           for s in tracer.spans()
                           if s.name == "executor/compile"]
        finally:
            trace.disable()
            tracer.clear()
    assert got == {True: L * B * T * 5 * D * 4, "full": 0}


def test_unknown_remat_value_is_refused():
    """``"dots"`` went with its set: a program that still states it
    fails where it is traced, it does not fall back to another policy."""
    grad, ins = _stack_grad("dots")
    with pytest.raises(ValueError, match="remat 'dots'"):
        grad(ins)


def test_gpipe_branch_paired_matches_unpaired_bitwise(monkeypatch):
    """The stack's GPipe branch (a ``pp`` mesh axis, fake CPU devices):
    the paired step equals the step built with the property off — the
    parent's program — bit for bit, and compiles one loop nest fewer."""
    from paddle_tpu.parallel import make_mesh, pipeline_plan

    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])

    def run(paired):
        monkeypatch.setattr(get_op(STACK), "has_loop", paired)
        main, startup, loss, _ = _lm(True)
        assert len(backward.vjp_pairs(main.global_block.ops)) == int(paired)
        exe = pt.Executor(mesh=mesh, plan=pipeline_plan(mesh))
        vals, scope = _train_steps(main, startup, loss, _lm_feed(), exe=exe)
        return vals, _while_loops(exe, main, _lm_feed(), [loss], scope)

    vals_p, loops_p = run(True)
    vals_u, loops_u = run(False)
    for a, b in zip(vals_p, vals_u):
        np.testing.assert_array_equal(a, b)
    assert loops_p < loops_u


def test_second_loss_over_the_same_forward_keeps_the_generic_grad():
    """One closure serves one grad op: a second append_backward over the
    same forward op emits a plain ``grad`` (it traces its own forward)."""
    main, startup, loss, _ = _lm(True, train=False)
    with pt.program_guard(main, startup):
        pt.append_backward(loss)
        pt.append_backward(loss)
    grads = [op for op in main.global_block.ops
             if op.type == "grad" and op.attrs["fwd_type"] == STACK]
    assert len(grads) == 2
    assert [backward.VJP_KEY_ATTR in g.attrs for g in grads] == [True, False]
    assert len(backward.vjp_pairs(main.global_block.ops)) == 1


# --------------------------------------------------------------------------
# the sweep: which registered ops double their loop without the pairing
# --------------------------------------------------------------------------
def _seq_data(d):
    x = layers.data("x", shape=[6, d])
    y = layers.data("y", shape=[1])
    feed = {"x": np.random.RandomState(0).randn(4, 6, d).astype("float32"),
            "y": np.ones((4, 1), "float32")}
    return x, y, feed


def _regress(seq, y):
    pred = layers.fc(layers.sequence_last_step(seq), size=1)
    return layers.mean(layers.square_error_cost(pred, y))


def _case_stack():
    ids = layers.data("ids", shape=[T], dtype="int64")
    logits = models.transformer_lm(
        ids, vocab_size=VOCAB, d_model=D, n_layers=2, num_heads=H, d_ff=FF,
        max_len=T, pipeline_stack=True, remat=True)
    return layers.mean(logits), {"ids": _lm_feed()["ids"]}


def _case_static_rnn():
    x, y, feed = _seq_data(3)
    acc0 = layers.fill_constant_batch_size_like(
        y, shape=[-1, 1], dtype="float32", value=0.0)
    rnn = layers.StaticRNN()
    with rnn.step():
        xt = rnn.step_input(x)
        acc = rnn.memory(init=acc0)
        new = layers.tanh(layers.elementwise_add(
            acc, layers.fc(xt, size=1, bias_attr=False)))
        rnn.update_memory(acc, new)
        rnn.step_output(new)
    return _regress(rnn(), y), feed


def _case_while():
    x = layers.data("x", shape=[4])
    n = layers.data("n", shape=[], dtype="float32", append_batch_size=False)
    state = layers.fc(x, size=4, bias_attr=False)
    i = layers.fill_constant(shape=[], value=0.0, dtype="float32")
    cond = layers.less_than(i, n)
    w = layers.While(cond, max_iters=5)
    with w.block():
        layers.assign(layers.scale(layers.tanh(state), 0.9), output=state)
        layers.assign(layers.increment(i, 1.0), output=i)
        layers.assign(layers.less_than(i, n), output=cond)
    return layers.mean(state), {
        "x": np.ones((3, 4), "float32"), "n": np.float32(3.0)}


def _case_lstm():
    x, y, feed = _seq_data(8)
    h, _ = layers.dynamic_lstm(x, size=8)
    return _regress(h, y), feed


def _case_gru():
    x, y, feed = _seq_data(6)
    return _regress(layers.dynamic_gru(x, size=2), y), feed


def _case_simple_rnn():
    x, y, feed = _seq_data(3)
    return _regress(layers.simple_rnn(x), y), feed


def _case_crf():
    x, _, feed = _seq_data(3)
    label = layers.data("label", shape=[6], dtype="int64")
    feed = {"x": feed["x"], "label": np.zeros((4, 6), "int64")}
    em = layers.fc(x, size=3, num_flatten_dims=2)
    return layers.mean(layers.linear_chain_crf(em, label)), feed


def _case_ctc():
    x, _, feed = _seq_data(4)
    label = layers.data("label", shape=[2], dtype="int32")
    feed = {"x": feed["x"], "label": np.ones((4, 2), "int32")}
    logits = layers.fc(x, size=4, num_flatten_dims=2)
    return layers.mean(layers.warpctc(logits, label)), feed


def _case_nmt_teacher():
    src = layers.data("src", shape=[6], dtype="int64")
    slen = layers.data("slen", shape=[], dtype="int32")
    tgt = layers.data("tgt", shape=[5], dtype="int64")
    logits = models.transformer_nmt_teacher(
        src, slen, tgt, src_vocab_size=16, tgt_vocab_size=16, d_model=8,
        n_layers=2, num_heads=2, max_src_len=6, max_tgt_len=5)
    return layers.mean(logits), {
        "src": np.ones((2, 6), "int64"), "slen": np.full((2,), 6, "int32"),
        "tgt": np.ones((2, 5), "int64")}


# every registered op that is differentiated through the generic ``grad``
# op and whose kernel holds a lax.scan / while_loop / fori_loop
SWEEP = {
    STACK: _case_stack,
    "static_rnn": _case_static_rnn,
    "while": _case_while,
    "lstm": _case_lstm,
    "gru": _case_gru,
    "simple_rnn": _case_simple_rnn,
    "linear_chain_crf": _case_crf,
    "warpctc": _case_ctc,
    "transformer_encdec_teacher": _case_nmt_teacher,
}


def _sweep_counts(op_type, paired, monkeypatch):
    monkeypatch.setattr(get_op(op_type), "has_loop", paired)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        loss, feed = SWEEP[op_type]()
        fwd = main.clone(for_test=True)
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(
            loss, startup_program=startup)
    assert any(op.type == op_type for op in main.global_block.ops)
    main.random_seed = startup.random_seed = 5
    scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    n_fwd = _while_loops(exe, fwd, feed, [loss], scope)
    n_train = _while_loops(exe, main, feed, [loss], scope)
    out = [np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                              scope=scope)[0]) for _ in range(2)]
    params = [np.asarray(scope.get(p.name))
              for p in main.global_block.all_parameters()]
    return n_fwd, n_train, out + params


@pytest.mark.parametrize("op_type", sorted(SWEEP))
def test_sweep_ops_whose_loop_doubles_carry_the_property(op_type,
                                                         monkeypatch):
    """Count ``while`` loops of the forward-only and the train program
    with the op paired and unpaired. Unpaired, the train program holds
    the forward's loops twice; paired, exactly that many fewer — and the
    same losses and parameters, bit for bit. The registry carries
    ``has_loop`` for exactly the ops that doubled."""
    registered = get_op(op_type).has_loop
    n_fwd, n_unpaired, vals_u = _sweep_counts(op_type, False, monkeypatch)
    _, n_paired, vals_p = _sweep_counts(op_type, True, monkeypatch)
    assert n_fwd >= 1, "the sweep lists loop-bearing ops only"
    doubled = n_unpaired > n_paired
    assert registered == doubled, (n_fwd, n_unpaired, n_paired)
    if doubled:
        assert n_unpaired - n_paired == n_fwd
    for a, b in zip(vals_p, vals_u):
        np.testing.assert_array_equal(a, b)


def test_the_property_sits_only_on_ops_the_generic_grad_differentiates():
    """Every ``has_loop`` op is a case of the sweep, and the registry
    audit (no ``grad_fn``: those get ``grad_custom``, which takes the
    forward's outputs and traces no forward; not ``special``; no
    randomness) finds nothing to say about any of them."""
    from paddle_tpu.analysis.conformance import audit_op

    flagged = [t for t in registered_ops() if get_op(t).has_loop]
    assert sorted(flagged) == sorted(SWEEP)
    for t in flagged:
        assert audit_op(t) == [], t
        assert t not in backward.NON_DIFFERENTIABLE, t
