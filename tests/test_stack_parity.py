"""The stacked (scan/pipeline) transformer and the per-layer encoder path
are two implementations of the same block; with identical weights they
must produce identical logits. Guards the pair against silent drift."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models

VOCAB, D, L, H, T, FF = 32, 16, 3, 2, 12, 64


def _build(pipeline_stack):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        logits = models.transformer_lm(ids, vocab_size=VOCAB, d_model=D,
                                       n_layers=L, num_heads=H, d_ff=FF,
                                       max_len=T,
                                       pipeline_stack=pipeline_stack)
    return main, startup, logits


@pytest.mark.slow  # tier-1 budget (PR 20): full stacked-vs-per-layer
# parity sweep; stack correctness stays tier-1 via test_attention and
# the sharded-stack tests
def test_stacked_matches_per_layer_with_copied_weights():
    exe = pt.Executor(pt.TPUPlace())

    # per-layer model: initialize, then read its weights in creation order
    scope_a = pt.Scope()
    main_a, startup_a, logits_a = _build(False)
    exe.run(startup_a, scope=scope_a)
    params_a = [p.name for p in main_a.global_block.all_parameters()]

    def val(name):
        return np.asarray(scope_a.get(name))

    # creation order per encoder layer: ln1 s/b, qkv w, out w, ln2 s/b,
    # ff w1, ff b1, ff w2, ff b2 — then the final ln s/b and head w.
    per_layer = [n for n in params_a if n not in ("tok_emb", "pos_emb")]
    assert len(per_layer) == L * 10 + 3, per_layer
    stack_vals = {k: [] for k in ("ln1_s", "ln1_b", "qkv_w", "out_w",
                                  "ln2_s", "ln2_b", "ff_w1", "ff_b1",
                                  "ff_w2", "ff_b2")}
    order = ["ln1_s", "ln1_b", "qkv_w", "out_w", "ln2_s", "ln2_b",
             "ff_w1", "ff_b1", "ff_w2", "ff_b2"]
    for i in range(L):
        chunk = per_layer[i * 10:(i + 1) * 10]
        for key, name in zip(order, chunk):
            stack_vals[key].append(val(name))
    fin_s, fin_b, head_w = per_layer[-3:]

    # stacked model in a fresh scope; overwrite its weights with A's
    scope_b = pt.Scope()
    main_b, startup_b, logits_b = _build(True)
    exe.run(startup_b, scope=scope_b)
    for key in order:
        stacked = np.stack(stack_vals[key], axis=0)
        name = f"lm_stack.stack_{key}"
        assert np.asarray(scope_b.get(name)).shape == stacked.shape, \
            (name, stacked.shape, np.asarray(scope_b.get(name)).shape)
        scope_b.set(name, stacked)
    scope_b.set("tok_emb", val("tok_emb"))
    scope_b.set("pos_emb", val("pos_emb"))
    scope_b.set("final_ln.scale", val(fin_s))
    scope_b.set("final_ln.bias", val(fin_b))
    scope_b.set("lm_head.w", val(head_w))

    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, (4, T)).astype("int64")
    out_a, = exe.run(main_a, feed={"ids": ids}, fetch_list=[logits_a],
                     scope=scope_a)
    out_b, = exe.run(main_b, feed={"ids": ids}, fetch_list=[logits_b],
                     scope=scope_b)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_a),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # tier-1 budget: the policies' gradients are pinned by test_backward_loop_once
def test_stack_remat_policies_match_numerically():
    """remat=False / True (the stream and the saved set) / "full" (the
    stream alone) are pure memory-schedule choices — identical losses
    through training steps."""
    def run(remat):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            ids = layers.data("ids", shape=[T], dtype="int64")
            logits = models.transformer_lm(
                ids, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, d_ff=FF, max_len=T, pipeline_stack=True,
                remat=remat)
            nxt = layers.data("nxt", shape=[T], dtype="int64")
            loss = layers.mean(
                layers.softmax_with_cross_entropy(
                    logits, layers.reshape(nxt, shape=[0, T, 1])))
            pt.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(
                loss, startup_program=startup)
        main.random_seed = startup.random_seed = 13
        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        ids_v = rng.randint(0, VOCAB, size=(2, T)).astype("int64")
        feed = {"ids": ids_v, "nxt": np.roll(ids_v, -1, 1)}
        return [float(np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[loss],
                                         scope=scope)[0]))
                for _ in range(4)]

    plain = run(False)
    saved = run(True)
    full = run("full")
    assert np.isfinite(plain).all()
    np.testing.assert_allclose(saved, plain, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(full, plain, rtol=1e-5, atol=1e-6)
    assert plain[-1] < plain[0]


@pytest.mark.parametrize("rope", [False, True])
@pytest.mark.parametrize("backend", ["reference", "kernels"])
def test_packed_and_heads_first_entries_agree(backend, rope, request,
                                              monkeypatch):
    """A two-layer train stack through ``flash_attention_packed`` (what
    its head width picks) and through ``flash_attention`` over
    [B, H, T, D] (``lane_block`` made to find no block): the loss and
    every weight gradient, under ``remat=True`` so that the backward reads
    the saved residuals (``_STACK_SAVED``) of whichever entry ran. On the
    reference backend the two are the same arithmetic, bit for bit; the
    interpret kernels differ by the order of their float32 sums."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.registry import get_op
    from paddle_tpu.lm_spec import Block
    from paddle_tpu.ops import pipeline_ops

    if backend == "kernels":
        request.getfixturevalue("pallas_path")
    n_layers, heads, d, t = 2, 2, 128, 128     # d_head 64: two a lane block
    blk = Block(num_heads=heads, use_rope=rope)
    rng = np.random.RandomState(3)
    shapes = {"ln1_s": (d,), "ln1_b": (d,), "qkv_w": (d, 3 * d),
              "out_w": (d, d), "ln2_s": (d,), "ln2_b": (d,),
              "ff_w1": (d, 2 * d), "ff_b1": (2 * d,), "ff_w2": (2 * d, d),
              "ff_b2": (d,)}
    params = {k: jnp.asarray(0.1 * rng.randn(n_layers, *s)
                             .astype(np.float32))
              for k, s in shapes.items()}
    x = jnp.asarray(rng.randn(2, t, d).astype(np.float32))
    attrs = dict(blk.attrs(), causal=True, remat=True)
    stack = get_op("pipelined_transformer_stack").fn

    def loss(params):
        ins = {slot: [params[key]]
               for slot, key in blk.stack_slots().items()}
        return jnp.sum(stack(attrs, dict(ins, X=[x]))["Out"][0] ** 2)

    assert pipeline_ops.lane_block(heads, d // heads) == 128
    packed = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(pipeline_ops, "lane_block", lambda *a: None)
    heads_first = jax.jit(jax.value_and_grad(loss))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(packed),
                            jax.tree_util.tree_leaves(heads_first)):
        a, b = np.asarray(a), np.asarray(b)
        if backend == "reference":
            np.testing.assert_array_equal(a, b, err_msg=str(path))
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, err_msg=str(path),
                                       atol=1e-5 * np.abs(b).max())
