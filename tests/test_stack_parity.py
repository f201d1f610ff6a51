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
