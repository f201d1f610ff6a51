"""KV-cache incremental decoding (transformer_stack_generate): the decode
loop must agree token-for-token with iterative full re-forwarding through
the training graph — the O(T) cache path vs the O(T^2) naive path."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models

VOCAB, D, L, H, MAXLEN = 32, 32, 2, 2, 32


def _build_train(T):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        logits = models.transformer_lm(ids, vocab_size=VOCAB, d_model=D,
                                       n_layers=L, num_heads=H,
                                       max_len=MAXLEN, pipeline_stack=True)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, VOCAB]),
            layers.reshape(tgt, shape=[-1, 1])))
        pt.optimizer.AdamOptimizer(learning_rate=5e-3).minimize(
            loss, startup_program=startup)
    return main, startup, logits, loss


def _build_full_forward(T):
    """Plain forward at length T (for the naive re-forward baseline)."""
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        ids = layers.data("ids_fwd", shape=[T], dtype="int64")
        logits = models.transformer_lm(ids, vocab_size=VOCAB, d_model=D,
                                       n_layers=L, num_heads=H,
                                       max_len=MAXLEN, pipeline_stack=True)
    return prog, logits


def test_generate_matches_naive_reforwarding():
    Tp, N = 8, 6
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    main, startup, _, loss = _build_train(Tp)
    exe.run(startup, scope=scope)

    # teach it something non-trivial: next token = (cur + 3) % VOCAB
    rng = np.random.RandomState(0)
    start = rng.randint(0, VOCAB, (64, 1))
    seq = (start + 3 * np.arange(Tp + 1)) % VOCAB
    feed = {"ids": seq[:, :-1].astype("int64"),
            "tgt": seq[:, 1:].astype("int64")}
    for _ in range(60):
        l, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    # generation program shares the trained weights by name (its startup
    # is never run)
    gen_prog, gen_startup = pt.Program(), pt.Program()
    with pt.program_guard(gen_prog, gen_startup):
        prompt = layers.data("prompt", shape=[Tp], dtype="int64")
        out_ids = models.transformer_lm_generate(
            prompt, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, max_new_tokens=N)
    p = ((rng.randint(0, VOCAB, (4, 1)) + 3 * np.arange(Tp)) % VOCAB
         ).astype("int64")
    got, = exe.run(gen_prog, feed={"prompt": p}, fetch_list=[out_ids],
                   scope=scope)
    got = np.asarray(got)
    assert got.shape == (4, Tp + N)
    np.testing.assert_array_equal(got[:, :Tp], p)

    # naive baseline: iteratively re-forward the whole sequence
    cur = p
    for t in range(N):
        prog_t, logits_t = _build_full_forward(Tp + t)
        lg, = exe.run(prog_t, feed={"ids_fwd": cur}, fetch_list=[logits_t],
                      scope=scope)
        nxt = np.argmax(np.asarray(lg)[:, -1], axis=-1)[:, None]
        cur = np.concatenate([cur, nxt.astype("int64")], axis=1)
    np.testing.assert_array_equal(got, cur)

    # and the learned rule mostly holds on generated tokens (the exact
    # decode==reforward equality above is the correctness property; this
    # one just shows the tiny model learned something real)
    expect = (p[:, -1:] + 3 * (1 + np.arange(N))) % VOCAB
    assert np.mean(got[:, Tp:] == expect) >= 0.85


def test_generate_rejects_overflow():
    """Prompt + new tokens beyond the position table fails at BUILD time
    (shape inference runs the lowering abstractly), not at step N."""
    import pytest

    prog, startup = pt.Program(), pt.Program()
    with pytest.raises(Exception, match="exceeds max_len"):
        with pt.program_guard(prog, startup):
            prompt = layers.data("p2", shape=[MAXLEN], dtype="int64")
            models.transformer_lm_generate(
                prompt, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, max_len=MAXLEN, max_new_tokens=4)


def test_sampled_generation_varies_and_respects_topk():
    """temperature>0 routes through the RNG plane: successive runs draw
    different continuations, and top_k=1 collapses back to greedy."""
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        prompt = layers.data("p3", shape=[4], dtype="int64")
        sampled = models.transformer_lm_generate(
            prompt, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, max_new_tokens=12, temperature=1.5)
        greedy = models.transformer_lm_generate(
            prompt, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, max_new_tokens=12)
        top1 = models.transformer_lm_generate(
            prompt, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, max_new_tokens=12, temperature=0.7, top_k=1)
    exe = pt.Executor(pt.TPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    p = np.arange(8, dtype="int64").reshape(2, 4)
    a, g1, t1 = exe.run(prog, feed={"p3": p},
                        fetch_list=[sampled, greedy, top1], scope=scope)
    b_, g2, t2 = exe.run(prog, feed={"p3": p},
                         fetch_list=[sampled, greedy, top1], scope=scope)
    a, b_ = np.asarray(a), np.asarray(b_)
    assert (a >= 0).all() and (a < VOCAB).all()
    # the RNG state advances between runs -> different draws
    assert not np.array_equal(a[:, 4:], b_[:, 4:])
    # greedy is deterministic run to run
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    # top_k=1 keeps only the argmax bucket: equals greedy regardless of
    # temperature or RNG draws
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(g1))
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(g1))


def test_greedy_generation_leaves_rng_untouched():
    """Greedy decode must not consume the scope RNG stream: interleaving
    eval-generation with training cannot perturb dropout draws or break
    bit-exact resume (the op's needs_rng is an attr predicate)."""
    from paddle_tpu.core.program import RNG_VAR

    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        prompt = layers.data("p4", shape=[4], dtype="int64")
        greedy = models.transformer_lm_generate(
            prompt, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, max_new_tokens=4)
    exe = pt.Executor(pt.TPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    p = np.arange(8, dtype="int64").reshape(2, 4)
    before = np.asarray(scope.get(RNG_VAR)) if scope.has(RNG_VAR) else None
    exe.run(prog, feed={"p4": p}, fetch_list=[greedy], scope=scope)
    after = np.asarray(scope.get(RNG_VAR)) if scope.has(RNG_VAR) else None
    if before is None:
        assert after is None
    else:
        np.testing.assert_array_equal(before, after)


class TestBeamSearch:
    def _trained(self, Tp=8):
        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        main, startup, _, loss = _build_train(Tp)
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        start = rng.randint(0, VOCAB, (64, 1))
        seq = (start + 3 * np.arange(Tp + 1)) % VOCAB
        feed = {"ids": seq[:, :-1].astype("int64"),
                "tgt": seq[:, 1:].astype("int64")}
        for _ in range(40):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        return exe, scope, rng

    def test_beam1_equals_greedy(self):
        Tp, N = 8, 5
        exe, scope, rng = self._trained(Tp)
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            prompt = layers.data("pb", shape=[Tp], dtype="int64")
            greedy = models.transformer_lm_generate(
                prompt, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, max_len=MAXLEN, max_new_tokens=N)
            beams, scores = models.transformer_lm_beam_search(
                prompt, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, max_len=MAXLEN, max_new_tokens=N, beam_size=1)
        p = ((rng.randint(0, VOCAB, (3, 1)) + 3 * np.arange(Tp)) % VOCAB
             ).astype("int64")
        g, bm = exe.run(prog, feed={"pb": p}, fetch_list=[greedy, beams],
                        scope=scope)
        np.testing.assert_array_equal(np.asarray(bm)[:, 0], np.asarray(g))

    @pytest.mark.slow  # tier-1 budget (PR 20): full-reforward score
    # audit; beam ordering/semantics stay tier-1 via beam1==greedy and
    # the eos/length-penalty tests
    def test_scores_match_independent_forward(self):
        """The reported beam scores must equal the sum of next-token
        log-probs of the RETURNED sequences computed by a full forward —
        the end-to-end check that per-step cache reordering is correct."""
        Tp, N, K = 8, 4, 3
        exe, scope, rng = self._trained(Tp)
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            prompt = layers.data("pb2", shape=[Tp], dtype="int64")
            beams, scores = models.transformer_lm_beam_search(
                prompt, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, max_len=MAXLEN, max_new_tokens=N, beam_size=K)
        p = ((rng.randint(0, VOCAB, (2, 1)) + 3 * np.arange(Tp)) % VOCAB
             ).astype("int64")
        bm, sc = exe.run(prog, feed={"pb2": p}, fetch_list=[beams, scores],
                         scope=scope)
        bm, sc = np.asarray(bm), np.asarray(sc)
        assert bm.shape == (2, K, Tp + N) and sc.shape == (2, K)
        # scores sorted best-first
        assert (np.diff(sc, axis=1) <= 1e-5).all()

        # independent scoring: full forward over each returned sequence
        full_prog, logits_full = _build_full_forward(Tp + N - 1)
        for bi in range(2):
            for ki in range(K):
                seq = bm[bi, ki]
                lg, = exe.run(full_prog,
                              feed={"ids_fwd": seq[None, :-1]},
                              fetch_list=[logits_full], scope=scope)
                lp = np.asarray(lg)[0].astype(np.float64)
                lp = lp - np.log(np.exp(lp - lp.max(-1, keepdims=True)
                                        ).sum(-1, keepdims=True)) \
                    - lp.max(-1, keepdims=True)
                want = sum(lp[Tp - 1 + t, seq[Tp + t]] for t in range(N))
                np.testing.assert_allclose(sc[bi, ki], want, rtol=2e-3,
                                           atol=2e-3)

    def test_eos_freezes_beams_and_length_penalty_normalises(self):
        Tp, N, K = 8, 5, 2
        exe, scope, rng = self._trained(Tp)
        p = ((rng.randint(0, VOCAB, (1, 1)) + 3 * np.arange(Tp)) % VOCAB
             ).astype("int64")

        # find what greedy emits first, use THAT as eos: the best beam
        # then finishes at length 1 and must stay frozen
        prog0, startup0 = pt.Program(), pt.Program()
        with pt.program_guard(prog0, startup0):
            pr = layers.data("pe0", shape=[Tp], dtype="int64")
            g = models.transformer_lm_generate(
                pr, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                max_len=MAXLEN, max_new_tokens=1)
        gout, = exe.run(prog0, feed={"pe0": p}, fetch_list=[g], scope=scope)
        eos = int(np.asarray(gout)[0, -1])

        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            pr = layers.data("pe", shape=[Tp], dtype="int64")
            beams, scores = models.transformer_lm_beam_search(
                pr, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                max_len=MAXLEN, max_new_tokens=N, beam_size=K, eos_id=eos,
                length_penalty=1.0)
        bm, sc = exe.run(prog, feed={"pe": p}, fetch_list=[beams, scores],
                         scope=scope)
        bm, sc = np.asarray(bm), np.asarray(sc)
        # some beam ends with eos at step 0 and stays frozen: all-eos tail
        done = [k for k in range(K) if bm[0, k, Tp] == eos]
        assert done, bm[:, :, Tp:]
        for k in done:
            assert (bm[0, k, Tp:] == eos).all()
        # its normalised score: logp(eos) / ((5+1)/6)^1 == logp(eos)
        full_prog, logits_full = _build_full_forward(Tp)
        lg, = exe.run(full_prog, feed={"ids_fwd": p},
                      fetch_list=[logits_full], scope=scope)
        lp = np.asarray(lg)[0, -1].astype(np.float64)
        lp = lp - np.log(np.exp(lp - lp.max()).sum()) - lp.max()
        np.testing.assert_allclose(sc[0, done[0]], lp[eos], rtol=2e-3,
                                   atol=2e-3)

    @pytest.mark.slow  # tier-1 budget (PR 20): single-step edge variant
    # of the beam plane; core beam behavior stays tier-1 above
    def test_single_new_token_beams(self):
        Tp, K = 8, 3
        exe, scope, rng = self._trained(Tp)
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            pr = layers.data("p1t", shape=[Tp], dtype="int64")
            beams, scores = models.transformer_lm_beam_search(
                pr, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                max_len=MAXLEN, max_new_tokens=1, beam_size=K)
        p = ((rng.randint(0, VOCAB, (2, 1)) + 3 * np.arange(Tp)) % VOCAB
             ).astype("int64")
        bm, sc = exe.run(prog, feed={"p1t": p}, fetch_list=[beams, scores],
                         scope=scope)
        bm, sc = np.asarray(bm), np.asarray(sc)
        assert bm.shape == (2, K, Tp + 1) and sc.shape == (2, K)
        # K distinct top tokens, scores strictly ordered
        for bi in range(2):
            assert len(set(bm[bi, :, -1].tolist())) == K
        assert (np.diff(sc, axis=1) <= 1e-6).all()


def _decode_vs_reforward(lm_kwargs):
    """Shared harness: train a tiny stacked LM variant, decode N tokens
    through the KV cache, and pin the result token-for-token against
    iterative full re-forwarding with the same geometry."""
    Tp, N = 8, 4
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())

    def build_lm(T, name):
        ids = layers.data(name, shape=[T], dtype="int64")
        return ids, models.transformer_lm(
            ids, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, pipeline_stack=True, **lm_kwargs)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        _, logits = build_lm(Tp, "ids")
        tgt = layers.data("tgt", shape=[Tp], dtype="int64")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, VOCAB]),
            layers.reshape(tgt, shape=[-1, 1])))
        pt.optimizer.AdamOptimizer(learning_rate=5e-3).minimize(
            loss, startup_program=startup)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    seq = (rng.randint(0, VOCAB, (32, 1)) + 3 * np.arange(Tp + 1)) % VOCAB
    feed = {"ids": seq[:, :-1].astype("int64"),
            "tgt": seq[:, 1:].astype("int64")}
    for _ in range(30):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    gen_prog, gen_startup = pt.Program(), pt.Program()
    with pt.program_guard(gen_prog, gen_startup):
        prompt = layers.data("prompt_h", shape=[Tp], dtype="int64")
        out_ids = models.transformer_lm_generate(
            prompt, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, max_new_tokens=N, **lm_kwargs)
    p = ((rng.randint(0, VOCAB, (3, 1)) + 3 * np.arange(Tp)) % VOCAB
         ).astype("int64")
    got, = exe.run(gen_prog, feed={"prompt_h": p}, fetch_list=[out_ids],
                   scope=scope)
    got = np.asarray(got)

    cur = p
    for t in range(N):
        prog_t, s_t = pt.Program(), pt.Program()
        with pt.program_guard(prog_t, s_t):
            _, lg_t = build_lm(Tp + t, "idf")
        lg, = exe.run(prog_t, feed={"idf": cur}, fetch_list=[lg_t],
                      scope=scope)
        nxt = np.argmax(np.asarray(lg)[:, -1], axis=-1)[:, None]
        cur = np.concatenate([cur, nxt.astype("int64")], axis=1)
    np.testing.assert_array_equal(got, cur)


@pytest.mark.slow  # tier-1 budget (PR 14): the rope+gqa COMBINED leg
# below covers both mechanisms; the single-feature variants are the
# redundant twins
def test_gqa_stack_decode_matches_reforwarding():
    """Grouped-query attention (multi-query extreme, Hkv=1): the cache
    holds one KV head plane and decode must equal re-forwarding."""
    _decode_vs_reforward({"num_kv_heads": 1})


@pytest.mark.slow  # tier-1 budget (PR 14): see the gqa twin above
def test_rope_stack_decode_matches_reforwarding():
    """RoPE: rotated keys enter the cache at their absolute positions,
    so incremental decode must equal re-forwarding (which re-rotates
    from scratch each step)."""
    _decode_vs_reforward({"use_rope": True})


def test_rope_gqa_combined_decode_matches_reforwarding():
    _decode_vs_reforward({"use_rope": True, "num_kv_heads": 2})


def test_generation_on_dp_mesh_matches_single_device():
    """Serving scales like training: the same generation program under a
    data-parallel mesh (batch sharded over dp) must emit exactly the
    single-device tokens."""
    import jax

    from paddle_tpu.parallel import data_parallel_plan, make_mesh

    Tp, N = 8, 5
    feed_ids = np.random.RandomState(3).randint(
        0, VOCAB, (8, Tp)).astype("int64")

    def run(mesh):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            prompt = layers.data("pm", shape=[Tp], dtype="int64")
            out_ids = models.transformer_lm_generate(
                prompt, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, max_len=MAXLEN, max_new_tokens=N)
        scope = pt.Scope()
        exe = (pt.Executor(mesh=mesh, plan=data_parallel_plan(mesh))
               if mesh else pt.Executor(pt.TPUPlace()))
        # same seed -> same weights in both runs
        startup.random_seed = 9
        exe.run(startup, scope=scope)
        got, = exe.run(main, feed={"pm": feed_ids},
                       fetch_list=[out_ids], scope=scope)
        return np.asarray(got)

    single = run(None)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    sharded = run(mesh)
    np.testing.assert_array_equal(sharded, single)
