"""Seq2seq/NMT decode pins: the encoder-decoder GenerationEngine config
— greedy token-exact vs the teacher-forced reference, beam-as-paged-
forks token-exact vs a naive exhaustive host reference, cross-KV row
sharing across beam forks, memplan pricing of the cross cache, and the
/v1 serving leg."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.decoding import Seq2SeqGenerationEngine, Seq2SeqSpec

VS, VT, D, L, H = 24, 20, 16, 2, 2
TS, TT = 16, 32
BOS, EOS = 0, 1

_WEIGHTS = {}
# one module-level executor: every teacher-reference program of a given
# target length compiles ONCE and is shared by the greedy and beam
# reference rollouts (tier-1 budget)
_EXE = [None]


def _exe():
    if _EXE[0] is None:
        _EXE[0] = pt.Executor(pt.TPUPlace())
    return _EXE[0]


def _teacher_prog(ts, tt):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        src = layers.data(f"src{ts}", shape=[ts], dtype="int64")
        slen = layers.data(f"slen{ts}", shape=[], dtype="int32")
        tgt = layers.data(f"tgt{tt}", shape=[tt], dtype="int64")
        logits = models.transformer_nmt_teacher(
            src, slen, tgt, src_vocab_size=VS, tgt_vocab_size=VT,
            d_model=D, n_layers=L, num_heads=H,
            max_src_len=TS, max_tgt_len=TT)
    return prog, startup, logits


def _nmt_scope(seed=11):
    exe = _exe()
    if seed not in _WEIGHTS:
        scope = pt.Scope()
        _, startup, _ = _teacher_prog(TS, 4)
        startup.random_seed = seed
        exe.run(startup, scope=scope)
        _WEIGHTS[seed] = {n: scope.get(n) for n in scope.keys()}
    scope = pt.Scope()
    for n, v in _WEIGHTS[seed].items():
        scope.set(n, v)
    return scope, exe


def _teacher_logits(scope, exe, src, tgt_in):
    tt = len(tgt_in)
    prog, _, lv = _teacher_prog(TS, tt)
    s = np.zeros((1, TS), np.int64)
    s[0, :src.size] = src
    lo, = exe.run(prog, feed={f"src{TS}": s,
                              f"slen{TS}": np.asarray([src.size],
                                                      np.int32),
                              f"tgt{tt}": np.asarray(tgt_in,
                                                     np.int64)[None]},
                  fetch_list=[lv], scope=scope)
    return np.asarray(lo)[0]


def _spec():
    return Seq2SeqSpec(src_vocab_size=VS, tgt_vocab_size=VT, d_model=D,
                       n_layers=L, num_heads=H, max_src_len=TS,
                       max_tgt_len=TT)


# ONE engine (and therefore one encode/prefill/decode compile set)
# shared by the tier-1 tests — drives leave no state behind, counters
# are asserted as deltas (tier-1 budget)
_ENGINE = [None]


def _shared_engine():
    if _ENGINE[0] is None:
        _ENGINE[0] = Seq2SeqGenerationEngine(
            _spec(), _nmt_scope()[0], slots=5, page_size=4, bos_id=BOS,
            beam_width=4)
    return _ENGINE[0]


def _lsm(x):
    m = x.max()
    e = x - m
    return e - np.log(np.sum(np.exp(e)))


def _exhaustive_beam(scope, exe, src, K, N, alpha, eos):
    """Naive exhaustive reference: every step re-forwards the FULL
    teacher graph for every alive hypothesis and scores ALL V
    continuations — no cache, no top-K pruning shortcuts."""
    lo = _teacher_logits(scope, exe, src, [BOS])
    logp = _lsm(lo[-1].astype(np.float64))
    order = np.argsort(-logp, kind="stable")[:K]
    beams = [([int(t)], float(logp[t]), int(t) != eos) for t in order]
    for _ in range(N - 1):
        cands = []
        for idx, (toks, sc, alive) in enumerate(beams):
            if not alive:
                cands.append((sc, idx * VT + eos, idx, eos))
                continue
            lo = _teacher_logits(scope, exe, src, [BOS] + toks)
            lp = _lsm(lo[-1].astype(np.float64))
            for t in range(VT):
                cands.append((sc + lp[t], idx * VT + t, idx, t))
        cands.sort(key=lambda c: (-c[0], c[1]))
        beams = [(beams[p][0] + [t], sc, beams[p][2] and t != eos)
                 for sc, _flat, p, t in cands[:K]]
    toks = np.asarray([b[0] for b in beams], np.int64)
    scores = np.asarray([b[1] for b in beams])
    if alpha:
        has = (toks == eos).any(axis=1)
        first = np.argmax(toks == eos, axis=1) + 1
        gl = np.where(has, np.minimum(first, N), N).astype(np.float64)
        scores = scores / (((5.0 + gl) / 6.0) ** alpha)
    o = np.argsort(-scores, kind="stable")
    return toks[o], scores[o]


class TestNmtDecode:
    @pytest.mark.slow  # tier-1 budget (PR 20): the beam-vs-exhaustive
    # pin below covers the same encoder-decoder decode path and more;
    # the greedy sweep rides the slow tier
    def test_greedy_token_exact_vs_teacher(self):
        """Admission-time encoder + paged cross-attention decode emits
        exactly the teacher-forced argmax rollout, across a mixed-length
        source batch served concurrently."""
        scope, exe = _nmt_scope()
        rng = np.random.RandomState(3)
        srcs = [rng.randint(2, VS, (n,)).astype("int64")
                for n in (9, 13)]
        N = 5
        refs = []
        for src in srcs:
            gen = [BOS]
            for _ in range(N):
                lo = _teacher_logits(scope, exe, src, gen)
                gen.append(int(np.argmax(lo[-1])))
            refs.append(np.asarray(gen, np.int64))
        eng = _shared_engine()
        encodes0 = eng.metrics.counter("encodes")
        got = eng.translate(srcs, max_new_tokens=N)
        for g, r in zip(got, refs):
            np.testing.assert_array_equal(g, r)
        assert eng.metrics.counter("encodes") - encodes0 == len(srcs)
        assert eng.pool.pages_in_use() == 0
        # cross rows all released
        assert int(eng._xrow_ref.sum()) == 0

    def test_beam_token_exact_vs_exhaustive_and_row_sharing(self):
        """THE NMT acceptance pin: K=4 length-normalized beam through
        paged forks is token-exact and score-identical vs the NAIVE
        EXHAUSTIVE reference (full re-forward per hypothesis per step),
        while all K hypotheses share ONE cross-KV row (the source is
        encoded once, refcounted — never copied per beam)."""
        scope, exe = _nmt_scope()
        rng = np.random.RandomState(5)
        src = rng.randint(2, VS, (9,)).astype("int64")
        K, N, alpha = 4, 5, 0.6
        ref_toks, ref_sc = _exhaustive_beam(scope, exe, src, K, N,
                                            alpha, EOS)
        eng = _shared_engine()
        encodes0 = eng.metrics.counter("encodes")
        max_ref = [0]
        orig = eng._gauges

        def gauged():
            orig()
            max_ref[0] = max(max_ref[0], int(eng._xrow_ref.max()))

        eng._gauges = gauged
        try:
            ids, sc = eng.translate_beam(src, beam_size=K,
                                         max_new_tokens=N, eos_id=EOS,
                                         length_penalty=alpha)
        finally:
            eng._gauges = orig
        np.testing.assert_array_equal(ids[:, 1:], ref_toks)  # ids = BOS+
        np.testing.assert_allclose(sc, ref_sc, rtol=1e-4, atol=1e-5)
        # the source was encoded ONCE and shared by every fork
        assert eng.metrics.counter("encodes") - encodes0 == 1
        assert max_ref[0] >= 2  # forks really shared the row
        assert int(eng._xrow_ref.sum()) == 0  # and released it

    def test_encoder_pool_batching_token_exact(self):
        """Satellite pin: sources admitted together encode as bucket-
        padded BATCHES (fewer encoder passes than sources), and the
        pooled tokens are byte-identical to the batch-1 path on the
        same engine — padding rows land in the scrap row, never a live
        cross-KV row."""
        rng = np.random.RandomState(17)
        srcs = [rng.randint(2, VS, (n,)).astype("int64")
                for n in (6, 9, 11)]
        eng = _shared_engine()
        # batch-1 reference: one source per admission round
        want = [eng.translate([s], max_new_tokens=5)[0] for s in srcs]
        e0 = eng.metrics.counter("encodes")
        b0 = eng.metrics.counter("encode_batches")
        got = eng.translate(srcs, max_new_tokens=5)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert eng.metrics.counter("encodes") - e0 == len(srcs)
        # lengths (6, 9, 11) group as src buckets {8: [6], 16: [9, 11]}
        assert eng.metrics.counter("encode_batches") - b0 == 2
        assert eng.pool.pages_in_use() == 0
        assert int(eng._xrow_ref.sum()) == 0

    def test_a_deferred_admission_completes_token_exact(self):
        """A pool too small for the sources at once defers admissions
        (``_admit_deferred`` runs the deferred request's group through
        THIS engine's ``_run_prefill_group``, which flushes the encodes
        first): every source still translates, token-exact vs the roomy
        engine, and the pages and cross rows come back."""
        rng = np.random.RandomState(23)
        srcs = [rng.randint(2, VS, (n,)).astype("int64")
                for n in (5, 7, 6)]
        want = _shared_engine().translate(srcs, max_new_tokens=5)
        eng = Seq2SeqGenerationEngine(
            _spec(), _nmt_scope()[0], slots=4, page_size=4, bos_id=BOS,
            beam_width=1, n_pages=3)
        got = eng.translate(srcs, max_new_tokens=5)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert eng.metrics.counter("admission_deferred") > 0
        assert eng.pool.pages_in_use() == 0
        assert int(eng._xrow_ref.sum()) == 0

    def test_cross_kv_priced_by_memplan(self):
        """The analysis plane prices the cross-KV slot cache: the
        engine-scope decode target's resident bytes cover the page pool
        PLUS [L, S+1, Hkv, Ts, dh] x2 cross planes."""
        from paddle_tpu import analysis

        eng = _shared_engine()
        prog, outs = eng._decode_prog
        mem = analysis.analyze_memory(
            prog, list(eng._decode_feed_names),
            [v.name for v in eng._fetches(outs)],
            scope=eng.scope, batch_size=eng.slots)
        cross_bytes = 2 * L * (eng.slots + 1) * H * TS * (D // H) * 4
        pool_bytes = 2 * L * eng.n_pages * H * eng.page_size \
            * (D // H) * 4
        assert mem.resident_bytes >= cross_bytes + pool_bytes
        snap = eng.metrics.snapshot()["gauges"]
        assert snap["mem/cross_kv_bytes"] == float(cross_bytes)

    @pytest.mark.slow
    def test_nmt_serves_over_v1_http(self):
        """The serving leg: a Seq2Seq engine behind Server /v1/generate
        takes {'src': ...} with beam fields and answers with beams +
        scores; absent decode-platform fields keep greedy byte-exact."""
        import json
        import urllib.request

        from paddle_tpu.serving import Server

        scope, exe = _nmt_scope()
        rng = np.random.RandomState(7)
        src = rng.randint(2, VS, (7,)).astype("int64")
        eng = Seq2SeqGenerationEngine(_spec(), scope, slots=4,
                                      page_size=4, bos_id=BOS,
                                      beam_width=3)
        solo = Seq2SeqGenerationEngine(_spec(), _nmt_scope()[0],
                                       slots=4, page_size=4, bos_id=BOS,
                                       beam_width=3)
        want_greedy = solo.translate([src], max_new_tokens=5)[0]
        want_beam, want_sc = solo.translate_beam(
            src, beam_size=3, max_new_tokens=5, eos_id=EOS)
        server = Server(eng, batch_buckets=(1, 2))
        server.start()
        try:
            port = server.serve_http(port=0)

            def post(body):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    return json.loads(r.read())

            out = post({"src": src.tolist(), "max_new_tokens": 5})
            np.testing.assert_array_equal(np.asarray(out["ids"]),
                                          want_greedy)
            out = post({"src": src.tolist(), "max_new_tokens": 5,
                        "beam_size": 3, "eos_id": EOS,
                        "return_beams": True})
            np.testing.assert_array_equal(np.asarray(out["beams"]),
                                          want_beam)
            np.testing.assert_allclose(np.asarray(out["scores"]),
                                       want_sc, rtol=1e-4)
        finally:
            server.stop()
