"""paddle_tpu.serving.disagg: prefill/decode split pools + KV handoff.

Pins the disaggregation contracts:

1. HANDOFF IS MIGRATION — tokens are byte-identical to a unified
   engine at every pool shape; the decode pool's ``prefills`` counter
   stays 0 (never a recompute) and the prefill pool never runs a
   decode step (role purity);
2. SAME-PROCESS is a refcount transfer through ONE shared page pool
   (``DisaggEngine.build``); separate-pool legs move serialized page
   ranges instead — both drain the source pool clean;
3. CROSS-PROCESS handoffs ride ``POST /v1/adopt`` on the existing
   HTTP surface (``RemoteDecodeLeg``) and the SOURCE request's future
   resolves with the remote decode's tokens — the client never sees
   the pool boundary;
4. schema/page-shape mismatches are a typed BadRequestError, never
   silent cache corruption.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.decoding import SamplingParams
from paddle_tpu.serving import GenerationEngine, LMSpec, Server
from paddle_tpu.serving.batcher import Request
from paddle_tpu.serving.disagg import (HANDOFF_V, DecodePool, DisaggEngine,
                                       PrefillPool, RemoteDecodeLeg,
                                       install_handoff)
from paddle_tpu.serving.errors import BadRequestError

VOCAB, D, L, H, MAXLEN = 32, 16, 2, 2, 32
SEED = 7
MAXNEW = 6
PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 3, 4]]
# the last request decodes SAMPLED: the handoff must carry the decode
# policy (temperature/top_k/seed) so migration is invisible to it too
SAMPLING = [None, None, None,
            SamplingParams(temperature=0.7, top_k=4, seed=11)]

_WEIGHTS = {}


def _lm_scope(seed=SEED):
    exe = pt.Executor(pt.TPUPlace())
    if seed not in _WEIGHTS:
        scope = pt.Scope()
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            prompt = layers.data("p_init", shape=[8], dtype="int64")
            models.transformer_lm_generate(
                prompt, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, max_len=MAXLEN, max_new_tokens=1)
        startup.random_seed = seed
        exe.run(startup, scope=scope)
        _WEIGHTS[seed] = {n: scope.get(n) for n in scope.keys()}
    scope = pt.Scope()
    for n, v in _WEIGHTS[seed].items():
        scope.set(n, v)
    return scope


def _spec():
    return LMSpec(vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                  max_len=MAXLEN)


def _engine(**kw):
    return GenerationEngine(_spec(), _lm_scope(), slots=4, page_size=8,
                            **kw)


def _reqs():
    return [Request({"prompt": p},
                    {"max_new_tokens": MAXNEW, "sampling_params": sp},
                    None)
            for p, sp in zip(PROMPTS, SAMPLING)]


def _results(reqs, timeout=60):
    return [np.asarray(r.future.result(timeout=timeout)) for r in reqs]


@pytest.fixture(scope="module")
def reference():
    """The unified-engine tokens every split shape must reproduce."""
    uni = _engine()
    outs = uni.generate_all(PROMPTS, max_new_tokens=MAXNEW,
                            sampling=SAMPLING)
    return [np.asarray(o) for o in outs]


def _assert_disagg_matches(dis, reference, *, timeout=60):
    reqs = _reqs()
    dis._drive(reqs)
    for got, want in zip(_results(reqs, timeout=timeout), reference):
        np.testing.assert_array_equal(got, want)


def _counters(obj) -> dict:
    snap = obj.metrics.snapshot() if hasattr(obj, "metrics") else obj
    return snap.get("counters", snap)


def _drained(eng) -> int:
    """Pages still referenced once the prefix index's deliberate
    retention (finished prompts cached for reuse) is dropped — 0 means
    no migration leaked a refcount in either direction."""
    if eng.prefix_index is not None:
        eng.prefix_index.clear()
    return eng.pool.pages_in_use()


def _role_purity(prefill_engines, decode_engines):
    """The split's whole point: prefill legs never decode, decode legs
    never prefill (so a migration was never a recompute)."""
    for eng in prefill_engines:
        assert _counters(eng).get("decode_steps", 0) == 0
    for eng in decode_engines:
        assert _counters(eng).get("prefills", 0) == 0


# ---------------------------------------------------------------------------
# same-process: ONE shared page pool, migration by refcount
# ---------------------------------------------------------------------------
class TestSharedPoolHandoff:
    def test_tokens_byte_identical_and_roles_pure(self, reference):
        dis = DisaggEngine.build(_spec(), prefill_replicas=1,
                                 decode_replicas=1, scope=_lm_scope(),
                                 slots=4, page_size=8)
        _assert_disagg_matches(dis, reference)
        pf = _counters(dis.prefill.engines[0])
        de = _counters(dis.decode.engines[0])
        assert pf.get("kv_handoffs_out") == len(PROMPTS)
        assert de.get("kv_handoffs_in") == len(PROMPTS)
        assert pf.get("kv_handoff_pages", 0) >= len(PROMPTS)
        _role_purity(dis.prefill.engines, dis.decode.engines)
        # every migration moved pages, and finishing released them all:
        # the shared pool drains clean (no refcount leak either way)
        for eng in dis.engines:
            assert _drained(eng) == 0
        assert _counters(dis.prefill.engines[0]).get("kv_migrations") \
            == len(PROMPTS)

    @pytest.mark.slow
    def test_pool_shape_2x2(self, reference):
        # redundant shape variant: same contract, more legs
        dis = DisaggEngine.build(_spec(), prefill_replicas=2,
                                 decode_replicas=2, scope=_lm_scope(),
                                 slots=4, page_size=8)
        _assert_disagg_matches(dis, reference)
        _role_purity(dis.prefill.engines, dis.decode.engines)
        assert sum(_counters(e).get("kv_handoffs_in", 0)
                   for e in dis.decode.engines) == len(PROMPTS)


# ---------------------------------------------------------------------------
# separate pools in one process: serialized page ranges
# ---------------------------------------------------------------------------
class TestSerializedHandoff:
    def test_separate_pool_migration_moves_bytes(self, reference):
        eng_a, eng_b = _engine(), _engine()   # distinct scopes + pools
        assert eng_a.pool is not eng_b.pool
        dis = DisaggEngine(PrefillPool([eng_a]), DecodePool([eng_b]))
        _assert_disagg_matches(dis, reference)
        b = _counters(eng_b)
        assert b.get("kv_handoffs_in") == len(PROMPTS)
        assert b.get("kv_handoff_pages", 0) >= len(PROMPTS)
        _role_purity([eng_a], [eng_b])
        # the exporter released its page claims to the bytes
        assert _drained(eng_a) == 0
        assert _drained(eng_b) == 0

    def test_handoff_schema_and_shape_typed(self):
        eng = _engine()
        req = Request({"prompt": [1]}, {}, None)
        with pytest.raises(BadRequestError, match="schema"):
            install_handoff(eng, {"v": HANDOFF_V + 1}, req)
        with pytest.raises(BadRequestError, match="page_size"):
            install_handoff(eng, {"v": HANDOFF_V,
                                  "page_size": eng.page_size * 2}, req)
        with pytest.raises(BadRequestError, match="context"):
            install_handoff(eng, {"v": HANDOFF_V,
                                  "page_size": eng.page_size,
                                  "prompt": [1] * MAXLEN,
                                  "max_new": MAXLEN}, req)

    def test_remote_only_decode_needs_a_leg(self):
        with pytest.raises(ValueError, match="decode leg"):
            DisaggEngine(PrefillPool([_engine()]), DecodePool([]))


# ---------------------------------------------------------------------------
# cross-process: POST /v1/adopt over the HTTP replica leg
# ---------------------------------------------------------------------------
class TestRemoteAdopt:
    def test_handoff_rides_v1_adopt(self, reference):
        decode_eng = _engine()
        srv = Server([decode_eng])
        srv.start()
        port = srv.serve_http(port=0)
        try:
            pre = _engine()
            dis = DisaggEngine(
                PrefillPool([pre]), DecodePool([]),
                remote_decode=[RemoteDecodeLeg(
                    f"http://127.0.0.1:{port}")])
            _assert_disagg_matches(dis, reference)
            _role_purity([pre], [decode_eng])
            de = _counters(decode_eng)
            assert de.get("kv_handoffs_in") == len(PROMPTS)
            assert _drained(pre) == 0
            assert _drained(decode_eng) == 0
        finally:
            srv.stop()
