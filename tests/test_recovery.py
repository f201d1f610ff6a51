"""Work-preserving serving recovery: lineage, resume, decode-leg failover.

Pins the recovery contracts:

1. REPLICA KILL MID-STREAM IS NOT A FAILURE — with >= 4 generations in
   flight, a fault-plan ``replica_kill`` produces ZERO failed requests
   and bitwise-identical final tokens (the (request, seed) determinism
   contract extended across a crash);
2. EMITTED TOKENS ARE NEVER RE-DECODED — the survivors re-enter via
   chunked prefill only, pinned by the per-token ``decode_tokens``
   counters: the killed fleet decodes STRICTLY FEWER tokens than the
   uninterrupted reference;
3. DISAGG DECODE-LEG DEATH AFTER KV HANDOFF fails over by re-prefill on
   another leg (the pages are bytes by then — no rollback exists) and
   stays token-exact;
4. RECOVERY HAS PRIORITY ADMISSION — pool pressure defers NEW work
   first; a recovery re-admission lands ahead of earlier-queued new
   admissions and never pop-fails with CacheExhaustedError;
5. the feedback joiner's pending window survives a joiner crash via the
   ``window.spill`` sidecar (original deadlines, exactly-once examples);
6. ``HttpReplica`` types its transport failures: split connect/read
   timeouts, and a mid-body reset is a retryable
   :class:`ConnectionDroppedError`, never a hang or a generic failure.
"""
import json
import socket
import struct
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.decoding import SamplingParams
from paddle_tpu.feedback import FeedbackHook, ImpressionLog, OutcomeJoiner
from paddle_tpu.resilience import Retry, faults
from paddle_tpu.serving import (ConnectionDroppedError, DecodePool,
                                DisaggEngine, Fleet, GenerationEngine,
                                HttpReplica, LineageStore, LMSpec,
                                PrefillPool, RemoteDecodeLeg, Server)
from paddle_tpu.serving.batcher import Request
from paddle_tpu.serving.errors import RequestTimeoutError

VOCAB, D, L, H, MAXLEN = 32, 16, 2, 2, 32
SEED = 7
MAXNEW = 6
PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 3, 4]]
#: every request SAMPLED with an explicit seed — recovery must hold for
#: the hard case (stochastic decode), not just greedy
SAMPLING = SamplingParams(temperature=0.7, top_k=4, seed=11)

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
    kw.setdefault("slots", 4)
    return GenerationEngine(_spec(), _lm_scope(), page_size=8, **kw)


def _counters(obj) -> dict:
    snap = obj.metrics.snapshot() if hasattr(obj, "metrics") else obj
    return snap.get("counters", snap)


@pytest.fixture(scope="module")
def reference():
    """Uninterrupted tokens + the decode-token spend to beat."""
    uni = _engine(slots=8)
    outs = uni.generate_all(PROMPTS, max_new_tokens=MAXNEW,
                            sampling=[SAMPLING] * len(PROMPTS))
    return ([np.asarray(o) for o in outs],
            _counters(uni)["decode_tokens"])


# ---------------------------------------------------------------------------
# 1+2: the kill-mid-stream acceptance pin
# ---------------------------------------------------------------------------
class TestReplicaKillRecovery:
    def test_kill_mid_stream_zero_failures_token_exact(self, reference):
        refs, ref_decode_tokens = reference
        engines = [_engine(slots=8), _engine(slots=8)]
        fleet = Fleet([Server(e) for e in engines], hedge=False)
        plan = faults.FaultPlan().at(kind="replica_kill", after_tokens=3)
        try:
            with plan.active():
                futs = [fleet.submit({"prompt": np.array(p)},
                                     max_new_tokens=MAXNEW,
                                     sampling_params=SAMPLING)
                        for p in PROMPTS]
                outs = [f.result(timeout=60) for f in futs]
        finally:
            fleet.stop()
        assert plan.fired_log == [("replica_kill", None)]
        fc = _counters(fleet)
        # zero failed requests under the kill
        assert fc["failed"] == 0
        assert fc["completed"] == len(PROMPTS)
        # bitwise-identical to the uninterrupted run
        for want, got in zip(refs, outs):
            np.testing.assert_array_equal(want, np.asarray(got))
        # the in-flight streams RESUMED (not restarted): lineage counted
        # them and the engines chunk-prefilled the emitted context
        assert fc["requests_recovered"] >= 1
        assert fc["recovered_tokens"] >= 1
        ec = [_counters(e) for e in engines]
        assert sum(c.get("requests_resumed", 0) for c in ec) >= 1
        assert sum(c.get("recovery_prefill_tokens", 0) for c in ec) > 0
        # already-emitted tokens were NEVER re-decoded: the killed fleet
        # spends strictly fewer decode steps than the uninterrupted
        # reference (the crashed tokens re-enter via prefill only)
        fleet_decode_tokens = sum(c.get("decode_tokens", 0) for c in ec)
        assert fleet_decode_tokens < ref_decode_tokens
        # exactly one engine hard-died; its in-flight futures all failed
        # retryable and its counter shows the kill
        kills = [c.get("replica_kills", 0) for c in ec]
        assert sorted(kills) == [0, 1]

    def test_kill_then_revive_serves_again(self):
        eng = _engine()
        srv = Server(eng)
        fleet = Fleet([srv, Server(_engine())], hedge=False)
        plan = faults.FaultPlan().at(kind="replica_kill", after_tokens=1)
        try:
            with plan.active():
                out1 = fleet.generate(np.array(PROMPTS[0]),
                                      max_new_tokens=MAXNEW,
                                      sampling_params=SAMPLING)
            assert eng._killed
            eng.revive()
            assert not eng._killed
            out2 = fleet.generate(np.array(PROMPTS[0]),
                                  max_new_tokens=MAXNEW,
                                  sampling_params=SAMPLING)
            np.testing.assert_array_equal(np.asarray(out1),
                                          np.asarray(out2))
        finally:
            fleet.stop()

    @pytest.mark.slow
    def test_kill_storm_sequential_kills_both_replicas(self):
        """Chaos variant: BOTH replicas die (one after the other, each
        revived before the next wave) across three waves of traffic —
        availability stays 1.0 and every stream is token-exact."""
        uni = _engine(slots=8)
        refs = [np.asarray(o) for o in uni.generate_all(
            PROMPTS, max_new_tokens=MAXNEW,
            sampling=[SAMPLING] * len(PROMPTS))]
        engines = [_engine(slots=8), _engine(slots=8)]
        # patient retries: mid-wave BOTH breakers can be open for a beat
        # (one quarantined kill + the probe window) — the storm must
        # outwait the recovery timer, not fail fast through it
        fleet = Fleet([Server(e) for e in engines], hedge=False,
                      retry=Retry(max_attempts=8, backoff=0.05,
                                  multiplier=2.0, max_backoff=0.5,
                                  name="fleet"))
        try:
            for wave in range(3):
                plan = faults.FaultPlan().at(kind="replica_kill",
                                             after_tokens=2)
                with plan.active():
                    futs = [fleet.submit({"prompt": np.array(p)},
                                         max_new_tokens=MAXNEW,
                                         sampling_params=SAMPLING)
                            for p in PROMPTS]
                    outs = [f.result(timeout=60) for f in futs]
                for want, got in zip(refs, outs):
                    np.testing.assert_array_equal(want, np.asarray(got))
                for e in engines:
                    e.revive()
            assert _counters(fleet)["failed"] == 0
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# 3: disagg decode-leg failover (the remote-adopt chaos pin)
# ---------------------------------------------------------------------------
class TestDecodeLegFailover:
    def test_decode_leg_crash_after_handoff_re_prefills(self, reference):
        refs, _ = reference
        decode_engines = [_engine(), _engine()]
        servers = [Server([e]) for e in decode_engines]
        ports = []
        for srv in servers:
            srv.start()
            ports.append(srv.serve_http(port=0))
        try:
            pre = _engine()
            dis = DisaggEngine(
                PrefillPool([pre]), DecodePool([]),
                remote_decode=[RemoteDecodeLeg(f"http://127.0.0.1:{p}")
                               for p in ports])
            plan = faults.FaultPlan().at(kind="decode_leg_crash")
            reqs = [Request({"prompt": p},
                            {"max_new_tokens": MAXNEW,
                             "sampling_params": SAMPLING}, None)
                    for p in PROMPTS]
            with plan.active():
                dis._drive(reqs)
            outs = [np.asarray(r.future.result(timeout=60))
                    for r in reqs]
            assert plan.fired_log == [("decode_leg_crash", None)]
            for want, got in zip(refs, outs):
                np.testing.assert_array_equal(want, got)
            dc = _counters(dis)
            assert dc.get("decode_leg_failovers", 0) == 1
            pc = _counters(pre)
            # the failed-over context re-entered through chunked prefill
            assert pc.get("requests_resumed", 0) >= 1
            assert pc.get("recovery_prefill_tokens", 0) > 0
        finally:
            for srv in servers:
                srv.stop()


# ---------------------------------------------------------------------------
# 4: recovery-priority admission under pool pressure
# ---------------------------------------------------------------------------
class TestRecoveryPriorityAdmission:
    def test_recovery_lands_before_deferred_new_work(self):
        eng = _engine(slots=1)

        def _req(name, prompt, extra_meta=None):
            meta = {"max_new_tokens": MAXNEW,
                    "sampling_params": SAMPLING}
            meta.update(extra_meta or {})
            return Request({"prompt": prompt}, meta, None)

        occupant = _req("occupant", PROMPTS[0])
        assert eng.admit([occupant]) == 1
        # pool at capacity: NEW work defers...
        new_work = _req("new", PROMPTS[1])
        assert eng.admit([new_work]) == 0
        assert [it[0] for it in eng._deferred] == [new_work]
        # ...and a recovery re-admission queues AHEAD of it
        rec_work = _req("recovery", PROMPTS[2],
                        {"resume_tokens": [20, 21], "recovery": True})
        eng.admit([rec_work])
        assert [it[0] for it in eng._deferred] == [rec_work, new_work]
        tracked = [("occupant", occupant), ("new", new_work),
                   ("recovery", rec_work)]
        order = []
        deadline = time.monotonic() + 60
        while len(order) < 3 and time.monotonic() < deadline:
            eng._admit_deferred()
            eng.prefill_tick()
            eng.decode_tick()
            for name, r in tracked:
                if r.future.done() and name not in order:
                    order.append(name)
        # the recovery completed before the earlier-queued new admission
        assert order == ["occupant", "recovery", "new"]
        for _, r in tracked:
            np.asarray(r.future.result(timeout=0))  # none failed

    def test_resume_is_token_exact_and_skips_decode(self):
        """Direct engine-level resume: admitting prompt+emitted via
        ``resume_tokens`` reproduces the uninterrupted suffix without
        re-decoding the emitted prefix."""
        eng = _engine()
        full = np.asarray(eng.generate_all(
            [PROMPTS[0]], max_new_tokens=MAXNEW,
            sampling=[SAMPLING])[0])
        full_decodes = _counters(eng)["decode_tokens"]
        plen = len(PROMPTS[0])
        emitted = [int(t) for t in full[plen:plen + 2]]
        eng2 = _engine()
        req = Request({"prompt": PROMPTS[0]},
                      {"max_new_tokens": MAXNEW,
                       "sampling_params": SAMPLING,
                       "resume_tokens": emitted, "recovery": True}, None)
        eng2._drive([req])
        np.testing.assert_array_equal(
            np.asarray(req.future.result(timeout=60)), full)
        # exactly len(emitted) decode steps saved, never the prefix
        resumed_decodes = _counters(eng2)["decode_tokens"]
        assert resumed_decodes == full_decodes - len(emitted)
        assert _counters(eng2)["recovery_prefill_tokens"] > 0


# ---------------------------------------------------------------------------
# lineage store (unit)
# ---------------------------------------------------------------------------
class TestLineageStore:
    def test_register_progress_resume_discard(self):
        store = LineageStore(limit=4, register_flight=False)
        rec = store.register("k1", [1, 2, 3], {"seed": 11}, None)
        store.progress("k1", 0, 7)
        store.progress("k1", 1, 9)
        # idempotent positional overwrite (hedged attempts re-report)
        store.progress("k1", 0, 7)
        assert rec.resume_tokens() == [7, 9]
        with pytest.raises(ValueError):
            rec.progress(5, 1)          # a gap is a broken contract
        assert store.mark_recovery("k1").recoveries == 1
        store.discard("k1")
        assert store.get("k1") is None
        assert store.stats()["discarded"] == 1

    def test_bounded_lru_eviction(self):
        store = LineageStore(limit=2, register_flight=False)
        for i in range(4):
            store.register(f"k{i}", [i], {}, None)
        assert len(store) == 2
        assert store.stats()["evicted"] == 2
        assert store.get("k0") is None and store.get("k3") is not None
        state = store.flight_state()
        assert [r["key"] for r in state["records"]] == ["k2", "k3"]


# ---------------------------------------------------------------------------
# 5: joiner window durability (the spill sidecar)
# ---------------------------------------------------------------------------
class _Clock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


def _log_impressions(dirname, n, clock):
    log = ImpressionLog(str(dirname), segment_records=8, flush_s=0.002,
                        clock=clock)
    hook = FeedbackHook(log, clock=clock)
    rids = []
    for i in range(n):
        rid = f"r{i}"
        assert hook.on_served(rid, {"q": i}, [float(i)])
        rids.append(rid)
    log.close()
    return rids


class TestJoinerWindowSpill:
    def test_crash_preserves_pending_window_and_deadlines(self, tmp_path):
        clk = _Clock()
        rids = _log_impressions(tmp_path / "log", 4, clk)
        j1 = OutcomeJoiner(str(tmp_path / "log"),
                           str(tmp_path / "joined"), window_s=30.0,
                           clock=clk)
        j1.poll_once()                     # 4 pending, spilled
        assert j1.post_outcome("r9", 1.0) == "parked"   # parked, spilled
        assert j1.stats()["window_spilled"] >= 5
        clk.advance(10.0)
        # j1 dies here: NO seal, no close — the sidecar is the survivor
        j2 = OutcomeJoiner(str(tmp_path / "log"),
                           str(tmp_path / "joined"), window_s=30.0,
                           clock=clk)
        s = j2.stats()
        assert s["window_replayed"] == 5
        assert s["pending"] == 4 and s["parked"] == 1
        # an in-window outcome after the restart still joins POSITIVE —
        # without the spill it would have re-expired as a negative
        assert j2.post_outcome(rids[0], 1.0) == "joined"
        # deadlines are the ORIGINALS: 10s already elapsed, so +25s
        # crosses t0+30 and expires the rest
        clk.advance(25.0)
        j2.poll_once()
        assert j2.stats()["expired_negatives"] == 3
        assert j2.stats()["orphan_outcomes"] == 0   # park TTL is 60s
        j2.seal()
        from paddle_tpu.feedback import read_records, sealed_segments
        ex = [rec for path in sealed_segments(str(tmp_path / "joined"))
              for _, rec in read_records(path)]
        assert sorted(e["rid"] for e in ex) == sorted(rids)  # no dupes
        assert sum(e["label"] for e in ex) == 1.0

    def test_spill_compacts_on_seal(self, tmp_path):
        clk = _Clock()
        _log_impressions(tmp_path / "log", 6, clk)
        j = OutcomeJoiner(str(tmp_path / "log"),
                          str(tmp_path / "joined"), window_s=5.0,
                          clock=clk)
        j.poll_once()
        clk.advance(6.0)
        j.poll_once()                      # all expire -> all dropped
        j.seal()
        from paddle_tpu.feedback import read_records
        spill = list(read_records(str(tmp_path / "joined" / "window.spill")))
        assert spill == []                 # compacted to the live (empty) window
        j2 = OutcomeJoiner(str(tmp_path / "log"),
                           str(tmp_path / "joined"), window_s=5.0,
                           clock=clk)
        assert j2.stats()["window_replayed"] == 0


# ---------------------------------------------------------------------------
# 6: HttpReplica transport hardening
# ---------------------------------------------------------------------------
def _one_shot_server(handler):
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def run():
        conn, _ = srv.accept()
        try:
            handler(conn)
        finally:
            srv.close()

    threading.Thread(target=run, daemon=True).start()
    return port


class TestHttpReplicaHardening:
    def test_mid_body_reset_is_connection_dropped(self):
        def reset_mid_body(conn):
            conn.recv(65536)
            conn.sendall(b"HTTP/1.1 200 OK\r\n"
                         b"Content-Length: 100\r\n\r\n{\"par")
            conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))   # RST, not FIN
            conn.close()

        port = _one_shot_server(reset_mid_body)
        rep = HttpReplica(f"http://127.0.0.1:{port}", name="t")
        with pytest.raises(ConnectionDroppedError):
            rep._http("GET", "/metrics")

    def test_torn_body_is_connection_dropped(self):
        def torn(conn):
            conn.recv(65536)
            conn.sendall(b"HTTP/1.1 200 OK\r\n"
                         b"Content-Length: 5\r\n\r\n{\"pa")
            conn.close()

        port = _one_shot_server(torn)
        rep = HttpReplica(f"http://127.0.0.1:{port}", name="t")
        with pytest.raises(ConnectionDroppedError):
            rep._http("GET", "/metrics")

    def test_dropped_is_retryable_connection_error(self):
        # subclassing ConnectionError is what puts mid-stream drops
        # inside every existing retry-on-ConnectionError policy
        assert issubclass(ConnectionDroppedError, ConnectionError)

    def test_split_read_timeout(self):
        def slow(conn):
            conn.recv(65536)
            time.sleep(1.5)
            conn.close()

        port = _one_shot_server(slow)
        rep = HttpReplica(f"http://127.0.0.1:{port}", name="t",
                          connect_timeout_s=10.0, read_timeout_s=0.2)
        t0 = time.monotonic()
        with pytest.raises(RequestTimeoutError):
            rep._http("GET", "/metrics")
        # the READ timeout governed (0.2s), not the 10s connect timeout
        assert time.monotonic() - t0 < 5.0

    def test_connect_refused_is_plain_connection_error(self):
        rep = HttpReplica("http://127.0.0.1:1", name="t",
                          connect_timeout_s=0.5)
        with pytest.raises(ConnectionError) as ei:
            rep._http("GET", "/metrics")
        assert not isinstance(ei.value, ConnectionDroppedError)

    def test_happy_path_round_trip(self):
        def ok(conn):
            conn.recv(65536)
            body = json.dumps({"x": 1}).encode()
            conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: "
                         + str(len(body)).encode() + b"\r\n\r\n" + body)
            conn.close()

        port = _one_shot_server(ok)
        rep = HttpReplica(f"http://127.0.0.1:{port}", name="t")
        assert rep._http("GET", "/metrics") == {"x": 1}
