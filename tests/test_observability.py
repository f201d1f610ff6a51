"""PR 12 observability plane: cross-process trace propagation, TTFT/TPOT
histograms with correct fleet-level merge, SLO burn-rate tracking, and
the crash-safe flight recorder.

The two acceptance pins live here:

1. a hedged request through a 2-process fleet (one local replica, one
   remote subprocess replica) yields ONE trace id across the router's
   attempt/hedge spans and BOTH replicas' queue/prefill/decode spans,
   and ``tools/trace_summary.py --distributed`` stitches the two span
   journals into that request's cross-process critical path;
2. an injected ``FaultPlan`` ``executor_error`` in the serving dispatch
   loop produces a flight bundle carrying the recent requests' spans,
   metric snapshots, and live engine state — also served by
   ``/admin/flightdump``.

Plus the satellites: the cross-replica P99 regression pin (summing
histogram buckets is right, averaging per-replica quantiles is provably
wrong), tracer-under-concurrency coverage, malformed-traceparent
fallbacks, and the per-device memory gauge labels.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models, trace
from paddle_tpu.resilience import FaultPlan
from paddle_tpu.serving import (Fleet, GenerationEngine, HttpReplica,
                                LMSpec, MetricsRegistry, Request,
                                RoundRobinPolicy, Server)
from paddle_tpu.serving.metrics import HIST_BUCKET_BOUNDS, hist_quantile
from paddle_tpu.trace import SLO, FlightRecorder, SLOTracker, Tracer
from paddle_tpu.trace.flight import get_recorder

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, D, L, H, MAXLEN = 32, 16, 2, 2, 64

# weight cache shared across this module's engines (PR 10's pattern:
# immutable arrays, decode never writes them) — keeps the file off the
# startup-compile hot path
_WEIGHTS = {}


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    tracer = trace.get_tracer()
    tracer.configure(level=0, sample_rate=1.0)
    tracer.clear()
    yield
    tracer.configure(level=0, sample_rate=1.0)
    tracer.clear()


def _init_lm_scope(seed=7):
    exe = pt.Executor(pt.TPUPlace())
    if seed not in _WEIGHTS:
        scope = pt.Scope()
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            p = layers.data("p_init", shape=[8], dtype="int64")
            models.transformer_lm_generate(
                p, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                max_len=MAXLEN, max_new_tokens=1)
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


def _gen_engine(**kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prompt_buckets", (4, 8, 16))
    return GenerationEngine(_spec(), _init_lm_scope(), **kw)


# ---------------------------------------------------------------------------
# W3C context propagation (unit)
# ---------------------------------------------------------------------------
class TestTraceContext:
    def test_inject_extract_roundtrip(self):
        t = Tracer(level=1)
        sp = t.start_span("root", detached=True)
        header = t.inject(sp)
        assert header.startswith("00-") and header.endswith("-01")
        ctx = t.extract(header)
        assert ctx.trace_id == sp.trace_id
        assert ctx.span_id == sp.span_id
        child = t.start_span("child", parent=ctx, detached=True)
        assert child.trace_id == sp.trace_id
        assert child.parent_id == sp.span_id

    def test_malformed_headers_fall_back_never_raise(self):
        t = Tracer(level=1)
        bad = [None, "", "garbage", 42, b"00-aa-bb-01",
               "00-short-1111111111111111-01",
               "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # zero trace
               "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # zero span
               "ff-" + "a" * 32 + "-" + "1" * 16 + "-01",   # bad version
               "zz-" + "a" * 32 + "-" + "1" * 16 + "-01",   # non-hex
               "00-" + "a" * 32 + "-" + "1" * 16 + "-00"]   # unsampled
        for header in bad:
            assert t.extract(header) is None, header
        # a fresh trace is started when extraction fails
        sp = t.start_span("root", parent=t.extract("garbage"),
                          detached=True)
        assert sp.trace_id != 0

    def test_trace_ids_globally_unique_128bit(self):
        ids = set()
        for tracer in (Tracer(level=1), Tracer(level=1)):
            for _ in range(64):
                ids.add(tracer.start_span("s", detached=True).trace_id)
        assert len(ids) == 128
        assert any(i.bit_length() > 64 for i in ids)

    def test_span_ids_salted_per_process_tracer(self):
        a, b = Tracer(level=1), Tracer(level=1)
        sa = a.start_span("s", detached=True)
        sb = b.start_span("s", detached=True)
        assert sa.span_id != sb.span_id  # same counter, different salt

    def test_inject_without_span_is_none(self):
        t = Tracer(level=1)
        assert t.inject() is None
        t.level = 0
        assert t.inject() is None

    def test_batcher_resumes_trace_from_meta(self):
        trace.enable(level=1)
        root = trace.start_span("upstream", detached=True)
        header = trace.inject(root)
        req = Request({"prompt": [1]}, {"traceparent": header}, None)
        req.begin_trace()
        assert req.span.trace_id == root.trace_id
        req.end_trace(status="ok")
        root.finish()
        # malformed header: fresh trace, no exception
        req2 = Request({"prompt": [1]}, {"traceparent": "junk"}, None)
        req2.begin_trace()
        assert req2.span.trace_id != root.trace_id
        req2.end_trace(status="ok")


class TestTracerConcurrency:
    def test_ring_overwrite_under_8_writers(self):
        t = Tracer(capacity=256, level=1)
        errors = []

        def writer(k):
            try:
                for i in range(500):
                    sp = t.start_span(f"w{k}/{i}", detached=True)
                    sp.set_attr("i", i)
                    sp.finish()
            except Exception as exc:  # noqa: BLE001 - the assertion
                errors.append(exc)

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors
        spans = t.spans()
        assert len(spans) == 256  # ring held its bound, oldest fell off
        assert all(sp.end is not None for sp in spans)
        assert len({sp.span_id for sp in spans}) == 256


# ---------------------------------------------------------------------------
# histograms + the cross-replica aggregation regression pin
# ---------------------------------------------------------------------------
class TestHistograms:
    def test_fixed_buckets_and_quantiles(self):
        reg = MetricsRegistry()
        for v in (0.001, 0.001, 0.01, 0.1):
            reg.observe_hist("ttft", v)
        h = reg.snapshot()["hist"]["ttft"]
        assert h["count"] == 4
        assert len(h["counts"]) == len(HIST_BUCKET_BOUNDS) + 1
        assert sum(h["counts"]) == 4
        assert abs(h["sum_ms"] - 112.0) < 1e-6
        # quantile interpolation stays within the owning bucket
        assert 0.0005 < hist_quantile(h["counts"], 0.25) <= 0.0018

    def test_overflow_bucket(self):
        reg = MetricsRegistry()
        reg.observe_hist("x", 1000.0)  # beyond the last bound (100 s)
        h = reg.snapshot()["hist"]["x"]
        assert h["counts"][-1] == 1

    def test_merge_sums_buckets_correct_fleet_p99(self):
        """THE satellite regression pin. Two replicas with disjoint
        latency distributions: r0 answers in ~1 ms, r1 in ~1 s, equal
        traffic. True fleet P99 is ~1 s. The bucket-summing merge gets
        it right; the pre-fix aggregate — per-replica quantile summaries
        combined by averaging (there was no fleet number at all, so an
        operator averaged the per-replica P99s) — lands near 500 ms,
        provably wrong. Keep the wrongness assertion as the pin."""
        r0, r1 = MetricsRegistry(), MetricsRegistry()
        rng = np.random.RandomState(0)
        for _ in range(300):
            r0.observe_latency(float(rng.uniform(0.0009, 0.0011)))
            r1.observe_latency(float(rng.uniform(0.95, 1.05)))
        merged = MetricsRegistry.merge(
            {"r0": r0.snapshot(), "r1": r1.snapshot()})
        h = merged["hist"]["request"]
        assert h["count"] == 600
        true_p99_ms = 1000.0
        # bucket resolution is ~1.78x: correct within one bucket
        assert true_p99_ms / 1.8 <= h["p99_ms"] <= true_p99_ms * 1.8
        # the pre-fix value: averaging the per-replica p99 summaries
        avg_of_p99s = (r0.snapshot()["latency"]["request_ms"]["p99"]
                       + r1.snapshot()["latency"]["request_ms"]["p99"]) / 2
        assert avg_of_p99s < true_p99_ms / 1.8  # provably wrong
        # per-replica summaries are still exported, namespaced
        assert "r0/request_ms" in merged["latency"]

    def test_merge_sums_mixed_hist_names(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.observe_hist("ttft", 0.01)
        b.observe_hist("ttft", 0.02)
        b.observe_hist("tpot", 0.005)
        m = MetricsRegistry.merge({"a": a.snapshot(), "b": b.snapshot()})
        assert m["hist"]["ttft"]["count"] == 2
        assert m["hist"]["tpot"]["count"] == 1

    def test_prometheus_histogram_exposition_cumulative(self):
        reg = MetricsRegistry()
        for v in (0.001, 0.01, 50.0):
            reg.observe_hist("ttft", v)
        text = reg.prometheus_text()
        assert "# TYPE paddle_tpu_ttft_seconds histogram" in text
        assert 'paddle_tpu_ttft_seconds_bucket{le="+Inf"} 3' in text
        assert "paddle_tpu_ttft_seconds_count 3" in text
        # cumulative counts never decrease
        cums = [int(line.rsplit(" ", 1)[1])
                for line in text.splitlines()
                if line.startswith("paddle_tpu_ttft_seconds_bucket")]
        assert cums == sorted(cums)


# ---------------------------------------------------------------------------
# decode timelines (TTFT / TPOT) on the serving engine
# ---------------------------------------------------------------------------
class TestDecodeTimelines:
    def test_ttft_tpot_queue_wait_histograms_recent_ring_and_state(self):
        # one engine serves both the histogram and the flight-state
        # assertions (engine builds compile; tier-1 budget)
        eng = _gen_engine()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, VOCAB, (n,)).astype("int64")
                   for n in (3, 5, 8, 11)]
        eng.generate_all(prompts, max_new_tokens=5)
        hist = eng.metrics.snapshot()["hist"]
        assert hist["ttft"]["count"] == 4          # one per request
        assert hist["tpot"]["count"] == 4 * 4      # tokens - 1 each
        assert hist["queue_wait"]["count"] == 4
        assert len(eng._recent) == 4
        row = eng._recent[0]
        assert row["tokens"] == 5
        assert row["ttft_s"] is not None and row["ttft_s"] >= 0
        assert len(row["decode_deltas_ms"]) == 4
        assert row["prefill_chunks"]  # at least one chunk span
        state = eng.flight_state()
        assert state["slots_total"] == 4
        assert state["slots"] == []  # all done
        assert len(state["recent_requests"]) == 4
        assert "pool" in state and "deferred" in state


# ---------------------------------------------------------------------------
# SLO plane
# ---------------------------------------------------------------------------
class TestSLO:
    def _reg_with_ttft(self, values_ms):
        reg = MetricsRegistry()
        for v in values_ms:
            reg.observe_hist("ttft", v / 1e3)
        return reg

    def test_attainment_and_budget_math(self):
        # 90 fast + 10 slow against a 99%-under-100ms objective:
        # attainment 0.9, bad fraction 0.1 = 10x the 0.01 budget
        reg = self._reg_with_ttft([10.0] * 90 + [5000.0] * 10)
        clock = [0.0]
        tracker = SLOTracker(SLO(ttft_ms=100.0, target=0.99),
                             clock=lambda: clock[0])
        st = tracker.status(reg.snapshot())
        obj = st["objectives"]["ttft"]
        assert obj["total"] == 100
        assert abs(obj["attainment"] - 0.9) < 0.02
        assert obj["error_budget_remaining"] < -8  # budget blown 10x
        # burn rate over both windows ~ 0.1 / 0.01 = 10x
        for w in obj["burn"].values():
            assert 8 <= w["burn_rate"] <= 12

    def test_multiwindow_alert_requires_both_windows(self):
        clock = [0.0]
        tracker = SLOTracker(
            SLO(ttft_ms=100.0, target=0.99, windows_s=(60.0, 300.0),
                burn_thresholds=(2.0, 2.0)),
            clock=lambda: clock[0])
        reg = self._reg_with_ttft([10.0] * 1000)  # healthy history
        tracker.sample(reg.snapshot())
        clock[0] = 400.0
        st = tracker.status(reg.snapshot())
        assert st["alerting"] is False
        # the same registry turns ALL-bad: both windows burn -> alert
        for _ in range(500):
            reg.observe_hist("ttft", 5.0)
        clock[0] = 460.0
        st = tracker.status(reg.snapshot())
        obj = st["objectives"]["ttft"]
        assert all(w["burn_rate"] > 2.0 for w in obj["burn"].values())
        assert obj["alerting"] is True
        assert st["alerting"] is True

    def test_availability_objective_from_counters(self):
        reg = MetricsRegistry()
        reg.inc("completed", 999)
        reg.inc("failed", 1)
        tracker = SLOTracker(SLO(availability=0.999))
        st = tracker.status(reg.snapshot())
        obj = st["objectives"]["availability"]
        assert obj["total"] == 1000
        assert abs(obj["attainment"] - 0.999) < 1e-6
        assert abs(obj["error_budget_remaining"]) < 0.02

    def test_publish_gauges_prometheus(self):
        reg = self._reg_with_ttft([10.0] * 10)
        tracker = SLOTracker(SLO(ttft_ms=100.0))
        tracker.publish_gauges(reg, tracker.status(reg.snapshot()))
        text = reg.prometheus_text()
        assert 'paddle_tpu_slo_attainment{objective="ttft"} 1' in text
        assert "paddle_tpu_slo_burn_rate" in text


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_rings_sources_and_dump(self, tmp_path):
        rec = FlightRecorder(events=4)
        for i in range(9):
            rec.note("evt", i=i)
        reg = MetricsRegistry()
        reg.inc("completed", 3)
        assert rec.maybe_sample(reg, min_interval_s=0.0)
        rec.add_source("static", lambda: {"hello": 1}, weak=False)

        class Eng:
            def state(self):
                return {"slots": 2}

        eng = Eng()
        key = rec.add_source("engine", eng.state)
        bundle = rec.bundle("test")
        assert [e["i"] for e in bundle["events"]] == [5, 6, 7, 8]  # ring
        assert bundle["metric_snapshots"][0]["counters"][
            "completed"] == 3
        vals = list(bundle["state"].values())
        assert {"hello": 1} in vals and {"slots": 2} in vals
        # weak source dies with its owner, bundle never raises
        del eng
        bundle = rec.bundle("after-gc")
        assert key not in bundle["state"]
        path = rec.dump("disk", path=str(tmp_path / "b.json"))
        assert json.load(open(path))["reason"] == "disk"

    def test_auto_dump_throttles(self):
        rec = FlightRecorder(min_dump_interval_s=3600.0)
        rec.auto_dump("boom", error=RuntimeError("x"))
        first = rec.last_bundle
        rec.auto_dump("boom2", error=RuntimeError("y"))
        assert rec.last_bundle is first  # second within window: skipped

    def test_disabled_recorder_is_inert(self):
        rec = FlightRecorder()
        rec.enabled = False
        rec.note("evt")
        assert rec.auto_dump("x") is None
        assert not rec.bundle("manual")["events"]

    def test_executor_error_fault_dump_and_admin_endpoint(self):
        """THE flight-recorder acceptance pin: an injected FaultPlan
        executor_error in the serving dispatch loop captures a bundle
        with the recent requests' spans, metric snapshots, and live
        engine state; /admin/flightdump serves it over HTTP."""
        trace.enable(level=1)
        eng = _gen_engine()
        rec = get_recorder()
        rec._last_auto_dump = 0.0  # other tests may have dumped recently
        baseline_dumps = rec.dumps
        srv = Server(eng, max_wait_ms=1.0)
        port = srv.serve_http()
        with srv:
            # one healthy request first: its spans + timeline are the
            # "what was the engine doing" context the bundle must carry
            ids = srv.generate(np.arange(4, dtype=np.int64),
                               max_new_tokens=3, timeout_s=60)
            assert len(np.asarray(ids)) == 7
            with FaultPlan().at(step=None, kind="executor_error").active() \
                    as plan:
                deadline = time.monotonic() + 20
                while rec.dumps == baseline_dumps \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
            assert ("executor_error", srv._dispatch_step) \
                in plan.fired_log
            assert rec.dumps > baseline_dumps
            bundle = rec.last_bundle
            assert "executor_error" in bundle["error"]
            span_names = {s["name"] for s in bundle["trace"]["spans"]}
            assert "serving/request" in span_names   # the request's spans
            assert "serving/decode_step" in span_names
            engine_states = [v for v in bundle["state"].values()
                             if isinstance(v, dict)
                             and v.get("engine") == "GenerationEngine"]
            assert engine_states, bundle["state"].keys()
            mine = [s for s in engine_states
                    if s.get("recent_requests")]
            assert mine and mine[-1]["recent_requests"][-1]["tokens"] == 3
            assert srv.metrics.counter("dispatch_errors") >= 1
            # the HTTP twin
            raw = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/admin/flightdump",
                timeout=10).read()
            doc = json.loads(raw)
            assert doc["reason"] == "admin"
            assert {"events", "metric_snapshots", "state",
                    "trace"} <= set(doc)

    def test_sigusr1_dumps_bundle(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        rec = FlightRecorder()
        rec.note("before-signal")
        from paddle_tpu.trace import install_signal_handler

        assert install_signal_handler(recorder=rec)
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            deadline = time.monotonic() + 10
            while not list(tmp_path.glob("flight-*.json")) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            dumps = list(tmp_path.glob("flight-*.json"))
            assert dumps, "no flight dump written on SIGUSR1"
            doc = json.load(open(dumps[0]))
            assert doc["reason"] == "sigusr1"
            assert any(e["kind"] == "signal" for e in doc["events"])
        finally:
            signal.signal(signal.SIGUSR1, signal.SIG_DFL)


# ---------------------------------------------------------------------------
# per-device memory gauges (satellite)
# ---------------------------------------------------------------------------
class TestPerDeviceGauges:
    def test_labeled_device_memory_series(self):
        import jax.numpy as jnp

        keep = jnp.zeros((8, 8), jnp.float32) + 1  # ensure live bytes
        from paddle_tpu.trace import per_device_memory_stats

        per_dev = per_device_memory_stats()
        assert per_dev, "no devices reported"
        assert "0" in per_dev
        assert all(v > 0 for row in per_dev.values()
                   for v in row.values())
        reg = MetricsRegistry()
        reg.update_device_gauges()
        text = reg.prometheus_text()
        assert 'paddle_tpu_device_memory_bytes{device="0"' in text
        del keep


# ---------------------------------------------------------------------------
# the tentpole pin: 2-process hedged fleet, one trace, stitched
# ---------------------------------------------------------------------------
class TestDistributedFleetTrace:
    def test_hedged_request_one_trace_across_processes_and_stitch(
            self, tmp_path):
        trace.enable(level=1)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(_REPO, "tests",
                                          "obs_worker.py"),
             "--slow-ms", "250"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=_REPO)
        try:
            port = int(proc.stdout.readline())
            url = f"http://127.0.0.1:{port}"
            remote = HttpReplica(url, name="remote",
                                 connect_timeout_s=120.0)
            local = _gen_engine()
            # remote first in round-robin order -> it is the primary;
            # its 250 ms batcher wait guarantees the hedge fires to the
            # local replica, which wins — spans land in BOTH processes
            fleet = Fleet([remote, local], policy=RoundRobinPolicy(),
                          hedge=True, hedge_delay_ms=40.0)
            with fleet:
                ids = fleet.generate(np.arange(6, dtype=np.int64),
                                     max_new_tokens=4, timeout_s=120)
                assert len(np.asarray(ids)) == 10
                assert fleet.metrics.counter("hedges") >= 1
                # wait for BOTH replicas to finish their copy of the
                # hedged request (the loser keeps decoding after the
                # winner answered) so every span is closed pre-export
                deadline = time.monotonic() + 90
                while time.monotonic() < deadline:
                    snap = remote.metrics_snapshot()
                    if (snap.get("counters") or {}).get("completed",
                                                        0) >= 1 \
                            and local.metrics.counter("completed") >= 1 \
                            and local.active == 0:
                        break
                    time.sleep(0.05)
                remote_journal = str(tmp_path / "remote.jsonl")
                out = remote._http("POST", "/admin/trace_export",
                                   {"path": remote_journal},
                                   timeout_s=30.0)
                assert out["spans"] > 0
        finally:
            proc.stdin.close()
            proc.wait(timeout=30)
        router_journal = str(tmp_path / "router.jsonl")
        trace.export_jsonl(router_journal)

        def spans_of(path):
            rows = []
            for line in open(path):
                row = json.loads(line)
                if row.get("type") == "span":
                    rows.append(row)
            return rows

        router_spans = spans_of(router_journal)
        remote_spans = spans_of(remote_journal)
        fleet_roots = [s for s in router_spans
                       if s["name"] == "fleet/request"]
        assert len(fleet_roots) == 1
        tid = fleet_roots[0]["trace_id"]
        assert tid.bit_length() > 64  # globally unique, not a counter

        # ONE trace id spans the router's attempt/hedge records AND both
        # replicas' serving spans
        router_names = {s["name"] for s in router_spans
                        if s["trace_id"] == tid}
        assert "fleet/attempt" in router_names
        assert "fleet/hedge" in router_names
        assert "serving/request" in router_names   # local (winning) leg
        assert "serving/queue" in router_names
        assert "serving/execute" in router_names   # prefill
        assert "serving/decode" in router_names
        remote_names = {s["name"] for s in remote_spans
                        if s["trace_id"] == tid}
        assert "serving/request" in remote_names   # the hedged loser
        assert "serving/queue" in remote_names
        # no other trace id leaks into the request's remote spans
        assert all(s["trace_id"] == tid for s in remote_spans
                   if s["name"] == "serving/request")

        # --distributed stitches both journals and prints the critical
        # path of exactly this trace
        out = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, "tools", "trace_summary.py"),
             "--distributed", router_journal, remote_journal,
             "--trace-id", f"{tid:032x}"],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert f"{tid:032x}" in out.stdout
        assert "2 journal(s)" in out.stdout
        assert "remote.jsonl" in out.stdout
        assert "critical path" in out.stdout
        assert "queue" in out.stdout
        assert "prefill" in out.stdout
        assert "decode" in out.stdout
        # default trace selection (no --trace-id) finds the same request
        out2 = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, "tools", "trace_summary.py"),
             "--distributed", router_journal, remote_journal],
            capture_output=True, text=True, timeout=120)
        assert out2.returncode == 0
        assert f"{tid:032x}" in out2.stdout


# ---------------------------------------------------------------------------
# PR 24: a scoped span is also a profiler annotation, on the profiler's
# clock, and changes no compiled program
# ---------------------------------------------------------------------------
def _toy_trainer():
    from paddle_tpu import reader as reader_mod
    from paddle_tpu.trainer import SGD

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[8])
        y = layers.data("y", shape=[1], dtype="int64")
        cost = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(x, size=3), y))
        sgd = SGD(cost=cost, optimizer=pt.optimizer.SGDOptimizer(0.2),
                  feed_list=[x, y], place=pt.CPUPlace())
    rng = np.random.RandomState(0)
    xs = rng.rand(32, 8).astype("float32")
    ys = rng.randint(0, 3, size=(32, 1)).astype("int64")

    def rows():
        for i in range(32):
            yield xs[i], ys[i]

    return sgd, reader_mod.batch(rows, 8)


class _Profile:
    """``with _Profile(dir) as p:`` ... then ``p.host()``: every event of
    the xplane's host plane as (thread line index, name, start s, end s,
    stats), read the way ``benchmark/trace_reduce.load`` does."""

    def __init__(self, logdir):
        self.dir = str(logdir)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()

    def host(self):
        import glob

        import jax

        path = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        out = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if "/" not in ev.name or ev.name.startswith("$"):
                        continue
                    out.append((i, ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                {k: v for k, v in ev.stats}))
        return out


class TestSpansOnTheProfilerClock:
    def test_level1_chunked_prefill_finishes_and_carries_offset(self):
        """Pins the collision ``prefill_tick`` had: ``start=`` handed to
        ``trace.record(name, start, end, ...)`` raised after the device
        ran, so with the tracer on no prompt longer than one chunk ever
        finished."""
        trace.enable(level=1)
        eng = _gen_engine(prefill_chunk=8, prompt_buckets=(8, 16))
        prompt = np.arange(20, dtype=np.int64) % VOCAB   # 3 chunks
        with Server(eng, max_wait_ms=1.0) as srv:
            ids = srv.generate(prompt, max_new_tokens=3, timeout_s=120)
        assert len(np.asarray(ids)) == 23
        spans = trace.get_tracer().spans()
        chunks = [s for s in spans if s.name == "serving/prefill_chunk"]
        assert [s.attrs["offset"] for s in chunks] == [0, 8, 16]
        execs = [s for s in spans if s.name == "serving/execute"
                 and s.attrs.get("phase") == "prefill_chunk"]
        assert [s.attrs["offset"] for s in execs] == [0, 8, 16]
        assert [s.attrs["tokens"] for s in execs] == [8, 8, 4]
        assert eng.metrics.counter("prefill_chunks") == 3

    def test_level0_spans_sit_in_the_profile_by_thread(self, tmp_path):
        """Tracer off, profiler on: the serve pass holds its chunk and
        its tick, the trainer's feed stage shows on a thread of its own,
        and the ring buffer keeps nothing."""
        eng = _gen_engine(prefill_chunk=8, prompt_buckets=(8, 16))
        sgd, batches = _toy_trainer()
        prompt = np.arange(20, dtype=np.int64) % VOCAB
        with Server(eng, max_wait_ms=1.0) as srv:
            # warm every shape on another prompt: the same one again
            # would hit the prefix cache and skip its chunks
            srv.generate((prompt + 5) % VOCAB, max_new_tokens=2,
                         timeout_s=120)
            with _Profile(tmp_path) as prof:
                srv.generate(prompt, max_new_tokens=4, timeout_s=120)
                sgd.train(batches, num_passes=1,
                          event_handler=lambda e: None, async_depth=2)
        assert len(trace.get_tracer()) == 0
        events = prof.host()
        passes = [e for e in events if e[1] == "serving/pass"]
        assert passes

        def inside(name):
            return [e for e in events if e[1] == name and any(
                p[0] == e[0] and p[2] <= e[2] and e[3] <= p[3]
                for p in passes)]

        chunks = inside("serving/prefill_chunk")
        assert [c[4]["offset"] for c in chunks] == [0, 8, 16]
        assert all(c[4]["tokens"] in (8, 4) for c in chunks)
        assert len(inside("serving/decode_step")) >= 3
        assert inside("serving/admit")
        # every pass did work: one device call at least
        calls = [e for e in events if e[1] in (
            "serving/prefill_chunk", "serving/prefill_group",
            "serving/decode_step")]
        for p in passes:
            assert any(p[2] <= c[2] and c[3] <= p[3] for c in calls), p
        stack = [e for e in events if e[1] == "trainer/feed_stack"]
        put = [e for e in events if e[1] == "trainer/feed_put"]
        dispatch = [e for e in events if e[1] == "trainer/dispatch"]
        assert len(stack) == len(put) == len(dispatch) == 4
        feed_threads = {e[0] for e in stack + put}
        assert len(feed_threads) == 1
        assert feed_threads.isdisjoint({e[0] for e in dispatch})
        assert feed_threads.isdisjoint({p[0] for p in passes})

    def test_level1_span_and_annotation_agree_to_100us(self, tmp_path):
        """One region, two records: the ring-buffer span (perf_counter)
        mapped through a ``bench/clock_sync``-style marker lands on its
        annotation (the profiler's clock)."""
        import jax

        tracer = trace.enable(level=1)
        with _Profile(tmp_path) as prof:
            for _ in range(20):
                with jax.profiler.TraceAnnotation(
                        "test/clock_sync", perf_ns=time.perf_counter_ns()):
                    pass
            for i in range(20):
                with trace.span("test/probe", i=i):
                    time.sleep(0.001)
        pc = time.perf_counter()
        epoch = pc - trace.record("test/epoch", pc, pc).start
        events = prof.host()
        # the clock is read before the marker opens: the largest
        # difference is the one with the least delay in between
        offset = max(int(e[4]["perf_ns"]) * 1e-9 - e[2]
                     for e in events if e[1] == "test/clock_sync")
        anns = {int(e[4]["i"]): e for e in events if e[1] == "test/probe"}
        spans = {s.attrs["i"]: s for s in tracer.spans()
                 if s.name == "test/probe"}
        assert len(anns) == len(spans) == 20
        off_start, off_end = [], []
        for i, sp in spans.items():
            _, _, a0, a1, _ = anns[i]
            s0, s1 = sp.start + epoch - offset, sp.end + epoch - offset
            assert a0 - 1e-4 <= s0 and s1 <= a1 + 1e-4   # contained
            off_start.append(s0 - a0)
            off_end.append(a1 - s1)
        assert sorted(off_start)[10] < 100e-6
        assert sorted(off_end)[10] < 100e-6

    @pytest.mark.parametrize("what", ["train_step", "paged_decode"])
    def test_tracer_level_changes_no_lowered_program(self, what,
                                                     monkeypatch):
        """The StableHLO text of every program the flow compiles, debug
        locations included, is byte-identical with the tracer at level 0
        and at level 1: a span adds nothing to what is lowered."""
        from paddle_tpu.core.executor import Executor

        texts = []
        real = Executor._aot_compile

        def spy(self, jitted, args):
            texts.append(jitted.lower(*args).as_text(debug_info=True))
            return real(self, jitted, args)

        _init_lm_scope()    # the module's weight cache compiles but once
        monkeypatch.setattr(Executor, "_aot_compile", spy)
        seen = []
        for level in (0, 1):
            trace.get_tracer().configure(level=level)
            texts.clear()
            if what == "train_step":
                sgd, batches = _toy_trainer()
                sgd.train(batches, num_passes=1,
                          event_handler=lambda e: None, async_depth=2)
            else:
                eng = _gen_engine(prefill_chunk=8, prompt_buckets=(8, 16))
                eng.generate_all([np.arange(20, dtype=np.int64) % VOCAB],
                                 max_new_tokens=3)
            seen.append(list(texts))
        assert seen[0] and seen[0] == seen[1]
        assert any("stablehlo" in t for t in seen[0])

    def test_feed_put_span_waits_for_no_transfer(self, monkeypatch):
        """No span site syncs: ``trainer/feed_put`` closes on the feed
        thread while what ``device_put`` returned is still in flight, and
        nothing ever waits for it: the feed ring polls ``is_ready`` and,
        told no, has the batch stacked into fresh arrays, which the
        ``trainer/feed_stack`` span says (``reused`` false)."""
        import jax

        waited = []

        class InFlight(np.ndarray):
            def block_until_ready(self):
                waited.append(threading.get_ident())
                return self

            def is_ready(self):
                return False

        def device_put(value, device=None, **kw):
            return np.asarray(value).view(InFlight)

        monkeypatch.setattr(jax, "device_put", device_put)
        tracer = trace.enable(level=1)
        sgd, batches = _toy_trainer()
        sgd.train(batches, num_passes=1, event_handler=lambda e: None,
                  async_depth=2)
        puts = [s for s in tracer.spans() if s.name == "trainer/feed_put"]
        stacks = [s for s in tracer.spans()
                  if s.name == "trainer/feed_stack"]
        assert len(puts) == len(stacks) == 4
        assert {s.thread for s in puts} == {s.thread for s in stacks}
        assert threading.get_ident() not in {s.thread for s in puts}
        assert waited == []
        assert [s.attrs["reused"] for s in stacks] == [False] * 4
