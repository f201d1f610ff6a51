"""Server front end: a dispatch thread over engines + a JSON HTTP endpoint.

``Server`` owns the DynamicBatcher and a daemon dispatch loop that drives
one or more engines' ``serve_step`` — an InferenceEngine executes whole
batches, a GenerationEngine interleaves prefill admissions with decode
ticks (continuous batching). Multiple engines round-robin the shared
queue: the local-replica pattern (one engine per device via ``place``).

The HTTP endpoint is stdlib ``http.server`` (no framework dependency —
the container bakes none), JSON in/out:

    POST /v1/generate  {"prompt": [ids], "max_new_tokens": n, "eos_id": e,
                        # decode-platform fields (all optional; absent =
                        # legacy greedy, byte-identical):
                        "temperature": t, "top_k": k, "top_p": p,
                        "seed": s, "stop": [[ids], ...],
                        "beam_size": K, "length_penalty": a,
                        "return_beams": bool,
                        # seq2seq engines: "src" replaces/joins "prompt"
                        "src": [ids]}
                       -> {"ids": [...]} (+ "beams"/"scores" for
                       return_beams)
    POST /v1/infer     {"inputs": {feed: nested-list-row}}
                       -> {"outputs": [...]}
    GET  /metrics      -> MetricsRegistry snapshot + serving timers
    GET  /metrics?format=prom -> Prometheus text exposition (v0.0.4),
                       also selected by an Accept: text/plain header
    GET  /healthz      -> {"ok": true, "active": ..., "queue": ...};
                       503 while ``warming`` (boot-time manifest replay /
                       warmup) or ``draining``, so routers only send
                       traffic to ready replicas

Typed errors map onto status codes: QueueFullError -> 429,
RequestTimeoutError -> 504, BadRequestError -> 400.
"""
from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Sequence

import numpy as np

from .. import profiler, trace
from ..resilience.faults import TransientFault, active_plan
from ..trace import flight as trace_flight
from ..trace.slo import SLOTracker
from .batcher import DynamicBatcher, Future
from .errors import (BadRequestError, EngineClosedError,
                     ModelNotFoundError, QueueFullError,
                     RequestTimeoutError, ServingError)
from .metrics import MetricsRegistry

_IDLE_WAIT_S = 0.02  # dispatch-loop poll when the queue is empty

#: /v1/generate request fields forwarded into the engine meta — the
#: decode-platform schema (paddle_tpu.decoding.SamplingParams/BeamParams)
GENERATE_META = ("max_new_tokens", "eos_id", "temperature", "top_k",
                 "top_p", "seed", "stop", "beam_size", "length_penalty",
                 "return_beams",
                 # work-preserving recovery: a resumed stream carries the
                 # already-emitted tokens (re-entering as prefill
                 # context) and the recovery flag (priority admission)
                 "resume_tokens", "recovery")


class Server:
    """Dispatch loop + admission queue over one or more engines."""

    def __init__(self, engine, *, batcher: Optional[DynamicBatcher] = None,
                 batch_buckets: Sequence[int] = (1, 2, 4, 8),
                 max_wait_ms: float = 5.0, max_queue: int = 256,
                 default_timeout_ms: Optional[float] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 serve_retry=None, warmup=False, slo=None,
                 model_ids: Sequence[str] = ()):
        self.engines = list(engine) if isinstance(
            engine, (list, tuple)) else [engine]
        self.metrics = metrics or self.engines[0].metrics
        # (None for every engine without a vision tower: ``submit`` asks
        # nothing of their payloads)
        self._check_media = (
            self.engines[0].check_payload
            if getattr(getattr(self.engines[0], "spec", None), "vision",
                       None) is not None else None)
        # ids this replica answers a "model"/"tenant" request field
        # with; anything else is a typed 404 — an unknown id must never
        # silently fall through to the default engine
        self.model_ids = tuple(model_ids)
        self.batcher = batcher or DynamicBatcher(
            buckets=batch_buckets, max_wait_ms=max_wait_ms,
            max_queue=max_queue, default_timeout_ms=default_timeout_ms,
            metrics=self.metrics)
        if self.batcher.metrics is None:
            self.batcher.metrics = self.metrics
        # Optional resilience.Retry applied around each serve_step: a
        # transient dispatch failure (ConnectionError/TimeoutError/
        # injected TransientFault) retries with backoff instead of
        # failing the whole formed batch.
        self._serve_retry = serve_retry
        # warmup=True runs each engine's warm_start()/warmup() on the
        # dispatch thread before serving; a callable runs instead of the
        # default. While it runs, /healthz reports ``warming`` (503) so a
        # router never sends traffic to a cold replica — the boot-side
        # mirror of the drain machinery.
        self._warmup = warmup
        # declarative SLO (trace.SLO): evaluated from the TTFT/TPOT/
        # request histograms on every metrics render; burn-rate gauges
        # land on /metrics?format=prom
        self.slo_tracker = (SLOTracker(slo) if slo is not None else None)
        # flight recorder: dispatch-loop errors capture a bundle
        # (throttled); /admin/flightdump serves it on demand
        self.flight = trace_flight.get_recorder()
        self._dispatch_step = 0
        self._thread: Optional[threading.Thread] = None
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._running = False
        self._paused = False
        self._state = "ready"
        # feedback plane (paddle_tpu.feedback): attach_feedback() starts
        # impression logging + the /v1/outcome endpoint
        self.feedback = None

    @property
    def state(self) -> str:
        """``warming`` | ``ready`` | ``draining`` | ``closed`` — what
        /healthz reports (load balancers route to ``ready`` only:
        ``warming`` covers boot exactly like ``draining`` covers
        shutdown)."""
        return self._state

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "Server":
        if self._thread is not None:
            return self
        self._running = True
        self._paused = False
        self._state = "warming" if self._warmup else "ready"
        self._thread = threading.Thread(target=self._loop,
                                        name="paddle-tpu-serving",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = False, timeout: float = 30.0) -> None:
        """Stop the server. Default fails queued requests immediately;
        ``drain=True`` first stops admissions (submit raises
        EngineClosedError, /healthz flips to ``draining``/503), lets the
        dispatch loop finish the backlog (bounded by ``timeout``), and
        gracefully releases engines that support ``close``."""
        if drain:
            self._state = "draining"
            for b in self._batchers():
                b.close(drain=True)
            deadline = time.monotonic() + timeout
            while self._queue_depth() > 0 and time.monotonic() < deadline:
                time.sleep(0.01)
        self._running = False
        for b in self._batchers():
            b.close()  # fail whatever remains (no-op when drained)
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if drain:
            # graceful shutdown releases the engines too; the default
            # stop() leaves them usable (tests restart servers on them)
            for eng in self.engines:
                if hasattr(eng, "close"):
                    try:
                        eng.close(drain=True)
                    except TypeError:  # engines with a plain close()
                        eng.close()
        self._state = "closed"
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd = None

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- reversible drain (rolling updates) --------------------------------
    def pause(self, wait: bool = True, timeout: float = 30.0) -> None:
        """REVERSIBLE drain — the per-replica step of a rolling weight
        update. Admissions stop (submit raises EngineClosedError,
        /healthz flips to ``draining``/503 so routers hold traffic) but
        the dispatch loop keeps running and finishes the backlog;
        ``wait=True`` blocks (bounded by ``timeout``) until the queue is
        empty and every engine is idle — the safe point for
        ``swap_params``. :meth:`resume` rejoins. Unlike :meth:`stop`,
        nothing is closed."""
        self._paused = True
        if self._state == "ready":
            self._state = "draining"
        if not wait:
            return
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            busy = self._queue_depth() > 0 or any(
                getattr(eng, "active", 0) or getattr(eng, "_inflight", 0)
                for eng in self.engines)
            if not busy:
                break
            time.sleep(0.005)

    def resume(self) -> None:
        """Rejoin after :meth:`pause`: admissions reopen and /healthz
        reports ``ready`` again."""
        self._paused = False
        if self._state == "draining" and self._running:
            self._state = "ready"

    def swap_params(self, source, *, strict: bool = True,
                    tenant: Optional[str] = None) -> dict:
        """Hot-swap every engine's params (see engine.swap_params);
        call between :meth:`pause` and :meth:`resume`. ``tenant``
        scopes the swap on a multi-tenant server; this single-model
        server answers a tenant-scoped swap with a typed 404."""
        if tenant is not None:
            raise ModelNotFoundError(
                f"unknown tenant {tenant!r}: this replica hosts one "
                "unnamed model (tenant-scoped swaps need a "
                "MultiTenantServer)")
        stats: dict = {}
        for eng in self.engines:
            for k, v in eng.swap_params(source, strict=strict).items():
                stats[k] = stats.get(k, 0) + v
        return stats

    def _do_warmup(self) -> None:
        """Manifest replay / warmup on the dispatch thread, before the
        first batch is pulled. Requests submitted meanwhile queue in the
        batcher; /healthz says ``warming`` so routers hold traffic. A
        warmup failure downgrades to lazy compiles instead of killing the
        replica."""
        t0 = time.monotonic()
        try:
            if callable(self._warmup):
                self._warmup()
            else:
                for eng in self.engines:
                    if not self._running:
                        break
                    warm = (getattr(eng, "warm_start", None)
                            or getattr(eng, "warmup", None))
                    if warm is not None:
                        warm()
        except Exception:  # noqa: BLE001 - cold replica beats dead replica
            self.metrics.inc("warmup_errors")
        self.metrics.set_gauge("warmup/boot_s",
                               round(time.monotonic() - t0, 6))
        if self._state == "warming":  # stop() during warmup wins
            self._state = "ready"

    def _batchers(self):
        """Every admission queue this server owns — one for the base
        server; one per tenant on a MultiTenantServer."""
        return [self.batcher]

    def _queue_depth(self) -> int:
        return sum(b.depth for b in self._batchers())

    def _dispatch_pairs(self):
        """(engine, batcher) pairs the dispatch loop round-robins. The
        base server shares ONE admission queue across its engines; a
        MultiTenantServer pairs each tenant's engines with that
        tenant's own queue."""
        return [(eng, self.batcher) for eng in self.engines]

    def _loop(self) -> None:
        if self._warmup:
            self._do_warmup()
        idx = 0
        while self._running:
            pairs = self._dispatch_pairs()
            engine, batcher = pairs[idx % len(pairs)]
            idx += 1
            try:
                plan = active_plan()
                if plan is not None and plan.fire(
                        "executor_error", self._dispatch_step) is not None:
                    raise TransientFault(
                        "injected executor_error (fault plan) in the "
                        "serving dispatch loop")
                if self._serve_retry is not None:
                    did = self._serve_retry.call(
                        engine.serve_step, batcher,
                        idle_wait_s=_IDLE_WAIT_S)
                else:
                    did = engine.serve_step(batcher,
                                            idle_wait_s=_IDLE_WAIT_S)
            except Exception as exc:  # noqa: BLE001 - keep dispatching
                # engine errors fail their requests individually; a crash
                # here would silently stop dispatch — keep looping, but
                # FIRST capture the flight bundle: spans, metric history
                # and engine state at the moment it fell over
                self.metrics.inc("dispatch_errors")
                self.flight.auto_dump("dispatch_error", error=exc)
                did = False
            else:
                if did:
                    self._dispatch_step += 1
            if not did and len(pairs) > 1:
                continue  # try the next replica before idling

    # -- in-process API ----------------------------------------------------
    def submit(self, payload, timeout_ms: Optional[float] = None,
               **meta) -> Future:
        """Enqueue a request; returns a Future. Raises QueueFullError on
        backpressure. For generation engines the payload is a prompt (or
        {"prompt": ids}, or {"prompt": ids, "media": [frames, ...]} for a
        spec with a vision tower) with max_new_tokens/eos_id in ``meta``; for
        inference engines it is a per-row feed dict."""
        if self._paused:
            raise EngineClosedError(
                "server is draining (paused for a rolling update); "
                "route to another replica")
        model = meta.pop("model", None)
        if model is not None and model not in self.model_ids:
            self.metrics.inc("model_not_found")
            raise ModelNotFoundError(
                f"unknown model/tenant {model!r}: this replica serves "
                + (f"{sorted(self.model_ids)}" if self.model_ids
                   else "one unnamed model"))
        # an engine with a vision tower checks a payload's media HERE
        # (``{"prompt": ids, "media": [frames uint8 [F, S, S, 3], ...]}``:
        # one entry a vision span; a span that is not whole frames, a missing
        # entry or a wrong shape is refused, typed, and counted); the layout
        # it made rides the request to admission
        if self._check_media is not None:
            meta["media"] = self._check_media(payload)
        fut = self.batcher.submit(payload, timeout_ms=timeout_ms, **meta)
        return self._feedback_tap(fut, payload, model)

    # -- feedback plane ----------------------------------------------------
    def attach_feedback(self, hook) -> "Server":
        """Start logging served impressions through ``hook``
        (:class:`paddle_tpu.feedback.FeedbackHook`): every successful
        submit gains a ``request_id`` (returned on the HTTP surface) and
        lands one impression record in the hook's log; ``POST
        /v1/outcome`` routes into the hook's joiner."""
        self.feedback = hook
        return self

    def _feedback_tap(self, fut: Future, payload, model):
        """Tag the future with a request id and log the impression at
        completion. The tap rides set_result (success only — failed
        requests are not impressions) and costs one bounded-buffer
        append on the dispatch thread; the serving thread pays
        nothing."""
        fb = self.feedback
        if fb is None:
            return fut
        rid = fb.new_request_id()
        fut.request_id = rid
        inner = fut.set_result

        def tapped(result, _inner=inner, _rid=rid, _payload=payload,
                   _model=model):
            _inner(result)
            try:
                fb.on_served(_rid, _payload, result, model=_model)
            except Exception:  # noqa: BLE001 - never fail the request
                pass

        fut.set_result = tapped
        return fut

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 timeout_s: Optional[float] = 60.0) -> np.ndarray:
        """Blocking convenience wrapper around submit() for LM engines."""
        fut = self.submit({"prompt": prompt},
                          max_new_tokens=max_new_tokens, eos_id=eos_id)
        return fut.result(timeout=timeout_s)

    def metrics_snapshot(self) -> dict:
        self.metrics.update_device_gauges()
        snap = self.metrics.merge_timer_dict(
            profiler.global_stat.as_dict(prefix="serving/"))
        for i, eng in enumerate(self.engines):
            if hasattr(eng, "cache_stats"):
                snap[f"compile_cache/engine{i}"] = eng.cache_stats()
        snap["queue_depth"] = self._queue_depth()
        if self.slo_tracker is not None:
            snap["slo"] = self.slo_tracker.publish_gauges(
                self.metrics, self.slo_tracker.status(snap))
        return snap

    def metrics_prometheus(self) -> str:
        """The /metrics?format=prom body: Prometheus text exposition of
        the registry + serving timers + compile-cache/queue gauges +
        TTFT/TPOT histograms and SLO burn-rate gauges."""
        self.metrics.update_device_gauges()
        self.metrics.set_gauge("queue_depth", self._queue_depth())
        for i, eng in enumerate(self.engines):
            if hasattr(eng, "cache_stats"):
                for k, v in eng.cache_stats().items():
                    self.metrics.set_gauge(f"compile_cache/e{i}_{k}", v)
        if self.slo_tracker is not None:
            self.slo_tracker.publish_gauges(
                self.metrics,
                self.slo_tracker.status(self.metrics.snapshot()))
        return self.metrics.prometheus_text(
            timers=profiler.global_stat.as_dict(prefix="serving/"))

    # -- HTTP front end ----------------------------------------------------
    def serve_http(self, host: str = "127.0.0.1", port: int = 0,
                   socket_timeout_s: Optional[float] = 30.0) -> int:
        """Start the JSON endpoint on a daemon thread; returns the bound
        port (pass port=0 for an ephemeral one).

        ``socket_timeout_s`` bounds how long a stalled client may hold a
        handler thread: the per-connection socket timeout covers both
        the request line and the body read — a client that stops sending
        mid-request gets 408 (when addressable) and the connection is
        closed, counted as ``http_408_timeouts`` in the
        MetricsRegistry. Without it, one dead client per thread is a
        slow-loris outage."""
        server = self
        # operator poke: SIGUSR1 dumps a flight bundle (written to
        # $PADDLE_TPU_FLIGHT_DIR when set; in-memory last_bundle
        # always). Best-effort — a no-op off the main thread.
        trace_flight.install_signal_handler(recorder=self.flight)

        class Handler(BaseHTTPRequestHandler):
            timeout = socket_timeout_s  # socketserver: settimeout per conn

            def log_message(self, *a):  # quiet: metrics carry the signal
                pass

            def log_error(self, fmt, *args):
                # stdlib handle_one_request swallows a request-line
                # timeout after logging it — the only seam to count it
                if fmt.startswith("Request timed out"):
                    server.metrics.inc("http_408_timeouts")

            def _send(self, code: int, obj) -> None:
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path, _, query = self.path.partition("?")
                if path == "/metrics":
                    want_prom = ("format=prom" in query
                                 or "text/plain" in
                                 (self.headers.get("Accept") or ""))
                    if want_prom:
                        body = server.metrics_prometheus().encode()
                        self.send_response(200)
                        self.send_header(
                            "Content-Type",
                            "text/plain; version=0.0.4; charset=utf-8")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    self._send(200, server.metrics_snapshot())
                elif path == "/admin/flightdump":
                    # GET = read-only: assemble and return the bundle
                    self._send(200, server.flight.bundle("admin"))
                elif path == "/healthz":
                    # ready -> 200; warming/draining/closed -> 503 so load
                    # balancers route neither to a cold replica still
                    # compiling nor to one finishing in-flight work
                    state = server.state
                    self._send(200 if state == "ready" else 503, {
                        "ok": state == "ready",
                        "state": state,
                        "queue": server._queue_depth(),
                        "engines": len(server.engines),
                        "engine_states": [getattr(e, "state", "ready")
                                          for e in server.engines],
                    })
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    raw = self.rfile.read(n)
                except TimeoutError:
                    # stalled client mid-body: free the thread with 408
                    # instead of holding it for the connection's lifetime
                    server.metrics.inc("http_408_timeouts")
                    self.close_connection = True
                    try:
                        self._send(408, {"error": "client stalled; "
                                         "request body timed out"})
                    except OSError:
                        pass  # peer already gone
                    return
                except (ValueError, TypeError) as exc:
                    self._send(400, {"error": f"bad length: {exc}"})
                    return
                try:
                    req = json.loads(raw or b"{}")
                except (ValueError, TypeError) as exc:
                    self._send(400, {"error": f"bad JSON: {exc}"})
                    return
                try:
                    # resume the caller's trace across the HTTP hop: the
                    # request's queue/prefill/decode spans join the
                    # router's trace id instead of starting a fresh one
                    tmeta = {}
                    tp = self.headers.get("traceparent")
                    if tp:
                        tmeta["traceparent"] = tp
                    if self.path.startswith("/admin/"):
                        self._admin(req)
                    elif self.path == "/v1/generate":
                        # sampling / stop / beam request fields — absent
                        # fields keep the legacy greedy behavior
                        # byte-identical (GENERATE_META names the schema)
                        meta = {k: req[k] for k in GENERATE_META
                                if req.get(k) is not None}
                        # multi-tenant routing field ("tenant" is an
                        # accepted alias); unknown ids are a typed 404
                        model = (req.get("model")
                                 if req.get("model") is not None
                                 else req.get("tenant"))
                        if model is not None:
                            meta["model"] = model
                        payload = ({"src": req["src"],
                                    "prompt": req.get("prompt")}
                                   if req.get("src") is not None
                                   else {"prompt": req["prompt"]})
                        fut = server.submit(
                            payload, timeout_ms=req.get("timeout_ms"),
                            **meta, **tmeta)
                        res = fut.result(timeout=req.get("timeout_s", 60))
                        rid = getattr(fut, "request_id", None)
                        if isinstance(res, tuple):  # all beams requested
                            ids, scores = res
                            body = {
                                "ids": np.asarray(ids)[0].tolist(),
                                "beams": np.asarray(ids).tolist(),
                                "scores": np.asarray(scores).tolist()}
                        else:
                            body = {"ids": np.asarray(res).tolist()}
                        if rid is not None:  # feedback plane attached
                            body["request_id"] = rid
                        self._send(200, body)
                    elif self.path == "/v1/adopt":
                        # cross-process KV handoff: the prefill pool
                        # POSTs serialized page ranges + the block
                        # table; the engine installs them and resumes
                        # decode (never a prefill recompute). Blocks
                        # until generation completes, like /v1/generate.
                        meta = {}
                        model = (req.get("model")
                                 if req.get("model") is not None
                                 else req.get("tenant"))
                        if model is not None:
                            meta["model"] = model
                        fut = server.submit(
                            {"handoff": req["handoff"]},
                            timeout_ms=req.get("timeout_ms"),
                            **meta, **tmeta)
                        res = fut.result(timeout=req.get("timeout_s", 60))
                        self._send(200,
                                   {"ids": np.asarray(res).tolist()})
                    elif self.path == "/v1/infer":
                        inputs = {k: np.asarray(v)
                                  for k, v in req["inputs"].items()}
                        fut = server.submit(inputs,
                                            timeout_ms=req.get("timeout_ms"),
                                            **tmeta)
                        outs = fut.result(timeout=req.get("timeout_s", 60))
                        body = {"outputs": [
                            np.asarray(o).tolist() for o in outs]}
                        rid = getattr(fut, "request_id", None)
                        if rid is not None:  # feedback plane attached
                            body["request_id"] = rid
                        self._send(200, body)
                    elif self.path == "/v1/outcome":
                        # the joiner ingress: outcomes post back keyed
                        # by the request_id a /v1/* response carried
                        fb = server.feedback
                        joiner = getattr(fb, "joiner", None)
                        if joiner is None:
                            self._send(404, {
                                "error": "no outcome joiner attached "
                                         "to this replica"})
                        else:
                            status = joiner.post_outcome(
                                req["request_id"],
                                req.get("outcome", req.get("label")))
                            self._send(200, {"status": status})
                    else:
                        self._send(404, {"error": "not found"})
                except KeyError as exc:
                    self._send(400, {"error": f"missing field {exc}"})
                except ValueError as exc:  # e.g. swap shape mismatch
                    self._send(400, {"error": str(exc)})
                except BadRequestError as exc:
                    self._send(400, {"error": str(exc)})
                except QueueFullError as exc:
                    self._send(429, {"error": str(exc)})
                except (RequestTimeoutError, TimeoutError) as exc:
                    self._send(504, {"error": str(exc) or "timed out"})
                except ModelNotFoundError as exc:
                    self._send(404, {"error": str(exc)})
                except (EngineClosedError, ServingError) as exc:
                    self._send(503, {"error": str(exc)})

            def _admin(self, req):
                """Replica control plane — what HttpReplica and
                tools/fleetctl.py drive during rolling updates."""
                if self.path == "/admin/drain":
                    server.pause(wait=req.get("wait", True),
                                 timeout=req.get("timeout", 30.0))
                    self._send(200, {"ok": True, "state": server.state})
                elif self.path == "/admin/resume":
                    server.resume()
                    self._send(200, {"ok": True, "state": server.state})
                elif self.path == "/admin/swap":
                    stats = server.swap_params(
                        req["checkpoint_dir"],
                        strict=req.get("strict", True),
                        tenant=req.get("tenant"))
                    self._send(200, stats)
                elif self.path == "/admin/warm":
                    warmed = 0
                    for eng in server.engines:
                        warm = getattr(eng, "warm_from_manifest", None)
                        if warm is not None:
                            warmed += warm() or 0
                    self._send(200, {"ok": True, "warmed": warmed})
                elif self.path == "/admin/flightdump":
                    # POST {"path": ...} writes the bundle to disk on
                    # the SERVER box and returns where; without a path
                    # it returns the bundle itself (the GET twin)
                    if req.get("path"):
                        written = server.flight.dump(
                            req.get("reason", "admin"),
                            path=req["path"])
                        self._send(200, {"ok": written is not None,
                                         "path": written})
                    else:
                        self._send(200, server.flight.bundle(
                            req.get("reason", "admin")))
                elif self.path == "/admin/trace_export":
                    # write this process's span journal (JSONL) so a
                    # fleet operator can stitch replica traces with
                    # tools/trace_summary.py --distributed
                    from ..trace import export_jsonl

                    n = export_jsonl(req["path"],
                                     drain=req.get("drain", False))
                    self._send(200, {"ok": True, "spans": n,
                                     "path": req["path"]})
                else:
                    self._send(404, {"error": "not found"})

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        threading.Thread(target=self._httpd.serve_forever,
                         name="paddle-tpu-serving-http",
                         daemon=True).start()
        return self._httpd.server_address[1]
