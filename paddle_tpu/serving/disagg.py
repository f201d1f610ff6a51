"""Prefill/decode disaggregation: split pools, KV handoff, one router.

Prefill and decode want different machines: prefill is compute-bound
(long chunked matmuls, few slots), decode is latency-bound (one token
per tick across many slots). Batching them in one engine makes every
decode tick wait behind whatever prefill chunk is in flight — the
classic TTFT-vs-TPOT interference. This module splits them:

- :class:`PrefillPool` / :class:`DecodePool` — engine groups with
  independent replica counts and admission policies. Same-process
  pools are built over ONE shared page pool
  (``share_cache_with=``), so migration is free.
- :class:`DisaggEngine` — the composite the :class:`~.server.Server`
  drives like any engine: admissions place onto a prefill engine (the
  :class:`~.router.Router` is the placement layer — least-loaded with
  per-leg breakers), prefill ticks run there, and the moment a
  request's prompt K/V is fully cached it MIGRATES to a decode leg.
- **KV handoff** — the migration is refcounted pages + the int32 block
  table, never a recompute. Same-process: ``export_slot`` /
  ``adopt_slot`` transfer by refcount through the shared pool.
  Cross-process: :func:`serialize_handoff` moves the page byte ranges
  over the existing HTTP leg (``POST /v1/adopt``), and
  :func:`install_serialized_handoff` writes them into the remote
  pool and resumes decode — byte-identical tokens, zero prefill
  recompute (the decode pool's ``prefills`` counter stays 0).

Judged on goodput: the A/B that matters is SLO-good fraction vs a
unified pool at equal engine count (the ``disagg`` row in bench.py),
not aggregate QPS.
"""
from __future__ import annotations

import base64
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from .. import trace
from .errors import BadRequestError, EngineClosedError, QueueFullError
from .metrics import MetricsRegistry
from .router import LeastLoadedPolicy, Router

#: serialized-handoff schema version (reject anything else, typed).
#: v2: page bytes are [L, n, page_size, Hkv*dh] (one row a token); a v1
#: blob carried [L, n, Hkv, page_size, dh] and must not be installed.
HANDOFF_V = 2


# ---------------------------------------------------------------------------
# cross-process KV handoff: serialize / install
# ---------------------------------------------------------------------------
def _b64(arr: np.ndarray) -> str:
    return base64.b64encode(np.ascontiguousarray(arr).tobytes()).decode(
        "ascii")


def serialize_handoff(engine, handoff: dict, release: bool = True) -> dict:
    """Turn an :meth:`~.generation.GenerationEngine.export_slot`
    handoff into a JSON-safe migration payload: the slot's page byte
    ranges (gathered from the paged K/V tensors by block-table order),
    the decode cursor, and the request's decode policy. ``release=True``
    drops the exporter's page references afterwards (the bytes are the
    handoff now); pass False to keep them so a failed install can roll
    back via ``adopt_slot``."""
    st = handoff["st"]
    (cache,), (held,) = engine._caches, st.held     # one kind, K and V
    pids = cache.table_of(held)
    k, v = (np.asarray(engine.scope.get(name))[:, pids]
            for name in cache.scope_names)
    sp = st.sampling
    blob = {
        "v": HANDOFF_V,
        "prompt": np.asarray(st.prompt, np.int64).tolist(),
        "generated": [int(t) for t in st.generated],
        # a resumed slot's prompt CONTAINS its first ``resumed`` emitted
        # tokens (the re-prefilled context); _finish strips the overlap,
        # so the cursor must migrate with the slot
        "resumed": int(getattr(st, "resumed", 0) or 0),
        "max_new": int(st.max_new),
        "eos_id": None if st.eos_id is None else int(st.eos_id),
        "tok": int(handoff["tok"]),
        "pos": int(handoff["pos"]),
        "page_size": int(engine.page_size),
        "sampling": {
            "temperature": float(sp.temperature),
            "top_k": int(sp.top_k), "top_p": float(sp.top_p),
            "seed": sp.seed if sp.seed is None else int(sp.seed),
            "max_tokens": (None if sp.max_tokens is None
                           else int(sp.max_tokens)),
            "stop": [list(map(int, s)) for s in sp.stop],
        },
        "dtype": str(k.dtype), "shape": list(k.shape),
        "k": _b64(k), "v_": _b64(v),
    }
    if release:
        release_handoff(engine, handoff)
    return blob


def release_handoff(engine, handoff: dict) -> None:
    """Drop the exporter's claim on a serialized-away handoff: decref
    every page (shared prefix pages just lose one holder) and release
    the copy-on-write reservation."""
    engine._caches[0].release(handoff["st"].held[0])


def install_handoff(engine, blob: dict, request) -> bool:
    """Install a serialized handoff into ``engine`` and resume decode
    for ``request``. Returns False — with the engine untouched — when
    there is no free slot or not enough pages (transient pressure: the
    caller retries or rolls back); raises :class:`BadRequestError` when
    the payload can never fit this engine (schema/page-size/context
    mismatch). Every migrated-in page is exclusively owned, so the
    prefix-sharing copy-on-write machinery never fires for it."""
    if blob.get("v") != HANDOFF_V:
        raise BadRequestError(
            f"handoff schema v{blob.get('v')!r} != v{HANDOFF_V}")
    if int(blob["page_size"]) != engine.page_size:
        raise BadRequestError(
            f"handoff page_size {blob['page_size']} != engine page_size "
            f"{engine.page_size} — pools must agree on the page shape")
    prompt = np.asarray(blob["prompt"], np.int64)
    if prompt.size + int(blob["max_new"]) > engine.tmax:
        raise BadRequestError(
            f"handoff needs context {prompt.size + int(blob['max_new'])}"
            f" > engine serving context ({engine.tmax})")
    n = int(blob["shape"][1])
    if engine.free_slots == 0:
        return False
    from .generation import _Slot
    from ..decoding import SamplingParams

    s = blob["sampling"]
    sampling = SamplingParams(
        temperature=s["temperature"], top_k=s["top_k"],
        top_p=s["top_p"], seed=s["seed"], max_tokens=s["max_tokens"],
        stop=tuple(tuple(x) for x in s["stop"]))
    st = _Slot(request, prompt, int(blob["max_new"]), blob["eos_id"],
               sampling)
    (cache,), (held,) = engine._caches, st.held     # one kind, K and V
    # a migrated-in table must never half-land: room for all of it first
    # (the prefix index is evicted for it), then every page at once
    if not cache.make_room(n):
        return False
    cache.take(held, [], 0, n, n, 0)
    pids = cache.table_of(held)
    shape = tuple(blob["shape"])
    dtype = np.dtype(blob["dtype"])
    for name, key in zip(cache.scope_names, ("k", "v_")):
        pages = np.frombuffer(base64.b64decode(blob[key]),
                              dtype).reshape(shape)
        full = np.array(np.asarray(engine.scope.get(name)))
        full[:, pids] = pages
        engine.scope.set(name, full)
    st.prefill_done = prompt.size
    st.state = "decode"
    st.generated = [int(t) for t in blob["generated"]]
    st.resumed = int(blob.get("resumed", 0) or 0)
    # tokens already emitted at the source: advance the timeline so the
    # next emit records TPOT (the migration gap, honestly), not a fake
    # TTFT on this pool
    import time as _time

    for _ in st.generated:
        st.timeline.mark_token(_time.monotonic())
    slot = engine._slots.index(None)
    engine._slots[slot] = st
    engine._tok[slot] = int(blob["tok"])
    engine._pos[slot] = int(blob["pos"])
    engine.metrics.inc("kv_handoffs_in")
    engine.metrics.inc("kv_handoff_pages", n)
    return True


def install_serialized_handoff(engine, req) -> bool:
    """The admission-path entry (``admit`` intercepts payloads carrying
    ``handoff``): install and resume, or complete the request's future
    typed — BadRequestError for payloads that can never fit,
    QueueFullError (429, retryable) under transient slot/page
    pressure."""
    try:
        ok = install_handoff(engine, req.payload["handoff"], req)
    except BadRequestError as exc:
        engine.metrics.inc("bad_requests")
        req.end_trace(status="bad_request")
        req.future.set_exception(exc)
        return False
    if not ok:
        engine.metrics.inc("handoff_rejected")
        req.end_trace(status="handoff_rejected")
        req.future.set_exception(QueueFullError(
            "no free slot/pages to adopt the KV handoff; retry"))
    return ok


# ---------------------------------------------------------------------------
# pools and placement legs
# ---------------------------------------------------------------------------
class EnginePool:
    """N engines of one role. Same-process pools share ONE page pool
    (build the extra engines with ``share_cache_with=``), which is what
    makes migration a refcount transfer."""

    role = "pool"

    def __init__(self, engines):
        self.engines = list(engines) if isinstance(
            engines, (list, tuple)) else [engines]

    @property
    def free_slots(self) -> int:
        return sum(e.free_slots for e in self.engines)

    @property
    def active(self) -> int:
        return sum(e.active for e in self.engines)

    def __len__(self) -> int:
        return len(self.engines)


class PrefillPool(EnginePool):
    role = "prefill"


class DecodePool(EnginePool):
    role = "decode"


class _EngineLeg:
    """One local engine as a routable placement target — the Replica
    surface (:attr:`routable`/:attr:`inflight`/:meth:`healthz`) the
    :class:`Router` picks over."""

    def __init__(self, engine, name: str, index: int, fleet_size: int):
        self.engine = engine
        self.name = name
        self.index = index
        self.fleet_size = fleet_size
        self.remote = False

    @property
    def routable(self) -> bool:
        return self.engine.free_slots > 0

    @property
    def inflight(self) -> int:
        return self.engine.active

    def healthz(self) -> dict:
        return {"state": "ready", "ok": True,
                "free_slots": self.engine.free_slots}


class RemoteDecodeLeg:
    """A decode pool in ANOTHER process as a placement target. The
    migration rides the existing HTTP replica leg: serialized page
    ranges POST to ``/v1/adopt``, the response carries the finished
    ids, and the SOURCE request's future resolves with them — the
    client never sees the pool boundary."""

    def __init__(self, base_url: str, name: Optional[str] = None,
                 model: Optional[str] = None, max_inflight: int = 8,
                 timeout_s: float = 120.0):
        from .fleet import HttpReplica

        self.name = name or f"remote:{base_url}"
        self.index = 0
        self.fleet_size = 1
        self.model = model
        self.remote = True
        self.max_inflight = int(max_inflight)
        self.timeout_s = float(timeout_s)
        self._rep = HttpReplica(base_url, name=self.name)
        self._lock = threading.Lock()
        self._inflight = 0
        # DisaggEngine installs its failover hook here: a leg that dies
        # AFTER the pages were serialized away (the no-rollback window)
        # hands (blob, request) back instead of failing the future
        self.on_failure = None

    @property
    def routable(self) -> bool:
        with self._lock:
            return self._inflight < self.max_inflight

    @property
    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def healthz(self) -> dict:
        return self._rep.healthz()

    def _fail(self, blob: dict, request, exc: BaseException) -> None:
        """A dead/overloaded leg hands the work BACK (the failover
        hook re-prefills it elsewhere); non-retryable errors still fail
        the future typed."""
        cb = self.on_failure
        if cb is not None and isinstance(
                exc, (ConnectionError, TimeoutError, EngineClosedError,
                      QueueFullError)):
            cb(self, blob, request, exc)
        else:
            request.future.set_exception(exc)

    def adopt(self, blob: dict, request) -> bool:
        """Ship the serialized handoff; resolve the source request's
        future from the remote decode. The pages were already released
        to the bytes, so there is no rollback past this point — a leg
        that dies here goes through :meth:`_fail`, and the failover
        hook re-prefills the blob's context on another leg. Returns
        False when the leg died before dispatch (the fault-plan
        ``decode_leg_crash`` window) so the caller records a failure,
        not a success."""
        body: Dict[str, object] = {"handoff": blob}
        if self.model is not None:
            body["model"] = self.model
        from ..resilience import faults

        plan = faults.active_plan()
        if plan is not None \
                and plan.fire("decode_leg_crash") is not None:
            self._fail(blob, request, ConnectionError(
                f"{self.name} died after KV handoff (fault-plan "
                "decode_leg_crash) — pages already serialized away"))
            return False
        with self._lock:
            self._inflight += 1

        def run():
            try:
                out = self._rep._http("POST", "/v1/adopt", body,
                                      timeout_s=self.timeout_s)
                request.future.set_result(np.asarray(out["ids"]))
            except BaseException as exc:  # noqa: BLE001 - typed upstream
                self._fail(blob, request, exc)
            finally:
                with self._lock:
                    self._inflight -= 1

        threading.Thread(target=run, name=f"kv-handoff-{self.name}",
                         daemon=True).start()
        return True


# ---------------------------------------------------------------------------
# the composite engine
# ---------------------------------------------------------------------------
class DisaggEngine:
    """Prefill pool + decode pool behind one engine surface.

    Drives like any engine from a :class:`~.server.Server` (or
    directly): ``serve_step`` admits onto the least-loaded prefill leg,
    runs prefill ticks there, migrates every handoff-ready slot to a
    decode leg (local adopt through the shared page pool; remote legs
    get serialized page ranges), and runs decode ticks on the decode
    pool only — so a prefill burst can never stall a decode tick, and
    the prefill pool's ``decode_steps`` / the decode pool's
    ``prefills`` both pin at 0 (beam requests, whose job state is
    engine-local, live their whole life on a decode leg instead).

    Backpressure is structural: a slot whose decode legs are all full
    simply stays on its prefill engine — holding its pages, admitting
    no successor — until a decode slot frees.
    """

    def __init__(self, prefill, decode, *, policy=None,
                 remote_decode=(), metrics: Optional[MetricsRegistry] = None):
        self.prefill = (prefill if isinstance(prefill, EnginePool)
                        else PrefillPool(prefill))
        self.decode = (decode if isinstance(decode, EnginePool)
                       else DecodePool(decode))
        if not self.prefill.engines:
            raise ValueError("PrefillPool needs >= 1 local engine")
        if not self.decode.engines and not remote_decode:
            raise ValueError("need >= 1 decode leg (local or remote)")
        self.metrics = metrics or self.prefill.engines[0].metrics
        legs = [_EngineLeg(e, f"prefill{i}", i, len(self.prefill))
                for i, e in enumerate(self.prefill.engines)]
        self._prefill_router = Router(legs, policy=policy
                                      or LeastLoadedPolicy())
        dlegs: List[object] = [
            _EngineLeg(e, f"decode{i}", i,
                       len(self.decode) + len(remote_decode))
            for i, e in enumerate(self.decode.engines)]
        for j, leg in enumerate(remote_decode):
            if not isinstance(leg, RemoteDecodeLeg):
                leg = RemoteDecodeLeg(str(leg))
            leg.index = len(self.decode.engines) + j
            leg.fleet_size = len(self.decode) + len(remote_decode)
            dlegs.append(leg)
        self.router = Router(dlegs, policy=policy or LeastLoadedPolicy())
        self._remote = [leg for leg in dlegs
                        if isinstance(leg, RemoteDecodeLeg)]
        # decode-leg failover: a remote leg that dies after the KV
        # handoff parks (blob, request) here; _failover_tick re-prefills
        # the context on another leg — work-preserving, never a failure
        self._failover: deque = deque()
        self._failover_lock = threading.Lock()
        for leg in self._remote:
            leg.on_failure = self._decode_leg_failed
        self.engines = self.prefill.engines + self.decode.engines
        self.spec = self.engines[0].spec

    @classmethod
    def build(cls, spec, *, prefill_replicas: int = 1,
              decode_replicas: int = 1, scope=None, **engine_kw):
        """Construct split pools over ONE scope (shared weights) and ONE
        page pool (``share_cache_with`` chain) — the same-process
        deployment where migration is a pure refcount transfer."""
        from .generation import GenerationEngine

        spec.block.require_stateless("DisaggEngine.build (prefill and "
                                     "decode pools hand slots over)")
        first = GenerationEngine(spec, scope=scope, **engine_kw)
        engines = [first]
        for _ in range(prefill_replicas + decode_replicas - 1):
            engines.append(GenerationEngine(
                spec, scope=first.scope, share_cache_with=first,
                **engine_kw))
        return cls(PrefillPool(engines[:prefill_replicas]),
                   DecodePool(engines[prefill_replicas:]))

    # -- engine surface (what Server drives) -------------------------------
    @property
    def active(self) -> int:
        return (self.prefill.active + self.decode.active
                + sum(leg.inflight for leg in self._remote))

    @property
    def free_slots(self) -> int:
        return self.prefill.free_slots

    def _is_beam(self, req) -> bool:
        k = (req.meta or {}).get("beam_size")
        return bool(k) and int(k) > 1

    def _place(self, reqs) -> Dict[object, list]:
        """Admission placement: the Router picks a prefill leg per
        request (least loaded); beam requests go straight to a decode
        leg — their BeamJob holds engine-local state that cannot
        migrate, so they live their whole lifecycle decode-side."""
        groups: Dict[object, list] = {}
        for req in reqs:
            if self._is_beam(req) and not self.decode.engines:
                # a BeamJob's state is engine-local and cannot ride the
                # serialized handoff — remote-only decode can't host it
                req.future.set_exception(BadRequestError(
                    "beam requests need a local decode engine"))
                continue
            router = (self.router if self._is_beam(req)
                      else self._prefill_router)
            leg = router.route(req.meta)
            if leg is None or getattr(leg, "remote", False):
                # no local capacity: fall back to any local engine — its
                # own deferral queue is the backpressure, typed
                eng = (self.decode.engines[0] if self._is_beam(req)
                       else self.prefill.engines[0])
            else:
                eng = leg.engine
            groups.setdefault(eng, []).append(req)
        return groups

    def _migrate(self) -> int:
        """Move every handoff-ready slot from the prefill pool to a
        decode leg. Local legs adopt by refcount through the shared
        pool; remote legs get the serialized page ranges. A slot with
        no routable decode leg stays put (backpressure, retried next
        step)."""
        moved = 0
        for src in self.prefill.engines:
            for slot in src.handoff_ready():
                leg = self.router.route()
                if leg is None:
                    self.metrics.inc("kv_migration_stalls")
                    return moved
                if isinstance(leg, RemoteDecodeLeg):
                    hand = src.export_slot(slot)
                    req = hand["st"].request
                    blob = serialize_handoff(src, hand, release=True)
                    if leg.adopt(blob, req):
                        self.router.record(leg, ok=True)
                elif leg.engine.pool is src.pool:
                    hand = src.export_slot(slot)
                    leg.engine.adopt_slot(hand)
                else:  # local leg, separate pool: move the bytes
                    hand = src.export_slot(slot)
                    blob = serialize_handoff(src, hand, release=False)
                    if install_handoff(leg.engine, blob,
                                       hand["st"].request):
                        release_handoff(src, hand)
                    else:  # transient: roll back, retry next step
                        src.adopt_slot(hand)
                        self.metrics.inc("kv_migration_stalls")
                        return moved
                moved += 1
                self.metrics.inc("kv_migrations")
        return moved

    def _decode_leg_failed(self, leg, blob: dict, request,
                           exc: BaseException) -> None:
        """RemoteDecodeLeg failure hook (handoff-thread-safe: only
        enqueues). The leg is quarantined immediately — a mid-handoff
        death is a strong signal — and the blob re-enters via
        :meth:`_failover_tick` on the drive loop."""
        self.router.record(leg, ok=False, reason=type(exc).__name__)
        self.router.quarantine(leg, reason="decode leg crash")
        with self._failover_lock:
            self._failover.append((blob, request))
        self.metrics.inc("decode_leg_failovers")
        now = time.perf_counter()
        trace.record("disagg/decode_leg_failover", now, now,
                     leg=leg.name, error=repr(exc)[:200],
                     tokens_reused=len(blob.get("generated", [])))

    def _failover_tick(self) -> bool:
        """Re-admit every parked failover: the blob's already-emitted
        tokens become ``resume_tokens`` (chunk-prefilled, never
        re-decoded) and ``recovery=True`` buys priority admission on
        the prefill pool — pressure defers NEW work, not recoveries."""
        did = False
        while True:
            with self._failover_lock:
                if not self._failover:
                    return did
                blob, req = self._failover.popleft()
            meta = dict(req.meta or {})
            meta["resume_tokens"] = [int(t) for t in blob["generated"]]
            meta["recovery"] = True
            req.meta = meta
            leg = self._prefill_router.route(meta)
            eng = (leg.engine
                   if leg is not None and not getattr(leg, "remote",
                                                      False)
                   else self.prefill.engines[0])
            eng.admit([req])
            did = True

    def serve_step(self, batcher,
                   idle_wait_s: Optional[float] = None) -> bool:
        did = self._failover_tick()
        did = self._migrate() > 0 or did
        free = self.prefill.free_slots
        deferred = any(e._deferred for e in self.engines)
        if free and not deferred:
            wait = 0 if (self.active or did) else idle_wait_s
            reqs = batcher.next_batch(max_n=free, wait_s=wait)
            for eng, group in self._place(reqs or []).items():
                did = eng.admit(group) > 0 or did
        for eng in self.prefill.engines:
            did = eng._admit_deferred() > 0 or did
            did = eng.prefill_tick() or did
        for eng in self.decode.engines:
            did = eng._beam_maintenance() or did
            did = eng._admit_deferred() > 0 or did
            did = eng.prefill_tick() or did  # beam lifecycles only
            did = eng.decode_tick() or did
        return did

    def _drive(self, reqs) -> None:
        """Run the split-pool loop until every request completes — the
        in-process test/bench harness, like the engine's own."""
        pending = list(reqs)
        while pending or self.active or self._failover \
                or any(e._deferred for e in self.engines) \
                or any(e._beam_jobs for e in self.engines):
            if pending and self.prefill.free_slots:
                k = min(len(pending), self.prefill.free_slots)
                for eng, group in self._place(pending[:k]).items():
                    eng.admit(group)
                pending = pending[k:]
            self._failover_tick()
            self._migrate()
            for eng in self.prefill.engines:
                eng._admit_deferred()
                eng.prefill_tick()
            for eng in self.decode.engines:
                eng._beam_maintenance()
                eng._admit_deferred()
                eng.prefill_tick()
                eng.decode_tick()

    # -- maintenance pass-throughs -----------------------------------------
    def warm_start(self) -> None:
        for eng in self.engines:
            warm = (getattr(eng, "warm_start", None)
                    or getattr(eng, "warmup", None))
            if warm is not None:
                warm()

    def warm_from_manifest(self, dirname: Optional[str] = None):
        warmed = None
        for eng in self.engines:
            warm = getattr(eng, "warm_from_manifest", None)
            if warm is None:
                continue
            n = warm(dirname) if dirname is not None else warm()
            if n is not None:
                warmed = (warmed or 0) + n
        return warmed

    def swap_params(self, source, *, strict: bool = True) -> dict:
        """One swap covers both pools — they share the scope in the
        ``build()`` shape, but per-engine swaps also invalidate each
        engine's prefix index, which must happen pool-wide."""
        stats: Dict[str, int] = {}
        for eng in self.engines:
            for k, v in eng.swap_params(source, strict=strict).items():
                stats[k] = stats.get(k, 0) + v
        return stats

    def cache_stats(self) -> dict:
        out: Dict[str, float] = {}
        for eng in self.engines:
            for k, v in eng.cache_stats().items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + v
        return out

    def flight_state(self) -> dict:
        return {
            "prefill": [e.flight_state() for e in self.prefill.engines],
            "decode": [e.flight_state() for e in self.decode.engines],
            "remote_inflight": sum(leg.inflight for leg in self._remote),
        }

    def metrics_snapshot(self) -> dict:
        return MetricsRegistry.merge(
            {f"{'p' if i < len(self.prefill.engines) else 'd'}{i}":
             e.metrics.snapshot() for i, e in enumerate(self.engines)})

    def close(self, drain: bool = False) -> None:
        for eng in self.engines:
            if hasattr(eng, "close"):
                try:
                    eng.close(drain=drain)
                except TypeError:
                    eng.close()
