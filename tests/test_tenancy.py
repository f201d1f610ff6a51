"""paddle_tpu.serving.tenancy: multi-tenant model registry + one /v1.

Pins the multi-tenancy contracts:

1. ROUTING — requests route on their ``model``/``tenant`` field into
   the named tenant's own queue and engines; absent means the default
   tenant; unknown ids are a typed ModelNotFoundError (HTTP 404 on the
   wire, mapped BACK to the typed error by HttpReplica), never a silent
   fall-through;
2. ISOLATION — per-tenant admission quotas (QueueFullError), per-tenant
   sampling defaults, per-tenant labeled gauges and SLO burn-rate
   planes on ONE shared registry;
3. TENANT-SCOPED ROLLS — ``swap_params(tenant=...)`` / a tenant-scoped
   ``online.Publisher`` roll one tenant to a new weight generation
   while the other tenant keeps serving token-exact with zero failed
   requests, and the ``weights_version{tenant=...}`` gauges move
   independently;
4. the 2-replica FLEET STORM — two models behind one fleet under
   concurrent mixed traffic: zero failed requests, zero cross-tenant
   interference in sampled tokens, zero steady-state fresh compiles.
"""
import json
import os
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.decoding import SamplingParams
from paddle_tpu.serving import (Fleet, GenerationEngine, HttpReplica,
                                LMSpec, QueueFullError)
from paddle_tpu.serving.errors import ModelNotFoundError
from paddle_tpu.serving.tenancy import (ModelRegistry, MultiTenantServer,
                                        Tenant)
from paddle_tpu.trace.slo import SLO

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, D, L, H, MAXLEN = 32, 16, 2, 2, 32
SEED_RANKER, SEED_CHAT = 7, 13

# startup-compile cache: weights initialized once per seed, shared as
# immutable arrays across fresh scopes (tier-1 budget)
_WEIGHTS = {}


def _lm_scope(seed):
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


def _engine(seed, **kw):
    # narrow bucket grids so warmup() covers every steady-state shape
    # with a handful of compiles (tier-1 budget)
    return GenerationEngine(_spec(), _lm_scope(seed), slots=4,
                            page_size=8, prompt_buckets=(8,),
                            prefill_batch_buckets=(1, 2, 4), **kw)


def _registry(slo=None):
    """Two resident models: 'ranker' (greedy default) and 'chat' (a
    seeded sampled default — deterministic, but different weights AND
    different decode behavior)."""
    reg = ModelRegistry()
    reg.register("ranker", [_engine(SEED_RANKER)], slo=slo)
    reg.register("chat", [_engine(SEED_CHAT)],
                 sampling=SamplingParams(temperature=0.7, top_k=8,
                                         seed=5), slo=slo)
    return reg


PROMPT = [1, 2, 3]


@pytest.fixture(scope="module")
def mts():
    srv = MultiTenantServer(_registry())
    srv.start()
    yield srv
    srv.stop()


# ---------------------------------------------------------------------------
# registry + tenant (unit)
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_registry_contract(self):
        eng = _engine(SEED_RANKER)
        reg = ModelRegistry()
        t = reg.register("a", [eng])
        assert reg.default is t and reg.resolve(None) is t
        assert "a" in reg and reg.names() == ("a",)
        with pytest.raises(ValueError):
            reg.register("a", [eng])
        with pytest.raises(ModelNotFoundError):
            reg.get("nope")
        # prebuilt tenant under a mismatched name is an error
        with pytest.raises(ValueError):
            reg.register("b", tenant=t)

    def test_tenant_namespace_and_sampling_defaults(self):
        eng = _engine(SEED_RANKER)
        sp = SamplingParams(temperature=0.5, top_k=4, seed=9)
        t = Tenant("canary", eng, sampling=sp, max_pending=2)
        # the tenant name became the engine's manifest/compile namespace
        assert eng.namespace == "canary"
        assert "canary" in eng.manifest_name
        assert eng.default_sampling is sp
        assert eng.temperature == 0.5 and eng.top_k == 4
        # quota: the tenant's own queue bound, typed
        t.batcher.submit({"prompt": PROMPT})
        t.batcher.submit({"prompt": PROMPT})
        with pytest.raises(QueueFullError):
            t.batcher.submit({"prompt": PROMPT})
        t.batcher.close()

    def test_fleetctl_renders_tenant_table(self):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import fleetctl
        finally:
            sys.path.pop(0)
        status = {
            "replicas": [], "pending": 0, "fleet": {},
            "tenants": [
                {"tenant": "ranker", "queue_depth": 2, "active": 1,
                 "pages_in_use": 6, "weights_version": 5.0,
                 "slo_max_burn": 0.5, "slo_alerting": False,
                 "paused": False},
                {"tenant": "chat", "queue_depth": 0, "active": 0,
                 "pages_in_use": 0, "weights_version": 0.0,
                 "slo_max_burn": None, "slo_alerting": False,
                 "paused": True},
            ],
        }
        table = fleetctl.render_status_table(status)
        assert "tenant" in table and "ranker" in table and "chat" in table
        assert "0.5x" in table            # SLO burn column
        assert "paused" in table          # chat's state column
        assert "5" in table               # weights version


# ---------------------------------------------------------------------------
# the multi-tenant server
# ---------------------------------------------------------------------------
class TestMultiTenantServer:
    def test_routing_defaults_and_typed_404(self, mts):
        a = mts.submit({"prompt": PROMPT}, model="ranker",
                       max_new_tokens=4).result(timeout=30)
        b = mts.submit({"prompt": PROMPT}, model="chat",
                       max_new_tokens=4).result(timeout=30)
        d = mts.submit({"prompt": PROMPT},
                       max_new_tokens=4).result(timeout=30)
        # default tenant is the first registered; tenants really serve
        # from their OWN weights/sampling (outputs differ)
        np.testing.assert_array_equal(d, a)
        assert not np.array_equal(a, b)
        # chat's sampled default carries a pinned seed: deterministic
        b2 = mts.submit({"prompt": PROMPT}, model="chat",
                        max_new_tokens=4).result(timeout=30)
        np.testing.assert_array_equal(b, b2)
        nf0 = mts.metrics.counter("model_not_found")
        with pytest.raises(ModelNotFoundError):
            mts.submit({"prompt": PROMPT}, model="nope")
        assert mts.metrics.counter("model_not_found") == nf0 + 1

    def test_tenant_status_rows_and_labeled_gauges(self, mts):
        rows = {r["tenant"]: r for r in mts.tenant_status()}
        assert set(rows) == {"ranker", "chat"}
        for row in rows.values():
            for key in ("queue_depth", "active", "pages_in_use",
                        "weights_version", "completed", "failed",
                        "paused", "max_pending"):
                assert key in row
        prom = mts.metrics_prometheus()
        assert 'tenant_queue_depth{tenant="ranker"}' in prom
        assert 'weights_version{tenant="chat"}' in prom
        snap = mts.metrics_snapshot()
        assert {r["tenant"] for r in snap["tenants"]} == {"ranker",
                                                          "chat"}

    def test_tenant_scoped_swap_other_tenant_serves_through(self, mts):
        before_r = mts.submit({"prompt": PROMPT}, model="ranker",
                              max_new_tokens=4).result(timeout=30)
        before_c = mts.submit({"prompt": PROMPT}, model="chat",
                              max_new_tokens=4).result(timeout=30)
        swaps0 = mts.metrics.counter("tenant_swaps")
        new = _lm_scope(99)
        mts.swap_params({k: np.asarray(new.get(k)) for k in new.keys()},
                        tenant="chat")
        after_c = mts.submit({"prompt": PROMPT}, model="chat",
                             max_new_tokens=4).result(timeout=30)
        after_r = mts.submit({"prompt": PROMPT}, model="ranker",
                             max_new_tokens=4).result(timeout=30)
        # chat rolled; ranker byte-identical (its engines, queue and
        # pages were never touched)
        assert not np.array_equal(after_c, before_c)
        np.testing.assert_array_equal(after_r, before_r)
        assert mts.metrics.counter("tenant_swaps") == swaps0 + 1
        rows = {r["tenant"]: r for r in mts.tenant_status()}
        assert rows["chat"]["weights_version"] > 0
        assert not rows["chat"]["paused"]  # resumed after the roll
        # roll back so later tests see the module fixture's weights
        old = _lm_scope(SEED_CHAT)
        mts.swap_params({k: np.asarray(old.get(k)) for k in old.keys()},
                        tenant="chat")

    def test_plain_server_answers_tenant_swap_typed(self):
        from paddle_tpu.serving import Server

        eng = _engine(SEED_RANKER)
        srv = Server([eng])
        with pytest.raises(ModelNotFoundError):
            srv.swap_params({}, tenant="whoever")

    def test_http_model_routing_404_and_replica_mapping(self, mts):
        """Satellite pin: unknown model/tenant is HTTP 404 on /v1/*,
        and HttpReplica maps the 404 BACK to ModelNotFoundError (which
        the fleet treats as give-up — every replica serves the same
        registry, retrying elsewhere only burns attempts)."""
        port = mts.serve_http(port=0)
        base = f"http://127.0.0.1:{port}"

        def post(body):
            req = urllib.request.Request(
                base + "/v1/generate", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        out = post({"prompt": PROMPT, "model": "chat",
                    "max_new_tokens": 4})
        want = mts.submit({"prompt": PROMPT}, model="chat",
                          max_new_tokens=4).result(timeout=30)
        np.testing.assert_array_equal(np.asarray(out["ids"]), want)
        # the "tenant" alias routes identically
        out2 = post({"prompt": PROMPT, "tenant": "chat",
                     "max_new_tokens": 4})
        np.testing.assert_array_equal(np.asarray(out2["ids"]), want)
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            post({"prompt": PROMPT, "model": "nope"})
        assert exc_info.value.code == 404
        detail = json.loads(exc_info.value.read())["error"]
        assert "nope" in detail and "ranker" in detail
        # the typed round-trip through a fleet leg
        rep = HttpReplica(base)
        att = rep.begin({"prompt": PROMPT}, {"model": "nope"}, 5_000.0)
        with pytest.raises(ModelNotFoundError):
            att.future.result(timeout=10)


# ---------------------------------------------------------------------------
# the 2-replica fleet: storm + tenant-scoped publisher roll
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tenant_fleet():
    slo = SLO(ttft_ms=10_000.0, availability=0.9)
    servers = [MultiTenantServer(_registry(slo=slo)) for _ in range(2)]
    for eng in _fleet_engines(servers):
        eng.warmup()  # settle every steady-state shape before counting
    fleet = Fleet(servers, hedge=False, default_timeout_ms=60_000.0)
    fleet.start()
    yield fleet, servers
    fleet.stop()


def _fleet_engines(servers):
    return [eng for srv in servers for eng in srv.engines]


@pytest.mark.slow  # tier-1 budget (PR 20): the 2-replica x 2-model
# fleet fixture alone costs ~50 s of warmup; the tenancy contracts
# (routing, quotas, labeled gauges, tenant-scoped swap) stay tier-1 via
# TestRegistry/TestMultiTenantServer above
class TestTenantFleet:
    def test_two_model_storm_no_interference_no_recompiles(
            self, tenant_fleet):
        """ACCEPTANCE PIN: two models on one 2-replica fleet under a
        concurrent mixed storm — zero failed requests, every sampled
        token stream identical to its quiet-fleet reference (zero
        cross-tenant interference), zero steady-state fresh compiles,
        and per-tenant SLO burn-rate gauges on /fleet/status."""
        fleet, servers = tenant_fleet
        rng = np.random.RandomState(0)
        jobs = []      # (model, prompt, meta)
        for i in range(12):
            model = ("ranker", "chat")[i % 2]
            prompt = rng.randint(0, VOCAB, (4 + i % 3,)).tolist()
            meta = {"model": model, "max_new_tokens": 4}
            if model == "chat":
                # explicit per-request seed: output is a pure function
                # of (request, seed) whichever replica serves it
                meta.update(temperature=0.7, top_k=8, seed=100 + i)
            jobs.append((prompt, meta))
        # quiet reference pass (also settles every compile)
        want = [fleet.submit({"prompt": p}, **dict(m)).result(timeout=60)
                for p, m in jobs]
        compiles0 = sum(e.cache_stats()["fresh_compiles"]
                        for e in _fleet_engines(servers))
        failed, results = [], {}
        lock = threading.Lock()

        def storm(ids):
            for i in ids:
                p, m = jobs[i]
                try:
                    got = fleet.submit({"prompt": p},
                                       **dict(m)).result(timeout=60)
                    with lock:
                        results.setdefault(i, []).append(got)
                except Exception as exc:  # noqa: BLE001 - the pin
                    failed.append(repr(exc))

        threads = [threading.Thread(target=storm,
                                    args=(range(k, 12, 3),))
                   for k in range(3)]
        for _ in range(2):          # two storm waves
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            threads = [threading.Thread(target=storm,
                                        args=(range(k, 12, 3),))
                       for k in range(3)]
        assert failed == []
        for i, (p, m) in enumerate(jobs):
            for got in results[i]:
                np.testing.assert_array_equal(got, want[i])
        # zero steady-state fresh compiles per tenant
        assert sum(e.cache_stats()["fresh_compiles"]
                   for e in _fleet_engines(servers)) == compiles0
        # per-tenant SLO plane on the fleet status
        status = fleet.status()
        rows = {r["tenant"]: r for r in status["tenants"]}
        assert set(rows) == {"ranker", "chat"}
        for row in rows.values():
            assert row["slo"] is not None
            assert not row["slo_alerting"]
            assert row["failed"] == 0
        prom = servers[0].metrics_prometheus()
        assert 'slo_burn_rate{objective="availability",tenant="ranker"' \
            in prom
        # unknown model through the fleet: typed give-up, no retry storm
        att0 = fleet.metrics.counter("attempts")
        with pytest.raises(ModelNotFoundError):
            fleet.submit({"prompt": PROMPT},
                         model="nope").result(timeout=30)
        assert fleet.metrics.counter("attempts") == att0 + 1

    def test_publisher_rolls_one_tenant_while_other_serves(
            self, tenant_fleet, tmp_path):
        """Satellite pin: a tenant-scoped Publisher rolls 'ranker' to a
        new checkpoint generation while 'chat' storms — chat stays
        token-exact throughout with ZERO failed requests, ranker's
        outputs move to the new generation, and the
        weights_version{tenant=...} gauges move independently."""
        from paddle_tpu import checkpoint as ckpt_mod
        from paddle_tpu.online import Publisher

        fleet, servers = tenant_fleet
        ck = str(tmp_path / "ranker-ck")
        ckpt_mod.save_checkpoint(ck, scope=_lm_scope(99), step=5)

        chat_meta = {"model": "chat", "max_new_tokens": 4,
                     "temperature": 0.7, "top_k": 8, "seed": 42}
        want_chat = fleet.submit({"prompt": PROMPT},
                                 **dict(chat_meta)).result(timeout=60)
        before_rank = fleet.submit(
            {"prompt": PROMPT}, model="ranker",
            max_new_tokens=4).result(timeout=60)

        pub = Publisher(fleet, ck, verify=False, pin=False,
                        tenant="ranker")
        assert fleet.tenant_publishers["ranker"] is pub
        assert fleet.publisher is None  # untenanted slot untouched

        stop, failed, served = threading.Event(), [], [0]

        def storm():
            while not stop.is_set():
                try:
                    got = fleet.submit(
                        {"prompt": PROMPT},
                        **dict(chat_meta)).result(timeout=60)
                    np.testing.assert_array_equal(got, want_chat)
                    served[0] += 1
                except Exception as exc:  # noqa: BLE001 - the pin
                    failed.append(repr(exc))

        threads = [threading.Thread(target=storm) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            step = pub.poll_once()
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert step == 5
        assert failed == []                    # chat: zero downtime
        assert served[0] > 0
        after_rank = fleet.submit(
            {"prompt": PROMPT}, model="ranker",
            max_new_tokens=4).result(timeout=60)
        assert not np.array_equal(after_rank, before_rank)
        # independent weights gauges: ranker at the published step,
        # chat untouched — on the fleet registry AND per-replica rows
        status = fleet.status()
        rows = {r["tenant"]: r for r in status["tenants"]}
        assert rows["ranker"]["weights_version"] == 5.0
        assert rows["chat"]["weights_version"] == 0.0
        assert rows["ranker"]["weights"]["tenant"] == "ranker"
        assert rows["ranker"]["weights"]["published_step"] == 5
        labeled = fleet.metrics.snapshot()["labeled"]
        assert labeled["weights_version"]['{tenant="ranker"}'] == 5.0
