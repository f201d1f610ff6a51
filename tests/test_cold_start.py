"""Cold-start plane: signature manifests, AOT warmup replay, donating
executables restored from the persistent cache, /healthz warming, and
compile-source counters (ISSUE 8 — boot-to-first-token without fresh
compiles)."""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.core.executor as executor_mod
from paddle_tpu import layers
from paddle_tpu.core import manifest as manifest_mod
from paddle_tpu.core.manifest import ManifestError, SignatureManifest


def _square_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.mean(layers.fc(x, size=3))
    return main, startup, y


def _train_program():
    """fc + momentum step: donates parameter/accumulator state."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        t = layers.data("t", shape=[1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, t)))
        pt.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(
            loss, startup_program=startup)
    return main, startup, loss


@pytest.fixture
def fresh_cache_wiring(tmp_path):
    """A private --compilation_cache_dir for one test, with the module
    wiring reset on both sides."""
    d = str(tmp_path / "xla_cache")
    pt.set_flags({"compilation_cache_dir": d})
    executor_mod.reset_compilation_cache()
    yield d
    pt.set_flags({"compilation_cache_dir": ""})
    executor_mod.reset_compilation_cache()


# ---------------------------------------------------------------------------
# manifest schema + round trip
# ---------------------------------------------------------------------------
class TestManifest:
    def test_record_save_load_roundtrip(self, tmp_path):
        main, startup, y = _square_program()
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[y], scope=scope)
        exe.run(main, feed={"x": np.ones((4, 4), np.float32)},
                fetch_list=[y], scope=scope)
        assert len(exe.manifest) == 3  # startup + two main signatures
        path = exe.manifest.save(str(tmp_path))
        assert os.path.basename(path) == "warmup_manifest.json"
        loaded = manifest_mod.load(str(tmp_path))
        canon = lambda m: sorted(  # noqa: E731
            json.dumps(s, sort_keys=True) for s in m.signatures())
        assert canon(loaded) == canon(exe.manifest)

    def test_save_merges_existing(self, tmp_path):
        a, b = SignatureManifest(), SignatureManifest()
        a.record("p1", [("x", (2, 4), "float32")], ["y"])
        b.record("p1", [("x", (8, 4), "float32")], ["y"])
        a.save(str(tmp_path))
        b.save(str(tmp_path))  # merge=True folds a's signature back in
        assert len(manifest_mod.load(str(tmp_path))) == 2

    def test_unknown_version_rejected_with_location(self, tmp_path):
        path = tmp_path / "warmup_manifest.json"
        path.write_text(json.dumps({"schema": "paddle_tpu/warmup_manifest",
                                    "version": 99, "signatures": []}))
        with pytest.raises(ManifestError) as ei:
            manifest_mod.load(str(tmp_path))
        msg = str(ei.value)
        assert str(path) in msg and "99" in msg and "version" in msg
        # try_load must stay loud on version problems (only absence is None)
        with pytest.raises(ManifestError):
            manifest_mod.try_load(str(tmp_path))
        assert manifest_mod.try_load(str(tmp_path / "nope")) is None

    def test_malformed_signature_rejected(self, tmp_path):
        path = tmp_path / "warmup_manifest.json"
        path.write_text(json.dumps({
            "schema": "paddle_tpu/warmup_manifest", "version": 1,
            "signatures": [{"program": "p", "feeds": [["x"]],
                            "fetches": ["y"]}]}))
        with pytest.raises(ManifestError, match="signature #0"):
            manifest_mod.load(str(tmp_path))

    def test_replay_compiles_identical_signature_set(self, tmp_path):
        main, startup, y = _square_program()
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        for n in (2, 4):
            exe.run(main, feed={"x": np.ones((n, 4), np.float32)},
                    fetch_list=[y], scope=scope)
        exe.manifest.save(str(tmp_path))

        exe2 = pt.Executor(pt.CPUPlace())
        scope2 = pt.Scope()
        exe2.run(startup, scope=scope2)
        stats = manifest_mod.replay(
            exe2, [main], scope=scope2,
            manifest=manifest_mod.load(str(tmp_path)))
        # both main signatures compile; the startup digest is skipped
        assert stats["compiled"] == 2 and stats["skipped"] == 1
        misses0 = exe2.cache_stats()["misses"]
        for n in (2, 4):
            exe2.run(main, feed={"x": np.ones((n, 4), np.float32)},
                     fetch_list=[y], scope=scope2)
        assert exe2.cache_stats()["misses"] == misses0, \
            "post-replay traffic must be pure in-process cache hits"

    def test_replay_is_idempotent(self, tmp_path):
        main, startup, y = _square_program()
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[y], scope=scope)
        exe.manifest.save(str(tmp_path))
        manifest = manifest_mod.load(str(tmp_path))
        again = manifest_mod.replay(exe, [main], scope=scope,
                                    manifest=manifest)
        assert again["compiled"] == 0 and again["already"] == 1

    def test_program_digest_ignores_callsites(self):
        main1, _, _ = _square_program()
        main2, _, _ = _square_program()  # different build line, same shape
        d1 = manifest_mod.program_digest(main1)
        # names embed global uid counters, so only programs built from an
        # identical counter state digest equal — what matters here is that
        # the digest is stable for the SAME program and attr-private data
        # does not perturb it
        assert d1 == manifest_mod.program_digest(main1)
        assert isinstance(manifest_mod.program_digest(main2), str)


# ---------------------------------------------------------------------------
# compile-source counters + spans
# ---------------------------------------------------------------------------
class TestCompileSourceCounters:
    def test_cache_stats_classify_fresh_vs_hit(self):
        main, startup, y = _square_program()
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        feed = {"x": np.ones((2, 4), np.float32)}
        exe.run(main, feed=feed, fetch_list=[y], scope=scope)
        exe.run(main, feed=feed, fetch_list=[y], scope=scope)
        stats = exe.cache_stats()
        assert stats["fresh_compiles"] == 2  # startup + main
        assert stats["persistent_hits"] == 0
        assert stats["hits"] == 1 and stats["misses"] == 2

    def test_compile_span_carries_source(self):
        from paddle_tpu import trace

        main, startup, y = _square_program()
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        trace.enable(level=1)
        try:
            trace.get_tracer().clear()
            exe.run(startup, scope=scope)
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y], scope=scope)
            compile_spans = [s for s in trace.get_tracer().spans()
                             if s.name == "executor/compile"]
            assert compile_spans
            assert all(s.attrs.get("source") == "fresh"
                       for s in compile_spans)
        finally:
            trace.disable()

    def test_statset_counts_compile_sources(self):
        from paddle_tpu import profiler

        profiler.global_stat.reset()
        main, startup, y = _square_program()
        scope = pt.Scope()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[y], scope=scope)
        d = profiler.global_stat.as_dict(
            prefix="executor/compile_cache/fresh_compile")
        assert d and next(iter(d.values()))["calls"] == 2


# ---------------------------------------------------------------------------
# persistent cache: restored executables that donate state
# ---------------------------------------------------------------------------
class TestRestoredDonation:
    def test_restored_train_step_is_bit_exact(self, fresh_cache_wiring,
                                              tmp_path):
        """A training step whose DONATING executable is restored from
        --compilation_cache_dir must produce the identical (finite) loss
        trajectory. Older jaxlibs' deserialized CPU executables read
        freed donated buffers and went NaN here; the no-donation-twin
        guard that worked around it is gone (PR 21: the raw repro is
        clean on the installed jaxlib), so this test is the standing
        witness that the restored executable runs directly and is
        sound."""
        main, startup, loss = _train_program()
        rng = np.random.RandomState(0)
        batches = [(rng.randn(8, 4).astype(np.float32),
                    rng.randn(8, 1).astype(np.float32)) for _ in range(5)]

        def run_all(exe, scope):
            out = []
            for bx, bt in batches:
                (lo,) = exe.run(main, feed={"x": bx, "t": bt},
                                fetch_list=[loss], scope=scope)
                out.append(float(lo))
            return out

        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        ref = run_all(exe, scope)
        assert np.all(np.isfinite(ref))

        # fresh-process equivalent: drop the in-memory executables so the
        # next compile deserializes from the on-disk cache
        import jax

        jax.clear_caches()
        exe2 = pt.Executor(pt.CPUPlace())
        scope2 = pt.Scope()
        exe2.run(startup, scope=scope2)
        got = run_all(exe2, scope2)
        assert got == ref  # bit-exact, not merely close
        stats = exe2.cache_stats()
        assert stats["persistent_hits"] >= 1, stats  # restore path taken
        assert stats["fresh_compiles"] == 0, stats

    def test_save_resume_bit_exact_with_warm_cache(self, fresh_cache_wiring,
                                                   tmp_path):
        """test_master_checkpoint's save/resume scenario WITH the
        persistent cache active — the exact setup the old conftest note
        said NaN'd at step 3."""
        from paddle_tpu.checkpoint import load_checkpoint, save_checkpoint

        main, startup, loss = _train_program()
        rng = np.random.RandomState(0)
        batches = [(rng.randn(8, 4).astype(np.float32),
                    rng.randn(8, 1).astype(np.float32)) for _ in range(8)]
        ckdir = str(tmp_path / "ck")

        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        for bx, bt in batches[:4]:
            exe.run(main, feed={"x": bx, "t": bt}, fetch_list=[loss],
                    scope=scope)
        save_checkpoint(ckdir, scope=scope, step=4)
        ref = [float(exe.run(main, feed={"x": bx, "t": bt},
                             fetch_list=[loss], scope=scope)[0])
               for bx, bt in batches[4:]]

        import jax

        jax.clear_caches()  # resume in a fresh-process equivalent
        exe2 = pt.Executor(pt.CPUPlace())
        scope2 = pt.Scope()
        exe2.run(startup, scope=scope2)
        load_checkpoint(ckdir, scope=scope2)
        got = [float(exe2.run(main, feed={"x": bx, "t": bt},
                              fetch_list=[loss], scope=scope2)[0])
               for bx, bt in batches[4:]]
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    def test_restored_executable_still_donates(self, fresh_cache_wiring):
        """The restored step donates its state like the fresh one: the
        old parameter buffer is consumed (deleted client-side), not
        copied — nothing reroutes restores through a no-donation twin."""
        import jax

        main, startup, loss = _train_program()
        feed = {"x": np.ones((8, 4), np.float32),
                "t": np.ones((8, 1), np.float32)}

        def consumed(exe):
            scope = pt.Scope()
            exe.run(startup, scope=scope)
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            name = next(n for n in scope.keys() if n.endswith("_acc"))
            old = scope.get(name)
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            return old.is_deleted()

        fresh = pt.Executor(pt.CPUPlace())
        assert consumed(fresh)
        assert fresh.cache_stats()["persistent_hits"] == 0
        jax.clear_caches()
        restored = pt.Executor(pt.CPUPlace())
        assert consumed(restored)
        assert restored.cache_stats()["fresh_compiles"] == 0


# ---------------------------------------------------------------------------
# engines + server boot path
# ---------------------------------------------------------------------------
def _save_dense_model(tmp_path):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[6])
        y = layers.fc(x, size=4, act="softmax")
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    startup.random_seed = 11
    exe.run(startup, scope=scope)
    d = str(tmp_path / "dense")
    pt.io.save_inference_model(d, ["x"], [y], exe, main_program=main,
                               scope=scope)
    return d


class TestEngineWarmStart:
    def test_warmup_persists_manifest_and_replay_precompiles(self, tmp_path):
        from paddle_tpu.serving import InferenceEngine

        d = _save_dense_model(tmp_path)
        eng = InferenceEngine(d, batch_buckets=(1, 2))
        assert eng.warm_start() == 2  # no manifest yet -> execute warmup
        assert os.path.exists(os.path.join(d, "warmup_manifest.json"))

        eng2 = InferenceEngine(d, batch_buckets=(1, 2))
        assert eng2.warm_start() == 2  # manifest replay, no execution
        misses0 = eng2.cache_stats()["misses"]
        x = np.random.RandomState(0).rand(2, 6).astype(np.float32)
        eng2.run({"x": x})
        assert eng2.cache_stats()["misses"] == misses0
        assert eng2.metrics.counter("warmup_replayed") == 2

    def test_bad_manifest_degrades_to_warmup(self, tmp_path):
        from paddle_tpu.serving import InferenceEngine

        d = _save_dense_model(tmp_path)
        with open(os.path.join(d, "warmup_manifest.json"), "w") as f:
            json.dump({"version": 99}, f)
        eng = InferenceEngine(d, batch_buckets=(1, 2))
        with pytest.warns(RuntimeWarning, match="warmup manifest"):
            assert eng.warm_start() == 2  # fell back to execute warmup

    def test_server_warming_healthz(self, tmp_path):
        from paddle_tpu.serving import InferenceEngine, Server

        d = _save_dense_model(tmp_path)
        eng = InferenceEngine(d, batch_buckets=(1, 2))
        gate = threading.Event()

        def slow_warm():
            assert gate.wait(10)
            eng.warm_start()

        srv = Server(eng, batch_buckets=(1, 2), warmup=slow_warm)
        srv.start()
        port = srv.serve_http()
        try:
            assert srv.state == "warming"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["state"] == "warming" and body["ok"] is False
            gate.set()
            deadline = time.monotonic() + 30
            while srv.state != "ready" and time.monotonic() < deadline:
                time.sleep(0.02)
            assert srv.state == "ready"
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5)
            assert resp.status == 200
            # the boot gauge landed and the engine serves
            assert srv.metrics.snapshot()["gauges"]["warmup/boot_s"] >= 0
            x = np.random.RandomState(0).rand(6).astype(np.float32)
            srv.submit({"x": x}).result(timeout=30)
            # compile-source dimensions reach the Prometheus exposition
            prom = srv.metrics_prometheus()
            assert "fresh_compiles" in prom and "persistent_hits" in prom
        finally:
            gate.set()
            srv.stop()

    def test_server_default_warmup_uses_engine_warm_start(self, tmp_path):
        from paddle_tpu.serving import InferenceEngine, Server

        d = _save_dense_model(tmp_path)
        eng = InferenceEngine(d, batch_buckets=(1, 2))
        srv = Server(eng, batch_buckets=(1, 2), warmup=True)
        srv.start()
        try:
            deadline = time.monotonic() + 60
            while srv.state != "ready" and time.monotonic() < deadline:
                time.sleep(0.02)
            assert srv.state == "ready"
            assert eng.cache_stats()["entries"] == 2  # both buckets warm
        finally:
            srv.stop()

    def test_generation_engine_manifest_roundtrip(self, tmp_path):
        from paddle_tpu import models
        from paddle_tpu.serving import GenerationEngine

        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            prompt = layers.data("p_save", shape=[8], dtype="int64")
            out_ids = models.transformer_lm_generate(
                prompt, vocab_size=32, d_model=16, n_layers=2, num_heads=2,
                max_len=32, max_new_tokens=4)
        startup.random_seed = 7
        exe.run(startup, scope=scope)
        d = str(tmp_path / "lm")
        pt.io.save_inference_model(d, ["p_save"], [out_ids], exe,
                                   main_program=prog, scope=scope)

        eng = GenerationEngine.from_saved(d, slots=2, prompt_buckets=(8,),
                                          prefill_batch_buckets=(1, 2))
        eng.warm_start()
        prompts = np.random.RandomState(6).randint(
            0, 32, (2, 8)).astype("int64")
        ref = np.stack(eng.generate_all(list(prompts), max_new_tokens=4))

        eng2 = GenerationEngine.from_saved(d, slots=2, prompt_buckets=(8,),
                                           prefill_batch_buckets=(1, 2))
        # 2 prefill batch buckets + decode + the copy-on-write page copy
        assert eng2.warm_from_manifest() == 4
        misses0 = eng2.cache_stats()["misses"]
        got = np.stack(eng2.generate_all(list(prompts), max_new_tokens=4))
        np.testing.assert_array_equal(got, ref)
        assert eng2.cache_stats()["misses"] == misses0


class TestTrainerManifest:
    def _build_trainer(self):
        from paddle_tpu.core import program as prog_mod
        from paddle_tpu.core import scope as scope_mod

        # fresh-boot equivalent inside one process: reset the global
        # programs/scope AND the uid counter so rebuilt programs are
        # name-identical to the first build (what a real process restart
        # gives for free)
        prog_mod.Program._uid_counter = 0
        prog_mod._main_program = prog_mod.Program()
        prog_mod._startup_program = prog_mod.Program()
        scope_mod._global_scope = scope_mod.Scope()
        scope_mod._scope_stack[:] = [scope_mod._global_scope]
        x = layers.data("x", shape=[4])
        t = layers.data("t", shape=[1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, t)))
        return pt.trainer.SGD(
            cost=loss,
            optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.1),
            feed_list=[x, t], scope=pt.Scope())

    def test_sgd_resume_bit_exact_with_warm_cache(self, tmp_path,
                                                  fresh_cache_wiring):
        """THE acceptance pin: SGD.train resume with
        --compilation_cache_dir set (manifest replay + restored
        executables + donation guard) reaches bitwise-identical params
        vs an uninterrupted run."""
        from paddle_tpu.resilience import CheckpointConfig

        rng = np.random.RandomState(0)
        rows = [(rng.randn(4).astype(np.float32),
                 rng.randn(1).astype(np.float32)) for _ in range(8)]

        def reader():
            for i in range(0, 8, 4):
                yield rows[i:i + 4]

        quiet = lambda e: None  # noqa: E731

        def params_of(trainer):
            names = sorted(trainer.scope.keys())  # params, lr, RNG stream
            assert any(".w" in n for n in names), names
            return {n: np.asarray(trainer.scope.get(n)) for n in names}

        # uninterrupted 2-pass reference (no checkpointing at all)
        ref_t = self._build_trainer()
        ref_t.train(reader, num_passes=2, event_handler=quiet)
        ref = params_of(ref_t)

        # pass 0 with checkpointing, then a fresh-process-equivalent
        # resume (in-memory executables dropped -> disk restores) for
        # pass 1
        ckdir = str(tmp_path / "ck")

        def config():
            return CheckpointConfig(ckdir, every_n_steps=1,
                                    background=False,
                                    install_signal_handlers=False)

        t1 = self._build_trainer()
        t1.train(reader, num_passes=1, event_handler=quiet,
                 checkpoint=config())
        import jax

        jax.clear_caches()
        t2 = self._build_trainer()
        t2.train(reader, num_passes=2, event_handler=quiet,
                 checkpoint=config())
        got = params_of(t2)
        assert sorted(got) == sorted(ref)
        for name in ref:
            assert np.isfinite(got[name]).all(), name
            np.testing.assert_array_equal(got[name], ref[name],
                                          err_msg=name)
        # the resume actually took the cold-start path
        assert t2.exe.cache_stats()["persistent_hits"] >= 1

    def test_resume_replays_manifest(self, tmp_path):
        from paddle_tpu.resilience import CheckpointConfig

        ckdir = str(tmp_path / "ck")
        rng = np.random.RandomState(0)
        rows = [(rng.randn(4).astype(np.float32),
                 rng.randn(1).astype(np.float32)) for _ in range(8)]

        def reader():
            for i in range(0, 8, 4):
                yield rows[i:i + 4]

        def config():
            return CheckpointConfig(ckdir, every_n_steps=1,
                                    background=False,
                                    install_signal_handlers=False)

        quiet = lambda e: None  # noqa: E731
        t1 = self._build_trainer()
        t1.train(reader, num_passes=1, event_handler=quiet,
                 checkpoint=config())
        assert os.path.exists(os.path.join(ckdir, "warmup_manifest.json"))

        t2 = self._build_trainer()
        t2.train(reader, num_passes=2, event_handler=quiet,
                 checkpoint=config())
        assert getattr(t2, "_last_replay", None) is not None
        assert t2._last_replay["compiled"] >= 1, t2._last_replay


# ---------------------------------------------------------------------------
# zero fresh compiles across real process boots (slow: subprocesses)
# ---------------------------------------------------------------------------
_BOOT_CHILD = r'''
import json, os, sys
import numpy as np
import paddle_tpu as pt
from paddle_tpu.serving import InferenceEngine
model_dir, cache_dir = sys.argv[1:3]
pt.set_flags({"compilation_cache_dir": cache_dir})
eng = InferenceEngine(model_dir, batch_buckets=(1, 2))
warmed = eng.warm_start()
out, = eng.run({"x": np.ones((2, 6), np.float32)})
print(json.dumps({"warmed": warmed, "out": np.asarray(out).tolist(),
                  **eng.cache_stats()}))
'''


@pytest.mark.slow
def test_second_boot_zero_fresh_compiles(tmp_path):
    """Boot the same artifact in two fresh processes with manifest +
    persistent cache: the second boot must not compile anything fresh."""
    d = _save_dense_model(tmp_path)
    cache = str(tmp_path / "xla_cache")
    child = str(tmp_path / "boot_child.py")
    with open(child, "w") as f:
        f.write(_BOOT_CHILD)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))

    def boot():
        proc = subprocess.run([sys.executable, child, d, cache], env=env,
                              capture_output=True, text=True, timeout=300,
                              cwd=repo)
        assert proc.returncode == 0, proc.stderr[-800:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    first = boot()
    second = boot()
    assert first["fresh_compiles"] > 0
    assert second["fresh_compiles"] == 0, second
    assert second["persistent_hits"] >= second["warmed"]
    np.testing.assert_allclose(first["out"], second["out"], rtol=1e-6)
