"""Bring-up contracts (PR 21): where the compile cache goes, that a place
is a real device, and that importing the package holds no chip."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.core.executor as executor_mod
from paddle_tpu import layers, models, xla_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# compile cache: one resolution, placeable from outside
# ---------------------------------------------------------------------------
@pytest.fixture
def cache_wiring(monkeypatch):
    """Unwired cache on both sides, plus a record of every
    ``jax.config.update`` the code under test makes."""
    monkeypatch.delenv(xla_env.CACHE_DIR_ENV, raising=False)
    pt.set_flags({"compilation_cache_dir": ""})
    executor_mod.reset_compilation_cache()
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append((name, value))
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    yield updates
    monkeypatch.undo()
    pt.set_flags({"compilation_cache_dir": ""})
    executor_mod.reset_compilation_cache()


def _run_tiny_step():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.mean(layers.fc(x, size=3))
    scope, exe = pt.Scope(), pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[y],
            scope=scope)
    return exe


def test_env_var_wins_and_nothing_overrides_it(cache_wiring, tmp_path,
                                               monkeypatch):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv(xla_env.CACHE_DIR_ENV, env_dir)
    pt.set_flags({"compilation_cache_dir": str(tmp_path / "from_flag")})
    assert xla_env.compilation_cache_dir() == env_dir
    assert xla_env.compilation_cache_dir(platform="tpu") == env_dir
    _run_tiny_step()
    executor_mod.reset_compilation_cache()
    # the thresholds drop to 0 on this route too; the DIRECTORY is jax's
    # own reading of the variable — never set, never cleared, from code
    names = [name for name, _ in cache_wiring]
    assert "jax_compilation_cache_dir" not in names
    assert "jax_persistent_cache_min_compile_time_secs" in names
    assert "jax_persistent_cache_min_entry_size_bytes" in names
    assert not (tmp_path / "from_flag").exists()


def test_unset_on_cpu_stays_in_memory(cache_wiring):
    assert xla_env.compilation_cache_dir() is None
    exe = _run_tiny_step()
    assert not executor_mod._pc_enabled()
    assert cache_wiring == []  # no cache config touched at all
    assert exe.cache_stats()["persistent_hits"] == 0


def test_tpu_default_is_one_fixed_path_in_the_checkout(cache_wiring):
    got = xla_env.compilation_cache_dir(platform="tpu")
    assert got == os.path.join(REPO, ".jax_cache") == xla_env.REPO_CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_flag_places_the_cache_and_reset_unwires_it(cache_wiring, tmp_path):
    d = str(tmp_path / "from_flag")
    pt.set_flags({"compilation_cache_dir": d})
    assert xla_env.compilation_cache_dir() == d
    _run_tiny_step()
    assert ("jax_compilation_cache_dir", d) in cache_wiring
    assert os.listdir(d)  # even a sub-second compile is persisted
    executor_mod.reset_compilation_cache()
    assert ("jax_compilation_cache_dir", None) in cache_wiring
    assert not executor_mod._pc_enabled()


# ---------------------------------------------------------------------------
# a place is a device
# ---------------------------------------------------------------------------
def _momentum_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        t = layers.data("t", shape=[1])
        loss = layers.mean(layers.square(
            layers.elementwise_sub(layers.fc(x, size=1), t)))
        pt.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(
            loss, startup_program=startup)
    feed = {"x": np.ones((8, 4), np.float32),
            "t": np.ones((8, 1), np.float32)}
    return main, startup, loss, feed


def test_executor_place_puts_state_and_outputs_on_its_device(cpu_mesh8):
    main, startup, loss, feed = _momentum_program()
    dev = jax.devices()[3]
    exe, scope = pt.Executor(pt.TPUPlace(3)), pt.Scope()
    exe.run(startup, scope=scope)
    for _ in range(3):
        out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                       return_numpy=False)
    assert out.devices() == {dev}
    arrays = [scope.get(n) for n in scope.keys()]
    assert arrays and all(a.devices() == {dev} for a in arrays)
    # every call ran its AOT executable: 2 compiles, then pure hits
    stats = exe.cache_stats()
    assert stats["fresh_compiles"] == 2 and stats["hits"] == 2

    # state that lives on another device is an error naming both — not
    # a per-call copy across the interconnect, not a jit re-dispatch
    with pytest.raises(ValueError, match="lives on.*computes on"):
        pt.Executor(pt.TPUPlace(0)).run(main, feed=feed, fetch_list=[loss],
                                        scope=scope)


def test_generation_engine_place_owns_weights_pools_and_outputs(cpu_mesh8):
    from paddle_tpu.serving import GenerationEngine, LMSpec

    spec = LMSpec(vocab_size=32, d_model=16, n_layers=2, num_heads=2,
                  max_len=32)
    gen, gen_startup = pt.Program(), pt.Program()
    gen_startup.random_seed = 5
    with pt.program_guard(gen, gen_startup):
        prompt = layers.data("prompt", shape=[4], dtype="int64")
        models.transformer_lm_generate(
            prompt, vocab_size=32, d_model=16, n_layers=2, num_heads=2,
            max_len=32, max_new_tokens=2)
    trained = pt.Scope()  # weights made on device 0, as a trainer would
    pt.Executor(pt.TPUPlace(0)).run(gen_startup, scope=trained)

    def engine(i):
        scope = pt.Scope()
        for name in trained.keys():
            scope.set(name, trained.get(name))
        return GenerationEngine(spec, scope, slots=2, page_size=8,
                                prompt_buckets=(8,),
                                prefill_batch_buckets=(1,),
                                place=pt.TPUPlace(i))

    prompts = [np.arange(5) % 7, np.arange(3) + 2]
    outs = {}
    for i in (0, 5):
        eng = engine(i)
        outs[i] = eng.generate_all(prompts, max_new_tokens=4)
        dev = {jax.devices()[i]}
        for name in ("tok_emb", "lm_stack.stack_qkv_w",
                     "serving.paged_cache_k", "serving.paged_cache_v"):
            assert eng.scope.get(name).devices() == dev, (i, name)
        stats = eng.cache_stats()
        assert stats["fresh_compiles"] == stats["misses"] > 0
    for a, b in zip(outs[0], outs[5]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# importing the package holds no chip
# ---------------------------------------------------------------------------
def test_importing_the_package_initialises_no_backend():
    """A parent that imports paddle_tpu (to build programs, parse flags,
    spawn a worker) must not claim the chip: a TPU belongs to one
    process. Checked in a fresh interpreter — this one already runs
    JAX."""
    code = ("import paddle_tpu, paddle_tpu.serving, paddle_tpu.trainer, "
            "paddle_tpu.models, paddle_tpu.xla_env as x; "
            "import sys; sys.exit(3 if x.backend_initialized() else 0)")
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert xla_env.backend_initialized()  # and the probe can say yes
