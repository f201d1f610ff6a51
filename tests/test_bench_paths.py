"""The bench's measurement paths must be runnable — they normally execute
only on the real chip, so a build/measure crash would otherwise surface for
the first time on bench day. Toy shapes, CPU."""
import sys

import numpy as np
import pytest


def _bench():
    import bench
    return bench


def test_transformer_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    tok_s, flops_s = _bench().bench_transformer_step(
        jax, pt, layers, models, bs=2, T=128, vocab=64, d=32, L=1, H=2,
        steps=2)
    assert tok_s > 0 and flops_s > 0


def test_transformer_bench_fused_head_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    tok_s, flops_s = _bench().bench_transformer_step(
        jax, pt, layers, models, bs=2, T=128, vocab=64, d=32, L=1, H=2,
        steps=2, fused_head=True)
    assert tok_s > 0 and flops_s > 0


def test_lstm_varlen_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers

    res = _bench().bench_lstm_varlen(jax, pt, layers, batch=4, hidden=8,
                                     vocab=50, mean_len=6, cap=12, steps=2)
    assert res["tokens_per_sec"] > 0
    assert 0.0 <= res["padded_flop_waste"] < 1.0
    assert res["max_len"] <= 12


@pytest.mark.slow  # tier-1 budget: heaviest bench path
def test_inference_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    res = _bench().bench_inference(jax, pt, layers, models, "resnet50",
                                   batch=2, hw=32, steps=2)
    assert res["img_per_sec"] > 0 and res["ms_per_batch"] > 0


def test_transformer_flop_model_is_sane():
    b = _bench()
    # 2 FLOPs/MAC, fwd x3: dense part alone for one layer
    fl = b.transformer_train_flops(1, 128, 64, 1, 32, d_ff=256)
    dense = 2 * 128 * 64 * (4 * 64) + 2 * 128 * 64 * (2 * 256)
    attn = 2 * 128 * 128 * 64
    head = 2 * 128 * 64 * 32
    assert fl == 3 * (dense + attn + head)


def test_decode_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    res = _bench().bench_decode(jax, pt, layers, models, bs=2, Tp=8, N=4,
                                vocab=32, d=16, L=1, H=2, steps=1)
    assert res["tokens_per_sec"] > 0


def test_bench_requires_a_tpu_and_measures_nothing_without(monkeypatch,
                                                          capsys):
    """``python bench.py`` off-chip: non-zero exit, no record on stdout,
    and no measurement function is ever entered."""
    b = _bench()

    def boom(*a, **kw):
        raise AssertionError("bench measured something on a CPU")

    monkeypatch.setattr(b, "run_bench", boom)
    assert b.main() == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err and "'cpu'" in out.err


def test_row_that_raises_is_recorded_and_fails_the_run(monkeypatch, capsys):
    b = _bench()
    rows = b.run_rows([("good", lambda x: x + 1, (1,), {}),
                       ("bad", lambda: 1 / 0, (), {}),
                       ("after", lambda: "ran", (), {})])
    assert rows["good"] == {"result": 2}
    assert "ZeroDivisionError" in rows["bad"]["error"]
    assert rows["after"] == {"result": "ran"}  # the sweep goes on
    assert "ZeroDivisionError" in capsys.readouterr().err  # loudly

    # main(): the record still prints, the exit code is non-zero
    class _Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    rows["info"] = {"result": {"platform": "tpu",
                               "device_kind": "TPU v5 lite",
                               "device_count": 1, "batch": 256,
                               "image_size": 224}}
    monkeypatch.setattr(b, "run_bench", lambda jax_, dev: rows)
    assert b.main() == 1
    rec = __import__("json").loads(capsys.readouterr().out)
    assert rec["value"] is None  # no headline: never a plausible 0.0
    assert "bad" in rec["extra"]["failed"]


def test_assemble_names_the_device_and_needs_its_peak():
    b = _bench()
    rows = {
        "info": {"result": {"platform": "tpu", "device_kind": "TPU v5 lite",
                            "device_count": 1, "batch": 256,
                            "image_size": 224}},
        "resnet": {"result": {"img_per_sec": 1000.0}},
        "transformer_wide": {"result": [39100.0, 110e12]},
        "lstm": {"error": "raised"},
    }
    out = b.assemble(rows)
    assert out["value"] == 1000.0
    assert out["extra"]["platform"] == "tpu"
    assert out["extra"]["device_kind"] == "TPU v5 lite"
    assert out["extra"]["mfu"] == pytest.approx(
        1000.0 * b.RESNET50_TRAIN_FLOPS_224 / 197e12, rel=1e-3)
    assert out["extra"]["transformer_wide_mfu"] is not None
    assert out["extra"]["transformer_lm_tokens_per_sec"] is None
    assert out["extra"]["failed"] == {"lstm": "raised"}
    # a device the one peaks table does not know is an error, not a
    # record without an MFU
    rows["info"]["result"]["device_kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError, match="no published peak"):
        b.assemble(rows)


@pytest.mark.slow  # tier-1 budget: overhead A/B is a sweep row, not a correctness gate
def test_trace_overhead_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models, trace

    res = _bench().bench_trace_overhead(jax, pt, layers, models,
                                        batch=2, hw=32, steps=3, warmup=1)
    assert res["untraced_ms_per_batch"] > 0
    assert res["traced_ms_per_batch"] > 0
    assert res["spans_recorded"] > 0
    # measurement must leave the global tracer off for later tests
    assert not trace.enabled()


@pytest.mark.slow  # tier-1 budget (PR 20): like the other bench-path
# sweeps in this file, the obs-overhead A/B rides the slow tier
def test_obs_overhead_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models, trace

    res = _bench().bench_obs_overhead(jax, pt, layers, models, d=16,
                                      L=2, H=2, tmax=64, slots=4,
                                      page_size=8, n_requests=6,
                                      max_new=4, rounds=1)
    assert res["baseline_ms_per_token"] > 0
    assert res["full_plane_ms_per_token"] > 0
    assert res["spans_recorded"] > 0
    assert res["new_tokens"] == 6 * 4
    assert res["ttft_p50_ms"] > 0 and res["tpot_p50_ms"] > 0
    assert res["flight_bundle_spans"] > 0
    # measurement must leave the global planes restored for later tests
    assert not trace.enabled()
    from paddle_tpu.trace import get_recorder

    assert get_recorder().enabled


def test_train_pipeline_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers

    res = _bench().bench_train_pipeline(jax, pt, layers, batch=8, dim=16,
                                        depth=3, steps=4, warmup=1,
                                        rounds=1)
    assert res["sync_ms_per_step"] > 0
    assert res["async_ms_per_step"] > 0
    assert res["device_ms_per_step"] > 0
    assert res["async_depth"] == 3
    # host gap is a subtraction; both signs are legal on a noisy CPU
    # smoke run, but the keys must exist for the PERF.md record
    assert "host_gap_sync_ms" in res and "host_gap_async_ms" in res


def test_goodput_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers

    res = _bench().bench_goodput(jax, pt, layers, batch=8, dim=16,
                                 depth=3, steps=4, warmup=1, rounds=1)
    assert res["off_ms_per_step"] > 0
    assert res["on_ms_per_step"] > 0
    assert res["async_depth"] == 3
    # overhead is a subtraction; both signs are legal on a noisy CPU
    # smoke run, but the record keys must exist for PERF.md
    assert "overhead_pct" in res
    # the instrumented run actually attributed time somewhere
    assert res["buckets_attributed"] >= 1
    assert 0.0 <= (res["goodput_fraction"] or 0.0) <= 1.0


@pytest.mark.slow  # tier-1 budget (PR 12): 31s — two resnet50 compiles;
# the op-cut + pass-stats contracts are pinned tier-1 in
# test_transpiler.py, so only the bench-path crash guard rides here
def test_transpiler_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    res = _bench().bench_transpiler(jax, pt, layers, models, "resnet50",
                                    batch=2, hw=32, steps=2)
    assert res["transpiled_ops"] < res["raw_ops"]
    assert res["transpiled_ms_per_batch"] > 0
    assert res["pass_stats"], "per-pass stats must be recorded"


def test_checkpoint_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers

    res = _bench().bench_checkpoint(jax, pt, layers, batch=8, dim=32,
                                    steps=6, every=2, rounds=1)
    assert res["base_ms_per_step"] > 0
    assert res["sync_ms_per_step"] > 0
    assert res["background_ms_per_step"] > 0
    assert res["ckpt_bytes"] > 0
    # the stall plane (the resilience acceptance metric) must exist, and
    # background stall can never exceed the full synchronous save path
    # by more than noise on a 1-core smoke box
    assert "background_stall_pct" in res and "sync_stall_pct" in res


def test_sharding_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers

    # this test process already owns the 8-device virtual mesh, so the
    # bench measures inline (no child spawn)
    res = _bench().bench_sharding(jax, pt, layers, batch=16, dim=64,
                                  steps=2, rounds=1, warmup=1)
    assert res["single"]["ms_per_step"] > 0
    assert "dp8" in res and "dp4xmp2" in res
    # the tp axis halves per-device parameter bytes; dp leaves them full
    assert (res["dp4xmp2"]["per_device_param_bytes"]
            < 0.7 * res["single"]["per_device_param_bytes"])
    assert res["dp8"]["collective_bytes_est"] > 0
    # losses across all three legs agree (the correctness witness)
    assert res["loss_parity_max_abs"] < 1e-5
    # plan-digest cache key: the timed rounds never recompile
    for leg in ("single", "dp8", "dp4xmp2"):
        assert res[leg]["steady_state_fresh_compiles"] == 0


@pytest.mark.slow  # tier-1 budget: the V=1e6 legs are heavy on 1 core
def test_online_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers

    res = _bench().bench_online(jax, pt, layers, vocab=20_000, batch=16,
                                steps=2, warmup=1, storm_s=0.05)
    assert res["dense_step_ms"] > 0 and res["sparse_step_ms"] > 0
    # the sparse step's static peak excludes the [V, D] gradient plane
    assert res["sparse_peak_mb"] < res["dense_peak_mb"]
    assert res["publish_generation"] == 1
    assert res["storm_failed"] == 0


@pytest.mark.slow
def test_decode_platform_bench_path_runs():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    res = _bench().bench_decode_platform(
        jax, pt, layers, models, tmax=64, page_size=8, slots=4,
        prompt_len=12, max_new=6, n_requests=8, d=16, L=2, H=2,
        vocab=32, beam_k=3, beam_new=6)
    # mixed sampling rides the SAME executables as greedy
    assert res["mixed_sampling"]["fresh_compiles"] == 0
    assert res["greedy"]["ms_per_token"] > 0
    # beam forks share prefix pages: under the dense K-copy baseline
    assert res["beam"]["pages_hwm"] < res["beam"]["dense_copy_pages"]
    assert res["beam"]["forks"] >= res["beam"]["beam_size"] - 1
