"""chip_smoke.py's phases at toy width on the CPU mesh: the SAME code the
chip gets (``chip_smoke.run``), so the first thing to meet a TPU is never
an untested line. Only ``main()`` holds the TPU requirement, and every
record a rehearsal prints names ``platform: cpu`` — it cannot be read as
a chip run. One module-level run feeds every test here."""
import contextlib
import io
import json

import pytest

import chip_smoke

TINY = dict(
    chip_smoke.FULL,
    vocab=64, d_model=32, n_layers=2, heads=2, max_len=64, batch=8, seq=64,
    sync_steps=6, async_steps=6, lr=1e-2, stream_mod=64, loss_margin=0.2,
    slots=4, prompt_buckets=(8, 16), prefill_batch_buckets=(1, 2), page=8,
    wave=((8, 4, None), (8, 4, None), (5, 3, None), (20, 6, None),
          (40, 8, None),
          (6, 6, dict(temperature=0.8, top_p=0.9, seed=1234)),
          (12, 5, dict(temperature=1.0, top_p=0.95, seed=7)),
          (21, 4, None)),
    oneshot=(8, 4), prefix_len=16, sharer=(5, 3),
    resnet=dict(batch=8, hw=32, classes=10, steps=2),
    multichip=dict(steps=2, loss_tol=2e-2, replica_requests=8,
                   replica_prompt=6, replica_new=4),
)


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    from paddle_tpu.ops import common

    out_dir = tmp_path_factory.mktemp("chip_smoke_out")
    buf = io.StringIO()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(chip_smoke, "OUT_DIR", str(out_dir))
        # run() pins AMP on, then off; later tests expect the unpinned
        # tri-state (--use_amp decides)
        mp.setattr(common, "_AMP", common._AMP)
        with contextlib.redirect_stdout(buf):
            summary = chip_smoke.run(TINY)
    records = [json.loads(line) for line in buf.getvalue().splitlines()]
    return {"summary": summary, "out_dir": out_dir,
            "by_phase": {r["phase"]: r for r in records}}


def test_every_record_names_the_device_it_ran_on(smoke):
    assert list(smoke["by_phase"]) == ["lm_train", "lm_serve",
                                       "resnet50_train", "multichip",
                                       "summary"]
    for rec in smoke["by_phase"].values():
        assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
        assert rec["device_count"] == 8 and rec["jax"]
        assert rec["seconds"] >= 0


def test_train_phase_counts(smoke):
    rec = smoke["by_phase"]["lm_train"]
    assert rec["steps"] == TINY["sync_steps"] + TINY["async_steps"]
    assert rec["last_loss"] < rec["first_loss"] - TINY["loss_margin"]
    # sync and async share ONE compiled step (+ the startup program)
    assert rec["fresh_compiles"] == 2 and rec["entries"] == 2
    # off the chip attention is the jnp reference: no Mosaic call — on a
    # TPU the phase REQUIRES the three flash kernels
    assert rec["mosaic_calls_in_compiled_step"] == {}
    # the stack is traced once for forward and backward (on a TPU the
    # phase also REQUIRES two loops and ONE flash_fwd call a layer; the
    # CPU backend adds loops of its own for scatters and sorts)
    assert rec["while_loops_in_compiled_step"] >= 2
    assert rec["paired_vjp_ops"] == 1


def test_serve_phase_counts(smoke):
    rec = smoke["by_phase"]["lm_serve"]
    assert rec["requests"] == len(TINY["wave"]) + 2
    assert rec["cache_misses_after_warmup"] == 0
    assert rec["prefix_hit_tokens"] >= TINY["prefix_len"]
    assert rec["seeded_alone_equals_in_wave"] is True
    greedy_new = sum(n for _, n, meta in TINY["wave"] if meta is None)
    assert rec["greedy_positions_scored"] == greedy_new
    # f32 on the CPU mesh: the engine and the training graph agree on
    # every argmax, and so does the one-shot op
    assert rec["logit_gap_max"] == 0.0
    assert rec["oneshot_equal_tokens"] == rec["oneshot_tokens"] > 0
    # the half-gigabyte artifact (at full width) is never left behind
    assert not (smoke["out_dir"] / "lm").exists()


def test_multichip_phase_counts(smoke):
    rec = smoke["by_phase"]["multichip"]
    first = smoke["by_phase"]["lm_train"]["first_loss"]
    for leg in ("dp4", "dp2xmp2"):
        assert abs(rec[leg]["losses"][0] - first) <= 2e-2
    # tensor parallelism cuts what each device holds; dp replicates it
    assert (rec["dp2xmp2"]["state_bytes_per_device"][0]
            < rec["dp4"]["state_bytes_per_device"][0])
    assert len(set(rec["dp4"]["state_bytes_per_device"])) == 1
    assert all(n > 0 for n in rec["replicas_served"])
    assert len(rec["replicas_served"]) == 4


def test_summary_totals(smoke):
    rec = smoke["summary"]
    assert rec["phase"] == "summary"
    assert rec["fresh_compiles"] == rec["entries"] > 0
    assert rec["persistent_hits"] == 0  # CPU, env unset: in-memory only
    assert rec["compilation_cache_dir"] is None
    assert list(rec)[-1] == "claim" and rec["claim"] is None


def test_main_refuses_to_start_without_a_tpu(monkeypatch, capsys):
    def boom(cfg):
        raise AssertionError("chip_smoke ran a phase on a CPU")

    monkeypatch.setattr(chip_smoke, "run", boom)
    assert chip_smoke.main() == 1
    out = capsys.readouterr()
    assert out.out == ""  # no result line a driver could parse
    assert "needs a TPU" in out.err


def test_last_line_is_the_result_the_driver_parses(monkeypatch, capsys):
    """On a TPU the last stdout line is ``{"ok", "device": {"platform",
    "kind", "count"}}`` and nothing else — the driver refuses an extra
    key (it refused ``"claim"`` there; that lives on the summary line)."""
    import jax

    class FakeTPU:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTPU()])
    monkeypatch.setattr(chip_smoke, "run",
                        lambda cfg: print('{"phase": "summary"}'))
    assert chip_smoke.main() == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_a_failed_check_is_not_carried_past(monkeypatch, tmp_path):
    """The first failing check raises out of ``run`` — later phases do
    not execute and no summary is printed."""
    from paddle_tpu.ops import common

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(common, "_AMP", common._AMP)
    ran = []
    monkeypatch.setattr(chip_smoke, "phase_resnet",
                        lambda cfg: ran.append("resnet"))
    impossible = dict(TINY, loss_margin=1e9, sync_steps=1, async_steps=1)
    with pytest.raises(AssertionError, match="loss did not fall"):
        chip_smoke.run(impossible)
    assert ran == []
