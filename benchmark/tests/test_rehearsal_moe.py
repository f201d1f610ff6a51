"""The ``moe_lm`` family and its three readers on the CPU mesh: the serve
driver end to end at a toy OLMoE-shaped configuration (``tests/data``:
its own manifest ``BENCHMARK-moe.json``, so no file the rehearsal already
had changes), the readers on hand-built device events, and the real
configuration file against the catalog row's published keys. Every
number these runs print names ``platform: cpu``: none is a measurement.
Run by hand: ``pytest benchmark/tests`` (not part of tier-1)."""
import contextlib
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, trace_reduce
from benchmark.families import moe_lm
from benchmark.layer_metrics import (moe_load_imbalance, moe_roofline,
                                     moe_share_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-moe.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
#: the catalog row's ``config`` (model-configs guide, OLMoE-1B-7B-0125)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=2.0):
    import jax

    cell = harness.load_cell("tiny-serve-moe", manifest=MANIFEST,
                             data_dir=DATA)
    if traced:
        real = trace_reduce.load
        monkeypatch.setattr(trace_reduce, "load",
                            lambda path: real(RECORDED))
        monkeypatch.setattr(harness, "OUT_DIR",
                            os.path.join(DATA, ".bench_out"))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        line = harness.run_cell(cell, 2**31 + 5, seconds, traced,
                                jax.devices()[:1], time.monotonic())
    assert json.loads(json.dumps(line)) == line
    return cell, line, buf.getvalue()


def test_untraced_line_is_the_contract():
    cell, line, out = run(traced=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    notes = json.loads(out.splitlines()[0])["notes"]
    assert notes["logit_gap_positions"] > 0
    # bf16 weights and pages, as the configuration states
    assert cell.config["assumed"]["param_dtype"] == "bfloat16"


def test_engine_weights_are_seeded_stored_bf16_with_the_scaled_embedding():
    """``assumed.embedding_scale``: the seeded Xavier embedding (std
    sqrt(2 / (V + d))) times the scale, still in the stored dtype; the
    same seed gives the same weights."""
    cell = harness.load_cell("tiny-serve-moe", manifest=MANIFEST,
                             data_dir=DATA)
    embs = []
    for _ in range(2):
        eng, _ = moe_lm.build_engine(cell.config, cell.mix, 2**31 + 5)
        embs.append(eng.scope.get("tok_emb"))
    assert str(embs[0].dtype) == "bfloat16"
    got = np.asarray(embs[0], np.float32)
    assert np.array_equal(got, np.asarray(embs[1], np.float32))
    want = 1024 * (2.0 / (128 + 64)) ** 0.5
    assert got.std() == pytest.approx(want, rel=0.05)


def test_traced_line_reads_the_counter_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense model's: no grouped matmul in it, so
    # the two device-trace readers return nothing and are left out
    assert "moe_load_imbalance" in got
    assert not got & {"moe_share_pct", "moe_roofline"}
    assert 1.0 <= line["metrics"]["moe_load_imbalance"]["value"] <= 8.0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built events
# ---------------------------------------------------------------------------
CONFIG = {"num_experts": 64, "hidden_size": 2048, "intermediate_size": 1024,
          "vocab_size": 50304}
GATE = ("%ragged-dot-none.1 = f32[256,1024]{1,0:T(8,128)} custom-call("
        "s32[1]{0} %a, bf16[256,2048]{1,0} %rows, "
        "bf16[512,2048,1024]{2,1,0} %w), custom_call_target=\"tpu_custom_call\"")
SLICE = ("%dynamic-slice_bitcast_fusion.6 = bf16[64,2048,1024]{2,1,0} fusion("
         "bf16[8,64,2048,1024]{3,2,1,0} %w, s32[] %l), kind=kLoop")
DOWN = ("%ragged-dot-none.3 = f32[256,2048]{1,0} custom-call(bf16[256,1024]"
        "{1,0} %h, bf16[64,1024,2048]{2,1,0} %w), custom_call_target="
        "\"tpu_custom_call\"")
META = ("%ragged-dot-metadata = (s32[65]{0}, s32[64]{0}) custom-call("
        "s32[64]{0} %gs), custom_call_target=\"tpu_custom_call\"")
ROUTER = "%fusion.7 = f32[32,64]{1,0} fusion(f32[32,2048]{1,0} %b, f32[2048,64]{1,0} %r), kind=kOutput"
SORT = "%sort.2 = (s32[256]{0}, s32[256]{0}) sort(s32[256]{0} %e, s32[256]{0} %i), dimensions={0}"
VOCAB_SORT = "%sort.9 = f32[32,50304]{1,0} sort(f32[32,50304]{1,0} %z), dimensions={1}"
HEAD = "%fusion.1 = f32[32,50304]{1,0} fusion(bf16[32,2048]{1,0} %h, bf16[2048,50304]{1,0} %w), kind=kOutput"


class Cell:
    config = CONFIG
    family = moe_lm
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(events):
    ops, t = [], 0.0
    for text, seconds in events:
        ops.append((text, t, t + seconds))
        t += seconds
    return trace_reduce.Trace({0: ops}, {}, {}, [], (0.0, t * 2))


@pytest.mark.parametrize("text,part", [
    (GATE, "grouped_matmul"), (DOWN, "grouped_matmul"),
    (META, "grouped_matmul"), (SLICE, "grouped_matmul"), (ROUTER, "route"),
    (SORT, "route"),
    (VOCAB_SORT, None), (HEAD, None)])
def test_moe_op_tells_the_expert_layer_by_signature(text, part):
    assert moe_lm.moe_op(text, CONFIG) == part


def test_share_counts_the_expert_layer_over_busy_time(capsys):
    tr = _trace([(GATE, 1e-3), (DOWN, 1e-3), (ROUTER, 0.5e-3),
                 (HEAD, 2.5e-3)])
    assert moe_share_pct.read(tr, [], {}, Cell) == pytest.approx(50.0)
    assert "largest_other_ops_pct" in capsys.readouterr().out
    assert moe_share_pct.read(_trace([(HEAD, 1e-3)]), [], {}, Cell) is None


def test_roofline_is_least_time_over_device_time(capsys):
    """256 rows that touched 48 of the 64 experts: 201 MB of bf16
    weights = 248 us at 819 GB/s against 5.5 us of FLOPs: memory bound; a
    call that took twice that is at half its roofline. Without the
    engine's counter (the parent's program) the reader returns nothing."""
    cost = moe_lm.grouped_matmul_cost(CONFIG, 256, 2048, 1024, 48.0)
    assert cost["flops"] == 2 * 256 * 2048 * 1024
    least = cost["bytes"] / 819e9
    assert least == pytest.approx(248e-6, rel=0.02)
    counted = {"moe_touched_experts": 48 * 8 * 10, "moe_layer_calls": 80}
    tr = _trace([(GATE, 2 * least), (META, 1e-6), (ROUTER, 1e-4)])
    assert moe_roofline.read(tr, [], counted, Cell) == pytest.approx(
        50.0, rel=1e-3)
    assert '"bound": "memory"' in capsys.readouterr().out
    assert moe_roofline.read(tr, [], {}, Cell) is None
    assert moe_roofline.read(_trace([(HEAD, 1e-3)]), [], counted,
                             Cell) is None


def test_load_imbalance_is_hot_rows_times_experts_over_assignments():
    even = {"moe_assignments": 64 * 40, "moe_hot_expert_rows": 40}
    assert moe_load_imbalance.read(None, [], even, Cell) == 1.0
    assert moe_load_imbalance.read(None, [], {}, Cell) is None
    assert moe_load_imbalance.read(None, [], {"moe_assignments": 8},
                                   Cell) is None


@pytest.mark.parametrize("reader", [moe_share_pct, moe_roofline])
def test_a_trace_reader_leaves_its_metric_out_and_never_raises(reader,
                                                                capsys):
    """A traced run that fails refuses the PR; its numbers refuse
    nothing. A trace the reader cannot walk (here: no trace at all) is a
    metric left out of the line, with the reason on stderr."""
    counted = {"moe_touched_experts": 48, "moe_layer_calls": 1}
    assert reader.read(None, [], counted, Cell) is None
    assert "left out" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_depth_only():
    cell = harness.load_cell("olmoe-serve-chat")
    config = cell.config
    differ = {k for k, v in PUBLISHED.items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 8
    assert cell.family is moe_lm and cell.mix["kind"] == "serve"
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    assert {"moe_share_pct", "moe_roofline", "moe_load_imbalance"} <= {
        m["name"] for m in cell.per_layer}
    spec = moe_lm.spec_of(config)
    # the issue's arithmetic: 419,569,664 a layer, 3.563 B in all
    per_layer = (spec.n_params() - 2 * 50304 * 2048 - 2048) // 8
    assert per_layer == 419_569_664
    assert round(spec.n_params() / 1e9, 3) == 3.563
    e = cell.mix["engine"]
    assert e["max_len"] == 2048 and e["page_size"] * 32 == e["max_len"]
    longest = (cell.mix["prompt"]["shared_prefix"]["tokens"]
               + cell.mix["prompt"]["user"]["max"]
               + cell.mix["output"]["max"])
    assert longest == 1792 <= e["max_len"]
