"""The ``mla_moe_lm`` family and its three readers on the CPU mesh: the
serve driver end to end at a toy Mistral-Small-4-shaped configuration
(``tests/data``: its own manifest ``BENCHMARK-mla.json``, a twin of the
configuration and of the mix), the readers on hand-built counters and
device events, and the real configuration file against the catalog row's
published keys. Every number these runs print names ``platform: cpu``:
none is a measurement. Run by hand: ``pytest benchmark/tests`` (not part
of tier-1)."""
import contextlib
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, trace_reduce
from benchmark.families import mla_moe_lm
from benchmark.layer_metrics import (mla_decode_roofline, moe_held_roofline,
                                     moe_held_rows_pct, moe_load_imbalance)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-mla.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
CELL = "mistral4-serve-longdoc"
#: the numbers of the catalog row's ``config`` (model-configs guide,
#: Mistral-Small-4-119B-2603)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
    "kv_lora_rank": 256, "max_position_embeddings": 1048576,
    "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 36, "num_key_value_heads": 32, "q_lora_rank": 1024,
    "qk_head_dim": 128, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    "routed_scaling_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 131072}


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=2.0):
    import jax

    cell = harness.load_cell("tiny-serve-longdoc", manifest=MANIFEST,
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
    assert notes["logit_gap_max"] <= cell.mix["check"]["logit_gap_tol"]
    assert notes["prefix_hit_tokens"] > 0        # hits on latent pages


def test_traced_line_reads_the_counters_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense MHA model's: neither the latent kernel
    # nor a grouped matmul in it, so the device-trace readers are left out
    assert {"moe_held_rows_pct", "moe_load_imbalance"} <= got
    assert not got & {"mla_decode_roofline", "moe_held_roofline"}
    assert 0.0 < line["metrics"]["moe_held_rows_pct"]["value"] < 100.0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "mistral-small-4-119b.json")) as f:
        return json.load(f)


def _mla_call(name="paged_mla_decode.3"):
    return (f"%{name} = bf16[64,32,384]{{2,1,0}} custom-call(s32[1]{{0}} %l, "
            "s32[5120]{0} %t, s32[64]{0} %n, bf16[64,32,384]{2,1,0} %q, "
            "bf16[6,1536,256,384]{3,2,1,0:T(8,128)(2,1)} %k), "
            "custom_call_target=\"tpu_custom_call\"")


def _ragged(rows, cols, name="ragged-dot.5"):
    return (f"%{name} = f32[{rows},{cols}]{{1,0}} custom-call(bf16[{rows},"
            f"{4096 if cols == 2048 else 2048}]{{1,0}} %a, "
            f"bf16[192,{4096 if cols == 2048 else 2048},{cols}]{{2,1,0}} %w, "
            "s32[192]{0} %g), custom_call_target=\"ragged_dot\"")


class Cell:
    config = _config()
    family = mla_moe_lm
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(events):
    ops, t = [], 0.0
    for text, seconds in events:
        ops.append((text, t, t + seconds))
        t += seconds
    return trace_reduce.Trace({0: ops}, {}, {}, [], (0.0, t * 2))


def test_latent_roofline_counts_one_pool_read_once(capsys):
    """A tick that walks 1000 latent pages: 256 rows x 320 values x 2 B =
    163,840 B a page (the stored row's 64 columns of lane padding are not
    work), ONE pool: 200 us at 819 GB/s. Calls that took twice that read
    50%; a call of the K/V kernel is not one of these."""
    cost = mla_moe_lm.mla_decode_cost(Cell.config, 1000, 256, 2)
    assert cost["bytes"] == 1000 * 256 * 320 * 2
    least = cost["bytes"] / 819e9
    assert least == pytest.approx(200e-6, rel=0.01)
    counted = {"decode_steps": 10, "paged_attn_pages_read": 10000}
    tr = _trace([(_mla_call(), 2 * least),
                 (_mla_call("paged_mla_decode.9"), 2 * least),
                 (_mla_call("paged_attention_decode.1"), 1.0),
                 ("%fusion.1 = f32[64,32768]{1,0} fusion()", 1e-3)])
    assert mla_decode_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert '"calls": 2' in capsys.readouterr().out
    assert mla_moe_lm.mla_decode_call(_mla_call()) == {
        "page_size": 256, "itemsize": 2}
    # a missing counter, no call, no trace, a family without the kernel
    assert mla_decode_roofline.read(tr, [], {"decode_steps": 10},
                                    Cell) is None
    assert mla_decode_roofline.read(
        _trace([("%fusion.1 = f32[8]{0} fusion()", 1e-3)]), [], counted,
        Cell) is None
    assert mla_decode_roofline.read(None, [], counted, Cell) is None

    class Other(Cell):
        from benchmark.families import moe_lm as family

    assert mla_decode_roofline.read(tr, [], counted, Other) is None
    assert "left out" in capsys.readouterr().err


def test_held_roofline_counts_the_held_rows_never_the_static_ones(capsys):
    """A decode tick of 64 rows routes 256 assignments, a quarter of them
    to the 32 held experts, touching 20: gate / up [256, 2048] and down
    [256, 4096] are costed at 64 rows and 20 experts' weights (memory
    bound: 20 x 4096 x 2048 x 2 B = 336 MB = 410 us). Calls at twice
    their least time read 50%."""
    counted = {"moe_assignments": 4000, "moe_held_assignments": 1000,
               "moe_absent_assignments": 3000, "moe_touched_experts": 200,
               "moe_layer_calls": 10}
    c = mla_moe_lm.grouped_matmul_cost(Cell.config, 64.0, 4096, 2048, 20.0)
    assert c["bytes"] == 20 * 4096 * 2048 * 2 + 64 * (4096 * 2 + 2048 * 4)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    assert least == pytest.approx(410e-6, rel=0.01)
    c_down = mla_moe_lm.grouped_matmul_cost(Cell.config, 64.0, 2048, 4096,
                                            20.0)
    least_d = max(c_down["flops"] / 197e12, c_down["bytes"] / 819e9)
    tr = _trace([(_ragged(256, 2048), 2 * least),
                 (_ragged(256, 2048, "ragged-dot.6"), 2 * least),
                 (_ragged(256, 4096, "ragged-dot.7"), 2 * least_d),
                 ("%fusion.9 = f32[64,4096]{1,0} fusion(bf16[6,4096,2048]"
                  "{2,1,0} %s)", 1.0)])          # the shared expert: not one
    assert moe_held_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert '"held_share_pct": 25.0' in capsys.readouterr().out
    # the traced slice's own counters, where the driver took them, price
    # the slice's calls: the window's mean (here 40 experts a call, which
    # would read over 100%) is then not used; an empty slice falls back
    skewed = dict(counted, moe_touched_experts=400, slice=counted)
    assert moe_held_roofline.read(tr, [], skewed, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert '"counted_over": "slice"' in capsys.readouterr().out
    assert moe_held_roofline.read(
        tr, [], dict(counted, slice={"moe_layer_calls": 0}), Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert '"counted_over": "window"' in capsys.readouterr().out
    assert moe_held_roofline.read(tr, [], dict(counted, slice=None), Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert moe_held_rows_pct.read(None, [], counted, Cell) == 25.0
    assert moe_held_rows_pct.read(None, [], {}, Cell) is None
    assert moe_held_roofline.read(tr, [], {"moe_assignments": 5,
                                           "moe_layer_calls": 1}, Cell) is None
    assert moe_held_roofline.read(None, [], counted, Cell) is None
    assert "left out" in capsys.readouterr().err


def test_moe_op_tells_held_stacks_shared_expert_and_router_apart():
    cfg = Cell.config
    assert mla_moe_lm.moe_op(_ragged(256, 2048), cfg) == "grouped_matmul"
    assert mla_moe_lm.moe_op(
        "%fusion.2 = bf16[256,2048]{1,0} fusion(bf16[6,32,4096,2048]"
        "{3,2,1,0} %w)", cfg) == "grouped_matmul"
    assert mla_moe_lm.moe_op(
        "%fusion.9 = f32[64,4096]{1,0} fusion(bf16[6,2048,4096]{2,1,0} %s)",
        cfg) == "shared_expert"
    assert mla_moe_lm.moe_op(
        "%fusion.3 = f32[64,128]{1,0} fusion(f32[64,4096]{1,0} %h, "
        "bf16[4096,128]{1,0} %r)", cfg) == "route"
    assert mla_moe_lm.moe_op(
        "%sort.1 = f32[64,32768]{1,0} sort(f32[64,32768]{1,0} %z)",
        cfg) is None                      # the sampling plane's
    assert mla_moe_lm.moe_op(
        "%fusion.4 = f32[64,4096]{1,0} fusion(bf16[6,4096,1024]{2,1,0} %q)",
        cfg) is None
    # the unedited reader indexes the router's published width
    assert moe_load_imbalance.read(None, [], {
        "moe_assignments": 1280, "moe_hot_expert_rows": 30}, Cell) == 3.0


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_three():
    cell = harness.load_cell(CELL)
    config = cell.config
    differ = {k for k, v in PUBLISHED.items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 32, 32768)
    assert config["reduced_from"] == {"num_hidden_layers": 36,
                                      "n_routed_experts": 128,
                                      "vocab_size": 131072}
    assert config["router_outputs"] == config["num_experts"] == 128
    assert cell.family is mla_moe_lm and cell.mix["kind"] == "serve"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"mla_decode_roofline", "moe_held_roofline", "moe_held_rows_pct",
            "moe_share_pct", "moe_load_imbalance"} <= names
    assert "paged_attn_page_share_pct" in names     # the same page walk
    assert not names & {"moe_roofline", "paged_attn_roofline",
                        "mixed_attn_roofline", "kv_held_vs_uniform_pct"}
    # ``correct`` holds the served log-prob error's p90 (the family's
    # ``reference_logit_gaps``), at the family's own limit
    assert cell.mix["check"]["logit_gap_tol"] \
        == mla_moe_lm.CHECK_LOGPROB_TOL
    spec = mla_moe_lm.spec_of(config)
    assert spec.experts_held == (0, 32) and spec.num_experts == 128
    # the issue's arithmetic: 859,055,360 a layer, 5,422,771,712 in all
    per_layer = (spec.n_params() - 2 * 32768 * 4096 - 4096) // 6
    assert per_layer == 859_055_360
    assert spec.n_params() == 5_422_771_712
    e = cell.mix["engine"]
    longest = (cell.mix["prompt"]["shared_prefix"]["tokens"]
               + cell.mix["prompt"]["user"]["max"]
               + cell.mix["output"]["max"])
    assert longest <= e["max_len"] == 20480 == config["assumed"]["max_len"]
    assert (e["slots"], e["page_size"], e["prefill_chunk"]) == (64, 256, 256)


def test_the_checked_requests_include_three_beyond_the_shared_document():
    """``check.greedy_requests``: at least three of the six greedy requests
    the driver checks against the reference hold a context beyond 16384
    tokens (the mix's ``schedule_seed`` was chosen so)."""
    from benchmark import traffic

    cell = harness.load_cell(CELL)
    mix = cell.mix
    planned = traffic.schedule(
        mix, 1, mix["ramp_s"], 51,
        lambda rng, n: np.zeros(n, np.int64))
    due = [p for p in planned if p.due >= mix["ramp_s"]]
    checked = [p for p in due
               if p.sampling is None][:mix["check"]["greedy_requests"]]
    assert len(checked) == 6
    assert sum(p.prompt.size + p.max_new_tokens > 16384
               for p in checked) >= 3
