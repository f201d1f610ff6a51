"""The two readers of the paged decode-attention kernel on hand-built
events: the call is told by its NAME, a trace or a program without it
leaves the metrics out, and an olmoe-shaped call (1024 pages =
``intermediate_size``, a row of 2048 = ``hidden_size``) is no grouped
matmul for ``moe_lm.moe_op``. By hand (``pytest benchmark/tests``), CPU."""
import pytest

from benchmark import harness, trace_reduce
from benchmark.families import moe_lm, paged_attention
from benchmark.layer_metrics import (moe_roofline, moe_share_pct,
                                     paged_attn_page_share_pct,
                                     paged_attn_roofline)

OLMOE = {"num_experts": 64, "hidden_size": 2048, "intermediate_size": 1024,
         "vocab_size": 50304}
#: as the chip names them (PR 24: ``pallas_call(name=)`` is the HLO name)
CALL_OLMOE = (
    "%paged_attention_decode.7 = bf16[32,1,2048]{2,1,0:T(2,128)(2,1)S(1)} "
    "custom-call(s32[1]{0} %dynamic_slice.81, s32[1024]{0} %table, s32[32]{0} "
    "%lengths, bf16[32,1,2048]{2,1,0} %q, bf16[8,1024,64,2048]{3,2,1,0:"
    "T(8,128)(2,1)} %fusion.153, bf16[8,1024,64,2048]{3,2,1,0} %fusion.155), "
    "custom_call_target=\"tpu_custom_call\"")
CALL_GPT2M = (
    "%paged_attention_decode.4 = f32[32,1,1024]{2,1,0:T(1,128)S(1)} "
    "custom-call(s32[1]{0} %l, s32[512]{0} %table, s32[32]{0} %lengths, "
    "f32[32,1,1024]{2,1,0} %q, f32[24,400,64,1024]{3,2,1,0} %fusion.168, "
    "f32[24,400,64,1024]{3,2,1,0} %fusion.169), "
    "custom_call_target=\"tpu_custom_call\"")
#: another Mosaic call with the same operands under another name: a flash
#: kernel, a future kernel — not this one
OTHER_CALL = CALL_GPT2M.replace("%paged_attention_decode.4", "%flash_fwd.4")
GATHER = ("%fusion.187 = f32[512,64,1024]{2,1,0} fusion(f32[24,400,64,1024]"
          "{3,2,1,0} %pool, s32[32,16]{1,0} %table), kind=kLoop")
GATE = ("%ragged-dot-none.1 = f32[256,1024]{1,0:T(8,128)} custom-call("
        "s32[1]{0} %a, bf16[256,2048]{1,0} %rows, "
        "bf16[512,2048,1024]{2,1,0} %w), custom_call_target=\"tpu_custom_call\"")


class Cell:
    config = OLMOE
    family = moe_lm
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(events):
    ops, t = [], 0.0
    for text, seconds in events:
        ops.append((text, t, t + seconds))
        t += seconds
    return trace_reduce.Trace({0: ops}, {}, {}, [], (0.0, t * 2))


@pytest.mark.parametrize("text,geometry", [
    (CALL_OLMOE, {"page_size": 64, "kv_width": 2048, "itemsize": 2}),
    (CALL_GPT2M, {"page_size": 64, "kv_width": 1024, "itemsize": 4}),
    (OTHER_CALL, None), (GATHER, None), (GATE, None)])
def test_the_call_is_told_by_its_name_and_read_off_its_pool(text, geometry):
    assert paged_attention.decode_call(text) == geometry


@pytest.mark.parametrize("text", [CALL_OLMOE, CALL_GPT2M])
def test_the_kernel_is_no_part_of_the_expert_layer(text):
    """``moe_share_pct`` and ``moe_roofline`` keep their meaning: the new
    call is neither a grouped matmul nor routing, although its pool is
    [.., 1024, .., 2048] like an expert stack's [.., f, d]."""
    assert moe_lm.moe_op(text, OLMOE) is None
    counted = {"moe_touched_experts": 48 * 8, "moe_layer_calls": 8}
    tr = _trace([(text, 1e-3)])
    assert moe_share_pct.read(tr, [], counted, Cell) is None
    assert moe_roofline.read(tr, [], counted, Cell) is None
    both = _trace([(text, 1e-3), (GATE, 1e-3)])
    assert moe_share_pct.read(both, [], counted, Cell) == pytest.approx(50.0)


def test_cost_counts_the_k_and_v_pages_only():
    # 56 pages x 64 rows x 1024 floats x 4 B x 2 pools = 29.4 MB
    cost = paged_attention.decode_cost(56, 64, 1024, 4)
    assert cost == {"bytes": 2 * 56 * 64 * 1024 * 4}


def test_roofline_is_page_bytes_over_bandwidth_over_device_time(capsys):
    """170 pages a tick (the window's mean) of 262,144 B, K and V: 89 MB =
    108.8 us a call at 819 GB/s; calls that took twice that are at half
    their roofline, whatever else is in the trace."""
    counted = {"paged_attn_pages_read": 170 * 40, "decode_steps": 40}
    least = 2 * 170 * 64 * 2048 * 2 / 819e9
    assert least == pytest.approx(108.8e-6, rel=1e-3)
    tr = _trace([(CALL_OLMOE, 2 * least), (GATE, 1e-3), (OTHER_CALL, 1e-3),
                 (CALL_OLMOE, 2 * least)])
    assert paged_attn_roofline.read(tr, [], counted, Cell) == pytest.approx(
        50.0, rel=1e-6)
    note = capsys.readouterr().out
    assert '"calls": 2' in note and '"bound": "memory"' in note


@pytest.mark.parametrize("trace,counted", [
    (_trace([(GATHER, 1e-3), (OTHER_CALL, 1e-3)]),
     {"paged_attn_pages_read": 170, "decode_steps": 1}),    # the CPU path
    (_trace([(CALL_OLMOE, 1e-3)]), {"decode_steps": 40}),   # the parent
    (_trace([(CALL_OLMOE, 1e-3)]), {}),
    (None, {"paged_attn_pages_read": 170, "decode_steps": 1})])
def test_roofline_is_left_out_and_never_raises(trace, counted, capsys):
    assert paged_attn_roofline.read(trace, [], counted, Cell) is None
    assert "left out" in capsys.readouterr().err


def test_page_share_is_pages_read_over_table_pages():
    counted = {"paged_attn_pages_read": 56 * 10,
               "paged_attn_table_pages": 32 * 16 * 10}
    assert paged_attn_page_share_pct.read(None, [], counted,
                                          Cell) == pytest.approx(10.9375)
    assert paged_attn_page_share_pct.read(None, [], {}, Cell) is None
    assert paged_attn_page_share_pct.read(
        None, [], {"decode_steps": 3}, Cell) is None


@pytest.mark.parametrize("workload", ["gpt2m-serve-chat", "olmoe-serve-chat"])
def test_both_serve_cells_report_the_two_metrics(workload):
    cell = harness.load_cell(workload)
    by_name = {m["name"]: m for m in cell.per_layer}
    assert by_name["paged_attn_page_share_pct"]["layer"] == "serving scheduler"
    assert by_name["paged_attn_roofline"]["layer"] == "kernels"
    assert {by_name[n]["moves"] for n in (
        "paged_attn_page_share_pct", "paged_attn_roofline")} == {"tpot_p95_ms"}
