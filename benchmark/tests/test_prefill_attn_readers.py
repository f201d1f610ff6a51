"""The reader of ``prefill_attn_page_share_pct`` (PR 50): the engine's
``prefill_attn_pages_read*`` over ``prefill_attn_table_pages*``, and where
``BENCHMARK.json`` lists it. (A file of its own: the cases belong beside
``test_paged_attn_readers.py``'s, which a PR that adds a metric may not
edit.)"""
import pytest

from benchmark import harness
from benchmark.layer_metrics import prefill_attn_page_share_pct


@pytest.mark.parametrize("counted,want", [
    # one kind of cache: 22 units of one row under a table 96 wide
    ({"prefill_attn_pages_read": 22 * 12,
      "prefill_attn_table_pages": 22 * 96}, 12.5),
    # a cache held by kind: the kinds' pages over the kinds' tables (the
    # tick's counters beside them are not read)
    ({"prefill_attn_pages_read_global": 300,
      "prefill_attn_pages_read_window": 100,
      "prefill_attn_table_pages_global": 1600,
      "prefill_attn_table_pages_window": 1600,
      "paged_attn_table_pages": 7, "paged_attn_pages_read": 7}, 12.5),
    # an engine whose prefill programs gather counts neither
    ({}, None), ({"prefill_chunks": 3, "paged_attn_table_pages": 9}, None),
])
def test_prefill_page_share_is_pages_read_over_table_pages(counted, want):
    got = prefill_attn_page_share_pct.read(None, [], counted, None)
    assert got == (want if want is None else pytest.approx(want))


def test_prefill_page_share_is_listed_where_the_chunk_walk_engages():
    """K/V pages under heads of whole lane rows: not the float32 16 x 64
    cell, not the latent cells, no train cell."""
    listed = []
    for name in ("gpt2m-serve-chat", "olmoe-serve-chat",
                 "smallthinker-serve-mixed", "mistral4-serve-longdoc",
                 "ling3-serve-reason", "solar2-serve-agent",
                 "kexaone-serve-reason", "gpt2m-train"):
        for m in harness.load_cell(name).per_layer:
            if m["name"] == "prefill_attn_page_share_pct":
                assert (m["layer"], m["moves"], m["better"], m["source"]) == (
                    "serving scheduler", "tpot_p95_ms", "lower",
                    "program_counter")
                listed.append(name)
    assert listed == ["olmoe-serve-chat", "smallthinker-serve-mixed",
                      "solar2-serve-agent", "kexaone-serve-reason"]
