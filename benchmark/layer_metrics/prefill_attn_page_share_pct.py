"""Serving scheduler: the share of the block table a prefill unit's
attention has to walk: the pages its rows' queries reach (a full layer from
page 0, a window layer from its window's page, to the page of the chunk's
last real key; a padding row none) over rows x table width, a layer of
each kind of page cache, summed over the window's units (the engine's
``prefill_attn_pages_read*`` / ``prefill_attn_table_pages*``, after -
before; an engine that holds its cache by kind counts each kind under its
suffix). What the chunk walk (``paged_attention_prefill``) reads against
what the full-table gather moved. None where the engine counts neither: its
prefill programs gather (a latent pool, a head narrower than the lanes, a
parent of PR 50). Source: program counter."""

READ, TABLE = "prefill_attn_pages_read", "prefill_attn_table_pages"


def read(trace, spans, counters, cell):
    table = sum(v for k, v in counters.items() if k.startswith(TABLE))
    if not table:
        return None
    return 100.0 * sum(v for k, v in counters.items()
                       if k.startswith(READ)) / table
