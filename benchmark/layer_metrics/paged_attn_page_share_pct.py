"""Serving scheduler: the share of the block table a decode tick has to
walk: pages the rows hold (one per slot at least: a vacant slot reads the
scrap page) over slots x table width, summed over the window's ticks (the
engine's ``paged_attn_pages_read`` / ``paged_attn_table_pages``, after -
before). What the paged decode kernel reads against what a full-table
gather moves. Source: program counter."""


def read(trace, spans, counters, cell):
    table = counters.get("paged_attn_table_pages")
    if not table:
        return None
    return 100.0 * counters.get("paged_attn_pages_read", 0) / table
