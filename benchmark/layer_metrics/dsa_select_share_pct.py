"""Serving scheduler: the share of the cached rows in reach that the sparse
latent layers READ: latent rows the attention gathered for the window's
queries (picked groups x the group's tokens, the tail's masked rows
included: the engine's ``dsa_rows_attended``) over what a page walk without
selection would read for the same queries (``dsa_rows_in_reach``), after -
before over the window, prefill chunks and ticks alike. 12.5 at 16k tokens
of context under a pick of 2048; 100 where nothing is selected: lower is
sparser. Beside it, on one line of its own: the share of the window's queries
whose context held no more groups than a query may pick, so that nothing was
selected (``dsa_dense_queries`` / ``dsa_queries``), and the pooled keys a
query scored (``dsa_groups_scored`` / ``dsa_queries``). Source: program
counter."""
import json


def read(trace, spans, counters, cell):
    reach = counters.get("dsa_rows_in_reach")
    if not reach:
        return None
    queries = counters.get("dsa_queries")
    if queries:
        print(json.dumps({"dsa_select_share_pct": {
            "queries": queries, "dense_queries_pct":
                100.0 * counters.get("dsa_dense_queries", 0) / queries,
            "groups_scored_a_query":
                counters.get("dsa_groups_scored", 0) / queries}}), flush=True)
    return 100.0 * counters.get("dsa_rows_attended", 0) / reach
