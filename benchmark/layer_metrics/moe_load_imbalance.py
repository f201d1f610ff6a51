"""Serving scheduler: how unevenly the window's calls loaded the
experts: sum over calls and layers of the busiest expert's rows, times
the number of experts, over all assignments (the engine's
``moe_hot_expert_rows`` x E / ``moe_assignments``, after - before). 1.0 is
an even spread; E would be every row on one expert. Every row of the
static batch counts, vacant slots and padding too: it is the load the
device sees. Source: program counter."""


def read(trace, spans, counters, cell):
    took = counters.get("moe_assignments", 0)
    hot = counters.get("moe_hot_expert_rows")
    if not took or hot is None:
        return None
    return hot * cell.config["num_experts"] / took
