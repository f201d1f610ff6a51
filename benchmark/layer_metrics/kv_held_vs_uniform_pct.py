"""Serving scheduler: what a cache held BY LAYER KIND holds against one
table for all layers, summed over the window's decode ticks: (pages of the
full-attention kind some slot holds x its layers + pages of the window kind
some slot holds x its layers) over (the pages one table would hold for the
same slots x all layers) — the engine's ``kv_pages_held_global``,
``kv_pages_held_window`` and ``kv_pages_uniform_equiv``, after - before; the
layer counts come from the configuration's ``sliding_window_layout``. 100 =
nothing saved. A page several slots share counts once; pages only a prefix
index still caches are evictable and not counted. Source: program counter."""
import sys


def read(trace, spans, counters, cell):
    """None with the reason on stderr, never an exception, where the
    program counts none of this (a one-kind engine, the parent)."""
    try:
        return _read(counters, cell.config)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"kv_held_vs_uniform_pct: left out ({type(exc).__name__}: "
              f"{exc})", file=sys.stderr)
        return None


def _read(counters, config):
    L = config["num_hidden_layers"]
    n_window = sum(config["sliding_window_layout"][:L])
    uniform = counters.get("kv_pages_uniform_equiv")
    if not uniform:
        print("kv_held_vs_uniform_pct: left out (the engine counts no "
              "kv_pages_uniform_equiv)", file=sys.stderr)
        return None
    held = (counters["kv_pages_held_global"] * (L - n_window)
            + counters["kv_pages_held_window"] * n_window)
    return 100.0 * held / (uniform * L)
