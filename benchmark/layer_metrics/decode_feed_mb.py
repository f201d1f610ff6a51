"""Serving scheduler: host bytes a decode tick hands ``Executor.run``:
the engine's ``decode_feed_host_bytes`` (the ``nbytes`` of every
``np.ndarray`` feed of a tick, summed) over ``decode_steps``, after -
before, in MB (1e6 bytes). What has to cross to the device before the
tick's program can be enqueued. Source: program counter. None, with the
reason on stderr, where the engine counts no such bytes (the parent of
PR 40) or the window held no tick."""
import sys


def read(trace, spans, counters, cell):
    fed, steps = (counters.get("decode_feed_host_bytes"),
                  counters.get("decode_steps"))
    if fed is None or not steps:
        print(f"decode_feed_mb: decode_feed_host_bytes {fed}, decode_steps "
              f"{steps}: nothing to read", file=sys.stderr, flush=True)
        return None
    return fed / steps / 1e6
