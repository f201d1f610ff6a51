"""Serving scheduler: host arrays a decode tick hands ``Executor.run``:
the engine's ``decode_feed_host_arrays`` (the ``np.ndarray`` feeds of a
tick, counted) over ``decode_steps``, after - before. Each is one
host-to-device copy on the engine's thread before the tick's enqueue;
1.0 where a tick packs its rows into one plane and feeds the mask from the
device. Source: program counter. None, with the reason on stderr, where
the engine counts no such arrays (the parent of PR 47) or the window held
no tick."""
import sys


def read(trace, spans, counters, cell):
    fed, steps = (counters.get("decode_feed_host_arrays"),
                  counters.get("decode_steps"))
    if fed is None or not steps:
        print(f"tick_feed_host_arrays: decode_feed_host_arrays {fed}, "
              f"decode_steps {steps}: nothing to read", file=sys.stderr,
              flush=True)
        return None
    return fed / steps
