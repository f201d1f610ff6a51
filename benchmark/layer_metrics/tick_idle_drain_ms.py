"""Executor, serving: chip 0's idle time a decode tick between the end
of the tick's module and the close of the ``executor/fetch`` that waited
for it: the result coming back (the runtime noticing the program's end,
its callbacks, the device-to-host copy of the fetched rows). Source:
program span + device trace; the split and its clock are
``tick_idle_fill_ms``'s. None where that finds nothing to split."""
from benchmark.layer_metrics.tick_idle_fill_ms import per_tick_ms, split


def read(trace, spans, counters, cell):
    return per_tick_ms(split(trace, "tick_idle_drain_ms"),
                       "tick_idle_drain_ms", "drain")
