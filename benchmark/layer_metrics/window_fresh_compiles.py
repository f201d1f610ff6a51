"""Executor: executables compiled fresh inside the measured window
(``Executor.cache_stats()['fresh_compiles']``, after - before). Must
read 0: every shape is warmed during set-up. Source: program counter."""


def read(trace, spans, counters, cell):
    return counters.get("window_fresh_compiles")
