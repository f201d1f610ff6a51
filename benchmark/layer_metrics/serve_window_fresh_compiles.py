"""Executor, serving cells: executables compiled fresh inside the
window; must read 0. Source: program counter. (A name of its own because
a metric names ONE end-to-end metric it moves.)"""
from benchmark.layer_metrics.window_fresh_compiles import read  # noqa: F401
