"""Device, serving cells: share of the traced slice in which no op ran
on the chip. Source: device trace. (A name of its own because a metric
names ONE end-to-end metric it moves; the arithmetic is
``device_idle_pct``'s.)"""
from benchmark.layer_metrics.device_idle_pct import read  # noqa: F401
