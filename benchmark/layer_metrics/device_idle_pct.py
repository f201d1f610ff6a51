"""Device: share of the traced slice in which no op ran on the chip
(mean over the chips used). Source: device trace."""


def read(trace, spans, counters, cell):
    return 100.0 * (1.0 - trace.mean_busy_s / trace.window_s)
