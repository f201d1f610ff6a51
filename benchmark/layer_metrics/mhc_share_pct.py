"""Program to XLA: the manifold-constrained residual's share of the chip's
busy time in the traced slice — device time of the ops the family's
``mhc_op`` tells (everything shaped like the residual streams or their
flattening: the read, the write-back, the norm; the mixes' projection and
the Sinkhorn rounds), over the busy time of the slice. Source: device trace.
The split by part goes to stdout."""
import sys

from benchmark.layer_metrics.dsa_share_pct import share_of

NAME, HOOK = "mhc_share_pct", "mhc_op"


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return share_of(trace, cell, NAME, HOOK)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"{NAME}: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None
