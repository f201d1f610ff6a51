"""Program to XLA: the vision tower's share of the chip's busy time in the
traced slice — device time of the ops the family's ``vision_op`` tells (the
patch embedding, a block's attention within a frame, its feed-forward, the
final norm and the 2 x 2 merger), over the busy time of the slice. The tower
runs inside the prefill unit that needs its rows, so this is the part of a
unit (and of what a waiting decoder waits behind) that pixels cost. Source:
device trace. The split by part goes to stdout."""
import sys

from benchmark.layer_metrics.dsa_share_pct import share_of

NAME, HOOK = "vision_share_pct", "vision_op"


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return share_of(trace, cell, NAME, HOOK)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"{NAME}: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None
