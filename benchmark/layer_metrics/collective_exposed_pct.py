"""Sharding: share of the traced slice in which chip 0 had a collective
in flight (all-reduce, reduce-scatter, all-gather, all-to-all,
collective-permute; sync ops and async start-to-done spans) and ran no
other op. Source: device trace. The in-flight total goes to stdout."""
import json


def read(trace, spans, counters, cell):
    if cell.chips < 2:
        return None
    in_flight, exposed = trace.collective_seconds(chip=0)
    print(json.dumps({"collective_in_flight_pct":
                      100.0 * in_flight / trace.window_s}), flush=True)
    return 100.0 * exposed / trace.window_s
