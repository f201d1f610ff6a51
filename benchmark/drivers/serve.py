"""Driver ``serve``: the family's engine behind ``serving.Server`` under
an OPEN loop — requests are sent on a schedule fixed by the mix and the
seed, whether or not earlier ones have finished.

Threads: this one (set-up, the clock of the window, the checks), one load
generator (sleeps to each request's due time, submits, notes how late it
was), the server's own dispatch thread (which also runs every request's
``on_token`` callback: one clock read and one append), and in a traced
run one that starts and stops the profiler round ``trace_slice_s`` seconds.

Times: every request is timed from when it was DUE, on the client's
side, token by token, through ``on_token``. The schedule starts
``ramp_s`` seconds before the window opens, so the window sees the system
as it is under the load, not as it fills; the ramp counts as set-up.

End to end the driver reports ``tpot_p95_ms`` alone: a percentile over
the window's thousands of gaps, which a host stall of a second does not
move. Tokens per second in the window and the mean time to first token
go to the per-layer readers (``counters``): below the knee the first is
the offered load minus whatever a stall pushed past the window's edge,
the second a mean over some fifty requests, and on a shared host both
swing with the neighbours (PERF.md sections 2 and 6).

Mix parameters: see ``benchmark/traffic.py`` for the traffic; ``engine``
(slots, page_size, n_pages, prompt_buckets, prefill_batch_buckets,
prefill_chunk), ``server`` (max_wait_ms, max_queue), ``ramp_s``,
``drain_s``, ``trace_after_s``, ``trace_slice_s``, ``slo`` (ttft_ms,
mean_gap_ms and, optionally, ttft_ms_per_prompt_token: see ``slo_met``),
``check`` (greedy_requests, logit_gap_tol).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

from benchmark import harness, traffic
from benchmark.layer_metrics import (gaps_over_p95_mode_pct,
                                     gaps_over_tick_pct)
from benchmark.trace_reduce import percentile


@dataclasses.dataclass
class Sent:
    plan: traffic.Planned
    due: float                              # monotonic
    sent: Optional[float] = None
    future: object = None
    error: Optional[str] = None             # refused, failed or unfinished
    result: Optional[np.ndarray] = None     # prompt + generated ids
    token_times: List[float] = dataclasses.field(default_factory=list)

    def on_token(self, _pos, _token):
        self.token_times.append(time.monotonic())


def _generate(srv, requests: List[Sent], log: harness.SpanLog):
    for r in requests:
        wait = r.due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        with log.span("bench/submit"):
            r.sent = time.monotonic()
            try:
                r.future = srv.submit(
                    {"prompt": r.plan.prompt},
                    max_new_tokens=r.plan.max_new_tokens,
                    on_token=r.on_token, **(r.plan.sampling or {}))
            except Exception as exc:  # noqa: BLE001 - a refusal is a result
                r.error = f"{type(exc).__name__}: {exc}"


def _engine_counters(eng) -> dict:
    snap = eng.metrics.snapshot()
    out = dict(snap["counters"])
    for name, h in snap["hist"].items():
        out[f"{name}_sum_ms"] = h["sum_ms"]
        out[f"{name}_count"] = h["count"]
    out["decode_step_p50_ms"] = snap["latency"].get(
        "decode_step_ms", {}).get("p50")
    out["fresh_compiles"] = eng.executor.cache_stats()["fresh_compiles"]
    out["cache_misses"] = eng.cache_stats()["misses"]
    return out


def slo_met(slo: dict, prompt_tokens: int, first_ms: float,
            mean_gap_ms: float) -> bool:
    """Whether an ANSWERED request met the mix's limits: time to first
    token within ``ttft_ms`` + ``ttft_ms_per_prompt_token`` (default 0:
    the flat limit) x its prompt's tokens, and mean gap between its
    tokens within ``mean_gap_ms``. A limit that grows with the prompt
    makes the share a reading of the LOAD: under a flat one a prompt
    with more than that much prefill of its own misses at any rate."""
    ttft_limit = (slo["ttft_ms"]
                  + slo.get("ttft_ms_per_prompt_token", 0.0) * prompt_tokens)
    return first_ms <= ttft_limit and mean_gap_ms <= slo["mean_gap_ms"]


#: edges, in medians of the window's gaps, of the notes' histogram
_EDGES = (0.75, 1.25, 1.5, 2.0, 3.0, 4.0, 6.0, 10.0)


def _by_median(gaps: List[float]) -> dict:
    """Percent of the gaps in each stretch of multiples of their median:
    where the modes lie, so that a reader of the notes can judge
    ``gaps_over_tick_pct``'s cut."""
    if not gaps:
        return {}
    med = float(np.median(gaps))
    counts = np.histogram(np.asarray(gaps) / med,
                          bins=(0.0,) + _EDGES + (np.inf,))[0]
    lows = ("0",) + tuple(f"{e:g}" for e in _EDGES)
    return {f"from_{lo}": 100.0 * int(c) / len(gaps)
            for lo, c in zip(lows, counts)}


def _in_flight(requests: List[Sent], t: float) -> int:
    """Requests due before ``t`` whose last token had not come by then."""
    return sum(1 for r in requests if r.due < t
               and (not r.token_times or r.token_times[-1] >= t))


def _sleep_until(t: float) -> None:
    time.sleep(max(t - time.monotonic(), 0.0))


def run(cell, seed: int, seconds: float, traced: bool, devices,
        t0: float) -> harness.Measured:
    import paddle_tpu as pt
    from paddle_tpu.serving import Server

    mix, config, family = cell.mix, cell.config, cell.family
    pt.set_amp(config["amp"] == "bfloat16")
    eng, executors = family.build_engine(config, mix, seed)
    shapes = eng.warmup()
    setup_fresh = sum(e.cache_stats()["fresh_compiles"] for e in executors)
    ramp = mix["ramp_s"]
    planned = traffic.schedule(
        mix, seed, ramp, seconds,
        lambda rng, n: family.draw_prompt_ids(rng, n, config))
    log = harness.SpanLog()
    # The program's own tracer stays OFF, in a traced run too: with it on,
    # every chunked prefill raises (generation.py prefill_tick passes
    # ``start=`` to trace.record, which already has a ``start``) and no
    # request longer than one chunk ever finishes (PERF.md section 7).
    slice_ = harness.TraceSlice(cell.name) if traced else None
    srv = Server(eng, max_wait_ms=mix["server"]["max_wait_ms"],
                 max_queue=mix["server"]["max_queue"])
    threads = []
    try:
        srv.start()
        start = time.monotonic() + 0.05
        t_open, t_close = start + ramp, start + ramp + seconds
        requests = [Sent(p, start + p.due) for p in planned]
        threads.append(threading.Thread(
            target=_generate, args=(srv, requests, log), name="bench-load"))
        at_slice = []       # the engine's counters round the traced slice
        if traced:
            def profile():
                _sleep_until(t_open + mix["trace_after_s"])
                slice_.start()
                try:
                    at_slice.append(_engine_counters(eng))
                    _sleep_until(t_open + mix["trace_after_s"]
                                 + mix["trace_slice_s"])
                    at_slice.append(_engine_counters(eng))
                finally:
                    slice_.stop()

            threads.append(threading.Thread(target=profile,
                                            name="bench-profiler"))
        for th in threads:
            th.start()
        _sleep_until(t_open)
        at_open = _engine_counters(eng)
        _sleep_until(t_close)
        at_close = _engine_counters(eng)
        live_peak = harness.live_peak_bytes(devices)
        for th in threads:
            th.join()
        drain_end = t_close + mix["drain_s"]
        for r in requests:
            if r.future is not None:
                try:
                    r.result = np.asarray(r.future.result(
                        timeout=max(drain_end - time.monotonic(), 0.01)))
                except Exception as exc:  # noqa: BLE001 - counted, not hidden
                    r.error = f"{type(exc).__name__}: {exc}"
        t_drained = time.monotonic()
    finally:
        srv.stop()
        for th in threads:
            th.join()

    # ---- the client's side -------------------------------------------------
    due = [r for r in requests if t_open <= r.due < t_close]
    in_window = sum(1 for r in requests for t in r.token_times
                    if t_open <= t < t_close)
    ttft, gaps, late, met = [], [], [], 0
    slo = mix["slo"]
    for r in requests:
        ts = r.token_times
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                    if t_open <= b < t_close)
    for r in due:
        ts = r.token_times
        # a request that never answered waited at least until the drain
        # gave up on it
        first_ms = ((ts[0] if ts else t_drained) - r.due) * 1e3
        ttft.append(first_ms)
        late.append((r.sent - r.due) * 1e3)
        mean_gap = ((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3
                    if len(ts) > 1 else 0.0)
        if r.result is not None and slo_met(slo, r.plan.prompt.size,
                                            first_ms, mean_gap):
            met += 1
    failed = [r for r in due if r.result is None]

    # ---- correctness, outside the window -------------------------------------
    problems = []
    for r in requests:
        if r.result is None:
            continue
        p = r.plan.prompt
        if r.result.size != p.size + r.plan.max_new_tokens:
            problems.append(f"request {r.plan.index}: asked "
                            f"{r.plan.max_new_tokens} tokens after {p.size}, "
                            f"got {r.result.size - p.size}")
        elif not np.array_equal(r.result[:p.size], p):
            problems.append(f"request {r.plan.index}: prompt not echoed")
    malformed = len(problems)
    check = mix["check"]
    greedy = [r for r in due if r.result is not None
              and r.plan.sampling is None][:check["greedy_requests"]]
    gaps_ref = family.reference_logit_gaps(
        config, family.weights_of(None, eng.scope),
        [(r.plan.prompt.size, r.result) for r in greedy])
    gap_max = float(gaps_ref.max()) if gaps_ref.size else float("nan")
    if not gaps_ref.size or gap_max > check["logit_gap_tol"]:
        problems.append(f"emitted token's reference logit is {gap_max} below "
                        f"that position's max (tol {check['logit_gap_tol']}, "
                        f"{gaps_ref.size} positions)")

    delta = {k: at_close[k] - at_open.get(k, 0) for k in at_close
             if k != "decode_step_p50_ms"}
    # what the engine counted while the profiler ran: a reader that prices
    # the slice's device ops by a counter's mean wants the mean over THOSE
    # calls (a slice with fewer prefill units than the window's share of
    # them read mistral4's expert roofline at 101%, PERF.md section 6)
    in_slice = ({k: at_slice[1][k] - at_slice[0].get(k, 0)
                 for k in at_slice[1] if k != "decode_step_p50_ms"}
                if len(at_slice) == 2 else None)
    return harness.Measured(
        correct=not problems and not failed and len(due) > 0,
        attempted=len(due), failed=len(failed),
        setup_s=t_open - t0,
        end_to_end={"tpot_p95_ms": percentile(gaps, 95)},
        counters={
            **delta, "slice": in_slice,
            "window": (t_open, t_close), "slots": eng.slots,
            "tokens_per_s": in_window / seconds,
            "decode_step_p50_ms": at_close["decode_step_p50_ms"],
            "window_fresh_compiles": delta["fresh_compiles"],
            "gen_late_ms": late, "ttft_ms": ttft, "slo_met": met,
            "due": len(due), "gap_ms": gaps,
        },
        spans=log.spans,
        executors=executors,
        xplane=slice_.xplane if slice_ else None,
        live_peak_bytes=live_peak,
        checks={
            # at most the limit (None: no position to compare, which
            # fails); at least one position; no request lost; none wrong
            "logit_gap_max": {"value": gap_max if gaps_ref.size else None,
                              "limit": check["logit_gap_tol"]},
            "logit_gap_positions": {"value": int(gaps_ref.size),
                                    "limit": 1},
            "requests_failed": {"value": len(failed), "limit": 0},
            "answers_malformed": {"value": malformed, "limit": 0},
        },
        notes={
            "requests_due": len(due), "requests_failed": len(failed),
            "errors": sorted({r.error for r in failed if r.error})[:5],
            "problems": problems[:5],
            "offered_req_per_s": mix["arrivals"]["rate_per_s"],
            "tokens_in_window": in_window,
            "ttft_mean_ms": sum(ttft) / max(len(ttft), 1),
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p95_ms": percentile(ttft, 95),
            "ttft_p80_ms": percentile(ttft, 80),
            "ttft_p90_ms": percentile(ttft, 90),
            "tpot_mean_ms": sum(gaps) / max(len(gaps), 1),
            "tpot_p50_ms": percentile(gaps, 50),
            "tpot_p90_ms": percentile(gaps, 90),
            "tpot_p99_ms": percentile(gaps, 99),
            "gaps_over_tick_pct": (gaps_over_tick_pct.share(gaps)
                                   if gaps else None),
            "gaps_over_p95_mode_pct": (gaps_over_p95_mode_pct.share(gaps)
                                       if gaps else None),
            "gaps_by_median": _by_median(gaps),
            "decode_step_p50_ms": at_close["decode_step_p50_ms"],
            "gen_late_p95_ms": percentile(late, 95),
            "slo_attain_pct": 100.0 * met / max(len(due), 1),
            "logit_gap_max": gap_max,
            "logit_gap_positions": int(gaps_ref.size),
            "warmup_shapes": shapes,
            "setup_fresh_compiles": setup_fresh,
            "prefix_hit_tokens": delta.get("prefix_hit_tokens", 0),
            "admission_deferred": delta.get("admission_deferred", 0),
            "cache_misses_in_window": delta["cache_misses"],
            "in_flight_at_close": _in_flight(requests, t_close),
            "in_flight_over_window": [
                _in_flight(requests, t_open + seconds * i / 8)
                for i in range(1, 8)],
        })
