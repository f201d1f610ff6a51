"""The one general traffic generator: a mix file's parameters and a seed
in, a list of requests on a schedule out. A new mix is a new data file,
never new code.

Parameters it reads (``mixes/<mix>.json``, kind ``serve``):

    arrivals: {rate_per_s, cv}        exactly round(rate * seconds)
                                      requests; inter-arrival times are
                                      gamma with coefficient of variation
                                      cv (cv 1 = Poisson, cv > 1 = bursts)
    prompt.shared_prefix: {prob, count, tokens, zipf_s}
                                      with probability ``prob`` the prompt
                                      opens with one of ``count`` fixed
                                      prefixes of ``tokens`` tokens,
                                      chosen Zipf(s)
    prompt.user, output: {dist: "lognormal", median, sigma, min, max}
                                      or {dist: "uniform", min, max};
                                      lengths are the distribution's
                                      quantiles, dealt out in a drawn
                                      order
    sampling: {greedy_share, temperature, top_p}
                                      the rest sample with a per-request
                                      seed

    schedule_seed                     fixes the SHAPE of the traffic

Every ``--seed`` replays the SAME schedule — arrival times, prompt and
output lengths, who shares which prefix, who samples — which the mix's
``schedule_seed`` drew once; ``--seed`` decides the token ids, the
sampling seeds and (in the driver) the weights. Why: at the rates this
system sustains a window holds some tens of requests, whose lifetimes are
as long as the window. With schedules drawn per seed, six runs of one
code spread by 38% in p95 TTFT and 6% in tokens/s (my chip runs, PR 22):
the draw, not the system. A fixed schedule makes a run a replay, so what
moves between two runs is the system. Lengths are the distributions'
quantiles (i + 1/2) / n dealt out in a drawn order, and there are exactly
round(rate x seconds) requests, so a schedule_seed changes the order of
the work, not its amount.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the schedule. ``due`` is seconds from the start of
    the schedule (ramp included)."""
    index: int
    due: float
    prompt: np.ndarray
    max_new_tokens: int
    sampling: Optional[dict]        # None = greedy


def _quantile(spec: dict, u: float) -> int:
    """The ``u``-quantile of a length distribution, clipped."""
    if spec["dist"] == "lognormal":
        n = math.exp(math.log(spec["median"])
                     + spec["sigma"] * statistics.NormalDist().inv_cdf(u))
    elif spec["dist"] == "uniform":
        n = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(max(int(n), spec["min"]), spec["max"]))


def stratified_lengths(rng: np.random.RandomState, spec: dict,
                       n: int) -> List[int]:
    """``n`` lengths at the quantiles (i + 1/2) / n of the distribution,
    in an order drawn from ``rng``: every seed sends the SAME multiset of
    lengths — a fixed amount of work — and differs in who gets which."""
    lengths = [_quantile(spec, (i + 0.5) / n) for i in range(n)]
    rng.shuffle(lengths)
    return lengths


def _flags(rng: np.random.RandomState, share: float, n: int) -> List[bool]:
    """Exactly round(share * n) True among ``n``, in a drawn order."""
    flags = [i < round(share * n) for i in range(n)]
    rng.shuffle(flags)
    return flags


def arrivals(rng: np.random.RandomState, spec: dict, t0: float,
             seconds: float) -> List[float]:
    """Exactly round(rate * seconds) arrival times in [t0, t0 + seconds):
    gamma inter-arrival times with coefficient of variation ``cv``
    (cv 1: a Poisson process; cv > 1: bursts), scaled so that this many
    fill the span — the process given its count, so every seed offers
    the same load."""
    n = round(spec["rate_per_s"] * seconds)
    shape = 1.0 / (spec["cv"] ** 2)
    gaps = rng.gamma(shape, 1.0 / shape, size=n + 1)
    at = np.cumsum(gaps)[:n] / np.sum(gaps) * seconds
    return [t0 + float(t) for t in at]


def _span(mix: dict, seed: int, t0: float, seconds: float, draw_ids,
          prefixes, weights, first_index: int) -> List[Planned]:
    # The SHAPE of the traffic (when, how long, who shares, who samples)
    # comes from the mix's own ``schedule_seed``; ``seed`` fills it with
    # token ids and sampling seeds. Each property has a stream of its own,
    # so changing one distribution leaves the other draws as they were.
    r_arrive, r_prefix, r_len, r_flag = (
        np.random.RandomState([mix["schedule_seed"], i, first_index])
        for i in range(4))
    r_ids, r_samp = (np.random.RandomState([seed, i, first_index])
                     for i in range(2))
    due = arrivals(r_arrive, mix["arrivals"], t0, seconds)
    n = len(due)
    user = stratified_lengths(r_len, mix["prompt"]["user"], n)
    new = stratified_lengths(r_len, mix["output"], n)
    sp = mix["prompt"].get("shared_prefix")
    shared = _flags(r_prefix, sp["prob"] if sp else 0.0, n)
    sampled = _flags(r_flag, 1.0 - mix["sampling"]["greedy_share"], n)
    out = []
    for i in range(n):
        prompt = draw_ids(r_ids, user[i])
        if shared[i]:
            which = r_prefix.choice(len(prefixes), p=weights)
            prompt = np.concatenate([prefixes[which], prompt])
        sampling = None
        if sampled[i]:
            sampling = {"temperature": mix["sampling"]["temperature"],
                        "top_p": mix["sampling"]["top_p"],
                        "seed": int(r_samp.randint(1, 2 ** 31 - 1))}
        out.append(Planned(first_index + i, due[i],
                           prompt.astype(np.int64), new[i], sampling))
    return out


def schedule(mix: dict, seed: int, ramp_s: float, window_s: float,
             draw_ids: Callable[[np.random.RandomState, int], np.ndarray]
             ) -> List[Planned]:
    """The requests of the ramp, due in ``[0, ramp_s)``, then those of the
    window, due in ``[ramp_s, ramp_s + window_s)``. Each span holds
    exactly round(rate * its seconds) requests. The mix's
    ``schedule_seed`` fixes arrival times, lengths and who shares a
    prefix or samples; ``seed`` fills in the token ids (prefixes
    included) and the sampling seeds. ``draw_ids(rng, n)`` makes ``n``
    token ids (the family knows the vocabulary)."""
    sp = mix["prompt"].get("shared_prefix")
    prefixes, weights = [], None
    if sp:
        r_pre = np.random.RandomState([seed, 99])
        prefixes = [draw_ids(r_pre, sp["tokens"]) for _ in range(sp["count"])]
        weights = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
        weights /= weights.sum()
    ramp = _span(mix, seed, 0.0, ramp_s, draw_ids, prefixes, weights, 0)
    return ramp + _span(mix, seed, ramp_s, window_s, draw_ids, prefixes,
                        weights, len(ramp))
