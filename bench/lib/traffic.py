"""Seeded open-loop traffic from a workload file's parameters.

Every seed gets the same multiset of sizes and arrival gaps, drawn at fixed
quantiles of the stated distributions, in an order of its own; the seed
also draws the prompt tokens.  Lengths are lognormal with a published mean
and standard deviation; prompts are rounded up to a few bucket lengths,
since the engine compiles one prefill per prompt length.

The requests due in the window's last ``same_tail_s`` seconds come in one
order for every seed: a long answer due there is still decoding when the
window closes, so which answers fall there would decide how many tokens the
window counts.  The seed orders everything due before; those requests
finish inside the window, and the tail starts at the same time for every
seed, since the gaps before it are the same set.  So two seeds differ in
order and content, not in the amount of work the window counts, and a
run's spread is the system's.

The open loop (requests carry a due time and are sent when due, whether or
not earlier ones finished) follows ``benchmarks/fig11_serve.poisson_requests``.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of the schedule: due ``due_s`` after the window opens."""

    index: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` draws at fixed quantiles of the lognormal with the published
    ``mean`` and ``std`` (its parameters by the method of moments)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    sigma2 = math.log1p((spec["std"] / spec["mean"]) ** 2)
    mu = math.log(spec["mean"]) - sigma2 / 2
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    return np.exp(mu + math.sqrt(sigma2) * z)


def request_sizes(traffic: dict, n: int) -> tuple:
    """(prompt lengths, output lengths) of ``n`` requests, the same for
    every seed.  A prompt is rounded up to the smallest of ``buckets`` that
    holds it, or cut to the largest; an output is rounded, kept at least
    ``min`` and cut so that prompt and output fit the engine's
    ``max_seq_len``.  Prompts and outputs are paired by a fixed shuffle,
    so the cut, and with it the work, does not depend on the seed."""
    pspec, ospec = traffic["prompt_len"], traffic["output_len"]
    buckets = np.asarray(pspec["buckets"], int)
    raw = lognormal_quantiles(pspec, n)
    plens = buckets[np.minimum(np.searchsorted(buckets, raw),
                               buckets.size - 1)]
    olens = np.rint(lognormal_quantiles(ospec, n)).astype(int)
    olens = olens[np.random.default_rng(0).permutation(n)]
    olens = np.clip(olens, ospec["min"],
                    traffic["engine"]["max_seq_len"] - plens)
    return plens, olens


def arrival_gaps(rate_hz: float, seconds: float, n: int) -> np.ndarray:
    """Poisson gaps at exponential quantiles, scaled to fill the window
    exactly."""
    gaps = -np.log1p(-_quantiles(n)) / rate_hz
    return gaps * (seconds / gaps.sum())


def seeded_order(n: int, n_tail: int, stream: int,
                 rng: np.random.Generator) -> np.ndarray:
    """A fixed shuffle of ``range(n)`` (one per ``stream``) whose first
    ``n - n_tail`` entries ``rng`` shuffles again; the last ``n_tail`` stay
    as they are."""
    order = np.random.default_rng([0, stream]).permutation(n)
    head = n - n_tail
    order[:head] = order[:head][rng.permutation(head)]
    return order


def requests_in_window(traffic: dict, seconds: float, seed: int,
                       vocab: int, rate_hz: float | None = None) -> list:
    """The schedule of one run: ``rate x seconds`` requests due inside a
    window of ``seconds``, the first when it opens."""
    arrivals = traffic["arrivals"]
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    rate = float(rate_hz if rate_hz is not None else arrivals["rate_hz"])
    n = max(1, int(round(rate * seconds)))
    n_tail = min(n, math.ceil(rate * arrivals["same_tail_s"]))
    rng = np.random.default_rng(seed)
    plens, olens = request_sizes(traffic, n)
    order = seeded_order(n, n_tail, 1, rng)
    gaps = arrival_gaps(rate, seconds, n)[seeded_order(n, n_tail, 2, rng)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Planned(i, float(due[i]),
                    rng.integers(0, vocab, int(plens[order[i]]),
                                 dtype=np.int32),
                    int(olens[order[i]]))
            for i in range(n)]
