"""Open-loop traffic from a mix file (``bench/traffic/<mix>.json``).

A mix file gives lognormal prompt and output lengths (median, sigma, caps)
and a Poisson arrival rate. The schedule has three segments: a lead-in
before the measured window (so the batch is in steady state when it opens),
the window itself, and a tail of arrivals that keeps the load on while the
requests due in the window drain.

Every seed gets the same work. Each segment holds ``round(rate * length)``
requests whose prompt lengths, output lengths and inter-arrival gaps are the
quantiles ``(i + 0.5) / n`` of their distributions. Prompt and output
lengths are paired by a fixed permutation, and requests and gaps ordered by
permutations drawn from ``order_seed``, a constant unless a caller asks for
another order; the seed draws the token ids. So two seeds send the same requests at the same
times, with other tokens. (With a dozen requests in a window, the order
alone moved the mean TTFT of a run by 12% from seed to seed on the chip,
where two runs of one order agreed to 1%.)

With ``n`` requests in a segment the longest prompt is the
``1 - 0.5 / n`` quantile: a segment of a dozen requests never holds a
prompt from the top 4% of the distribution.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

SEGMENTS = ("lead_in", "window", "tail")
ORDER_SEED = 0x9A1D        # the pairing of lengths and the default order


@dataclass(frozen=True)
class Mix:
    name: str
    prompt: dict          # median, sigma, min, max
    output: dict
    rate_rps: float
    lead_in_s: float
    tail_s: float

    @classmethod
    def load(cls, path: str) -> "Mix":
        with open(path) as f:
            d = json.load(f)
        return cls(name=d["name"], prompt=d["prompt"], output=d["output"],
                   rate_rps=float(d["rate_rps"]),
                   lead_in_s=float(d["lead_in_s"]), tail_s=float(d["tail_s"]))


@dataclass
class Arrival:
    idx: int              # position in the schedule
    segment: str          # lead_in | window | tail
    due_s: float          # seconds after the window opens (lead-in < 0)
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """The ``(i + 0.5) / n`` quantiles of a lognormal, rounded and clipped
    to ``[lo, hi]`` — the same multiset of lengths for every seed."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(median * np.exp(sigma * z))
    return np.clip(x, lo, hi).astype(np.int64)


def poisson_times(n: int, start: float, length: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` arrival times in ``[start, start + length)`` whose gaps are the
    exponential quantiles in a seeded order, scaled to fill the segment."""
    if n == 0:
        return np.zeros(0)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = gaps[rng.permutation(n)]
    offs = np.cumsum(gaps) - gaps[0]
    return start + length * offs / gaps.sum()


def schedule(mix: Mix, window_s: float, seed: int, vocab_size: int,
             order_seed: int = ORDER_SEED) -> list[Arrival]:
    """The whole arrival schedule of one run, sorted by due time."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    spans = {"lead_in": (-mix.lead_in_s, mix.lead_in_s),
             "window": (0.0, float(window_s)),
             "tail": (float(window_s), mix.tail_s)}
    out: list[Arrival] = []
    for seg in SEGMENTS:
        start, length = spans[seg]
        n = int(round(mix.rate_rps * length))
        p, o = mix.prompt, mix.output
        pair = np.random.default_rng([ORDER_SEED, n]).permutation(n)
        fixed = np.random.default_rng([order_seed, n, 1])
        order = fixed.permutation(n)
        lin = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])[order]
        lout = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                   o["max"])[pair][order]
        times = poisson_times(n, start, length, fixed)
        for t, a, b in zip(times, lin, lout):
            toks = rng.integers(0, vocab_size, size=int(a)).astype(np.int32)
            out.append(Arrival(0, seg, float(t), toks, int(b)))
    out.sort(key=lambda a: a.due_s)
    for i, a in enumerate(out):
        a.idx = i
    return out
