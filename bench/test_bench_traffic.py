"""The traffic generator: the same seed gives the same schedule, every
seed gives the same work in another order, and the lengths follow the
mix's lognormals (Azure conversation and coding, Heron paper Fig 12)."""
import os
from collections import Counter

import numpy as np
import pytest

from bench import traffic

TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")
# the published fits the mix files copy: (median, sigma) of prompt, output
PUBLISHED = {"conversation": ((950.0, 0.95), (220.0, 0.85)),
             "coding": ((1900.0, 0.90), (80.0, 0.55))}
BIG_SEED = 2 ** 31 + 123_457


def _mix(name):
    return traffic.Mix.load(os.path.join(TRAFFIC, name + ".json"))


def _key(a):
    return (a.segment, round(a.due_s, 9), a.max_new_tokens,
            a.prompt.tobytes())


@pytest.mark.parametrize("name", ["conversation"])
def test_mix_files_copy_the_published_lengths(name):
    m = _mix(name)
    (pm, ps), (om, os_) = PUBLISHED[name]
    assert (m.prompt["median"], m.prompt["sigma"]) == (pm, ps)
    assert (m.output["median"], m.output["sigma"]) == (om, os_)
    assert m.prompt["max"] == 8191 and m.output["max"] == 1024
    assert m.rate_rps > 0


def test_same_seed_same_schedule():
    m = _mix("conversation")
    a = traffic.schedule(m, 30, BIG_SEED, 1000)
    b = traffic.schedule(m, 30, BIG_SEED, 1000)
    assert [_key(x) for x in a] == [_key(x) for x in b]


def test_seeds_send_the_same_requests_with_other_tokens():
    m = _mix("conversation")
    a = traffic.schedule(m, 30, 7, 1000)
    b = traffic.schedule(m, 30, BIG_SEED, 1000)
    assert [(x.segment, x.due_s, len(x.prompt), x.max_new_tokens)
            for x in a] == [(y.segment, y.due_s, len(y.prompt),
                             y.max_new_tokens) for y in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))


def test_another_order_keeps_the_requests():
    m = _mix("conversation")
    a = traffic.schedule(m, 30, 7, 1000)
    b = traffic.schedule(m, 30, 7, 1000, order_seed=11)
    for seg in traffic.SEGMENTS:
        ra = Counter((len(x.prompt), x.max_new_tokens) for x in a
                     if x.segment == seg)
        rb = Counter((len(x.prompt), x.max_new_tokens) for x in b
                     if x.segment == seg)
        assert ra == rb
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_segments_counts_and_times():
    m = traffic.Mix("t", {"median": 100, "sigma": 0.5, "min": 1, "max": 400},
                    {"median": 10, "sigma": 0.5, "min": 1, "max": 50},
                    rate_rps=2.0, lead_in_s=5.0, tail_s=7.0)
    s = traffic.schedule(m, 20, 3, 64)
    n = Counter(a.segment for a in s)
    assert n == {"lead_in": 10, "window": 40, "tail": 14}
    spans = {"lead_in": (-5, 0), "window": (0, 20), "tail": (20, 27)}
    for a in s:
        lo, hi = spans[a.segment]
        assert lo <= a.due_s < hi
        assert a.prompt.dtype == np.int32 and a.prompt.max() < 64
    assert [a.idx for a in s] == list(range(len(s)))
    assert all(x.due_s <= y.due_s for x, y in zip(s, s[1:]))


def test_lengths_follow_the_lognormal():
    q = traffic.lognormal_quantiles(1001, 950.0, 0.95, 1, 8191)
    assert q[500] == 950                       # the median quantile
    assert q.max() == 8191 and q.min() >= 1    # the cap holds
    # the 84th percentile is median * e^sigma
    assert abs(np.percentile(q, 84.13) / (950 * np.exp(0.95)) - 1) < 0.02


def test_gaps_are_exponential_quantiles():
    rng = np.random.default_rng(0)
    t = traffic.poisson_times(400, 10.0, 100.0, rng)
    gaps = np.diff(t)
    assert t[0] == 10.0 and t[-1] < 110.0
    # exponential: coefficient of variation about one
    assert 0.9 < gaps.std() / gaps.mean() < 1.1
