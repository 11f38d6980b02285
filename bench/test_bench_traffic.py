"""The traffic generator: the same seed gives the same schedule, every
length lies in its mix's range, the long-run rate is the mix's, the
backlog is due at the ramp's start, and seeds permute one fixed set of
sizes rather than drawing new ones."""
from __future__ import annotations

import numpy as np
import pytest

from bench.traffic.generate import BLOCK, Traffic, load_mix

VOCAB = 1000


def drain(t: Traffic, horizon: float):
    """Every request the schedule hands out until ``horizon``, each
    finishing 1 ms per output token after it was due (so sessions move
    on to their next round)."""
    out, now = [], -t.ramp_s
    while now < horizon:
        for s in t.due(now):
            out.append(s)
            t.done(s, [7] * s.max_new, now + 1e-3 * s.max_new)
        nxt = t.next_due()
        if nxt is None:
            break
        now = max(now, nxt)
    return out


def key(specs):
    return [(s.due, s.round, s.max_new, tuple(s.prompt)) for s in specs]


@pytest.mark.parametrize("mix", ["reflect", "think"])
def test_same_seed_same_schedule(mix):
    m = load_mix(mix)
    a = drain(Traffic(m, 2**31 + 11, VOCAB, 10.0), 5.0)
    b = drain(Traffic(m, 2**31 + 11, VOCAB, 10.0), 5.0)
    c = drain(Traffic(m, 12, VOCAB, 10.0), 5.0)
    assert a and key(a) == key(b) and key(a) != key(c)


def test_sessions_follow_the_mix():
    m = load_mix("reflect")
    t = Traffic(m, 3, VOCAB, 40.0)
    n = len(t.units)
    specs = drain(t, 1e9)
    r1 = [s for s in specs if s.round == 1]
    assert len(r1) == n
    # the backlog is due at the ramp's start; the rest arrive at the rate
    arrivals = sorted(s.due for s in r1)
    k = m["backlog"]
    assert arrivals[:k] == [-m["ramp_s"]] * k
    span = (n - k) / m["rate_per_s"]
    assert arrivals[-1] - arrivals[k] == pytest.approx(span, rel=0.1)
    sys_n = m["system_prompt_tokens"]
    lo, hi = m["body_tokens"]
    for s in r1:
        assert lo <= len(s.prompt) - sys_n <= hi
    for s in specs:
        assert m["output_tokens"][0] <= s.max_new <= m["output_tokens"][1]
    # a later round resubmits prompt + answer + feedback
    by_session = {}
    for s in specs:
        by_session.setdefault(s.tag[0], []).append(s)
    grown = [v for v in by_session.values() if len(v) > 1]
    assert grown
    for v in grown:
        for a, b in zip(v, v[1:]):
            fb = len(b.prompt) - len(a.prompt) - a.max_new
            assert b.prompt[:len(a.prompt)] == a.prompt
            assert m["feedback_tokens"][0] <= fb <= m["feedback_tokens"][1]
    # sessions of one domain share their system prompt
    doms = {}
    for s in r1:
        doms.setdefault(s.domain, set()).add(tuple(s.prompt[:sys_n]))
    assert len(doms) == m["domains"] and all(len(v) == 1
                                             for v in doms.values())
    assert t.max_tokens() < 8192


def test_seeds_permute_one_set_of_sizes():
    m = load_mix("reflect")
    sizes = []
    for seed in (1, 2):
        t = Traffic(m, seed, VOCAB, 40.0)
        # every block of 8 consecutive sessions holds the same sizes
        for a in range(0, len(t.units) - BLOCK + 1, BLOCK):
            block = t.units[a:a + BLOCK]
            sizes.append((a, seed, sorted(len(u["prompt"]) for u in block),
                          sorted(u["rounds"] for u in block)))
    half = len(sizes) // 2
    assert [x[2:] for x in sizes[:half]] == [x[2:] for x in sizes[half:]]


def test_requests_follow_the_mix():
    m = load_mix("think")
    t = Traffic(m, 5, VOCAB, 40.0)
    first = t.due(-t.ramp_s)
    k = m["backlog"]
    assert len(first) == k
    lo, hi = m["output_tokens"]
    # residual lives: each at most a whole request, on average shorter,
    # one set of them for every seed
    assert all(1 <= s.max_new <= hi for s in first)
    assert np.mean([s.max_new for s in first]) < (lo + hi) / 2 * 1.2
    other = Traffic(m, 2**31 + 99, VOCAB, 40.0).due(-t.ramp_s)
    assert sorted(s.max_new for s in first) == sorted(s.max_new
                                                      for s in other)
    rest = drain(t, 1e9)
    assert len(rest) == len(t.units) - k
    for s in rest:
        assert lo <= s.max_new <= hi
        assert m["prompt_tokens"][0] <= len(s.prompt) <= m["prompt_tokens"][1]
    due = sorted(s.due for s in rest)
    assert (len(due) - 1) / (due[-1] - due[0]) == pytest.approx(
        m["rate_per_s"], rel=0.1)
    assert t.max_tokens() < 4096


def test_bursty_gaps_keep_the_mean_rate():
    """Pareto gaps and the diurnal multiplier (copied arithmetic) keep
    the long-run rate."""
    m = {"kind": "sessions", "rate_per_s": 5.0, "pareto_alpha": 1.8,
         "diurnal_amp": 0.6, "diurnal_period_s": 20.0}
    from bench.traffic.generate import arrival_times
    t = arrival_times(m, 4000, np.random.default_rng(0), 0.0)
    assert 4000 / t[-1] == pytest.approx(5.0, rel=0.15)
    assert np.all(np.diff(t) > 0)


def test_residual_life_quantiles():
    """The backlog's residual lives follow P(L > r) / E[L] for L uniform
    on [lo, hi]: mean E[L^2] / (2 E[L]), none past hi."""
    from bench.traffic.generate import residual_life
    lo, hi = 1024, 3568
    q = (np.arange(4096) + 0.5) / 4096
    r = residual_life(q, (lo, hi))
    assert np.all(np.diff(r) > 0) and 0 < r[0] and r[-1] <= hi
    want = (lo * lo + lo * hi + hi * hi) / 3.0 / (lo + hi)
    assert r.mean() == pytest.approx(want, rel=1e-3)
    assert residual_life(np.array([0.25]), (lo, hi))[0] == pytest.approx(
        0.25 * (lo + hi) / 2)
