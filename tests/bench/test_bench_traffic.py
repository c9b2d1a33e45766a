"""The traffic generator: deterministic by seed, the same lengths and gaps
for every seed in another order, lengths as the file defines them."""

import numpy as np
import pytest

from bench.traffic import Traffic, stratified_lengths

OPEN = {"loop": "open", "rate_per_s": 5.0,
        "prompt": {"median": 64, "sigma": 0.6, "min": 16, "max": 256},
        "output": {"median": 48, "sigma": 0.6, "min": 8, "max": 192}}
CLOSED = {"loop": "closed", "clients": 3,
          "prompt": {"median": 96, "sigma": 0.6, "min": 32, "max": 128},
          "output": {"median": 192, "sigma": 0.6, "min": 96, "max": 256}}


def lengths(t):
    return (np.array([len(r.prompt) for r in t.requests]),
            np.array([r.max_new for r in t.requests]))


def test_same_seed_same_requests():
    big = 2 ** 31 + 12345
    a, b = Traffic(OPEN, big, 1000, 30), Traffic(OPEN, big, 1000, 30)
    assert len(a) == len(b)
    for x, y in zip(a.requests, b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s) == (y.max_new, y.due_s)


@pytest.mark.parametrize("spec", [OPEN, CLOSED])
def test_every_seed_gets_the_same_sizes_and_arrivals(spec):
    a, b = Traffic(spec, 1, 1000, 30), Traffic(spec, 2, 1000, 30)
    pa, oa = lengths(a)
    pb, ob = lengths(b)
    assert np.array_equal(pa, pb) and np.array_equal(oa, ob)
    assert [r.due_s for r in a.requests] == [r.due_s for r in b.requests]
    # the seed draws the tokens
    assert not np.array_equal(a.requests[0].prompt, b.requests[0].prompt)


def test_lengths_follow_the_file():
    t = Traffic(OPEN, 7, 1000, 60)
    p, o = lengths(t)
    assert p.min() >= 16 and p.max() <= 256
    assert o.min() >= 8 and o.max() <= 192
    assert abs(np.median(p) - 64) <= 1
    assert abs(np.median(o) - 48) <= 1
    # lognormal sigma 0.6: the quartiles lie at exp(+-0.6 * 0.674)
    q1, q3 = np.percentile(p, [25, 75])
    assert q3 / q1 == pytest.approx(np.exp(2 * 0.6 * 0.6745), rel=0.1)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000
               for r in t.requests)


def test_open_loop_arrivals():
    t = Traffic(OPEN, 3, 1000, 60)
    due = np.array([r.due_s for r in t.requests])
    assert due[0] == 0.0
    assert np.all(np.diff(due) > 0)
    assert np.mean(np.diff(due)) == pytest.approx(1 / 5.0, rel=0.1)
    assert due[-1] >= 60          # the arrivals outlast the window


def test_closed_loop_has_no_due_times():
    t = Traffic(CLOSED, 3, 1000, 60)
    assert t.clients == 3
    assert all(r.due_s is None for r in t.requests)


def test_stratified_lengths_are_clipped():
    x = stratified_lengths({"median": 10, "sigma": 2.0, "min": 5,
                            "max": 20}, 100)
    assert x.min() == 5 and x.max() == 20


@pytest.mark.parametrize("bad", [
    {**OPEN, "loop": "bursty"},
    {**OPEN, "rate_per_s": 0},
    {**OPEN, "prompt": {"median": 8, "sigma": 0.6, "min": 16, "max": 32}},
    {**CLOSED, "clients": 0},
])
def test_bad_traffic_is_refused(bad):
    with pytest.raises((ValueError, KeyError)):
        Traffic(bad, 1, 1000, 10)


def test_any_window_of_requests_carries_about_the_same_work():
    # 64 consecutive requests hold close to the mean output and prompt,
    # wherever the window starts
    p, o = lengths(Traffic(OPEN, 0, 1000, 120))
    for x in (p, o):
        means = [x[i:i + 64].mean() for i in range(0, len(x) - 64, 7)]
        assert max(abs(m / x.mean() - 1) for m in means) < 0.05
