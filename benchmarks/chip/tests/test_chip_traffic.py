"""The traffic generator: seeded, the same work for every seed, in range."""
from __future__ import annotations

import numpy as np
import pytest

import harness
import traffic

MIXES = sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json"))


def _mix(name):
    return traffic.load_mix(harness.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = _draw(mix, 2**31 + 5)
    b = _draw(mix, 2**31 + 5)
    assert [(len(x.prompt), x.max_new_tokens, x.due) for x in a] == \
        [(len(x.prompt), x.max_new_tokens, x.due) for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = _mix(name)
    a, b = _draw(mix, 7), _draw(mix, 8)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range(name):
    mix = _mix(name)
    items = _draw(mix, 3)
    p, o = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in items)
    assert all(o["min"] <= x.max_new_tokens <= o["max"] for x in items)
    assert all(0 <= int(x.prompt.min()) and int(x.prompt.max()) < 1000
               for x in items)


def test_open_loop_rate_and_window():
    mix = dict(_mix("chat"), rate_per_s=4.0)
    items = traffic.open_schedule(mix, 1, 100.0, 1000)
    due = np.array([x.due for x in items])
    assert due[0] == 0.0 and (np.diff(due) >= 0).all() and due[-1] < 100.0
    assert 380 <= len(items) <= 400
    assert abs(np.median(np.diff(due)) - np.log(2) / 4.0) < 0.03


def test_poisson_arrivals_clump_at_every_scale():
    """Sums of k consecutive gaps spread as a Poisson stream's do (their
    coefficient of variation near 1/sqrt(k)), not smoothed by the dealing of
    sizes into rounds."""
    mix = dict(_mix("chat"), rate_per_s=4.0)
    due = np.array([x.due for x in traffic.open_schedule(mix, 2**31 + 9,
                                                         400.0, 1000)])
    gaps = np.diff(due)
    for k in (1, 8, 32):
        sums = gaps[:len(gaps) // k * k].reshape(-1, k).sum(1)
        cv = sums.std() / sums.mean()
        assert 0.75 / np.sqrt(k) < cv < 1.25 / np.sqrt(k), (k, cv)


def test_lognormal_median():
    spec = {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 1,
            "max": 10**6}
    u = (np.arange(2001) + 0.5) / 2001
    assert traffic.quantile(spec, u)[1000] == 256


def test_bursty_arrivals_keep_the_rate():
    mix = dict(_mix("chat"), arrivals="bursty", burst=8, rate_per_s=4.0)
    traffic.validate_mix(mix)
    items = traffic.open_schedule(mix, 1, 200.0, 1000)
    gaps = np.diff([x.due for x in items])
    assert 0.8 * 800 <= len(items) <= 800
    assert np.median(gaps) < 0.1 / 4.0       # most gaps are inside a burst


def test_bad_mix_refused():
    with pytest.raises(ValueError):
        traffic.validate_mix({"loop": "closed"})
    with pytest.raises(ValueError):
        traffic.validate_mix(dict(_mix("chat"), rate_per_s=0))


def _draw(mix, seed):
    if mix["loop"] == "open":
        return traffic.open_schedule(mix, seed, 30.0, 1000)
    s = traffic.BatchStream(mix, seed, 1000)
    return [s.next() for _ in range(4 * mix["strata"])]
