"""The load generator: every seed serves the same sizes, shuffled or in one
fixed order."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from traffic import Traffic, percentile

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def open_mix():
    """An open-loop mix: chat's sizes, Poisson arrivals at 4 per second."""
    spec = dict(mix("chat"), loop="open", rate_per_s=4.0)
    del spec["clients"]
    return spec


@pytest.mark.parametrize("order", ["shuffle", "fixed"])
def test_every_seed_serves_the_same_sizes(order):
    spec = dict(mix("chat"), order=order)
    n = spec["population"]
    runs = []
    for seed in (1, 2 ** 40 + 7):
        t = Traffic(spec, seed, 256000)
        runs.append([(len(p), m) for p, m in
                     (t.next_request() for _ in range(2 * n))])
    assert sorted(runs[0]) == sorted(runs[1])
    assert (runs[0] == runs[1]) == (order == "fixed")
    assert sorted(runs[0][:n]) == sorted(runs[0][n:])


def test_fixed_serves_one_cycle_and_seeds_only_the_tokens():
    spec = mix("chat")
    assert spec["order"] == "fixed"
    n = spec["population"]
    a, b = Traffic(spec, 1, 256000), Traffic(spec, 2 ** 40 + 7, 256000)
    ra = [a.next_request() for _ in range(2 * n)]
    rb = [b.next_request() for _ in range(2 * n)]
    sizes = [(len(p), m) for p, m in ra]
    assert sizes == [(len(p), m) for p, m in rb]
    assert sizes[:n] == sizes[n:]
    assert not any(np.array_equal(pa, pb)
                   for (pa, _), (pb, _) in zip(ra, rb))


def test_unknown_order_is_refused():
    with pytest.raises(ValueError, match="order"):
        Traffic(dict(mix("chat"), order="sorted"), 1, 1000)


@pytest.mark.parametrize("name", ["chat", "chat-4slots"])
def test_lengths_keep_their_bounds_and_median(name):
    spec = mix(name)
    t = Traffic(spec, 3, 256000)
    p = np.array([a for a, _ in t.pop])
    o = np.array([b for _, b in t.pop])
    assert p.min() >= 64 and p.max() <= 1536 and np.median(p) == 384
    assert o.min() >= 16 and o.max() <= 512 and np.median(o) == 128


def test_open_loop_gaps_keep_the_rate():
    spec = open_mix()
    t = Traffic(spec, 9, 256000)
    gaps = [t.next_gap() for _ in range(spec["population"])]
    assert abs(np.mean(gaps) * spec["rate_per_s"] - 1) < 0.05
    t2 = Traffic(spec, 10, 256000)
    gaps2 = [t2.next_gap() for _ in range(spec["population"])]
    assert sorted(gaps) == sorted(gaps2) and gaps != gaps2


def test_same_seed_same_requests():
    a = Traffic(open_mix(), 5, 1000)
    b = Traffic(open_mix(), 5, 1000)
    for _ in range(5):
        (pa, ma), (pb, mb) = a.next_request(), b.next_request()
        assert ma == mb and np.array_equal(pa, pb)


def test_percentile_is_nearest_rank():
    assert percentile(range(1, 101), 95) == 95
    assert percentile([5.0], 95) == 5.0
