"""Every seed offers the same work in another order."""

import numpy as np

from benchmark import traffic

PROMPTS = {"min": 32, "max": 512, "pareto_alpha": 2.0, "mean_excess": 160, "block": 64}
ARRIVALS = {"rate_rps": 3.0, "burstiness": 2.0, "burst_jitter_s": 0.05, "block": 32}


def lengths(seed, n):
    gen = traffic.prompt_stream(PROMPTS, 50257, seed)
    return [len(next(gen)) for _ in range(n)]


def test_same_lengths_every_seed_other_order():
    a, b = lengths(1, 64), lengths(3_000_000_011, 64)
    assert sorted(a) == sorted(b) == sorted(traffic.length_block(PROMPTS))
    assert a != b
    assert min(a) == 32 and max(a) == 512
    assert lengths(1, 64) == a                      # the same seed repeats


def test_arrivals_offer_the_stated_rate():
    gaps, sizes = traffic.arrival_block(ARRIVALS)
    assert sizes.sum() / gaps.sum() == np.float64(3.0) or abs(sizes.sum() / gaps.sum() - 3.0) < 1e-9
    assert sizes.min() == 1 and 1.5 < sizes.mean() < 2.5
    a = traffic.arrival_times(ARRIVALS, 5, 60.0)
    b = traffic.arrival_times(ARRIVALS, 2**31 + 9, 60.0)
    assert np.all(np.diff(a) >= 0) and a[-1] < 60.0
    assert abs(len(a) - 180) <= 12 and abs(len(b) - 180) <= 12
    assert not np.array_equal(a[:10], b[:10])
