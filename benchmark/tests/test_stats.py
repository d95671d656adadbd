"""Percentile and window arithmetic, by hand."""

import pytest

from benchmark import stats


def test_percentile_is_linear_between_order_statistics():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)     # pos 3.8
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_tpot_is_over_gaps_not_tokens():
    assert stats.tpot_ms(1.0, 1.63, 64) == pytest.approx(10.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None


def test_window_rules():
    reqs = [
        # finished inside: counts tokens, a TPOT and a TTFT
        {"due": 10.0, "ttft": 0.5, "e2e": 1.13, "generated": 64},
        # due inside, finished outside: a TTFT sample, no tokens
        {"due": 19.5, "ttft": 0.2, "e2e": 2.0, "generated": 64},
        # due before, finished inside: tokens, no TTFT sample
        {"due": 9.0, "ttft": 0.1, "e2e": 1.36, "generated": 64},
        # failed: no tokens; due inside, so it enters TTFT at the time waited
        {"due": 15.0, "failed": True},
        # never retired, due inside: same
        {"due": 18.0},
        # outside altogether
        {"due": 25.0, "ttft": 0.1, "e2e": 1.0, "generated": 64},
    ]
    win = stats.serve_window(reqs, 10.0, 20.0, grace_end=30.0)
    assert win["finished"] == 2 and win["tokens"] == 128
    assert win["tpot_ms"] == pytest.approx([10.0, 20.0])
    assert win["failed_due"] == 2
    assert sorted(win["ttft_ms"]) == pytest.approx([200.0, 500.0, 12000.0, 15000.0])
