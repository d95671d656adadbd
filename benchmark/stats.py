"""Percentile and window arithmetic: pure functions, checked by hand in
``benchmark/tests/test_stats.py``."""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(values: Iterable[float], q: float) -> float:
    """Exact ``q``-th percentile (0..100) of the samples, linear between
    order statistics (numpy's default rule). Raises on no samples: a metric
    with nothing behind it is left out, not reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t: float, start: float, end: float) -> bool:
    """``start <= t <= end``: a request that finished on the last step of the
    window counts, one that finished after it does not."""
    return start <= t <= end


def tpot_ms(first_token_t: float, last_token_t: float, generated: int) -> float | None:
    """Time per output token of one request: (last - first) / (tokens - 1)
    in ms; None for a request with fewer than two tokens."""
    if generated < 2:
        return None
    return (last_token_t - first_token_t) / (generated - 1) * 1e3


def serve_window(requests: Sequence[dict], start: float, end: float, *,
                 grace_end: float | None = None) -> dict:
    """Reduce per-request records to the serving end-to-end samples.

    Each record: ``due`` (absolute time the request was due), and, once it
    retired, ``ttft`` and ``e2e`` (seconds after ``due``), ``generated`` and
    ``failed``. ``grace_end`` is the time the load stopped (>= ``end``).

    * finished in the window: ``start <= due + e2e <= end`` and not failed —
      their generated tokens make ``tokens``, their TPOTs make ``tpot_ms``;
    * due in the window: ``start <= due < end`` — each gives one TTFT
      sample; a request that failed or has no first token by ``grace_end``
      counts in ``failed_due`` and enters at the time it had waited.
    """
    grace_end = end if grace_end is None else grace_end
    tokens, tpots, ttfts, failed_due = 0, [], [], 0
    finished = 0
    for r in requests:
        done = r.get("e2e") is not None and not r.get("failed")
        if done and in_window(r["due"] + r["e2e"], start, end):
            finished += 1
            tokens += int(r["generated"])
            t = tpot_ms(r["due"] + r["ttft"], r["due"] + r["e2e"], r["generated"])
            if t is not None:
                tpots.append(t)
        if start <= r["due"] < end:
            if done:
                ttfts.append(r["ttft"] * 1e3)
            else:
                failed_due += 1
                ttfts.append(max(grace_end - r["due"], 0.0) * 1e3)
    return {
        "finished": finished, "tokens": tokens, "tpot_ms": tpots,
        "ttft_ms": ttfts, "failed_due": failed_due,
    }
