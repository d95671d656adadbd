"""The one general traffic generator. A traffic mix is a data file
(``benchmark/traffic/<name>.json``) of parameters; this module turns it and
``--seed`` into prompts, arrivals and batches.

Every seed gets the SAME set of sizes and gaps, in another order: lengths,
cluster gaps and cluster sizes are taken at fixed quantiles of their
distributions, one block at a time, and the seed only permutes each block.
So two seeds offer the same work and a run-to-run difference is the
system's, not the draw's. The distributions are the ones
``fleet/loadgen.py`` draws from (Pareto prompt lengths over a minimum,
clustered arrivals with geometric cluster sizes), without its day.
"""

from __future__ import annotations

import math
from typing import Iterator

import numpy as np


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """One independent generator per (seed, purpose). Seeds may exceed 2**31."""
    return np.random.default_rng([int(seed), int(stream)])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_block(spec: dict) -> np.ndarray:
    """The fixed multiset of prompt lengths of one block: ``min`` plus a
    Pareto(alpha) excess of mean ``mean_excess``, clipped to ``max``."""
    alpha = float(spec["pareto_alpha"])
    if alpha <= 1.0:
        raise ValueError("pareto_alpha must be > 1 (the mean must exist)")
    u = _quantiles(int(spec["block"]))
    excess = ((1.0 - u) ** (-1.0 / alpha) - 1.0) * (
        float(spec["mean_excess"]) * (alpha - 1.0)
    )
    return np.clip(
        int(spec["min"]) + excess.astype(np.int64), int(spec["min"]),
        int(spec["max"]),
    )


def prompt_stream(spec: dict, vocab_size: int, seed: int) -> Iterator[np.ndarray]:
    """Endless prompts: each block is ``length_block`` permuted by the seed,
    tokens uniform over ``1..vocab_size-1``."""
    rng = seed_rng(seed, 1)
    block = length_block(spec)
    while True:
        for n in rng.permutation(block):
            yield rng.integers(1, vocab_size, size=(int(n),)).astype(np.int32)


def arrival_block(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """Fixed cluster gaps (seconds) and cluster sizes of one block of
    clustered arrivals at ``rate_rps`` with mean cluster size
    ``burstiness``. The gaps are scaled so that a block offers exactly
    ``rate_rps``."""
    rate, burst = float(spec["rate_rps"]), float(spec["burstiness"])
    if rate <= 0 or burst < 1.0:
        raise ValueError("rate_rps must be > 0 and burstiness >= 1")
    u = _quantiles(int(spec["block"]))
    if burst == 1.0:
        sizes = np.ones(len(u), np.int64)
    else:
        p = 1.0 / burst
        sizes = np.maximum(
            1, np.ceil(np.log1p(-u) / math.log1p(-p))
        ).astype(np.int64)
    gaps = -np.log1p(-u)
    gaps *= (sizes.sum() / rate) / gaps.sum()
    return gaps, sizes


def arrival_times(spec: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Sorted times (seconds from 0) at which requests are due, up to
    ``horizon_s``. Members of a cluster follow its head by exponential
    jitters of mean ``burst_jitter_s``."""
    rng = seed_rng(seed, 2)
    gaps, sizes = arrival_block(spec)
    jitter = float(spec["burst_jitter_s"])
    times: list[float] = []
    t = 0.0
    while t < horizon_s:
        for gap, size in zip(rng.permutation(gaps), rng.permutation(sizes)):
            t += float(gap)
            offs = np.concatenate(
                [[0.0], np.cumsum(rng.exponential(jitter, size=int(size) - 1))]
            )
            times.extend(t + offs)
    out = np.sort(np.asarray(times))
    return out[out < horizon_s]


def token_batches(batch: int, seq: int, vocab_size: int, seed: int, pool: int):
    """``pool`` host batches of ``(batch, seq + 1)`` uniform random tokens;
    the trainer cycles through them, one per step."""
    rng = seed_rng(seed, 3)
    return [
        rng.integers(0, vocab_size, size=(batch, seq + 1)).astype(np.int32)
        for _ in range(pool)
    ]
