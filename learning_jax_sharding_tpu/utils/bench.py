"""Correct benchmarking: warmup, device sync, FLOP counting, MFU.

The reference's only benchmark is a 10-iteration wall-clock loop with two
flaws (`/root/reference/case6_attention.py:234-238`, SURVEY.md §3.4): iteration
0 includes compilation, and JAX's async dispatch is never synchronized, so the
measured time is neither pure-execution nor complete. This harness fixes both
and adds what the driver metric needs (`/root/repo/BASELINE.json`): FLOPs from
XLA's own cost analysis → TFLOP/s per chip → MFU against the chip's peak.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax

# Peak dense bf16 matmul throughput per chip, FLOP/s. Sources: public Google
# Cloud TPU system specs. Keyed by `jax.Device.device_kind`.
PEAK_BF16_FLOPS: dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
}


def device_peak_flops(device: jax.Device | None = None) -> float | None:
    """Peak bf16 FLOP/s for ``device`` (default: first local device), or None
    if unknown (e.g. emulated CPU)."""
    device = device or jax.devices()[0]
    return PEAK_BF16_FLOPS.get(device.device_kind)


# Peak HBM bandwidth per chip, bytes/s. Sources: public Google Cloud TPU
# system specs. The roofline for bandwidth-bound programs (decode!) the way
# PEAK_BF16_FLOPS is for matmul-bound ones.
PEAK_HBM_BYTES: dict[str, float] = {
    "TPU v4": 1.2e12,
    "TPU v5 lite": 819e9,    # v5e
    "TPU v5": 2.765e12,      # v5p
    "TPU v5p": 2.765e12,
    "TPU v6 lite": 1.64e12,  # v6e / Trillium
}


def device_peak_hbm_bw(device: jax.Device | None = None) -> float | None:
    """Peak HBM bytes/s for ``device``, or None if unknown."""
    device = device or jax.devices()[0]
    return PEAK_HBM_BYTES.get(device.device_kind)


def mbu(
    bytes_per_iter: float,
    seconds_per_iter: float,
    device: jax.Device | None = None,
) -> float | None:
    """Memory-bandwidth utilization in [0, 1]: achieved bytes/s over the
    chip's peak HBM bandwidth.

    The roofline metric for DECODE — each generated token must stream the
    served weights plus the valid KV cache through HBM, so
    ``bytes_per_iter`` is (weight bytes + mean valid cache bytes) per token
    step and an MBU near 1 means the step is running at the memory-system
    limit (MFU is near-meaningless there: decode matmuls are thin). None on
    unknown devices (e.g. emulated CPU)."""
    peak = device_peak_hbm_bw(device)
    if peak is None or seconds_per_iter <= 0:
        return None
    return bytes_per_iter / seconds_per_iter / peak


def compiled_flops(fn: Callable, *args, **kwargs) -> float | None:
    """Total FLOPs of one execution, from the compiled program's own cost
    analysis — no hand-derived formulas to drift out of sync with the model."""
    jitted = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn)
    analysis = jitted.lower(*args, **kwargs).compile().cost_analysis()
    if not analysis:
        return None
    flops = analysis.get("flops")
    return float(flops) if flops and flops > 0 else None


@dataclasses.dataclass(frozen=True)
class BenchResult:
    """One measurement. ``flops`` is per-execution (whole program, all chips);
    throughput fields are per chip."""

    seconds_per_iter: float
    iters: int | None  # fixed iteration count, or None if chosen adaptively
    flops: float | None = None
    n_devices: int = 1
    peak_flops_per_chip: float | None = None

    @property
    def tflops_per_chip(self) -> float | None:
        if self.flops is None:
            return None
        return self.flops / self.seconds_per_iter / self.n_devices / 1e12

    @property
    def mfu(self) -> float | None:
        """Model FLOPs utilization in [0,1] — the BASELINE.json north-star
        metric ("≥45% MFU")."""
        t = self.tflops_per_chip
        if t is None or self.peak_flops_per_chip is None:
            return None
        return t * 1e12 / self.peak_flops_per_chip

    def summary(self) -> dict[str, Any]:
        return {
            "seconds_per_iter": self.seconds_per_iter,
            "iters": self.iters,
            "flops_per_iter": self.flops,
            "n_devices": self.n_devices,
            "tflops_per_chip": self.tflops_per_chip,
            "mfu": self.mfu,
        }


def _sync(out: Any) -> None:
    """Wait until ``out`` is computed: ``jax.block_until_ready``.

    It waits on today's chip (``chip_smoke.py``, PR 24, TPU v5 lite: an 8192³
    bf16 matmul that cannot take less than 5.6 ms returned from dispatch in
    0.25 ms, from ``block_until_ready`` in 6.6 ms and from a one-element host
    readback in 7.0 ms). Rounds 1-5 read one element back instead, because
    their remotely attached chip returned from ``block_until_ready`` at once.
    """
    jax.block_until_ready(out)


def _timed_run(fn: Callable, n: int, *args, **kwargs) -> float:
    start = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn(*args, **kwargs)
    _sync(out)
    return time.perf_counter() - start


def time_fn(
    fn: Callable,
    *args,
    iters: int | None = None,
    warmup: int = 2,
    min_time: float = 1.0,
    repeats: int = 3,
    **kwargs,
) -> float:
    """Seconds per iteration of ``fn(*args)``: compile/warmup excluded, fixed
    dispatch/transport latency cancelled out.

    The corrected form of the reference's timing loop
    (`/root/reference/case6_attention.py:234-238`, which excludes neither
    compile time nor async dispatch). Method: enqueued programs execute
    serially on the device, so a run of ``k`` calls followed by one host
    sync costs ``L + k·c`` (L = fixed dispatch/sync latency, c =
    per-iteration device time). Two runs at ``k`` and ``2k`` give
    ``c = (t₂ - t₁) / k`` with L eliminated. ``k`` is grown until a run takes
    ≥ ``min_time`` (device time ≫ jitter of L) and the diff is taken as the
    median of ``repeats`` pairs. (Rounds 1-5 ran on a remotely attached chip
    with L ≈ 100 ms; L on today's machine is not measured.)

    Args:
        iters: fixed k; None (default) picks k adaptively from ``min_time``.
    """
    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args, **kwargs)
    _sync(out)

    if iters is None:
        iters = 1
        while True:
            t = _timed_run(fn, iters, *args, **kwargs)
            if t >= min_time or iters >= 1_000_000:
                break
            # Aim past min_time in one hop using the (latency-inflated, hence
            # conservative) current estimate.
            iters = max(2 * iters, int(iters * 1.5 * min_time / max(t, 1e-9)))

    diffs = []
    for _ in range(max(repeats, 1)):
        t1 = _timed_run(fn, iters, *args, **kwargs)
        t2 = _timed_run(fn, 2 * iters, *args, **kwargs)
        diffs.append(t2 - t1)
    diffs.sort()
    per_iter = diffs[len(diffs) // 2] / iters
    if per_iter <= 0:
        # Noise floor: bound from above with the single-run estimate.
        per_iter = t2 / (2 * iters)
    return per_iter


def measure(
    fn: Callable,
    *args,
    iters: int | None = None,
    warmup: int = 2,
    min_time: float = 1.0,
    repeats: int = 3,
    flops: float | None = None,
    n_devices: int | None = None,
    **kwargs,
) -> BenchResult:
    """Time ``fn`` and derive per-chip throughput / MFU.

    Args:
        flops: per-execution FLOPs; if None, read from XLA cost analysis.
        n_devices: chips sharing the work (default: all local devices).
        repeats: latency-cancelled pairs to median over (see ``time_fn``);
            raise together with ``min_time`` for drift-robust headline
            numbers: short chains sample one drift state while long chains
            average it.
    """
    if flops is None:
        flops = compiled_flops(fn, *args, **kwargs)
    secs = time_fn(
        fn, *args, iters=iters, warmup=warmup, min_time=min_time,
        repeats=repeats, **kwargs,
    )
    return BenchResult(
        seconds_per_iter=secs,
        iters=iters,
        flops=flops,
        n_devices=n_devices if n_devices is not None else len(jax.devices()),
        peak_flops_per_chip=device_peak_flops(),
    )
