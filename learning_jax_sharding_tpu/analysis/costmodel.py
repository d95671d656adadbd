"""costmodel: price a shardflow prediction into a step time.

Three-term roofline over the quantities :mod:`.shardflow` accumulates
per entry point (2211.05322's communication model layered on the
classic compute/memory roofline):

* **compute**: ``flops / (peak_flops × mfu_eff)``
* **memory**:  ``hbm_bytes / (hbm_bw × mbu_eff)`` — loop-body operands
  (weights, KV) already carry their trip multiplier, so this is the
  decode regime's dominant term;
* **collectives**: per predicted event, ring cost on the event's mesh
  axis (all-reduce ``2B(n-1)/n``, all-gather / reduce-scatter
  ``B(n-1)/n``, all-to-all ``B(n-1)/n``, permute ``B``) over the
  per-link bandwidth, × trip for in-loop events.

``predicted_s = max(compute, memory, collective)`` — the terms overlap
on real hardware (async collectives, prefetch), and the efficiency
factors are *seeded from the repo's own bench trajectory* (BENCH_r04–r05
on TPU v5e: train steps sustain ~50% MFU, bandwidth-bound decode ~80%
MBU), so each term is already an achieved-rate estimate, not a
theoretical peak.

On hosts without a known peak table entry (the CPU tier-1 environment),
:func:`calibrate` measures effective matmul FLOP/s and stream bytes/s
live with two microbenches and caches them per process — the same
numbers `bench.py` then validates against measured step times (the
``shardflow`` bench block, gated by ``scripts/bench_compare.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Iterable

from learning_jax_sharding_tpu.analysis.shardflow import (
    CommEvent,
    ShardflowReport,
)
from learning_jax_sharding_tpu.analysis.topology import (
    TIER_DCN,
    TopologyProfile,
)

# ---------------------------------------------------------------------------
# Platform profiles
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Profile:
    """Achieved-rate model for one platform.

    ``mfu_eff`` / ``mbu_eff`` scale the peak rates down to what this
    repo's kernels actually sustain; for calibrated (CPU) profiles the
    measured rates are already effective and the factors are 1.0.
    """

    name: str
    peak_flops: float          # FLOP/s (bf16 on TPU, measured f32 on CPU)
    hbm_bw: float              # bytes/s
    link_bw: float             # per-device interconnect bytes/s
    mfu_eff: float = 1.0
    mbu_eff: float = 1.0
    #: Achieved FLOP/s for GEMV-regime dots (a handful of rows against a
    #: big weight — the decode token step). None → fall back to
    #: ``peak_flops × mfu_eff``; on TPU the decode lines are priced by
    #: the memory term anyway, but CPU thin matmuls run ~7× below the
    #: square-matmul rate and need their own bucket.
    thin_flops: float | None = None
    #: Measured per-axis α–β link models from the commscope calibration
    #: ladder: ``((axis, alpha_s, beta_bytes_per_s), ...)``. None → every
    #: collective prices on the flat ``link_bw`` (the pinned-table
    #: fallback). Attach via :func:`calibrate_axis_profiles`.
    axis_profiles: tuple[tuple[str, float, float], ...] | None = None
    source: str = "table"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


#: Seeded from the repo's own bench trajectory: BENCH_r04–r05 (TPU v5e)
#: hold train at 49–50% MFU and bandwidth-bound decode at ~80% MBU, so
#: those are the achieved-rate factors; ICI link bandwidth per 2211.05322
#: §2 / public v5e specs (4 ICI links, ~45 GB/s effective per direction).
_TPU_PROFILES: dict[str, Profile] = {
    "TPU v5 lite": Profile(
        "TPU v5 lite", peak_flops=197e12, hbm_bw=819e9, link_bw=45e9,
        mfu_eff=0.50, mbu_eff=0.80,
    ),
    "TPU v4": Profile(
        "TPU v4", peak_flops=275e12, hbm_bw=1.2e12, link_bw=100e9,
        mfu_eff=0.50, mbu_eff=0.80,
    ),
    "TPU v5": Profile(
        "TPU v5", peak_flops=459e12, hbm_bw=2.8e12, link_bw=100e9,
        mfu_eff=0.50, mbu_eff=0.80,
    ),
    "TPU v6 lite": Profile(
        "TPU v6 lite", peak_flops=918e12, hbm_bw=1.6e12, link_bw=90e9,
        mfu_eff=0.50, mbu_eff=0.80,
    ),
}


@functools.lru_cache(maxsize=4)
def calibrate(platform: str = "cpu") -> Profile:
    """Measure effective FLOP/s (square matmul) and stream bytes/s (big
    copy) on the current backend. Used where the peak table has no entry
    — the emulated-CPU tier-1 host — so predicted-vs-measured stays a
    meaningful check everywhere the suite runs. Cached per process; the
    two probes take well under a second."""
    import jax
    import jax.numpy as jnp

    from learning_jax_sharding_tpu.utils.bench import time_fn

    n = 512
    a = jnp.ones((n, n), jnp.bfloat16)
    mm = jax.jit(lambda x: x @ x)
    t_mm = time_fn(mm, a, min_time=0.05, repeats=2)
    flops = 2.0 * n ** 3 / max(t_mm, 1e-9)

    # Train regime: a mini tied-embedding LM step (gather → MLP+residual
    # → tied logits → log-softmax loss, forward+backward) at a FIXED
    # reference shape. A bare matmul overstates what a training step
    # sustains by ~2-3× on the CPU backend — transposed backward dots,
    # f32→bf16 parameter conversions, and the fp32 loss all bill real
    # time there. The probe's achieved rate over its analytic matmul
    # FLOPs is this platform's honest MFU; the tracked programs then
    # drift against a fixed yardstick, not against themselves.
    V, d, h = 4096, 256, 1024
    bq, sq, nh, hd = 4, 256, 4, 64
    tok = bq * sq
    emb = jnp.full((V, d), 0.01, jnp.float32)
    wqkv = jnp.full((d, 3 * nh * hd), 0.01, jnp.float32)
    wo = jnp.full((nh * hd, d), 0.01, jnp.float32)
    w1 = jnp.full((d, h), 0.01, jnp.float32)
    w2 = jnp.full((h, d), 0.01, jnp.float32)
    idx = (jnp.arange(tok, dtype=jnp.int32) % V).reshape(bq, sq)
    tgt = ((jnp.arange(tok, dtype=jnp.int32) + 1) % V).reshape(bq, sq)
    causal = jnp.tril(jnp.ones((sq, sq), bool))

    def norm(x):
        x32 = x.astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + 1e-6)
        return (x32 * r).astype(x.dtype)

    def lm_loss(emb, wqkv, wo, w1, w2):
        x = emb[idx].astype(jnp.bfloat16)   # (bq, sq, d)
        qkv = (norm(x) @ wqkv.astype(jnp.bfloat16)).reshape(
            bq, sq, 3, nh, hd
        )
        q, k, v = (
            qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3)
        )   # (bq, nh, sq, hd)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(hd), -1e9)
        p = jax.nn.softmax(s, -1).astype(jnp.bfloat16)
        att = jnp.einsum("bhqk,bhkd->bhqd", p, v).transpose(0, 2, 1, 3)
        y = x + att.reshape(bq, sq, nh * hd) @ wo.astype(jnp.bfloat16)
        y = y + jax.nn.gelu(norm(y) @ w1.astype(jnp.bfloat16)) @ w2.astype(
            jnp.bfloat16
        )
        logits = (norm(y) @ emb.astype(jnp.bfloat16).T).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], axis=-1))

    g = jax.jit(jax.grad(lm_loss, argnums=(0, 1, 2, 3, 4)))
    t_tr = time_fn(g, emb, wqkv, wo, w1, w2, min_time=0.05, repeats=2)
    train_rate = 3.0 * 2.0 * tok * (
        d * 3 * nh * hd + 2 * nh * hd * sq + nh * hd * d
        + d * h * 2 + d * V
    ) / max(t_tr, 1e-9)
    mfu_eff = min(1.0, train_rate / max(flops, 1.0))

    # Decode regime: one cached token step (qkv → attention over a full
    # cache → out/FF → tied head) at b=4. GEMV-shaped dots plus the
    # batched attention-over-cache contractions run far below the
    # square-matmul rate; the probe's achieved rate prices the thin
    # bucket directly (TPU table profiles leave it None — decode there
    # is billed by the memory term).
    S, nh, hd = 512, 4, 64
    bq = 4
    wq = jnp.full((d, nh * hd), 0.01, jnp.bfloat16)
    wo = jnp.full((nh * hd, d), 0.01, jnp.bfloat16)
    kc = jnp.full((bq, nh, S, hd), 0.01, jnp.bfloat16)
    xd = jnp.full((bq, d), 0.01, jnp.bfloat16)

    def tok_step(xd, wq, wo, w1, w2, emb, kc):
        q = (xd @ wq).reshape(bq, nh, 1, hd)
        s = jax.nn.softmax(
            jnp.einsum("bhqd,bhkd->bhqk", q, kc).astype(jnp.float32), -1
        ).astype(jnp.bfloat16)
        y = jnp.einsum("bhqk,bhkd->bhqd", s, kc).reshape(bq, nh * hd) @ wo
        y = y + jax.nn.gelu(y @ w1.astype(jnp.bfloat16)) @ w2.astype(
            jnp.bfloat16
        )
        return y @ emb.astype(jnp.bfloat16).T

    t_tok = time_fn(jax.jit(tok_step), xd, wq, wo, w1, w2, emb, kc,
                    min_time=0.05, repeats=2)
    tok_flops = 2.0 * bq * (
        d * nh * hd + 2 * nh * S * hd + nh * hd * d + d * h * 2 + d * V
    )
    thin = tok_flops / max(t_tok, 1e-9)

    m = 1 << 22   # 16 MiB f32
    b = jnp.ones((m,), jnp.float32)
    cp = jax.jit(lambda x: x + 1.0)
    t_cp = time_fn(cp, b, min_time=0.05, repeats=2)
    bw = 2.0 * 4 * m / max(t_cp, 1e-9)   # read + write

    # Emulated-device "links" are memcpy through the same memory system.
    return Profile(
        name=f"calibrated:{platform}",
        peak_flops=flops, hbm_bw=bw, link_bw=bw,
        mfu_eff=mfu_eff, mbu_eff=1.0, thin_flops=thin,
        source="calibrated",
    )


def current_profile(device: Any = None) -> Profile:
    """The Profile for the live backend: table entry when the device
    kind is known, live calibration otherwise."""
    import jax

    device = device or jax.devices()[0]
    kind = getattr(device, "device_kind", "cpu")
    prof = _TPU_PROFILES.get(kind)
    if prof is not None:
        return prof
    return calibrate(str(kind))


def table_profile(kind: str) -> Profile:
    """The seeded profile for ``kind`` (e.g. ``"TPU v5 lite"``), for
    pricing a trace on hardware OTHER than the live backend — case24
    prices its mis-sharding on a v5e while running on emulated CPU
    devices. Raises ``KeyError`` for unknown kinds."""
    return _TPU_PROFILES[kind]


# ---------------------------------------------------------------------------
# Pricing
# ---------------------------------------------------------------------------

#: Ring wire-volume factor per collective op: transferred bytes =
#: factor(n) × buffer bytes, n = axis size (2211.05322 Table 1).
def _ring_factor(op: str, n: int) -> float:
    if n <= 1 or op == "slice":
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op in ("all-gather", "reduce-scatter", "all-to-all"):
        return (n - 1) / n
    if op == "collective-permute":
        return 1.0
    return 1.0


def quantized_variant(
    ev: CommEvent, *, itemsize: int = 4, block: int = 32
) -> CommEvent:
    """The int8 block-scaled wire form of one predicted reduce event —
    what ``parallel/compression.py``'s codec would actually put on the
    link: int8 payloads plus one fp32 scale per ``block`` elements, so
    ``bytes × wire_scale(itemsize, block)`` (≈ 0.28 × for fp32 inputs,
    a 3.6× wire reduction). The semantic event is unchanged — same
    axes, same cause, same realization ops — only the wire weight
    moves, which is exactly how the engine's quantized TP matmul and
    the ZeRO-1 int8 ring behave."""
    from learning_jax_sharding_tpu.parallel.compression import wire_scale

    return dataclasses.replace(
        ev,
        bytes=int(math.ceil(ev.bytes * wire_scale(itemsize, block))),
        reason=ev.reason + " [int8 block-scaled wire]",
    )


def _quantizable(ev: CommEvent, axes: set[str]) -> bool:
    # The codec seams the stack actually ships quantize REDUCTIONS (the
    # ZeRO ring, the TP matmul's all-reduce site): pure data movement
    # (permutes, reshars gathers) has cheap exact alternatives and the
    # searchable move stays honest by not claiming them.
    return bool(
        ev.realizations
        and ev.realizations[0][0] in ("all-reduce", "reduce-scatter")
        and set(ev.axes) & axes
        and "[int8 block-scaled wire]" not in ev.reason
    )


def quantize_events(
    events: list, axes: Iterable[str], *, itemsize: int = 4,
    block: int = 32,
) -> list:
    """Re-weight a predicted multiset as if every reduce-family event
    touching one of ``axes`` ran through the int8 codec. Non-reduce
    events and other axes pass through untouched — this is the
    transform behind the layout search's "quantize this axis's
    collective" move."""
    q = set(axes)
    return [
        quantized_variant(ev, itemsize=itemsize, block=block)
        if _quantizable(ev, q) else ev
        for ev in events
    ]


def codec_overhead_s(
    events: list, axes: Iterable[str], profile: Profile, *,
    block: int = 32,
) -> float:
    """Seconds of elementwise codec work the quantized variants add:
    quantize before the wire and dequantize after are each a read+write
    pass over the raw buffer, ≈ 4 × raw bytes of HBM traffic per
    quantized event (× trip in loops). Charged against the profile's
    achieved HBM rate — on hosts where the "link" IS memory bandwidth
    (the CPU tier-1 environment) this is what makes flat pricing
    honestly DECLINE quantization: the codec passes cost more than the
    wire they save."""
    q = set(axes)
    t = 0.0
    for ev in events:
        if not _quantizable(ev, q):
            continue
        trip = (ev.trip or 1) if ev.in_loop else 1
        t += trip * (4.0 * ev.bytes) / max(
            profile.hbm_bw * profile.mbu_eff, 1.0
        )
    return t


def _axis_alpha_beta(
    profile: Profile, axes: tuple[str, ...]
) -> tuple[float, float] | None:
    """Combined (α, β) when EVERY event axis has a measured profile:
    latencies add across axes (sequential phases), bandwidth is the
    slowest link. None when any axis is uncalibrated — the event then
    falls back to the flat ``link_bw`` table path."""
    if not profile.axis_profiles or not axes:
        return None
    table = {a: (al, be) for a, al, be in profile.axis_profiles}
    alpha = 0.0
    beta = math.inf
    for a in axes:
        ab = table.get(a)
        if ab is None:
            return None
        alpha += ab[0]
        beta = min(beta, ab[1])
    return alpha, beta


def price_event(
    ev: CommEvent, profile: Profile, mesh_sizes: dict[str, int]
) -> float:
    """Seconds of wire time for one predicted event (× trip in loops).

    With measured ``axis_profiles`` attached (commscope calibration) the
    event's axes price as ``α + wire_bytes / β``; otherwise the flat
    pinned ``link_bw`` divides the wire bytes as before. Zero-wire
    events (axis size 1, reshard slices) stay free either way — no
    collective runs, so no α is paid."""
    t = 0.0
    for (op, _ax) in ev.realizations[:1]:
        n = 1
        for a in ev.axes:
            n *= mesh_sizes.get(a, 1)
        wire = ev.bytes * _ring_factor(op, n)
        if wire <= 0:
            t = 0.0
            continue
        ab = _axis_alpha_beta(profile, ev.axes)
        if ab is not None:
            t = ab[0] + wire / max(ab[1], 1.0)
        else:
            t = wire / max(profile.link_bw, 1.0)
    return t * ((ev.trip or 1) if ev.in_loop else 1)


def calibrate_axis_profiles(
    measurements: Iterable[dict] | Any,
    base: Profile | None = None,
) -> Profile:
    """Fold measured commscope data into a pricing profile.

    ``measurements`` is either the raw ladder record list
    (``telemetry.commscope.run_ladder`` output — the α–β fit runs here)
    or an already-fitted ``telemetry.commscope.CommProfile``. Returns a
    copy of ``base`` (default: the live backend's profile) with
    ``axis_profiles`` attached; everything else — including the pinned
    ``link_bw`` fallback for uncalibrated axes — is preserved.
    """
    from learning_jax_sharding_tpu.telemetry import commscope

    if base is None:
        base = current_profile()
    if isinstance(measurements, commscope.CommProfile):
        axis_ab = measurements.axis_alpha_beta()
    else:
        fitted = commscope.fit_axis_profiles(measurements)
        axis_ab = tuple(
            (a, p.alpha_s, p.beta_bytes_per_s)
            for a, p in sorted(fitted.items())
        )
    return dataclasses.replace(
        base, axis_profiles=axis_ab, source=base.source + "+commscope",
    )


def price_event_topo(
    ev: CommEvent,
    profile: Profile,
    mesh_sizes: dict[str, int],
    topology: TopologyProfile,
) -> tuple[float, float, bool]:
    """Tier-aware serial price for one predicted event: ``(seconds,
    wire_bytes, is_dcn)``, both × trip for in-loop events.

    The event's axes price under the TOPOLOGY's α–β (latencies add,
    bandwidth is the slowest link — a ring with one DCN hop moves at
    DCN speed); an event with any untagged axis falls back to the flat
    :func:`price_event` path and stays in the ICI bucket, so an
    untagged mesh prices exactly as the flat model. ``is_dcn`` marks
    events whose ring crosses a DCN boundary — the bytes the topo pass
    audits and the layout search minimizes."""
    t = 0.0
    wire_total = 0.0
    is_dcn = False
    for (op, _ax) in ev.realizations[:1]:
        n = 1
        for a in ev.axes:
            n *= mesh_sizes.get(a, 1)
        wire = ev.bytes * _ring_factor(op, n)
        if wire <= 0:
            t = 0.0
            wire_total = 0.0
            continue
        wire_total = wire
        ab = topology.alpha_beta(ev.axes)
        if ab is not None:
            is_dcn = topology.bucket(ev.axes) == TIER_DCN
            t = ab[0] + wire / max(ab[1], 1.0)
        else:
            ab_flat = _axis_alpha_beta(profile, ev.axes)
            if ab_flat is not None:
                t = ab_flat[0] + wire / max(ab_flat[1], 1.0)
            else:
                t = wire / max(profile.link_bw, 1.0)
    trip = (ev.trip or 1) if ev.in_loop else 1
    return t * trip, wire_total * trip, is_dcn


@dataclasses.dataclass(frozen=True)
class TopoMultisetPrice:
    """A tier-bucketed, overlap-discounted collective multiset price.

    ``serial_s`` is what the flat model would bill under the tier-
    correct α–β (every event end to end); ``collective_s`` is the
    EXPOSED time after the realized-overlap discount — the number that
    lands in a step-time prediction. Per-tier seconds/bytes carry the
    split the gates consume (``dcn_bytes`` is the metric a hierarchy-
    aware layout search drives down)."""

    collective_s: float
    serial_s: float
    ici_s: float
    dcn_s: float
    ici_bytes: float
    dcn_bytes: float
    overlap_ratio: float | None
    aborted: bool = False

    @property
    def wire_bytes(self) -> float:
        return self.ici_bytes + self.dcn_bytes

    def to_dict(self) -> dict:
        return {
            "collective_s": self.collective_s,
            "serial_s": self.serial_s,
            "ici_s": self.ici_s,
            "dcn_s": self.dcn_s,
            "ici_bytes": self.ici_bytes,
            "dcn_bytes": self.dcn_bytes,
            "overlap_ratio": self.overlap_ratio,
            "aborted": self.aborted,
        }


def price_multiset_topo(
    events: list,
    profile: Profile,
    mesh_sizes: dict[str, int],
    *,
    topology: TopologyProfile,
    overlap_ratio: float | None = None,
    abort_above: float | None = None,
) -> TopoMultisetPrice:
    """The topology/overlap mode of :func:`price_multiset`: every event
    priced under its axes' TIER α–β, bucketed ICI vs DCN, and the
    exposed total discounted by the program family's measured realized-
    overlap ratio (``exposed = (1 − r) × serial``, applied per event so
    ``abort_above`` prunes on the same quantity the caller compares).
    ``overlap_ratio=None`` bills serial — the honest upper bound when
    no measurement exists. Memoized alongside the flat path; the
    topology's :meth:`~.topology.TopologyProfile.key` and the discount
    join the memo key, so a re-tagged axis or a new overlap table can
    never serve stale prices."""
    r = 0.0 if overlap_ratio is None else min(max(overlap_ratio, 0.0), 1.0)
    key_base = (
        profile.name, profile.link_bw, profile.axis_profiles,
        tuple(sorted(mesh_sizes.items())), topology.key(),
    )
    exposed = serial = 0.0
    ici_s = dcn_s = 0.0
    ici_b = dcn_b = 0.0
    for ev in events:
        trip = (ev.trip or 1) if ev.in_loop else 1
        key = key_base + (
            ev.realizations[:1], ev.axes, int(ev.bytes), trip,
        )
        row = _MULTISET_MEMO.get(key)
        if row is None:
            if len(_MULTISET_MEMO) >= _MULTISET_MEMO_MAX:
                _MULTISET_MEMO.clear()
            row = _MULTISET_MEMO[key] = price_event_topo(
                ev, profile, mesh_sizes, topology,
            )
        t, wire, is_dcn = row
        serial += t
        exposed += t * (1.0 - r)
        if is_dcn:
            dcn_s += t
            dcn_b += wire
        else:
            ici_s += t
            ici_b += wire
        if abort_above is not None and exposed > abort_above:
            return TopoMultisetPrice(
                exposed, serial, ici_s, dcn_s, ici_b, dcn_b,
                overlap_ratio, aborted=True,
            )
    return TopoMultisetPrice(
        exposed, serial, ici_s, dcn_s, ici_b, dcn_b, overlap_ratio,
    )


#: Per-(op, axes, bytes, trip) wire-seconds memo for :func:`price_multiset`,
#: additionally keyed by (profile name, link bandwidth, mesh sizes) so a
#: calibrated profile or a different mesh can never serve stale prices.
#: Bounded: distinct keys are few (one per distinct event shape), but a
#: long-lived search session gets a hard cap instead of unbounded growth.
_MULTISET_MEMO: dict[tuple, float] = {}
_MULTISET_MEMO_MAX = 65536


def price_multiset(
    events: list,
    profile: Profile,
    mesh_sizes: dict[str, int],
    *,
    abort_above: float | None = None,
    topology: TopologyProfile | None = None,
    overlap_ratio: float | None = None,
) -> tuple[float, float, bool]:
    """Batch-price a collective event multiset with memoized per-(op,
    axes, bytes, trip) pricing — the layout search's inner loop
    (``analysis.layout_search``) prices hundreds of candidate layouts
    whose events repeat the same few shapes, and re-deriving ring
    factors per candidate is pure waste. Term-exact: the total equals
    ``sum(price_event(ev, ...))`` bit-for-bit (same per-event products,
    same accumulation order; ``tests/test_shardflow.py`` pins this).

    Returns ``(collective_seconds, wire_bytes, aborted)``. With
    ``abort_above`` set, accumulation stops as soon as the partial sum
    exceeds it and ``aborted`` is True — the search's dominance prune: a
    candidate whose collective term alone already exceeds the incumbent's
    total step time cannot win, so the rest of its events go unpriced.

    **Topology/overlap mode** (round 21): with ``topology`` set, every
    event prices under its axes' TIER α–β and the total is the EXPOSED
    time after the ``overlap_ratio`` discount — the delegation target
    is :func:`price_multiset_topo`; use it directly when the ICI/DCN
    split matters. Flat callers are bit-identical to before.
    """
    if topology is not None:
        tp = price_multiset_topo(
            events, profile, mesh_sizes, topology=topology,
            overlap_ratio=overlap_ratio, abort_above=abort_above,
        )
        return tp.collective_s, tp.wire_bytes, tp.aborted
    key_base = (
        profile.name, profile.link_bw, profile.axis_profiles,
        tuple(sorted(mesh_sizes.items())),
    )
    total = 0.0
    for ev in events:
        trip = (ev.trip or 1) if ev.in_loop else 1
        key = key_base + (
            ev.realizations[:1], ev.axes, int(ev.bytes), trip,
        )
        t = _MULTISET_MEMO.get(key)
        if t is None:
            if len(_MULTISET_MEMO) >= _MULTISET_MEMO_MAX:
                _MULTISET_MEMO.clear()
            t = _MULTISET_MEMO[key] = price_event(ev, profile, mesh_sizes)
        total += t
        if abort_above is not None and total > abort_above:
            return total, total * profile.link_bw, True
    return total, total * profile.link_bw, False


@dataclasses.dataclass
class PredictedCost:
    """A priced shardflow report: the three roofline terms and the
    modelled step time / MFU for one entry point."""

    name: str
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    hbm_bytes: float
    wire_bytes: float
    profile: Profile
    n_dev: int = 1

    @property
    def predicted_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def bound(self) -> str:
        best = max(
            ("compute", self.compute_s),
            ("memory", self.memory_s),
            ("collective", self.collective_s),
            key=lambda kv: kv[1],
        )
        return best[0]

    @property
    def predicted_mfu(self) -> float:
        """Standard per-chip MFU: whole-program FLOPs over
        time × chips × per-chip peak."""
        if self.predicted_s <= 0 or self.profile.peak_flops <= 0:
            return 0.0
        return self.flops / (
            self.predicted_s * max(1, self.n_dev) * self.profile.peak_flops
        )

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "predicted_s": self.predicted_s,
            "bound": self.bound,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "wire_bytes": self.wire_bytes,
            "predicted_mfu": self.predicted_mfu,
            "profile": self.profile.name,
        }


def price(
    report: ShardflowReport,
    profile: Profile | None = None,
) -> PredictedCost:
    """Price one shardflow report on ``profile`` (default: live backend).

    FLOPs/bytes in the report are whole-program; both are per-device
    already (shard factors divided out during propagation), so each
    roofline term is a per-device time and the max is the step estimate.
    """
    if profile is None:
        profile = current_profile()
    mesh_sizes = dict(zip(report.mesh_axes, report.mesh_shape))
    n_dev = max(1, math.prod(report.mesh_shape))
    coll, wire, _ = price_multiset(report.events, profile, mesh_sizes)
    # FLOPs are whole-program; per-device share under SPMD is /n_dev.
    # Thin (GEMV-regime) dots get their own achieved rate — the two
    # kernel populations run serially within a step, so the terms add.
    thin = min(report.flops_thin, report.flops)
    thin_rate = profile.thin_flops or (profile.peak_flops * profile.mfu_eff)
    compute = ((report.flops - thin) / n_dev) / max(
        profile.peak_flops * profile.mfu_eff, 1.0
    ) + (thin / n_dev) / max(thin_rate, 1.0)
    memory = report.hbm_bytes / max(profile.hbm_bw * profile.mbu_eff, 1.0)
    return PredictedCost(
        name=report.name,
        compute_s=compute,
        memory_s=memory,
        collective_s=coll,
        flops=report.flops,
        hbm_bytes=report.hbm_bytes,
        wire_bytes=wire,
        profile=profile,
        n_dev=n_dev,
    )


@dataclasses.dataclass
class TopoPredictedCost:
    """An overlap-aware, hierarchy-priced step estimate.

    The flat model takes ``max(compute, memory, collective)`` — right
    when comm fully hides OR fully dominates, wrong in between. The
    overlap-aware form follows the round-19 ledger's decomposition
    (``decompose_overlap``: device = compute + exposed + overlapped):
    the overlapped share of the collective serial time hides under the
    compute/memory roofline, the EXPOSED share adds on top —

        ``predicted_s = max(compute_s, memory_s) + exposed collective``

    With no measured overlap ratio the exposed share is the full
    serial time, which upper-bounds the flat max — never optimistic.
    """

    name: str
    compute_s: float
    memory_s: float
    comm: TopoMultisetPrice
    flops: float
    hbm_bytes: float
    profile: Profile
    topology: TopologyProfile
    n_dev: int = 1

    @property
    def predicted_s(self) -> float:
        return max(self.compute_s, self.memory_s) + self.comm.collective_s

    @property
    def serial_predicted_s(self) -> float:
        """The flat combination under tier-correct α–β — what this
        topology costs WITHOUT the overlap discount."""
        return max(self.compute_s, self.memory_s, self.comm.serial_s)

    @property
    def bound(self) -> str:
        best = max(
            ("compute", self.compute_s),
            ("memory", self.memory_s),
            ("collective", self.comm.serial_s),
            key=lambda kv: kv[1],
        )
        return best[0]

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "predicted_s": self.predicted_s,
            "serial_predicted_s": self.serial_predicted_s,
            "bound": self.bound,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.comm.collective_s,
            "collective_serial_s": self.comm.serial_s,
            "ici_s": self.comm.ici_s,
            "dcn_s": self.comm.dcn_s,
            "ici_bytes": self.comm.ici_bytes,
            "dcn_bytes": self.comm.dcn_bytes,
            "overlap_ratio": self.comm.overlap_ratio,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "profile": self.profile.name,
            "topology": self.topology.name,
        }


def price_topo(
    report: ShardflowReport,
    profile: Profile | None = None,
    *,
    topology: TopologyProfile,
    overlap_ratio: float | None = None,
) -> TopoPredictedCost:
    """Price one shardflow report under a two-tier topology with the
    overlap-aware combination. ``overlap_ratio=None`` consults the
    topology's own per-family table (keyed by the report name, then
    ``"_default"``); pass an explicit ratio to override — the topo
    pass feeds the ledger's measured per-family ratio here."""
    if profile is None:
        profile = current_profile()
    if overlap_ratio is None:
        overlap_ratio = topology.overlap_ratio(report.name)
    mesh_sizes = dict(zip(report.mesh_axes, report.mesh_shape))
    n_dev = max(1, math.prod(report.mesh_shape))
    comm = price_multiset_topo(
        report.events, profile, mesh_sizes, topology=topology,
        overlap_ratio=overlap_ratio,
    )
    thin = min(report.flops_thin, report.flops)
    thin_rate = profile.thin_flops or (profile.peak_flops * profile.mfu_eff)
    compute = ((report.flops - thin) / n_dev) / max(
        profile.peak_flops * profile.mfu_eff, 1.0
    ) + (thin / n_dev) / max(thin_rate, 1.0)
    memory = report.hbm_bytes / max(profile.hbm_bw * profile.mbu_eff, 1.0)
    return TopoPredictedCost(
        name=report.name,
        compute_s=compute,
        memory_s=memory,
        comm=comm,
        flops=report.flops,
        hbm_bytes=report.hbm_bytes,
        profile=profile,
        topology=topology,
        n_dev=n_dev,
    )


def compare(predicted_s: float, measured_s: float) -> dict:
    """The bench-gate record: signed + absolute error of the model
    against a measured step time."""
    err = (predicted_s - measured_s) / max(measured_s, 1e-12)
    return {
        "predicted_ms": predicted_s * 1e3,
        "measured_ms": measured_s * 1e3,
        "err_pct": abs(err) * 100.0,
        "signed_err_pct": err * 100.0,
    }


def rank_events(
    report: ShardflowReport,
    profile: Profile | None = None,
    top: int = 5,
) -> list[dict]:
    """The priciest predicted collectives, for the --explain report and
    case24's "this line costs you X ms" demo."""
    if profile is None:
        profile = current_profile()
    mesh_sizes = dict(zip(report.mesh_axes, report.mesh_shape))
    rows = []
    for ev in report.events:
        t = price_event(ev, profile, mesh_sizes)
        rows.append({
            "where": ev.where,
            "op": ev.realizations[0][0] if ev.realizations else "?",
            "axis": "+".join(ev.axes),
            "bytes": ev.bytes,
            "trip": ev.trip,
            "seconds": t,
            "reason": ev.reason,
        })
    rows.sort(key=lambda r: -r["seconds"])
    return rows[:top]
