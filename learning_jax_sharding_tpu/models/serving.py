"""Continuous batching: a PERSISTENT engine serving requests over time.

The last piece of serving realism the rectangular stack could not express
(after ragged batches, round 3): a REQUEST QUEUE served through a fixed
batch of cache slots, where a finished row's slot is immediately refilled
with the next queued prompt instead of idling until the whole batch
drains. The reference has no inference path at all (SURVEY.md §5); this is
the engine loop that production serving runs.

Round 5 makes the engine PERSISTENT (``ContinuousEngine``): the compiled
programs, the KV cache, the paged page pool, and the prefix-cache
registry all live on the engine OBJECT, not inside a ``serve()`` call —
so a second call re-prefills nothing it already holds (prefix hits span
calls and sessions), allocates nothing (the cache-creating first refill
runs once per engine, ever), and requests can be ADMITTED OVER TIME
(``add_request`` / ``step``) instead of only as a one-shot queue. The
engine also measures what production engines measure: per-request TTFT,
per-token latency (TPOT), and inter-token gaps (ITL), with p50/p99.

TPU-shaped design — the host drives, the device stays static:

* two steady-state compiled programs serve any workload — ``refill_step``
  (a fixed ``(B, refill_chunk)`` array of CHUNK ROWS; a row is one chunk
  of one slot's prompt: the slot it belongs to (``rows``), how far past
  the slot's consumed tokens it starts (``offsets``) and its valid length
  (the ragged ``chunk_lengths``), so any mix of fresh prompts, several
  consecutive chunks of one long prompt, and unused rows shares one
  executable) and ``decode_block`` (K tokens per active row, scanned on
  device) — plus the one-shot cache-creating first refill (once per
  ENGINE, not per call);
* admission is a pure cache-index RESET (per-slot counters zero; stale K/V
  beyond a slot's new index is invisible to the causal-at-index masks and
  overwritten as the new request advances) — no cache clearing, no
  reallocation;
* a refill dispatch PACKS CHUNKS, NOT SLOTS: every slot with prompt left
  takes its next chunk in its own row; on a paged engine the rows nobody
  refills then carry FURTHER consecutive chunks of those prompts, fewest
  chunks left first. Rows of one slot share its block table: every layer
  scatters the chunks' K,V into the page pool and then attends through
  the table, each row causal at its own index, so the second row reads in
  each layer what the first wrote there — the mathematics of two
  dispatches in one. A contiguous ``(B, L, ...)`` cache owns its rows and
  keeps one row a slot (``rows = arange(B)``, ``offsets = 0``), so there a
  prompt longer than ``refill_chunk`` streams through several dispatches;
* decoding slots keep their state while others refill (their counters
  are not touched; their rows ride with length 0 or carry another slot's
  chunk) and resume on the next decode block — the batch never DRAINS to
  admit work, though decoding pauses for the refill dispatches themselves;
* rows freeze IN-SCAN at their generation budget (a per-row ``remaining``
  counter carried through the decode block), so a retired row's
  ``cache_index`` can never advance past ``prompt + max_new_tokens`` —
  the cache-capacity invariant holds on device, not just in host
  bookkeeping;
* SPECULATIVE decoding (``draft_config``): each decode-block step drafts
  ``num_draft`` tokens with the draft model, verifies them in ONE target
  chunk, and accepts PER-ROW — rollback rewinds each row's own
  ``cache_index`` (``models/speculative.py``'s ragged machinery inside
  the engine), so one round emits 1..num_draft+1 tokens per row and the
  block returns per-row counts. With ``temperature > 0`` the block runs
  speculative SAMPLING (Leviathan rejection) whose per-request rejection
  streams are keyed by (request id, generated position, stream tag) —
  sampled speculative outputs are schedule-independent like every other
  engine mode.

* MIXED scheduling (``mixed=True``, round 9): one FUSED program per
  iteration advances all decoding rows by one token AND pushes a
  token-budgeted refill chunk for admitting/streaming rows (refill rows
  ride their ragged ``chunk_lengths``, decode rows ride with length 1) —
  decode never stalls behind another slot's prefill, and admission lands
  at chunk granularity on every dispatch instead of at decode-block
  boundaries.

Oracles (test-pinned): under GREEDY decoding every request's output is
bit-identical to a rectangular single-prompt ``make_generate_fn`` run —
slot reuse, chunk scheduling, speculation, and engine persistence change
throughput, never results. With ``temperature > 0`` every sampling draw
is keyed by (REQUEST id, generated position), so a request's sampled
stream is reproducible across schedules too: the same queue served with
any batch size, arrival order, or slot assignment yields the same tokens
per request (given the same ``rng``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import time
from collections import Counter, deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from learning_jax_sharding_tpu.models.decoding import (
    apply_dequantize_policy,
    check_sequence_budget,
    derive_decode_config,
    make_cached_apply,
    make_param_caster,
)
from learning_jax_sharding_tpu.models.attention import resolve_decode_backend
from learning_jax_sharding_tpu.models.engine_programs import (
    _PAGE_LEAF_KEYS,
    _SLOT_STATE_KEYS,
    Program,
    _is_table,
    build_drift_probe,
    build_programs,
    merge_cache,
    split_cache,
)
from learning_jax_sharding_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
)
from learning_jax_sharding_tpu.parallel.compression import (
    CommCompression,
    get_codec,
    make_compressed_matmul_fn,
)
from learning_jax_sharding_tpu.parallel.logical import Rules, activate
from learning_jax_sharding_tpu.robustness.chaos import InjectedFault, chaos_hook
from learning_jax_sharding_tpu.telemetry import (
    GoodputLedger,
    MetricsRegistry,
    Tracer,
)
from learning_jax_sharding_tpu.telemetry.compile_watch import cache_size
from learning_jax_sharding_tpu.telemetry.registry import labeled_name

#: Dispatch failures the engine RECOVERS from (quarantine/requeue)
#: instead of propagating: the chaos harness's injected faults and the
#: NaN-trap FloatingPointError a checking()-style dispatch raises. Real
#: infrastructure errors (OOM, XLA internal) still propagate — recovery
#: must never guess.
_RECOVERABLE_DISPATCH = (InjectedFault, FloatingPointError)

#: The phases of the request clock (``ContinuousEngine._tick``): every
#: second of a request between its admission and its retirement is in
#: exactly one, by what the dispatch whose readback just returned gave
#: it. Before its first token: ``refill`` (the dispatch carried at least
#: one of its chunk rows) or ``refill_wait`` (none). Holding one:
#: ``decode`` (the dispatch gave it tokens) or ``stall`` (nothing: the
#: split engine's refill dispatch). ``queue`` is in front of them
#: (``queue_wait``, ``requeue_wait_s``); the seconds of an admission that
#: a preemption threw away move to a further series, ``redone``.
#: Pinned by ``tests/test_engine_spans.py``.
_PHASES = ("refill_wait", "refill", "stall", "decode")
_REFILL_WAIT, _REFILL, _STALL, _DECODE, _REDONE = range(5)

def _dispatch_span(kind):
    """Wrap a dispatch method in the tracer span ``engine.<kind>``. The
    event is kept only when the method dispatched a program of that
    kind: not when nothing ran, a fault cut it short, or
    ``_mixed_dispatch`` fell through to a split program (which wrote its
    own span)."""
    name = f"engine.{kind}"

    def deco(fn):
        @functools.wraps(fn)
        def dispatch(self, params, d_params, retired):
            with self.tracer.span(name, keep=False) as sp:
                ran = fn(self, params, d_params, retired)
                if ran is True or ran == kind:
                    sp.keep = True
                    sp.args["retired"] = len(retired)
            return ran

        return dispatch

    return deco


class AdmissionError(RuntimeError):
    """Admission control rejected the request (bounded queue full, or
    the degradation ladder reached its shedding level). The caller
    should back off / retry elsewhere — nothing was enqueued."""


@dataclasses.dataclass
class RequestFailure:
    """A request that retired WITHOUT completing, surfaced through
    ``pop_finished`` so failures are a terminal status, never a silent
    drop. ``tokens`` carries the partial ``[prompt, generated...]``
    output when the request had been admitted (None when it failed in
    the queue). Status ``"rerouted"`` is terminal only for THIS engine:
    the fleet router drained the request for failover/handoff and will
    recompute it bit-identically on another replica — visible here so a
    failover never masquerades as a fresh admission."""

    rid: int
    status: str              # deadline|poisoned|malformed|shutdown|rerouted
    error: str | None = None
    tokens: np.ndarray | None = None


@dataclasses.dataclass
class _Request:
    """Host bookkeeping for one request, from arrival to retirement."""

    rid: int
    prompt: np.ndarray
    arrival_t: float
    admit_t: float | None = None
    first_token_t: float | None = None
    finish_t: float | None = None
    tokens: np.ndarray | None = None      # final [prompt, generated...]
    status: str = "ok"    # or deadline|poisoned|malformed|shutdown|rerouted
    error: str | None = None
    deadline_s: float | None = None       # per-request TTL override
    strikes: int = 0                      # dispatch faults while admitted
    version: int = 0                      # weights version pinned at admission
    adapter: str | None = None            # AdapterPool tenant (None = base)
    enqueue_t: float | None = None        # when THIS engine queued it (a
    #                                       rerouted request keeps its fleet
    #                                       arrival_t but re-enqueues here)
    ingested: bool = False                # admitted via kv_ingest: the prefill
    #                                       happened on another replica
    tenant: str | None = None             # cost-attribution / SLO label
    # The request clock's books of what a preemption threw away: the
    # seconds of its abandoned admissions, the instant of the last
    # preemption while it waits in the queue, the seconds of such waits.
    redone_s: float = 0.0
    preempt_t: float | None = None
    requeue_wait_s: float = 0.0


class ContinuousEngine:
    """A persistent continuous-batching engine.

    Construction compiles the engine's programs and validates the
    configuration; the returned object then serves any number of
    workloads through TWO entry styles:

    * **one-shot**: ``engine.serve(params, prompts, rng=..., draft_params=...)``
      — drain a whole queue, return outputs in queue order (the original
      ``make_continuous_engine`` contract, bit-identity oracles intact);
    * **streaming**: ``engine.add_request(prompt)`` at any time (an
      arrival process), ``engine.step(params, ...)`` to run ONE scheduler
      iteration (admission + one refill or decode dispatch), and
      ``engine.pop_finished()`` to collect completed requests — the shape
      a serving frontend drives.

    What persists across calls (the round-5 redesign — previously all of
    this was rebuilt per ``serve()`` call):

    * the compiled programs AND the KV cache — the cache-creating first
      refill runs once per engine ever (``engine.cache_creations`` counts
      it, test-pinned at 1 across calls);
    * the paged page pool and its allocator;
    * the PREFIX-CACHE registry/refcounts/LRU — a request in a later
      ``serve()`` call (or streaming session) whose prompt starts with a
      previously retired prompt's page-aligned prefix is admitted with
      those pages already mapped: the shared-system-prompt workload this
      feature exists for. NOTE the registry keys pages by TOKEN BYTES
      only: it assumes the engine serves ONE fixed set of params. Call
      ``flush_prefix_cache()`` when swapping checkpoints.

    ``prompts`` entries are 1-D int32 arrays; each result is
    ``[prompt, generated...]`` — generation stops at ``eos_id`` (included)
    or after ``max_new_tokens``.

    ``batch_size`` fixes the device batch (cache slots); ``refill_chunk``
    fixes the admission chunk length (a longer prompt takes several chunk
    rows: of one refill call where a paged engine has rows to spare, of
    several calls otherwise); ``decode_block_steps`` fixes how many decode
    rounds each dispatch scans on device (the host loop pays one
    round-trip per block; rows freeze in-scan at EOS or at their budget,
    so a retired row's cache index never advances past
    ``prompt + max_new_tokens``). All are compile-time shapes: the whole
    engine runs on two executables regardless of queue size or length mix.

    ``draft_config``: enable SPECULATIVE decode blocks — a draft model
    proposes ``num_draft`` tokens per round, the target verifies them in
    one chunked forward, acceptance and cache rollback are PER-ROW. Pass
    the draft params as ``serve(..., draft_params=...)``. At
    ``temperature == 0`` output stays bit-identical to non-speculative
    greedy serving (test-pinned) — the draft changes only how many target
    dispatches the tokens cost. At ``temperature > 0`` the block runs
    speculative sampling (acceptance ``u·q < p``, residual draws from
    ``norm(max(p − q, 0))``) with draws keyed by (request id, generated
    position, stream tag): outputs follow the target's filtered sampling
    distribution and are schedule-independent, though not token-identical
    to non-speculative sampling (different draw structure).

    ``temperature > 0``: every draw is keyed by (request id, generated
    position) folded into ``rng`` — sampled outputs are reproducible
    across schedules (batch size, arrival order, slot assignment).
    ``serve()`` numbers requests by QUEUE INDEX per call (the pinned
    schedule-independence contract); streaming ``add_request`` assigns
    engine-global monotonic ids.

    ``decode_chain``: dispatch up to this many decode blocks (and refill
    chunks) BACK-TO-BACK, carrying tok/active/remaining device-to-device
    and syncing the host once per chain. Rows freeze on device at
    EOS/budget exactly as within one block, so chaining cannot change
    results (test-pinned). Rounds 1-5 measured this on a remotely
    attached chip that no longer exists
    (``scripts/perf_block_ladder.py``): there each jitted CALL cost
    ~120 ms in the dispatch itself, so the first-order decode lever was
    ``decode_block_steps`` (tokens per compiled program — 823 → 2,637
    tok/s from K=16 to K=128 on the standard queue; size K ≈
    max_new_tokens so rows retire at block boundaries); the dispatch
    cost of today's machine is not measured. Chaining stacks
    a further gain on decode (K=64 chain=2 > K=64) and is the MAIN
    lever for REFILL, whose chunk contents are host-known (long-prompt
    prefill 13.0k → 20.2k tok/s at S=4096). The cost of both is
    scheduling granularity: retirement/admission coarsen by up to a
    chain/block, and token-visibility telemetry (ITL) becomes
    chain-granular — size to the workload (throughput queues high,
    latency-sensitive arrivals low; ``decode_chain`` is a public
    attribute, tunable per phase at runtime).

    ``mixed=True``: the FUSED refill+decode scheduler (round 9). The
    split engine dispatches refill OR decode per iteration, so every
    decoding row pauses while another slot's prompt streams through
    refill chunks — measured at 86-87% of engine time on the 125M
    serving bench, the direct cause of its ITL p99 and queue-wait tails.
    The mixed engine runs ONE compiled program per iteration
    (``mixed_step`` / ``spec_mixed_step``) in which every decoding row
    advances one token (speculative: one draft-verify round with per-row
    rollback) AND pending prompts push refill chunks under
    ``token_budget`` — a per-dispatch token ceiling (decode rows funded
    first; refill takes the remainder; uncapped when nothing is
    decoding). Admission happens at EVERY dispatch, at chunk
    granularity. The two-steady-state-programs invariant holds — fixed
    ``(B, refill_chunk)`` shapes, no recompiles — and ``decode_chain``
    still carries device-to-device (each link is one mixed step, so a
    chain emits ``chain`` decode tokens per host sync). PURE-DECODE
    phases (no pending prompt tokens anywhere) fall through to the
    K-token ``decode_block`` — a fused link costs one dispatch per token
    and exists to overlap refill; with nothing to overlap, the scanned
    block's decode throughput wins and admission loses nothing (a queued
    request only rides out a block when every slot is busy). Greedy outputs
    stay bit-identical to the split engine (ragged rows are independent:
    each row's computation is exactly what the split programs run for
    it), and sampled streams are identical too (draws keyed by request
    id and position, never by schedule) — test-pinned. ``token_budget``
    is a public runtime-tunable attribute like ``decode_chain``: size it
    to the per-dispatch latency you can afford between decode tokens
    (see PERF.md round 9 for the measured ladder).

    ``dequantize``: serve QUANTIZED target weights, exactly as
    ``make_generate_fn`` does — ``True`` for an int8/int4 tree from
    ``quantize_tree`` dequantized inside the jitted steps, ``"fused"`` /
    ``"fused_w4a8"`` for an int4 tree streamed through the fused
    dequant-matmul kernels (whole-FF + q/k/v on single-device serving; an
    injected shard_map matmul under TP). ``draft_dequantize`` applies the
    same policy (``True`` → in-jit dequant) to the DRAFT tree — pass a
    quantized draft to ``serve(..., draft_params=...)``. Greedy engine
    outputs are bit-identical to the corresponding
    ``make_generate_fn(dequantize=...)`` single runs (test-pinned).

    ``paged_pages``: PAGED KV cache — each layer's K/V live in a physical
    pool of ``paged_pages`` pages of ``page_size`` tokens (page 0 is a
    reserved scratch target), indirected through per-row block tables
    that the host loop owns: pages are allocated on demand as a row's
    index approaches a page boundary and freed the moment the request
    retires, so cache HBM scales with tokens actually in flight instead
    of ``batch_size × max_seq_len`` — and slot count is no longer bounded
    by worst-case length. Requires the blocked decode backend. Outputs
    are bit-identical to the unpaged engine (test-pinned); the allocator
    raises if a dispatch would need more pages than the pool holds.
    ``prefix_cache`` (paged only): PREFIX CACHING — when a request
    retires, the pages fully covered by its prompt are RETAINED (keyed by
    their page-aligned token prefix) instead of freed; a later request
    whose prompt starts with the same tokens is admitted with those pages
    already in its block table and its counters set to the shared length,
    so the shared prefix is neither re-stored nor re-prefilled — both the
    HBM and the prefill compute are saved, ACROSS ``serve()`` calls.
    Sharing is all-or-nothing per page, capped at ``len(prompt) - 1`` (the
    last prompt token always recomputes: its logits seed generation), and
    reference-counted; retained pages with no references are evicted LRU
    when the allocator runs dry (chain tails strictly before their roots,
    across retirements), so the pool never shrinks. Outputs are
    bit-identical to the uncached engine (test-pinned): shared pages hold
    exactly the bytes the evicted computation wrote.

    After each ``serve`` call (and on demand via ``latency_stats()``):

    * ``last_stats`` — ``page_high_water`` / ``pages_total`` (paged — the
      LIVE footprint, excluding retained reference-free prefix pages,
      which are reported separately as ``prefix_pages_retained``),
      ``prefix_hits`` / ``prefix_pages_reused`` (prefix caching), and
      ``spec_accepted`` / ``spec_proposed`` / ``spec_accept_rate``
      (speculative — verifier acceptance before EOS/budget truncation,
      the number to tune ``num_draft`` against); ``None`` when none of
      the modes are on.
    * ``last_latency`` — per-request latency telemetry: ``ttft_p50/p99``
      (arrival → first generated token visible on the host),
      ``tpot_p50/p99`` (per-request mean inter-token time after the
      first), ``itl_p50/p99`` (raw host-visibility gaps — block-granular
      by design: tokens land ``decode_block_steps`` at a time),
      ``queue_wait_p50/p99`` (arrival → slot admission), and the request
      clock's split of the two (``_PHASES``): ``decode_per_token_p50/p99``
      + ``stall_per_token_p50/p99`` (a request's TPOT is the sum of its
      two; stall: seconds a token it held a first token and a dispatch
      gave it nothing) and ``refill_wait_p50/p99`` (admitted, waiting
      for a refill dispatch to take one of its chunks).

    TELEMETRY (round 6): the engine meters into a
    :class:`~learning_jax_sharding_tpu.telemetry.MetricsRegistry`
    (``engine.registry`` — counters/gauges/histograms with Prometheus
    text exposition; engine-local unless one is passed in, and passing a
    shared one makes the counters fleet totals while ``last_stats``
    windows then span every engine metering into it) and traces
    into a :class:`~learning_jax_sharding_tpu.telemetry.Tracer`
    (``engine.tracer`` — a per-request span timeline arrival → admit →
    first token → finish plus per-dispatch refill/decode spans,
    exportable as Perfetto-loadable Chrome trace JSON). ``last_stats``
    and ``last_latency`` are re-derived from the registry (window deltas
    over cumulative counters), so their shapes and values keep the
    pinned contract. ``compile_counts()`` reports per-program compile
    counts and ``collective_inventory()`` the per-dispatch collective
    ops from the compiled HLO.

    DIAGNOSIS (round 7): the engine feeds a flight recorder
    (``engine.recorder`` — process-wide default ring; arrival/admission/
    preemption/retirement/cache-creation events plus every tracer span
    closure when attached) whose ``dump_diagnostics()`` writes a
    post-mortem bundle; an optional ``slo=``
    :class:`~learning_jax_sharding_tpu.telemetry.SLOMonitor` receives
    TTFT/TPOT/ITL/queue-wait/e2e per retirement (streaming percentiles +
    burn-rate targets, exported through the engine registry); and
    ``collective_axis_volume()`` attributes each program's collective
    bytes to the mesh axes that carry them.

    RECOVERY (round 10): detection is wired to action —

    * ``deadline_s`` (engine default, per-request override on
      ``add_request``): a request older than its TTL is EVICTED with
      terminal status ``"deadline"`` — queued or mid-flight — and
      surfaced through ``pop_finished`` as a :class:`RequestFailure`
      (partial tokens included), never a silent drop.
    * ``max_queue``: bounded admission — an arrival past the bound is
      SHED (:class:`AdmissionError`, nothing enqueued), so backpressure
      reaches the frontend instead of growing an unbounded queue whose
      every entry will miss its SLO together.
    * ``degradation=``
      :class:`~learning_jax_sharding_tpu.robustness.DegradationLadder`
      (requires ``slo=``): the monitor's burn rate walks disable
      speculation → halve ``token_budget`` → shed admits, with
      hysteresis; every transition lands in the flight recorder and the
      ``engine_degradation_level`` gauge. De-escalation restores the
      knobs it took over.
    * poison quarantine (``max_dispatch_strikes``): a dispatch that
      raises a recoverable fault (injected NaN-trap/hang-watchdog abort
      — see :mod:`~learning_jax_sharding_tpu.robustness.chaos`) strikes
      every involved request; repeat offenders are FAILED
      (``"poisoned"``) and isolated, the rest are requeued and
      re-admitted one at a time (probation) so the poison trips alone —
      then recomputed exactly (the ``_unadmit`` recompute-preemption
      guarantee), so survivors' outputs are bit-identical to a
      fault-free run (test-pinned).
    * ``close()`` drains: every in-flight/queued request gets terminal
      status ``"shutdown"`` before the device state drops — callers
      polling ``pop_finished`` always terminate. Idempotent.

    FLEET (round 11): the engine is one REPLICA of a
    :class:`~learning_jax_sharding_tpu.fleet.FleetRouter` fleet —

    * ``drain_requests(status="rerouted")`` is the failover drain: every
      queued/in-flight request retires here with a ``"rerouted"``
      terminal status (``engine_rerouted_total``,
      ``latency_stats()["rerouted"]`` — a failover is visible, never
      disguised as fresh admissions) and returns requeueable records
      that RECOMPUTE BIT-IDENTICALLY on a survivor (the ``_unadmit``
      recompute guarantee: draws are keyed by (request id, position)).
    * ``export_kv`` / ``ingest_kv`` are the DISAGGREGATED handoff: a
      dedicated prefill engine (``max_new_tokens=1``) retires a request
      at its first token, its cache row streams to a decode engine
      through the explicit resharding transfer plan
      (``fleet.kv_transfer`` — host-plan bytes, no hidden XLA
      collectives: the ``kv_export``/``kv_ingest`` goldens pin both
      device programs), and the decode engine continues the stream
      bit-identically to a single engine of the same mesh shape.
      Unpaged, non-speculative engines only.

    * ``comm_compression=CommCompression(...)`` turns on the COMM
      COMPRESSION layer: the fused-step families compile the serving
      block's one TP all-reduce (the FF down projection) as a
      block-scaled int8 gather (~``1/itemsize`` of the wire bytes), and
      every counted host transfer — page spill/fill, disaggregated KV
      handoff via the fleet, cross-device-set swap staging — ships
      int8 (or delta-vs-base) blocks through the
      ``parallel.resharding`` codec seam, with wire AND raw bytes
      booked. A drift governor probes the compressed apply against a
      plain oracle every ``drift_check_every`` dispatches; breaching
      ``drift_budget`` trips a dedicated degradation ladder that
      disables compression and retraces every program back to the
      bit-identical plain contraction.
    """

    def __init__(
        self,
        config: TransformerConfig,
        mesh: Mesh,
        rules: Rules,
        *,
        batch_size: int,
        max_new_tokens: int,
        eos_id: Optional[int] = None,
        refill_chunk: int = 64,
        decode_block_steps: int = 16,
        decode_chain: int = 1,
        mixed: bool = False,
        token_budget: int | None = None,
        horizon: int = 1,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        min_p: float | None = None,
        vocab_limit: int | None = None,
        inference_dtype: Any | None = None,
        dequantize: bool | str = False,
        draft_config: Optional[TransformerConfig] = None,
        draft_dequantize: bool = False,
        num_draft: int = 4,
        paged_pages: Optional[int] = None,
        page_size: int = 64,
        prefix_cache: bool = False,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
        slo: Any | None = None,
        recorder: Any | None = None,
        deadline_s: float | None = None,
        max_queue: int | None = None,
        degradation: Any | None = None,
        max_dispatch_strikes: int = 2,
        adapter_pool: Any | None = None,
        comm_compression: Any | None = None,
    ):
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_dispatch_strikes < 1:
            raise ValueError(
                f"max_dispatch_strikes must be >= 1, got "
                f"{max_dispatch_strikes}"
            )
        if degradation is not None and slo is None:
            raise ValueError(
                "degradation needs slo=SLOMonitor(...): the ladder is "
                "driven by the monitor's burn rate"
            )
        if batch_size < 1 or refill_chunk < 1 or decode_block_steps < 1:
            raise ValueError(
                "batch_size, refill_chunk, decode_block_steps must be >= 1"
            )
        if decode_chain < 1:
            raise ValueError(f"decode_chain must be >= 1, got {decode_chain}")
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        if horizon > 1 and not mixed:
            raise ValueError(
                "horizon > 1 requires mixed=True: the multi-step scan "
                "fuses the MIXED iteration body (the split engine's "
                "decode_block already amortizes its loop on device)"
            )
        if token_budget is not None and not mixed:
            raise ValueError("token_budget requires mixed=True")
        if token_budget is not None and token_budget < 1:
            raise ValueError(
                f"token_budget must be >= 1, got {token_budget}"
            )
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if refill_chunk > config.max_seq_len:
            raise ValueError(
                f"refill_chunk ({refill_chunk}) exceeds max_seq_len "
                f"({config.max_seq_len})"
            )
        speculative = draft_config is not None
        if speculative:
            if num_draft < 1:
                raise ValueError(f"num_draft must be >= 1, got {num_draft}")
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"target vocab {config.vocab_size} != draft vocab "
                    f"{draft_config.vocab_size}"
                )
        if draft_dequantize and not speculative:
            raise ValueError("draft_dequantize requires draft_config")
        paged = paged_pages is not None
        if prefix_cache and not paged:
            raise ValueError(
                "prefix_cache requires the paged KV cache (paged_pages=N): "
                "sharing is expressed through block-table entries"
            )
        if adapter_pool is not None:
            # Multi-LoRA serving (round 12) composes with the FUSED
            # engine only: the adapter gather lives inside
            # ``adapter_mixed_step``, and every split-program fallback
            # (refill_step / decode_block) would run adapter rows
            # through the BASE weights.
            if not mixed:
                raise ValueError(
                    "adapter_pool requires mixed=True: adapters are "
                    "gathered per row inside the fused step"
                )
            if paged:
                raise ValueError(
                    "adapter_pool requires the unpaged cache: the per-row "
                    "vmapped apply maps over batch-major cache rows, which "
                    "the paged pool's page-major leaves do not have (the "
                    "AdapterPool does its own page-granular residency "
                    "accounting instead)"
                )
            if degradation is not None:
                raise ValueError(
                    "adapter_pool does not compose with degradation=: the "
                    "ladder's split-program fallbacks would serve adapter "
                    "rows with the base weights"
                )
        # Comm compression (this PR): quantized serving collectives +
        # compressed KV movement. ``True`` means the defaults; anything
        # else must be a ``CommCompression`` so the knobs are validated
        # in one place (its ``__post_init__``).
        comp = CommCompression() if comm_compression is True else comm_compression
        if comp is not None:
            if not isinstance(comp, CommCompression):
                raise ValueError(
                    "comm_compression must be True or a "
                    f"parallel.compression.CommCompression, got {comp!r}"
                )
            if comp.collectives and not mixed:
                raise ValueError(
                    "comm_compression with collectives=True requires "
                    "mixed=True: the quantized TP matmul is compiled into "
                    "the fused step families, and the drift governor "
                    "probes at fused-dispatch granularity"
                )

        # A latent-attention config caches ONE row [c_kv | k_rope] a token,
        # a dropless expert layer reports its counts with the split
        # programs' readbacks, and a state-space layer keeps a fixed-size
        # recurrent state a slot beside the pages: what has not been taught
        # each of them yet is refused here, by name, never served wrong.
        latent = bool(config.latent_kv_rank)
        moe_counted = (
            config.num_experts > 0 and config.moe_routing == "sigmoid_dropless"
        )
        ssm = "M" in (config.layer_pattern or "")
        # A reason a kind: the first kind the config has names the refusal.
        kinds = {
            "state-space layers": ssm, "latent attention": latent,
            "dropless experts": moe_counted,
        }
        if any(kinds.values()):
            what = next(k for k, has in kinds.items() if has)
            refused = {
                "draft_config (speculative decoding)": (
                    speculative,
                    "a rejected draft rewinds cache_index, and a recurrent "
                    "state has no rollback (no snapshot to return to)"
                    if ssm else
                    "rollback and the draft's lockstep cache assume K and V "
                    "per head",
                ),
                "prefix_cache": (
                    prefix_cache,
                    "a shared prefix page holds K and V only: the recurrent "
                    "state at the end of the prefix would have to be "
                    "snapshotted with it, and no snapshot exists yet"
                    if ssm else
                    "page copies and the registry were written for (P, "
                    "N_kv, page, 2H) pools and are untested over latent rows",
                ),
                "mixed / horizon": (
                    mixed, "the fused step families do not return the expert "
                    "counters, skip idle rows' latent attention or carry a "
                    "recurrent state from one chunk row to the next",
                ),
                "adapter_pool": (adapter_pool is not None, "it needs mixed=True"),
                "comm_compression": (comp is not None, "it needs mixed=True"),
                "dequantize": (
                    bool(dequantize), "the latent and state-space projections "
                    "and the expert matrices have no quantized form",
                ),
                "a mesh of more than one device": (
                    mesh.size > 1, "the latent row is one shared head (nothing "
                    "to split over KV heads), a recurrent state has no "
                    "sharded scan, and an expert layer computes the experts "
                    "it HOLDS (moe_held) without the exchange that gathers "
                    "the other chips' parts",
                ),
            }
            for name, (asked, why) in refused.items():
                if asked:
                    raise ValueError(
                        f"{name} is not supported with {what}: {why}"
                    )
        if ssm and paged and 1 < refill_chunk < config.ssm_conv_kernel - 1:
            raise ValueError(
                f"refill_chunk ({refill_chunk}) must cover the convolution's "
                f"{config.ssm_conv_kernel - 1} cached inputs: a chunk row "
                "that continues another starts from that row's last inputs"
            )

        def check_paged(name, c):
            # ONE copy of the paged preconditions, applied to the target and
            # (when speculative) the draft — their caches page side by side.
            if (
                not c.latent_kv_rank   # its one cached path IS the kernel
                and resolve_decode_backend(c.decode_attention) != "blocked"
            ):
                raise ValueError(
                    f"paged_pages requires the blocked decode backend for the "
                    f"{name} config (decode_attention='blocked', or 'auto' on "
                    f"TPU)"
                )
            if c.max_seq_len % page_size:
                raise ValueError(
                    f"{name} max_seq_len ({c.max_seq_len}) must be a multiple "
                    f"of page_size ({page_size})"
                )

        def pagedify(c):
            return dataclasses.replace(
                c, decode_paged=True, decode_page_count=paged_pages,
                decode_block_k=page_size,
            )

        if paged:
            if paged_pages < 2:
                raise ValueError(
                    "paged_pages must be >= 2 (page 0 is the scratch page)"
                )
            check_paged("target", config)
        cfg = derive_decode_config(
            config, inference_dtype, mesh=mesh, rules=rules
        )
        cfg = dataclasses.replace(cfg, decode_ragged=True)
        cfg, fused = apply_dequantize_policy(cfg, dequantize, mesh, rules)
        if paged:
            cfg = pagedify(cfg)
        if comp is not None and comp.collectives:
            # Compile the quantized TP all-reduce into every apply-family
            # program: the FF down projection — the serving block's one
            # all-reduce site — routes through the block-scaled int8
            # gather (``parallel.compression.make_compressed_matmul_fn``).
            # The injected fn reads ``comp.enabled`` at TRACE time, so a
            # drift-budget trip + cache clear retraces every program back
            # to the plain (bit-identical) contraction.
            cfg = dataclasses.replace(
                cfg,
                comm_compress_fn=make_compressed_matmul_fn(
                    mesh, rules, comp
                ),
            )
        model = Transformer(cfg)
        apply = make_cached_apply(
            model, dequantize=bool(dequantize) and not fused,
            dequant_dtype=cfg.param_dtype,
        )
        maybe_cast = make_param_caster(
            inference_dtype, dequantize=bool(dequantize)
        )
        d_cfg = None
        if speculative:
            if paged:
                check_paged("draft", draft_config)
            d_cfg = derive_decode_config(
                draft_config, inference_dtype, mesh=mesh, rules=rules
            )
            d_cfg = dataclasses.replace(d_cfg, decode_ragged=True)
            if paged:
                d_cfg = pagedify(d_cfg)
            # The draft may be served quantized too (`draft_dequantize` —
            # in-jit int8/int4 dequant, the non-fused policy: a draft is
            # small, the fused kernels' launch floor would dominate it).
            d_apply = make_cached_apply(
                Transformer(d_cfg), dequantize=draft_dequantize,
                dequant_dtype=d_cfg.param_dtype,
            )
            d_cast = make_param_caster(
                inference_dtype, dequantize=draft_dequantize
            )
        else:
            d_apply = None
            d_cast = maybe_cast

        comp_probe = None
        if comp is not None and comp.collectives:
            # Drift oracle: the SAME weights and cache served through a
            # plain-collective apply (``comm_compress_fn=None`` — same
            # param tree, since _CompressedDense declares the identical
            # down/kernel).
            comp_probe = build_drift_probe(apply, make_cached_apply(
                Transformer(
                    dataclasses.replace(cfg, comm_compress_fn=None)
                ),
                dequantize=bool(dequantize) and not fused,
                dequant_dtype=cfg.param_dtype,
            ))

        # --- engine configuration and compiled programs -------------------
        self._mesh, self._rules = mesh, rules
        self._cfg, self._d_cfg = cfg, d_cfg
        self._b = batch_size
        self._max_new = max_new_tokens
        self._eos = eos_id
        self._refill_chunk = refill_chunk
        self._block_steps = decode_block_steps
        # Public and runtime-tunable: a frontend can raise it for
        # throughput phases and drop it to 1 for latency-sensitive
        # arrival bursts (read at each dispatch).
        self.decode_chain = decode_chain
        self._mixed = bool(mixed)
        # Public and runtime-tunable like decode_chain: the per-dispatch
        # token ceiling of the MIXED scheduler (decode rows funded first,
        # refill takes the remainder). The default funds one full refill
        # chunk alongside a full decode wave; read at each dispatch.
        self.token_budget = (
            token_budget if token_budget is not None
            else refill_chunk + batch_size
        )
        # Public and runtime-tunable like decode_chain/token_budget: the
        # number of fused engine iterations ONE dispatch advances
        # (ROADMAP item 1). ``horizon=1`` IS today's loop — same
        # programs, same goldens, same telemetry counters (test-pinned);
        # ``horizon>1`` routes the steady-state mixed path through the
        # scanned ``multi_step`` family (one executable per horizon) and
        # demotes the host to the async boundary planner
        # (``_plan_next_horizon``). Read at each dispatch.
        self.horizon = horizon
        self._num_draft = num_draft
        self._speculative = speculative
        # Recovery policies (round 10): request TTLs, admission control,
        # the burn-rate degradation ladder, and poison quarantine.
        self._deadline_s = deadline_s
        self._any_req_deadline = False
        self._max_queue = max_queue
        self._ladder = degradation
        self._max_strikes = max_dispatch_strikes
        self._spec_disabled = False
        self._shed_all = False
        self._base_budget: int | None = None
        self._latent = latent
        self._moe_counted = moe_counted
        self._ssm = ssm
        self._paged = paged
        self._paged_pages = paged_pages
        self._page_size = page_size
        self._prefix = prefix_cache
        self._maybe_cast = maybe_cast
        self._d_cast = d_cast
        # THE table of step programs this engine's mode can dispatch
        # (family -> Program): every report of "which programs exist"
        # maps over it.
        self._programs = build_programs(
            # A one-mixer-a-layer config refills as a latent one does: the
            # head on one position a row, and no contiguous rows to hand off.
            apply, d_apply, adapter=adapter_pool is not None,
            head_on_last=latent or config.layer_pattern is not None,
            kv_rows=not (latent or config.layer_pattern is not None),
            moe_counted=moe_counted, mixed=bool(mixed), paged=paged,
            prefix_cache=prefix_cache, temperature=temperature, top_k=top_k,
            top_p=top_p, min_p=min_p, vocab_limit=vocab_limit,
            max_new_tokens=max_new_tokens, eos_id=eos_id,
            decode_block_steps=decode_block_steps, num_draft=num_draft,
        )
        # Comm compression: the validated config, the drift probe, and
        # the host-side KV codec every counted transfer threads through.
        # The drift ladder is a dedicated one-level DegradationLadder —
        # same hysteresis machinery as the SLO ladder (round 10), driven
        # by drift-rate burn instead of SLO burn; level 1 means the
        # budget is breached and compression turns itself off.
        self._comp = comp
        self._comp_probe_fn = comp_probe
        if comp is not None and comp.collectives:
            from learning_jax_sharding_tpu.robustness.policies import (
                DegradationLadder,
            )

            self._comp_ladder = DegradationLadder(patience=1, max_level=1)
        else:
            self._comp_ladder = None
        self._comp_n = 0
        self._kv_codec = (
            get_codec(comp.kv_codec, block=comp.block)
            if comp is not None else None
        )

        # --- persistent state ---------------------------------------------
        self.rng = jax.random.key(0)
        self.cache_creations = 0     # lifetime count of cache-creating calls
        self.last_stats: dict | None = None
        self.last_latency: dict | None = None
        self._cache = None
        self._queue: deque[_Request] = deque()
        self._finished: dict[int, _Request] = {}
        self._next_rid = 0
        self._cast_src: tuple | None = None
        self._cast_out: tuple | None = None
        # The async planner's staged next-horizon plan: (fingerprint,
        # plan) — consumed by the next _multi_dispatch only when the
        # boundary state still matches the prediction (see
        # _plan_next_horizon), so staging can never change results.
        self._staged_plan = None
        # Tenancy (round 12): zero-downtime weight hot-swap + multi-LoRA.
        # ``weights_version`` is pinned onto every request AT ADMISSION —
        # in-flight requests finish (or recompute bit-identically) on the
        # version they were admitted under, never a silent mid-sequence
        # weight change; ``finished_versions`` is the attribution log
        # (rid → version) the zero-downtime oracle audits.
        self.weights_version = 0
        self.finished_versions: dict[int, int] = {}
        self._staged_swap: dict | None = None
        self._installed: tuple | None = None   # committed (params, draft)
        self._swap_jit_cache: dict = {}        # device_reshard programs
        self._swap_plan_cache: dict = {}       # host transfer plans
        # KV economy (round 15): the prefix-registry DIGEST the fleet
        # router queries for prefix-aware placement, plus the tier
        # ladder's spill/fill bookkeeping. ``prefix_epoch`` bumps on any
        # registry KEY change (register, evict, spill, fill, flush), so
        # a digest is valid exactly while its epoch matches.
        self.prefix_epoch = 0
        self._digest_cache: tuple | None = None     # (epoch, hashes) memo
        self.expected_prefix: dict[int, int] = {}   # rid → predicted hit toks
        self.prefix_realized: dict[int, int] = {}   # rid → realized hit toks
        self._page_plan_cache: dict = {}            # spill/fill host plans
        self._adapter_pool = adapter_pool
        self._init_telemetry(registry, tracer, slo, recorder)
        if adapter_pool is not None:
            adapter_pool.bind(self.registry, self.recorder)
        self._init_slots()
        if paged:
            self._init_pool()
        self.reset_stats()

    # --- state initialisation --------------------------------------------

    def _init_telemetry(self, registry, tracer, slo=None, recorder=None):
        # Engine-local by default: each engine is its own measurement
        # window and trace timeline. A shared registry AGGREGATES: the
        # cumulative engine_* counters then carry every engine's
        # activity, so a scraper sees fleet totals — but window-derived
        # per-call stats (last_stats/last_latency) would include the
        # other engines' increments too. Keep the default (engine-local)
        # when per-engine stats matter; share only for fleet export.
        self.registry = (
            registry if registry is not None else MetricsRegistry()
        )
        self.tracer = tracer if tracer is not None else Tracer()
        # The flight recorder defaults to the PROCESS ring (post-mortems
        # want the whole process's recent history in one place); the SLO
        # monitor, if handed in unbound, exports through this engine's
        # registry/recorder.
        from learning_jax_sharding_tpu.telemetry import (
            default_flight_recorder,
        )

        self.recorder = (
            recorder if recorder is not None else default_flight_recorder()
        )
        # Span closures ride the ring next to the lifecycle events (the
        # dispatch timeline a post-mortem needs). With several engines on
        # one recorder, the last attachment wins the recorder's default
        # tracer for dump(); dump_diagnostics always passes its own.
        self.recorder.attach_tracer(self.tracer)
        self.slo = slo
        if slo is not None:
            if slo.registry is None:
                slo.registry = self.registry
            if slo.recorder is None:
                slo.recorder = self.recorder
        r = self.registry
        self._c_requests = r.counter(
            "engine_requests_total", "requests enqueued")
        self._c_finished = r.counter(
            "engine_requests_finished_total", "requests retired")
        self._c_tokens = r.counter(
            "engine_tokens_generated_total", "generated tokens emitted")
        self._c_preempt = r.counter(
            "engine_preemptions_total",
            "recompute preemptions under page-pool pressure")
        self._c_pfx_hits = r.counter(
            "engine_prefix_hits_total",
            "admissions that reused retained prefix pages")
        self._c_pfx_pages = r.counter(
            "engine_prefix_pages_reused_total",
            "prefix pages mapped on admission")
        self._c_spec_acc = r.counter(
            "engine_spec_accepted_total",
            "draft tokens accepted by the verifier")
        self._c_spec_prop = r.counter(
            "engine_spec_proposed_total", "draft tokens proposed")
        self._c_refill_s = r.counter(
            "engine_refill_seconds_total",
            "host-observed refill dispatch+sync seconds")
        self._c_decode_s = r.counter(
            "engine_decode_seconds_total",
            "host-observed decode dispatch+sync seconds")
        self._c_refill_n = r.counter(
            "engine_refill_dispatches_total", "refill dispatches")
        self._c_decode_n = r.counter(
            "engine_decode_dispatches_total", "decode dispatches")
        self._c_mixed_s = r.counter(
            "engine_mixed_seconds_total",
            "host-observed fused refill+decode dispatch+sync seconds")
        self._c_mixed_n = r.counter(
            "engine_mixed_dispatches_total",
            "fused refill+decode dispatches")
        self._c_stall_s = r.counter(
            "engine_decode_stall_seconds_total",
            "dispatch seconds during which decoding rows sat idle "
            "behind another slot's refill")
        self._c_multi_n = r.counter(
            "engine_multi_dispatches_total",
            "fused multi-step dispatches (horizon > 1 — one scanned "
            "program advancing N engine iterations)")
        self._c_multi_links = r.counter(
            "engine_multi_links_total",
            "engine iterations advanced inside multi-step dispatches "
            "(steps_per_dispatch = links / dispatches)")
        self._c_plan_staged = r.counter(
            "engine_plan_staged_total",
            "next-horizon refill plans staged by the async planner "
            "while a multi-step program was in flight")
        self._c_plan_reused = r.counter(
            "engine_plan_reused_total",
            "staged plans consumed at the next horizon boundary (the "
            "boundary state matched the planner's prediction)")
        self._c_creations = r.counter(
            "engine_cache_creations_total", "cache-creating first refills")
        self._c_shed = r.counter(
            "engine_shed_total",
            "arrivals rejected by admission control (bounded queue or "
            "degradation-ladder shedding)")
        self._c_deadline = r.counter(
            "engine_deadline_evictions_total",
            "requests failed by their TTL deadline (queued or in-flight)")
        self._c_quarantined = r.counter(
            "engine_quarantined_total",
            "requests failed as poison after repeated dispatch faults")
        self._c_dispatch_faults = r.counter(
            "engine_dispatch_faults_total",
            "dispatches aborted by a recoverable fault")
        self._c_req_failed = r.counter(
            "engine_requests_failed_total",
            "requests retired with a non-ok terminal status")
        self._c_rerouted = r.counter(
            "engine_rerouted_total",
            "requests drained with status 'rerouted' — failover/handoff "
            "requeue onto another fleet replica, never a lost request")
        self._c_kv_exports = r.counter(
            "engine_kv_exports_total",
            "retired-request KV rows exported for disaggregated handoff")
        self._c_kv_ingests = r.counter(
            "engine_kv_ingests_total",
            "externally prefilled requests ingested (disaggregated "
            "handoff)")
        self._c_pg_spills = r.counter(
            "engine_kv_page_spills_total",
            "retained prefix pages demoted (spilled) out of HBM to a "
            "host tier")
        self._c_pg_fills = r.counter(
            "engine_kv_page_fills_total",
            "prefix pages promoted (filled) back into HBM from a tier")
        self._c_pg_bytes_out = r.counter(
            "engine_kv_page_spill_bytes_total",
            "bytes moved HBM → host demoting prefix pages")
        self._c_pg_bytes_in = r.counter(
            "engine_kv_page_fill_bytes_total",
            "bytes moved host → HBM promoting prefix pages")
        self._c_kv_raw_bytes = r.counter(
            "engine_kv_raw_bytes_total",
            "pre-codec bytes of counted KV/page/swap host transfers — "
            "the *_bytes_total counters book WIRE bytes, so the gap to "
            "this counter is what the codec saved")
        self._c_comp_probes = r.counter(
            "engine_comp_drift_probes_total",
            "compressed-vs-plain-oracle drift probes run")
        self._c_comp_disagree = r.counter(
            "engine_comp_drift_disagreements_total",
            "active rows whose greedy pick diverged from the plain "
            "oracle during a drift probe")
        self._c_comp_trips = r.counter(
            "engine_comp_drift_trips_total",
            "drift-budget breaches that auto-disabled the quantized "
            "serving collectives (one-way until an operator re-enables)")
        self._c_pfx_expected = r.counter(
            "engine_prefix_expected_total",
            "admissions the router placed expecting a prefix hit")
        self._c_tier_miss = r.counter(
            "engine_tier_misses_total",
            "admissions whose realized prefix hit fell short of the "
            "router's prediction (page evicted/raced away mid-route) — "
            "the request gracefully re-prefilled the missing tokens")
        self._c_swap_staged = r.counter(
            "engine_swap_staged_total",
            "weight swaps staged (resharded into the serving layout off "
            "the hot path)")
        self._c_swap_commits = r.counter(
            "engine_swap_commits_total",
            "weight swaps atomically committed between dispatches")
        self._c_swap_aborted = r.counter(
            "engine_swap_aborted_total",
            "weight swaps aborted during staging — the engine kept the "
            "old version, in-flight requests unaffected")
        self._c_swap_bytes = r.counter(
            "engine_swap_bytes_total",
            "bytes moved staging swapped weight trees into the serving "
            "layout")
        self._c_adapter_n = r.counter(
            "engine_adapter_dispatches_total",
            "fused dispatches that gathered per-row adapters")
        self._c_adapter_rows = r.counter(
            "engine_adapter_rows_total",
            "occupied row-dispatches served under a non-base adapter")
        self._g_degraded = r.gauge(
            "engine_degradation_level",
            "current graceful-degradation ladder level (0 = normal)")
        self._g_queue = r.gauge(
            "engine_queue_depth", "requests waiting for a slot")
        self._g_active = r.gauge(
            "engine_active_slots", "slots actively decoding")
        self._g_pages = r.gauge(
            "engine_pages_live", "live (non-retained) pages held")
        self._g_retained = r.gauge(
            "engine_prefix_pages_retained",
            "reference-free retained prefix pages")
        self._g_comp_on = r.gauge(
            "engine_comm_compression_active",
            "1 while quantized serving collectives are compiled in")
        self._g_comp_ratio = r.gauge(
            "engine_kv_compression_ratio",
            "raw/wire byte ratio of the most recent counted KV transfer "
            "batch (1.0 when no codec is attached)")
        self._g_comp_on.set(
            1 if (self._comp is not None and self._comp.active) else 0
        )
        self._g_comp_ratio.set(1.0)
        self._h_ttft = r.histogram(
            "engine_ttft_seconds", "arrival to first visible token")
        self._h_tpot = r.histogram(
            "engine_tpot_seconds", "per-request mean inter-token seconds")
        self._h_wait = r.histogram(
            "engine_queue_wait_seconds", "arrival to slot admission")
        self._h_e2e = r.histogram(
            "engine_e2e_seconds", "arrival to retirement")
        self._h_swap_stall = r.histogram(
            "engine_swap_stall_seconds",
            "stage-to-commit latency of weight swaps (drain or preempt)")
        # Host phases of a step, at the ledger frames' funnel (all
        # cumulative; the frames' span names are tabled at _led_device).
        self._c_step_s = r.counter(
            "engine_step_seconds_total",
            "wall seconds inside step() (the ledger's covered seconds of "
            "its step frames): the denominator of the host-share metrics")
        self._c_enqueue_s = r.counter(
            "engine_enqueue_seconds_total",
            "host seconds inside jitted calls (argument transfer, output "
            "allocation, launch)")
        self._c_wait_s = r.counter(
            "engine_wait_seconds_total",
            "seconds in the blocking readbacks that drain a dispatched "
            "program")
        self._c_h2d_s = r.counter(
            "engine_h2d_seconds_total",
            "host seconds pushing block tables and step inputs to the "
            "device ahead of an enqueue")
        self._c_table_leaves = r.counter(
            "engine_table_push_leaves_total",
            "block_table leaves of the cache tree that pushes installed "
            "a fresh table in (every layer's, each push)")
        self._c_table_arrays = r.counter(
            "engine_table_push_arrays_total",
            "host-to-device arrays the table pushes made (one per distinct "
            "leaf width a push; the leaves of a width share it)")
        self._c_prefill_tok = r.counter(
            "engine_prefill_tokens_total",
            "prompt tokens consumed by refill dispatches")
        self._c_refill_slots = r.counter(
            "engine_refill_token_slots_total",
            "token slots refill dispatches ran (batch x refill_chunk a "
            "dispatch, whoever refills): engine_prefill_tokens_total over "
            "this is how full the split engine's refill dispatches were")
        self._c_chunk_rows = r.counter(
            "engine_refill_chunk_rows_total",
            "rows of refill dispatches that carried prompt tokens")
        # A state-space config only (models/ssm.py): the second kind of
        # state in the cache tree, by slot.
        self._c_ssm_carried = r.counter(
            "engine_ssm_carried_rows_total",
            "refill chunk rows whose recurrent state started from an "
            "earlier row of the same dispatch (a long prompt's further "
            "chunks), not from the slot's cached state")
        self._c_ssm_resets = r.counter(
            "engine_ssm_state_resets_total",
            "slot admissions that zeroed a recurrent state")
        self._g_ssm_bytes = r.gauge(
            "engine_ssm_state_bytes",
            "bytes of recurrent and convolution state the cache holds (all "
            "slots, all state-space layers)")
        self._g_attn_depth = r.gauge(
            "engine_decode_attn_pages_in_flight",
            "cache blocks the decode-attention kernel keeps in flight over "
            "this engine's cache layers (the smallest over them): the depth "
            "of the loop form's DMA ring, 0 where the pipeline-emitter form "
            "runs (ops.decode_attention.pages_in_flight: fixed when the "
            "programs are traced, from the cache's shape and dtype)")
        self._g_donated_bytes = r.gauge(
            "engine_cache_donated_bytes",
            "bytes of the cache leaves the step programs update in place "
            "(donated and aliased to their successors: pools, counters, "
            "scales, expert counts, recurrent state; the block tables are "
            "kept out); 0 before a cache exists")
        self._c_decode_steps = r.counter(
            "engine_decode_steps_total",
            "decode row-steps advanced (tokens emitted after the first)")
        self._c_decode_ctx = r.counter(
            "engine_decode_context_tokens_total",
            "sum over decode row-steps of the row's cache length at that "
            "step: x 2 x layers x kv_heads x head_dim x bytes is the K,V "
            "a decode kernel had to read (a latent cache: x layers x "
            "(kv_rank + rope_dim) x bytes)")
        # Dropless expert layers (models.moe.DroplessMoE), counted on the
        # device and read back with a split dispatch's own readback, by
        # phase of the dispatch.
        self._c_moe = {
            phase: tuple(
                r.counter(labeled_name(name, phase=phase), help_)
                for name, help_ in (
                    ("engine_moe_assignments_total",
                     "token x expert pairs routed"),
                    ("engine_moe_expert_reads_total",
                     "summed over expert layers and model steps: experts "
                     "with at least one token, whose three matrices the "
                     "expert kernel read"),
                    ("engine_moe_layer_steps_total",
                     "expert-layer applications that routed anything"),
                )
            )
            for phase in ("decode", "refill")
        } if self._moe_counted else {}
        # The request clock (_tick): slot-seconds and readbacks by the
        # phase they put a request in, counted where they are assigned,
        # so a window's edge loses nothing. ``redone`` counts AGAIN what
        # a preemption threw away (those seconds were first counted in
        # the phase they ran in): the four phases less ``redone`` are
        # the seconds retired requests kept.
        self._c_phase = [
            (
                r.counter(
                    labeled_name(
                        "engine_request_phase_seconds_total", phase=phase
                    ),
                    "slot-seconds of admitted requests by the phase the "
                    "dispatch just read back put them in"),
                r.counter(
                    labeled_name(
                        "engine_request_phase_dispatches_total", phase=phase
                    ),
                    "readbacks that put a request in the phase, summed "
                    "over requests"),
            )
            for phase in (*_PHASES, "redone")
        ]
        # Requests by phase over the readbacks since the last
        # engine.dispatch event (which reports and clears them).
        self._ph_event = [0] * len(_PHASES)
        self._span_names: dict[tuple[str, str], str] = {}  # (phase, family)
        # Goodput ledger (round 14): exhaustive wall-clock attribution
        # for the engine loop. step() is the top-level frame (its
        # unclaimed remainder is host scheduling, bucket "sched");
        # dispatch/sync regions book "device" (re-bucketed to "compile"
        # when the executable cache grew), admission/page/handoff/swap/
        # recovery/telemetry paths open their own frames, and idle is
        # derived — reconcile() must hold after any run (tier-1 gated).
        # Meters into this registry as ledger_seconds_total{bucket=...}.
        # Every frame is also a span on this engine's tracer, and the
        # frames carry the empty-device clock
        # (engine_device_starved_seconds_total).
        self.ledger = GoodputLedger(registry=r, tracer=self.tracer)
        # Programs dispatched whose results the host has not read: the
        # empty-device clock runs only while this is 0.
        self._in_flight = 0
        self._step_n = 0
        # What the last engine.dispatch event has accounted for.
        self._starved_at_enqueue = 0.0
        self._last_family = None
        self._compiled = False
        self._booked = dict.fromkeys(self._DISPATCH_DELTAS, 0.0)
        self._booked["starved_s"] = 0.0
        if self._moe_counted:
            self._booked.update(moe_assignments=0.0, expert_reads=0.0)
        # Request-scoped trace sink (telemetry.tracecontext.TraceStore).
        # The fleet router attaches its store (and the replica name) to
        # every replica; a solo driver may attach its own — legs are
        # recorded at retirement from the stamps _Request already
        # carries, so the sink costs nothing when absent.
        self.trace_sink = None
        self.trace_replica = "engine"

    # Every goodput-ledger frame the engine opens is a span: a tracer
    # event and a ``jax.profiler.TraceAnnotation`` of the same name, so
    # each instant of ``step()`` lies in exactly one innermost span. A
    # span is finer than its bucket, never a new bucket. The frame's
    # ``label`` is its series of the empty-device clock
    # (``engine_device_starved_seconds_total{span=...}``). Pinned by
    # ``tests/test_engine_spans.py``; ``scripts/engine_breakdown.py``
    # reads them back from a flight-recorder bundle and a capture.
    #
    # span                      bucket      label       wraps
    # engine.step               sched       sched       one step(); annotation
    #                                                   arguments step, ts_us
    # engine.admission          admission   admission   deadline sweep, _admit
    # engine.page_alloc         page_alloc  page_alloc  _ensure when it claims
    #                                                   pages (no ring event)
    # engine.h2d                sched       h2d         the block-table push
    #                                                   (arguments leaves,
    #                                                   arrays), the
    #                                                   jnp.asarray of inputs
    # engine.enqueue.<family>   device      enqueue     the jitted call alone
    #                           / compile
    # engine.wait.<family>      device      wait        the blocking readback
    # engine.consume            sched       consume     token / first-token /
    #                                                   retire loop after it
    # engine.plan               sched       plan        the horizon planner
    # engine.telemetry          telemetry   telemetry   counters, recorder, SLO,
    #                                                   the request clock's
    #                                                   books (_flush_ticks)
    # engine.recovery / engine.kv_handoff / engine.swap: their bucket
    #
    # ``<family>`` is the ``Program``'s. ``engine.refill`` /
    # ``engine.decode`` / ``engine.mixed`` (``_dispatch_span``) are tracer
    # spans around a whole dispatch, not frames: their own time is the
    # step's.

    def _led_h2d(self, **args):
        """Ledger frame ``engine.h2d``: host-to-device pushes ahead of
        an enqueue (block tables, step inputs)."""
        return self.ledger.measure(
            "sched", span="engine.h2d", label="h2d", counter=self._c_h2d_s,
            **args,
        )

    def _led_consume(self):
        """Ledger frame ``engine.consume``: the token / first-token /
        retire loop after a readback (its telemetry frames nest)."""
        return self.ledger.measure(
            "sched", span="engine.consume", label="consume"
        )

    #: ``engine.dispatch`` event field -> the cumulative counter whose
    #: growth since the previous event it reports.
    _DISPATCH_DELTAS = {
        "enqueue_s": "_c_enqueue_s", "wait_s": "_c_wait_s",
        "h2d_s": "_c_h2d_s", "table_leaves": "_c_table_leaves",
        "table_arrays": "_c_table_arrays",
        "prefill_tokens": "_c_prefill_tok",
        "token_slots": "_c_refill_slots", "chunk_rows": "_c_chunk_rows",
        "carried_rows": "_c_ssm_carried",
        "decode_steps": "_c_decode_steps",
        "context_tokens": "_c_decode_ctx",
    }

    def _book_moe(self, phase, stats):
        """Add one readback's expert counts ``(3,)`` (a dropless-expert
        config's programs return them; already on the host) to
        ``phase``'s counters (``_flush_ticks``, inside its caller's
        telemetry frame)."""
        for counter, n in zip(self._c_moe[phase], stats.tolist()):
            counter.inc(n)

    @contextlib.contextmanager
    def _led_device(self, prog: Program | None = None, family=None,
                    in_flight=0):
        """Ledger frame for a dispatch or blocking readback: books to
        the ``device`` bucket (tagged with ``prog``'s family for
        :meth:`overlap_report`), unless ``prog``'s executable cache GREW
        inside the region — then the call paid a trace+compile, not a
        device step, and the whole frame re-buckets to ``compile`` (the
        compile-steal idiom; ``cache_size`` probes the jit cache).

        ``family`` tags a frame WITHOUT a cache probe — the sync-frame
        form: under async dispatch the dispatch frame books only enqueue
        microseconds, so the blocking readback that drains a program's
        in-flight seconds must carry the SAME family tag or the
        overlap_report attribution would book the device time as
        unattributed.

        The two forms are the spans ``engine.enqueue.<family>`` (the
        jitted call alone) and ``engine.wait.<family>``. ``in_flight``
        is how many dispatched programs are still unread once this
        readback returns (the device runs them in order); at 0 the chip
        is empty until the next enqueue returns."""
        if prog is not None:
            before, fam, phase = cache_size(prog.fn), prog.family, "enqueue"
        else:
            fam, phase = family, "wait"
        span = self._span_names.get((phase, fam))
        if span is None:
            span = self._span_names[phase, fam] = f"engine.{phase}.{fam}"
        with self.ledger.measure(
            "device", family=fam, span=span, label=phase,
            counter=self._c_enqueue_s if prog is not None else self._c_wait_s,
        ) as f:
            yield f
            if prog is not None:
                if before is not None and (cache_size(prog.fn) or 0) > before:
                    f.rebucket("compile")
                    self._compiled = True
                self.ledger.device_busy()
                self._starved_at_enqueue = self.ledger.starved_s
                self._in_flight += 1
                self._last_family = fam
            else:
                self._in_flight = in_flight
                if not in_flight:
                    self.ledger.device_empty()

    def _cache_args(self):
        # The engine's cache as a program's cache arguments: the one
        # tree, or a speculative engine's (target, draft) pair as two.
        return self._cache if self._speculative else (self._cache,)

    def _enqueue(self, prog, head, tail, *, cache=None, frame=True):
        """Call a step program that DONATES its cache: ``prog.fn(*head,
        <the cache arguments without their block tables>, <the tables>,
        *tail)`` (``engine_programs._donating``). ``cache()`` gives the
        cache arguments as they stand (default: ``_cache_args``). The
        call consumes what it is given; its outputs come back with the
        tables the host holds put back under the caches that replace
        them, and the caller installs those in ``self._cache`` before it
        does anything else: the engine never keeps a consumed tree across
        a statement that can raise (a call that raises after it took the
        cache: ``_on_dispatch_fault``). ``prog.last_args`` becomes this
        call's arguments with the cache read LIVE at relower time (a
        captured tree would be a consumed one, or pin a stale pool in
        HBM). Splitting and merging are host work outside the enqueue
        span."""
        cache = cache or self._cache_args

        def args():
            caches, tables = split_cache(tuple(cache()))
            return (*head, *caches, tables, *tail), tables

        operands, tables = args()
        with self._led_device(prog) if frame else contextlib.nullcontext():
            out = prog.fn(*operands)
        prog.last_args = lambda: args()[0]
        return merge_cache(out, tables)

    def _win_delta(self, counter):
        # The stats window (reset_stats → snapshot) over a cumulative
        # counter: value minus its base at the last reset.
        return counter.value - self._win_base.get(counter.name, 0.0)

    def _init_slots(self):
        b = self._b
        # A slot is: idle (req < 0), refilling (pending prompt tokens
        # remain), or decoding (active).
        self._req = [-1] * b               # request id per slot
        self._plen = [0] * b               # admitted prompt length per slot
        self._pending: list[np.ndarray] = [np.zeros((0,), np.int32)] * b
        self._emitted = [0] * b
        self._out: list[list[int]] = [[] for _ in range(b)]
        self._ttimes: list[list[float]] = [[] for _ in range(b)]
        self._slot_req: list[_Request | None] = [None] * b
        self._tok = np.zeros((b,), np.int32)
        self._active = np.zeros((b,), bool)
        # Per-slot adapter slot index into the AdapterPool's stacked tree
        # (0 = the base/zero adapter; always allocated — harmlessly all
        # zero on engines without a pool).
        self._aidx = np.zeros((b,), np.int32)
        # Admission reset flags live on the ENGINE, not in step() locals:
        # they are consumed by the first SUCCESSFUL refill dispatch, so a
        # raise between admission and dispatch (pool exhaustion) cannot
        # lose a row's counter reset (review finding, round 5).
        self._needs_reset = np.zeros((b,), bool)
        self._reset_to = np.zeros((b,), np.int32)
        # The request clock (_tick): seconds and readbacks of the slot's
        # request by phase (_PHASES) since its admission here at
        # _ph_admit, accounted up to _ph_last. Plain lists: the clock
        # runs on the host right after a blocking readback, where a loop
        # over 16-32 slots costs a third of the same books kept in numpy
        # arrays (PERF.md, Findings, PR 37).
        self._ph_s = [[0.0] * len(_PHASES) for _ in range(b)]
        self._ph_n = [[0] * len(_PHASES) for _ in range(b)]
        self._ph_last = [0.0] * b
        self._ph_admit = [0.0] * b
        self._ticks: list[tuple] = []      # readbacks noted, not yet booked
        # Retired-request → slot map while the slot's KV is still intact
        # (export window for the disaggregated handoff); entries drop the
        # moment the slot is reused by a later admission/ingestion.
        self._export_ok: dict[int, int] = {}

    def _init_pool(self):
        # Host-owned page allocator: page 0 is scratch; a slot holds a
        # prefix of logical blocks mapped to arbitrary physical pages.
        b = self._b
        self._free_pages = list(range(self._paged_pages - 1, 0, -1))
        self._held: list[list[int]] = [[] for _ in range(b)]
        t_cap = self._cfg.max_seq_len // self._page_size
        self._table_np = np.zeros((b, t_cap), np.int32)
        self._tables_dirty = True
        self._table_widths = None      # width -> block_table leaves of it
        # Prefix-cache state (the metrics registry is the separate,
        # public ``self.registry``): page-aligned token-prefix bytes →
        # the page holding that prefix's LAST page of K/V; refcounts for pages
        # shared by live slots; ref-0 registered pages stay evictable in
        # LRU order (dict preserves insertion order).
        self._prefix_registry: dict[bytes, int] = {}
        self._key_of_page: dict[int, bytes] = {}
        self._refcnt: dict[int, int] = {}
        self._cached_lru: dict[int, None] = {}
        self._shared_count = [0] * b   # leading registry pages per slot
        self.prefix_epoch += 1         # any prior digest is now stale
        self._g_pages.set(0)
        self._g_retained.set(0)

    def reset_stats(self):
        """Start a stats window (``serve()`` calls this at entry;
        streaming users call it to start a measurement window). The
        registry's counters are CUMULATIVE (Prometheus semantics) and
        are never zeroed — the window is a base snapshot, and
        ``last_stats``/``latency_stats`` report deltas against it, so
        per-call stats keep their pinned meaning while a scraper sees
        monotone series."""
        self._completed: list[dict] = []
        self._itl: list[float] = []
        self._win_base = {
            c.name: c.value
            for c in (
                self._c_preempt, self._c_pfx_hits, self._c_pfx_pages,
                self._c_spec_acc, self._c_spec_prop, self._c_refill_s,
                self._c_decode_s, self._c_mixed_s, self._c_stall_s,
                self._c_multi_n, self._c_multi_links,
                self._c_plan_staged, self._c_plan_reused,
                self._c_requests, self._c_finished, self._c_shed,
                self._c_deadline, self._c_req_failed, self._c_rerouted,
                self._c_pg_spills, self._c_pg_fills,
                self._c_pg_bytes_out, self._c_pg_bytes_in,
                self._c_pfx_expected, self._c_tier_miss,
            )
        }
        # Window high-water for the page-pool gauge (live value rides on).
        self._g_pages.reset_high_water()
        self.ledger.begin_window()

    def reset(self):
        """Abandon all in-flight work and return the engine to idle.

        Frees every page (INCLUDING the prefix registry — retained K/V
        may be mid-write when this is called), clears the queue and
        slots; keeps the compiled programs and the allocated cache
        arrays (admission resets their counters)."""
        self._queue.clear()
        self._init_slots()
        if self._paged:
            self._init_pool()

    def drain_requests(
        self, *, status: str = "rerouted", error: str | None = None
    ) -> list[dict]:
        """DRAIN-AND-HANDOFF (round 11): retire EVERY queued and
        in-flight request with terminal ``status`` (surfaced through
        ``pop_finished`` — default ``"rerouted"``, the fleet router's
        failover drain, counted by ``engine_rerouted_total`` and
        ``latency_stats()["rerouted"]`` so a failover is visible instead
        of looking like fresh admissions elsewhere) and return
        requeueable records ``{rid, prompt, deadline_s, arrival_t}`` in
        slot-then-queue order.

        The drained requests RECOMPUTE EXACTLY on whatever engine
        re-admits them — the same guarantee as ``_unadmit``'s recompute
        preemption: greedy decoding is deterministic and every sampling
        draw is keyed by (request id, generated position), never by
        schedule or replica. Device state needs no repair (admission
        resets per-row counters); the compiled programs and cache stay
        for the next dispatch."""
        now = time.perf_counter()
        records: list[dict] = []

        def rec(r):
            records.append(dict(
                rid=r.rid, prompt=r.prompt, deadline_s=r.deadline_s,
                arrival_t=r.arrival_t,
            ))

        for slot in range(self._b):
            if self._slot_req[slot] is not None:
                rec(self._slot_req[slot])
                self._fail_slot(slot, status, error, now)
        while self._queue:
            r = self._queue.popleft()
            rec(r)
            self._fail_request(r, status, error, now=now)
        self._g_queue.set(0)
        self._g_active.set(0)
        self.recorder.record(
            "engine.drain", status=status, n=len(records),
        )
        return records

    def close(self):
        """Shut the engine down to idle: every in-flight or queued
        request is DRAINED TO A TERMINAL STATUS (``"shutdown"`` — a
        :class:`RequestFailure` with any partial tokens, surfaced
        through ``pop_finished``; never a silent drop a caller would
        poll forever), then the device state (KV cache + page pool +
        prefix registry) is released so HBM can be reclaimed.
        IDEMPOTENT: closing an idle/closed engine is a no-op beyond the
        state drop. Completed-but-unpopped results are host-side and
        survive. The engine stays usable: the next dispatch re-creates
        the cache (``cache_creations`` increments)."""
        self.drain_requests(status="shutdown", error="engine closed")
        self._cache = None
        self._cast_src = self._cast_out = None
        self._clear_dispatch_args()
        self._export_ok = {}
        if self._paged:
            self._init_pool()
        self.recorder.record("engine.close")

    def flush_prefix_cache(self):
        """Drop EVERY retained prefix page — call between checkpoints:
        the registry keys pages by token bytes only, so K/V computed
        under old params would silently serve new-params requests.
        Requires an IDLE engine (a live request sharing a registered
        page, or retiring after the flush, would re-expose or re-register
        old-params K/V — swap params only between requests)."""
        if not self._paged:
            return
        if self.has_work():
            raise RuntimeError(
                "flush_prefix_cache() requires an idle engine: drain "
                "in-flight work first (params must not change mid-request)"
            )
        self._drop_prefix_registry()

    def _drop_prefix_registry(self):
        # The registry-dropping core of ``flush_prefix_cache``, minus its
        # idle guard: a swap COMMIT calls this directly — commit requires
        # empty SLOTS only (retained pages are reference-free then), and
        # queued requests are fine: they admit after the commit, under
        # the new version, and can never see old-params K/V.
        for pid in list(self._cached_lru):
            del self._cached_lru[pid]
            del self._prefix_registry[self._key_of_page.pop(pid)]
            del self._refcnt[pid]
            self._free_pages.append(pid)
        # A dropped registry invalidates every exported digest — the
        # router's prefix-aware placement must stop scoring stale hits
        # (old-params K/V must never be routed TO, either).
        self.prefix_epoch += 1
        # Refresh the export gauges: retained pages just went to zero and
        # a scraper must not keep seeing the flushed K/V.
        self._update_high_water()

    # --- page allocator ----------------------------------------------------

    def _take_page(self):
        # Chaos seam: kind="oom" raises this allocator's own
        # RuntimeError, driving the recompute-preemption backpressure
        # path without actually draining the pool.
        chaos_hook("engine.page_alloc", free=len(self._free_pages))
        if self._free_pages:
            return self._free_pages.pop()
        if self._cached_lru:
            # Evict the oldest reference-free cached page — the pool must
            # serve live requests before retained ones.
            pid = next(iter(self._cached_lru))
            del self._cached_lru[pid]
            del self._prefix_registry[self._key_of_page.pop(pid)]
            del self._refcnt[pid]
            self.prefix_epoch += 1
            return pid
        raise RuntimeError(
            f"page pool exhausted ({self._paged_pages - 1} pages "
            f"× {self._page_size} tokens): raise paged_pages or "
            "lower concurrency"
        )

    def _live_pages(self) -> int:
        # LIVE pages only: retained reference-free prefix pages are
        # reclaimable at will, so they are not footprint — they are
        # reported separately (``prefix_pages_retained``).
        return (
            (self._paged_pages - 1)
            - len(self._free_pages)
            - len(self._cached_lru)
        )

    def _update_high_water(self):
        # The gauge carries both the live value (export) and the window
        # maximum (``last_stats["page_high_water"]``).
        self._g_pages.set(self._live_pages())
        self._g_retained.set(len(self._cached_lru))

    def _ensure(self, slot, tokens_through):
        # Allocate pages so positions [0, tokens_through) are mapped
        # before the dispatch that writes them.
        need = -(-int(tokens_through) // self._page_size)
        if len(self._held[slot]) >= need:
            return   # steady-state decode mostly allocates nothing
        with self.ledger.measure(
            "page_alloc", span="engine.page_alloc", ring=False
        ):
            while len(self._held[slot]) < need:
                p = self._take_page()
                self._table_np[slot, len(self._held[slot])] = p
                self._held[slot].append(p)
                self._tables_dirty = True
            self._update_high_water()

    def _release(self, slot, register=True):
        # ``register=False``: the slot is being UN-admitted (backpressure),
        # so its prompt pages may be only partially written — never
        # register them; just free privates and drop shared refs.
        page_size = self._page_size
        if self._prefix and not register:
            pages, ns = self._held[slot], self._shared_count[slot]
            self._free_pages.extend(pages[ns:])
            for pid in reversed(pages[:ns]):
                self._refcnt[pid] -= 1
                if self._refcnt[pid] == 0:
                    self._cached_lru[pid] = None
            self._shared_count[slot] = 0
            self._held[slot] = []
            self._table_np[slot, :] = 0
            self._tables_dirty = True
            self._update_high_water()
            return
        if self._prefix:
            pages, ns = self._held[slot], self._shared_count[slot]
            # Private pages: RETAIN the ones fully inside the prompt
            # (immutable once written — generation never rewrites earlier
            # positions) under their token-prefix key; free the rest
            # (generated-region K/V). DEEPEST page first into the LRU —
            # admission chains break at the first missing page, so
            # eviction must take chain tails before roots or the stranded
            # descendants retain HBM with zero hit potential.
            p_toks = np.asarray(self._out[slot][: self._plen[slot]], np.int32)
            full = self._plen[slot] // page_size
            for j in range(len(pages) - 1, ns - 1, -1):
                pid = pages[j]
                if j < full:
                    key = p_toks[: (j + 1) * page_size].tobytes()
                    if key not in self._prefix_registry:
                        self._prefix_registry[key] = pid
                        self._key_of_page[pid] = key
                        self._refcnt[pid] = 0
                        self._cached_lru[pid] = None
                        self.prefix_epoch += 1
                        continue
                self._free_pages.append(pid)
            for pid in reversed(pages[:ns]):   # drop shared refs,
                self._refcnt[pid] -= 1         # tails first too
                if self._refcnt[pid] == 0:
                    self._cached_lru[pid] = None
            # LRU refresh across RETIREMENTS (advisor r4): a chain root
            # registered by an earlier retirement would sit OLDER in the
            # LRU than a tail registered just now, so eviction could take
            # the root first and strand its descendants as unmatchable.
            # Touch this prompt's whole chain deepest-first, so every
            # ancestor ends up newer than its deepest tail.
            for k in range(full, 0, -1):
                pid = self._prefix_registry.get(p_toks[: k * page_size].tobytes())
                if pid is not None and pid in self._cached_lru:
                    del self._cached_lru[pid]
                    self._cached_lru[pid] = None
            self._shared_count[slot] = 0
        else:
            self._free_pages.extend(self._held[slot])
        self._held[slot] = []
        self._table_np[slot, :] = 0
        self._tables_dirty = True
        self._update_high_water()

    def _set_tables(self, cache, frame=True):
        # Push the host table to the device ONCE per distinct leaf width
        # and install that one array in every block_table leaf of the
        # width (target AND draft trees; the draft's table may be
        # narrower — same prefix, same page ids — so a speculative
        # engine pushes one or two arrays, every other engine one). The
        # cost of a push is per array (allocate, linearize, transfer),
        # not per byte. Sharing one buffer under many leaves is legal
        # because the tables are never donated: the step programs take
        # them beside the donated cache and do not return them
        # (engine_programs._donating), so what is installed here stays
        # under its leaves until the next push. Skipped entirely when
        # no allocation changed since the last push — the steady-state
        # decode loop mostly doesn't allocate. The push is the frame
        # ``engine.h2d``; ``frame=False`` is for a caller outside step().
        if not self._tables_dirty:
            return cache
        self._tables_dirty = False

        if self._table_widths is None:
            # Counted once, the cache's tree never changes shape: the
            # width of every table leaf (one leaf a layer).
            self._table_widths = Counter(
                x.shape[1]
                for path, x in jax.tree_util.tree_flatten_with_path(cache)[0]
                if _is_table(path)
            )
        leaves, arrays = self._table_widths.total(), len(self._table_widths)
        self._c_table_leaves.inc(leaves)
        self._c_table_arrays.inc(arrays)
        with (
            self._led_h2d(leaves=leaves, arrays=arrays) if frame
            else contextlib.nullcontext()
        ):
            # .copy(): the full-width slice is a contiguous view and
            # jnp.asarray may alias it zero-copy — the host table is
            # mutated in place by later allocations/releases.
            pushed = {
                width: jnp.asarray(self._table_np[:, :width].copy())
                for width in self._table_widths
            }
            return jax.tree_util.tree_map_with_path(
                lambda path, x: pushed[x.shape[1]] if _is_table(path) else x,
                cache,
            )

    # --- request lifecycle -------------------------------------------------

    def _validate_prompt(self, p: np.ndarray):
        if p.size < 1:
            raise ValueError("empty prompt")
        headroom = self._num_draft + 1 if self._speculative else 0
        budget_cfgs = (
            [("target", self._cfg), ("draft", self._d_cfg)]
            if self._speculative else [("target", self._cfg)]
        )
        for name, c in budget_cfgs:
            # The draft cache must fit the same worst case as the
            # target's: its index walks in lockstep through prefill,
            # proposals, and rollback.
            check_sequence_budget(
                p.size + self._max_new + headroom, c.max_seq_len,
                f"prompt ({p.size}) + max_new_tokens ({self._max_new})"
                + (f" + draft headroom ({headroom})" if headroom else "")
                + f" for {name}",
            )

    def _check_draft_args(self, draft_params):
        if self._speculative and draft_params is None:
            raise ValueError(
                "draft_config was given: pass draft_params to serve()/step()"
            )
        if not self._speculative and draft_params is not None:
            raise ValueError("draft_params requires draft_config")

    def _cast_params(self, params, draft_params):
        # The eager inference cast runs once per (params, draft_params)
        # OBJECT pair, not once per step — the cached copies are keyed by
        # identity and hold a reference, so the same tree passed across
        # steps (and across serve() calls) is cast exactly once.
        if self._cast_src is not None and (
            self._cast_src[0] is params and self._cast_src[1] is draft_params
        ):
            return self._cast_out
        out = (
            self._maybe_cast(params),
            self._d_cast(draft_params) if draft_params is not None else None,
        )
        self._cast_src = (params, draft_params)
        self._cast_out = out
        # The stored dispatch-args closures reference the PREVIOUS cast
        # trees — stale for collective_inventory(), and keeping them
        # would hold both parameter trees in HBM across a checkpoint
        # swap. Drop them; the next dispatch re-captures.
        self._clear_dispatch_args()
        return out

    def _clear_dispatch_args(self):
        for prog in self._programs.values():
            prog.last_args = None
        self._staged_plan = None

    # --- zero-downtime weight hot-swap (round 12) --------------------------

    def swap_weights(
        self, new_params, *, version: int, draft_params=None,
        mode: str = "drain",
    ) -> bool:
        """Stage ``new_params`` for a ZERO-DOWNTIME weight swap and
        commit it atomically between dispatches.

        Staging happens NOW, off the dispatch hot path: the tree is run
        through the engine's inference cast and RESHARDED into the
        serving layout (``parallel.resharding.reshard_tree`` — the
        single-program device path for an intra-mesh layout change, the
        explicit counted host plan across device sets; plans and
        compiled movers are cached across swaps). The engine keeps
        serving the OLD version throughout; nothing the scheduler
        touches changes until the commit.

        The COMMIT flips ``weights_version`` to ``version`` and installs
        the staged tree as the engine's own weights (later ``step()``
        calls may omit ``params``; a stale caller-passed tree is
        overridden). It fires only when ZERO slots are occupied:

        * ``mode="drain"`` (default): admission pauses, in-flight
          requests FINISH ON THE OLD WEIGHTS, and the first
          ``step()`` that finds the slots empty commits — then re-admits
          the queued backlog under the new version in that same step, so
          a loaded engine swaps with zero dropped/failed requests.
        * ``mode="preempt"``: every in-flight request is requeued
          (recompute preemption — it RECOMPUTES BIT-IDENTICALLY under
          the new version, the ``_unadmit`` guarantee) and the commit
          happens immediately.

        Every request is attributable to exactly one version: pinned at
        admission (``_Request.version``), logged at retirement
        (``finished_versions``), never changed mid-sequence. On paged
        engines the commit drops the prefix registry (old-params K/V
        must not seed new-params requests).

        A fault injected at the ``engine.swap_stage`` chaos seam (or a
        recoverable staging failure) ABORTS the swap: the engine stays
        on the old version, in-flight requests are unaffected, and the
        abort lands in ``engine_swap_aborted_total`` and the flight
        recorder. Returns True when staged (the commit may still be
        pending), False on an aborted staging."""
        from learning_jax_sharding_tpu.parallel.resharding import (
            reshard_tree,
        )

        if mode not in ("drain", "preempt"):
            raise ValueError(
                f"mode must be 'drain' or 'preempt', got {mode!r}"
            )
        self._check_draft_args(draft_params)
        if self._staged_swap is not None:
            raise RuntimeError(
                f"a weight swap is already staged (version "
                f"{self._staged_swap['version']}): it commits when the "
                "slots drain — stage the next version after that"
            )
        ref = self._cast_out

        def stage(tree, ref_tree):
            if tree is None:
                return None, 0
            if ref_tree is None:
                # Never dispatched: no serving layout to mirror yet —
                # the cast tree is staged as-given and the first
                # dispatch places it like any initial params.
                return tree, 0
            dst = jax.tree.map(lambda x: x.sharding, ref_tree)
            # The engine's KV codec rides the swap too: the intra-mesh
            # device fast path stays exact (the swap_reshard golden's
            # program), but a cross-device-set HOST leg ships weights as
            # block-scaled int8 — the quantized grad-sync premise
            # (zero.py) applied to staging traffic, and the staged tree
            # is what every later dispatch AND recompute serves, so
            # version attribution stays exact.
            with activate(self._mesh, self._rules):
                out, stats = reshard_tree(
                    tree, dst, plan_cache=self._swap_plan_cache,
                    jit_cache=self._swap_jit_cache, codec=self._kv_codec,
                )
            return out, int(stats["bytes"])

        t0 = time.perf_counter()
        with self.ledger.measure("swap", span="engine.swap", busy=True):
            try:
                chaos_hook("engine.swap_stage", version=version, mode=mode)
                cast = self._maybe_cast(new_params)
                d_cast = (
                    self._d_cast(draft_params)
                    if draft_params is not None else None
                )
                cast, p_bytes = stage(cast, ref[0] if ref else None)
                d_cast, d_bytes = stage(d_cast, ref[1] if ref else None)
            except _RECOVERABLE_DISPATCH as e:
                self._c_swap_aborted.inc()
                self.recorder.record(
                    "engine.swap_abort", version=version, mode=mode,
                    error=str(e),
                )
                return False
            moved = p_bytes + d_bytes
            self._staged_swap = dict(
                version=version, mode=mode,
                raw=(new_params, draft_params), cast=(cast, d_cast),
                staged_t=time.perf_counter(),
            )
            self._c_swap_staged.inc()
            self._c_swap_bytes.inc(moved)
            self.recorder.record(
                "engine.swap_stage", version=version, mode=mode, bytes=moved,
                stage_s=time.perf_counter() - t0,
                occupied=sum(q >= 0 for q in self._req),
                queue_depth=len(self._queue),
            )
            if mode == "preempt":
                for slot in range(self._b):
                    if self._req[slot] >= 0:
                        self._unadmit(slot)
                        self._c_preempt.inc()
            # An idle engine (and every preempt-mode swap) commits here
            # and now; a draining engine commits in the step() that
            # empties it.
            self._try_commit_swap()
        return True

    def _try_commit_swap(self) -> bool:
        # The atomic switch: between dispatches, only with EMPTY slots —
        # no in-flight request can ever straddle two versions.
        s = self._staged_swap
        if s is None or any(q >= 0 for q in self._req):
            return False
        with self.ledger.measure("swap", span="engine.swap", busy=True):
            if self._paged:
                # Old-params K/V must not seed new-params requests; slots
                # are empty, so every retained page is reference-free.
                self._drop_prefix_registry()
            self._installed = s["raw"]
            # Prime the identity-keyed cast cache with the STAGED trees:
            # the next dispatch's _cast_params hits it, so the swap costs
            # the hot path nothing (staging already cast and resharded).
            self._cast_src = s["raw"]
            self._cast_out = s["cast"]
            self._clear_dispatch_args()
            prev = self.weights_version
            self.weights_version = s["version"]
            self._staged_swap = None
            stall = time.perf_counter() - s["staged_t"]
            self._c_swap_commits.inc()
            self._h_swap_stall.observe(stall)
            self.recorder.record(
                "engine.swap_commit", version=s["version"], previous=prev,
                mode=s["mode"], stall_s=stall,
            )
            if self.trace_sink is not None:
                # Version-pin attribution: every request still queued
                # here will (re-)admit under the NEW version — the pin
                # lands on its trace, so a swap-preempt recompute's
                # before/after legs are tell-apart-able by version.
                for r in self._queue:
                    self.trace_sink.instant(
                        r.rid, "swap_pin", replica=self.trace_replica,
                        version=s["version"], previous=prev,
                        stall_s=stall,
                    )
        return True

    def add_request(
        self, prompt, *, rid: int | None = None,
        deadline_s: float | None = None,
        arrival_t: float | None = None,
        adapter: str | None = None,
        tenant: str | None = None,
    ) -> int:
        """Enqueue one request (the arrival process). Returns its id —
        the key ``pop_finished()`` will report it under, and (at
        ``temperature > 0``) the identity its sampling streams are keyed
        by. Admission happens inside a later ``step()``.

        ``deadline_s`` overrides the engine's default TTL for this
        request (arrival-to-retirement; exceeded → failed with status
        ``"deadline"``). Raises :class:`AdmissionError` when admission
        control sheds the arrival (queue at ``max_queue``, or the
        degradation ladder at its shedding level) — nothing is
        enqueued, so the caller can back off.

        ``arrival_t`` (a ``time.perf_counter`` stamp) preserves the
        ORIGINAL arrival clock when re-queuing after a failover drain
        (``drain_requests``) — deadlines and queue-wait telemetry then
        measure the request's true age, not its age on this replica.

        ``adapter`` names an :class:`~learning_jax_sharding_tpu.tenancy.
        AdapterPool` tenant (engine built with ``adapter_pool=``): every
        token of this request is then generated against the BASE +
        tenant-adapter merged weights inside the fused multi-LoRA step.
        The adapter is ACQUIRED here (refcounted — it cannot be evicted
        while this request is live) and released at retirement.

        ``tenant`` labels the request for per-tenant cost attribution
        and SLO burn accounting (round 20): the retirement's SLO
        observations carry it, and the fleet's TraceStore record is
        minted with it — purely observational, never a routing input.
        """
        p = np.asarray(prompt, np.int32).reshape(-1)
        self._validate_prompt(p)
        if adapter is not None and self._adapter_pool is None:
            raise ValueError(
                "adapter= requires an engine built with adapter_pool="
            )
        if self._shed_all or (
            self._max_queue is not None
            and len(self._queue) >= self._max_queue
        ):
            self._c_shed.inc()
            why = (
                "degradation ladder is shedding"
                if self._shed_all
                else f"queue full ({self._max_queue})"
            )
            self.recorder.record(
                "engine.shed", reason=why, queue_depth=len(self._queue),
            )
            raise AdmissionError(f"request shed: {why}")
        if deadline_s is not None:
            if deadline_s <= 0:
                raise ValueError(
                    f"deadline_s must be > 0, got {deadline_s}"
                )
            self._any_req_deadline = True
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            # An explicit id must be unique among everything live NOW
            # (silent result overwrite in _finished otherwise) and must
            # not collide with later auto-assigned ones.
            if (
                rid in self._finished
                or rid in self._req
                or any(r.rid == rid for r in self._queue)
            ):
                raise ValueError(f"request id {rid} already in use")
            self._next_rid = max(self._next_rid, rid + 1)
        if adapter is not None:
            # Acquire BEFORE enqueueing: an unknown tenant raises here
            # (nothing enqueued), and the refcount pins the adapter's
            # pool slot for the request's whole lifetime.
            self._adapter_pool.acquire(adapter)
        now = time.perf_counter()
        self._queue.append(
            _Request(
                rid=rid, prompt=p,
                arrival_t=now if arrival_t is None else arrival_t,
                deadline_s=deadline_s,
                version=self.weights_version,
                adapter=adapter,
                enqueue_t=now,
                tenant=tenant,
            )
        )
        self._c_requests.inc()
        self._g_queue.set(len(self._queue))
        if self.trace_sink is not None:
            # Solo engines mint here; under a fleet router the id was
            # minted at ROUTER admission and this is an idempotent
            # lookup (reroutes re-enqueue under the same rid → same
            # trace id, the continuity the tracecontext tests pin).
            self.trace_sink.mint(
                rid, arrival_t=self._queue[-1].arrival_t, tenant=tenant,
            )
        self.tracer.instant(
            "request.arrival", rid=rid, prompt_len=int(p.size)
        )
        self.recorder.record(
            "engine.arrival", rid=rid, prompt_len=int(p.size),
            queue_depth=len(self._queue),
        )
        return rid

    def has_work(self) -> bool:
        # A staged-but-uncommitted weight swap is work: it takes one
        # more step() to commit, and a driver that stops stepping at
        # "no requests left" must not strand the engine mid-swap.
        return (
            bool(self._queue)
            or any(r >= 0 for r in self._req)
            or self._staged_swap is not None
        )

    @property
    def swap_pending(self) -> bool:
        """True while a staged weight swap awaits its commit (drivers
        that pace their own swap cadence poll this instead of staging
        on top of a pending one, which raises)."""
        return self._staged_swap is not None

    def queue_depth(self) -> int:
        """Requests waiting for a slot — the fleet router's load probe."""
        return len(self._queue)

    def active_slots(self) -> int:
        """Slots actively decoding right now."""
        return int(self._active.sum())

    def occupied_slots(self) -> int:
        """Slots holding a request — decoding OR mid-prefill (a slot is
        occupied from admission, before its first decode token; the
        fleet placement score must see that load too)."""
        return sum(1 for r in self._req if r >= 0)

    def free_slots(self) -> int:
        """Idle slots available for admission or external KV ingestion."""
        return sum(1 for r in self._req if r < 0)

    def pop_finished(self) -> dict[int, Any]:
        """Collect every request RETIRED since the last pop. Completed
        requests map to their ``[prompt, generated...]`` token array;
        requests that hit a recovery policy (deadline TTL, poison
        quarantine, malformed admission, ``close()``) map to a
        :class:`RequestFailure` carrying the terminal status and any
        partial tokens — an error is a result, never a silent drop."""
        fin = {
            rid: (
                r.tokens if r.status == "ok"
                else RequestFailure(
                    rid=rid, status=r.status, error=r.error, tokens=r.tokens,
                )
            )
            for rid, r in self._finished.items()
        }
        self._finished = {}
        return fin

    # --- disaggregated prefill/decode handoff (round 11) -------------------

    def _check_handoff_supported(self, what: str):
        if self._ssm:
            raise ValueError(
                f"{what}: engines with state-space layers are not supported "
                "— the transfer plans move K and V rows, and a request's "
                "recurrent state would have to travel with them"
            )
        if self._latent:
            raise ValueError(
                f"{what}: latent-attention engines are not supported — the "
                "transfer plans move (B, L, N_kv, H) K and V rows, and a "
                "latent cache holds one [c_kv | k_rope] row a token"
            )
        if self._speculative:
            raise ValueError(
                f"{what}: speculative engines are not supported — the "
                "draft cache would have to ride the handoff in lockstep"
            )
        if self._paged:
            raise ValueError(
                f"{what}: paged engines are not supported — rows live "
                "behind host-owned block tables, not contiguous cache rows"
            )
        if self._adapter_pool is not None:
            raise ValueError(
                f"{what}: multi-LoRA engines are not supported — a handed-"
                "off row's K/V was computed under a tenant adapter the "
                "receiving engine may not hold"
            )

    def ensure_cache(self, params, draft_params=None):
        """Create the engine's (zeroed) KV cache WITHOUT admitting work —
        the disaggregated-decode bring-up hook: ``ingest_kv`` and
        ``kv_row_shardings`` need the cache arrays (and the shardings the
        compiler gave them) to exist before the first external row lands.
        Runs the one-shot cache-creating program with an all-zero-length
        chunk (no writes, no advances — the same trick the paged path
        uses), so ``cache_creations`` counts it like any other creation.
        No-op when the cache already exists."""
        self._check_draft_args(draft_params)
        params, d_params = self._cast_params(params, draft_params)
        if self._cache is not None:
            return
        with activate(self._mesh, self._rules):
            # Outside step(), which is what the ledger covers.
            self._create_cache(params, d_params, frame=False)
            if self._paged:
                self._cache = self._set_tables(self._cache, frame=False)

    def _create_cache(self, params, d_params, *, frame=True):
        """Create faithful zero caches with a NO-OP first refill (every
        length 0 — no writes, no advances): a paged engine's first real
        chunk then runs through the steady-state path with the block
        tables already installed, and a multi-LoRA engine never streams
        prompt CONTENT through the base weights."""
        prog = self._programs["first_refill"]
        first_args = (
            params, d_params,
            jnp.zeros((self._b, self._refill_chunk), jnp.int32),
            jnp.zeros((self._b,), jnp.int32), self._rid_arr(), self.rng,
        )
        with self._led_device(prog) if frame else contextlib.nullcontext():
            self._cache = prog.fn(*first_args)[1]
        self._book_cache_creation(first_args)

    def _book_cache_creation(self, first_args):
        self.cache_creations += 1
        self._c_creations.inc()
        leaves = [
            (getattr(path[-1], "key", None), x)
            for path, x in jax.tree_util.tree_flatten_with_path(self._cache)[0]
        ]
        self._g_donated_bytes.set(sum(
            x.nbytes for key, x in leaves if key != "block_table"
        ))
        if self._ssm:
            self._g_ssm_bytes.set(sum(
                x.nbytes for key, x in leaves if key in _SLOT_STATE_KEYS
            ))
        # The blocked kernel's cache layers (the dense path names its
        # leaves otherwise), by the rule the kernel itself applies to what
        # one device holds of them.
        from learning_jax_sharding_tpu.ops.decode_attention import (
            auto_block_k,
            pages_in_flight,
        )

        def depth(x):
            shape = x.sharding.shard_shape(x.shape)
            return pages_in_flight(
                shape, x.dtype,
                shape[2] if self._paged
                else self._cfg.decode_block_k or auto_block_k(shape[2]),
                latent=self._latent,
            )

        self._g_attn_depth.set(min(
            (depth(x) for key, x in leaves if key == "cached_kv"), default=0
        ))
        self.recorder.record("engine.cache_create", n=self.cache_creations)
        self._programs["first_refill"].last_args = lambda: first_args

    def kv_row_shardings(self):
        """Per-leaf :class:`~jax.sharding.NamedSharding` of ONE cache row
        (the batch dim dropped) — the destination layout a KV transfer
        plan reshards into (``fleet.kv_transfer.transfer_tree``). Rows
        delivered in this layout make ``kv_ingest`` a purely local
        update, which is exactly what its golden contract pins."""
        if self._cache is None:
            raise RuntimeError(
                "kv_row_shardings: the engine holds no cache yet — call "
                "ensure_cache(params) first"
            )
        from jax.sharding import NamedSharding, PartitionSpec

        def leaf(x):
            spec = getattr(x.sharding, "spec", None)
            if spec is None or len(tuple(spec)) == 0:
                return NamedSharding(self._mesh, PartitionSpec())
            return NamedSharding(self._mesh, PartitionSpec(*tuple(spec)[1:]))

        return jax.tree.map(leaf, self._cache)

    def kv_row_seq_dims(self):
        """Per-leaf SEQUENCE dim of one cache row (``-1`` = no sequence
        dim — transfer the leaf whole; a plain int, not None, so the
        map stays a well-formed pytree), for the transfer plan's
        valid-length clipping. Derived from the row SHAPES, not assumed:
        the dense decode backend caches rows sequence-major
        ``(S, n_kv, h)`` but the blocked backend (the TPU ``auto``
        default) is HEAD-major ``(n_kv, S, h)`` — a hard-coded dim 0
        would clip the KV-heads dim there and hand the decode replica
        zeroed heads. A row dim is the sequence dim iff it is the ONE
        dim sized ``max_seq_len``; ambiguous shapes fall back to -1
        (whole-leaf transfer: always correct, just unclipped)."""
        if self._cache is None:
            raise RuntimeError(
                "kv_row_seq_dims: the engine holds no cache yet — call "
                "ensure_cache(params) first"
            )
        s = self._cfg.max_seq_len

        def leaf(x):
            if x.ndim < 2:
                return -1
            row_shape = tuple(x.shape[1:])
            hits = [d for d, n in enumerate(row_shape) if n == s]
            return hits[0] if len(hits) == 1 else -1

        return jax.tree.map(leaf, self._cache)

    def export_kv(self, rid: int):
        """DISAGGREGATED-PREFILL hook: ``(rows, length)`` for a request
        that RETIRED here — every cache leaf's row for the slot it
        occupied (counters included; one fixed-shape executable), plus
        the row's valid length (``prompt + generated − 1``: the last
        emitted token was never written back). Valid until a later
        admission reuses the slot, so export immediately after the
        ``step()`` that retired the request — the fleet router does.
        ``length`` bounds the transfer plan: bytes past it are invisible
        to the causal-at-index masks and never cross the wire."""
        self._check_handoff_supported("export_kv")
        slot = self._export_ok.get(rid)
        if slot is None:
            raise KeyError(
                f"request {rid} is not exportable: it never retired here, "
                "or its slot was already reused by a later admission"
            )
        if self._cache is None:
            raise RuntimeError("export_kv: the engine holds no cache")
        with self.ledger.measure(
            "kv_handoff", span="engine.kv_handoff", busy=True
        ):
            slot_j = jnp.int32(slot)
            prog = self._programs["kv_export"]
            with activate(self._mesh, self._rules):
                rows = prog.fn(self._cache, slot_j)
            # Read the LIVE cache at relower time (like the decode
            # programs' slots) — capturing the tuple would pin this
            # moment's cache tree in HBM after later dispatches replace it.
            prog.last_args = lambda: (self._cache, slot_j)
            length = max(0, self._plen[slot] + self._emitted[slot] - 1)
            self._c_kv_exports.inc()
            self.recorder.record(
                "engine.kv_export", rid=rid, slot=slot, length=length,
            )
        return rows, length

    def ingest_kv(
        self, params, prompt, first_token, rows, *, rid: int,
        deadline_s: float | None = None,
        arrival_t: float | None = None,
        admit_t: float | None = None,
        first_token_t: float | None = None,
        tenant: str | None = None,
    ) -> int:
        """EXTERNAL KV INGESTION: occupy a free slot with a request whose
        PREFILL RAN ON ANOTHER ENGINE — write its transferred cache
        ``rows`` (an ``export_kv`` tree, resharded to this mesh by the
        fleet transfer plan), set the row's counters to the prompt
        length, and mark it decoding with ``first_token`` pending. The
        request then advances through the normal ``step()`` path; greedy
        AND sampled streams are bit-identical to serving the whole
        request on one engine of the same mesh shape (the rows hold
        exactly the bytes this engine's own prefill would have written,
        and every sampling draw is keyed by (request id, generated
        position) — test-pinned). The ``*_t`` stamps carry the request's
        ORIGINAL clock across the handoff so deadlines and latency
        percentiles stay honest. Returns the slot taken; raises
        ``RuntimeError`` when no slot is free (the router holds the
        handoff until one is)."""
        self._check_handoff_supported("ingest_kv")
        with self.ledger.measure(
            "kv_handoff", span="engine.kv_handoff", busy=True
        ):
            p = np.asarray(prompt, np.int32).reshape(-1)
            self._validate_prompt(p)
            if (
                rid in self._finished
                or rid in self._req
                or any(r.rid == rid for r in self._queue)
            ):
                raise ValueError(f"request id {rid} already in use")
            self._next_rid = max(self._next_rid, rid + 1)
            slot = next(
                (s for s in range(self._b) if self._req[s] < 0), None
            )
            if slot is None:
                raise RuntimeError(
                    "ingest_kv: no free slot — poll free_slots() before "
                    "transferring"
                )
            self.ensure_cache(params)
            slot_j, idx_j = jnp.int32(slot), jnp.int32(int(p.size))
            prog = self._programs["kv_ingest"]
            with activate(self._mesh, self._rules):
                # (last_args: only the one transferred row tree stays
                # retained for relowering, see export_kv.)
                self._cache = self._enqueue(
                    prog, (), (rows, slot_j, idx_j), frame=False
                )
            now = time.perf_counter()
            r = _Request(
                rid=rid, prompt=p,
                arrival_t=now if arrival_t is None else arrival_t,
                deadline_s=deadline_s,
                version=self.weights_version,
                tenant=tenant,
            )
            r.admit_t = now if admit_t is None else admit_t
            r.first_token_t = now if first_token_t is None else first_token_t
            r.enqueue_t = now
            # Prefill ran on ANOTHER engine: this engine's trace legs
            # must cover only its own decode work (the handoff leg is the
            # router's to record — it saw both ends of the transfer).
            r.ingested = True
            if deadline_s is not None:
                self._any_req_deadline = True
            self._export_ok = {
                k: v for k, v in self._export_ok.items() if v != slot
            }
            self._slot_req[slot] = r
            self._req[slot] = rid
            # An ingested row's clock opens HERE: ``decode`` and ``stall``
            # only, from its ingestion (the hand-off is the router's leg).
            self._open_clock(slot, now)
            self._plen[slot] = int(p.size)
            self._pending[slot] = np.zeros((0,), np.int32)
            self._emitted[slot] = 1
            self._out[slot] = list(p) + [int(first_token)]
            self._ttimes[slot] = [r.first_token_t]
            self._tok[slot] = int(first_token)
            self._needs_reset[slot] = False
            self._reset_to[slot] = 0
            self._c_requests.inc()
            self._c_kv_ingests.inc()
            self.tracer.async_begin(
                "request", rid, prompt_len=int(p.size), slot=slot,
            )
            self.recorder.record(
                "engine.kv_ingest", rid=rid, slot=slot, length=int(p.size),
            )
            if (
                self._eos is not None and int(first_token) == self._eos
            ) or self._max_new <= 1:
                # The handed-off first token already ends the request.
                self._retire(slot, now, [])
            else:
                self._active[slot] = True
                self._g_active.set(int(self._active.sum()))
        return slot

    # --- KV tier ladder (round 15): prefix digest + page spill/fill --------

    @staticmethod
    def prefix_hash(key: bytes) -> bytes:
        """The 8-byte digest hash of one registry key (page-aligned
        token-prefix bytes) — the unit :meth:`prefix_digest` exports and
        the router matches prompt chains against."""
        return hashlib.blake2b(key, digest_size=8).digest()

    def prefix_digest(self) -> tuple[int, frozenset]:
        """``(epoch, hashes)`` — a compact, queryable digest of the
        prefix registry for PREFIX-AWARE FLEET PLACEMENT: one
        :meth:`prefix_hash` per registered page-aligned token prefix.
        The router hashes an arriving prompt's page chain and walks it
        against each replica's digest to predict the longest cached
        prefix BEFORE placing the request. ``epoch`` bumps on any
        registry key change (register / evict / spill / fill /
        swap-commit flush), so a cached digest is valid exactly while
        its epoch matches; the memo makes steady-state queries O(1)."""
        if not (self._paged and self._prefix):
            return (self.prefix_epoch, frozenset())
        if (
            self._digest_cache is None
            or self._digest_cache[0] != self.prefix_epoch
        ):
            self._digest_cache = (
                self.prefix_epoch,
                frozenset(
                    self.prefix_hash(k) for k in self._prefix_registry
                ),
            )
        return self._digest_cache

    def retained_prefixes(self) -> list[bytes]:
        """Registry keys of the REFERENCE-FREE retained pages, oldest
        (LRU-eviction order) first — the tier ladder's demotion
        candidates. Pages shared by live slots are excluded: they cannot
        leave HBM mid-request."""
        if not (self._paged and self._prefix):
            return []
        return [
            self._key_of_page[pid]
            for pid in self._cached_lru
            if pid in self._key_of_page
        ]

    def touch_prefix(self, key: bytes) -> bool:
        """LRU-refresh a resident reference-free prefix page. The tier
        ladder touches a chain's RESIDENT ancestors before promoting its
        missing descendants, so the promotion's own ``_take_page`` calls
        cannot evict the chain out from under itself. No-op (``False``)
        if the key is unregistered or the page is shared by a live
        slot."""
        if not (self._paged and self._prefix):
            return False
        pid = self._prefix_registry.get(key)
        if pid is None or pid not in self._cached_lru:
            return False
        self._cached_lru.pop(pid)
        self._cached_lru[pid] = None
        return True

    def _check_tier_supported(self, what: str):
        if self._ssm:
            raise ValueError(
                f"{what}: engines with state-space layers are not tiered — "
                "a spilled page holds K and V alone, and the recurrent "
                "state that goes with a prefix has no snapshot to spill"
            )
        if not (self._paged and self._prefix):
            raise RuntimeError(
                f"{what} requires a paged engine with prefix_cache=True"
            )
        if self._speculative:
            # A spec engine's retained pages hold target AND draft K/V
            # under one page id; spilling only the target leaves would
            # hand a promoted page garbage draft state. Tier the plain
            # engines; spec replicas serve prefix hits from HBM only.
            raise RuntimeError(f"{what}: speculative engines are not tiered")

    def _page_row_shardings(self) -> list:
        """Per-leaf :class:`~jax.sharding.NamedSharding` of ONE page row
        (the pool dim dropped), flatten-ordered like ``kv_page_spill``'s
        output list — the destination layout host→HBM promotion reshards
        into, making ``kv_page_fill`` a purely local update (what its
        golden pins)."""
        from jax.sharding import NamedSharding, PartitionSpec

        rows = []
        for path, x in jax.tree_util.tree_flatten_with_path(self._cache)[0]:
            if getattr(path[-1], "key", None) not in _PAGE_LEAF_KEYS:
                continue
            spec = getattr(x.sharding, "spec", None)
            if spec is None or len(tuple(spec)) == 0:
                rows.append(NamedSharding(self._mesh, PartitionSpec()))
            else:
                rows.append(
                    NamedSharding(
                        self._mesh, PartitionSpec(*tuple(spec)[1:])
                    )
                )
        return rows

    def spill_page(self, key: bytes, *, drop: bool = True, base_rows=None):
        """DEMOTE one retained prefix page out of HBM: gather its K/V
        rows (``kv_page_spill``, one fixed-shape executable) and move
        them to host numpy through the counted
        ``parallel.resharding`` segment plan — every spilled byte is
        priced and booked to the ledger's ``kv_handoff`` bucket. With
        ``drop=True`` (demotion) the page leaves the registry and
        returns to the free pool; ``drop=False`` is a NON-DESTRUCTIVE
        read — the peer-tier path, where another replica copies this
        replica's warm page without disturbing it. Returns
        ``(rows, stats)``: flatten-ordered host page rows (the
        ``fill_page`` input) and ``{"bytes", "raw_bytes", "segments"}``
        — ``bytes`` is WIRE bytes: with a ``comm_compression`` KV codec
        attached the rows ship as block-scaled int8 through the plan's
        codec seam and land decoded (on the int8 grid) host-side, so a
        later re-spill of the same rows is bit-identical (quantization
        is a fixed point on its own image). ``base_rows`` (same
        flatten order, or ``None``) is the delta codec's
        version-stamped base: with ``kv_codec="int8_delta"`` only
        blocks that changed since the base version ship, so a tier
        re-demotion after a version bump pays for the novel suffix,
        not the whole page."""
        self._check_tier_supported("spill_page")
        pid = self._prefix_registry.get(key)
        if pid is None:
            raise KeyError("spill_page: key not in the prefix registry")
        if drop and pid not in self._cached_lru:
            raise RuntimeError(
                "spill_page(drop=True): page is shared by live slots — "
                "it cannot leave HBM mid-request"
            )
        if self._cache is None:
            raise RuntimeError("spill_page: the engine holds no cache")
        from learning_jax_sharding_tpu.parallel.resharding import (
            HostBuffer,
            execute_transfer,
            plan_transfer,
        )

        with self.ledger.measure(
            "kv_handoff", span="engine.kv_handoff", busy=True
        ):
            pid_j = jnp.int32(pid)
            prog = self._programs["kv_page_spill"]
            with activate(self._mesh, self._rules):
                dev_rows = prog.fn(self._cache, pid_j)
            # Live-cache closure (see export_kv): relowering reads the
            # engine's CURRENT cache, never a pinned stale copy.
            prog.last_args = lambda: (self._cache, pid_j)
            codec = self._kv_codec
            ckey = (
                (codec.name, getattr(codec, "block", 0))
                if codec is not None else None
            )
            host = HostBuffer()
            rows, nbytes, raw_bytes, nsegs = [], 0, 0, 0
            for i, x in enumerate(dev_rows):
                base = base_rows[i] if base_rows is not None else None
                pkey = (
                    tuple(x.shape), str(x.dtype), x.sharding, "spill", ckey,
                )
                plan = self._page_plan_cache.get(pkey)
                if plan is None:
                    plan = plan_transfer(
                        x.shape, x.dtype.itemsize, x.sharding, host,
                        seq_dim=None, page_tokens=None, codec=codec,
                    )
                    self._page_plan_cache[pkey] = plan
                buf, stats = execute_transfer(plan, x, base=base)
                rows.append(buf)
                nbytes += stats["bytes"]
                raw_bytes += stats.get("raw_bytes", stats["bytes"])
                nsegs += stats["segments"]
            if drop:
                del self._cached_lru[pid]
                del self._prefix_registry[self._key_of_page.pop(pid)]
                del self._refcnt[pid]
                self._free_pages.append(pid)
                self.prefix_epoch += 1
                self._update_high_water()
            self._c_pg_spills.inc()
            self._c_pg_bytes_out.inc(nbytes)
            self._c_kv_raw_bytes.inc(raw_bytes)
            if nbytes:
                self._g_comp_ratio.set(raw_bytes / nbytes)
            self.recorder.record(
                "engine.kv_page_spill", pid=pid, bytes=nbytes,
                raw_bytes=raw_bytes, segments=nsegs, dropped=drop,
            )
        return rows, {
            "bytes": nbytes, "raw_bytes": raw_bytes, "segments": nsegs,
        }

    def fill_page(self, key: bytes, rows) -> dict:
        """PROMOTE a spilled page back into HBM: take a physical page
        (may LRU-evict a colder retained page), commit the host rows
        under this cache's page-row layout through the counted host
        plan, write them in with ``kv_page_fill``, and register ``key``
        as a reference-free retained page (LRU-newest). The next
        admission whose prompt chain reaches ``key`` maps it like any
        HBM-resident prefix page. Returns ``{"bytes", "raw_bytes",
        "segments", "pid"}`` (``bytes`` is wire bytes — the same codec
        seam as :meth:`spill_page`, and re-encoding already-quantized
        spill output is exact, so a spill → fill → spill round trip is
        bit-stable at page boundaries); raises if ``key`` is already
        resident (promotion is not idempotent — check the digest
        first)."""
        self._check_tier_supported("fill_page")
        if key in self._prefix_registry:
            raise ValueError("fill_page: key is already resident")
        if self._cache is None:
            raise RuntimeError(
                "fill_page: the engine holds no cache — ensure_cache() "
                "or serve a request first"
            )
        from learning_jax_sharding_tpu.parallel.resharding import (
            HostBuffer,
            execute_transfer,
            plan_transfer,
        )

        with self.ledger.measure(
            "kv_handoff", span="engine.kv_handoff", busy=True
        ):
            with self.ledger.measure(
                "page_alloc", span="engine.page_alloc", ring=False
            ):
                pid = self._take_page()
            codec = self._kv_codec
            ckey = (
                (codec.name, getattr(codec, "block", 0))
                if codec is not None else None
            )
            host = HostBuffer()
            dev_rows, nbytes, raw_bytes, nsegs = [], 0, 0, 0
            for x, dst in zip(rows, self._page_row_shardings()):
                buf = np.asarray(x)
                pkey = (tuple(buf.shape), str(buf.dtype), dst, "fill", ckey)
                plan = self._page_plan_cache.get(pkey)
                if plan is None:
                    plan = plan_transfer(
                        buf.shape, buf.dtype.itemsize, host, dst,
                        seq_dim=None, page_tokens=None, codec=codec,
                    )
                    self._page_plan_cache[pkey] = plan
                out, stats = execute_transfer(plan, buf)
                dev_rows.append(out)
                nbytes += stats["bytes"]
                raw_bytes += stats.get("raw_bytes", stats["bytes"])
                nsegs += stats["segments"]
            pid_j = jnp.int32(pid)
            prog = self._programs["kv_page_fill"]
            with activate(self._mesh, self._rules):
                # (last_args: only the one promoted row list stays
                # retained for relowering.)
                self._cache = self._enqueue(
                    prog, (), (dev_rows, pid_j), frame=False
                )
            self._prefix_registry[key] = pid
            self._key_of_page[pid] = key
            self._refcnt[pid] = 0
            self._cached_lru[pid] = None
            self.prefix_epoch += 1
            self._update_high_water()
            self._c_pg_fills.inc()
            self._c_pg_bytes_in.inc(nbytes)
            self._c_kv_raw_bytes.inc(raw_bytes)
            if nbytes:
                self._g_comp_ratio.set(raw_bytes / nbytes)
            self.recorder.record(
                "engine.kv_page_fill", pid=pid, bytes=nbytes,
                raw_bytes=raw_bytes, segments=nsegs,
            )
        return {
            "bytes": nbytes, "raw_bytes": raw_bytes, "segments": nsegs,
            "pid": pid,
        }

    # --- the request clock -------------------------------------------------
    #
    # One clock a request: every second between its admission and its
    # retirement is in exactly one of _PHASES. The clock ticks once a
    # readback, at the ``now`` the dispatch functions stamp first tokens
    # and retirements with, BEFORE their consume loop: so the interval
    # since the previous readback, host time in front of the dispatch
    # included, goes whole to the phase THIS dispatch put the request
    # in, and for every retired request, to float rounding,
    #
    #   refill_wait_s + refill_s == first_token_t - (its last admission)
    #   stall_s + decode_s       == finish_t - first_token_t
    #                            == tpot x (generated - 1)
    #
    # (an ingested row: from its ingestion here; the hand-off is the
    # router's leg). A request that fails or is preempted between two
    # readbacks is accounted up to that instant (_settle); what a
    # preemption throws away moves to ``redone_s`` and the wait for the
    # next admission to ``requeue_wait_s``, so that
    # e2e == queue_wait + redone_s + requeue_wait_s + the four phases.
    # A tick only NOTES the readback (``_tick``: its instant, who held a
    # first token, who rode); the books are written by ``_flush_ticks``
    # at the head of the next telemetry frame that reads or closes a
    # request's books (a retirement, a failure, the dispatch's own
    # books), so the clock opens no frame of its own: a frame costs more
    # than the loop it would wrap (PERF.md, Findings, PR 37).
    # tests/test_engine_spans.py pins the names and the identities;
    # scripts/engine_breakdown.py prints them from a bundle.

    def _open_clock(self, slot, now):
        """``slot`` admits (or ingests) a request at ``now``."""
        self._flush_ticks()
        self._ph_s[slot] = [0.0] * len(_PHASES)
        self._ph_n[slot] = [0] * len(_PHASES)
        self._ph_last[slot] = self._ph_admit[slot] = now

    def _tick(self, now, carried=(), advanced=None, moe=()):
        """A dispatch's readback returned at ``now``: every live slot's
        seconds since its last tick go to the phase the dispatch put its
        request in. ``carried``: the slots whose chunk rows rode the
        dispatch (anything that answers ``slot in carried``);
        ``advanced``: the slots it could give tokens to, ``(slots,)``
        bool, or True for every holder of a first token. Read against
        ``_active`` as it stands NOW, before the readback's first tokens
        are stamped: a slot without a first token is refilling or waiting
        to, one with it decodes or stalls. ``moe``: the readback's
        ``(phase, expert counts)``. Noted here, booked by
        ``_flush_ticks``."""
        first = self._active.tolist()
        if advanced is not None:
            advanced = first if advanced is True else advanced.tolist()
        self._ticks.append((now, first, carried, advanced, moe))

    def _flush_ticks(self):
        """Write the books of the readbacks noted since the last flush.
        Inside the caller's telemetry frame, and before anything frees
        or fills a slot: the slots live now are those that were live at
        each of them."""
        ticks = self._ticks
        if not ticks:
            return
        n_phases = len(_PHASES)
        seconds, hits = [0.0] * n_phases, [0] * n_phases
        last, ph_s, ph_n = self._ph_last, self._ph_s, self._ph_n
        live = [slot for slot, rid in enumerate(self._req) if rid >= 0]
        for now, first, carried, advanced, moe in ticks:
            for phase, stats in moe:
                self._book_moe(phase, stats)
            for slot in live:
                if first[slot]:
                    p = _DECODE if advanced and advanced[slot] else _STALL
                else:
                    p = _REFILL if slot in carried else _REFILL_WAIT
                dt = now - last[slot]
                last[slot] = now
                ph_s[slot][p] += dt
                ph_n[slot][p] += 1
                seconds[p] += dt
                hits[p] += 1
        ticks.clear()
        for p, n in enumerate(hits):
            if n:
                c_seconds, c_hits = self._c_phase[p]
                c_seconds.inc(seconds[p])
                c_hits.inc(n)
                self._ph_event[p] += n

    def _settle(self, slot, now):
        """Account ``slot``'s request up to ``now`` between two readbacks
        (it fails, or is preempted): it was waiting, for a refill turn or
        behind one. No dispatch is counted."""
        self._flush_ticks()
        p = _STALL if self._active[slot] else _REFILL_WAIT
        dt = now - self._ph_last[slot]
        self._ph_s[slot][p] += dt
        self._ph_last[slot] = now
        self._c_phase[p][0].inc(dt)

    def _request_phases(self, slot, r, now):
        """The request clock's fields of ``r`` (in ``slot``, accounted up
        to ``now``) for its ``engine.retire`` / ``engine.request_failed``
        event and its ``latency_stats()`` record. ``*_unix`` put the
        admission the phases count from and the first token on the
        recorder's ``t`` clock."""
        refill_wait_s, refill_s, stall_s, decode_s = self._ph_s[slot]
        _, refills, stalls, decodes = self._ph_n[slot]
        gaps = self._emitted[slot] - 1
        first_token_t = r.first_token_t
        unix = time.time() - time.perf_counter()
        return dict(
            queue_wait=r.admit_t - r.arrival_t,
            tpot=(
                (now - first_token_t) / gaps
                if gaps > 0 and first_token_t is not None else None
            ),
            refill_wait_s=refill_wait_s, refill_s=refill_s,
            stall_s=stall_s, decode_s=decode_s,
            refill_dispatches=refills, stall_dispatches=stalls,
            decode_dispatches=decodes,
            stall_per_token_s=stall_s / gaps if gaps > 0 else None,
            decode_per_token_s=decode_s / gaps if gaps > 0 else None,
            redone_s=r.redone_s, requeue_wait_s=r.requeue_wait_s,
            admit_unix=self._ph_admit[slot] + unix,
            first_token_unix=(
                first_token_t + unix if first_token_t is not None else None
            ),
        )

    def _retire(self, slot, now, retired):
        r = self._slot_req[slot]
        r.tokens = np.asarray(self._out[slot], np.int32)
        r.finish_t = now
        n = self._emitted[slot]
        times = self._ttimes[slot]
        gaps = [b - a for a, b in zip(times, times[1:])]
        self._itl.extend(gaps)
        # Histograms carry the same observations for export; the exact
        # percentiles in latency_stats() stay sample-based (pinned). All
        # of this booking is the observability tax — it lands in the
        # ledger's telemetry bucket so perf_goodput.py can pin it.
        with self.ledger.measure("telemetry", span="engine.telemetry"):
            self._flush_ticks()
            phases = self._request_phases(slot, r, now)
            rec = dict(
                rid=r.rid,
                prompt_len=int(r.prompt.size),
                generated=n,
                ttft=(
                    r.first_token_t - r.arrival_t
                    if r.first_token_t is not None else None
                ),
                e2e=now - r.arrival_t,
                **phases,
            )
            self._completed.append(rec)
            self._c_finished.inc()
            self._c_tokens.inc(n)
            self._h_wait.observe(rec["queue_wait"])
            self._h_e2e.observe(rec["e2e"])
            if rec["ttft"] is not None:
                self._h_ttft.observe(rec["ttft"])
            if rec["tpot"] is not None:
                self._h_tpot.observe(rec["tpot"])
            self.tracer.async_end(
                "request", r.rid, generated=n,
                refill_wait_s=phases["refill_wait_s"],
                refill_s=phases["refill_s"], stall_s=phases["stall_s"],
                decode_s=phases["decode_s"],
            )
            self.recorder.record(
                "engine.retire", rid=r.rid, slot=slot, generated=n,
                ttft=rec["ttft"], e2e=rec["e2e"], version=r.version,
                **phases,
            )
            if self.slo is not None:
                ten = r.tenant
                self.slo.observe(
                    "queue_wait", rec["queue_wait"], tenant=ten
                )
                self.slo.observe("e2e", rec["e2e"], tenant=ten)
                if rec["ttft"] is not None:
                    self.slo.observe("ttft", rec["ttft"], tenant=ten)
                if rec["tpot"] is not None:
                    self.slo.observe("tpot", rec["tpot"], tenant=ten)
                for g in gaps:
                    self.slo.observe("itl", g, tenant=ten)
            if self.trace_sink is not None:
                self._record_trace_legs(r, now, generated=n, phases=phases)
                if self.trace_sink.auto_complete:
                    self.trace_sink.complete(
                        r.rid, status="ok", finish_t=now,
                    )
        self._finished[r.rid] = r
        # Version attribution (round 12): every response is traceable to
        # exactly ONE weights version — the one pinned at its (last)
        # admission. The zero-downtime swap oracle audits this log.
        self.finished_versions[r.rid] = r.version
        retired.append(r.rid)
        if r.adapter is not None and self._adapter_pool is not None:
            self._adapter_pool.release(r.adapter)
        # Open the export window (disaggregated handoff): the row's KV
        # stays intact until a later admission reuses this slot.
        self._export_ok[r.rid] = slot
        self._slot_req[slot] = None
        self._req[slot] = -1
        self._active[slot] = False
        self._aidx[slot] = 0
        if self._paged:
            self._release(slot)

    def _record_trace_legs(
        self, r, now, *, generated=0, wasted=False, status="ok",
        phases=None,
    ):
        """Append THIS engine's spans of ``r``'s journey to the trace
        sink, from the request's own stamps. The queue leg opens at
        ``enqueue_t`` (not the fleet ``arrival_t``): a rerouted request
        keeps its original arrival for deadlines and latency honesty,
        but it only waited HERE from its re-enqueue — the requeue gap
        shows up as the trace's ``stall``, which is the truth.
        ``wasted=True`` marks compute legs thrown away by a failover
        (they sum separately in the critical path). Ingested rows emit
        only a decode leg — their queue/prefill ran on the prefill
        replica and the handoff leg is the router's to record (it alone
        saw both ends of the transfer). ``phases`` (the request clock's
        fields, ``_request_phases``) hands the ``prefill`` leg its
        ``refill_wait_s`` and the ``decode`` leg its ``stall_s``: the
        store's critical path reports that measured stall beside its
        remainder."""
        ts = self.trace_sink
        rep = self.trace_replica
        waited = {} if phases is None else {
            "refill_wait_s": phases["refill_wait_s"]
        }
        stalled = {} if phases is None else {"stall_s": phases["stall_s"]}
        q0 = r.enqueue_t if r.enqueue_t is not None else r.arrival_t
        if r.admit_t is None:
            # Never admitted here: all wait, no compute to waste.
            ts.leg(r.rid, "queue", q0, now, replica=rep, status=status)
            return
        if r.ingested:
            ts.leg(
                r.rid, "decode", q0, now, replica=rep,
                generated=generated, version=r.version,
                wasted=wasted, status=status, **stalled,
            )
            return
        ts.leg(r.rid, "queue", q0, r.admit_t, replica=rep)
        ft = r.first_token_t
        if ft is None:
            # Died mid-prefill (chaos kill before the first token).
            ts.leg(
                r.rid, "prefill", r.admit_t, now, replica=rep,
                version=r.version, wasted=wasted, status=status, **waited,
            )
            return
        ts.leg(
            r.rid, "prefill", r.admit_t, ft, replica=rep,
            first_token_t=ft, version=r.version, wasted=wasted, **waited,
        )
        if now > ft:
            ts.leg(
                r.rid, "decode", ft, now, replica=rep,
                generated=generated, version=r.version,
                wasted=wasted, status=status, **stalled,
            )

    def _fail_request(
        self, r, status, error, *, now=None, tokens=None, slot=None,
    ):
        """Retire ``r`` with a terminal non-ok status: surfaced through
        ``pop_finished`` as a :class:`RequestFailure` — the recovery
        policies' one exit path (deadline, quarantine, malformed,
        shutdown). ``slot``: the slot it fails in (``_fail_slot``); its
        request clock is then accounted up to ``now`` and written to the
        event."""
        now = time.perf_counter() if now is None else now
        r.status = status
        r.error = error
        r.finish_t = now
        if tokens is not None:
            r.tokens = tokens
        with self.ledger.measure("telemetry", span="engine.telemetry"):
            self._c_req_failed.inc()
            if status == "rerouted":
                self._c_rerouted.inc()
            phases = None
            if slot is not None:
                self._settle(slot, now)
                phases = self._request_phases(slot, r, now)
            self.recorder.record(
                "engine.request_failed", rid=r.rid, status=status,
                error=error, **(phases or {}),
            )
            if r.admit_t is not None:
                # async_begin was issued at first admission; close the
                # span so the trace shows the failed request's full
                # lifetime.
                self.tracer.async_end("request", r.rid, status=status)
            if self.trace_sink is not None:
                # A reroute throws this engine's partial compute away —
                # the next engine recomputes it. Mark those legs wasted
                # so the fleet critical path separates real progress
                # from failover churn.
                self._record_trace_legs(
                    r, now,
                    wasted=(status == "rerouted"), status=status,
                    phases=phases,
                )
                if self.trace_sink.auto_complete and status != "rerouted":
                    self.trace_sink.complete(
                        r.rid, status=status, finish_t=now,
                    )
        if r.adapter is not None and self._adapter_pool is not None:
            self._adapter_pool.release(r.adapter)
        self._finished[r.rid] = r
        self.finished_versions[r.rid] = r.version

    def _fail_slot(self, slot, status, error, now=None):
        """Fail the request occupying ``slot`` and free the slot — the
        in-flight arm of :meth:`_fail_request` (partial output kept:
        the caller sees how far the request got)."""
        r = self._slot_req[slot]
        self._fail_request(
            r, status, error, now=now, slot=slot,
            tokens=np.asarray(self._out[slot], np.int32),
        )
        if self._paged:
            # Never register a failed request's pages: a deadline/poison
            # eviction can land mid-prefill, with pages partially written.
            self._release(slot, register=False)
        self._slot_req[slot] = None
        self._req[slot] = -1
        self._active[slot] = False
        self._aidx[slot] = 0
        self._pending[slot] = np.zeros((0,), np.int32)
        self._needs_reset[slot] = False
        self._reset_to[slot] = 0

    def _sweep_deadlines(self):
        """TTL eviction: fail every queued or in-flight request whose
        age exceeds its deadline (per-request ``deadline_s`` override,
        else the engine default). Skipped in O(1) when no deadline is
        configured anywhere."""
        if self._deadline_s is None and not self._any_req_deadline:
            return
        if self._deadline_s is None:
            # Engine-level TTL off: the sweep exists only for per-request
            # deadlines. Re-arm the O(1) skip once none remain live —
            # one early request with a TTL must not tax every later step
            # of the engine's lifetime.
            if not any(
                r.deadline_s is not None for r in self._queue
            ) and not any(
                r is not None and r.deadline_s is not None
                for r in self._slot_req
            ):
                self._any_req_deadline = False
                return
        with self.ledger.measure("admission", span="engine.admission"):
            now = time.perf_counter()

            def expired(r):
                dl = (
                    r.deadline_s if r.deadline_s is not None
                    else self._deadline_s
                )
                return dl is not None and now - r.arrival_t > dl

            if any(expired(r) for r in self._queue):
                keep = deque()
                for r in self._queue:
                    if expired(r):
                        self._c_deadline.inc()
                        self._fail_request(
                            r, "deadline", "deadline exceeded in queue",
                            now=now,
                        )
                    else:
                        keep.append(r)
                self._queue = keep
                self._g_queue.set(len(self._queue))
            for slot in range(self._b):
                r = self._slot_req[slot]
                if r is not None and expired(r):
                    self._c_deadline.inc()
                    self._fail_slot(
                        slot, "deadline", "deadline exceeded in flight",
                        now,
                    )

    def _on_dispatch_fault(self, e):
        """A dispatch raised a RECOVERABLE fault (injected NaN-trap /
        hang-watchdog abort). Every involved request earns a strike;
        requests at ``max_dispatch_strikes`` are FAILED as poison, the
        rest are requeued (recompute preemption — exact, see
        ``_unadmit``) and re-admitted ONE AT A TIME (probation, see
        ``_admit``) so the poison trips alone instead of striking its
        batchmates to death. The engine's device state needs no repair:
        re-admission resets every per-row counter. The one exception is a
        program that raised AFTER it took the cache (a donated argument is
        gone whether or not the call returns): that cache is dropped whole,
        with the page pool's host state as ``close()`` drops it, and the
        next dispatch creates a new one."""
        with self.ledger.measure("recovery", span="engine.recovery"):
            self._c_dispatch_faults.inc()
            self._flush_ticks()
            self._ph_event = [0] * len(_PHASES)   # no engine.dispatch event follows
            self.recorder.record(
                "engine.dispatch_fault",
                error=type(e).__name__, message=str(e),
                rids=[r for r in self._req if r >= 0],
            )
            now = time.perf_counter()
            for slot in range(self._b):
                r = self._slot_req[slot]
                if r is None:
                    continue
                r.strikes += 1
                if r.strikes >= self._max_strikes:
                    self._c_quarantined.inc()
                    self.recorder.record(
                        "engine.quarantine", rid=r.rid, strikes=r.strikes,
                    )
                    self._fail_slot(slot, "poisoned", str(e), now)
                else:
                    self._unadmit(slot)
            if self._cache is not None and any(
                x.is_deleted() for x in jax.tree.leaves(self._cache)
            ):
                self._cache = None
                self._export_ok = {}
                if self._paged:
                    self._init_pool()

    def _consume(self, slot, tokens, now, retired):
        # Append a decode dispatch's tokens for one slot; retire at
        # EOS or budget — ONE copy of the retirement rule for both
        # engine modes.
        out = self._out[slot]
        ctx = len(out)      # prompt + emitted: the cache length at the
        for t in tokens:    # step that emits the row's next token
            out.append(int(t))
            self._emitted[slot] += 1
            self._tok[slot] = int(t)
            self._ttimes[slot].append(now)
            if (self._eos is not None and t == self._eos) or (
                self._emitted[slot] >= self._max_new
            ):
                self._retire(slot, now, retired)
                break
        k = len(out) - ctx
        self._c_decode_steps.inc(k)
        self._c_decode_ctx.inc(k * ctx + k * (k - 1) // 2)

    def _rid_arr(self):
        return jnp.asarray(np.maximum(self._req, 0), jnp.int32)

    # --- the scheduler -----------------------------------------------------

    def _unadmit(self, slot):
        """Backpressure/preemption: push an in-flight request back to the
        queue head and free its slot — taken when the page pool cannot
        cover its next dispatch but OTHER slots still hold pages that
        will free as they retire. The request restarts from scratch on a
        later admission (RECOMPUTE preemption): any consumed chunks and
        emitted tokens are discarded and re-derived — EXACTLY, because
        greedy decoding is deterministic and every sampling draw is
        keyed by (request id, generated position), not by schedule. So
        preemption, like every other scheduling decision, cannot change
        results (test-pinned)."""
        r = self._slot_req[slot]
        self._queue.appendleft(r)
        self.tracer.instant("request.preempted", rid=r.rid, slot=slot)
        self.recorder.record("engine.preempt", rid=r.rid, slot=slot)
        # The request clock: this admission's seconds are work to redo.
        # They stay counted in the phases they ran in, and count again
        # under ``redone``.
        r.preempt_t = time.perf_counter()
        self._settle(slot, r.preempt_t)
        redone_s = sum(self._ph_s[slot])
        r.redone_s += redone_s
        c_seconds, c_hits = self._c_phase[_REDONE]
        c_seconds.inc(redone_s)
        c_hits.inc(sum(self._ph_n[slot]))
        if self._paged:
            self._release(slot, register=False)
        self._slot_req[slot] = None
        self._req[slot] = -1
        self._active[slot] = False
        self._aidx[slot] = 0
        self._pending[slot] = np.zeros((0,), np.int32)
        self._needs_reset[slot] = False
        self._reset_to[slot] = 0

    def _admission_ok(self, p: np.ndarray) -> str | None:
        """Cheap admission-time re-validation: the queue is not trusted
        between ``add_request`` and admission — a frontend race (or the
        chaos harness) can corrupt a queued prompt, and a malformed
        prompt must FAIL THE REQUEST, not wedge the slot or crash the
        scheduler. Shape/dtype here; sequence budgets via THE validator
        (``_validate_prompt`` — target AND draft configs), so the two
        paths cannot drift."""
        if p.ndim != 1 or p.dtype.kind not in "iu":
            return f"malformed prompt (shape {p.shape}, dtype {p.dtype})"
        try:
            self._validate_prompt(p)
        except ValueError as e:
            return str(e)
        return None

    def _pop_admittable(self):
        """The next request to admit, honoring PROBATION: while any
        request carries dispatch strikes, suspects are re-admitted ONE
        AT A TIME into an otherwise idle engine (so a poison request
        trips its fault alone and its former batchmates are never
        struck to quarantine alongside it), and nothing else admits
        until the suspects are cleared (completed or failed)."""
        if any(
            r is not None and r.strikes > 0 for r in self._slot_req
        ):
            return None             # a suspect is live: solo probation
        si = next(
            (i for i, r in enumerate(self._queue) if r.strikes > 0), None
        )
        if si is None:
            return self._queue.popleft()
        if any(q >= 0 for q in self._req):
            return None             # wait for idle before the next suspect
        r = self._queue[si]
        del self._queue[si]
        return r

    def _admit(self):
        if self._staged_swap is not None:
            # A staged swap DRAINS the engine: no new admissions until
            # occupancy hits zero and the commit flips versions — an
            # admission now would pin the OLD version onto a request that
            # outlives it. Queued requests keep their place; the very
            # step that commits re-runs admission under the new version.
            self._g_queue.set(len(self._queue))
            return
        b = self._b
        with self.ledger.measure("admission", span="engine.admission"):
            now = time.perf_counter()
            for slot in range(b):
                if self._req[slot] < 0 and self._queue:
                    r = self._pop_admittable()
                    if r is None:
                        break
                    r.prompt = np.asarray(
                        chaos_hook("engine.admit", value=r.prompt, rid=r.rid)
                    )
                    bad = self._admission_ok(r.prompt)
                    if bad is not None:
                        self.recorder.record(
                            "engine.malformed", rid=r.rid, error=bad,
                        )
                        self._fail_request(r, "malformed", bad, now=now)
                        continue
                    # A preempted request keeps its first admission time
                    # (and counts its prefix hit once — re-admission
                    # re-maps the same pages, not new savings).
                    first_admission = r.admit_t is None
                    if first_admission:
                        r.admit_t = now
                        self.tracer.async_begin(
                            "request", r.rid,
                            prompt_len=int(r.prompt.size), slot=slot,
                        )
                    self.tracer.instant(
                        "request.admit", rid=r.rid, slot=slot
                    )
                    self.recorder.record(
                        "engine.admit", rid=r.rid, slot=slot,
                        prompt_len=int(r.prompt.size),
                        readmission=not first_admission,
                    )
                    prompt = r.prompt
                    # (Re-)pin the weights version at EVERY admission: a
                    # preempted/requeued request recomputes from scratch,
                    # so it recomputes UNDER — and is attributed to —
                    # whatever version is serving when it readmits.
                    r.version = self.weights_version
                    # The slot is being reused: any retired request whose
                    # KV row lived here is no longer exportable.
                    self._export_ok = {
                        k: v for k, v in self._export_ok.items()
                        if v != slot
                    }
                    self._slot_req[slot] = r
                    self._req[slot] = r.rid
                    self._open_clock(slot, now)
                    if r.preempt_t is not None:
                        r.requeue_wait_s += now - r.preempt_t
                        r.preempt_t = None
                    self._aidx[slot] = (
                        self._adapter_pool.slot_of(r.adapter)
                        if r.adapter is not None else 0
                    )
                    self._plen[slot] = prompt.size
                    self._pending[slot] = prompt
                    self._emitted[slot] = 0
                    self._out[slot] = list(prompt)
                    self._ttimes[slot] = []
                    self._needs_reset[slot] = True
                    self._reset_to[slot] = 0
                    if self._paged and self._prefix:
                        # Longest chain of retained pages whose token
                        # prefix matches; the last prompt token always
                        # recomputes (its logits seed generation).
                        shared = []
                        for k in range(
                            1, (prompt.size - 1) // self._page_size + 1
                        ):
                            pid = self._prefix_registry.get(
                                prompt[: k * self._page_size].tobytes()
                            )
                            if pid is None:
                                break
                            shared.append(pid)
                        for j, pid in enumerate(shared):
                            self._refcnt[pid] = self._refcnt.get(pid, 0) + 1
                            self._cached_lru.pop(pid, None)
                            self._table_np[slot, j] = pid
                            self._held[slot].append(pid)
                            self._tables_dirty = True
                        self._shared_count[slot] = len(shared)
                        if shared:
                            s_len = len(shared) * self._page_size
                            self._pending[slot] = prompt[s_len:]
                            self._reset_to[slot] = s_len
                            if first_admission:
                                self._c_pfx_hits.inc()
                                self._c_pfx_pages.inc(len(shared))
                            self._update_high_water()
                        if first_admission:
                            # Predicted-vs-realized (round 15): the router
                            # records its digest-based prediction under
                            # the rid before placement; admission is the
                            # moment of truth. A shortfall means the page
                            # was evicted/spilled between scoring and
                            # admission — the request just re-prefills
                            # the missing tokens (graceful miss), and the
                            # counter makes the race visible.
                            realized = len(shared) * self._page_size
                            self.prefix_realized[r.rid] = realized
                            exp = self.expected_prefix.pop(r.rid, None)
                            if exp is not None and exp > 0:
                                self._c_pfx_expected.inc()
                                if realized < exp:
                                    self._c_tier_miss.inc()
                                    self.recorder.record(
                                        "engine.tier_miss", rid=r.rid,
                                        expected=int(exp),
                                        realized=realized,
                                    )
            self._g_queue.set(len(self._queue))

    def _spare_chunk_rows(self, firsts):
        """The chunk rows a refill dispatch has LEFT once every refilling
        slot holds its next chunk (``firsts``: slot -> tokens of that
        chunk): further consecutive chunks ``(slot, offset, n)`` of slots
        with prompt left, fewest chunks left first, so the dispatch
        completes as many prompts as it can. Only a paged cache can lend
        a slot several rows (rows of one table share its pages; a
        contiguous cache owns its rows), and only a chunk wider than one
        token writes its K,V before it attends (a one-token step folds
        the write into the kernel). Pages for every position the rows
        write are claimed here; under page backpressure a slot keeps the
        whole chunks its pages cover."""
        b, c = self._b, self._refill_chunk
        spare = b - len(firsts)
        extra = []
        if not (self._paged and c > 1 and spare):
            return extra
        left = {
            slot: -(-(self._pending[slot].size - n) // c)
            for slot, n in firsts.items() if self._pending[slot].size > n
        }
        for slot in sorted(left, key=lambda s: (left[s], s)):
            if not spare:
                break
            k = min(left[slot], spare)
            size = self._pending[slot].size
            consumed = self._plen[slot] - size
            try:
                self._ensure(slot, consumed + min(size, (k + 1) * c))
            except RuntimeError:
                covered = len(self._held[slot]) * self._page_size - consumed
                k = min(k, covered // c - 1)
            extra += [
                (slot, j * c, min(c, size - j * c)) for j in range(1, k + 1)
            ]
            spare -= k
        return extra

    @_dispatch_span("refill")
    def _refill_dispatch(self, params, d_params, retired):
        # One dispatch carries up to B CHUNK ROWS (slot, offset, n): the
        # next chunk of every slot with pending prompt tokens (fresh or
        # continuing) in the slot's own row, then, on a paged engine,
        # further chunks of the same prompts in the rows nobody refills
        # (_spare_chunk_rows); rows left over ride with length 0, as
        # decoding slots do. With ``decode_chain > 1`` up to that many
        # dispatches go back-to-back with a single host sync at the end —
        # chunk contents are host-known (the pending prompt), so nothing
        # in a later dispatch depends on an earlier one's readback; a long
        # prompt pays one round trip per CHAIN instead of per dispatch.
        b = self._b
        segs = []            # (tok_new_device, completes, ...) per dispatch
        for _ in range(self.decode_chain):
            firsts = {
                slot: min(self._pending[slot].size, self._refill_chunk)
                for slot in range(b) if self._pending[slot].size
            }
            if not firsts:
                break
            with self.ledger.measure("recovery", span="engine.recovery"):
                # An armed chaos seam spends its injected delay (hang,
                # slow) HERE — fault time is recovery, never device.
                chaos_hook(
                    "engine.dispatch", phase="refill",
                    rids=[r for r in self._req if r >= 0],
                )
            extra = []
            if self._paged:
                for slot, n in list(firsts.items()):
                    consumed = self._plen[slot] - self._pending[slot].size
                    try:
                        self._ensure(slot, consumed + n)
                    except RuntimeError:
                        # Backpressure instead of a wedge: if any
                        # OTHER slot is mid-flight, its retirement
                        # will free pages — requeue this request and
                        # serve the rest. Raise only when this
                        # request is alone (it can never fit).
                        if not any(
                            self._req[s] >= 0
                            for s in range(b) if s != slot
                        ):
                            raise
                        self._unadmit(slot)
                        self._c_preempt.inc()
                        del firsts[slot]
                if not firsts:
                    break
                extra = self._spare_chunk_rows(firsts)
                if self._cache is None:
                    self._create_cache(params, d_params)
                self._cache = self._set_tables(self._cache)
            # Row r of the dispatch: slot rows[r]'s chunk at offsets[r]
            # past what the slot has consumed. A slot's next chunk sits in
            # its own row; the further ones fill the rows without one.
            rows = np.arange(b, dtype=np.int32)
            offsets = np.zeros((b,), np.int32)
            lengths = np.zeros((b,), np.int32)
            chunk = np.zeros((b, self._refill_chunk), np.int32)
            last_row = rows.copy()      # slot -> the row of its last chunk
            took = dict.fromkeys(firsts, 0)     # slot -> tokens it sends
            free = (r for r in range(b) if r not in firsts)
            for slot, off, n in (
                *((slot, 0, n) for slot, n in firsts.items()), *extra
            ):
                r = next(free) if off else slot
                rows[r], offsets[r], lengths[r] = slot, off, n
                chunk[r, :n] = self._pending[slot][off:off + n]
                last_row[slot] = r
                took[slot] += n
            if self._cache is None:
                # A contiguous cache's first real chunk creates it.
                prog = self._programs["first_refill"]
                with self._led_h2d():
                    first_args = (
                        params, d_params, jnp.asarray(chunk),
                        jnp.asarray(lengths), self._rid_arr(), self.rng,
                    )
                with self._led_device(prog):
                    tok_new, self._cache, *moe = prog.fn(*first_args)
                self._book_cache_creation(first_args)
            else:
                # COPIES, not the live arrays: jnp.asarray of a numpy
                # array can be zero-copy (the jax.Array aliases the host
                # buffer), and the flags are cleared in place below while
                # the dispatch may still be executing asynchronously — an
                # aliased clear would erase the admission resets
                # mid-flight (observed as flaky stale-counter corruption
                # on CPU).
                with self._led_h2d():
                    chunk_d = jnp.asarray(chunk)
                    lengths_d = jnp.asarray(lengths)
                    reset_d = jnp.asarray(self._needs_reset.copy())
                    reset_to_d = jnp.asarray(self._reset_to.copy())
                    rid_d = self._rid_arr()
                    rows_d = jnp.asarray(rows)
                    offsets_d = jnp.asarray(offsets)
                prog = self._programs["refill_step"]
                # The speculative pair is ONE argument of this program.
                tok_new, self._cache, *moe = self._enqueue(
                    prog, (params, d_params), (
                        chunk_d, lengths_d, reset_d, reset_to_d, rid_d,
                        self.rng, rows_d, offsets_d,
                    ), cache=lambda: (self._cache,),
                )
            # The dispatch has its own copy of the admission resets, so
            # consume the flags (every flagged slot had pending tokens and
            # therefore rode this dispatch).
            n_reset = int(self._needs_reset.sum())
            self._needs_reset[:] = False
            self._reset_to[:] = 0
            # Advance the host-side pending views NOW (later dispatches in
            # the chain read them); completions are processed after the
            # single sync, per segment, in order.
            seg_completes = []
            for slot, n in took.items():
                self._pending[slot] = self._pending[slot][n:]
                if self._pending[slot].size == 0 and self._req[slot] >= 0:
                    seg_completes.append(slot)
            segs.append(
                (tok_new, seg_completes, last_row, prog.family, moe, took)
            )
            self._c_prefill_tok.inc(sum(took.values()))
            self._c_refill_slots.inc(chunk.size)
            self._c_chunk_rows.inc(len(firsts) + len(extra))
            if self._ssm:
                self._c_ssm_carried.inc(len(extra))
                self._c_ssm_resets.inc(n_reset)
        if not segs:
            return False
        for i, (
            tok_new, seg_completes, last_row, seg_fam, moe, took
        ) in enumerate(segs):
            with self._led_device(
                family=seg_fam, in_flight=len(segs) - 1 - i
            ):
                # Each segment's own sync: its tokens and, from the same
                # program, a dropless-expert config's counts.
                tok_new, *moe = (np.asarray(x) for x in (tok_new, *moe))
            now = time.perf_counter()       # its host-visibility time
            self._tick(now, carried=took, moe=[("refill", m) for m in moe])
            with self._led_consume():
                # A slot's first token: the pick of its LAST chunk's row.
                self._first_tokens(
                    seg_completes, tok_new[last_row], now, retired
                )
        return True

    def _first_tokens(self, slots, tok_new, now, retired):
        # Prompt complete: each slot's first token came from its last
        # refill chunk's last valid position (``tok_new[slot]``).
        for slot in slots:
            t = int(tok_new[slot])
            self._out[slot].append(t)
            self._emitted[slot] = 1
            self._tok[slot] = t
            self._slot_req[slot].first_token_t = now
            self._ttimes[slot].append(now)
            self.tracer.instant("request.first_token", rid=self._req[slot])
            if (self._eos is not None and t == self._eos) or (
                self._max_new == 1
            ):
                self._retire(slot, now, retired)
            else:
                self._active[slot] = True

    @_dispatch_span("decode")
    def _decode_dispatch(self, params, d_params, retired):
        # Up to ``decode_chain`` decode BLOCKS dispatched back-to-back —
        # the carries (tok/active/remaining[/pos]) flow device-to-device
        # and the host syncs ONCE at the end. Rows freeze in-scan at
        # EOS/budget exactly as within one block, so chaining cannot
        # change results (test-pinned). Scheduling tradeoff, not
        # correctness: a slot retiring mid-chain idles until the chain's
        # one sync, so admission (and queued-request TTFT) coarsens by
        # up to chain-1 blocks — decode_chain is an explicit opt-in
        # (default 1). NOTE the first-order decode lever measured in
        # round 5 is decode_block_steps (see perf_block_ladder.py; that
        # round's remotely attached chip paid ~120 ms per CALL, not
        # measured on today's machine) — chaining stacks a further gain
        # and is the main lever for refill. Returns whether
        # a dispatch actually ran (idle polling must not accrue time).
        if not self._active.any():
            return False
        b = self._b
        # Degradation level 1 turns the draft-verify rounds off: the
        # SPEC engine decodes through the plain decode_block (its own
        # target apply — the same program a non-spec engine runs, so it
        # checks against the plain ``decode_step`` golden). The draft
        # cache sits idle; on re-enable its stale K/V only costs
        # acceptance rate, never correctness — the verifier decides
        # every emitted token.
        spec = self._speculative and not self._spec_disabled
        remaining = np.asarray(
            [max(0, self._max_new - e) for e in self._emitted], np.int32
        )
        # Never dispatch blocks that CANNOT emit: the host knows every
        # row's remaining budget, so the chain is capped at the blocks
        # the longest-running active row can still use — with
        # K = max_new_tokens an entire wave retires in block 1 and an
        # uncapped chain would run chain-1 fully-frozen (but fully
        # priced) no-op blocks.
        worst = int(remaining[self._active].max())
        per_block = self._block_steps * (
            (self._num_draft + 1) if spec else 1
        )
        chain = min(self.decode_chain, -(-worst // per_block))
        with self.ledger.measure("recovery", span="engine.recovery"):
            # Armed chaos delay (hang/slow) books as recovery, not
            # device — the attribution the chaos tests pin.
            chaos_hook(
                "engine.dispatch", phase="decode",
                rids=[r for r in self._req if r >= 0],
            )
        if self._paged:
            # Cover every position this chain can write: chain·K new
            # tokens per row (plain), or chain·K rounds of up to
            # num_draft+1 plus the verify chunk's headroom (speculative)
            # — capped by the row's remaining budget either way.
            for slot in range(b):
                if not self._active[slot]:
                    continue
                pos_s = self._plen[slot] + self._emitted[slot] - 1
                if spec:
                    span = (
                        min(
                            int(remaining[slot]),
                            chain * self._block_steps
                            * (self._num_draft + 1),
                        )
                        + self._num_draft + 1
                    )
                else:
                    span = min(
                        int(remaining[slot]), chain * self._block_steps
                    )
                try:
                    self._ensure(slot, pos_s + span)
                except RuntimeError:
                    # Decode-time RECOMPUTE preemption (exact — see
                    # _unadmit): requeue this row unless it is the only
                    # request left holding pages (then it can never fit).
                    if not any(
                        self._req[s] >= 0 for s in range(b) if s != slot
                    ):
                        raise
                    self._unadmit(slot)
                    self._c_preempt.inc()
            if not self._active.any():
                return False
            self._cache = self._set_tables(self._cache)
            # Re-cap the chain from the SURVIVING rows: if backpressure
            # just un-admitted the longest-running row, the chain sized
            # to it would dispatch fully-frozen no-op blocks.
            worst = int(remaining[self._active].max())
            chain = min(self.decode_chain, -(-worst // per_block))
        with self._led_h2d():
            tok_d = jnp.asarray(self._tok)
            active_d = jnp.asarray(self._active.astype(np.int32))
            remaining_d = jnp.asarray(remaining)
            rid = self._rid_arr()
            if spec:
                # Each row's current cache index: prompt + emitted - 1
                # (its pending token is not yet in the cache).
                pos_d = jnp.asarray(
                    np.asarray(
                        [
                            max(0, p + e - 1)
                            for p, e in zip(self._plen, self._emitted)
                        ],
                        np.int32,
                    )
                )
        if spec:
            prog = self._programs["decode_block_spec"]
            segs = []
            for _ in range(chain):
                # Each link's caches are installed as it returns: the ones
                # it was given are gone, and a later link may raise.
                (buffer, counts, acc, prop, tok_d, pos_d, active_d,
                 remaining_d, t_cache, d_cache) = self._enqueue(
                    prog, (params, d_params),
                    (tok_d, active_d, pos_d, remaining_d, rid, self.rng),
                )
                self._cache = (t_cache, d_cache)
                segs.append((buffer, counts, acc, prop))
            # ONE sync for the whole chain.
            with self._led_device(family="decode_block_spec"):
                segs = [
                    tuple(np.asarray(x) for x in seg) for seg in segs
                ]
            now = time.perf_counter()
            was_active = self._active.copy()
            self._tick(now, advanced=True)
            with self._led_consume():
                for buffer, counts, acc, prop in segs:
                    self._c_spec_acc.inc(int(acc.sum()))
                    self._c_spec_prop.inc(int(prop.sum()))
                    for slot in range(b):
                        # Consume segments chronologically; a slot retired
                        # in an earlier segment (req < 0) emits nothing
                        # real in later ones — its lane froze on device.
                        if was_active[slot] and self._req[slot] >= 0:
                            self._consume(
                                slot, buffer[slot, : counts[slot]].tolist(),
                                now, retired,
                            )
        else:
            def target():
                # Degraded (a speculative engine): advance the TARGET
                # cache only; the idle draft cache rides along untouched.
                return (self._cache[0] if self._speculative else self._cache,)

            prog = self._programs["decode_block"]
            segs, moe_segs = [], []
            for _ in range(chain):
                toks, active_d, remaining_d, cache, *moe = self._enqueue(
                    prog, (params,),
                    (tok_d, active_d, remaining_d, rid, self.rng),
                    cache=target,
                )
                # Installed as each link returns (see the speculative arm).
                self._cache = (
                    (cache, self._cache[1]) if self._speculative else cache
                )
                # Next block's pending token: each row's last emitted
                # (frozen rows repeat their token — correct carry).
                tok_d = toks[:, -1]
                segs.append(toks)
                moe_segs += moe
            with self._led_device(family="decode_block"):
                segs = [np.asarray(t) for t in segs]   # ONE sync
                moe_segs = jax.device_get(moe_segs)    # the same programs' counts
            now = time.perf_counter()
            was_active = self._active.copy()
            self._tick(
                now, advanced=True, moe=[("decode", m) for m in moe_segs]
            )
            with self._led_consume():
                for toks in segs:
                    for slot in range(b):
                        if was_active[slot] and self._req[slot] >= 0:
                            self._consume(
                                slot, toks[slot].tolist(), now, retired
                            )
        return True

    def _schedule_refill(self, budget):
        """The token-budget refill schedule for ONE mixed link: FCFS over
        slots with pending prompt tokens (admission order — the oldest
        request's prompt streams first), each taking
        ``min(pending, refill_chunk, budget left)``. Returns
        ``(chunk, lengths, starved)`` — ``starved`` counts slots that held
        pending tokens but got none this link (the scheduler decision the
        flight recorder logs)."""
        b = self._b
        lengths = np.zeros((b,), np.int32)
        chunk = np.zeros((b, self._refill_chunk), np.int32)
        starved = 0
        order = sorted(
            (s for s in range(b) if self._pending[s].size),
            # Admission order, not request id: callers may pass arbitrary
            # rids to add_request. Same-pass admissions share admit_t, so
            # arrival breaks the tie; a preempted request keeps its first
            # admission time and so its place in line.
            key=lambda s: (
                self._slot_req[s].admit_t, self._slot_req[s].arrival_t
            ),
        )
        for slot in order:
            if budget <= 0:
                starved += 1
                continue
            n = min(self._pending[slot].size, self._refill_chunk, budget)
            if self._paged:
                consumed = self._plen[slot] - self._pending[slot].size
                try:
                    self._ensure(slot, consumed + n)
                except RuntimeError:
                    # Backpressure, exactly as in _refill_dispatch: requeue
                    # unless this request is the only one holding pages.
                    if not any(
                        self._req[s] >= 0
                        for s in range(b) if s != slot
                    ):
                        raise
                    self._unadmit(slot)
                    self._c_preempt.inc()
                    continue
            chunk[slot, :n] = self._pending[slot][:n]
            lengths[slot] = n
            budget -= n
        return chunk, lengths, starved

    @_dispatch_span("mixed")
    def _mixed_dispatch(self, params, d_params, retired):
        # The FUSED scheduler iteration (``mixed=True``): up to
        # ``decode_chain`` mixed links dispatched back-to-back, each
        # advancing every decoding row by one token (speculative: one
        # draft-verify round) AND pushing budgeted refill chunks for
        # admitting/streaming rows — decode rows are funded first out of
        # ``token_budget``, refill takes the remainder (uncapped when no
        # row is decoding: with no one to protect, refill runs at the
        # split engine's full width). Carries flow device-to-device; ONE
        # host sync at the end. Cache creation still routes through the
        # refill path (the one-shot ``first_refill`` program). Returns
        # the program class that actually ran ("mixed" / "refill" /
        # "decode" — step() books wall time per class) or False when
        # nothing dispatched.
        if self._cache is None:
            if self._adapter_pool is None:
                return (
                    "refill"
                    if self._refill_dispatch(params, d_params, retired)
                    else False
                )
            # Adapter engines must never stream prompt CONTENT through
            # the base-weights refill programs: create the cache empty
            # and fall through to the fused adapter step below, which
            # prefills every row through its own tenant's merged weights.
            self._create_cache(params, d_params)
        b = self._b
        if self._speculative and self._spec_disabled:
            # Degradation level >= 1 on a speculative MIXED engine: run
            # the SPLIT programs (refill_step still prefills the draft
            # cache, so re-enabling speculation stays sound; decode runs
            # the plain decode_block via _decode_dispatch's degraded
            # path). Everything dispatched here is an already-known
            # program family — an overload incident must not trigger
            # fresh compiles of a one-off fused variant.
            if any(p.size for p in self._pending):
                return (
                    "refill"
                    if self._refill_dispatch(params, d_params, retired)
                    else False
                )
            return (
                "decode"
                if self._decode_dispatch(params, d_params, retired)
                else False
            )
        if (
            not any(p.size for p in self._pending)
            and self._adapter_pool is None
        ):
            # PURE-DECODE phase: nothing to fuse — run the K-token decode
            # block (full decode throughput; a fused link costs one
            # dispatch per token and exists to overlap refill, absent
            # here). Admission is unaffected: _admit ran before this
            # dispatch, and a queued request only waits on a block when
            # every slot is busy — in which case it could not have been
            # admitted under any granularity. (Adapter-pool engines skip
            # this: the split decode block applies BASE weights, so
            # their pure-decode phase runs fused adapter links instead.)
            return (
                "decode"
                if self._decode_dispatch(params, d_params, retired)
                else False
            )
        if (
            self._speculative and not self._active.any()
            and self._adapter_pool is None
        ):
            # PURE-REFILL phase in speculative mode: a fused link would
            # pay a full draft-verify round with every row frozen (draft
            # applies, a verify apply, two rollback broadcasts — zero
            # tokens out) on top of the refill. Outputs are
            # schedule-independent, so run the split refill path until a
            # row starts decoding. (A non-speculative refill-only link
            # costs what refill_step costs; no fallback needed there.)
            return (
                "refill"
                if self._refill_dispatch(params, d_params, retired)
                else False
            )
        per_link = (self._num_draft + 1) if self._speculative else 1
        # The fused-link count ONE host iteration covers: the multi-step
        # horizon when engaged, else the decode chain (horizon=1 IS
        # today's loop — same programs, byte-for-byte).
        horizon = int(self.horizon)
        n_links = horizon if horizon > 1 else max(1, self.decode_chain)

        def chain_cap(remaining, active):
            # Links the longest-running decoding row can still use
            # (optimistic for speculative — same convention as
            # _decode_dispatch's per-block cap).
            if not active.any():
                return 0
            return -(-int(remaining[active].max()) // per_link)

        remaining = np.asarray(
            [max(0, self._max_new - e) for e in self._emitted], np.int32
        )
        chain_dec = chain_cap(remaining, self._active)
        if self._paged and self._active.any():
            # Cover every decode position this chain can write, with the
            # decode path's recompute-preemption fallback.
            links_hint = min(n_links, max(chain_dec, 1))
            for slot in range(b):
                if not self._active[slot]:
                    continue
                pos_s = self._plen[slot] + self._emitted[slot] - 1
                span = min(int(remaining[slot]), links_hint * per_link)
                if self._speculative:
                    span += self._num_draft + 1
                try:
                    self._ensure(slot, pos_s + span)
                except RuntimeError:
                    if not any(
                        self._req[s] >= 0 for s in range(b) if s != slot
                    ):
                        raise
                    self._unadmit(slot)
                    self._c_preempt.inc()
            remaining = np.asarray(
                [max(0, self._max_new - e) for e in self._emitted],
                np.int32,
            )
            chain_dec = chain_cap(remaining, self._active)
        was_active = self._active.copy()
        n_active = int(was_active.sum())
        pos_d = None
        with self._led_h2d():
            tok_d = jnp.asarray(self._tok)
            active_d = jnp.asarray(was_active.astype(np.int32))
            remaining_d = jnp.asarray(remaining)
            rid = self._rid_arr()
            if self._speculative:
                # Every row's CURRENT cache index: decoding rows at
                # prompt + emitted - 1, refilling rows at their consumed
                # count (the round's rollback broadcast must re-assert,
                # never rewind, a refill advance — the device adds each
                # link's chunk lengths on top of this).
                pos_d = jnp.asarray(
                    np.asarray(
                        [
                            max(0, self._plen[s] + self._emitted[s] - 1)
                            if was_active[s]
                            else (
                                self._plen[s] - self._pending[s].size
                                if self._req[s] >= 0 else 0
                            )
                            for s in range(b)
                        ],
                        np.int32,
                    )
                )
            if self._adapter_pool is not None:
                aidx_d = jnp.asarray(self._aidx)
        with self.ledger.measure("recovery", span="engine.recovery"):
            # Armed chaos delay books as recovery, never device.
            chaos_hook(
                "engine.dispatch", phase="mixed",
                rids=[r for r in self._req if r >= 0],
            )
        # A multi-LoRA engine's fused programs take (pool, aidx) after
        # params; the prefix is empty without a pool. One program serves
        # every tenant in the batch: the stacked pool rides in as an
        # argument (stable treedef → stable compile) and the per-row
        # adapter index gathers each row's slice on device. _aidx is fixed
        # for the whole chain: admission ran before this dispatch and
        # nothing re-admits mid-chain.
        adapter_ops = (
            (self._adapter_pool.tree, aidx_d)
            if self._adapter_pool is not None else ()
        )
        if horizon > 1:
            # Device-resident multi-step path: the horizon's plan is
            # staged host-side and ONE scanned program advances all of
            # it — same preamble (chaos seam, paged pre-ensure, chain
            # caps) as the link loop below, so the two paths cannot
            # drift on scheduling policy.
            return self._multi_dispatch(
                params, d_params, retired, n_links=n_links,
                per_link=per_link, chain_dec=chain_dec,
                was_active=was_active, n_active=n_active, tok_d=tok_d,
                active_d=active_d, remaining_d=remaining_d, rid=rid,
                pos_d=pos_d, adapter_ops=adapter_ops,
            )
        prog = self._programs[
            "adapter_mixed_step" if adapter_ops else "mixed_step"
        ]

        def operands():
            # ``(head, tail)`` around the cache, as the locals stand.
            if self._speculative:
                return (params, *adapter_ops, d_params), (
                    chunk_d, lengths_d, reset_d, reset_to_d, tok_d,
                    active_d, pos_d, remaining_d, rid, self.rng,
                )
            return (params, *adapter_ops), (
                chunk_d, lengths_d, reset_d, reset_to_d, tok_d, active_d,
                remaining_d, rid, self.rng,
            )

        segs = []
        starved_total = 0
        refill_scheduled = 0
        for link in range(max(1, self.decode_chain)):
            # Decode rows are funded at their true per-link consumption:
            # 1 token plain, num_draft + 1 verify-chunk positions
            # speculative — otherwise a spec dispatch overruns the
            # documented per-dispatch ceiling by n_active * num_draft.
            budget = (
                max(0, self.token_budget - n_active * per_link)
                if n_active else b * self._refill_chunk
            )
            chunk, lengths, starved = self._schedule_refill(budget)
            has_decode = n_active > 0 and link < chain_dec
            if not lengths.any() and not has_decode:
                break
            starved_total += starved
            refill_scheduled += int(lengths.sum())
            if self._paged:
                self._cache = self._set_tables(self._cache)
            # COPIES of the admission resets (see _refill_dispatch: the
            # dispatch is async; an aliased in-place clear would corrupt
            # it). Link 0 carries every pending reset — including rows the
            # budget starved this link: the on-device counter reset is
            # idempotent and nothing advances a row before its first
            # chunk, so resetting early is safe and the flags can clear.
            with self._led_h2d():
                chunk_d = jnp.asarray(chunk)
                lengths_d = jnp.asarray(lengths)
                reset_d = jnp.asarray(self._needs_reset.copy())
                reset_to_d = jnp.asarray(self._reset_to.copy())
            if self._speculative:
                (first_tok, buffer, counts, acc, prop, tok_d, pos_d,
                 active_d, remaining_d, t_cache, d_cache) = self._enqueue(
                    prog, *operands()
                )
                self._cache = (t_cache, d_cache)
            else:
                first_tok, tok_d, active_d, remaining_d, self._cache = (
                    self._enqueue(prog, *operands())
                )
                buffer = counts = acc = prop = None
            self._needs_reset[:] = False
            self._reset_to[:] = 0
            # Advance the host-side pending views NOW (later links read
            # them); completions are processed after the single sync.
            seg_completes = []
            for slot in range(b):
                if lengths[slot]:
                    self._pending[slot] = (
                        self._pending[slot][lengths[slot]:]
                    )
                    if (
                        self._pending[slot].size == 0
                        and self._req[slot] >= 0
                    ):
                        seg_completes.append(slot)
            segs.append(
                (first_tok, buffer, counts, acc, prop, seg_completes,
                 set(np.flatnonzero(lengths).tolist()))
            )
        if not segs:
            return False
        self._c_prefill_tok.inc(refill_scheduled)
        self.recorder.record(
            "engine.mixed_schedule", links=len(segs),
            decode_rows=n_active, refill_tokens=refill_scheduled,
            starved=starved_total, budget=self.token_budget,
            queue_depth=len(self._queue),
        )
        if self._adapter_pool is not None:
            self._c_adapter_n.inc(len(segs))
            occ = np.asarray([q >= 0 for q in self._req])
            self._c_adapter_rows.inc(
                int(((self._aidx > 0) & occ).sum()) * len(segs)
            )
        for i, (
            first_tok, buffer, counts, acc, prop, seg_completes, carried
        ) in enumerate(segs):
            left = len(segs) - 1 - i
            with self._led_device(family=prog.family, in_flight=left):
                first_np = np.asarray(first_tok)   # each link's own sync
            now = time.perf_counter()
            # Each request by what IT got from the link: a chunk, a token
            # (the rows decoding at chain start), or nothing.
            self._tick(now, carried=carried, advanced=was_active)
            if self._speculative:
                with self._led_device(family=prog.family, in_flight=left):
                    counts_np = np.asarray(counts)
                    buffer_np = np.asarray(buffer)
                    acc_np = np.asarray(acc)
                    prop_np = np.asarray(prop)
                self._c_spec_acc.inc(int(acc_np.sum()))
                self._c_spec_prop.inc(int(prop_np.sum()))
            with self._led_consume():
                # Same first-token rule as _refill_dispatch.
                self._first_tokens(seg_completes, first_np, now, retired)
                for slot in range(b):
                    # Decode consumption: rows decoding at CHAIN START
                    # that are still live (a row retired while processing
                    # an earlier link froze on device — its later-link
                    # lanes carry no real tokens).
                    if was_active[slot] and self._req[slot] >= 0:
                        if self._speculative:
                            toks = (
                                buffer_np[slot, : counts_np[slot]].tolist()
                            )
                        else:
                            toks = [int(first_np[slot])]
                        self._consume(slot, toks, now, retired)
        return "mixed"

    def _plan_horizon_links(
        self, n_links, n_active, per_link, chain_dec, *, allow_preempt,
    ):
        """The HOST half of the multi-step scheduler: the per-link refill
        plan for up to ``n_links`` fused links — ``_schedule_refill``'s
        policy (FCFS by admission order, decode funded first out of
        ``token_budget``) applied over a VIRTUAL pending advance: reads
        ``self._pending`` through per-slot offsets and never consumes it;
        the caller commits the advance when (and only when) the plan
        dispatches. Returns ``(links, offs)`` where each link is
        ``(chunk, lengths, starved, completes)``, or ``None`` when
        ``allow_preempt=False`` (the in-flight planner) and the page pool
        cannot cover the plan — preemption is a BOUNDARY decision, so
        speculative staging aborts instead of un-admitting anyone."""
        b = self._b
        with self.ledger.measure("sched", span="engine.plan", label="plan"):
            offs = [0] * b
            links = []
            for link in range(n_links):
                budget = (
                    max(0, self.token_budget - n_active * per_link)
                    if n_active else b * self._refill_chunk
                )
                lengths = np.zeros((b,), np.int32)
                chunk = np.zeros((b, self._refill_chunk), np.int32)
                starved = 0
                completes = []
                order = sorted(
                    (
                        s for s in range(b)
                        if self._pending[s].size - offs[s] > 0
                    ),
                    key=lambda s: (
                        self._slot_req[s].admit_t,
                        self._slot_req[s].arrival_t,
                    ),
                )
                for slot in order:
                    if budget <= 0:
                        starved += 1
                        continue
                    n = min(
                        self._pending[slot].size - offs[slot],
                        self._refill_chunk, budget,
                    )
                    if self._paged:
                        consumed = (
                            self._plen[slot] - self._pending[slot].size
                            + offs[slot]
                        )
                        try:
                            self._ensure(slot, consumed + n)
                        except RuntimeError:
                            if not allow_preempt:
                                return None
                            # Backpressure, exactly as _schedule_refill:
                            # requeue unless this request is the only one
                            # holding pages. Scrub the un-admitted slot
                            # from the earlier planned links — nothing
                            # dispatched yet, so the plan must not
                            # stream a requeued request's chunks.
                            if not any(
                                self._req[s] >= 0
                                for s in range(b) if s != slot
                            ):
                                raise
                            self._unadmit(slot)
                            self._c_preempt.inc()
                            offs[slot] = 0
                            for ch2, ln2, _s2, comp2 in links:
                                ln2[slot] = 0
                                ch2[slot, :] = 0
                                if slot in comp2:
                                    comp2.remove(slot)
                            continue
                    chunk[slot, :n] = (
                        self._pending[slot][offs[slot]: offs[slot] + n]
                    )
                    lengths[slot] = n
                    offs[slot] += n
                    budget -= n
                    if (
                        offs[slot] == self._pending[slot].size
                        and self._req[slot] >= 0
                    ):
                        completes.append(slot)
                has_decode = n_active > 0 and link < chain_dec
                if not lengths.any() and not has_decode:
                    break
                links.append((chunk, lengths, starved, completes))
            return links, offs

    def _boundary_fingerprint(self, n_links, n_active, per_link, chain_dec):
        # Everything _plan_horizon_links reads: the slot occupancy, the
        # pending sizes (contents are immutable between admissions, so
        # sizes + request ids pin them), and the budget/cap inputs.
        return (
            tuple(self._req),
            tuple(int(p.size) for p in self._pending),
            int(n_active), int(chain_dec), int(n_links), int(per_link),
            int(self.token_budget),
        )

    def _take_staged_plan(self, n_links, n_active, per_link, chain_dec):
        """Consume the async planner's staged plan iff the boundary state
        matches its prediction exactly — an EOS retirement, an admission,
        a deadline eviction, a preemption, or a runtime knob change all
        miss the fingerprint and fall back to live planning, so the
        staged plan can only move host work off the boundary, never
        change what dispatches."""
        staged, self._staged_plan = self._staged_plan, None
        if staged is None:
            return None
        fp, plan = staged
        if fp != self._boundary_fingerprint(
            n_links, n_active, per_link, chain_dec
        ):
            return None
        self._c_plan_reused.inc()
        return plan

    def _multi_dispatch(
        self, params, d_params, retired, *, n_links, per_link, chain_dec,
        was_active, n_active, tok_d, active_d, remaining_d, rid,
        pos_d=None, adapter_ops=(),
    ):
        # The DEVICE-RESIDENT steady-state loop (horizon > 1): plan the
        # whole horizon's refill schedule host-side, dispatch ONE scanned
        # ``multi_step`` program covering up to ``n_links`` fused
        # iterations, overlap the NEXT horizon's planning with the
        # in-flight device work (``_plan_next_horizon``), then sync ONCE
        # and process every link's completions/consumption exactly as the
        # per-link loop does. Reached from _mixed_dispatch AFTER its
        # fallthroughs and preamble, so cache creation, degradation,
        # pure-decode/pure-refill phases, the chaos seam, and the paged
        # decode pre-ensure behave identically at every horizon.
        b = self._b
        plan = self._take_staged_plan(n_links, n_active, per_link, chain_dec)
        reused = plan is not None
        if plan is None:
            plan = self._plan_horizon_links(
                n_links, n_active, per_link, chain_dec, allow_preempt=True,
            )
        links, offs = plan
        if not links:
            return False
        n_live = len(links)
        # Commit the virtual pending advance NOW: the plan is final and
        # the dispatch below is async — completions are processed after
        # the one sync, from the per-link ``completes`` the plan carries.
        for slot in range(b):
            if offs[slot]:
                self._pending[slot] = self._pending[slot][offs[slot]:]
        starved_total = sum(link[2] for link in links)
        refill_scheduled = sum(int(link[1].sum()) for link in links)
        # Stack the plan into fixed-shape (N, B, ...) scan inputs — ONE
        # executable per (horizon, program family); trailing padded
        # steps ride the scan's cond skip. Link 0 carries every pending
        # admission reset (idempotent on device, same as the link loop).
        chunks = np.zeros((n_links, b, self._refill_chunk), np.int32)
        lens = np.zeros((n_links, b), np.int32)
        resets = np.zeros((n_links, b), bool)
        reset_tos = np.zeros((n_links, b), np.int32)
        for i, (chunk, lengths, _starved, _completes) in enumerate(links):
            chunks[i] = chunk
            lens[i] = lengths
        resets[0] = self._needs_reset
        reset_tos[0] = self._reset_to
        if self._paged:
            # All page allocation for the horizon happened in the plan
            # (refill) and the preamble's pre-ensure (decode): push the
            # final tables once for the whole horizon.
            self._cache = self._set_tables(self._cache)
        live = np.zeros((n_links,), np.int32)
        live[:n_live] = 1
        with self._led_h2d():
            chunks_d = jnp.asarray(chunks)
            lens_d = jnp.asarray(lens)
            resets_d = jnp.asarray(resets)
            reset_tos_d = jnp.asarray(reset_tos)
            live_d = jnp.asarray(live)
        prog = self._programs[
            "adapter_multi_step" if adapter_ops else "multi_step"
        ]

        if self._speculative:
            head, tail = (params, *adapter_ops, d_params), (
                chunks_d, lens_d, resets_d, reset_tos_d, live_d, tok_d,
                active_d, pos_d, remaining_d, rid, self.rng,
            )
        else:
            head, tail = (params, *adapter_ops), (
                chunks_d, lens_d, resets_d, reset_tos_d, live_d, tok_d,
                active_d, remaining_d, rid, self.rng,
            )
        if self._speculative:
            (first_toks, buffers, counts, accs, props, tok_d, pos_d,
             active_d, remaining_d, t_cache, d_cache) = self._enqueue(
                prog, head, tail
            )
            self._cache = (t_cache, d_cache)
        else:
            first_toks, tok_d, active_d, remaining_d, self._cache = (
                self._enqueue(prog, head, tail)
            )
            buffers = counts = accs = props = None
        self._needs_reset[:] = False
        self._reset_to[:] = 0
        self.recorder.record(
            "engine.mixed_schedule", links=n_live,
            decode_rows=n_active, refill_tokens=refill_scheduled,
            starved=starved_total, budget=self.token_budget,
            queue_depth=len(self._queue), horizon=n_links,
            plan_reused=reused,
        )
        self._c_multi_n.inc()
        self._c_multi_links.inc(n_live)
        self._c_prefill_tok.inc(refill_scheduled)
        if self._adapter_pool is not None:
            self._c_adapter_n.inc(n_live)
            self._c_adapter_rows.inc(
                sum(
                    1 for s in range(self._b)
                    if self._req[s] >= 0 and self._aidx[s] > 0
                ) * n_live
            )
        # THE async-planner window: the fused program is in flight and
        # nothing below needs its results yet — stage the next horizon.
        self._plan_next_horizon(n_links, per_link, chain_dec, links)
        # ONE blocking readback for the whole horizon (the host's single
        # touch per N iterations — books as in-flight device time).
        with self._led_device(family=prog.family):
            toks_np = np.asarray(first_toks)
            if self._speculative:
                counts_np = np.asarray(counts)
                buffers_np = np.asarray(buffers)
                acc_np = np.asarray(accs)
                props_np = np.asarray(props)
        if self._speculative:
            self._c_spec_acc.inc(int(acc_np[:n_live].sum()))
            self._c_spec_prop.inc(int(props_np[:n_live].sum()))
        now = time.perf_counter()
        # ONE readback shows every link's tokens: one tick, a request
        # carried if any link held one of its chunks.
        self._tick(
            now, carried=set(np.flatnonzero(lens.any(axis=0)).tolist()),
            advanced=was_active,
        )
        with self._led_consume():
            for i in range(n_live):
                first_np = toks_np[i]
                # Prompts complete at link i (same rule as the link loop).
                self._first_tokens(links[i][3], first_np, now, retired)
                for slot in range(b):
                    # Decode consumption: rows decoding at HORIZON START
                    # that are still live (a row that retired at an
                    # earlier link froze on device — its later lanes
                    # carry no real tokens). Same rule as the link loop's
                    # per-seg pass.
                    if was_active[slot] and self._req[slot] >= 0:
                        if self._speculative:
                            toks = (
                                buffers_np[i, slot, : counts_np[i, slot]]
                                .tolist()
                            )
                        else:
                            toks = [int(first_np[slot])]
                        self._consume(slot, toks, now, retired)
        return "mixed"

    def _plan_next_horizon(self, n_links, per_link, chain_dec, links):
        """The ASYNC PLANNER: runs while the fused multi-step program is
        in flight (between its dispatch and the one blocking sync) and
        stages the NEXT horizon's refill plan — including its page-run
        reservations — against a PREDICTED boundary state. Reads only
        host state the in-flight program never writes (pending prompt
        views, the host page allocator) and performs NO device readback:
        a planner sync would re-serialize the host onto the device clock
        (lint-pinned, ``host-sync-in-hot-loop``). The staged plan
        carries a fingerprint of the predicted state; the next dispatch
        consumes it only on an exact match (``_take_staged_plan``), so a
        wrong prediction costs a re-plan at the boundary, never a wrong
        dispatch. Prediction is conservative: every active row advances
        its MINIMUM (one token/round per decode link) and nobody emits
        EOS — any faster drain or retirement misses the fingerprint."""
        self._staged_plan = None
        b = self._b
        with self.ledger.measure("sched", span="engine.plan", label="plan"):
            n_dec = min(len(links), max(0, chain_dec))
            rem = np.asarray(
                [max(0, self._max_new - e) for e in self._emitted],
                np.int32,
            )
            act = self._active.copy()      # horizon-start active rows
            surv = act & (rem > n_dec)
            rem_pred = rem.copy()
            rem_pred[act] = np.maximum(rem_pred[act] - n_dec, 0)
            req_pred = list(self._req)
            for s in range(b):
                if act[s] and not surv[s]:
                    req_pred[s] = -1
            for _c, _l, _st, comp in links:
                for s in comp:
                    # A prompt completing this horizon becomes an active
                    # decode row at the boundary (unless it retires at
                    # its first token — max_new == 1 here; EOS misses
                    # the fingerprint).
                    if self._max_new > 1:
                        surv[s] = True
                        rem_pred[s] = self._max_new - 1
                    else:
                        req_pred[s] = -1
            n_active_pred = int(surv.sum())
            chain_pred = (
                -(-int(rem_pred[surv].max()) // per_link)
                if surv.any() else 0
            )
            plan = self._plan_horizon_links(
                n_links, n_active_pred, per_link, chain_pred,
                allow_preempt=False,
            )
            if plan is None or not plan[0]:
                return
            fp = (
                tuple(req_pred),
                tuple(int(p.size) for p in self._pending),
                n_active_pred, chain_pred, int(n_links), int(per_link),
                int(self.token_budget),
            )
            self._staged_plan = (fp, plan)
            self._c_plan_staged.inc()
            self.recorder.record(
                "engine.plan_staged", links=len(plan[0]),
                predicted_active=n_active_pred,
            )

    @property
    def comm_compression_active(self) -> bool:
        """True while the quantized serving collectives are compiled in
        (False when never enabled, or after a drift-budget trip)."""
        return self._comp is not None and self._comp.active

    def _comp_maintain(self, params):
        """Drift governor for the compressed serving collectives: every
        ``drift_check_every``-th dispatched step with active decode rows,
        run one greedy decode step under BOTH applies (compressed and
        plain oracle) on the live cache and count diverging rows. The
        drift rate over the budget feeds a dedicated one-level
        :class:`~learning_jax_sharding_tpu.robustness.policies.
        DegradationLadder`; a trip disables compression and clears every
        apply-family executable cache, so the NEXT dispatch retraces to
        the plain — bit-identical — contraction. Probe caches are
        discarded; the served stream never observes the probe."""
        comp = self._comp
        if (
            comp is None or not comp.active
            or self._comp_probe_fn is None or self._comp_ladder is None
            or self._cache is None or not self._active.any()
        ):
            return
        self._comp_n += 1
        if self._comp_n % comp.drift_check_every:
            return
        # Observability tax, like _retire's booking: the probe is an
        # extra (cached) program dispatch, not serving work.
        with self.ledger.measure(
            "telemetry", span="engine.telemetry", busy=True
        ):
            cache = self._cache[0] if self._speculative else self._cache
            tok = jnp.asarray(self._tok, jnp.int32)
            act = jnp.asarray(self._active.astype(np.int32))
            with activate(self._mesh, self._rules):
                n_live, n_diff = self._comp_probe_fn(
                    params, cache, tok, act
                )
            n_live, n_diff = int(n_live), int(n_diff)
            if not self._in_flight:
                self.ledger.device_empty()   # the probe has been read back
            self._c_comp_probes.inc()
            self._c_comp_disagree.inc(n_diff)
            frac = (n_diff / n_live) if n_live else 0.0
            # drift_budget <= 0 is the deterministic test hook: every
            # probe reads as breached, so the first probe trips.
            burn = (
                frac / comp.drift_budget if comp.drift_budget > 0
                else float("inf")
            )
            self.recorder.record(
                "engine.comp_drift_probe", active=n_live,
                disagreements=n_diff, drift=frac,
            )
            if self._comp_ladder.update(burn) >= 1:
                self._trip_compression(frac)

    def _trip_compression(self, frac: float):
        comp = self._comp
        if comp is None or not comp.enabled:
            return
        comp.enabled = False
        # Every program that traces the apply retraces; the cache-moving
        # ones never embed it.
        stale = [p.fn for p in self._programs.values() if p.applies]
        for fn in (*stale, self._comp_probe_fn):
            fn.clear_cache()
        cleared = len(stale)
        self._c_comp_trips.inc()
        self._g_comp_on.set(0)
        self.recorder.record(
            "engine.comp_drift_trip", drift=frac,
            budget=comp.drift_budget, programs_cleared=cleared,
        )

    @property
    def degradation_level(self) -> int:
        """Current graceful-degradation level (0 when no ladder is
        attached): 0 normal, 1 speculation off, 2 reduced
        ``token_budget``, 3 shedding new admits."""
        return self._ladder.level if self._ladder is not None else 0

    def _apply_degradation(self):
        """Feed the SLO burn rate into the attached ladder and apply a
        level change to the engine's runtime knobs. The levers are the
        SAME public knobs an operator can turn (``token_budget``), so
        de-escalation restores the value captured when the ladder took
        it over, not a constructor constant."""
        if self._ladder is None or self.slo is None:
            return
        burn = max(
            (self.slo.burn_rate(t.name) for t in self.slo.targets),
            default=0.0,
        )
        prev = self._ladder.level
        level = self._ladder.update(burn)
        if level == prev:
            return
        if self._speculative:
            self._spec_disabled = level >= 1
        if self._mixed:
            if level >= 2 and self._base_budget is None:
                self._base_budget = self.token_budget
                self.token_budget = max(self._b, self.token_budget // 2)
            elif level < 2 and self._base_budget is not None:
                self.token_budget = self._base_budget
                self._base_budget = None
        self._shed_all = level >= 3
        self._g_degraded.set(level)
        self.recorder.record(
            "engine.degrade", level=level, name=self._ladder.name,
            burn_rate=burn, spec_disabled=self._spec_disabled,
            token_budget=self.token_budget, shedding=self._shed_all,
        )

    def step(self, params=None, draft_params=None) -> list[int]:
        """ONE scheduler iteration: admit queued requests into idle
        slots, then run exactly one dispatch — a refill chunk if any slot
        has pending prompt tokens, else a decode block if any row is
        active, else nothing. With ``mixed=True`` the one dispatch is the
        FUSED program instead: every decoding row advances (one token per
        link, or one draft-verify round) AND pending prompts push
        budgeted refill chunks, so decode never stalls behind refill and
        admission lands at every dispatch. Returns the ids of requests
        that finished during this step (their outputs await
        ``pop_finished()``).

        A staged ``swap_weights`` commits HERE, at the top of the step,
        before this step's admissions — so the backlog re-admitted in
        the committing step is pinned to (and served by) the NEW
        version. Once a swap has committed, the engine owns its weights:
        the installed tree overrides whatever ``params`` the caller
        still passes (a driver mid-rollout keeps handing in its stale
        copy), and ``step()`` may be called with no params at all."""
        # GOODPUT LEDGER: step() is the top-level frame — the whole
        # iteration is COVERED wall, bucketed "sched" by default, and
        # every specialized region inside (dispatch → device/compile,
        # admission, page_alloc, kv_handoff, swap, recovery, telemetry)
        # claims its own exclusive slice via nested frames. Time between
        # step() calls is nobody's and derives as "idle". That is the
        # whole reconciliation argument: Σ buckets == wall, gated.
        self._step_n += 1
        with self.ledger.measure(
            "sched", span="engine.step", step=self._step_n
        ) as frame:
            if self._staged_swap is not None:
                self._try_commit_swap()
            if self._installed is not None:
                params, draft_params = self._installed
            elif params is None:
                raise TypeError(
                    "step() without params: no swapped-in weights "
                    "installed — pass params, or swap_weights() first"
                )
            self._check_draft_args(draft_params)
            params, d_params = self._cast_params(params, draft_params)
            retired: list[int] = []
            with activate(self._mesh, self._rules):
                # TTL eviction before admission: an expired queued request
                # must not take a slot, and an expired in-flight one frees
                # its slot for this step's admission.
                self._sweep_deadlines()
                self._admit()
                rows = int(self._active.sum())
                t0 = time.perf_counter()
                try:
                    if self._mixed:
                        kind = self._mixed_dispatch(params, d_params, retired)
                    elif self._refill_dispatch(params, d_params, retired):
                        kind = "refill"
                    elif self._decode_dispatch(params, d_params, retired):
                        kind = "decode"
                    else:
                        # Only DISPATCHED time accrues: an idle poll
                        # (streaming drivers spin step() between
                        # arrivals) must not drown the refill/decode
                        # split.
                        kind = None
                    if kind:
                        self._book_dispatch(kind, t0, rows)
                except _RECOVERABLE_DISPATCH as e:
                    # Poison-request quarantine: strike every involved
                    # request, fail the repeat offenders, requeue the rest
                    # for probationary (solo) recompute — see
                    # _on_dispatch_fault. Infrastructure errors propagate.
                    self._on_dispatch_fault(e)
                self._apply_degradation()
                self._comp_maintain(params)
            self._g_active.set(int(self._active.sum()))
            self._g_queue.set(len(self._queue))
        self._c_step_s.inc(frame.total_s)
        return retired

    def _book_dispatch(self, kind, t0, rows):
        """The books of ONE dispatch of ``kind`` ("refill" / "decode" /
        "mixed") that started at ``t0`` with ``rows`` rows decoding.

        Wall time accrues to the program class that actually ran:
        ``_mixed_dispatch``'s fallthroughs (cache creation and
        speculative pure-refill → "refill", pure-decode block →
        "decode") land in refill_s/decode_s, not mixed_s, or refill_frac
        would understate refill serialization.

        Decode-stall accounting: a dispatch "stalls decode" when rows
        were actively decoding but the dispatch advanced none of them —
        the split engine's refill, and a mixed engine's "refill" in
        exactly one regime, the degradation ladder's split fallback on a
        speculative engine. It books stall time, and the SLO feed sees a
        0/1 stall indicator per dispatch-with-active-rows, so a
        ``decode_stall_share`` target reads as the fraction of such
        dispatches that parked decode behind refill: the ladder is driven
        by that monitor, and a degraded engine must not blind the very
        telemetry that degraded it.

        One ``engine.dispatch`` flight-recorder event per dispatch: its
        seconds, leaves, arrays and tokens are the growth of the cumulative
        counters since the previous event; ``starved_s`` is the
        empty-device time that ended at this dispatch's enqueue;
        ``carried`` / ``waiting`` / ``stalled`` are the request clock's
        (``_tick``): requests with a chunk row in the dispatch, admitted
        requests without a first token and without one, and requests
        holding a first token that it gave nothing (summed over its
        readbacks where it has several): the cause of each request's
        phase, by dispatch."""
        dt = time.perf_counter() - t0
        with self.ledger.measure("telemetry", span="engine.telemetry"):
            self._flush_ticks()
            seconds, dispatches = {
                "refill": (self._c_refill_s, self._c_refill_n),
                "decode": (self._c_decode_s, self._c_decode_n),
                "mixed": (self._c_mixed_s, self._c_mixed_n),
            }[kind]
            seconds.inc(dt)
            dispatches.inc()
            stalled = kind == "refill" and rows > 0
            if stalled:
                self._c_stall_s.inc(dt)
            if rows and self.slo is not None:
                self.slo.observe(
                    "decode_stall_share", 1.0 if stalled else 0.0
                )
            now = {
                field: getattr(self, attr).value
                for field, attr in self._DISPATCH_DELTAS.items()
            }
            now["starved_s"] = self._starved_at_enqueue
            if self._moe_counted:
                # Both phases' series: a dispatch grows one of them.
                for field, i in (("moe_assignments", 0), ("expert_reads", 1)):
                    now[field] = sum(
                        series[i].value for series in self._c_moe.values()
                    )
            by_phase, self._ph_event = self._ph_event, [0] * len(_PHASES)
            self.recorder.record(
                "engine.dispatch", family=self._last_family, phase=kind,
                step=self._step_n, rows=rows, compiled=self._compiled,
                carried=by_phase[_REFILL], waiting=by_phase[_REFILL_WAIT],
                stalled=by_phase[_STALL],
                **{f: v - self._booked[f] for f, v in now.items()},
            )
            self._booked = now
            self._compiled = False

    # --- stats -------------------------------------------------------------

    def latency_stats(self) -> dict | None:
        """Latency percentiles over the requests completed in the current
        stats window (see class docstring for the field meanings)."""
        comp = self._completed
        if not comp:
            return None

        def pcts(values, name):
            a = np.asarray([v for v in values if v is not None], np.float64)
            if not a.size:
                return {}
            return {
                f"{name}_p50": float(np.percentile(a, 50)),
                f"{name}_p99": float(np.percentile(a, 99)),
            }

        out = {"requests": len(comp)}
        out.update(pcts([c["queue_wait"] for c in comp], "queue_wait"))
        out.update(pcts([c["ttft"] for c in comp], "ttft"))
        out.update(pcts([c["tpot"] for c in comp], "tpot"))
        out.update(pcts(self._itl, "itl"))
        out.update(pcts([c["e2e"] for c in comp], "e2e"))
        # The request clock: a request's TPOT is its decode plus its
        # stall seconds a token; its time from admission to first token
        # is refill plus the wait for a refill turn.
        for name in ("decode_per_token", "stall_per_token", "refill_wait"):
            out.update(pcts([c[f"{name}_s"] for c in comp], name))
        refill_s = self._win_delta(self._c_refill_s)
        decode_s = self._win_delta(self._c_decode_s)
        mixed_s = self._win_delta(self._c_mixed_s)
        stall_s = self._win_delta(self._c_stall_s)
        busy = refill_s + decode_s + mixed_s
        out.update(
            refill_s=refill_s, decode_s=decode_s, mixed_s=mixed_s,
            refill_frac=(refill_s / busy) if busy else None,
            # Decode-stall share: the fraction of dispatched engine time
            # that parked decoding rows behind another slot's refill —
            # the number the mixed engine exists to drive to ~0.
            decode_stall_s=stall_s,
            decode_stall_share=(stall_s / busy) if busy else None,
        )
        # Multi-step scheduler (round 16): engine iterations fused per
        # host dispatch this window. 1.0 means the host round-tripped
        # every token (horizon=1); the gate in scripts/bench_compare.py
        # tracks it direction-aware (up = fewer host touches per token).
        multi_n = self._win_delta(self._c_multi_n)
        if multi_n:
            out.update(
                multi_dispatches=int(multi_n),
                steps_per_dispatch=(
                    self._win_delta(self._c_multi_links) / multi_n
                ),
                plan_reuse_rate=(
                    self._win_delta(self._c_plan_reused)
                    / max(1.0, self._win_delta(self._c_plan_staged))
                ),
            )
        # Recovery-policy telemetry (round 10), window-derived like the
        # rest: shed_rate is the fraction of ARRIVALS admission control
        # rejected; deadline_miss_rate the fraction of RETIREMENTS that
        # were TTL evictions — both gated direction-aware by
        # scripts/bench_compare.py so robustness hooks can't silently
        # regress the serving trajectory.
        shed = self._win_delta(self._c_shed)
        offered = self._win_delta(self._c_requests) + shed
        done = (
            self._win_delta(self._c_finished)
            + self._win_delta(self._c_req_failed)
        )
        dl = self._win_delta(self._c_deadline)
        out.update(
            shed_rate=(shed / offered) if offered else 0.0,
            deadline_miss_rate=(dl / done) if done else 0.0,
            failed=int(self._win_delta(self._c_req_failed)),
            # Failover visibility (round 11): requests drained to another
            # replica are counted apart from true failures, so a router
            # kill shows up as rerouted work, not as fresh admissions.
            rerouted=int(self._win_delta(self._c_rerouted)),
        )
        if self._paged and self._prefix:
            # KV economy (round 15): the fraction of this window's
            # admissions that reused retained prefix pages, and the
            # fraction of router-predicted hits that admission could not
            # realize (evicted/spilled mid-route — the tier race).
            hits = self._win_delta(self._c_pfx_hits)
            admitted = self._win_delta(self._c_requests)
            exp = self._win_delta(self._c_pfx_expected)
            miss = self._win_delta(self._c_tier_miss)
            out.update(
                prefix_hit_rate=(hits / admitted) if admitted else 0.0,
                tier_miss_rate=(miss / exp) if exp else 0.0,
            )
        return out

    def _snapshot_stats(self):
        # Mode stats keep the pre-persistence contract exactly (None when
        # no mode is on — test-pinned); the VALUES are window deltas over
        # the cumulative registry counters, so last_stats is re-derived
        # from the same metrics a Prometheus scrape would see.
        stats = {}
        if self._paged:
            stats.update(
                page_high_water=int(self._g_pages.high_water),
                pages_total=self._paged_pages - 1,
                page_size=self._page_size,
                preemptions=int(self._win_delta(self._c_preempt)),
            )
            if self._prefix:
                stats.update(
                    prefix_hits=int(self._win_delta(self._c_pfx_hits)),
                    prefix_pages_reused=int(
                        self._win_delta(self._c_pfx_pages)
                    ),
                    prefix_pages_retained=len(self._cached_lru),
                )
        if self._speculative:
            acc = self._win_delta(self._c_spec_acc)
            prop = self._win_delta(self._c_spec_prop)
            stats.update(
                spec_accepted=int(acc),
                spec_proposed=int(prop),
                spec_accept_rate=(acc / prop) if prop else None,
            )
        self.last_stats = stats or None
        self.last_latency = self.latency_stats()

    def program(self, family: str) -> Program:
        """The table's entry for ``family`` (KeyError where this engine's
        mode cannot dispatch it): its jitted ``fn`` and, once it has
        dispatched, ``last_args()``."""
        return self._programs[family]

    def compile_counts(self) -> dict[str, int | None]:
        """Executable-cache size per compiled engine program — each is
        that program's lifetime compile count (one executable per
        distinct shape/static combination), the "did serving recompile
        mid-flight?" probe. The steady-state engine holds these at 1
        (the fused horizon program too: one executable per horizon, by
        the same fixed-shape plan arrays that hold mixed_step at 1).
        Programs off the steady path are listed once they have
        dispatched (``Program.steady``)."""
        return {
            p.family: cache_size(p.fn) for p in self._programs.values()
            if p.steady or p.last_args is not None
        }

    def _dispatched_programs(self):
        """``(program_name, jitted_fn, args)`` for every engine program
        that has dispatched at least once — THE one list of relowerable
        programs, shared by the runtime reports and the static contract
        pass so a new program cannot be visible to one and invisible to
        the other. ``first_refill`` is included so single-chunk prefills
        are not silently missing."""
        return [
            (p.family, p.fn, p.last_args())
            for p in self._programs.values() if p.last_args is not None
        ]

    def _program_reports(self) -> dict[str, dict]:
        """Full ``executable_report`` per dispatched engine program,
        re-lowered AOT with its most recent dispatch arguments (costs a
        compile per program — diagnostics, not hot path; coverage per
        :meth:`_dispatched_programs`)."""
        from learning_jax_sharding_tpu.telemetry.compile_watch import (
            executable_report,
        )

        with activate(self._mesh, self._rules):
            return {
                name: executable_report(fn, *args)
                for name, fn, args in self._dispatched_programs()
            }

    def collective_inventory(self) -> dict[str, dict[str, int]]:
        """Per-dispatch collective counts read off the engine's OWN
        compiled programs — ``parallel.hlo.collective_counts`` over each
        program (see :meth:`_program_reports` for cost and coverage)."""
        return {
            name: rep["collectives"]
            for name, rep in self._program_reports().items()
        }

    def program_hlo(self) -> dict[str, str]:
        """Optimized HLO text per dispatched engine program — the static
        contract pass's view of the serving path (``analysis.contracts``).
        Same AOT-relower cost and coverage as :meth:`_program_reports`
        (both map over :meth:`_dispatched_programs`)."""
        from learning_jax_sharding_tpu.parallel.hlo import compiled_hlo

        with activate(self._mesh, self._rules):
            return {
                name: compiled_hlo(fn, *args)
                for name, fn, args in self._dispatched_programs()
            }

    def donation_audit(self) -> dict[str, dict]:
        """``analysis.donation.donation_report`` per dispatched engine
        program: what each asked XLA to update in place (every cache leaf
        but the block tables, ``engine_programs._donating``) and whether
        the executable aliases it. Same AOT-relower cost and coverage as
        :meth:`program_hlo`."""
        from learning_jax_sharding_tpu.analysis.donation import (
            donation_report,
        )

        with activate(self._mesh, self._rules):
            return {
                name: donation_report(fn, *args)
                for name, fn, args in self._dispatched_programs()
            }

    def contract_name(self, program: str) -> str:
        """Engine program → golden contract name
        (``analysis/golden/<name>.json``) — the names
        ``analysis.entrypoints`` generates under: the table's base name,
        ``_q8`` under comm compression, and a ``spec_`` prefix on a
        SPECULATIVE engine's programs (its refill also prefills the draft
        cache — a different program family with its own goldens:
        spec_first_prefill / spec_prefill / spec_decode_step)."""
        prog = self._programs.get(program)
        base = prog.contract if prog is not None else program
        comp = self._comp
        if prog is not None and not prog.applies:
            # The handoff programs are only dispatchable on non-spec
            # engines (export/ingest raise otherwise) — one golden each.
            # A KV codec does not change the DEVICE program (the codec
            # runs in the host transfer plan), but a compression engine
            # contracts under ``*_q8`` names anyway: the golden set must
            # say, checkably, which byte-movement regime it was pinned
            # under.
            if comp is not None and comp.kv_codec is not None:
                return f"{base}_q8"
            return base
        if comp is not None and comp.active:
            # Apply-family programs compile the quantized TP matmul in:
            # a DIFFERENT steady-state program with its own golden. A
            # drift trip flips ``comp.enabled`` off and the retraced
            # programs contract under the plain names again.
            base = f"{base}_q8"
        if program == "decode_block":
            # The plain decode program keeps its plain golden even on a
            # speculative engine: the degradation ladder dispatches it
            # with the target cache only, and it compiles to the same
            # HLO a non-speculative engine's decode_block does — no new
            # steady-state program beyond the documented set.
            return base
        return f"spec_{base}" if self._speculative else base

    def check_contracts(self, golden_dir):
        """Check every dispatched engine program against its golden SPMD
        contract in ``golden_dir`` (:meth:`contract_name` maps programs
        to golden files) and return the findings — the serving-side
        enforcement hook for ``scripts/shardcheck.py``. Findings also
        land in this engine's flight recorder and registry, so a contract
        drift shows up in the same diagnosis bundle as the runtime events
        it explains."""
        from learning_jax_sharding_tpu.analysis.contracts import (
            check_against_golden,
            contract_of,
        )
        from learning_jax_sharding_tpu.analysis.findings import (
            report_findings,
        )

        findings = []
        for prog, text in self.program_hlo().items():
            observed = contract_of(
                self.contract_name(prog), text, mesh=self._mesh
            )
            findings.extend(check_against_golden(golden_dir, observed))
        report_findings(
            findings, recorder=self.recorder, registry=self.registry
        )
        return findings

    def explain_collectives(
        self, *, measured: bool = False, profile=None
    ) -> dict[str, "object"]:
        """Pre-compile collective attribution for every dispatched engine
        program: run the GSPMD propagation simulator
        (``analysis.shardflow``) over each program's jaxpr and return a
        :class:`~learning_jax_sharding_tpu.analysis.shardflow.
        ShardflowReport` per contract name — each predicted collective
        carries the SOURCE LINE that causes it, which the compiled-HLO
        inventory (:meth:`collective_inventory`) can never recover.
        Trace-only (``jax.make_jaxpr``): no compiles, so this is cheap
        enough to run on a live engine. Decode-family programs advance
        ``decode_block_steps`` tokens per dispatch inside their device
        loop; that trip count prices the in-loop collectives.

        With ``measured=True`` each contract name instead maps to
        ``{"report", "measured_comm_s", "lines"}``: the same report plus
        the ledger window's measured collective seconds for that program
        family (exposed + overlapped from :meth:`overlap_report`),
        attributed per SOURCE LINE proportionally to the costmodel's
        per-line prediction (``telemetry.commscope``) — the
        predicted-vs-measured table ``shardcheck --explain`` prints."""
        from learning_jax_sharding_tpu.analysis.shardflow import (
            trace_shardflow,
        )

        out = {}
        with activate(self._mesh, self._rules):
            for name, fn, args in self._dispatched_programs():
                cname = self.contract_name(name)
                # The fused horizon program scans its body ``horizon``
                # times, not ``decode_block_steps``: price its in-loop
                # collectives at the horizon trip count so the reconciled
                # total caps at N× the single-step multiset.
                hint = (
                    int(self.horizon)
                    if name in ("multi_step", "adapter_multi_step")
                    else int(self._block_steps)
                )
                out[cname] = trace_shardflow(
                    cname, fn, *args, mesh=self._mesh,
                    while_trip_hint=hint,
                )
        if not measured:
            return out

        from learning_jax_sharding_tpu.analysis import costmodel
        from learning_jax_sharding_tpu.telemetry import commscope

        if profile is None:
            profile = costmodel.current_profile()
        overlap = self.ledger.overlap_report(
            predicted=self._comm_predictions(profile, out)
        )
        res = {}
        for name, _fn, _args in self._dispatched_programs():
            cname = self.contract_name(name)
            rep = out.get(cname)
            if rep is None:
                continue
            fam = overlap["families"].get(name)
            meas = (
                fam["exposed_comm_s"] + fam["overlapped_comm_s"]
                if fam else 0.0
            )
            res[cname] = {
                "report": rep,
                "measured_comm_s": meas,
                "lines": commscope.line_report(rep, profile, meas),
            }
        return res

    def _comm_predictions(self, profile, reports) -> dict[str, dict]:
        """Per-dispatch ``{"compute_s", "comm_s"}`` costmodel prediction
        per program family (keys = :meth:`_dispatched_programs` names,
        matching the ledger's device-family tags). ``compute_s`` is the
        non-collective roofline (max of compute/memory terms) — the
        serial lens :func:`~.commscope.decompose_overlap` needs."""
        from learning_jax_sharding_tpu.analysis import costmodel

        preds = {}
        for name, _fn, _args in self._dispatched_programs():
            rep = reports.get(self.contract_name(name))
            if rep is None:
                continue
            cost = costmodel.price(rep, profile)
            preds[name] = {
                "compute_s": max(cost.compute_s, cost.memory_s),
                "comm_s": cost.collective_s,
            }
        return preds

    def overlap_report(self, profile=None) -> dict:
        """Decompose the ledger window's device seconds into compute /
        exposed-comm / overlapped-comm per program family
        (``GoodputLedger.overlap_report``), with per-dispatch costmodel
        predictions derived from this engine's own shardflow reports.
        The decomposition sums back to the device bucket exactly, so
        ``reconcile()`` is untouched."""
        from learning_jax_sharding_tpu.analysis import costmodel

        if profile is None:
            profile = costmodel.current_profile()
        reports = self.explain_collectives()
        return self.ledger.overlap_report(
            predicted=self._comm_predictions(profile, reports)
        )

    def comm_report(
        self, profile=None, comm_profile=None, *, export_gauges=True,
    ) -> dict:
        """The comm-observatory verdict for the current ledger window.

        Combines the overlap decomposition with per-source-line
        predicted-vs-measured attribution for every program family, and
        (by default) publishes the ``comm_axis_bandwidth_bytes_per_s``
        and ``comm_exposed_seconds_total{family,axis}`` gauges into this
        engine's registry — the Prometheus/fleet-merge path.

        ``comm_profile`` is a measured ``telemetry.commscope.CommProfile``
        (calibration ladder output); when given, pricing uses its
        per-axis α–β models via ``costmodel.calibrate_axis_profiles``
        with the pinned table as fallback."""
        from learning_jax_sharding_tpu.analysis import costmodel
        from learning_jax_sharding_tpu.telemetry import commscope

        if profile is None:
            profile = costmodel.current_profile()
        if comm_profile is not None:
            profile = costmodel.calibrate_axis_profiles(
                comm_profile, base=profile)
            if export_gauges:
                commscope.export_profile_gauges(self.registry, comm_profile)
        reports = self.explain_collectives()
        overlap = self.ledger.overlap_report(
            predicted=self._comm_predictions(profile, reports)
        )
        families = {}
        for name, fam in overlap["families"].items():
            rep = reports.get(self.contract_name(name))
            meas = fam["exposed_comm_s"] + fam["overlapped_comm_s"]
            shares = (
                commscope.axis_comm_shares(rep, profile)
                if rep is not None else {}
            )
            if export_gauges:
                commscope.export_exposed_gauges(
                    self.registry, name, fam["exposed_comm_s"], shares)
            families[name] = {
                **fam,
                "measured_comm_s": meas,
                "axis_shares": shares,
                "lines": (
                    commscope.line_report(rep, profile, meas)
                    if rep is not None else []
                ),
            }
        return {
            "profile": profile.to_dict(),
            "overlap": overlap,
            "families": families,
        }

    def collective_axis_volume(self) -> dict[str, dict]:
        """Per-MESH-AXIS collective byte volume for each engine program:
        what one refill/decode dispatch puts on the wire, attributed to
        the mesh axis whose device groups carry it
        (``telemetry.devview.axis_collective_volume``). Same AOT-relower
        cost and coverage as :meth:`collective_inventory`."""
        from learning_jax_sharding_tpu.telemetry.devview import (
            axis_collective_volume,
        )

        return {
            name: axis_collective_volume(
                rep["collective_instructions"], self._mesh
            )
            for name, rep in self._program_reports().items()
        }

    def dump_diagnostics(self, outdir=None):
        """Write the engine's post-mortem bundle (flight-recorder events +
        registry snapshot + Chrome trace + device memory stats) and return
        its directory — the on-demand form of what
        ``recorder.capture()`` dumps on exception."""
        return self.recorder.dump(
            outdir, registry=self.registry, tracer=self.tracer
        )

    # --- one-shot entry ----------------------------------------------------

    def serve(self, params, prompts, rng=None, draft_params=None):
        """Drain a whole queue: outputs in queue order, requests numbered
        by queue index (the sampling-stream identity). Requires an idle
        engine (streaming work must finish first); persistent state —
        cache, pool, prefix registry — carries over BETWEEN calls."""
        self._check_draft_args(draft_params)
        if self.has_work():
            raise RuntimeError(
                "serve() requires an idle engine: drain streaming work "
                "(step() until not has_work()) first"
            )
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        # Validate EVERYTHING before touching any state: a bad prompt
        # must raise without costing the engine its persistent registry
        # (the failure path below resets the pool).
        for p in prompts:
            self._validate_prompt(p)
        self.rng = jax.random.key(0) if rng is None else rng
        self.reset_stats()
        # The per-call rid namespace (0..n-1) must not collide with
        # un-popped streaming results: stash them, restore after — a
        # failed call's partial outputs are dropped with its state.
        stash = self._finished
        self._finished = {}
        ok = False
        try:
            for i, p in enumerate(prompts):
                self.add_request(p, rid=i)
            with self.tracer.span("engine.serve", requests=len(prompts)):
                while self.has_work():
                    self.step(params, draft_params)
            ok = True
        finally:
            # Stats must reflect THIS call even when it raises — pool
            # exhaustion is exactly when the measured footprint matters.
            self._snapshot_stats()
            if not ok:
                # Leave the engine reusable: drop the wedged in-flight
                # state (and the registry — partial writes may alias it).
                self.reset()
                self._finished = stash
        results = []
        for i in range(len(prompts)):
            r = self._finished.pop(i)
            if r.status == "ok":
                results.append(np.asarray(r.tokens, np.int32))
            else:
                # Recovery policies can retire a request WITHOUT
                # completing it (deadline TTL, poison quarantine,
                # malformed) — its queue-order slot carries the terminal
                # status instead of tokens, never a silent gap.
                results.append(RequestFailure(
                    rid=r.rid, status=r.status, error=r.error,
                    tokens=r.tokens,
                ))
        self._finished = stash
        return results


def make_continuous_engine(
    config: TransformerConfig, mesh: Mesh, rules: Rules, **kwargs
):
    """Build a persistent :class:`ContinuousEngine` and return its
    one-shot entry ``serve(params, prompts, rng, draft_params) ->
    list[np.ndarray]`` (the original engine API — every oracle pinned on
    it holds unchanged). The wrapped engine is reachable at
    ``serve.engine`` for streaming admission and telemetry; after each
    call ``serve.last_stats`` / ``serve.last_latency`` mirror the
    engine's. Because the engine persists, repeated calls share the KV
    cache, page pool, and prefix registry — see
    :class:`ContinuousEngine` for the full contract."""
    engine = ContinuousEngine(config, mesh, rules, **kwargs)

    def serve(params, prompts, rng=None, draft_params=None):
        try:
            return engine.serve(
                params, prompts, rng=rng, draft_params=draft_params
            )
        finally:
            serve.last_stats = engine.last_stats
            serve.last_latency = engine.last_latency

    serve.engine = engine
    serve.last_stats = None
    serve.last_latency = None
    return serve
