"""Goodput ledger: exhaustive wall-clock attribution for serving + training.

ROADMAP item 1 says the engine serves at ~6% of its raw decode ceiling,
but until now the repo could not *prove where the missing time goes*:
spans time what they wrap, counters count what they see, and everything
else vanishes. The ledger closes that hole with an accounting identity —
every second of a loop's wall-clock lands in EXACTLY ONE bucket, and the
buckets must sum back to the wall within ε (:meth:`GoodputLedger.reconcile`,
gated in tier-1). The invariant holds *by construction*:

* :meth:`~GoodputLedger.measure` opens a frame on a stack; a frame's
  bucket receives its EXCLUSIVE time (elapsed minus time spent in child
  frames), so nesting never double-counts;
* a TOP-LEVEL frame (the engine's ``step()``, one ``fit()`` iteration)
  also accrues ``covered`` wall — anything inside it that no child frame
  claims falls to the frame's own bucket (the engine's host-scheduling
  remainder), never on the floor;
* ``idle`` is DERIVED, not measured: window wall minus covered time is
  time nobody was stepping (a starved engine between arrivals, the
  driver doing its own work).

So ``Σ buckets == covered + idle == wall`` up to float rounding, and a
new code path can only break the identity by spending time *outside
every frame inside a frame-covered region* — which is impossible — or
by mis-bucketing, which :func:`analysis.source_lint`'s
``untimed-engine-phase`` rule catches statically.

Canonical buckets (:data:`BUCKETS`; the ledger accepts any name, these
are what the engine/loop wiring uses):

==============  ==========================================================
``device``      dispatch + blocking readback of compiled programs — the
                only bucket the hardware roofline can be charged against
``compile``     a dispatch whose executable cache GREW (trace+compile
                rode this call; re-bucketed from ``device`` via
                :meth:`Frame.rebucket`)
``sched``       host scheduling remainder: slot bookkeeping, chunk
                assembly, retirement — the step's own bucket
``admission``   queue admission + deadline sweeps
``page_alloc``  paged-KV page claims / prefix-cache mapping
``kv_handoff``  export/ingest + cross-mesh KV transfer (disaggregation)
``swap``        weight hot-swap staging and commit stalls
``recovery``    chaos seams, dispatch-fault quarantine, degradation,
                rollback/emergency-save — time spent *because something
                failed* (injected hangs land here, not in ``device``)
``telemetry``   the observability tax: span/recorder/SLO bookkeeping
                (perf_goodput.py pins this < 2% of wall)
``idle``        derived starvation/idle time (never opened as a frame)
==============  ==========================================================

A frame can also be a SPAN (``measure(bucket, span="engine.h2d")``):
opening it opens the ledger's tracer span of that name, which enters a
``jax.profiler.TraceAnnotation``. A span is FINER than its bucket, never
a new bucket: the buckets, ``ledger_seconds_total`` and
:meth:`~GoodputLedger.reconcile` read what they read without it. Because
the frames partition a loop's step exhaustively, every instant of it lies
in exactly one innermost span, on the tracer's ring and on the profiler's
host timeline alike. The names are the caller's: the engine's
(``engine.step`` > ``engine.admission`` / ``engine.page_alloc`` /
``engine.h2d`` / ``engine.enqueue.<family>`` / ``engine.wait.<family>`` /
``engine.consume`` / ``engine.plan`` / ``engine.telemetry`` /
``engine.recovery``, and ``engine.kv_handoff`` / ``engine.swap``) are
tabled beside ``ContinuousEngine._led_device`` in ``models/serving.py``
and pinned by ``tests/test_engine_spans.py``.

The same frames carry a second clock, the EMPTY-DEVICE clock: between
:meth:`~GoodputLedger.device_empty` (a readback returned and nothing
else is dispatched) and :meth:`~GoodputLedger.device_busy` (the next
enqueue returned) every second goes to :data:`STARVED_METRIC` and, by
the innermost frame it was spent in, to ``STARVED_METRIC{span=<label>}``:
the frame's ``label`` (its bucket unless the caller names a finer one),
or ``outside_step`` outside every frame. It is an ESTIMATE of device
idle on the host's clock, with an error each way: the device starts
inside the jitted call, so the tail of every enqueue is counted though
the chip is already working; and while the caller keeps programs in
flight the clock stands, though the chip may idle between them.

Windowing mirrors the engine's ``reset_stats`` idiom: cumulative totals
plus a :meth:`begin_window` base snapshot; :meth:`window_report` emits
the per-window breakdown, ``host_share`` (1 − device/busy — the
host-vs-device gap itself), a ``goodput_ratio`` against an optional
roofline-seconds estimate (``analysis.costmodel``), and the NAMED top
gap contributor, so "where did the 16× go" is one dict per window.

Every booked second also meters into the owning registry as the labeled
counter ``ledger_seconds_total{bucket="..."}`` — the fleet merge
(``parallel.multihost.merge_registry_snapshots``) splices a ``replica``
label alongside and ``snapshot_prometheus_text`` renders both, so one
scrape carries the whole fleet's time accounting.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Iterator, Optional

#: Canonical bucket names, in report order. ``idle`` is derived.
BUCKETS = (
    "device", "compile", "sched", "admission", "page_alloc",
    "kv_handoff", "swap", "recovery", "telemetry", "idle",
)


#: The empty-device clock's counter, plain and ``{span=<label>}``.
STARVED_METRIC = "engine_device_starved_seconds_total"


class Frame:
    """One open :meth:`GoodputLedger.measure` region. Exposed so callers
    can :meth:`rebucket` after the fact — the compile-steal idiom: open
    as ``device``, check the executable cache after the call, and move
    the frame to ``compile`` if the cache grew (the dispatch paid a
    trace+compile, not a device step)."""

    __slots__ = ("bucket", "t0", "child_s", "family", "label", "total_s")

    def __init__(
        self, bucket: str, t0: float, family: Optional[str] = None,
        label: Optional[str] = None,
    ):
        self.bucket = bucket
        self.t0 = t0
        self.child_s = 0.0
        self.family = family
        self.label = label or bucket   # the empty-device clock's series
        self.total_s = 0.0             # wall of the frame, set at its close

    def rebucket(self, bucket: str) -> None:
        self.bucket = bucket


class GoodputLedger:
    """Exclusive-bucket wall-clock accounting with a reconciliation
    invariant. Single-threaded by design (the engine loop and ``fit()``
    are single-threaded); one ledger per loop, not per process.
    """

    def __init__(
        self,
        *,
        registry: Any | None = None,
        metric: str = "ledger_seconds_total",
        clock: Callable[[], float] = time.perf_counter,
        tracer: Any | None = None,
    ):
        self._clock = clock
        self._registry = registry
        self._metric = metric
        self._tracer = tracer
        # The tracer's clock is perf_counter: where the ledger reads the
        # same one, a frame hands its own edges to its span.
        self._same_clock = clock is time.perf_counter
        self._starved: dict[str, Any] = {}
        self._empty_t: float | None = None   # device known empty since
        self.starved_s = 0.0
        self._counters: dict[str, Any] = {}
        self._totals: dict[str, float] = {}
        self._covered = 0.0          # cumulative top-level frame seconds
        self._windows = 0            # top-level frames opened (≈ steps)
        self._stack: list[Frame] = []
        # Per-family DEVICE attribution: every device-bucket second also
        # lands under exactly one program-family key ("unattributed" when
        # the caller didn't tag), so Σ families == device bucket by
        # construction — the base overlap_report() decomposes on.
        self._dev_family: dict[str, float] = {}
        self._dev_calls: dict[str, int] = {}
        t = clock()
        self._t_created = t
        self._win_t = t
        self._win_totals: dict[str, float] = {}
        self._win_covered = 0.0
        self._win_dev_family: dict[str, float] = {}
        self._win_dev_calls: dict[str, int] = {}

    # --- recording ---------------------------------------------------------

    def _add(
        self, bucket: str, seconds: float, family: Optional[str] = None
    ) -> None:
        self._totals[bucket] = self._totals.get(bucket, 0.0) + seconds
        if bucket == "device":
            fam = family or "unattributed"
            self._dev_family[fam] = self._dev_family.get(fam, 0.0) + seconds
        if self._registry is not None:
            c = self._counters.get(bucket)
            if c is None:
                c = self._registry.counter(
                    f'{self._metric}{{bucket="{bucket}"}}',
                    "ledger wall-clock seconds per exclusive bucket",
                )
                self._counters[bucket] = c
            if seconds > 0:
                c.inc(seconds)

    @contextlib.contextmanager
    def measure(
        self, bucket: str, family: Optional[str] = None, *,
        span: Optional[str] = None, label: Optional[str] = None,
        ring: bool = True, busy: bool = False, counter: Any | None = None,
        **args,
    ) -> Iterator[Frame]:
        """Attribute the enclosed wall-clock to ``bucket``, exclusively:
        time claimed by nested ``measure`` frames is subtracted here and
        booked there. A top-level frame also accrues covered wall (the
        idle-derivation base). ``family`` tags device frames with the
        program family for :meth:`overlap_report` — frames that rebucket
        away from ``device`` (compile-steal) drop out of the family
        totals together with their device seconds.

        ``span`` names the frame on the tracer and the profiler (module
        docstring); ``args`` go to the span, and ``ring=False`` keeps it
        off the tracer's ring. ``label`` is the frame's series of the
        empty-device clock (default: its bucket). ``counter`` also
        receives the frame's exclusive seconds. ``busy`` says the frame
        dispatches device work outside the caller's enqueue/wait funnel:
        the empty-device clock stops when it opens."""
        t0 = self._clock()
        self._tick(t0)
        f = Frame(bucket, t0, family, label)
        self._stack.append(f)
        if busy:
            self._empty_t = None
        sp = None
        if span is not None and self._tracer is not None:
            sp = self._tracer.begin(
                span, keep=ring, at=t0 if self._same_clock else None,
                **args,
            )
        try:
            yield f
        finally:
            t1 = self._clock()
            if sp is not None:
                self._tracer.end(sp, at=t1 if self._same_clock else None)
            self._tick(t1)
            total = f.total_s = t1 - f.t0
            own = max(0.0, total - f.child_s)
            self._stack.pop()
            self._add(f.bucket, own, f.family)
            if counter is not None:
                counter.inc(own)
            if f.bucket == "device":
                fam = f.family or "unattributed"
                self._dev_calls[fam] = self._dev_calls.get(fam, 0) + 1
            if self._stack:
                self._stack[-1].child_s += total
            else:
                self._covered += total
                self._windows += 1

    # --- the empty-device clock ---------------------------------------------

    def _tick(self, t: float) -> None:
        """Book the empty-device seconds up to ``t`` to the innermost
        open frame (every frame edge and both marks call this)."""
        if self._empty_t is None:
            return
        dt, self._empty_t = t - self._empty_t, t
        if dt <= 0:
            return
        self.starved_s += dt
        if self._registry is None:
            return
        label = self._stack[-1].label if self._stack else "outside_step"
        for key in (None, label):
            c = self._starved.get(key)
            if c is None:
                name = STARVED_METRIC
                if key is not None:
                    name += f'{{span="{key}"}}'
                c = self._starved[key] = self._registry.counter(
                    name,
                    "seconds from a readback that left nothing in flight "
                    "to the return of the next enqueue, by the innermost "
                    "frame the host spent them in: an estimate of device "
                    "idle on the host's clock (over by the tail of each "
                    "enqueue, which the chip already works through; "
                    "under while a chain is in flight, when it stands)",
                )
            c.inc(dt)

    def device_empty(self) -> None:
        """A blocking readback returned and nothing else is dispatched:
        the device has no work until the next enqueue."""
        if self._empty_t is None:
            self._empty_t = self._clock()

    def device_busy(self) -> None:
        """An enqueue returned (or work was dispatched some other way):
        stop the empty-device clock."""
        self._tick(self._clock())
        self._empty_t = None

    def account(
        self, bucket: str, seconds: float, family: Optional[str] = None
    ) -> None:
        """Retrospective booking: ``seconds`` of wall that already passed
        land in ``bucket``. Inside an open frame this STEALS from the
        enclosing frame (its exclusive time shrinks by the same amount,
        so the identity is conserved); outside any frame the seconds
        count as covered wall — only book time that genuinely elapsed on
        this loop's clock."""
        if seconds < 0:
            raise ValueError(f"cannot account {seconds} s")
        self._add(bucket, seconds, family)
        if self._stack:
            self._stack[-1].child_s += seconds
        else:
            self._covered += seconds

    @property
    def in_frame(self) -> bool:
        return bool(self._stack)

    # --- windows -----------------------------------------------------------

    def begin_window(self) -> None:
        """Start a fresh reporting window (the engine's ``reset_stats``
        calls this): subsequent :meth:`window_report`/:meth:`reconcile`
        deltas run from here."""
        self._win_t = self._clock()
        self._win_totals = dict(self._totals)
        self._win_covered = self._covered
        self._win_dev_family = dict(self._dev_family)
        self._win_dev_calls = dict(self._dev_calls)

    @property
    def window_start(self) -> float:
        """Clock timestamp of the current window's start (creation time
        until the first :meth:`begin_window`) — the cut economics uses to
        keep pre-window (warm-up) trace legs out of attribution."""
        return self._win_t

    def window_buckets(self) -> dict[str, float]:
        """Per-bucket seconds since :meth:`begin_window`, with derived
        ``idle`` — keys ordered canonically, zero buckets included."""
        out = {
            b: self._totals.get(b, 0.0) - self._win_totals.get(b, 0.0)
            for b in BUCKETS if b != "idle"
        }
        for b in self._totals:        # non-canonical buckets still report
            if b not in out:
                out[b] = self._totals[b] - self._win_totals.get(b, 0.0)
        wall = self._clock() - self._win_t
        covered = self._covered - self._win_covered
        out["idle"] = max(0.0, wall - covered)
        return out

    def window_report(
        self, *, roofline_device_s: Optional[float] = None
    ) -> dict:
        """The goodput verdict for the current window.

        * ``host_share`` — 1 − device/busy, where busy is all covered
          (non-idle) time: the fraction of the engine's active wall spent
          anywhere but the device bucket. THE number ROADMAP item 1's
          refactor must push down.
        * ``goodput_ratio`` — roofline seconds over wall when a roofline
          estimate is given (what an ideally-scheduled device would have
          needed for the same tokens), else measured device over wall.
        * ``top_contributor`` — the named largest non-device bucket:
          where the next optimization round should look first.
        """
        wall = self._clock() - self._win_t
        covered = self._covered - self._win_covered
        buckets = self.window_buckets()
        device = buckets.get("device", 0.0)
        busy = max(covered, 1e-12)
        gaps = {b: s for b, s in buckets.items() if b != "device"}
        top = max(gaps, key=gaps.get) if gaps else None
        ratio = (
            roofline_device_s / wall
            if roofline_device_s is not None and wall > 0
            else (device / wall if wall > 0 else 0.0)
        )
        return {
            "wall_s": wall,
            "busy_s": covered,
            "steps": self._windows,
            "buckets": buckets,
            "device_s": device,
            "host_share": 1.0 - device / busy if covered > 0 else None,
            "goodput_ratio": ratio,
            "roofline_device_s": roofline_device_s,
            "top_contributor": top,
            "top_contributor_s": gaps.get(top, 0.0) if top else 0.0,
            "telemetry_share": (
                buckets.get("telemetry", 0.0) / wall if wall > 0 else 0.0
            ),
        }

    def reconcile(self, *, eps: float | None = None) -> dict:
        """The hard invariant, as a checkable dict: window buckets must
        sum to window wall within ``eps`` (default: 1 µs per recorded
        frame plus 0.1% of wall — pure float-rounding slack; a real leak
        is milliseconds). ``ok`` is False on residual past eps or any
        negative bucket. Raises nothing — tests assert on it so the
        failure message carries the whole breakdown."""
        wall = self._clock() - self._win_t
        buckets = self.window_buckets()
        total = sum(buckets.values())
        if eps is None:
            eps = 1e-6 * max(1, self._windows) + 1e-3 * max(wall, 1e-9)
        residual = wall - total
        return {
            "ok": abs(residual) <= eps
            and all(s >= -1e-9 for s in buckets.values())
            and not self._stack,
            "wall_s": wall,
            "sum_s": total,
            "residual_s": residual,
            "eps": eps,
            "open_frames": len(self._stack),
            "buckets": buckets,
        }

    def device_families(self) -> dict[str, dict[str, float]]:
        """Window device seconds and dispatch counts per program family.

        Σ over families of ``seconds`` equals the window's ``device``
        bucket by construction — every device booking (measure-close,
        :meth:`account`, rebucket-into-device) passes through
        :meth:`_add`, which accrues the family total with the SAME
        number."""
        out: dict[str, dict[str, float]] = {}
        for fam, s in self._dev_family.items():
            d = s - self._win_dev_family.get(fam, 0.0)
            n = self._dev_calls.get(fam, 0) - self._win_dev_calls.get(fam, 0)
            if d != 0.0 or n != 0:
                out[fam] = {"seconds": d, "calls": float(n)}
        return out

    def overlap_report(
        self,
        predicted: Optional[dict[str, dict[str, float]]] = None,
    ) -> dict:
        """Decompose the window's ``device`` bucket into compute /
        exposed-comm / overlapped-comm per program family (ROADMAP item
        4's *realized overlap* signal).

        ``predicted`` maps family → ``{"compute_s", "comm_s"}``
        PER-DISPATCH costmodel predictions; each is scaled by the
        family's window dispatch count before
        :func:`~.commscope.decompose_overlap` splits that family's
        measured device seconds. Families without a prediction count as
        pure compute — comm seconds are never invented. The parts sum
        back to the device bucket exactly (exposed comm books under
        ``device``, never ``telemetry``), so :meth:`reconcile` is
        untouched by construction.
        """
        from .commscope import decompose_overlap

        fams = self.device_families()
        device = self.window_buckets().get("device", 0.0)
        predicted = predicted or {}
        families: dict[str, dict] = {}
        tot = {"compute_s": 0.0, "exposed_comm_s": 0.0,
               "overlapped_comm_s": 0.0}
        attributed = 0.0
        pred_comm = 0.0
        for fam, rec in sorted(fams.items()):
            d_s, calls = rec["seconds"], int(rec["calls"])
            p = predicted.get(fam)
            scale = calls if calls > 0 else 1
            c_s = (p.get("compute_s", 0.0) * scale) if p else d_s
            k_s = (p.get("comm_s", 0.0) * scale) if p else 0.0
            dec = decompose_overlap(d_s, c_s, k_s)
            families[fam] = {
                "device_s": d_s,
                "calls": calls,
                "predicted_compute_s": c_s if p else None,
                "predicted_comm_s": k_s if p else None,
                **dec,
            }
            attributed += d_s
            pred_comm += k_s
            for k in tot:
                tot[k] += dec[k]
        overlapped = tot["overlapped_comm_s"]
        return {
            "families": families,
            "device_s": device,
            "attributed_s": attributed,
            "residual_s": device - attributed,
            **tot,
            "exposed_comm_share": (
                tot["exposed_comm_s"] / device if device > 0 else 0.0
            ),
            "realized_overlap_ratio": (
                overlapped / pred_comm if pred_comm > 0 else None
            ),
        }

    def totals(self) -> dict[str, float]:
        """Cumulative (all-time) per-bucket seconds, no derived idle."""
        return dict(self._totals)
