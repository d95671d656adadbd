"""Structured spans: nested wall-clock timing that lands in three places.

A :class:`Tracer` records host-side events (spans, instants, per-request
async intervals) and exports them as

* **Chrome trace-event JSON** (:meth:`Tracer.chrome_trace`) — load the
  file straight into Perfetto (https://ui.perfetto.dev) or
  ``chrome://tracing``;
* **JSONL** (:meth:`Tracer.dump_jsonl`) — one event per line for
  machine consumption, the ``BENCH_r{N}.json`` style;
* **XProf/TensorBoard**, live: every :meth:`Tracer.span` also enters a
  ``jax.profiler.TraceAnnotation``, so when a ``utils.profiling.trace``
  capture is active the framework phases appear on the profiler's host
  timeline next to the device ops they dispatched. The annotation
  carries the span's ``args``; a ROOT span's (``engine.step``) also
  carries ``ts_us``, the tracer's own timestamp of its start, so a
  reader of the ``.xplane.pb`` can map tracer time onto profiler time,
  and ``chrome_trace()["otherData"]["epoch_unix_ns"]`` is the wall time
  of the tracer's epoch, which maps both onto the flight recorder's
  ``t`` (``scripts/engine_breakdown.py --xplane`` places a bundle's
  ``engine.dispatch`` events on a capture that way).

The engine's goodput-ledger frames are spans (``GoodputLedger.measure(
..., span=)`` opens one), so the ``engine.*`` names (the table is in
``models/serving.py``) partition every ``step()`` on that timeline. They
are about 22 ring events a dispatch: at the default ``max_events`` the
ring then holds the last 9,000 dispatches or so, and the ``request.*``
events written between them.

Honesty under async dispatch is explicit: a span around a jitted call
measures DISPATCH unless it contains a sync point (the reference's
timing flaw, `case6_attention.py:234-238`). :meth:`Tracer.sync` is that
sync point — it waits for its argument (``utils/bench.py::_sync``, i.e.
``jax.block_until_ready``) and records an instant event marking where in
the timeline the device was known to be done.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Iterator

import jax


class OpenSpan:
    """An open :meth:`Tracer.span`: the event's ``args`` (add to them
    before the span closes) and ``keep``, cleared for a span that turns
    out to have wrapped nothing (a dispatch that did not run): its event
    is then not written."""

    __slots__ = ("name", "args", "keep", "start", "parent", "annotation")

    def __init__(self, name: str, args: dict, keep: bool = True):
        self.name = name
        self.args = args
        self.keep = keep
        self.start = 0.0                       # tracer µs
        self.parent: str | None = None
        self.annotation = None


#: What a disabled tracer hands out: nothing is timed, nothing allocated
#: (callers may still write its fields; nobody reads them).
_NO_SPAN = OpenSpan("", {}, keep=False)


def device_sync(out: Any) -> None:
    """Force completion of ``out`` — THE honest sync point. Delegates to
    ``utils.bench._sync`` so the repo has exactly one definition of what
    "synced" means."""
    from learning_jax_sharding_tpu.utils.bench import _sync

    _sync(out)


class Tracer:
    """Collects trace events; cheap enough to leave on.

    Events are Chrome trace-event dicts (``ph`` phases used: ``X``
    complete, ``i`` instant, ``b``/``e`` async begin/end). Timestamps are
    microseconds since tracer construction; the buffer is a bounded RING
    (``max_events``): past the cap the OLDEST events are dropped (with a
    count), because the trace someone exports after an incident needs
    the most recent window, not the run's first minutes.
    """

    def __init__(
        self,
        *,
        enabled: bool = True,
        max_events: int = 200_000,
        name: str | None = None,
    ):
        import collections

        self.enabled = enabled
        self.name = name or "tracer"
        self.dropped = 0
        self.sink_errors = 0   # on_event sink raises (counted, not fatal)
        self._events: "collections.deque[dict]" = collections.deque(
            maxlen=max_events
        )
        self._lock = threading.Lock()
        self._local = threading.local()
        self._t0 = time.perf_counter()
        # Wall time of the epoch: ``ts`` µs after it is unix time
        # ``epoch_unix_ns / 1e9 + ts / 1e6`` — the flight recorder's ``t``.
        self._epoch_unix_ns = time.time_ns()
        # Deterministic ids: the OS pid and raw thread idents change per
        # run, which made merged fleet timelines interleave replicas
        # nondeterministically in Perfetto. Events carry pid 1 and small
        # first-seen thread indexes; the real OS pid survives in the
        # process-name metadata (`chrome_trace`), and `merge_tracers`
        # re-pids per replica.
        self._pid = 1
        self._os_pid = os.getpid()
        self._tid_of: dict[int, int] = {}
        self._max_events = max_events
        # Optional event sink (``FlightRecorder.attach_tracer`` sets it):
        # called with each emitted event dict, outside the ring lock. A
        # raising sink must not take the traced code down with it.
        self.on_event = None

    # --- time/emission -----------------------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) >= self._max_events:
                self.dropped += 1   # the append below evicts the oldest
            self._events.append(ev)
        cb = self.on_event
        if cb is not None:
            try:
                cb(ev)
            except Exception:
                # A raising sink must not take the traced code down with
                # it — but the failure must not vanish either
                # (swallowed-exception lint): count it, so a broken
                # recorder attachment is visible in the tracer's state.
                self.sink_errors += 1

    def _tid(self) -> int:
        """Stable small tid for the calling thread: 1, 2, ... in
        first-seen order — deterministic for single-threaded loops
        (always 1), and never a raw ident that reshuffles every run."""
        ident = threading.get_ident()
        t = self._tid_of.get(ident)
        if t is None:
            with self._lock:
                t = self._tid_of.setdefault(ident, len(self._tid_of) + 1)
        return t

    def thread_ids(self) -> list[int]:
        """Assigned tids, sorted — for thread-name metadata emission."""
        with self._lock:
            return sorted(self._tid_of.values())

    def _base(self, name: str, ph: str, **extra) -> dict:
        ev = {
            "name": name,
            "ph": ph,
            "ts": self._now_us(),
            "pid": self._pid,
            "tid": self._tid(),
        }
        ev.update(extra)
        return ev

    def _stack(self) -> list[OpenSpan]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # --- recording API -----------------------------------------------------

    @contextlib.contextmanager
    def span(
        self, name: str, *, keep: bool = True, **args
    ) -> Iterator[OpenSpan]:
        """Nested complete event + XProf bridge. ``args`` become the
        event's ``args`` dict (JSON-able values only) and the profiler
        annotation's arguments.

        ``keep=False`` opens a span that reaches the profiler and writes
        no ring event unless the block sets ``sp.keep``: for a span that
        can close many times inside one parent (a page claim per slot
        per link), whose time then stays in that parent's event."""
        sp = self.begin(name, keep=keep, **args)
        try:
            yield sp
        finally:
            self.end(sp)

    def begin(
        self, name: str, *, keep: bool = True, at: float | None = None,
        **args,
    ) -> OpenSpan:
        """Open a span; :meth:`end` closes it (:meth:`span` is the pair
        as a context manager). ``at`` is the caller's own reading of
        the ``perf_counter`` clock for the edge — the goodput ledger
        passes its frame's, so the event IS the frame."""
        if not self.enabled:
            return _NO_SPAN
        sp = OpenSpan(name, args, keep)
        stack = self._stack()
        sp.start = (
            self._now_us() if at is None else (at - self._t0) * 1e6
        )
        if stack:
            sp.parent = stack[-1].name
            sp.annotation = jax.profiler.TraceAnnotation(name, **args)
        else:
            # A root span anchors the profiler's clock to the tracer's.
            sp.annotation = jax.profiler.TraceAnnotation(
                name, ts_us=sp.start, **args
            )
        stack.append(sp)
        sp.annotation.__enter__()
        return sp

    def end(self, sp: OpenSpan, at: float | None = None) -> None:
        if sp is _NO_SPAN:
            return
        sp.annotation.__exit__(None, None, None)
        self._stack().pop()
        if not sp.keep:
            return
        end = self._now_us() if at is None else (at - self._t0) * 1e6
        ev = self._base(sp.name, "X", dur=end - sp.start)
        ev["ts"] = sp.start
        args = sp.args
        if sp.parent is not None:
            args = dict(args, parent=sp.parent)
        if args:
            ev["args"] = args
        self._emit(ev)

    def complete(
        self, name: str, start_perf: float, duration_s: float, **args
    ) -> None:
        """Record a complete event retrospectively from host timestamps
        (``time.perf_counter()`` start + seconds) — for call sites that
        only know after the fact whether a dispatch actually ran."""
        if not self.enabled:
            return
        ev = self._base(name, "X", dur=duration_s * 1e6)
        ev["ts"] = (start_perf - self._t0) * 1e6
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        ev = self._base(name, "i", s="t")
        if args:
            ev["args"] = args
        self._emit(ev)

    def async_begin(self, name: str, id: int, **args) -> None:
        """Open an async interval (e.g. one request's admit→finish
        lifetime) — Perfetto renders ``b``/``e`` pairs keyed by
        (category, id) as horizontal tracks independent of call nesting."""
        if not self.enabled:
            return
        ev = self._base(name, "b", id=int(id), cat=name)
        if args:
            ev["args"] = args
        self._emit(ev)

    def async_end(self, name: str, id: int, **args) -> None:
        if not self.enabled:
            return
        ev = self._base(name, "e", id=int(id), cat=name)
        if args:
            ev["args"] = args
        self._emit(ev)

    def sync(self, out: Any, name: str = "device_sync") -> None:
        """Honest sync point: wait for ``out``, then mark the instant the
        device was known done (see module docstring)."""
        if not self.enabled:
            device_sync(out)
            return
        t0 = time.perf_counter()
        device_sync(out)
        self.complete(name, t0, time.perf_counter() - t0)

    # --- export ------------------------------------------------------------

    @property
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def metadata_events(self, *, pid: int | None = None) -> list[dict]:
        """Chrome ``M``-phase name rows for this tracer's process and
        threads — deterministic content, so exported traces diff cleanly
        run-to-run (the real OS pid rides in args, not in the ids)."""
        pid = self._pid if pid is None else pid
        rows = [{
            "name": "process_name", "ph": "M", "ts": 0.0, "pid": pid,
            "tid": 0,
            "args": {"name": self.name, "os_pid": self._os_pid},
        }]
        rows.extend(
            {
                "name": "thread_name", "ph": "M", "ts": 0.0, "pid": pid,
                "tid": t,
                "args": {"name": f"thread {t}"},
            }
            for t in self.thread_ids()
        )
        return rows

    def chrome_trace(self) -> dict:
        """Perfetto/chrome://tracing-loadable trace object, metadata
        (process/thread names) first."""
        return {
            "traceEvents": self.metadata_events() + self.events,
            "displayTimeUnit": "ms",
            "otherData": {
                "dropped_events": self.dropped,
                "epoch_unix_ns": self._epoch_unix_ns,
            },
        }

    def dump_chrome_trace(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def dump_jsonl(self, path) -> None:
        with open(path, "w") as f:
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")


_DEFAULT = Tracer()


def default_tracer() -> Tracer:
    """The process-wide tracer — subsystems not handed one trace here."""
    return _DEFAULT
