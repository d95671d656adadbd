"""Compile accounting: recompiles, compile seconds, per-executable cost.

The silent performance killer in a jit-driven stack is the compile you
did not know happened — a shape drift that recompiles the decode step
mid-serve, a config toggle that doubles trace time. This module makes
compilation first-class telemetry, three ways:

* :class:`CompileWatch` — process-wide listener on JAX's monitoring
  events (``/jax/core/compile/*``): counts jaxpr traces, MLIR lowerings,
  and backend compiles, with seconds for each, optionally mirrored into
  a :class:`~..telemetry.registry.MetricsRegistry`.
* :func:`watched` — per-function accounting: wraps a jitted callable and
  detects recompiles per CALL via the executable cache size
  (``PjitFunction._cache_size``), so "which function recompiled, and on
  which call" has an answer.
* :func:`executable_report` — per-executable ground truth from the
  compiled artifact itself: XLA ``cost_analysis()`` FLOPs/bytes,
  ``memory_analysis()`` buffer sizes, and the collective-op inventory
  via :func:`~..parallel.hlo.collective_counts` — what EQuARX
  (arXiv 2506.17615) and the model-parallel communication literature
  (arXiv 2211.05322) say dominates scaled cost, now machine-readable
  per step.

The listeners are the public ``jax.monitoring`` ones. With the
persistent compilation cache on, ``backend_compile`` still fires for a
program found in the cache (its seconds are then the retrieval), and the
cache's own hit/miss events are counted beside it.
"""

from __future__ import annotations

import threading
from typing import Any, Callable

import jax
from jax import monitoring

from learning_jax_sharding_tpu.parallel.hlo import (
    collective_counts,
    collective_instructions,
)

#: Duration events; other ``/jax/core/compile`` keys are kept under "other".
EVENT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}

#: Plain events of the persistent compilation cache. JAX records a "miss"
#: when it WRITES a freshly compiled program to the cache; a program that
#: compiles faster than the cache's threshold is neither hit nor miss.
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}


class CompileWatch:
    """Count and time every compilation the process performs while the
    watch is active.

    Use as a context manager (or ``start()``/``stop()``). Numbers
    accumulate across nested activations of the same object; a registry
    passed at construction receives the same accounting as counters
    (``compile_events_total``/``compile_seconds_total`` per kind).
    """

    def __init__(
        self, registry: Any | None = None, *, recorder: Any | None = None
    ):
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}
        self._seconds: dict[str, float] = {}
        self._active = 0
        self._registry = registry
        self._recorder = recorder

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        kind = EVENT_KINDS.get(name)
        if kind is None:
            if not name.startswith("/jax/core/compile"):
                return
            kind = "other"
        with self._lock:
            self._counts[kind] = self._counts.get(kind, 0) + 1
            self._seconds[kind] = self._seconds.get(kind, 0.0) + secs
        if self._recorder is not None:
            self._recorder.record("compile", compile_kind=kind, seconds=secs)
        if self._registry is not None:
            self._registry.counter(
                f"compile_{kind}_total",
                "compile events observed by CompileWatch",
            ).inc()
            self._registry.counter(
                f"compile_{kind}_seconds_total",
                "seconds spent in compile events",
            ).inc(secs)

    def _on_event(self, name: str, **kw) -> None:
        kind = CACHE_EVENTS.get(name)
        if kind is not None:
            with self._lock:
                self._counts[kind] = self._counts.get(kind, 0) + 1

    def start(self) -> "CompileWatch":
        self._active += 1
        if self._active == 1:
            monitoring.register_event_duration_secs_listener(
                self._on_duration
            )
            monitoring.register_event_listener(self._on_event)
        return self

    def stop(self) -> None:
        if self._active == 0:
            return
        self._active -= 1
        if self._active == 0:
            monitoring.unregister_event_duration_listener(
                self._on_duration
            )
            monitoring.unregister_event_listener(self._on_event)

    def __enter__(self) -> "CompileWatch":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    @property
    def backend_compiles(self) -> int:
        return self._counts.get("backend_compile", 0)

    @property
    def backend_compile_seconds(self) -> float:
        return self._seconds.get("backend_compile", 0.0)

    def report(self) -> dict:
        """``{kind: n, kind_seconds: s, ...}`` for trace / lower /
        backend_compile, plus the persistent cache's hits and misses.
        ``monitoring_available`` is always True: the listeners are public
        API, and a JAX without them fails at import, not with zeros."""
        out: dict = {"monitoring_available": True}
        for kind in ("trace", "lower", "backend_compile", "other"):
            out[f"{kind}s"] = self._counts.get(kind, 0)
            out[f"{kind}_seconds"] = self._seconds.get(kind, 0.0)
        out["cache_hits"] = self._counts.get("cache_hit", 0)
        out["cache_misses"] = self._counts.get("cache_miss", 0)
        return out


def cache_size(jitted: Callable) -> int | None:
    """Number of compiled executables a jitted function currently holds —
    i.e. its lifetime compile count (one per distinct shape/dtype/static
    combination). None when the runtime doesn't expose it."""
    probe = getattr(jitted, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


class WatchedFunction:
    """A jitted callable with per-call compile detection.

    ``calls`` counts invocations; ``compiles`` counts calls whose
    dispatch grew the executable cache (a fresh trace+compile);
    ``compile_calls`` lists which call indices compiled — the answer to
    "did serving hit a recompile mid-flight, and when".
    """

    def __init__(self, fn: Callable, name: str | None = None):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", repr(fn))
        self.calls = 0
        self.compiles = 0
        self.compile_calls: list[int] = []

    def __call__(self, *args, **kwargs):
        before = cache_size(self.fn)
        out = self.fn(*args, **kwargs)
        self.calls += 1
        after = cache_size(self.fn)
        if before is not None and after is not None and after > before:
            self.compiles += after - before
            self.compile_calls.append(self.calls)
        return out

    def stats(self) -> dict:
        return {
            "name": self.name,
            "calls": self.calls,
            "compiles": self.compiles,
            "compile_calls": list(self.compile_calls),
            "cache_size": cache_size(self.fn),
        }


def watched(fn: Callable, name: str | None = None) -> WatchedFunction:
    """Wrap a jitted function for per-call compile detection."""
    return WatchedFunction(fn, name)


def _cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(ca) if ca else {}


def executable_report(fn: Callable, *args, **kwargs) -> dict:
    """Ground-truth accounting for ONE executable: lower+compile ``fn``
    on ``args`` (AOT — costs a compile; a diagnostic, not a hot-path
    call) and report

    * ``flops`` / ``bytes_accessed`` from XLA cost analysis (None when
      the backend doesn't report them);
    * ``memory``: argument/output/temp/code bytes from
      ``memory_analysis()``;
    * ``collectives``: per-op-kind instruction counts from the optimized
      HLO (``parallel.hlo.collective_counts`` — async pairs count once);
    * ``collective_instructions``: per-instruction records (op, bytes,
      replica groups) — feed ``telemetry.devview.axis_collective_volume``
      with the program's mesh to attribute bytes per mesh axis.

    ``args`` should carry their real shardings so the partitioner makes
    the same collective choices the runtime would.
    """
    jitted = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    ca = _cost_analysis_dict(compiled)
    flops = ca.get("flops")
    bytes_accessed = ca.get("bytes accessed")
    memory: dict = {}
    try:
        ms = compiled.memory_analysis()
        memory = {
            "argument_bytes": int(ms.argument_size_in_bytes),
            "output_bytes": int(ms.output_size_in_bytes),
            "temp_bytes": int(ms.temp_size_in_bytes),
            "generated_code_bytes": int(ms.generated_code_size_in_bytes),
            "alias_bytes": int(ms.alias_size_in_bytes),
        }
    except Exception:  # backends without memory stats
        memory = {}
    text = compiled.as_text()
    return {
        "flops": float(flops) if flops and flops > 0 else None,
        "bytes_accessed": (
            float(bytes_accessed)
            if bytes_accessed and bytes_accessed > 0 else None
        ),
        "memory": memory,
        "collectives": collective_counts(text),
        "collective_instructions": collective_instructions(text),
    }
