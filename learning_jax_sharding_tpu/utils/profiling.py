"""Tracing / profiling + the XLA-world "sanitizers" (SURVEY.md §5).

The reference's entire observability story is ``visualize_array_sharding``
plus one flawed timing loop (`/root/reference/case6_attention.py:234-238`).
The TPU-native equivalents:

* :func:`trace` — ``jax.profiler`` capture to an XPlane/Perfetto logdir
  (open in XProf/TensorBoard to see per-op device time, HBM traffic, and
  which collectives ride ICI);
* :func:`annotate` — named trace spans so framework phases (init, step,
  eval) are findable in the timeline;
* :func:`checking` — the nearest analogue of a race/memory sanitizer in the
  SPMD/XLA model, where user-level data races don't exist (SURVEY.md §5
  "Race detection"): NaN/Inf trapping (``jax_debug_nans``) and internal
  invariant checks (``jax_enable_checks``), scoped and restored on exit.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator

import jax


@contextlib.contextmanager
def trace(logdir: str | os.PathLike, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a profiler trace of the enclosed block into ``logdir``.

    The capture includes device (TPU) activity, host Python/runtime activity
    at ``host_tracer_level``, and all :func:`annotate` spans.
    """
    os.makedirs(os.fspath(logdir), exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    with jax.profiler.trace(os.fspath(logdir), profiler_options=options):
        yield


def annotate(name: str) -> jax.profiler.TraceAnnotation:
    """Named span visible in the profiler timeline::

        with annotate("train_step"):
            state, loss = step(state, batch)
    """
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def checking(*, nans: bool = True, checks: bool = True) -> Iterator[None]:
    """Scoped debug mode: trap NaN/Inf the moment a primitive produces one
    (``nans``) and enable JAX's internal invariant checks (``checks``).

    Costs recompilation and sync on entry/exit — a debugging tool, not a
    production setting.
    """
    prev_nans = jax.config.jax_debug_nans
    prev_checks = jax.config.jax_enable_checks
    try:
        jax.config.update("jax_debug_nans", nans)
        jax.config.update("jax_enable_checks", checks)
        # Executables compiled before the toggle can be replayed from the
        # dispatch cache WITHOUT the nan checks (observed: a warm cache from
        # unrelated prior compilations let a 0/0 divide through silently), so
        # force recompilation inside — and again outside, where check-laden
        # executables must not leak into production dispatch.
        jax.clear_caches()
        yield
    finally:
        # The block typically exits by RAISING (that is the tool's point:
        # FloatingPointError from a nan trap, or an invariant failure
        # mid-compile), so the restore path must itself be exception-safe:
        # drop the check-laden executables FIRST, then restore each flag
        # under its own finally — a failure in any one step must not
        # leave check-mode caches or flags live in production dispatch.
        try:
            jax.clear_caches()
        finally:
            try:
                jax.config.update("jax_debug_nans", prev_nans)
            finally:
                jax.config.update("jax_enable_checks", prev_checks)
