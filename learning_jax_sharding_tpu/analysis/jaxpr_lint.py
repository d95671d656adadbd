"""Jaxpr-level lint: precision drift and dead compute, before XLA sees it.

The HLO contract pass (``analysis.contracts``) checks what the COMPILER
chose; this pass checks what the TRACE asked for — the level where a
silent ``bf16 → f32`` promotion (one forgotten ``.astype``, one numpy
scalar) or a computed-then-discarded output is still attributable to a
primitive, not smeared across fusions. Both failure classes are invisible
at runtime: the f32 matmul just runs at half throughput and double bytes,
the dead eqn just burns FLOPs XLA may or may not DCE.

Rules (stable ids for the baseline file / registry):

* ``f32-promotion``     — a ``convert_element_type`` widening bf16/f16 to
  f32 in a graph whose inputs are majority low-precision. Deliberate fp32
  islands (loss accumulation, norms over the reduce) typically convert
  REDUCED tensors; the finding reports the operand shape so a reviewer
  can tell a scalar-accumulator upcast from a whole-activation one.
* ``f32-dot-in-bf16-graph`` — a ``dot_general`` computing entirely in f32
  inside a majority-bf16 graph: the promotion already happened upstream
  and this is where it gets expensive (half MXU throughput).
* ``dead-eqn``          — an equation none of whose outputs reach the
  jaxpr's outputs (transitively): traced compute with no consumer.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
from jax.core import DropVar
from jax.extend import core as jax_core

from learning_jax_sharding_tpu.analysis.findings import Finding

_LOW = ("bfloat16", "float16")


def _sub_jaxprs(eqn) -> list:
    """Closed/open sub-jaxprs carried in an eqn's params (scan/while/cond
    bodies, pjit/custom-vjp calls) — wherever they hide, lint descends."""
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for item in vs:
            if isinstance(item, jax_core.ClosedJaxpr):
                out.append(item.jaxpr)
            elif isinstance(item, jax_core.Jaxpr):
                out.append(item)
    return out


def _walk(jaxpr, path: str = ""):
    """Yield ``(eqn, path)`` over ``jaxpr`` and every sub-jaxpr."""
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}[{i}]{eqn.primitive.name}"
        yield eqn, here
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub, path=f"{here}/")


def _dtype_of(v) -> str | None:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return str(dt) if dt is not None else None


def _shape_of(v) -> tuple:
    return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())


def _low_precision_share(jaxpr) -> float:
    """Fraction of floating input ELEMENTS that are bf16/f16 — the graph's
    dominant precision, weighted so one f32 scalar step-counter cannot
    flip a bf16 model's census."""
    low = hi = 0.0
    for v in (*jaxpr.invars, *jaxpr.constvars):
        dt = _dtype_of(v)
        if dt is None or not dt.startswith(("bfloat", "float")):
            continue
        n = float(math.prod(_shape_of(v)) or 1)
        if dt in _LOW:
            low += n
        else:
            hi += n
    total = low + hi
    return low / total if total else 0.0


def lint_jaxpr(fn_or_jaxpr: Any, *args, **kwargs) -> list[Finding]:
    """Lint a jaxpr (or trace ``fn(*args)`` to one) for precision drift
    and dead equations. Accepts a ``ClosedJaxpr``, a ``Jaxpr``, or a
    callable plus example args (traced via ``jax.make_jaxpr`` — jit
    wrappers are fine, tracing unwraps them)."""
    if isinstance(fn_or_jaxpr, jax_core.ClosedJaxpr):
        jaxpr = fn_or_jaxpr.jaxpr
    elif isinstance(fn_or_jaxpr, jax_core.Jaxpr):
        jaxpr = fn_or_jaxpr
    else:
        # A jitted wrapper traces to one opaque pjit eqn; unwrap so the
        # lint sees the body's primitives directly.
        fn = getattr(fn_or_jaxpr, "__wrapped__", fn_or_jaxpr)
        jaxpr = jax.make_jaxpr(fn)(*args, **kwargs).jaxpr
    out: list[Finding] = []
    low_share = _low_precision_share(jaxpr)
    bf16_graph = low_share >= 0.5

    for eqn, path in _walk(jaxpr):
        prim = eqn.primitive.name
        if bf16_graph and prim == "convert_element_type":
            src = _dtype_of(eqn.invars[0])
            dst = str(eqn.params.get("new_dtype"))
            if src in _LOW and dst == "float32":
                shape = _shape_of(eqn.invars[0])
                out.append(Finding(
                    "jaxpr", "f32-promotion", path,
                    f"{src}{list(shape)} widened to float32 in a "
                    f"{low_share:.0%} low-precision graph — doubles the "
                    "buffer and poisons downstream compute to f32",
                    data={"src": src, "shape": list(shape)},
                ))
        if bf16_graph and prim == "dot_general":
            dts = {_dtype_of(v) for v in eqn.invars}
            if dts == {"float32"}:
                shapes = [list(_shape_of(v)) for v in eqn.invars]
                out.append(Finding(
                    "jaxpr", "f32-dot-in-bf16-graph", path,
                    f"dot_general runs fully in float32 ({shapes}) inside "
                    f"a {low_share:.0%} low-precision graph — half MXU "
                    "throughput where the promotion lands",
                    data={"shapes": shapes},
                ))

    out.extend(_dead_eqns(jaxpr))
    return out


def _dead_eqns(jaxpr, path: str = "") -> list[Finding]:
    """Equations whose outputs never (transitively) reach the jaxpr's
    outvars — per nesting level, because a sub-jaxpr's variables are its
    own namespace. Effectful eqns (debug prints, io callbacks) are kept
    alive by definition."""
    out: list[Finding] = []
    live: set = set()
    for v in jaxpr.outvars:
        if isinstance(v, jax_core.Var):
            live.add(v)
    # Backward sweep: an eqn is live if any outvar is live; its invars
    # become live. One reverse pass suffices — eqns are topologically
    # ordered, so every consumer appears after its producer.
    for i in reversed(range(len(jaxpr.eqns))):
        eqn = jaxpr.eqns[i]
        is_live = bool(getattr(eqn, "effects", None)) or any(
            (not isinstance(v, DropVar)) and v in live
            for v in eqn.outvars
        )
        if is_live:
            for v in eqn.invars:
                if isinstance(v, jax_core.Var):
                    live.add(v)
        else:
            out.append(Finding(
                "jaxpr", "dead-eqn",
                f"{path}[{i}]{eqn.primitive.name}",
                f"`{eqn.primitive.name}` output never reaches the jaxpr's "
                "outputs — computed then discarded (XLA may DCE it, but "
                "the trace asked for wasted work)",
            ))
    for i, eqn in enumerate(jaxpr.eqns):
        for sub in _sub_jaxprs(eqn):
            out.extend(
                _dead_eqns(sub, path=f"{path}[{i}]{eqn.primitive.name}/")
            )
    return out


def lint_fn(fn: Callable, *args, **kwargs) -> list[Finding]:
    """Convenience alias: trace and lint in one call."""
    return lint_jaxpr(fn, *args, **kwargs)
