"""memflow — static per-device peak-HBM analysis over traced jaxprs.

Shardflow (PR 15) made *communication* a statically checkable quantity;
memflow does the same for the other axis that decides whether a layout is
runnable at all: per-device peak live bytes. It walks the SAME traced
program shardflow interprets — one :class:`~.shardflow.Spec` per var,
recorded by running shardflow's interpreter with a recording ``write`` —
then runs a classic liveness pass over the equations:

* **sharding-aware** — every buffer is its logical ``_aval_bytes`` divided
  (ceil) by ``Spec.shard_factor``, i.e. by the product of mesh-axis sizes
  it is actually placed on, so a ZeRO-1 sharded Adam moment costs 1/8th of
  its replicated twin on a 2x4 mesh.
* **donation-aware** — donated inputs are freed at their last use *before*
  the consuming equation's outputs are charged, modelling XLA's
  input/output buffer aliasing (the ``input_output_alias`` table
  ``analysis/donation.py`` parses). Which inputs count as donated is the
  caller's to say — :func:`analyze_entry` cross-checks the jit-level
  ``args_info.donated`` flags against donation verdicts so a requested-
  but-not-applied donation is NOT credited as freed memory.
* **scan/remat-aware** — a ``scan``/``while`` body contributes its
  per-iteration high-water above its carried state once, not
  trip-count times (memory, unlike FLOPs, does not accumulate across
  iterations); a ``remat2`` body's intermediates die inside the body, so
  rematerialization's activation savings fall out of the liveness model
  with no special casing.

The predicted peak is reconciled against ``compiled.memory_analysis()``
(the numbers ``telemetry/compile_watch.py`` already snapshots) by
:func:`reconcile_memory`: measured peak = arguments + outputs + temps −
aliased, every other XLA byte class (generated code, host offload) is
attributed by name, and anything the model cannot name lands in an
``unexplained`` dict that the memflow pass gates on — the same
"explain every byte or fail" contract shardflow applies to collectives.
Per-entry-point tolerances live in ``analysis/baseline.json`` under
``memflow_tolerance_pct``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import numpy as np

from learning_jax_sharding_tpu.analysis.findings import Finding
from learning_jax_sharding_tpu.analysis.shardflow import (
    Spec,
    _Interp,
    _aval_bytes,
    _source_line,
    _sub_jaxprs,
    spec_of_sharding,
)

__all__ = [
    "MemflowReport",
    "buffer_bytes",
    "simulate_memflow",
    "trace_memflow",
    "memory_stats_dict",
    "reconcile_memory",
    "analyze_entry",
    "memory_findings",
]

#: How many of the largest live buffers to keep in the peak snapshot.
_TOP_K = 8

#: Primitives whose output XLA fuses into the consumer instead of
#: materializing: a broadcast or iota alone never owns HBM (a consumer
#: that does need the expanded buffer — e.g. a scatter destination —
#: charges its own output, so the bytes are still counted exactly once).
_VIRTUAL = frozenset({"broadcast_in_dim", "iota"})

#: XLA ``CompiledMemoryStats`` fields the reconciliation model names.
#: Device peak working set = arguments + outputs + temps − aliased;
#: the rest are attributed (reported by name, excluded from the peak)
#: rather than silently dropped.
_MEASURED_FIELDS = ("argument", "output", "temp")
_ALIAS_FIELD = "alias"
_ATTRIBUTED_FIELDS = (
    "generated_code",
    "host_argument",
    "host_output",
    "host_temp",
    "host_alias",
    "host_generated_code",
)


def buffer_bytes(v, spec: Spec | None = None,
                 mesh_sizes: dict[str, int] | None = None) -> int:
    """Per-device bytes of one buffer: logical ``_aval_bytes`` divided
    (ceil — a padded shard still occupies whole elements) by the spec's
    shard factor. With no spec this IS ``_aval_bytes``, which is what the
    sizing property test pins."""
    nb = _aval_bytes(v)
    if spec is None or not mesh_sizes:
        return nb
    factor = max(1, spec.shard_factor(mesh_sizes))
    return int(-(-nb // factor))


class _SpecRecorder(_Interp):
    """Shardflow's interpreter with a recording ``write``: after one
    ``run`` the final Spec of every var in the whole jaxpr nest (scan
    bodies included — the counted body pass goes through ``self.run``)
    is in ``var_specs``, so memflow sizes buffers with the exact same
    placement algebra shardflow prices collectives with."""

    def __init__(self, mesh, *, while_trip_hint: int | None = None):
        super().__init__(mesh, while_trip_hint=while_trip_hint)
        self.var_specs: dict[Any, Spec] = {}

    def run(self, jaxpr, in_specs: list[Spec],
            out_hint: list[Spec] | None = None) -> list[Spec]:
        from jax.core import DropVar
        from jax.extend import core as jax_core

        env: dict[Any, Spec] = {}

        def read(v) -> Spec:
            if isinstance(v, jax_core.Literal):
                return Spec.replicated(np.ndim(v.val))
            return env.get(v, Spec.replicated(
                len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            ))

        def write(v, spec: Spec):
            if not isinstance(v, DropVar):
                env[v] = spec
                self.var_specs[v] = spec

        for v, s in zip(jaxpr.invars, in_specs):
            write(v, s)
        for v in jaxpr.constvars:
            write(v, Spec.replicated(
                len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            ))
            self.hbm_bytes += _aval_bytes(v) * self._trip_mult()

        for eqn in jaxpr.eqns:
            self._eqn(eqn, read, write)

        outs = []
        for i, v in enumerate(jaxpr.outvars):
            spec = read(v)
            hint = out_hint[i] if out_hint and i < len(out_hint) else None
            if spec.partial:
                spec = spec.drop_partial()
            if hint is not None and hint.dims != spec.dims:
                spec = Spec(hint.dims, spec.partial)
            outs.append(spec)
        return outs


@dataclasses.dataclass(frozen=True)
class _WalkResult:
    peak_bytes: int          # high-water inside this jaxpr, inputs included
    peak_where: str          # source line of the equation at the peak
    peak_live: tuple         # top-K (bytes, where, kind, label) at the peak
    invar_bytes: tuple       # per-invar per-device sizes (callers slice this)
    in_bytes: int            # invars + constvars resident at entry


@dataclasses.dataclass
class MemflowReport:
    """Per-device peak-HBM verdict for one traced entry point."""

    name: str
    mesh_axes: tuple
    mesh_shape: tuple
    peak_bytes: int
    peak_where: str
    peak_buffers: tuple      # top-K (bytes, where, kind, label) at the peak
    input_bytes: int         # per-device bytes resident as program arguments
    donated_bytes: int       # per-device argument bytes freed by donation
    output_bytes: int        # per-device bytes of program outputs

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "mesh_axes": list(self.mesh_axes),
            "mesh_shape": list(self.mesh_shape),
            "peak_bytes": int(self.peak_bytes),
            "peak_mib": round(self.peak_bytes / 2**20, 2),
            "peak_where": self.peak_where,
            "peak_buffers": [
                {"bytes": int(b), "where": w, "kind": k, "label": lbl}
                for (b, w, k, lbl) in self.peak_buffers
            ],
            "input_bytes": int(self.input_bytes),
            "donated_bytes": int(self.donated_bytes),
            "output_bytes": int(self.output_bytes),
        }


def _label(v) -> str:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    shape = tuple(getattr(aval, "shape", ()) or ())
    return f"{getattr(dt, 'name', dt)}{list(shape)}"


class _Liveness:
    """The liveness pass proper: one recursive walk over the jaxpr nest,
    sizing every var through the recorded spec env."""

    def __init__(self, mesh_sizes: dict[str, int],
                 var_specs: dict[Any, Spec]):
        self.sizes = mesh_sizes
        self.var_specs = var_specs

    def _size(self, v) -> int:
        return buffer_bytes(v, self.var_specs.get(v), self.sizes)

    def _sub_extra(self, eqn) -> tuple[int, _WalkResult | None]:
        """Bytes a structured op holds ABOVE its operands: the sub-jaxpr
        high-water minus whatever of its inputs alias caller buffers.
        ``scan`` xs arrive as fresh per-iteration slices (a copy), so only
        consts+carry alias; everything else (while/cond/pjit/remat/custom)
        aliases all of its invars. Exclusive branches take the max."""
        subs = _sub_jaxprs(eqn)
        if not subs:
            return 0, None
        prim = eqn.primitive.name
        best, best_res = 0, None
        for key, sub in subs:
            res = self.walk(sub)
            if prim == "scan":
                n_alias = (int(eqn.params.get("num_consts", 0))
                           + int(eqn.params.get("num_carry", 0)))
                aliased = sum(res.invar_bytes[:n_alias])
            else:
                aliased = sum(res.invar_bytes)
            extra = max(0, res.peak_bytes - aliased)
            if extra >= best:
                best, best_res = extra, res
        return best, best_res

    def walk(self, jaxpr, donated: frozenset = frozenset(),
             arg_names: Sequence[str] | None = None) -> _WalkResult:
        from jax.core import DropVar
        from jax.extend import core as jax_core

        eqns = jaxpr.eqns
        n = len(eqns)

        # Last use per var: outvars live to the end; a defined-but-unused
        # var dies at its defining equation.
        last: dict[Any, int] = {}
        for v in jaxpr.outvars:
            if isinstance(v, jax_core.Var):
                last[v] = n
        for i in range(n - 1, -1, -1):
            for v in eqns[i].invars:
                if isinstance(v, jax_core.Var):
                    last.setdefault(v, i)
            for v in eqns[i].outvars:
                if isinstance(v, jax_core.Var) and not isinstance(
                        v, DropVar):
                    last.setdefault(v, i)

        live: dict[Any, int] = {}
        meta: dict[Any, tuple] = {}   # var -> (where, kind)
        total = 0

        def add(v, where: str, kind: str, nbytes: int | None = None):
            nonlocal total
            b = self._size(v) if nbytes is None else nbytes
            live[v] = b
            meta[v] = (where, kind)
            total += b

        def drop(v):
            nonlocal total
            total -= live.pop(v, 0)

        invar_bytes = []
        for i, v in enumerate(jaxpr.invars):
            name = (arg_names[i] if arg_names and i < len(arg_names)
                    else f"arg[{i}]")
            kind = "donated-input" if i in donated else "input"
            add(v, f"<{name}>", kind)
            invar_bytes.append(live[v])
        for v in jaxpr.constvars:
            add(v, "<const>", "const")
        in_bytes = total

        def snapshot():
            top = sorted(live.items(), key=lambda kv: -kv[1])[:_TOP_K]
            return tuple(
                (b, meta[v][0], meta[v][1], _label(v)) for v, b in top
            )

        peak, peak_where, peak_live = total, "<inputs>", snapshot()
        free_at: dict[int, list] = {}
        for v, i in last.items():
            if i < n:
                free_at.setdefault(i, []).append(v)
        outset = {v for v in jaxpr.outvars if isinstance(v, jax_core.Var)}

        for i, eqn in enumerate(eqns):
            where = _source_line(eqn)
            extra, inner = self._sub_extra(eqn)

            # Donated operands at their last use free BEFORE outputs are
            # charged: the aliased output reuses the buffer in place.
            for v in free_at.get(i, ()):
                if v in live and meta[v][1] == "donated-input":
                    drop(v)

            # XLA's buffer assignment reuses a dying operand's allocation
            # for a same-sized result (fusion never even materializes the
            # middle of an elementwise chain). Model it: each output of a
            # non-structured op may claim ONE dying operand of identical
            # per-device size; caller-owned inputs are never reusable.
            reusable = []
            if inner is None:
                reusable = [
                    v for v in free_at.get(i, ())
                    if v in live and meta[v][1] == "intermediate"
                ]
            virtual = (eqn.primitive.name in _VIRTUAL and inner is None)
            for v in eqn.outvars:
                if isinstance(v, DropVar):
                    continue
                if virtual and v not in outset:
                    add(v, where, "intermediate", nbytes=0)
                    continue
                b = self._size(v)
                for j, u in enumerate(reusable):
                    if live.get(u) == b:
                        drop(u)
                        reusable.pop(j)
                        break
                add(v, where, "output" if v in outset else "intermediate")

            cand = total + extra
            if cand > peak:
                peak = cand
                if inner is not None and extra > 0:
                    peak_where = inner.peak_where
                    body = tuple(e for e in inner.peak_live
                                 if e[2] in ("intermediate", "output"))
                    peak_live = tuple(sorted(
                        snapshot() + body, key=lambda e: -e[0]))[:_TOP_K]
                else:
                    peak_where = where
                    peak_live = snapshot()

            # Operands and outputs coexist during the op; everything else
            # whose last use was this equation dies after it.
            for v in free_at.get(i, ()):
                if v in live and meta[v][1] != "input":
                    drop(v)

        return _WalkResult(
            peak_bytes=int(peak), peak_where=peak_where,
            peak_live=peak_live, invar_bytes=tuple(invar_bytes),
            in_bytes=int(in_bytes),
        )


def simulate_memflow(name: str, closed, in_specs: Sequence[Spec], mesh, *,
                     donated: Sequence[int] = (),
                     while_trip_hint: int | None = None,
                     out_hint: Sequence[Spec] | None = None,
                     arg_names: Sequence[str] | None = None,
                     ) -> MemflowReport:
    """Peak-HBM analysis of an already-traced closed jaxpr.

    ``in_specs`` follow the flattened invar order (padded with replicated
    like :func:`~.shardflow.simulate_jaxpr`); ``donated`` are flat invar
    indices whose buffers XLA will alias to outputs."""
    jaxpr = closed.jaxpr
    specs = list(in_specs) + [
        Spec.replicated(len(getattr(getattr(v, "aval", None), "shape", ())
                            or ()))
        for v in jaxpr.invars[len(in_specs):]
    ]
    rec = _SpecRecorder(mesh, while_trip_hint=while_trip_hint)
    rec.run(jaxpr, specs, list(out_hint) if out_hint else None)

    sizes = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
    lv = _Liveness(sizes, rec.var_specs)
    res = lv.walk(jaxpr, donated=frozenset(int(i) for i in donated),
                  arg_names=arg_names)

    donated_bytes = sum(
        res.invar_bytes[i] for i in donated if i < len(res.invar_bytes))
    output_bytes = sum(
        buffer_bytes(v, rec.var_specs.get(v), sizes)
        for v in jaxpr.outvars
    )
    return MemflowReport(
        name=name,
        mesh_axes=tuple(str(a) for a in mesh.axis_names),
        mesh_shape=tuple(int(mesh.shape[a]) for a in mesh.axis_names),
        peak_bytes=res.peak_bytes,
        peak_where=res.peak_where,
        peak_buffers=res.peak_live,
        input_bytes=res.in_bytes,
        donated_bytes=int(donated_bytes),
        output_bytes=int(output_bytes),
    )


def trace_memflow(name: str, fn: Callable, *args, mesh,
                  donated: Sequence[int] = (),
                  while_trip_hint: int | None = None,
                  arg_names: Sequence[str] | None = None,
                  **kwargs) -> MemflowReport:
    """Trace ``fn`` abstractly (same contract as ``trace_shardflow``:
    flattened-leaf order == invar order) and analyze its peak."""
    import jax

    inner = getattr(fn, "__wrapped__", fn)
    closed = jax.make_jaxpr(inner)(*args, **kwargs)
    flat, _ = jax.tree_util.tree_flatten((args, kwargs))
    in_specs = []
    for leaf in flat:
        sh = getattr(leaf, "sharding", None)
        nd = int(np.ndim(leaf)) if not hasattr(leaf, "ndim") else int(
            leaf.ndim)
        in_specs.append(spec_of_sharding(sh, nd) if sh is not None
                        else Spec.replicated(nd))
    if arg_names is None:
        paths, _ = jax.tree_util.tree_flatten_with_path((args, kwargs))
        arg_names = [jax.tree_util.keystr(p) for p, _leaf in paths]
    return simulate_memflow(
        name, closed, in_specs, mesh, donated=donated,
        while_trip_hint=while_trip_hint, arg_names=arg_names,
    )


def memory_stats_dict(compiled) -> dict[str, int] | None:
    """``compiled.memory_analysis()`` as a plain ``{field: bytes}`` dict
    (field names with ``_size_in_bytes`` stripped), or ``None`` on
    backends without memory stats — same guard as
    ``telemetry/compile_watch.py``."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out: dict[str, int] = {}
    for attr in dir(ma):
        if attr.endswith("_size_in_bytes"):
            try:
                out[attr[: -len("_size_in_bytes")]] = int(getattr(ma, attr))
            except Exception:
                continue
    return out or None


def reconcile_memory(report: MemflowReport,
                     memory: dict[str, int] | None) -> dict:
    """Square memflow's predicted peak against XLA's allocator view.

    measured peak = arguments + outputs + temps − aliased (donated
    buffers are reused, not double-counted). Every other byte class XLA
    reports is *attributed* by name; a field this model has never heard
    of lands in ``unexplained`` and the memflow pass gates on it."""
    if not memory:
        return {
            "name": report.name,
            "predicted_bytes": int(report.peak_bytes),
            "measured_bytes": None,
            "err_pct": None,
            "signed_err_pct": None,
            "classes": {},
            "attributed": {},
            "unexplained": {},
        }
    measured = sum(memory.get(f, 0) for f in _MEASURED_FIELDS)
    measured -= memory.get(_ALIAS_FIELD, 0)
    attributed = {
        f: memory[f] for f in _ATTRIBUTED_FIELDS
        if memory.get(f, 0)
    }
    known = set(_MEASURED_FIELDS) | {_ALIAS_FIELD} | set(_ATTRIBUTED_FIELDS)
    unexplained = {
        k: v for k, v in memory.items() if k not in known and v
    }
    signed = 100.0 * (report.peak_bytes - measured) / max(1, measured)
    return {
        "name": report.name,
        "predicted_bytes": int(report.peak_bytes),
        "measured_bytes": int(measured),
        "err_pct": abs(signed),
        "signed_err_pct": signed,
        "classes": {f: int(memory.get(f, 0))
                    for f in _MEASURED_FIELDS + (_ALIAS_FIELD,)},
        "attributed": {k: int(v) for k, v in attributed.items()},
        "unexplained": {k: int(v) for k, v in unexplained.items()},
    }


def analyze_entry(entry: str, mesh=None) -> dict:
    """End-to-end memflow verdict for one searchable entry point:
    trace → liveness peak, AOT-compile → ``memory_analysis()`` →
    reconcile, with donation flags cross-checked against
    ``analysis/donation.py`` verdicts (a requested-but-not-applied
    donation is not credited as freed)."""
    import jax

    from learning_jax_sharding_tpu.analysis import donation as donation_mod
    from learning_jax_sharding_tpu.analysis.entrypoints import (
        build_search_inputs,
    )
    from learning_jax_sharding_tpu.parallel.logical import activate

    t = build_search_inputs(entry, mesh)
    fn, args, kwargs = t["fn"], t["args"], t["kwargs"]
    with activate(t["mesh"], t["rules"]):
        jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
        lowered = jfn.lower(*args, **kwargs)
        compiled = lowered.compile()

        requested = [
            i for i, info in enumerate(jax.tree.leaves(lowered.args_info))
            if getattr(info, "donated", False)
        ]
        # Cross-check against donation.py: only donations the executable
        # actually aliased ("donated" verdict) are credited as freed —
        # a requested-but-dropped donation keeps both generations live.
        try:
            dreport = donation_mod.report_from_lowered(
                lowered, compiled.as_text(), compiled=compiled)
            applied = {r["param"] for r in dreport["inputs"]
                       if r["verdict"] == "donated"}
            donated = [i for i in requested if i in applied]
        except Exception:
            donated = list(requested)

        report = trace_memflow(
            entry, fn, *args, mesh=t["mesh"], donated=donated,
            while_trip_hint=t["while_trip_hint"], **kwargs,
        )
        memory = memory_stats_dict(compiled)
    return {
        "report": report,
        "reconciled": reconcile_memory(report, memory),
        "donated": donated,
        "donation_requested": requested,
    }


def memory_findings(analysis: dict, *,
                    budget_bytes: float | None,
                    headroom: float,
                    tolerance_pct: float | None) -> list[Finding]:
    """Turn one :func:`analyze_entry` result into gated findings:
    over-budget peaks (at the peak-owning buffer's source line),
    reconciliation drift beyond the baseline-pinned tolerance, and any
    XLA byte class the model could not name."""
    report: MemflowReport = analysis["report"]
    rec = analysis["reconciled"]
    out: list[Finding] = []

    if budget_bytes is not None:
        cap = float(budget_bytes) * float(headroom)
        if report.peak_bytes > cap:
            owner = report.peak_buffers[0] if report.peak_buffers else None
            where = (owner[1] if owner and not owner[1].startswith("<")
                     else report.peak_where)
            owner_s = (f"; largest live buffer {owner[3]} "
                       f"({owner[0] / 2**20:.1f} MiB, {owner[2]}, "
                       f"{owner[1]})" if owner else "")
            out.append(Finding(
                "memflow", "memflow-over-budget", where,
                f"{report.name}: predicted per-device peak "
                f"{report.peak_bytes / 2**20:.1f} MiB exceeds "
                f"{cap / 2**20:.1f} MiB "
                f"({budget_bytes / 2**30:.1f} GiB x {headroom:.2f} "
                f"headroom){owner_s}",
                data={"peak_bytes": int(report.peak_bytes),
                      "budget_bytes": int(budget_bytes),
                      "headroom": float(headroom)},
            ))

    if rec.get("err_pct") is not None and tolerance_pct is not None:
        if rec["err_pct"] > tolerance_pct:
            out.append(Finding(
                "memflow", "memflow-reconcile", report.name,
                f"predicted peak {rec['predicted_bytes'] / 2**20:.1f} MiB "
                f"vs XLA {rec['measured_bytes'] / 2**20:.1f} MiB: "
                f"{rec['signed_err_pct']:+.1f}% drift exceeds the "
                f"{tolerance_pct:.1f}% tolerance pinned in baseline.json",
                data={"err_pct": rec["err_pct"],
                      "tolerance_pct": tolerance_pct},
            ))
    for cls, nbytes in rec.get("unexplained", {}).items():
        out.append(Finding(
            "memflow", "memflow-unexplained-class",
            f"{report.name}:{cls}",
            f"XLA reports {nbytes / 2**20:.2f} MiB under '{cls}', a byte "
            f"class the reconciliation model does not name",
            data={"class": cls, "bytes": int(nbytes)},
        ))
    return out
