"""shardflow: GSPMD sharding-propagation simulator over closed jaxprs.

The contract pass (:mod:`.contracts`) diffs the *compiled* HLO against
goldens — it tells you **that** a collective appeared, never **which
equation caused it** or what it costs. This module runs the propagation
algorithm of GSPMD (arXiv 2105.04663) as an abstract interpreter over the
jaxpr — the level where every tensor still has a source line — and emits
the **predicted collective multiset** before XLA ever runs:

* ``dot_general`` contraction rules: contracting dims sharded alike on
  both operands leave the product *partial* on that mesh axis (a pending
  cross-device reduction); mismatched contracting shardings force a
  reshard of one operand (2105.04663 §4.2);
* elementwise merge: operands of equal shape unify to the most-sharded
  compatible spec; a replicated operand shards for free (slice), a
  conflicting sharded one must move (reshard);
* ``reshape``/``transpose``/``broadcast`` spec rewriting through the dim
  mapping, with an all-gather where a sharded dim cannot survive;
* ``scan``/``while``/``pjit``/remat recursion, with a carry fixpoint and
  per-iteration event multiplication (a collective inside a decode loop
  costs trip_count × its bytes — the exact silent cost the contract
  pass's ``while_collectives`` cap bounds);
* explicit ``shard_map`` collectives (``psum``/``all_gather``/
  ``ppermute``/``all_to_all``) pass through verbatim.

Every predicted event carries the **source line** (``eqn.source_info``)
of the equation that caused it, the op it realizes as, the mesh axis, and
shard-local bytes. Because XLA's post-partitioning pipeline legally
rewrites the GSPMD insertion set (all-reduce → reduce-scatter +
all-gather, collective combining/CSE, reshard op selection by cost),
events carry *realization options*, and :func:`reconcile` matches an
actual compiled contract against them: every actual collective must be
claimed by a predicted event (else ``unexplained-collective`` — a gated
finding: the propagation rules drifted from the real partitioner), while
predicted-but-absent events are reported as XLA wins (``elided``), the
same asymmetry the contract diff itself uses.

The same walk accumulates the roofline inputs (:mod:`.costmodel`):
``dot_general`` FLOPs and per-iteration HBM bytes (loop-body operands are
re-streamed every trip — the decode regime, where weights dominate).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable

import numpy as np

from learning_jax_sharding_tpu.analysis.findings import Finding

# ---------------------------------------------------------------------------
# Spec algebra
# ---------------------------------------------------------------------------

#: One dim's sharding: a tuple of mesh-axis names (GSPMD allows several
#: axes on one dim, major-to-minor).
Dim = tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class Spec:
    """Abstract sharding of one value: per-dim mesh axes + *partial* axes.

    ``partial`` is GSPMD's pending-reduction state (2105.04663 §3.2): the
    value exists on every device along those axes as an unreduced
    summand; consuming it (outside another reduction) forces the
    all-reduce the simulator predicts.
    """

    dims: tuple[Dim, ...]
    partial: frozenset[str] = frozenset()
    #: source line of the equation that CREATED the pending reduction —
    #: the line the eventual all-reduce is attributed to (the cause),
    #: with the consuming line in the event's reason.
    origin: str | None = None

    @classmethod
    def replicated(cls, ndim: int) -> "Spec":
        return cls(dims=((),) * ndim)

    def sharded_axes(self) -> set[str]:
        return {a for d in self.dims for a in d}

    def shard_factor(self, mesh_sizes: dict[str, int]) -> int:
        f = 1
        for d in self.dims:
            for a in d:
                f *= mesh_sizes.get(a, 1)
        return f

    def drop_partial(self) -> "Spec":
        return Spec(self.dims)

    def with_dims(self, dims: Iterable[Dim]) -> "Spec":
        return Spec(tuple(tuple(d) for d in dims), self.partial, self.origin)


def spec_of_sharding(sharding: Any, ndim: int) -> Spec:
    """Normalize a ``NamedSharding``/``PartitionSpec``-ish into a Spec."""
    try:
        pspec = getattr(sharding, "spec", sharding)
        dims: list[Dim] = []
        for i in range(ndim):
            entry = pspec[i] if pspec is not None and i < len(pspec) else None
            if entry is None:
                dims.append(())
            elif isinstance(entry, (tuple, list)):
                dims.append(tuple(str(a) for a in entry))
            else:
                dims.append((str(entry),))
        return Spec(tuple(dims))
    except Exception:
        return Spec.replicated(ndim)


# ---------------------------------------------------------------------------
# Predicted events
# ---------------------------------------------------------------------------

#: Realization option: (collective op name, mesh axis label) as the HLO
#: contract records them (``op@axis``).
Realization = tuple[str, str]


@dataclasses.dataclass
class CommEvent:
    """One predicted communication event, attributed to a source line.

    ``kind`` is the semantic cause (``"reduce"`` — a pending partial sum
    materialized; ``"reshard"`` — a spec change on already-sharded data;
    ``"explicit"`` — a shard_map collective the user wrote).
    ``realizations`` are the (op, axis) instruction forms XLA may pick
    for it — ``reconcile`` lets the actual contract consume any one of
    them (plus the reduce-scatter+all-gather split for reduces).
    """

    kind: str
    axes: tuple[str, ...]
    bytes: int
    where: str          # file:line of the causing equation
    primitive: str      # jaxpr primitive at that line
    reason: str         # human sentence: why this event exists
    realizations: tuple[Realization, ...]
    in_loop: bool = False
    trip: int | None = None

    def to_dict(self) -> dict:
        return {
            "kind": self.kind,
            "axes": list(self.axes),
            "bytes": int(self.bytes),
            "where": self.where,
            "primitive": self.primitive,
            "reason": self.reason,
            "realizations": [list(r) for r in self.realizations],
            "in_loop": self.in_loop,
            "trip": self.trip,
        }


@dataclasses.dataclass
class ShardflowReport:
    """Everything the simulator predicts for one entry point."""

    name: str
    mesh_axes: list[str]
    mesh_shape: list[int]
    events: list[CommEvent]
    flops: float
    hbm_bytes: float            # per-device, loop trips multiplied in
    out_specs: list[Spec] = dataclasses.field(default_factory=list)
    flops_thin: float = 0.0     # GEMV-regime share of ``flops``

    def predicted_counts(self) -> dict[str, int]:
        """``op@axis → count`` taking each event's FIRST realization —
        the simulator's best guess at what GSPMD inserts (before XLA's
        combiners), comparable to a :class:`~.contracts.Contract`."""
        out: dict[str, int] = {}
        for ev in self.events:
            if not ev.realizations or ev.kind == "slice":
                continue
            op, ax = ev.realizations[0]
            key = f"{op}@{ax}"
            out[key] = out.get(key, 0) + 1
        return out

    def by_line(self) -> dict[str, list[CommEvent]]:
        out: dict[str, list[CommEvent]] = {}
        for ev in self.events:
            out.setdefault(ev.where, []).append(ev)
        return out

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "mesh_axes": self.mesh_axes,
            "mesh_shape": self.mesh_shape,
            "events": [e.to_dict() for e in self.events],
            "flops": self.flops,
            "flops_thin": self.flops_thin,
            "hbm_bytes": self.hbm_bytes,
            "predicted_counts": self.predicted_counts(),
        }


# ---------------------------------------------------------------------------
# The interpreter
# ---------------------------------------------------------------------------

_ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "pow", "atan2", "rem",
    "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "nextafter", "complex",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp",
    "add_any",
}

_UNARY = {
    "neg", "sign", "floor", "ceil", "round", "exp", "exp2", "expm1",
    "log", "log1p", "tanh", "sin", "cos", "tan", "asin", "acos", "atan",
    "sinh", "cosh", "asinh", "acosh", "atanh", "sqrt", "rsqrt", "cbrt",
    "logistic", "erf", "erfc", "erf_inv", "is_finite", "not",
    "integer_pow", "square", "abs", "real", "imag", "conj",
    "convert_element_type", "copy", "stop_gradient", "reduce_precision",
    "erf_inv", "population_count", "clz", "bitcast_convert_type",
    "name",     # jax.ad_checkpoint.checkpoint_name: an identity with a tag
}

#: Reductions keep the *partial* abstraction regardless of monoid — the
#: realization is an all-reduce either way.
_REDUCES = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin",
}

_EXPLICIT = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pshuffle": "collective-permute",
    "reduce_scatter": "reduce-scatter",
    "psum_scatter": "reduce-scatter",
}


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None:
        return 0
    shape = getattr(aval, "shape", ())
    dt = getattr(aval, "dtype", None)
    try:
        item = np.dtype(dt).itemsize if dt is not None else 4
    except TypeError:
        # Extended dtypes (PRNG keys) — itemsize via the dtype itself.
        item = int(getattr(dt, "itemsize", 4) or 4)
    return int(math.prod(shape) or 1) * item


def _source_line(eqn) -> str:
    # jax-internal, no public equivalent: the first frame of the eqn's
    # traceback that is outside jax itself.
    from jax._src import source_info_util

    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is not None:
        return f"{fr.file_name}:{fr.start_line}"
    return "<unknown>"


def _sub_jaxprs(eqn):
    from jax.extend import core as jax_core

    out = []
    for k, v in eqn.params.items():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for item in vs:
            if isinstance(item, jax_core.ClosedJaxpr):
                out.append((k, item.jaxpr))
            elif isinstance(item, jax_core.Jaxpr):
                out.append((k, item))
    return out


class _Interp:
    """One walk over a closed jaxpr, propagating :class:`Spec` per var."""

    def __init__(self, mesh, *, while_trip_hint: int | None = None):
        self.mesh = mesh
        self.sizes = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
        self.events: list[CommEvent] = []
        self.flops = 0.0
        self.flops_thin = 0.0
        self.hbm_bytes = 0.0
        self.while_trip_hint = while_trip_hint
        self._loop_depth = 0
        self._trip_stack: list[int] = []

    # -- helpers ----------------------------------------------------------

    def _local_bytes(self, v, spec: Spec) -> int:
        return max(1, _aval_bytes(v) // max(1, spec.shard_factor(self.sizes)))

    def _trip_mult(self) -> int:
        m = 1
        for t in self._trip_stack:
            m *= max(1, t)
        return m

    def _emit(self, kind, axes, nbytes, eqn, reason, realizations,
              where=None):
        axes = tuple(a for a in axes if self.sizes.get(a, 1) > 1)
        if not axes or not realizations:
            return
        self.events.append(CommEvent(
            kind=kind, axes=axes, bytes=int(nbytes),
            where=where or _source_line(eqn), primitive=eqn.primitive.name,
            reason=reason, realizations=tuple(realizations),
            in_loop=self._loop_depth > 0,
            trip=self._trip_mult() if self._loop_depth else None,
        ))

    def _materialize(self, spec: Spec, v, eqn, why: str) -> Spec:
        """Force a pending partial sum concrete: the predicted all-reduce
        (or reduce-scatter + later all-gather — XLA's pick). Attributed
        to the line that CREATED the partial (the contraction/reduction
        whose operands were sharded), not the line that happened to
        consume it."""
        if not spec.partial:
            return spec
        for ax in sorted(spec.partial):
            self._emit(
                "reduce", (ax,), self._local_bytes(v, spec), eqn,
                why, (
                    ("all-reduce", ax),
                    ("reduce-scatter", ax),
                    ("all-gather", ax),
                ),
                where=spec.origin,
            )
        return spec.drop_partial()

    def _reshard(self, src: Spec, dst_dims: tuple[Dim, ...], v, eqn,
                 why: str) -> Spec:
        """Emit the event(s) a spec change on sharded data costs.

        replicated→sharded is free (a slice); sharded→replicated is an
        all-gather; a sharded dim moving to another dim/axis is an
        all-to-all or collective-permute — XLA picks by cost, so the
        event carries all three forms.
        """
        src_ax, dst_ax = src.sharded_axes(), {
            a for d in dst_dims for a in d
        }
        lost = {a for a in src_ax if self.sizes.get(a, 1) > 1} - dst_ax
        moved = set()
        for i, (s, d) in enumerate(zip(src.dims, dst_dims)):
            if s != d and s and d:
                moved |= set(s) & set(d)
        for ax in sorted(lost):
            # The gathered buffer is the honest wire-volume proxy
            # (parallel.hlo's convention: post-collective bytes).
            after = Spec(dst_dims)
            self._emit(
                "reshard", (ax,), self._local_bytes(v, after), eqn, why,
                (
                    ("all-gather", ax),
                    ("all-to-all", ax),
                    ("collective-permute", ax),
                ),
            )
        for ax in sorted(moved - lost):
            self._emit(
                "reshard", (ax,), self._local_bytes(v, Spec(dst_dims)),
                eqn, why,
                (
                    ("all-to-all", ax),
                    ("collective-permute", ax),
                    ("all-gather", ax),
                ),
            )
        return Spec(dst_dims, src.partial)

    # -- the walk ---------------------------------------------------------

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
                # Materialize at the boundary: if the destination is
                # sharded on the pending axis a reduce-scatter suffices,
                # else the full all-reduce.
                spec = self._materialize(
                    spec, v, jaxpr.eqns[-1] if jaxpr.eqns else _FakeEqn(),
                    "pending partial sum reaches the program output",
                )
            if hint is not None and hint.dims != spec.dims:
                spec = self._reshard(
                    spec, hint.dims, v,
                    jaxpr.eqns[-1] if jaxpr.eqns else _FakeEqn(),
                    "output pinned to a different sharding "
                    "(out_shardings / donation layout)",
                )
            outs.append(spec)
        return outs

    # -- per-primitive rules ---------------------------------------------

    def _eqn(self, eqn, read, write):
        prim = eqn.primitive.name
        handler = getattr(self, f"_p_{prim}", None)
        if handler is not None:
            handler(eqn, read, write)
            return
        if prim in _EXPLICIT:
            self._explicit(eqn, read, write)
        elif prim in _REDUCES:
            self._reduce(eqn, read, write)
        elif prim in _ELEMENTWISE or prim in _UNARY:
            self._elementwise(eqn, read, write)
        elif _sub_jaxprs(eqn):
            self._call(eqn, read, write)
        else:
            # Unknown structured op: conservative — materialize partials,
            # all-gather sharded operands feeding it, outputs replicated.
            self._opaque(eqn, read, write)

    # elementwise / unary -------------------------------------------------

    def _elementwise(self, eqn, read, write):
        specs = [read(v) for v in eqn.invars]
        self.flops += math.prod(
            getattr(eqn.outvars[0].aval, "shape", ()) or (1,)
        ) * self._trip_mult()
        # Partial sums flow through linear ops whose other operands are
        # replicated (GSPMD keeps the pending reduce open through adds
        # and scales); any other combination materializes.
        partial = frozenset().union(*(s.partial for s in specs))
        if partial and eqn.primitive.name not in (
            "add", "add_any", "sub", "neg", "mul", "div",
            "convert_element_type", "copy", "stop_gradient", "name",
        ):
            for i, s in enumerate(specs):
                if s.partial:
                    specs[i] = self._materialize(
                        s, eqn.invars[i], eqn,
                        f"partial sum consumed by `{eqn.primitive.name}`",
                    )
            partial = frozenset()
        ndim = len(getattr(eqn.outvars[0].aval, "shape", ()) or ())
        merged: list[Dim] = []
        for d in range(ndim):
            cands = [
                s.dims[d] for s in specs
                if len(s.dims) > d and s.dims[d]
            ]
            merged.append(cands[0] if cands else ())
        # Conflicting sharded operands must move to the merged spec; a
        # replicated operand aligns for free (a slice) — though XLA may
        # still realize the alignment as a collective when the device
        # order demands (observed: tuple all-to-alls over broadcast
        # operands in the train step's optimizer arithmetic), so record
        # a zero-cost `slice` event the reconciler can let those claim.
        sliced_axes: set[str] = set()
        for i, s in enumerate(specs):
            if len(s.dims) != ndim:
                continue
            conflict = False
            for d in range(ndim):
                if s.dims[d] and merged[d] and s.dims[d] != tuple(merged[d]):
                    self._reshard(
                        s, tuple(merged), eqn.invars[i], eqn,
                        f"operand {i} of `{eqn.primitive.name}` sharded "
                        f"{s.dims} against {tuple(merged)}",
                    )
                    conflict = True
                    break
            if conflict:
                continue
            if not s.sharded_axes():
                for d in range(ndim):
                    sliced_axes.update(
                        a for a in merged[d] if a not in sliced_axes
                    )
        origin = next(
            (s.origin for s in specs if s.partial and s.origin), None
        )
        for ax in sorted(sliced_axes):
            self._emit(
                "slice", (ax,), 0, eqn,
                f"replicated operand of `{eqn.primitive.name}` aligns "
                "to a sharded peer (free slice; XLA may realize it as "
                "a collective under device-order constraints)",
                (
                    ("slice", ax),
                    ("all-to-all", ax),
                    ("collective-permute", ax),
                    ("all-gather", ax),
                ),
            )
        for v in eqn.outvars:
            write(v, Spec(tuple(merged), partial, origin))

    # dot_general ---------------------------------------------------------

    def _p_dot_general(self, eqn, read, write):
        lhs, rhs = eqn.invars[:2]
        ls, rs = read(lhs), read(rhs)
        ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
        lshape = tuple(lhs.aval.shape)
        rshape = tuple(rhs.aval.shape)
        m_dims = [i for i in range(len(lshape)) if i not in lc and i not in lb]
        n_dims = [i for i in range(len(rshape)) if i not in rc and i not in rb]
        flops = 2.0 * math.prod(
            [lshape[i] for i in lb]
            + [lshape[i] for i in m_dims]
            + [rshape[i] for i in n_dims]
            + [lshape[i] for i in lc]
        )
        self.flops += flops * self._trip_mult()
        # GEMV-regime dots (decode token steps: a handful of rows against
        # a big weight) sustain a far lower rate than square matmuls on
        # every backend; bucket them so the cost model can price the two
        # regimes separately (the decode bench line is ~all thin flops).
        m_size = math.prod([lshape[i] for i in m_dims]) if m_dims else 1
        n_size = math.prod([rshape[i] for i in n_dims]) if n_dims else 1
        if min(m_size, n_size) < 64:
            self.flops_thin += flops * self._trip_mult()

        ls = self._materialize(
            ls, lhs, eqn, "partial sum feeds a dot_general lhs"
        ) if ls.partial else ls
        rs = self._materialize(
            rs, rhs, eqn, "partial sum feeds a dot_general rhs"
        ) if rs.partial else rs

        partial: set[str] = set()
        ls_d, rs_d = list(ls.dims), list(rs.dims)
        if len(ls_d) != len(lshape) or len(rs_d) != len(rshape):
            ls_d = [()] * len(lshape)
            rs_d = [()] * len(rshape)
        for li, ri in zip(lc, rc):
            la, ra = tuple(ls_d[li]), tuple(rs_d[ri])
            if la and la == ra:
                # Matched contraction sharding: local partial products,
                # pending reduce over the axis (2105.04663 §4.2 case 2).
                partial.update(la)
            elif la or ra:
                # Mismatched: GSPMD reshards ONE side to match the other
                # (cost-picked). Predict gathering the sharded side.
                side, sd, s_ax = (
                    (lhs, ls, la) if la else (rhs, rs, ra)
                )
                dst = list(ls_d if la else rs_d)
                dst[li if la else ri] = ()
                self._reshard(
                    sd, tuple(tuple(x) for x in dst), side, eqn,
                    "contracting dim sharded on one dot operand only — "
                    "GSPMD must gather it (or reshard the peer) before "
                    "the contraction",
                )
                if la:
                    ls_d[li] = ()
                else:
                    rs_d[ri] = ()
        for li, ri in zip(lb, rb):
            la, ra = tuple(ls_d[li]), tuple(rs_d[ri])
            if la != ra and (la or ra):
                if la and ra:
                    self._reshard(
                        rs, tuple(
                            la if i == ri else rs_d[i]
                            for i in range(len(rs_d))
                        ), rhs, eqn,
                        "batch dims sharded differently across dot "
                        "operands",
                    )
                rs_d[ri] = la or ra
                ls_d[li] = la or ra
        out_dims: list[Dim] = (
            [tuple(ls_d[i]) for i in lb]
            + [tuple(ls_d[i]) for i in m_dims]
            + [tuple(rs_d[i]) for i in n_dims]
        )
        # One mesh axis can shard at most ONE dim of the product: when
        # both operands bring free dims sharded on the same axis (e.g.
        # batch-sharded lhs against an output-sharded rhs on one axis),
        # GSPMD keeps the first and gathers the other operand off the
        # axis before the dot.
        kept: set[str] = set()
        fixed: list[Dim] = []
        for pos, d in enumerate(out_dims):
            dup = tuple(a for a in d if a in kept and self.sizes.get(a, 1) > 1)
            if dup:
                if pos < len(lb):
                    side_v, side_dims, idx = lhs, ls_d, lb[pos]
                elif pos < len(lb) + len(m_dims):
                    side_v, side_dims, idx = lhs, ls_d, m_dims[pos - len(lb)]
                else:
                    side_v, side_dims, idx = (
                        rhs, rs_d, n_dims[pos - len(lb) - len(m_dims)]
                    )
                dst = [tuple(x) for x in side_dims]
                dst[idx] = tuple(a for a in dst[idx] if a not in dup)
                self._reshard(
                    Spec(tuple(tuple(x) for x in side_dims)), tuple(dst),
                    side_v, eqn,
                    "free dims of both dot operands sharded on the same "
                    "axis — the product can use it once; GSPMD gathers "
                    "the other side",
                )
                side_dims[idx] = dst[idx]
                d = tuple(a for a in d if a not in dup)
            kept.update(d)
            fixed.append(tuple(d))
        out_dims = fixed
        # A free dim sharded on the same axis as a pending partial can't
        # coexist (an axis shards OR reduces, not both): drop the dim
        # sharding — GSPMD replicates that operand dim into the product.
        out_dims = [
            tuple(a for a in d if a not in partial) for d in out_dims
        ]
        write(eqn.outvars[0], Spec(
            tuple(out_dims), frozenset(partial),
            _source_line(eqn) if partial else None,
        ))

    # structure rewrites --------------------------------------------------

    def _p_broadcast_in_dim(self, eqn, read, write):
        (x,) = eqn.invars[:1]
        s = read(x)
        bdims = eqn.params["broadcast_dimensions"]
        ndim = len(eqn.params["shape"])
        dims: list[Dim] = [()] * ndim
        if len(s.dims) == len(bdims):
            in_shape = tuple(getattr(x.aval, "shape", ()) or ())
            for i, d in enumerate(bdims):
                # A size-1 dim broadcast to size-n replicates — sharding
                # doesn't carry through.
                if i < len(in_shape) and in_shape[i] == eqn.params["shape"][d]:
                    dims[d] = tuple(s.dims[i])
        write(eqn.outvars[0], Spec(tuple(dims), s.partial))

    def _p_transpose(self, eqn, read, write):
        s = read(eqn.invars[0])
        perm = eqn.params["permutation"]
        if len(s.dims) == len(perm):
            dims = tuple(s.dims[p] for p in perm)
        else:
            dims = s.dims
        write(eqn.outvars[0], Spec(dims, s.partial))

    def _p_reshape(self, eqn, read, write):
        x = eqn.invars[0]
        s = read(x)
        in_shape = tuple(getattr(x.aval, "shape", ()) or ())
        out_shape = tuple(eqn.params["new_sizes"])
        dims, ok = _map_reshape(s.dims, in_shape, out_shape, self.sizes)
        if not ok:
            s = self._reshard(
                s, ((),) * len(in_shape), x, eqn,
                "reshape splits/merges through a sharded dim the tiling "
                "cannot follow — GSPMD gathers first",
            )
            dims = ((),) * len(out_shape)
        write(eqn.outvars[0], Spec(tuple(dims), s.partial))

    def _p_squeeze(self, eqn, read, write):
        s = read(eqn.invars[0])
        drop = set(eqn.params["dimensions"])
        dims = tuple(d for i, d in enumerate(s.dims) if i not in drop)
        write(eqn.outvars[0], Spec(dims, s.partial))

    def _p_expand_dims(self, eqn, read, write):
        s = read(eqn.invars[0])
        dims = list(s.dims)
        for d in sorted(eqn.params["dimensions"]):
            dims.insert(d, ())
        write(eqn.outvars[0], Spec(tuple(dims), s.partial))

    def _p_concatenate(self, eqn, read, write):
        specs = [read(v) for v in eqn.invars]
        dim = eqn.params["dimension"]
        ndim = len(getattr(eqn.outvars[0].aval, "shape", ()) or ())
        merged: list[Dim] = [()] * ndim
        for s in specs:
            if len(s.dims) != ndim:
                continue
            for d in range(ndim):
                if d != dim and s.dims[d] and not merged[d]:
                    merged[d] = tuple(s.dims[d])
        for i, s in enumerate(specs):
            if len(s.dims) == ndim and s.dims[dim]:
                # Concatenating along a sharded dim gathers it.
                self._reshard(
                    s,
                    tuple(
                        () if d == dim else tuple(merged[d])
                        for d in range(ndim)
                    ),
                    eqn.invars[i], eqn,
                    "concatenate along a sharded dim",
                )
        write(eqn.outvars[0], Spec(tuple(tuple(d) for d in merged)))

    def _p_slice(self, eqn, read, write):
        self._shrink_like(eqn, read, write, "slice")

    def _p_dynamic_slice(self, eqn, read, write):
        self._shrink_like(eqn, read, write, "dynamic_slice")

    def _p_dynamic_update_slice(self, eqn, read, write):
        # Update rides the operand's spec; a sharded updated dim needs
        # the update gathered/aligned — treat as free when update is
        # replicated (the common KV-cache write).
        s = read(eqn.invars[0])
        write(eqn.outvars[0], s)

    def _shrink_like(self, eqn, read, write, label):
        x = eqn.invars[0]
        s = read(x)
        in_shape = tuple(getattr(x.aval, "shape", ()) or ())
        out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()) or ())
        dims = list(s.dims) if len(s.dims) == len(in_shape) else (
            [()] * len(in_shape)
        )
        for d in range(min(len(in_shape), len(out_shape))):
            if dims[d] and out_shape[d] != in_shape[d]:
                # Slicing across a sharded dim forces a gather unless the
                # slice is shard-aligned; predict the gather (GSPMD's
                # fallback) — cheap slices just never show up in HLO.
                self._reshard(
                    s, tuple(
                        () if i == d else tuple(dims[i])
                        for i in range(len(dims))
                    ), x, eqn, f"{label} across a sharded dim",
                )
                dims[d] = ()
        write(eqn.outvars[0], Spec(tuple(tuple(d) for d in dims[:len(out_shape)]), s.partial))

    def _p_gather(self, eqn, read, write):
        x, idx = eqn.invars[0], eqn.invars[1]
        s, si = read(x), read(idx)
        dnums = eqn.params["dimension_numbers"]
        offset_dims = tuple(dnums.offset_dims)
        ndim = len(getattr(eqn.outvars[0].aval, "shape", ()) or ())
        in_shape = tuple(getattr(x.aval, "shape", ()) or ())
        slice_sizes = tuple(eqn.params.get("slice_sizes", ()) or ())
        indexed = set(getattr(dnums, "start_index_map", ()))
        for d in indexed:
            if len(s.dims) > d and s.dims[d]:
                # Dynamic indices into a sharded dim: GSPMD gathers the
                # operand (the embedding-table case when VOCAB shards).
                s = self._reshard(
                    s, tuple(
                        () if i == d else tuple(s.dims[i])
                        for i in range(len(s.dims))
                    ), x, eqn,
                    "gather indexes into a sharded dim",
                )
        out_dims: list[Dim] = [()] * ndim
        # Batch output dims (not offset) take the INDEX sharding — the
        # embedding-lookup path where batch/seq sharding rides through.
        batch_out = [d for d in range(ndim) if d not in offset_dims]
        idx_dims = [
            si.dims[i] for i in range(len(si.dims))
            if i != len(si.dims) - 1 or len(si.dims) == len(batch_out)
        ]
        for k, d in enumerate(batch_out):
            if k < len(idx_dims):
                out_dims[d] = tuple(idx_dims[k])
        # Offset dims taking a FULL slice of the operand dim keep the
        # operand's sharding (feature dim of an embedding table).
        op_dims = [
            i for i in range(len(in_shape))
            if i not in set(dnums.collapsed_slice_dims)
        ]
        for k, d in enumerate(offset_dims):
            if k < len(op_dims):
                i = op_dims[k]
                if (
                    len(s.dims) > i and i < len(slice_sizes)
                    and slice_sizes[i] == in_shape[i]
                ):
                    out_dims[d] = tuple(s.dims[i])
        write(eqn.outvars[0], Spec(tuple(out_dims), si.partial))

    def _p_iota(self, eqn, read, write):
        ndim = len(getattr(eqn.outvars[0].aval, "shape", ()) or ())
        write(eqn.outvars[0], Spec.replicated(ndim))

    def _p_pad(self, eqn, read, write):
        s = read(eqn.invars[0])
        write(eqn.outvars[0], s.drop_partial() if False else s)

    def _p_rev(self, eqn, read, write):
        write(eqn.outvars[0], read(eqn.invars[0]))

    def _p_sort(self, eqn, read, write):
        for v in eqn.outvars:
            write(v, read(eqn.invars[0]))

    def _p_cumsum(self, eqn, read, write):
        self._elementwise(eqn, read, write)

    def _p_cumlogsumexp(self, eqn, read, write):
        self._elementwise(eqn, read, write)

    def _p_cummax(self, eqn, read, write):
        self._elementwise(eqn, read, write)

    # reductions ----------------------------------------------------------

    def _reduce(self, eqn, read, write):
        x = eqn.invars[0]
        s = read(x)
        axes = set(eqn.params.get("axes", ()))
        self.flops += math.prod(
            getattr(x.aval, "shape", ()) or (1,)
        ) * self._trip_mult()
        partial = set(s.partial)
        dims: list[Dim] = []
        for i, d in enumerate(s.dims):
            if i in axes:
                partial.update(d)   # reduce over a sharded dim → pending
            else:
                dims.append(d)
        origin = s.origin or (_source_line(eqn) if partial else None)
        for v in eqn.outvars:
            write(v, Spec(tuple(dims), frozenset(partial), origin))

    # sharding constraints -------------------------------------------------

    def _p_sharding_constraint(self, eqn, read, write):
        s = read(eqn.invars[0])
        sh = eqn.params.get("sharding")
        ndim = len(getattr(eqn.invars[0].aval, "shape", ()) or ())
        dst = spec_of_sharding(
            getattr(sh, "_to_xla_hlo_sharding", None) and sh or sh, ndim
        )
        try:
            dst = spec_of_sharding(sh, ndim)
        except Exception:
            dst = Spec.replicated(ndim)
        s = self._materialize(
            s, eqn.invars[0], eqn,
            "partial sum reaches a sharding constraint",
        ) if s.partial and not (s.partial <= set(dst.sharded_axes())) else s
        out = self._reshard(
            s, dst.dims, eqn.invars[0], eqn,
            "with_sharding_constraint forces a layout change",
        ) if any(
            sd and sd != dd for sd, dd in zip(s.dims, dst.dims)
        ) else Spec(dst.dims, s.partial)
        write(eqn.outvars[0], Spec(dst.dims, out.partial))

    # calls / control flow -------------------------------------------------

    def _call(self, eqn, read, write):
        subs = _sub_jaxprs(eqn)
        in_specs = [read(v) for v in eqn.invars]
        _, sub = subs[0]
        n = len(sub.invars)
        outs = self.run(sub, in_specs[-n:] if n <= len(in_specs) else (
            in_specs + [Spec.replicated(0)] * (n - len(in_specs))
        ))
        for v, s in zip(eqn.outvars, outs[-len(eqn.outvars):]):
            write(v, s)

    def _p_pjit(self, eqn, read, write):
        self._call(eqn, read, write)

    def _p_remat2(self, eqn, read, write):
        self._call(eqn, read, write)

    def _p_checkpoint(self, eqn, read, write):
        self._call(eqn, read, write)

    def _p_custom_jvp_call(self, eqn, read, write):
        self._call(eqn, read, write)

    def _p_custom_vjp_call(self, eqn, read, write):
        self._call(eqn, read, write)

    def _p_custom_vjp_call_jaxpr(self, eqn, read, write):
        self._call(eqn, read, write)

    def _p_scan(self, eqn, read, write):
        from jax.extend import core as jax_core

        closed = eqn.params["jaxpr"]
        body = closed.jaxpr if isinstance(
            closed, jax_core.ClosedJaxpr
        ) else closed
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        length = int(eqn.params.get("length", 1) or 1)
        in_specs = [read(v) for v in eqn.invars]
        consts = in_specs[:n_consts]
        carry = [s.drop_partial() for s in in_specs[n_consts:n_consts + n_carry]]
        xs = [
            # Per-iteration slice: drop the leading (scanned) dim.
            Spec(s.dims[1:], frozenset()) if s.dims else s
            for s in in_specs[n_consts + n_carry:]
        ]
        # Carry fixpoint: widen to the body's output spec until stable,
        # then one final counted pass with the loop multiplier on.
        for _ in range(3):
            probe = _Interp(self.mesh)
            outs = probe.run(body, consts + carry + xs)
            new_carry = [s.drop_partial() for s in outs[:n_carry]]
            if [s.dims for s in new_carry] == [s.dims for s in carry]:
                break
            carry = [
                Spec(tuple(
                    cd if cd == nd else ()
                    for cd, nd in zip(c.dims, n.dims)
                )) if len(c.dims) == len(n.dims) else Spec.replicated(
                    len(c.dims)
                )
                for c, n in zip(carry, new_carry)
            ]
        self._loop_depth += 1
        self._trip_stack.append(length)
        outs = self.run(body, consts + carry + xs)
        self._trip_stack.pop()
        self._loop_depth -= 1
        carry_out = outs[:n_carry]
        ys = [Spec(((),) + s.dims, frozenset()) for s in outs[n_carry:]]
        for v, s in zip(eqn.outvars, carry_out + ys):
            write(v, s)

    def _p_while(self, eqn, read, write):
        from jax.extend import core as jax_core

        body_closed = eqn.params["body_jaxpr"]
        cond_closed = eqn.params["cond_jaxpr"]
        body = body_closed.jaxpr if isinstance(
            body_closed, jax_core.ClosedJaxpr
        ) else body_closed
        cond = cond_closed.jaxpr if isinstance(
            cond_closed, jax_core.ClosedJaxpr
        ) else cond_closed
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        in_specs = [read(v) for v in eqn.invars]
        carry = [s.drop_partial() for s in in_specs[cn + bn:]]
        bconsts = in_specs[cn:cn + bn]
        for _ in range(3):
            probe = _Interp(self.mesh)
            outs = probe.run(body, bconsts + carry)
            new_carry = [s.drop_partial() for s in outs]
            if [s.dims for s in new_carry] == [s.dims for s in carry]:
                break
            carry = [
                Spec(tuple(
                    cd if cd == nd else ()
                    for cd, nd in zip(c.dims, n.dims)
                )) if len(c.dims) == len(n.dims) else Spec.replicated(
                    len(c.dims)
                )
                for c, n in zip(carry, new_carry)
            ]
        trip = self.while_trip_hint or 1
        self._loop_depth += 1
        self._trip_stack.append(trip)
        self.run(cond, in_specs[:cn] + carry)
        outs = self.run(body, bconsts + carry)
        self._trip_stack.pop()
        self._loop_depth -= 1
        for v, s in zip(eqn.outvars, outs):
            write(v, s)

    def _p_cond(self, eqn, read, write):
        from jax.extend import core as jax_core

        branches = eqn.params["branches"]
        in_specs = [read(v) for v in eqn.invars[1:]]
        all_outs = []
        for br in branches:
            b = br.jaxpr if isinstance(br, jax_core.ClosedJaxpr) else br
            all_outs.append(self.run(b, in_specs))
        for i, v in enumerate(eqn.outvars):
            cands = [outs[i] for outs in all_outs if i < len(outs)]
            write(v, cands[0] if cands else Spec.replicated(0))

    def _p_shard_map(self, eqn, read, write):
        """Explicit-collective region: walk the body for psum/all_gather/
        ppermute/all_to_all and pass them through verbatim; outputs take
        the region's declared out_specs."""
        from jax.extend import core as jax_core

        closed = eqn.params.get("jaxpr")
        body = closed.jaxpr if isinstance(
            closed, jax_core.ClosedJaxpr
        ) else closed
        if body is not None:
            self._walk_explicit(body)
        out_names = eqn.params.get("out_names") or eqn.params.get(
            "out_specs"
        )
        for i, v in enumerate(eqn.outvars):
            ndim = len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            spec = Spec.replicated(ndim)
            try:
                names = out_names[i]
                if hasattr(names, "items"):   # {dim: (axis,...)}
                    dims = [()] * ndim
                    for d, axes in names.items():
                        dims[int(d)] = tuple(
                            str(a) for a in (
                                axes if isinstance(axes, (tuple, list))
                                else (axes,)
                            )
                        )
                    spec = Spec(tuple(dims))
                else:
                    spec = spec_of_sharding(names, ndim)
            except Exception:
                # Unrecognized sharding param shape from a newer jax:
                # keep the operand's propagated spec (already in `spec`).
                write(v, spec)
                continue
            write(v, spec)

    def _walk_explicit(self, jaxpr):
        from jax.extend import core as jax_core

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in _EXPLICIT:
                op = _EXPLICIT[prim]
                axes = eqn.params.get("axes") or eqn.params.get(
                    "axis_name"
                ) or ()
                if not isinstance(axes, (tuple, list)):
                    axes = (axes,)
                axes = tuple(str(a) for a in axes)
                nbytes = max(
                    (_aval_bytes(v) for v in (
                        list(eqn.outvars) + list(eqn.invars)
                    )), default=0,
                )
                for ax in axes:
                    self._emit(
                        "explicit", (ax,), nbytes, eqn,
                        f"explicit `{prim}` over mesh axis {ax!r} "
                        "(shard_map)",
                        ((op, ax),),
                    )
            for _, sub in _sub_jaxprs(eqn):
                if prim in ("scan", "while"):
                    trip = int(eqn.params.get("length", 0) or 0) or (
                        self.while_trip_hint or 1
                    )
                    self._loop_depth += 1
                    self._trip_stack.append(trip)
                    self._walk_explicit(sub)
                    self._trip_stack.pop()
                    self._loop_depth -= 1
                else:
                    self._walk_explicit(sub)

    # RNG / misc ----------------------------------------------------------

    def _p_random_seed(self, eqn, read, write):
        for v in eqn.outvars:
            write(v, Spec.replicated(
                len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            ))

    def _p_random_bits(self, eqn, read, write):
        for v in eqn.outvars:
            write(v, Spec.replicated(
                len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            ))

    def _p_scatter_add(self, eqn, read, write):
        s = read(eqn.invars[0])
        write(eqn.outvars[0], s)

    def _opaque(self, eqn, read, write):
        for i, v in enumerate(eqn.invars):
            s = read(v)
            if s.partial:
                self._materialize(
                    s, v, eqn,
                    f"partial sum consumed by opaque "
                    f"`{eqn.primitive.name}`",
                )
        for v in eqn.outvars:
            write(v, Spec.replicated(
                len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            ))
        self.hbm_bytes += sum(
            _aval_bytes(v) for v in eqn.outvars
        ) * self._trip_mult()


class _FakeEqn:
    class _P:
        name = "<output>"

    primitive = _P()
    source_info = None
    params: dict = {}


def _map_reshape(dims, in_shape, out_shape, sizes):
    """Carry per-dim sharding through a reshape when the tiling survives:
    a sharded dim whose size is preserved maps through; a sharded MAJOR
    dim of a merge/split maps when the shard factor still divides the new
    major dim. Returns (new_dims, ok)."""
    if len(dims) != len(in_shape):
        return ((),) * len(out_shape), True
    out: list[Dim] = [()] * len(out_shape)
    i = j = 0
    ok = True
    while i < len(in_shape) and j < len(out_shape):
        if in_shape[i] == out_shape[j]:
            out[j] = tuple(dims[i])
            i += 1
            j += 1
            continue
        # group: accumulate until products match
        pi, pj = in_shape[i], out_shape[j]
        gi, gj = [i], [j]
        while pi != pj:
            if pi < pj and gi[-1] + 1 < len(in_shape):
                gi.append(gi[-1] + 1)
                pi *= in_shape[gi[-1]]
            elif gj[-1] + 1 < len(out_shape):
                gj.append(gj[-1] + 1)
                pj *= out_shape[gj[-1]]
            else:
                break
        sharded = [k for k in gi if dims[k]]
        if sharded:
            if sharded == [gi[0]]:
                f = 1
                for a in dims[gi[0]]:
                    f *= sizes.get(a, 1)
                if out_shape[gj[0]] % f == 0:
                    out[gj[0]] = tuple(dims[gi[0]])
                else:
                    ok = False
            else:
                ok = False
        i = gi[-1] + 1
        j = gj[-1] + 1
    return tuple(tuple(d) for d in out), ok


# ---------------------------------------------------------------------------
# Entry API
# ---------------------------------------------------------------------------


def simulate_jaxpr(
    name: str,
    closed: Any,
    in_specs: list[Spec],
    mesh: Any,
    *,
    while_trip_hint: int | None = None,
    out_hint: list[Spec] | None = None,
    arg_avals: list[Any] | None = None,
) -> ShardflowReport:
    """Run the propagation interpreter over an ALREADY-TRACED closed
    jaxpr with explicit per-invar input :class:`Spec`\\ s — the layout
    search's inner loop (``analysis.layout_search``): the jaxpr is
    traced once per entry point, then re-simulated per candidate
    sharding assignment with no re-trace and no compile. ``arg_avals``
    (default: the jaxpr invars' avals) sizes the input HBM streaming
    charge; :func:`trace_shardflow` passes the concrete argument leaves
    so its accounting is unchanged."""
    in_specs = list(in_specs)
    # make_jaxpr flattens args in tree order == invars order.
    if len(in_specs) < len(closed.jaxpr.invars):
        in_specs += [Spec.replicated(0)] * (
            len(closed.jaxpr.invars) - len(in_specs)
        )
    if arg_avals is None:
        arg_avals = [v.aval for v in closed.jaxpr.invars]
    interp = _Interp(mesh, while_trip_hint=while_trip_hint)
    # Program inputs are streamed from HBM once (loop bodies re-charge
    # their own operands through the trip multiplier).
    sizes = interp.sizes
    for leaf, spec in zip(arg_avals, in_specs):
        interp.hbm_bytes += _aval_bytes(leaf) / max(
            1, spec.shard_factor(sizes)
        )
    out_specs = interp.run(closed.jaxpr, in_specs[:len(closed.jaxpr.invars)],
                           out_hint)
    for v, spec in zip(closed.jaxpr.outvars, out_specs):
        interp.hbm_bytes += _aval_bytes(v) / max(
            1, spec.shard_factor(sizes)
        )
    return ShardflowReport(
        name=name,
        mesh_axes=[str(a) for a in mesh.axis_names],
        mesh_shape=[int(mesh.shape[a]) for a in mesh.axis_names],
        events=interp.events,
        flops=interp.flops,
        hbm_bytes=interp.hbm_bytes,
        out_specs=out_specs,
        flops_thin=interp.flops_thin,
    )


def trace_shardflow(
    name: str,
    fn: Callable,
    *args,
    mesh: Any,
    while_trip_hint: int | None = None,
    out_shardings: Any = None,
    **kwargs,
) -> ShardflowReport:
    """Trace ``fn(*args)`` to a jaxpr (no compile) and simulate GSPMD
    propagation from the arguments' REAL shardings. ``args`` must carry
    them (committed arrays), same convention as ``parallel.hlo.
    compiled_hlo``. ``while_trip_hint`` prices collectives/bytes inside
    ``while`` loops whose trip count the trace can't see (e.g. a decode
    loop's max_new_tokens)."""
    import jax

    inner = getattr(fn, "__wrapped__", fn)
    closed = jax.make_jaxpr(inner)(*args, **kwargs)
    flat, _ = jax.tree_util.tree_flatten((args, kwargs))
    in_specs = []
    for leaf in flat:
        ndim = int(getattr(leaf, "ndim", np.ndim(leaf)))
        sh = getattr(leaf, "sharding", None)
        in_specs.append(
            spec_of_sharding(sh, ndim) if sh is not None
            else Spec.replicated(ndim)
        )
    out_hint = None
    if out_shardings is not None:
        import jax as _jax

        hint_flat = _jax.tree_util.tree_leaves(out_shardings)
        out_hint = []
        for v, sh in zip(closed.jaxpr.outvars, hint_flat):
            ndim = len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            out_hint.append(spec_of_sharding(sh, ndim))
    return simulate_jaxpr(
        name, closed, in_specs, mesh,
        while_trip_hint=while_trip_hint, out_hint=out_hint, arg_avals=flat,
    )


# ---------------------------------------------------------------------------
# Reconciliation against the compiled contract
# ---------------------------------------------------------------------------

#: Axis labels the HLO contract uses that any predicted axis may explain:
#: ``unattributed`` (reshard permutes across both axes), ``none``
#: (degenerate all-singleton groups), and ``data+model`` (whole-mesh).
_WILD_AXES = ("unattributed", "none", "data+model")


def reconcile(
    report: ShardflowReport,
    contract: Any,
) -> dict:
    """Match the ACTUAL compiled contract against the prediction.

    Every actual collective must be claimed by a predicted event through
    one of its realizations (XLA picks the op form per reshard/reduce by
    cost — 2105.04663 §3.5); one ``reduce`` event may claim a
    reduce-scatter AND an all-gather on its axis (the split form), and an
    axis-wildcard group (``@unattributed``/``@none``/whole-mesh) may be
    claimed by an event on any axis. What remains ACTUAL-side is
    ``unexplained`` — the propagation rules drifted from the real
    partitioner (a gated finding in the shardflow pass). What remains
    PREDICTED-side is ``elided`` — XLA combined or optimized it away
    (reported, not gated; same asymmetry as ``missing-collective``).
    """
    actual: dict[str, int] = {
        k: int(v["count"]) for k, v in contract.collectives.items()
    }
    remaining = dict(actual)

    def claim(op: str, ax: str) -> bool:
        key = f"{op}@{ax}"
        if remaining.get(key, 0) > 0:
            remaining[key] -= 1
            return True
        return False

    def claim_wild(op: str) -> bool:
        for wax in _WILD_AXES:
            if claim(op, wax):
                return True
        return False

    matched = []
    unmatched_events = []
    for ev in report.events:
        got = None
        for op, ax in ev.realizations:
            if claim(op, ax) or claim_wild(op):
                got = (op, ax)
                break
        if got is None and ev.kind == "reduce":
            # The split form: reduce-scatter + all-gather pair.
            pass
        if got is None:
            unmatched_events.append(ev)
        else:
            matched.append((ev, got))
            if ev.kind == "reduce" and got[0] == "reduce-scatter":
                # The paired all-gather of the RS+AG split rides the
                # same predicted reduce.
                claim(got[0] if False else "all-gather", got[1]) or (
                    claim_wild("all-gather")
                )
    # Second chance: events may explain MULTIPLE actual instructions when
    # XLA splits one logical reshard per operand (tuple shardings) — let
    # still-unclaimed actuals drain against matched events' realization
    # sets before calling them unexplained.
    for key in list(remaining):
        while remaining[key] > 0:
            op, ax = key.split("@", 1)
            donor = next(
                (
                    ev for ev, _ in matched
                    if any(
                        o == op and (a == ax or ax in _WILD_AXES)
                        for o, a in ev.realizations
                    )
                ),
                None,
            )
            if donor is None:
                break
            remaining[key] -= 1

    unexplained = {k: v for k, v in remaining.items() if v > 0}
    elided = {}
    for ev in unmatched_events:
        if ev.kind == "slice":
            continue    # free by design — absence is the normal case
        op, ax = ev.realizations[0]
        key = f"{op}@{ax}"
        elided[key] = elided.get(key, 0) + 1
    return {
        "name": report.name,
        "actual_total": sum(actual.values()),
        "predicted_total": len(report.events),
        "matched": len(matched),
        "unexplained": unexplained,
        "elided": elided,
    }


def reconcile_findings(result: dict) -> list[Finding]:
    """Gate: one ``unexplained-collective`` finding per actual (op,axis)
    group the prediction cannot claim."""
    out = []
    for key, n in sorted(result["unexplained"].items()):
        out.append(Finding(
            "shardflow", "unexplained-collective",
            f"{result['name']}:{key}",
            f"{n} compiled {key} collective(s) no predicted event "
            "explains — the propagation simulator drifted from the real "
            "partitioner (fix the rule, or the program grew "
            "communication shardflow cannot attribute)",
            data={"unexplained": n, "group": key},
        ))
    return out


def render_explanation(
    report: ShardflowReport, *, max_lines: int = 0
) -> str:
    """The per-source-line "why does this collective exist" report."""
    lines = []
    by_line = {
        w: [e for e in evs if e.kind != "slice"]
        for w, evs in report.by_line().items()
    }
    by_line = sorted(
        ((w, evs) for w, evs in by_line.items() if evs),
        key=lambda kv: -sum(
            e.bytes * (e.trip or 1) for e in kv[1]
        ),
    )
    if max_lines:
        by_line = by_line[:max_lines]
    for where, evs in by_line:
        total = sum(e.bytes * (e.trip or 1) for e in evs)
        lines.append(f"{where}  ({len(evs)} event(s), {total:,} B wire)")
        groups: dict[tuple, list[CommEvent]] = {}
        for ev in evs:
            key = (ev.realizations[0], ev.in_loop, ev.trip, ev.reason)
            groups.setdefault(key, []).append(ev)
        for ((op, ax), in_loop, trip, reason), g in groups.items():
            loop = (
                f" ×{trip}/loop" if in_loop and trip else
                (" in-loop" if in_loop else "")
            )
            mult = f" ×{len(g)}" if len(g) > 1 else ""
            gbytes = sum(e.bytes for e in g)
            lines.append(
                f"    {op}@{ax}{mult}{loop}  {gbytes:,} B  "
                f"[{g[0].primitive}] {reason}"
            )
    return "\n".join(lines)
