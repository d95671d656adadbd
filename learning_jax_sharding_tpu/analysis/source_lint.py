"""Repo-wide AST lint for the JAX-specific footguns tests can't see.

``tests/test_timing_audit.py`` proved the shape works: a textual
tripwire (raw clock ⇒ nearby sync) kept every ``cases/`` timing loop
honest across five rounds of refactors. This module generalizes that
tripwire into reusable rules over the WHOLE repo, AST-based where
structure matters:

* ``jit-in-loop``        — ``jax.jit(...)`` (or ``partial(jax.jit, ...)``)
  called inside a ``for``/``while`` body: a fresh wrapper per iteration
  defeats the compile cache, so every pass through the loop recompiles —
  the recompile hazard PR 1's ``CompileWatch`` detects at runtime, caught
  here at review time.
* ``nonhashable-static`` — a function jitted with
  ``static_argnames``/``static_argnums`` whose named parameter defaults
  to a mutable literal (list/dict/set): the first call with the default
  raises ``unhashable type`` — or worse, callers pass fresh literals and
  every call recompiles.
* ``captured-device-array`` — a jit-decorated function reading a
  module-level name bound to a ``jnp.``/``device_put`` result: the array
  is baked into the trace as a constant (bloating the executable and
  pinning device memory) instead of being passed as an argument.
* ``raw-clock``          — a raw wall-clock read (``time.time`` /
  ``perf_counter`` call) with no honest sync idiom within ±10 lines:
  times dispatch, not execution (the reference's original flaw,
  case6_attention.py:234-238).
* ``host-sync-in-hot-loop`` — a blocking host↔device sync
  (``.block_until_ready()``, ``np.asarray(...)``, ``.item()``,
  ``jax.device_get``) inside a ``for``/``while`` body of an
  ``*Engine`` class (``ContinuousEngine``'s dispatch/step loops): each
  iteration stalls the dispatch queue for a device round-trip, the
  host-loop overhead ROADMAP item 1 tracks. Batch the readback after
  the loop or keep the value on device; the engine's deliberate
  result-materialization points ride the baseline with reasons.
* ``untimed-engine-phase`` — a wall-clock-taking call (a compiled-fn
  dispatch ``self._*_fn(...)``, a blocking host sync, a ``chaos_hook``
  seam) inside an ``*Engine`` class's ledger-covered phase methods
  (``step`` / ``*dispatch*`` / ``_admit`` / ``_sweep_deadlines`` /
  ``_try_commit_swap`` / ``export_kv`` / ``ingest_kv``) that is NOT
  lexically inside a goodput-ledger frame (``with ...measure(...)`` /
  ``with ..._led_device(...)`` / ``_led_h2d()`` / ``_led_consume()``):
  time it spends escapes the
  Σ buckets == wall reconciliation invariant
  (``telemetry/ledger.py``) — the static face of the accounting
  identity tier-1 gates at runtime. New engine code paths must open (or
  sit inside) a bucket frame; every frame the engine opens is also a
  named span (``engine.h2d``, ``engine.consume``, ...; the table is
  beside ``ContinuousEngine._led_device``).
* ``unbounded-host-buffer`` — a ``.append(...)`` of a device-array
  value (a ``jnp.``/``jax.device_put``/``jax.random.`` result, direct
  or via a local name) onto a container inside a loop body of an
  ``*Engine`` class, where the container is never evicted in the same
  function (no ``pop``/``popleft``/``popitem``/``clear``, no ``del
  c[...]``, never rebound): the host-side analogue of a KV leak — each
  retained element pins its device buffer, so the engine's resident
  set grows with requests served until the allocator fails far from
  the append that caused it. Cap the container (deque/maxlen), evict
  on a schedule, or read the value back to host before retaining it.
* ``swallowed-exception`` — a bare ``except:`` that does not re-raise,
  or an ``except Exception/BaseException:`` whose body is only
  ``pass``/``...``: the failure vanishes without a record — in a
  recovery-oriented stack (``robustness/``) every swallowed exception
  is a fault the flight recorder never saw. Catch the narrowest type
  and at least ``recorder.record(...)`` it; genuinely-intentional
  crash-path guards ride the baseline with a reason.
* ``axis-literal`` — a bare ``"data"``/``"model"``/``"pipe"`` string
  constant in the topology-aware surfaces (``fleet/``, ``analysis/``):
  these modules plan placement against whatever axes the MESH and the
  :class:`~.topology.TopologyProfile` actually carry, so a hardcoded
  axis name silently breaks on a single-axis mesh or a renamed axis —
  the planner prices the wrong tier and nobody notices. Import
  ``DATA_AXIS``/``MODEL_AXIS``/``DEFAULT_AXIS_NAMES`` from
  ``parallel.mesh`` (or thread the axis through from the mesh/profile
  in scope). Scoped to fleet/ and analysis/ because the model/rules
  layers (``parallel/logical.py``) are the canonical DEFINITION sites
  of those names; definition-site and fixture literals ride the
  baseline with reasons.

* ``unguarded-scale-decision`` — a fleet scale action
  (``adopt_replica`` / ``retire_replica`` / ``preempt_replica`` /
  ``kill_replica`` / ``rolling_swap``) called from inside an
  ``*Autoscaler`` class outside a ``with ..._decision(...)`` frame:
  the autoscaler's contract is that EVERY action it takes is a logged
  decision — flight-recorded, counted, and appended to the timeline
  the replay artifact and the planner-vs-live score are built from
  (``fleet/autoscaler.py``'s ``_decision`` context manager). An
  unframed action mutates the fleet invisibly: the scale_timeline
  artifact, the ``fleet_scale_decisions_total`` counter, and the K(t)
  integral all silently miss it. Zero suppressions — the decision log
  is complete by construction, not by baseline budget.

* ``uncounted-compression`` — a direct call to the wire codec's
  primitives (``quantize_blocks``/``quantize_absmax`` and friends, or
  ``<codec>.encode``/``<codec>.decode`` on a codec-named receiver)
  OUTSIDE the counted seams (``parallel/compression.py`` defines them,
  ``parallel/resharding.py``'s ``execute_transfer`` and
  ``parallel/collectives.py``'s quantized ring book every byte they
  move): compression applied anywhere else produces wire traffic the
  ``*_raw_bytes`` counters and ``compression_ratio`` gauges never see,
  so the byte accounting the whole observability story gates on
  silently understates what crossed the link. Route the payload
  through ``plan_transfer(codec=...)``/``execute_transfer`` or the
  collectives seam instead.

Findings carry ``file:line`` and a stable rule id; pre-existing hits are
carried in ``analysis/baseline.json`` — a (file, rule) → count budget —
so the repo gates on NEW findings without a flag-day cleanup.
"""

from __future__ import annotations

import ast
import json
import pathlib
import re
from typing import Iterable

from learning_jax_sharding_tpu.analysis.findings import Finding

#: Same idioms the timing-audit test pins, kept textually in sync with
#: tests/test_timing_audit.py (that test remains the cases/-specific
#: tripwire; this rule is the repo-wide generalization).
RAW_CLOCKS = re.compile(
    r"time\.perf_counter\(|time\.time\(|time\.monotonic\(|timeit\."
)
SYNC_IDIOMS = re.compile(
    r"measure\(|time_fn\(|block_until_ready|np\.asarray\(|"
    r"\.sync\(|device_sync\(|latency_stats\(|\.step\(|serve\("
)
SYNC_WINDOW = 10

_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", "build", "dist"}


def _dotted(node: ast.AST) -> str:
    """`jax.jit` / `partial` / `np.asarray` — the dotted name of a call
    target, best effort ('' for subscripts/lambdas)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_call(node: ast.Call) -> bool:
    name = _dotted(node.func)
    if name in ("jax.jit", "jit", "pjit", "jax.pjit"):
        return True
    # functools.partial(jax.jit, ...) — the decorator spelling.
    if name.endswith("partial") and node.args:
        return _dotted(node.args[0]) in ("jax.jit", "jit", "pjit", "jax.pjit")
    return False


def _static_names(call: ast.Call) -> set[str]:
    """Parameter names a jit call pins static via ``static_argnames``."""
    out: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    out.add(n.value)
    return out


_DEVICE_MAKERS = re.compile(
    r"^(jnp|jax\.numpy)\.|^jax\.device_put$|^jax\.random\.|device_put$"
)

#: Dotted call names that force a blocking host↔device transfer.
_HOST_SYNC_CALLS = {
    "np.asarray", "numpy.asarray", "jax.device_get", "device_get",
    "jax.block_until_ready",
}
#: Method names that do the same as attribute calls on an array.
_HOST_SYNC_METHODS = {"block_until_ready", "item"}
#: Classes whose loops are the serving hot path.
_HOT_CLASS_RE = re.compile(r"Engine")

#: Engine methods whose ENTIRE wall-clock the goodput ledger must
#: account for (telemetry/ledger.py's Σ buckets == wall invariant).
#: Round 16 adds the multi-step planner family (``_plan_*``,
#: ``_take_staged_plan``, ``_boundary_fingerprint``): the host's
#: next-horizon planning runs CONCURRENT with an in-flight fused
#: dispatch, so an untimed or device-syncing planner would both skew
#: the sched bucket and serialize the overlap the design exists for.
_LEDGER_PHASE_RE = re.compile(
    r"^(step|_admit|_sweep_deadlines|_try_commit_swap|export_kv|"
    r"ingest_kv|_take_staged_plan|_boundary_fingerprint)$"
    r"|dispatch|^_plan_"
)

#: Compiled-executable dispatch: the engine's jitted callables are all
#: ``self._<name>_fn`` attributes by convention.
_COMPILED_FN_RE = re.compile(r"^self\._\w+_fn$")


def _is_ledger_frame(item: ast.withitem) -> bool:
    """Does one ``with`` item open a goodput-ledger bucket frame?
    Matches ``<anything>.measure(...)`` (GoodputLedger.measure — the
    lint deliberately also accepts utils.bench.measure, which times a
    region and is never an engine phase) and the engine's
    ``self._led_*(...)`` helpers: ``_led_device`` (compile-steal) and the
    named host frames ``_led_h2d`` / ``_led_consume``."""
    expr = item.context_expr
    if not isinstance(expr, ast.Call):
        return False
    name = _dotted(expr.func)
    return name.endswith(".measure") or (
        name.rpartition(".")[2].startswith("_led_")
    )


#: Fleet scale actions the ``unguarded-scale-decision`` rule polices:
#: every call to one of these from inside an ``*Autoscaler`` class must
#: sit lexically inside a ``with ..._decision(...)`` frame. Kept
#: textually in sync with :class:`~..fleet.router.FleetRouter`'s
#: elastic surface (same deliberate-copy rationale as RAW_CLOCKS: the
#: lint must not import jax-loading modules).
_SCALE_ACTIONS = frozenset({
    "adopt_replica", "retire_replica", "preempt_replica",
    "kill_replica", "rolling_swap",
})
#: Classes whose scale actions must be logged decisions.
_AUTOSCALER_CLASS_RE = re.compile(r"Autoscaler")


def _is_decision_frame(item: ast.withitem) -> bool:
    """Does one ``with`` item open an autoscaler decision frame?
    Matches ``<anything>._decision(...)`` (the Autoscaler's own frame)
    and a public ``.decision(...)`` spelling, so a future rename from
    private to public does not orphan the rule."""
    expr = item.context_expr
    if not isinstance(expr, ast.Call):
        return False
    name = _dotted(expr.func)
    return name.endswith("._decision") or name.endswith(".decision")


def _host_sync_name(node: ast.Call) -> str | None:
    """The sync idiom a call spells, or None."""
    name = _dotted(node.func)
    if name in _HOST_SYNC_CALLS:
        return name
    if (
        isinstance(node.func, ast.Attribute)
        and node.func.attr in _HOST_SYNC_METHODS
    ):
        return f".{node.func.attr}()"
    return None


def _flat_targets(t: ast.AST):
    """Names bound by one assignment target (handles Tuple/List/Starred)."""
    if isinstance(t, ast.Name):
        yield t.id
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            yield from _flat_targets(e)
    elif isinstance(t, ast.Starred):
        yield from _flat_targets(t.value)


def _bound_names(fn: ast.AST) -> set[str]:
    """Every name BOUND anywhere inside ``fn``'s body: assignments
    (plain/aug/annotated, tuple unpacking), ``for`` targets, ``with ...
    as``, comprehension targets, ``except ... as``, imports, nested
    def/class names. A module-level device-array name shadowed by any of
    these is a local, not a capture — missing a binding form here turns
    correct code into a CI-gating false positive."""
    out: set[str] = set()
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                out.update(_flat_targets(t))
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            out.update(_flat_targets(n.target))
        elif isinstance(n, (ast.For, ast.AsyncFor)):
            out.update(_flat_targets(n.target))
        elif isinstance(n, (ast.With, ast.AsyncWith)):
            for item in n.items:
                if item.optional_vars is not None:
                    out.update(_flat_targets(item.optional_vars))
        elif isinstance(n, ast.comprehension):
            out.update(_flat_targets(n.target))
        elif isinstance(n, ast.ExceptHandler) and n.name:
            out.add(n.name)
        elif isinstance(n, (ast.Import, ast.ImportFrom)):
            out.update(
                (a.asname or a.name.split(".")[0]) for a in n.names
            )
        elif isinstance(
            n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ) and n is not fn:
            out.add(n.name)
        elif isinstance(n, ast.NamedExpr):
            out.update(_flat_targets(n.target))
    return out


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, lines: list[str]):
        self.path = path
        self.lines = lines
        self.findings: list[Finding] = []
        self.loop_depth = 0
        self.func_depth = 0
        self.class_stack: list[str] = []
        # untimed-engine-phase state: are we inside an Engine phase
        # method, and how many ledger frames enclose the current node?
        self.phase_stack: list[bool] = []
        self.ledger_depth = 0
        # unguarded-scale-decision state: how many `with ..._decision`
        # frames enclose the current node?
        self.decision_depth = 0
        # Names bound at MODULE scope to device-array-producing calls —
        # function-local `x = jnp...` bindings must not poison the set
        # (a jitted function elsewhere reading an unrelated global `x`
        # would false-positive and gate CI).
        self.device_names: set[str] = set()

    # --- loops: jit construction inside is a per-iteration recompile ---
    def _loop(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_While = visit_AsyncFor = _loop

    def visit_ClassDef(self, node: ast.ClassDef):
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def _with(self, node):
        opened = sum(1 for item in node.items if _is_ledger_frame(item))
        decisions = sum(
            1 for item in node.items if _is_decision_frame(item)
        )
        self.ledger_depth += opened
        self.decision_depth += decisions
        self.generic_visit(node)
        self.ledger_depth -= opened
        self.decision_depth -= decisions

    visit_With = visit_AsyncWith = _with

    def _in_engine_phase(self) -> bool:
        return bool(self.phase_stack) and self.phase_stack[-1]

    def _check_untimed(self, node: ast.Call):
        """untimed-engine-phase: a wall-clock taker in a ledger-covered
        engine phase with NO enclosing bucket frame leaks time out of
        the Σ buckets == wall identity."""
        if not self._in_engine_phase() or self.ledger_depth > 0:
            return
        name = _dotted(node.func)
        what = None
        if _COMPILED_FN_RE.match(name):
            what = f"compiled dispatch `{name}(...)`"
        elif name.endswith("chaos_hook"):
            what = "chaos seam `chaos_hook(...)`"
        else:
            sync = _host_sync_name(node)
            if sync is not None:
                what = f"host sync `{sync}`"
        if what is not None:
            self.findings.append(Finding(
                "ast", "untimed-engine-phase",
                f"{self.path}:{node.lineno}",
                f"{what} in an engine phase method outside any "
                "goodput-ledger frame — its wall-clock escapes the "
                "ledger's Σ buckets == wall reconciliation (gated in "
                "tier-1); wrap it in `with self.ledger.measure(...)`"
                " or `with self._led_device(...)`, or put it inside "
                "the frame it belongs to: `self._led_h2d()` (span "
                "`engine.h2d`: host-to-device pushes ahead of an "
                "enqueue) or `self._led_consume()` (`engine.consume`: "
                "the loop after a readback)",
            ))

    def visit_Call(self, node: ast.Call):
        if _is_jit_call(node) and self.loop_depth > 0:
            self.findings.append(Finding(
                "ast", "jit-in-loop", f"{self.path}:{node.lineno}",
                "jax.jit called inside a loop body — each iteration "
                "builds a fresh wrapper with its own compile cache, so "
                "every pass recompiles; hoist the jit out of the loop",
            ))
        sync = _host_sync_name(node)
        if (
            sync is not None
            and self.loop_depth > 0
            and any(_HOT_CLASS_RE.search(c) for c in self.class_stack)
        ):
            self.findings.append(Finding(
                "ast", "host-sync-in-hot-loop",
                f"{self.path}:{node.lineno}",
                f"`{sync}` inside a loop on the engine hot path — each "
                "iteration blocks the dispatch queue on a host-device "
                "round-trip; batch the readback outside the loop or "
                "keep the value on device (ROADMAP item 1 host-loop "
                "overhead)",
            ))
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _SCALE_ACTIONS
            and any(
                _AUTOSCALER_CLASS_RE.search(c) for c in self.class_stack
            )
            and self.decision_depth == 0
        ):
            self.findings.append(Finding(
                "ast", "unguarded-scale-decision",
                f"{self.path}:{node.lineno}",
                f"scale action `{_dotted(node.func)}(...)` inside an "
                "autoscaler outside any `with ..._decision(...)` frame "
                "— the action never reaches the decision timeline, the "
                "fleet_scale_decisions_total counter, or the flight "
                "recorder, so the scale_timeline artifact and the "
                "planner-vs-live score silently miss it; wrap it in "
                "`with self._decision(action, ...)`",
            ))
        self._check_untimed(node)
        self.generic_visit(node)

    # --- module-scope device arrays + jitted functions that read them ---
    def visit_Assign(self, node: ast.Assign):
        if (
            self.loop_depth == 0
            and self.func_depth == 0
            and isinstance(node.value, ast.Call)
        ):
            maker = _dotted(node.value.func)
            if _DEVICE_MAKERS.search(maker):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.device_names.add(t.id)
        self.generic_visit(node)

    def _check_function(self, node):
        jit_decos = [
            d for d in node.decorator_list
            if (isinstance(d, ast.Call) and _is_jit_call(d))
            or _dotted(d) in ("jax.jit", "jit")
        ]
        if jit_decos:
            self._check_static_defaults(node, jit_decos)
            self._check_captures(node)
        # unbounded-host-buffer runs per DIRECT Engine method (one walk
        # covers its nested closures; the func_depth guard stops nested
        # defs from re-reporting).
        if (
            self.func_depth == 0
            and self.class_stack
            and _HOT_CLASS_RE.search(self.class_stack[-1])
        ):
            self._check_unbounded_buffers(node)
        # A DIRECT method of an *Engine class whose name marks it a
        # ledger-covered phase; nested closures inherit the flag (their
        # bodies run inside the phase), unrelated nested defs don't
        # clear it — they are part of the phase's wall too.
        is_phase = (
            self.func_depth == 0
            and bool(self.class_stack)
            and bool(_HOT_CLASS_RE.search(self.class_stack[-1]))
            and bool(_LEDGER_PHASE_RE.search(node.name))
        )
        self.phase_stack.append(is_phase or self._in_engine_phase())
        self.func_depth += 1
        self.generic_visit(node)
        self.func_depth -= 1
        self.phase_stack.pop()

    visit_FunctionDef = visit_AsyncFunctionDef = _check_function

    def _check_static_defaults(self, node, jit_decos):
        static: set[str] = set()
        for d in jit_decos:
            if isinstance(d, ast.Call):
                static |= _static_names(d)
        if not static:
            return
        args = node.args
        pos = args.posonlyargs + args.args
        defaults = [None] * (len(pos) - len(args.defaults)) + list(args.defaults)
        pairs = list(zip(pos, defaults)) + list(
            zip(args.kwonlyargs, args.kw_defaults)
        )
        for arg, default in pairs:
            if arg.arg in static and isinstance(
                default, (ast.List, ast.Dict, ast.Set)
            ):
                self.findings.append(Finding(
                    "ast", "nonhashable-static",
                    f"{self.path}:{default.lineno}",
                    f"static arg {arg.arg!r} of jitted "
                    f"`{node.name}` defaults to a mutable literal — "
                    "static args key the compile cache by hash; a "
                    "list/dict default raises `unhashable type` on "
                    "first use (use a tuple/frozen value)",
                ))

    # --- unbounded host buffers: the host-side KV leak ------------------
    _EVICTORS = ("pop", "popleft", "popitem", "clear")

    def _check_unbounded_buffers(self, fn):
        """unbounded-host-buffer over one Engine method: device-valued
        ``.append`` in a loop onto a container with no eviction (and no
        rebinding — ``self._log = self._log[-n:]`` is a trim) anywhere
        in the function."""
        dev_local: set[str] = set()
        evicted: set[str] = set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign):
                if isinstance(n.value, ast.Call) and _DEVICE_MAKERS.search(
                    _dotted(n.value.func)
                ):
                    for t in n.targets:
                        dev_local.update(_flat_targets(t))
                for t in n.targets:
                    if isinstance(t, (ast.Name, ast.Attribute)):
                        evicted.add(_dotted(t))
            elif isinstance(n, ast.Call) and isinstance(
                n.func, ast.Attribute
            ) and n.func.attr in self._EVICTORS:
                evicted.add(_dotted(n.func.value))
            elif isinstance(n, ast.Delete):
                for t in n.targets:
                    if isinstance(t, ast.Subscript):
                        evicted.add(_dotted(t.value))
        self._walk_appends(fn, 0, dev_local, evicted)

    def _walk_appends(self, node, depth, dev_local, evicted):
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            depth += 1
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "append"
            and depth > 0
            and node.args
        ):
            arg = node.args[0]
            is_dev = (
                isinstance(arg, ast.Call)
                and bool(_DEVICE_MAKERS.search(_dotted(arg.func)))
            ) or (isinstance(arg, ast.Name) and arg.id in dev_local)
            container = _dotted(node.func.value)
            if is_dev and container and container not in evicted:
                self.findings.append(Finding(
                    "ast", "unbounded-host-buffer",
                    f"{self.path}:{node.lineno}",
                    f"`{container}.append(...)` retains a device array "
                    "per loop iteration in an engine with no eviction "
                    "of the container in scope — the host-side KV leak: "
                    "each element pins its device buffer and the "
                    "resident set grows with requests served; cap the "
                    "container, evict on a schedule, or move the value "
                    "to host first",
                ))
        for child in ast.iter_child_nodes(node):
            self._walk_appends(child, depth, dev_local, evicted)

    # --- swallowed exceptions: failures that leave no trace -------------
    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        def is_noop(stmt):
            return isinstance(stmt, ast.Pass) or (
                isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant)
                and stmt.value.value is ...
            )

        reraises = any(
            isinstance(s, ast.Raise) for s in ast.walk(node)
        )
        if node.type is None:
            if not reraises:
                self.findings.append(Finding(
                    "ast", "swallowed-exception",
                    f"{self.path}:{node.lineno}",
                    "bare `except:` without a re-raise — catches "
                    "everything (including KeyboardInterrupt/SystemExit) "
                    "and the failure leaves no trace; catch the "
                    "narrowest type and record the error",
                ))
        else:
            broad = {
                _dotted(n)
                for n in (
                    node.type.elts
                    if isinstance(node.type, ast.Tuple) else [node.type]
                )
            } & {"Exception", "BaseException"}
            if broad and all(is_noop(s) for s in node.body):
                self.findings.append(Finding(
                    "ast", "swallowed-exception",
                    f"{self.path}:{node.lineno}",
                    f"`except {'/'.join(sorted(broad))}: pass` — the "
                    "failure vanishes without a record; catch the "
                    "narrowest type and at least record it to the "
                    "flight recorder",
                ))
        self.generic_visit(node)

    def _check_captures(self, node):
        params = {
            a.arg for a in (
                node.args.posonlyargs + node.args.args + node.args.kwonlyargs
            )
        }
        local = _bound_names(node)
        seen: set[str] = set()
        for n in ast.walk(node):
            if (
                isinstance(n, ast.Name)
                and isinstance(n.ctx, ast.Load)
                and n.id in self.device_names
                and n.id not in params
                and n.id not in local
                and n.id not in seen
            ):
                seen.add(n.id)
                self.findings.append(Finding(
                    "ast", "captured-device-array",
                    f"{self.path}:{n.lineno}",
                    f"jitted `{node.name}` closes over module-level "
                    f"device array `{n.id}` — it is baked into the "
                    "executable as a constant (replicated on every "
                    "device, invisible to donation); pass it as an "
                    "argument instead",
                ))


#: Mesh-axis names whose bare-literal spelling the ``axis-literal``
#: rule flags, and the source surfaces it polices. Kept textually in
#: sync with ``parallel.mesh.DATA_AXIS``/``MODEL_AXIS`` and
#: ``parallel.pipeline.PIPE_AXIS`` (a deliberate copy: the lint must
#: not import jax-loading modules to stay milliseconds-cheap).
_AXIS_LITERALS = frozenset({"data", "model", "pipe"})
_AXIS_LINT_DIRS = frozenset({"fleet", "analysis"})


def _axis_literal_findings(path: str, tree: ast.AST) -> list[Finding]:
    """``axis-literal`` over one parsed file — every string constant
    spelling a mesh-axis name in a fleet/ or analysis/ source file.
    Equality (not substring) keeps docstrings and prose out; the
    path gate keeps the canonical definition sites (parallel/) and the
    model layers out."""
    parts = pathlib.PurePosixPath(path).parts
    if not (_AXIS_LINT_DIRS & set(parts)):
        return []
    out: list[Finding] = []
    for n in ast.walk(tree):
        if (
            isinstance(n, ast.Constant)
            and isinstance(n.value, str)
            and n.value in _AXIS_LITERALS
        ):
            out.append(Finding(
                "ast", "axis-literal", f"{path}:{n.lineno}",
                f"hardcoded mesh-axis name {n.value!r} in a "
                "topology-aware surface — a single-axis mesh or a "
                "renamed axis silently misprices the tier; import "
                "DATA_AXIS/MODEL_AXIS/DEFAULT_AXIS_NAMES from "
                "parallel.mesh or thread the axis from the "
                "mesh/TopologyProfile in scope",
            ))
    return out


#: The modules allowed to touch codec primitives directly: the codec's
#: own definition site plus the two seams that COUNT what they move
#: (execute_transfer's wire/raw stats, the quantized ring's ledgered
#: payloads). Everything else must go through them.
_COMPRESSION_SEAMS = frozenset({
    "learning_jax_sharding_tpu/parallel/compression.py",
    "learning_jax_sharding_tpu/parallel/resharding.py",
    "learning_jax_sharding_tpu/parallel/collectives.py",
})

_CODEC_PRIMITIVES = frozenset({
    "quantize_blocks", "dequantize_blocks",
    "quantize_absmax", "dequantize_absmax",
})


def _compression_findings(path: str, tree: ast.AST) -> list[Finding]:
    """``uncounted-compression`` over one parsed file: direct codec
    primitive calls, or ``.encode``/``.decode`` on a codec-named
    receiver, outside the counted seams. The receiver-name gate keeps
    ``str.encode`` and tokenizer methods out — only a name/attribute
    ending in ``codec`` (``self._kv_codec.encode(...)``) counts."""
    if pathlib.PurePosixPath(path).as_posix() in _COMPRESSION_SEAMS:
        return []
    out: list[Finding] = []
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        dotted = _dotted(n.func)
        tail = dotted.rsplit(".", 1)[-1]
        hit = tail in _CODEC_PRIMITIVES
        if not hit and tail in ("encode", "decode") and "." in dotted:
            recv = dotted.rsplit(".", 1)[0].rsplit(".", 1)[-1]
            hit = recv.lower().endswith("codec")
        if hit:
            out.append(Finding(
                "ast", "uncounted-compression", f"{path}:{n.lineno}",
                f"direct codec call {dotted!r} outside the counted "
                "compression seams — bytes it produces never reach the "
                "*_raw_bytes counters or compression_ratio gauges; "
                "route the payload through plan_transfer(codec=...)/"
                "execute_transfer or parallel.collectives' quantized "
                "ring so the wire accounting stays whole",
            ))
    return out


def _raw_clock_findings(path: str, lines: list[str]) -> list[Finding]:
    out: list[Finding] = []
    for i, line in enumerate(lines):
        if not RAW_CLOCKS.search(line):
            continue
        lo, hi = max(0, i - SYNC_WINDOW), i + SYNC_WINDOW + 1
        if not any(SYNC_IDIOMS.search(l) for l in lines[lo:hi]):
            out.append(Finding(
                "ast", "raw-clock", f"{path}:{i + 1}",
                "raw wall-clock read with no sync idiom within "
                f"±{SYNC_WINDOW} lines — times dispatch, not execution; "
                "use utils.bench.measure/time_fn or read a result back "
                "before stopping the clock",
            ))
    return out


def lint_source(path: str | pathlib.Path, text: str | None = None) -> list[Finding]:
    """Lint ONE Python source file; ``path`` is the label findings carry
    (pass repo-relative paths so the baseline file stays portable)."""
    p = pathlib.Path(path)
    if text is None:
        text = p.read_text()
    lines = text.splitlines()
    out = _raw_clock_findings(str(path), lines)
    try:
        tree = ast.parse(text)
    except SyntaxError as e:
        return out + [Finding(
            "ast", "syntax-error", f"{path}:{e.lineno or 0}", str(e.msg),
        )]
    v = _Visitor(str(path), lines)
    v.visit(tree)
    return (
        out
        + _axis_literal_findings(str(path), tree)
        + _compression_findings(str(path), tree)
        + v.findings
    )


def lint_tree(
    root: str | pathlib.Path,
    *,
    include: Iterable[str] = ("learning_jax_sharding_tpu", "cases", "scripts", "bench.py"),
) -> list[Finding]:
    """Lint every ``.py`` under ``root``'s source surfaces (not tests/ —
    tests legitimately construct pathological jits on purpose). Paths in
    findings are repo-relative, stable for the baseline file."""
    root = pathlib.Path(root)
    files: list[pathlib.Path] = []
    for entry in include:
        p = root / entry
        if p.is_file():
            files.append(p)
        elif p.is_dir():
            files.extend(
                f for f in sorted(p.rglob("*.py"))
                if not any(part in _SKIP_DIRS for part in f.parts)
            )
    out: list[Finding] = []
    for f in files:
        out.extend(lint_source(f.relative_to(root).as_posix(), f.read_text()))
    return out


# --- baseline suppression -------------------------------------------------


def load_baseline(path: str | pathlib.Path) -> dict[tuple[str, str], int]:
    """``{(file, rule): allowed_count}`` from ``analysis/baseline.json``.
    A missing file is an empty baseline (everything gates)."""
    p = pathlib.Path(path)
    if not p.exists():
        return {}
    text = p.read_text()
    if not text.strip():   # empty file / /dev/null: everything gates
        return {}
    doc = json.loads(text)
    return {
        (s["file"], s["rule"]): int(s.get("count", 1))
        for s in doc.get("suppressions", [])
    }


def apply_baseline(
    findings: list[Finding], baseline: dict[tuple[str, str], int]
) -> list[Finding]:
    """Findings NOT covered by the baseline budget. Budgets are per
    (file, rule) counts — line numbers drift with every edit, counts
    only change when a finding is added or fixed. The baseline is a
    ceiling: a count below budget passes here, and
    ``tests/test_repo_lint.py`` separately fails on stale/loose budgets
    so the slack cannot silently accumulate."""
    used: dict[tuple[str, str], int] = {}
    out: list[Finding] = []
    for f in findings:
        key = (f.where.rsplit(":", 1)[0], f.rule)
        used[key] = used.get(key, 0) + 1
        if used[key] > baseline.get(key, 0):
            out.append(f)
    return out
