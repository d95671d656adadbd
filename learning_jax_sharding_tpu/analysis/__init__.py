"""Static sharding analysis: catch distributed-cost regressions pre-run.

Three levels, one finding type, one CLI (``scripts/shardcheck.py``):

1. **HLO contracts** (:mod:`.contracts`) — golden per-entry-point
   multisets of ``(collective op, mesh axis, byte bound)`` over compiled
   programs; drift (a new all-gather, a collective inside a while body,
   an oversized replicated constant) fails before a step runs.
2. **jaxpr / executable lint** (:mod:`.jaxpr_lint`, :mod:`.donation`) —
   silent f32 promotions in bf16 graphs, dead equations, and donations
   requested-but-dropped or eligible-but-never-requested, cross-checked
   against ``utils.memory.memory_plan``.
3. **AST source lint** (:mod:`.source_lint`) — jit-in-loop, non-hashable
   static args, closure-captured device arrays, raw unsynced clocks,
   host syncs inside engine hot loops; pre-existing findings ride
   ``analysis/baseline.json``.
4. **shardflow** (:mod:`.shardflow` + :mod:`.costmodel`) — the
   pre-compile layer: a GSPMD propagation simulator over the jaxpr
   predicts the collective multiset with per-source-line attribution
   and a roofline-priced step time, reconciled against the SAME golden
   contracts level 1 checks (an actual collective no predicted event
   explains is a gated ``unexplained-collective`` finding).
5. **memflow** (:mod:`.memflow`) — the memory face of level 4: a
   jaxpr-level liveness walk predicts per-device peak HBM (sharding-,
   donation- and scan/remat-aware), reconciled against
   ``compiled.memory_analysis()`` under baseline-pinned tolerances and
   gated against the device HBM budget (``shardcheck --memory``).
6. **comm** (:mod:`..telemetry.commscope`) — the measured face of
   level 4: run the commscope calibration ladder on the live mesh, fit
   per-axis α–β link profiles, gate the fit's reconciliation error
   against the baseline's ``commscope_tolerance_pct``, and re-price
   every entry point's predicted collectives with the MEASURED profile
   next to the pinned-table prediction (``shardcheck --comm``).
7. **topo** (:mod:`.topology`) — the hierarchy face of level 4: price
   every entry point under the two-tier (ICI|DCN) interconnect profile
   with the overlap-aware combination, reconcile against measured step
   seconds under baseline-pinned ``topo_tolerance_pct``, and gate
   golden-contract collectives that cross a DCN boundary the static
   model didn't predict (``unexplained-cross-tier-bytes``,
   ``shardcheck --topo``).

Static verdicts land in the PR-2 flight recorder / registry
(:func:`~.findings.report_findings`), so a post-mortem bundle shows what
the static layer already knew.
"""

from __future__ import annotations

import contextlib
import pathlib
import time

from learning_jax_sharding_tpu.analysis.contracts import (
    Contract,
    ShardingContractError,
    check_against_golden,
    check_contract,
    contract_of,
    enforce_contract,
)
from learning_jax_sharding_tpu.analysis.donation import (
    check_train_step_donation,
    donation_report,
    missed_donation_bytes,
)
from learning_jax_sharding_tpu.analysis.findings import (
    Finding,
    report_findings,
)
from learning_jax_sharding_tpu.analysis.jaxpr_lint import lint_fn, lint_jaxpr
from learning_jax_sharding_tpu.analysis.source_lint import (
    apply_baseline,
    lint_source,
    lint_tree,
    load_baseline,
)

#: Checked-in goldens / baseline, relative to the repo root.
GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "golden"
BASELINE_PATH = pathlib.Path(__file__).resolve().parent / "baseline.json"


@contextlib.contextmanager
def _program_timer(program_seconds: dict | None, name: str):
    """Accumulate one program's wall-clock into ``program_seconds`` (the
    ``shardcheck --timings`` attribution surface). Host-side only: the
    passes compile and walk jaxprs, they dispatch no device work, so
    there is nothing to sync before reading the clock."""
    if program_seconds is None:
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        program_seconds[name] = (
            program_seconds.get(name, 0.0) + time.perf_counter() - t0
        )


def run_contract_pass(
    golden_dir: str | pathlib.Path = GOLDEN_DIR,
    *,
    names: list[str] | None = None,
    update: bool = False,
    programs: list | None = None,
    baseline: str | pathlib.Path | None = BASELINE_PATH,
    program_seconds: dict | None = None,
) -> list[Finding]:
    """Compile every registered entry point (``analysis.entrypoints``)
    and diff its collective contract against the goldens. With
    ``update=True``, (re)write the goldens instead and return [].
    ``programs`` shares one ``build_entry_programs`` result across
    passes (their per-program caches hold the built state/step, so the
    jaxpr pass then reuses this pass's compiles instead of re-paying
    them). ``program_seconds`` accumulates per-program wall-clock for
    ``shardcheck --timings``.

    Per-entry byte slack: the ``oversized-collective`` rule multiplies
    each golden ``max_bytes`` by the slack pinned in the baseline
    file's ``contract_byte_slack`` section for that entry
    (:data:`~.contracts.DEFAULT_BYTE_SLACK` otherwise) — every pinned
    entry carries a dated justification in the baseline's notes, and
    count drift still gates at zero slack."""
    import json

    from learning_jax_sharding_tpu.analysis.contracts import (
        DEFAULT_BYTE_SLACK,
    )
    from learning_jax_sharding_tpu.analysis.entrypoints import (
        build_entry_programs,
    )

    slacks: dict = {}
    if baseline is not None:
        p = pathlib.Path(baseline)
        if p.exists() and p.read_text().strip():
            slacks = json.loads(p.read_text()).get(
                "contract_byte_slack", {})
    golden_dir = pathlib.Path(golden_dir)
    findings: list[Finding] = []
    for prog in (programs if programs is not None
                 else build_entry_programs(names)):
        with _program_timer(program_seconds, prog.name):
            observed = contract_of(prog.name, prog.hlo(), mesh=prog.mesh)
            if update:
                golden_dir.mkdir(parents=True, exist_ok=True)
                (golden_dir / f"{prog.name}.json").write_text(
                    observed.to_json())
            else:
                findings.extend(check_against_golden(
                    golden_dir, observed,
                    byte_slack=float(
                        slacks.get(prog.name, DEFAULT_BYTE_SLACK)
                    ),
                ))
    return findings


def run_jaxpr_pass(
    *,
    names: list[str] | None = None,
    baseline: str | pathlib.Path | None = BASELINE_PATH,
    programs: list | None = None,
    program_seconds: dict | None = None,
) -> list[Finding]:
    """Jaxpr lint over the train-shaped entry points and the donation
    audit over every entry point that has one: the train steps AND the
    engine's step programs, which donate their cache
    (``models/engine_programs.py::_donating``). The jaxpr rules (f32
    promotions, f32 dots in bf16 graphs, dead equations) gate through
    per-program budgets in the baseline file's ``jaxpr_budgets`` section —
    the framework's own traces carry a known population of trivially-DCE'd
    flax/optax internals (recorded as a ceiling, so NEW dead compute still
    fails), while the precision rules run at zero budget. The donation
    rules gate the same way through ``donation_budgets``:
    ``donation-not-applied`` at zero everywhere (a cache leaf the
    executable does not alias is a second pool in HBM), ``donation-missed``
    at the count of a program's small per-dispatch operands that happen to
    match an output (recorded with its reason)."""
    import json

    from learning_jax_sharding_tpu.analysis.entrypoints import (
        build_entry_programs,
    )

    sections: dict = {}
    if baseline is not None:
        p = pathlib.Path(baseline)
        if p.exists() and p.read_text().strip():
            sections = json.loads(p.read_text())

    def over_budget(found, section, name):
        # Findings beyond the program's per-rule ceiling in ``section``.
        allowed = sections.get(section, {}).get(name, {})
        used: dict[str, int] = {}
        for f in found:
            used[f.rule] = used.get(f.rule, 0) + 1
            if used[f.rule] > int(allowed.get(f.rule, 0)):
                yield f

    findings: list[Finding] = []
    for prog in (programs if programs is not None
                 else build_entry_programs(names)):
        with _program_timer(program_seconds, prog.name):
            if prog.donation is not None:
                findings.extend(over_budget(
                    prog.donation()["findings"], "donation_budgets",
                    prog.name,
                ))
            if prog.jaxpr is not None:
                findings.extend(
                    over_budget(prog.jaxpr(), "jaxpr_budgets", prog.name)
                )
    return findings


def run_shardflow_pass(
    golden_dir: str | pathlib.Path = GOLDEN_DIR,
    *,
    names: list[str] | None = None,
    programs: list | None = None,
    explain: bool = False,
    profile=None,
    program_seconds: dict | None = None,
) -> tuple[list[Finding], list[dict]]:
    """The pre-compile pass: simulate GSPMD propagation over every entry
    point's jaxpr (:mod:`.shardflow`), reconcile the predicted collective
    multiset against the checked-in golden contract, and price the
    prediction (:mod:`.costmodel`). Returns ``(findings, reports)``:
    findings are the gated ``unexplained-collective`` diffs (a compiled
    collective no predicted event explains — the simulator's rules
    drifted from the real partitioner, or new communication appeared
    that static analysis cannot attribute); reports are per-entry-point
    dicts with the reconciliation, the priced roofline, the top cost
    lines, and (``explain=True``) the rendered per-source-line
    attribution text. Entry points without a golden are skipped — the
    contract pass owns the no-golden finding."""
    from learning_jax_sharding_tpu.analysis import costmodel
    from learning_jax_sharding_tpu.analysis.entrypoints import (
        build_entry_programs,
    )
    from learning_jax_sharding_tpu.analysis.shardflow import (
        reconcile,
        reconcile_findings,
        render_explanation,
    )

    golden_dir = pathlib.Path(golden_dir)
    if profile is None:
        profile = costmodel.current_profile()
    findings: list[Finding] = []
    reports: list[dict] = []
    for prog in (programs if programs is not None
                 else build_entry_programs(names)):
        if prog.shardflow is None:
            continue
        path = golden_dir / f"{prog.name}.json"
        if not path.exists():
            continue
        with _program_timer(program_seconds, prog.name):
            rep = prog.shardflow()
            result = reconcile(rep, Contract.load(path))
            findings.extend(reconcile_findings(result))
            cost = costmodel.price(rep, profile)
            entry = {
                "name": prog.name,
                "reconcile": result,
                "cost": cost.to_dict(),
                "top_events": costmodel.rank_events(rep, profile),
            }
            if explain:
                entry["explanation"] = render_explanation(rep)
        reports.append(entry)
    return findings, reports


def run_memflow_pass(
    *,
    names: list[str] | None = None,
    baseline: str | pathlib.Path | None = BASELINE_PATH,
    budget_bytes: float | None = None,
    headroom: float = 0.8,
    mesh=None,
    program_seconds: dict | None = None,
) -> tuple[list[Finding], list[dict]]:
    """The memory face of the shardflow pass (``shardcheck --memory``):
    for every searchable entry point, run :mod:`.memflow`'s jaxpr-level
    liveness analysis (sharding- and donation-aware), reconcile the
    predicted per-device peak against ``compiled.memory_analysis()``
    under the per-entry tolerance pinned in the baseline file's
    ``memflow_tolerance_pct`` section, and gate peaks that exceed
    ``budget_bytes x headroom``. With ``budget_bytes=None`` the budget
    defaults to :func:`utils.memory.device_hbm_bytes` — ``None`` on
    emulated-CPU hosts, where only the reconciliation gates."""
    import json

    from learning_jax_sharding_tpu.analysis import memflow
    from learning_jax_sharding_tpu.analysis.entrypoints import (
        SEARCHABLE_ENTRIES,
    )
    from learning_jax_sharding_tpu.utils.memory import device_hbm_bytes

    tolerances: dict = {}
    if baseline is not None:
        p = pathlib.Path(baseline)
        if p.exists() and p.read_text().strip():
            tolerances = json.loads(p.read_text()).get(
                "memflow_tolerance_pct", {})
    if budget_bytes is None:
        budget_bytes = device_hbm_bytes()
    findings: list[Finding] = []
    reports: list[dict] = []
    for name in SEARCHABLE_ENTRIES:
        if names is not None and name not in names:
            continue
        with _program_timer(program_seconds, name):
            analysis = memflow.analyze_entry(name, mesh)
            tol = tolerances.get(name)
            findings.extend(memflow.memory_findings(
                analysis,
                budget_bytes=budget_bytes,
                headroom=headroom,
                tolerance_pct=float(tol) if tol is not None else None,
            ))
        reports.append({
            "name": name,
            "report": analysis["report"].to_dict(),
            "reconciled": analysis["reconciled"],
            "donated": analysis["donated"],
        })
    return findings, reports


def run_comm_pass(
    *,
    names: list[str] | None = None,
    baseline: str | pathlib.Path | None = BASELINE_PATH,
    mesh=None,
    programs: list | None = None,
    profile=None,
    ops: tuple[str, ...] = ("psum", "all_gather", "ppermute"),
    sizes_bytes: tuple[int, ...] = (1 << 16, 1 << 19, 1 << 22),
    program_seconds: dict | None = None,
) -> tuple[list[Finding], dict]:
    """The measured face of the shardflow pass (``shardcheck --comm``):
    run the commscope calibration ladder (a REDUCED sweep — three ops,
    three sizes — sized for CI) on the entry points' mesh, fit per-axis
    α–β link profiles, gate the fit's worst per-axis reconciliation
    error against the ceilings pinned in the baseline file's
    ``commscope_tolerance_pct`` section, and re-price every entry
    point's predicted collective multiset with the measured profile —
    the per-line pinned-prediction vs measured-profile table.

    Returns ``(findings, report)`` where ``report`` is JSON-plain:
    ``{"profile": <CommProfile dict>, "fit_errors_pct": {axis: pct},
    "programs": [{"name", "pinned_s", "measured_s", "lines": [...]}]}``.
    Opt-in only (not part of the budgeted full run): the ladder times
    real dispatches, so it costs wall-clock the static passes don't.
    """
    import json

    from learning_jax_sharding_tpu.analysis import costmodel
    from learning_jax_sharding_tpu.analysis.entrypoints import (
        build_entry_programs,
    )
    from learning_jax_sharding_tpu.telemetry import commscope

    tolerances: dict = {}
    if baseline is not None:
        p = pathlib.Path(baseline)
        if p.exists() and p.read_text().strip():
            tolerances = json.loads(p.read_text()).get(
                "commscope_tolerance_pct", {})
    progs = (programs if programs is not None
             else build_entry_programs(names))
    if mesh is None:
        if not progs:
            raise ValueError("run_comm_pass needs a mesh or ≥1 program")
        mesh = progs[0].mesh

    findings: list[Finding] = []
    with _program_timer(program_seconds, "commscope_ladder"):
        comm_profile = commscope.calibrate_mesh(
            mesh, ops=ops, sizes_bytes=sizes_bytes,
        )
    errs = commscope.fit_errors(comm_profile.axes,
                                comm_profile.measurements)
    default_tol = tolerances.get("_default")
    for axis, err in sorted(errs.items()):
        tol = tolerances.get(axis, default_tol)
        if tol is not None and err > float(tol):
            findings.append(Finding(
                "comm", "commscope-fit-tolerance", f"mesh axis {axis!r}",
                f"α–β fit misses its own ladder measurements by "
                f"{err:.1f}% (worst cell), over the {float(tol):.1f}% "
                "ceiling pinned in baseline.json — the link is not "
                "α–β-linear here (noisy host, cache cliff, or the sweep "
                "sizes need rebalancing); re-run scripts/commscope.py "
                "and re-justify the tolerance",
                data={"axis": axis, "err_pct": round(err, 2),
                      "tolerance_pct": float(tol)},
            ))

    base = profile if profile is not None else costmodel.current_profile()
    calibrated = costmodel.calibrate_axis_profiles(comm_profile, base=base)
    prog_rows: list[dict] = []
    for prog in progs:
        if prog.shardflow is None:
            continue
        with _program_timer(program_seconds, prog.name):
            rep = prog.shardflow()
            pinned = commscope.line_comm_predictions(rep, base)
            measured = commscope.line_comm_predictions(rep, calibrated)
        lines = [
            {
                "where": w,
                "pinned_s": pinned[w],
                "measured_s": measured.get(w, 0.0),
            }
            for w in sorted(pinned, key=lambda w: -pinned[w])
        ]
        prog_rows.append({
            "name": prog.name,
            "pinned_s": sum(pinned.values()),
            "measured_s": sum(measured.values()),
            "lines": lines,
        })
    report = {
        "profile": comm_profile.to_dict(),
        "fit_errors_pct": {a: round(e, 2) for a, e in sorted(errs.items())},
        "programs": prog_rows,
    }
    return findings, report


def run_topo_pass(
    *,
    names: list[str] | None = None,
    baseline: str | pathlib.Path | None = BASELINE_PATH,
    golden_dir: str | pathlib.Path = GOLDEN_DIR,
    mesh=None,
    topology=None,
    profile=None,
    min_time: float = 0.15,
    program_seconds: dict | None = None,
) -> tuple[list[Finding], dict]:
    """The hierarchy face of the shardflow pass (``shardcheck --topo``):
    re-price every searchable entry point under the two-tier
    :class:`~.topology.TopologyProfile` (checked-in
    ``analysis/profiles/topology_<platform>_<shape>.json`` when present,
    else calibrated live from a reduced commscope ladder), measure each
    program's actual step seconds on the live mesh, and gate two ways:

    * ``topo-reconcile-tolerance`` — the overlap-aware prediction
      (``max(compute, memory) + exposed comm``) misses the measured
      step time by more than the per-entry ceiling pinned in the
      baseline file's ``topo_tolerance_pct`` section (``_default``
      fallback).
    * ``unexplained-cross-tier-bytes`` — the GOLDEN contract carries
      collectives on DCN-tier axes whose ceiling bytes
      (``count × max_bytes``) exceed the shardflow-predicted DCN-bucket
      bytes × the ``topo_byte_slack`` pinned for the entry: cross-domain
      traffic the static model cannot attribute. Contract groups on
      wildcard axes (``@unattributed``/``@none``) stay out of the audit
      — their axis is unknown by construction and the shardflow pass
      already reconciles their counts.

    Returns ``(findings, report)``; the report is JSON-plain with the
    resolved topology, per-program measured/predicted seconds (serial
    vs overlap-aware, so the "closer than serial-sum" claim is
    auditable), the realized overlap decomposition
    (:func:`~..telemetry.commscope.decompose_overlap`), and the
    ICI/DCN byte split. Opt-in like ``--comm``: it times real
    dispatches and pays one jit compile per entry point."""
    import json

    import jax
    import jax.numpy as jnp

    from learning_jax_sharding_tpu.analysis import costmodel
    from learning_jax_sharding_tpu.analysis import topology as topo_mod
    from learning_jax_sharding_tpu.analysis.entrypoints import (
        SEARCHABLE_ENTRIES,
        build_search_inputs,
    )
    from learning_jax_sharding_tpu.analysis.shardflow import trace_shardflow
    from learning_jax_sharding_tpu.parallel.logical import activate
    from learning_jax_sharding_tpu.telemetry import commscope
    from learning_jax_sharding_tpu.utils.bench import time_fn

    tolerances: dict = {}
    slacks: dict = {}
    if baseline is not None:
        p = pathlib.Path(baseline)
        if p.exists() and p.read_text().strip():
            doc = json.loads(p.read_text())
            tolerances = doc.get("topo_tolerance_pct", {})
            slacks = doc.get("topo_byte_slack", {})
    golden_dir = pathlib.Path(golden_dir)

    entries = [
        n for n in SEARCHABLE_ENTRIES if names is None or n in names
    ]
    built = {}
    for n in entries:
        with _program_timer(program_seconds, f"{n}:build"):
            built[n] = build_search_inputs(n, mesh)
    if not built:
        raise ValueError("run_topo_pass matched no searchable entry")
    first = built[entries[0]]["mesh"]

    platform = jax.devices()[0].platform
    if topology is None:
        shape = tuple(int(first.shape[a]) for a in first.axis_names)
        path = topo_mod.TopologyProfile.default_path(platform, shape)
        if path.exists():
            topology = topo_mod.TopologyProfile.load(path)
        else:
            # No checked-in profile for this platform/mesh: calibrate
            # live (reduced ladder, same sweep as --comm) and tag with
            # the canonical tier map.
            with _program_timer(program_seconds, "topo_calibrate"):
                topology = topo_mod.TopologyProfile.from_comm_profile(
                    commscope.calibrate_mesh(
                        first,
                        ops=("psum", "all_gather", "ppermute"),
                        sizes_bytes=(1 << 16, 1 << 19, 1 << 22),
                    ),
                )
    if profile is None:
        profile = costmodel.current_profile()

    default_tol = tolerances.get("_default")
    default_slack = float(slacks.get("_default", 1.25))
    findings: list[Finding] = []
    prog_rows: list[dict] = []
    for name in entries:
        t = built[name]
        t_mesh = t["mesh"]
        mesh_sizes = {
            str(a): int(t_mesh.shape[a]) for a in t_mesh.axis_names
        }
        with _program_timer(program_seconds, name):
            with activate(t_mesh, t["rules"]):
                rep = trace_shardflow(
                    name, t["fn"], *t["args"], mesh=t_mesh,
                    while_trip_hint=t["while_trip_hint"], **t["kwargs"],
                )
                jitted = jax.jit(t["fn"])
                timed = jitted
                if platform == "cpu":
                    # Emulated hosts run collectives as an in-process
                    # host-thread rendezvous; with many async executions
                    # of a partitioned module in flight, per-device
                    # execute threads can pick runs up in different
                    # orders and deadlock one run's rendezvous behind
                    # another's (observed on a 1-core container ~1 min
                    # into the pass). Serialize executions there — a
                    # real accelerator keeps the latency-cancelling
                    # async form.
                    def timed(*a, _j=jitted, **k):
                        return jax.block_until_ready(_j(*a, **k))
                measured_s = time_fn(
                    timed, *t["args"], min_time=min_time, repeats=2,
                    **t["kwargs"],
                )
            flat_cost = costmodel.price(rep, profile)
            topo_cost = costmodel.price_topo(
                rep, profile, topology=topology,
            )
        floor = max(topo_cost.compute_s, topo_cost.memory_s)
        decomp = commscope.decompose_overlap(
            measured_s, floor, topo_cost.comm.serial_s,
        )
        # Tokens the dispatch touches — the largest 2-D integer operand
        # (the (B, S) token batch for train entries, the padded token
        # buffer for engine dispatches). Lets bench normalize the DCN
        # bucket to bytes/token; 0 when the entry carries no token
        # operand.
        tokens = max(
            (
                int(leaf.shape[0]) * int(leaf.shape[1])
                for leaf in jax.tree.leaves((t["args"], t["kwargs"]))
                if getattr(leaf, "ndim", 0) == 2
                and jnp.issubdtype(leaf.dtype, jnp.integer)
            ),
            default=0,
        )
        err_topo = (
            abs(topo_cost.predicted_s - measured_s) / measured_s * 100.0
            if measured_s > 0 else 0.0
        )
        err_serial = (
            abs(topo_cost.serial_predicted_s - measured_s)
            / measured_s * 100.0 if measured_s > 0 else 0.0
        )
        tol = tolerances.get(name, default_tol)
        if tol is not None and err_topo > float(tol):
            findings.append(Finding(
                "topo", "topo-reconcile-tolerance", name,
                f"overlap-aware prediction {topo_cost.predicted_s:.4g}s "
                f"misses measured {measured_s:.4g}s by {err_topo:.1f}%, "
                f"over the {float(tol):.1f}% ceiling pinned in "
                "baseline.json — the two-tier profile or the overlap "
                "table drifted from this host; re-run "
                "scripts/topo_profile.py and re-justify the tolerance",
                data={"entry": name, "err_pct": round(err_topo, 2),
                      "tolerance_pct": float(tol)},
            ))

        # Cross-tier byte audit: golden-contract collectives on
        # DCN-tier axes vs the shardflow-predicted DCN bucket.
        predicted_dcn = topo_cost.comm.dcn_bytes
        observed_dcn = 0.0
        observed_keys: list[str] = []
        gpath = golden_dir / f"{name}.json"
        if gpath.exists():
            golden = Contract.load(gpath)
            for key, grp in golden.collectives.items():
                _op, _, ax = key.partition("@")
                parts = tuple(ax.split("+"))
                if any(p not in mesh_sizes for p in parts):
                    continue  # wildcard axis: unattributable
                if topology.bucket(parts) == topo_mod.TIER_DCN:
                    observed_dcn += (
                        int(grp["count"]) * int(grp["max_bytes"])
                    )
                    observed_keys.append(key)
        slack = float(slacks.get(name, default_slack))
        if observed_dcn > predicted_dcn * slack:
            findings.append(Finding(
                "topo", "unexplained-cross-tier-bytes", name,
                f"compiled contract moves {observed_dcn:.0f} ceiling "
                f"bytes across the DCN tier ({', '.join(observed_keys)}) "
                f"but shardflow only predicts {predicted_dcn:.0f} "
                f"DCN-bucket bytes (slack ×{slack:g}) — cross-domain "
                "traffic the static model cannot attribute; fix the "
                "propagation rules or re-justify topo_byte_slack in "
                "baseline.json",
                data={"entry": name,
                      "observed_dcn_bytes": round(observed_dcn),
                      "predicted_dcn_bytes": round(predicted_dcn),
                      "slack": slack},
            ))
        prog_rows.append({
            "name": name,
            "measured_s": measured_s,
            "flat_predicted_s": flat_cost.predicted_s,
            "topo_predicted_s": topo_cost.predicted_s,
            "serial_predicted_s": topo_cost.serial_predicted_s,
            "err_topo_pct": round(err_topo, 2),
            "err_serial_pct": round(err_serial, 2),
            "overlap_ratio_used": topo_cost.comm.overlap_ratio,
            "realized": decomp,
            "ici_bytes": topo_cost.comm.ici_bytes,
            "dcn_bytes": topo_cost.comm.dcn_bytes,
            "observed_dcn_bytes": observed_dcn,
            "tokens_per_step": tokens,
        })
    report = {
        "topology": topology.to_dict(),
        "programs": prog_rows,
    }
    return findings, report


def run_ast_pass(
    root: str | pathlib.Path,
    *,
    baseline: str | pathlib.Path | None = BASELINE_PATH,
) -> list[Finding]:
    """Repo-wide source lint under the baseline budget."""
    findings = lint_tree(root)
    budget = load_baseline(baseline) if baseline else {}
    return apply_baseline(findings, budget)


__all__ = [
    "BASELINE_PATH",
    "Contract",
    "Finding",
    "GOLDEN_DIR",
    "ShardingContractError",
    "enforce_contract",
    "apply_baseline",
    "check_against_golden",
    "check_contract",
    "check_train_step_donation",
    "contract_of",
    "donation_report",
    "lint_fn",
    "lint_jaxpr",
    "lint_source",
    "lint_tree",
    "load_baseline",
    "missed_donation_bytes",
    "report_findings",
    "run_ast_pass",
    "run_comm_pass",
    "run_contract_pass",
    "run_jaxpr_pass",
    "run_memflow_pass",
    "run_shardflow_pass",
    "run_topo_pass",
]
