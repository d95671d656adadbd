#!/usr/bin/env python
"""Regression gate over the bench trajectory: diff BENCH_r*.json rounds.

The driver appends one ``BENCH_r{N}.json`` per round — the headline JSON
line under ``"parsed"`` plus the full stderr context under ``"tail"``. This
script makes the trajectory MACHINE-CHECKABLE instead of eyeballed: it
extracts every named metric from the two most recent rounds (or any two
given explicitly), prints the per-metric % delta, and exits non-zero when
any metric regressed past the threshold in its OWN bad direction (tok/s,
TFLOP/s, MFU, MBU, agreement: lower is worse; ms/step, ms/token-step,
latency ms, seconds: higher is worse).

Usage:
    python scripts/bench_compare.py [--threshold 0.10] [--repo DIR] [--json]
    python scripts/bench_compare.py old.json new.json [--threshold 0.10]

The gate also cross-checks the NEW round's collective inventory (the
bench JSON line's ``telemetry.headline_collectives``) against the golden
SPMD contract ``analysis/golden/bench_headline.json`` (shardcheck's
declarative layer): a bench round whose headline executable suddenly
contains collectives the contract doesn't admit fails exactly like a
metric regression — communication drift IS a perf regression, it just
shows up in HLO before it shows up in tok/s. Rounds without a telemetry
block (pre-PR-1 rounds) skip the check with a note.

Exit codes: 0 clean, 1 regression past threshold or collective-inventory
drift, 2 not enough rounds.

Metrics that appear in only one round (benches come and go) are reported
as added/removed, never failed — the gate compares what is comparable.
Rounds 1-5 ran on a remotely attached chip that drifted ±30% across
windows, so the default threshold is deliberately loose (the drift of
today's machine is not measured); tighten per-invocation when
comparing same-session runs.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys

#: (regex over one `[bench] name: ...` line tail, metric suffix,
#:  higher_is_better). Applied per line; the metric key is the bench line's
#: name plus the suffix, so every line's numbers stay distinct.
_PATTERNS: list[tuple[re.Pattern, str, bool]] = [
    (re.compile(r"([\d,.]+)\s*tok/s"), "tok_s", True),
    (re.compile(r"([\d.]+)\s*TFLOP/s/chip"), "tflops", True),
    (re.compile(r"(?<![-\w])MFU=([\d.]+)%"), "mfu_pct", True),
    (re.compile(r"activated-MFU=([\d.]+)%"), "act_mfu_pct", True),
    (re.compile(r"MBU=([\d.]+)%"), "mbu_pct", True),
    (re.compile(r"([\d.]+)\s*ms/step"), "ms_per_step", False),
    (re.compile(r"([\d.]+)\s*ms/token-step"), "ms_per_token", False),
    (re.compile(r"([\d.]+)\s*us/forward"), "us_per_forward", False),
    (re.compile(r"TTFT p50 ([\d.]+)\s*ms"), "ttft_p50_ms", False),
    (re.compile(r"p99 ([\d.]+)\s*ms"), "p99_ms", False),
    # Round-9 serving-latency gates: ITL p99 and queue wait are the
    # numbers the mixed engine exists to hold down; refill share and
    # decode-stall share regress UPWARD when decode re-stalls behind
    # refill — all four are direction-aware like every other metric.
    (re.compile(r"ITL p99 ([\d.]+)\s*ms"), "itl_p99_ms", False),
    (re.compile(r"queue wait p50 ([\d.]+)\s*ms"), "queue_wait_p50_ms",
     False),
    (re.compile(r"refill ([\d.]+)% of engine time"), "refill_share_pct",
     False),
    (re.compile(r"decode stalled ([\d.]+)%"), "decode_stall_share_pct",
     False),
    # Round-10 recovery-policy gates: with no faults injected the
    # tracked line must hold shed and deadline-miss at ~0 — a robustness
    # hook that starts shedding or missing TTLs under clean load IS a
    # latency regression, caught here before it ships.
    (re.compile(r"shed ([\d.]+)%"), "shed_rate_pct", False),
    (re.compile(r"deadline miss ([\d.]+)%"), "deadline_miss_pct", False),
    (re.compile(r"agreement vs plain: ([\d.]+)%"), "agreement_pct", True),
    # Round-11 fleet gates: the tracked fleet lines report AGGREGATE
    # throughput and router-side end-to-end tail latency per replica
    # count — both direction-aware (the generic tok/s pattern also
    # matches the aggregate number; these keep the fleet-specific names
    # stable even if the line's phrasing around them changes).
    (re.compile(r"aggregate ([\d,.]+)\s*tok/s"), "aggregate_tok_s", True),
    (re.compile(r"e2e p99 ([\d,.]+)\s*ms"), "e2e_p99_ms", False),
    # Round-12 tenancy gates: the hot-swap lines track the stall the
    # zero-downtime machinery exists to bound (stage → commit serve
    # gap, regresses UPWARD); the multi-LoRA lines track the fused
    # mixed-batch throughput, the serial solo baseline, and their ratio
    # — all higher-is-better (the ratio regressing means the per-row
    # adapter gather got more expensive relative to folded weights).
    (re.compile(r"swap stall p99 ([\d,.]+)\s*ms"), "swap_stall_p99_ms",
     False),
    # Round-13 shardflow gate: the cost model's predicted-vs-measured
    # step-time error per tracked line (bench.py's `[bench] shardflow
    # ...` lines). Lower is better — the error growing means the
    # propagation rules or the platform profile drifted from the real
    # machine, the analyzer's own regression signal.
    (re.compile(r"model err ([\d,.]+)%"), "predicted_vs_measured_pct",
     False),
    (re.compile(r"mixed ([\d,.]+)\s*tok/s"), "mixed_tok_s", True),
    (re.compile(r"solo ([\d,.]+)\s*tok/s"), "solo_tok_s", True),
    (re.compile(r"([\d.]+)x solo"), "vs_solo_ratio", True),
    # Round-14 goodput-ledger gates (bench.py's `[bench] goodput:` line):
    # host_share is the fraction of the engine's busy wall spent outside
    # the device bucket — THE number ROADMAP item 1 pushes down, so it
    # regresses UPWARD; goodput_ratio (roofline seconds over window
    # wall) regresses DOWNWARD; the telemetry self-overhead share must
    # stay pinned near zero (perf_goodput.py's <2% budget); the
    # trace-derived TTFT critical-path tails regress upward like every
    # latency metric.
    (re.compile(r"host_share ([\d,.]+)%"), "host_share_pct", False),
    (re.compile(r"goodput_ratio ([\d,.]+)%"), "goodput_ratio_pct", True),
    (re.compile(r"telemetry overhead ([\d,.]+)%"),
     "telemetry_overhead_pct", False),
    (re.compile(r"critical path p50 ([\d,.]+)\s*ms"), "ttft_cp_p50_ms",
     False),
    # Round-15 KV-economy gates (bench.py's `[bench] kv economy ...`
    # A/B lines): fleet TTFT p99 tracked explicitly (the generic `p99`
    # pattern predates comma grouping); the realized prefix-hit rate is
    # the placement-quality number (higher); the tier-miss rate counts
    # routing predictions admission could not realize — graceful
    # re-prefill, never a wrong token, but each one wasted a placement
    # (lower); kv moved is what the tier ladder pays the host/peer
    # buses per request — every byte is ledgered, fewer is cheaper
    # (lower).
    (re.compile(r"TTFT p99 ([\d,.]+)\s*ms"), "ttft_p99_ms", False),
    (re.compile(r"prefix hit ([\d,.]+)%"), "prefix_hit_rate_pct", True),
    (re.compile(r"tier miss ([\d,.]+)%"), "tier_miss_rate_pct", False),
    (re.compile(r"kv moved ([\d,.]+)\s*kB/req"),
     "kv_bytes_moved_per_req_kb", False),
    # Round-16 multi-step gates (bench.py's `[bench] multistep ...`
    # lines): steps/dispatch is engine iterations fused per host
    # round-trip — THE number the device-resident scheduler exists to
    # push up (1.0 means the host touched Python every token); it pairs
    # with host_share_pct above, which the same refactor pushes down.
    # Boundary-stall share is the fraction of engine busy time parked at
    # horizon boundaries waiting on the single sync + re-plan — the
    # async planner holds it down, so it regresses UPWARD.
    (re.compile(r"steps/dispatch ([\d,.]+)"), "steps_per_dispatch", True),
    (re.compile(r"boundary stall ([\d,.]+)%"), "boundary_stall_pct",
     False),
    # Round-17 layout-search gates (bench.py's `[bench] layout_search
    # ...` lines): `layout gap` is the priced searched-vs-hand gap — a
    # growing gap means the committed hand layouts drifted away from the
    # searchable optimum (down is better; 0 = hand layout already
    # argmin); `layout err` is the search-specific predicted-vs-measured
    # error on the two layouts it actually compiles (phrased distinctly
    # from the shardflow pass's `model err` so the two gates never
    # double-match one line).
    (re.compile(r"layout gap ([\d,.]+)%"), "layout_search_gap_pct",
     False),
    (re.compile(r"layout err ([\d,.]+)%"),
     "layout_predicted_vs_measured_pct", False),
    # Round-18 memflow gates (bench.py's `[bench] memflow ...` lines):
    # `memflow err` is the static liveness analyzer's per-entry
    # predicted-vs-measured peak-HBM error against XLA's
    # ``compiled.memory_analysis()`` — phrased distinctly from `model
    # err` (shardflow time) and `layout err` (layout search) so the
    # three analyzer gates never double-match one line. Lower is
    # better: the error growing means the liveness model (donation
    # credits, scan high-water, sharded buffer sizing) drifted from
    # what XLA actually allocates, which is the OOM-gate's accuracy.
    (re.compile(r"memflow err ([\d,.]+)%"),
     "memflow_predicted_vs_measured_pct", False),
    # Round-19 commscope gates (bench.py's `[bench] commscope ...`
    # lines): per-axis measured link bandwidth from the calibration
    # ladder (higher — the fitted β dropping means dispatch overheads
    # crept into the collectives themselves); `comm fit err` is the
    # α–β model's worst-cell error against its own ladder (lower);
    # `exposed comm` is the share of the serving window's device
    # seconds NOT hidden behind compute (lower — the overlap goal);
    # `comm prediction err` is the calibrated costmodel's serial
    # prediction vs the measured device bucket (lower; phrased
    # distinctly from `model err` / `layout err` / `memflow err` so
    # the four analyzer gates never double-match one line). The
    # `overlap ratio` on the same line is deliberately NOT gated:
    # overlapping more or less comm is a scheduling outcome, not
    # monotonic goodness.
    (re.compile(r"axis bandwidth ([\d,.]+)\s*GB/s"),
     "comm_axis_bandwidth_gb_s", True),
    (re.compile(r"comm fit err ([\d,.]+)%"), "comm_fit_err_pct", False),
    (re.compile(r"exposed comm ([\d,.]+)% of device"),
     "exposed_comm_share_pct", False),
    (re.compile(r"comm prediction err ([\d,.]+)%"),
     "comm_model_err_pct", False),
    # Round-20 workload-observatory gates (bench.py's `[bench] economics
    # ...` line): fleet-wide cost per generated token on the canonical
    # replayed day (lower — the economics JOIN pricing the same trace
    # getting dearer means capacity got wasted somewhere); the worst
    # tenant's SLO burn rate (lower; 0.00 on a clean round, and the
    # zero-old floor above means any burn past the threshold fails the
    # gate rather than sailing through on a div-by-zero pass). The
    # line's `goodput_ratio ...%` is picked up by the round-14 pattern.
    (re.compile(r"cost/token ([\d,.]+)\s*u\$"), "cost_per_token_uusd",
     False),
    (re.compile(r"worst tenant burn ([\d,.]+)"),
     "worst_tenant_burn_rate", False),
    # Round-21 topology gates (bench.py's `[bench] topo ...` lines):
    # `topo err` is the overlap-aware two-tier prediction's error vs the
    # measured step per searchable entry (lower; phrased distinctly from
    # `model err` / `layout err` / `memflow err` / `comm prediction
    # err` so the five analyzer gates never double-match one line);
    # `dcn B/token` is what the static model prices across the slow
    # tier per trained token (lower — growth means a layout or
    # propagation change started shipping gradients over DCN); `overlap
    # gap` is the pinned profile overlap ratio vs the ledger's realized
    # one in percentage points (lower — drift means the overlap table
    # no longer describes this host). `topo argmin gap` is the seeded
    # two-tier canary: flat-argmin re-priced under the hierarchy vs the
    # topology-aware argmin — deterministic abstract pricing, so it is
    # the one HIGHER-is-better analyzer gate (the gap collapsing to 0
    # means hierarchy pricing lost its discrimination power, not that
    # anything got faster).
    (re.compile(r"topo err ([\d,.]+)%"), "topo_reconcile_err_pct",
     False),
    (re.compile(r"([\d,.]+)\s*dcn B/token"), "dcn_bytes_per_token",
     False),
    (re.compile(r"overlap gap ([\d,.]+)\s*pp"),
     "overlap_predicted_vs_realized_pp", False),
    (re.compile(r"topo argmin gap ([\d,.]+)%"), "topo_argmin_gap_pct",
     True),
    # Round-22 comm-compression gates (bench.py's `[bench] comm
    # compression ...` lines): `compressed N tok/s` is the int8-wire
    # mixed engine's throughput (higher — on the emulated host it pays
    # the codec without the wire win, so the gate catches the codec
    # path bloating); `q8 agreement` is the greedy token match vs the
    # plain engine, which the drift oracle holds at 100% (phrased
    # distinctly from the speculative pass's `agreement vs plain:`);
    # `kv wire` is the post-codec kB the tier ladder actually moved per
    # request (lower; distinct from round-15's pre-codec `kv moved`);
    # `compression ratio` is raw/wire over the same window (higher —
    # it collapsing toward 1 means pages stopped compressing, e.g. a
    # dtype or codec regression upstream of the ledger).
    (re.compile(r"compressed ([\d,.]+)\s*tok/s"), "compressed_tok_s",
     True),
    (re.compile(r"q8 agreement ([\d,.]+)%"), "q8_agreement_pct", True),
    (re.compile(r"kv wire ([\d,.]+)\s*kB/req"),
     "kv_wire_bytes_per_req_kb", False),
    (re.compile(r"compression ratio ([\d,.]+)x"),
     "comm_compression_ratio", True),
    # Round-23 elastic-fleet gates (scripts/replay.py --autoscale's
    # `[bench] autoscale replay ...` line): `elastic N uusd/tok` is the
    # autoscaled fleet's provisioned cost per generated token on the
    # canonical day (lower — and the same line carries the best static
    # fleet's number as ungated context, phrased `static N uusd/tok`,
    # deliberately NOT matching round-20's `cost/token N u$` serving-
    # cost gate); `drain p99` is the scale-in drain-and-migrate wall
    # tail, THE latency the elastic path adds (lower); `planner gap`
    # is the capacity planner's K(t) integral vs the live controller's,
    # in % of planned replica-seconds (lower — widening means either
    # the planner's model or the controller's judgement drifted;
    # phrased distinctly from `layout gap` / `overlap gap` / `topo
    # argmin gap` so no two gap gates double-match one line).
    (re.compile(r"elastic ([\d,.]+)\s*uusd/tok"),
     "autoscale_cost_per_token_uusd", False),
    (re.compile(r"drain p99 ([\d,.]+)\s*ms"), "scale_in_drain_ms_p99",
     False),
    (re.compile(r"planner gap ([\d,.]+)%"), "planner_vs_live_gap_pct",
     False),
]

_NAME_RE = re.compile(r"\[bench\]\s+([^:]+):")


def _round_of(path: pathlib.Path) -> int:
    m = re.search(r"BENCH_r(\d+)\.json$", path.name)
    return int(m.group(1)) if m else -1


def extract_metrics(doc: dict) -> dict[str, tuple[float, bool]]:
    """``{metric: (value, higher_is_better)}`` from one round's record."""
    out: dict[str, tuple[float, bool]] = {}
    parsed = doc.get("parsed") or {}
    if isinstance(parsed.get("value"), (int, float)):
        out["headline:" + str(parsed.get("metric", "value"))] = (
            float(parsed["value"]), True,
        )
    if isinstance(parsed.get("vs_baseline"), (int, float)):
        out["headline:vs_baseline"] = (float(parsed["vs_baseline"]), True)
    for line in (doc.get("tail") or "").splitlines():
        nm = _NAME_RE.search(line)
        if nm is None:
            continue
        name = re.sub(r"\s+", "_", nm.group(1).strip())
        for pat, suffix, higher in _PATTERNS:
            m = pat.search(line)
            if m is None:
                continue
            key = f"{name}:{suffix}"
            if key in out:   # first occurrence wins (ladder lines repeat)
                continue
            out[key] = (float(m.group(1).replace(",", "")), higher)
    return out


def extract_collective_inventory(doc: dict) -> dict[str, int] | None:
    """The round's ``telemetry.headline_collectives`` per-op counts, from
    the bench's JSON line (``parsed`` when the driver kept it whole, else
    re-parsed out of the ``tail`` text). None when the round predates the
    telemetry block."""
    tel = (doc.get("parsed") or {}).get("telemetry")
    if isinstance(tel, dict) and "headline_collectives" in tel:
        return {k: int(v) for k, v in tel["headline_collectives"].items()}
    for line in (doc.get("tail") or "").splitlines():
        line = line.strip()
        if not (line.startswith("{") and '"telemetry"' in line):
            continue
        try:
            tel = json.loads(line).get("telemetry") or {}
        except json.JSONDecodeError:
            continue
        if "headline_collectives" in tel:
            return {k: int(v) for k, v in tel["headline_collectives"].items()}
    return None


def check_collective_contract(
    inventory: dict[str, int], golden_path: pathlib.Path
) -> list[str]:
    """Diff per-op collective counts against a golden contract file
    (plain JSON read — the shardcheck golden's ``collectives`` section
    keyed ``op@axis``, summed per op here because the bench inventory is
    axis-blind). Returns human-readable drift lines; empty == clean."""
    golden = json.loads(golden_path.read_text())
    allowed: dict[str, int] = {}
    for key, grp in (golden.get("collectives") or {}).items():
        op = key.split("@", 1)[0]
        allowed[op] = allowed.get(op, 0) + int(grp["count"])
    drift = []
    for op in sorted(set(inventory) | set(allowed)):
        got, want = inventory.get(op, 0), allowed.get(op, 0)
        if got != want:
            drift.append(
                f"collective inventory drift vs {golden_path.name}: "
                f"{got} x {op} in the bench round, contract admits {want}"
            )
    return drift


def compare(
    old: dict, new: dict, threshold: float
) -> tuple[list[dict], list[str], list[str]]:
    """Per-metric deltas plus added/removed names. A REGRESSION is a move
    past ``threshold`` in the metric's own bad direction. A ZERO old
    value gets a 1-unit floor instead of a div-by-zero pass: the
    recovery/stall gates hold at exactly 0 in a clean round, and
    0% → 12% shed must fail the gate, not sail through as delta 0."""
    om, nm = extract_metrics(old), extract_metrics(new)
    rows: list[dict] = []
    for key in sorted(om.keys() & nm.keys()):
        (ov, higher), (nv, _) = om[key], nm[key]
        delta = (nv - ov) / (abs(ov) if ov else 1.0)
        worse = -delta if higher else delta
        rows.append(
            {
                "metric": key,
                "old": ov,
                "new": nv,
                "delta_pct": 100.0 * delta,
                "higher_is_better": higher,
                "regressed": worse > threshold,
            }
        )
    added = sorted(nm.keys() - om.keys())
    removed = sorted(om.keys() - nm.keys())
    return rows, added, removed


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*", help="two BENCH json files (old new);"
                    " default: the two most recent BENCH_r*.json in --repo")
    ap.add_argument("--repo", default=".", help="directory holding BENCH_r*.json")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="regression threshold as a fraction (default 0.10)")
    ap.add_argument("--contracts", default=None,
                    help="golden contract dir for the collective-inventory "
                    "cross-check (default: the source checkout's "
                    "learning_jax_sharding_tpu/analysis/golden, resolved "
                    "from this script's location — NOT --repo, which may "
                    "be a bare artifacts dir; pass '' to disable)")
    ap.add_argument("--contract-name", default="bench_headline",
                    help="golden contract the bench inventory is held to")
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    if args.files:
        if len(args.files) != 2:
            ap.error("pass exactly two files (old new), or none")
        paths = [pathlib.Path(f) for f in args.files]
    else:
        found = sorted(
            pathlib.Path(args.repo).glob("BENCH_r*.json"), key=_round_of
        )
        if len(found) < 2:
            print(f"need >= 2 BENCH_r*.json in {args.repo}, "
                  f"found {len(found)}", file=sys.stderr)
            return 2
        paths = found[-2:]

    docs = [json.loads(p.read_text()) for p in paths]
    rows, added, removed = compare(docs[0], docs[1], args.threshold)
    regressed = [r for r in rows if r["regressed"]]

    drift: list[str] = []
    contracts = args.contracts
    if contracts is None:
        # Anchored to the script's checkout, not --repo: CI points
        # --repo at a bare BENCH-artifacts dir, and a default that
        # resolved there would silently skip the gate every run.
        contracts = str(
            pathlib.Path(__file__).resolve().parents[1]
            / "learning_jax_sharding_tpu" / "analysis" / "golden"
        )
    if contracts:
        golden = pathlib.Path(contracts) / f"{args.contract_name}.json"
        inventory = extract_collective_inventory(docs[1])
        if inventory is None:
            print(f"bench_compare: {paths[1].name} carries no collective "
                  "inventory (pre-telemetry round) — contract check skipped",
                  file=sys.stderr)
        elif not golden.exists():
            print(f"bench_compare: no golden contract at {golden} — "
                  "contract check skipped", file=sys.stderr)
        else:
            drift = check_collective_contract(inventory, golden)

    if args.json:
        print(json.dumps(
            {
                "old": str(paths[0]), "new": str(paths[1]),
                "threshold": args.threshold, "metrics": rows,
                "added": added, "removed": removed,
                "regressions": [r["metric"] for r in regressed],
                "collective_drift": drift,
            },
            indent=2,
        ))
    else:
        print(f"bench_compare: {paths[0].name} -> {paths[1].name} "
              f"(threshold {args.threshold:.0%})")
        for r in rows:
            arrow = "v" if r["delta_pct"] < 0 else "^"
            flag = "  REGRESSED" if r["regressed"] else ""
            print(f"  {r['metric']:60s} {r['old']:>12.3f} -> "
                  f"{r['new']:>12.3f}  {arrow}{abs(r['delta_pct']):6.1f}%"
                  f"{flag}")
        for k in added:
            print(f"  + {k} (new)")
        for k in removed:
            print(f"  - {k} (gone)")
        for d in drift:
            print(f"  ! {d}")
        n = len(regressed)
        print(f"bench_compare: {len(rows)} compared, {n} regression(s), "
              f"{len(drift)} collective drift(s)")
    return 1 if (regressed or drift) else 0


if __name__ == "__main__":
    sys.exit(main())
