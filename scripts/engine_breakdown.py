#!/usr/bin/env python
"""Where an engine's host time went, from what the engine itself wrote.

The operator's reader of the serving engine's spans and counters (the
table beside ``ContinuousEngine._led_device`` in ``models/serving.py``).
Its input is a post-mortem bundle, ``engine.dump_diagnostics(outdir)``:
``events.json`` (flight recorder), ``registry.json`` and ``trace.json``
(the tracer's Chrome trace). It prints

* per program family, from the ``engine.dispatch`` events: dispatches
  (those that compiled apart), seconds waiting on the chip, empty-chip
  seconds that ended at the enqueue, ``engine.h2d`` and
  ``engine.enqueue`` seconds, block-table pushes with the leaves and the
  host-to-device arrays of a push,
  prompt tokens (with the chunk rows a dispatch and, on an engine with
  state-space layers, how many of them carried their recurrent state from
  an earlier row), decode row-steps and the cached tokens they read;
* from the registry, cumulative since the engine was built (set-up's
  compiles included): ``engine_device_starved_seconds_total`` by span,
  over ``engine_step_seconds_total``, with the wait, enqueue and h2d
  shares; how many cache blocks the decode-attention kernel keeps in
  flight (``engine_decode_attn_pages_in_flight``) and the bytes of cache
  the step programs update in place (``engine_cache_donated_bytes``); on an
  engine with window layers, the ring pages one window layer read a decode
  row-step (``engine_decode_window_pages_total`` over
  ``engine_decode_steps_total``) and the bytes of the window rings and of
  the global pools (``engine_window_ring_bytes``, ``engine_global_pool_bytes``);
* from the registry, the request clock's slot-seconds and readbacks by
  phase (``engine_request_phase_seconds_total{phase=...}``,
  ``..._dispatches_total``), and
  from the ``engine.retire`` events, its table: p50 and
  p95 of each phase of a request in ms (``refill_wait``, ``refill``,
  ``stall``, ``decode``) and a token (a request's TPOT is its decode plus
  its stall seconds a token), how many requests sat through how many
  refill and stall dispatches, the request at the 95th percentile of
  TPOT, and the five slowest requests by TPOT with their phases.

With ``--xplane`` (a ``jax.profiler`` capture taken while that engine
served, a file or a directory holding one) it also places the bundle's
dispatch events on the capture. One clock: every ``engine.step`` host
event carries the tracer's own ``ts_us``, and ``trace.json`` the wall
time of the tracer's epoch, so the recorder's ``t`` maps onto profiler
time. It then prints, for the captured interval alone: the empty-chip
seconds the host counted beside the idle the device planes show, and
the idle gaps by the innermost ``engine.*`` span at their middle, then
by the innermost runtime event inside it; and the device time of the
kernels a trace names by scope (``ssm.*``, ``moe.*``, ``attn.*``), by the
program that ran them; and where on the capture each of the five slowest
requests was admitted, got its first token and retired (their
``admit_unix`` / ``first_token_unix`` / ``t`` on the same clock).

Usage:
    python scripts/engine_breakdown.py BUNDLE_DIR [--xplane PATH] [--json]
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import math
import os
import pathlib
import re
import statistics

STARVED = "engine_device_starved_seconds_total"
STEP = "engine_step_seconds_total"
#: Gauge since PR 34: cache blocks the decode-attention kernel keeps in
#: flight (0 = its pipeline-emitter form, one block beside the one computed).
ATTN_DEPTH = "engine_decode_attn_pages_in_flight"
#: Gauge since PR 36: bytes of the cache leaves the step programs donate.
DONATED = "engine_cache_donated_bytes"
#: An engine with window layers: ring pages one window layer read over
#: decode row-steps, and the bytes of the rings and of the pools
#: the host allocator serves.
WINDOW_PAGES = "engine_decode_window_pages_total"
DECODE_STEPS = "engine_decode_steps_total"
RING_BYTES = "engine_window_ring_bytes"
POOL_BYTES = "engine_global_pool_bytes"
SUMMED = (
    "starved_s", "enqueue_s", "wait_s", "h2d_s", "table_leaves",
    "prefill_tokens", "decode_steps", "context_tokens",
)
#: Fields only a dropless-expert engine's events carry (PR 29).
MOE_SUMMED = ("moe_assignments", "expert_reads")
#: Fields of a refill dispatch since PR 30: the token slots the program ran
#: (batch x refill_chunk a dispatch) and the rows that carried a chunk.
REFILL_SUMMED = ("token_slots", "chunk_rows")
#: Field of a refill dispatch since PR 33: the chunk rows whose recurrent
#: state started from an earlier row of the same dispatch (0 on an engine
#: without state-space layers).
CARRY_SUMMED = ("carried_rows",)
#: Field of a table push since PR 32: the host-to-device arrays it made (one
#: per distinct leaf width), which every ``table_leaves`` leaf shares.
PUSH_SUMMED = ("table_arrays",)
MOE = "engine_moe_"
#: The request clock's phases (``models/serving.py::_PHASES``), fields
#: ``<phase>_s`` of an ``engine.retire`` event since PR 37.
PHASES = ("refill_wait", "refill", "stall", "decode")
#: Gaps shorter than this are launch latency between ops, not the host.
SMALL_GAP_NS = 20_000.0
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def window_pages(reg: dict) -> dict | None:
    """The window layers' decode reads and the cache split by kind, or None
    on an engine without window layers (or one whose bundle predates the
    gauges)."""
    if not reg.get(RING_BYTES):
        return None
    steps = reg.get(DECODE_STEPS) or 0
    return {
        "pages_per_decode_step": reg.get(WINDOW_PAGES, 0) / steps if steps else None,
        "ring_bytes": reg[RING_BYTES],
        "pool_bytes": reg.get(POOL_BYTES, 0),
    }


def load_bundle(bundle: str | os.PathLike) -> dict:
    root = pathlib.Path(bundle)
    events = json.loads((root / "events.json").read_text())["events"]
    trace = json.loads((root / "trace.json").read_text())
    return {
        "dispatches": [e for e in events if e["kind"] == "engine.dispatch"],
        "retired": [e for e in events if e["kind"] == "engine.retire"],
        "registry": json.loads((root / "registry.json").read_text()),
        "epoch_unix_ns": trace["otherData"]["epoch_unix_ns"],
    }


def by_family(dispatches: list[dict]) -> dict[str, dict]:
    """The ``engine.dispatch`` events summed per program family. A
    dispatch that compiled is counted and left out of the sums: its
    enqueue is a trace and a compile, not the steady state."""
    out: dict[str, dict] = {}
    for e in dispatches:
        row = out.setdefault(e["family"], collections.defaultdict(float))
        if e["compiled"]:
            row["compiled"] += 1
            continue
        row["dispatches"] += 1
        row["pushes"] += bool(e["table_leaves"])
        for key in SUMMED:
            row[key] += e[key]
        for key in MOE_SUMMED + REFILL_SUMMED + PUSH_SUMMED + CARRY_SUMMED:
            if key in e:
                row[key] += e[key]
    return {family: dict(row) for family, row in out.items()}


def starved_by_span(registry: dict) -> dict:
    """The empty-device clock from a registry snapshot: seconds by span
    (largest first) and the shares of ``engine_step_seconds_total``."""
    spans = {
        k[len(STARVED) + 7:-2]: v for k, v in registry.items()
        if k.startswith(STARVED + '{span="')
    }
    step_s = registry.get(STEP, 0.0)
    total = registry.get(STARVED, 0.0)

    def share(seconds):
        return 100.0 * seconds / step_s if step_s else None

    return {
        "step_s": step_s,
        "starved_s": total,
        "by_span_s": dict(sorted(spans.items(), key=lambda kv: -kv[1])),
        "labelled_minus_plain_s": sum(spans.values()) - total,
        "starved_share_pct": share(total),
        "wait_share_pct": share(registry.get("engine_wait_seconds_total", 0.0)),
        "enqueue_share_pct": share(
            registry.get("engine_enqueue_seconds_total", 0.0)
        ),
        "h2d_share_pct": share(registry.get("engine_h2d_seconds_total", 0.0)),
    }


def phase_counters(registry: dict) -> dict[str, dict]:
    """The request clock's counters of a registry snapshot, cumulative
    since the engine was built: slot-seconds and readbacks by the phase
    they put a request in (``redone``: counted again when a preemption
    threw them away). Empty for a bundle from before PR 37."""
    out: dict[str, dict] = {}
    for key, value in registry.items():
        m = re.fullmatch(
            r'engine_request_phase_(seconds|dispatches)_total\{phase="(\w+)"\}', key
        )
        if m:
            out.setdefault(m.group(2), {})[m.group(1)] = value
    return out


def _percentile(values, q: float) -> float:
    """``q``-th percentile, linear between order statistics."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    return xs[lo] + (xs[min(lo + 1, len(xs) - 1)] - xs[lo]) * (pos - lo)


def _request_row(e: dict) -> dict:
    """One request for the table: its phases in ms and its counts."""
    row = {
        "rid": e["rid"], "generated": e["generated"],
        "tpot_ms": 1e3 * e["tpot"],
        **{f"{p}_ms": 1e3 * e[f"{p}_s"] for p in PHASES},
    }
    for key in (
        "refill_dispatches", "stall_dispatches", "decode_dispatches",
        "admit_unix", "first_token_unix",
    ):
        row[key] = e[key]
    row["retire_unix"] = e["t"]
    return row


def request_table(
    retired: list[dict], dispatches: list[dict] = (), slowest: int = 5
) -> dict | None:
    """The request clock's table from the ``engine.retire`` events that
    carry it (None for a bundle from before PR 37, or one without a
    request of two tokens): ``phases_ms`` and ``per_token_ms`` (p50, p95
    of each phase, and of TPOT = decode + stall a token),
    ``refill_dispatches`` / ``stall_dispatches`` (requests by count),
    ``at_tpot_p95`` (the request nearest the 95th percentile of TPOT) and
    the ``slowest`` requests by TPOT. A request that sat through a
    dispatch that compiled is counted (``compiled``) and left out, as
    such a dispatch is in the family table."""
    compiles = sorted(e["t"] for e in dispatches if e["compiled"])
    reqs, compiled = [], 0
    for e in retired:
        if e.get("stall_per_token_s") is None:
            continue
        i = bisect.bisect_left(compiles, e["admit_unix"])
        if i < len(compiles) and compiles[i] <= e["t"]:
            compiled += 1
        else:
            reqs.append(e)
    if not reqs:
        return None

    def p50_p95(values):
        values = list(values)
        return {"p50": _percentile(values, 50), "p95": _percentile(values, 95)}

    by_tpot = sorted(reqs, key=lambda e: e["tpot"])
    return {
        "requests": len(reqs),
        "compiled": compiled,
        "phases_ms": {
            p: p50_p95(1e3 * e[f"{p}_s"] for e in reqs) for p in PHASES
        },
        "per_token_ms": {
            "tpot": p50_p95(1e3 * e["tpot"] for e in reqs),
            **{
                p: p50_p95(1e3 * e[f"{p}_per_token_s"] for e in reqs)
                for p in ("stall", "decode")
            },
        },
        **{
            key: {
                str(k): n for k, n in
                sorted(collections.Counter(e[key] for e in reqs).items())
            }
            for key in ("refill_dispatches", "stall_dispatches")
        },
        "at_tpot_p95": _request_row(
            by_tpot[round((len(by_tpot) - 1) * 0.95)]
        ),
        "slowest": [_request_row(e) for e in by_tpot[:-slowest - 1:-1]],
    }


# --- the capture ---------------------------------------------------------------


def _find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load_capture(path: str) -> dict:
    """Host threads as sorted ``(name, start_ns, dur_ns)`` lists, split
    into ``engine.*`` events and the runtime's; the ``engine.step``
    anchors ``(start_ns, ts_us, step)``; the TPU planes' op intervals."""
    from jax.profiler import ProfileData

    engine, runtime, anchors, ops = [], [], [], []
    kernels: dict = collections.defaultdict(lambda: [0.0, 0])
    for plane in ProfileData.from_file(_find_xplane(path)).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                eng, rt = [], []
                for ev in line.events:
                    item = (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    if not ev.name.startswith("engine."):
                        rt.append(item)
                        continue
                    eng.append(item)
                    if ev.name == "engine.step":
                        stats = dict(ev.stats)
                        anchors.append((
                            item[1], float(stats["ts_us"]), int(stats["step"])
                        ))
                engine.append(sorted(eng, key=lambda e: e[1]))
                runtime.append(sorted(rt, key=lambda e: e[1]))
        elif _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                ops.extend(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns))
                    for e in line.events
                )
            _add_kernel_times(kernels, lines)
    return {
        "engine": [t for t in engine if t], "runtime": [t for t in runtime if t],
        "anchors": sorted(anchors), "ops": sorted(ops),
        "kernels": {k: tuple(v) for k, v in kernels.items()},
    }


_KERNEL = re.compile(r"^%?((?:ssm|moe|attn)\.[A-Za-z_]+(?:\.[A-Za-z_]+)*)")


def _add_kernel_times(kernels: dict, lines: dict) -> None:
    """Device seconds and calls of the ops a trace can name by SCOPE: the
    Pallas kernels (``ssm.state_update``, ``ssm.chunk_scan``,
    ``moe.experts``, ``attn.*``), whose instruction carries the scope it
    was called under, by the program that ran them. A scope that holds
    plain XLA ops only (``ssm.in_proj``, ``ssm.conv``, ``moe.latent_down``,
    ``moe.latent_up``, ``moe.shared``, ``moe.route``) is in the
    instruction's metadata, which a capture does not keep: its fusions
    show as ``fusion.N`` and cannot be told apart here."""
    modules = sorted(
        (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name.split("(")[0])
        for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ())
    )
    starts = [m[0] for m in modules]
    for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
        m = _KERNEL.match(e.name)
        if not m:
            continue
        i = bisect.bisect_right(starts, float(e.start_ns)) - 1
        module = modules[i][2] if i >= 0 and e.start_ns < modules[i][1] else "?"
        row = kernels[f"{module} | {m.group(1)}"]
        row[0] += e.duration_ns / 1e9
        row[1] += 1


def _idle_gaps(ops):
    """``(start, length)`` of every hole in the union of the op intervals."""
    gaps, end = [], None
    for a, b in ops:
        if end is not None and a > end:
            gaps.append((end, a - end))
        end = b if end is None else max(end, b)
    return gaps


def _innermost(threads, t):
    """Name of the shortest event on any thread that covers instant ``t``;
    ``threads`` pairs each thread's start times with its events."""
    best = None
    for starts, events in threads:
        i = bisect.bisect_right(starts, t) - 1
        for name, start, dur in reversed(events[max(i - 256, 0):i + 1]):
            if start + dur >= t and (best is None or dur < best[1]):
                best = (name, dur)
    return best[0] if best else None


def place_on_capture(bundle: dict, capture: dict) -> dict:
    """The bundle's dispatch events on the capture's clock, and the
    captured interval by both clocks."""
    anchors = capture["anchors"]
    if not anchors:
        raise ValueError("the capture holds no engine.step host event")
    # profiler ns = offset + tracer us * 1e3, from every step's anchor
    offset = statistics.median(ns - us * 1e3 for ns, us, _ in anchors)

    def profiler_ns(unix_s):
        return offset + (unix_s * 1e9 - bundle["epoch_unix_ns"])

    ops = capture["ops"]
    if ops:
        lo, hi = ops[0][0], max(b for _, b in ops)
    else:   # no device plane (a CPU capture): the captured steps
        steps = [e for t in capture["engine"] for e in t if e[0] == "engine.step"]
        lo = min(s for _, s, _ in steps)
        hi = max(s + d for _, s, d in steps)
    # An event is written when its dispatch has been read back; the
    # empty-chip seconds it reports ended ``wait_s`` before that.
    inside = [
        e for e in bundle["dispatches"]
        if lo <= profiler_ns(e["t"] - e["wait_s"]) <= hi
    ]
    out = {
        "captured_steps": [step for _, _, step in anchors],
        "interval_s": (hi - lo) / 1e9,
        "dispatch_steps": [e["step"] for e in inside],
        "host_starved_s": sum(e["starved_s"] for e in inside),
        "by_family": by_family(inside),
    }
    table = request_table(bundle.get("retired", ()), bundle["dispatches"])
    if table:
        # The slowest requests' instants as seconds from the capture's
        # start (negative or past ``interval_s``: outside it).
        out["slowest_requests_s"] = [
            {
                "rid": r["rid"],
                **{
                    key: (profiler_ns(r[f"{key}_unix"]) - lo) / 1e9
                    for key in ("admit", "first_token", "retire")
                },
            }
            for r in table["slowest"]
        ]
    if not ops:
        return out
    out["kernel_device_s"] = dict(
        sorted(capture.get("kernels", {}).items(), key=lambda kv: -kv[1][0])
    )
    gaps = _idle_gaps(ops)
    out["device_idle_s"] = sum(d for _, d in gaps) / 1e9
    engine, runtime = (
        [([e[1] for e in events], events) for events in capture[kind]]
        for kind in ("engine", "runtime")
    )
    by_span = collections.defaultdict(float)
    by_pair = collections.defaultdict(float)
    for start, dur in gaps:
        if dur < SMALL_GAP_NS:
            by_span["(gaps under 20 us)"] += dur / 1e9
            continue
        mid = start + dur / 2
        span = _innermost(engine, mid) or "(no engine span)"
        event = _innermost(runtime, mid) or "(no runtime event)"
        by_span[span] += dur / 1e9
        by_pair[f"{span} | {event}"] += dur / 1e9
    out["idle_by_span_s"] = dict(sorted(by_span.items(), key=lambda kv: -kv[1]))
    out["idle_by_span_and_runtime_event_s"] = dict(
        sorted(by_pair.items(), key=lambda kv: -kv[1])[:20]
    )
    return out


# --- printing ------------------------------------------------------------------


def _print_families(rows: dict[str, dict]) -> None:
    for family, r in rows.items():
        n = r.get("dispatches")
        if not n:
            print(f"  {family}: {r['compiled']:.0f} compiling dispatches only")
            continue
        print(
            f"  {family}: {n:.0f} dispatches (and {r.get('compiled', 0):.0f} "
            f"that compiled, left out); "
            f"wait {r['wait_s']:.3f} s ({1e3 * r['wait_s'] / n:.1f} ms each), "
            f"starved {r['starved_s']:.3f} s ({1e3 * r['starved_s'] / n:.1f}), "
            f"h2d {r['h2d_s']:.3f}, enqueue {r['enqueue_s']:.3f}; "
            f"{r['pushes']:.0f} table pushes"
            + (f" of {r['table_leaves'] / r['pushes']:.0f} leaves"
               if r["pushes"] else "")
            + (f" in {r['table_arrays'] / r['pushes']:.0f} arrays"
               if r.get("table_arrays") else "")
            + f"; {r['prefill_tokens']:.0f} prompt tokens"
            + (f" in {r['chunk_rows'] / n:.1f} chunk rows a dispatch, fill "
               f"{100 * r['prefill_tokens'] / r['token_slots']:.1f} % of "
               f"{r['token_slots'] / n:.0f} token slots"
               if r.get("token_slots") else "")
            + (f", {r['carried_rows'] / n:.1f} of the rows carried from an "
               f"earlier row's recurrent state"
               if r.get("carried_rows") else "")
            + f", {r['decode_steps']:.0f} row-steps over "
            f"{r['context_tokens']:.0f} cached tokens"
            + (f"; {r['moe_assignments']:.0f} expert assignments over "
               f"{r['expert_reads']:.0f} expert reads"
               if r.get("expert_reads") else "")
        )


def _print_requests(table: dict) -> None:
    def pair(d):
        return f"{d['p50']:.3f} / {d['p95']:.3f}"

    print(
        f"requests retired with the request clock ({table['requests']}, and "
        f"{table['compiled']} that sat through a compiling dispatch, left "
        "out), p50 / p95:"
    )
    print("  ms a request: " + ", ".join(
        f"{p} {pair(table['phases_ms'][p])}" for p in PHASES
    ))
    per = table["per_token_ms"]
    print(
        f"  ms a token: tpot {pair(per['tpot'])} = decode "
        f"{pair(per['decode'])} + stall {pair(per['stall'])} (a request's "
        "own two add up; the percentiles need not)"
    )
    for key in ("refill_dispatches", "stall_dispatches"):
        print(f"  requests by {key}: " + ", ".join(
            f"{n} x {k}" for k, n in table[key].items()
        ))

    def line(r):
        return (
            f"rid {r['rid']}: tpot {r['tpot_ms']:.3f} ms over "
            f"{r['generated']} tokens; " + ", ".join(
                f"{p} {r[f'{p}_ms']:.1f}" for p in PHASES
            ) + f" ms; dispatches: refill {r['refill_dispatches']}, stall "
            f"{r['stall_dispatches']}, decode {r['decode_dispatches']}"
        )

    print("  at the 95th percentile of tpot: " + line(table["at_tpot_p95"]))
    for r in table["slowest"]:
        print("  slowest: " + line(r))


def moe_by_phase(registry: dict) -> dict[str, dict]:
    """The dropless-expert counters of a registry snapshot, by phase:
    assignments, expert reads, layer-steps, and tokens per expert read."""
    out: dict[str, dict] = {}
    for key, value in registry.items():
        m = re.fullmatch(MOE + r'(\w+)_total\{phase="(\w+)"\}', key)
        if m:
            out.setdefault(m.group(2), {})[m.group(1)] = value
    for row in out.values():
        if row.get("expert_reads"):
            row["tokens_per_expert_read"] = (
                row["assignments"] / row["expert_reads"]
            )
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("bundle", help="directory written by dump_diagnostics()")
    ap.add_argument("--xplane", help="a capture: .xplane.pb or its directory")
    ap.add_argument("--json", action="store_true", help="one JSON object")
    args = ap.parse_args(argv)
    bundle = load_bundle(args.bundle)
    out = {
        "by_family": by_family(bundle["dispatches"]),
        "registry": starved_by_span(bundle["registry"]),
        "moe": moe_by_phase(bundle["registry"]),
        "decode_attn_pages_in_flight": bundle["registry"].get(ATTN_DEPTH),
        "cache_donated_bytes": bundle["registry"].get(DONATED),
        "window": window_pages(bundle["registry"]),
        "requests": request_table(bundle["retired"], bundle["dispatches"]),
        "request_phases": phase_counters(bundle["registry"]),
    }
    if args.xplane:
        out["capture"] = place_on_capture(bundle, load_capture(args.xplane))
    if args.json:
        print(json.dumps(out))
        return out
    print(f"engine.dispatch events in the bundle ({len(bundle['dispatches'])}):")
    _print_families(out["by_family"])
    reg = out["registry"]
    if reg["step_s"]:
        print(
            f"registry: step {reg['step_s']:.3f} s; starved "
            f"{reg['starved_s']:.3f} s = {reg['starved_share_pct']:.2f} %, "
            f"wait {reg['wait_share_pct']:.2f} %, enqueue "
            f"{reg['enqueue_share_pct']:.2f} %, h2d {reg['h2d_share_pct']:.2f} %"
        )
        print("  starved by span: " + ", ".join(
            f"{k} {v:.4f}" for k, v in reg["by_span_s"].items()
        ))
    depth = out["decode_attn_pages_in_flight"]
    if depth is not None:
        print(
            f"decode attention: {depth:.0f} cache blocks in flight"
            + (" (the loop form)" if depth else " (the emitter form)")
        )
    win = out["window"]
    if win is not None:
        per = win["pages_per_decode_step"]
        print(
            "window layers: "
            + (f"{per:.3f} ring pages read a decode row-step (one layer), "
               if per is not None else "")
            + f"rings {win['ring_bytes'] / 1e9:.3f} GB beside global pools "
            f"{win['pool_bytes'] / 1e9:.3f} GB"
        )
    donated = out["cache_donated_bytes"]
    if donated is not None:
        print(
            f"cache: {donated / 1e9:.3f} GB updated in place (donated to the "
            "step programs; the block tables ride beside it)"
        )
    for phase, row in out["moe"].items():
        print(
            f"experts, {phase}: {row.get('assignments', 0):.0f} assignments, "
            f"{row.get('expert_reads', 0):.0f} expert reads in "
            f"{row.get('layer_steps', 0):.0f} layer-steps"
            + (f": {row['tokens_per_expert_read']:.2f} tokens a read"
               if "tokens_per_expert_read" in row else "")
        )
    if out["request_phases"]:
        print("request clock, slot-seconds (readbacks) since the engine was "
              "built: " + ", ".join(
                  f"{phase} {row.get('seconds', 0.0):.3f} "
                  f"({row.get('dispatches', 0):.0f})"
                  for phase, row in out["request_phases"].items()
              ))
    if out["requests"]:
        _print_requests(out["requests"])
    cap = out.get("capture")
    if cap:
        print(
            f"capture: {cap['interval_s']:.3f} s, steps "
            f"{cap['captured_steps'][0]}-{cap['captured_steps'][-1]}, "
            f"{len(cap['dispatch_steps'])} dispatches placed in it; the host "
            f"counted {cap['host_starved_s']:.3f} s of empty chip"
            + (f", the device planes show {cap['device_idle_s']:.3f} s idle"
               if "device_idle_s" in cap else "")
        )
        _print_families(cap["by_family"])
        for name, (seconds, calls) in cap.get("kernel_device_s", {}).items():
            print(
                f"  kernel: {seconds:.4f} s in {calls} calls "
                f"({1e6 * seconds / calls:.1f} us each)  {name}"
            )
        for key in ("idle_by_span_s", "idle_by_span_and_runtime_event_s"):
            for name, seconds in cap.get(key, {}).items():
                print(f"  {key[:-2]}: {seconds:.4f} s  {name}")
        for r in cap.get("slowest_requests_s", ()):
            print(
                f"  slowest on the capture: rid {r['rid']} admitted at "
                f"{r['admit']:.4f} s, first token {r['first_token']:.4f}, "
                f"retired {r['retire']:.4f} (of {cap['interval_s']:.4f})"
            )
    return out


if __name__ == "__main__":
    main()
