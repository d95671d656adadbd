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
  the step programs update in place (``engine_cache_donated_bytes``).

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
program that ran them.

Usage:
    python scripts/engine_breakdown.py BUNDLE_DIR [--xplane PATH] [--json]
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
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
#: Gaps shorter than this are launch latency between ops, not the host.
SMALL_GAP_NS = 20_000.0
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load_bundle(bundle: str | os.PathLike) -> dict:
    root = pathlib.Path(bundle)
    events = json.loads((root / "events.json").read_text())["events"]
    trace = json.loads((root / "trace.json").read_text())
    return {
        "dispatches": [e for e in events if e["kind"] == "engine.dispatch"],
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
    return out


if __name__ == "__main__":
    main()
