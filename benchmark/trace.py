"""From a profiler trace (``.xplane.pb``) to numbers: busy union, idle share,
time by module and by op-name prefix. Uses device durations only, never a
host clock. Checked on a recorded trace in ``benchmark/tests/test_trace.py``.

What a TPU trace holds (looked at by hand, PR 26): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per run of a
jitted program, named ``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per
HLO instruction run, named by the instruction's text, ``%<name> = ...``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
_OP_NAME = re.compile(r"^%?([^\s=(]+)")
_TRAILING_N = re.compile(r"(\.\d+)+$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``; a module's
    ``jit_step(123)`` -> ``jit_step``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def strip_n(name: str) -> str:
    """``attn.136`` -> ``attn``; ``copy.1448`` -> ``copy``."""
    return _TRAILING_N.sub("", name)


@dataclasses.dataclass
class Plane:
    """One chip's events as ``(name, start_ns, duration_ns)`` lists."""

    name: str
    modules: list[tuple[str, float, float]]
    ops: list[tuple[str, float, float]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _sorted_events(line, name_of=lambda n: n):
    return sorted(
        ((name_of(e.name), float(e.start_ns), float(e.duration_ns)) for e in line.events),
        key=lambda ev: ev[1],
    )


def load_trace(path: str) -> tuple[list[Plane], list[list[tuple[str, float, float]]]]:
    """One parse of a trace file: the device planes, and per host thread
    its events; all sorted by start."""
    from jax.profiler import ProfileData

    planes, threads = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            threads.extend(evs for evs in map(_sorted_events, plane.lines) if evs)
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            planes.append(Plane(plane.name, *(
                _sorted_events(lines[n], op_name) if n in lines else []
                for n in (MODULE_LINE, OP_LINE)
            )))
    return planes, threads


def load_planes(path: str) -> list[Plane]:
    return load_trace(path)[0]


def busy_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for _, start, dur in sorted(events, key=lambda ev: ev[1]):
        end = start + dur
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def span_ns(events) -> float:
    """First start to last end."""
    if not events:
        return 0.0
    return max(s + d for _, s, d in events) - min(s for _, s, _ in events)


def ops_in_modules(plane: Plane, module_prefix: str | None):
    """The op events that start inside a run of a module whose name starts
    with ``module_prefix`` (all ops when it is None)."""
    if module_prefix is None:
        return plane.ops
    runs = [(s, s + d) for n, s, d in plane.modules if n.startswith(module_prefix)]
    starts = [s for s, _ in runs]
    out = []
    for ev in plane.ops:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < runs[i][1]:
            out.append(ev)
    return out


def op_time_ns(plane: Plane, op_prefix: str, module_prefix: str | None = None) -> tuple[float, int]:
    """Summed duration and count of the ops whose name starts with
    ``op_prefix``, inside ``module_prefix`` runs."""
    hits = [d for n, _, d in ops_in_modules(plane, module_prefix) if n.startswith(op_prefix)]
    return sum(hits), len(hits)


def module_runs(plane: Plane, module_prefix: str) -> list[float]:
    """Durations (ns) of the runs of modules whose name starts with the prefix."""
    return [d for n, _, d in plane.modules if n.startswith(module_prefix)]


def top_ops(plane: Plane, k: int = 10) -> list[list]:
    """The ``k`` op names that took most time, ``.N`` stripped, seconds."""
    by_name: dict[str, float] = {}
    for n, _, d in plane.ops:
        key = strip_n(n)
        by_name[key] = by_name.get(key, 0.0) + d
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in top]


@dataclasses.dataclass
class Reduced:
    """What the readers get: the device planes and, averaged over them, the
    busy seconds and the length of the traced window."""

    planes: list[Plane]
    busy_s: float
    window_s: float
    host_threads: list = dataclasses.field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce_trace(path: str) -> Reduced:
    """``window_s`` is the span of device activity in the trace: first op
    start to last op end on each chip (the profiler's own start and stop are
    host times, which this module never reads)."""
    planes, threads = load_trace(path)
    planes = [p for p in planes if p.ops or p.modules]
    if not planes:
        raise ValueError(f"{path}: no device plane with events — nothing ran on a TPU")
    busy = [busy_ns(p.ops or p.modules) for p in planes]
    window = [span_ns(p.ops or p.modules) for p in planes]
    return Reduced(
        planes, sum(busy) / len(busy) / 1e9, sum(window) / len(window) / 1e9, threads
    )


def module_summary(plane: Plane) -> dict:
    """Per module name: runs and summed seconds (for the info line)."""
    out: dict[str, list] = {}
    for n, _, d in plane.modules:
        ent = out.setdefault(n, [0, 0.0])
        ent[0] += 1
        ent[1] += d / 1e9
    return out


# --- idle gaps by what the host was doing -----------------------------------
#: The host's and the chip's clocks in one trace are about a millisecond
#: apart (in the recorded fixture the first device op starts 1.05 ms BEFORE
#: the host call that launched it), so only gaps much longer than that are
#: given to a host event; shorter ones are summed by size.
ATTRIBUTE_FROM_NS = 5e6
SMALL_GAP_NS = 20e3


def idle_gaps(events) -> list[tuple[float, float]]:
    """``(start_ns, duration_ns)`` of the gaps in the union of the events."""
    gaps, cur_end = [], None
    for _, start, dur in sorted(events, key=lambda ev: ev[1]):
        if cur_end is not None and start > cur_end:
            gaps.append((cur_end, start - cur_end))
        cur_end = start + dur if cur_end is None else max(cur_end, start + dur)
    return gaps


def gaps_by_host_event(plane: Plane, threads, k: int = 10) -> list[list]:
    """Idle seconds by the innermost host event at the middle of each long
    gap; short gaps by size class. At most ``k`` entries, largest first."""
    by_name: dict[str, float] = {}
    starts = [[e[1] for e in evs] for evs in threads]

    def innermost(t):
        best = None
        for evs, st in zip(threads, starts):
            i = bisect.bisect_right(st, t) - 1
            for j in range(i, max(i - 64, -1), -1):
                n, s, d = evs[j]
                if s + d >= t and (best is None or d < best[1]):
                    best = (n, d)
        return best[0] if best else "no_host_event"

    for start, dur in idle_gaps(plane.ops or plane.modules):
        if dur < SMALL_GAP_NS:
            key = "gaps_under_20us"
        elif dur < ATTRIBUTE_FROM_NS:
            key = "gaps_20us_to_5ms"
        else:
            key = innermost(start + dur / 2)
        by_name[key] = by_name.get(key, 0.0) + dur
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in top]
