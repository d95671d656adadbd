"""The readers a per-layer metric file can name. A reader takes the file's
``params`` and what the run observed, and returns the number — or None when
there is nothing to read, and the harness then leaves the metric out.

``obs`` holds: ``setup_watch`` (``CompileWatch.report()`` when the window
opened), ``ledger`` (the engine's goodput-ledger window report),
``registry`` (``{"start", "end"}`` snapshots of the ``MetricsRegistry``),
``tracer_events``, ``recorder_events`` (inside the window), ``requests``
and ``windows`` (serving: per-request records and the two populations),
``trace`` (``trace.Reduced`` or None), ``work`` (shapes for ``costs``) and
``peaks``.
"""

from __future__ import annotations

import statistics

from benchmark import costs, stats
from benchmark import trace as tr


def compile_watch(p: dict, obs: dict):
    return (obs.get("setup_watch") or {}).get(p["field"])


def ledger_share(p: dict, obs: dict):
    """Named buckets of the goodput ledger over the window's wall, in %."""
    rep = obs.get("ledger")
    if not rep or not rep["wall_s"]:
        return None
    return 100.0 * sum(rep["buckets"].get(b, 0.0) for b in p["buckets"]) / rep["wall_s"]


def _registry_delta(obs: dict, name: str):
    reg = obs.get("registry")
    if not reg or name not in reg["end"]:
        return None
    end, start = reg["end"][name], reg["start"].get(name, 0.0)
    if isinstance(end, dict):          # histogram: mean of the window's samples
        n = end["count"] - (start["count"] if isinstance(start, dict) else 0)
        total = end["sum"] - (start["sum"] if isinstance(start, dict) else 0.0)
        return total / n if n else None
    return end - start


def registry(p: dict, obs: dict):
    """Window delta of a counter or gauge (a histogram gives the mean of the
    window's samples), times ``scale``; ``over`` names a second one to divide by."""
    num = _registry_delta(obs, p["name"])
    if num is None:
        return None
    if "over" in p:
        den = _registry_delta(obs, p["over"])
        if not den:
            return None
        num /= den
    return num * p.get("scale", 1.0)


def tracer_span(p: dict, obs: dict):
    """``total`` seconds or ``count`` of the tracer's complete events of a name."""
    evs = [
        e for e in obs.get("tracer_events") or []
        if e.get("ph") == "X" and e["name"] == p["name"]
    ]
    if not evs:
        return None
    if p.get("what", "total") == "count":
        return float(len(evs))
    return sum(e["dur"] for e in evs) / 1e6


def recorder_field(p: dict, obs: dict):
    """Quantile of a numeric field of the window's flight-recorder events of a kind."""
    vals = [
        e[p["field"]] for e in obs.get("recorder_events") or []
        if e["kind"] == p["kind"] and e.get(p["field"]) is not None
    ]
    if not vals:
        return None
    return stats.percentile(vals, p["quantile"]) * p.get("scale", 1.0)


def request_field(p: dict, obs: dict):
    """Quantile of a per-request field (``ttft``, ``e2e``, ``queue_wait``,
    seconds) over the requests ``due_in_window`` or ``finished_in_window``."""
    lo, hi = obs["windows"][p["population"]]
    vals = []
    for r in obs.get("requests") or []:
        if r.get(p["field"]) is None or r.get("failed"):
            continue
        t = r["due"] if p["population"] == "due_in_window" else r["due"] + r["e2e"]
        if lo <= t <= hi:
            vals.append(r[p["field"]])
    if not vals:
        return None
    return stats.percentile(vals, p["quantile"]) * p.get("scale", 1.0)


def _planes(obs: dict):
    red = obs.get("trace")
    return red.planes if red is not None else []


def trace_module_share(p: dict, obs: dict):
    """Device time in the modules of the given prefixes over device busy time, %."""
    if obs.get("trace") is None:
        return None
    hit = sum(
        sum(tr.module_runs(pl, prefix)) for pl in _planes(obs) for prefix in p["modules"]
    )
    busy = obs["trace"].busy_s * 1e9 * len(_planes(obs))
    return 100.0 * hit / busy if busy else None


def trace_op_time(p: dict, obs: dict):
    """Seconds in the ops of a name prefix (inside ``module`` runs), summed over chips."""
    if obs.get("trace") is None:
        return None
    ns = sum(tr.op_time_ns(pl, p["op"], p.get("module"))[0] for pl in _planes(obs))
    return ns / 1e9 if ns else None


def trace_roofline(p: dict, obs: dict):
    """Share of the roofline, %: the least time the chip could take for the
    work ``costs.FORMULAS[formula]`` counts, over the device time measured —
    the summed ops of prefix ``op`` inside ``module`` runs, or with
    ``per_run`` the median duration of one run of ``module``."""
    if obs.get("trace") is None:
        return None
    amount, peak = costs.FORMULAS[p["formula"]](obs["work"])
    if p.get("per_run"):
        runs = [d for pl in _planes(obs) for d in tr.module_runs(pl, p["module"])]
        ns = statistics.median(runs) if runs else 0.0
    else:
        ns = sum(tr.op_time_ns(pl, p["op"], p.get("module"))[0] for pl in _planes(obs))
    if not ns or not amount:
        return None
    return 100.0 * (amount / obs["peaks"][peak]) / (ns / 1e9)


def trace_idle(p: dict, obs: dict):
    """1 - union of the device-op intervals over the traced slice, %."""
    if obs.get("trace") is None:
        return None
    return 100.0 * obs["trace"].idle_share


READERS = {
    f.__name__: f for f in (
        compile_watch, ledger_share, registry, tracer_span, recorder_field,
        request_field, trace_module_share, trace_op_time, trace_roofline,
        trace_idle,
    )
}
