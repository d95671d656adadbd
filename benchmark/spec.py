"""Finds a cell's files by the names in ``BENCHMARK.json`` and refuses what
it does not know: an unknown key, kind or reader, a metric whose ``moves``
its cell does not report, a metric file that disagrees with its entry."""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent

TRAFFIC_KINDS = ("serve_closed", "serve_open", "train")
CONFIG_KEYS = {"family", "source", "changed", "reduced", "assumed", "deployment", "check", "rehearse"}
METRIC_FILE_KEYS = {"layer", "unit", "better", "source", "moves", "reader", "params", "what"}


class SpecError(ValueError):
    pass


def _load(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict                 # the configuration file (rehearsal applied)
    family: object              # benchmark.families.<family>
    traffic: dict               # the traffic file (rehearsal applied)
    end_to_end: list[dict]      # BENCHMARK.json entries that apply
    per_layer: list[dict]       # entries that apply, each with its file under "file"


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def load_cell(workload: str, *, rehearse: bool, readers: dict, bench: dict | None = None,
              here: pathlib.Path = HERE) -> Cell:
    bench = _load(ROOT / "BENCHMARK.json") if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r} (known: {sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise SpecError(f"{workload}: unknown config {entry['config']!r}")

    model = _load(ROOT / configs[entry["config"]]["file"])
    for key in CONFIG_KEYS - {"rehearse"}:
        if key not in model:
            raise SpecError(f"config {entry['config']}: missing key {key!r}")
    try:
        family = importlib.import_module(f"benchmark.families.{model['family']}")
    except ModuleNotFoundError as e:
        raise SpecError(f"config {entry['config']}: unknown family {model['family']!r}") from e

    spec = _load(here / "traffic" / f"{entry['traffic']}.json")
    if spec.get("kind") not in TRAFFIC_KINDS:
        raise SpecError(
            f"traffic {entry['traffic']}: unknown kind {spec.get('kind')!r} "
            f"(known: {TRAFFIC_KINDS})"
        )
    if rehearse:
        model = _merge(model, model.get("rehearse", {}))
        spec = _merge(spec, spec.get("rehearse", {}))

    e2e = [m for m in bench["end_to_end"] if applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if not applies(m, workload):
            continue
        f = _load(here / "metrics" / f"{m['name']}.json")
        unknown = set(f) - METRIC_FILE_KEYS
        if unknown:
            raise SpecError(f"metric {m['name']}: unknown keys {sorted(unknown)}")
        for key in ("layer", "unit", "better", "source", "moves"):
            if f.get(key) != m.get(key):
                raise SpecError(
                    f"metric {m['name']}: {key!r} is {f.get(key)!r} in its file "
                    f"and {m.get(key)!r} in BENCHMARK.json"
                )
        if f.get("reader") not in readers:
            raise SpecError(
                f"metric {m['name']}: unknown reader {f.get('reader')!r} "
                f"(known: {sorted(readers)})"
            )
        if m["moves"] not in e2e_names:
            raise SpecError(
                f"metric {m['name']} moves {m['moves']!r}, which cell "
                f"{workload} does not report"
            )
        per_layer.append({**m, "file": f})
    if "setup_s" not in e2e_names or len(e2e_names) < 2 or not per_layer:
        raise SpecError(
            f"{workload}: a cell reports setup_s, one more end-to-end metric "
            f"and a per-layer metric"
        )
    return Cell(workload, int(entry["chips"]), model, family, spec, e2e, per_layer)
