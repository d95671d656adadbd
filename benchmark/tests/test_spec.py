"""Loader errors: the harness fails loudly on what it does not know."""

import copy
import json
import pathlib
import shutil

import pytest

from benchmark import readers, spec

HERE = pathlib.Path(__file__).resolve().parents[1]
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def load(workload="gpt2-xl.chat_backlog", bench=BENCH, here=HERE, rehearse=False):
    return spec.load_cell(
        workload, rehearse=rehearse, readers=readers.READERS, bench=bench, here=here
    )


@pytest.fixture
def files(tmp_path):
    for sub in ("traffic", "metrics"):
        shutil.copytree(HERE / sub, tmp_path / sub)
    return tmp_path


def edit(path, **changes):
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(w):
    cell = load(w)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    assert all(m["moves"] in names for m in cell.per_layer)


def test_rehearsal_swaps_in_the_tiny_sizes():
    cell = load(rehearse=True)
    assert cell.model["n_layer"] == 2 and cell.traffic["engine"]["slots"] == 4
    assert load().model["n_layer"] == 48


def test_unknown_workload():
    with pytest.raises(spec.SpecError, match="unknown workload"):
        load("gpt2-xl.nope")


def test_unknown_reader(files):
    edit(files / "metrics" / "sched_host_share_pct.json", reader="crystal_ball")
    with pytest.raises(spec.SpecError, match="unknown reader 'crystal_ball'"):
        load(here=files)


def test_unknown_key_in_a_metric_file(files):
    edit(files / "metrics" / "setup_trace_s.json", why="because")
    with pytest.raises(spec.SpecError, match="unknown keys"):
        load(here=files)


def test_unknown_traffic_kind(files):
    edit(files / "traffic" / "chat_backlog.json", kind="serve_sideways")
    with pytest.raises(spec.SpecError, match="unknown kind"):
        load(here=files)


def test_metric_that_moves_what_its_cell_does_not_report(files):
    bench = copy.deepcopy(BENCH)
    for m in bench["per_layer"]:
        if m["name"] == "sched_host_share_pct":
            m["moves"] = "train_tok_s"
    edit(files / "metrics" / "sched_host_share_pct.json", moves="train_tok_s")
    with pytest.raises(spec.SpecError, match="does not report"):
        load(bench=bench, here=files)


def test_metric_file_that_disagrees_with_its_entry(files):
    edit(files / "metrics" / "sched_host_share_pct.json", unit="s")
    with pytest.raises(spec.SpecError, match="'unit' is 's' in its file"):
        load(here=files)


def test_the_files_kept_for_chat_steady_still_fit_together():
    """``gpt2-xl.chat_steady`` is not a cell yet (PERF.md, Open questions);
    its traffic file and its two metric files wait in the tree. With its
    entries added, the cell loads."""
    bench = copy.deepcopy(BENCH)
    cell = "gpt2-xl.chat_steady"
    bench["workloads"].append(
        {"name": cell, "config": "gpt2-xl", "traffic": "chat_steady", "chips": 1, "why": "x"}
    )
    bench["end_to_end"].append(
        {"name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": [cell]}
    )
    for m in bench["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append(cell)
    for name, moves in (("queue_wait_p95_ms", "ttft_p95_ms"), ("device_idle_pct.steady", "tpot_p95_ms")):
        f = json.loads((HERE / "metrics" / f"{name}.json").read_text())
        bench["per_layer"].append(
            {"name": name, **{k: f[k] for k in ("unit", "better", "source", "layer")},
             "moves": moves, "workloads": [cell]}
        )
    loaded = load(cell, bench=bench)
    assert loaded.traffic["kind"] == "serve_open"
    assert {m["name"] for m in loaded.per_layer} >= {"queue_wait_p95_ms", "device_idle_pct.steady"}
