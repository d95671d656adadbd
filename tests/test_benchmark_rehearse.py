"""The benchmark's serving cells, rehearsed: ``benchmark/run.py --workload
<cell> --rehearse`` swaps in each file's tiny sizes and runs the WHOLE path
of a cell on the CPU (weights from the seed, the engine through its normal
entry point, the ramp, a short window, the teacher-forced comparison with
the family's float32 reference) to a result line. Not a measurement: what
is held here is that the line says ``correct: true`` with nothing compiled
inside the window, nothing failed, and the cell's per-layer counters on it.

A child process, as the driver runs it (the chip is not involved: the
platform is named outright), with a time limit of its own.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _rehearse(cell: str, trace: int) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--rehearse",
         "--seconds", "4", "--seed", "2147483999", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=170,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()]
    assert all("info" in line for line in lines[:-1])
    setup = next(line for line in lines if line.get("info") == "setup")
    assert setup["window_backend_compiles"] == 0
    return lines[-1]


@pytest.mark.parametrize(
    "cell,trace,reports",
    [
        ("joyai-llm-flash.chat_backlog_2k", 0, {"serve_tok_s", "tpot_p95_ms", "setup_s"}),
        (
            "joyai-llm-flash.chat_backlog_2k", 1,
            {"moe_tokens_per_expert_read", "refill_host_share_pct", "refill_fill_pct",
             "tpot_stall_p95_ms", "tpot_decode_p95_ms", "wait_host_share_pct"},
        ),
        (
            "nemotron-3-super-120b-a12b.chat_backlog_2k", 1,
            {"moe_tokens_per_expert_read", "refill_fill_pct", "ssm_carried_rows_pct",
             "wait_host_share_pct"},
        ),
        ("gpt2-xl.chat_backlog", 0, {"serve_tok_s", "tpot_p95_ms", "setup_s"}),
        (
            "gpt2-xl.chat_backlog", 1,
            {"refill_host_share_pct", "refill_fill_pct", "tpot_stall_p95_ms",
             "tpot_decode_p95_ms", "wait_host_share_pct"},
        ),
    ],
)
def test_a_serving_cell_rehearses_to_a_correct_line(cell, trace, reports):
    line = _rehearse(cell, trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert "rehearsal" in line and line["device"]["platform"] == "cpu"
    assert reports <= set(line["metrics"])
    # A rehearsal never reports a device-trace metric: it has no device.
    assert not {
        "mla_decode_attn_roofline", "moe_expert_roofline", "decode_attn_roofline",
        "ssm_state_update_roofline", "ssm_chunk_scan_roofline", "latent_moe_expert_roofline",
    } & set(line["metrics"])
    if "ssm_carried_rows_pct" in reports:
        # PR 30's packing is on: long prompts' further chunks ride along.
        assert 0.0 < line["metrics"]["ssm_carried_rows_pct"]["value"] < 100.0
    if "refill_fill_pct" in reports:
        # Prompt tokens over token slots: a share, and the dispatches carry prompts.
        assert 0.0 < line["metrics"]["refill_fill_pct"]["value"] <= 100.0
