"""The nemotron_h family's formulas, by hand, and how the family brings them
to the harness's table."""

import pytest

import types

from benchmark import costs, nemotron_h_costs as nc, readers
from benchmark import trace as tr
from benchmark.families import nemotron_h  # noqa: F401  (registers)

MODEL = {
    "pattern": "MEMEMEM*EME", "ssm_heads": 128, "ssm_head_dim": 64, "ssm_groups": 8,
    "ssm_state": 128, "ssm_chunk": 128, "latent": 1024, "expert_hidden": 2688,
}


def test_a_decode_token_moves_every_mamba_layers_state_both_ways():
    assert nc.ssm_state_bytes(MODEL) == 128 * 64 * 128 * 4 == 4_194_304
    assert nc.ssm_state_update_bytes(MODEL, 1) == 5 * 2 * 4_194_304
    assert nc.ssm_state_update_bytes(MODEL, 32 * 16) == 512 * 41_943_040


def test_a_latent_expert_read_is_its_two_matrices():
    assert nc.latent_moe_expert_bytes(MODEL, 1) == 2 * 1024 * 2688 * 2 == 11_010_048
    assert nc.latent_moe_expert_bytes(MODEL, 96.5) == 96.5 * 11_010_048


def test_a_chunk_row_is_bound_by_its_bytes_on_a_v5e():
    flops, nbytes = nc.ssm_chunk_scan_cost(MODEL, 1)
    # Per layer: C B^T 33.6 M, in-tile outputs 268.4 M, the state's two
    # products 536.9 M operations; state 8.39 MB, u and y 4.19 MB, B and C
    # 0.52 MB, dt 0.07 MB.
    assert flops == 5 * (2 * 8 * 128 * 128 * 128 + 2 * 128 * 128 * 128 * 64 + 4 * 128 * 128 * 64 * 128)
    assert nbytes == 5 * (2 * 4_194_304 + 2 * 128 * 8192 * 2 + 2 * 128 * 1024 * 2 + 128 * 128 * 4)
    assert flops / 197e12 < nbytes / 819e9
    assert nc.slower_bound(flops, nbytes) == (nbytes, "hbm_bytes_per_s")
    assert nc.slower_bound(1e15, 1.0) == (1e15, "bf16_flops")


def test_the_formulas_read_the_work_table():
    work = {
        "model": MODEL, "decode_contexts_in_slice": [300] * 64,
        "moe_expert_reads_in_slice": 10.0, "ssm_chunk_rows_in_slice": 3.0,
    }
    assert costs.FORMULAS["ssm_state_update_bytes"](work) == (64 * 41_943_040.0, "hbm_bytes_per_s")
    assert costs.FORMULAS["latent_moe_expert_bytes"](work) == (110_100_480.0, "hbm_bytes_per_s")
    amount, peak = costs.FORMULAS["ssm_chunk_scan_cost"](work)
    assert peak == "hbm_bytes_per_s" and amount == 3 * nc.ssm_chunk_scan_cost(MODEL, 1)[1]


def _slice(calls, refill_runs=1):
    """A traced slice with ``calls`` scan calls of 750 us inside refill runs, and one stray call outside."""
    ops = [("ssm.chunk_scan.%d" % i, 100.0 + i, 750_000.0) for i in range(calls)]
    ops.append(("ssm.chunk_scan.99", 5e9, 750_000.0))          # inside no refill run
    plane = tr.Plane("chip0", [("jit_refill_step", 0.0, 1e9)] * refill_runs, ops)
    return {
        "trace": types.SimpleNamespace(planes=[plane]),
        "work": {"model": MODEL}, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "recorder_events": [
            {"kind": "engine.dispatch", "family": "decode_block", "token_slots": 0},
            {"kind": "engine.dispatch", "family": "refill_step", "token_slots": 4096},
            {"kind": "engine.dispatch", "family": "refill_step", "token_slots": 8192},   # a chained pair
        ],
    }


PARAMS = {
    "formula": "ssm_chunk_scan_cost", "op": "ssm.chunk_scan", "module": "jit_refill_step",
    "family": "refill_step", "into": "ssm_chunk_rows_in_slice",
}


def test_the_scan_is_priced_on_the_calls_the_slice_holds():
    # 25 calls = 5 dispatches of 32 rows through 5 layers: 160 rows x 5 x
    # 13,172,736 B = 12.87 ms at 819 GB/s over 25 x 750 us: 68.6 %, whatever
    # else the slice holds (what a window-wide rate read as 114 %).
    got = readers.READERS["trace_roofline_calls"](PARAMS, _slice(25))
    assert got == pytest.approx(100 * (160 * 5 * 13_172_736 / 819e9) / (25 * 750e-6))
    assert 68 < got < 69
    assert readers.READERS["trace_roofline_calls"](PARAMS, _slice(5)) == pytest.approx(got)


def test_the_scan_reader_returns_nothing_where_there_is_nothing_to_read():
    reader = readers.READERS["trace_roofline_calls"]
    assert reader(PARAMS, {**_slice(25), "trace": None}) is None
    assert reader(PARAMS, {**_slice(25), "recorder_events": []}) is None
    assert reader(PARAMS, _slice(0)) is None


def test_the_family_registers_without_replacing():
    nc.register()                              # again: its own keys, no error
    costs.FORMULAS["ssm_state_update_bytes"] = lambda work: (0.0, "hbm_bytes_per_s")
    try:
        with pytest.raises(KeyError, match="already registered"):
            nc.register()
    finally:
        costs.FORMULAS["ssm_state_update_bytes"] = nc._ssm_state_update
