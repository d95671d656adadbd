"""The latent-attention and expert formulas, the counted reader, and how a
family brings them to the harness's tables."""

import pytest

from benchmark import costs, moe_costs, readers
from benchmark.families import joyai_llm_flash  # noqa: F401  (registers)

MODEL = {"kv_rank": 512, "rope_dim": 64, "num_layers": 5, "features": 2048, "expert_hidden": 768}


def test_latent_bytes_count_whole_pages_of_one_row_a_token():
    # 130 tokens at page 128 are two pages; 576 values x 2 B x 5 layers a token.
    assert moe_costs.mla_decode_attn_bytes(MODEL, 128, [130]) == 2 * 128 * 1152 * 5
    assert moe_costs.mla_decode_attn_bytes(MODEL, 128, [1, 128, 129]) == 4 * 128 * 1152 * 5


def test_an_expert_read_is_its_three_matrices():
    assert moe_costs.moe_expert_bytes(MODEL, 1) == 9_437_184
    assert moe_costs.moe_expert_bytes(MODEL, 162.5) == 162.5 * 9_437_184


def test_the_family_registers_without_replacing():
    assert costs.FORMULAS["mla_decode_attn_bytes"] is moe_costs._mla_decode_attn
    assert readers.READERS["trace_roofline_counted"] is moe_costs.trace_roofline_counted
    moe_costs.register()                       # again: its own keys, no error
    costs.FORMULAS["moe_expert_bytes"] = lambda work: (0.0, "hbm_bytes_per_s")
    try:
        with pytest.raises(KeyError, match="already registered"):
            moe_costs.register()
    finally:
        costs.FORMULAS["moe_expert_bytes"] = moe_costs._moe_experts


class _Plane:
    pass


def test_the_counted_reader_prices_reads_per_decode_token(monkeypatch):
    reads, steps = 'engine_moe_expert_reads_total{phase="decode"}', "engine_decode_steps_total"
    obs = {
        "registry": {"start": {reads: 100.0, steps: 10.0}, "end": {reads: 2100.0, steps: 410.0}},
        "work": {"model": MODEL, "page_size": 128, "decode_contexts_in_slice": [300] * 64},
        "peaks": {"hbm_bytes_per_s": 819e9}, "trace": object(),
    }
    seen = {}

    def fake_roofline(p, o):
        seen.update(o["work"])
        return 42.0

    monkeypatch.setattr(readers, "trace_roofline", fake_roofline)
    p = {"count": reads, "per": steps, "into": "moe_expert_reads_in_slice", "formula": "moe_expert_bytes"}
    assert moe_costs.trace_roofline_counted(p, obs) == 42.0
    assert seen["moe_expert_reads_in_slice"] == 2000 / 400 * 64      # 5 reads a token
    assert costs.FORMULAS["moe_expert_bytes"](seen) == (320 * 9_437_184.0, "hbm_bytes_per_s")
    # Nothing counted (the parent, or an engine without experts): no metric.
    assert moe_costs.trace_roofline_counted(p, {**obs, "registry": {"start": {}, "end": {}}}) is None
