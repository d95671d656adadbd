"""The ``lfm2_moe`` family's formulas at the published widths of
``configs/lfm2-8b-a1b.json``, and its reader on a made-up trace."""

import json

import pytest

from benchmark import costs, lfm2_moe_costs, readers, spec
from benchmark import trace as tr
from benchmark.families import lfm2_moe as family

MODEL = family.model_dims(
    json.loads((spec.ROOT / "benchmark" / "configs" / "lfm2-8b-a1b.json").read_text())
)
BATCH, SEQ = 2, 8192


def test_registered_without_replacing_anything():
    for key in ("lfm2_moe_train_step_flops", "lfm2_moe_flash_attn_flops", "lfm2_moe_expert_flops"):
        assert key in costs.FORMULAS
    assert "trace_roofline_expert_passes" in readers.READERS
    assert costs.FORMULAS["train_step_flops"] is costs._train_step     # the GPT-2 one stays
    lfm2_moe_costs.register()                                          # twice is the same


def test_forward_flops_a_token_by_part():
    """The issue's table, MFLOP a token forward: 5 conv operators 168, ONE
    attention layer's projections 21 and scores 34, 2 dense feed-forwards
    176, 4 expert layers' router + expected held picks 89, the head 67."""
    d = MODEL["features"]
    assert lfm2_moe_costs.attn_layers(MODEL) == 1
    assert lfm2_moe_costs.held_assignments(MODEL, 16384) == 16384        # 4 x 8 / 32 = 1 a token
    params = lfm2_moe_costs.matmul_params_per_token(MODEL)
    conv, attn = 5 * 4 * d * d, 2 * d * 2048 + 2 * d * 512
    dense, head = 2 * 3 * d * 7168, d * 16384
    routed = 4 * (d * 32 + 3 * d * 1792)
    assert params == conv + attn + dense + routed + head == 260_308_992
    scores = lfm2_moe_costs.attn_flops_per_token_fwd(MODEL, SEQ)
    assert scores == 4 * SEQ * 32 * 64 * 0.5 == 33_554_432
    forward = 2 * params + scores
    assert round(forward / 1e6) == 554


def test_step_flops_and_the_kernels_shares():
    step = lfm2_moe_costs.train_step_flops(MODEL, BATCH, SEQ)
    assert step == pytest.approx(27.24e12, rel=2e-3)
    flash = lfm2_moe_costs.flash_attn_flops(MODEL, BATCH, SEQ)
    assert flash == 3 * 33_554_432 * BATCH * SEQ
    # costs.flash_attn_flops counts every layer an attention layer: 6 x too much here.
    assert costs.flash_attn_flops({**MODEL, "num_layers": 6}, BATCH, SEQ) == 6 * flash
    one_pass = lfm2_moe_costs.expert_pass_flops(MODEL, BATCH * SEQ)
    assert one_pass == 16384 * 4 * 6 * 2048 * 1792
    # Forward + backward of the experts (3 passes) is 16 % of the step's model FLOPs.
    assert 3 * one_pass / step == pytest.approx(0.159, abs=2e-3)


def _plane(ops):
    t, events = 0.0, []
    for name, dur in ops:
        events.append((name, t, dur))
        t += dur
    return tr.Plane("/device:TPU:0", [("jit_step", 0.0, t)], events)


def test_the_reader_counts_forward_passes_from_the_trace():
    """One traced step of 4 expert layers under remat: 8 forward calls, 4
    ``_dx`` and 4 ``_dw``: (8 + 2 x 4) / 4 = 4 passes over every layer."""
    params = json.loads((spec.HERE / "metrics" / "moe_expert_train_roofline.json").read_text())["params"]
    ms = 1e6
    ops = (
        [("moe.experts.%d" % i, 4 * ms) for i in range(8)]
        + [("moe.experts_dx.%d" % i, 6 * ms) for i in range(4)]
        + [("moe.experts_dw.%d" % i, 4 * ms) for i in range(4)]
        + [("fusion.1", 100 * ms)]
    )
    plane = _plane(ops)
    obs = {
        "trace": tr.Reduced([plane], 0.172, 0.172),
        "work": {"model": MODEL, "batch": BATCH, "seq": SEQ, "steps_in_slice": 1},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    }
    share = readers.READERS["trace_roofline_expert_passes"](params, obs)
    flops = 4 * lfm2_moe_costs.expert_pass_flops(MODEL, BATCH * SEQ)
    assert share == pytest.approx(100 * (flops / 197e12) / 0.072)
    assert 0 < share < 100
    # A program without the backward calls (the parent commit): nothing to read.
    forward_only = _plane(ops[:8])
    obs["trace"] = tr.Reduced([forward_only], 0.032, 0.032)
    assert readers.READERS["trace_roofline_expert_passes"](params, obs) is None
    assert readers.READERS["trace_roofline_expert_passes"](params, {**obs, "trace": None}) is None
