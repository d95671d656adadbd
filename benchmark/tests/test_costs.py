"""The flops/bytes table against the program's own arithmetic and by hand."""

import json
import pathlib

import pytest

from benchmark import costs
from benchmark.families import gpt2

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("name", ["gpt2-large", "gpt2-xl"])
def test_train_step_flops_matches_the_program(name):
    model = json.loads((CONFIGS / f"{name}.json").read_text())
    dims = gpt2.model_dims(model)
    cfg = gpt2.to_config(model)
    assert costs.train_step_flops(dims, 8, 1024) == pytest.approx(
        cfg.train_step_flops(8, 1024), rel=1e-12
    )


def test_gpt2_large_step_by_hand():
    dims = gpt2.model_dims(json.loads((CONFIGS / "gpt2-large.json").read_text()))
    per_layer = 4 * 1280 * 1280 + 2 * 1280 * 5120
    params = 36 * per_layer + 1280 * 50257
    assert costs.matmul_params(dims) == params == 772_117_760
    attn = 4 * 1024 * 20 * 64 * 36 * 0.5
    assert costs.train_step_flops(dims, 8, 1024) == (6 * params + 3 * attn) * 8192
    assert costs.flash_attn_flops(dims, 8, 1024) == 3 * attn * 8192


def test_decode_step_bytes_by_hand():
    dims = gpt2.model_dims(json.loads((CONFIGS / "gpt2-xl.json").read_text()))
    # one token's K and V in one layer: 2 x 25 heads x 64 x 2 B
    assert costs.kv_bytes_per_token_layer(dims) == 6400
    # rows with 100 and 129 cached tokens, pages of 64: 2 + 3 pages
    want = (2 + 3) * 64 * 6400 * 48
    assert costs.decode_attn_bytes(dims, 64, [100, 129]) == want == 98_304_000
