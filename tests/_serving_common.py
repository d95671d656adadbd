"""What the three ``test_serving*.py`` files share: the tiny float32
model and its prompt queue (``setup``), the draft model, and the
rectangular single-prompt reference every scheduling oracle compares
against. One xdist worker takes a whole file (``--dist loadfile``), so the
engine's tests are three files, not one."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.models.generate import make_generate_fn
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_TINY,
    Transformer,
)
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP

NEW = 6


@pytest.fixture(scope="module")
def setup(mesh22):
    cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
    rng = np.random.default_rng(11)
    model = Transformer(cfg)
    probe = np.zeros((2, 8), np.int32)
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(3), probe
        )["params"]
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in (3, 9, 5, 1, 12, 7, 4)
    ]
    return cfg, params, prompts


def _rect_reference(cfg, mesh22, params, prompt, eos_id=None):
    gen = make_generate_fn(
        cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW, eos_id=eos_id
    )
    # b=2: the mesh's data axis must divide the batch.
    out = np.asarray(
        gen(params, np.repeat(prompt[None, :], 2, axis=0), jax.random.key(0))
    )
    return out[0]


DRAFT_CFG = dataclasses.replace(
    CONFIG_TINY, num_layers=1, hidden=64, dtype=jnp.float32
)


def _draft_params():
    model = Transformer(DRAFT_CFG)
    toks = np.zeros((2, 8), np.int32)
    return nn.meta.unbox(
        model.init({"params": jax.random.key(7)}, toks)["params"]
    )
