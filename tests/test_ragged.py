"""Ragged serving: per-row cache lengths end-to-end.

The serving case the rectangular stack could not express: a batch of
MIXED-length prompts, each row generating from its own length. Oracles:

* kernel level — ``decode_attention`` with a per-row ``(B,)`` index equals
  running each row separately at its scalar index (the per-row clamp maps
  cannot leak across rows);
* model level — ``make_generate_fn(ragged=True)`` on a right-padded
  mixed-length batch produces EXACTLY what per-row single (rectangular)
  runs produce, dense AND blocked backends, greedy fp32 (bit-identical on
  the CPU backend);
* EOS rows stop consuming cache — a ``chunk_lengths=0`` step leaves
  ``cache_index``/``position`` untouched (the mechanism behind "finished
  rows stop paying attention traffic").

The throughput claim (short rows fetch fewer cache blocks than pad-to-max)
is a real-TPU measurement — PERF.md "Ragged serving".
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_jax_sharding_tpu.models.generate import make_generate_fn
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_TINY,
    Transformer,
)
from learning_jax_sharding_tpu.ops.decode_attention import (
    decode_attention,
    fuse_kv,
)
from learning_jax_sharding_tpu.parallel import mesh_sharding, put
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from learning_jax_sharding_tpu.training.pipeline import sharded_train_state

LENGTHS = [3, 8, 5, 1]  # includes the batch max (8) and a length-1 row
PROMPT_MAX = 8
NEW = 6


@pytest.fixture(scope="module")
def tiny_setup(mesh22):
    cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, cfg.vocab_size, size=(4, PROMPT_MAX)).astype(np.int32)
    for b, l in enumerate(LENGTHS):
        prompt[b, l:] = 0  # right-pad with an arbitrary id
    x = put(prompt, mesh_sharding(mesh22, "data", None))
    state, _ = sharded_train_state(
        Transformer(cfg), optax.sgd(1e-2), x,
        {"params": jax.random.key(0)}, mesh22, RULES_DP_TP,
    )
    return cfg, nn.meta.unbox(state.params), prompt


class TestKernelPerRowIndex:
    def test_matches_per_row_scalar_runs(self, rng):
        b, n_kv, length, h, group = 4, 2, 64, 16, 2
        n = n_kv * group
        q = jnp.asarray(rng.normal(size=(b, 1, n, h)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, n_kv, length, h)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, n_kv, length, h)), jnp.float32)
        idx = jnp.asarray([5, 40, 17, 0], jnp.int32)
        with jax.default_matmul_precision("float32"):
            batched = decode_attention(
                q, fuse_kv(kc, vc), idx, block_k=16, interpret=True
            )
            for row in range(b):
                single = decode_attention(
                    q[row : row + 1],
                    fuse_kv(kc[row : row + 1], vc[row : row + 1]),
                    int(idx[row]), block_k=16, interpret=True,
                )
                np.testing.assert_allclose(
                    np.asarray(batched[row]), np.asarray(single[0]), atol=1e-6
                )

    def test_per_row_window(self, rng):
        """Sliding windows compose with per-row indexes (each row's band
        starts at ITS index)."""
        b, n_kv, length, h = 3, 1, 64, 16
        q = jnp.asarray(rng.normal(size=(b, 1, n_kv, h)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, n_kv, length, h)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, n_kv, length, h)), jnp.float32)
        idx = jnp.asarray([50, 9, 23], jnp.int32)
        with jax.default_matmul_precision("float32"):
            batched = decode_attention(
                q, fuse_kv(kc, vc), idx, window=16, block_k=8, interpret=True
            )
            for row in range(b):
                single = decode_attention(
                    q[row : row + 1],
                    fuse_kv(kc[row : row + 1], vc[row : row + 1]),
                    int(idx[row]), window=16, block_k=8, interpret=True,
                )
                np.testing.assert_allclose(
                    np.asarray(batched[row]), np.asarray(single[0]), atol=1e-6
                )


class TestRaggedGenerate:
    @pytest.mark.parametrize("backend", ["dense", "blocked"])
    def test_matches_per_row_single_runs(self, tiny_setup, mesh22, backend):
        """THE ragged oracle: every row of the mixed-length batch generates
        exactly what a rectangular run of that row alone produces."""
        cfg, params, prompt = tiny_setup
        cfg = dataclasses.replace(cfg, decode_attention=backend)
        gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW, ragged=True
        )
        out = np.asarray(
            gen(params, prompt, jax.random.key(1), lengths=np.asarray(LENGTHS))
        )
        single_gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW
        )
        for row, l in enumerate(LENGTHS):
            # Rectangular run on the row's exact prompt (duplicated to b=2:
            # the mesh's data axis must divide the batch).
            ref = np.asarray(
                single_gen(
                    params,
                    np.repeat(prompt[row : row + 1, :l], 2, axis=0),
                    jax.random.key(1),
                )
            )
            np.testing.assert_array_equal(
                out[row, : l + NEW], ref[0],
                err_msg=f"row {row} (length {l}, backend {backend})",
            )

    @pytest.mark.parametrize("backend", ["dense", "blocked"])
    def test_int8_cache_ragged(self, tiny_setup, mesh22, backend):
        """Per-row scale writes land at per-row offsets too — including the
        blocked backend's FOLDED in-kernel write of values AND scales."""
        cfg, params, prompt = tiny_setup
        cfg = dataclasses.replace(
            cfg, kv_cache_dtype=jnp.int8, decode_attention=backend
        )
        gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW, ragged=True
        )
        out = np.asarray(
            gen(params, prompt, jax.random.key(1), lengths=np.asarray(LENGTHS))
        )
        single_gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW
        )
        for row, l in enumerate(LENGTHS):
            ref = np.asarray(
                single_gen(
                    params,
                    np.repeat(prompt[row : row + 1, :l], 2, axis=0),
                    jax.random.key(1),
                )
            )
            np.testing.assert_array_equal(out[row, : l + NEW], ref[0])

    def test_eos_rows_and_output_layout(self, tiny_setup, mesh22):
        """With eos_id set: output rows read [prompt_b, generated..., eos
        fill] and the result still matches per-row single runs."""
        cfg, params, prompt = tiny_setup
        # Use greedy output of the plain run to find a token the row WILL
        # emit, then rerun with that as eos — deterministic early stop.
        gen_plain = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW, ragged=True
        )
        out_plain = np.asarray(
            gen_plain(params, prompt, jax.random.key(1), lengths=np.asarray(LENGTHS))
        )
        eos = int(out_plain[0, LENGTHS[0] + 1])  # row 0's second new token
        gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW, ragged=True,
            eos_id=eos,
        )
        out = np.asarray(
            gen(params, prompt, jax.random.key(1), lengths=np.asarray(LENGTHS))
        )
        single_gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW, eos_id=eos
        )
        for row, l in enumerate(LENGTHS):
            ref = np.asarray(
                single_gen(
                    params,
                    np.repeat(prompt[row : row + 1, :l], 2, axis=0),
                    jax.random.key(1),
                )
            )
            np.testing.assert_array_equal(out[row, : l + NEW], ref[0])
            # EVERYTHING past the generated span is the eos fill — including
            # where the caller's prompt padding used to sit. A consumer
            # scanning for the terminator can never read stale pad ids.
            assert (out[row, l + NEW :] == eos).all(), out[row]

    def test_validation(self, tiny_setup, mesh22):
        cfg, params, prompt = tiny_setup
        gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=2, ragged=True
        )
        with pytest.raises(ValueError, match="lengths"):
            gen(params, prompt, jax.random.key(0))
        plain = make_generate_fn(cfg, mesh22, RULES_DP_TP, max_new_tokens=2)
        with pytest.raises(ValueError, match="ragged"):
            plain(params, prompt, jax.random.key(0), lengths=np.asarray(LENGTHS))
        with pytest.raises(ValueError, match="prefill_chunk_size"):
            make_generate_fn(
                cfg, mesh22, RULES_DP_TP, max_new_tokens=2, ragged=True,
                prefill_chunk_size=4,
            )


class TestFrozenRowsStopConsumingCache:
    def test_chunk_lengths_zero_freezes_index(self, tiny_setup, mesh22):
        """A step with chunk_lengths=0 must leave every cache_index AND the
        position counter untouched — how EOS-finished rows stop consuming
        cache slots (their writes land on the same dead slot forever)."""
        from learning_jax_sharding_tpu.models.decoding import (
            derive_decode_config,
            make_cached_apply,
        )
        from learning_jax_sharding_tpu.parallel.logical import activate

        cfg, params, prompt = tiny_setup
        dcfg = derive_decode_config(dataclasses.replace(cfg, decode_ragged=True))
        apply = make_cached_apply(Transformer(dcfg))
        lengths = jnp.asarray(LENGTHS, jnp.int32)
        with activate(mesh22, RULES_DP_TP):
            _, cache = apply(params, None, jnp.asarray(prompt), lengths)
            tok = jnp.zeros((4, 1), jnp.int32)
            active = jnp.asarray([1, 0, 1, 0], jnp.int32)
            _, cache2 = apply(params, cache, tok, active)

        def indexes(c):
            vals = []
            for path, leaf in jax.tree_util.tree_leaves_with_path(c):
                if getattr(path[-1], "key", None) in ("cache_index", "position"):
                    vals.append(np.asarray(leaf))
            return vals

        before, after = indexes(cache), indexes(cache2)
        assert before and len(before) == len(after)
        for bf, af in zip(before, after):
            np.testing.assert_array_equal(bf, np.asarray(LENGTHS))
            np.testing.assert_array_equal(af, np.asarray(LENGTHS) + [1, 0, 1, 0])
