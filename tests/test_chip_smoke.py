"""``chip_smoke.py`` away from the chip: what can be checked on the CPU.

The script itself has no CPU mode. These tests pin the parts of it that a
chip run leans on — the plain float32 reference really is the model, the
teacher-forced rule excuses only low-margin positions, the compile cache is
placed from outside — and rehearse its control flow at a tiny size with the
kernels under the interpreter, so a broken phase costs no chip time.
"""

import dataclasses
import functools
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as cs
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_TINY,
    Transformer,
)
from learning_jax_sharding_tpu.ops import flash_attention as flash_mod
from learning_jax_sharding_tpu.parallel import single_device_mesh
from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING
from learning_jax_sharding_tpu.telemetry import CompileWatch
from learning_jax_sharding_tpu.utils import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_without_a_tpu_it_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_reference_is_the_model_in_float32():
    cfg = CONFIG_TINY                       # float32 compute and params
    params = cs.init_params(cfg, single_device_mesh(), RULES_TP_SERVING, 3)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, size=(2, 48)),
        jnp.int32,
    )
    with jax.default_matmul_precision("highest"):
        want = Transformer(cfg).apply({"params": params}, tokens)
    got = cs.reference_fn(cfg)(params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


class TestTeacherForced:
    """The rule on hand-made reference logits: vocab 4, one request, a
    prompt of one token, two generated tokens."""

    prompts = [np.array([0], np.int32)]

    @staticmethod
    def logits(*rows):
        out = np.zeros((1, 3, 4), np.float32)
        out[0, : len(rows)] = rows
        return out

    def test_argmax_stream_passes_with_nothing_excused(self):
        out = cs.teacher_forced(
            self.logits([0, 1.0, 0, 0], [0, 0, 0, 1.0]),
            self.prompts, [np.array([0, 1, 3], np.int32)],
        )
        assert (out["positions"], out["excused_low_margin"]) == (2, 0)

    def test_low_margin_departure_is_excused_and_counted(self):
        gap = cs.MARGIN_TOL / 2
        out = cs.teacher_forced(
            self.logits([0, 1.0, 1.0 - gap, 0], [0, 0, 0, 1.0]),
            self.prompts, [np.array([0, 2, 3], np.int32)],
        )
        assert out["excused_low_margin"] == 1
        assert out["largest_excused_gap"] == pytest.approx(gap, abs=1e-5)

    def test_confident_departure_fails(self):
        logits = self.logits(
            [0, 1.0, 1.0 - 2 * cs.MARGIN_TOL, 0], [0, 0, 0, 1.0]
        )
        with pytest.raises(AssertionError, match="confident position 1"):
            cs.teacher_forced(
                logits, self.prompts, [np.array([0, 2, 3], np.int32)]
            )

    def test_streams_may_part_only_where_the_reference_is_indifferent(self):
        a, b = np.array([0, 1, 3], np.int32), np.array([0, 2, 0], np.int32)
        near = self.logits([0, 1.0, 1.0 - cs.MARGIN_TOL / 2, 0])
        out = cs.first_divergences(near, [a, a], [b, a])
        assert out["streams_identical"] == 1
        assert out["streams_split_at_low_margin"] == 1
        far = self.logits([0, 1.0, 1.0 - 2 * cs.MARGIN_TOL, 0])
        with pytest.raises(AssertionError, match="part at position 1"):
            cs.first_divergences(far, [a], [b])


class TestCompileCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        seen = []
        monkeypatch.setattr(
            compile_cache.jax.config, "update",
            lambda name, value: seen.append((name, value)),
        )
        return seen

    def test_env_decides_and_code_sets_nothing(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.place_compile_cache() == "/somewhere/else"
        assert updates == []

    def test_default_is_the_checkouts_own_directory(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
        assert compile_cache.place_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]

    def test_the_suite_itself_runs_without_it(self):
        assert not jax.config.jax_compilation_cache_dir


@pytest.fixture
def tiny(monkeypatch):
    """chip_smoke's sizes cut to CONFIG_TINY, kernels interpreted, and the
    compiled-kernel proof (there is no Mosaic on the CPU) switched off."""
    monkeypatch.setattr(cs, "BATCH", 4)
    monkeypatch.setattr(cs, "SEQ", 64)
    monkeypatch.setattr(cs, "TRAIN_STEPS", 3)
    monkeypatch.setattr(cs, "PROMPT_LENS", (3, 9, 5, 12, 7, 20))
    monkeypatch.setattr(cs, "NEW_TOKENS", 6)
    monkeypatch.setattr(cs, "SLOTS", 2)
    monkeypatch.setattr(cs, "REFILL_CHUNK", 8)
    monkeypatch.setattr(cs, "PAGE", 8)
    monkeypatch.setattr(cs, "require_kernel", lambda name, text: 0)
    monkeypatch.setattr(
        cs, "flash_attention",
        functools.partial(flash_mod.flash_attention, interpret=True),
    )
    return dataclasses.replace(
        CONFIG_TINY, dtype=jnp.bfloat16, decode_attention="blocked"
    )


def test_rehearse_kernels_and_trainer(tiny, capsys):
    flash = functools.partial(flash_mod.make_flash_attn_fn, interpret=True)
    errs = cs.check_kernels(tiny, 0)["rel_err"]
    assert set(errs) >= {"flash_fwd", "flash_dq", "decode_fold", "decode_chunk"}
    out = cs.check_trainer(tiny, 0, flash)
    assert out["losses"][-1] < out["losses"][0]
    assert out["loss_diff"] <= cs.LOSS_TOL


def test_rehearse_engine_families(tiny, capsys):
    import json

    with CompileWatch() as watch:
        cs.check_engine(tiny, 0, watch)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["phase"] for x in lines] == [
        f"engine_{name}" for name, _ in cs.ENGINE_FAMILIES
    ]
    for x in lines:
        assert x["tokens_served"] == 2 * 6 * 6      # two passes
        assert x["reference"]["positions"] == 6 * 6
        assert x["reference_warm_pass"]["positions"] == 6 * 6
        assert x["backend_compiles"] > 0
    assert "multi_step" in lines[2]["programs_kernels"]
    assert "mixed_step" in lines[1]["programs_kernels"]
