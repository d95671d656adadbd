"""Continuous batching (models/serving.py): slot reuse over ragged caches.

THE oracle: scheduling must never change results — every request's output
is bit-identical to a rectangular single-prompt ``make_generate_fn`` run
of the same params (greedy, fp32, CPU backend), whatever batch size,
queue order, refill chunking, or slot the request landed on.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.models.generate import make_generate_fn
from learning_jax_sharding_tpu.models.serving import make_continuous_engine
from learning_jax_sharding_tpu.models.transformer import Transformer
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from tests._serving_common import (  # noqa: F401  (setup is a fixture)
    DRAFT_CFG,
    NEW,
    _draft_params,
    _rect_reference,
    setup,
)


class TestContinuousBatching:
    @pytest.mark.parametrize("backend", ["dense", "blocked"])
    def test_requests_match_single_runs(self, setup, mesh22, backend):
        """7 mixed-length requests through 2 slots: every output equals the
        rectangular single run — slots are reused ≥ 3 times each, and the
        12-token prompt streams through multiple refill chunks."""
        cfg, params, prompts = setup
        cfg = dataclasses.replace(cfg, decode_attention=backend)
        serve = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4,
        )
        outs = serve(params, prompts)
        assert len(outs) == len(prompts)
        for prompt, got in zip(prompts, outs):
            ref = _rect_reference(cfg, mesh22, params, prompt)
            np.testing.assert_array_equal(
                got, ref[: len(got)],
                err_msg=f"prompt len {len(prompt)}",
            )
            assert len(got) == len(prompt) + NEW

    def test_eos_retires_and_refills(self, setup, mesh22):
        """With an eos known to fire early for one request, its slot must
        retire at eos (output ends there) and still serve later queue
        entries correctly."""
        cfg, params, prompts = setup
        # Find an eos that row 0 emits as its second generated token.
        plain = _rect_reference(cfg, mesh22, params, prompts[0])
        eos = int(plain[len(prompts[0]) + 1])
        serve = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, eos_id=eos,
        )
        outs = serve(params, prompts)
        for prompt, got in zip(prompts, outs):
            ref = _rect_reference(cfg, mesh22, params, prompt, eos_id=eos)
            np.testing.assert_array_equal(got, ref[: len(got)])
            # Output ends at eos (inclusive) or at the budget.
            if eos in got[len(prompt):].tolist():
                assert got[-1] == eos
            else:
                assert len(got) == len(prompt) + NEW

    def test_more_slots_than_requests(self, setup, mesh22):
        cfg, params, prompts = setup
        serve = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=4, max_new_tokens=NEW,
            refill_chunk=8,
        )
        outs = serve(params, prompts[:2])
        for prompt, got in zip(prompts[:2], outs):
            ref = _rect_reference(cfg, mesh22, params, prompt)
            np.testing.assert_array_equal(got, ref[: len(got)])

    def test_masked_write_never_clamps_onto_history(self):
        """row_update_masked — the write primitive behind mixed
        refill/decode batches (the round-3 review bug): a zero-length row
        whose window start would CLAMP below its index (idx near the
        buffer end) must leave its buffer untouched, and a clamped
        PARTIAL chunk must land at its true offset."""
        from learning_jax_sharding_tpu.models.attention import (
            row_update_masked,
        )

        rng = np.random.default_rng(0)
        L, s = 64, 16
        buf = jnp.asarray(rng.normal(size=(3, L, 4)), jnp.float32)
        chunk = jnp.asarray(rng.normal(size=(3, s, 4)), jnp.float32)
        idx = jnp.asarray([60, 5, 56], jnp.int32)     # 60, 56 clamp (>48)
        lengths = jnp.asarray([0, 16, 8], jnp.int32)  # idle, full, partial
        out = np.asarray(
            row_update_masked(buf, chunk, idx, lengths, seq_dim=1)
        )
        # Row 0 (zero-length, clamped window): bitwise untouched.
        np.testing.assert_array_equal(out[0], np.asarray(buf[0]))
        # Row 1 (plain full write at 5): chunk lands at [5, 21).
        np.testing.assert_array_equal(out[1, 5:21], np.asarray(chunk[1]))
        np.testing.assert_array_equal(out[1, :5], np.asarray(buf[1, :5]))
        np.testing.assert_array_equal(out[1, 21:], np.asarray(buf[1, 21:]))
        # Row 2 (clamped partial): first 8 chunk positions land at their
        # TRUE offset 56..64; everything below 56 keeps history.
        np.testing.assert_array_equal(out[2, 56:], np.asarray(chunk[2, :8]))
        np.testing.assert_array_equal(out[2, :56], np.asarray(buf[2, :56]))

    def test_validation(self, setup, mesh22):
        cfg, params, prompts = setup
        with pytest.raises(ValueError, match="batch_size"):
            make_continuous_engine(
                cfg, mesh22, RULES_DP_TP, batch_size=0, max_new_tokens=2
            )
        with pytest.raises(ValueError, match="max_new_tokens"):
            make_continuous_engine(
                cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=0
            )
        with pytest.raises(ValueError, match="refill_chunk"):
            make_continuous_engine(
                cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=2,
                refill_chunk=cfg.max_seq_len + 1,
            )
        serve = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2,
            max_new_tokens=cfg.max_seq_len,
        )
        with pytest.raises(ValueError, match="max_seq_len"):
            serve(params, [np.ones((8,), np.int32)])


class TestSpeculativeEngine:
    """Speculative decode blocks inside the continuous engine: a draft
    model proposes inside every decode dispatch, acceptance and cache
    rollback are per-row. Oracle: output bit-identical to the plain
    (non-speculative) greedy engine — which is itself pinned to
    rectangular single runs — whatever the draft proposes."""

    @pytest.mark.parametrize("backend", ["dense", "blocked"])
    def test_matches_plain_engine(self, setup, mesh22, backend):
        cfg, params, prompts = setup
        cfg = dataclasses.replace(cfg, decode_attention=backend)
        dcfg = dataclasses.replace(DRAFT_CFG, decode_attention=backend)
        plain = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4,
        )
        spec = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, draft_config=dcfg, num_draft=3,
        )
        ref = plain(params, prompts)
        got = spec(params, prompts, draft_params=_draft_params())
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)

    def test_eos_truncates_in_round(self, setup, mesh22):
        """EOS emitted mid-round (inside an accepted draft run) must
        truncate that row's emission exactly where the plain engine
        stops."""
        cfg, params, prompts = setup
        plain_out = _rect_reference(cfg, mesh22, params, prompts[0])
        eos = int(plain_out[len(prompts[0]) + 1])
        plain = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, eos_id=eos,
        )
        spec = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, eos_id=eos, draft_config=DRAFT_CFG, num_draft=3,
        )
        ref = plain(params, prompts)
        got = spec(params, prompts, draft_params=_draft_params())
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)

    def test_self_draft_matches_too(self, setup, mesh22):
        """Draft == target: the all-accept path (every round emits
        num_draft+1 tokens) — still bit-identical."""
        cfg, params, prompts = setup
        plain = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
        )
        spec = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            draft_config=cfg, num_draft=2,
        )
        ref = plain(params, prompts[:3])
        got = spec(params, prompts[:3], draft_params=params)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)

    def test_acceptance_stats(self, setup, mesh22):
        """serve.last_stats surfaces verifier acceptance: self-draft is
        exactly 1.0; an untrained draft against the trained-ish target is
        below it; the plain engine reports None."""
        cfg, params, prompts = setup
        spec = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, draft_config=cfg, num_draft=3,
        )
        spec(params, prompts[:3], draft_params=params)
        stats = spec.last_stats
        assert stats["spec_accept_rate"] == 1.0
        assert stats["spec_proposed"] > 0
        weak = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, draft_config=DRAFT_CFG, num_draft=3,
        )
        weak(params, prompts[:3], draft_params=_draft_params())
        assert weak.last_stats["spec_accept_rate"] < 1.0
        plain = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
        )
        plain(params, prompts[:3])
        assert plain.last_stats is None

    def test_validation(self, setup, mesh22):
        cfg, params, prompts = setup
        spec = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=4,
            draft_config=DRAFT_CFG,
        )
        with pytest.raises(ValueError, match="draft_params"):
            spec(params, prompts[:1])
        plain = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=4,
        )
        with pytest.raises(ValueError, match="draft_config"):
            plain(params, prompts[:1], draft_params=_draft_params())


class TestReproducibleSampling:
    """temperature > 0: every draw is keyed by (request id, generated
    position), so a request's sampled stream is a function of (rng,
    request index, its own prompt) — NOT of scheduling. The same queue
    served under any batch size / chunking yields identical outputs."""

    def test_schedule_independent(self, setup, mesh22):
        cfg, params, prompts = setup
        key = jax.random.key(5)
        outs = []
        for bs, chunk in ((2, 4), (3, 8), (4, 16)):
            serve = make_continuous_engine(
                cfg, mesh22, RULES_DP_TP, batch_size=bs, max_new_tokens=NEW,
                refill_chunk=chunk, temperature=1.0, top_k=16,
            )
            outs.append(serve(params, prompts, rng=key))
        for other in outs[1:]:
            for a, b in zip(outs[0], other):
                np.testing.assert_array_equal(a, b)

    def test_rng_varies(self, setup, mesh22):
        cfg, params, prompts = setup
        serve = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            temperature=1.0, top_k=16,
        )
        a = serve(params, prompts[:3], rng=jax.random.key(5))
        b = serve(params, prompts[:3], rng=jax.random.key(6))
        assert any((x.shape != y.shape) or (x != y).any() for x, y in zip(a, b))


class TestQuantizedEngine:
    """Quantized weights through the continuous engine (`dequantize=`,
    mirroring make_generate_fn). Oracle: every request bit-identical to
    the same-dequantize rectangular single run."""

    def _ref(self, cfg, mesh22, tree, prompt, dequantize):
        gen = make_generate_fn(
            cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW,
            dequantize=dequantize,
        )
        out = np.asarray(
            gen(tree, np.repeat(prompt[None, :], 2, axis=0),
                jax.random.key(0))
        )
        return out[0]

    @pytest.mark.parametrize("dequantize,bits", [(True, 8), ("fused", 4)])
    def test_matches_single_runs(self, setup, mesh22, dequantize, bits):
        from learning_jax_sharding_tpu.models.quantize import quantize_tree

        cfg, params, prompts = setup
        tree = quantize_tree(params, bits=bits)
        serve = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, dequantize=dequantize,
        )
        outs = serve(tree, prompts[:4])
        for p, got in zip(prompts[:4], outs):
            ref = self._ref(cfg, mesh22, tree, p, dequantize)
            np.testing.assert_array_equal(got, ref[: len(got)])

    def test_validation(self, setup, mesh22):
        cfg, _, _ = setup
        with pytest.raises(ValueError, match="dequantize"):
            make_continuous_engine(
                cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=4,
                dequantize="nope",
            )


class TestSampledSpeculativeEngine:
    """Speculative SAMPLING inside the engine: Leviathan rejection with
    draws keyed by (request id, generated position, stream tag). Oracles:
    a request's sampled output is independent of scheduling — same queue
    under any batch size / refill chunk, and equal to the request served
    ALONE — and different rngs give different streams."""

    def _engine(self, cfg, mesh22, **kw):
        args = dict(
            batch_size=2, max_new_tokens=NEW, refill_chunk=4,
            draft_config=DRAFT_CFG, num_draft=3, temperature=1.0, top_k=16,
        )
        args.update(kw)
        return make_continuous_engine(cfg, mesh22, RULES_DP_TP, **args)

    def test_schedule_independent(self, setup, mesh22):
        cfg, params, prompts = setup
        key = jax.random.key(7)
        dp = _draft_params()
        outs = []
        for bs, chunk in ((2, 4), (3, 8), (4, 16)):
            serve = self._engine(cfg, mesh22, batch_size=bs,
                                 refill_chunk=chunk)
            outs.append(serve(params, prompts, rng=key, draft_params=dp))
        for other in outs[1:]:
            for a, b in zip(outs[0], other):
                np.testing.assert_array_equal(a, b)

    def test_equals_request_served_alone(self, setup, mesh22):
        cfg, params, prompts = setup
        key = jax.random.key(7)
        dp = _draft_params()
        batched_engine = self._engine(cfg, mesh22, batch_size=4)
        solo_engine = self._engine(cfg, mesh22, batch_size=1)
        for i, p in enumerate(prompts[:4]):
            # Request identity is the QUEUE INDEX: served alone a request
            # is request 0, so rotate the queue to put prompt i at the
            # head — its keys then match the solo run's.
            rotated = prompts[i:] + prompts[:i]
            batched = batched_engine(
                params, rotated, rng=key, draft_params=dp
            )
            solo = solo_engine(params, [p], rng=key, draft_params=dp)
            np.testing.assert_array_equal(batched[0], solo[0])

    def test_rng_varies(self, setup, mesh22):
        cfg, params, prompts = setup
        dp = _draft_params()
        serve = self._engine(cfg, mesh22)
        a = serve(params, prompts[:2], rng=jax.random.key(1), draft_params=dp)
        b = serve(params, prompts[:2], rng=jax.random.key(2), draft_params=dp)
        assert any(
            (x.shape != y.shape) or (x != y).any() for x, y in zip(a, b)
        )

    def test_joint_matches_target_distribution(self, setup, mesh22):
        """The Leviathan math itself, pinned at engine level: 1024
        requests with the SAME prompt are 1024 iid (request-id-keyed)
        2-token samples whose first token comes from the refill's plain
        filtered sampling and whose second comes through the spec block's
        accept/residual paths (an untrained draft keeps acceptance
        genuinely partial). Their empirical joint must match the exact
        target joint — a wrong acceptance rule or residual skews it."""
        from learning_jax_sharding_tpu.models.generate import top_k_filter
        from learning_jax_sharding_tpu.models.transformer import Transformer

        cfg, params, _ = setup
        dp = _draft_params()
        n = 1024
        prompt_row = np.asarray(
            np.random.default_rng(4).integers(1, cfg.vocab_size, size=(1, 8)),
            np.int32,
        )
        serve = self._engine(
            cfg, mesh22, batch_size=32, max_new_tokens=2, num_draft=1,
            top_k=4, refill_chunk=8,
        )
        outs = serve(
            params, [prompt_row[0]] * n, rng=jax.random.key(13),
            draft_params=dp,
        )
        pairs = np.stack([o[8:10] for o in outs])

        model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32))
        v = cfg.vocab_size

        def filtered_probs(toks):
            logits = model.apply({"params": params}, jnp.asarray(toks))
            return np.asarray(
                jax.nn.softmax(
                    top_k_filter(logits[:, -1].astype(jnp.float32), 4),
                    axis=-1,
                )
            )

        p0 = filtered_probs(prompt_row)[0]
        exact = np.zeros((v, v))
        (support0,) = np.nonzero(p0)
        for t0 in support0:
            row = np.concatenate(
                [prompt_row, [[t0]]], axis=1
            ).astype(np.int32)
            exact[t0] = p0[t0] * filtered_probs(row)[0]
        emp = np.zeros((v, v))
        for t0, t1 in pairs:
            emp[t0, t1] += 1.0 / n
        assert (emp[exact == 0] == 0).all()
        tv = 0.5 * np.abs(emp - exact).sum()
        # 1024 samples over <=16 cells: expected TV ~0.06.
        assert tv < 0.15, f"total variation {tv:.3f}"

    def test_greedy_spec_unchanged(self, setup, mesh22):
        """temperature=0 speculative must still be bit-identical to plain
        greedy engine output (the pre-existing oracle, re-pinned across
        this change)."""
        cfg, params, prompts = setup
        dp = _draft_params()
        plain = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4,
        )
        spec = self._engine(cfg, mesh22, temperature=0.0, top_k=None)
        a = plain(params, prompts)
        b = spec(params, prompts, draft_params=dp)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
