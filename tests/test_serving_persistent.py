"""The persistent engine object (models/serving.py, round 5): state that
survives across ``serve()`` calls and streaming sessions. Split from
``test_serving.py`` so that one xdist worker does not carry all of the
engine's tests."""


import dataclasses

import jax
import numpy as np
import pytest

from learning_jax_sharding_tpu.models.serving import make_continuous_engine
from learning_jax_sharding_tpu.parallel.logical import (
    RULES_DP_TP,
    RULES_TP_SERVING,
)
from tests._serving_common import (  # noqa: F401  (setup is a fixture)
    DRAFT_CFG,
    NEW,
    _draft_params,
    _rect_reference,
    setup,
)


class TestPersistentEngine:
    """Round 5: the engine OBJECT owns the cache, page pool, and prefix
    registry — state survives across serve() calls (and streaming
    sessions), so prefix hits span calls, the cache-creating refill runs
    once per engine ever, and requests can arrive over time."""

    PAGE = 16

    def _paged(self, cfg, mesh22, **kw):
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        return ContinuousEngine(
            dataclasses.replace(cfg, decode_attention="blocked"),
            mesh22, RULES_TP_SERVING, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, paged_pages=9, page_size=self.PAGE, **kw,
        )

    def test_prefix_hit_spans_serve_calls(self, setup, mesh22):
        """THE persistence payoff: a second serve() call with the same
        system prompt reuses the pages the first call retired — zero
        hits in call 1, hits in call 2, outputs bit-identical both
        times."""
        cfg, params, _ = setup
        bcfg = dataclasses.replace(cfg, decode_attention="blocked")
        rng = np.random.default_rng(21)
        base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
        plain = make_continuous_engine(
            bcfg, mesh22, RULES_TP_SERVING, batch_size=2,
            max_new_tokens=NEW, refill_chunk=4,
        )
        eng = self._paged(cfg, mesh22, prefix_cache=True)
        ref = plain(params, [base])
        got1 = eng.serve(params, [base])
        assert eng.last_stats["prefix_hits"] == 0
        assert eng.last_stats["prefix_pages_retained"] >= 1
        got2 = eng.serve(params, [base.copy()])
        assert eng.last_stats["prefix_hits"] == 1
        assert eng.last_stats["prefix_pages_reused"] >= 1
        np.testing.assert_array_equal(got1[0], ref[0])
        np.testing.assert_array_equal(got2[0], ref[0])

    def test_cache_created_once_across_calls(self, setup, mesh22):
        """No per-call reallocation: the cache-creating first refill runs
        on the first call only; the second call reuses the live arrays
        (counter pinned, paged and unpaged)."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        eng = ContinuousEngine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4,
        )
        eng.serve(params, prompts[:2])
        assert eng.cache_creations == 1
        eng.serve(params, prompts[2:4])
        assert eng.cache_creations == 1
        paged = self._paged(cfg, mesh22)
        paged.serve(params, prompts[:2])
        paged.serve(params, prompts[2:4])
        assert paged.cache_creations == 1

    def test_streaming_matches_rectangular(self, setup, mesh22):
        """add_request/step/pop_finished — requests admitted OVER TIME
        (two up front, the rest injected while the engine is mid-flight)
        still produce bit-identical outputs per request."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        eng = ContinuousEngine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4,
        )
        rids = {}
        for p in prompts[:2]:
            rids[eng.add_request(p)] = p
        results = {}
        steps = 0
        late = list(prompts[2:5])
        while eng.has_work() or late:
            eng.step(params)
            results.update(eng.pop_finished())
            steps += 1
            if late and steps >= 2:      # arrivals while serving
                p = late.pop(0)
                rids[eng.add_request(p)] = p
        assert set(results) == set(rids)
        for rid, p in rids.items():
            ref = _rect_reference(cfg, mesh22, params, p)
            np.testing.assert_array_equal(
                results[rid], ref[: len(results[rid])]
            )
            assert len(results[rid]) == len(p) + NEW

    def test_latency_telemetry(self, setup, mesh22):
        """serve() reports per-request latency percentiles: TTFT, TPOT,
        ITL, queue wait — all positive and ordered sanely."""
        cfg, params, prompts = setup
        serve = make_continuous_engine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4,
        )
        serve(params, prompts)
        lat = serve.last_latency
        assert lat["requests"] == len(prompts)
        for k in ("ttft_p50", "ttft_p99", "tpot_p50", "queue_wait_p50",
                  "e2e_p50", "itl_p50"):
            assert k in lat, k
        assert 0 < lat["ttft_p50"] <= lat["ttft_p99"]
        assert lat["ttft_p50"] <= lat["e2e_p50"]
        assert lat["tpot_p50"] > 0

    def test_serve_requires_idle(self, setup, mesh22):
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        eng = ContinuousEngine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
        )
        eng.add_request(prompts[0])
        with pytest.raises(RuntimeError, match="idle"):
            eng.serve(params, prompts[:1])
        while eng.has_work():
            eng.step(params)
        eng.pop_finished()
        eng.serve(params, prompts[:1])   # idle again: fine

    def test_flush_prefix_cache(self, setup, mesh22):
        """flush_prefix_cache returns every retained page to the free
        pool (the params-swap hook); the next same-prompt call re-fills
        from scratch (no hit) but still matches."""
        cfg, params, _ = setup
        rng = np.random.default_rng(22)
        base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
        eng = self._paged(cfg, mesh22, prefix_cache=True)
        got1 = eng.serve(params, [base])
        assert eng.last_stats["prefix_pages_retained"] >= 1
        eng.flush_prefix_cache()
        assert len(eng._cached_lru) == 0
        assert len(eng._free_pages) == 8    # the whole pool is free again
        got2 = eng.serve(params, [base.copy()])
        assert eng.last_stats["prefix_hits"] == 0
        np.testing.assert_array_equal(got2[0], got1[0])

    def test_engine_reusable_after_exhaustion(self, setup, mesh22):
        """A pool-exhaustion raise must not wedge the persistent engine:
        reset() runs automatically and the next (feasible) call serves."""
        cfg, params, prompts = setup
        eng = self._paged(cfg, mesh22)
        eng2 = self._paged(cfg, mesh22)
        small = dataclasses.replace(cfg, decode_attention="blocked")
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        tight = ContinuousEngine(
            small, mesh22, RULES_TP_SERVING, batch_size=2,
            max_new_tokens=NEW, refill_chunk=4, paged_pages=2,
            page_size=self.PAGE,
        )
        with pytest.raises(RuntimeError, match="page pool exhausted"):
            tight.serve(params, [prompts[4], prompts[1]])
        got = tight.serve(params, [prompts[3]])    # 1-token prompt fits
        ref = eng.serve(params, [prompts[3]])
        np.testing.assert_array_equal(got[0], ref[0])
        del eng2

    def test_serve_preserves_streaming_results(self, setup, mesh22):
        """Un-popped streaming results survive an interleaved serve()
        call — serve's per-call rid namespace must not collide with
        them (review finding, round 5)."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        eng = ContinuousEngine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
        )
        rid = eng.add_request(prompts[0])     # rid 0 — collides with serve's
        while eng.has_work():
            eng.step(params)
        # NOT popped; serve() must stash it.
        out = eng.serve(params, [prompts[1]])
        ref0 = _rect_reference(cfg, mesh22, params, prompts[0])
        ref1 = _rect_reference(cfg, mesh22, params, prompts[1])
        np.testing.assert_array_equal(out[0], ref1[: len(out[0])])
        fin = eng.pop_finished()
        assert set(fin) == {rid}
        np.testing.assert_array_equal(fin[rid], ref0[: len(fin[rid])])

    def test_close_releases_and_recreates(self, setup, mesh22):
        """close() drops the device cache (HBM reclaim for multi-engine
        processes); the engine stays usable and re-creates on demand."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        eng = ContinuousEngine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
        )
        a = eng.serve(params, [prompts[0]])
        assert eng.cache_creations == 1
        eng.close()
        assert eng._cache is None
        b = eng.serve(params, [prompts[0]])
        assert eng.cache_creations == 2
        np.testing.assert_array_equal(a[0], b[0])

    def test_preemption_under_pressure_is_exact(self, setup, mesh22):
        """Pool pressure triggers RECOMPUTE preemption instead of a
        raise whenever another request holds reclaimable pages: two
        2-page requests through a 3-page pool must preempt (one row
        yields, requeues, restarts) and still emit bit-identical
        outputs — scheduling, including preemption, never changes
        results."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, _ = setup
        bcfg = dataclasses.replace(cfg, decode_attention="blocked")
        rng = np.random.default_rng(23)
        # 14-token prompts: 1 page to refill, a 2nd page mid-decode
        # (14 + 6 tokens > 16), so two concurrent rows want 4 of 3 pages.
        queue = [
            rng.integers(1, cfg.vocab_size, size=(14,)).astype(np.int32)
            for _ in range(2)
        ]
        plain = make_continuous_engine(
            bcfg, mesh22, RULES_TP_SERVING, batch_size=2,
            max_new_tokens=NEW, refill_chunk=4,
        )
        ref = plain(params, queue)
        tight = ContinuousEngine(
            bcfg, mesh22, RULES_TP_SERVING, batch_size=2,
            max_new_tokens=NEW, refill_chunk=4, paged_pages=4,
            page_size=self.PAGE,
        )
        got = tight.serve(params, queue)
        assert tight.last_stats["preemptions"] >= 1
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)

    def test_sampled_preemption_is_exact(self, setup, mesh22):
        """Same pressure at temperature > 0: the preempted request's
        re-derived draws are keyed by (request id, position), so even
        SAMPLED output is identical to the unpressured engine."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, _ = setup
        bcfg = dataclasses.replace(cfg, decode_attention="blocked")
        rng = np.random.default_rng(24)
        queue = [
            rng.integers(1, cfg.vocab_size, size=(14,)).astype(np.int32)
            for _ in range(2)
        ]
        kw = dict(
            batch_size=2, max_new_tokens=NEW, refill_chunk=4,
            temperature=1.0, top_k=16,
        )
        roomy = ContinuousEngine(
            bcfg, mesh22, RULES_TP_SERVING, paged_pages=9,
            page_size=self.PAGE, **kw,
        )
        tight = ContinuousEngine(
            bcfg, mesh22, RULES_TP_SERVING, paged_pages=4,
            page_size=self.PAGE, **kw,
        )
        key = jax.random.key(31)
        ref = roomy.serve(params, queue, rng=key)
        assert roomy.last_stats["preemptions"] == 0
        got = tight.serve(params, queue, rng=key)
        assert tight.last_stats["preemptions"] >= 1
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)

    def test_busy_guards_and_duplicate_rid(self, setup, mesh22):
        """flush_prefix_cache() refuses a busy engine (re-exposing
        old-params K/V); duplicate explicit rids are rejected instead of
        silently overwriting results. close() no longer refuses a busy
        engine — it DRAINS in-flight work to a terminal status (round
        10; pinned in tests/test_zero_downtime.py)."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        eng = self._paged(cfg, mesh22, prefix_cache=True)
        eng.add_request(prompts[0], rid=7)
        with pytest.raises(ValueError, match="already in use"):
            eng.add_request(prompts[1], rid=7)
        with pytest.raises(RuntimeError, match="idle"):
            eng.flush_prefix_cache()
        while eng.has_work():
            eng.step(params)
        with pytest.raises(ValueError, match="already in use"):
            eng.add_request(prompts[1], rid=7)   # finished, un-popped
        assert set(eng.pop_finished()) == {7}
        eng.close()                              # idle: fine

    def test_invalid_prompt_preserves_registry(self, setup, mesh22):
        """A validation error in serve() must raise BEFORE touching any
        state: the persistent prefix registry survives (review finding —
        the failure path resets the pool, so validation must be atomic)."""
        cfg, params, _ = setup
        rng = np.random.default_rng(25)
        base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
        eng = self._paged(cfg, mesh22, prefix_cache=True)
        eng.serve(params, [base])
        assert eng.last_stats["prefix_pages_retained"] >= 1
        too_long = rng.integers(
            1, cfg.vocab_size, size=(cfg.max_seq_len,)
        ).astype(np.int32)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.serve(params, [base.copy(), too_long])
        got = eng.serve(params, [base.copy()])
        assert eng.last_stats["prefix_hits"] == 1   # registry intact

    def test_long_prompt_chunked_paged_matches(self, setup, mesh22):
        """A longer prompt (112 tokens) streamed through 7 refill chunks
        over the paged pool — the composition the long-context serving
        measurement runs at depth — stays bit-identical to the plain
        engine."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, _ = setup
        bcfg = dataclasses.replace(cfg, decode_attention="blocked")
        rng = np.random.default_rng(26)
        # 44 tokens through 8-token chunks: 6 refill dispatches (last one
        # partial), 3 pages — long relative to every shape dimension.
        long_p = rng.integers(1, cfg.vocab_size, size=(44,)).astype(np.int32)
        plain = make_continuous_engine(
            bcfg, mesh22, RULES_TP_SERVING, batch_size=2,
            max_new_tokens=NEW, refill_chunk=8,
        )
        paged = ContinuousEngine(
            bcfg, mesh22, RULES_TP_SERVING, batch_size=2,
            max_new_tokens=NEW, refill_chunk=8, paged_pages=11,
            page_size=self.PAGE,
        )
        ref = plain(params, [long_p])
        got = paged.serve(params, [long_p.copy()])
        np.testing.assert_array_equal(got[0], ref[0])
        assert paged.last_stats["page_high_water"] >= 44 // self.PAGE

    @pytest.mark.parametrize("temp", [0.0, 1.0])
    def test_decode_chain_bit_identical(self, setup, mesh22, temp):
        """decode_chain > 1 (device-carried block chaining, one host
        sync per chain) cannot change results: greedy AND sampled, with
        EOS retirement mid-chain, vs the chain=1 engine."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        kw = dict(
            batch_size=2, max_new_tokens=NEW, refill_chunk=4,
            decode_block_steps=2, temperature=temp,
            top_k=16 if temp else None,
        )
        key_probe = jax.random.key(9)
        if temp == 0.0:
            plain_ref = _rect_reference(cfg, mesh22, params, prompts[0])
            eos = int(plain_ref[len(prompts[0]) + 1])
        else:
            # Derive an eos the SAMPLED streams actually emit, so EOS
            # retirement mid-chain is exercised at temperature > 0 too.
            probe = ContinuousEngine(cfg, mesh22, RULES_DP_TP, **kw)
            outs = probe.serve(params, prompts, rng=key_probe)
            gen = np.concatenate(
                [o[len(p):] for o, p in zip(outs, prompts)]
            )
            eos = int(np.bincount(gen).argmax())
        one = ContinuousEngine(cfg, mesh22, RULES_DP_TP, eos_id=eos, **kw)
        chained = ContinuousEngine(
            cfg, mesh22, RULES_DP_TP, eos_id=eos, decode_chain=3, **kw
        )
        a = one.serve(params, prompts, rng=key_probe)
        b = chained.serve(params, prompts, rng=key_probe)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(y, x)

    def test_decode_chain_speculative_paged(self, setup, mesh22):
        """Chained SPECULATIVE blocks over the paged pool — the whole
        carry set (tok/pos/active/remaining + both caches) rides the
        chain; outputs stay bit-identical to the unchained engine."""
        from learning_jax_sharding_tpu.models.serving import ContinuousEngine

        cfg, params, prompts = setup
        bcfg = dataclasses.replace(cfg, decode_attention="blocked")
        dcfg = dataclasses.replace(DRAFT_CFG, decode_attention="blocked")
        kw = dict(
            batch_size=2, max_new_tokens=NEW, refill_chunk=4,
            decode_block_steps=2, draft_config=dcfg, num_draft=2,
            paged_pages=9, page_size=self.PAGE,
        )
        dp = _draft_params()
        one = ContinuousEngine(bcfg, mesh22, RULES_TP_SERVING, **kw)
        chained = ContinuousEngine(
            bcfg, mesh22, RULES_TP_SERVING, decode_chain=4, **kw
        )
        a = one.serve(params, prompts[:4], draft_params=dp)
        b = chained.serve(params, prompts[:4], draft_params=dp)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(y, x)
