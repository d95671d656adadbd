"""Paged KV cache in the continuous engine (models/serving.py,
``paged_pages``): split from ``test_serving.py`` so that one xdist worker
does not carry all of the engine's tests. The oracle is test_serving's:
scheduling and paging never change results."""


import collections
import dataclasses

import jax
import jax.numpy as jnp
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
    setup,
)


class TestPagedKVCache:
    """Paged serving: per-layer page pools + host-owned block tables.
    Oracles: outputs bit-identical to the unpaged engine; measured page
    high-water scales with tokens in flight (NOT batch × max_seq_len);
    allocation/release conserve the pool across slot reuse; exhaustion
    raises instead of corrupting."""

    PAGE = 16

    def _engine(self, cfg, mesh22, **kw):
        # Paged pools are shared across rows, so the batch must stay
        # replicated: TP-only rules (the guard in make_decode_attn_fn
        # rejects batch-sharding rules — RULES_DP_TP here raises).
        return make_continuous_engine(
            cfg, mesh22, RULES_TP_SERVING, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, **kw,
        )

    def test_matches_unpaged_engine(self, setup, mesh22):
        cfg, params, prompts = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        plain = self._engine(cfg, mesh22)
        paged = self._engine(cfg, mesh22, paged_pages=9, page_size=self.PAGE)
        ref = plain(params, prompts)
        got = paged(params, prompts)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)
        # The footprint claim: the whole 7-request mixed-length workload
        # through 2 slots never needed the full slot-reservation
        # (2 slots × 4 blocks = 8 pages).
        stats = paged.last_stats
        assert stats["page_high_water"] < 2 * (cfg.max_seq_len // self.PAGE)
        assert stats["page_high_water"] >= 1

    def test_whole_tile_heads_take_the_loop_form(self, mesh22):
        """Heads of 64 make the cache's fused k | v rows whole 128-lane
        tiles, so every cached-attention call of the engine (refill chunks,
        folded decode writes, frozen rows on scratch page 0) takes the
        kernel's loop form: same tokens as the dense contiguous engine."""
        import flax.linen as nn

        from learning_jax_sharding_tpu.models.transformer import (
            CONFIG_TINY,
            Transformer,
        )

        cfg = dataclasses.replace(CONFIG_TINY, head_dim=64, dtype=jnp.float32)
        params = nn.meta.unbox(
            jax.jit(lambda r, t: Transformer(cfg).init({"params": r}, t))(
                jax.random.key(5), np.zeros((2, 8), np.int32)
            )["params"]
        )
        rng = np.random.default_rng(5)
        prompts = [
            rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in (3, 17, 5, 1, 30, 9)
        ]
        with jax.default_matmul_precision("float32"):
            dense = self._engine(
                dataclasses.replace(cfg, decode_attention="dense"), mesh22
            )
            ref = dense(params, prompts)
            paged = self._engine(
                dataclasses.replace(cfg, decode_attention="blocked"), mesh22,
                paged_pages=9, page_size=self.PAGE,
            )
            got = paged(params, prompts)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)
        gauge = "engine_decode_attn_pages_in_flight"
        assert paged.engine.registry.snapshot()[gauge] == 8
        assert dense.engine.registry.snapshot()[gauge] == 0  # no blocked cache

    def test_high_water_tracks_in_flight_tokens(self, setup, mesh22):
        """Short requests (1 page each) vs long requests (2+ pages each)
        must show different high-water marks — the footprint follows the
        tokens actually held, not the configured maximum."""
        cfg, params, _ = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        rng = np.random.default_rng(5)
        short = [
            rng.integers(1, cfg.vocab_size, size=(3,)).astype(np.int32)
            for _ in range(4)
        ]
        long = [
            rng.integers(1, cfg.vocab_size, size=(30,)).astype(np.int32)
            for _ in range(4)
        ]
        eng = self._engine(cfg, mesh22, paged_pages=9, page_size=self.PAGE)
        eng(params, short)
        hw_short = eng.last_stats["page_high_water"]
        eng(params, long)
        hw_long = eng.last_stats["page_high_water"]
        assert hw_short <= 2          # 2 slots × 1 page
        assert hw_long >= 2 * 2       # 2 slots × >=2 pages mid-flight
        assert hw_long > hw_short

    @staticmethod
    def _draft(max_seq_len):
        """The draft cut to ``max_seq_len`` positions: a shorter draft
        holds a narrower block table."""
        dcfg = dataclasses.replace(
            DRAFT_CFG, decode_attention="blocked", max_seq_len=max_seq_len
        )
        d_params = dict(_draft_params())
        d_params["pos_embed"] = d_params["pos_embed"][:max_seq_len]
        return dcfg, d_params

    @pytest.mark.parametrize("draft_len", [64, 32], ids=["draft_table_as_wide", "narrower_draft_table"])
    def test_paged_speculative_matches(self, setup, mesh22, draft_len):
        """The draft's block table holds the same prefix of the same page
        ids, narrower where its ``max_seq_len`` is shorter: a push sends
        one array a width, and the emitted tokens do not change."""
        cfg, params, prompts = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        dcfg, d_params = self._draft(draft_len)
        plain = self._engine(cfg, mesh22)
        paged_spec = self._engine(
            cfg, mesh22, paged_pages=9, page_size=self.PAGE,
            draft_config=dcfg, num_draft=2,
        )
        ref = plain(params, prompts)
        got = paged_spec(params, prompts, draft_params=d_params)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)
        snap = paged_spec.engine.registry.snapshot()
        pushes = snap["engine_table_push_arrays_total"] / (1 + (draft_len < 64))
        assert pushes >= 1 and snap["engine_table_push_leaves_total"] == (
            pushes * (cfg.num_layers + dcfg.num_layers)
        )

    #: engine kind -> (configuration changes, engine arguments, the
    #: draft's ``max_seq_len`` or None without one).
    PUSH_KINDS = {
        "split": ({}, {}, None),
        "mixed": ({}, dict(mixed=True), None),
        "int8_kv": (dict(kv_cache_dtype=jnp.int8), {}, None),
        "speculative": ({}, dict(num_draft=2), 64),
        "speculative_narrower_draft": ({}, dict(num_draft=2), 32),
    }

    @pytest.mark.parametrize("kind", PUSH_KINDS)
    def test_a_push_sends_one_array_a_width(self, setup, mesh22, kind):
        """The dirty host table reaches the device once per distinct leaf
        width, whatever the layer count; every ``block_table`` leaf of a
        width IS that one array; and it is a copy: the host table, which
        later allocations and releases mutate in place, cannot reach it."""
        cfg, params, _ = setup
        cfg_kw, kw, draft_len = self.PUSH_KINDS[kind]
        cfg = dataclasses.replace(cfg, decode_attention="blocked", **cfg_kw)
        d_params = None
        if draft_len:
            dcfg, d_params = self._draft(draft_len)
            kw = dict(kw, draft_config=dcfg)
        eng = self._engine(
            cfg, mesh22, paged_pages=9, page_size=self.PAGE, **kw
        ).engine
        eng.ensure_cache(params, d_params)          # the first push

        def tables():
            return [
                x for path, x in
                jax.tree_util.tree_flatten_with_path(eng._cache)[0]
                if getattr(path[-1], "key", None) == "block_table"
            ]

        widths = collections.Counter(
            [cfg.max_seq_len // self.PAGE] * cfg.num_layers
            + ([draft_len // self.PAGE] * DRAFT_CFG.num_layers if draft_len else [])
        )
        before = eng.registry.snapshot()
        eng._ensure(0, 2 * self.PAGE + 1)           # three pages: dirty
        eng._ensure(1, 1)
        assert eng._tables_dirty
        eng._cache = eng._set_tables(eng._cache, frame=False)
        after = eng.registry.snapshot()
        assert {
            k: after[f"engine_table_push_{k}_total"]
            - before[f"engine_table_push_{k}_total"]
            for k in ("arrays", "leaves")
        } == {"arrays": len(widths), "leaves": widths.total()}
        pushed = tables()
        assert collections.Counter(t.shape[1] for t in pushed) == widths
        assert len({id(t) for t in pushed}) == len(widths)
        held = eng._table_np.copy()
        assert np.count_nonzero(held) == 4          # page 0 is scratch
        for t in pushed:
            assert t.dtype == jnp.int32
            np.testing.assert_array_equal(t, held[:, : t.shape[1]])
        # The host table moves on; what was pushed stays what it was.
        eng._release(0)
        eng._ensure(1, 3 * self.PAGE)
        assert not np.array_equal(eng._table_np, held)
        for t in pushed:
            np.testing.assert_array_equal(t, held[:, : t.shape[1]])

    def test_paged_int8_kv_matches_unpaged(self, setup, mesh22):
        """Paged pools carry the int8 KV scales in page-shaped pools of
        their own — the quantized cache must page bit-identically to its
        unpaged (quantized) self."""
        cfg, params, prompts = setup
        cfg = dataclasses.replace(
            cfg, decode_attention="blocked", kv_cache_dtype=jnp.int8
        )
        plain = self._engine(cfg, mesh22)
        paged = self._engine(cfg, mesh22, paged_pages=9, page_size=self.PAGE)
        ref = plain(params, prompts)
        got = paged(params, prompts)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)

    def test_prefix_cache_matches_and_reuses(self, setup, mesh22):
        """Prefix caching: repeated prompts re-admit with retired
        requests' prompt pages already in their tables — outputs stay
        bit-identical to the unpaged engine, and the stats show real
        reuse (hits for both full repeats and shared-prefix variants)."""
        cfg, params, _ = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        rng = np.random.default_rng(9)
        base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
        variant = base.copy()
        variant[self.PAGE + 1] += 1     # same first page, different tail
        queue = [base, variant, base, base.copy(), variant.copy()]
        plain = self._engine(cfg, mesh22)
        ref = plain(params, queue)
        pfx = self._engine(
            cfg, mesh22, paged_pages=9, page_size=self.PAGE,
            prefix_cache=True,
        )
        got = pfx(params, queue)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)
        stats = pfx.last_stats
        # 2 slots serve 5 requests: at least the later base repeats and
        # the tail variant admit after a retirement registered page 0.
        assert stats["prefix_hits"] >= 2
        assert stats["prefix_pages_reused"] >= stats["prefix_hits"]

    def test_prefix_cache_eviction_under_pressure(self, setup, mesh22):
        """Retained pages must yield to live requests: distinct prompts
        through a pool sized with no slack for retention still serve
        (LRU eviction), bit-identical to the unpaged engine."""
        cfg, params, _ = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        rng = np.random.default_rng(10)
        queue = [
            rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
            for _ in range(6)
        ]
        plain = self._engine(cfg, mesh22)
        ref = plain(params, queue)
        # 2 slots × 20+NEW=26 tokens → 2 pages/slot live + scratch; 5
        # pages total leaves ZERO headroom for retention.
        pfx = self._engine(
            cfg, mesh22, paged_pages=5, page_size=self.PAGE,
            prefix_cache=True,
        )
        got = pfx(params, queue)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)

    def test_prefix_cache_speculative(self, setup, mesh22):
        """Prefix sharing + speculative decode blocks: the draft pool's
        pages share through the same tables, in lockstep."""
        cfg, params, _ = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        dcfg = dataclasses.replace(DRAFT_CFG, decode_attention="blocked")
        rng = np.random.default_rng(11)
        base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
        queue = [base, base.copy(), base.copy()]
        plain = self._engine(cfg, mesh22)
        ref = plain(params, queue)
        pfx = self._engine(
            cfg, mesh22, paged_pages=9, page_size=self.PAGE,
            prefix_cache=True, draft_config=dcfg, num_draft=2,
        )
        got = pfx(params, queue, draft_params=_draft_params())
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)
        assert pfx.last_stats["prefix_hits"] >= 1

    def test_everything_composes(self, setup, mesh22):
        """The whole round-4 serving stack AT ONCE — int4-fused weights +
        paged KV + prefix cache + speculative decode blocks — must still
        be bit-identical to the plain int4 engine. The features were each
        pinned alone; this is the composition oracle."""
        from learning_jax_sharding_tpu.models.quantize import quantize_tree

        cfg, params, _ = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        dcfg = dataclasses.replace(DRAFT_CFG, decode_attention="blocked")
        rng = np.random.default_rng(12)
        base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
        queue = [base, base.copy(), base.copy(), base.copy()]
        q4 = quantize_tree(params, bits=4)
        plain = self._engine(cfg, mesh22, dequantize="fused")
        ref = plain(q4, queue)
        allon = self._engine(
            cfg, mesh22, dequantize="fused", paged_pages=9,
            page_size=self.PAGE, prefix_cache=True, draft_config=dcfg,
            num_draft=2,
        )
        got = allon(q4, queue, draft_params=_draft_params())
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)
        stats = allon.last_stats
        assert stats["prefix_hits"] >= 1
        assert stats["spec_proposed"] > 0

    def test_everything_composes_quantized_draft(self, setup, mesh22):
        """The all-on stack with the DRAFT quantized too (int4-fused
        target + int8 in-jit-dequant draft + paged + prefix + spec):
        still bit-identical to the plain int4 engine — a quantized draft
        changes only what gets proposed, never what gets emitted."""
        from learning_jax_sharding_tpu.models.quantize import quantize_tree

        cfg, params, _ = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        dcfg = dataclasses.replace(DRAFT_CFG, decode_attention="blocked")
        rng = np.random.default_rng(13)
        base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
        queue = [base, base.copy(), base.copy(), base.copy()]
        q4 = quantize_tree(params, bits=4)
        d8 = quantize_tree(_draft_params(), bits=8)
        plain = self._engine(cfg, mesh22, dequantize="fused")
        ref = plain(q4, queue)
        allon = self._engine(
            cfg, mesh22, dequantize="fused", paged_pages=9,
            page_size=self.PAGE, prefix_cache=True, draft_config=dcfg,
            draft_dequantize=True, num_draft=2,
        )
        got = allon(q4, queue, draft_params=d8)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)
        assert allon.last_stats["prefix_hits"] >= 1
        assert allon.last_stats["spec_proposed"] > 0

    def test_prefix_cache_requires_paged(self, setup, mesh22):
        cfg, _, _ = setup
        with pytest.raises(ValueError, match="prefix_cache"):
            make_continuous_engine(
                dataclasses.replace(cfg, decode_attention="blocked"),
                mesh22, RULES_TP_SERVING, batch_size=2, max_new_tokens=NEW,
                prefix_cache=True,
            )

    def test_pool_exhaustion_raises(self, setup, mesh22):
        cfg, params, prompts = setup
        cfg = dataclasses.replace(cfg, decode_attention="blocked")
        eng = self._engine(cfg, mesh22, paged_pages=2, page_size=self.PAGE)
        with pytest.raises(RuntimeError, match="page pool exhausted"):
            eng(params, [prompts[4], prompts[1]])  # 12- and 9-token prompts

    def test_validation(self, setup, mesh22):
        cfg, params, prompts = setup
        with pytest.raises(ValueError, match="blocked"):
            self._engine(
                dataclasses.replace(cfg, decode_attention="dense"),
                mesh22, paged_pages=8, page_size=self.PAGE,
            )
        blocked = dataclasses.replace(cfg, decode_attention="blocked")
        with pytest.raises(ValueError, match="paged_pages"):
            self._engine(blocked, mesh22, paged_pages=1, page_size=self.PAGE)
        with pytest.raises(ValueError, match="multiple"):
            self._engine(blocked, mesh22, paged_pages=8, page_size=48)
        # Batch-sharding rules must be rejected: any row can read any
        # page, so a batch shard would need its own pool.
        eng_dp = make_continuous_engine(
            blocked, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=NEW,
            refill_chunk=4, paged_pages=9, page_size=self.PAGE,
        )
        with pytest.raises(ValueError, match="cannot shard the batch"):
            eng_dp(params, prompts[:1])


# --- how a refill chunk goes into the pools --------------------------------


def _pool_and_chunk(case, leaf):
    """A pool, a chunk and where it goes, as a refill dispatch gives them:
    every row's chunk starts on a page. ``(pool, chunk, table, idx,
    lengths, ring)`` with pages of 4 and chunks of two pages."""
    from learning_jax_sharding_tpu.ops.decode_attention import ring_pages

    rng = np.random.default_rng(["aligned", "mixed_lengths", "ring"].index(case))
    page, s, n_kv, rows = 4, 8, 2, 5
    if case == "ring":
        r = ring_pages(8, page)          # a window of 8: 4 pages a slot
        table = 1 + np.arange(rows * r, dtype=np.int32).reshape(rows, r)
        pool_pages = rows * r + 1
        idx = page * rng.integers(0, 30, size=rows)   # turns of the ring
    else:
        cols, pool_pages = 8, 48          # 32 positions a row
        # Rows own distinct pages; some pages belong to no row.
        owned = rng.permutation(np.arange(1, pool_pages))[: rows * cols]
        table = owned.reshape(rows, cols).astype(np.int32)
        idx = page * rng.integers(0, cols - s // page + 1, size=rows)
    if case == "aligned":
        lengths = np.full(rows, s)
    else:
        lengths = np.array([0, s, 3, 5, 1])   # empty, whole, partial pages
    rest = (6,) if leaf == "kv" else ()       # k | v lanes, or int8 scales
    pool = rng.standard_normal((pool_pages, n_kv, page, *rest)).astype(np.float32)
    chunk = rng.standard_normal((rows, n_kv, s, *rest)).astype(np.float32)
    return (
        pool, chunk, jnp.asarray(table), jnp.asarray(idx, jnp.int32),
        jnp.asarray(lengths, jnp.int32), case == "ring",
    )


@pytest.mark.parametrize("leaf", ["kv", "int8_scales"])
@pytest.mark.parametrize("case", ["aligned", "mixed_lengths", "ring"])
def test_whole_page_write_matches_the_scatter(case, leaf):
    """A refill chunk written as whole pages on the pool's leading axis
    leaves the pool as the position-by-position scatter does wherever
    anything reads: every position below a row's new length, every page no
    row owns. What differs is the padding past a row's length inside the
    row's own last page, and scratch page 0; a row of length 0 touches
    nothing but page 0."""
    from learning_jax_sharding_tpu.models.attention import (
        paged_page_write,
        paged_scatter,
    )

    pool, chunk, table, idx, lengths, ring = _pool_and_chunk(case, leaf)
    page, s = pool.shape[2], chunk.shape[2]
    want = np.asarray(jax.jit(
        paged_scatter, static_argnames=("page", "length", "ring")
    )(pool, chunk, table, idx, page=page, chunk_lengths=lengths,
      length=table.shape[1] * page, ring=ring))
    got = np.asarray(jax.jit(
        paged_page_write, static_argnames=("page", "ring")
    )(pool, chunk, table, idx, page=page, chunk_lengths=lengths, ring=ring))

    table, idx, lengths = map(np.asarray, (table, idx, lengths))
    may_differ = np.zeros(pool.shape[:1] + pool.shape[2:3], bool)  # (P, page)
    may_differ[0] = True
    may_touch = {0}
    for b in range(len(idx)):
        n = int(lengths[b])
        for t in range(n):           # what the row writes: the chunk itself
            col = (idx[b] + t) // page
            pid = table[b, col % table.shape[1] if ring else col]
            np.testing.assert_array_equal(
                got[pid, :, (idx[b] + t) % page], chunk[b, :, t]
            )
        for t in range(n, -(-n // page) * page):   # its last page's padding
            col = (idx[b] + t) // page
            pid = table[b, col % table.shape[1] if ring else col]
            may_differ[pid, (idx[b] + t) % page] = True
        may_touch |= {
            int(table[b, (idx[b] // page + j) % table.shape[1]])
            for j in range(-(-n // page))
        }
    keep = ~may_differ            # by (page, slot): heads moved behind
    np.testing.assert_array_equal(
        np.moveaxis(got, 2, 1)[keep], np.moveaxis(want, 2, 1)[keep]
    )
    touched = {
        int(p) for p in np.flatnonzero(
            (got != pool).reshape(len(pool), -1).any(axis=1)
        )
    }
    assert touched <= may_touch, (touched, may_touch)
    assert s % page == 0 and (idx % page == 0).all()


def _page_writes(eng):
    snap = eng.engine.registry.snapshot()
    return {
        form: snap[f'engine_refill_page_writes_total{{form="{form}"}}']
        for form in ("whole_page", "scatter")
    }


@pytest.mark.parametrize("kind", ["plain", "prefix_cache", "speculative", "int8_kv"])
def test_whole_page_refill_matches_the_unpaged_engine(setup, mesh22, kind):
    """A split paged engine whose refill chunk is whole pages (16 tokens,
    pages of 8, as xl's 128 over 64) writes its refill chunks as whole
    pages: the tokens are the contiguous engine's, admissions at a shared
    prefix's page included, and every row starts on a page (the engine
    checks each row's start as it packs the dispatch)."""
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, decode_attention="blocked")
    if kind == "int8_kv":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=jnp.int8)
    rng = np.random.default_rng(21)
    base = rng.integers(1, cfg.vocab_size, size=(20,)).astype(np.int32)
    queue = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in (16, 37, 5, 48, 21)
    ] + [base, base.copy(), np.concatenate([base, base[:7]])]
    kw, call = {}, {}
    if kind == "prefix_cache":
        kw = dict(prefix_cache=True)
    if kind == "speculative":
        kw = dict(
            draft_config=dataclasses.replace(DRAFT_CFG, decode_attention="blocked"),
            num_draft=2,
        )
        call = dict(draft_params=_draft_params())
    plain = make_continuous_engine(
        cfg, mesh22, RULES_TP_SERVING, batch_size=2, max_new_tokens=NEW,
        refill_chunk=4,
    )
    paged = make_continuous_engine(
        cfg, mesh22, RULES_TP_SERVING, batch_size=2, max_new_tokens=NEW,
        refill_chunk=16, paged_pages=17, page_size=8, **kw,
    )
    ref = plain(params, queue)
    got = paged(params, queue, **call)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(g, r)
    writes = _page_writes(paged)
    assert writes["whole_page"] > 0 and writes["scatter"] == 0
    if kind == "prefix_cache":
        assert paged.last_stats["prefix_hits"] >= 1


def test_page_write_counter_by_form(setup, mesh22):
    """``engine_refill_page_writes_total{form=}``: a split engine's aligned
    refill dispatch counts ``rows x S / page`` whole pages (prompts of
    whole chunks fill every row); a mixed engine, whose token budget cuts
    chunks anywhere, scatters every prompt position it refills."""
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, decode_attention="blocked")
    rng = np.random.default_rng(22)
    queue = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in (16, 48, 32)
    ]
    args = dict(batch_size=2, max_new_tokens=NEW, paged_pages=17, page_size=8)
    split = make_continuous_engine(
        cfg, mesh22, RULES_TP_SERVING, refill_chunk=16, **args
    )
    split(params, queue)
    snap = split.engine.registry.snapshot()
    rows = snap["engine_refill_chunk_rows_total"]
    assert rows == sum(len(p) for p in queue) // 16
    assert _page_writes(split) == {"whole_page": rows * 16 // 8, "scatter": 0}

    mixed = make_continuous_engine(
        cfg, mesh22, RULES_TP_SERVING, refill_chunk=16, mixed=True, **args
    )
    mixed(params, queue)
    snap = mixed.engine.registry.snapshot()
    assert snap["engine_mixed_dispatches_total"] > 0
    assert _page_writes(mixed) == {
        "whole_page": 0, "scatter": snap["engine_prefill_tokens_total"],
    }
    # A chunk that is not whole pages keeps the scatter on a split engine.
    narrow = make_continuous_engine(
        cfg, mesh22, RULES_TP_SERVING, refill_chunk=12, **args
    )
    narrow(params, queue)
    assert _page_writes(narrow) == {
        "whole_page": 0, "scatter": sum(len(p) for p in queue),
    }
