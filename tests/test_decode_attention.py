"""Length-aware blocked decode attention (ops/decode_attention.py).

The kernel's claims, pinned:

* parity with the dense cached path (the masked ``dot_product_attention``
  oracle) across GQA grouping, chunked prefill, sliding windows, and int8
  caches — every configuration the serving stack composes;
* the model-level blocked backend (``decode_attention="blocked"``) generates
  the SAME tokens as the dense backend, end to end through
  ``make_generate_fn`` — including through the shard_map wrapper on the
  emulated multi-device mesh (``make_decode_attn_fn``), which multi-chip
  serving uses because GSPMD cannot partition a Pallas custom call.

The bandwidth claim (per-token HBM traffic scales with valid cache length,
not buffer length) is a real-TPU measurement, recorded in PERF.md — the
interpreter cannot observe DMA elision.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.models.transformer import CONFIG_TINY, TransformerConfig
from learning_jax_sharding_tpu.ops.attention import dot_product_attention
from learning_jax_sharding_tpu.ops.decode_attention import (
    auto_block_k,
    decode_attention,
    fuse_kv,
    make_decode_attn_fn,
)


def _dense_oracle(q, kc, vc, idx, window=None):
    """Masked dense attention over the (B, N_kv, L, H) cache layout."""
    b, s, n, h = q.shape
    n_kv, length = kc.shape[1], kc.shape[2]
    group = n // n_kv
    k = jnp.repeat(kc.transpose(0, 2, 1, 3), group, axis=2)
    v = jnp.repeat(vc.transpose(0, 2, 1, 3), group, axis=2)
    q_pos = idx + jnp.arange(s)[:, None]
    k_pos = jnp.arange(length)[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    return dot_product_attention(q, k, v, mask=mask[None, None])


class TestKernelParity:
    B, L, NKV, H = 2, 64, 2, 16

    def _rand(self, rng, *shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    @pytest.mark.parametrize(
        "s,idx,group,window,block_k",
        [
            (1, 17, 1, None, None),     # single-token MHA decode
            (1, 0, 1, None, None),      # first token
            (1, 33, 3, None, 16),       # GQA decode, multi-block
            (5, 20, 1, None, 16),       # chunked prefill
            (7, 30, 2, 16, 16),         # GQA chunk + sliding window
            (1, 40, 1, 8, 8),           # SWA decode: band start skips blocks
            (4, 60, 2, None, None),     # chunk ending at the buffer edge
        ],
    )
    def test_matches_dense(self, rng, s, idx, group, window, block_k):
        n = self.NKV * group
        q = self._rand(rng, self.B, s, n, self.H)
        kc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        vc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        with jax.default_matmul_precision("float32"):
            out = decode_attention(
                q, fuse_kv(kc, vc), idx, window=window, block_k=block_k,
                interpret=True,
            )
            ref = _dense_oracle(q, kc, vc, idx, window=window)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    @pytest.mark.parametrize("s,block_q,group", [(7, 4, 1), (9, 2, 2), (16, 8, 1)])
    def test_q_tiling(self, rng, s, block_q, group):
        """Chunks tile over block_q-row grid steps (incl. a non-dividing
        last tile) — what bounds prefill VMEM for long prompts."""
        n = self.NKV * group
        q = self._rand(rng, self.B, s, n, self.H)
        kc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        vc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        with jax.default_matmul_precision("float32"):
            out = decode_attention(
                q, fuse_kv(kc, vc), 20, block_k=16, block_q=block_q,
                interpret=True,
            )
            ref = _dense_oracle(q, kc, vc, 20)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_int8_cache(self, rng):
        group, s, idx = 3, 1, 21
        n = self.NKV * group
        q = self._rand(rng, self.B, s, n, self.H)
        kf = rng.normal(size=(self.B, self.NKV, self.L, self.H))
        vf = rng.normal(size=(self.B, self.NKV, self.L, self.H))
        ks = np.abs(kf).max(-1) / 127.0
        vs = np.abs(vf).max(-1) / 127.0
        ki = np.round(kf / ks[..., None]).astype(np.int8)
        vi = np.round(vf / vs[..., None]).astype(np.int8)
        with jax.default_matmul_precision("float32"):
            out = decode_attention(
                q, fuse_kv(jnp.asarray(ki), jnp.asarray(vi)), idx,
                k_scale=jnp.asarray(ks, jnp.float32),
                v_scale=jnp.asarray(vs, jnp.float32),
                block_k=16, interpret=True,
            )
            ref = _dense_oracle(
                q,
                jnp.asarray(ki * ks[..., None], jnp.float32),
                jnp.asarray(vi * vs[..., None], jnp.float32),
                idx,
            )
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_only_valid_slots_read(self, rng):
        """Slots past index+S can hold ANY garbage without changing the
        output — the behavioral face of 'the tail is never fetched'."""
        q = self._rand(rng, self.B, 1, self.NKV, self.H)
        kc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        vc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        idx = 9
        poison = jnp.full_like(kc, 1e9).at[:, :, : idx + 1].set(kc[:, :, : idx + 1])
        poison_v = jnp.full_like(vc, 1e9).at[:, :, : idx + 1].set(vc[:, :, : idx + 1])
        with jax.default_matmul_precision("float32"):
            clean = decode_attention(
                q, fuse_kv(kc, vc), idx, block_k=8, interpret=True
            )
            dirty = decode_attention(
                q, fuse_kv(poison, poison_v), idx, block_k=8, interpret=True
            )
        np.testing.assert_allclose(clean, dirty, atol=1e-6)

    def test_shard_map_wrapper(self, rng, mesh22):
        from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP

        group = 2
        n = self.NKV * group
        q = self._rand(rng, self.B, 1, n, self.H)
        kc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        vc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        fn = make_decode_attn_fn(mesh22, RULES_DP_TP, block_k=16, interpret=True)
        with jax.default_matmul_precision("float32"):
            out = jax.jit(fn)(q, fuse_kv(kc, vc), jnp.asarray(25, jnp.int32))
            ref = _dense_oracle(q, kc, vc, 25)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_validation(self, rng):
        q = self._rand(rng, self.B, 1, self.NKV, self.H)
        kc = self._rand(rng, self.B, self.NKV, self.L, self.H)
        kv = fuse_kv(kc, kc)
        with pytest.raises(ValueError, match="k_scale and v_scale"):
            decode_attention(q, kv, 0, k_scale=jnp.ones((self.B, self.NKV, self.L)))
        with pytest.raises(ValueError, match="not divisible"):
            decode_attention(q, kv, 0, block_k=48, interpret=True)
        with pytest.raises(ValueError, match="does not match queries"):
            decode_attention(q, kc, 0, interpret=True)  # k alone, not k|v

    def test_auto_block_k(self):
        assert auto_block_k(1024) == 256
        assert auto_block_k(64) == 64
        assert auto_block_k(96) == 32
        assert auto_block_k(100) == 100  # no p2 factor ≥ 8 → single block


class TestModelParity:
    """make_generate_fn with decode_attention='blocked' vs 'dense': same
    greedy tokens through prefill + the whole decode loop."""

    def _generate(self, cfg, mesh, prompt, **kw):
        import dataclasses

        import optax

        from learning_jax_sharding_tpu.models.generate import make_generate_fn
        from learning_jax_sharding_tpu.models.transformer import Transformer
        from learning_jax_sharding_tpu.parallel import mesh_sharding, put
        from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
        from learning_jax_sharding_tpu.training.pipeline import sharded_train_state

        train_cfg = dataclasses.replace(cfg, decode=False)
        x = put(np.asarray(prompt), mesh_sharding(mesh, "data", None))
        state, _ = sharded_train_state(
            Transformer(train_cfg), optax.adamw(3e-4), x,
            {"params": jax.random.key(0)}, mesh, RULES_DP_TP,
        )
        gen = make_generate_fn(cfg, mesh, RULES_DP_TP, max_new_tokens=8, **kw)
        return np.asarray(gen(state.params, prompt))

    @pytest.mark.parametrize(
        "variant",
        ["mha", "gqa_rope", "int8_cache", "window"],
    )
    def test_blocked_matches_dense(self, mesh22, variant):
        import dataclasses

        mods = {
            "mha": {},
            "gqa_rope": dict(num_kv_heads=2, rope=True),
            "int8_cache": dict(kv_cache_dtype=jnp.int8),
            "window": dict(window=16),
        }[variant]
        base = dataclasses.replace(CONFIG_TINY, **mods)
        prompt = jnp.asarray(
            np.random.default_rng(3).integers(0, base.vocab_size, (4, 12)),
            jnp.int32,
        )
        with jax.default_matmul_precision("float32"):
            dense = self._generate(
                dataclasses.replace(base, decode_attention="dense"),
                mesh22, prompt,
            )
            blocked = self._generate(
                dataclasses.replace(
                    base, decode_attention="blocked", decode_block_k=16
                ),
                mesh22, prompt,
            )
        np.testing.assert_array_equal(dense, blocked)


class TestFoldedWriteEnable:
    """``write_enable``: a frozen row (zero chunk length in a mixed ragged
    batch) must leave its cache buffers BIT-IDENTICAL through a folded
    write — no garbage token at its un-advanced slot, not even
    transiently (the round-3 advisor finding)."""

    def test_disabled_row_cache_untouched(self):
        rng = np.random.default_rng(3)
        b, n_kv, length, h = 2, 2, 64, 16
        kc = jnp.asarray(rng.normal(size=(b, n_kv, length, h)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, n_kv, length, h)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, 1, n_kv, h)), jnp.float32)
        k_new = jnp.asarray(rng.normal(size=(b, n_kv, 1, h)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(b, n_kv, 1, h)), jnp.float32)
        idx = jnp.asarray([17, 9], jnp.int32)
        enable = jnp.asarray([1, 0], jnp.int32)

        out, kv_out = decode_attention(
            q, fuse_kv(kc, vc), idx, kv_new=fuse_kv(k_new, v_new),
            write_enable=enable, block_k=16, interpret=True,
        )
        k_out, v_out = np.asarray(kv_out[..., :h]), np.asarray(kv_out[..., h:])
        # Row 0 (enabled): new token lands at its slot, rest unchanged.
        np.testing.assert_array_equal(k_out[0, :, 17], np.asarray(k_new)[0, :, 0])
        np.testing.assert_array_equal(v_out[0, :, 17], np.asarray(v_new)[0, :, 0])
        np.testing.assert_array_equal(k_out[0, :, :17], np.asarray(kc)[0, :, :17])
        # Row 1 (disabled): every buffer bit-identical.
        np.testing.assert_array_equal(k_out[1], np.asarray(kc)[1])
        np.testing.assert_array_equal(v_out[1], np.asarray(vc)[1])
        # Row 0's output equals the dense oracle over the merged cache.
        merged_k = kc.at[0, :, 17].set(k_new[0, :, 0])
        merged_v = vc.at[0, :, 17].set(v_new[0, :, 0])
        ref = _dense_oracle(q[:1], merged_k[:1], merged_v[:1], 17)
        np.testing.assert_allclose(
            np.asarray(out)[0], np.asarray(ref)[0], rtol=1e-5, atol=1e-5
        )

    def test_write_enable_requires_fold(self):
        rng = np.random.default_rng(0)
        kc = jnp.asarray(rng.normal(size=(1, 1, 16, 8)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(1, 1, 1, 8)), jnp.float32)
        with pytest.raises(ValueError, match="write_enable"):
            decode_attention(
                q, fuse_kv(kc, kc), 3, write_enable=jnp.ones((1,), jnp.int32),
                interpret=True,
            )


def _quantize(x):
    """Per-(token, head) symmetric int8: values and fp32 scales."""
    scale = np.maximum(np.abs(x).max(-1), 1e-6) / 127.0
    return np.round(x / scale[..., None]).astype(np.int8), scale.astype(np.float32)


# Paged serving cases, each against the dense fp32 reference over the row's
# own logical cache. ``idx``: tokens each row holds before the call; page 8,
# table 4 wide (capacity 32). ``enable`` 0 freezes a row (folded write only).
_PAGED_CASES = {
    # S = 1, folded write: the decode step
    "fold-frozen-empty-row": dict(idx=[0, 11], enable=[0, 1]),
    "fold-one-token": dict(idx=[1, 1]),
    "fold-exactly-a-page": dict(idx=[8, 16]),
    "fold-a-page-plus-one": dict(idx=[9, 17]),
    "fold-full-capacity": dict(idx=[31, 24]),
    "fold-shared-prefix-pages": dict(idx=[19, 21], share=2),
    "fold-tail-at-scratch-page": dict(idx=[3, 12], scratch_tail=True),
    "fold-window": dict(idx=[29, 6], window=10),
    "fold-int8": dict(idx=[13, 30], int8=True),
    "fold-gqa": dict(idx=[15, 2, 27], group=3),
    "fold-frozen-full-row": dict(idx=[31, 5], enable=[0, 1]),
    # S > 1: a chunk already written to the cache (refill, verification)
    "chunk": dict(idx=[0, 10], s=5),
    "chunk-to-capacity": dict(idx=[24, 3], s=8),
    "chunk-gqa-tiled-window": dict(idx=[9, 20], s=6, group=2, window=12, block_q=4),
    "chunk-int8-scratch-tail": dict(idx=[4, 17], s=3, int8=True, scratch_tail=True),
}


class TestPagedCache:
    """Paged layout: (P, N_kv, page, 2H) pools indirected through per-row
    block tables. Oracles: the dense fp32 reference over each row's logical
    cache, and bit-identical attention (and folded writes) to the
    contiguous layout holding the same logical contents, for ANY page
    permutation — the table is pure indirection."""

    def _paged_from_contiguous(self, kc, vc, page, rng):
        b, n_kv, L, h = kc.shape
        T = L // page
        P = b * T + 1
        table = rng.permutation(np.arange(1, P)).reshape(b, T)
        pool_k = np.zeros((P, n_kv, page, h), np.float32)
        pool_v = np.zeros((P, n_kv, page, h), np.float32)
        for bi in range(b):
            for t in range(T):
                pool_k[table[bi, t]] = np.asarray(kc)[bi, :, t*page:(t+1)*page]
                pool_v[table[bi, t]] = np.asarray(vc)[bi, :, t*page:(t+1)*page]
        return (
            fuse_kv(jnp.asarray(pool_k), jnp.asarray(pool_v)),
            jnp.asarray(table, jnp.int32),
        )

    @pytest.mark.parametrize("case", _PAGED_CASES)
    def test_contexts_match_dense(self, case):
        """The grid walks the pages each row HOLDS: rows of every length,
        shared and scratch-page table entries, frozen rows."""
        c = dict(
            s=1, group=1, window=None, int8=False, enable=None, share=0,
            scratch_tail=False, block_q=128,
        )
        c.update(_PAGED_CASES[case])
        rng = np.random.default_rng(7)
        idx, s, page, T, n_kv, h = np.asarray(c["idx"]), c["s"], 8, 4, 2, 16
        b, L, fold = len(idx), T * page, c["s"] == 1
        P = b * T + 1
        kf = rng.normal(size=(b, n_kv, L, h)).astype(np.float32)
        vf = rng.normal(size=(b, n_kv, L, h)).astype(np.float32)
        table = rng.permutation(np.arange(1, P)).reshape(b, T)
        for t in range(c["share"]):          # rows share their prefix pages
            table[1:, t] = table[0, t]
            kf[1:, :, t*page:(t+1)*page] = kf[0, :, t*page:(t+1)*page]
            vf[1:, :, t*page:(t+1)*page] = vf[0, :, t*page:(t+1)*page]
        if c["scratch_tail"]:                # unallocated entries: page 0
            for bi in range(b):
                table[bi, -(-(idx[bi] + s) // page):] = 0
        if c["int8"]:
            (ki, ks), (vi, vs) = _quantize(kf), _quantize(vf)
            kf, vf = ki * ks[..., None], vi * vs[..., None]
        # Page 0 (scratch) and every slot no row maps hold poison.
        pool = np.full((P, n_kv, page, 2 * h), 100 if c["int8"] else 1e9)
        pool = pool.astype(np.int8 if c["int8"] else np.float32)
        pool_ks = np.full((P, n_kv, page), 1e9, np.float32)
        pool_vs = np.full((P, n_kv, page), 1e9, np.float32)
        for bi in range(b):
            for t in range(T):
                at = np.s_[bi, :, t*page:(t+1)*page]
                if table[bi, t] == 0:
                    continue
                pool[table[bi, t]] = np.concatenate(
                    [ki[at], vi[at]] if c["int8"] else [kf[at], vf[at]], -1
                )
                if c["int8"]:
                    pool_ks[table[bi, t]], pool_vs[table[bi, t]] = ks[at], vs[at]
        n = n_kv * c["group"]
        q = jnp.asarray(rng.normal(size=(b, s, n, h)), jnp.float32)
        kw = dict(
            block_table=jnp.asarray(table, jnp.int32), window=c["window"],
            block_q=c["block_q"], interpret=True,
        )
        if c["int8"]:
            kw.update(k_scale=jnp.asarray(pool_ks), v_scale=jnp.asarray(pool_vs))
        enable = np.ones(b, np.int32) if c["enable"] is None else np.asarray(c["enable"])
        if fold:
            k_new = rng.normal(size=(b, n_kv, 1, h)).astype(np.float32)
            v_new = rng.normal(size=(b, n_kv, 1, h)).astype(np.float32)
            if c["int8"]:
                (kn_i, kn_s), (vn_i, vn_s) = _quantize(k_new), _quantize(v_new)
                kw.update(ks_new=jnp.asarray(kn_s), vs_new=jnp.asarray(vn_s))
                kv_new = np.concatenate([kn_i, vn_i], -1)
                k_new, v_new = kn_i * kn_s[..., None], vn_i * vn_s[..., None]
            else:
                kv_new = np.concatenate([k_new, v_new], -1)
            kw.update(kv_new=jnp.asarray(kv_new))
            if c["enable"] is not None:
                kw.update(write_enable=jnp.asarray(enable))
        with jax.default_matmul_precision("float32"):
            result = decode_attention(
                q, jnp.asarray(pool), jnp.asarray(idx, jnp.int32), **kw
            )
        out = np.asarray(result[0] if fold else result)
        for bi in range(b):
            if not enable[bi]:
                continue                     # a frozen row's output is unused
            kr, vr = kf[bi:bi+1].copy(), vf[bi:bi+1].copy()
            if fold:
                kr[0, :, idx[bi]], vr[0, :, idx[bi]] = k_new[bi, :, 0], v_new[bi, :, 0]
            with jax.default_matmul_precision("float32"):
                ref = _dense_oracle(
                    q[bi:bi+1], jnp.asarray(kr), jnp.asarray(vr), int(idx[bi]),
                    window=c["window"],
                )
            np.testing.assert_allclose(
                out[bi], np.asarray(ref)[0], atol=1e-4 if c["int8"] else 1e-5
            )
        if fold:
            # The pool afterwards: each writing row's slot holds its token;
            # EVERYTHING else — frozen rows' pages, shared pages, the
            # scratch page — is bit-identical.
            want, want_ks, want_vs = pool.copy(), pool_ks.copy(), pool_vs.copy()
            for bi in np.flatnonzero(enable):
                at = (table[bi, idx[bi] // page], slice(None), idx[bi] % page)
                want[at] = kv_new[bi, :, 0]
                if c["int8"]:
                    want_ks[at], want_vs[at] = kn_s[bi, :, 0], vn_s[bi, :, 0]
            np.testing.assert_array_equal(np.asarray(result[1]), want)
            if c["int8"]:
                np.testing.assert_array_equal(np.asarray(result[2]), want_ks)
                np.testing.assert_array_equal(np.asarray(result[3]), want_vs)

    @pytest.mark.parametrize("s,group", [(1, 1), (1, 2), (5, 1)])
    def test_read_parity(self, s, group):
        rng = np.random.default_rng(0)
        b, n_kv, page, h, T = 3, 2, 16, 8, 4
        L = T * page
        kc = jnp.asarray(rng.normal(size=(b, n_kv, L, h)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, n_kv, L, h)), jnp.float32)
        idx = jnp.asarray([17, 33, 5], jnp.int32)
        q = jnp.asarray(
            rng.normal(size=(b, s, n_kv * group, h)), jnp.float32
        )
        ref = decode_attention(
            q, fuse_kv(kc, vc), idx, block_k=page, interpret=True
        )
        pkv, table = self._paged_from_contiguous(kc, vc, page, rng)
        out = decode_attention(
            q, pkv, idx, block_table=table, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6
        )

    def test_folded_write_parity(self):
        rng = np.random.default_rng(1)
        b, n_kv, page, h, T = 2, 2, 16, 8, 4
        L = T * page
        kc = jnp.asarray(rng.normal(size=(b, n_kv, L, h)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, n_kv, L, h)), jnp.float32)
        idx = jnp.asarray([17, 9], jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, 1, n_kv, h)), jnp.float32)
        kv_new = jnp.asarray(rng.normal(size=(b, n_kv, 1, 2 * h)), jnp.float32)
        ref, rkv = decode_attention(
            q, fuse_kv(kc, vc), idx, kv_new=kv_new, block_k=page,
            interpret=True,
        )
        pkv, table = self._paged_from_contiguous(kc, vc, page, rng)
        out, okv = decode_attention(
            q, pkv, idx, kv_new=kv_new, block_table=table, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6
        )
        okv, tbl = np.asarray(okv), np.asarray(table)
        for bi in range(b):
            i = int(idx[bi])
            t, o = i // page, i % page
            np.testing.assert_array_equal(
                okv[tbl[bi, t], :, o], np.asarray(rkv)[bi, :, i]
            )

    def test_block_k_mismatch_rejected(self):
        rng = np.random.default_rng(0)
        pkv = jnp.zeros((5, 1, 16, 16), jnp.float32)
        q = jnp.asarray(rng.normal(size=(1, 1, 1, 8)), jnp.float32)
        table = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(ValueError, match="page"):
            decode_attention(
                q, pkv, 3, block_table=table, block_k=8, interpret=True
            )


# --- the loop form: rows are the grid, each program fetches its own blocks ---

# Whole-tile shapes, where `pages_in_flight` gives the loop form a ring: fused
# rows of 128 lanes (head 64, float32: sublane tile 8) and of 256 (head 128,
# 32 query heads on 2 KV heads, a bfloat16 cache: tile 16).
_LOOP_SHAPES = {
    "h64p64": dict(h=64, page=64, pages=12, group=1, dtype=jnp.float32),
    "h128p128g16": dict(h=128, page=128, pages=4, group=16, dtype=jnp.bfloat16),
}
# ``idx``: tokens each row holds before the call, in PAGES + slots so one
# list serves both shapes (``(p, o)`` = p pages and o tokens; -1 = the last
# slot of the table). ``wen`` / ``ren``: write_enable / row_enable.
_LOOP_CASES = {
    # S = 1 with the folded write: the decode step
    "fold-more-pages-than-ring-and-fewer": dict(idx=[(1, 6), (0, 5), (3, 60)],
                                                ring=3),
    "fold-one-page-exactly": dict(idx=[(0, 63), (1, 0), (0, 0)]),
    "fold-row-at-capacity": dict(idx=[-1, (1, 36)]),
    "fold-enables-mixed": dict(idx=[(2, 2), (0, 40), (3, 10), (0, 0)],
                               wen=[1, 0, 1, 0], ren=[1, 1, 0, 1]),
    "fold-window": dict(idx=[(3, 50), (1, 26), (1, 0)], window=100),
    "fold-shared-prefix-page": dict(idx=[(1, 36), (2, 2)], share=1),
    # S = 1 already written, and chunks (whichever form their S takes)
    "read": dict(idx=[(1, 6), (0, 5), (3, 60)], fold=False),
    "chunk": dict(idx=[(0, 0), (1, 10), (2, 40)], s=5),
    "chunk-tiled-window-row-off": dict(idx=[(1, 9), (0, 20), (2, 0)], s=70,
                                       block_q=32, window=90, ren=[1, 0, 1]),
}


class TestLoopForm:
    """The loop form of the kernel (``_loop_kernel``) against the plain dense
    reference, and against the emitter form bit for bit where it writes."""

    @pytest.fixture(autouse=True)
    def _fresh_traces(self):
        """The form is fixed when the jitted body is traced: a test that
        patches the rule must not meet, or leave, another's trace."""
        from learning_jax_sharding_tpu.ops import decode_attention as da

        da._decode_attention.clear_cache()
        yield
        da._decode_attention.clear_cache()

    def _emitter(self):
        """The same call through the emitter form (for comparison only: no
        option chooses a form, the operands do)."""
        from unittest import mock

        from learning_jax_sharding_tpu.ops import decode_attention as da

        da._decode_attention.clear_cache()
        return mock.patch.object(da, "pages_in_flight", lambda *a, **k: 0)

    @pytest.mark.parametrize(
        "case,shape,layout",
        [(c, sh, "paged") for c in _LOOP_CASES for sh in _LOOP_SHAPES]
        # per-row buffers: one shape, and nothing to share
        + [(c, "h64p64", "rows") for c, v in _LOOP_CASES.items()
           if not v.get("share")],
    )
    def test_matches_dense_and_emitter(self, monkeypatch, case, shape, layout):
        from learning_jax_sharding_tpu.ops import decode_attention as da

        c = dict(s=1, fold=True, window=None, wen=None, ren=None, share=0,
                 block_q=128, ring=None)
        c.update(_LOOP_CASES[case])
        sh = _LOOP_SHAPES[shape]
        h, page, T, group, dt = (
            sh["h"], sh["page"], sh["pages"], sh["group"], sh["dtype"]
        )
        s, fold, n_kv = c["s"], c["fold"] and c["s"] == 1, 2
        L = T * page
        # (pages, slots) at this shape's page size, scaled into the table.
        idx = np.asarray([
            L - s if i == -1 else min(i[0] * page + i[1] * page // 64, L - s)
            for i in c["idx"]
        ])
        b = len(idx)
        rng = np.random.default_rng(11)
        rnd = lambda *sz: np.array(
            jnp.asarray(rng.normal(size=sz), dt), np.float32
        )                                    # values the cache dtype holds
        kf, vf = rnd(b, n_kv, L, h), rnd(b, n_kv, L, h)
        wen = np.ones(b, np.int32) if c["wen"] is None else np.asarray(c["wen"])
        ren = np.ones(b, np.int32) if c["ren"] is None else np.asarray(c["ren"])
        paged = layout == "paged"
        kw = dict(window=c["window"], block_q=c["block_q"], interpret=True)
        if paged:
            P = b * T + 1
            table = rng.permutation(np.arange(1, P)).reshape(b, T)
            for t in range(c["share"]):      # a full prefix page in common
                table[1:, t] = table[0, t]
                kf[1:, :, t*page:(t+1)*page] = kf[0, :, t*page:(t+1)*page]
                vf[1:, :, t*page:(t+1)*page] = vf[0, :, t*page:(t+1)*page]
            for bi in range(b):              # unallocated entries: scratch
                table[bi, -(-(idx[bi] + s) // page):] = 0
            cache = np.full((P, n_kv, page, 2 * h), 1e4, np.float32)
            for bi in range(b):
                for t in range(T):
                    if table[bi, t]:
                        at = np.s_[bi, :, t*page:(t+1)*page]
                        cache[table[bi, t]] = np.concatenate([kf[at], vf[at]], -1)
            kw.update(block_table=jnp.asarray(table, jnp.int32))
        else:
            cache = np.concatenate([kf, vf], -1)
            kw.update(block_k=page)
        cache = jnp.asarray(cache, dt)
        depth = da.pages_in_flight(cache.shape, cache.dtype, page)
        assert depth == 8                    # these shapes take the loop
        if c["ring"]:
            monkeypatch.setattr(da, "_MAX_IN_FLIGHT", c["ring"])
        q = jnp.asarray(rng.normal(size=(b, s, n_kv * group, h)), jnp.float32)
        if c["ren"] is not None:
            kw.update(row_enable=jnp.asarray(ren))
        if fold:
            kv_new = rnd(b, n_kv, 1, 2 * h)
            kw.update(kv_new=jnp.asarray(kv_new, dt))
            if c["wen"] is not None:
                kw.update(write_enable=jnp.asarray(wen))

        def call():
            with jax.default_matmul_precision("float32"):
                return decode_attention(
                    q, cache, jnp.asarray(idx, jnp.int32), **kw
                )

        result = call()
        out = np.asarray(result[0] if fold else result)
        for bi in range(b):
            if not ren[bi]:
                np.testing.assert_array_equal(out[bi], 0)
                continue
            if not wen[bi]:
                continue                     # a frozen row's output is unused
            kr, vr = kf[bi:bi+1].copy(), vf[bi:bi+1].copy()
            if fold:
                kr[0, :, idx[bi]] = kv_new[bi, :, 0, :h]
                vr[0, :, idx[bi]] = kv_new[bi, :, 0, h:]
            with jax.default_matmul_precision("float32"):
                ref = _dense_oracle(
                    q[bi:bi+1], jnp.asarray(kr), jnp.asarray(vr), int(idx[bi]),
                    window=c["window"],
                )
            np.testing.assert_allclose(out[bi], np.asarray(ref)[0], atol=2e-5)
        if not fold:
            return
        # The cache afterwards: the slot of every row that writes holds its
        # token; everything else (frozen and disabled rows' pages, shared
        # pages, scratch page 0) is bit-identical. And the emitter form
        # leaves the same cache, bit for bit.
        want = np.asarray(cache, np.float32).copy()
        for bi in np.flatnonzero(wen * ren):
            if paged:
                want[table[bi, idx[bi] // page], :, idx[bi] % page] = kv_new[bi, :, 0]
            else:
                want[bi, :, idx[bi]] = kv_new[bi, :, 0]
        np.testing.assert_array_equal(np.asarray(result[1], np.float32), want)
        with self._emitter():
            e_out, e_cache = call()
        np.testing.assert_array_equal(
            np.asarray(result[1], np.float32), np.asarray(e_cache, np.float32)
        )
        live = (wen * ren).astype(bool)
        np.testing.assert_allclose(out[live], np.asarray(e_out)[live], atol=2e-5)

    def test_form_rule(self):
        """Which operands take which form, from shape and dtype alone (the
        compiler's own alignment rule: the same answer on every backend)."""
        from learning_jax_sharding_tpu.ops.decode_attention import pages_in_flight

        bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
        # gpt2-xl's pool: 8 x 409,600 B = 3.3 MB of ring.
        assert pages_in_flight((96, 25, 64, 128), bf16, 64) == 8
        # nemotron's one attention layer: 2 KV heads of 128.
        assert pages_in_flight((512, 2, 128, 256), bf16, 128) == 8
        # per-row buffers in blocks of 256: 12 x 256 x 128 x 2 B = 786 KB.
        assert pages_in_flight((8, 12, 1024, 128), bf16, 256) == 5
        # a block over half the ring: two, the emitter's own depth.
        assert pages_in_flight((4, 64, 1024, 256), f32, 256) == 2
        # not whole tiles: lanes, sublanes (bf16 packs 16 rows a tile).
        assert pages_in_flight((9, 2, 8, 32), f32, 8) == 0
        assert pages_in_flight((96, 25, 64, 64), bf16, 64) == 0
        assert pages_in_flight((33, 2, 8, 128), bf16, 8) == 0
        assert pages_in_flight((33, 2, 8, 128), f32, 8) == 8
        # int8 (float32 scales of (..., N_kv, page)) and the latent row.
        assert pages_in_flight((96, 25, 64, 128), i8, 64, quantized=True) == 0
        assert pages_in_flight((512, 1, 128, 576), bf16, 128, latent=True) == 0
        assert pages_in_flight((512, 1, 128, 640), bf16, 128, latent=True) == 0
