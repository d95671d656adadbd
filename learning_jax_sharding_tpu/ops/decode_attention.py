"""Length-aware KV-cache decode attention as a Pallas (Mosaic) TPU kernel.

Serving-time attention reads the KV cache every generated token, and the
cache buffer is statically sized at ``max_seq_len`` — so a naive decode step
(the dense path in ``models/attention.py::_cached_attention``) reads and
multiplies the WHOLE buffer even when only ``index + S`` slots hold real
tokens. Measured on the v5e 125M decode bench (1024-slot caches, ≤256 valid),
that is ~4.6× off the HBM bandwidth roofline: decode is cache-bandwidth-bound,
and most of the bandwidth went to zero padding.

This kernel makes decode traffic proportional to the VALID cache length:

* the k/v grid dimension covers the full buffer (grids must be static), but
  block index maps CLAMP out-of-range steps to the last needed block — Pallas
  only issues a DMA when a block index changes between consecutive grid
  steps, so clamped (repeated) steps move no HBM bytes, and ``pl.when`` skips
  their compute. Cost scales with ``index + S``, not ``max_seq_len``.
* ALL kv heads ride one grid step (batched dot_generals over the head dim).
  At serving shapes the per-step work is tiny — a (B·N_kv, nk) grid was
  measured grid-step-bound on the v5e, and folding heads cut the 125M decode
  grid from 384 steps to 32.
* the cache layout is ``(B, N_kv, L, H)`` — sequence-major per head — so each
  ``(block_k, H)`` tile is one contiguous DMA (the model's ``(B, L, N, H)``
  training layout would make every cache row a strided 128-byte read).
* GQA-native: q arrives at full ``N = N_kv × group`` heads and is folded to
  ``(group·S, H)`` rows per kv head — the cache is never expanded by
  ``repeat_kv``, so K/V HBM traffic stays at ``N_kv`` heads (the whole point
  of GQA at serving time).
* int8 cache blocks are dequantized INSIDE the kernel, and only for blocks
  actually read. Per-(token, head) scales multiply the score columns
  (``q·(k_int·s) = (q·k_int)·s``) and the probability columns for v, so the
  int8 bytes are what crosses HBM — the upcast never materializes.
* a sliding window additionally advances the FIRST block read
  (``kstart = (index - window + 1) // block_k``), so SWA decode touches only
  the window band.
* chunk queries (prefill / speculative verification) are tiled over a third
  grid dimension in ``block_q``-row tiles, each stopping at its own causal
  frontier — long prompts stay inside VMEM and skip strictly-future blocks'
  traffic and compute both.

The reference has no decode path at all (its attention forward is a timing
harness, `/root/reference/case6_attention.py:229-238`); this is the serving
kernel that replaces it, designed for the TPU memory system rather than
translated from anything.

Inference-only: no VJP (decode is never differentiated).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/max NaN-free
_BLOCK_Q = 128    # q rows per grid tile; bounds VMEM for long prefill chunks


def auto_block_k(length: int, cap: int = 256) -> int:
    """Largest power of two ≤ ``cap`` dividing ``length`` (the k-block size);
    falls back to one full-length block when ``length`` has no power-of-two
    factor ≥ 8 (TPU sublane tiling wants multiples of 8)."""
    blk = 1
    while blk < cap and length % (blk * 2) == 0:
        blk *= 2
    return blk if blk >= 8 else length


def _last_block(bi, qi, sref, *, qb: int, s: int, block_k: int):
    """Last cache block q-tile ``qi`` of row ``bi`` may touch: its causal
    frontier (the tile's final query sits at ``index_b + min((qi+1)·qb, s)
    - 1``), which never exceeds the row's valid prefix ``sref[bi, 1] - 1``.
    Per-ROW: ragged batches (mixed prompt lengths) clamp each row to its own
    frontier, so short rows fetch fewer cache blocks."""
    last_q = jnp.minimum((qi + 1) * qb, s) - 1
    return jnp.minimum(sref[bi, 1] - 1, (sref[bi, 2] + last_q) // block_k)


def _kernel(
    s_ref,                # SMEM (B, 5): [kstart_block, valid_blocks, index,
    #                       write_block, write_offset] per row
    *rest,                # [t_ref (paged block table, index maps only),]
    #                       q_ref (1, N_kv, GQ, H),
    #                       k_ref/v_ref (1, N_kv, block_k, H), ...
    scale: float, block_k: int, group: int, qb: int, s: int,
    window, quantized: bool, fold: bool, paged: bool = False,
):
    rest = list(rest)
    if paged:
        rest.pop(0)  # the block table feeds the index maps, not the body
    q_ref, k_ref, v_ref = rest.pop(0), rest.pop(0), rest.pop(0)
    if quantized:
        ks_ref, vs_ref = rest.pop(0), rest.pop(0)
    if fold:
        kn_ref, vn_ref = rest.pop(0), rest.pop(0)
        if quantized:
            ksn_ref, vsn_ref = rest.pop(0), rest.pop(0)
    o_ref = rest.pop(0)
    if fold:
        ok_ref, ov_ref = rest.pop(0), rest.pop(0)
        if quantized:
            oks_ref, ovs_ref = rest.pop(0), rest.pop(0)
    acc_ref, m_ref, l_ref = rest
    bi, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    blk = s_ref[bi, 0] + j

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(blk <= _last_block(bi, qi, s_ref, qb=qb, s=s, block_k=block_k))
    def _step():
        k_blk = k_ref[0]                                   # (N_kv, bk, H)
        v_blk = v_ref[0]
        if quantized:
            ks_blk, vs_blk = ks_ref[0], vs_ref[0]          # (N_kv, bk)
        if fold:
            # The new token's k/v merge IN-VMEM at this row's write slot —
            # the separate per-row cache scatter (and its serial launch)
            # never exists. Merged blocks flush back through the aliased
            # cache outputs below.
            slot = jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k, 1), 1
            ) == s_ref[bi, 4]
            here = blk == s_ref[bi, 3]

            def merge(blk_vals, new_ref):
                return jnp.where(
                    jnp.logical_and(here, slot), new_ref[0], blk_vals
                )

            k_blk = merge(k_blk, kn_ref)
            v_blk = merge(v_blk, vn_ref)
            if quantized:
                # A second iota, not ``slot[..., 0]``: Mosaic cannot
                # squeeze a mask vector (i1 has no vreg bitcast).
                slot2 = jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1
                ) == s_ref[bi, 4]
                ks_blk = jnp.where(
                    jnp.logical_and(here, slot2), ksn_ref[0], ks_blk
                )
                vs_blk = jnp.where(
                    jnp.logical_and(here, slot2), vsn_ref[0], vs_blk
                )

            @pl.when(here)
            def _write_back():
                ok_ref[0] = k_blk
                ov_ref[0] = v_blk
                if quantized:
                    oks_ref[0] = ks_blk
                    ovs_ref[0] = vs_blk

        q = q_ref[0].astype(jnp.float32) * scale           # (N_kv, GQ, H)
        k = k_blk.astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                  # (N_kv, GQ, bk)
        if quantized:
            # Per-(token, head) k scales are constant over H, so they commute
            # with the contraction: scale the score COLUMNS instead of
            # dequantizing the k block.
            sc = sc * ks_blk[:, None, :]

        gq = q.shape[1]
        # Tile row r is query (qi·qb + r // group) at absolute position
        # index + that; column c is cache slot blk·block_k + c. Rows past the
        # chunk (non-dividing last tile) mask nothing extra — their stores
        # are dropped by the blocked write.
        rows = jax.lax.broadcasted_iota(jnp.int32, (1, gq, 1), 1)
        qpos = s_ref[bi, 2] + qi * qb + (rows // group if group > 1 else rows)
        cols = blk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_k), 2
        )
        mask = cols <= qpos                     # causal + hides the unwritten
        if window is not None:                  # tail of the cache buffer
            mask = jnp.logical_and(mask, cols > qpos - window)
        sc = jnp.where(mask, sc, _NEG_INF)

        m_prev = m_ref[:, :, :1]                           # (N_kv, GQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
        p = jnp.exp(sc - m_new)                            # (N_kv, GQ, bk)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_ref[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
        if quantized:
            # v scales are per cache row = per probability column.
            p = p * vs_blk[:, None, :]
        v = v_blk.astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    # Output block index is constant over j, so it flushes once per q tile;
    # write at the STATIC last step (skipped steps don't touch acc).
    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    index: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    k_new: jax.Array | None = None,
    v_new: jax.Array | None = None,
    ks_new: jax.Array | None = None,
    vs_new: jax.Array | None = None,
    write_enable: jax.Array | None = None,
    block_table: jax.Array | None = None,
    window: int | None = None,
    scale: float | None = None,
    block_k: int | None = None,
    block_q: int = _BLOCK_Q,
    interpret: bool | None = None,
):
    """Attend chunk queries against the valid prefix of a KV cache.

    Args:
        q: ``(B, S, N, H)`` chunk queries (S = 1 for token steps, the prompt
            length for prefill). N may exceed the cache's head count (GQA).
        k_cache / v_cache: ``(B, N_kv, L, H)`` cache buffers — float, or int8
            with ``k_scale``/``v_scale``.
        index: int32 scalar, or per-row ``(B,)`` for RAGGED batches (mixed
            prompt/generation lengths) — absolute position of each row's
            first chunk query; the chunk's own k/v must already be written
            at ``[index_b, index_b + S)``. Slots past a row's frontier are
            never read: per-row block clamping means short rows also fetch
            fewer cache blocks, so ragged decode pays per-row valid-length
            traffic, not the batch max.
        k_scale / v_scale: ``(B, N_kv, L)`` fp32 per-(token, head) scales for
            int8 caches (both or neither).
        window: causal sliding window — query at position p attends
            ``(p - window, p]``; blocks before every query's window are not
            even fetched.
        k_new / v_new: FOLDED WRITE (ragged decode, S = 1 only):
            ``(B, N_kv, 1, H)`` sequence-major new-token k/v, merged
            IN-KERNEL at each row's ``index_b`` slot before attention and
            flushed back through cache outputs ALIASED to the cache inputs
            — one modified block per row moves, and the per-row cache
            scatter (measured at ~18 µs of serial launch per layer,
            PERF.md "Ragged serving") never exists. The chunk must NOT
            already be written to the cache. With int8 caches pass
            ``ks_new``/``vs_new`` ``(B, N_kv, 1)`` chunk scales too.
        write_enable: folded write only — per-row ``(B,)`` mask (nonzero =
            write). Rows with 0 (frozen rows riding a mixed batch with a
            zero chunk length) have their merge slot pushed out of range,
            so their cache block flushes back UNCHANGED — no garbage token
            ever lands in the cache, even transiently. ``None`` writes
            every row.
        block_table: PAGED cache — ``(B, T)`` int32 mapping each row's
            logical block ``t`` (cache positions ``[t·page, (t+1)·page)``)
            to a physical PAGE in a shared pool. The caches then arrive as
            ``(P, N_kv, page, H)`` pools (scales ``(P, N_kv, page)``)
            instead of per-row buffers: physical HBM scales with pages
            actually allocated, not ``B × max_len`` — the block table is
            a SECOND scalar-prefetch operand, and every BlockSpec index
            map simply indirects its logical block through it (the kernel
            body is untouched: all its arithmetic is logical). The folded
            write flushes through the row's mapped page. Unallocated
            entries are never read (per-row frontier clamping) but should
            point at a reserved scratch page for masked writes.
        block_k: cache block size; None auto-selects (≤256 dividing L).
        block_q: q rows per grid tile (VMEM bound for long chunks).
        interpret: run the Pallas interpreter; None = auto (True off-TPU).

    Returns:
        ``(B, S, N, H)`` attention output in ``q.dtype`` — plus, when
        ``k_new`` is given, the updated cache buffers (and scale buffers
        for int8): ``(out, k_cache, v_cache[, k_scale, v_scale])``.
    """
    b, s, n, h = q.shape
    paged = block_table is not None
    if paged:
        pool, n_kv, page, hk = k_cache.shape
        if block_table.shape[0] != b or block_table.ndim != 2:
            raise ValueError(
                f"block_table {block_table.shape} must be (B, T) = ({b}, *)"
            )
        if block_k is not None and block_k != page:
            raise ValueError(
                f"paged cache: block_k ({block_k}) must equal the page "
                f"size ({page})"
            )
        block_k = page
        length = block_table.shape[1] * page   # logical per-row capacity
        bk = b
    else:
        bk, n_kv, length, hk = k_cache.shape
    if (bk, hk) != (b, h) or v_cache.shape != k_cache.shape:
        raise ValueError(
            f"cache shapes {k_cache.shape}/{v_cache.shape} do not match "
            f"queries {q.shape} (want "
            f"{'(P, N_kv, page, H)' if paged else '(B, N_kv, L, H)'} "
            f"with H = {h})"
        )
    if n % n_kv:
        raise ValueError(f"num_heads {n} not a multiple of kv heads {n_kv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    quantized = k_scale is not None
    group = n // n_kv
    scale = h**-0.5 if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_k = auto_block_k(length) if block_k is None else block_k
    if length % block_k:
        raise ValueError(f"cache length {length} not divisible by block_k {block_k}")
    nk = length // block_k
    # q rows tile in whole queries (qb of them → gq = qb·group rows) so a
    # tile's causal frontier is well-defined; single-token decode is one tile.
    qb = min(s, max(1, block_q // group))
    gq = qb * group
    nq = pl.cdiv(s, qb)

    fold = k_new is not None
    if fold:
        if v_new is None:
            raise ValueError("k_new and v_new must be given together")
        if s != 1:
            raise ValueError(f"folded cache write requires S = 1, got {s}")
        if quantized and (ks_new is None or vs_new is None):
            raise ValueError("int8 folded write needs ks_new and vs_new")

    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    valid_blocks = (idx + s + block_k - 1) // block_k
    if window is not None:
        kstart = jnp.maximum(0, (idx - (window - 1)) // block_k)
    else:
        kstart = jnp.zeros((b,), jnp.int32)
    # Disabled rows get a write offset of block_k — outside the kernel's
    # slot iota (0..block_k-1) — so the merge never matches and the block
    # flushes back bit-identical (the write-back itself still runs; it
    # rewrites unchanged data).
    woff = idx % block_k
    if write_enable is not None:
        if not fold:
            raise ValueError("write_enable requires the folded write (k_new)")
        woff = jnp.where(
            jnp.broadcast_to(write_enable, (b,)) != 0, woff, block_k
        )
    sargs = jnp.stack(
        [kstart, valid_blocks, idx, idx // block_k, woff], axis=1
    ).astype(jnp.int32)

    # (B, S, N, H) → (B, N_kv, S·group, H): row r = query (r // group) for
    # in-group head (r % group); q head n belongs to kv head n // group
    # (matching models.attention.repeat_kv's jnp.repeat expansion).
    qr = (
        q.reshape(b, s, n_kv, group, h)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, n_kv, s * group, h)
    )

    last_block = functools.partial(_last_block, qb=qb, s=s, block_k=block_k)

    # All index maps take the scalar-prefetch refs as varargs: ``pf[0]`` is
    # sargs, ``pf[1]`` (paged only) the block table. Paged maps indirect the
    # LOGICAL block through the table into the page pool's leading axis —
    # the only difference between the layouts; the kernel body is shared.
    def qmap(bi, qi, j, *pf):
        return (bi, 0, qi, 0)

    def clamped(bi, qi, j, *pf):
        lb = jnp.minimum(pf[0][bi, 0] + j, last_block(bi, qi, pf[0]))
        return (pf[1][bi, lb], 0, 0, 0) if paged else (bi, 0, lb, 0)

    def clamped_sc(bi, qi, j, *pf):
        lb = jnp.minimum(pf[0][bi, 0] + j, last_block(bi, qi, pf[0]))
        return (pf[1][bi, lb], 0, 0) if paged else (bi, 0, lb)

    in_specs = [
        pl.BlockSpec((1, n_kv, gq, h), qmap),
        pl.BlockSpec((1, n_kv, block_k, h), clamped),
        pl.BlockSpec((1, n_kv, block_k, h), clamped),
    ]
    operands = [qr, k_cache, v_cache]
    if quantized:
        in_specs += [pl.BlockSpec((1, n_kv, block_k), clamped_sc)] * 2
        operands += [k_scale, v_scale]

    out_specs = [pl.BlockSpec((1, n_kv, gq, h), qmap)]
    out_shapes = [jax.ShapeDtypeStruct((b, n_kv, s * group, h), q.dtype)]
    aliases = {}
    prefetch = 2 if paged else 1
    if fold:
        # New-token chunks enter whole; the merged cache block flushes back
        # through outputs ALIASED to the cache inputs (alias indices count
        # the scalar-prefetch operands), so only each row's one modified
        # block moves.
        chunk_spec = pl.BlockSpec(
            (1, n_kv, 1, h), lambda bi, qi, j, *pf: (bi, 0, 0, 0)
        )
        in_specs += [chunk_spec, chunk_spec]
        operands += [k_new, v_new]

        def wb(bi, qi, j, *pf):
            blk = pf[0][bi, 3]
            return (pf[1][bi, blk], 0, 0, 0) if paged else (bi, 0, blk, 0)

        out_specs += [
            pl.BlockSpec((1, n_kv, block_k, h), wb),
            pl.BlockSpec((1, n_kv, block_k, h), wb),
        ]
        out_shapes += [
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ]
        kidx = prefetch + 1              # operand index of k_cache
        aliases[kidx] = 1                # k_cache → output 1
        aliases[kidx + 1] = 2            # v_cache → output 2
        if quantized:
            sc_chunk = pl.BlockSpec(
                (1, n_kv, 1), lambda bi, qi, j, *pf: (bi, 0, 0)
            )
            in_specs += [sc_chunk, sc_chunk]
            operands += [ks_new, vs_new]

            def wbs(bi, qi, j, *pf):
                blk = pf[0][bi, 3]
                return (pf[1][bi, blk], 0, 0) if paged else (bi, 0, blk)

            out_specs += [
                pl.BlockSpec((1, n_kv, block_k), wbs),
                pl.BlockSpec((1, n_kv, block_k), wbs),
            ]
            out_shapes += [
                jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
            ]
            aliases[kidx + 2] = 3        # k_scale → output 3
            aliases[kidx + 3] = 4        # v_scale → output 4

    prefetch_args = (
        (sargs, block_table.astype(jnp.int32)) if paged else (sargs,)
    )
    result = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_k=block_k, group=group, qb=qb, s=s,
            window=window, quantized=quantized, fold=fold, paged=paged,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetch,
            grid=(b, nq, nk),
            in_specs=in_specs,
            out_specs=out_specs if fold else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((n_kv, gq, h), jnp.float32),
                pltpu.VMEM((n_kv, gq, LANES), jnp.float32),
                pltpu.VMEM((n_kv, gq, LANES), jnp.float32),
            ],
        ),
        out_shape=out_shapes if fold else out_shapes[0],
        input_output_aliases=aliases,
        interpret=interpret,
    )(*prefetch_args, *operands)

    out = result[0] if fold else result
    out = (
        out.reshape(b, n_kv, s, group, h)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, s, n, h)
    )
    if fold:
        return (out, *result[1:])
    return out


def make_decode_attn_fn(mesh, rules, **kwargs):
    """A mesh-aware wrapper of :func:`decode_attention` for multi-device
    serving: runs the kernel under ``shard_map`` with batch and heads
    partitioned per the logical ``rules`` (GSPMD cannot partition a custom
    kernel by itself). Mirrors ``ops.flash_attention.make_flash_attn_fn``.

    The returned callable accepts :func:`decode_attention` keywords at CALL
    time (``window``, ``block_k``, ...), which override any baked here — the
    attention module passes its own ``window``/``decode_block_k`` on every
    call, so a wrapper built without them cannot silently drop the model's
    sliding window.
    """
    from flax.linen import partitioning as nn_partitioning
    from jax.sharding import PartitionSpec

    from learning_jax_sharding_tpu.parallel.logical import BATCH, HEADS

    def to_spec(logical):
        return PartitionSpec(
            *nn_partitioning.logical_to_mesh_axes(logical, tuple(rules))
        )

    q_spec = to_spec((BATCH, None, HEADS, None))
    sc_spec = to_spec((BATCH, HEADS, None))
    row_idx_spec = to_spec((BATCH,))
    # Paged pools lead with the shared PAGE axis: heads-only sharding. Any
    # row may read any page, so the batch must NOT be sharded in paged mode
    # (checked in attn_fn) — the engine serves with TP over heads.
    paged_kv_spec = to_spec((None, HEADS, None, None))
    paged_sc_spec = to_spec((None, HEADS, None))

    def attn_fn(
        q, k_cache, v_cache, index, *,
        k_scale=None, v_scale=None,
        k_new=None, v_new=None, ks_new=None, vs_new=None,
        write_enable=None, block_table=None,
        **call_kwargs,
    ):
        fn = functools.partial(decode_attention, **{**kwargs, **call_kwargs})
        paged = block_table is not None
        if paged:
            batch_axes = nn_partitioning.logical_to_mesh_axes(
                (BATCH,), tuple(rules)
            )[0]
            axes = (
                (batch_axes,) if isinstance(batch_axes, str)
                else tuple(batch_axes or ())
            )
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if size > 1:
                raise ValueError(
                    "paged serving cannot shard the batch (any row may "
                    "read any page): use rules that leave BATCH unmapped "
                    "(TP over heads) or a batch mesh axis of size 1"
                )
        kv_spec = paged_kv_spec if paged else to_spec((BATCH, HEADS, None, None))
        # Scalar index replicates; a per-row (B,) index (ragged serving)
        # shards with the batch.
        idx_spec = row_idx_spec if jnp.ndim(index) == 1 else PartitionSpec()
        in_specs = [q_spec, kv_spec, kv_spec, idx_spec]
        args = [q, k_cache, v_cache, index]
        quantized = k_scale is not None
        fold = k_new is not None
        keys = []
        cache_sc_spec = paged_sc_spec if paged else sc_spec
        if quantized:
            in_specs += [cache_sc_spec, cache_sc_spec]
            args += [k_scale, v_scale]
            keys += ["k_scale", "v_scale"]
        if fold:
            # New-token chunks (and their scales) are PER-ROW even in paged
            # mode — only the pools lose their batch axis.
            chunk_spec = to_spec((BATCH, HEADS, None, None))
            in_specs += [chunk_spec, chunk_spec]
            args += [k_new, v_new]
            keys += ["k_new", "v_new"]
            if quantized:
                in_specs += [sc_spec, sc_spec]
                args += [ks_new, vs_new]
                keys += ["ks_new", "vs_new"]
            if write_enable is not None:
                in_specs += [row_idx_spec]
                args += [write_enable]
                keys += ["write_enable"]
        elif write_enable is not None:
            # Mirror decode_attention's own guard — the wrapper must not
            # silently drop a misused mask.
            raise ValueError("write_enable requires the folded write (k_new)")
        if paged:
            in_specs += [to_spec((BATCH, None))]
            args += [block_table]
            keys += ["block_table"]
        # Folded writes return the updated cache (+ scale) buffers alongside
        # the attention output; each keeps its input's sharding.
        out_specs = q_spec
        if fold:
            out_specs = (q_spec, kv_spec, kv_spec)
            if quantized:
                out_specs += (cache_sc_spec, cache_sc_spec)

        def body(q_, k_, v_, i_, *rest):
            return fn(q_, k_, v_, i_, **dict(zip(keys, rest)))

        # check_vma=False: pallas_call's out_shape carries no varying-axes
        # metadata, which the static replication checker requires.
        return jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
            check_vma=False,
        )(*args)

    return attn_fn
