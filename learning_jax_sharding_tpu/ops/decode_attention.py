"""Length-aware KV-cache decode attention as a Pallas (Mosaic) TPU kernel.

Serving-time attention reads the KV cache every generated token, and the
cache buffer is statically sized at ``max_seq_len`` — so a naive decode step
(the dense path in ``models/attention.py::_cached_attention``) reads and
multiplies the WHOLE buffer even when only ``index + S`` slots hold real
tokens. Measured on the v5e 125M decode bench (1024-slot caches, ≤256 valid),
that is ~4.6× off the HBM bandwidth roofline: decode is cache-bandwidth-bound,
and most of the bandwidth went to zero padding.

This kernel makes decode traffic AND steps proportional to the VALID cache
length. Each row is worked through from its first block to its last valid
one and no further; HOW a block reaches VMEM has two forms, chosen from what
the operands show (:func:`pages_in_flight`: shape and dtype alone, the same
answer on every backend, no option):

* the LOOP form (``_loop_kernel``), wherever Mosaic can slice the cache in
  HBM: rows of whole 128-lane tiles, blocks of whole sublane tiles (gpt2-xl's
  fused ``(96, 25, 64, 128)`` bf16 pool, a GQA layer's ``(512, 2, 128,
  256)``). The grid is ``(q tiles, rows)``, the cache stays in HBM
  (``pl.ANY``), and each program loops over the blocks its row holds,
  fetching them with its own DMAs into a ring of up to eight VMEM buffers. A
  cursor in SMEM runs ahead of the computing program over every program's
  blocks in grid order, so up to seven later blocks (the next rows' first
  ones too) are on their way while one is computed, and a block costs no
  grid step: 0.90 µs a 410 KB page of gpt2-xl's where the emitter form
  takes 1.20 (PERF.md, PR 34; what is left is the step's arithmetic).
* the EMITTER form (``_kernel``), for the layouts that are not whole tiles
  (an int8 cache's float32 scale arrays ``(…, N_kv, block)``, the 576-value
  latent row; ROADMAP S12): the grid walks a WORK LIST, one step per
  (row, cache block) a row holds: ``(nq, W)`` with ``W = Σ_rows blocks
  held`` a RUN-TIME scalar (Pallas takes a traced grid bound), and two
  scalar-prefetched arrays naming each step's row and logical block. Index
  maps read the list, so the Pallas pipeline fetches exactly one block that
  some row needs a step, double-buffered and no deeper; no shape depends on
  the traffic. A static ``(B, nq, L // block_k)`` grid, its out-of-range
  steps clamped (no DMA) and skipped by ``pl.when``, still pays the
  pipeline's per-step cost on every skipped step: at the paged serving
  shape (16 rows under a table 16 pages wide, ~3.4 pages held a row) 256
  steps for ~54 that read anything, 0.29 µs each, a third of the call
  (PERF.md, PR 28).

Both forms run ONE online-softmax step (``_attend_block``: mask, window, GQA
fold, ``m / l / acc``) and share everything below:

* each position's k and v share ONE cache row, ``k | v`` on the minor axis
  (:func:`fuse_kv`): ``(B, N_kv, L, 2H)``. A TPU array's minor axis is
  padded to 128 lanes in HBM, so at head size 64 two ``(…, 64)`` buffers
  took twice their bytes in memory and in every block moved; the fused row
  is exactly 128 lanes, and a block is one DMA instead of two. The kernel
  splits the tile's lane halves in VMEM.
* ALL kv heads ride one step (batched dot_generals over the head dim).
  At serving shapes the per-step work is tiny — a (B·N_kv, nk) grid was
  measured grid-step-bound on the v5e, which is why heads fold into one
  step (and why no step reads nothing).
* the cache layout is ``(B, N_kv, L, 2H)`` — sequence-major per head — so
  each ``(block_k, 2H)`` tile is one contiguous DMA (the model's
  ``(B, L, N, H)`` training layout would make every cache row a strided
  read).
* GQA-native: q arrives at full ``N = N_kv × group`` heads and is folded to
  ``(group·S, H)`` rows per kv head — the cache is never expanded by
  ``repeat_kv``, so K/V HBM traffic stays at ``N_kv`` heads (the whole point
  of GQA at serving time).
* int8 cache blocks are dequantized INSIDE the kernel, and only for blocks
  actually read. Per-(token, head) scales multiply the score columns
  (``q·(k_int·s) = (q·k_int)·s``) and the probability columns for v, so the
  int8 bytes are what crosses HBM — the upcast never materializes.
* a sliding window additionally advances the FIRST block read
  (``kstart = (index - window + 1) // block_k``; the row's blocks start
  there), so SWA decode touches only the window band. A window layer of a
  paged cache may keep a RING of ``R`` pages a row (``ring=True``,
  :func:`ring_pages`): logical block ``j`` is read and written at
  ``table[row, j mod R]``, whatever form the call takes, and such a call
  runs under a name of its own (``attn.window_cached_attention``).
* chunk queries (prefill / speculative verification) are tiled over the
  grid's leading dimension in ``block_q``-row tiles, each stopping at its
  own causal frontier — long prompts stay inside VMEM; in the emitter form
  a tile repeats its last block for the row's strictly-future blocks (no
  DMA, no compute), in the loop form its program simply ends there.
* the FOLDED WRITE (S = 1): the new token's k | v merges in VMEM into the
  row's write block before that block's step and goes back through a cache
  output aliased to the input — a whole block a row in the emitter form,
  the one sublane tile that holds the slot in the loop form.

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
# The name the kernel runs under in a device trace: XLA names the custom call
# after the innermost scope, which was the attention module's cached-attention
# method until the call was jitted here; the benchmark's trace metrics find the
# kernel by it (benchmark/metrics/decode_attn_roofline.json).
_TRACE_NAME = "attn._blocked_cached_attention"
# The latent (MLA) form of the same call: one shared "head" whose cache row
# ``[c_kv | k_rope]`` is both the key (all of it) and the value (its first
# ``latent_v`` lanes) — benchmark/metrics/mla_decode_attn_roofline.json.
_LATENT_TRACE_NAME = "attn.mla_cached_attention"
# A sliding-window layer's call (benchmark/metrics/swa_decode_attn_roofline.json):
# its own name, so the window layers' time and the global layers' read apart.
_WINDOW_TRACE_NAME = "attn.window_cached_attention"


def ring_pages(window: int, page: int) -> int:
    """Pages of a window layer's RING a slot: logical block ``j`` of its
    cache lives in ring page ``j mod R``. A query at ``p`` reads positions
    ``(p - window, p]``, and a dispatch writes at most ``window`` positions
    of a slot before its first query reads (the engine's bound: a refill
    chunk is no wider than the window, a slot takes at most ``window //
    chunk`` rows of one dispatch, a decode step writes one position), so
    the ring must hold every page that ``2 * window - 1`` consecutive
    positions touch, and no page of them may stand for two blocks."""
    span = 2 * window - 1
    return -(-(span - 1) // page) + 1


def window_band(index, s: int, window: int, block_k: int):
    """``(first block, blocks through the last query)`` a row of a
    sliding-window call reads: the scalars the call computes for a chunk of
    ``s`` queries whose first sits at ``index`` (before it clamps them to
    its buffer). Plain integer arithmetic, for the engine to count what
    decode read."""
    last = (index + s + block_k - 1) // block_k
    first = (index - (window - 1)) // block_k
    return first * (first > 0), last


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
    - 1``), which never exceeds the row's valid prefix ``sref[1, bi] - 1``.
    Per-ROW: ragged batches (mixed prompt lengths) clamp each row to its own
    frontier, so short rows fetch fewer cache blocks."""
    last_q = jnp.minimum((qi + 1) * qb, s) - 1
    return jnp.minimum(sref[1, bi] - 1, (sref[2, bi] + last_q) // block_k)


def _step_of(w, sref, *, b: int):
    """Grid step ``w`` of the work list → ``(row, logical block)``. The list
    is never materialized: rows follow each other in order, row ``r``
    holding steps ``[ends[r-1], ends[r])`` with ``ends = sref[5]`` the
    running total of blocks held, so the row is a binary search on the
    scalar core and the block follows from the row's last one. ``sref[5]``
    is padded to a power of two with a sentinel no step reaches, so the
    search needs no bounds check. Bare ``lax`` primitives: every index map
    of a call traces this, and ``jnp`` wrappers cost several times more."""
    row = jnp.int32(0)
    for shift in reversed(range((b - 1).bit_length())):
        # Do at least ``row + 2^shift`` rows end at or before w?
        ends = sref[5, jax.lax.add(row, jnp.int32((1 << shift) - 1))]
        row = jax.lax.select(
            jax.lax.le(ends, w), jax.lax.add(row, jnp.int32(1 << shift)), row
        )
    return row, jax.lax.sub(sref[1, row], jax.lax.sub(sref[5, row], w))


def _merge_new(blk, new, here, woff, ndim: int):
    """The folded write's merge: ``new`` (one position's values) replaces
    position ``woff`` of the cache block ``blk`` where ``here`` (this IS the
    row's write block). A disabled row's ``woff`` is past the block, so
    nothing matches. A fresh iota per rank, not a squeezed mask: Mosaic
    cannot squeeze a mask vector (i1 has no vreg bitcast)."""
    shape = (1, blk.shape[1]) + (1,) * (ndim - 2)
    slot = jax.lax.broadcasted_iota(jnp.int32, shape, 1) == woff
    return jnp.where(jnp.logical_and(here, slot), new, blk)


def _attend_block(
    kv_blk, ks_blk, vs_blk, q_ref, acc_ref, m_ref, l_ref, *, index, qi, blk,
    scale: float, block_k: int, group: int, qb: int, window,
    latent_v: int | None,
):
    """One online-softmax step: q tile ``qi`` of a row whose first query
    sits at ``index`` against cache block ``blk`` (``kv_blk``
    ``(N_kv, block_k, 2H)``, with its ``(N_kv, block_k)`` scales for an
    int8 cache), folded into the running ``m / l / acc``. The one piece of
    arithmetic both kernel bodies run, however the block reached VMEM."""
    h = q_ref.shape[-1]
    if latent_v is None:
        k_blk, v_blk = kv_blk[:, :, :h], kv_blk[:, :, h:]  # lane halves
        q = q_ref[0].astype(jnp.float32) * scale       # (N_kv, GQ, H)
        k = k_blk.astype(jnp.float32)
        mm = jnp.float32
    else:
        # Latent rows: the whole row is the key, its first ``latent_v``
        # lanes (whole 128-lane tiles) the value — no second operand.
        # The dots take the cache's own type (bf16 products are exact
        # in the f32 accumulator; a chunk's 128-row dots over 576 lanes
        # are real MXU work), the scale moves onto the f32 scores.
        k_blk, v_blk = kv_blk, kv_blk[:, :, :latent_v]
        mm = kv_blk.dtype
        q, k = q_ref[0].astype(mm), k_blk
    sc = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                  # (N_kv, GQ, bk)
    if latent_v is not None:
        sc = sc * scale
    if ks_blk is not None:
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
    qpos = index + qi * qb + (rows // group if group > 1 else rows)
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
    if vs_blk is not None:
        # v scales are per cache row = per probability column.
        p = p * vs_blk[:, None, :]
    v = v_blk.astype(mm)
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        p.astype(mm), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _start_row(acc_ref, m_ref, l_ref):
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)


def _finish_row(o_ref, acc_ref, l_ref):
    l = l_ref[:, :, :1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _kernel(
    s_ref,                # SMEM (6, B): kstart_block, valid_blocks, index,
    #                       write_block, write_offset, blocks held by
    #                       this row and the rows before it — per row
    *rest,                # [t_ref (paged block table, index maps only),]
    #                       q_ref (1, N_kv, GQ, H),
    #                       kv_ref (1, N_kv, block_k, 2H), ...
    scale: float, block_k: int, group: int, qb: int, s: int,
    window, quantized: bool, fold: bool, paged: bool = False,
    latent_v: int | None = None,
):
    """The EMITTER form: the grid walks the work list, the Pallas pipeline
    brings each step's block (one in flight beside the one computed)."""
    rest = list(rest)
    if paged:
        rest.pop(0)  # the block table feeds the index maps, not the body
    q_ref, kv_ref = rest.pop(0), rest.pop(0)
    if quantized:
        ks_ref, vs_ref = rest.pop(0), rest.pop(0)
    if fold:
        kvn_ref = rest.pop(0)
        if quantized:
            ksn_ref, vsn_ref = rest.pop(0), rest.pop(0)
    o_ref = rest.pop(0)
    if fold:
        okv_ref = rest.pop(0)
        if quantized:
            oks_ref, ovs_ref = rest.pop(0), rest.pop(0)
    acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(0)
    bi, blk = _step_of(pl.program_id(1), s_ref, b=s_ref.shape[1])

    @pl.when(blk == s_ref[0, bi])
    def _init():
        _start_row(acc_ref, m_ref, l_ref)

    @pl.when(blk <= _last_block(bi, qi, s_ref, qb=qb, s=s, block_k=block_k))
    def _step():
        kv_blk = kv_ref[0]                                 # (N_kv, bk, 2H)
        ks_blk = vs_blk = None
        if quantized:
            ks_blk, vs_blk = ks_ref[0], vs_ref[0]          # (N_kv, bk)
        if fold:
            # The new token's k|v merges IN-VMEM at this row's write slot —
            # the separate per-row cache scatter (and its serial launch)
            # never exists. The merged block flushes back through the
            # aliased cache output below.
            here, woff = blk == s_ref[3, bi], s_ref[4, bi]
            kv_blk = _merge_new(kv_blk, kvn_ref[0], here, woff, 3)
            if quantized:
                ks_blk = _merge_new(ks_blk, ksn_ref[0], here, woff, 2)
                vs_blk = _merge_new(vs_blk, vsn_ref[0], here, woff, 2)

            @pl.when(here)
            def _write_back():
                okv_ref[0] = kv_blk
                if quantized:
                    oks_ref[0] = ks_blk
                    ovs_ref[0] = vs_blk

        _attend_block(
            kv_blk, ks_blk, vs_blk, q_ref, acc_ref, m_ref, l_ref,
            index=s_ref[2, bi], qi=qi, blk=blk, scale=scale,
            block_k=block_k, group=group, qb=qb, window=window,
            latent_v=latent_v,
        )

    # The output block index is constant over a row's steps, so it flushes
    # once per row and q tile; write at the row's LAST listed block (a tile
    # whose causal frontier came earlier skipped the steps between).
    @pl.when(blk == s_ref[1, bi] - 1)
    def _finish():
        _finish_row(o_ref, acc_ref, l_ref)


def _loop_kernel(
    s_ref,                # SMEM (6, B) as above, but row 5 = blocks held by
    #                       THIS row (0: the row is off ``row_enable``)
    *rest,                # [t_ref (paged block table),] q_ref, kv_hbm (the
    #                       whole cache, in HBM), [kvn_ref,] o_ref, [okv_hbm
    #                       (the same buffer, aliased),] scratch
    scale: float, block_k: int, group: int, qb: int, s: int,
    window, fold: bool, paged: bool, depth: int, ringed: bool = False,
):
    """The LOOP form: the grid is ``(q tiles, rows)`` and each program loops
    over the blocks its row holds, fetching them with its own DMAs into a
    ring of ``depth`` VMEM buffers. A block costs no grid step and waits
    for no DMA: up to ``depth - 1`` later blocks are already on their way
    while one is computed, across program boundaries too — a cursor in
    SMEM runs ahead over the programs' blocks in grid order, so a row's
    first blocks were asked for while the rows before it computed.

    The folded write reads its page through the aliased INPUT and writes
    the one sublane tile that holds the new token through the OUTPUT, both
    the same HBM buffer. That is safe: a write page is private to its row,
    and a page two rows share (a prefix) is full and never written, so no
    row reads what another row writes in the call. A row with its write
    off (``write_enable`` 0, or off ``row_enable``) starts no write-back:
    frozen rows all sit on scratch page 0 and must not race there.
    """
    rest = list(rest)
    t_ref = rest.pop(0) if paged else None
    q_ref, kv_hbm = rest.pop(0), rest.pop(0)
    kvn_ref = rest.pop(0) if fold else None
    o_ref = rest.pop(0)
    okv_hbm = rest.pop(0) if fold else None
    acc_ref, m_ref, l_ref, ring, sems, cur = rest[:6]
    qi, bi = pl.program_id(0), pl.program_id(1)
    nq, b = pl.num_programs(0), pl.num_programs(1)
    last_block = functools.partial(_last_block, qb=qb, s=s, block_k=block_k)

    def blocks_of(q_, b_):
        return jnp.where(
            s_ref[5, b_] > 0, last_block(b_, q_, s_ref) - s_ref[0, b_] + 1, 0
        )

    def page_of(b_, blk_):
        # A ring table maps logical block j to its page j mod R.
        return t_ref[b_, jax.lax.rem(blk_, t_ref.shape[1]) if ringed else blk_]

    def block_copy(b_, blk_, slot):
        if paged:
            src = kv_hbm.at[page_of(b_, blk_)]
        else:
            src = kv_hbm.at[
                b_, :, pl.ds(pl.multiple_of(blk_ * block_k, block_k), block_k)
            ]
        return pltpu.make_async_copy(src, ring.at[slot], sems.at[slot])

    def after(q_, b_):
        wrap = b_ + 1 == b
        return jnp.where(wrap, q_ + 1, q_), jnp.where(wrap, 0, b_ + 1)

    def seek(q_, b_):
        """The first program at or after ``(q_, b_)`` that holds a block,
        or ``(nq, 0)``."""
        def found(q__, b__):
            return jnp.logical_or(q__ >= nq, blocks_of(q__, b__) > 0)

        def advance(c):
            q__, b__ = after(c[0], c[1])
            return q__, b__, found(q__, b__)

        q_, b_, _ = jax.lax.while_loop(
            lambda c: jnp.logical_not(c[2]), advance, (q_, b_, found(q_, b_))
        )
        return q_, b_

    # cur (SMEM): the cursor's q tile, row, block; blocks asked for; blocks
    # taken; write-backs started.
    def set_cursor(q_, b_):
        cur[0], cur[1], cur[2] = q_, b_, s_ref[0, b_]

    def fetch_ahead():
        @pl.when(cur[0] < nq)
        def _():
            q_, b_, blk_ = cur[0], cur[1], cur[2]
            block_copy(b_, blk_, cur[3] % depth).start()
            cur[3] += 1
            row_done = blk_ >= last_block(b_, q_, s_ref)

            @pl.when(jnp.logical_not(row_done))
            def _():
                cur[2] = blk_ + 1

            @pl.when(row_done)
            def _():
                set_cursor(*seek(*after(q_, b_)))

    @pl.when(jnp.logical_and(qi == 0, bi == 0))
    def _first_program():
        set_cursor(*seek(jnp.int32(0), jnp.int32(0)))
        cur[3] = cur[4] = cur[5] = 0
        for _ in range(depth - 1):
            fetch_ahead()

    if fold:
        stage, wsems = rest[6:]
        tile = stage.shape[2]

        def write_back_wait(k):
            # Any descriptor of the staged tile's size waits for slot k.
            pltpu.make_async_copy(
                stage.at[k], okv_hbm.at[0, :, pl.ds(0, tile)], wsems.at[k]
            ).wait()

    _start_row(acc_ref, m_ref, l_ref)
    first = s_ref[0, bi]

    def step(i, carry):
        blk = first + i
        fetch_ahead()
        slot = cur[4] % depth
        block_copy(bi, blk, slot).wait()
        cur[4] += 1
        if fold:
            here, woff = blk == s_ref[3, bi], s_ref[4, bi]

            @pl.when(jnp.logical_and(here, woff < block_k))
            def _merge_and_write_back():
                # The new token lands in the ring's copy of the row's write
                # block before the step below reads it. Only the sublane
                # tile that holds the slot goes back, from a staging pair
                # of its own: the ring slot is free for the next fetch
                # while the write is on its way.
                k = cur[5] % 2

                @pl.when(cur[5] >= 2)
                def _():
                    write_back_wait(k)

                sub = pl.multiple_of((woff // tile) * tile, tile)
                merged = _merge_new(
                    ring[slot, :, pl.ds(sub, tile)], kvn_ref[0], True,
                    woff - sub, 3,
                )
                ring[slot, :, pl.ds(sub, tile)] = merged
                stage[k] = merged
                if paged:
                    dst = okv_hbm.at[page_of(bi, blk), :, pl.ds(sub, tile)]
                else:
                    dst = okv_hbm.at[
                        bi, :,
                        pl.ds(pl.multiple_of(blk * block_k + sub, tile), tile),
                    ]
                pltpu.make_async_copy(stage.at[k], dst, wsems.at[k]).start()
                cur[5] += 1

        kv_blk = ring[slot]                                # (N_kv, bk, 2H)
        _attend_block(
            kv_blk, None, None, q_ref, acc_ref, m_ref, l_ref,
            index=s_ref[2, bi], qi=qi, blk=blk, scale=scale,
            block_k=block_k, group=group, qb=qb, window=window,
            latent_v=None,
        )
        return carry

    jax.lax.fori_loop(0, blocks_of(qi, bi), step, 0)
    _finish_row(o_ref, acc_ref, l_ref)

    if fold:
        @pl.when(jnp.logical_and(qi == nq - 1, bi == b - 1))
        def _last_program():
            for k in range(2):
                @pl.when(cur[5] > k)
                def _():
                    write_back_wait(k)


# The loop form's ring of block buffers may take this much VMEM.
_RING_BYTES = 4 * 1024 * 1024
_MAX_IN_FLIGHT = 8


def pages_in_flight(
    cache_shape, dtype, block_k: int, *, quantized: bool = False,
    latent: bool = False,
) -> int:
    """How many cache blocks a call keeps in flight: the depth of the loop
    form's ring, or 0 where the call takes the emitter form. Decided from
    what the operands show, alike on every backend: Mosaic slices an HBM
    operand only in whole tiles, so the loop form needs cache rows of whole
    128-lane tiles and blocks of whole sublane tiles; the int8 cache's
    float32 scale arrays ``(…, N_kv, block)`` and the 576-value latent row
    are not (ROADMAP S12). The depth is as many blocks as fit
    ``_RING_BYTES``, at most ``_MAX_IN_FLIGHT`` and at least the emitter's
    own two."""
    n_kv, hk = cache_shape[1], cache_shape[3]
    itemsize = jnp.dtype(dtype).itemsize
    if (
        quantized or latent or itemsize == 1
        or hk % LANES or block_k % (32 // itemsize)
    ):
        return 0
    block_bytes = n_kv * block_k * hk * itemsize
    return max(2, min(_MAX_IN_FLIGHT, _RING_BYTES // block_bytes))


def fuse_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """``(..., H)`` keys and values → the cache's fused ``(..., 2H)`` rows:
    k in lanes ``[0, H)``, v in ``[H, 2H)``."""
    return jnp.concatenate([k, v], axis=-1)


def decode_attention(
    q: jax.Array,
    kv_cache: jax.Array,
    index: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    kv_new: jax.Array | None = None,
    ks_new: jax.Array | None = None,
    vs_new: jax.Array | None = None,
    write_enable: jax.Array | None = None,
    block_table: jax.Array | None = None,
    row_enable: jax.Array | None = None,
    window: int | None = None,
    scale: float | None = None,
    block_k: int | None = None,
    block_q: int = _BLOCK_Q,
    latent_v: int | None = None,
    ring: bool = False,
    interpret: bool | None = None,
):
    """Attend chunk queries against the valid prefix of a KV cache.

    Args:
        q: ``(B, S, N, H)`` chunk queries (S = 1 for token steps, the prompt
            length for prefill). N may exceed the cache's head count (GQA).
        kv_cache: ``(B, N_kv, L, 2H)`` cache buffer, each position's k and v
            FUSED on the minor axis (:func:`fuse_kv`) — float, or int8 with
            ``k_scale``/``v_scale``. One 128-lane row at head size 64: a
            ``(..., 64)`` minor axis is padded to 128 lanes in HBM, so
            separate k and v buffers cost twice their bytes in memory and
            in every block the kernel moves.
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
        kv_new: FOLDED WRITE (ragged decode, S = 1 only):
            ``(B, N_kv, 1, 2H)`` sequence-major new-token k|v, merged
            IN-KERNEL at each row's ``index_b`` slot before attention and
            flushed back through a cache output ALIASED to the cache input
            — one modified block per row moves (in the loop form one
            sublane tile of it), and the per-row cache scatter (measured
            at ~18 µs of serial launch per layer, PERF.md "Ragged
            serving") never exists. The chunk must NOT
            already be written to the cache. With int8 caches pass
            ``ks_new``/``vs_new`` ``(B, N_kv, 1)`` chunk scales too.
        write_enable: folded write only — per-row ``(B,)`` mask (nonzero =
            write). Rows with 0 (frozen rows riding a mixed batch with a
            zero chunk length) have their merge slot pushed out of range,
            so their cache block flushes back UNCHANGED (the emitter form)
            or not at all (the loop form) — no garbage token ever lands in
            the cache, even transiently. ``None`` writes every row.
        block_table: PAGED cache — ``(B, T)`` int32 mapping each row's
            logical block ``t`` (cache positions ``[t·page, (t+1)·page)``)
            to a physical PAGE in a shared pool. The cache then arrives as
            a ``(P, N_kv, page, 2H)`` pool (scales ``(P, N_kv, page)``)
            instead of per-row buffers: physical HBM scales with pages
            actually allocated, not ``B × max_len`` — the block table is
            a SECOND scalar-prefetch operand, and every block address (a
            BlockSpec index map's, or the loop form's own DMA's) simply
            indirects its logical block through it: the arithmetic is all
            logical. The folded write flushes through the row's mapped
            page. Unallocated
            entries are never read (per-row frontier clamping) but should
            point at a reserved scratch page for masked writes.
        ring: the block table is a RING of ``R`` pages a row (a sliding-
            window layer's, :func:`ring_pages`): logical block ``t`` lives
            in page ``block_table[b, t mod R]``, so the row's logical
            capacity is not the table's width. The caller keeps every
            block the window band and the chunk touch within ``R``.
        row_enable: per-row ``(B,)`` mask (nonzero = attend). A row with 0
            gets NO step of the work list: none of its blocks is read, its
            folded write does not happen, and its output is zeros. For
            refill chunks, where every slot of the engine rides the call
            and only the refilling ones have queries worth answering.
        latent_v: LATENT cache (multi-head latent attention, absorbed
            form): ``kv_cache`` is ``(B | P, 1, L | page, R)`` with one
            row ``[c_kv | k_rope]`` a token shared by every head, ``q`` is
            ``(B, S, N, R)`` (``[q_nope · W_k | q_rope]``), scores run over
            all ``R`` lanes and the values are the row's first
            ``latent_v`` lanes (a multiple of 128): the result is
            ``(B, S, N, latent_v)``. ``scale`` has to be given (the model's
            ``(nope + rope) ** -0.5``, not ``R ** -0.5``). No int8 form.
        block_k: cache block size; None auto-selects (≤256 dividing L).
        block_q: q rows per grid tile (VMEM bound for long chunks).
        interpret: run the Pallas interpreter; None = auto (True off-TPU).

    Returns:
        ``(B, S, N, H)`` attention output in ``q.dtype`` — plus, when
        ``kv_new`` is given, the updated cache buffer (and scale buffers
        for int8): ``(out, kv_cache[, k_scale, v_scale])``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _decode_attention(
        q, kv_cache, index, k_scale, v_scale, kv_new, ks_new, vs_new,
        write_enable, block_table, row_enable, window=window, scale=scale,
        block_k=block_k, block_q=block_q, latent_v=latent_v, ring=ring,
        interpret=interpret,
    )


def _emitter_specs(
    *, b, n_kv, gq, h, h_out, hk, block_k, qb, s, paged, quantized, fold,
    ring=False,
):
    """``(in_specs, out_specs)`` of the emitter form: every index map reads
    the work list, so a grid step's blocks are what that step needs."""
    last_block = functools.partial(_last_block, qb=qb, s=s, block_k=block_k)

    # All index maps take the scalar-prefetch refs as varargs: ``pf[0]`` is
    # sargs, ``pf[1]`` (paged only) the block table. Paged maps indirect
    # the LOGICAL block through the table into the page pool's leading axis
    # — the only difference between the layouts; the kernel body is shared.
    # ``tail`` is the block index of the dims after the sequence dim:
    # ``(0,)`` for k|v, ``()`` for scales.
    step_of = functools.partial(_step_of, b=b)

    def row_map(tail):
        return lambda qi, w, *pf: (step_of(w, pf[0])[0], 0, *tail)

    def block_at(bi, lb, pf, tail):
        if not paged:
            return (bi, 0, lb, *tail)
        # A ring table maps logical block j to its page j mod R.
        col = jax.lax.rem(lb, pf[1].shape[1]) if ring else lb
        return (pf[1][bi, col], 0, 0, *tail)

    def clamped(tail):
        # A q tile whose causal frontier comes before the row's last block
        # repeats its own last block for the steps between: no DMA moves.
        def index_map(qi, w, *pf):
            bi, blk = step_of(w, pf[0])
            lb = jnp.minimum(blk, last_block(bi, qi, pf[0]))
            return block_at(bi, lb, pf, tail)

        return index_map

    def written(tail):
        def index_map(qi, w, *pf):
            bi, _ = step_of(w, pf[0])
            return block_at(bi, pf[0][3, bi], pf, tail)

        return index_map

    def q_tile(width):
        return pl.BlockSpec(
            (1, n_kv, gq, width),
            lambda qi, w, *pf: (step_of(w, pf[0])[0], 0, qi, 0),
        )

    in_specs = [q_tile(h), pl.BlockSpec((1, n_kv, block_k, hk), clamped((0,)))]
    if quantized:
        in_specs += [pl.BlockSpec((1, n_kv, block_k), clamped(()))] * 2
    out_specs = [q_tile(h_out)]
    if fold:
        # The new-token chunk enters whole; the merged cache block flushes
        # back through the aliased output, one modified block a row.
        in_specs += [pl.BlockSpec((1, n_kv, 1, hk), row_map((0, 0)))]
        out_specs += [pl.BlockSpec((1, n_kv, block_k, hk), written((0,)))]
        if quantized:
            in_specs += [pl.BlockSpec((1, n_kv, 1), row_map((0,)))] * 2
            out_specs += [pl.BlockSpec((1, n_kv, block_k), written(()))] * 2
    return in_specs, out_specs


# One jitted body for every call of one configuration: a model's layers call
# with the same shapes and statics, so the kernel, its index maps and the
# scalars around it are traced and lowered ONCE per program, not per layer.
@functools.partial(
    jax.jit,
    static_argnames=(
        "window", "scale", "block_k", "block_q", "latent_v", "ring",
        "interpret",
    ),
)
def _decode_attention(
    q, kv_cache, index, k_scale, v_scale, kv_new, ks_new, vs_new,
    write_enable, block_table, row_enable, *, window, scale, block_k,
    block_q, latent_v, ring, interpret,
):
    b, s, n, h = q.shape
    latent = latent_v is not None
    h_out = latent_v if latent else h
    paged = block_table is not None
    if paged:
        pool, n_kv, page, hk = kv_cache.shape
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
        if ring and window is None:
            raise ValueError("a ring of pages serves a sliding window only")
    else:
        bk, n_kv, length, hk = kv_cache.shape
    if latent:
        if (
            n_kv != 1 or hk != h or not 0 < latent_v <= h
            or (latent_v % LANES and not interpret)   # Mosaic: whole tiles
        ):
            raise ValueError(
                f"latent cache {kv_cache.shape} with queries {q.shape}: want "
                f"one shared row of the queries' width R = {h} a token and "
                f"latent_v ({latent_v}) a multiple of {LANES} within it"
            )
        if k_scale is not None or scale is None:
            raise ValueError(
                "latent cache: no int8 form, and scale must be given"
            )
    elif (bk, hk) != (b, 2 * h):
        raise ValueError(
            f"cache shape {kv_cache.shape} does not match queries "
            f"{q.shape} (want "
            f"{'(P, N_kv, page, 2H)' if paged else '(B, N_kv, L, 2H)'} "
            f"with H = {h})"
        )
    if n % n_kv:
        raise ValueError(f"num_heads {n} not a multiple of kv heads {n_kv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    quantized = k_scale is not None
    group = n // n_kv
    scale = h**-0.5 if scale is None else scale
    block_k = auto_block_k(length) if block_k is None else block_k
    if length % block_k:
        raise ValueError(f"cache length {length} not divisible by block_k {block_k}")
    # A ring's logical blocks run past its table: nothing to clamp to.
    nk = jnp.iinfo(jnp.int32).max // block_k if ring else length // block_k
    # q rows tile in whole queries (qb of them → gq = qb·group rows) so a
    # tile's causal frontier is well-defined; single-token decode is one tile.
    # A tile that is not the whole chunk holds whole sublane tiles of rows
    # (a group of 6 takes 20 queries, 120 rows, not 21).
    qb = min(s, max(1, block_q // group))
    while qb < s and (qb * group) % 8 and qb > 1:
        qb -= 1
    gq = qb * group
    nq = pl.cdiv(s, qb)

    fold = kv_new is not None
    if fold:
        if s != 1:
            raise ValueError(f"folded cache write requires S = 1, got {s}")
        if quantized and (ks_new is None or vs_new is None):
            raise ValueError("int8 folded write needs ks_new and vs_new")

    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    # Block numbers stay inside the buffer (and the table): the index maps
    # take addresses from them even for a row at or past its capacity.
    # (window_band's arithmetic, written out: the program of a call
    # without a window stays as it was.)
    valid_blocks = jnp.clip((idx + s + block_k - 1) // block_k, 1, nk)
    if window is not None:
        kstart = jnp.maximum(0, (idx - (window - 1)) // block_k)
    else:
        kstart = jnp.zeros((b,), jnp.int32)
    kstart = jnp.minimum(kstart, valid_blocks - 1)
    # Disabled rows get a write offset of block_k — outside the kernel's
    # slot iota (0..block_k-1) — so the merge never matches: the emitter
    # form flushes the block back bit-identical (the write-back itself
    # still runs; it rewrites unchanged data), the loop form starts none.
    woff = idx % block_k
    if write_enable is not None:
        if not fold:
            raise ValueError("write_enable requires the folded write (kv_new)")
        woff = jnp.where(
            jnp.broadcast_to(write_enable, (b,)) != 0, woff, block_k
        )
    # How a block reaches VMEM is read off the operands: the loop form with
    # ``depth`` blocks in flight where Mosaic can slice the cache in HBM,
    # the emitter form (depth 0) otherwise.
    depth = pages_in_flight(
        kv_cache.shape, kv_cache.dtype, block_k, quantized=quantized,
        latent=latent,
    )
    # Either form works through each row's blocks from its first
    # (``kstart``) to its last valid one, rows in order; a row off
    # ``row_enable`` holds none.
    held = valid_blocks - kstart
    if row_enable is not None:
        enable = jnp.broadcast_to(row_enable, (b,)) != 0
        listed = enable
        if not depth:
            # A list with no step at all would be a grid of size 0: row 0
            # then stays on it (its output is zeroed below like any
            # disabled row's).
            listed = enable | (~jnp.any(enable) & (jnp.arange(b) == 0))
        held = jnp.where(listed, held, 0)
    if not depth:
        # The emitter's grid walks a WORK LIST, one step per (row, block)
        # held. Its length ``ends[-1]`` is a run-time scalar — the grid's
        # bound — so the step count follows the pages held, not ``B × nk``,
        # and no shape depends on the traffic. Only the running totals are
        # computed here (48 layers do it every decode step); each step
        # finds its row and block from them (``_step_of``).
        ends = jnp.sum(
            jnp.where(jnp.tri(b, dtype=bool), held[None], 0), axis=1
        )                              # a running total in one fusion
    sargs = jnp.stack(
        [kstart, valid_blocks, idx, jnp.minimum(idx // block_k, nk - 1), woff,
         held if depth else ends]
    ).astype(jnp.int32)
    if not depth:
        # Columns up to a power of two, the padding beyond every step
        # (_step_of).
        sargs = jnp.pad(
            sargs, ((0, 0), (0, (1 << (b - 1).bit_length()) - b)),
            constant_values=jnp.iinfo(jnp.int32).max,
        )

    # (B, S, N, H) → (B, N_kv, S·group, H): row r = query (r // group) for
    # in-group head (r % group); q head n belongs to kv head n // group
    # (matching models.attention.repeat_kv's jnp.repeat expansion).
    qr = (
        q.reshape(b, s, n_kv, group, h)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, n_kv, s * group, h)
    )

    prefetch_args = [sargs]
    if paged:
        prefetch_args.append(block_table.astype(jnp.int32))
    scratch = [
        pltpu.VMEM((n_kv, gq, h_out), jnp.float32),
        pltpu.VMEM((n_kv, gq, LANES), jnp.float32),
        pltpu.VMEM((n_kv, gq, LANES), jnp.float32),
    ]
    statics = dict(
        scale=scale, block_k=block_k, group=group, qb=qb, s=s, window=window,
        fold=fold, paged=paged,
    )
    out_shapes = [jax.ShapeDtypeStruct((b, n_kv, s * group, h_out), q.dtype)]
    aliases = {}
    if fold:
        # The updated cache is an output ALIASED to the cache input (alias
        # indices count the scalar-prefetch operands): only what a row
        # modified moves.
        out_shapes += [jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype)]
        kidx = len(prefetch_args) + 1    # operand index of kv_cache
        aliases[kidx] = 1                # kv_cache → output 1
    operands = [qr, kv_cache]

    if depth:
        # The loop form: rows are the grid, the cache stays in HBM and each
        # program fetches its row's blocks itself (``_loop_kernel``).
        def row_block(*shape):
            return pl.BlockSpec(
                (1, n_kv, *shape), lambda qi, bi, *pf: (bi, 0, qi, 0)
            )

        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [row_block(gq, h), in_hbm]
        out_specs = [row_block(gq, h_out)]
        scratch += [
            pltpu.VMEM((depth, n_kv, block_k, hk), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SMEM((6,), jnp.int32),
        ]
        if fold:
            in_specs += [
                pl.BlockSpec(
                    (1, n_kv, 1, hk), lambda qi, bi, *pf: (bi, 0, 0, 0)
                )
            ]
            operands += [kv_new]
            out_specs += [in_hbm]
            tile = 32 // kv_cache.dtype.itemsize
            scratch += [
                pltpu.VMEM((2, n_kv, tile, hk), kv_cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]
        kernel = functools.partial(
            _loop_kernel, depth=depth, ringed=ring, **statics
        )
        grid = (nq, b)
    else:
        in_specs, out_specs = _emitter_specs(
            b=b, n_kv=n_kv, gq=gq, h=h, h_out=h_out, hk=hk, block_k=block_k,
            qb=qb, s=s, paged=paged, quantized=quantized, fold=fold,
            ring=ring,
        )
        if quantized:
            operands += [k_scale, v_scale]
        if fold:
            operands += [kv_new]
            if quantized:
                operands += [ks_new, vs_new]
                out_shapes += [
                    jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                    jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
                ]
                aliases[kidx + 1] = 2        # k_scale → output 2
                aliases[kidx + 2] = 3        # v_scale → output 3
        kernel = functools.partial(
            _kernel, quantized=quantized, latent_v=latent_v, **statics
        )
        grid = (nq, ends[-1])

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch_args),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs if fold else out_specs[0],
            scratch_shapes=scratch,
        ),
        out_shape=out_shapes if fold else out_shapes[0],
        input_output_aliases=aliases,
        interpret=interpret,
    )
    name = (
        _LATENT_TRACE_NAME if latent
        else _TRACE_NAME if window is None else _WINDOW_TRACE_NAME
    )
    with jax.named_scope(name):
        result = call(*prefetch_args, *operands)

    out = result[0] if fold else result
    out = (
        out.reshape(b, n_kv, s, group, h_out)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, s, n, h_out)
    )
    if row_enable is not None:
        # A row off the list was never written: zeros, not what was there.
        out = jnp.where(enable[:, None, None, None], out, 0)
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
        q, kv_cache, index, *,
        k_scale=None, v_scale=None,
        kv_new=None, ks_new=None, vs_new=None,
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
        in_specs = [q_spec, kv_spec, idx_spec]
        args = [q, kv_cache, index]
        quantized = k_scale is not None
        fold = kv_new is not None
        keys = []
        cache_sc_spec = paged_sc_spec if paged else sc_spec
        if quantized:
            in_specs += [cache_sc_spec, cache_sc_spec]
            args += [k_scale, v_scale]
            keys += ["k_scale", "v_scale"]
        if fold:
            # The new-token chunk (and its scales) is PER-ROW even in paged
            # mode — only the pools lose their batch axis.
            in_specs += [to_spec((BATCH, HEADS, None, None))]
            args += [kv_new]
            keys += ["kv_new"]
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
            raise ValueError("write_enable requires the folded write (kv_new)")
        if paged:
            in_specs += [to_spec((BATCH, None))]
            args += [block_table]
            keys += ["block_table"]
        # Folded writes return the updated cache (+ scale) buffers alongside
        # the attention output; each keeps its input's sharding.
        out_specs = q_spec
        if fold:
            out_specs = (q_spec, kv_spec)
            if quantized:
                out_specs += (cache_sc_spec, cache_sc_spec)

        def body(q_, kv_, i_, *rest):
            return fn(q_, kv_, i_, **dict(zip(keys, rest)))

        # check_vma=False: pallas_call's out_shape carries no varying-axes
        # metadata, which the static replication checker requires.
        return jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
            check_vma=False,
        )(*args)

    return attn_fn
