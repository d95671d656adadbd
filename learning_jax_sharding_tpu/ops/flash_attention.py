"""Flash attention as a Pallas (Mosaic) TPU kernel, with custom VJP.

The reference materializes full (B, N, S, S) attention scores
(`/root/reference/case6_attention.py:125-127`), which caps sequence length at
a few thousand tokens (SURVEY.md §2.4 "Context parallelism: absent"). This
kernel is the TPU-native fix: scores are computed blockwise in VMEM with an
online softmax, so HBM traffic is O(S·H) instead of O(S²) and the S² work
streams through the MXU tile by tile — the idiomatic TPU equivalent of the
CUDA flash-attention kernel family.

Layout notes (see /opt/skills/guides/pallas_guide.md):
* grids iterate (batch·head, q-block, k-block) with the k-block dim innermost
  and sequential; running max/denominator/accumulator live in VMEM scratch
  that persists across the k-block sweep;
* running max/denominator are kept as (block_q, 128) lane-replicated tiles
  (TPU vectors want a 128 lane dim);
* all matmuls request fp32 accumulation via ``preferred_element_type``.

The backward follows the standard two-kernel flash scheme: the forward saves
only the per-row logsumexp; dq and dk/dv are computed by separate kernels that
recompute probabilities blockwise (q-major and k-major grids respectively).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/max NaN-free


def _row_ids(qi, block_q, group=1):
    """Query POSITION of each row in q-block ``qi``. Under GQA the q rows of
    one kv head interleave ``group`` query heads per position (row r ↔
    position r // group), so masks compare positions, not rows."""
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    return rows // group if group > 1 else rows


def _col_ids(ki, block_k):
    return ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)


def _run_condition(qi, ki, block_q, block_k, causal, window, group=1):
    """Does (q-block qi, k-block ki) contain any unmasked position?

    Causal skips strictly-future blocks; a sliding window additionally skips
    blocks entirely BEFORE every query's window (col ≤ pos - window). Block
    bounds are in row units; positions are rows // group (GQA row folding).
    """
    pos_max = ((qi + 1) * block_q - 1) // group
    pos_min = (qi * block_q) // group
    run = pos_max >= ki * block_k if causal else True
    if window is not None:
        run = jnp.logical_and(run, (ki + 1) * block_k > pos_min - window + 1)
    return run


def _interior(qi, ki, block_q, block_k, causal, window, group=1):
    """Is block (qi, ki) fully unmasked (no causal-diagonal or window-edge
    crossing)? Such blocks skip the mask's where pass entirely."""
    pos_min = (qi * block_q) // group
    pos_max = ((qi + 1) * block_q - 1) // group
    col_max = (ki + 1) * block_k - 1
    interior = pos_min >= col_max if causal else jnp.bool_(True)
    if window is not None:
        # every column inside every row's window: col_min > pos_max - window
        interior = jnp.logical_and(interior, ki * block_k > pos_max - window)
    return interior


def _block_mask(qi, ki, block_q, block_k, causal, window, group=1):
    """In-block mask (True = keep), or None when nothing masks here."""
    rows, cols = _row_ids(qi, block_q, group), _col_ids(ki, block_k)
    mask = None
    if causal:
        mask = rows >= cols
    if window is not None:
        wmask = cols > rows - window          # keep (row-window, row]
        mask = wmask if mask is None else jnp.logical_and(mask, wmask)
    return mask


# --- banded grids for sliding-window attention -----------------------------
#
# With a window, iterating ALL k blocks per q block only skips COMPUTE:
# Pallas still DMAs every (skipped) block from HBM, so cost stays O(S²) in
# bandwidth (measured: window=1024 at S=8192 ran only 1.5× faster than full
# causal). The banded grid makes the inner grid dimension the band itself —
# its width the exact block-count maximum over the (static) outer blocks —
# so both compute AND traffic are O(S·window). Band index maps clamp at the
# sequence edge; the kernel recomputes the true block index and masks
# out-of-range steps.


def _band_kstart(qi, block_q, block_k, window, group=1):
    """First k-block intersecting q-block ``qi``'s window band."""
    pos_min = (qi * block_q) // group
    return jnp.maximum(0, (pos_min - (window - 1)) // block_k)


def _band_qstart(ki, block_q, block_k, group=1):
    """First q-block attending into k-block ``ki`` (causal: pos ≥ col)."""
    return (ki * block_k * group) // block_q


def _fwd_band_width(
    nq: int, nk: int, block_q: int, block_k: int, window: int, group: int = 1
) -> int:
    """Exact max k-blocks any q-block's (causal) window band touches.

    Computed by enumerating the (static) q blocks rather than a worst-case
    alignment bound: the loose ``ceil + 1`` formula fetched a third, always-
    masked k/v block per q block in the aligned window==block case — ~50%
    extra band traffic, the very cost the banded grid removes.
    """
    width = 1
    for i in range(nq):
        pos_min = (i * block_q) // group
        pos_max = ((i + 1) * block_q - 1) // group
        s = max(0, (pos_min - (window - 1)) // block_k)
        e = min(nk - 1, pos_max // block_k)  # causal end
        width = max(width, e - s + 1)
    return width


def _dkv_band_width(
    nq: int, nk: int, block_q: int, block_k: int, window: int, group: int = 1
) -> int:
    """Exact max q-blocks attending into any k-block (causal window)."""
    width = 1
    for i in range(nk):
        s = (i * block_k * group) // block_q
        last_pos = i * block_k + block_k - 1 + window - 1
        e = min(nq - 1, (last_pos * group + group - 1) // block_q)
        width = max(width, e - s + 1)
    return width


def _band_k_map(block_q: int, block_k: int, window: int, nk: int, group: int = 1):
    """Clamped index map: grid step j → k-block within q-block i's band."""
    def k_map(b, i, j):
        return (
            b,
            jnp.minimum(
                _band_kstart(i, block_q, block_k, window, group) + j, nk - 1
            ),
            0,
        )
    return k_map


def _band_q_map(block_q: int, block_k: int, nq: int, group: int = 1):
    """Clamped index map: grid step j → q-block attending into k-block i."""
    def q_map(b, i, j):
        return (
            b,
            jnp.minimum(_band_qstart(i, block_q, block_k, group) + j, nq - 1),
            0,
        )
    return q_map


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref,  # (block_q, H), (block_k, H), (block_k, H)
    o_ref,                # (block_q, H)
    lse_ref,              # (block_q, 1) — per-row logsumexp (kept as a
                          # lane-size-1 3D array: Mosaic block tiling wants
                          # the sublane dim divisible by 8, which (1, block_q)
                          # 2D blocks violate on real TPU)
    acc_ref, m_ref, l_ref,  # VMEM scratch
    *, scale: float, causal: bool, window, block_q: int, block_k: int,
    nk: int, banded: bool, group: int,
):
    qi, kj = pl.program_id(1), pl.program_id(2)
    last_j = pl.num_programs(2) - 1

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    if banded:
        ki = _band_kstart(qi, block_q, block_k, window, group) + kj
        run = jnp.logical_and(
            ki < nk, _run_condition(qi, ki, block_q, block_k, causal, window, group)
        )
    else:
        ki = kj
        # With causal masking, blocks strictly in the future contribute nothing.
        run = _run_condition(qi, ki, block_q, block_k, causal, window, group)

    def _accumulate(s):
        m_prev = m_ref[:, :1]                      # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (block_q, block_k)
        correction = jnp.exp(m_prev - m_new)       # (block_q, 1)
        l_new = correction * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)

        v = v_ref[0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, H)
        acc_ref[:] = acc_ref[:] * correction + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def _scores():
        # Matmuls run at the INPUT dtype with fp32 accumulation: on bf16
        # operands the MXU runs at full rate; products accumulate in fp32
        # either way, and the scale folds in after the dot, exactly.
        return jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)

    mask = _block_mask(qi, ki, block_q, block_k, causal, window, group)
    if mask is None:
        @pl.when(run)
        def _step():
            _accumulate(_scores())
    else:
        # Only blocks crossing a mask edge (the causal diagonal / the window
        # boundary) pay the where pass — interior blocks of the band are
        # fully unmasked, and the extra VPU pass over the (block_q, block_k)
        # scores is measurable at long S where the kernel is VPU-bound.
        interior = _interior(qi, ki, block_q, block_k, causal, window, group)

        @pl.when(jnp.logical_and(run, interior))
        def _step_interior():
            _accumulate(_scores())

        @pl.when(jnp.logical_and(run, jnp.logical_not(interior)))
        def _step_edge():
            _accumulate(jnp.where(mask, _scores(), _NEG_INF))

    @pl.when(kj == last_j)
    def _finish():
        l = l_ref[:, :1]
        # Fully-masked rows (can't happen causally, but guard) → zero output.
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(safe_l)


def _fwd(q, k, v, *, scale, causal, window, block_q, block_k, interpret, group=1):
    bn, s_q, h = q.shape
    s_kv = k.shape[1]
    nq, nk = pl.cdiv(s_q, block_q), pl.cdiv(s_kv, block_k)

    banded = (
        window is not None
        and causal
        and _fwd_band_width(nq, nk, block_q, block_k, window, group) < nk
    )
    if banded:
        nkb = _fwd_band_width(nq, nk, block_q, block_k, window, group)
        k_map = _band_k_map(block_q, block_k, window, nk, group)
    else:
        nkb = nk

        def k_map(b, i, j):
            return (b, j, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, banded=banded, group=group,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bn, nq, nkb),
        in_specs=[
            pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, h), k_map),
            pl.BlockSpec((1, block_k, h), k_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, s_q, h), q.dtype),
            jax.ShapeDtypeStruct((bn, s_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, h), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, causal: bool, window, block_q: int, block_k: int,
    nq: int, banded: bool, group: int,
):
    """k-major sweep: for one k/v block, accumulate dk/dv over the q blocks
    that attend into it (all of them, or the window band)."""
    ki, qj = pl.program_id(1), pl.program_id(2)
    last_j = pl.num_programs(2) - 1

    @pl.when(qj == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if banded:
        qi = _band_qstart(ki, block_q, block_k, group) + qj
        run = jnp.logical_and(
            qi < nq, _run_condition(qi, ki, block_q, block_k, causal, window, group)
        )
    else:
        qi = qj
        run = _run_condition(qi, ki, block_q, block_k, causal, window, group)

    @pl.when(run)
    def _step():
        # Native-dtype matmul operands, fp32 accumulation (see _fwd_kernel).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                            # (block_q, 1)
        delta = delta_ref[0]                        # (block_q, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _block_mask(qi, ki, block_q, block_k, causal, window, group)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                        # (block_q, block_k)

        # dv += pᵀ · do
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = do · vᵀ ; ds = p ∘ (dp − delta) ; dk += dsᵀ · q
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qj == last_j)
    def _finish():
        # ds·q accumulated UNSCALED (native-dtype q); ds/dk = scale·q.
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_acc,
    *, scale: float, causal: bool, window, block_q: int, block_k: int,
    nk: int, banded: bool, group: int,
):
    """q-major sweep: for one q block, accumulate dq over its k blocks
    (all of them, or the window band)."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    last_j = pl.num_programs(2) - 1

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if banded:
        ki = _band_kstart(qi, block_q, block_k, window, group) + kj
        run = jnp.logical_and(
            ki < nk, _run_condition(qi, ki, block_q, block_k, causal, window, group)
        )
    else:
        ki = kj
        run = _run_condition(qi, ki, block_q, block_k, causal, window, group)

    @pl.when(run)
    def _step():
        # Native-dtype matmul operands, fp32 accumulation (see _fwd_kernel).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                            # (block_q, 1)
        delta = delta_ref[0]                        # (block_q, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _block_mask(qi, ki, block_q, block_k, causal, window, group)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        # dq += ds · k, then scaled at the end (d(q·scale)/dq = scale).
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == last_j)
    def _finish():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd(scale, causal, window, block_q, block_k, interpret, group, residuals, do):
    # ``delta_i = Σ_h do_ih · o_ih`` comes in with the residuals: a tiny
    # elementwise reduction the caller makes wherever ``out`` lives.
    q, k, v, lse, delta = residuals
    bn, s_q, h = q.shape
    s_kv = k.shape[1]
    nq, nk = pl.cdiv(s_q, block_q), pl.cdiv(s_kv, block_k)

    # Banded grids mirror the forward (see the banded-grid comment block):
    # dkv sweeps only the q blocks attending into its k block, dq only the
    # k blocks inside its q block's window band.
    dkv_banded = (
        window is not None
        and causal
        and _dkv_band_width(nq, nk, block_q, block_k, window, group) < nq
    )
    if dkv_banded:
        nqb = _dkv_band_width(nq, nk, block_q, block_k, window, group)
        q_map = _band_q_map(block_q, block_k, nq, group)
    else:
        nqb = nq

        def q_map(b, i, j):
            return (b, j, 0)

    common_specs = [
        pl.BlockSpec((1, block_q, h), q_map),                          # q by inner
        pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, i, 0)),      # k by outer
        pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, i, 0)),      # v by outer
        pl.BlockSpec((1, block_q, h), q_map),                          # do
        pl.BlockSpec((1, block_q, 1), q_map),                          # lse
        pl.BlockSpec((1, block_q, 1), q_map),                          # delta
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, nq=nq, banded=dkv_banded,
            group=group,
        ),
        grid=(bn, nk, nqb),
        in_specs=common_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, h), jnp.float32),
            pltpu.VMEM((block_k, h), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dq_banded = (
        window is not None
        and causal
        and _fwd_band_width(nq, nk, block_q, block_k, window, group) < nk
    )
    if dq_banded:
        nkb = _fwd_band_width(nq, nk, block_q, block_k, window, group)
        k_map = _band_k_map(block_q, block_k, window, nk, group)
    else:
        nkb = nk

        def k_map(b, i, j):
            return (b, j, 0)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, nk=nk, banded=dq_banded,
            group=group,
        ),
        grid=(bn, nq, nkb),
        in_specs=[
            pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),      # q by outer
            pl.BlockSpec((1, block_k, h), k_map),                          # k by inner
            pl.BlockSpec((1, block_k, h), k_map),                          # v by inner
            pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),      # do
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),      # lse
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),      # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, h), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def _auto_block(s: int, cap: int = 1024) -> int:
    """Largest power of two ≤ ``cap`` that divides ``s``.

    If ``s`` has no power-of-two factor ≥ 8 (TPU sublane tiling wants
    sublane-dim multiples of 8), a sliver grid would be pathological — fall
    back to one full-sequence block instead, or reject sequences too long
    for a single VMEM tile (mirroring the explicit-block divisibility error).
    """
    blk = 1
    while blk < cap and s % (blk * 2) == 0:
        blk *= 2
    if blk < 8:
        if s > cap:
            raise ValueError(
                f"sequence length {s} has no usable power-of-two block "
                f"factor; pad the sequence or pass block_q/block_k explicitly"
            )
        blk = s
    return blk


def _q_rows(x, n_kv):
    """(B, S, N, H) → (B·N_kv, S·group, H): each (batch, kv-head) slice is
    independent; under GQA the group's query heads FOLD INTO THE ROW DIM
    (row r = position r // group), so k/v enter at their native N_kv heads
    — no repeat_kv materialization, and dk/dv reduce over the group for
    free in the kernel's q-row sweep. MHA is the group == 1 case."""
    b, s, n, h = x.shape
    return (
        x.reshape(b, s, n_kv, n // n_kv, h)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b * n_kv, s * (n // n_kv), h)
    )


def _q_unrows(x, b, n):
    bn_kv, rows, h = x.shape
    n_kv = bn_kv // b
    group = n // n_kv
    return (
        x.reshape(b, n_kv, rows // group, group, h)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, rows // group, n, h)
    )


def _kv_rows(x):
    b, s, n, h = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * n, s, h)


def _kv_unrows(x, b):
    bn, s, h = x.shape
    return x.reshape(b, bn // b, s, h).transpose(0, 2, 1, 3)


def _attend(q, k, v, scale, causal, window, block_q, block_k, interpret):
    """The forward kernel over ``(B, S, N, H)`` operands: ``out`` in their
    layout, ``lse`` and the operands in the kernels' row layout."""
    b, _, n, _ = q.shape
    n_kv = k.shape[2]
    rows = _q_rows(q, n_kv), _kv_rows(k), _kv_rows(v)
    out, lse = _fwd(
        *rows, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
        group=n // n_kv,
    )
    return _q_unrows(out, b, n), lse, rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, scale, causal, window, block_q, block_k,
           bwd_block_q, bwd_block_k, interpret):
    out, _, _ = _attend(
        q, k, v, scale, causal, window, block_q, block_k, interpret
    )
    return out


def _flash_fwd(q, k, v, scale, causal, window, block_q, block_k,
               bwd_block_q, bwd_block_k, interpret):
    out, lse, (qr, kr, vr) = _attend(
        q, k, v, scale, causal, window, block_q, block_k, interpret
    )
    # The backward reads ``out`` only for ``delta``, a sum over H it can take
    # in any layout, so the residual is the (B, S, N·H) form the output
    # projection multiplies anyway: whole lane tiles, where the kernel's own
    # (rows, 64) is padded to 128 lanes in HBM, twice its bytes in every
    # block that keeps it. ``lse`` is kept without its trailing 1 for the
    # same reason (128 x its bytes). Both carry names
    # (utils.memory.REMAT_GROUPS): under a ``jax.checkpoint`` whose policy
    # saves them the saved values ARE the backward's residuals and the
    # forward kernel is dead code in the recomputation. The names sit in the
    # VJP's forward rule alone: the primal (serving) never sees them.
    b, s_q, n, h = out.shape
    kept = checkpoint_name(out.reshape(b, s_q, n * h), "flash_out")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return kept.reshape(out.shape), (qr, kr, vr, kept, lse)


def _flash_bwd(
    scale, causal, window, block_q, block_k, bwd_block_q, bwd_block_k,
    interpret, residuals, do,
):
    qr, kr, vr, out, lse = residuals
    b, _, n, _ = do.shape
    n_kv = kr.shape[0] // b
    delta = jnp.sum(
        do.astype(jnp.float32) * out.reshape(do.shape).astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    # The backward's optimal tiles differ from the forward's (it holds
    # more live tensors per block: do, lse, delta, two accumulators) —
    # tunable independently; None inherits the forward tiles.
    dq, dk, dv = _bwd(
        scale, causal, window,
        block_q if bwd_block_q is None else bwd_block_q,
        block_k if bwd_block_k is None else bwd_block_k,
        interpret, n // n_kv,
        (qr, kr, vr, lse[..., None], _q_rows(delta, n_kv)),
        _q_rows(do, n_kv),
    )
    return _q_unrows(dq, b, n), _kv_unrows(dk, b), _kv_unrows(dv, b)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    mask: jax.Array | None = None,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise-softmax attention over ``(B, S, N, H)`` inputs.

    ``window``: sliding-window (local) attention — each query attends only
    to the last ``window`` positions including itself (Mistral-style SWA).
    Requires ``causal=True``. Blocks wholly outside the band are SKIPPED,
    so compute is O(S·window) instead of O(S²): long-context cost grows
    linearly in S.

    Drop-in for :func:`ops.attention.dot_product_attention` (same signature
    shape-wise) but with O(S·H) memory. Differentiable via the flash backward
    kernels. ``mask`` is accepted for API compatibility but only the causal
    structural mask is supported (pass ``causal=True``); arbitrary masks
    require the dense op.

    Args:
        block_q / block_k: VMEM tile sizes; None (default) auto-selects the
            largest power of two ≤1024 dividing the sequence length. Big tiles
            matter: measured on the v5e at (8, 1024, 12, 64), 1024² blocks run
            the fwd+bwd 2.9× faster than 128² (22 vs 7.6 TFLOP/s) because each
            k-step's matmuls are MXU-sized instead of sliver-sized; 1024×1024
            fp32 scores are 4 MB, comfortably inside the ~16 MB/core VMEM
            alongside the q/k/v tiles.
        bwd_block_q / bwd_block_k: BACKWARD tile sizes; None inherits the
            forward's. The backward holds more live VMEM per block (do,
            lse, delta, two fp32 accumulators), so its optimum can sit at
            smaller tiles than the forward's — tune on-chip per shape.
        interpret: run the Pallas interpreter (CPU testing).
    """
    if mask is not None:
        raise NotImplementedError(
            "flash_attention supports only the structural causal mask "
            "(causal=True); use dot_product_attention for arbitrary masks"
        )
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window attention) requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    b, s_q, n, h = q.shape
    s_kv, n_kv = k.shape[1], k.shape[2]
    if n % n_kv:
        raise ValueError(f"num_heads {n} not a multiple of kv heads {n_kv}")
    group = n // n_kv
    if group > 1 and s_q != s_kv:
        raise ValueError("GQA flash requires matching q/kv sequence lengths")
    rows_q = s_q * group
    if block_q is None:
        block_q = _auto_block(rows_q)
    if block_k is None:
        block_k = _auto_block(s_kv)
    if rows_q % block_q or s_kv % block_k:
        block_q = min(block_q, rows_q)
        block_k = min(block_k, s_kv)
        if rows_q % block_q or s_kv % block_k:
            raise ValueError(
                f"sequence lengths ({s_q}, {s_kv}) must be divisible by "
                f"block sizes ({block_q}, {block_k})"
            )
    scale = h**-0.5 if scale is None else scale

    for bwd_blk, rows in ((bwd_block_q, rows_q), (bwd_block_k, s_kv)):
        if bwd_blk is not None and rows % bwd_blk:
            raise ValueError(
                f"sequence rows ({rows}) must be divisible by the backward "
                f"block size ({bwd_blk})"
            )
    return _flash(
        q, k, v, scale, causal, window,
        block_q, block_k, bwd_block_q, bwd_block_k, interpret,
    )


def make_flash_attn_fn(mesh=None, rules=None, **kwargs) -> Any:
    """An ``attn_fn`` for :class:`models.attention.MultiHeadAttention`:
    ``attn_fn(q, k, v, *, causal)`` routed to the flash kernel.

    With ``mesh``/``rules``, the kernel runs under ``shard_map`` with batch
    and heads partitioned per the rules (GSPMD cannot partition a custom
    kernel by itself). The sequence stays unsharded inside the kernel — flash
    needs every key/value; sequence-sharded attention is ring attention's job
    (:mod:`ops.ring_attention`).
    """
    in_spec = None
    if mesh is not None:
        if rules is None:
            raise ValueError("rules are required when a mesh is given")
        from flax.linen import partitioning as nn_partitioning
        from jax.sharding import PartitionSpec

        from learning_jax_sharding_tpu.parallel.logical import BATCH, HEADS

        axes = nn_partitioning.logical_to_mesh_axes(
            (BATCH, None, HEADS, None), tuple(rules)
        )
        in_spec = PartitionSpec(*axes)
        heads_entry = axes[2]
        if heads_entry is None:
            heads_axis_size = 1
        elif isinstance(heads_entry, (tuple, list)):
            heads_axis_size = 1
            for a in heads_entry:
                heads_axis_size *= mesh.shape[a]
        else:
            heads_axis_size = mesh.shape[heads_entry]

    def attn_fn(q, k, v, *, causal: bool = False):
        fn = functools.partial(flash_attention, causal=causal, **kwargs)
        if mesh is None:
            return fn(q, k, v)
        if k.shape[2] != q.shape[2] and k.shape[2] % heads_axis_size:
            # GQA-native k/v whose kv-head count the heads mesh axis cannot
            # divide: expand to full heads so the shard_map spec holds (the
            # pre-GQA-native behavior; costs the repeat materialization).
            reps = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
        # check_vma=False: pallas_call's out_shape carries no varying-axes
        # metadata, which the static replication checker requires.
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(in_spec, in_spec, in_spec), out_specs=in_spec,
            check_vma=False,
        )(q, k, v)

    # The kernel reads grouped k/v at their native head count (row folding);
    # the attention module checks this flag to skip repeat_kv entirely.
    attn_fn.supports_gqa = True
    return attn_fn
