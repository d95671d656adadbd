"""Dropless routed-expert compute: each TOUCHED expert's matrices (three in
the gated form, two in the ungated) are read once, no untouched expert's at
all.

The capacity scheme of ``models/moe.py::assign_slots`` gives every expert
``C`` slots and multiplies by one-hot ``(T, E, C)`` tensors: every expert's
weights are read every step, and tokens over capacity are dropped. At
decode shapes (32 rows, top-8 of 256 experts of 9.4 MB each) that reads
2.4 GB a layer where the tokens touch about 1.5 GB of it, and a gather per
assignment would copy 9.4 MB 256 times. Here:

* the ``T x K`` assignments are SORTED by expert (:func:`plan`); each
  expert's run of rows is padded to whole row tiles of ``tm`` rows, so a
  tile belongs to exactly one expert and the tile list is the work list;
* ONE Pallas call (:func:`moe_experts`, traced as ``moe.experts``) walks
  that list: grid step ``i`` multiplies tile ``i`` by the gate, up and down
  matrices of ``tile_expert[i]`` (a scalar-prefetched array the index maps
  read). The grid's bound is the number of tiles in use, a run-time scalar:
  no shape depends on the routing, and an expert nobody picked costs no
  step and no byte. Consecutive tiles of one expert keep its block index,
  so its weights are fetched once;
* invalid tokens (a refill chunk's padding, a frozen decode row) carry the
  sentinel expert ``E``: they sort behind everything, get no tile, and
  come back as zeros;
* a chip that HOLDS a range of the experts (``held=(first, count)``: an
  expert-parallel group's share) has weights for those alone: a pick
  outside the range carries the sentinel too, so it is computed nowhere
  here, reads nothing and is counted in neither assignments nor reads.

:func:`routed_experts` is the whole layer-side call (plan, gather, kernel
or plain ``ragged_dot``, combine); both backends share the plan, so the
counts the engine reports (assignments, experts read) are the kernel's own
work list whichever runs.

**Training.** The gated form differentiates under either backend. The
Pallas path carries a VJP (:func:`_tiled_experts`) whose forward is the
serving call above and whose backward is two more walks of the SAME tile
list: :func:`moe_experts_dx` (traced ``moe.experts_dx``: the gate and up
recomputed from the tile, ``dH = dY W_d^T``, ``dX = dG W_g^T + dU W_u^T``;
it also writes ``dG``, ``dU`` and ``H`` for the next call) and
:func:`moe_experts_dw` (``moe.experts_dw``: ``dW[e] = X_e^T d._e``
accumulated in float32 over an expert's consecutive tiles, a block of the
expert width at a time; an expert nobody was routed to gets zeros). The
gathers around them are transposed as gathers (``src`` and ``pos`` invert
each other), never as scatters; the combine weights' cotangent comes from
plain autodiff of the combine, so the router trains. A pick outside
``held`` is on no tile: it reads nothing and sends no gradient. The
``ragged`` backend is differentiated by XLA; on a TPU ``auto`` never takes
it. Measured on the chip at ``lfm2-8b-a1b.pretrain_8k``'s shapes (16,384
tokens x top 4 of 32, 8 held, 2048 x 1792, float32 weights; PERF.md, PR
35): forward + backward 24.3 ms through these calls against 57.8 ms through
XLA's transposes of ``ragged_dot``, whose ``dX`` also read 9.6 % off a
float32 dense loop where this one reads 0.45 %. The ungated (relu^2) kernel
has no VJP: that form is served, not trained, so far.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TRACE_NAME = "moe.experts"
# One expert's three matrices are double-buffered whole (2 x 9.4 MB at
# d 2048, f 768 in bf16) beside the row tiles: past Mosaic's default 16 MiB.
_VMEM_LIMIT = 64 * 1024 * 1024
# The backward calls: the same three matrices beside four row tiles out
# (dx), or three float32 blocks of an expert's gradient (dw, _dw_block).
_VMEM_LIMIT_BWD = 100 * 1024 * 1024
_DW_VMEM = 48 * 1024 * 1024


def tile_rows(assignments: int, num_experts: int) -> int:
    """Rows a tile: the power of two at or above the mean run of an expert
    when every token is valid, within [16, 128] (16 = one bf16 sublane
    tile: decode's 1-2 rows an expert; 128 = an MXU pass: a refill chunk)."""
    mean = -(-assignments // num_experts)
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def plan(expert: jax.Array, num_experts: int, tm: int):
    """The sorted, tile-padded layout of ``expert`` ``(A,)`` int32, one
    entry an assignment, ``num_experts`` marking an invalid one.

    Returns ``(src, pos, tile_expert, num_tiles, counts, order)``: ``src``
    ``(M,)`` the assignment each padded row holds (``A`` = none: a zero
    row), ``pos`` ``(A,)`` each assignment's row (``M`` = none),
    ``tile_expert`` ``(M // tm,)`` each tile's expert, ``num_tiles`` the
    tiles in use (scalar), ``counts`` ``(E,)`` assignments an expert,
    ``order`` ``(A,)`` the assignments sorted by expert (the unpadded
    runs)."""
    a, e = expert.shape[0], num_experts
    m = (a + min(a, e) * (tm - 1) + tm - 1) // tm * tm
    order = jnp.argsort(expert, stable=True)
    sorted_e = expert[order]
    counts = jnp.zeros((e + 1,), jnp.int32).at[expert].add(1)[:e]
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    run_start = jnp.cumsum(counts) - counts
    safe_e = jnp.minimum(sorted_e, e - 1)
    row = (tile_end - tiles)[safe_e] * tm + jnp.arange(a) - run_start[safe_e]
    row = jnp.where(sorted_e < e, row, m).astype(jnp.int32)
    src = jnp.full((m + 1,), a, jnp.int32).at[row].set(order.astype(jnp.int32))[:m]
    pos = jnp.zeros((a,), jnp.int32).at[order].set(row)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(m // tm), side="right"), e - 1
    ).astype(jnp.int32)
    return src, pos, tile_expert, tile_end[-1].astype(jnp.int32), counts, order


def _kernel(te_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    del te_ref                       # the index maps' operand
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    o_ref[...] = jnp.dot(
        h, wd_ref[0], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def _kernel_ungated(te_ref, x_ref, wu_ref, wd_ref, o_ref):
    del te_ref
    x = x_ref[...]
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = jnp.square(jnp.maximum(u, 0.0)).astype(x.dtype)
    o_ref[...] = jnp.dot(
        h, wd_ref[0], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts(
    x_rows, tile_expert, num_tiles, w_gate, w_up, w_down, *, tm: int,
    interpret: bool = False,
):
    """``silu(x W_g[e]) * (x W_u[e])) W_d[e]`` for every row tile of
    ``x_rows`` ``(M, d)`` with ``e = tile_expert[tile]``, tiles
    ``[0, num_tiles)`` only: the rows of the others come back unwritten.
    Weights ``(E, d, f)``, ``(E, d, f)``, ``(E, f, d)``; ``w_gate`` None:
    the ungated form ``relu(x W_u[e])^2 W_d[e]``."""
    m, d = x_rows.shape
    e, _, f = w_up.shape
    if m % tm or tile_expert.shape != (m // tm,):
        raise ValueError(
            f"{m} rows in tiles of {tm} need tile_expert ({m // tm},), got "
            f"{tile_expert.shape}"
        )
    rows = pl.BlockSpec((tm, d), lambda i, te: (i, 0))
    into = pl.BlockSpec((1, d, f), lambda i, te: (te[i], 0, 0))
    out_of = pl.BlockSpec((1, f, d), lambda i, te: (te[i], 0, 0))
    gated = w_gate is not None
    call = pl.pallas_call(
        _kernel if gated else _kernel_ungated,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[rows, *([into] * (1 + gated)), out_of],
            out_specs=rows,
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    weights = (w_gate, w_up, w_down) if gated else (w_up, w_down)
    with jax.named_scope(_TRACE_NAME):
        return call(tile_expert, x_rows, *weights)


def _dx_kernel(
    te_ref, x_ref, dy_ref, wg_ref, wu_ref, wd_ref, dx_ref, dg_ref, du_ref, h_ref
):
    del te_ref
    nt = (((1,), (1,)), ((), ()))                   # a . b^T
    x, dy = x_ref[...], dy_ref[...]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    sig = jax.nn.sigmoid(g)
    act = g * sig
    h_ref[...] = (act * u).astype(h_ref.dtype)
    dh = jax.lax.dot_general(dy, wd_ref[0], nt, preferred_element_type=jnp.float32)
    dg = (dh * u * (sig * (1.0 + g * (1.0 - sig)))).astype(x.dtype)
    du = (dh * act).astype(x.dtype)
    dg_ref[...], du_ref[...] = dg, du
    dx_ref[...] = (
        jax.lax.dot_general(dg, wg_ref[0], nt, preferred_element_type=jnp.float32)
        + jax.lax.dot_general(du, wu_ref[0], nt, preferred_element_type=jnp.float32)
    ).astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts_dx(
    x_rows, dy_rows, tile_expert, num_tiles, w_gate, w_up, w_down, *, tm: int,
    interpret: bool = False,
):
    """The row side of :func:`moe_experts`' backward, tile by tile:
    ``(dx_rows (M, d), dg, du, h (M, f))`` for the cotangent ``dy_rows`` of
    its output, all in ``x_rows.dtype``; rows of tiles past ``num_tiles``
    come back unwritten."""
    m, d = x_rows.shape
    f = w_up.shape[2]
    rows = pl.BlockSpec((tm, d), lambda i, te: (i, 0))
    wide = pl.BlockSpec((tm, f), lambda i, te: (i, 0))
    into = pl.BlockSpec((1, d, f), lambda i, te: (te[i], 0, 0))
    out_of = pl.BlockSpec((1, f, d), lambda i, te: (te[i], 0, 0))
    call = pl.pallas_call(
        _dx_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[rows, rows, into, into, out_of],
            out_specs=[rows, wide, wide, wide],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((m, d), x_rows.dtype),
            *([jax.ShapeDtypeStruct((m, f), x_rows.dtype)] * 3),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BWD),
        interpret=interpret,
    )
    with jax.named_scope(_TRACE_NAME + "_dx"):
        return call(tile_expert, x_rows, dy_rows, w_gate, w_up, w_down)


def _dw_kernel(
    te_ref, x_ref, dy_ref, dg_ref, du_ref, h_ref, dwg_ref, dwu_ref, dwd_ref
):
    i = pl.program_id(1)
    tn = (((0,), (0,)), ((), ()))                   # a^T . b

    @pl.when((i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)]))
    def _first_tile_of_an_expert():
        dwg_ref[...] = jnp.zeros_like(dwg_ref)
        dwu_ref[...] = jnp.zeros_like(dwu_ref)
        dwd_ref[...] = jnp.zeros_like(dwd_ref)

    x = x_ref[...]
    dwg_ref[0] += jax.lax.dot_general(
        x, dg_ref[...], tn, preferred_element_type=jnp.float32
    )
    dwu_ref[0] += jax.lax.dot_general(
        x, du_ref[...], tn, preferred_element_type=jnp.float32
    )
    dwd_ref[0] += jax.lax.dot_general(
        h_ref[...], dy_ref[...], tn, preferred_element_type=jnp.float32
    )


def _dw_block(d: int, f: int) -> int:
    """Columns of the expert width one :func:`moe_experts_dw` pass
    accumulates: the widest whole-lane-tile divisor of ``f`` whose three
    float32 blocks, double-buffered, stay under ``_DW_VMEM``; ``f`` itself
    when it has none (a test's narrow experts)."""
    fits = [
        fb for fb in range(128, f + 1, 128)
        if f % fb == 0 and 6 * d * fb * 4 <= _DW_VMEM
    ]
    return max(fits) if fits else f


@functools.partial(jax.jit, static_argnames=("tm", "num_experts", "interpret"))
def moe_experts_dw(
    x_rows, dy_rows, dg, du, h, tile_expert, num_tiles, *, tm: int,
    num_experts: int, interpret: bool = False,
):
    """The weight side of :func:`moe_experts`' backward: ``(dW_gate, dW_up
    (E, d, f), dW_down (E, f, d))`` in float32, ``dW_gate[e] = X_e^T dG_e``
    and so on, summed over expert ``e``'s consecutive tiles. The grid is
    ``(f // block, num_tiles)``: a block of the expert width stays in VMEM
    while its expert's tiles go by. An expert with no tile is NOT written
    (the caller zeroes it)."""
    m, d = x_rows.shape
    f = h.shape[1]
    fb = _dw_block(d, f)
    rows = pl.BlockSpec((tm, d), lambda j, i, te: (i, 0))
    wide = pl.BlockSpec((tm, fb), lambda j, i, te: (i, j))
    into = pl.BlockSpec((1, d, fb), lambda j, i, te: (te[i], 0, j))
    out_of = pl.BlockSpec((1, fb, d), lambda j, i, te: (te[i], j, 0))
    call = pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(f // fb, num_tiles),
            in_specs=[rows, rows, wide, wide, wide],
            out_specs=[into, into, out_of],
        ),
        out_shape=[
            *([jax.ShapeDtypeStruct((num_experts, d, f), jnp.float32)] * 2),
            jax.ShapeDtypeStruct((num_experts, f, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BWD,
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )
    with jax.named_scope(_TRACE_NAME + "_dw"):
        return call(tile_expert, x_rows, dy_rows, dg, du, h)


def _gather_rows(x, src, k: int):
    """``x`` ``(T, d)`` laid out on the plan's padded rows: row ``r``
    holds the token of assignment ``src[r]``, or zeros where it holds none."""
    t, d = x.shape
    x_ext = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    return x_ext[jnp.minimum(src // k, t)]          # A // k == t: the zero row


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _tiled_experts(
    tm, k, interpret, x, w_gate, w_up, w_down, src, pos, tile_expert,
    num_tiles, counts,
):
    """``y`` ``(A, d)``: each assignment's expert output, through the
    Pallas call (an unrouted assignment's row holds anything). The weights
    may be wider than ``x.dtype`` (a trainer's float32 parameters): the
    kernel reads them in ``x.dtype``."""
    del counts
    w_gate, w_up, w_down = (w.astype(x.dtype) for w in (w_gate, w_up, w_down))
    y_rows = moe_experts(
        _gather_rows(x, src, k), tile_expert, num_tiles, w_gate, w_up, w_down,
        tm=tm, interpret=interpret,
    )
    return y_rows[jnp.minimum(pos, y_rows.shape[0] - 1)]


def _tiled_experts_fwd(tm, k, interpret, x, w_gate, w_up, w_down, *layout):
    y = _tiled_experts(tm, k, interpret, x, w_gate, w_up, w_down, *layout)
    return y, (x, w_gate, w_up, w_down, *layout)


def _tiled_experts_bwd(tm, k, interpret, saved, dy):
    x, w_gate, w_up, w_down, src, pos, tile_expert, num_tiles, counts = saved
    (t, d), a = x.shape, dy.shape[0]
    wg, wu, wd = (w.astype(x.dtype) for w in (w_gate, w_up, w_down))
    x_rows = _gather_rows(x, src, k)
    # The transpose of ``y_rows[pos]`` as a gather: row r takes the
    # cotangent of the assignment it holds.
    dy_ext = jnp.concatenate([dy.astype(x.dtype), jnp.zeros((1, d), x.dtype)])
    dy_rows = dy_ext[src]
    dx_rows, dg, du, h = moe_experts_dx(
        x_rows, dy_rows, tile_expert, num_tiles, wg, wu, wd, tm=tm,
        interpret=interpret,
    )
    grads = moe_experts_dw(
        x_rows, dy_rows, dg, du, h, tile_expert, num_tiles, tm=tm,
        num_experts=w_up.shape[0], interpret=interpret,
    )
    touched = (counts > 0)[:, None, None]
    d_gate, d_up, d_down = (
        jnp.where(touched, g, 0).astype(w.dtype)
        for g, w in zip(grads, (w_gate, w_up, w_down))
    )
    # ... and of ``x_ext[src // k]``: a token sums its routed assignments'
    # rows (an unrouted one has pos == M and no row).
    m = dx_rows.shape[0]
    mine = dx_rows[jnp.minimum(pos, m - 1)].astype(jnp.float32)
    mine = jnp.where((pos < m)[:, None], mine, 0.0).reshape(t, k, d)
    dx = jnp.sum(mine, axis=1).astype(x.dtype)
    return dx, d_gate, d_up, d_down, None, None, None, None, None


_tiled_experts.defvjp(_tiled_experts_fwd, _tiled_experts_bwd)


def resolve_backend(mode: str) -> str:
    """``"auto"``: the Pallas call on a TPU, sorted ``ragged_dot``
    elsewhere (off the TPU the kernel runs under the interpreter)."""
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ragged"
    if mode not in ("pallas", "ragged"):
        raise ValueError(
            f"unknown moe_experts {mode!r}: 'auto', 'pallas' or 'ragged'"
        )
    return mode


def routed_experts(
    x, idx, weights, w_gate, w_up, w_down, *, valid=None, backend="auto",
    interpret: bool | None = None, first: int | None = None,
):
    """``out[t] = sum_k weights[t, k] * E_{idx[t, k]}(x[t])`` for ``x``
    ``(T, d)``, ``idx`` / ``weights`` ``(T, K)``; a token with ``valid``
    false is routed nowhere and comes back zero. No token is dropped.
    With ``first`` the weights are those of experts ``[first, first + E)``
    of a wider router: a pick outside that range adds nothing here (its
    expert lives on another chip).
    ``w_gate`` None: ungated experts, ``relu(x W_up)^2 W_down``.

    Returns ``(out (T, d) in x.dtype, stats (3,) int32)``: assignments
    routed, experts with at least one token (whose weights were read), and
    1 if anything was routed at all (a layer-step)."""
    t, d = x.shape
    k, e = idx.shape[1], w_up.shape[0]
    a = t * k
    backend = resolve_backend(backend)
    expert = idx.reshape(a).astype(jnp.int32)
    if first is not None:
        expert = expert - first
        expert = jnp.where((expert >= 0) & (expert < e), expert, e)
    if valid is not None:
        expert = jnp.where(jnp.repeat(valid, k), expert, e)
    tm = tile_rows(a, e)
    src, pos, tile_expert, num_tiles, counts, order = plan(expert, e, tm)
    if backend == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if w_gate is None:                              # no VJP: served only
            y_rows = moe_experts(
                _gather_rows(x, src, k), tile_expert, num_tiles, None, w_up,
                w_down, tm=tm, interpret=interpret,
            )
            y = y_rows[jnp.minimum(pos, y_rows.shape[0] - 1)]
        else:
            y = _tiled_experts(
                tm, k, interpret, x, w_gate, w_up, w_down, src, pos,
                tile_expert, num_tiles, counts,
            )
    else:
        # The same sorted runs, unpadded, through XLA's grouped matmul.
        xs = x[order // k]
        dot = functools.partial(jax.lax.ragged_dot, group_sizes=counts)
        u = dot(xs, w_up.astype(x.dtype), preferred_element_type=jnp.float32)
        if w_gate is None:
            h = jnp.square(jnp.maximum(u, 0.0)).astype(x.dtype)
        else:
            g = dot(xs, w_gate.astype(x.dtype), preferred_element_type=jnp.float32)
            h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        ys = dot(h, w_down.astype(x.dtype), preferred_element_type=jnp.float32)
        y = jnp.zeros((a, d), x.dtype).at[order].set(ys.astype(x.dtype))
    routed = (expert < e).reshape(t, k)
    # where, not a product with a zero weight: an unwritten row may hold
    # anything, NaN included.
    y = jnp.where(routed[..., None], y.reshape(t, k, d), 0)
    out = jnp.einsum(
        "tkd,tk->td", y.astype(jnp.float32), weights.astype(jnp.float32)
    ).astype(x.dtype)
    n = jnp.sum(counts)
    stats = jnp.stack(
        [n, jnp.sum(counts > 0), (n > 0).astype(jnp.int32)]
    ).astype(jnp.int32)
    return out, stats
