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

Inference and forward only: no VJP for the kernel (training uses the
``ragged_dot`` backend, which XLA differentiates).
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
        x_ext = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
        x_rows = x_ext[jnp.minimum(src // k, t)]       # A // k == t: the zero row
        y_rows = moe_experts(
            x_rows, tile_expert, num_tiles, w_gate, w_up, w_down, tm=tm,
            interpret=interpret,
        )
        y = y_rows[jnp.minimum(pos, y_rows.shape[0] - 1)]
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
