"""The Mamba-2 recurrence as two Pallas kernels (``models/ssm.py`` holds the
equations and the plain XLA forms these mirror).

* :func:`state_update` (traced as ``ssm.state_update``): one decode token a
  row. Grid ``(rows, groups)``; a step reads one group's heads of a row's
  float32 state, applies ``h = decay h + du (x) B``, writes
  it back IN PLACE (the state operand is aliased to the output) and reduces
  ``y = h C``. Bandwidth-bound: the state goes through once each way.
* :func:`chunk_scan` (``ssm.chunk_scan``): one tile of ``Q`` tokens a row,
  with the inter-chunk recurrence run over the ROWS of the call. Grid
  ``(groups, rows)``, rows innermost and visited in CHAIN order (``order``,
  scalar-prefetched): a row that continues the row before it in that order
  (``carried``) starts from the state that row left in a VMEM scratch, any
  other from its own cached state. A step computes, for one group's heads,
  ``C B^T`` once and then per head the decay-weighted in-tile product, the
  starting state's part of the outputs, and the state at the tile's end —
  all matrix products of 128-wide tiles.

The state's layout is the kernels' (and therefore the cache leaf's): heads in
PAIRS, state size on sublanes, the pair's values on lanes — ``(B, H/2, N,
2P)``, :func:`pack_state`. At ``P = 64`` a pair's values fill a lane tile, so
every block is whole tiles; ``decay`` and ``dt u`` broadcast along SUBLANES
(free) and ``B``, ``C`` along lanes once a group; ``y = h C`` is a sublane
sum; the outputs are lane-dense rows of the ``(B, d_inner)`` activations.

Off the TPU both run under the interpreter (tests); ``models/ssm.py`` picks
them on a TPU when the shapes are whole tiles (:func:`resolve_backend`), and
its XLA forms otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_UPDATE_NAME = "ssm.state_update"
_SCAN_NAME = "ssm.chunk_scan"
_VMEM_LIMIT = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))      # A @ B.T
_TN = (((0,), (0,)), ((), ()))      # A.T @ B


def resolve_backend(*, p: int, n: int, q: int | None = None) -> str:
    """``"pallas"``: the kernels, on a TPU when a pair of heads' values fill
    a lane tile (``2 P = 128``) and the state size and the tile (``q``, the
    chunked scan only) are whole lane tiles; ``"xla"``: ``models/ssm.py``'s
    forms otherwise. No option chooses: a test that wants the kernels under
    the interpreter patches this function."""
    whole = 2 * p == 128 and n % 128 == 0 and (q is None or q % 128 == 0)
    return "pallas" if whole and jax.default_backend() == "tpu" else "xla"


PACK = 2      # heads a lane tile


def pack_state(h):
    """``(B, H, P, N)`` -> the cache's ``(B, H/2, N, 2P)``."""
    b, heads, p, n = h.shape
    h = h.reshape(b, heads // PACK, PACK, p, n)
    return h.transpose(0, 1, 4, 2, 3).reshape(b, heads // PACK, n, PACK * p)


def unpack_state(s):
    """The cache's ``(B, H/2, N, 2P)`` -> ``(B, H, P, N)``."""
    b, pairs, n, lanes = s.shape
    s = s.reshape(b, pairs, n, PACK, lanes // PACK)
    return s.transpose(0, 1, 3, 4, 2).reshape(b, pairs * PACK, lanes // PACK, n)


def _grouped(x, grp):
    """``(B, H, ...)`` -> ``(B, G, H/G, ...)``."""
    return x.reshape(x.shape[0], grp, x.shape[1] // grp, *x.shape[2:])


def _column(row, n):
    """A ``(1, n)`` row as an ``(n, 1)`` column (mask the diagonal of its
    sublane broadcast, sum along lanes: no transpose of a thin tile)."""
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


# --- one decode token ------------------------------------------------------------


def _update_kernel(h_ref, du_ref, dec_ref, b_ref, c_ref, hout_ref, y_ref, *, pairs, lanes):
    n = h_ref.shape[2]
    b_col = jnp.broadcast_to(_column(b_ref[0, 0], n), (n, lanes))
    c_col = jnp.broadcast_to(_column(c_ref[0, 0], n), (n, lanes))
    for k in range(pairs):
        at = slice(k * lanes, (k + 1) * lanes)
        h = h_ref[0, k] * dec_ref[0, :, at] + b_col * du_ref[0, :, at]      # (N, 2P)
        hout_ref[0, k] = h
        y_ref[0, :, at] = jnp.sum(h * c_col, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_update(h, decay, du, bm, cm, *, interpret: bool = False):
    """``h' = decay h + du (x) B``, ``y = h' C`` for one token a row.

    ``h`` ``(B, H/2, N, 2P)`` float32 (:func:`pack_state`), ``decay``
    ``(B, H)``, ``du`` ``(B, H, P)`` (``dt u``), ``bm`` / ``cm``
    ``(B, G, N)``, all float32. Returns ``(h'`` (aliased to ``h``),
    ``y (B, H, P))``."""
    bsz, pairs, n, lanes = h.shape
    heads, grp = decay.shape[1], bm.shape[1]
    p, per = lanes // PACK, pairs // grp
    # A group a step (0.5 MB of state each way at the published sizes). Four
    # groups a step read the same share of the roofline on the chip (79.5
    # against 78.8 %, PR 33): the step count is not what bounds it.
    state = pl.BlockSpec((1, per, n, lanes), lambda r, g: (r, g, 0, 0))
    vec = pl.BlockSpec((1, 1, 1, n), lambda r, g: (r, g, 0, 0))
    row = pl.BlockSpec((1, 1, per * lanes), lambda r, g: (r, 0, g))
    call = pl.pallas_call(
        functools.partial(_update_kernel, pairs=per, lanes=lanes),
        grid=(bsz, grp),
        in_specs=[state, row, row, vec, vec],
        out_specs=[state, row],
        out_shape=[
            jax.ShapeDtypeStruct(h.shape, jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1, heads * p), jnp.float32),
        ],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )
    with jax.named_scope(_UPDATE_NAME):
        h_new, y = call(
            h, du.reshape(bsz, 1, heads * p),
            jnp.repeat(decay, p, axis=1)[:, None, :],
            bm[:, :, None, :], cm[:, :, None, :],
        )
    return h_new, y.reshape(bsz, heads, p)


# --- a tile of tokens a row, chained over rows ------------------------------------


def _scan_kernel(
    order_ref, carried_ref, u_ref, b_ref, c_ref, col_ref, grow_ref, dtrow_ref,
    h0_ref, y_ref, hend_ref, carry_ref, *, hb, p, q,
):
    del order_ref                                       # the index maps' operand
    i = pl.program_id(1)
    u, bm, cm = u_ref[0], b_ref[0], c_ref[0]            # (Q, hb P), (Q, N) x 2
    mm = u.dtype
    col = col_ref[0, 0]           # (Q, 2 hb): [:, j] = g_t, [:, hb + j] = exp(g_Q - g_t) dt_t
    grow, dtrow = grow_ref[0, 0], dtrow_ref[0, 0]       # (hb, Q): g_u, dt_u
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=jnp.float32)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    )
    first = jax.lax.broadcasted_iota(jnp.int32, (q, PACK * p), 1) < p
    first_row = jax.lax.broadcasted_iota(jnp.int32, (1, PACK * p), 1) < p
    use_prev = carried_ref[i] == 1
    for pair in range(hb // PACK):
        j0, j1 = PACK * pair, PACK * pair + 1
        at = slice(pair * PACK * p, (pair + 1) * PACK * p)
        h0 = jnp.where(use_prev, carry_ref[pair], h0_ref[0, pair])       # (N, 2P)
        u_pair = u[:, at]                                                # (Q, 2P)
        within = []
        for j in (j0, j1):
            seg = col[:, j:j + 1] - grow[j:j + 1, :]                     # g_t - g_u
            w = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
            w = w * dtrow[j:j + 1, :] * cb
            within.append(
                jnp.dot(w.astype(mm), u_pair, preferred_element_type=jnp.float32)
            )
        from_state = jnp.dot(cm, h0.astype(mm), preferred_element_type=jnp.float32)
        grown = jnp.where(first, jnp.exp(col[:, j0:j0 + 1]), jnp.exp(col[:, j1:j1 + 1]))
        y_ref[0, :, at] = jnp.where(first, within[0], within[1]) + grown * from_state
        to_end = jnp.where(
            first, col[:, hb + j0:hb + j0 + 1], col[:, hb + j1:hb + j1 + 1]
        )
        local = jax.lax.dot_general(
            bm, (u_pair.astype(jnp.float32) * to_end).astype(mm), _TN,
            preferred_element_type=jnp.float32,
        )                                                                # (N, 2P)
        decay = jnp.where(                        # a row: lanes, then sublanes
            first_row, jnp.exp(col[q - 1:q, j0:j0 + 1]), jnp.exp(col[q - 1:q, j1:j1 + 1])
        )
        h_end = h0 * decay + local
        hend_ref[0, pair] = h_end
        carry_ref[pair] = h_end


def chain_depth(carry_from):
    """``(root, depth)`` of every row of a call whose rows chain:
    ``carry_from`` ``(B,)`` names the row each row continues (-1: none),
    ``depth`` counts the rows before it in its chain and ``root`` is the
    chain's first row. Pointer hops whose count is the deepest chain of
    this call (a run-time scalar: no shape depends on the packing)."""
    b = carry_from.shape[0]
    linked = carry_from >= 0
    src = jnp.maximum(carry_from, 0)

    def hop(state):
        root, depth, _ = state
        new_root = jnp.where(linked, root[src], jnp.arange(b))
        new_depth = jnp.where(linked, depth[src] + 1, 0)
        return new_root, new_depth, jnp.any(new_depth != depth)

    root, depth, _ = jax.lax.while_loop(
        lambda s: s[2], hop,
        (jnp.arange(b), jnp.zeros((b,), jnp.int32), jnp.any(linked)),
    )
    return root, depth


def chain_order(carry_from):
    """``(order, carried)`` for :func:`chunk_scan`: the rows sorted so that
    every chain is contiguous and in sequence, and per sorted position 1
    where the row continues the one before it."""
    root, depth = chain_depth(carry_from)
    order = jnp.argsort(root * carry_from.shape[0] + depth).astype(jnp.int32)
    return order, (carry_from >= 0)[order].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("d_inner", "grp", "interpret"))
def chunk_scan(
    xbc, dt, g, h_rows, carry_from, *, d_inner: int, grp: int,
    interpret: bool = False,
):
    """One tile a row through the recurrence, rows chained by ``carry_from``.

    ``xbc`` ``(B, Q, d_inner + 2 G N)`` (``[u | B | C]`` after the
    convolution), ``dt`` ``(B, Q, H)`` float32 (0 at padding), ``g``
    ``(B, Q, H)`` its cumulative ``dt A``, ``h_rows`` ``(B, H/2, N, 2P)``
    float32 (each row's own cached state, :func:`pack_state`). Returns
    ``(y (B, Q, d_inner)`` float32 without the ``D`` term, ``h_end`` as
    ``h_rows)``."""
    bsz, q, _ = xbc.shape
    heads, n = dt.shape[2], h_rows.shape[2]
    p, hb = h_rows.shape[3] // PACK, heads // grp
    if hb % PACK or d_inner % n:
        raise ValueError(
            f"{heads} heads in {grp} groups do not pair up, or d_inner "
            f"({d_inner}) is not whole blocks of the state size ({n})"
        )
    order, carried = chain_order(carry_from)
    to_end = jnp.exp(g[:, -1:, :] - g) * dt
    col = jnp.concatenate(
        [_grouped(jnp.swapaxes(x, 1, 2), grp) for x in (g, to_end)], axis=2
    )                                                     # (B, G, 2 hb, Q)
    col = jnp.swapaxes(col, 2, 3)                         # (B, G, Q, 2 hb)
    grow = _grouped(jnp.swapaxes(g, 1, 2), grp)           # (B, G, hb, Q)
    dtrow = _grouped(jnp.swapaxes(dt, 1, 2), grp)
    wide = hb * p

    def row(*block):
        """A block of row ``order[i]``, group ``gi``."""
        return pl.BlockSpec(block, lambda gi, i, o, c: (o[i], gi, 0, 0))

    state = row(1, hb // PACK, n, PACK * p)
    call = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, p=p, q=q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(grp, bsz),
            in_specs=[
                pl.BlockSpec((1, q, wide), lambda gi, i, o, c: (o[i], 0, gi)),
                pl.BlockSpec(
                    (1, q, n), lambda gi, i, o, c: (o[i], 0, d_inner // n + gi)
                ),
                pl.BlockSpec(
                    (1, q, n), lambda gi, i, o, c: (o[i], 0, d_inner // n + grp + gi)
                ),
                row(1, 1, q, 2 * hb), row(1, 1, hb, q), row(1, 1, hb, q),
                state,
            ],
            out_specs=[
                pl.BlockSpec((1, q, wide), lambda gi, i, o, c: (o[i], 0, gi)),
                state,
            ],
            scratch_shapes=[pltpu.VMEM((hb // PACK, n, PACK * p), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, q, d_inner), jnp.float32),
            jax.ShapeDtypeStruct(h_rows.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )
    with jax.named_scope(_SCAN_NAME):
        return call(order, carried, xbc, xbc, xbc, col, grow, dtrow, h_rows)
