"""Mamba-2 mixer (``nemotron_h``'s ``M`` layers): a selective state-space
layer whose memory is a fixed-size recurrent state, not a cache of keys.

Equations (``H`` heads of ``P`` values, ``G`` groups, state ``N``,
``d_inner = H P``; one scalar ``A``, ``D`` and ``dt_bias`` a head)::

    [z | xBC | dt] = x W_in              widths d_inner | d_inner + 2GN | H
    xBC = silu(causal_depthwise_conv1d(xBC, kernel K) + b_conv)
    [u | B | C] = xBC                    d_inner | GN | GN
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t u_t (x) B_t       (P, N) a head, float32
    y_t = h_t C_t + D u_t
    out = (GroupRMSNorm_G(y * silu(z)) * w) W_out

Three forms of one recurrence:

* **chunked scan** (``ssm.chunk_scan``; a whole sequence, or a refill chunk
  of ``S`` tokens with ``chunk_lengths``): tiles of ``chunk`` tokens, all
  matrix products. With ``g_t`` the cumulative ``dt A`` of a tile,
  ``y_t = exp(g_t) h_0 C_t + sum_{u<=t} exp(g_t - g_u) dt_u (C_t . B_u) u_u
  + D u_t`` and ``h_Q = exp(g_Q) h_0 + sum_u exp(g_Q - g_u) dt_u u_u (x)
  B_u``. A padded position has ``dt = 0`` and stays out of the cached
  convolution inputs: the state passes through it untouched;
* **across the rows of one dispatch** (the serving engine packs several
  consecutive chunks of ONE prompt as separate rows of a refill dispatch):
  the same inter-chunk recurrence, run over rows. ``carry_from[r]`` names
  the row whose final state (and last ``K - 1`` convolution inputs) row
  ``r`` starts from, -1 for "this row's own cached state";
* **one-token update** (``ssm.state_update``; decode): a frozen row
  (``chunk_lengths`` 0) has ``dt = 0`` and changes nothing.

Cache leaves (collection ``"cache"``, one set a layer, by SLOT like the
attention layers' counters): ``ssm_state`` ``(B, H/2, N, 2P)`` float32 (heads
in pairs, the layout of ``ops/ssm_scan.py``'s kernels: ``pack_state``),
``conv_state`` ``(B, K - 1, d_inner + 2GN)`` in the compute dtype, and
``carry_from`` ``(B,)`` int32 (-1 at rest; ``engine_programs._take_rows``
fills it for a refill dispatch's chunk rows).

Beside it, :class:`ShortConv`: the ``lfm2`` family's gated short convolution
(no recurrent state; the trainer's whole-sequence form only).
"""

from __future__ import annotations

import math
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from learning_jax_sharding_tpu.parallel.logical import BATCH, EMBED, MLP, SEQ


def _a_log_init(key, shape, dtype):
    """``A`` uniform in [1, 16] (the published initialisation), kept as its log."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(dt_min: float, dt_max: float, dt_floor: float) -> Callable:
    """The inverse softplus of a log-uniform step in ``[dt_min, dt_max]``
    (floored): heads start with time constants spread over two decades, so
    some forget within a few tokens and some remember a whole prompt."""

    def init(key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


class _Conv(nn.Module):
    """The depthwise convolution's ``kernel`` ``(K, C)`` (tap ``K - 1``
    multiplies the current position) and ``bias`` ``(C,)`` (None without
    ``use_bias``)."""

    width: int
    channels: int
    param_dtype: jnp.dtype = jnp.float32
    use_bias: bool = True

    @nn.compact
    def __call__(self):
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0),
                (None, MLP),
            ),
            (self.width, self.channels), self.param_dtype,
        )
        if not self.use_bias:
            return kernel, None
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), (MLP,)),
            (self.channels,), self.param_dtype,
        )
        return kernel, bias


class _Scale(nn.Module):
    """A norm's ``scale`` ``(width,)``, ones at the start."""

    width: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self):
        return self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), (MLP,)),
            (self.width,), self.param_dtype,
        )


def _tile_scan(u, dt, a, bm, cm, tile: int, mm_dtype):
    """The chunked scan of ``S`` tokens from a ZERO state.

    ``u`` ``(B, S, H, P)``, ``dt`` ``(B, S, H)`` float32 (0 at padding),
    ``a`` ``(H,)`` float32 (negative), ``bm`` / ``cm`` ``(B, S, G, N)``.
    Returns ``y`` ``(B, S, H, P)`` float32 (without the ``D`` term and
    without the initial state's part), ``h`` ``(B, H, P, N)`` float32 (the
    final state a zero start gives) and ``g`` ``(B, S, H)``, the log-decay
    accumulated from the sequence's start through each position."""
    b, s, h, p = u.shape
    grp, n = bm.shape[2], bm.shape[3]
    nc = -(-s // tile)
    pad = nc * tile - s
    if pad:
        u, dt, bm, cm = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (u, dt, bm, cm)
        )
    q = tile
    u = u.reshape(b, nc, q, h, p)
    dt = dt.reshape(b, nc, q, h)
    # (B, nc, G, H/G, ...): a head reads its group's B and C.
    hg = h // grp
    bm = bm.reshape(b, nc, q, grp, n)
    cm = cm.reshape(b, nc, q, grp, n)
    g = jnp.cumsum(dt * a, axis=2)                        # (B, nc, Q, H), <= 0
    # Within a tile: y_t += sum_{u<=t} exp(g_t - g_u) dt_u (C_t . B_u) u_u.
    cb = jnp.einsum(
        "bcqgn,bckgn->bcgqk", cm.astype(mm_dtype), bm.astype(mm_dtype),
        preferred_element_type=jnp.float32,
    )                                                     # (B, nc, G, Q, Q)
    gh = jnp.moveaxis(g, 3, 2)                            # (B, nc, H, Q)
    seg = gh[..., :, None] - gh[..., None, :]             # g_t - g_u
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    w = decay * jnp.moveaxis(dt, 3, 2)[..., None, :]      # x dt_u
    w = w.reshape(b, nc, grp, hg, q, q) * cb[:, :, :, None]
    y = jnp.einsum(
        "bcgeqk,bckgep->bcqgep", w.astype(mm_dtype),
        u.reshape(b, nc, q, grp, hg, p).astype(mm_dtype),
        preferred_element_type=jnp.float32,
    ).reshape(b, nc, q, h, p)
    # What each tile adds to the state by its end:
    # sum_u exp(g_Q - g_u) dt_u u_u (x) B_u.
    to_end = jnp.exp(g[:, :, -1:, :] - g) * dt            # (B, nc, Q, H)
    local = jnp.einsum(
        "bcqgep,bcqgn->bcgepn",
        (u * to_end[..., None]).reshape(b, nc, q, grp, hg, p).astype(mm_dtype),
        bm.astype(mm_dtype), preferred_element_type=jnp.float32,
    ).reshape(b, nc, h, p, n)
    if nc == 1:
        return y[:, 0, :s], local[:, 0], g[:, 0, :s]
    tile_decay = jnp.exp(g[:, :, -1, :])                  # (B, nc, H)

    # Tile to tile: h_0(c + 1) = exp(g_Q(c)) h_0(c) + local(c).
    def step(carry, x):
        d, loc = x
        return carry * d[..., None, None] + loc, carry

    h_end, h_in = jax.lax.scan(
        step, jnp.zeros((b, h, p, n), jnp.float32),
        (jnp.moveaxis(tile_decay, 1, 0), jnp.moveaxis(local, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)                       # (B, nc, H, P, N)
    y = y + _from_state(h_in, cm, g, mm_dtype)
    # g from the SEQUENCE's start: add the tiles before.
    before = jnp.cumsum(g[:, :, -1, :], axis=1) - g[:, :, -1, :]
    g = g + before[:, :, None, :]
    return (
        y.reshape(b, nc * q, h, p)[:, :s], h_end, g.reshape(b, nc * q, h)[:, :s]
    )


def _from_state(h0, cm, g, mm_dtype):
    """``exp(g_t) h_0 C_t``: what a starting state ``h0`` ``(..., H, P, N)``
    adds to the outputs of the positions after it (``cm`` ``(..., Q, G, N)``,
    ``g`` ``(..., Q, H)``) -> ``(..., Q, H, P)`` float32."""
    grp = cm.shape[-2]
    h, p, n = h0.shape[-3:]
    hs = h0.reshape(*h0.shape[:-3], grp, h // grp, p, n)
    y = jnp.einsum(
        "...qgn,...gepn->...qgep", cm.astype(mm_dtype), hs.astype(mm_dtype),
        preferred_element_type=jnp.float32,
    )
    return y.reshape(*y.shape[:-3], h, p) * jnp.exp(g)[..., None]


def _chain_states(h_slot, local, decay, carry_from):
    """Each row's STARTING state when rows chain: row ``r`` starts from the
    final state of row ``carry_from[r]`` (``h_Q = decay h_0 + local`` there),
    or from its own ``h_slot[r]`` at -1. One pass a level of the deepest
    chain of this call (rows that chain nowhere cost none)."""
    from learning_jax_sharding_tpu.ops.ssm_scan import chain_depth

    _, depth = chain_depth(carry_from)
    src = jnp.maximum(carry_from, 0)

    def body(d, h0):
        h_end = h0 * decay[..., None, None] + local
        take = (depth == d)[:, None, None, None]
        return jnp.where(take, h_end[src], h0)

    return jax.lax.fori_loop(1, jnp.max(depth) + 1, body, h_slot)


class Mamba2Mixer(nn.Module):
    """One Mamba-2 layer (module docstring). Parameters: ``in_proj/kernel``
    ``(M, 2 d_inner + 2GN + H)``, ``conv/{kernel,bias}``, ``dt_bias``,
    ``A_log``, ``D`` ``(H,)``, ``norm/scale`` ``(d_inner,)``,
    ``out_proj/kernel`` ``(d_inner, M)``; no bias but the convolution's."""

    features: int
    num_heads: int
    head_dim: int
    groups: int
    state_size: int
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    state_dtype: jnp.dtype = jnp.float32    # the model's rule; a test lowers
                                            # it to show the tolerance bites
    decode: bool = False
    kernel_init: Callable = nn.initializers.lecun_normal()
    out_init: Callable | None = None        # out_proj's own (None: kernel_init)

    def _dense(self, features: int, axes, name: str, init=None) -> nn.Module:
        return nn.Dense(
            features, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(init or self.kernel_init, axes),
            name=name,
        )

    @nn.compact
    def __call__(self, x: jax.Array, *, chunk_lengths=None) -> jax.Array:
        from learning_jax_sharding_tpu.ops import ssm_scan

        b, s, _ = x.shape
        h, p, grp, n = self.num_heads, self.head_dim, self.groups, self.state_size
        if h % (grp * ssm_scan.PACK):
            raise ValueError(
                f"{h} heads do not divide into {grp} groups of whole pairs"
            )
        d_inner, k = h * p, self.conv_kernel
        conv_dim = d_inner + 2 * grp * n
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))

        with jax.named_scope("ssm.in_proj"):
            zxbcdt = self._dense(
                2 * d_inner + 2 * grp * n + h, (EMBED, MLP), "in_proj"
            )(x)
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
        dt_raw = zxbcdt[..., d_inner + conv_dim:]
        conv_w, conv_b = _Conv(k, conv_dim, self.param_dtype, name="conv")()
        vec = nn.with_logical_partitioning
        dt_bias = self.param(
            "dt_bias",
            vec(_dt_bias_init(self.dt_min, self.dt_max, self.dt_floor), (MLP,)),
            (h,), self.param_dtype,
        )
        a_log = self.param("A_log", vec(_a_log_init, (MLP,)), (h,), self.param_dtype)
        d_skip = self.param(
            "D", vec(nn.initializers.ones_init(), (MLP,)), (h,), self.param_dtype
        )

        valid = None
        if chunk_lengths is not None:
            valid = jnp.arange(s)[None, :] < chunk_lengths[:, None]   # (B, S)
        state = conv_state = carry_from = None
        if self.decode:
            # Heads in pairs, state size before values: the kernels' layout
            # (ops/ssm_scan.py::pack_state).
            state = self.variable(
                "cache", "ssm_state", jnp.zeros,
                (b, h // ssm_scan.PACK, n, ssm_scan.PACK * p), self.state_dtype,
            )
            conv_state = self.variable(
                "cache", "conv_state", jnp.zeros, (b, k - 1, conv_dim), self.dtype
            )
            carry_from = self.variable(
                "cache", "carry_from", lambda: jnp.full((b,), -1, jnp.int32)
            )

        with jax.named_scope("ssm.conv"):
            if conv_state is None:
                before = jnp.zeros((b, k - 1, conv_dim), xbc.dtype)
            else:
                before = conv_state.value
                if s >= k - 1 > 0:
                    # A row that continues another starts from that row's
                    # last K - 1 inputs: every row with a successor is FULL
                    # (the engine packs consecutive whole chunks, and lends
                    # a slot several rows only at chunks this wide).
                    link = carry_from.value
                    before = jnp.where(
                        (link >= 0)[:, None, None],
                        xbc[jnp.maximum(link, 0), s - (k - 1):], before,
                    )
            ext = jnp.concatenate([before, xbc], axis=1)     # (B, S + K - 1, C)
            acc = conv_b.astype(jnp.float32)
            for tap in range(k):
                acc = acc + (
                    ext[:, tap:tap + s].astype(jnp.float32)
                    * conv_w[tap].astype(jnp.float32)
                )
            xbc_act = jax.nn.silu(acc).astype(self.dtype)
            if conv_state is not None:
                # The K - 1 inputs before the next position: padding stays
                # out (a row of n valid tokens keeps ext[n : n + K - 1]).
                n_valid = (
                    jnp.full((b,), s, jnp.int32) if chunk_lengths is None
                    else chunk_lengths
                )
                at = n_valid[:, None] + jnp.arange(k - 1)[None, :]
                conv_state.value = jnp.take_along_axis(
                    ext, at[:, :, None], axis=1
                ).astype(self.dtype)

        u = xbc_act[..., :d_inner].reshape(b, s, h, p)
        bm = xbc_act[..., d_inner:d_inner + grp * n].reshape(b, s, grp, n)
        cm = xbc_act[..., d_inner + grp * n:].reshape(b, s, grp, n)
        dt = jax.nn.softplus(
            dt_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32)
        )                                                    # (B, S, H)
        if valid is not None:
            dt = jnp.where(valid[..., None], dt, 0.0)
        a = -jnp.exp(a_log.astype(jnp.float32))              # (H,)
        skip = d_skip.astype(jnp.float32)[:, None] * u.astype(jnp.float32)

        # The kernels keep the state float32 and take one tile a row.
        kernels = (
            self.decode and self.state_dtype == jnp.float32
            and s in (1, self.chunk)
            and ssm_scan.resolve_backend(p=p, n=n, q=None if s == 1 else s) == "pallas"
        )
        interpret = jax.default_backend() != "tpu"
        if kernels and s == 1:
            d1 = dt[:, 0]
            state.value, y = ssm_scan.state_update(
                state.value, jnp.exp(d1 * a),
                d1[..., None] * u[:, 0].astype(jnp.float32),
                bm[:, 0].astype(jnp.float32), cm[:, 0].astype(jnp.float32),
                interpret=interpret,
            )
            y = y[:, None]
        elif kernels:
            y, h_end = ssm_scan.chunk_scan(
                xbc_act, dt, jnp.cumsum(dt * a, axis=1), state.value,
                carry_from.value, d_inner=d_inner, grp=grp, interpret=interpret,
            )
            state.value = h_end
            y = y.reshape(b, s, h, p)
        elif self.decode and s == 1:
            with jax.named_scope("ssm.state_update"):
                d1 = dt[:, 0]                                            # (B, H)
                h_prev = ssm_scan.unpack_state(state.value).astype(jnp.float32)
                rep = h // grp
                b_h = jnp.repeat(bm[:, 0].astype(jnp.float32), rep, axis=1)  # (B,H,N)
                c_h = jnp.repeat(cm[:, 0].astype(jnp.float32), rep, axis=1)
                du = d1[..., None] * u[:, 0].astype(jnp.float32)         # (B, H, P)
                h_new = (
                    h_prev * jnp.exp(d1 * a)[..., None, None]
                    + du[..., None] * b_h[:, :, None, :]
                )
                # Through the state's own type: what the next step reads
                # is what this step's output saw.
                h_new = h_new.astype(self.state_dtype)
                state.value = ssm_scan.pack_state(h_new)
                y = jnp.sum(
                    h_new.astype(jnp.float32) * c_h[:, :, None, :], axis=-1
                )[:, None]                                               # (B,1,H,P)
        else:
            with jax.named_scope("ssm.chunk_scan"):
                y, local, g = _tile_scan(u, dt, a, bm, cm, self.chunk, self.dtype)
                if state is not None:
                    h0 = _chain_states(
                        ssm_scan.unpack_state(state.value).astype(jnp.float32), local,
                        jnp.exp(g[:, -1]), carry_from.value,
                    )
                    y = y + _from_state(h0, cm, g, self.dtype)
                    h_end = h0 * jnp.exp(g[:, -1])[..., None, None] + local
                    state.value = ssm_scan.pack_state(h_end).astype(self.state_dtype)
        y = (y + skip).reshape(b, s, d_inner)

        # Gate first, then RMS over each group's d_inner / G values.
        y = y * jax.nn.silu(z.astype(jnp.float32))
        yg = y.reshape(b, s, grp, d_inner // grp)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + self.norm_eps
        )
        norm_w = _Scale(d_inner, self.param_dtype, name="norm")()
        y = (yg.reshape(b, s, d_inner) * norm_w.astype(jnp.float32)).astype(self.dtype)
        with jax.named_scope("ssm.out_proj"):
            out = self._dense(
                self.features, (MLP, EMBED), "out_proj", self.out_init
            )(y)
        return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))


class ShortConv(nn.Module):
    """The gated short convolution of the ``lfm2`` family's ``conv`` layers:
    no recurrent state, a depthwise causal convolution of ``kernel`` taps
    between two element-wise gates::

        [B | C | u] = x W_in             three thirds of width M, that order
        z = B * u
        c_t = sum_j w[j] * z_{t - (K - 1) + j}     zeros left of the sequence
        out = (C * c) W_out

    No activation and no bias. Parameters: ``in_proj/kernel`` ``(M, 3M)``,
    ``conv/kernel`` ``(K, M)``, ``out_proj/kernel`` ``(M, M)``. Whole
    sequences only (the trainer's path): served, the ``K - 1`` inputs before
    the next token would be a slot's state beside the paged cache, as
    :class:`Mamba2Mixer`'s ``conv_state`` is; that is not built."""

    features: int
    kernel: int = 3
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    def _dense(self, features: int, axes, name: str) -> nn.Module:
        return nn.Dense(
            features, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(self.kernel_init, axes),
            name=name,
        )

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        m, k, s = self.features, self.kernel, x.shape[1]
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))
        with jax.named_scope("conv.in_proj"):
            # A residual a rematerialized block may keep
            # (utils.memory.REMAT_GROUPS); an identity elsewhere.
            bcu = checkpoint_name(
                self._dense(3 * m, (EMBED, MLP), "in_proj")(x), "conv_in_proj"
            )
        taps, _ = _Conv(k, m, self.param_dtype, use_bias=False, name="conv")()
        with jax.named_scope("conv.taps"):
            gate_b, gate_c, u = bcu[..., :m], bcu[..., m:2 * m], bcu[..., 2 * m:]
            z = jnp.pad(gate_b * u, ((0, 0), (k - 1, 0), (0, 0)))
            c = sum(
                z[:, tap:tap + s].astype(jnp.float32) * taps[tap].astype(jnp.float32)
                for tap in range(k)
            )
            y = gate_c * c.astype(self.dtype)
        with jax.named_scope("conv.out_proj"):
            out = self._dense(m, (MLP, EMBED), "out_proj")(y)
        return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))
