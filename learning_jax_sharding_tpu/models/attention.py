"""Logically partitioned multi-head attention (the case-5/6 model, L4).

Rebuilds the reference's ``FlaxAttention``
(`/root/reference/case6_attention.py:42-143`, minimal form
`/root/reference/case5_attention_dense.py:41-71`) as a framework module:

* Q/K/V projections with logical kernel axes ``(EMBED, HEADS)`` and output
  projection ``(HEADS, EMBED)`` — matching `case6_attention.py:56-90`, so the
  case-6 parity oracles hold (Wq (640,512) → shard (320,512) under the
  reference rules on a 2×2 mesh, SURVEY.md §8);
* activation sharding constraints between every stage
  (`case6_attention.py:105-116,137,141`), expressed with honest axis names
  (``SEQ`` for the sequence dim — see logical.py's design note);
* fp32 softmax upcast (`case6_attention.py:121-130`) via ``ops.attention``;
* selectable attention backend: dense einsum attention (reference semantics),
  or the Pallas flash kernel for long sequences.

Two attention modules live here. :class:`MultiHeadAttention` (MHA / GQA /
MQA, learned positions or RoPE, one global window; K and V cached per head:
every GPT-2-shaped and LLaMA-shaped config) and :class:`LatentAttention`
(low-rank q and a shared kv latent cached as one row a token:
JoyAI-LLM-Flash, ``TransformerConfig.latent_kv_rank``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from learning_jax_sharding_tpu.ops.attention import (
    causal_mask,
    dot_product_attention,
    sliding_window_mask,
)
from learning_jax_sharding_tpu.ops.rope import apply_rope
from learning_jax_sharding_tpu.parallel.logical import BATCH, EMBED, HEADS, KV, SEQ


def resolve_decode_backend(mode: str) -> str:
    """``"auto"`` → the blocked Pallas cache kernel on TPU, the dense cached
    path elsewhere (the kernel runs off-TPU only under the slow interpreter).
    Explicit ``"dense"`` / ``"blocked"`` force a backend."""
    if mode == "auto":
        return "blocked" if jax.default_backend() == "tpu" else "dense"
    if mode not in ("dense", "blocked"):
        raise ValueError(
            f"unknown decode_attention {mode!r}: expected 'auto', 'dense', "
            f"or 'blocked'"
        )
    return mode


def _dense_attention(q, k, v, mask, *, num_heads):
    """Positional-array-args wrapper so ``jax.checkpoint`` can wrap the dense
    op. The GQA head expansion happens INSIDE: a checkpoint always saves its
    arguments, so expanding before it would store group-factor-times-larger
    k/v residuals — on exactly the long-context path ``remat_attention``
    exists to shrink."""
    return dot_product_attention(
        q, repeat_kv(k, num_heads), repeat_kv(v, num_heads), mask=mask
    )


def quantize_kv_chunk(chunk: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization of a K/V chunk along its last (head-dim)
    axis: per-(token, head) fp32 scales + clipped integer values. THE single
    definition of the cache quantization step — both cached-attention
    backends (dense and blocked) write with it, so the stored values cannot
    drift between layouts."""
    absmax = jnp.max(jnp.abs(chunk.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(chunk.astype(jnp.float32) / scale[..., None]), -127, 127
    )
    return scale, q


def row_update(buf: jax.Array, chunk: jax.Array, idx: jax.Array, *, seq_dim: int) -> jax.Array:
    """Write ``chunk`` into ``buf`` at a PER-ROW offset along ``seq_dim``
    (both batch-leading): row ``b``'s chunk lands at ``idx[b]`` — the ragged
    cache write, where every sequence in the batch sits at its own length.
    A vmapped ``dynamic_update_slice`` (lowers to one scatter); the scalar
    path keeps its plain ``dynamic_update_slice``."""

    def one(b_buf, b_chunk, i):
        starts = [0] * b_buf.ndim
        starts[seq_dim - 1] = i
        return jax.lax.dynamic_update_slice(b_buf, b_chunk, tuple(starts))

    return jax.vmap(one)(buf, chunk, idx)


def row_update_masked(
    buf: jax.Array, chunk: jax.Array, idx: jax.Array, lengths: jax.Array,
    *, seq_dim: int,
) -> jax.Array:
    """Length-aware :func:`row_update`: row ``b`` writes only its first
    ``lengths[b]`` chunk positions at ``idx[b]``; the rest of the window
    writes back the buffer's OWN values.

    Why this exists (continuous batching): a refill chunk runs for EVERY
    row, and a zero-length row near the buffer end would have its
    ``dynamic_update_slice`` start CLAMPED below its index — overwriting
    valid attended history with chunk padding. The masked read-modify-write
    makes any clamped or zero-length window a no-op on existing data (and
    aligns a clamped partial chunk to its true offset), so mixed
    refill/decode batches can never corrupt a row's cache.
    """
    s = chunk.shape[seq_dim]
    cap = buf.shape[seq_dim]

    def one(b_buf, b_chunk, i, n):
        start_v = jnp.minimum(i, cap - s)
        starts = [jnp.zeros((), jnp.int32)] * b_buf.ndim
        starts[seq_dim - 1] = start_v
        win = jax.lax.dynamic_slice(b_buf, tuple(starts), b_chunk.shape)
        off = i - start_v          # 0 unless the window start clamped
        pos = jnp.arange(s)
        shape = [1] * b_buf.ndim
        shape[seq_dim - 1] = s
        mask = ((pos >= off) & (pos < off + n)).reshape(shape)
        rolled = jnp.roll(b_chunk, off, axis=seq_dim - 1)
        merged = jnp.where(mask, rolled, win)
        return jax.lax.dynamic_update_slice(b_buf, merged, tuple(starts))

    return jax.vmap(one)(buf, chunk, idx, lengths)


def paged_slots(
    table, idx, s: int, page: int, chunk_lengths, length: int,
    ring: bool = False,
):
    """Where a chunk's ``s`` positions land in a page pool: cache position
    ``idx_b + t`` lives at ``(table[b, pos // page], pos % page)`` (a RING
    table: ``table[b, (pos // page) mod R]``). Invalid positions (padding
    past a row's ``chunk_lengths``, or past ``length`` without them) are
    redirected to the reserved scratch page 0, so masked writes can never
    touch live pages. Returns ``(pages, slots)``, ``(B, S)`` each."""
    pos = idx[:, None] + jnp.arange(s)[None, :]
    col = pos // page
    col = col % table.shape[1] if ring else jnp.minimum(col, table.shape[1] - 1)
    pages = jnp.take_along_axis(table, col, axis=1)
    if chunk_lengths is not None:
        valid = jnp.arange(s)[None, :] < chunk_lengths[:, None]
    else:
        valid = pos < length
    return jnp.where(valid, pages, 0), pos % page


def repeat_kv(kv: jax.Array, num_heads: int) -> jax.Array:
    """Broadcast grouped k/v heads ``(B, S, N_kv, H)`` to ``num_heads``.

    Grouped-query attention shares each k/v head across a group of query
    heads. Parameters, gradients, and (crucially) the decode KV cache stay at
    ``N_kv`` heads — the repeat happens only at attention-compute time so the
    score einsums see matching head counts and every backend (dense, flash,
    ring) works unchanged.
    """
    n_kv = kv.shape[2]
    if n_kv == num_heads:
        return kv
    if num_heads % n_kv:
        raise ValueError(f"num_heads {num_heads} not a multiple of kv heads {n_kv}")
    return jnp.repeat(kv, num_heads // n_kv, axis=2)


class MultiHeadAttention(nn.Module):
    """Multi-head self-attention with logical partitioning.

    Attributes:
        features: residual-stream width M (the reference's M=640,
            `/root/reference/case6_attention.py:151`).
        num_heads: attention heads N (reference: 8, `case6_attention.py:44`).
        head_dim: per-head width H (reference: 64, `case6_attention.py:45`).
        dropout_rate: output dropout (reference: 0.1, `case6_attention.py:91`).
        causal: apply a causal mask (reference attention is bidirectional;
            the case-7 transformer sets this True).
        dtype: computation dtype (bf16 on TPU for MXU throughput; softmax
            still runs fp32 via the op).
        param_dtype: parameter storage dtype.
        attn_fn: attention backend taking ``(q, k, v, *, causal: bool)`` with
            (B, S, N, H) operands (see ops.flash_attention.make_flash_attn_fn
            / ops.ring_attention.make_ring_attn_fn); None (default) uses the
            dense einsum op, which also supports arbitrary masks.
        remat_attention: recompute the O(S²) score/softmax tensors in the
            backward pass instead of saving them (``jax.checkpoint`` around
            the dense attention op). Costs ~one extra score einsum per layer
            (a few % of step FLOPs) and removes the (B, N, S, S) arrays from
            saved activations — the dominant activation-memory term, and what
            otherwise caps batch size (flash-attention memory behavior
            without the kernel). Dense backend only.
    """

    features: int
    num_heads: int = 8
    head_dim: int = 64
    num_kv_heads: Optional[int] = None   # < num_heads → GQA; 1 → MQA
    rope: bool = False                   # rotary positions on q/k
    rope_theta: float = 10_000.0
    rotary_dim: Optional[int] = None     # rotate the first this many values
                                         # of a head, pass the rest (None: all)
    rope_yarn: Optional[Any] = None      # ops.rope.Yarn: scaled frequencies
    gate: bool = False                   # per-head sigmoid output gate from
                                         # the attention's input (``gate``:
                                         # one scalar a head a token)
    qk_norm: bool = False                # RMSNorm over each head's values of
                                         # q and of k (one learned head_dim
                                         # vector each: q_norm, k_norm),
                                         # before the rotation
    norm_eps: float = 1e-6               # qk_norm's
    window: Optional[int] = None         # causal sliding-window size (SWA)
    dropout_rate: float = 0.0
    causal: bool = False
    use_bias: bool = False               # biases on q/k/v/out (GPT-2 style)
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    attn_fn: Optional[Callable] = None
    remat_attention: bool = False
    decode: bool = False
    max_decode_len: int = 0
    kv_cache_dtype: Optional[jnp.dtype] = None
    # Decode-cache storage format. None stores at compute dtype (default).
    # jnp.int8 quantizes K/V on write with a per-(token, head) fp32 scale —
    # the cache is usually what caps batch x context at serving time, and
    # int8 roughly halves it vs bf16 (fp32 scales add 4/head_dim of the int8
    # bytes: 6% at head_dim=64). Any other dtype (e.g. bf16 under fp32
    # compute) is a plain storage cast.
    decode_attention: str = "auto"
    # Decode-attention backend: "dense" attends the WHOLE max_decode_len
    # buffer every step (reference-style, O(max_len) HBM traffic per token);
    # "blocked" uses the length-aware Pallas cache kernel
    # (ops/decode_attention.py) whose traffic scales with the VALID cache
    # length and which reads GQA caches at N_kv heads with no repeat_kv
    # expansion. "auto" (default) picks blocked on TPU, dense elsewhere.
    # The backends differ in cache layout: dense stores k and v as
    # (B, L, N_kv, H) each, blocked stores one (B, N_kv, L, 2H) buffer
    # (sequence-major per head, k | v fused on the minor axis, so each cache
    # block is one contiguous, lane-dense DMA).
    decode_block_k: Optional[int] = None   # blocked-backend cache block size
    quantization: Optional[str] = None
    # "int4": projections consume quantize_tree(bits=4) params VERBATIM via
    # the fused dequant-matmul kernel (ops/int4_matmul.py) — packed nibbles
    # stream into the dot, no dequantized weights in HBM. None = nn.Dense.
    quantization_group: int = 128
    quantized_matmul_fn: Optional[Callable] = None  # mesh-aware fused-int4
                                         # matmul (make_int4_matmul_fn)
    decode_attn_fn: Optional[Callable] = None
    # Mesh-aware override for the blocked backend (shard_map-wrapped kernel
    # from ops.decode_attention.make_decode_attn_fn); None calls the kernel
    # directly (single-device, or GSPMD-replicated).
    decode_ragged: bool = False
    # Per-ROW cache positions: ``cache_index`` is (B,), writes scatter each
    # row's chunk at its own offset, and masks/rope use per-row positions —
    # mixed-length prompt batches (the normal serving case) become
    # expressible, and rows advance independently (a finished row passes
    # chunk_lengths 0 and stops consuming cache). False keeps the scalar
    # rectangular machinery (no scatter on the hot path).
    decode_paged: bool = False
    # PAGED cache (blocked backend + ragged only): K/V live in per-layer
    # physical page POOLS of ``decode_page_count`` pages ×
    # ``decode_block_k`` tokens, indirected through a per-row
    # ``block_table`` cache variable that the HOST allocator owns
    # (models/serving.py) — cache HBM scales with pages allocated, not
    # B × max_decode_len. Page 0 is a reserved scratch target for masked
    # writes; this module never touches the table.
    decode_page_count: int = 0

    @property
    def inner_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_heads(self) -> int:
        """K/V head count: ``num_kv_heads`` (GQA/MQA) or all heads (MHA).

        Grouped heads shrink k/v projection params, gradients, and the decode
        KV cache by ``num_heads / num_kv_heads`` — the cache is usually what
        caps batch×context at serving time. Query heads are unchanged. Under
        TP rules (HEADS→model) the mesh axis size must divide this count.
        """
        n = self.num_kv_heads if self.num_kv_heads is not None else self.num_heads
        if self.num_heads % n:
            raise ValueError(
                f"num_kv_heads {n} must divide num_heads {self.num_heads}"
            )
        return n

    def _dense(self, features: int, kernel_axes, name: str):
        """nn.Dense, or the fused-int4 drop-in under quantization="int4"
        (one shared dispatch, models/quantize.py::projection_dense)."""
        from learning_jax_sharding_tpu.models.quantize import projection_dense

        return projection_dense(
            quantization=self.quantization,
            features=features,
            kernel_axes=kernel_axes,
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=self.kernel_init,
            group_size=self.quantization_group,
            quantized_matmul_fn=self.quantized_matmul_fn,
            name=name,
        )

    def _fused_qkv(self, m: int) -> bool:
        """Route q/k/v through one ``int4_matmul3`` launch: int4 serving,
        single-device (no TP shard_map injection), MHA (equal projection
        widths; GQA's narrower k/v keep per-projection calls), no biases,
        and a group layout the kernel can tile."""
        if (
            self.quantization != "int4"
            or self.quantized_matmul_fn is not None
            or self.use_bias
            or self.kv_heads != self.num_heads
            or m % 2
        ):
            return False
        g = min(self.quantization_group, m)
        return g == m or (m // 2) % g == 0

    def _proj(self, name: str, heads: int) -> nn.Module:
        # Kernel (M, heads*H) carries logical axes (EMBED, HEADS): under the
        # reference rules EMBED→model splits its rows
        # (`/root/reference/case6_attention.py:56-59`); under Megatron-style
        # rules HEADS→model splits its columns.
        return self._dense(heads * self.head_dim, (EMBED, HEADS), name)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        deterministic: bool = True,
        chunk_lengths: Optional[jax.Array] = None,
    ) -> jax.Array:
        """``chunk_lengths``: ragged decode only — per-row count of VALID
        tokens in this chunk (prefill: the prompt lengths; a frozen row
        passes 0). Drives how far each row's cache index advances; the
        chunk's padded tail is still written but never attended (causal
        masks stop at each row's index)."""
        b, s, m = x.shape
        if chunk_lengths is not None and not self.decode_ragged:
            raise ValueError("chunk_lengths requires decode_ragged=True")
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))

        if self._fused_qkv(m):
            # q/k/v in ONE kernel launch: at M = 8 decode the serial launch
            # chain, not bytes, is the int4 floor (PERF.md round 3) — the
            # three projections share x, so two dependent boundaries per
            # block vanish. Param layout matches Int4Dense verbatim
            # (quantized trees apply unchanged).
            from learning_jax_sharding_tpu.models.quantize import Int4ProjParams
            from learning_jax_sharding_tpu.ops.int4_matmul import int4_matmul3

            g = min(self.quantization_group, m)
            n_out = self.num_heads * self.head_dim
            pairs = [
                Int4ProjParams(m // 2, n_out, m // g, name=nm)()
                for nm in ("query", "key", "value")
            ]
            q, k, v = int4_matmul3(x.astype(self.dtype), pairs, group=g)
        else:
            q = self._proj("query", self.num_heads)(x)
            k = self._proj("key", self.kv_heads)(x)
            v = self._proj("value", self.kv_heads)(x)
        # Projections emerge (B, S, N*H); constrain before the head split
        # (the reference constrains the same three activations,
        # `case6_attention.py:105-116`, but names dim 1 'embed'). Named
        # HERE, whole lane tiles wide, for a rematerialized block to keep
        # (utils.memory.REMAT_GROUPS; an identity elsewhere): split by head
        # a 64-wide minor axis is padded to 128 lanes in HBM.
        q = checkpoint_name(
            nn.with_logical_constraint(q, (BATCH, SEQ, HEADS)), "attn_q"
        )
        k = checkpoint_name(
            nn.with_logical_constraint(k, (BATCH, SEQ, HEADS)), "attn_k"
        )
        v = checkpoint_name(
            nn.with_logical_constraint(v, (BATCH, SEQ, HEADS)), "attn_v"
        )

        q = q.reshape(b, s, self.num_heads, self.head_dim)
        k = k.reshape(b, s, self.kv_heads, self.head_dim)
        v = v.reshape(b, s, self.kv_heads, self.head_dim)
        q = nn.with_logical_constraint(q, (BATCH, SEQ, HEADS, KV))
        k = nn.with_logical_constraint(k, (BATCH, SEQ, HEADS, KV))
        v = nn.with_logical_constraint(v, (BATCH, SEQ, HEADS, KV))

        if self.qk_norm:
            q, k = (
                nn.RMSNorm(
                    epsilon=self.norm_eps, dtype=self.dtype,
                    param_dtype=self.param_dtype, name=name,
                )(t)
                for name, t in (("q_norm", q), ("k_norm", k))
            )

        if self.rope:
            # Rotate BEFORE caching so cached keys carry their absolute
            # positions and chunked decode needs no re-rotation.
            if self.decode:
                # Read-only peek: _cached_attention owns (declares and
                # advances) this variable; during init it doesn't exist yet
                # and the chunk starts at position 0.
                idx = self.get_variable(
                    "cache", "cache_index",
                    jnp.zeros((b,) if self.decode_ragged else (), jnp.int32),
                )
                if self.decode_ragged:
                    positions = idx[:, None] + jnp.arange(s)   # (B, S)
                else:
                    positions = idx + jnp.arange(s)
            else:
                positions = jnp.arange(s)
            rot = dict(rotary_dim=self.rotary_dim, yarn=self.rope_yarn)
            q = apply_rope(q, positions, self.rope_theta, **rot)
            k = apply_rope(k, positions, self.rope_theta, **rot)

        if self.decode:
            out = self._cached_attention(q, k, v, chunk_lengths)
        elif self.attn_fn is None:
            if self.window is not None:
                if not self.causal:
                    raise ValueError("window (sliding-window attention) requires causal=True")
                mask = sliding_window_mask(s, self.window)
            else:
                mask = causal_mask(s) if self.causal else None
            dense = functools.partial(_dense_attention, num_heads=self.num_heads)
            if self.remat_attention:
                dense = jax.checkpoint(
                    dense, policy=jax.checkpoint_policies.nothing_saveable
                )
            out = dense(q, k, v, mask)
        else:
            if self.window is not None:
                raise ValueError(
                    "window with a custom attn_fn: configure the backend "
                    "instead (e.g. make_flash_attn_fn(window=...))"
                )
            # Custom backends (flash/ring) take the structural flag, not a
            # dense mask — they cannot honor arbitrary masks and must not
            # silently reinterpret one. GQA-native backends (the flash
            # kernel) read k/v at N_kv heads directly — no repeat_kv
            # expansion materializes, which is GQA's bandwidth win.
            if getattr(self.attn_fn, "supports_gqa", False):
                out = self.attn_fn(q, k, v, causal=self.causal)
            else:
                out = self.attn_fn(
                    q, repeat_kv(k, self.num_heads),
                    repeat_kv(v, self.num_heads),
                    causal=self.causal,
                )
        out = nn.with_logical_constraint(out, (BATCH, SEQ, HEADS, KV))
        if self.gate:
            # sigmoid(x W_gate): one scalar a head, from the same input as q.
            g = self._dense(self.num_heads, (EMBED, HEADS), "gate")(x)
            out = out * jax.nn.sigmoid(g.astype(jnp.float32)).astype(
                out.dtype
            )[..., None]
        out = out.reshape(b, s, self.inner_dim)

        # Output projection (N*H, M) with logical (HEADS, EMBED)
        # (`case6_attention.py:83-90`).
        out = self._dense(self.features, (HEADS, EMBED), "out")(out)
        out = nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))
        if self.dropout_rate > 0.0:
            out = nn.Dropout(rate=self.dropout_rate, deterministic=deterministic)(out)
        return out

    def _advance(self, cache_index, s: int, chunk_lengths) -> jax.Array:
        """Read the index, advance it by the chunk's VALID length — ``s``
        (rectangular), or per-row ``chunk_lengths`` (ragged: prefill passes
        prompt lengths, a frozen row passes 0 and stops consuming cache)."""
        idx = cache_index.value
        cache_index.value = idx + (s if chunk_lengths is None else chunk_lengths)
        return idx

    def _cached_attention(
        self, q: jax.Array, k: jax.Array, v: jax.Array, chunk_lengths=None
    ) -> jax.Array:
        """Autoregressive attention against an in-module KV cache.

        The cache (absent from the reference, which has no inference path —
        SURVEY.md §5) holds ``(B, max_decode_len, N, H)`` keys/values in
        Flax's ``"cache"`` collection plus a write index. Each call appends
        the chunk's k/v at the index and attends q against the full cache
        with positions past the chunk masked — so one code path serves both
        prompt prefill (S = prompt length) and single-token decode (S = 1).
        Shapes stay static (attention always spans the whole cache buffer):
        XLA compiles exactly two executables for the whole generate loop.

        ``decode_ragged``: the index is per-row ``(B,)`` — writes scatter
        each row's chunk at its own offset and the causal mask compares
        per-row positions, so mixed-length batches attend exactly their own
        valid prefixes (padded prefill rows produce garbage outputs that
        the caller discards by gathering logits at each row's length).
        """
        if self.attn_fn is not None:
            raise ValueError(
                "decode mode uses the cached paths (dense or blocked); "
                "attn_fn backends (flash/ring) are for training-length "
                "sequences"
            )
        if self.max_decode_len <= 0:
            raise ValueError("decode=True requires max_decode_len > 0")
        if resolve_decode_backend(self.decode_attention) == "blocked":
            return self._blocked_cached_attention(q, k, v, chunk_lengths)
        if self.decode_paged:
            raise ValueError(
                "decode_paged requires the blocked decode backend (the "
                "dense path attends per-row buffers, not page pools)"
            )
        b, s, n, h = q.shape
        n_kv = k.shape[2]  # GQA caches only the k/v heads — the GQA win
        ragged = self.decode_ragged
        length = self.max_decode_len
        store = self.kv_cache_dtype if self.kv_cache_dtype is not None else self.dtype
        quantized = store == jnp.int8

        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros, (b, length, n_kv, h), store
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros, (b, length, n_kv, h), store
        )
        cache_index = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((b,) if ragged else (), jnp.int32),
        )
        if quantized:
            # Symmetric per-(token, kv-head) scales, written with the chunk.
            k_scale = self.variable(
                "cache", "key_scale", jnp.ones, (b, length, n_kv), jnp.float32
            )
            v_scale = self.variable(
                "cache", "value_scale", jnp.ones, (b, length, n_kv), jnp.float32
            )

        def ragged_write(buf, chunk, seq_dim):
            # Length-aware when per-row valid counts ride the call: rows
            # with 0 valid tokens (and clamped near-end windows) must not
            # disturb existing cache (see row_update_masked).
            if chunk_lengths is not None:
                return row_update_masked(
                    buf, chunk, idx, chunk_lengths, seq_dim=seq_dim
                )
            return row_update(buf, chunk, idx, seq_dim=seq_dim)

        def write(var, chunk, scale_var=None):
            if quantized:
                scale, chunk = quantize_kv_chunk(chunk)
                if ragged:
                    scale_var.value = ragged_write(scale_var.value, scale, 1)
                else:
                    scale_var.value = jax.lax.dynamic_update_slice(
                        scale_var.value, scale, (0, idx, 0)
                    )
            if ragged:
                var.value = ragged_write(var.value, chunk.astype(store), 1)
            else:
                var.value = jax.lax.dynamic_update_slice(
                    var.value, chunk.astype(store), (0, idx, 0, 0)
                )

        def read(var, scale_var=None):
            full = var.value
            if quantized:
                full = full.astype(jnp.float32) * scale_var.value[..., None]
            return repeat_kv(
                nn.with_logical_constraint(
                    full.astype(self.dtype), (BATCH, None, HEADS, KV)
                ),
                n,
            )

        idx = self._advance(cache_index, s, chunk_lengths)
        write(cached_k, k, k_scale if quantized else None)
        write(cached_v, v, v_scale if quantized else None)

        k_full = read(cached_k, k_scale if quantized else None)
        v_full = read(cached_v, v_scale if quantized else None)
        # Query i sits at absolute position idx + i: attend to every cache
        # slot at or before it (this also hides the zero-initialized tail).
        if ragged:
            q_pos = idx[:, None, None] + jnp.arange(s)[None, :, None]  # (B,S,1)
            k_pos = jnp.arange(length)[None, None, :]
        else:
            q_pos = idx + jnp.arange(s)[:, None]
            k_pos = jnp.arange(length)[None, :]
        mask = k_pos <= q_pos                          # (S, L) or (B, S, L)
        if self.window is not None:
            # SWA decode: attend only to the last `window` cache slots.
            mask = mask & (k_pos > q_pos - self.window)
        mask = mask[:, None] if ragged else mask[None, None]
        return dot_product_attention(q, k_full, v_full, mask=mask)

    def _blocked_cached_attention(
        self, q: jax.Array, k: jax.Array, v: jax.Array, chunk_lengths=None
    ) -> jax.Array:
        """Length-aware cached attention via the Pallas decode kernel.

        Same cache protocol as the dense path (append chunk at the index,
        attend against the valid prefix) but the cache lives sequence-major
        per head with k | v fused — ``(B, N_kv, L, 2H)`` — and attention
        runs through
        :func:`ops.decode_attention.decode_attention`: HBM traffic per step
        scales with the valid cache length instead of ``max_decode_len``,
        GQA caches are read at N_kv heads (no ``repeat_kv`` expansion), and
        int8 caches are dequantized only for the blocks actually read —
        the three decode costs the dense path pays in full every token.
        """
        from learning_jax_sharding_tpu.ops.decode_attention import (
            decode_attention,
            fuse_kv,
        )

        b, s, n, h = q.shape
        n_kv = k.shape[2]
        ragged = self.decode_ragged
        paged = self.decode_paged
        length = self.max_decode_len
        store = self.kv_cache_dtype if self.kv_cache_dtype is not None else self.dtype
        quantized = store == jnp.int8

        # Each position's k and v share one cache row, k | v on the minor
        # axis: at head size 64 that is one full 128-lane row, where two
        # (…, 64) buffers would each be padded to 128 lanes in HBM.
        # A sliding-window layer of a paged cache keeps a RING of pages a
        # slot instead of the page list all other layers share: slot s owns
        # pages 1 + s·R … R + s·R of a pool of its own (page 0 is scratch),
        # logical block j of its cache lives in the ring's page j mod R, and
        # the table never changes (the host allocates nothing for it).
        ring = paged and self.window is not None
        if paged:
            if not ragged:
                raise ValueError("decode_paged requires decode_ragged")
            page = self.decode_block_k
            if not page or length % page:
                raise ValueError(
                    f"decode_paged needs decode_block_k (page size) "
                    f"dividing max_decode_len ({length}); got {page}"
                )
            if ring:
                from learning_jax_sharding_tpu.ops.decode_attention import (
                    ring_pages,
                )

                r = ring_pages(self.window, page)
                pool = b * r + 1
                block_table = self.variable(
                    "cache", "ring_table",
                    lambda: 1 + jnp.arange(b * r, dtype=jnp.int32).reshape(b, r),
                )
            else:
                pool = self.decode_page_count
                block_table = self.variable(
                    "cache", "block_table", jnp.zeros, (b, length // page),
                    jnp.int32,
                )
            kv_shape, sc_shape = (pool, n_kv, page, 2 * h), (pool, n_kv, page)
        else:
            kv_shape, sc_shape = (b, n_kv, length, 2 * h), (b, n_kv, length)
        cached_kv = self.variable(
            "cache", "cached_kv", jnp.zeros, kv_shape, store
        )
        cache_index = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((b,) if ragged else (), jnp.int32),
        )
        if quantized:
            k_scale = self.variable(
                "cache", "key_scale", jnp.ones, sc_shape, jnp.float32
            )
            v_scale = self.variable(
                "cache", "value_scale", jnp.ones, sc_shape, jnp.float32
            )

        idx = self._advance(cache_index, s, chunk_lengths)
        # Ragged single-token steps FOLD the write into the kernel: the new
        # k|v merges in-VMEM at each row's slot and flushes back through a
        # cache output aliased to the input — the per-row scatter (measured
        # at ~18 µs of serial launch per layer, PERF.md "Ragged serving")
        # never exists. Multi-token ragged chunks (prefill) still scatter —
        # once per generation, amortized.
        fold = ragged and s == 1

        # The chunk, sequence-major and fused: (B, N_kv, S, 2H), with its
        # (B, N_kv, S) k and v scales when the cache is int8.
        ks_sm = vs_sm = None
        if quantized:
            (ks_sm, k), (vs_sm, v) = quantize_kv_chunk(k), quantize_kv_chunk(v)
            ks_sm, vs_sm = ks_sm.transpose(0, 2, 1), vs_sm.transpose(0, 2, 1)
        kv_sm = fuse_kv(k, v).astype(store).transpose(0, 2, 1, 3)

        def ragged_write(buf, chunk):
            # Length-aware when per-row valid counts ride the call (see
            # row_update_masked: zero-length / clamped windows must be
            # no-ops on existing cache).
            if chunk_lengths is not None:
                return row_update_masked(
                    buf, chunk, idx, chunk_lengths, seq_dim=2
                )
            return row_update(buf, chunk, idx, seq_dim=2)

        def paged_write(pool_buf, chunk):
            # Scatter a sequence-major chunk through the block table
            # (paged_slots: masked positions go to scratch page 0).
            pages, slots = paged_slots(
                block_table.value, idx, s, page, chunk_lengths, length, ring
            )
            # chunk (B, N_kv, S, ...) → (B, S, N_kv, ...): advanced indices
            # on pool axes 0 and 2 put the (B, S) index shape in front.
            upd = jnp.moveaxis(chunk, 2, 1)
            return pool_buf.at[pages, :, slots].set(upd)

        def write(var, chunk):
            if paged:
                var.value = paged_write(var.value, chunk)
            elif ragged:
                var.value = ragged_write(var.value, chunk)
            else:
                var.value = jax.lax.dynamic_update_slice(
                    var.value, chunk, (0, 0, idx) + (0,) * (chunk.ndim - 3)
                )

        fold_args = {}
        if fold:
            fold_args = dict(kv_new=kv_sm)
            if quantized:
                fold_args.update(ks_new=ks_sm, vs_new=vs_sm)
            if chunk_lengths is not None:
                # Frozen rows (length 0) must not have their garbage token
                # merged into the cache — the kernel pushes their write
                # slot out of range and flushes the block unchanged.
                fold_args["write_enable"] = chunk_lengths
        else:
            write(cached_kv, kv_sm)
            if quantized:
                write(k_scale, ks_sm)
                write(v_scale, vs_sm)

        # Paged pools lead with the PAGE axis (shared across rows), so only
        # the heads dim carries a sharding hint; per-row buffers shard
        # batch × heads as before.
        kv_axes = (None, HEADS, None, KV) if paged else (BATCH, HEADS, None, KV)
        sc_axes = kv_axes[:-1]
        kvc = nn.with_logical_constraint(cached_kv.value, kv_axes)
        scales = {}
        if quantized:
            scales = dict(
                k_scale=nn.with_logical_constraint(k_scale.value, sc_axes),
                v_scale=nn.with_logical_constraint(v_scale.value, sc_axes),
            )
        fn = self.decode_attn_fn if self.decode_attn_fn is not None else decode_attention
        table_args = {}
        if paged:
            table_args = dict(block_table=block_table.value)
            if ring:
                table_args["ring"] = True
        # window/block_k pass at CALL time either way: the module is the
        # single source of truth, so a mesh-aware wrapper built without them
        # cannot silently drop the sliding window.
        result = fn(
            q, kvc, idx,
            window=self.window, block_k=self.decode_block_k,
            **scales, **fold_args, **table_args,
        )
        if not fold:
            return result
        out, cached_kv.value = result[:2]
        if quantized:
            k_scale.value, v_scale.value = result[2:]
        return out


class _Kernel(nn.Module):
    """A projection's ``kernel`` parameter alone, for a caller that
    contracts it itself (same tree as an ``nn.Dense`` of that name)."""

    shape: tuple
    axes: tuple
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param(
            "kernel", nn.with_logical_partitioning(self.kernel_init, self.axes),
            self.shape, self.param_dtype,
        )


class LatentAttention(nn.Module):
    """Multi-head LATENT attention (DeepSeek-V2's MLA; JoyAI-LLM-Flash).

    Queries pass a rank-``q_rank`` bottleneck with its own RMSNorm; keys and
    values are expanded from ONE rank-``kv_rank`` latent a token (RMSNorm'd)
    plus ONE ``rope_dim``-wide rotary key shared by every head::

        c_q = RMSNorm(x W_qa);  [q_nope | q_rope]_h = c_q W_qb
        [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_rope = RoPE(k_r)
        [k_nope | v]_h = c_kv W_kvb
        s_h(t, u) = (q_nope_h(t)·k_nope_h(u) + RoPE(q_rope_h)(t)·k_rope(u))
                    / sqrt(nope_dim + rope_dim)

    RoPE rotates neighbouring pairs (``rope_interleave``). No biases.

    Two forms of the same mathematics. EXPANDED (``absorbed=False``, the
    training form): ``k_nope, v`` are materialised per head and ordinary
    attention runs over ``nope + rope`` / ``v_dim`` wide heads. ABSORBED:
    ``W_kvb``'s key half moves onto the query (``q~_h = q_nope_h W_k,h^T``,
    ``kv_rank`` wide) and its value half behind the softmax, so attention
    itself is 32 heads against one shared row ``[c_kv | k_rope]``.

    ``decode=True`` always runs the absorbed form against a cache of those
    rows — ``kv_rank + rope_dim`` values a token a layer where per-head K
    and V would be ``heads x (nope + rope + v)`` — through the latent form
    of the paged kernel (``ops.decode_attention``, ``latent_v``): S = 1
    folds the write, a refill chunk scatters its rows first and attends
    earlier chunks through the cache. The cache variable keeps the name
    ``cached_kv``; its shape ``(P | B, 1, page | L, kv_rank + rope_dim)`` is
    what tells the layouts apart. There is one cached path: the kernel (the
    interpreter off the TPU), whatever ``decode_attention`` says.
    """

    features: int
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    causal: bool = True
    absorbed: bool = False
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    decode: bool = False
    max_decode_len: int = 0
    decode_block_k: Optional[int] = None
    decode_ragged: bool = False
    decode_paged: bool = False
    decode_page_count: int = 0

    def _dense(self, features: int, kernel_axes, name: str) -> nn.Module:
        return nn.Dense(
            features, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                self.kernel_init, kernel_axes
            ),
            name=name,
        )

    def _norm(self, name: str) -> nn.Module:
        return nn.RMSNorm(
            epsilon=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name,
        )

    @nn.compact
    def __call__(
        self, x: jax.Array, *, deterministic: bool = True,
        chunk_lengths: Optional[jax.Array] = None,
    ) -> jax.Array:
        b, s, _ = x.shape
        n, dn, dr, dv, r = (
            self.num_heads, self.nope_dim, self.rope_dim, self.v_dim,
            self.kv_rank,
        )
        if chunk_lengths is not None and not self.decode_ragged:
            raise ValueError("chunk_lengths requires decode_ragged=True")
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))
        scale = (dn + dr) ** -0.5

        c_q = self._norm("q_norm")(self._dense(self.q_rank, (EMBED, None), "q_a")(x))
        q = self._dense(n * (dn + dr), (None, HEADS), "q_b")(c_q)
        q = q.reshape(b, s, n, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        kv = self._dense(r + dr, (EMBED, None), "kv_a")(x)
        c_kv = self._norm("kv_norm")(kv[..., :r])
        # W_kvb (r, N, nope | v): contracted with c_kv (expanded) or split
        # onto the query and the attention output (absorbed).
        w_kvb = _Kernel(
            (r, n * (dn + dv)), (None, HEADS), self.param_dtype,
            self.kernel_init, name="kv_b",
        )().astype(self.dtype).reshape(r, n, dn + dv)

        if self.decode:
            idx0 = self.get_variable(
                "cache", "cache_index",
                jnp.zeros((b,) if self.decode_ragged else (), jnp.int32),
            )
            positions = (
                idx0[:, None] + jnp.arange(s) if self.decode_ragged
                else idx0 + jnp.arange(s)
            )
        else:
            positions = jnp.arange(s)
        q_rope = apply_rope(q_rope, positions, self.rope_theta, interleave=True)
        k_rope = apply_rope(
            kv[..., None, r:], positions, self.rope_theta, interleave=True
        )                                                   # (B, S, 1, dr)
        rows = jnp.concatenate([c_kv[..., None, :], k_rope], axis=-1)

        if self.decode or self.absorbed:
            q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, w_kvb[..., :dn])
            q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)    # (B,S,N,r+dr)
            if self.decode:
                o_lat = self._cached_attention(q_cat, rows, chunk_lengths, scale)
            else:
                sc = jnp.einsum(
                    "bqnr,bkr->bnqk", q_cat.astype(jnp.float32),
                    rows[:, :, 0].astype(jnp.float32),
                ) * scale
                if self.causal:
                    sc = jnp.where(causal_mask(s), sc, -jnp.inf)
                p = jax.nn.softmax(sc, axis=-1)
                o_lat = jnp.einsum(
                    "bnqk,bkr->bqnr", p, c_kv.astype(jnp.float32)
                ).astype(self.dtype)
            out = jnp.einsum("bsnr,rnd->bsnd", o_lat, w_kvb[..., dn:])
        else:
            k_v = jnp.einsum("bsr,rnd->bsnd", c_kv, w_kvb)
            k = jnp.concatenate(
                [k_v[..., :dn], jnp.broadcast_to(k_rope, (b, s, n, dr))], axis=-1
            )
            out = dot_product_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1), k, k_v[..., dn:],
                mask=causal_mask(s) if self.causal else None, scale=scale,
            )
        out = nn.with_logical_constraint(out, (BATCH, SEQ, HEADS, KV))
        out = self._dense(self.features, (HEADS, EMBED), "out")(
            out.reshape(b, s, n * dv)
        )
        return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))

    def _cached_attention(self, q_cat, rows, chunk_lengths, scale):
        """Absorbed attention of chunk queries ``(B, S, N, R)`` against the
        latent cache, after appending the chunk's ``rows`` ``(B, S, 1, R)``.
        The protocol of ``MultiHeadAttention._blocked_cached_attention``
        (same variable names, same index advance, page 0 the scratch target
        of masked writes) over one shared row a token."""
        from learning_jax_sharding_tpu.ops.decode_attention import (
            decode_attention,
        )

        b, s, n, width = q_cat.shape
        if self.max_decode_len <= 0:
            raise ValueError("decode=True requires max_decode_len > 0")
        ragged, paged = self.decode_ragged, self.decode_paged
        length = self.max_decode_len
        if paged:
            if not ragged:
                raise ValueError("decode_paged requires decode_ragged")
            page = self.decode_block_k
            if not page or length % page:
                raise ValueError(
                    f"decode_paged needs decode_block_k (page size) "
                    f"dividing max_decode_len ({length}); got {page}"
                )
            shape = (self.decode_page_count, 1, page, width)
            block_table = self.variable(
                "cache", "block_table", jnp.zeros, (b, length // page),
                jnp.int32,
            )
        else:
            shape = (b, 1, length, width)
        cached = self.variable("cache", "cached_kv", jnp.zeros, shape, self.dtype)
        cache_index = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((b,) if ragged else (), jnp.int32),
        )
        idx = cache_index.value
        cache_index.value = idx + (s if chunk_lengths is None else chunk_lengths)
        rows_sm = rows.astype(self.dtype).transpose(0, 2, 1, 3)   # (B,1,S,R)
        fold = ragged and s == 1
        kw = {}
        if paged:
            kw["block_table"] = block_table.value
        if chunk_lengths is not None:
            # A row with nothing valid in this chunk (a decoding slot riding
            # a refill, a frozen slot riding a decode step) is not attended
            # at all: at 32 heads over 576 lanes its blocks are real work.
            kw["row_enable"] = chunk_lengths
        if fold:
            kw["kv_new"] = rows_sm
            if chunk_lengths is not None:
                kw["write_enable"] = chunk_lengths
        elif paged:
            pages, slots = paged_slots(
                block_table.value, idx, s, page, chunk_lengths, length
            )
            cached.value = cached.value.at[pages, 0, slots].set(rows_sm[:, 0])
        elif ragged:
            write = (
                functools.partial(row_update_masked, lengths=chunk_lengths)
                if chunk_lengths is not None else row_update
            )
            cached.value = write(cached.value, rows_sm, idx, seq_dim=2)
        else:
            cached.value = jax.lax.dynamic_update_slice(
                cached.value, rows_sm, (0, 0, idx, 0)
            )
        result = decode_attention(
            q_cat, cached.value, idx, scale=scale, latent_v=self.kv_rank,
            block_k=self.decode_block_k, **kw,
        )
        if not fold:
            return result
        out, cached.value = result
        return out
