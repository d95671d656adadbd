"""Composed transformer (case 7): the FF + attention blocks as one model.

The reference stops at a standalone attention module
(`/root/reference/case6_attention.py:42-143`) and a standalone GSPMD
feed-forward matmul (`/root/reference/case4_gspmd_ff.py:36-58`); the driver's
north star composes them into "a minimal transformer training step under a 2D
(data × model) mesh … ≥45% MFU" (`/root/repo/BASELINE.json`). This module is
that composition:

* :class:`FeedForward` — the case-4 DP×MP projection as a module: up-kernel
  logically ``(EMBED, MLP)`` (column-parallel), down-kernel ``(MLP, EMBED)``
  (row-parallel) — under ``RULES_DP_TP`` each token crosses the model axis
  once per block, the GSPMD §3.2 pattern;
* :class:`TransformerBlock` — pre-LayerNorm attention + FF with residuals;
* :class:`Transformer` — token embedding, N blocks (optionally rematerialized),
  final norm, logits head: the 125M-parameter flagship configuration of
  `BASELINE.json` ("case4+case6 composed 125M transformer").

Block kinds (a config picks one attention and, per layer, one feed-forward):

* attention: :class:`~.attention.MultiHeadAttention` (MHA / GQA / MQA,
  learned positions or RoPE, one window: the GPT-2 cells and every test
  preset) or :class:`~.attention.LatentAttention` when
  ``latent_kv_rank`` is set (JoyAI-LLM-Flash);
* feed-forward: two matrices with GELU (GPT-2), or three with a SiLU gate
  (``ff_gated``); or an expert layer from ``models/moe.py`` when
  ``num_experts > 0`` — in every block, or from block ``first_k_dense`` on
  (JoyAI-LLM-Flash: one dense layer, then expert layers);
* or, with ``layer_pattern``, ONE mixer a layer (:class:`MixerBlock`,
  ``x + Mixer(norm(x))``): ``M`` a Mamba-2 layer (``models/ssm.py``), ``E``
  an expert layer, ``*`` attention (the ``nemotron_h`` family);
* or, with ``layer_types``, the block's OPERATOR by layer: ``"conv"`` a gated
  short convolution (``models/ssm.py::ShortConv``) or ``"full_attention"``,
  each followed by the layer's feed-forward as above (the ``lfm2_moe``
  family: q / k RMSNorm a head, a head tied to the embedding).

Everything is dtype-parameterized: bf16 compute / fp32 params is the TPU MXU
sweet spot and the default for benchmarks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.ad_checkpoint import checkpoint_name

from learning_jax_sharding_tpu.models.attention import (
    LatentAttention,
    MultiHeadAttention,
)
from learning_jax_sharding_tpu.parallel.logical import (
    BATCH,
    EMBED,
    HIDDEN,
    LAYERS,
    MLP,
    SEQ,
    VOCAB,
)


def resolve_remat_policy(name: Optional[str]):
    """Named ``jax.checkpoint`` policies for block rematerialization, the
    same for every block.

    ``"nothing"`` — save nothing, recompute everything (the
    ``jax.checkpoint`` default; minimum memory, ~1/3 extra FLOPs);
    ``"dots"`` — save matmul outputs, recompute only elementwise/softmax work
    (most of the memory win at a fraction of the recompute);
    ``"dots_no_batch"`` — save only batch-free matmuls (i.e. none in a
    transformer block: everything carries the batch dim, so this is the
    conservative middle ground XLA offload papers use).

    ``None`` is none of these: "keep what the chip has room for", a policy
    by block that :func:`block_remat_policies` resolves from the train
    step's memory. Here it maps, like ``"nothing"``, to no policy.
    """
    if name is None or name == "nothing":
        return None
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat_policy {name!r}: expected None, 'nothing', "
            f"'dots', or 'dots_no_batch'"
        )
    return policies[name]


def block_remat_policies(
    cfg: "TransformerConfig", batch: int, seq: int, *, uniform: bool = False
) -> list:
    """The ``jax.checkpoint`` policy of each block under ``cfg.remat``: THE
    resolver of every rematerialization site (the unrolled stack, the
    scanned one, ``models/pipelined.py``'s stage).

    An explicit ``cfg.remat_policy`` is every block's. ``None`` keeps what
    fits: the train step that traces this model says what its device has
    left (``utils.memory.remat_scope``, set by ``make_train_step``), and
    ``utils.memory.remat_plan`` picks, from that and the traced ``batch`` x
    ``seq``, which of a block's NAMED residuals
    (``utils.memory.REMAT_GROUPS``) ``save_only_these_names`` keeps. With no
    scope around the trace (a bare ``apply``), or a device whose memory is
    unknown (the emulated CPU mesh), nothing is kept: full recomputation.
    ``uniform``: one plan for all blocks (a scanned stack traces one).
    """
    if cfg.remat_policy is not None:
        return [resolve_remat_policy(cfg.remat_policy)] * cfg.num_layers
    from learning_jax_sharding_tpu.utils.memory import current_remat_scope

    scope = current_remat_scope()
    if scope is None:
        return [None] * cfg.num_layers
    plan = scope.resolve(cfg, batch, seq, uniform=uniform)
    # One policy object a distinct set of names: the stack builds one
    # rematerialized block class for each.
    by_names = {
        names: jax.checkpoint_policies.save_only_these_names(*names)
        for names in set(plan.names) if names
    }
    return [by_names.get(names) for names in plan.names]


class _CompressedDense(nn.Module):
    """Param-compatible stand-in for a projection ``nn.Dense`` whose TP
    reduction ships int8 blocks instead of floats.

    Declares the identical ``kernel`` (and ``bias``) parameters — same
    name, shape, dtype, init, and logical axes — so a checkpoint or a
    born-sharded init transfers verbatim across the ``comm_compress_fn``
    flag, exactly like :class:`~..models.quantize.Int4Dense` mirrors its
    plain twin. The compute is delegated to ``compress_fn`` (built by
    ``parallel.compression.make_compressed_matmul_fn``), which reads the
    live :class:`~..parallel.compression.CommCompression` policy at TRACE
    time: compression on → shard_map with quantized all-gathers;
    off (never configured, axis not wire-bound, or drift-tripped) → the
    very ``dot_general`` ``nn.Dense`` lowers to, bit-identical.
    """

    features: int
    kernel_axes: tuple
    use_bias: bool = False
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    compress_fn: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(self.kernel_init, self.kernel_axes),
            (x.shape[-1], self.features),
            self.param_dtype,
        )
        x = x.astype(self.dtype)
        kernel = kernel.astype(self.dtype)
        y = self.compress_fn(x, kernel, kernel_axes=tuple(self.kernel_axes))
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (self.kernel_axes[-1],)
                ),
                (self.features,),
                self.param_dtype,
            )
            y = y + bias.astype(y.dtype)
        return y


def zero_mean(init: Callable, axis: int) -> Callable:
    """``init`` with its mean over ``axis`` (the input axis) removed: a
    projection that follows a non-negative or biased activation (relu^2, a
    silu-gated norm) then has no gain for that activation's MEAN, which a
    plain initialiser sends to every token as the SAME vector. A trained
    model has no such common mode; :class:`MixerBlock` gives seeded weights
    none this way (PERF.md, PR 33)."""

    def centred(key, shape, dtype=jnp.float32):
        w = init(key, shape, jnp.float32)
        return (w - w.mean(axis, keepdims=True)).astype(dtype)

    return centred


class FeedForward(nn.Module):
    """Position-wise FF: up-project → GELU → down-project; with ``gated``
    the three-matrix SiLU form ``(silu(x W_gate) * x W_up) W_down``.

    The case-4 feed-forward (`/root/reference/case4_gspmd_ff.py:36-58`) grown
    into a real module: with MLP→model rules the up-projection is
    column-parallel and the down-projection row-parallel, so its output
    arrives as partial sums that GSPMD all-reduces (or reduce-scatters under
    sequence sharding) — one collective per block, the minimum for TP.
    """

    features: int
    hidden: int
    use_bias: bool = False
    gated: bool = False
    activation: str = "gelu"      # the two-matrix form's: "gelu" | "relu2"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    down_init: Optional[Callable] = None     # the down projection's own
                                             # (None: kernel_init)
    quantization: Optional[str] = None       # "int4" → fused-kernel serving
    quantization_group: int = 128
    quantized_matmul_fn: Optional[Callable] = None
    comm_compress_fn: Optional[Callable] = None  # int8-wire TP reduction for
                                  # the down projection (the block's one
                                  # all-reduce site); built by
                                  # parallel.compression.make_compressed_matmul_fn

    def _dense(self, features: int, kernel_axes, name: str, kernel_init=None):
        from learning_jax_sharding_tpu.models.quantize import projection_dense

        return projection_dense(
            quantization=self.quantization,
            features=features,
            kernel_axes=kernel_axes,
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=kernel_init or self.kernel_init,
            group_size=self.quantization_group,
            quantized_matmul_fn=self.quantized_matmul_fn,
            name=name,
        )

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))
        k = x.shape[-1]
        if self._use_fused_ff(k):
            # Whole-FF fused kernel: up, GELU, and down in ONE pallas call —
            # the hidden activation never leaves VMEM, and decode's serial
            # launch chain shrinks by one dependent kernel per block
            # (PERF.md "int4 decode: where the time actually goes").
            # Single-device/replicated serving only: under TP the hidden dim
            # is sharded and the per-projection shard_map path applies.
            from learning_jax_sharding_tpu.models.quantize import Int4ProjParams
            from learning_jax_sharding_tpu.ops.int4_ff import int4_ff

            g = self.quantization_group
            q4_up, s_up = Int4ProjParams(
                k // 2, self.hidden, k // min(g, k), name="up"
            )()
            q4_dn, s_dn = Int4ProjParams(
                self.hidden // 2, self.features,
                self.hidden // min(g, self.hidden), name="down",
            )()
            out = int4_ff(
                x.astype(self.dtype), q4_up, s_up, q4_dn, s_dn, group=g
            )
            return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))
        # The activation's inputs are residuals a rematerialized block may
        # keep (utils.memory.REMAT_GROUPS); a name is an identity elsewhere.
        h = self._dense(self.hidden, (EMBED, MLP), "up")(x)
        h = checkpoint_name(
            nn.with_logical_constraint(h, (BATCH, SEQ, HIDDEN)), "ff_up"
        )
        if self.gated:
            g = self._dense(self.hidden, (EMBED, MLP), "gate")(x)
            g = checkpoint_name(
                nn.with_logical_constraint(g, (BATCH, SEQ, HIDDEN)), "ff_gate"
            )
            h = nn.silu(g) * h
        elif self.activation == "relu2":
            h = jnp.square(nn.relu(h))
        elif self.activation == "gelu":
            h = nn.gelu(h)
        else:
            raise ValueError(
                f"unknown activation {self.activation!r}: 'gelu' or 'relu2'"
            )
        if self.comm_compress_fn is not None and self.quantization is None:
            # The down projection is the block's one all-reduce site (the
            # up projection is column-parallel, collective-free): swap in
            # the param-identical compressed dense so the reduction ships
            # int8 blocks when the engine's CommCompression policy is live.
            out = _CompressedDense(
                features=self.features,
                kernel_axes=(MLP, EMBED),
                use_bias=self.use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=self.down_init or self.kernel_init,
                compress_fn=self.comm_compress_fn,
                name="down",
            )(h)
        else:
            out = self._dense(
                self.features, (MLP, EMBED), "down", self.down_init
            )(h)
        return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))

    def _use_fused_ff(self, k: int) -> bool:
        from learning_jax_sharding_tpu.ops.int4_ff import int4_ff_eligible

        return (
            self.quantization == "int4"
            and self.quantized_matmul_fn is None
            and not self.use_bias
            and not self.gated
            and self.activation == "gelu"
            and self.features == k
            and int4_ff_eligible(k, self.hidden, self.quantization_group)
        )


def make_norm(kind: str, dtype, param_dtype, name: str, eps: float = 1e-6) -> nn.Module:
    """``"layernorm"`` (GPT-2 style, scale+bias) or ``"rmsnorm"`` (LLaMA
    style, scale only — one fewer reduction and parameter vector; the modern
    default). Scale/bias carry the ``(EMBED,)`` logical axis either way."""
    if kind == "layernorm":
        return nn.LayerNorm(
            epsilon=eps,
            dtype=dtype,
            param_dtype=param_dtype,
            scale_init=nn.with_logical_partitioning(nn.initializers.ones_init(), (EMBED,)),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), (EMBED,)),
            name=name,
        )
    if kind == "rmsnorm":
        return nn.RMSNorm(
            epsilon=eps,
            dtype=dtype,
            param_dtype=param_dtype,
            scale_init=nn.with_logical_partitioning(nn.initializers.ones_init(), (EMBED,)),
            name=name,
        )
    raise ValueError(f"unknown norm {kind!r}: expected 'layernorm' or 'rmsnorm'")


class FusedNorm(nn.Module):
    """Param-compatible replacement for :func:`make_norm` backed by the
    Pallas fused residual+norm kernel (``ops/fused_norm.py``): identical
    ``scale``/``bias`` param names, shapes, and ``(EMBED,)`` logical axes
    as ``nn.LayerNorm``/``nn.RMSNorm``, so checkpoints transfer verbatim
    across the ``fused_norm`` flag. Called as ``module(x, resid)`` →
    ``(normed, x + resid)`` — the whole block boundary (residual add +
    norm) in one HBM pass. Single-device oriented: GSPMD cannot partition
    the custom call, so multi-device training should keep the flag off
    (the math is identical either way)."""

    kind: str
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, resid=None):
        from learning_jax_sharding_tpu.ops.fused_norm import (
            fused_residual_norm,
        )

        m = x.shape[-1]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), (EMBED,)),
            (m,), self.param_dtype,
        )
        bias = None
        if self.kind == "layernorm":
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (EMBED,)
                ),
                (m,), self.param_dtype,
            )
        x = x.astype(self.dtype)
        if resid is not None:
            resid = resid.astype(self.dtype)
        return fused_residual_norm(
            x, resid, scale, bias, eps=self.eps, kind=self.kind
        )


class TransformerBlock(nn.Module):
    """Pre-LN block: x + Attn(LN(x)); x + FF(LN(x)).

    The composition BASELINE.json names "case4+case6": case-6's logically
    partitioned attention and case-4's DP×MP feed-forward, joined by residuals
    and LayerNorms (neither exists in the reference).
    """

    features: int
    num_heads: int
    head_dim: int
    hidden: int
    num_kv_heads: Optional[int] = None
    rope: bool = False
    rope_theta: float = 10_000.0
    window: Optional[int] = None
    dropout_rate: float = 0.0
    causal: bool = True
    use_bias: bool = False
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    attn_fn: Optional[Callable] = None
    remat_attention: bool = False
    num_experts: int = 0          # >0 swaps the dense FF for a routed MoE FF
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "einsum"  # "einsum" (EP-shardable) | "scatter"
                                  # (scatter/gather, single-device) |
                                  # "alltoall" (explicit EP exchange; needs
                                  # moe_dispatch_fn — moe.py/moe_dispatch.py)
    moe_dispatch_fn: Optional[Callable] = None
    decode: bool = False          # KV-cached autoregressive attention
    max_decode_len: int = 0
    kv_cache_dtype: Optional[Any] = None  # decode-cache storage: None =
                                  # compute dtype; jnp.int8 = quantized cache
    decode_attention: str = "auto"  # "dense" | "blocked" | "auto" (see
                                  # models.attention.MultiHeadAttention)
    decode_block_k: Optional[int] = None
    decode_attn_fn: Optional[Callable] = None
    decode_ragged: bool = False   # per-row cache positions (mixed-length
                                  # serving; see models.attention)
    decode_paged: bool = False    # paged KV pools + host-owned block tables
    decode_page_count: int = 0
    quantization: Optional[str] = None   # "int4" → fused-kernel projections
    quantization_group: int = 128
    quantized_matmul_fn: Optional[Callable] = None
    comm_compress_fn: Optional[Callable] = None  # int8-wire FF down reduction
    norm: str = "layernorm"       # "layernorm" | "rmsnorm"
    fused_norm: bool = False      # block boundaries through the Pallas
                                  # fused residual+norm kernel (param-tree
                                  # identical; see FusedNorm)
    scan: bool = False            # under nn.scan: return (x, None) pairs
    # Latent attention and the gated / dropless feed-forwards: the config's
    # fields of the same names (TransformerConfig).
    latent_kv_rank: Optional[int] = None
    latent_q_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    latent_absorbed: bool = False
    ff_gated: bool = False
    moe_routing: str = "softmax_capacity"
    moe_hidden: Optional[int] = None
    moe_shared_experts: int = 0
    moe_routed_scaling: float = 1.0
    moe_experts: str = "auto"
    moe_held: Optional[tuple] = None
    moe_renorm_eps: float = 1e-20
    moe_bias_init_std: float = 0.0
    moe_expert_init_scale: Optional[float] = None
    operator: str = "full_attention"   # or "conv": ShortConv in the
                                  # attention's place (norm ``ln_conv``)
    conv_kernel: int = 3
    qk_norm: bool = False

    def _norm(self, name: str):
        if self.fused_norm:
            return FusedNorm(
                kind=self.norm, eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name,
            )
        mod = make_norm(
            self.norm, self.dtype, self.param_dtype, name, self.norm_eps
        )
        return lambda x, resid=None: (
            (mod(x), x) if resid is None else (mod(x + resid), x + resid)
        )

    @nn.compact
    def __call__(
        self, x: jax.Array, deterministic: bool = True, chunk_lengths=None
    ):
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))
        if self.operator == "conv":
            from learning_jax_sharding_tpu.models.ssm import ShortConv

            h, _ = self._norm("ln_conv")(x)
            return self._finish(
                x,
                ShortConv(
                    features=self.features, kernel=self.conv_kernel,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="conv",
                )(h),
                deterministic, chunk_lengths,
            )
        h, _ = self._norm("ln_attn")(x)
        if self.latent_kv_rank:
            return self._finish(
                x,
                LatentAttention(
                    features=self.features,
                    num_heads=self.num_heads,
                    q_rank=self.latent_q_rank,
                    kv_rank=self.latent_kv_rank,
                    nope_dim=self.qk_nope_dim,
                    rope_dim=self.qk_rope_dim,
                    v_dim=self.v_head_dim,
                    rope_theta=self.rope_theta,
                    norm_eps=self.norm_eps,
                    causal=self.causal,
                    absorbed=self.latent_absorbed,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    decode=self.decode,
                    max_decode_len=self.max_decode_len,
                    decode_block_k=self.decode_block_k,
                    decode_ragged=self.decode_ragged,
                    decode_paged=self.decode_paged,
                    decode_page_count=self.decode_page_count,
                    name="attn",
                )(h, deterministic=deterministic, chunk_lengths=chunk_lengths),
                deterministic, chunk_lengths,
            )
        attn_out = MultiHeadAttention(
            features=self.features,
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            num_kv_heads=self.num_kv_heads,
            rope=self.rope,
            rope_theta=self.rope_theta,
            window=self.window,
            dropout_rate=self.dropout_rate,
            causal=self.causal,
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            attn_fn=self.attn_fn,
            remat_attention=self.remat_attention,
            decode=self.decode,
            max_decode_len=self.max_decode_len,
            kv_cache_dtype=self.kv_cache_dtype,
            decode_attention=self.decode_attention,
            decode_block_k=self.decode_block_k,
            decode_attn_fn=self.decode_attn_fn,
            decode_ragged=self.decode_ragged,
            decode_paged=self.decode_paged,
            decode_page_count=self.decode_page_count,
            quantization=self.quantization,
            quantization_group=self.quantization_group,
            quantized_matmul_fn=self.quantized_matmul_fn,
            qk_norm=self.qk_norm,
            norm_eps=self.norm_eps,
            name="attn",
        )(h, deterministic=deterministic, chunk_lengths=chunk_lengths)
        return self._finish(x, attn_out, deterministic, chunk_lengths)

    def _finish(self, x, attn_out, deterministic, chunk_lengths):
        """The second half of a block: residual add, norm, feed-forward."""
        # A rematerialized block may keep the operator's output
        # (utils.memory.REMAT_GROUPS); a name is an identity elsewhere.
        attn_out = checkpoint_name(attn_out, "operator_out")
        # The block boundary: residual add + norm — ONE fused HBM pass
        # under fused_norm, the plain pair otherwise (identical math).
        h, x = self._norm("ln_ff")(attn_out, x)
        if self.num_experts > 0 and self.moe_routing == "sigmoid_dropless":
            from learning_jax_sharding_tpu.models.moe import DroplessMoE

            valid = None
            if chunk_lengths is not None:
                valid = (
                    jnp.arange(h.shape[1])[None, :] < chunk_lengths[:, None]
                )
            x = x + DroplessMoE(
                features=self.features,
                hidden=self.moe_hidden or self.hidden,
                num_experts=self.num_experts,
                top_k=self.moe_top_k,
                shared_experts=self.moe_shared_experts,
                routed_scaling=self.moe_routed_scaling,
                held=self.moe_held,
                renorm_eps=self.moe_renorm_eps,
                bias_init_std=self.moe_bias_init_std,
                expert_init_scale=self.moe_expert_init_scale,
                experts=self.moe_experts,
                count=self.decode,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="moe",
            )(h, valid=valid)
        elif self.num_experts > 0:
            from learning_jax_sharding_tpu.models.moe import MoEFeedForward

            x = x + MoEFeedForward(
                features=self.features,
                hidden=self.hidden,
                num_experts=self.num_experts,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                dispatch=self.moe_dispatch,
                dispatch_fn=self.moe_dispatch_fn,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="moe",
            )(h, deterministic=deterministic)
        else:
            x = x + FeedForward(
                features=self.features,
                hidden=self.hidden,
                use_bias=self.use_bias,
                gated=self.ff_gated,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                quantization=self.quantization,
                quantization_group=self.quantization_group,
                quantized_matmul_fn=self.quantized_matmul_fn,
                comm_compress_fn=self.comm_compress_fn,
                name="ff",
            )(h)
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))
        # nn.scan's carry protocol wants (carry, per-step output) pairs.
        return (x, None) if self.scan else x


#: ``TransformerConfig.layer_types``' entries (the ``lfm2`` family's key).
OPERATOR_KINDS = ("conv", "full_attention")

#: ``TransformerConfig.layer_pattern``'s characters (the ``nemotron_h``
#: family's ``hybrid_override_pattern``; its "-", a dense MLP layer, is not
#: built).
MIXER_KINDS = {"M": "Mamba-2", "E": "expert layer", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Model hyperparameters (the reference hard-codes its dims inline,
    `/root/reference/case6_attention.py:149-151`; SURVEY.md §5 asks for a
    config object)."""

    vocab_size: int = 50304          # GPT-2 vocab rounded up to a 128 multiple
    num_layers: int = 12
    features: int = 768
    num_heads: int = 12
    head_dim: int = 64
    num_kv_heads: Optional[int] = None  # < num_heads → GQA; 1 → MQA
    rope: bool = False               # rotary positions instead of the learned table
    rope_theta: float = 10_000.0
    window: Optional[int] = None     # causal sliding-window attention size
    hidden: int = 3072
    max_seq_len: int = 1024
    dropout_rate: float = 0.0
    causal: bool = True
    use_bias: bool = False           # biases on all projections (GPT-2 style)
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False              # rematerialize each block's activations:
                                     # keep what the chip has room for and
                                     # recompute the rest in the backward
    remat_policy: Optional[str] = None  # what remat SAVES: None = the named
                                     # residuals that fit the train step's
                                     # free memory (block_remat_policies;
                                     # nothing where no step says what is
                                     # free); 'nothing' (recompute all),
                                     # 'dots', 'dots_no_batch' for every block
                                     # (resolve_remat_policy)
    remat_attention: bool = False    # rematerialize only the O(S²) attention
                                     # internals (cheap; lifts the batch cap)
    scan_layers: bool = False        # one nn.scan'd stacked block instead of
                                     # N unrolled blocks: O(1) compile time in
                                     # depth, params gain a leading (LAYERS,)
                                     # dim; math is identical (tests prove it)
    attn_fn: Optional[Callable] = None
    num_experts: int = 0             # >0: MoE FF in every block (EP over mesh)
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "einsum"     # routing implementation (models/moe.py):
                                     # "einsum" shards under EP rules;
                                     # "scatter" deletes the O(E*C*M*T) routing
                                     # FLOPs via scatter/gather (1-device);
                                     # "alltoall" explicit EP exchange (set
                                     # moe_dispatch_fn = make_moe_a2a_fn(mesh))
    moe_dispatch_fn: Optional[Callable] = None
    norm: str = "layernorm"          # "layernorm" | "rmsnorm"
    fused_norm: bool = False         # block boundaries (residual add + norm)
                                     # through the Pallas fused kernel
                                     # (ops/fused_norm.py); param-tree
                                     # identical to the plain path, so the
                                     # flag can flip on existing checkpoints.
                                     # Single-device oriented (GSPMD cannot
                                     # partition the custom call)
    decode: bool = False             # inference mode: KV cache, chunked input
    kv_cache_dtype: Optional[Any] = None  # decode KV-cache storage dtype:
                                     # None = compute dtype; jnp.int8 =
                                     # quantized cache with per-(token, head)
                                     # scales (~half the cache bytes of bf16)
    decode_attention: str = "auto"   # decode-attention backend: "dense"
                                     # (attend the whole cache buffer),
                                     # "blocked" (length-aware Pallas kernel,
                                     # ops/decode_attention.py), or "auto"
                                     # (blocked on TPU, dense elsewhere)
    decode_block_k: Optional[int] = None  # blocked-backend cache block size
    decode_attn_fn: Optional[Callable] = None  # mesh-aware blocked-kernel
                                     # override (make_decode_attn_fn);
                                     # injected by the serving entry points
    decode_ragged: bool = False      # per-row cache positions: mixed-length
                                     # prompt batches serve at each row's own
                                     # length (ragged prefill + independent
                                     # row advance; models.attention)
    decode_paged: bool = False       # PAGED KV cache: per-layer physical page
                                     # POOLS (decode_page_count pages of
                                     # decode_block_k tokens each) indirected
                                     # through per-row block tables — cache
                                     # HBM scales with allocated pages, not
                                     # B × max_seq_len. Requires decode_ragged
                                     # + the blocked backend + an explicit
                                     # decode_block_k (the page size); the
                                     # host allocator owns the tables
                                     # (models/serving.py)
    decode_page_count: int = 0       # physical pages per layer pool, incl.
                                     # the reserved scratch page 0
    quantization: Optional[str] = None  # "int4": every projection consumes a
                                     # quantize_tree(bits=4) tree verbatim
                                     # through the fused dequant-matmul
                                     # kernel (serving path; ops/int4_matmul)
    quantization_group: int = 128    # must match quantize_tree group_size
    quantized_matmul_fn: Optional[Callable] = None  # mesh-aware fused-int4
                                     # matmul (make_int4_matmul_fn); injected
                                     # by make_generate_fn on >1-device meshes
    comm_compress_fn: Optional[Callable] = None  # int8-wire TP reduction for
                                     # the FF down projection
                                     # (parallel/compression.py's
                                     # make_compressed_matmul_fn); injected by
                                     # ContinuousEngine(comm_compression=...);
                                     # param-tree identical to the plain path
    # --- latent attention (models.attention.LatentAttention) --------------
    latent_kv_rank: Optional[int] = None  # set: q through a rank-
                                     # latent_q_rank bottleneck, k/v from ONE
                                     # latent of this rank a token plus one
                                     # shared rotary key; num_heads heads of
                                     # qk_nope_dim + qk_rope_dim (scores) and
                                     # v_head_dim (values); head_dim,
                                     # num_kv_heads, window and rope are then
                                     # unused (positions are the rotary key's)
    latent_q_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    latent_absorbed: bool = False    # the NORMAL path's form: expanded
                                     # (per-head k and v) or absorbed (heads
                                     # against the shared latent row); decode
                                     # is always absorbed
    # --- feed-forward kinds ------------------------------------------------
    ff_gated: bool = False           # (silu(x W_gate) * x W_up) W_down
    first_k_dense: int = 0           # with num_experts > 0: blocks before
                                     # this one keep the dense FF (hidden)
    moe_routing: str = "softmax_capacity"  # models/moe.py: that rule
                                     # (softmax, capacity, drops:
                                     # MoEFeedForward) or "sigmoid_dropless"
                                     # (DroplessMoE: sigmoid scores, selection
                                     # bias, renormalised x moe_routed_scaling,
                                     # gated experts, shared experts)
    moe_hidden: Optional[int] = None  # expert width (None: hidden)
    moe_shared_experts: int = 0      # always-on experts beside the routed
    moe_routed_scaling: float = 1.0
    moe_experts: str = "auto"        # dropless expert compute: "pallas"
                                     # (ops/moe_experts.py), "ragged" (sorted
                                     # XLA ragged_dot) or "auto" (pallas on TPU)
    moe_held: Optional[tuple] = None  # (first, count): the experts THIS chip
                                     # holds of num_experts (an expert-parallel
                                     # group's share); the router keeps
                                     # num_experts outputs, a pick outside the
                                     # range is computed nowhere here
    moe_expert_act: str = "silu_gated"  # DroplessMoE's expert form: that, or
                                     # "relu2" (relu(x W_up)^2 W_down, no gate)
    moe_latent: int = 0              # > 0: routed experts live in a latent of
                                     # this width (features -> latent before
                                     # them, latent -> features after)
    moe_shared_hidden: Optional[int] = None  # the shared expert's width
                                     # (None: moe_shared_experts x moe_hidden)
    moe_expert_init_scale: Optional[float] = None  # a number: DroplessMoE's
                                     # experts start at each expert's OWN
                                     # fan-in, `down` times this; None: the
                                     # relu2 experts the same at 1, the gated
                                     # ones lecun_normal over the (E, in, out)
                                     # tensor (initialisation only)
    moe_renorm_eps: float = 1e-20    # added to the picks' score sum before
                                     # the weights are divided by it
    moe_bias_init_std: float = 0.0   # the selection bias is N(0, this) at
                                     # initialisation (0: zeros); it only
                                     # selects, and no optimizer step moves
                                     # it (its box: parallel.logical.Unstepped)
    # --- the block's operator by layer (TransformerBlock.operator) ---------
    layer_types: Optional[tuple] = None  # one entry a layer: "conv" (gated
                                     # short convolution, models/ssm.py) or
                                     # "full_attention"; None: attention in
                                     # every block. The feed-forward follows
                                     # first_k_dense / num_experts as ever
    conv_kernel: int = 3             # the short convolution's taps
    qk_norm: bool = False            # RMSNorm over each head of q and of k
                                     # (one learned vector each), before RoPE
    tie_embeddings: bool = False     # logits = hidden . tok_embed^T: no
                                     # lm_head parameters
    # --- one mixer a layer (MixerBlock) ------------------------------------
    layer_pattern: Optional[str] = None  # one character a layer: "M" Mamba-2,
                                     # "E" expert layer, "*" attention; None:
                                     # every block is attention + feed-forward
    no_positions: bool = False       # neither a learned table nor rotations
                                     # (the recurrent layers carry position)
    ssm_heads: int = 0               # Mamba-2 (models/ssm.py): heads of
    ssm_head_dim: int = 0            # ssm_head_dim values, ssm_groups groups
    ssm_groups: int = 1              # of B and C, a state of ssm_state_size
    ssm_state_size: int = 0          # a (head, value), a depthwise causal
    ssm_conv_kernel: int = 4         # convolution, the chunked scan's tile
    ssm_chunk: int = 128

    def __post_init__(self):
        # Fail fast on typos; 'nothing' is what remat=False ignores anyway,
        # so only a policy that changes behavior demands remat=True.
        if resolve_remat_policy(self.remat_policy) is not None and not self.remat:
            raise ValueError(
                "remat_policy is set but remat=False — the policy would "
                "be silently ignored; set remat=True (or drop the policy)"
            )
        if self.moe_routing not in ("softmax_capacity", "sigmoid_dropless"):
            raise ValueError(
                f"unknown moe_routing {self.moe_routing!r}: "
                f"'softmax_capacity' or 'sigmoid_dropless'"
            )
        if self.moe_expert_act not in ("silu_gated", "relu2"):
            raise ValueError(
                f"unknown moe_expert_act {self.moe_expert_act!r}: "
                f"'silu_gated' or 'relu2'"
            )
        if self.no_positions and self.rope:
            raise ValueError("no_positions and rope exclude each other")
        if self.layer_pattern is not None:
            unknown = sorted(set(self.layer_pattern) - set(MIXER_KINDS))
            if unknown or len(self.layer_pattern) != self.num_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r} must hold "
                    f"num_layers = {self.num_layers} characters of "
                    f"{sorted(MIXER_KINDS)} ({MIXER_KINDS}); unknown: {unknown}"
                )
            if self.scan_layers or self.first_k_dense or self.latent_kv_rank:
                raise ValueError(
                    "layer_pattern builds one mixer a layer: scan_layers, "
                    "first_k_dense and latent attention do not apply to it"
                )
            if "M" in self.layer_pattern and not (
                self.ssm_heads and self.ssm_head_dim and self.ssm_state_size
            ):
                raise ValueError(
                    "an 'M' layer needs ssm_heads, ssm_head_dim and "
                    "ssm_state_size"
                )
            if "E" in self.layer_pattern and not (
                self.num_experts and self.moe_routing == "sigmoid_dropless"
            ):
                raise ValueError(
                    "an 'E' layer needs num_experts and "
                    "moe_routing='sigmoid_dropless'"
                )
        if self.first_k_dense and self.scan_layers:
            raise ValueError(
                "first_k_dense makes the blocks differ by layer: scan_layers "
                "stacks ONE block"
            )
        if self.layer_types is not None:
            unknown = sorted(set(self.layer_types) - set(OPERATOR_KINDS))
            if unknown or len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types must hold num_layers = {self.num_layers} "
                    f"entries of {OPERATOR_KINDS}; unknown: {unknown}"
                )
            if (
                self.scan_layers or self.layer_pattern is not None
                or self.latent_kv_rank
            ):
                raise ValueError(
                    "layer_types makes the blocks differ by layer and picks "
                    "between a convolution and grouped-query attention: "
                    "scan_layers, layer_pattern and latent attention do not "
                    "apply to it"
                )
            if self.decode and "conv" in self.layer_types:
                raise ValueError(
                    "a 'conv' layer has no cached form: the short "
                    "convolution's last inputs are not a slot's state yet"
                )
        if self.latent_kv_rank and not (
            self.latent_q_rank and self.qk_nope_dim and self.qk_rope_dim
            and self.v_head_dim
        ):
            raise ValueError(
                "latent_kv_rank needs latent_q_rank, qk_nope_dim, "
                "qk_rope_dim and v_head_dim"
            )
        if self.latent_kv_rank and (
            self.kv_cache_dtype is not None or self.quantization
            or self.attn_fn is not None or self.window is not None
        ):
            raise ValueError(
                "latent attention has one cache row a token in the compute "
                "dtype and its own attention paths: kv_cache_dtype, "
                "quantization, attn_fn and window do not apply to it"
            )
        if self.decode_paged:
            if not self.decode_ragged:
                raise ValueError(
                    "decode_paged requires decode_ragged=True (per-row "
                    "cache positions drive the block tables)"
                )
            if not self.decode_block_k:
                raise ValueError(
                    "decode_paged requires an explicit decode_block_k — "
                    "it is the page size"
                )
            if self.max_seq_len % self.decode_block_k:
                raise ValueError(
                    f"max_seq_len ({self.max_seq_len}) must be a multiple "
                    f"of the page size ({self.decode_block_k})"
                )
            if self.decode_page_count < 2:
                raise ValueError(
                    "decode_page_count must be >= 2 (page 0 is the "
                    "reserved scratch page)"
                )

    def train_step_flops(self, batch: int, seq: int) -> float:
        """Analytic model FLOPs of one train step (fwd + bwd ≈ 3× fwd).

        XLA's ``cost_analysis`` undercounts programs containing Pallas
        kernels (custom calls carry no FLOP estimate) and ``lax.scan`` loops
        (the body is counted once, not trip-count times) — measured on the
        v5e, the flash+fused-loss step reports 4.5T where 6.5T of model math
        runs. MFU accounting therefore uses this standard analytic count
        (PaLM-style): ``6 × matmul_params`` per token plus the attention
        einsums, with causal attention counted at half the S² (what a
        block-skipping kernel actually computes). Expert layers count a
        token's ACTIVATED parameters (its picks, the shared experts, the
        router); latent attention its expanded form.
        """
        if self.layer_pattern is not None:
            # A Mamba layer's scan is linear in seq and small beside its
            # projections: not counted.
            blocks = sum(
                self._mixer_params(kind, activated=True)[0]
                for kind in self.layer_pattern
            )
            attn_layers = self.layer_pattern.count("*")
        else:
            blocks = sum(
                self._operator_params(i) + self._ff_params(i, activated=True)
                for i in range(self.num_layers)
            )
            attn_layers = sum(
                self._operator(i) == "full_attention"
                for i in range(self.num_layers)
            )
        matmul_params = blocks + self.features * self.vocab_size   # lm_head
        qk_v = (
            self.qk_nope_dim + self.qk_rope_dim + self.v_head_dim
            if self.latent_kv_rank else 2 * self.head_dim
        )
        attn_per_token = (
            2 * seq * self.num_heads * qk_v * attn_layers
        ) * (0.5 if self.causal else 1.0)
        per_token = 6 * matmul_params + 3 * attn_per_token
        return float(per_token) * batch * seq

    @property
    def _attn_proj_params(self) -> int:
        """q + k + v + out projection params (k/v shrink under GQA; the
        five low-rank matrices of latent attention)."""
        if self.latent_kv_rank:
            n, qk = self.num_heads, self.qk_nope_dim + self.qk_rope_dim
            return (
                self.features * self.latent_q_rank
                + self.latent_q_rank * n * qk
                + self.features * (self.latent_kv_rank + self.qk_rope_dim)
                + self.latent_kv_rank * n * (self.qk_nope_dim + self.v_head_dim)
                + n * self.v_head_dim * self.features
            )
        kv_heads = self.num_kv_heads if self.num_kv_heads is not None else self.num_heads
        return (
            2 * self.features * self.num_heads * self.head_dim   # q + out
            + 2 * self.features * kv_heads * self.head_dim       # k + v
        )

    def _operator(self, layer: int) -> str:
        return "full_attention" if self.layer_types is None else self.layer_types[layer]

    def _operator_params(self, layer: int) -> int:
        """Matrix parameters of block ``layer``'s operator: the attention
        projections, or the short convolution's ``in_proj`` and ``out_proj``."""
        if self._operator(layer) == "conv":
            return 4 * self.features * self.features
        return self._attn_proj_params

    def _ff_params(self, layer: int, *, activated: bool = False) -> int:
        """Feed-forward matmul parameters of block ``layer``: all of them
        that live here, or with ``activated`` those one token multiplies by
        here (with ``moe_held`` the uniform expectation of its picks that
        fall on a held expert)."""
        mats = 3 if self.ff_gated else 2
        if not self.num_experts or layer < self.first_k_dense:
            return mats * self.features * self.hidden
        router = self.features * self.num_experts
        if self.moe_routing == "softmax_capacity":
            expert = 2 * self.features * self.hidden
            return router + expert * (self.moe_top_k if activated else self.num_experts)
        expert = 3 * self.features * (self.moe_hidden or self.hidden)
        held = self.moe_held[1] if self.moe_held else self.num_experts
        if activated:
            held = self.moe_top_k * held / self.num_experts
        return int(router + expert * (held + self.moe_shared_experts))

    def _mixer_params(self, kind: str, *, activated: bool = False) -> tuple:
        """``(matrix, vector)`` parameters of one ``layer_pattern`` layer of
        ``kind``, its norm among the vectors; with ``activated`` the
        matrices one token multiplies by (its picks, not the experts held)."""
        m = self.features
        if kind == "*":
            return self._attn_proj_params, m
        if kind == "M":
            d_inner = self.ssm_heads * self.ssm_head_dim
            conv_dim = d_inner + 2 * self.ssm_groups * self.ssm_state_size
            return (
                m * (d_inner + conv_dim + self.ssm_heads) + d_inner * m,
                m + (self.ssm_conv_kernel + 1) * conv_dim
                + 3 * self.ssm_heads + d_inner,
            )
        mats = 3 if self.moe_expert_act == "silu_gated" else 2
        width, hidden = self.moe_latent or m, self.moe_hidden or self.hidden
        held = self.moe_held[1] if self.moe_held else self.num_experts
        shared = self.moe_shared_experts and (
            self.moe_shared_hidden or self.moe_shared_experts * hidden
        )
        return (
            m * self.num_experts + 2 * m * self.moe_latent + mats * m * shared
            + mats * width * hidden * (self.moe_top_k if activated else held),
            m + self.num_experts,
        )

    @property
    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks + head; a norm
        counted as 2 vectors whichever kind it is; a ``layer_pattern``
        model's is exact)."""
        if self.layer_pattern is not None:
            blocks = sum(sum(self._mixer_params(k)) for k in self.layer_pattern)
            return 2 * self.vocab_size * self.features + blocks + self.features
        if self.layer_types is not None:
            return self._layer_types_param_count()
        blocks = sum(
            self._attn_proj_params + self._ff_params(i) + 4 * self.features
            for i in range(self.num_layers)
        )
        pos = 0 if self.rope or self.no_positions else self.max_seq_len * self.features
        embed = self.vocab_size * self.features + pos
        head = self.features * self.vocab_size
        return embed + blocks + 2 * self.features + head


    def _layer_types_param_count(self) -> int:
        """Exact count of a ``layer_types`` model with RMSNorm: embedding
        (and head unless tied), final norm, and a layer's two norms,
        operator (the convolution's taps, the q / k norms) and feed-forward
        (the router's selection bias)."""
        m, total = self.features, 0
        for i in range(self.num_layers):
            total += 2 * m + self._operator_params(i) + self._ff_params(i)
            if self._operator(i) == "conv":
                total += self.conv_kernel * m
            elif self.qk_norm:
                total += 2 * self.head_dim
            if self.num_experts and i >= self.first_k_dense:
                total += self.num_experts            # the selection bias
        heads = 1 if self.tie_embeddings else 2
        return total + heads * self.vocab_size * m + m


#: The BASELINE.json flagship: "case4+case6 composed 125M transformer".
#: 12 × 768 × 12 heads ≈ 124M parameters at GPT-2-small shape.
CONFIG_125M = TransformerConfig()

#: Small config for tests and the emulated-CPU dry run.
CONFIG_TINY = TransformerConfig(
    vocab_size=256,
    num_layers=2,
    features=64,
    num_heads=4,
    head_dim=16,
    hidden=128,
    max_seq_len=64,
    dtype=jnp.float32,
)

#: Tiny MoE variant: 4 experts, top-2 routing (expert-parallel under
#: RULES_DP_TP_EP).
CONFIG_TINY_MOE = dataclasses.replace(CONFIG_TINY, num_experts=4)


class MixerBlock(nn.Module):
    """One layer of a ``layer_pattern`` model: ``x + Mixer(norm(x))`` with
    ONE mixer, by ``kind``: ``"M"`` :class:`~.ssm.Mamba2Mixer` (``ssm``),
    ``"E"`` :class:`~.moe.DroplessMoE` (``moe``), ``"*"``
    :class:`~.attention.MultiHeadAttention` (``attn``). The norm is ``ln``.

    The projections that read a non-negative or biased activation (the
    relu^2 experts' ``down``, the shared expert's, Mamba's ``out_proj`` after
    its silu-gated norm) are initialised :func:`zero_mean`: plainly
    initialised, relu^2's mean reached every token as the same vector, and
    at NVIDIA-Nemotron-3-Super's widths 86-94 % of the normalised residual
    was common to all tokens by layers 6-10 (32 tokens touched 55-111 of
    512 experts where independent picks touch 383; PERF.md, PR 33)."""

    config: TransformerConfig
    kind: str

    @nn.compact
    def __call__(self, x, deterministic: bool = True, chunk_lengths=None):
        cfg = self.config
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))
        h = make_norm(cfg.norm, cfg.dtype, cfg.param_dtype, "ln", cfg.norm_eps)(x)
        if self.kind == "M":
            from learning_jax_sharding_tpu.models.ssm import Mamba2Mixer

            out = Mamba2Mixer(
                features=cfg.features,
                num_heads=cfg.ssm_heads,
                head_dim=cfg.ssm_head_dim,
                groups=cfg.ssm_groups,
                state_size=cfg.ssm_state_size,
                conv_kernel=cfg.ssm_conv_kernel,
                chunk=cfg.ssm_chunk,
                norm_eps=cfg.norm_eps,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                decode=cfg.decode,
                out_init=zero_mean(nn.initializers.lecun_normal(), 0),
                name="ssm",
            )(h, chunk_lengths=chunk_lengths)
        elif self.kind == "E":
            from learning_jax_sharding_tpu.models.moe import DroplessMoE

            valid = None
            if chunk_lengths is not None:
                valid = jnp.arange(h.shape[1])[None, :] < chunk_lengths[:, None]
            out = DroplessMoE(
                features=cfg.features,
                hidden=cfg.moe_hidden or cfg.hidden,
                num_experts=cfg.num_experts,
                top_k=cfg.moe_top_k,
                shared_experts=cfg.moe_shared_experts,
                shared_hidden=cfg.moe_shared_hidden,
                routed_scaling=cfg.moe_routed_scaling,
                held=cfg.moe_held,
                gated=cfg.moe_expert_act == "silu_gated",
                latent=cfg.moe_latent,
                expert_init_scale=cfg.moe_expert_init_scale,
                centred_down=True,
                experts=cfg.moe_experts,
                count=cfg.decode,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe",
            )(h, valid=valid)
        else:
            out = MultiHeadAttention(
                features=cfg.features,
                num_heads=cfg.num_heads,
                head_dim=cfg.head_dim,
                num_kv_heads=cfg.num_kv_heads,
                rope=cfg.rope,
                rope_theta=cfg.rope_theta,
                window=cfg.window,
                causal=cfg.causal,
                use_bias=cfg.use_bias,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                attn_fn=cfg.attn_fn,
                decode=cfg.decode,
                max_decode_len=cfg.max_seq_len if cfg.decode else 0,
                kv_cache_dtype=cfg.kv_cache_dtype,
                decode_attention=cfg.decode_attention,
                decode_block_k=cfg.decode_block_k,
                decode_attn_fn=cfg.decode_attn_fn,
                decode_ragged=cfg.decode_ragged,
                decode_paged=cfg.decode_paged,
                decode_page_count=cfg.decode_page_count,
                name="attn",
            )(h, deterministic=deterministic, chunk_lengths=chunk_lengths)
        return nn.with_logical_constraint(x + out, (BATCH, SEQ, EMBED))


class Transformer(nn.Module):
    """Decoder-only LM: embed → N blocks → final LN → logits.

    Token embedding carries logical ``(VOCAB, EMBED)``; the logits head
    ``(EMBED, VOCAB)`` — under TP rules mapping VOCAB→model the head is
    column-parallel, keeping the big vocab matmul sharded.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        *,
        deterministic: bool = True,
        return_hidden: bool = False,
        chunk_lengths: Optional[jax.Array] = None,
        logit_positions: Optional[jax.Array] = None,
    ) -> jax.Array:
        """``chunk_lengths``: ragged decode only (``config.decode_ragged``)
        — per-row valid-token count of this chunk; see
        ``models.attention.MultiHeadAttention.__call__``.
        ``logit_positions`` ``(B,)``: the final norm and the head run on
        that ONE position a row and the logits come back ``(B, 1, V)`` (a
        refill chunk needs its last valid position's logits only; at a
        vocabulary of 129,280 the full ``(B, S, V)`` is gigabytes)."""
        cfg = self.config
        b, s = tokens.shape
        if s > cfg.max_seq_len:
            raise ValueError(f"sequence length {s} exceeds max_seq_len {cfg.max_seq_len}")
        if chunk_lengths is not None and not (cfg.decode and cfg.decode_ragged):
            raise ValueError(
                "chunk_lengths requires decode=True and decode_ragged=True"
            )

        embed = nn.Embed(
            cfg.vocab_size,
            cfg.features,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)
            ),
            name="tok_embed",
        )
        if cfg.rope or cfg.no_positions:
            # Positions enter as rotations inside each attention layer
            # (ops/rope.py), or not at all (no_positions: the recurrent
            # layers carry them) — no learned table, no position counter
            # here (the per-layer KV caches track their own indices in
            # decode mode).
            x = embed(tokens)
        else:
            pos_embed = self.param(
                "pos_embed",
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), (SEQ, EMBED)
                ),
                (cfg.max_seq_len, cfg.features),
                cfg.param_dtype,
            )
            if cfg.decode:
                # Chunked autoregressive input: this chunk's absolute
                # positions continue from the running cache position (the
                # per-module KV caches keep their own matching indices).
                # Ragged: a (B,) position counter and per-row gathers — rows
                # advance by their own valid counts.
                pos_var = self.variable(
                    "cache", "position",
                    lambda: jnp.zeros((b,) if cfg.decode_ragged else (), jnp.int32),
                )
                if cfg.decode_ragged:
                    positions = pos_var.value[:, None] + jnp.arange(s)  # (B,S)
                    pos_var.value = pos_var.value + (
                        s if chunk_lengths is None else chunk_lengths
                    )
                    pos_term = jnp.take(pos_embed, positions, axis=0)
                else:
                    positions = pos_var.value + jnp.arange(s)
                    pos_var.value = pos_var.value + s
                    pos_term = jnp.take(pos_embed, positions, axis=0)[None]
                x = embed(tokens) + pos_term.astype(cfg.dtype)
            else:
                x = embed(tokens) + pos_embed[None, :s].astype(cfg.dtype)
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))

        block_fields = dict(
            features=cfg.features,
            num_heads=cfg.num_heads,
            head_dim=cfg.head_dim,
            num_kv_heads=cfg.num_kv_heads,
            rope=cfg.rope,
            rope_theta=cfg.rope_theta,
            window=cfg.window,
            hidden=cfg.hidden,
            dropout_rate=cfg.dropout_rate,
            causal=cfg.causal,
            use_bias=cfg.use_bias,
            norm_eps=cfg.norm_eps,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            attn_fn=cfg.attn_fn,
            remat_attention=cfg.remat_attention,
            num_experts=cfg.num_experts,
            moe_top_k=cfg.moe_top_k,
            moe_capacity_factor=cfg.moe_capacity_factor,
            moe_dispatch=cfg.moe_dispatch,
            moe_dispatch_fn=cfg.moe_dispatch_fn,
            decode=cfg.decode,
            max_decode_len=cfg.max_seq_len if cfg.decode else 0,
            kv_cache_dtype=cfg.kv_cache_dtype,
            decode_attention=cfg.decode_attention,
            decode_block_k=cfg.decode_block_k,
            decode_attn_fn=cfg.decode_attn_fn,
            decode_ragged=cfg.decode_ragged,
            decode_paged=cfg.decode_paged,
            decode_page_count=cfg.decode_page_count,
            quantization=cfg.quantization,
            quantization_group=cfg.quantization_group,
            quantized_matmul_fn=cfg.quantized_matmul_fn,
            comm_compress_fn=cfg.comm_compress_fn,
            norm=cfg.norm,
            fused_norm=cfg.fused_norm,
            latent_kv_rank=cfg.latent_kv_rank,
            latent_q_rank=cfg.latent_q_rank,
            qk_nope_dim=cfg.qk_nope_dim,
            qk_rope_dim=cfg.qk_rope_dim,
            v_head_dim=cfg.v_head_dim,
            latent_absorbed=cfg.latent_absorbed,
            ff_gated=cfg.ff_gated,
            moe_routing=cfg.moe_routing,
            moe_hidden=cfg.moe_hidden,
            moe_shared_experts=cfg.moe_shared_experts,
            moe_routed_scaling=cfg.moe_routed_scaling,
            moe_experts=cfg.moe_experts,
            moe_held=cfg.moe_held,
            moe_renorm_eps=cfg.moe_renorm_eps,
            moe_bias_init_std=cfg.moe_bias_init_std,
            moe_expert_init_scale=cfg.moe_expert_init_scale,
            conv_kernel=cfg.conv_kernel,
            qk_norm=cfg.qk_norm,
        )

        def fields_of(i):
            # Blocks differ by layer in whether the FF is routed and in
            # their operator.
            fields = block_fields
            if cfg.num_experts and i < cfg.first_k_dense:
                fields = {**fields, "num_experts": 0}
            if cfg.layer_types is not None:
                fields = {**fields, "operator": cfg.layer_types[i]}
            return fields

        if cfg.layer_pattern is not None:
            for i, kind in enumerate(cfg.layer_pattern):
                x = MixerBlock(cfg, kind, name=f"block_{i}")(
                    x, deterministic, chunk_lengths
                )
        elif cfg.scan_layers:
            if cfg.decode:
                raise ValueError(
                    "scan_layers does not support decode mode yet: use the "
                    "unrolled stack for KV-cached generation"
                )
            # One stacked block scanned over a leading (LAYERS,) param dim:
            # XLA traces/compiles the block body ONCE regardless of depth
            # (unrolled 12-layer 125M: ~12x the block HLO), and the weights
            # stay stationary per scan step. split_rngs gives every layer its
            # own init (and dropout) stream; metadata_params records the new
            # leading axis as LAYERS in each param's logical names, so the
            # rule sets (which leave LAYERS unmapped) shard stacked kernels
            # exactly like their unrolled counterparts, layer dim whole.
            block_cls = TransformerBlock
            if cfg.remat:
                # prevent_cse is about XLA de-duplicating the rematerialized
                # ops against the forward; inside lax.scan that cannot happen,
                # so skip the (optimization-barrier) guards. static_argnums
                # counts the module method's args with self=0, so
                # deterministic — which nn.Dropout branches on in Python —
                # is arg 2 and must stay untraced.
                block_cls = nn.remat(
                    TransformerBlock,
                    prevent_cse=False,
                    policy=block_remat_policies(cfg, b, s, uniform=True)[0],
                    static_argnums=(2,),
                )
            stack = nn.scan(
                block_cls,
                variable_axes={"params": 0, "losses": 0, "intermediates": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast,),
                length=cfg.num_layers,
                metadata_params={nn.meta.PARTITION_NAME: LAYERS},
            )
            x, _ = stack(scan=True, **block_fields, name="blocks")(
                x, deterministic
            )
        else:
            remat_cls = {}
            if cfg.remat and not cfg.decode:
                # Trade FLOPs for HBM: recompute in the backward what a block
                # does not keep (its input, and the named residuals its
                # policy saves: block_remat_policies) instead of storing
                # everything (SURVEY.md's remat note; key to fitting long
                # sequences). The blocks are unrolled, so each may keep what
                # the plan gives it. deterministic is arg 2 (self=0) and must
                # stay untraced — nn.Dropout branches on it.
                policies = block_remat_policies(cfg, b, s)
                remat_cls = {
                    policy: nn.remat(
                        TransformerBlock, static_argnums=(2,), policy=policy,
                    )
                    for policy in set(policies)
                }
            for i in range(cfg.num_layers):
                if cfg.decode:
                    # chunk_lengths rides only the decode path (remat wraps
                    # the training call and pins its positional signature).
                    x = TransformerBlock(**fields_of(i), name=f"block_{i}")(
                        x, deterministic, chunk_lengths
                    )
                else:
                    block_cls = (
                        remat_cls[policies[i]] if remat_cls
                        else TransformerBlock
                    )
                    x = block_cls(**fields_of(i), name=f"block_{i}")(
                        x, deterministic
                    )

        if logit_positions is not None:
            x = jnp.take_along_axis(x, logit_positions[:, None, None], axis=1)
        if cfg.fused_norm:
            x, _ = FusedNorm(
                kind=cfg.norm, eps=cfg.norm_eps, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="ln_out",
            )(x)
        else:
            x = make_norm(
                cfg.norm, cfg.dtype, cfg.param_dtype, "ln_out", cfg.norm_eps
            )(x)
        if return_hidden:
            # Skip the logits projection: callers pairing this with
            # :func:`fused_next_token_loss` apply the lm_head kernel chunk by
            # chunk so the full (B, S, V) logits never materialize. (Init
            # runs with the default False, so lm_head params always exist.)
            return x
        if cfg.tie_embeddings:
            logits = embed.attend(x)
            return nn.with_logical_constraint(logits, (BATCH, SEQ, VOCAB))
        from learning_jax_sharding_tpu.models.quantize import projection_dense

        logits = projection_dense(
            quantization=cfg.quantization,
            features=cfg.vocab_size,
            kernel_axes=(EMBED, VOCAB),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(stddev=0.02),
            group_size=cfg.quantization_group,
            quantized_matmul_fn=cfg.quantized_matmul_fn,
            name="lm_head",
        )(x)
        # Keep the vocab dim sharded (VOCAB→model under TP rules): replicating
        # logits here would all-gather ~0.8 GB/device at the 125M bench shape
        # and the cross-entropy reductions partition fine.
        return nn.with_logical_constraint(logits, (BATCH, SEQ, VOCAB))


def fused_next_token_loss(
    hidden: jax.Array,
    batch: dict,
    params: Any,
    *,
    chunk_size: int = 128,
) -> jax.Array:
    """Causal-LM loss with a chunked logits head: O(B·chunk·V) peak memory.

    At large batch the full (B, S, V) logits — bf16 plus the fp32 softmax
    upcast — dominate HBM (measured on the v5e: they OOM the 125M model at
    B=32, S=1024 long before activations do). This computes the head matmul
    and fp32 cross-entropy per sequence chunk inside a ``lax.scan`` with
    ``jax.checkpoint``, so forward AND backward hold logits for only one
    chunk at a time; results are bit-comparable to the unfused loss (CE is
    independent across positions).

    Use with ``apply(..., return_hidden=True)`` (``hidden`` is the final-LN
    output) and ``make_train_step(..., loss_needs_params=True)``.
    """
    b, s, m = hidden.shape
    if s % chunk_size:
        raise ValueError(f"seq len {s} not divisible by chunk_size {chunk_size}")
    if "lm_head" in params:
        kernel = params["lm_head"]["kernel"]
    else:                            # tie_embeddings: the embedding IS the head
        kernel = params["tok_embed"]["embedding"].T

    @jax.checkpoint
    def chunk_total(h_chunk, t_chunk):
        logits = jnp.einsum(
            "bsm,mv->bsv", h_chunk, kernel.astype(h_chunk.dtype)
        )
        logits = nn.with_logical_constraint(logits, (BATCH, SEQ, VOCAB))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), t_chunk
        ).sum()

    hidden_c = hidden.reshape(b, s // chunk_size, chunk_size, m)
    targets_c = batch["targets"].reshape(b, s // chunk_size, chunk_size)

    def body(acc, ct):
        h, t = ct
        return acc + chunk_total(h, t), None

    total, _ = jax.lax.scan(
        body,
        jnp.zeros((), jnp.float32),
        (hidden_c.transpose(1, 0, 2, 3), targets_c.transpose(1, 0, 2)),
    )
    return total / (b * s)


def next_token_loss(logits: jax.Array, batch: dict) -> jax.Array:
    """Causal-LM loss: mean cross-entropy over all S positions.

    ``batch["targets"]`` must ALREADY be the inputs shifted left by one (the
    data pipeline's job — see ``tests/test_transformer.py::_batch``); no shift
    happens here. Computed in fp32 regardless of compute dtype (same stability
    reasoning as the reference's softmax upcast,
    `/root/reference/case6_attention.py:121-122`).
    """
    logits = logits.astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["targets"]
    ).mean()


def make_next_token_loss(
    *, label_smoothing: float = 0.0, z_loss: float = 0.0
):
    """Configurable causal-LM loss: label smoothing and/or z-loss.

    * ``label_smoothing`` ε: targets become ``(1-ε)·one_hot + ε/V·uniform``.
      Computed WITHOUT materializing the (B, S, V) one-hot — the smoothed
      cross-entropy decomposes as ``(1-ε)·nll + ε·(logsumexp - mean logits)``.
    * ``z_loss`` coefficient: adds ``z_loss · logsumexp(logits)²`` (PaLM-style),
      pulling the partition function toward 1 — keeps logits from drifting,
      which matters for bf16 serving and int8 quantization ranges.

    Defaults reproduce :func:`next_token_loss` exactly.
    """

    def loss_fn(logits: jax.Array, batch: dict) -> jax.Array:
        logits = logits.astype(jnp.float32)
        targets = batch["targets"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(
            logits, targets[..., None], axis=-1
        )[..., 0]
        loss = nll
        if label_smoothing:
            uniform_nll = lse - jnp.mean(logits, axis=-1)
            loss = (1.0 - label_smoothing) * nll + label_smoothing * uniform_nll
        if z_loss:
            loss = loss + z_loss * jnp.square(lse)
        return loss.mean()

    return loss_fn
