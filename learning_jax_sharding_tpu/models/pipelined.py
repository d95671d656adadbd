"""Pipeline-parallel transformer: the case-7 model over a ``pipe`` mesh axis.

The reference runs every layer on every device (SURVEY.md §2.4: pipeline
parallelism absent). This module splits the case-7 transformer's block stack
into contiguous stages carried by a ``pipe`` mesh axis and streams
microbatches through them with :func:`parallel.pipeline.spmd_pipeline` —
while the embedding, the stage-internal math, and the logits head keep their
data/tensor shardings under GSPMD (partial-manual ``shard_map``: only the
pipe axis is manual). One jitted train step therefore composes dp x tp x pp.

Design: this is an orchestrator over pure functions, not an ``nn.Module`` —
the per-layer parameters must live in ONE stacked pytree (leading dims
``(stages, layers_per_stage)``) so a single ``ppermute`` ring and a single
``lax.scan`` serve every stage, which is incompatible with Flax's
one-submodule-per-layer parameter naming. The blocks themselves ARE the
ordinary :class:`models.transformer.TransformerBlock`; their params are
created by ``jax.vmap`` of the block's init over per-layer PRNG keys.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.linen import partitioning as nn_partitioning
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from learning_jax_sharding_tpu.models.transformer import (
    TransformerBlock,
    TransformerConfig,
    block_remat_policies,
    make_norm,
)
from learning_jax_sharding_tpu.parallel.logical import (
    BATCH,
    EMBED,
    Rules,
    SEQ,
    VOCAB,
    activate,
)
from learning_jax_sharding_tpu.parallel.pipeline import (
    PIPE_AXIS,
    spmd_pipeline,
    stack_stage_params,
)


class _EmbedIn(nn.Module):
    """Token + position embedding (the case-7 model's input layer, run
    outside the pipeline: it is one cheap gather, not worth a stage)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        cfg = self.config
        s = tokens.shape[1]
        x = nn.Embed(
            cfg.vocab_size,
            cfg.features,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (VOCAB, EMBED)
            ),
            name="tok_embed",
        )(tokens)
        pos = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (SEQ, EMBED)
            ),
            (cfg.max_seq_len, cfg.features),
            cfg.param_dtype,
        )
        x = x + pos[None, :s].astype(cfg.dtype)
        return nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))


class _Head(nn.Module):
    """Final LayerNorm + logits projection (run outside the pipeline)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        x = make_norm(
            cfg.norm, cfg.dtype, cfg.param_dtype, "ln_out", cfg.norm_eps
        )(x)
        logits = nn.Dense(
            cfg.vocab_size,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (EMBED, VOCAB)
            ),
            name="lm_head",
        )(x)
        return nn.with_logical_constraint(logits, (BATCH, SEQ, VOCAB))


@dataclasses.dataclass
class PipelinedTransformer:
    """The case-7 transformer with its block stack pipelined over ``pipe``.

    Parameters are a plain dict pytree::

        {"embed": <_EmbedIn params>,
         "blocks": <TransformerBlock params, leaves (P, L/P, ...)>,
         "head":  <_Head params>}

    ``init_sharded`` births it already sharded (the reference's born-sharded
    init pattern, `/root/reference/case6_attention.py:189-196`, extended with
    the stage dim on the pipe axis).
    """

    config: TransformerConfig
    mesh: Mesh
    rules: Rules
    num_stages: int
    num_microbatches: Optional[int] = None
    interleave: int = 1
    # >1 = interleaved circular schedule: each device owns `interleave`
    # round-robin layer chunks and microbatches circulate the ring that many
    # times — the GPipe bubble shrinks ~interleave-fold
    # (parallel/pipeline.py module docstring has the measured tick counts).
    pipe_axis: str = PIPE_AXIS

    def __post_init__(self):
        cfg = self.config
        if cfg.num_layers % (self.num_stages * self.interleave):
            raise ValueError(
                f"num_layers {cfg.num_layers} not divisible by "
                f"num_stages {self.num_stages} × interleave {self.interleave}"
            )
        if self.mesh.shape[self.pipe_axis] != self.num_stages:
            raise ValueError(
                f"mesh axis {self.pipe_axis!r} has size "
                f"{self.mesh.shape[self.pipe_axis]}, want {self.num_stages}"
            )
        # Unsupported-config guard: silently training a different model than
        # the config asks for would be worse than refusing.
        if cfg.num_experts > 0:
            raise ValueError(
                "PipelinedTransformer does not support MoE blocks yet "
                "(num_experts > 0); use Transformer with RULES_DP_TP_EP"
            )
        if cfg.dropout_rate > 0:
            raise ValueError(
                "PipelinedTransformer does not support dropout yet "
                "(the pipelined stage_fn runs deterministically)"
            )
        self._embed = _EmbedIn(cfg)
        self._head = _Head(cfg)
        self._block = TransformerBlock(
            features=cfg.features,
            num_heads=cfg.num_heads,
            head_dim=cfg.head_dim,
            num_kv_heads=cfg.num_kv_heads,
            rope=cfg.rope,
            rope_theta=cfg.rope_theta,
            window=cfg.window,
            hidden=cfg.hidden,
            dropout_rate=0.0,
            causal=cfg.causal,
            use_bias=cfg.use_bias,
            norm_eps=cfg.norm_eps,
            norm=cfg.norm,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            attn_fn=cfg.attn_fn,
        )

    # -- init ---------------------------------------------------------------

    def _init_boxed(self, rng: jax.Array, tokens: jax.Array) -> dict:
        cfg = self.config
        k_embed, k_blocks, k_head = jax.random.split(rng, 3)
        embed_p = self._embed.init({"params": k_embed}, tokens)["params"]
        x = jax.eval_shape(
            lambda p, t: self._embed.apply({"params": p}, t),
            nn.meta.unbox(embed_p),
            tokens,
        )
        x = jnp.zeros(x.shape, x.dtype)
        # One init per layer, vmapped over keys → every leaf gains a leading
        # layer dim; the boxed logical names stay those of a single block.
        layer_keys = jax.random.split(k_blocks, cfg.num_layers)
        block_p = jax.vmap(
            lambda k: self._block.init({"params": k}, x)["params"]
        )(layer_keys)
        head_p = self._head.init({"params": k_head}, x)["params"]
        return {"embed": embed_p, "blocks": block_p, "head": head_p}

    def _shardings(self, abstract_boxed: dict) -> dict:
        """Map logical specs to shardings; block leaves get
        ``(pipe, None, *logical)`` for their ``(P, L/P, ...)`` layout."""
        rules = tuple(self.rules)

        def leaf_sharding(box: Any, extra: tuple) -> NamedSharding:
            axes = (
                nn_partitioning.logical_to_mesh_axes(tuple(box.names), rules)
                if isinstance(box, nn.LogicallyPartitioned)
                else PartitionSpec()
            )
            return NamedSharding(self.mesh, PartitionSpec(*extra, *axes))

        embed_sh = jax.tree.map(
            lambda b: leaf_sharding(b, ()),
            abstract_boxed["embed"],
            is_leaf=lambda b: isinstance(b, nn.LogicallyPartitioned),
        )
        head_sh = jax.tree.map(
            lambda b: leaf_sharding(b, ()),
            abstract_boxed["head"],
            is_leaf=lambda b: isinstance(b, nn.LogicallyPartitioned),
        )
        # Block leaves are (P, L/P, *weight_dims) — or (P, V, c, *weight_dims)
        # when interleaved: stage dim over pipe, chunk/layer dims replicated,
        # weight dims per their logical names (TP rides here).
        lead = (self.pipe_axis, None) + (None,) * (self.interleave > 1)
        blocks_sh = jax.tree.map(
            lambda b: leaf_sharding(b, lead),
            abstract_boxed["blocks"],
            is_leaf=lambda b: isinstance(b, nn.LogicallyPartitioned),
        )
        return {"embed": embed_sh, "blocks": blocks_sh, "head": head_sh}

    def init_sharded(self, rng: jax.Array, tokens: jax.Array) -> tuple[dict, dict]:
        """Born-sharded params: ``(params, shardings)``.

        The stacked per-layer block params are reshaped to
        ``(num_stages, layers_per_stage, ...)`` inside the jitted init so no
        replicated copy ever materializes.
        """

        def init_fn(rng, tokens):
            boxed = self._init_boxed(rng, tokens)
            params = nn.meta.unbox(boxed)
            params["blocks"] = stack_stage_params(
                params["blocks"], self.num_stages, self.interleave
            )
            return params

        def restack(box: Any) -> Any:
            # Abstract leaves are ShapeDtypeStructs, possibly inside
            # LogicallyPartitioned boxes (whose names cover only the weight
            # dims): rewrite (L, ...) shapes to (P, L/P, ...) in place.
            value = box.value if isinstance(box, nn.LogicallyPartitioned) else box
            chunks = self.num_stages * self.interleave
            lead = (
                (self.num_stages, value.shape[0] // self.num_stages)
                if self.interleave == 1
                else (self.num_stages, self.interleave, value.shape[0] // chunks)
            )
            value = jax.ShapeDtypeStruct(
                lead + tuple(value.shape[1:]), value.dtype
            )
            if isinstance(box, nn.LogicallyPartitioned):
                return box.replace_boxed(value)
            return value

        with activate(self.mesh, self.rules):
            abstract_boxed = jax.eval_shape(self._init_boxed, rng, tokens)
            # eval_shape sees the (L, ...) layout; reshape to (P, L/P, ...)
            # before computing shardings so specs line up with init_fn output.
            abstract_boxed["blocks"] = jax.tree.map(
                restack,
                abstract_boxed["blocks"],
                is_leaf=lambda b: isinstance(b, nn.LogicallyPartitioned),
            )
            shardings = self._shardings(abstract_boxed)
            params = jax.jit(init_fn, out_shardings=shardings)(rng, tokens)
        return params, shardings

    # -- forward ------------------------------------------------------------

    def apply(self, params: dict, tokens: jax.Array) -> jax.Array:
        """Forward pass: embed → pipelined block stack → head → logits."""

        def stage_fn(stage_params, h):
            def apply_layer(layer_params, h):
                return self._block.apply({"params": layer_params}, h)

            if self.config.remat:
                # Recompute each layer's activations in the backward pipeline
                # instead of holding M microbatches' worth of them live. One
                # policy for the scanned layers, from the stack's resolver
                # (no train step here says what is free: nothing is kept
                # unless ``remat_policy`` names a policy).
                apply_layer = jax.checkpoint(
                    apply_layer,
                    policy=block_remat_policies(
                        self.config, h.shape[0], h.shape[1], uniform=True
                    )[0],
                )

            def body(h, layer_params):
                return apply_layer(layer_params, h), None

            h, _ = lax.scan(body, h, stage_params)
            return h

        x = self._embed.apply({"params": params["embed"]}, tokens)
        x = spmd_pipeline(
            stage_fn,
            params["blocks"],
            x,
            mesh=self.mesh,
            axis=self.pipe_axis,
            num_microbatches=self.num_microbatches,
            interleave=self.interleave,
        )
        return self._head.apply({"params": params["head"]}, x)

    # -- training -----------------------------------------------------------

    def init_optimizer(
        self, params: dict, optimizer: optax.GradientTransformation
    ) -> Any:
        """Optimizer state born sharded like the params: ``optimizer.init``
        is jitted with the sharded params as input, so XLA propagates the
        parameter shardings onto the (shape-mirroring) moment buffers."""
        with activate(self.mesh, self.rules):
            return jax.jit(optimizer.init)(params)

    def make_train_step(
        self,
        optimizer: optax.GradientTransformation,
        loss_fn: Callable[[jax.Array, Any], jax.Array],
    ) -> Callable:
        """Jitted ``step((params, opt_state), batch) -> ((params, opt_state),
        loss)`` with the carry donated — the pp analogue of
        ``training.pipeline.make_train_step``. Pass sharded params and the
        state from :meth:`init_optimizer`; shardings flow from the inputs."""

        def step(carry, batch):
            params, opt_state = carry

            def loss_of(p):
                logits = self.apply(p, batch["inputs"])
                return loss_fn(logits, batch)

            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), loss

        jitted = jax.jit(step, donate_argnums=(0,))

        def run(carry, batch):
            with activate(self.mesh, self.rules):
                return jitted(carry, batch)

        run.jitted = jitted
        return run
