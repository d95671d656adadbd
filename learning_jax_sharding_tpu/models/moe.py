"""Mixture-of-Experts feed-forward with expert parallelism.

Expert parallelism is absent from the reference (SURVEY.md §2.4 "Expert
parallelism (EP/MoE): ❌"); this module adds it the TPU way:

* **Static shapes everywhere.** Routing uses the GShard/Switch capacity
  scheme: every expert processes exactly ``C`` token slots per step, chosen
  by position-in-expert cumsum; overflow tokens are dropped (their residual
  path carries them). No gather/scatter with data-dependent shapes — XLA
  sees three einsums it can tile onto the MXU.
* **Dispatch/combine as einsums.** ``dispatch (T,E,C)`` one-hot tensors
  route tokens to expert slots and back; under ``EXPERT→model`` rules GSPMD
  turns those einsums into the expert all-to-all over ICI.
* **Expert weights (E, M, H) / (E, H, M)** carry logical axes
  ``(EXPERT, EMBED, MLP)`` / ``(EXPERT, MLP, EMBED)`` — EP shards the E dim;
  a 3D mesh can additionally shard MLP for TP-within-expert.
* **fp32 router.** Gate logits/softmax stay fp32 regardless of compute dtype
  (the same stability reasoning as the reference's softmax upcast,
  `/root/reference/case6_attention.py:121-122`).

The load-balancing auxiliary loss (Switch Transformer eq. 4) is sown into the
``"losses"`` collection; ``training.pipeline.make_train_step(...,
aux_loss_collection="losses")`` adds it to the task loss.

Two routing rules live here (``TransformerConfig.moe_routing``):

* ``"softmax_capacity"`` — :class:`MoEFeedForward` and :func:`assign_slots`,
  everything above: softmax gates, a capacity per expert, overflow dropped,
  two-matrix GELU experts, three dispatches (einsum / scatter / all-to-all).
  Used by ``CONFIG_TINY_MOE``, the expert-parallel training tests and
  ``ops/moe_dispatch.py``; no benchmark cell runs it.
* ``"sigmoid_dropless"`` — :class:`DroplessMoE`: sigmoid scores, selection
  on score + a learned per-expert bias, weights renormalised over the picks
  and scaled, gated SiLU experts, always-on shared experts, NO capacity and
  no drop; expert compute through ``ops/moe_experts.py``. Used by
  JoyAI-LLM-Flash (``benchmark/configs/joyai-llm-flash.json``) on one chip;
  not sharded over experts yet.
* ``"softmax_dropless"`` — the same layer with softmax scores, no bias and
  no renormalisation (``DroplessMoE(score="softmax")``; Laguna).
"""

from __future__ import annotations

import math
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from learning_jax_sharding_tpu.parallel.logical import (
    BATCH,
    EMBED,
    EXPERT,
    MLP,
    SEQ,
    with_unstepped_partitioning,
)


#: The collection :class:`DroplessMoE` sows a layer's routing counts into
#: when a caller makes it mutable (``make_train_step(routing_stats=True)``).
ROUTING_STATS = "routing_stats"


def assign_slots(probs: jax.Array, top_k: int, capacity: int):
    """THE slot-assignment rule, shared by every dispatch implementation
    (einsum, scatter, all-to-all) so routing math cannot drift between
    them: top-k choices, rank-major GShard priority, int32 position
    cumsum, capacity drop, and surviving-gate renormalization.

    Returns ``(gate_vals, gate_idx, pos, fits, masks)`` for ``probs``
    of shape (T, E) — T is whatever token GROUP the caller routes over
    (the global batch for the single-group paths; one shard's tokens for
    the grouped all-to-all path, GShard's actual formulation)."""
    t, e = probs.shape
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # (T, k)
    masks = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)     # (T, k, E)
    # Rank-major priority: all rank-0 choices claim slots before any
    # rank-1 choice, matching GShard's dispatch order. Slot counting in
    # int32: fp32 cumsum would lose exactness past 2^24 slots per expert.
    flat = masks.transpose(1, 0, 2).reshape(top_k * t, e)      # (k·T, E)
    pos = jnp.cumsum(flat.astype(jnp.int32), axis=0) - flat.astype(jnp.int32)
    fits = flat * (pos < capacity)                             # drop overflow
    pos = pos.reshape(top_k, t, e).transpose(1, 0, 2)          # (T, k, E)
    fits = fits.reshape(top_k, t, e).transpose(1, 0, 2)        # (T, k, E)
    if top_k > 1:
        # Normalize the surviving gate weights per token (GShard).
        kept_vals = gate_vals * jnp.sum(masks * fits, axis=-1)  # (T, k)
        denom = jnp.maximum(jnp.sum(kept_vals, axis=-1, keepdims=True), 1e-9)
        gate_vals = kept_vals / denom
    else:
        gate_vals = gate_vals * jnp.sum(masks * fits, axis=-1)
    return gate_vals, gate_idx, pos, fits, masks


def scatter_slot_ids(pos, fits, masks, gate_idx, capacity, num_experts):
    """Each accepted (token, rank)'s flat slot id ``expert·C + position``
    (unique — ranks pick distinct experts); dropped entries target the
    dump slot ``E·C``. Shared by the scatter and all-to-all dispatches."""
    slot_pos = jnp.sum(pos * masks.astype(jnp.int32), axis=-1)   # (T, k)
    kept = jnp.sum(masks * fits, axis=-1) > 0                    # (T, k)
    return jnp.where(
        kept, gate_idx * capacity + slot_pos, num_experts * capacity
    ).reshape(-1)                                                # (T·k,)


def bucket_tokens(xf, flat_slot, num_experts, capacity, top_k, dtype):
    """Scatter tokens into the ``(E, C, M)`` slot pool by their flat slot
    ids (dump row absorbs capacity-dropped entries) — the movement half
    of the flop-free dispatch, shared by the scatter and all-to-all
    paths."""
    t, m = xf.shape
    token_of = jnp.repeat(jnp.arange(t), top_k)              # (T·k,)
    pool = jnp.zeros((num_experts * capacity + 1, m), dtype)
    pool = pool.at[flat_slot].set(xf.astype(dtype)[token_of])
    return pool[:-1].reshape(num_experts, capacity, m)


def combine_slots(expert_out, flat_slot, gate_vals, top_k, dtype):
    """Gather each (token, rank)'s slot output (dump slot reads zero) and
    fold the gate weights in one tiny contraction — gate_vals already
    carries the kept mask and normalization, exactly as the combine
    einsum's gating. Shared by the scatter and all-to-all paths."""
    e, c, m = expert_out.shape
    eflat = jnp.concatenate(
        [expert_out.reshape(e * c, m), jnp.zeros((1, m), expert_out.dtype)]
    )
    per_rank = eflat[flat_slot].reshape(gate_vals.shape[0], top_k, m)
    return jnp.einsum("tkm,tk->tm", per_rank, gate_vals.astype(dtype))


class MoEFeedForward(nn.Module):
    """Top-k routed expert FFN, drop-in for the dense ``FeedForward``.

    Attributes:
        features: residual-stream width M.
        hidden: per-expert FF hidden width H.
        num_experts: expert count E.
        top_k: experts per token (1 = Switch, 2 = GShard-style).
        capacity_factor: slack over the even-load capacity; each expert gets
            ``C = ceil(top_k · T · capacity_factor / E)`` slots for the
            ``T = B·S`` tokens of the step.
        aux_loss_weight: coefficient on the sown load-balancing loss.
        router_noise: stddev of multiplicative jitter on router logits during
            training (0 disables; Switch uses 1e-2).
    """

    features: int
    hidden: int
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    router_noise: float = 0.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    dispatch: str = "einsum"
    dispatch_fn: Callable | None = None
    # Token routing implementation — identical math, different cost model:
    # "einsum" builds (T, E, C) one-hot dispatch/combine tensors whose
    #   contractions cost O(E·C·M·T) MXU FLOPs (≈40% of MoE step time at
    #   E=8 top-2, PERF.md round 3) but shard cleanly under EXPERT→model
    #   rules (GSPMD lowers them to the expert all-to-all) — the
    #   zero-configuration multi-device EP path;
    # "scatter" computes each (token, rank)'s slot index directly from the
    #   shared cumsum (expert·C + position-in-expert) and moves rows by
    #   .at[].set scatter / gather — O(k·T·M) bytes, no routing FLOPs.
    #   Slot assignment is bit-identical to the einsum path (same cumsum,
    #   same GShard rank-major priority). Single-device oriented:
    #   data-dependent gathers don't partition over EXPERT.
    # "alltoall" (dispatch_fn = ops.moe_dispatch.make_moe_a2a_fn(mesh)):
    #   the EXPLICIT expert-parallel path — scatter's flop-free bucketing
    #   per TOKEN SHARD, then lax.all_to_all over the expert mesh axis
    #   each way (GShard's grouped formulation: capacity per token group,
    #   not global — see make_moe_a2a_fn). Deletes the one-hot FLOPs the
    #   einsum EP path still pays AND partitions over EXPERT.

    @nn.compact
    def __call__(self, x: jax.Array, *, deterministic: bool = True) -> jax.Array:
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k={self.top_k} not in [1, {self.num_experts}]")
        b, s, m = x.shape
        e = self.num_experts
        t = b * s
        capacity = min(t, max(1, math.ceil(self.top_k * t * self.capacity_factor / e)))

        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))

        # --- Router (fp32) -------------------------------------------------
        router = nn.Dense(
            e,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(self.kernel_init, (EMBED, EXPERT)),
            name="router",
        )
        logits = router(x.astype(jnp.float32)).reshape(t, e)
        if self.router_noise > 0.0 and not deterministic:
            key = self.make_rng("dropout")
            logits = logits * jax.random.uniform(
                key, logits.shape, jnp.float32,
                1.0 - self.router_noise, 1.0 + self.router_noise,
            )
        probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)

        # --- Load-balancing aux loss + the expert weights (shared by all
        # dispatch paths; the all-to-all path routes inside dispatch_fn).
        w_up = self.param(
            "up",
            nn.with_logical_partitioning(self.kernel_init, (EXPERT, EMBED, MLP)),
            (e, m, self.hidden),
            self.param_dtype,
        )
        w_down = self.param(
            "down",
            nn.with_logical_partitioning(self.kernel_init, (EXPERT, MLP, EMBED)),
            (e, self.hidden, m),
            self.param_dtype,
        )

        def sow_aux(probs, masks0):
            load = jnp.mean(masks0, axis=0)                         # (E,)
            importance = jnp.mean(probs, axis=0)                    # (E,)
            self.sow(
                "losses",
                "load_balancing",
                self.aux_loss_weight * e * jnp.sum(load * importance),
                reduce_fn=lambda a, b: a + b,
                init_fn=lambda: jnp.zeros((), jnp.float32),
            )

        if self.dispatch == "alltoall":
            if self.dispatch_fn is None:
                raise ValueError(
                    "dispatch='alltoall' needs dispatch_fn — build one with "
                    "ops.moe_dispatch.make_moe_a2a_fn(mesh)"
                )
            sow_aux(
                probs, jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=probs.dtype)
            )
            out = self.dispatch_fn(
                x.reshape(t, m), probs, w_up, w_down,
                top_k=self.top_k, capacity_factor=self.capacity_factor,
                dtype=self.dtype,
            )
            out = out.reshape(b, s, m)
            return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))

        # --- Top-k assignment with capacity (ONE global group) -------------
        gate_vals, gate_idx, pos, fits, masks = assign_slots(
            probs, self.top_k, capacity
        )

        if self.dispatch == "einsum":
            slot = jax.nn.one_hot(
                jnp.sum(pos * masks.astype(jnp.int32), axis=-1), capacity,
                dtype=jnp.float32,
            )                                                       # (T, k, C)
            # (T,k,E) × (T,k,C) → (T,E,C): one-hot routing tensors.
            dispatch = jnp.einsum("tke,tkc->tec", fits, slot)
            combine = jnp.einsum("tke,tkc,tk->tec", fits, slot, gate_vals)
        elif self.dispatch == "scatter":
            # Same priority/capacity assignment, but tokens MOVE by
            # scatter/gather instead of (T,E,C) contractions: each
            # accepted (token, rank) owns slot expert·C + position
            # (unique — ranks pick distinct experts); dropped entries
            # target a dump slot past the pool. The expensive part of the
            # einsum path was never the int cumsum above — it is the
            # O(E·C·M·T) dispatch/combine MXU work this branch deletes.
            flat_slot = scatter_slot_ids(
                pos, fits, masks, gate_idx, capacity, e
            )
        else:
            raise ValueError(
                f"unknown dispatch {self.dispatch!r}: 'einsum', 'scatter', "
                f"or 'alltoall'"
            )

        # --- Load-balancing aux loss (Switch eq. 4, on rank-0 choices) -----
        sow_aux(probs, masks[:, 0])

        # --- Expert computation --------------------------------------------
        xf = x.reshape(t, m)
        if self.dispatch == "scatter":
            expert_in = bucket_tokens(
                xf, flat_slot, e, capacity, self.top_k, self.dtype
            )
        else:
            expert_in = jnp.einsum(
                "tec,tm->ecm", dispatch.astype(self.dtype), xf.astype(self.dtype)
            )
        expert_in = nn.with_logical_constraint(expert_in, (EXPERT, None, EMBED))

        h = jnp.einsum("ecm,emh->ech", expert_in, w_up.astype(self.dtype))
        h = nn.with_logical_constraint(h, (EXPERT, None, MLP))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ech,ehm->ecm", h, w_down.astype(self.dtype))
        expert_out = nn.with_logical_constraint(expert_out, (EXPERT, None, EMBED))

        if self.dispatch == "scatter":
            out = combine_slots(
                expert_out, flat_slot, gate_vals, self.top_k, self.dtype
            )
        else:
            out = jnp.einsum(
                "tec,ecm->tm", combine.astype(self.dtype), expert_out
            )
        out = out.reshape(b, s, m)
        return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))


class DroplessMoE(nn.Module):
    """Sigmoid-routed, dropless expert FFN with shared experts (the
    DeepSeek-V3 rule; ``topk_method`` ``noaux_tc`` with one group)::

        s = sigmoid(x W_r)                      float32, (T, E)
        picks = top_k(s + bias)                 the bias only SELECTS
        w_i = routed_scaling * s_i / (sum_picks s + renorm_eps)
        y = sum_i w_i E_i(x) + E_shared(x)      E = (silu(x W_g) * x W_u) W_d

    (``renorm_eps`` is the configuration's: 1e-20 where the DeepSeek-V3
    rule is published, 1e-6 in the ``lfm2_moe`` family.)

    Every pick is computed: there is no capacity, so however uneven the
    routing no token's output lacks an expert. ``valid`` ``(B, S)`` marks
    the tokens that exist (a refill chunk's padding and a frozen decode row
    do not): the others are routed nowhere, read no expert and get the
    shared expert's output only (which their row discards).

    Variants (the ``nemotron_h`` family uses the first three):

    * ``held=(first, count)``: this chip HOLDS experts ``[first, first +
      count)`` of ``num_experts`` — its share of an expert-parallel group.
      The router keeps ``num_experts`` outputs and the weights their
      renormalisation over all picks; the expert matrices have ``count``
      rows; a pick outside the range is computed nowhere here (another
      chip adds it: what comes back is this chip's PART of the routed sum,
      and on one chip, without the exchange, that part is what goes on).
      ``None``: every expert is held;
    * ``gated=False``: ``E(x) = relu(x W_u)^2 W_d`` (no ``gate``), for the
      routed experts and the shared expert alike;
    * ``latent > 0``: the routed experts live in a latent of that width::

          y = (sum_i w_i E_i(x W_down_latent)) W_up_latent + E_shared(x)

    * ``score="softmax"``: the Qwen-MoE rule with ``norm_topk_prob`` false
      (the ``laguna`` family)::

          p = softmax(x W_r)                    float32, over all E
          picks = top_k(p);  w_i = routed_scaling * p_i     (no bias, no
                                                            renormalisation)

    Initialisers: with ``expert_init_scale`` a number the expert tensors
    take each expert's OWN fan-in, ``down`` times that number (a benchmark
    lowers it where its float32 reference cannot follow the program's
    picks: a bf16 flip of one pick then moves the output by that much
    less; the ``lfm2_moe`` family, with no shared expert to carry the
    layer, passes 1.0). ``None``: the ungated form still takes its own
    fan-in (scale 1); the gated form takes ``kernel_init`` as it is
    (``lecun_normal`` counts the expert axis of ``(E, in, out)`` into the
    fan-in: a routed sum far below the shared expert's output, which is
    how ``joyai-llm-flash``'s cell was measured and admitted). ``centred_down``: the down projections (routed and shared) have
    no gain for the mean of what they read (``transformer.zero_mean``).

    Parameters: ``router/kernel`` ``(M, E)``, ``bias`` ``(E,)`` (the
    selection bias of the sigmoid rule, a leaf of this module so that its
    path ends in ``['bias']``; the softmax rule has none), ``gate`` / ``up`` ``(E_held, W, H)``, ``down``
    ``(E_held, H, W)`` (``W`` = ``latent`` or ``M``), ``latent_down`` /
    ``latent_up`` kernels, and ``shared/{gate,up,down}/kernel`` of width
    ``shared_hidden`` (default ``shared_experts * H``). All in
    ``param_dtype``; router scores, bias add, top-k and weights run in
    float32 whatever the compute dtype.

    ``count``: keep cumulative ``moe_stats`` ``(3,)`` int32 in the
    ``"cache"`` collection (assignments routed to held experts, held
    experts read, layer-steps): the serving engine returns their growth
    with each dispatch's readback. A caller that makes the collection
    :data:`ROUTING_STATS` mutable (``make_train_step(routing_stats=True)``)
    gets one ``(3,)`` int32 a layer sown there: assignments routed to held
    experts, held experts touched, and the largest load of one held expert.

    ``bias_init_std``: the selection bias starts N(0, that) and not zero
    (a seeded benchmark exercises the term so); the bias only selects, so
    its gradient is zero, and its box (``parallel.logical.Unstepped``) has
    ``training.pipeline.sharded_train_state`` keep every optimizer's step,
    weight decay included, off it.
    """

    features: int
    hidden: int
    num_experts: int
    top_k: int
    shared_experts: int = 0
    shared_hidden: int | None = None
    routed_scaling: float = 1.0
    score: str = "sigmoid"          # or "softmax": no bias, no renormalisation
    renorm_eps: float = 1e-20
    bias_init_std: float = 0.0
    held: tuple | None = None
    gated: bool = True
    latent: int = 0
    expert_init_scale: float | None = None
    centred_down: bool = False
    experts: str = "auto"
    count: bool = False
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    router_dtype: jnp.dtype = jnp.float32   # the model's rule; a test lowers
                                            # it to show the tolerance bites
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jax.Array, *, valid: jax.Array | None = None) -> jax.Array:
        from learning_jax_sharding_tpu.models.transformer import (
            FeedForward,
            zero_mean,
        )
        from learning_jax_sharding_tpu.ops.moe_experts import routed_experts

        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k={self.top_k} not in [1, {self.num_experts}]")
        b, s, m = x.shape
        e, t = self.num_experts, b * s
        x = nn.with_logical_constraint(x, (BATCH, SEQ, EMBED))

        with jax.named_scope("moe.route"):
            router = nn.Dense(
                e, use_bias=False, dtype=self.router_dtype,
                param_dtype=self.param_dtype,
                # A TPU's default float32 matmul is one bf16 pass.
                precision=jax.lax.Precision.HIGHEST,
                kernel_init=nn.with_logical_partitioning(
                    self.kernel_init, (EMBED, EXPERT)
                ),
                name="router",
            )
            if self.score == "softmax":
                scores = jax.nn.softmax(
                    router(x.astype(self.router_dtype)).reshape(t, e), axis=-1
                )
                picked, idx = jax.lax.top_k(scores, self.top_k)
                weights = self.routed_scaling * picked
            elif self.score == "sigmoid":
                bias_init = (
                    nn.initializers.normal(self.bias_init_std)
                    if self.bias_init_std else nn.initializers.zeros_init()
                )
                bias = self.param(
                    "bias", with_unstepped_partitioning(bias_init, (EXPERT,)),
                    (e,), self.param_dtype,
                )
                scores = jax.nn.sigmoid(
                    router(x.astype(self.router_dtype)).reshape(t, e)
                )
                _, idx = jax.lax.top_k(
                    scores + bias.astype(self.router_dtype), self.top_k
                )
                picked = jnp.take_along_axis(scores, idx, axis=-1)
                weights = self.routed_scaling * picked / (
                    jnp.sum(picked, axis=-1, keepdims=True) + self.renorm_eps
                )
            else:
                raise ValueError(
                    f"unknown score {self.score!r}: 'sigmoid' or 'softmax'"
                )

        def experts(name, shape, axes, init=self.kernel_init):
            return self.param(
                name, nn.with_logical_partitioning(init, axes),
                shape, self.param_dtype,
            )

        first, n_held = self.held or (None, e)
        if self.held and not 0 <= first <= first + n_held <= e:
            raise ValueError(f"held={self.held} is not a range of {e} experts")

        def latent_proj(features, axes, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(self.kernel_init, axes),
                name=name,
            )

        xe, w = x.astype(self.dtype), self.latent or m
        if self.latent:
            with jax.named_scope("moe.latent_down"):
                xe = latent_proj(w, (EMBED, None), "latent_down")(xe)
        w_gate = None
        up_init = down_init = self.kernel_init
        init_scale = self.expert_init_scale
        if init_scale is None and not self.gated:
            init_scale = 1.0
        if init_scale is not None:
            up_init, down_init = (
                nn.initializers.variance_scaling(
                    scale, "fan_in", "truncated_normal", batch_axis=(0,)
                )
                for scale in (1.0, init_scale ** 2)
            )
        if self.gated:
            w_gate = experts(
                "gate", (n_held, w, self.hidden), (EXPERT, EMBED, MLP), up_init
            )
        if self.centred_down:
            down_init = zero_mean(down_init, 1)
        w_up = experts("up", (n_held, w, self.hidden), (EXPERT, EMBED, MLP), up_init)
        w_down = experts(
            "down", (n_held, self.hidden, w), (EXPERT, MLP, EMBED), down_init
        )
        out, stats = routed_experts(
            xe.reshape(t, w), idx, weights, w_gate, w_up,
            w_down, valid=None if valid is None else valid.reshape(t),
            backend=self.experts, first=first,
        )
        out = out.reshape(b, s, w)
        if self.latent:
            with jax.named_scope("moe.latent_up"):
                out = latent_proj(m, (None, EMBED), "latent_up")(out)
        if self.count:
            seen = self.variable(
                "cache", "moe_stats", jnp.zeros, (3,), jnp.int32
            )
            seen.value = seen.value + stats
        if self.is_mutable_collection(ROUTING_STATS) and not self.is_initializing():
            here = idx if valid is None else jnp.where(
                valid.reshape(t, 1), idx, e
            )
            load = jnp.zeros((e + 1,), jnp.int32).at[here.reshape(-1)].add(1)
            lo = first or 0
            self.sow(
                ROUTING_STATS, "routing",
                jnp.stack([stats[0], stats[1], jnp.max(load[lo:lo + n_held])]),
            )
        if self.shared_experts:
            with jax.named_scope("moe.shared"):
                out = out + FeedForward(
                    features=m,
                    hidden=self.shared_hidden or self.shared_experts * self.hidden,
                    gated=self.gated, activation="gelu" if self.gated else "relu2",
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    down_init=(
                        zero_mean(self.kernel_init, 0) if self.centred_down else None
                    ),
                    name="shared",
                )(x)
        return nn.with_logical_constraint(out, (BATCH, SEQ, EMBED))
