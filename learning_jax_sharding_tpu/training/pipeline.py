"""Sharded-init / train_step / apply pipeline (L5).

The reference's central training pattern, promoted to API
(`/root/reference/case6_attention.py:171-237`):

1. build the TrainState **abstractly** with ``jax.eval_shape`` — no device
   memory touched (`case6_attention.py:189`);
2. read logical specs off the abstract tree and map them through the rules to
   real shardings (`case6_attention.py:190-191`);
3. jit the real init with those shardings as ``out_shardings`` — parameters
   and optimizer moments are **born sharded**, never materialized replicated
   (`case6_attention.py:192-196`);
4. jit ``train_step`` / ``apply_fn`` with matching in/out shardings so each
   step is one SPMD executable with all collectives inside
   (`case6_attention.py:206-215,229-232`).

Additions over the reference: donation of the incoming state (in-place buffer
reuse — on TPU this halves peak optimizer-state HBM), a loss that is actually
returned (the reference's train_step discards it, SURVEY.md §5 "Metrics"), and
mesh/rules handled by one context helper instead of repeated ``with`` pairs.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding

from learning_jax_sharding_tpu.parallel.logical import (
    BATCH,
    MLP,
    Rules,
    Unstepped,
    activate,
    tree_shardings,
)
from learning_jax_sharding_tpu.utils.memory import (
    RematScope,
    device_memory_bytes,
    remat_scope,
)

TrainState = train_state.TrainState
log = logging.getLogger(__name__)


def default_loss(y: jax.Array, batch: Any) -> jax.Array:
    """The reference's loss: ``y.sum()`` (`/root/reference/case6_attention.py:210-211`).

    A stand-in that exercises the full backward; real tasks supply their own
    ``loss_fn(y, batch)`` (e.g. next-token cross-entropy against
    ``batch["targets"]``).
    """
    del batch
    return jnp.sum(y)


def _inputs_of(batch: Any) -> jax.Array:
    """A batch is either the bare input array (the reference's convention) or
    a dict with an ``"inputs"`` entry (plus e.g. ``"targets"``)."""
    return batch["inputs"] if isinstance(batch, dict) else batch


def _keep_unstepped(
    optimizer: optax.GradientTransformation, boxed_params: Any
) -> optax.GradientTransformation:
    """``optimizer`` with a zero update for every parameter its module boxed
    as :class:`~learning_jax_sharding_tpu.parallel.logical.Unstepped` (no
    gradient step, no weight decay). The state tree is ``optimizer``'s own,
    and a model with no such leaf gets ``optimizer`` back as it is."""
    marks, treedef = jax.tree.flatten(
        jax.tree.map(
            lambda box: isinstance(box, Unstepped), boxed_params,
            is_leaf=lambda box: isinstance(box, nn.meta.AxisMetadata),
        )
    )
    if not any(marks):
        return optimizer
    return _zero_updates_at(optimizer, treedef, tuple(marks))


@functools.lru_cache(maxsize=16)
def _zero_updates_at(optimizer, treedef, marks):
    # Cached: ``TrainState.tx`` is part of the state's tree TYPE, so a state
    # made again from the same optimizer and model must carry the SAME object
    # (a jitted step would otherwise see a new type and compile again).
    unstepped = jax.tree.unflatten(treedef, marks)

    def update(updates, state, params=None):
        updates, state = optimizer.update(updates, state, params)
        updates = jax.tree.map(
            lambda keep, u: jnp.zeros_like(u) if keep else u, unstepped, updates
        )
        return updates, state

    return optax.GradientTransformation(optimizer.init, update)


def sharded_train_state(
    model: Any,
    optimizer: optax.GradientTransformation,
    x: jax.Array,
    rngs: dict[str, jax.Array],
    mesh: Mesh,
    rules: Rules,
    *,
    zero1_axis: str | None = None,
) -> tuple[TrainState, Any]:
    """Create a TrainState whose every leaf is born sharded.

    Args:
        model: a Flax module with logically partitioned params.
        optimizer: optax transformation (reference uses Adam(1e-3),
            `/root/reference/case6_attention.py:181`).
        x: sample input, already placed with its sharding (its placement is
            what the jitted init sees as ``in_shardings``).
        rngs: init PRNG keys, e.g. ``{"params": key}``.
        mesh: device mesh.
        rules: logical→mesh rules.
        zero1_axis: mesh axis name (usually ``"data"``) to additionally shard
            the OPTIMIZER STATE over — ZeRO stage 1 (``training.zero``).
            Params keep their rule-derived shardings; moments/masters are
            born 1/D-sharded and GSPMD derives the reduce-scatter / gather.

    Returns:
        ``(state, state_shardings)`` — the sharded TrainState and the matching
        sharding tree (reused as in/out shardings for the step functions).
    """

    def boxed_state(params, tx):
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    with activate(mesh, rules):
        # The logical axis names live in flax's LogicallyPartitioned boxes;
        # they are read off the *abstract* tree, so the real state can carry
        # plain arrays (unboxed): optimizer and step functions then see
        # ordinary pytrees. The boxes also say which leaves no step may move.
        abstract_params = jax.eval_shape(
            lambda rngs, x: model.init(rngs, x)["params"], rngs, x
        )
        optimizer = _keep_unstepped(optimizer, abstract_params)
        abstract = jax.eval_shape(
            lambda params: boxed_state(params, optimizer), abstract_params
        )

        def init_fn(rngs, x):
            return nn.meta.unbox(
                boxed_state(model.init(rngs, x)["params"], optimizer)
            )

        state_shardings = tree_shardings(abstract, mesh, rules)
        # Optimizers with FACTORED state (e.g. adafactor's rank-1 v_row /
        # v_col, reduced from rank-2 kernels) inherit the param's logical
        # names but not its rank; a spec longer than the leaf's rank is
        # invalid, so such leaves fall back to replicated (they are the
        # tiny factored vectors — replication is the right call anyway).
        def _rank_safe(sh, leaf):
            if (
                isinstance(sh, NamedSharding)
                and len(sh.spec) > getattr(leaf, "ndim", 0)
            ):
                return NamedSharding(mesh, jax.sharding.PartitionSpec())
            return sh

        state_shardings = jax.tree.map(
            _rank_safe, state_shardings, nn.meta.unbox(abstract)
        )
        if zero1_axis is not None:
            from learning_jax_sharding_tpu.training.zero import zero1_shardings

            state_shardings = state_shardings.replace(
                opt_state=zero1_shardings(
                    nn.meta.unbox(abstract).opt_state,
                    state_shardings.opt_state,
                    mesh,
                    zero1_axis,
                )
            )
        jit_init = jax.jit(
            init_fn,
            in_shardings=(NamedSharding(mesh, jax.sharding.PartitionSpec()), x.sharding),
            out_shardings=state_shardings,
        )
        state = jit_init(rngs, x)
    return state, state_shardings


def _mesh_shards(mesh: Mesh, rules: Rules, logical_axis: str) -> int:
    """How many ways ``rules`` split ``logical_axis`` over ``mesh``."""
    from flax.linen import partitioning as nn_partitioning

    (axes,) = nn_partitioning.logical_to_mesh_axes((logical_axis,), tuple(rules))
    if axes is None:
        return 1
    names = (axes,) if isinstance(axes, str) else axes
    return math.prod(mesh.shape[a] for a in names)


def _device_bytes_of(tree: Any, shardings: Any) -> float:
    """Bytes ONE device holds of ``tree`` (arrays or tracers) placed by
    ``shardings``: every leaf's shard, from shapes alone."""
    return float(sum(
        math.prod(sh.shard_shape(leaf.shape)) * leaf.dtype.itemsize
        for leaf, sh in zip(
            jax.tree.leaves(tree), jax.tree.leaves(shardings), strict=True
        )
    ))


def make_train_step(
    state_shardings: Any,
    x_sharding: NamedSharding,
    mesh: Mesh,
    rules: Rules,
    *,
    loss_fn: Callable[..., jax.Array] = default_loss,
    donate_state: bool = True,
    dropout_rng: jax.Array | None = None,
    aux_loss_collection: str | None = None,
    loss_needs_params: bool = False,
    apply_kwargs: dict[str, Any] | None = None,
    grad_accum_steps: int = 1,
    steps_per_call: int = 1,
    with_grad_norm: bool = False,
    skip_nonfinite: bool = False,
    routing_stats: bool = False,
) -> Callable[[TrainState, Any], tuple[TrainState, jax.Array]]:
    """Build the jitted SPMD train step: grad → apply_gradients → (state, loss).

    Mirrors `/root/reference/case6_attention.py:206-215` with two fixes: the
    loss is returned (not discarded) and the incoming state is donated so
    parameter/moment buffers are updated in place.

    ``x_sharding`` must match the batch structure (a single sharding for a
    bare-array batch, or a dict of shardings for a dict batch).

    ``dropout_rng``: pass a PRNG key to train with dropout active — the model
    is then applied with ``deterministic=False`` and a per-step key folded in
    from ``state.step`` (the model must accept a ``deterministic`` kwarg, as
    all framework models do). Left ``None``, dropout stays off.

    ``aux_loss_collection``: name of a Flax variable collection (e.g.
    ``"losses"``) whose sown scalars — MoE load-balancing terms — are summed
    into the task loss each step.

    ``loss_needs_params``: call ``loss_fn(y, batch, params)`` — for losses
    that apply parameters themselves (e.g. the chunked logits head of
    ``models.transformer.fused_next_token_loss``).

    ``apply_kwargs``: extra kwargs for the model apply (e.g.
    ``{"return_hidden": True}`` to pair with the fused loss).

    ``grad_accum_steps``: split the batch into this many microbatches along
    the leading axis and accumulate gradients over a ``lax.scan`` before the
    single optimizer update — a global batch larger than HBM allows, at the
    cost of one fwd+bwd per microbatch. The per-device batch dim must divide.
    Loss and gradients are AVERAGED over microbatches, which reproduces the
    full-batch step exactly for mean-over-batch losses (``next_token_loss``
    etc.). A sum-style loss (including ``default_loss``) ends up scaled by
    ``1/grad_accum_steps`` relative to the unaccumulated step — use a mean
    loss when accumulating.

    ``with_grad_norm``: return ``(state, {"loss": ..., "grad_norm": ...})``
    instead of ``(state, loss)`` — the global gradient norm computed INSIDE
    the step (``optax.global_norm``, a reduction XLA fuses into the
    backward's epilogue: no extra pass, no extra sync), so a health
    watchdog (``telemetry.watchdog``) can check both numbers on device.

    ``skip_nonfinite``: gate the optimizer update ON DEVICE by
    ``isfinite(loss) & isfinite(grad_norm)`` — a NaN/Inf step returns the
    incoming params/optimizer state unchanged (element-wise selects, no
    new collectives: the program keeps the ``train_step_gn`` SPMD
    contract), so a bad batch can never write corruption into the state
    even with donation on. Implies the grad-norm dict output (the host
    reads the non-finite loss/grad-norm and knows the step was skipped);
    ``training/loop.py::fit(resilience=...)`` drives it.

    ``routing_stats``: return ``(state, {"loss": ..., "moe_held_assignments":
    ..., "moe_experts_touched": ..., "moe_expert_max_load": ...})``: what the
    dropless expert layers (``models.moe.DroplessMoE``) counted in this step,
    summed over the layers (the largest load of one expert in one layer for
    the last), int32 scalars computed on the device beside the loss and read
    with it: no sync of their own. Not with ``grad_accum_steps`` or
    ``aux_loss_collection``.

    A model built with ``remat=True`` (and no explicit ``remat_policy``)
    keeps of each block what this step has room for: while the step is
    traced it tells the model, through ``utils.memory.remat_scope``, what its
    device holds (``utils.memory.device_memory_bytes``: 0 of budget on the
    emulated CPU mesh), the bytes of ``state`` a device is given and how the
    mesh divides the batch and the widths. The returned callable's ``remat``
    (a ``utils.memory.RematScope``) holds the resolved ``plan`` after the
    first call (names by block, bytes kept, budget, predicted peak), which
    is also logged once at INFO.

    ``steps_per_call``: run this many FULL optimizer steps per jitted call
    (a ``lax.scan``); the batch then carries a leading ``(steps_per_call,)``
    dim of per-step batches and the returned loss is the per-step
    ``(steps_per_call,)`` vector. Each scan iteration is exactly the
    single-step program, with the state carried in place — this amortizes
    per-call host dispatch and keeps the optimizer update
    buffer-donating even when the CALLER cannot donate (the v5e 125M bench:
    single-call no-donate timing reads 66.5 ms/step, the scanned in-place
    regime 63.0 — the honest sustained-training number).
    """

    if routing_stats and (grad_accum_steps != 1 or aux_loss_collection):
        raise ValueError(
            "routing_stats counts one forward pass a step: not with "
            "grad_accum_steps or aux_loss_collection"
        )

    # What the model under ``remat=True`` resolves its plan from; the state's
    # bytes are read off the traced state, so every trace of ``step`` (the
    # call's, a ``.lower()``) sees the same budget.
    remat = RematScope(
        device_bytes=device_memory_bytes(mesh.devices.flat[0]),
        n_data_shards=_mesh_shards(mesh, rules, BATCH),
        n_model_shards=_mesh_shards(mesh, rules, MLP),
    )

    def step(state: TrainState, batch: Any):
        remat.state_bytes = _device_bytes_of(state, state_shardings)
        with remat_scope(remat):
            out = _step(state, batch)
        if remat.plan is not None:
            log.info("train step: %s", remat.plan.summary())
        return out

    def _step(state: TrainState, batch: Any):
        def loss_of_params(params, batch, micro_idx=0):
            kwargs: dict[str, Any] = dict(apply_kwargs or {})
            if dropout_rng is not None:
                # Per-step AND per-microbatch key: microbatches must draw
                # independent dropout masks or the accumulated gradient
                # correlates the noise across the whole global batch.
                key = jax.random.fold_in(dropout_rng, state.step)
                kwargs.update(
                    deterministic=False,
                    rngs={"dropout": jax.random.fold_in(key, micro_idx)},
                )
            aux, counted = 0.0, {}
            if aux_loss_collection is not None:
                y, mut = state.apply_fn(
                    {"params": params},
                    _inputs_of(batch),
                    mutable=(aux_loss_collection,),
                    **kwargs,
                )
                for leaf in jax.tree.leaves(mut):
                    aux = aux + jnp.sum(leaf)
            elif routing_stats:
                from learning_jax_sharding_tpu.models.moe import ROUTING_STATS

                y, mut = state.apply_fn(
                    {"params": params}, _inputs_of(batch),
                    mutable=(ROUTING_STATS,), **kwargs,
                )
                layers = jnp.stack(jax.tree.leaves(mut))       # (layers, 3)
                counted = {
                    "moe_held_assignments": jnp.sum(layers[:, 0]),
                    "moe_experts_touched": jnp.sum(layers[:, 1]),
                    "moe_expert_max_load": jnp.max(layers[:, 2]),
                }
            else:
                y = state.apply_fn({"params": params}, _inputs_of(batch), **kwargs)
            loss_args = (y, batch, params) if loss_needs_params else (y, batch)
            return loss_fn(*loss_args) + aux, counted

        # ``counted`` rides as the auxiliary output: {} without routing_stats.
        grad_fn = jax.value_and_grad(loss_of_params, has_aux=True)
        counted = {}
        if grad_accum_steps == 1:
            (loss, counted), grads = grad_fn(state.params, batch)
        else:
            accum_idx = jnp.arange(grad_accum_steps)
            def to_micro(x):
                if x.shape[0] % grad_accum_steps:
                    raise ValueError(
                        f"batch dim {x.shape[0]} not divisible by "
                        f"grad_accum_steps {grad_accum_steps}"
                    )
                return x.reshape(
                    grad_accum_steps, x.shape[0] // grad_accum_steps, *x.shape[1:]
                )

            micro = jax.tree.map(to_micro, batch)

            def body(acc, idx_mb):
                idx, mb = idx_mb
                (loss_i, _), grads_i = grad_fn(state.params, mb, idx)
                return (
                    acc[0] + loss_i,
                    jax.tree.map(jnp.add, acc[1], grads_i),
                ), None

            init = (
                jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, state.params),
            )
            (loss_sum, grad_sum), _ = jax.lax.scan(body, init, (accum_idx, micro))
            loss = loss_sum / grad_accum_steps
            grads = jax.tree.map(lambda g: g / grad_accum_steps, grad_sum)
        if with_grad_norm or skip_nonfinite:
            gnorm = optax.global_norm(grads)
            new_state = state.apply_gradients(grads=grads)
            if skip_nonfinite:
                # The guard: params/opt_state keep their OLD buffers when
                # the step's health check fails — step count still
                # advances (resume alignment: state.step == loop index).
                ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)

                def sel(new, old):
                    return jnp.where(ok, new, old)

                new_state = new_state.replace(
                    params=jax.tree.map(sel, new_state.params, state.params),
                    opt_state=jax.tree.map(
                        sel, new_state.opt_state, state.opt_state
                    ),
                )
            return new_state, {"loss": loss, "grad_norm": gnorm, **counted}
        new_state = state.apply_gradients(grads=grads)
        return new_state, {"loss": loss, **counted} if routing_stats else loss

    scalar_sh = NamedSharding(mesh, jax.sharding.PartitionSpec())
    if steps_per_call == 1:
        jitted = jax.jit(
            step,
            in_shardings=(state_shardings, x_sharding),
            out_shardings=(state_shardings, scalar_sh),
            donate_argnums=(0,) if donate_state else (),
        )
    else:
        def multi(state: TrainState, batches: Any):
            return jax.lax.scan(step, state, batches)

        def stack_sh(sh):
            return NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, *sh.spec)
            )

        jitted = jax.jit(
            multi,
            in_shardings=(state_shardings, jax.tree.map(stack_sh, x_sharding)),
            out_shardings=(state_shardings, scalar_sh),
            donate_argnums=(0,) if donate_state else (),
        )

    def run(state: TrainState, batch: Any):
        with activate(mesh, rules):
            return jitted(state, batch)

    run.jitted = jitted  # expose for lowering/HLO inspection
    run.remat = remat    # .plan: what remat=True keeps, once traced
    return run


def make_eval_step(
    mesh: Mesh,
    rules: Rules,
    *,
    loss_fn: Callable[..., jax.Array] = default_loss,
    loss_needs_params: bool = False,
    apply_kwargs: dict[str, Any] | None = None,
) -> Callable[[TrainState, Any], jax.Array]:
    """Build the jitted loss-only forward: ``eval_step(state, batch) -> loss``.

    No gradients, no state update — a held-out evaluation pass (absent from
    the reference, whose train_step even discards the training loss,
    SURVEY.md §5 "Metrics"). Input shardings are INFERRED from the state and
    batch actually passed (a trained state arrives correctly sharded from the
    train pipeline; rebuilding matching sharding trees is impossible anyway —
    TrainState's pytree metadata embeds the optimizer closures, so two
    ``sharded_train_state`` calls never compare equal); only the scalar loss
    is pinned, replicated.
    """

    def ev(state: TrainState, batch: Any):
        y = state.apply_fn(
            {"params": state.params}, _inputs_of(batch), **(apply_kwargs or {})
        )
        loss_args = (y, batch, state.params) if loss_needs_params else (y, batch)
        return loss_fn(*loss_args)

    jitted = jax.jit(
        ev,
        out_shardings=NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )

    def run(state: TrainState, batch: Any):
        with activate(mesh, rules):
            return jitted(state, batch)

    run.jitted = jitted
    return run


def make_apply_fn(
    state_shardings: Any,
    x_sharding: NamedSharding,
    mesh: Mesh,
    rules: Rules,
) -> Callable[[TrainState, jax.Array], jax.Array]:
    """Build the jitted forward: ``apply_fn(state, x) -> y``, y sharded like x.

    Mirrors `/root/reference/case6_attention.py:229-232`.
    """

    def fwd(state: TrainState, x: jax.Array):
        return state.apply_fn({"params": state.params}, x)

    jitted = jax.jit(
        fwd,
        in_shardings=(state_shardings, x_sharding),
        out_shardings=x_sharding,
    )

    def run(state: TrainState, x: jax.Array):
        with activate(mesh, rules):
            return jitted(state, x)

    run.jitted = jitted
    return run
