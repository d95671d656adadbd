"""Logical-axis layer (L3): named model axes mapped to mesh axes by rules.

The reference introduces this in cases 5-6: kernels are initialized with
logical axis names via ``nn.with_logical_partitioning``
(`/root/reference/case5_attention_dense.py:61-63`,
`/root/reference/case6_attention.py:56-59`), activations are constrained with
``nn.with_logical_constraint`` (`case6_attention.py:105-116,137,141`), and a
rules tuple maps logical names to mesh axes at trace time
(`case6_attention.py:183-187`). This module gives that pipeline a home:
canonical axis names, named rule presets, and the
``eval_shape → get_partition_spec → logical_to_mesh_sharding`` plumbing.

Design note: the reference names the *sequence* dimension of activations
``'embed'`` (`case6_attention.py:105-107`) and questions its own choice at
`case5_attention_dense.py:63`; under its rules that accidentally shards the
sequence over the model axis. Here the sequence axis has its own name
(``SEQ``), and sequence sharding is an intentional, named choice
(:data:`RULES_DP_TP_SP`) rather than a naming accident — same capability,
deliberate semantics (SURVEY.md §2.4 "Sequence parallelism").
"""

from __future__ import annotations

import contextlib
from typing import Any, Sequence

import flax.linen as nn
import jax
from flax.linen import partitioning as nn_partitioning
from jax.sharding import Mesh, NamedSharding

# Canonical logical axis names used by every model in the framework.
BATCH = "batch"    # examples — data-parallel
SEQ = "seq"        # sequence / context positions
EMBED = "embed"    # model (residual-stream) features
HEADS = "heads"    # attention heads
KV = "kv"          # per-head feature dim (the reference's 'kv',
                   # `/root/reference/case5_attention_dense.py:61-63`)
HIDDEN = "hidden"  # feed-forward hidden features
MLP = "mlp"        # alias kept distinct for gated-FF variants
VOCAB = "vocab"    # embedding rows / logits columns
STAGE = "stage"    # pipeline stage (stretch, not in reference)
EXPERT = "expert"  # MoE expert (stretch, not in reference)
LAYERS = "layers"  # stacked-layer dim of nn.scan'd block stacks
                   # (models.transformer scan_layers; unmapped in every rule
                   # set → the layer dim stays unsharded, each param leaf
                   # keeps its per-layer sharding)

Rules = tuple[tuple[str, str | None], ...]

#: Case-6 parity rules (`/root/reference/case6_attention.py:183-187`):
#: batch→data, embed→model, hidden→model; heads/kv unmapped (replicated).
#: Kernels with ('embed', 'heads') split on their embed rows.
RULES_REFERENCE: Rules = (
    (BATCH, "data"),
    (EMBED, "model"),
    (HIDDEN, "model"),
)

#: Megatron-style tensor parallelism: QKV kernels column-parallel over heads,
#: output/down projections row-parallel over hidden; embed stays replicated so
#: the residual stream never needs resharding between blocks.
RULES_DP_TP: Rules = (
    (BATCH, "data"),
    (HEADS, "model"),
    (HIDDEN, "model"),
    (MLP, "model"),
    (VOCAB, "model"),
)

#: DP×TP plus intentional sequence sharding over the model axis between
#: attention blocks — the deliberate version of the reference's accidental
#: sequence-over-'model' placement (`/root/reference/case6_attention.py:161`).
RULES_DP_TP_SP: Rules = RULES_DP_TP + ((SEQ, "model"),)

#: Long-context layout: batch over data, sequence over model, weights
#: replicated — the activation layout ring attention wants (heads stay whole
#: per device; the sequence ring runs over the 'model' axis).
RULES_DP_SP: Rules = (
    (BATCH, "data"),
    (SEQ, "model"),
)

#: DP×TP plus expert parallelism: expert kernels (EXPERT, EMBED, MLP) shard
#: their E dim over 'model' — flax resolves duplicate mappings in RULE order
#: (verified), so EXPERT is listed before MLP to claim the axis; within the
#: same spec the later MLP→model duplicate is dropped. Dense FF kernels
#: (EMBED, MLP) still shard MLP — one rule set serves mixed dense/MoE stacks.
RULES_DP_TP_EP: Rules = (
    (BATCH, "data"),
    (HEADS, "model"),
    (HIDDEN, "model"),
    (EXPERT, "model"),
    (MLP, "model"),
    (VOCAB, "model"),
)

#: Explicit expert parallelism for the ALL-TO-ALL MoE dispatch
#: (``ops.moe_dispatch.make_moe_a2a_fn``): experts shard over the SAME
#: axis as the batch — each data-parallel worker owns E/D experts and the
#: dispatch exchanges token shards ↔ expert shards with one
#: ``lax.all_to_all`` each way (the DeepSpeed-MoE / GShard EP=DP
#: topology). Attention stays tensor-parallel over 'model'; MLP is NOT
#: mapped (expert FF width stays whole per device — TP-within-expert
#: would need a second exchange).
RULES_DP_EP_A2A: Rules = (
    (BATCH, "data"),
    (EXPERT, "data"),
    (HEADS, "model"),
    (HIDDEN, "model"),
    (VOCAB, "model"),
)

#: Serving layout for the PAGED KV cache: tensor parallelism only. The
#: batch stays replicated because any row's block table may point at any
#: physical page — a batch shard would need its own page pool and
#: allocator (models/serving.py ``paged_pages``). Kernel axes shard over
#: 'model' exactly as RULES_DP_TP.
RULES_TP_SERVING: Rules = (
    (HEADS, "model"),
    (HIDDEN, "model"),
    (MLP, "model"),
    (VOCAB, "model"),
)

#: Fully-sharded data parallel flavor: parameters sharded over the data axis
#: too (the case-3 zero-redundancy pattern, `/root/reference/case3_fully_sharded.py`).
RULES_FSDP: Rules = (
    (BATCH, "data"),
    (EMBED, "data"),
    (HEADS, "model"),
    (HIDDEN, "model"),
    (MLP, "model"),
)


def axis_rules(rules: Rules):
    """Context manager binding logical→mesh rules for traces underneath.

    Wraps ``flax.linen.partitioning.axis_rules``
    (`/root/reference/case6_attention.py:219,234`).
    """
    return nn_partitioning.axis_rules(rules)


@contextlib.contextmanager
def activate(mesh: Mesh, rules: Rules):
    """Enter both the mesh and the logical rules — every jitted trace in the
    sharded pipeline needs the pair (`/root/reference/case6_attention.py:219`)."""
    with mesh, nn_partitioning.axis_rules(rules):
        yield


def logical_sharding(mesh: Mesh, rules: Rules, *logical_axes: str | None) -> NamedSharding:
    """NamedSharding for an array whose dims carry ``logical_axes`` names.

    E.g. ``logical_sharding(mesh, RULES_DP_TP, BATCH, SEQ, EMBED)`` for an
    activation of shape (B, S, M).
    """
    spec = nn_partitioning.logical_to_mesh_axes(tuple(logical_axes), tuple(rules))
    return NamedSharding(mesh, spec)


def tree_shardings(abstract_tree: Any, mesh: Mesh, rules: Rules) -> Any:
    """Shardings for a whole (abstract) variable/TrainState tree.

    The ``nn.get_partition_spec`` → ``nn.logical_to_mesh_sharding`` step of the
    sharded-init pipeline (`/root/reference/case6_attention.py:190-191`).
    """
    spec = nn.get_partition_spec(abstract_tree)
    return nn.logical_to_mesh_sharding(spec, mesh, tuple(rules))


class Unstepped(nn.LogicallyPartitioned):
    """The box of a parameter that NO optimizer step moves: a buffer that
    lives among the parameters (``models.moe.DroplessMoE``'s selection bias,
    which only picks experts: its gradient is zero, and weight decay must
    not pull it to zero either). A ``LogicallyPartitioned`` box in every
    other way; ``training.pipeline.sharded_train_state`` reads the mark off
    the abstract tree and gives whatever optimizer it was handed a zero
    update for these leaves, wherever the module is mounted."""


def with_unstepped_partitioning(init, names):
    """``nn.with_logical_partitioning`` whose box is :class:`Unstepped`."""

    def boxed(*args, **kwargs):
        return Unstepped(init(*args, **kwargs), names)

    return boxed


def attention_mesh_axes(
    rules: Rules, axis: str | None = None
) -> tuple[str | None, str, str | None]:
    """Resolve the (batch, seq, heads) mesh axes of ``(B, S, N, H)`` attention
    operands under ``rules`` — the shared plumbing of the sequence-parallel
    attention factories (``make_ring_attn_fn`` / ``make_ulysses_attn_fn``).

    ``axis`` overrides the sequence axis; raises if neither the rules nor the
    override names one.
    """
    axes = nn_partitioning.logical_to_mesh_axes((BATCH, SEQ, HEADS, KV), tuple(rules))
    seq_axis = axis if axis is not None else axes[1]
    if seq_axis is None:
        raise ValueError("rules map SEQ to no mesh axis and no axis= was given")
    return axes[0], seq_axis, axes[2]


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Constrain an activation's sharding by logical axis names.

    Re-export of ``nn.with_logical_constraint``
    (`/root/reference/case6_attention.py:105-116`): a no-op outside an
    ``axis_rules``/mesh context, a GSPMD sharding constraint inside one.
    """
    return nn.with_logical_constraint(x, tuple(logical_axes))
