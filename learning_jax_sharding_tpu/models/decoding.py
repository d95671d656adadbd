"""Shared serving-path plumbing for the decoding entry points.

``make_generate_fn`` (sampling), ``make_beam_search_fn`` (beam search), and
``make_speculative_generate_fn`` (draft-verify) all need the same four
pieces; this module is their single copy, so policies like "how params are
cast for inference" or "how quantized trees are handled" cannot drift
between decoders:

* :func:`derive_decode_config` — turn a TRAINING config into its decode
  variant (KV caches on, dropout off, optional inference dtype swap);
* :func:`make_param_caster` — the eager params cast for ``inference_dtype``
  (eager on purpose: an in-program cast re-runs every scan step — measured
  20% slower on the v5e decode bench — and keeps the fp32 copies resident),
  quantization-aware: quantized ``{"q","scale"}`` / ``{"q4","scale"}``
  nodes pass through untouched;
* :func:`make_cached_apply` — the mutable-cache model apply every decoder
  loops over (prefill creates the caches, later calls thread them), with
  optional in-jit dequantization of int8/int4 weight trees;
* :func:`check_sequence_budget` — the prompt+new vs ``max_seq_len`` guard.

(The reference has no inference path at all, SURVEY.md §5 — these helpers
back the serving stack that replaces its timing-only ``apply_fn``,
`/root/reference/case6_attention.py:229-238`.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from learning_jax_sharding_tpu.models.transformer import TransformerConfig


def derive_decode_config(
    config: TransformerConfig,
    inference_dtype: Any | None = None,
    *,
    mesh: Any | None = None,
    rules: Any | None = None,
) -> TransformerConfig:
    """Decode variant of a TRAINING config: KV caches on, dropout off, and —
    when ``inference_dtype`` is given — compute/param dtypes swapped to it,
    so train and serve share params verbatim.

    With ``mesh``/``rules`` and more than one device, the blocked decode
    backend gets its shard_map wrapper injected
    (``ops.decode_attention.make_decode_attn_fn``) — GSPMD cannot partition
    the Pallas cache kernel by itself, so multi-device serving needs the
    explicitly sharded call."""
    # Decode always runs the UNROLLED stack: scan_layers is a compile-time
    # lever for training depth; its stacked params are unstacked at serve
    # time (make_param_caster), so train-with-scan → generate just works.
    cfg = dataclasses.replace(
        config, decode=True, dropout_rate=0.0, scan_layers=False
    )
    if inference_dtype is not None:
        cfg = dataclasses.replace(
            cfg, dtype=inference_dtype, param_dtype=inference_dtype
        )
    if mesh is not None and rules is not None and cfg.decode_attn_fn is None:
        from learning_jax_sharding_tpu.models.attention import resolve_decode_backend

        if mesh.size > 1 and resolve_decode_backend(cfg.decode_attention) == "blocked":
            from learning_jax_sharding_tpu.ops.decode_attention import (
                make_decode_attn_fn,
            )

            # window/block_k are NOT baked: the attention module passes its
            # own on every call (single source of truth).
            cfg = dataclasses.replace(
                cfg, decode_attn_fn=make_decode_attn_fn(mesh, rules)
            )
    return cfg


def apply_dequantize_policy(
    cfg: TransformerConfig, dequantize: bool | str, mesh: Any, rules: Any
) -> tuple[TransformerConfig, bool]:
    """THE quantized-serving policy, shared by every decoder
    (``make_generate_fn``, the continuous engine) so it cannot drift:
    validates the ``dequantize`` mode, and for the fused modes sets the
    config's ``quantization`` so int4 trees apply VERBATIM through the
    fused dequant-matmul kernels (``models/quantize.py::Int4Dense``) — no
    in-jit dequantize_tree, no dequantized weights in HBM. On >1-device
    meshes the kernel runs under an injected shard_map matmul (GSPMD
    cannot partition the custom call and would gather the packed
    weights). ``"fused_w4a8"`` additionally quantizes activations per-row
    to int8 so the contraction runs int8×int4→int32 on the MXU.

    Returns ``(cfg, fused)`` — callers build their cached apply with
    ``dequantize=bool(dequantize) and not fused`` and their param caster
    with ``dequantize=bool(dequantize)``."""
    if isinstance(dequantize, str) and dequantize not in (
        "fused", "fused_w4a8"
    ):
        raise ValueError(
            f"dequantize must be False, True, 'fused', or 'fused_w4a8'; "
            f"got {dequantize!r}"
        )
    fused = dequantize in ("fused", "fused_w4a8")
    if fused:
        w4a8 = dequantize == "fused_w4a8"
        cfg = dataclasses.replace(
            cfg, quantization="int4_w4a8" if w4a8 else "int4"
        )
        if mesh.size > 1:
            from learning_jax_sharding_tpu.ops.int4_matmul import (
                make_int4_matmul_fn,
            )

            cfg = dataclasses.replace(
                cfg,
                quantized_matmul_fn=make_int4_matmul_fn(
                    mesh, rules, w4a8=w4a8
                ),
            )
    return cfg, fused


def make_param_caster(
    inference_dtype: Any | None, *, dequantize: bool = False
) -> Callable[[Any], Any]:
    """Eager ``maybe_cast(params)`` for serving.

    Casts floating leaves to ``inference_dtype`` (identity when ``None``).
    With ``dequantize`` the tree holds int8/int4 quantized nodes from
    ``models.quantize.quantize_tree``: those stay untouched (the in-jit
    dequant picks the target dtype) while everything else — embeddings,
    norms, biases, often the largest remaining fp32 blocks — still casts.
    """

    def maybe_cast(params: Any) -> Any:
        # Trees trained with scan_layers arrive in the stacked "blocks"
        # layout; decode always runs the unrolled stack (derive_decode_config
        # flips scan_layers off), so unstack here — eagerly, once per call,
        # like the dtype cast (slicing per decode step inside jit would
        # re-materialize every layer's weights each token).
        if isinstance(params, dict) and "blocks" in params:
            from learning_jax_sharding_tpu.models.convert import unstack_scan_params

            params = unstack_scan_params(params)
        if inference_dtype is None:
            return params

        def cast(x):
            return (
                x.astype(inference_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x
            )

        if not dequantize:
            return jax.tree.map(cast, params)
        from learning_jax_sharding_tpu.models.quantize import map_unquantized

        return map_unquantized(cast, params)

    return maybe_cast


def make_cached_apply(
    model: Any, *, dequantize: bool = False, dequant_dtype: Any | None = None
) -> Callable[[Any, Any, jax.Array], tuple[jax.Array, Any]]:
    """The decode-loop workhorse: ``apply(params, cache, tokens) ->
    (fp32 logits, new cache)``.

    With ``cache=None`` the mutable apply CREATES the (zeroed) caches — that
    is the prefill call; later calls thread the cache through. With
    ``dequantize`` the int8/int4 tree is dequantized INSIDE each apply so
    the decode scan holds only quantized weights in its carry/constants (the
    storage win); whether XLA streams them into the matmuls or materializes
    the upcast is its call — ``bench.py`` measures it.
    """

    def apply(
        params: Any, cache: Any, tokens: jax.Array, chunk_lengths=None,
        logit_positions=None,
    ):
        if dequantize:
            from learning_jax_sharding_tpu.models.quantize import dequantize_tree

            params = dequantize_tree(params, dequant_dtype)
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        kwargs = {}
        if chunk_lengths is not None:  # ragged decode only (decode_ragged)
            kwargs["chunk_lengths"] = chunk_lengths
        if logit_positions is not None:  # head on one position a row: (B, 1, V)
            kwargs["logit_positions"] = logit_positions
        logits, mut = model.apply(
            variables, tokens, mutable=("cache",), **kwargs
        )
        return logits.astype(jnp.float32), mut["cache"]

    return apply


def check_sequence_budget(needed: int, max_seq_len: int, what: str) -> None:
    """Raise if a decode plan would write past the KV caches."""
    if needed > max_seq_len:
        raise ValueError(f"{what} ({needed}) exceeds max_seq_len ({max_seq_len})")
