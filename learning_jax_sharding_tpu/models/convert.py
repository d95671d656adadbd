"""HuggingFace GPT-2 interop: load transformer weights into this framework.

"A user of the reference should be able to switch and find everything they
need" — including their existing checkpoints. GPT-2's architecture is a
pre-LN transformer with learned positions, biased projections, and tanh
GELU: exactly :class:`models.transformer.Transformer` at
``use_bias=True, norm_eps=1e-5`` (the reference has no model zoo or
checkpoint interop at all, SURVEY.md §5). This module maps a
``transformers`` GPT-2 state dict onto this framework's param tree, after
which the ENTIRE stack applies unchanged: sharded apply under any rule set,
KV-cached generation, beam search, int8/int4 serving, LoRA fine-tuning.

Parity is exact, not approximate: ``tests/test_convert.py`` checks logits
against the torch model to float tolerance. Works offline — the tests build
randomly initialized ``GPT2LMHeadModel``s (no downloads); real checkpoints
convert the same way.

Layout notes (verified against ``transformers`` GPT-2):

* HF ``Conv1D`` stores weights ``(in, out)`` — the same orientation as our
  Dense kernels, so no transposes except the tied LM head;
* ``c_attn`` packs q/k/v as one ``(E, 3E)`` kernel → split into three;
  the per-head layout after reshaping ``E → (heads, head_dim)`` matches our
  ``(B, S, N, H)`` reshape, so no head permutation is needed;
* the LM head is tied to the token embedding: ``lm_head.kernel = wteᵀ``.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from learning_jax_sharding_tpu.models.transformer import TransformerConfig


def config_from_hf_gpt2(hf_config: Any, **overrides) -> TransformerConfig:
    """TransformerConfig matching a ``transformers.GPT2Config``.

    ``overrides`` pass through to the dataclass (e.g. ``dtype=jnp.bfloat16``
    for TPU serving of a converted checkpoint).
    """
    if hf_config.activation_function not in ("gelu_new", "gelu_pytorch_tanh"):
        raise ValueError(
            f"unsupported activation {hf_config.activation_function!r}: the "
            "FeedForward uses tanh GELU (gelu_new)"
        )
    # GPT-2 attention variants this attention stack does not implement —
    # converting them would produce silently wrong logits, breaking the
    # module's exact-parity contract.
    for flag in ("scale_attn_by_inverse_layer_idx", "reorder_and_upcast_attn"):
        if getattr(hf_config, flag, False):
            raise ValueError(f"unsupported GPT-2 attention variant: {flag}=True")
    if not getattr(hf_config, "scale_attn_weights", True):
        # This attention stack always scales scores by head_dim**-0.5.
        raise ValueError(
            "unsupported GPT-2 attention variant: scale_attn_weights=False"
        )
    if hf_config.n_embd % hf_config.n_head:
        # HF only catches this at model init; fail at config conversion with
        # the same loudness as the unsupported-variant guards above.
        raise ValueError(
            f"n_embd {hf_config.n_embd} not divisible by n_head "
            f"{hf_config.n_head}: head_dim would be fractional"
        )
    import jax.numpy as jnp

    defaults = dict(
        vocab_size=hf_config.vocab_size,
        num_layers=hf_config.n_layer,
        features=hf_config.n_embd,
        num_heads=hf_config.n_head,
        head_dim=hf_config.n_embd // hf_config.n_head,
        # n_inner=None means the GPT-2 default of 4*n_embd.
        hidden=hf_config.n_inner or 4 * hf_config.n_embd,
        max_seq_len=hf_config.n_positions,
        use_bias=True,
        norm_eps=hf_config.layer_norm_epsilon,
        norm="layernorm",
        rope=False,
        causal=True,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def config_from_hf_joyai_llm_flash(hf_config: Any, **overrides) -> TransformerConfig:
    """TransformerConfig for a ``joyai_llm_flash`` ``config.json`` (the
    DeepSeek-V3 key set: latent attention, ``first_k_dense_replace`` dense
    layers, then sigmoid-routed dropless experts with shared experts).

    Refuses what the program does not compute rather than approximate it:
    grouped top-k (``n_group`` / ``topk_group`` > 1), rope scaling, biases,
    a non-interleaved rotary layout, unnormalised pick weights, expert
    layers at another frequency than every layer. The multi-token-
    prediction modules (``num_nextn_predict_layers``) are a training loss
    and an optional drafter: they are not built, whatever the key says.
    """
    c = hf_config
    unsupported = {
        "hidden_act": c.hidden_act != "silu",
        "scoring_func": c.scoring_func != "sigmoid",
        "topk_method": c.topk_method != "noaux_tc",
        "n_group/topk_group": (c.n_group, c.topk_group) != (1, 1),
        "norm_topk_prob": not c.norm_topk_prob,
        "rope_scaling": c.rope_scaling is not None,
        "rope_interleave": not c.rope_interleave,
        "attention_bias": bool(c.attention_bias),
        "moe_layer_freq": c.moe_layer_freq != 1,
        "tie_word_embeddings": bool(c.tie_word_embeddings),
        "num_key_value_heads": c.num_key_value_heads != c.num_attention_heads,
        "qk_head_dim": c.qk_head_dim != c.qk_nope_head_dim + c.qk_rope_head_dim,
    }
    bad = sorted(k for k, v in unsupported.items() if v)
    if bad:
        raise ValueError(
            f"unsupported joyai_llm_flash settings: {bad} (the program "
            "computes sigmoid noaux_tc routing in one group, interleaved "
            "un-scaled RoPE, no biases, an untied head)"
        )
    import jax.numpy as jnp

    defaults = dict(
        vocab_size=c.vocab_size,
        num_layers=c.num_hidden_layers,
        features=c.hidden_size,
        num_heads=c.num_attention_heads,
        head_dim=c.v_head_dim,
        hidden=c.intermediate_size,
        max_seq_len=c.max_position_embeddings,
        use_bias=False,
        norm="rmsnorm",
        norm_eps=c.rms_norm_eps,
        rope=True,                      # no position table
        rope_theta=float(c.rope_theta),
        causal=True,
        latent_kv_rank=c.kv_lora_rank,
        latent_q_rank=c.q_lora_rank,
        qk_nope_dim=c.qk_nope_head_dim,
        qk_rope_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim,
        ff_gated=True,
        first_k_dense=c.first_k_dense_replace,
        num_experts=c.n_routed_experts,
        moe_top_k=c.num_experts_per_tok,
        moe_routing="sigmoid_dropless",
        moe_hidden=c.moe_intermediate_size,
        moe_shared_experts=c.n_shared_experts,
        moe_routed_scaling=float(c.routed_scaling_factor),
        dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def config_from_hf_nemotron_h(hf_config: Any, **overrides) -> TransformerConfig:
    """TransformerConfig for a ``nemotron_h`` ``config.json`` (NVIDIA
    Nemotron-H / Nemotron-3: ONE mixer a layer by
    ``hybrid_override_pattern`` — ``M`` Mamba-2, ``E`` latent relu^2
    experts under sigmoid routing with a shared expert, ``*`` grouped-query
    attention without positional encoding).

    Refuses what the program does not compute rather than approximate it:
    a pattern character it does not build (``-``, the family's dense MLP
    layer), grouped top-k, unnormalised pick weights, biases other than
    the convolution's, a sliding window, tied embeddings, an expansion
    that is not ``mamba_num_heads x mamba_head_dim``, another activation
    than ``relu2`` / ``silu``. ``rope_theta`` and ``partial_rotary_factor``
    are in the file and the family's attention reads neither. The
    multi-token-prediction module (``num_nextn_predict_layers``) is not
    built, whatever the key says. ``chunk_size`` is the chunked scan's
    tile; ``time_step_*`` and ``rescale_prenorm_residual`` act at
    initialisation only.
    """
    from learning_jax_sharding_tpu.models.transformer import MIXER_KINDS

    c = hf_config
    pattern = c.hybrid_override_pattern
    if "-" in pattern:
        raise ValueError(
            "hybrid_override_pattern holds '-', the nemotron_h dense MLP "
            "layer: the program builds 'M' (Mamba-2), 'E' (experts) and '*' "
            "(attention) layers only"
        )
    unknown = sorted(set(pattern) - set(MIXER_KINDS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern holds unknown layer kinds {unknown} "
            f"(known: {MIXER_KINDS})"
        )
    unsupported = {
        "hybrid_override_pattern/num_hidden_layers":
            len(pattern) != c.num_hidden_layers,
        "mlp_hidden_act": c.mlp_hidden_act != "relu2",
        "mamba_hidden_act": c.mamba_hidden_act != "silu",
        "n_group/topk_group": (c.n_group, c.topk_group) != (1, 1),
        "norm_topk_prob": not c.norm_topk_prob,
        "attention_bias/mlp_bias/use_bias/mamba_proj_bias": bool(
            c.attention_bias or c.mlp_bias or c.use_bias or c.mamba_proj_bias
        ),
        "use_conv_bias": not c.use_conv_bias,
        "sliding_window": c.sliding_window is not None,
        "tie_word_embeddings": bool(c.tie_word_embeddings),
        "expand": c.expand * c.hidden_size != c.mamba_num_heads * c.mamba_head_dim,
        "moe_shared_expert_overlap": bool(c.moe_shared_expert_overlap),
        "n_shared_experts": c.n_shared_experts != 1,
    }
    bad = sorted(k for k, v in unsupported.items() if v)
    if bad:
        raise ValueError(
            f"unsupported nemotron_h settings: {bad} (the program computes "
            "relu2 experts under sigmoid routing in one group with one "
            "shared expert, silu Mamba-2 with a convolution bias and no "
            "other, full causal attention, an untied head)"
        )
    import jax.numpy as jnp

    defaults = dict(
        vocab_size=c.vocab_size,
        num_layers=c.num_hidden_layers,
        layer_pattern=pattern,
        features=c.hidden_size,
        num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads,
        head_dim=c.head_dim,
        hidden=c.intermediate_size,
        max_seq_len=c.max_position_embeddings,
        use_bias=False,
        norm="rmsnorm",
        norm_eps=c.layer_norm_epsilon,
        no_positions=True,
        causal=True,
        ssm_heads=c.mamba_num_heads,
        ssm_head_dim=c.mamba_head_dim,
        ssm_groups=c.n_groups,
        ssm_state_size=c.ssm_state_size,
        ssm_conv_kernel=c.conv_kernel,
        ssm_chunk=c.chunk_size,
        num_experts=c.n_routed_experts,
        moe_top_k=c.num_experts_per_tok,
        moe_routing="sigmoid_dropless",
        moe_hidden=c.moe_intermediate_size,
        moe_expert_act="relu2",
        moe_latent=c.moe_latent_size,
        moe_shared_experts=c.n_shared_experts,
        moe_shared_hidden=c.moe_shared_expert_intermediate_size,
        moe_routed_scaling=float(c.routed_scaling_factor),
        dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def config_from_hf_lfm2_moe(hf_config: Any, **overrides) -> TransformerConfig:
    """TransformerConfig for an ``lfm2_moe`` ``config.json`` (LiquidAI
    LFM2-8B-A1B): per layer an OPERATOR from ``layer_types`` (``conv``: a
    gated short convolution of ``conv_L_cache`` taps; ``full_attention``:
    grouped-query attention with an RMSNorm over each head of q and k before
    RoPE) and a feed-forward, dense SwiGLU of ``intermediate_size`` below
    ``num_dense_layers`` and ``num_experts`` sigmoid-routed SwiGLU experts
    of ``moe_intermediate_size`` from there on (top ``num_experts_per_tok``
    of score + a selection bias, weights renormalised over the picks with
    1e-6 in the sum, no shared expert); RMSNorm throughout, no bias, a head
    tied to the embedding unless ``tie_word_embeddings`` says otherwise.

    Refuses what the program does not compute rather than approximate it:
    ``conv_bias``, an operator it does not build, unnormalised pick weights,
    a router without its selection bias, RoPE scaling. The routing follows
    ``transformers``' ``modeling_lfm2_moe.py`` as recalled and the config's
    keys (that file is not on this machine; the operator, attention, dense
    feed-forward and layer are checked against ``modeling_lfm2.py``,
    ``tests/test_lfm2_moe.py``).
    """
    from learning_jax_sharding_tpu.models.transformer import OPERATOR_KINDS

    c = hf_config
    layer_types = tuple(c.layer_types)
    unknown = sorted(set(layer_types) - set(OPERATOR_KINDS))
    if unknown:
        raise ValueError(
            f"layer_types holds unknown operators {unknown} (known: "
            f"{OPERATOR_KINDS})"
        )
    unsupported = {
        "layer_types/num_hidden_layers": len(layer_types) != c.num_hidden_layers,
        "conv_bias": bool(c.conv_bias),
        "norm_topk_prob": not c.norm_topk_prob,
        "use_expert_bias": not c.use_expert_bias,
        "rope_scaling": getattr(c, "rope_scaling", None) is not None,
        "num_dense_layers": not 0 <= c.num_dense_layers <= c.num_hidden_layers,
    }
    bad = sorted(k for k, v in unsupported.items() if v)
    if bad:
        raise ValueError(
            f"unsupported lfm2_moe settings: {bad} (the program computes "
            "bias-free short convolutions, sigmoid routing with a selection "
            "bias and renormalised picks, un-scaled RoPE)"
        )
    import jax.numpy as jnp

    defaults = dict(
        vocab_size=c.vocab_size,
        num_layers=c.num_hidden_layers,
        layer_types=layer_types,
        conv_kernel=c.conv_L_cache,
        features=c.hidden_size,
        num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads,
        head_dim=getattr(c, "head_dim", None)
        or c.hidden_size // c.num_attention_heads,
        hidden=c.intermediate_size,
        max_seq_len=c.max_position_embeddings,
        use_bias=False,
        norm="rmsnorm",
        norm_eps=c.norm_eps,
        rope=True,
        rope_theta=float(c.rope_theta),
        qk_norm=True,
        causal=True,
        ff_gated=True,
        first_k_dense=c.num_dense_layers,
        num_experts=c.num_experts,
        moe_top_k=c.num_experts_per_tok,
        moe_routing="sigmoid_dropless",
        moe_hidden=c.moe_intermediate_size,
        moe_routed_scaling=float(c.routed_scaling_factor),
        moe_renorm_eps=1e-6,
        tie_embeddings=bool(getattr(c, "tie_word_embeddings", True)),
        dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def params_from_hf_gpt2(hf_model: Any) -> dict:
    """Map a ``transformers.GPT2LMHeadModel`` state dict onto this
    framework's ``Transformer`` param tree (plain numpy leaves — shard with
    ``jax.device_put`` / the sharded-init pipeline as usual)."""
    sd = {k: v.detach().cpu().numpy() for k, v in hf_model.state_dict().items()}
    n_layer = hf_model.config.n_layer
    e = hf_model.config.n_embd

    def t(name):
        return sd[f"transformer.{name}"].astype(np.float32)

    # GPT-2 usually ties the LM head to wte; reading "lm_head.weight" is
    # correct for tied AND untied checkpoints (tied state dicts alias it).
    head = sd.get("lm_head.weight", sd["transformer.wte.weight"])
    params: dict = {
        "tok_embed": {"embedding": t("wte.weight")},
        "pos_embed": t("wpe.weight"),
        "ln_out": {"scale": t("ln_f.weight"), "bias": t("ln_f.bias")},
        "lm_head": {"kernel": head.astype(np.float32).T},
    }
    for i in range(n_layer):
        p = f"h.{i}"
        qkv_w = t(f"{p}.attn.c_attn.weight")  # (E, 3E), Conv1D = (in, out)
        qkv_b = t(f"{p}.attn.c_attn.bias")
        params[f"block_{i}"] = {
            "ln_attn": {
                "scale": t(f"{p}.ln_1.weight"), "bias": t(f"{p}.ln_1.bias")
            },
            "attn": {
                "query": {"kernel": qkv_w[:, :e], "bias": qkv_b[:e]},
                "key": {"kernel": qkv_w[:, e : 2 * e], "bias": qkv_b[e : 2 * e]},
                "value": {"kernel": qkv_w[:, 2 * e :], "bias": qkv_b[2 * e :]},
                "out": {
                    "kernel": t(f"{p}.attn.c_proj.weight"),
                    "bias": t(f"{p}.attn.c_proj.bias"),
                },
            },
            "ln_ff": {
                "scale": t(f"{p}.ln_2.weight"), "bias": t(f"{p}.ln_2.bias")
            },
            "ff": {
                "up": {
                    "kernel": t(f"{p}.mlp.c_fc.weight"),
                    "bias": t(f"{p}.mlp.c_fc.bias"),
                },
                "down": {
                    "kernel": t(f"{p}.mlp.c_proj.weight"),
                    "bias": t(f"{p}.mlp.c_proj.bias"),
                },
            },
        }
    return params


def state_dict_from_params(params: dict, *, tie_head: bool = True) -> dict:
    """Inverse of :func:`params_from_hf_gpt2`: framework params → a
    ``transformers`` GPT-2 state dict (torch tensors), so models trained or
    fine-tuned here (e.g. LoRA-merged) export back to the HF ecosystem.

    ``tie_head`` drops the separate ``lm_head.weight`` entry and lets HF tie
    it to ``wte`` (set False for params whose head was trained untied).
    Load with ``hf_model.load_state_dict(sd, strict=False)`` (HF carries
    non-weight buffers like attention bias masks that this does not emit).
    Trees trained with ``scan_layers`` are unstacked automatically.
    """
    import torch

    params = unstack_scan_params(params)

    def tt(x):
        return torch.tensor(np.asarray(x, np.float32))

    sd = {
        "transformer.wte.weight": tt(params["tok_embed"]["embedding"]),
        "transformer.wpe.weight": tt(params["pos_embed"]),
        "transformer.ln_f.weight": tt(params["ln_out"]["scale"]),
        "transformer.ln_f.bias": tt(params["ln_out"]["bias"]),
    }
    head_t = np.asarray(params["lm_head"]["kernel"], np.float32).T
    if tie_head:
        # Guard the default: exporting a DIVERGED head as "tied" would
        # silently drop trained weights (HF re-ties lm_head to wte on load).
        wte = np.asarray(params["tok_embed"]["embedding"], np.float32)
        if not np.allclose(head_t, wte, atol=1e-6):
            raise ValueError(
                "lm_head is not tied to tok_embed (they differ); export with "
                "tie_head=False to keep the trained head"
            )
    else:
        sd["lm_head.weight"] = tt(head_t)
    n_layer = sum(1 for k in params if k.startswith("block_"))
    if n_layer == 0:
        raise ValueError("no block_i subtrees found — not a Transformer param tree")
    for i in range(n_layer):
        blk = params[f"block_{i}"]
        p = f"transformer.h.{i}"
        attn = blk["attn"]
        qkv_w = np.concatenate(
            [np.asarray(attn[k]["kernel"], np.float32) for k in ("query", "key", "value")],
            axis=1,
        )
        qkv_b = np.concatenate(
            [np.asarray(attn[k]["bias"], np.float32) for k in ("query", "key", "value")]
        )
        sd.update({
            f"{p}.ln_1.weight": tt(blk["ln_attn"]["scale"]),
            f"{p}.ln_1.bias": tt(blk["ln_attn"]["bias"]),
            f"{p}.attn.c_attn.weight": tt(qkv_w),
            f"{p}.attn.c_attn.bias": tt(qkv_b),
            f"{p}.attn.c_proj.weight": tt(attn["out"]["kernel"]),
            f"{p}.attn.c_proj.bias": tt(attn["out"]["bias"]),
            f"{p}.ln_2.weight": tt(blk["ln_ff"]["scale"]),
            f"{p}.ln_2.bias": tt(blk["ln_ff"]["bias"]),
            f"{p}.mlp.c_fc.weight": tt(blk["ff"]["up"]["kernel"]),
            f"{p}.mlp.c_fc.bias": tt(blk["ff"]["up"]["bias"]),
            f"{p}.mlp.c_proj.weight": tt(blk["ff"]["down"]["kernel"]),
            f"{p}.mlp.c_proj.bias": tt(blk["ff"]["down"]["bias"]),
        })
    return sd


def unstack_scan_params(params: dict) -> dict:
    """``scan_layers`` stacked params → the unrolled per-layer layout.

    A model trained with ``scan_layers=True`` (O(1) compile time in depth)
    keeps its block params as one ``"blocks"`` subtree whose leaves carry a
    leading ``(LAYERS,)`` dim. Serving and export run the unrolled stack
    (``block_0..block_{L-1}``) — this splits each stacked leaf along that
    dim so the SAME trained weights drive decode / HF export. Inverse of
    :func:`stack_scan_params`; a tree already in the unrolled layout passes
    through unchanged. Math is identical either way (test-pinned logit
    parity, ``tests/test_scan_layers.py``).
    """
    if "blocks" not in params:
        return params
    if "embed" in params or "head" in params:
        # PipelinedTransformer trees also keep a "blocks" subtree, but its
        # leading dims are (stages, ...) — splitting those as layers would
        # silently produce wrong-rank per-layer tensors. Fail loudly instead.
        raise ValueError(
            "params look like a PipelinedTransformer stage-stacked tree "
            "(embed/blocks/head); unstack_scan_params handles only "
            "Transformer scan_layers trees"
        )
    import jax

    blocks = params["blocks"]
    num_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(num_layers):
        out[f"block_{i}"] = jax.tree.map(lambda x, i=i: x[i], blocks)
    return out


def stack_scan_params(params: dict) -> dict:
    """Unrolled ``block_i`` params → the ``scan_layers`` stacked layout
    (leaves gain a leading layer dim). Inverse of
    :func:`unstack_scan_params`; a tree already stacked passes through."""
    import jax
    import jax.numpy as jnp

    n_layer = sum(1 for k in params if k.startswith("block_"))
    if n_layer == 0:
        return params
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[params[f"block_{i}"] for i in range(n_layer)],
    )
    rest = {k: v for k, v in params.items() if not k.startswith("block_")}
    return {**rest, "blocks": stacked}
