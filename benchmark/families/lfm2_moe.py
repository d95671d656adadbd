"""LFM2-8B-A1B (``LiquidAI/LFM2-8B-A1B``, ``model_type`` ``lfm2_moe``): per
layer an OPERATOR from ``layer_types`` (a gated short convolution of 3 taps,
or grouped-query attention with an RMSNorm over each head of q and k before
RoPE) and a feed-forward (two dense SwiGLU layers, then 32 sigmoid-routed
SwiGLU experts, top 4, no shared expert), a head tied to the embedding. The
configuration file holds the keys of the model's own ``config.json``; the
program's own ``models.convert.config_from_hf_lfm2_moe`` maps them, imported
when ``to_config`` is CALLED: a program without that converter fails there,
at once. The plain reference is beside this file (``lfm2_moe_reference``).

**The chip's share.** The file's ``num_experts`` counts the experts HELD
here (ids ``0 .. n - 1``: chip 0 of an expert-parallel group);
``published.num_experts`` is the router's width. ``to_config`` gives the
program the published width with ``moe_held=(0, held)``, and ``model_dims``
gives the reference the same range: both leave out what the absent experts
would add. ``vocab_size`` is the slice this chip holds.

Importing this module registers the family's cost formulas and its reader
(``benchmark/lfm2_moe_costs.py``: the whole step's model FLOPs on this chip,
the held experts' grouped products forward and backward, the ONE attention
layer's flash kernels). The short convolution is plain XLA ops, which a
capture names ``fusion.N`` whatever scope they were traced under: it has no
metric of its own.
"""

from __future__ import annotations

import types

from benchmark import lfm2_moe_costs
from benchmark.families.lfm2_moe_reference import reference_fn  # noqa: F401

lfm2_moe_costs.register()


def _router_width(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def model_dims(cfg: dict) -> dict:
    return {
        "num_layers": cfg["num_hidden_layers"],
        "layer_types": tuple(cfg["layer_types"]),
        "num_dense_layers": cfg["num_dense_layers"],
        "features": cfg["hidden_size"],
        "conv_kernel": cfg["conv_L_cache"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "hidden": cfg["intermediate_size"],
        "num_experts": _router_width(cfg),
        "held_first": 0,
        "held_count": cfg["num_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "expert_hidden": cfg["moe_intermediate_size"],
        "routed_scaling": cfg["routed_scaling_factor"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": cfg["norm_eps"],
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
    }


def to_config(cfg: dict, **overrides):
    from learning_jax_sharding_tpu.models.convert import config_from_hf_lfm2_moe

    keys = {k: v for k, v in cfg.items() if isinstance(k, str)}
    held = keys["num_experts"]
    keys["num_experts"] = _router_width(cfg)
    return config_from_hf_lfm2_moe(
        types.SimpleNamespace(**keys), moe_held=(0, held),
        moe_bias_init_std=cfg["init"]["expert_bias_init_std"],
        moe_expert_init_scale=cfg["init"]["expert_init_scale"],
        **overrides,
    )
