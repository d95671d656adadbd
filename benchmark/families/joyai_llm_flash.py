"""JoyAI-LLM-Flash (``jdopensource/JoyAI-LLM-Flash``, ``model_type``
``joyai_llm_flash``): latent attention, one dense layer, then 256
sigmoid-routed experts with a shared expert. The configuration file holds the
keys of the model's own ``config.json``; the program's own
``models.convert.config_from_hf_joyai_llm_flash`` maps them, imported when
``to_config`` is CALLED: a program without that converter fails there, at
once. The plain reference is beside this file (``joyai_llm_flash_reference``);
importing this module also registers the family's cost formulas and its
counted reader (``benchmark/moe_costs.py``).
"""

from __future__ import annotations

import types

from benchmark import moe_costs
from benchmark.families.joyai_llm_flash_reference import reference_fn  # noqa: F401

moe_costs.register()


def model_dims(cfg: dict) -> dict:
    return {
        "num_layers": cfg["num_hidden_layers"],
        "features": cfg["hidden_size"],
        "num_heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"],
        "nope_dim": cfg["qk_nope_head_dim"],
        "rope_dim": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"],
        "hidden": cfg["intermediate_size"],
        "first_k_dense": cfg["first_k_dense_replace"],
        "num_experts": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "expert_hidden": cfg["moe_intermediate_size"],
        "shared_experts": cfg["n_shared_experts"],
        "routed_scaling": cfg["routed_scaling_factor"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": cfg["rms_norm_eps"],
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
    }


def to_config(cfg: dict, **overrides):
    from learning_jax_sharding_tpu.models.convert import (
        config_from_hf_joyai_llm_flash,
    )

    if overrides.get("decode_attention") == "blocked":
        # Off the chip (a rehearsal) the harness forces the paged kernel
        # under the interpreter; the expert kernel goes the same way.
        overrides.setdefault("moe_experts", "pallas")
    hf = types.SimpleNamespace(**{k: v for k, v in cfg.items() if isinstance(k, str)})
    return config_from_hf_joyai_llm_flash(hf, **overrides)
