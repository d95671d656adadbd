"""poolside Laguna (``poolside/Laguna-XS.2``, ``model_type`` ``laguna``):
window and global attention layers by ``layer_types``, each with its own
head count (``num_attention_heads_per_layer``) and rotary, a per-head
sigmoid output gate, one leading dense SwiGLU layer, then 256 softmax-routed
SwiGLU experts (top 8, not renormalised) beside a shared expert. The
configuration file holds the keys of the model's own ``config.json``; the
program's own ``models.convert.config_from_hf_laguna`` maps them, imported
when ``to_config`` is CALLED: a program without that converter fails there,
at once. The plain reference is beside this file (``laguna_reference``).

Importing this module registers the family's cost formulas
(``benchmark/laguna_costs.py``: the window and global layers' decode pages).
"""

from __future__ import annotations

import types

from benchmark import laguna_costs
from benchmark.families.laguna_reference import reference_fn  # noqa: F401

laguna_costs.register()


def model_dims(cfg: dict) -> dict:
    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    return {
        "num_layers": cfg["num_hidden_layers"],
        "layer_types": list(cfg["layer_types"]),
        "mlp_layer_types": list(cfg["mlp_layer_types"]),
        "heads_per_layer": list(cfg["num_attention_heads_per_layer"]),
        "features": cfg["hidden_size"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "hidden": cfg["intermediate_size"],
        "num_experts": cfg["num_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "expert_hidden": cfg["moe_intermediate_size"],
        "shared_hidden": cfg["shared_expert_intermediate_size"],
        "routed_scaling": cfg["moe_routed_scaling_factor"],
        "norm_eps": cfg["rms_norm_eps"],
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
        "window": cfg["sliding_window"],
        "full_rope": {
            "theta": float(full["rope_theta"]),
            "rotary_dim": int(cfg["head_dim"] * full["partial_rotary_factor"]),
            "factor": float(full["factor"]),
            "original_max_positions": int(full["original_max_position_embeddings"]),
            "beta_fast": float(full["beta_fast"]),
            "beta_slow": float(full["beta_slow"]),
            "attention_factor": full.get("attention_factor"),
        },
        "sliding_theta": float(sliding["rope_theta"]),
    }


def to_config(cfg: dict, **overrides):
    from learning_jax_sharding_tpu.models.convert import config_from_hf_laguna

    if overrides.get("decode_attention") == "blocked":
        # Off the chip (a rehearsal) the harness forces the paged kernel
        # under the interpreter; the expert kernel goes the same way.
        overrides.setdefault("moe_experts", "pallas")
    hf = types.SimpleNamespace(**{k: v for k, v in cfg.items() if isinstance(k, str)})
    return config_from_hf_laguna(hf, **overrides)
