"""GPT-2 (``openai-community/gpt2*``): the configuration file holds the
keys of the model's own ``config.json``; the program's own
``models.convert.config_from_hf_gpt2`` maps them, as for a user who brings a
GPT-2 checkpoint."""

from __future__ import annotations

import types

from benchmark.reference import reference_fn  # noqa: F401  (the family's reference)


def model_dims(cfg: dict) -> dict:
    return {
        "num_layers": cfg["n_layer"],
        "features": cfg["n_embd"],
        "num_heads": cfg["n_head"],
        "head_dim": cfg["n_embd"] // cfg["n_head"],
        "hidden": cfg.get("n_inner") or 4 * cfg["n_embd"],
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["n_positions"],
        "norm_eps": cfg["layer_norm_epsilon"],
    }


def to_config(cfg: dict, **overrides):
    from learning_jax_sharding_tpu.models.convert import config_from_hf_gpt2

    hf = types.SimpleNamespace(**{k: v for k, v in cfg.items() if isinstance(k, str)})
    return config_from_hf_gpt2(hf, **overrides)
