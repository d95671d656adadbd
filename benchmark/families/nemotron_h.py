"""NVIDIA-Nemotron-3-Super-120B-A12B (``nvidia/NVIDIA-Nemotron-3-Super-
120B-A12B-BF16``, ``model_type`` ``nemotron_h``): ONE mixer a layer from
``hybrid_override_pattern`` — ``M`` Mamba-2 (a fixed-size recurrent state a
request, not a cache of keys), ``E`` 512 sigmoid-routed relu^2 experts in a
1024-wide latent with a shared expert at full width, ``*`` grouped-query
attention without positional encoding. The configuration file holds the keys
of the model's own ``config.json``; the program's own
``models.convert.config_from_hf_nemotron_h`` maps them, imported when
``to_config`` is CALLED: a program without that converter fails there, at
once. The plain reference is beside this file (``nemotron_h_reference``).

**The chip's share.** The file's ``n_routed_experts`` counts the experts
HELD here (ids ``0 .. n - 1``: chip 0 of an expert-parallel group);
``published.n_routed_experts`` is the router's width. ``to_config`` gives
the program the published width with ``moe_held=(0, held)``, and
``model_dims`` gives the reference the same range: both leave out what the
absent experts would add. ``vocab_size`` is the slice this chip holds.

Importing this module registers the family's cost formulas
(``benchmark/nemotron_h_costs.py``: the Mamba state update and chunked scan,
the latent experts' two matrices) beside ``moe_costs``' counted reader. The
trace metrics find device time by INSTRUCTION name, which only a kernel
carries (a capture keeps no scope of a plain XLA fusion): ``ssm.state_update``
and ``ssm.chunk_scan`` are the Pallas calls of ``ops/ssm_scan.py``.
"""

from __future__ import annotations

import types

from benchmark import moe_costs, nemotron_h_costs
from benchmark.families.nemotron_h_reference import reference_fn  # noqa: F401

moe_costs.register()
nemotron_h_costs.register()


def _router_width(cfg: dict) -> int:
    return cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])


def model_dims(cfg: dict) -> dict:
    return {
        "num_layers": cfg["num_hidden_layers"],
        "pattern": cfg["hybrid_override_pattern"],
        "features": cfg["hidden_size"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "ssm_heads": cfg["mamba_num_heads"],
        "ssm_head_dim": cfg["mamba_head_dim"],
        "ssm_groups": cfg["n_groups"],
        "ssm_state": cfg["ssm_state_size"],
        "conv_kernel": cfg["conv_kernel"],
        "ssm_chunk": cfg["chunk_size"],
        "num_experts": _router_width(cfg),
        "held_first": 0,
        "held_count": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "expert_hidden": cfg["moe_intermediate_size"],
        "latent": cfg["moe_latent_size"],
        "shared_hidden": cfg["moe_shared_expert_intermediate_size"],
        "routed_scaling": cfg["routed_scaling_factor"],
        "norm_eps": cfg["layer_norm_epsilon"],
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
    }


def to_config(cfg: dict, **overrides):
    from learning_jax_sharding_tpu.models.convert import config_from_hf_nemotron_h

    if overrides.get("decode_attention") == "blocked":
        # Off the chip (a rehearsal) the harness forces the paged kernel
        # under the interpreter; the expert kernel goes the same way. The
        # Mamba layers take their XLA forms there (the program picks the
        # two kernels on a TPU alone, by shape: no option).
        overrides.setdefault("moe_experts", "pallas")
    keys = {k: v for k, v in cfg.items() if isinstance(k, str)}
    held = keys["n_routed_experts"]
    keys["n_routed_experts"] = _router_width(cfg)
    return config_from_hf_nemotron_h(
        types.SimpleNamespace(**keys), moe_held=(0, held),
        moe_expert_init_scale=cfg["check"]["expert_init_scale"], **overrides
    )
