"""Operations and bytes a call needs, computed from its shapes. The table
``FORMULAS`` is what ``trace_roofline`` metric files name by key.

``train_step_flops`` is a copy of ``TransformerConfig.train_step_flops``
(dense decoder, causal attention counted at half the S^2 a block-skipping
kernel computes); recomputed operations (remat, the flash backward's second
QK^T) do not count.
"""

from __future__ import annotations

import math
from typing import Callable


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matmul per token: q, k, v, out, ff up
    and down in every layer, and the output head."""
    kv_heads = m.get("num_kv_heads") or m["num_heads"]
    attn = 2 * m["features"] * m["num_heads"] * m["head_dim"] + (
        2 * m["features"] * kv_heads * m["head_dim"]
    )
    ff = 2 * m["features"] * m["hidden"]
    return m["num_layers"] * (attn + ff) + m["features"] * m["vocab_size"]


def attn_flops_per_token_fwd(m: dict, seq: int) -> float:
    """QK^T and PV over ``seq`` keys for one query token, all layers, causal
    half counted."""
    return 4.0 * seq * m["num_heads"] * m["head_dim"] * m["num_layers"] * 0.5


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    per_token = 6.0 * matmul_params(m) + 3.0 * attn_flops_per_token_fwd(m, seq)
    return per_token * batch * seq


def flash_attn_flops(m: dict, batch: int, seq: int) -> float:
    """Forward (2 matmuls) and backward (4 matmuls) of causal attention for
    one step, all layers: 3 x the forward."""
    return 3.0 * attn_flops_per_token_fwd(m, seq) * batch * seq


def kv_bytes_per_token_layer(m: dict, cache_bytes: int = 2) -> int:
    kv_heads = m.get("num_kv_heads") or m["num_heads"]
    return 2 * kv_heads * m["head_dim"] * cache_bytes


def decode_attn_bytes(m: dict, page: int, contexts: list[int]) -> float:
    """K and V bytes the cached-attention kernel has to read for one decode
    token per entry of ``contexts`` (the row's tokens in the cache when the
    token is made), all layers: whole pages, as the paged kernel reads."""
    pages = sum(math.ceil(c / page) for c in contexts)
    return float(pages * page * kv_bytes_per_token_layer(m) * m["num_layers"])


def _flash(work: dict) -> tuple[float, str]:
    return (
        flash_attn_flops(work["model"], work["batch"], work["seq"])
        * work["steps_in_slice"],
        "bf16_flops",
    )


def _decode_attn(work: dict) -> tuple[float, str]:
    return (
        decode_attn_bytes(
            work["model"], work["page_size"], work["decode_contexts_in_slice"]
        ),
        "hbm_bytes_per_s",
    )


def _train_step(work: dict) -> tuple[float, str]:
    return (
        train_step_flops(work["model"], work["batch"], work["seq"]),
        "bf16_flops",
    )


#: formula key -> ``work -> (amount, name of the peak that bounds it)``.
FORMULAS: dict[str, Callable[[dict], tuple[float, str]]] = {
    "flash_attn_flops": _flash,
    "decode_attn_bytes": _decode_attn,
    "train_step_flops": _train_step,
}
