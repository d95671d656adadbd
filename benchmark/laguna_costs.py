"""Bytes the decode-attention kernel reads in a ``laguna`` model: the window
layers read the pages their window band covers (through a ring of pages a
slot), the global layers every page of the row's history. A family module
adds them to ``costs.FORMULAS`` when it is imported (``register``), so
``costs.py`` stays as it is.
"""

from __future__ import annotations

import math

from benchmark import costs


def page_bytes(m: dict, page: int) -> int:
    """K and V of one page of one layer, 2 B a value: ``page x 2 x kv heads
    x head_dim x 2``; 524,288 B at the cell's page of 128."""
    return page * 2 * m["num_kv_heads"] * m["head_dim"] * 2


def window_pages(context: int, window: int, page: int) -> int:
    """Pages a decode token's window band covers: the token at position
    ``p = context - 1`` reads positions ``(p - window, p]``, the blocks from
    ``(p - window + 1) // page`` (not below 0) through ``p // page``."""
    p = context - 1
    return p // page - max(0, (p - window + 1) // page) + 1


def swa_decode_attn_bytes(m: dict, page: int, contexts: list[int]) -> float:
    """Bytes of the window layers' decode reads for one decode token per entry
    of ``contexts`` (the row's tokens in the cache when the token is made):
    the pages of its window band, in every window layer."""
    layers = m["layer_types"].count("sliding_attention")
    pages = sum(window_pages(c, m["window"], page) for c in contexts)
    return float(pages * layers * page_bytes(m, page))


def global_decode_attn_bytes(m: dict, page: int, contexts: list[int]) -> float:
    """Bytes of the global layers' decode reads: ``ceil(context / page)``
    whole pages a token, in every full-attention layer."""
    layers = m["layer_types"].count("full_attention")
    pages = sum(math.ceil(c / page) for c in contexts)
    return float(pages * layers * page_bytes(m, page))


def _swa(work: dict) -> tuple[float, str]:
    return (
        swa_decode_attn_bytes(
            work["model"], work["page_size"], work["decode_contexts_in_slice"]
        ),
        "hbm_bytes_per_s",
    )


def _global(work: dict) -> tuple[float, str]:
    return (
        global_decode_attn_bytes(
            work["model"], work["page_size"], work["decode_contexts_in_slice"]
        ),
        "hbm_bytes_per_s",
    )


_FORMULAS = {
    "laguna_swa_decode_attn_bytes": _swa,
    "laguna_global_decode_attn_bytes": _global,
}


def register() -> None:
    """Add this file's formulas to the harness's table. A key that is there
    and is not this file's own is never replaced."""
    for key, fn in _FORMULAS.items():
        if costs.FORMULAS.get(key, fn) is not fn:
            raise KeyError(f"benchmark: {key!r} is already registered")
        costs.FORMULAS[key] = fn
