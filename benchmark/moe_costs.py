"""Bytes of the latent-attention and routed-expert kernels, and the reader
that counts expert reads instead of expecting them. A family module adds
them to ``costs.FORMULAS`` / ``readers.READERS`` when it is imported
(``register``; ``spec.load_cell`` imports the family before it looks a
metric's reader up), so ``costs.py`` and ``readers.py`` stay as they are.
"""

from __future__ import annotations

import math

from benchmark import costs, readers


def mla_decode_attn_bytes(m: dict, page: int, contexts: list[int]) -> float:
    """Latent-cache bytes the decode kernel has to read for one decode token
    per entry of ``contexts``: whole pages of one ``[c_kv | k_rope]`` row a
    token (``kv_rank + rope_dim`` values of 2 B), all layers. Logical bytes:
    a layout that pads the row to whole lane tiles moves more, and that is
    the kernel's cost, not the formula's."""
    pages = sum(math.ceil(c / page) for c in contexts)
    return float(pages * page * (m["kv_rank"] + m["rope_dim"]) * 2 * m["num_layers"])


def moe_expert_bytes(m: dict, reads: float) -> float:
    """Bytes of ``reads`` expert reads: an expert's gate, up and down
    matrices, ``3 x features x expert_hidden`` values of 2 B."""
    return float(reads * 3 * m["features"] * m["expert_hidden"] * 2)


def _mla_decode_attn(work: dict) -> tuple[float, str]:
    return (
        mla_decode_attn_bytes(
            work["model"], work["page_size"], work["decode_contexts_in_slice"]
        ),
        "hbm_bytes_per_s",
    )


def _moe_experts(work: dict) -> tuple[float, str]:
    return (
        moe_expert_bytes(work["model"], work["moe_expert_reads_in_slice"]),
        "hbm_bytes_per_s",
    )


def trace_roofline_counted(p: dict, obs: dict):
    """``trace_roofline`` for work the engine COUNTED: the window's growth
    of counter ``count`` over that of ``per`` (a rate per decode token),
    times the decode tokens of the traced slice, is put into the work table
    under ``into`` for ``formula`` to price. The counters stop where the
    slice starts (as ``refill_host_share_pct``'s do): the rate is the 27 s
    before it, the tokens and the device time the slice's own."""
    count = readers._registry_delta(obs, p["count"])
    per = readers._registry_delta(obs, p["per"])
    if not count or not per:
        return None
    tokens = len(obs["work"].get("decode_contexts_in_slice") or ())
    work = {**obs["work"], p["into"]: count / per * tokens}
    return readers.trace_roofline(p, {**obs, "work": work})


_FORMULAS = {
    "mla_decode_attn_bytes": _mla_decode_attn,
    "moe_expert_bytes": _moe_experts,
}
_READERS = {"trace_roofline_counted": trace_roofline_counted}


def register() -> None:
    """Add this file's formulas and reader to the harness's tables. A key
    that is there and is not this file's own is never replaced."""
    for table, new in ((costs.FORMULAS, _FORMULAS), (readers.READERS, _READERS)):
        for key, fn in new.items():
            if table.get(key, fn) is not fn:
                raise KeyError(f"benchmark: {key!r} is already registered")
            table[key] = fn
