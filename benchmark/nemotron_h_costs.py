"""Bytes and operations of the ``nemotron_h`` family's own device work: the
Mamba-2 state update (decode), its chunked scan (refill) and the latent
experts' two matrices. A family module adds them to ``costs.FORMULAS`` when
it is imported (``register``, as ``moe_costs.py`` does); the readers are the
harness's ``trace_roofline`` and ``moe_costs.trace_roofline_counted``, and for
the chunked scan ``trace_roofline_calls`` below.

``m`` is ``families.nemotron_h.model_dims``'s table. All counts are LOGICAL:
what the algorithm has to move or compute, whatever the program's layout or
intermediate passes add.
"""

from __future__ import annotations

from benchmark import costs, peaks, readers
from benchmark import trace as tr


def _ssm_layers(m: dict) -> int:
    return m["pattern"].count("M")


def ssm_state_bytes(m: dict) -> int:
    """One row's recurrent state in one Mamba layer: heads x head size x
    state size float32 values."""
    return m["ssm_heads"] * m["ssm_head_dim"] * m["ssm_state"] * 4


def ssm_state_update_bytes(m: dict, tokens: int) -> float:
    """Bytes of ``tokens`` decode tokens' one-step state updates: every
    Mamba layer reads a row's state and writes it back."""
    return float(tokens * _ssm_layers(m) * 2 * ssm_state_bytes(m))


def latent_moe_expert_bytes(m: dict, reads: float) -> float:
    """Bytes of ``reads`` expert reads: an ungated latent expert's up and
    down matrices, ``2 x latent x expert_hidden`` values of 2 B."""
    return float(reads * 2 * m["latent"] * m["expert_hidden"] * 2)


def ssm_chunk_scan_cost(m: dict, rows: float) -> tuple[float, float]:
    """``(flops, bytes)`` of ``rows`` refill chunk rows (one tile of
    ``ssm_chunk`` tokens each) through every Mamba layer's chunked scan.

    Operations of a tile (Q tokens, H heads of P, G groups, state N):
    ``C B^T`` 2 G Q^2 N, the in-tile outputs 2 H Q^2 P, the tile's
    contribution to the state 2 H Q P N, the starting state's part of the
    outputs 2 H Q P N. Bytes: the row's state in and out (float32), u and
    the outputs (Q x H P values of 2 B each way), B and C (2 x Q x G N x
    2 B), dt (Q x H float32)."""
    q, h, p = m["ssm_chunk"], m["ssm_heads"], m["ssm_head_dim"]
    g, n = m["ssm_groups"], m["ssm_state"]
    flops = 2 * g * q * q * n + 2 * h * q * q * p + 4 * h * q * p * n
    nbytes = (
        2 * ssm_state_bytes(m) + 2 * q * h * p * 2 + 2 * q * g * n * 2 + q * h * 4
    )
    layers = _ssm_layers(m)
    return float(rows * layers * flops), float(rows * layers * nbytes)


def slower_bound(flops: float, nbytes: float) -> tuple[float, str]:
    """The amount and peak of whichever bound takes longer: on every chip
    the peaks table knows (they have to agree: a formula names ONE peak)."""
    verdicts = {
        t["bf16_flops"] and flops / t["bf16_flops"] > nbytes / t["hbm_bytes_per_s"]
        for t in peaks.PEAKS.values()
    }
    if len(verdicts) != 1:
        raise ValueError("the peaks table's chips disagree on which bound is slower")
    return (flops, "bf16_flops") if verdicts.pop() else (nbytes, "hbm_bytes_per_s")


def _ssm_state_update(work: dict) -> tuple[float, str]:
    return (
        ssm_state_update_bytes(work["model"], len(work["decode_contexts_in_slice"])),
        "hbm_bytes_per_s",
    )


def _latent_moe_experts(work: dict) -> tuple[float, str]:
    return (
        latent_moe_expert_bytes(work["model"], work["moe_expert_reads_in_slice"]),
        "hbm_bytes_per_s",
    )


def _ssm_chunk_scan(work: dict) -> tuple[float, str]:
    return slower_bound(
        *ssm_chunk_scan_cost(work["model"], work["ssm_chunk_rows_in_slice"])
    )


def trace_roofline_calls(p: dict, obs: dict):
    """``trace_roofline`` for a kernel that runs EVERY row of a dispatch, used
    or not, once a layer: the slice's own calls of ``op`` inside ``module``
    (counted from the trace: the ops whose time the share divides by) times
    the rows a dispatch of ``family`` holds — its ``token_slots`` over the
    model's tile, from the window's ``engine.dispatch`` events — over the
    layers that call it, put into the work table under ``into`` as rows
    through every layer. Nothing of the slice is estimated from the
    window's rates: a slice that happens to hold few refill dispatches
    reads the same share as one that holds many."""
    if obs.get("trace") is None:
        return None
    slots = [
        e.get("token_slots") for e in obs.get("recorder_events") or []
        if e.get("kind") == "engine.dispatch" and e.get("family") == p["family"]
    ]
    calls = sum(
        tr.op_time_ns(pl, p["op"], p.get("module"))[1] for pl in obs["trace"].planes
    )
    if not calls or not any(slots):
        return None
    model = obs["work"]["model"]
    rows = min(s for s in slots if s) / model["ssm_chunk"]      # a chained event books several
    work = {**obs["work"], p["into"]: calls * rows / _ssm_layers(model)}
    return readers.trace_roofline(p, {**obs, "work": work})


_FORMULAS = {
    "ssm_state_update_bytes": _ssm_state_update,
    "latent_moe_expert_bytes": _latent_moe_experts,
    "ssm_chunk_scan_cost": _ssm_chunk_scan,
}
_READERS = {"trace_roofline_calls": trace_roofline_calls}


def register() -> None:
    """Add this file's formulas and reader to the harness's tables. A key
    that is there and is not this file's own is never replaced."""
    for table, new in ((costs.FORMULAS, _FORMULAS), (readers.READERS, _READERS)):
        for key, fn in new.items():
            if table.get(key, fn) is not fn:
                raise KeyError(f"benchmark: {key!r} is already registered")
            table[key] = fn
