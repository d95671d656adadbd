"""Operations of the ``lfm2_moe`` family's train step on ONE chip of its
expert-parallel group, and the reader that prices the routed experts by how
often the trace shows their forward ran. A family module adds them to
``costs.FORMULAS`` / ``readers.READERS`` when it is imported (``register``,
as ``moe_costs.py`` does).

``m`` is ``families.lfm2_moe.model_dims``'s table. All counts are LOGICAL:
what the algorithm has to compute here, whatever the program recomputes
(``remat``, the flash backward's second ``Q K^T``). The held experts are
counted at the UNIFORM EXPECTATION ``tokens x top_k x held / experts``
assignments a layer: ``benchmark/train.py`` hands a reader no registry, so
the counted assignments (``train_moe_held_assignments_total``) cannot reach
a formula yet (PERF.md section 7).
"""

from __future__ import annotations

from benchmark import costs, readers
from benchmark import trace as tr


def attn_layers(m: dict) -> int:
    return sum(kind == "full_attention" for kind in m["layer_types"])


def held_assignments(m: dict, tokens: int) -> float:
    """Expected assignments to a HELD expert, one expert layer, uniform routing."""
    return tokens * m["top_k"] * m["held_count"] / m["num_experts"]


def matmul_params_per_token(m: dict) -> float:
    """Parameters one token multiplies by on this chip, forward: a conv
    operator's ``in_proj`` and ``out_proj`` (4 M^2), an attention layer's
    four projections, a dense layer's three matrices, an expert layer's
    router and its expected share of held picks, the tied head."""
    d, hd = m["features"], m["head_dim"]
    attn = 2 * d * m["num_heads"] * hd + 2 * d * m["num_kv_heads"] * hd
    dense = 3 * d * m["hidden"]
    routed = d * m["num_experts"] + 3 * d * m["expert_hidden"] * held_assignments(m, 1)
    total = 0.0
    for i, kind in enumerate(m["layer_types"]):
        total += attn if kind == "full_attention" else 4 * d * d
        total += dense if i < m["num_dense_layers"] else routed
    return total + d * m["vocab_size"]


def attn_flops_per_token_fwd(m: dict, seq: int) -> float:
    """``Q K^T`` and ``P V`` over ``seq`` keys for one query token, the
    attention layers only, causal half counted."""
    return 4.0 * seq * m["num_heads"] * m["head_dim"] * attn_layers(m) * 0.5


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """Forward + backward (3 x forward) of what this chip computes: 6 FLOPs a
    matrix parameter a token, the attention layers' scores and values, and
    the convolutions' taps (2 FLOPs a tap a channel)."""
    taps = 2.0 * m["conv_kernel"] * m["features"] * (m["num_layers"] - attn_layers(m))
    per_token = (
        6.0 * matmul_params_per_token(m)
        + 3.0 * (attn_flops_per_token_fwd(m, seq) + taps)
    )
    return per_token * batch * seq


def flash_attn_flops(m: dict, batch: int, seq: int) -> float:
    """Forward (2 matmuls) and backward (4) of causal attention for one
    step, the attention layers only."""
    return 3.0 * attn_flops_per_token_fwd(m, seq) * batch * seq


def expert_pass_flops(m: dict, tokens: int) -> float:
    """One FORWARD pass of the held experts of every expert layer: three
    matrices of ``features x expert_hidden`` an assignment."""
    layers = m["num_layers"] - m["num_dense_layers"]
    return (
        held_assignments(m, tokens) * layers
        * 6.0 * m["features"] * m["expert_hidden"]
    )


def _train_step(work: dict) -> tuple[float, str]:
    return train_step_flops(work["model"], work["batch"], work["seq"]), "bf16_flops"


def _flash(work: dict) -> tuple[float, str]:
    return (
        flash_attn_flops(work["model"], work["batch"], work["seq"])
        * work["steps_in_slice"],
        "bf16_flops",
    )


def _experts(work: dict) -> tuple[float, str]:
    return (
        expert_pass_flops(work["model"], work["batch"] * work["seq"])
        * work["moe_expert_passes_in_slice"],
        "bf16_flops",
    )


def trace_roofline_expert_passes(p: dict, obs: dict):
    """``trace_roofline`` for the routed experts of a train step, whose
    forward call runs as often as the program recomputes it: the slice's
    calls of ``op`` that are NOT one of ``backward`` (name prefixes, each
    also starting with ``op``) are forward passes of one layer; a backward
    is priced as two forwards (its row and its weight products). The
    passes, in units of one forward over every expert layer, go into the
    work table under ``into``; the time divided by is every op of prefix
    ``op``, forward and backward."""
    if obs.get("trace") is None:
        return None
    planes = obs["trace"].planes
    calls = sum(tr.op_time_ns(pl, p["op"], p.get("module"))[1] for pl in planes)
    backward = [
        sum(tr.op_time_ns(pl, name, p.get("module"))[1] for pl in planes)
        for name in p["backward"]
    ]
    if not calls or not all(backward):
        return None
    model = obs["work"]["model"]
    layers = model["num_layers"] - model["num_dense_layers"]
    passes = (calls - sum(backward) + 2.0 * backward[0]) / layers
    work = {**obs["work"], p["into"]: passes}
    return readers.trace_roofline(p, {**obs, "work": work})


_FORMULAS = {
    "lfm2_moe_train_step_flops": _train_step,
    "lfm2_moe_flash_attn_flops": _flash,
    "lfm2_moe_expert_flops": _experts,
}
_READERS = {"trace_roofline_expert_passes": trace_roofline_expert_passes}


def register() -> None:
    """Add this file's formulas and reader to the harness's tables. A key
    that is there and is not this file's own is never replaced."""
    for table, new in ((costs.FORMULAS, _FORMULAS), (readers.READERS, _READERS)):
        for key, fn in new.items():
            if table.get(key, fn) is not fn:
                raise KeyError(f"benchmark: {key!r} is already registered")
            table[key] = fn
