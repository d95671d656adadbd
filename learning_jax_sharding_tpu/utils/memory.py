"""HBM budget estimation for a transformer training configuration.

Answers "will this config fit a chip?" before paying a compile + OOM cycle
(measured on the v5e: the 125M model at b=16, s=1024 OOMs from stored dense
attention probabilities alone — exactly the term this planner surfaces).
Estimates, not measurements: XLA fusion changes the constants, but the big
terms (parameters, optimizer moments, per-layer saved activations, S² score
tensors, (B,S,V) logits) dominate and are shape-arithmetic.

Conventions: fp32 params/optimizer (the framework default), activations in
``cfg.dtype``. ``saved`` activations are what backward needs — the planner
models the three attention regimes (dense / remat / flash) and the fused
vs. unfused loss head explicitly, because those are the order-of-magnitude
levers (PERF.md).

What ``remat=True`` keeps (PR 38). A block under ``jax.checkpoint`` saves its
input and, of the residuals the model NAMES (:data:`REMAT_GROUPS`; the names
are ``jax.ad_checkpoint.checkpoint_name`` tags in ``models/`` and
``ops/flash_attention.py``), those that :func:`remat_plan` fits into a byte
budget: the flash kernel's output and log-sum-exp in every attention block
first, then matmul outputs group by group while they fit. Everything else
is recomputed in the backward. The budget is what the train step can see
(:func:`remat_budget`: the device's memory less the train state, the block
inputs, the loss head and one block's working set); the step hands it to the
model through :func:`remat_scope`, and with no scope (a bare ``apply``, the
emulated CPU mesh) the budget is 0 and nothing is saved.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Iterator

import jax.numpy as jnp

#: Per-chip HBM, bytes. Public system specs, keyed by device_kind.
HBM_BYTES: dict[str, float] = {
    "TPU v4": 32e9,
    "TPU v5 lite": 16e9,   # v5e
    "TPU v5": 95e9,        # v5p
    "TPU v5p": 95e9,
    "TPU v6 lite": 32e9,   # v6e
}


def device_hbm_bytes(device: Any | None = None) -> float | None:
    """Spec HBM capacity for ``device`` (default: first local device), or
    None when unknown (emulated CPU). The static fallback for backends that
    report no ``bytes_limit`` — ``telemetry.devview.memory_report`` prefers
    the live limit when the runtime provides one."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return HBM_BYTES.get(getattr(device, "device_kind", None))


def device_memory_bytes(device: Any) -> float | None:
    """What ``device`` can hold: the runtime's ``bytes_limit`` where it
    reports one, else the spec capacity of its kind, and the smaller where
    both are known (the spec's is what a result line's peak is read against).
    None for a device that reports nothing and whose kind is unknown (the
    emulated CPU mesh)."""
    try:
        limit = (device.memory_stats() or {}).get("bytes_limit")
    except Exception:       # a described, unattached device has no runtime
        limit = None
    known = [b for b in (limit, device_hbm_bytes(device)) if b]
    return float(min(known)) if known else None


@dataclasses.dataclass(frozen=True)
class MemoryPlan:
    """Byte estimates for one train step (single chip unless divided)."""

    params: float
    grads: float
    optimizer_state: float
    saved_activations: float
    loss_head: float
    total: float
    detail: dict

    def fits(self, hbm_bytes: float, *, headroom: float = 0.8) -> bool:
        """Conservative fit check: estimate under ``headroom`` × capacity
        (XLA scratch, fragmentation, and fusion temporaries take the rest)."""
        return self.total <= hbm_bytes * headroom


def _kv_heads(cfg: Any) -> int:
    return cfg.num_kv_heads if cfg.num_kv_heads is not None else cfg.num_heads


def _loss_head_bytes(
    cfg: Any, tokens: float, seq: int, n_model_shards: int, unfused: bool = False
) -> float:
    act_bytes = jnp.dtype(cfg.dtype).itemsize
    if unfused:
        # bf16 logits + the fp32 softmax upcast both live at peak.
        return tokens * cfg.vocab_size / n_model_shards * (act_bytes + 4)
    chunk = min(seq, 128)  # fused_next_token_loss chunk size
    return tokens * chunk / seq * cfg.vocab_size / n_model_shards * (act_bytes + 4)


def _per_layer_residual_bytes(cfg: Any, tokens: float, n_model_shards: int) -> float:
    """Saved-per-layer residuals the backward reads (block input, LN outputs,
    q/k/v, attention output, FF up/GELU); coefficients from the block
    structure, not measured constants."""
    nh = cfg.num_heads * cfg.head_dim / n_model_shards
    nkv = _kv_heads(cfg) * cfg.head_dim / n_model_shards
    return tokens * jnp.dtype(cfg.dtype).itemsize * (
        4 * cfg.features            # block in, 2×LN out, attn out
        + nh + 2 * nkv              # q, k, v
        + 2 * cfg.hidden / n_model_shards  # FF up pre/post-GELU
    )


def memory_plan(
    cfg: Any,
    batch: int,
    seq: int,
    *,
    optimizer_slots: int = 2,       # adamw: m + v
    donate_state: bool = True,
    unfused_loss: bool = False,
    n_model_shards: int = 1,        # TP/FSDP degree dividing params & opt state
    n_data_shards: int = 1,         # DP degree dividing the batch dim
    device_bytes: float | None = None,   # with cfg.remat: what the chip holds
) -> MemoryPlan:
    """Estimate train-step HBM for a :class:`TransformerConfig`.

    Attention regime is read off the config: ``attn_fn`` set → flash-style
    (no S² saved); else ``remat_attention`` → q/k/v saved, scores recomputed;
    else dense → the S² softmax probabilities saved for backward (pre-softmax
    scores are fusion temporaries, not residuals).

    With ``cfg.remat`` (and no explicit ``remat_policy`` other than
    ``"nothing"``) a block keeps its input and what :func:`remat_plan` fits;
    one block's residuals are alive while its backward runs. The budget is
    :func:`remat_budget`'s for ``device_bytes`` (``None``: 0, nothing kept),
    the plan is in ``detail["remat_plan"]``.
    """
    act_bytes = jnp.dtype(cfg.dtype).itemsize
    param_bytes = jnp.dtype(cfg.param_dtype).itemsize
    b = batch / n_data_shards
    p = cfg.param_count / n_model_shards

    params = p * param_bytes
    grads = p * param_bytes
    opt = p * param_bytes * optimizer_slots
    if not donate_state:
        # Undonated input state stays alive next to the output state.
        params, opt = 2 * params, 2 * opt

    tokens = b * seq
    per_layer = _per_layer_residual_bytes(cfg, tokens, n_model_shards)
    if cfg.attn_fn is not None:
        scores = 0.0                # flash: O(S·H) only, counted in q/k/v
    elif getattr(cfg, "remat_attention", False):
        scores = 0.0                # recomputed in backward
    else:
        heads = cfg.num_heads / n_model_shards
        # Saved probabilities (softmax backward reads only its OUTPUT, so the
        # fp32 pre-softmax scores are fusion temporaries, not residuals).
        scores = b * heads * seq * seq * act_bytes
    head = _loss_head_bytes(cfg, tokens, seq, n_model_shards, unfused_loss)
    detail = {
        "per_layer_residuals": per_layer,
        "per_layer_scores": scores,
        "batch_per_shard": b,
    }
    if getattr(cfg, "remat", False) and cfg.remat_policy in (None, "nothing"):
        kept = None
        if cfg.remat_policy is None:
            kept = remat_plan(
                cfg, tokens,
                remat_budget(
                    cfg, tokens, seq, device_bytes=device_bytes,
                    state_bytes=params + opt, n_model_shards=n_model_shards,
                ),
                n_model_shards=n_model_shards, uniform=cfg.scan_layers,
            )
        detail["remat_plan"] = kept
        saved = (
            cfg.num_layers * tokens * cfg.features * act_bytes   # block inputs
            + per_layer + scores                                 # one block
            + (kept.saved_bytes if kept is not None else 0.0)
        )
    else:
        saved = cfg.num_layers * (per_layer + scores)

    total = params + grads + opt + saved + head
    return MemoryPlan(
        params=params, grads=grads, optimizer_state=opt,
        saved_activations=saved, loss_head=head, total=total,
        detail=detail,
    )


# --- what ``remat=True`` keeps ------------------------------------------------

#: The residuals a block names, in the order the plan takes them: the flash
#: kernel's output and log-sum-exp (they buy the slowest code on the chip for
#: the fewest bytes), then matmul outputs. Every matmul output here is a
#: product against ``features`` input columns, so each buys the same
#: arithmetic a byte and the order among them is the block's own.
REMAT_GROUPS: tuple[tuple[str, ...], ...] = (
    ("flash_out", "flash_lse"),         # ops/flash_attention.py::_flash_fwd
    ("attn_q", "attn_k", "attn_v"),     # MultiHeadAttention: the projections
    ("operator_out",),                  # TransformerBlock._finish: attention's
                                        # or the short convolution's output
    ("conv_in_proj",),                  # models/ssm.py::ShortConv
    ("ff_up", "ff_gate"),               # FeedForward: GELU's input; SwiGLU's
)

@dataclasses.dataclass(frozen=True)
class RematPlan:
    """Which named residuals each block keeps, and what that costs a device."""

    names: tuple[tuple[str, ...], ...]   # by block
    saved_bytes: float
    budget_bytes: float
    predicted_peak_bytes: float | None = None   # with the scope's state

    def summary(self) -> str:
        by_names: dict[tuple[str, ...], list[int]] = {}
        for i, names in enumerate(self.names):
            by_names.setdefault(names, []).append(i)
        blocks = "; ".join(
            f"blocks {layers[0]}-{layers[-1]} ({len(layers)}): "
            f"{', '.join(names) or 'nothing'}"
            for names, layers in by_names.items()
        )
        peak = (
            "" if self.predicted_peak_bytes is None
            else f", predicted peak {self.predicted_peak_bytes / 1e9:.2f} GB"
        )
        return (
            f"remat keeps {self.saved_bytes / 1e9:.3f} GB of a budget of "
            f"{self.budget_bytes / 1e9:.3f} GB{peak}: {blocks}"
        )


def block_residual_bytes(
    cfg: Any, layer: int, tokens: float, *, n_model_shards: int = 1
) -> dict[str, float]:
    """Bytes a device holds of each NAMED residual of block ``layer`` at
    ``tokens`` tokens a device. Only what the block really names: the flash
    names where a flash backend is configured, no feed-forward name in an
    expert layer (``models/moe.py`` names nothing), nothing for latent
    attention."""
    act = jnp.dtype(cfg.dtype).itemsize
    wide = tokens * cfg.features * act
    out: dict[str, float] = {}
    if cfg._operator(layer) == "conv":
        out["conv_in_proj"] = 3 * wide / n_model_shards
    elif not cfg.latent_kv_rank:
        heads = cfg.num_heads / n_model_shards
        q = tokens * heads * cfg.head_dim * act
        if cfg.attn_fn is not None:
            out["flash_out"] = q
            out["flash_lse"] = tokens * heads * 4
        kv = q * _kv_heads(cfg) / cfg.num_heads
        out.update(attn_q=q, attn_k=kv, attn_v=kv)
    out["operator_out"] = wide
    if not cfg.num_experts or layer < cfg.first_k_dense:
        hidden = tokens * cfg.hidden / n_model_shards * act
        out["ff_up"] = hidden
        if cfg.ff_gated:
            out["ff_gate"] = hidden
    return out


def _block_working_bytes(cfg: Any, layer: int, tokens: float, n_model_shards: int) -> float:
    """What one block's recomputation and backward hold at once: its
    residuals (:func:`memory_plan`'s per-layer arithmetic; an expert layer's
    routed rows in the feed-forward's place), as much again of cotangents,
    and its parameters' gradients until the update takes them."""
    act = jnp.dtype(cfg.dtype).itemsize
    per_layer = _per_layer_residual_bytes(cfg, tokens, n_model_shards)
    if cfg.num_experts and layer >= cfg.first_k_dense:
        # Dropless routing pads a row for every assignment: the gathered
        # inputs, the experts' outputs and the hidden's two halves.
        rows = tokens * cfg.moe_top_k
        hidden = cfg.moe_hidden or cfg.hidden
        per_layer += rows * act * (2 * cfg.features + 2 * hidden) - (
            2 * tokens * cfg.hidden / n_model_shards * act
        )
    grads = (
        (cfg._operator_params(layer) + cfg._ff_params(layer))
        / n_model_shards * jnp.dtype(cfg.param_dtype).itemsize
    )
    return 2 * per_layer + grads


def remat_fixed_bytes(
    cfg: Any, tokens: float, seq: int, *, state_bytes: float, n_model_shards: int = 1
) -> float:
    """What a device holds of a rematerialized train step whatever the plan:
    the train state, the block inputs full rematerialization keeps anyway,
    the loss head, the gradients of the embedding and the head (the last and
    the first the backward finishes), and the working set of its largest
    block."""
    act = jnp.dtype(cfg.dtype).itemsize
    tables = 1 if cfg.tie_embeddings else 2
    return (
        state_bytes
        + cfg.num_layers * tokens * cfg.features * act
        + _loss_head_bytes(cfg, tokens, seq, n_model_shards)
        + tables * cfg.vocab_size * cfg.features / n_model_shards
        * jnp.dtype(cfg.param_dtype).itemsize
        + max(
            _block_working_bytes(cfg, i, tokens, n_model_shards)
            for i in range(cfg.num_layers)
        )
    )


def remat_budget(
    cfg: Any,
    tokens: float,
    seq: int,
    *,
    device_bytes: float | None,
    state_bytes: float,
    n_model_shards: int = 1,
    headroom: float = 0.9,
) -> float:
    """Bytes a device has left for named residuals: ``headroom`` of its
    memory less :func:`remat_fixed_bytes`. 0 where the device's memory is
    unknown."""
    if not device_bytes:
        return 0.0
    fixed = remat_fixed_bytes(
        cfg, tokens, seq, state_bytes=state_bytes, n_model_shards=n_model_shards
    )
    return max(0.0, headroom * device_bytes - fixed)


def remat_plan(
    cfg: Any,
    tokens: float,
    budget_bytes: float,
    *,
    n_model_shards: int = 1,
    uniform: bool = False,
) -> RematPlan:
    """Fit named residuals into ``budget_bytes``: group by group in
    :data:`REMAT_GROUPS`' order, block by block within a group, and stop at
    the first that does not fit (so a larger budget keeps a superset, and a
    budget of 0 keeps nothing). ``uniform``: every block keeps the same names
    (a scanned stack traces ONE block), so a group is taken for all blocks
    or not at all."""
    layers = range(cfg.num_layers)
    sizes = [
        block_residual_bytes(cfg, i, tokens, n_model_shards=n_model_shards)
        for i in layers
    ]
    # (blocks, names, bytes) in the order they are taken.
    items: list[tuple[tuple[int, ...], tuple[str, ...], float]] = []
    for group in REMAT_GROUPS:
        per_block = [
            ((i,), names, sum(sizes[i][n] for n in names))
            for i in layers
            if (names := tuple(n for n in group if n in sizes[i]))
        ]
        if uniform and per_block:
            per_block = [(
                tuple(i for (i,), _, _ in per_block), per_block[0][1],
                sum(cost for _, _, cost in per_block),
            )]
        items.extend(per_block)
    kept: list[list[str]] = [[] for _ in layers]
    saved = 0.0
    for blocks, names, cost in items:
        if saved + cost > budget_bytes:
            break
        saved += cost
        for i in blocks:
            kept[i].extend(names)
    return RematPlan(
        names=tuple(tuple(k) for k in kept),
        saved_bytes=saved, budget_bytes=float(budget_bytes),
    )


@dataclasses.dataclass
class RematScope:
    """What a train step knows when it traces its model: the device's
    memory, the bytes of train state a device holds, how the mesh divides
    the batch and the widths. ``budget_bytes`` set outright takes the place
    of that arithmetic (a test's handle). The model leaves the plan it
    resolved in ``plan`` (None until traced, or where the model never asked)."""

    device_bytes: float | None = None
    state_bytes: float = 0.0
    n_data_shards: int = 1
    n_model_shards: int = 1
    budget_bytes: float | None = None
    plan: RematPlan | None = None

    def resolve(self, cfg: Any, batch: int, seq: int, *, uniform: bool = False) -> RematPlan:
        tokens = batch * seq / self.n_data_shards
        budget = self.budget_bytes
        if budget is None:
            budget = remat_budget(
                cfg, tokens, seq, device_bytes=self.device_bytes,
                state_bytes=self.state_bytes, n_model_shards=self.n_model_shards,
            )
        plan = remat_plan(
            cfg, tokens, budget, n_model_shards=self.n_model_shards,
            uniform=uniform,
        )
        plan = dataclasses.replace(
            plan, predicted_peak_bytes=plan.saved_bytes + remat_fixed_bytes(
                cfg, tokens, seq, state_bytes=self.state_bytes,
                n_model_shards=self.n_model_shards,
            ),
        )
        self.plan = plan
        return plan


_REMAT_SCOPE: contextvars.ContextVar[RematScope | None] = contextvars.ContextVar(
    "remat_scope", default=None
)


def current_remat_scope() -> RematScope | None:
    return _REMAT_SCOPE.get()


@contextlib.contextmanager
def remat_scope(scope: RematScope) -> Iterator[RematScope]:
    """Make ``scope`` what a model traced inside resolves its plan from."""
    token = _REMAT_SCOPE.set(scope)
    try:
        yield scope
    finally:
        _REMAT_SCOPE.reset(token)
