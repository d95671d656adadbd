"""Donation audit: requested vs applied vs eligible buffer donations.

A missed donation is the quietest way to double HBM: the step still
runs, just with the input state alive NEXT TO the output state —
``utils.memory.memory_plan(donate_state=False)`` vs ``True`` is exactly
2× on params + optimizer moments, the largest line items of a training
step. This pass reads the ground truth off the executable:

* **requested** — the lowering's per-arg ``donated`` flags
  (``Lowered.args_info``: what the ``jax.jit(donate_argnums=...)`` call
  asked for);
* **applied**   — the compiled module's ``input_output_alias`` header
  (what XLA actually aliased; a request with no matching output buffer,
  or on a backend without donation support, silently drops here);
* **eligible**  — non-donated inputs whose (shape, dtype, per-device
  bytes) matches an output buffer not already claimed by an alias: a
  donation the caller COULD have requested and didn't. Sizes are the
  SHARDED per-device buffers when the compiled executable is at hand,
  so a replicated input never claims a model-sharded output of the same
  logical shape and the bytes-at-stake agree with memflow's accounting.

Verdict rules: ``donation-not-applied`` (requested, dropped) and
``donation-missed`` (eligible, never requested). The train-step shaped
helper cross-checks against :func:`utils.memory.memory_plan` so the
finding carries the bytes at stake, not just the arg index.
"""

from __future__ import annotations

import re
from typing import Any

import jax

from learning_jax_sharding_tpu.analysis.findings import Finding

#: One alias entry inside `input_output_alias={ {0}: (2, {}, may-alias),
#: ... }` — `{output_index}: (param_number, ...` — capturing the PARAMETER
#: number. The shape (braced index list, colon, parenthesized integer) is
#: specific enough to run over the whole header line; nothing else in an
#: HloModule header matches it.
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}:\s*\((\d+),")


def aliased_params(compiled_text: str) -> set[int]:
    """Parameter numbers the compiled module aliases to outputs, parsed
    off the ``HloModule ... input_output_alias={...}`` header."""
    for line in compiled_text.splitlines():
        if "input_output_alias=" in line:
            tail = line.split("input_output_alias=", 1)[1]
            return {int(p) for p in _ALIAS_ENTRY_RE.findall(tail)}
    return set()


def _kept_args(lowered: Any, n_in: int) -> list[int]:
    """Flat argument index of each HLO parameter, in parameter order.
    ``jax.jit`` drops the arguments its jaxpr never reads before it lowers
    (an engine's greedy programs never read ``rng``, a speculative refill
    never reads the draft's head), so parameter ``p`` of the compiled
    module is the ``p``-th KEPT argument, not argument ``p``."""
    try:
        kept = sorted(lowered._lowering.compile_args["kept_var_idx"])
    except (AttributeError, KeyError, TypeError):
        return list(range(n_in))
    return kept if kept and kept[-1] < n_in else list(range(n_in))


def _device_bytes(info: Any, sharding: Any = None) -> int:
    """Per-device bytes of one buffer: the shard's shape when the
    compiled sharding is known, the logical shape otherwise (identical on
    an unsharded program, which is why the two keying modes agree there)."""
    import numpy as np

    shape = tuple(info.shape)
    if sharding is not None:
        try:
            shape = tuple(sharding.shard_shape(tuple(info.shape)))
        except (TypeError, ValueError, AttributeError):
            pass  # keep the logical shape: a sharding we cannot query
    try:
        itemsize = np.dtype(info.dtype).itemsize
    except TypeError:
        itemsize = int(getattr(info.dtype, "itemsize", 4) or 4)
    import math

    return int(math.prod(shape) or 1) * itemsize


def _leaf_key(info: Any, sharding: Any = None) -> tuple:
    # Keyed on the PER-DEVICE buffer, not just (shape, dtype): a donation
    # is only real if the shard XLA would reuse is the same size, and the
    # bytes-at-stake a finding reports must agree with memflow's sharded
    # accounting.
    return (tuple(info.shape), str(info.dtype), _device_bytes(info, sharding))


def donation_report(jitted: Any, *args, **kwargs) -> dict:
    """Audit one jitted function's donation behavior on ``args``.

    Returns ``{"inputs": [...], "aliased_params", "findings",
    "backend_applied_any"}`` where each input record carries its flat
    parameter index, shape/dtype, and verdict: ``"donated"`` (requested
    and applied), ``"not_applied"`` (requested, dropped — XLA found no
    matching output or the backend lacks donation), ``"eligible"``
    (matches a free output buffer but was never requested), or ``"ok"``
    (nothing to donate it against). Costs one AOT compile — a
    diagnostic, not a hot-path call (same trade as
    ``telemetry.compile_watch.executable_report``); callers that already
    hold the lowering/compiled text (the shardcheck entry points, whose
    contract pass compiled the same program) use
    :func:`report_from_lowered` to skip it.
    """
    if not isinstance(jitted, jax.stages.Wrapped):
        jitted = jax.jit(jitted)
    lowered = jitted.lower(*args, **kwargs)
    compiled = lowered.compile()
    return report_from_lowered(lowered, compiled.as_text(),
                               compiled=compiled)


def _flat_shardings(compiled: Any, n_in: int, n_out: int) -> tuple:
    """Per-leaf input/output shardings off the compiled executable, or
    ``(None, None)`` sides when the flat counts do not line up (then the
    keying falls back to logical sizes for that side)."""
    in_sh = out_sh = None
    if compiled is not None:
        try:
            args_sh, kwargs_sh = compiled.input_shardings
            flat = list(args_sh) + list(jax.tree.leaves(kwargs_sh))
            if len(flat) == n_in:
                in_sh = flat
        except (AttributeError, TypeError, ValueError):
            pass  # backend without sharding introspection
        try:
            flat = list(jax.tree.leaves(compiled.output_shardings))
            if len(flat) == n_out:
                out_sh = flat
        except (AttributeError, TypeError, ValueError):
            pass  # backend without sharding introspection
    return in_sh, out_sh


def report_from_lowered(lowered: Any, compiled_text: str, *,
                        compiled: Any = None) -> dict:
    """:func:`donation_report` from an existing ``Lowered`` + compiled
    HLO text (no extra compile). Pass ``compiled`` when available so
    eligibility is matched on sharded per-device buffer sizes — a
    replicated input does NOT claim a model-sharded output of the same
    logical shape."""
    in_leaves = jax.tree.leaves(lowered.args_info)
    out_leaves = jax.tree.leaves(
        lowered.out_info,
        is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"),
    )
    kept = _kept_args(lowered, len(in_leaves))
    aliases = {
        kept[p] for p in aliased_params(compiled_text) if p < len(kept)
    }
    in_sh, out_sh = _flat_shardings(compiled, len(in_leaves),
                                    len(out_leaves))

    # Free output buffers by (shape, dtype, per-device bytes): each
    # applied alias consumes one matching output; what remains is what an
    # un-donated input could still have claimed.
    free_outputs: dict[tuple, int] = {}
    for j, o in enumerate(out_leaves):
        k = _leaf_key(o, out_sh[j] if out_sh else None)
        free_outputs[k] = free_outputs.get(k, 0) + 1
    for i, info in enumerate(in_leaves):
        if i in aliases:
            k = _leaf_key(info, in_sh[i] if in_sh else None)
            if free_outputs.get(k, 0) > 0:
                free_outputs[k] -= 1

    inputs: list[dict] = []
    findings: list[Finding] = []
    for i, info in enumerate(in_leaves):
        k = _leaf_key(info, in_sh[i] if in_sh else None)
        donated = bool(getattr(info, "donated", False))
        if donated and i in aliases:
            verdict = "donated"
        elif donated:
            verdict = "not_applied"
            findings.append(Finding(
                "donation", "donation-not-applied", f"param{i}",
                f"donation of param {i} {k[1]}{list(k[0])} was requested "
                "but the executable carries no alias for it — no "
                "matching output buffer (shape/dtype/sharding changed?) "
                "or the backend dropped it; the input stays alive next "
                "to the output",
                data={"param": i, "shape": list(k[0]), "dtype": k[1],
                      "device_bytes": k[2]},
            ))
        elif free_outputs.get(k, 0) > 0:
            free_outputs[k] -= 1
            verdict = "eligible"
            findings.append(Finding(
                "donation", "donation-missed", f"param{i}",
                f"param {i} {k[1]}{list(k[0])} "
                f"({k[2] / 2**20:.2f} MiB/device) matches an un-aliased "
                "output buffer of the same per-device size but was never "
                "donated — donate it (e.g. donate_argnums) to update in "
                "place instead of holding both generations",
                data={"param": i, "shape": list(k[0]), "dtype": k[1],
                      "device_bytes": k[2]},
            ))
        else:
            verdict = "ok"
        inputs.append({
            "param": i, "shape": list(k[0]), "dtype": k[1],
            "device_bytes": k[2],
            "donated": donated, "aliased": i in aliases,
            "verdict": verdict,
        })
    return {
        "inputs": inputs,
        "aliased_params": sorted(aliases),
        "backend_applied_any": bool(aliases),
        "findings": findings,
    }


def missed_donation_bytes(cfg: Any, batch: int, seq: int, **plan_kwargs) -> float:
    """HBM at stake in a missed train-state donation, from the closed-form
    planner: ``memory_plan(donate_state=False) − memory_plan(True)`` —
    the extra generation of params + optimizer moments that stays alive
    when the step cannot update in place."""
    from learning_jax_sharding_tpu.utils.memory import memory_plan

    plan_kwargs.pop("donate_state", None)
    kept = memory_plan(cfg, batch, seq, donate_state=True, **plan_kwargs)
    lost = memory_plan(cfg, batch, seq, donate_state=False, **plan_kwargs)
    return lost.total - kept.total


def check_train_step_donation(
    step_fn: Any, state: Any, batch: Any, *, cfg: Any = None,
    batch_size: int | None = None, seq_len: int | None = None,
    precompiled: tuple[Any, str] | None = None,
) -> dict:
    """Donation audit for a train step built by
    ``training.pipeline.make_train_step`` (pass ``step_fn.jitted`` or the
    wrapper — the ``.jitted`` attribute is preferred when present).

    With ``cfg`` (+ ``batch_size``/``seq_len``, else read off the batch),
    every finding is annotated with the planner's bytes-at-stake for the
    whole state, turning "param 3 was not donated" into "this run holds
    N extra GB". ``precompiled=(lowered, compiled_text)`` reuses an
    existing AOT compile of the same program.
    """
    if precompiled is not None:
        report = report_from_lowered(*precompiled)
    else:
        jitted = getattr(step_fn, "jitted", step_fn)
        report = donation_report(jitted, state, batch)
    if cfg is not None:
        inputs = batch["inputs"] if isinstance(batch, dict) else batch
        b = batch_size if batch_size is not None else int(inputs.shape[0])
        s = seq_len if seq_len is not None else int(inputs.shape[1])
        at_stake = missed_donation_bytes(cfg, b, s)
        report["missed_donation_bytes"] = at_stake
        report["findings"] = [
            Finding(
                f.check, f.rule, f.where,
                f.message + f" (planner: ~{at_stake / 1e6:.1f} MB at stake "
                "across the full state)",
                data={**f.data, "plan_bytes_at_stake": at_stake},
            )
            for f in report["findings"]
        ]
    return report
