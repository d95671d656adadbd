"""Run a benchmark cell with a FAULT planted in the program, through the
harness's own comparison (the reference and the cell's limits stay as they
are): what ``correct`` reads for a step that is wrong in a known way. The
readings beside ``check``'s limits in ``benchmark/configs/lfm2-8b-a1b.json``
and ``PERF.md`` (Findings, PR 35) were taken with it.

    chiprun -- python scripts/bench_control.py <fault> --workload \
        lfm2-8b-a1b.pretrain_8k --seed <n> --seconds 5 --trace 0

Faults (a training cell over ``DroplessMoE``):

``none``                the program as it is.
``bf16_params``         the precision below the stated one: parameters (and
                        with them AdamW's moments and the update) in bf16
                        where the configuration states float32.
``half_batch``          the step trains on the first of its sequences only;
                        the loss it reports is the whole batch's.
``bf16_router``         the router's matmul and sigmoid in bf16.
``dropped_expert``      held expert 3's term left out of the combine.
``dropped_renorm_eps``  the picks' sum renormalised with 1e-20, not 1e-6.

Faults of a serving cell over window layers (the ``laguna`` family): the
program's own, or the REFERENCE's (``ref_*``: the harness compares a sound
program against a reference that computes the model wrongly in that way,
which reads as the program being wrong in the opposite one):

``ring_off_by_one``     the kernel reads a window layer's logical block j
                        from ring page (j + 1) mod R; writes go where they
                        belong.
``ref_window_ignored``  the window layers attend the whole history.
``ref_gate_dropped``    no per-head output gate.
``ref_full_rotary``     the global layers rotate all 128 dims of a head.
``ref_fp8_weights``     every weight matrix rounded to fp8 e4m3 under a
                        scale of its own as a block reads it (an expert's
                        matrices one at a time): the precision below the
                        stated bf16.

``REPLAY=<k>`` in the environment overrides ``check.replay_steps``.
"""
import os
import runpy
import sys

sys.path.insert(0, ".")
fault, sys.argv = sys.argv[1], ["benchmark/run.py", *sys.argv[2:]]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import spec  # noqa: E402

# ``models.transformer`` binds ``DroplessMoE`` by name when it is imported:
# nothing here imports it before a fault has replaced the class.
from learning_jax_sharding_tpu.models import moe  # noqa: E402
from learning_jax_sharding_tpu.ops import moe_experts  # noqa: E402

if fault == "bf16_params":
    from benchmark.families import lfm2_moe

    real_config = lfm2_moe.to_config

    def to_config(cfg, **overrides):
        return real_config(cfg, **{**overrides, "param_dtype": jnp.bfloat16})

    lfm2_moe.to_config = to_config
elif fault == "half_batch":
    from learning_jax_sharding_tpu.models import transformer

    real_loss = transformer.fused_next_token_loss

    def half(hidden, batch, params, **kw):
        whole = real_loss(hidden, batch, params, **kw)
        first = real_loss(hidden[:1], {"targets": batch["targets"][:1]}, params, **kw)
        share = first / hidden.shape[0]
        return jax.lax.stop_gradient(whole - share) + share

    transformer.fused_next_token_loss = half
elif fault == "bf16_router":

    class Bf16Router(moe.DroplessMoE):
        router_dtype: jnp.dtype = jnp.bfloat16

    moe.DroplessMoE = Bf16Router
elif fault == "dropped_expert":
    real_experts = moe_experts.routed_experts

    def dropped(x, idx, weights, *a, **k):
        return real_experts(x, idx, jnp.where(idx == 3, 0.0, weights), *a, **k)

    moe_experts.routed_experts = dropped
elif fault == "dropped_renorm_eps":

    class NoEps(moe.DroplessMoE):
        renorm_eps: float = 1e-20

    moe.DroplessMoE = NoEps
elif fault == "ring_off_by_one":
    from learning_jax_sharding_tpu.ops import decode_attention as kernel

    real_kernel = kernel.decode_attention

    def shifted(*args, block_table=None, ring=False, **kw):
        if ring:
            block_table = jnp.roll(block_table, -1, axis=1)
        return real_kernel(*args, block_table=block_table, ring=ring, **kw)

    kernel.decode_attention = shifted
elif fault.startswith("ref_"):
    from benchmark.families import laguna, laguna_reference

    real_reference = laguna.reference_fn

    def faulty(dims):
        if fault == "ref_window_ignored":
            dims = {**dims, "window": 1 << 30}
        elif fault == "ref_full_rotary":
            dims = {**dims, "full_rope": {**dims["full_rope"], "rotary_dim": dims["head_dim"]}}
        run = real_reference(dims)

        def wrapped(params, tokens):
            if fault == "ref_gate_dropped":
                # sigmoid(0) = 1/2 a head, undone by twice the output
                # projection: a layer without its gate.
                params = jax.tree_util.tree_map_with_path(
                    lambda p, x: (
                        jnp.zeros_like(x) if "'gate'" in jax.tree_util.keystr(p[-2:])
                        and "'attn'" in jax.tree_util.keystr(p) else
                        2 * x if "['attn']['out']" in jax.tree_util.keystr(p) else x
                    ), params,
                )
            return run(params, tokens)

        return wrapped

    if fault == "ref_fp8_weights":
        # Each weight matrix as a block reads it (an expert's at a time):
        # a rounded copy of the whole tree would not fit beside the served
        # one on the chip.
        real_f32 = laguna_reference._f32

        def fp8(x):
            x = real_f32(x)
            if x.ndim < 2:
                return x
            scale = jnp.max(jnp.abs(x)) / 448.0
            return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

        laguna_reference._f32 = fp8
    laguna.reference_fn = faulty
elif fault != "none":
    raise SystemExit(f"unknown fault {fault}")

if os.environ.get("REPLAY"):
    real_load = spec._load

    def patched(path):
        d = real_load(path)
        if "replay_steps" in d.get("check", {}):
            d["check"]["replay_steps"] = int(os.environ["REPLAY"])
        return d

    spec._load = patched

print('{"info": "control", "fault": "%s"}' % fault, flush=True)
runpy.run_path("benchmark/run.py", run_name="__main__")
