"""What the held experts of ``lfm2-8b-a1b.pretrain_8k`` are really given:
the cell's own model, state, optimizer and batches (built as
``benchmark/train.py`` builds them, from the same seed), stepped with
``make_train_step(routing_stats=True)`` so that every step returns, beside
its loss, the assignments routed to the held experts, the held experts
touched and the largest load of one held expert in one layer.

``moe_expert_train_roofline`` and ``mfu_pct.lfm2_moe`` count the held
experts' work by the UNIFORM expectation (tokens x top_k x held / experts
assignments a layer) because ``benchmark/train.py`` hands a reader no
counters; this script says how far the seeds' real routing is from it, at
the seeded state and at the steps the traced slice covers (``PERF.md``
section 5, PR 35).

    chiprun -- python scripts/perf_lfm2_routing.py --steps 72 --seeds 2147484201 ...

Writes one JSON line a seed to stdout and ``chiprun_out/lfm2_routing.json``.
``--rehearse`` runs the tiny sizes anywhere (not a measurement).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import readers, spec, traffic  # noqa: E402
from learning_jax_sharding_tpu.models.transformer import (  # noqa: E402
    Transformer,
    fused_next_token_loss,
)
from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn  # noqa: E402
from learning_jax_sharding_tpu.parallel import mesh_sharding, put, single_device_mesh  # noqa: E402
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP  # noqa: E402
from learning_jax_sharding_tpu.training.loop import TrainLoopConfig, default_optimizer  # noqa: E402
from learning_jax_sharding_tpu.training.pipeline import (  # noqa: E402
    make_train_step,
    sharded_train_state,
)

WORKLOAD = "lfm2-8b-a1b.pretrain_8k"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--steps", type=int, default=72)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    cell = spec.load_cell(WORKLOAD, rehearse=args.rehearse, readers=readers.READERS)
    model, tr = cell.model, cell.traffic
    dims = cell.family.model_dims(model)
    batch, seq = tr["batch"], tr["seq"]
    on_tpu = jax.default_backend() == "tpu"
    mesh = single_device_mesh()
    cfg = cell.family.to_config(
        model, dtype=jnp.bfloat16, param_dtype=jnp.float32,
        attn_fn=make_flash_attn_fn(interpret=not on_tpu), **tr.get("model_overrides", {}),
    )
    module = Transformer(cfg)
    optimizer = default_optimizer(TrainLoopConfig(global_batch_size=batch, **tr["optimizer"]))
    sh = mesh_sharding(mesh, "data", None)
    layers = sum(i >= dims["num_dense_layers"] for i in range(dims["num_layers"]))
    tokens = batch * seq
    expected = tokens * dims["top_k"] * dims["held_count"] // dims["num_experts"]

    step, lines = None, []
    for seed in args.seeds:
        host = traffic.token_batches(batch, seq, dims["vocab_size"], seed, tr["batch_pool"])

        def on_device(i):
            t = host[i % len(host)]
            return {"inputs": put(t[:, :-1], sh), "targets": put(t[:, 1:], sh)}

        first = on_device(0)
        key = jax.random.fold_in(jax.random.key(seed >> 31), seed & 0x7FFFFFFF)
        state, state_sh = sharded_train_state(
            module, optimizer, first["inputs"], {"params": key}, mesh, RULES_DP_TP
        )
        if step is None:
            step = make_train_step(
                state_sh, {k: v.sharding for k, v in first.items()}, mesh, RULES_DP_TP,
                loss_fn=fused_next_token_loss, loss_needs_params=True,
                apply_kwargs={"return_hidden": True}, routing_stats=True,
            )
        outs = []
        for i in range(args.steps):
            state, out = step(state, on_device(i))
            outs.append(out)
        del state
        per_step = [
            {
                "step": i, "loss": float(o["loss"]),
                "held_assignments": int(o["moe_held_assignments"]),
                "experts_touched": int(o["moe_experts_touched"]),
                "max_load": int(o["moe_expert_max_load"]),
            }
            for i, o in enumerate(outs)
        ]
        shown = [p for p in per_step if p["step"] < 2 or p["step"] >= args.steps - 3]
        line = {
            "seed": seed, "tokens": tokens, "expert_layers": layers,
            "expected_a_layer": expected, "expected_a_step": expected * layers,
            "held": dims["held_count"], "steps": shown,
            "counted_over_expected": [
                p["held_assignments"] / (expected * layers) for p in shown
            ],
            "max_load_over_uniform": [
                p["max_load"] / (expected / dims["held_count"]) for p in shown
            ],
        }
        print(json.dumps(line), flush=True)
        lines.append(line)
    if not args.rehearse:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/lfm2_routing.json", "w") as f:
            json.dump(lines, f)


if __name__ == "__main__":
    main()
