"""MoE performance story (VERDICT r2 item 7): measured, not asserted.

Three measurements, one process (run-to-run drift):
1. 125M-class MoE (E=8, top-2) train step at capacity 1.0/1.25/2.0 —
   ms/step + activated-MFU (the honest denominator for routed models).
2. Routing overhead: the same step with the MoE FF swapped for a DENSE FF
   of the activated width (2x hidden for top-2) — the delta is what the
   router + dispatch/combine einsums + capacity padding cost.
3. Capacity vs QUALITY: a small MoE byte-LM trained on real text (this
   repo's own sources — the zero-egress corpus) for 150 steps per
   capacity factor; final losses show what capacity buys.

Run from /root/repo:  python - < scripts/perf_moe.py
"""
import dataclasses
import pathlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_125M,
    Transformer,
    fused_next_token_loss,
    next_token_loss,
)
from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn
from learning_jax_sharding_tpu.parallel import build_mesh, mesh_sharding, put
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from learning_jax_sharding_tpu.training.pipeline import (
    make_train_step,
    sharded_train_state,
)
from learning_jax_sharding_tpu.utils.bench import measure

mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
# b=8, K=4 OOMs the 16 GB chip with E=8 fp32 AdamW state (~6.6 GB) +
# activations; b=4, K=2 fits and the per-token numbers are what matter.
b, s = 4, 1024
rng = np.random.default_rng(0)


def step_time(cfg, K=2):
    # sgd, not adamw: non-donating timing holds INPUT and OUTPUT states
    # simultaneously, and 2 x (E=8 fp32 AdamW state ~ 6.8 GB) + gradients
    # exhausts the chip. sgd state is params-only; every config in this
    # file uses it, so the MoE-vs-dense DELTAS are apples to apples.
    tokens = rng.integers(0, cfg.vocab_size, size=(b, s + 1)).astype(np.int32)
    sh = mesh_sharding(mesh, "data", None)
    batch = {"inputs": put(tokens[:, :-1], sh), "targets": put(tokens[:, 1:], sh)}
    state, state_sh = sharded_train_state(
        Transformer(cfg), optax.sgd(3e-4), batch["inputs"],
        {"params": jax.random.key(0)}, mesh, RULES_DP_TP,
    )
    stacked = {
        k: put(np.stack([np.asarray(v)] * K), mesh_sharding(mesh, None, "data", None))
        for k, v in batch.items()
    }
    step = make_train_step(
        state_sh, {k: v.sharding for k, v in batch.items()}, mesh, RULES_DP_TP,
        loss_fn=fused_next_token_loss, loss_needs_params=True,
        apply_kwargs={"return_hidden": True}, donate_state=False,
        steps_per_call=K,
    )
    r = measure(
        step, state, stacked, flops=cfg.train_step_flops(b, s) * K,
        n_devices=1, min_time=2.0,
    )
    return r.seconds_per_iter / K, r.mfu


# remat: the per-layer dispatch/combine tensors (GShard one-hots,
# ~(tokens x E x C) f32 per layer) otherwise stack up across 12 layers
# on top of the 6.6 GB fp32 AdamW state and exhaust the 16 GB chip --
# remat is how MoE trains at scale anyway.
base = dataclasses.replace(CONFIG_125M, attn_fn=make_flash_attn_fn(), remat=True)
for cap in (1.0, 1.25, 2.0):
    cfg = dataclasses.replace(
        base, num_experts=8, moe_top_k=2, moe_capacity_factor=cap
    )
    ms, mfu = step_time(cfg)
    print(
        f"MoE E=8 top-2 cap={cap}: {ms*1e3:.1f} ms/step, "
        f"activated-MFU={mfu:.1%}", flush=True,
    )

# Dense control at the activated width (2x hidden ~ top-2's activated FF
# params, same attention): the routing machinery's cost is the delta.
dense2x = dataclasses.replace(base, hidden=2 * base.hidden)
ms_d, mfu_d = step_time(dense2x)
print(f"dense control (hidden x2): {ms_d*1e3:.1f} ms/step, MFU={mfu_d:.1%}",
      flush=True)

# --- capacity vs loss on real text (repo sources as corpus) ---
src = sorted(pathlib.Path("learning_jax_sharding_tpu").rglob("*.py"))
corpus = "\n".join(p.read_text() for p in src)
data = np.frombuffer(corpus.encode("utf-8"), np.uint8).astype(np.int32)
print(f"corpus: {len(data):,} bytes of repo source", flush=True)

small = dataclasses.replace(
    CONFIG_125M, vocab_size=256, num_layers=4, features=256, num_heads=4,
    hidden=1024, max_seq_len=256, num_experts=8, moe_top_k=2,
)
bs, ss, steps = 16, 256, 150


def loss_run(cap, seed=0):
    cfg = dataclasses.replace(small, moe_capacity_factor=cap)
    r2 = np.random.default_rng(seed)
    sh = mesh_sharding(mesh, "data", None)
    starts0 = r2.integers(0, len(data) - ss - 1, size=bs)
    win0 = np.stack([data[i : i + ss + 1] for i in starts0])
    batch0 = {"inputs": put(win0[:, :-1], sh), "targets": put(win0[:, 1:], sh)}
    state, state_sh = sharded_train_state(
        Transformer(cfg), optax.adamw(1e-3), batch0["inputs"],
        {"params": jax.random.key(1)}, mesh, RULES_DP_TP,
    )
    step = make_train_step(
        state_sh, {k: v.sharding for k, v in batch0.items()}, mesh,
        RULES_DP_TP, loss_fn=next_token_loss, donate_state=False,
    )
    losses = []
    for i in range(steps):
        starts = r2.integers(0, len(data) - ss - 1, size=bs)
        win = np.stack([data[j : j + ss + 1] for j in starts])
        bt = {"inputs": put(win[:, :-1], sh), "targets": put(win[:, 1:], sh)}
        state, loss = step(state, bt)
        losses.append(float(loss))
    return np.mean(losses[:10]), np.mean(losses[-10:])


for cap in (1.0, 1.25, 2.0):
    first, last = loss_run(cap)
    print(
        f"byte-LM MoE cap={cap}: loss first10={first:.3f} -> last10={last:.3f}",
        flush=True,
    )
