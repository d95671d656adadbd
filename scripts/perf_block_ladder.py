"""Decode dispatch-granularity ladder: what one engine dispatch COSTS
and how block size / chaining amortize it.

Round-5 finding, on a remotely attached chip that no longer exists: a
jitted call cost ~120 ms in the DISPATCH itself (synchronous — chaining
device-carried calls without readbacks barely helps decode), so engine
throughput was set by tokens-per-dispatch. The ladder holds the workload fixed (32 x 64-token
prompts, +128 out, 8 slots, 125M bf16 blocked) and scales
decode_block_steps (tokens per compiled decode program) and
decode_chain (programs per host sync):

    K=16  chain=1:   823 tok/s     (round-4 default)
    K=32  chain=1: 1,346 tok/s
    K=64  chain=1: 2,036 tok/s
    K=128 chain=1: 2,637 tok/s     (one dispatch per generation wave)
    K=64  chain=2: 2,324 tok/s     (chaining stacks on block size)

Sizing rule: K ≈ max_new_tokens (rows retire at block boundaries, so
bigger K wastes nothing on uniform queues); chain amortizes the host
sync further when retirement detection can coarsen. The per-dispatch
floor of today's machine is not measured; where it is far smaller, K
matters less.

Run from /root/repo:  python - < scripts/perf_block_ladder.py
"""
import dataclasses
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from learning_jax_sharding_tpu.models.serving import make_continuous_engine
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_125M,
    Transformer,
)
from learning_jax_sharding_tpu.parallel import build_mesh
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP

cfg = dataclasses.replace(
    CONFIG_125M, max_seq_len=512, decode_attention="blocked"
)
mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
rng = np.random.default_rng(0)
model = Transformer(cfg)
params = nn.meta.unbox(
    jax.jit(lambda r, t: model.init({"params": r}, t))(
        jax.random.key(0), np.zeros((8, 64), np.int32)
    )["params"]
)
NREQ, NEW, PLEN = 32, 128, 64
prompts = [
    rng.integers(1, cfg.vocab_size, size=(PLEN,)).astype(np.int32)
    for _ in range(NREQ)
]
for steps, chain in ((16, 1), (32, 1), (64, 1), (128, 1), (64, 2)):
    serve = make_continuous_engine(
        cfg, mesh, RULES_DP_TP, batch_size=8, max_new_tokens=NEW,
        refill_chunk=64, inference_dtype=jnp.bfloat16,
        decode_block_steps=steps, decode_chain=chain,
    )
    serve(params, prompts[:9])
    t0 = time.perf_counter()
    outs = serve(params, prompts)
    dt = time.perf_counter() - t0
    lat = serve.last_latency
    toks = sum(len(o) - PLEN for o in outs)
    print(
        f"[block-ladder] K={steps} chain={chain}: {toks / dt:,.0f} tok/s "
        f"({dt:.2f} s; decode {lat['decode_s']:.2f} s)",
        flush=True,
    )
