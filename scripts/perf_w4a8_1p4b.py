"""One-process quantization-ladder A/B at 1.4B (PERF.md 'fused int4' table).

24 x 2048 x 16-head (head_dim 128), b=8, prompt 64, +64 new — the shape
where decode is weight-bandwidth-bound and the ladder separates cleanly.
Within-process comparisons only (run-to-run drift).
"""
import dataclasses
import gc

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from learning_jax_sharding_tpu.models.generate import make_generate_fn
from learning_jax_sharding_tpu.models.quantize import (
    map_unquantized, quantize_tree, quantized_bytes,
)
from learning_jax_sharding_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from learning_jax_sharding_tpu.parallel import build_mesh, mesh_sharding, put
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from learning_jax_sharding_tpu.utils.bench import mbu, time_fn

cfg = TransformerConfig(
    num_layers=24, features=2048, num_heads=16, head_dim=128, hidden=8192,
    max_seq_len=256,
)
mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
b, prompt_len, new = 8, 64, 64
rng = np.random.default_rng(0)
prompt = put(
    rng.integers(0, cfg.vocab_size, size=(b, prompt_len)).astype(np.int32),
    mesh_sharding(mesh, "data", None),
)
model = Transformer(cfg)
params = nn.meta.unbox(
    jax.jit(lambda r, t: model.init({"params": r}, t))(
        jax.random.key(0), prompt
    )["params"]
)
print(f"params ~{cfg.param_count/1e9:.2f}B", flush=True)


def to_bf16(x):
    return x.astype(jnp.bfloat16) if jnp.issubdtype(x.dtype, jnp.floating) else x


def bench(label, tree, dequantize):
    gen = make_generate_fn(
        cfg, mesh, RULES_DP_TP, max_new_tokens=new,
        inference_dtype=jnp.bfloat16, dequantize=dequantize,
    )
    out = np.asarray(gen(tree, prompt, jax.random.key(1)))  # warm + tokens
    secs = time_fn(gen, tree, prompt, jax.random.key(1), min_time=2.0)
    served = quantized_bytes(map_unquantized(to_bf16, tree))
    n_kv = cfg.num_kv_heads or cfg.num_heads
    cache = cfg.num_layers * b * n_kv * (prompt_len + new / 2) * cfg.head_dim * 2 * 2
    frac = mbu(served + cache, secs / new)
    print(
        f"{label}: {b*new/secs:,.0f} tok/s, {secs/new*1e3:.2f} ms/token-step, "
        f"served {served/1e9:.2f} GB, MBU={frac:.1%}",
        flush=True,
    )
    return out


out_bf16 = bench("bf16", params, False)
q8 = quantize_tree(params)
q4 = quantize_tree(params, bits=4)
del params
gc.collect()
out_i8 = bench("int8 in-jit dequant", q8, True)
del q8
gc.collect()
out_f = bench("int4 fused (w4a16)", q4, "fused")
out_w = bench("int4 fused w4a8", q4, "fused_w4a8")
# Accuracy deltas vs the bf16 reference tokens (greedy, random-init weights:
# agreement is a smoke signal, real evals live in case12's finetune pipeline).
for name, o in [("int8", out_i8), ("w4a16", out_f), ("w4a8", out_w)]:
    agree = (o[:, prompt_len:] == out_bf16[:, prompt_len:]).mean()
    print(f"token agreement vs bf16 [{name}]: {agree:.1%}", flush=True)
