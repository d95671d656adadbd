"""Ragged vs pad-to-max decode throughput (PERF.md "Ragged serving").

Skewed-length batch at 125M: one long row (512) + seven short rows (64),
+64 new tokens, blocked backend. Pad-to-max is the only thing the
rectangular stack could express: every row decodes at position 512+t and
the kernel reads every row's cache to the batch max. Ragged reads each
row's own valid prefix. One process (run-to-run drift).
"""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from learning_jax_sharding_tpu.models.generate import make_generate_fn
from learning_jax_sharding_tpu.models.transformer import CONFIG_125M, Transformer
from learning_jax_sharding_tpu.parallel import build_mesh, mesh_sharding, put
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from learning_jax_sharding_tpu.utils.bench import time_fn

cfg = CONFIG_125M
mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
b, new = 8, 64
lengths = np.asarray([512] + [64] * 7, np.int32)
pmax = int(lengths.max())
rng = np.random.default_rng(0)
tokens = rng.integers(0, cfg.vocab_size, size=(b, pmax)).astype(np.int32)
prompt = put(tokens, mesh_sharding(mesh, "data", None))
model = Transformer(cfg)
params = nn.meta.unbox(
    jax.jit(lambda r, t: model.init({"params": r}, t))(
        jax.random.key(0), prompt
    )["params"]
)

gen_rect = make_generate_fn(
    cfg, mesh, RULES_DP_TP, max_new_tokens=new, inference_dtype=jnp.bfloat16
)
secs_rect = time_fn(gen_rect, params, prompt, jax.random.key(1), min_time=2.0)
print(
    f"pad-to-max (all rows at {pmax}): {b*new/secs_rect:,.0f} tok/s, "
    f"{secs_rect/new*1e3:.2f} ms/token-step", flush=True,
)

gen_rag = make_generate_fn(
    cfg, mesh, RULES_DP_TP, max_new_tokens=new, inference_dtype=jnp.bfloat16,
    ragged=True,
)
secs_rag = time_fn(
    gen_rag, params, prompt, jax.random.key(1), jnp.asarray(lengths),
    min_time=2.0,
)
print(
    f"ragged (lengths {lengths.tolist()}): {b*new/secs_rag:,.0f} tok/s, "
    f"{secs_rag/new*1e3:.2f} ms/token-step ({secs_rect/secs_rag:.2f}x)",
    flush=True,
)

# A uniform-length control: ragged machinery at ALL-equal lengths vs the
# rectangular path — the cost of per-row scatters when nothing is ragged.
uni = np.full((b,), pmax, np.int32)
secs_uni = time_fn(
    gen_rag, params, prompt, jax.random.key(1), jnp.asarray(uni), min_time=2.0
)
print(
    f"ragged, uniform lengths ({pmax}): {b*new/secs_uni:,.0f} tok/s, "
    f"{secs_uni/new*1e3:.2f} ms/token-step "
    f"(overhead vs rect {secs_uni/secs_rect:.2f}x)", flush=True,
)

# Deeper skew: one 960-token row pins the batch max (960 + 64 new fills
# the 1024 cache); pad-to-max decodes EVERY row at position 960+t while
# ragged rows sit at 64+t.
lengths2 = np.asarray([960] + [64] * 7, np.int32)
pmax2 = int(lengths2.max())
tokens2 = rng.integers(0, cfg.vocab_size, size=(b, pmax2)).astype(np.int32)
prompt2 = put(tokens2, mesh_sharding(mesh, "data", None))
secs_rect2 = time_fn(gen_rect, params, prompt2, jax.random.key(1), min_time=2.0)
print(f"pad-to-max (1024): {b*new/secs_rect2:,.0f} tok/s, "
      f"{secs_rect2/new*1e3:.2f} ms/token-step", flush=True)
secs_rag2 = time_fn(
    gen_rag, params, prompt2, jax.random.key(1), jnp.asarray(lengths2),
    min_time=2.0,
)
print(f"ragged (1024 skew): {b*new/secs_rag2:,.0f} tok/s, "
      f"{secs_rag2/new*1e3:.2f} ms/token-step ({secs_rect2/secs_rag2:.2f}x)",
      flush=True)
