"""Chip micro-benchmark behind ``ops/moe_experts.py``'s choice of backward
(PR 35): ``routed_experts`` forward + backward at the shapes of
``lfm2-8b-a1b.pretrain_8k`` (16,384 tokens, top 4 of 32, experts 0-7 held,
2048 x 1792, float32 weights), the Pallas VJP (``moe.experts`` +
``moe.experts_dx`` + ``moe.experts_dw``) against XLA's own transposes of
``ragged_dot``, and how far the two sets of gradients lie apart. ONE
implementation ships (the Pallas one: 24.3 against 57.8 ms, ``PERF.md``
Findings, PR 35); this script is how to measure it again.

    chiprun -- python scripts/perf_moe_bwd.py      # writes chiprun_out/moe_bwd_bench.json
"""
import json, os, sys, time
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from learning_jax_sharding_tpu.ops.moe_experts import routed_experts

T, D, F, E, HELD, K = 16384, 2048, 1792, 32, 8, 4
ks = jax.random.split(jax.random.key(7), 8)
x = jax.random.normal(ks[0], (T, D), jnp.bfloat16)
wg = jax.random.normal(ks[1], (HELD, D, F), jnp.float32) / D**0.5
wu = jax.random.normal(ks[2], (HELD, D, F), jnp.float32) / D**0.5
wd = jax.random.normal(ks[3], (HELD, F, D), jnp.float32) / F**0.5
scores = jax.random.uniform(ks[4], (T, E))
w, idx = jax.lax.top_k(scores, K)
w = w / w.sum(-1, keepdims=True)

def make(backend):
    def loss(x, w, wg, wu, wd):
        out, _ = routed_experts(x, idx, w, wg, wu, wd, backend=backend, first=0)
        return jnp.sum(out.astype(jnp.float32) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))), jax.jit(loss)

out = {}
grads = {}
for backend in ("pallas", "ragged"):
    g, f = make(backend)
    try:
        t0 = time.perf_counter(); r = jax.block_until_ready(g(x, w, wg, wu, wd)); c = time.perf_counter() - t0
        ts = []
        for _ in range(5):
            t0 = time.perf_counter(); jax.block_until_ready(g(x, w, wg, wu, wd)); ts.append(time.perf_counter() - t0)
        jax.block_until_ready(f(x, w, wg, wu, wd))
        tf = []
        for _ in range(5):
            t0 = time.perf_counter(); jax.block_until_ready(f(x, w, wg, wu, wd)); tf.append(time.perf_counter() - t0)
        out[backend] = {"compile_s": c, "fwd_bwd_ms": sorted(ts)[2] * 1e3, "fwd_ms": sorted(tf)[2] * 1e3}
        grads[backend] = r
    except Exception as e:  # noqa: BLE001
        out[backend] = {"error": repr(e)[:400]}
if len(grads) == 2:
    out["rel_diff"] = [
        float(jnp.linalg.norm((a - b).astype(jnp.float32)) / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-9))
        for a, b in zip(grads["pallas"], grads["ragged"])
    ]
flops_fwd = T * K * HELD / E * 6 * D * F
out["fwd_tflop"] = flops_fwd / 1e12
print(json.dumps(out))
os.makedirs("chiprun_out", exist_ok=True)
json.dump(out, open("chiprun_out/moe_bwd_bench.json", "w"))
