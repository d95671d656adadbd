"""Per-pallas-call fixed cost on the v5e (written for the remotely
attached chip of rounds 1-5; not measured on today's machine).

If ~15-20us/call, the 1.4B int4 decode story is 169 custom calls x floor,
and the fix is CALL COUNT (qkv fusion, whole-FF kernels), not VPU work.
"""
import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from learning_jax_sharding_tpu.utils.bench import time_fn

rng = np.random.default_rng(0)
CH = 64


def chained(fn_one, x0):
    def run(x):
        def body(i, x):
            out = fn_one(x)
            return x + (out[:, :1] * 1e-30).astype(x.dtype)
        return jax.lax.fori_loop(0, CH, body, x)
    return jax.jit(run), x0


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


x_small = jnp.asarray(rng.standard_normal((8, 128)), jnp.bfloat16)
noop = pl.pallas_call(
    copy_kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.bfloat16)
)
f, x0 = chained(lambda x: noop(x), x_small)
t = time_fn(f, x0, min_time=1.0) / CH
print(f"no-op pallas call: {t*1e6:.1f} us", flush=True)

# XLA elementwise of same size, chained — the non-custom-call control.
f, x0 = chained(lambda x: x * 1.0000001 + 0.0, x_small)
t = time_fn(f, x0, min_time=1.0) / CH
print(f"XLA elementwise chain step: {t*1e6:.1f} us", flush=True)

# Same REAL matmul work, pallas vs XLA, identical operands (8,2048)x(2048,8192).
K, N = 2048, 8192
w = jnp.asarray(rng.standard_normal((K, N)) * 0.02, jnp.bfloat16)
x = jnp.asarray(rng.standard_normal((8, K)), jnp.bfloat16)


def mm_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


pmm = pl.pallas_call(
    mm_kernel,
    grid=(N // 512,),
    in_specs=[
        pl.BlockSpec((8, K), lambda j: (0, 0)),
        pl.BlockSpec((K, 512), lambda j: (0, j)),
    ],
    out_specs=pl.BlockSpec((8, 512), lambda j: (0, j)),
    out_shape=jax.ShapeDtypeStruct((8, N), jnp.bfloat16),
)
f, x0 = chained(lambda x: pmm(x, w), x)
t = time_fn(f, x0, min_time=1.0) / CH
print(f"pallas bf16 matmul call: {t*1e6:.1f} us", flush=True)
f, x0 = chained(lambda x: x @ w, x)
t = time_fn(f, x0, min_time=1.0) / CH
print(f"XLA    bf16 matmul step: {t*1e6:.1f} us", flush=True)

# Call-count scaling: one (8,2048)x(2048,8192) call vs four N=2048 calls.
def four_calls(x):
    outs = []
    for j in range(4):
        pj = pl.pallas_call(
            mm_kernel,
            grid=(4,),
            in_specs=[
                pl.BlockSpec((8, K), lambda j: (0, 0)),
                pl.BlockSpec((K, 512), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((8, 512), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((8, 2048), jnp.bfloat16),
        )
        outs.append(pj(x, w[:, j * 2048 : (j + 1) * 2048]))
    return jnp.concatenate(outs, axis=1)

f, x0 = chained(four_calls, x)
t = time_fn(f, x0, min_time=1.0) / CH
print(f"4x pallas calls (same total work): {t*1e6:.1f} us", flush=True)
