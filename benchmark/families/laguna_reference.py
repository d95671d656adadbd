"""The plain reference of the ``laguna`` family (poolside Laguna-XS.2):
float32 ``jax.numpy``, matmuls at the highest precision, bf16 parameters
upcast a layer at a time. No kernel, no cache, no batching: one stream at a
time, causal attention over the whole sequence with a mask (the window's
too), every expert visited for every token and weighted by a dense
``(T, E)`` matrix that is zero outside the picks. Nothing is imported from
``models/`` or ``ops/``; it reads the program's parameter tree as
``benchmark/reference.py`` does.

Equations (the model's ``config.json``; for layer ``l`` of kind ``k_l``)::

    h      = RMSNorm(x)                                         eps 1e-6
    q      = h W_q  -> H_l heads x 128   (H_l: 48 full, 64 sliding)
    k, v   = h W_k, h W_v -> 8 heads x 128
    full:    rotate q[..., :64], k[..., :64] (split half) by YaRN(theta 5e5,
             factor 64, orig 4096, beta_fast 64, beta_slow 1), cos and sin
             x attention_factor 1.41589; dims 64..127 pass through
    sliding: rotate all 128 dims, theta 1e4
    a_h    = softmax(q_h k^T / sqrt(128) + mask) v    causal; sliding: keys
                                                      in (p - 512, p]
    g      = sigmoid(h W_g)  (H_l scalars)             per-head output gate
    x      = x + concat_h(g_h * a_h) W_o
    h      = RMSNorm(x)
    dense:   x = x + SwiGLU_8192(h)
    sparse:  p = softmax(h W_r) over 256 (float32); picks = top8(p)
             x = x + 2.5 * sum_{i in picks} p_i * SwiGLU_512^(i)(h)
                   + SwiGLU_512^shared(h)
    SwiGLU(h) = (silu(h W_gate) * h W_up) W_down;  final RMSNorm, untied head

Attention runs in query blocks and the logits come back as a HOST array
built a block of positions at a time, so that 4 x 8,320 positions fit beside
7.7 GB of live bf16 weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 256
_LOGIT_BLOCK = 512


def _divisor(n: int, most: int) -> int:
    """The largest block of at most ``most`` positions that divides ``n``."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _yarn_freqs(dim, r):
    """YaRN's inverse frequencies for a rotation over ``dim`` values."""
    theta = r["theta"]
    freqs = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(turns):
        return dim * math.log(r["original_max_positions"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(corr(r["beta_fast"])), 0)
    high = min(math.ceil(corr(r["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001), 0, 1)
    freqs = freqs / r["factor"] * ramp + freqs * (1 - ramp)
    scale = r["attention_factor"]
    if scale is None:
        scale = 0.1 * math.log(r["factor"]) + 1.0
    return freqs, scale


def _rotate(x, positions, freqs, scale):
    """Split-half rotation of the first ``2 * len(freqs)`` values of each
    head of ``x`` ``(S, N, H)``; the rest pass through."""
    d = 2 * len(freqs)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    x1, x2 = x[..., : d // 2], x[..., d // 2 : d]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., d:]], -1)


def _attention(x, p, m, sliding, heads):
    s = x.shape[0]
    nkv, hd = m["num_kv_heads"], m["head_dim"]
    g = heads // nkv
    q = (x @ _f32(p["query"]["kernel"])).reshape(s, heads, hd)
    k = (x @ _f32(p["key"]["kernel"])).reshape(s, nkv, hd)
    v = (x @ _f32(p["value"]["kernel"])).reshape(s, nkv, hd)
    pos = jnp.arange(s)
    if sliding:
        freqs = 1.0 / m["sliding_theta"] ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
        q, k = _rotate(q, pos, freqs, 1.0), _rotate(k, pos, freqs, 1.0)
    else:
        freqs, scale = _yarn_freqs(m["full_rope"]["rotary_dim"], m["full_rope"])
        q, k = _rotate(q, pos, freqs, scale), _rotate(k, pos, freqs, scale)
    # Query head n reads kv head n // g.
    q = q.reshape(s, nkv, g, hd)
    qb = _divisor(s, _QUERY_BLOCK)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        sc = jnp.einsum("qngd,knd->ngqk", qi, k) * hd**-0.5
        qpos = (i * qb + jnp.arange(qb))[:, None]
        seen = pos[None, :] <= qpos
        if sliding:
            seen = seen & (pos[None, :] > qpos - m["window"])
        w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("ngqk,knd->qngd", w, v)

    a = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, heads, hd)
    gate = jax.nn.sigmoid(x @ _f32(p["gate"]["kernel"]))          # (S, H_l)
    return (a * gate[..., None]).reshape(s, heads * hd) @ _f32(p["out"]["kernel"])


def _swiglu(x, p):
    up = x @ _f32(p["up"]["kernel"])
    return (jax.nn.silu(x @ _f32(p["gate"]["kernel"])) * up) @ _f32(p["down"]["kernel"])


def _moe(x, p, m):
    t = x.shape[0]
    probs = jax.nn.softmax(x @ _f32(p["router"]["kernel"]), -1)      # (T, E)
    picked, idx = jax.lax.top_k(probs, m["top_k"])
    w = m["routed_scaling"] * picked                                  # not renormalised
    dense_w = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(w)

    def expert(acc, ew):
        gate, up, down, col = ew
        y = (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], dense_w.T)
    )
    return routed + _swiglu(x, p["shared"])


def reference_fn(model: dict):
    """``run(params, tokens) -> float32 logits`` as a host array, for the
    sizes ``families.laguna.model_dims`` gives."""
    eps = model["norm_eps"]

    def make_block(sliding, heads, sparse):
        def block(x, blk):
            x = x + _attention(
                _rms(x, blk["ln_attn"]["scale"], eps), blk["attn"], model,
                sliding, heads,
            )
            h = _rms(x, blk["ln_ff"]["scale"], eps)
            return x + (_moe(h, blk["moe"], model) if sparse else _swiglu(h, blk["ff"]))

        return jax.jit(block)

    kinds = [
        (t == "sliding_attention", n, f == "sparse")
        for t, n, f in zip(
            model["layer_types"], model["heads_per_layer"], model["mlp_layer_types"]
        )
    ]
    blocks = {kind: make_block(*kind) for kind in set(kinds)}
    embed = jax.jit(lambda table, tokens: _f32(table[tokens]))
    head = jax.jit(
        lambda x, params: _rms(x, params["ln_out"]["scale"], eps)
        @ _f32(params["lm_head"]["kernel"])
    )

    def run(params, tokens):
        b, s = tokens.shape
        out = np.empty((b, s, model["vocab_size"]), np.float32)
        step = _divisor(s, _LOGIT_BLOCK)
        with jax.default_matmul_precision("highest"):
            for row in range(b):
                x = embed(params["tok_embed"]["embedding"], tokens[row])
                for i, kind in enumerate(kinds):
                    x = blocks[kind](x, params[f"block_{i}"])
                for lo in range(0, s, step):
                    out[row, lo : lo + step] = np.asarray(head(x[lo : lo + step], params))
        return out

    return run
