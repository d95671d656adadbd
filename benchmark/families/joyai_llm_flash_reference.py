"""The plain reference of JoyAI-LLM-Flash: float32 ``jax.numpy``, matmuls at
the highest precision, the EXPANDED (non-absorbed) latent attention, every
expert visited for every token and weighted by a dense ``(T, E)`` matrix that
is zero outside the picks. No cache, no kernels, nothing sorted, nothing
imported from ``models/`` or ``ops/``; it reads the program's parameter tree
as ``benchmark/reference.py`` does.

Equations (the model's ``config.json``; d = hidden size, eps, theta)::

    h = x + MLA(RMSNorm(x));  y = h + FFN_l(RMSNorm(h));  final RMSNorm, head
    MLA: c_q = RMSNorm(x W_qa); [q_nope | q_rope]_h = c_q W_qb
         [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv)
         RoPE rotates the pairs (2i, 2i+1); k_rope is one vector for all heads
         [k_nope | v]_h = c_kv W_kvb
         s_h(t,u) = (q_nope_h(t).k_nope_h(u) + q_rope_h(t).k_rope(u)) / sqrt(nope+rope)
    FFN, layers < first_k_dense: (silu(x W_g) * x W_u) W_d
    FFN, later layers: s = sigmoid(x W_r) (float32); picks = top_k(s + b);
         w_i = scaling * s_i / (sum_picks s + 1e-20); sum_i w_i E_i(x) + E_shared(x)

Departures from the published model: the multi-token-prediction module is
not built (a training loss and an optional drafter). It has to fit beside
11 GB of live bf16 weights: one block is jitted and called once a layer, the
experts are scanned (one expert's float32 copy at a time), attention runs in
query blocks, and the logits come back as a HOST array built a block of
positions at a time (4 x 2,176 x 129,280 float32 is 4.5 GB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 128
_LOGIT_BLOCK = 128


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(p["scale"])


def _rope(x, theta):
    """Rotate the pairs (2i, 2i+1) of ``x`` ``(B, S, N, R)`` by position."""
    r, s = x.shape[-1], x.shape[1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv           # (S, R/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _gated(x, p):
    g, u = x @ _f32(p["gate"]["kernel"]), x @ _f32(p["up"]["kernel"])
    return (g * jax.nn.sigmoid(g) * u) @ _f32(p["down"]["kernel"])


def _attention(q, k, v, scale):
    """Causal softmax attention, ``_QUERY_BLOCK`` queries at a time."""
    b, s, n, _ = q.shape
    qb = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = jnp.einsum("bqnd,bknd->bnqk", qi, k) * scale
        seen = keys[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        return jnp.einsum(
            "bnqk,bknd->bqnd", jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1), v
        )

    out = jax.lax.map(block, jnp.arange(s // qb))         # (S/qb, B, qb, N, dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, n, v.shape[-1])


def _mla(x, p, m):
    b, s, _ = x.shape
    n, dn, dr, dv, r = m["num_heads"], m["nope_dim"], m["rope_dim"], m["v_dim"], m["kv_rank"]
    c_q = _rms(x @ _f32(p["q_a"]["kernel"]), p["q_norm"], m["norm_eps"])
    q = (c_q @ _f32(p["q_b"]["kernel"])).reshape(b, s, n, dn + dr)
    kv = x @ _f32(p["kv_a"]["kernel"])
    c_kv = _rms(kv[..., :r], p["kv_norm"], m["norm_eps"])
    k_rope = _rope(kv[..., None, r:], m["rope_theta"])             # (B,S,1,dr)
    k_v = (c_kv @ _f32(p["kv_b"]["kernel"])).reshape(b, s, n, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m["rope_theta"])], -1)
    k = jnp.concatenate([k_v[..., :dn], jnp.broadcast_to(k_rope, (b, s, n, dr))], -1)
    out = _attention(q, k, k_v[..., dn:], (dn + dr) ** -0.5)
    return out.reshape(b, s, n * dv) @ _f32(p["out"]["kernel"])


def _moe(x, p, m):
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xt @ _f32(p["router"]["kernel"]))        # (T, E)
    _, idx = jax.lax.top_k(scores + _f32(p["bias"]), m["top_k"])
    picked = jnp.take_along_axis(scores, idx, -1)
    w = m["routed_scaling"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    dense_w = jnp.zeros_like(scores).at[jnp.arange(b * s)[:, None], idx].set(w)

    def expert(acc, ew):
        g, u, dn, col = ew
        h = xt @ _f32(g)
        return acc + col[:, None] * ((h * jax.nn.sigmoid(h) * (xt @ _f32(u))) @ _f32(dn)), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(xt), (p["gate"], p["up"], p["down"], dense_w.T)
    )
    if m["shared_experts"]:
        out = out + _gated(xt, p["shared"])
    return out.reshape(b, s, d)


def _block(x, blk, m):
    h = x + _mla(_rms(x, blk["ln_attn"], m["norm_eps"]), blk["attn"], m)
    y = _rms(h, blk["ln_ff"], m["norm_eps"])
    return h + (_moe(y, blk["moe"], m) if "moe" in blk else _gated(y, blk["ff"]))


def reference_fn(model: dict):
    """``run(params, tokens) -> float32 logits`` as a host array, for the
    sizes ``families.joyai_llm_flash.model_dims`` gives."""
    embed = jax.jit(lambda table, tokens: _f32(table[tokens]))
    block = jax.jit(lambda x, blk: _block(x, blk, model))
    head = jax.jit(
        lambda x, params: _rms(x, params["ln_out"], model["norm_eps"])
        @ _f32(params["lm_head"]["kernel"])
    )

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = embed(params["tok_embed"]["embedding"], tokens)
            for i in range(model["num_layers"]):
                x = block(x, params[f"block_{i}"])
            b, s = tokens.shape
            step = _LOGIT_BLOCK if s % _LOGIT_BLOCK == 0 else s
            out = np.empty((b, s, model["vocab_size"]), np.float32)
            for lo in range(0, s, step):
                out[:, lo : lo + step] = np.asarray(head(x[:, lo : lo + step], params))
            return out

    return run
