"""The plain reference of the ``lfm2_moe`` family (LiquidAI LFM2-8B-A1B):
float32 ``jax.numpy``, matmuls at the highest precision. The short
convolution is a plain sum over its taps, attention is a masked softmax a
block of queries at a time, every HELD expert is visited for every token
and weighted by a dense ``(T, E)`` matrix that is zero outside the picks and
outside the held range. No kernels, nothing sorted, nothing imported from
``models/`` or ``ops/``; it reads the program's parameter tree as
``benchmark/reference.py`` does, and ``jax.grad`` goes through it
(``tests/test_lfm2_moe.py`` holds the program's gradients against it).

Equations (``config.json``'s keys; RMSNorm with a learned weight, no bias
anywhere; layer ``i``'s operator by ``layer_types[i]``, its feed-forward
dense below ``num_dense_layers``)::

    h = x + Op_i(RMSNorm(x));  y = h + FF_i(RMSNorm(h))
    conv:  [B | C | u] = n W_in;  z = B * u
           c_t = sum_{j<L} w[j] * z_{t-(L-1)+j}       zeros left of position 0
           out = (C * c) W_out
    full_attention:  q, k, v = n W_q, n W_k, n W_v   (N x hd, N_kv x hd)
           q, k <- RMSNorm over each head's hd values (one weight for q,
           one for k), then RoPE on all hd (theta; rotate-half pairing)
           out = causal_softmax(q k^T / sqrt(hd)) v W_o, N / N_kv queries a kv head
    dense FF:  (silu(x W_1) * x W_3) W_2
    experts:   s = sigmoid(x W_r) (float32, all E);  picks = top_k(s + b)
           w_i = scaling * s_i / (sum_picks s + 1e-6)
           out = sum_{i held} w_i (silu(x G_i) * x U_i) D_i
    logits = RMSNorm(x_L) E^T          E the token embedding (tied head)

The conv operator, the attention, the dense feed-forward and the layer are
held against ``transformers``' ``modeling_lfm2.py`` in the test file; the
routing lines follow ``modeling_lfm2_moe.py`` as recalled and the config's
keys (that file needs ``transformers`` >= 4.58, which is not on this
machine).

Departures from the published model: only the experts this chip holds add
to an expert layer's sum, and the vocabulary is this chip's slice (the
program's share and the reference's alike). One block of each kind is jitted
and called a layer; attention runs ``_QUERY_BLOCK`` queries at a time so
that 2 x 8,192 positions fit beside the trainer's state; the experts are
scanned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 256
_RENORM_EPS = 1e-6


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _conv(x, p, m):
    s, width = x.shape[1], x.shape[2]
    bcu = x @ _f32(p["in_proj"]["kernel"])
    gate_b, gate_c, u = bcu[..., :width], bcu[..., width:2 * width], bcu[..., 2 * width:]
    w = _f32(p["conv"]["kernel"])                       # (L, M); tap L-1 = now
    taps = w.shape[0]
    z = jnp.pad(gate_b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(z[:, j:j + s] * w[j] for j in range(taps))
    return (gate_c * c) @ _f32(p["out_proj"]["kernel"])


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, m):
    b, s, _ = x.shape
    n, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = (x @ _f32(p["query"]["kernel"])).reshape(b, s, n, hd)
    k = (x @ _f32(p["key"]["kernel"])).reshape(b, s, nkv, hd)
    v = (x @ _f32(p["value"]["kernel"])).reshape(b, s, nkv, hd)
    q = _rope(_rms(q, p["q_norm"]["scale"], m["norm_eps"]), m["rope_theta"])
    k = _rope(_rms(k, p["k_norm"]["scale"], m["norm_eps"]), m["rope_theta"])
    k, v = (jnp.repeat(t, n // nkv, axis=2) for t in (k, v))
    qb = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = jnp.einsum("bqnd,bknd->bnqk", qi, k) * hd**-0.5
        seen = keys[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        return jnp.einsum(
            "bnqk,bknd->bqnd", jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1), v
        )

    out = jax.lax.map(block, jnp.arange(s // qb))        # (S/qb, B, qb, N, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, n * hd)
    return out @ _f32(p["out"]["kernel"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def _dense_ff(x, p, m):
    return _swiglu(x, p["gate"]["kernel"], p["up"]["kernel"], p["down"]["kernel"])


def _moe(x, p, m):
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xt @ _f32(p["router"]["kernel"]))         # (T, E)
    _, idx = jax.lax.top_k(scores + _f32(p["bias"]), m["top_k"])
    picked = jnp.take_along_axis(scores, idx, -1)
    w = m["routed_scaling"] * picked / (picked.sum(-1, keepdims=True) + _RENORM_EPS)
    dense_w = jnp.zeros_like(scores).at[jnp.arange(b * s)[:, None], idx].set(w)
    # This chip's experts only: the other columns belong to other chips.
    first, count = m["held_first"], m["held_count"]
    cols = dense_w[:, first:first + count].T                          # (E_held, T)

    def expert(acc, ew):
        gate, up, down, col = ew
        return acc + col[:, None] * _swiglu(xt, gate, up, down), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(xt), (p["gate"], p["up"], p["down"], cols)
    )
    return out.reshape(b, s, d)


def _layer(x, blk, m, operator, routed):
    eps = m["norm_eps"]
    if operator == "conv":
        h = x + _conv(_rms(x, blk["ln_conv"]["scale"], eps), blk["conv"], m)
    else:
        h = x + _attention(_rms(x, blk["ln_attn"]["scale"], eps), blk["attn"], m)
    n = _rms(h, blk["ln_ff"]["scale"], eps)
    return h + (_moe(n, blk["moe"], m) if routed else _dense_ff(n, blk["ff"], m))


def reference_fn(model: dict):
    """``run(params, tokens) -> float32 logits`` for the sizes
    ``families.lfm2_moe.model_dims`` gives."""
    kinds = [
        (op, i >= model["num_dense_layers"])
        for i, op in enumerate(model["layer_types"])
    ]
    layers = {
        kind: jax.jit(lambda x, blk, kind=kind: _layer(x, blk, model, *kind))
        for kind in set(kinds)
    }
    embed = jax.jit(lambda table, tokens: _f32(table)[tokens])
    head = jax.jit(
        lambda x, params: _rms(x, params["ln_out"]["scale"], model["norm_eps"])
        @ _f32(params["tok_embed"]["embedding"]).T
    )

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = embed(params["tok_embed"]["embedding"], tokens)
            for i, kind in enumerate(kinds):
                x = layers[kind](x, params[f"block_{i}"])
            return head(x, params)

    return run
