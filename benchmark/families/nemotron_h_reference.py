"""The plain reference of the ``nemotron_h`` family (NVIDIA-Nemotron-3-Super):
float32 ``jax.numpy``, matmuls at the highest precision. The Mamba-2
recurrence runs TOKEN BY TOKEN (``lax.scan`` over positions: no chunked
form, no state carried between calls), the convolution is a plain sum over
its taps, every HELD expert is visited for every token and weighted by a
dense ``(T, E)`` matrix that is zero outside the picks and outside the held
range. No cache, no kernels, nothing sorted, nothing imported from
``models/`` or ``ops/``; it reads the program's parameter tree as
``benchmark/reference.py`` does.

Equations (the model's ``config.json``; one mixer a layer, by the layer's
character of ``hybrid_override_pattern``)::

    x_{l+1} = x_l + Mixer_l(RMSNorm(x_l));  final RMSNorm, untied head
    M: [z | xBC | dt] = x W_in;  xBC = silu(conv1d_causal_depthwise(xBC) + b)
       [u | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
       h_t = exp(dt_t A) h_{t-1} + dt_t u_t (x) B_t;  y_t = h_t C_t + D u_t
       out = (GroupRMSNorm(y * silu(z)) * w) W_out
    *: q, k, v = x W_q, x W_k, x W_v; causal softmax at head_dim^-0.5, GQA,
       W_o; NO positional encoding
    E: s = sigmoid(x W_r) (float32, all E experts); picks = top_k(s + b)
       w_i = scaling * s_i / (sum_picks s + 1e-20);  c = x W_down_latent
       out = (sum_{i held} w_i relu(c W_up_i)^2 W_down_i) W_up_latent
             + relu(x W_su)^2 W_sd

Departures from the published model: the multi-token-prediction module is
not built; only the experts this chip holds add to an expert layer's sum
(the program's and the reference's share alike). It has to fit beside
9.3 GB of live bf16 weights: one block of each kind is jitted and called a
layer, the experts are scanned (one expert's float32 copy at a time),
attention runs in query blocks, and the logits come back as a HOST array
built a block of positions at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 128
_LOGIT_BLOCK = 128


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _mamba(x, p, m):
    b, s, _ = x.shape
    h, pd, grp, n = m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"]
    d_inner, k = h * pd, m["conv_kernel"]
    conv_dim = d_inner + 2 * grp * n
    zxbcdt = x @ _f32(p["in_proj"]["kernel"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim:]
    w = _f32(p["conv"]["kernel"])                       # (K, C); tap K-1 = now
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, tap:tap + s] * w[tap] for tap in range(k))
    xbc = jax.nn.silu(conv + _f32(p["conv"]["bias"]))
    u = xbc[..., :d_inner].reshape(b, s, h, pd)
    # A head reads the B and C of its group (heads h // (H / G)).
    bm = jnp.repeat(
        xbc[..., d_inner:d_inner + grp * n].reshape(b, s, grp, n), h // grp, axis=2
    )
    cm = jnp.repeat(
        xbc[..., d_inner + grp * n:].reshape(b, s, grp, n), h // grp, axis=2
    )
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))       # (B, S, H)
    a = -jnp.exp(_f32(p["A_log"]))

    # float32, the model's rule; the check's lower-precision reading keeps
    # it in bf16 (``ssm_state_dtype``) to show that the tolerance bites
    # (reduce_precision: a compiler may elide a cast down and up again).
    kept = jnp.finfo(m.get("ssm_state_dtype", jnp.float32))

    def step(state, xs):
        u_t, b_t, c_t, dt_t = xs                        # (B,H,P) (B,H,N) (B,H,N) (B,H)
        state = (
            state * jnp.exp(dt_t * a)[..., None, None]
            + (dt_t[..., None] * u_t)[..., None] * b_t[:, :, None, :]
        )
        state = jax.lax.reduce_precision(state, kept.nexp, kept.nmant)
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    _, y = jax.lax.scan(
        step, jnp.zeros((b, h, pd, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (u, bm, cm, dt)),
    )
    y = jnp.moveaxis(y, 0, 1) + _f32(p["D"])[:, None] * u
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)
    yg = y.reshape(b, s, grp, d_inner // grp)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + m["norm_eps"])
    y = yg.reshape(b, s, d_inner) * _f32(p["norm"]["scale"])
    return y @ _f32(p["out_proj"]["kernel"])


def _attention(x, p, m):
    """Causal softmax attention without positions, ``_QUERY_BLOCK`` queries
    at a time; K and V heads repeated over their query group."""
    b, s, _ = x.shape
    n, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = (x @ _f32(p["query"]["kernel"])).reshape(b, s, n, hd)
    k = jnp.repeat((x @ _f32(p["key"]["kernel"])).reshape(b, s, nkv, hd), n // nkv, 2)
    v = jnp.repeat((x @ _f32(p["value"]["kernel"])).reshape(b, s, nkv, hd), n // nkv, 2)
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


def _relu2(x, p):
    return jnp.square(jax.nn.relu(x @ _f32(p["up"]["kernel"]))) @ _f32(p["down"]["kernel"])


def _moe(x, p, m):
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xt @ _f32(p["router"]["kernel"]))        # (T, E)
    _, idx = jax.lax.top_k(scores + _f32(p["bias"]), m["top_k"])
    picked = jnp.take_along_axis(scores, idx, -1)
    w = m["routed_scaling"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    dense_w = jnp.zeros_like(scores).at[jnp.arange(b * s)[:, None], idx].set(w)
    # This chip's experts only: the other columns belong to other chips.
    first, count = m["held_first"], m["held_count"]
    cols = dense_w[:, first:first + count].T                         # (E_held, T)
    c = xt @ _f32(p["latent_down"]["kernel"])

    def expert(acc, ew):
        up, down, col = ew
        y = jnp.square(jax.nn.relu(c @ _f32(up))) @ _f32(down)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(c), (p["up"], p["down"], cols))
    out = routed @ _f32(p["latent_up"]["kernel"]) + _relu2(xt, p["shared"])
    return out.reshape(b, s, d)


_MIXERS = {"M": ("ssm", _mamba), "*": ("attn", _attention), "E": ("moe", _moe)}


def reference_fn(model: dict):
    """``run(params, tokens) -> float32 logits`` as a host array, for the
    sizes ``families.nemotron_h.model_dims`` gives."""
    eps = model["norm_eps"]

    def block(kind):
        name, mixer = _MIXERS[kind]
        return jax.jit(
            lambda x, blk: x + mixer(_rms(x, blk["ln"]["scale"], eps), blk[name], model)
        )

    blocks = {kind: block(kind) for kind in set(model["pattern"])}
    embed = jax.jit(lambda table, tokens: _f32(table[tokens]))
    head = jax.jit(
        lambda x, params: _rms(x, params["ln_out"]["scale"], eps)
        @ _f32(params["lm_head"]["kernel"])
    )

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = embed(params["tok_embed"]["embedding"], tokens)
            for i, kind in enumerate(model["pattern"]):
                x = blocks[kind](x, params[f"block_{i}"])
            b, s = tokens.shape
            step = _LOGIT_BLOCK if s % _LOGIT_BLOCK == 0 else s
            out = np.empty((b, s, model["vocab_size"]), np.float32)
            for lo in range(0, s, step):
                out[:, lo : lo + step] = np.asarray(head(x[:, lo : lo + step], params))
            return out

    return run
