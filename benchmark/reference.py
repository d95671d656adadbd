"""The plain reference: a GPT-2-style pre-LN decoder in float32 ``jax.numpy``,
and the rules that compare the system with it.

A copy of ``chip_smoke.py``'s ``reference_fn`` and ``teacher_forced`` with
GPT-2's biases added (every projection has one; ``lm_head`` has none). It
reads the same parameter tree as ``models.transformer.Transformer`` and
imports nothing from ``models/`` or ``ops/``. Matmuls run at the highest
precision: on a TPU a float32 matmul is otherwise done in bf16 passes.
Embedding, one block and the head are jitted apart and the block is called
once per layer, so the compiled programs stay small enough to cache.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _dense(x, p):
    y = x @ _f32(p["kernel"])
    return y + _f32(p["bias"]) if "bias" in p else y


def _block(x, blk, heads, head_dim, eps):
    b, s, _ = x.shape
    y = _layer_norm(x, blk["ln_attn"], eps)
    q, k, v = (
        _dense(y, blk["attn"][name]).reshape(b, s, heads, head_dim)
        for name in ("query", "key", "value")
    )
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k) / math.sqrt(head_dim)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknh->bqnh", jax.nn.softmax(scores, -1), v)
    x = x + _dense(out.reshape(b, s, heads * head_dim), blk["attn"]["out"])
    y = _dense(_layer_norm(x, blk["ln_ff"], eps), blk["ff"]["up"])
    y = 0.5 * y * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (y + 0.044715 * y**3)))
    return x + _dense(y, blk["ff"]["down"])


def reference_fn(model: dict):
    """``run(params, tokens) -> float32 logits`` for the sizes in ``model``
    (``num_layers``, ``num_heads``, ``head_dim``, ``norm_eps``)."""
    heads, head_dim = model["num_heads"], model["head_dim"]
    eps, layers = model["norm_eps"], model["num_layers"]
    embed = jax.jit(
        lambda params, tokens: _f32(params["tok_embed"]["embedding"])[tokens]
        + _f32(params["pos_embed"])[None, : tokens.shape[1]]
    )
    block = jax.jit(lambda x, blk: _block(x, blk, heads, head_dim, eps))
    head = jax.jit(
        lambda x, params: _layer_norm(x, params["ln_out"], eps)
        @ _f32(params["lm_head"]["kernel"])
    )

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = embed(params, tokens)
            for i in range(layers):
                x = block(x, params[f"block_{i}"])
            return head(x, params)

    return run


def reference_loss(ref, params, tokens: np.ndarray, rows_per_call: int = 2) -> float:
    """Mean next-token cross-entropy of ``tokens`` ``(B, S + 1)`` under the
    reference, a few rows per call so that the float32 scores fit."""
    total, count = 0.0, 0
    for i in range(0, tokens.shape[0], rows_per_call):
        rows = tokens[i : i + rows_per_call]
        logits = ref(params, jnp.asarray(rows[:, :-1]))
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.asarray(rows[:, 1:])[..., None], -1)
        total += float(-picked.sum())
        count += picked.size
    return total / count


def teacher_forced(ref, params, prompts, streams, margin_tol: float, width: int) -> dict:
    """Every generated token must be the reference's argmax at its position
    given the stream's own earlier tokens, or the reference must prefer its
    argmax over that token by less than ``margin_tol`` logits (rounding in
    the serving type can flip such a pick). Streams are padded to ``width``
    (one compiled shape; causal attention keeps padding from reaching
    back). Returns counts and the confident departures; never raises on a
    departure, so the caller can still print its line."""
    padded = np.zeros((len(streams), width), np.int32)
    for i, s in enumerate(streams):
        padded[i, : len(s)] = s
    logits = np.asarray(ref(params, jnp.asarray(padded)))
    out = {
        "positions": 0, "excused_low_margin": 0, "largest_gap": 0.0,
        "margin_tolerance": margin_tol, "confident_departures": [],
        "finite": bool(np.all(np.isfinite(logits))),
    }
    for i, (p, s) in enumerate(zip(prompts, streams)):
        for t in range(len(p), len(s)):
            row = logits[i, t - 1]
            out["positions"] += 1
            if int(np.argmax(row)) == int(s[t]):
                continue
            gap = float(np.max(row) - row[s[t]])
            out["largest_gap"] = max(out["largest_gap"], gap)
            if gap >= margin_tol:
                out["confident_departures"].append(
                    {"stream": i, "position": t, "gap": gap}
                )
            else:
                out["excused_low_margin"] += 1
    out["ok"] = out["finite"] and not out["confident_departures"]
    return out
