"""Speculative decoding: a small draft model proposes, the target verifies.

Autoregressive decoding is latency-bound by one serialized target forward per
token. Speculative decoding breaks the serialization: a cheap draft model
greedily proposes ``num_draft`` tokens one-by-one, then the target scores the
whole proposal in ONE chunked forward (the same cache path that serves
prefill, `models/attention.py::_cached_attention` — chunk attention against
the KV cache at the current index). The longest prefix of draft tokens that
matches the target's own greedy choices is accepted, plus one bonus token
from the target's logits — so each round costs one target forward and yields
1..num_draft+1 tokens, and the output is EXACTLY what plain greedy decoding
of the target would produce (the oracle the tests pin).

Nothing like this exists in the reference (no inference path at all,
SURVEY.md §5); it composes the framework's own pieces:

* chunked verification reuses the cache-at-index attention;
* acceptance rollback is just rewinding each block's ``cache_index`` —
  stale K/V entries beyond the index are never attended (the causal mask is
  ``position < index + i``) and are overwritten by the next chunk write;
* batch handling (rectangular path) takes the MINIMUM acceptance across
  rows each round: rows that matched further ahead re-derive the same
  tokens in later rounds (the bonus token equals their next draft match),
  so exactness is preserved and only the speedup varies with batch
  agreement;
* the RAGGED path (``ragged=True``) upgrades acceptance to PER-ROW: each
  row keeps its own accepted count and its own cache rewind (the per-row
  ``cache_index`` the ragged serving machinery already provides), so one
  slow row no longer rolls back the whole batch — mixed-length prompt
  batches decode with per-row speeds, and rows that hit their budget
  freeze (``chunk_lengths`` 0) while the rest keep speculating;
* everything runs under mesh + rules — draft and target can use different
  shardings of the same mesh.

Two verification modes: greedy (``temperature == 0``, acceptance is a hard
token equality, output bit-identical to plain greedy) and **rejection
sampling** (``temperature > 0``, Leviathan-style: accept x with probability
``min(1, p(x)/q(x))``, correct rejections from ``norm(max(p − q, 0))``) —
the sampled output is distributed exactly as sampling the target alone,
with position-keyed randomness keeping the batch-min rollback exact.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from learning_jax_sharding_tpu.models.decoding import (
    check_sequence_budget,
    derive_decode_config,
    make_cached_apply,
    make_param_caster,
)
from learning_jax_sharding_tpu.models.transformer import Transformer, TransformerConfig
from learning_jax_sharding_tpu.parallel.logical import Rules, activate


def _rollback(cache: Any, index: jax.Array) -> Any:
    """Rewind the decode position counters to ``index``: every attention
    block's ``cache_index`` AND the transformer's top-level ``position``
    (which drives positional embeddings). Stale K/V beyond the index are
    masked out by the causal-at-index attention and later overwritten."""

    def leaf(path, x):
        if getattr(path[-1], "key", None) in ("cache_index", "position"):
            # Scalar index (rectangular) or per-row (B,) vector (ragged) —
            # broadcast either onto the counter's own shape.
            return jnp.broadcast_to(jnp.asarray(index, x.dtype), x.shape)
        return x

    return jax.tree_util.tree_map_with_path(leaf, cache)


def emit_vector(drafts: jax.Array, m: jax.Array, final: jax.Array) -> jax.Array:
    """``(B, num_draft + 1)`` emission rows: row b's accepted drafts below
    slot ``m_b``, its ``final`` token (greedy bonus / sampled residual)
    from slot ``m_b`` on (repeated past it — junk the caller masks or
    overwrites). ONE copy of the emission-vector rule for the greedy and
    sampling verifiers."""
    padded = jnp.pad(drafts, ((0, 0), (0, 1)))
    idx = jnp.arange(drafts.shape[1] + 1)
    return jnp.where(idx[None, :] < m[:, None], padded, final[:, None])


def greedy_accept_emit(
    drafts: jax.Array, choices: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """PER-ROW greedy acceptance over a verified chunk — THE shared core of
    ragged speculative decoding (used by both :func:`generate_ragged` here
    and the engine's speculative decode block, ``models/serving.py``, so
    the acceptance rule cannot drift between them).

    ``drafts`` is ``(B, num_draft)`` proposals; ``choices`` is
    ``(B, num_draft + 1)`` target greedy picks after each chunk position.
    Returns ``(m, emitted, bonus)``: ``m[b]`` = the longest prefix where
    row b's drafts match the target's own picks; ``emitted`` ``(B,
    num_draft+1)`` = the accepted drafts followed by the bonus/correction
    token (repeated past slot ``m`` — junk the caller masks or
    overwrites); ``bonus[b] = choices[b, m_b]``."""
    eq = drafts == choices[:, :-1]
    m = jnp.sum(jnp.cumprod(eq.astype(jnp.int32), axis=1), axis=1)
    bonus = jnp.take_along_axis(choices, m[:, None], axis=1)[:, 0]
    return m, emit_vector(drafts, m, bonus), bonus


def _greedy(logits: jax.Array, vocab_limit: int | None = None) -> jax.Array:
    if vocab_limit is not None:
        from learning_jax_sharding_tpu.models.generate import vocab_limit_filter

        logits = vocab_limit_filter(logits.astype(jnp.float32), vocab_limit)
    return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)


def _pos_key(rng: jax.Array, pos: jax.Array, tag: int) -> jax.Array:
    """Randomness keyed by ABSOLUTE generated position (+ a role tag:
    0 = draft proposal, 1 = acceptance uniform, 2 = residual/bonus sample).

    Position-keyed keys are what make batch-min rollback exact under
    sampling: a row that accepted further than the batch minimum re-derives
    the SAME draft proposals and acceptance draws for the rolled-back
    positions next round, so its tokens cannot drift."""
    return jax.random.fold_in(jax.random.fold_in(rng, pos), tag)


def make_speculative_generate_fn(
    target_config: TransformerConfig,
    draft_config: TransformerConfig,
    mesh: Mesh,
    rules: Rules,
    *,
    max_new_tokens: int,
    num_draft: int = 4,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    min_p: float | None = None,
    vocab_limit: int | None = None,
    inference_dtype: Any | None = None,
    ragged: bool = False,
):
    """Build ``generate(target_params, draft_params, prompt[, rng]) -> tokens``.

    ``target_config``/``draft_config`` are TRAINING configs sharing a vocab;
    decode variants are derived here (as in ``make_generate_fn``).

    ``temperature == 0`` (default): greedy verification — the output is
    bit-identical to greedy decoding of the target alone; the draft only
    changes how many serialized target passes it takes to get there.

    ``temperature > 0``: **speculative sampling** (Leviathan-style rejection):
    the draft SAMPLES proposals from its own filtered distribution q, the
    target computes its filtered distribution p in one chunked pass, each
    proposal x is accepted with probability ``min(1, p(x)/q(x))``, and the
    first rejection is replaced by a sample from ``norm(max(p - q, 0))``
    (full acceptance earns a bonus sample from p). The emitted tokens are
    distributed EXACTLY as sampling the target alone — the property
    ``tests/test_speculative.py`` pins distributionally. ``top_k``/``top_p``/
    ``min_p`` shape both p and q the same way, so exactness holds for the
    filtered distribution (what plain ``make_generate_fn`` samples too).
    ``repetition_penalty`` is NOT supported here: it conditions the
    distribution on the growing output, which would invalidate the draft's
    q at every accepted token — use plain ``make_generate_fn`` for it.

    ``ragged``: mixed-length prompt batches with PER-ROW acceptance. The
    returned function takes ``lengths`` (``(B,)`` int32; the prompt arrives
    right-padded) and every row keeps its OWN accepted count and cache
    rewind each round — one slow row no longer rolls back the whole batch
    (the rectangular path's batch-min). Greedy output is bit-identical to
    ``make_generate_fn(ragged=True)``'s per-row greedy decode; sampling
    keys every draw by (row, absolute position), so a row's rolled-back
    positions re-derive identical draws AND a row's output stream is
    independent of the other rows' prompts. Output rows follow the ragged
    ``make_generate_fn`` convention: ``[prompt_b, generated..., 0-fill]``
    with the generated span starting at ``lengths[b]``. The jitted function
    additionally returns per-row stats ``{"accepted", "rounds",
    "emitted"}`` (total accepted draft tokens, verify rounds, tokens
    emitted per row — emitted can exceed ``max_new_tokens`` by up to
    ``num_draft``; the output slice keeps exactly ``max_new_tokens``);
    ``run(..., return_stats=True)`` surfaces them.
    """
    if target_config.vocab_size != draft_config.vocab_size:
        raise ValueError(
            f"target vocab {target_config.vocab_size} != draft vocab "
            f"{draft_config.vocab_size}"
        )
    if num_draft < 1:
        raise ValueError(f"num_draft must be >= 1, got {num_draft}")
    for name, c in (("target", target_config), ("draft", draft_config)):
        if "M" in (c.layer_pattern or ""):
            raise ValueError(
                f"the {name} config has state-space layers: a rejected draft "
                "rewinds cache_index, and a recurrent state has no rollback"
            )

    t_cfg = derive_decode_config(target_config, inference_dtype, mesh=mesh, rules=rules)
    d_cfg = derive_decode_config(draft_config, inference_dtype, mesh=mesh, rules=rules)
    if ragged:
        import dataclasses as _dc

        t_cfg = _dc.replace(t_cfg, decode_ragged=True)
        d_cfg = _dc.replace(d_cfg, decode_ragged=True)
    target, draft = Transformer(t_cfg), Transformer(d_cfg)
    t_apply, d_apply = make_cached_apply(target), make_cached_apply(draft)
    maybe_cast = make_param_caster(inference_dtype)

    def generate(t_params, d_params, prompt):
        b, prompt_len = prompt.shape
        # Verification writes up to num_draft+1 positions past the accepted
        # prefix before rolling back, so leave that much headroom.
        need = prompt_len + max_new_tokens + num_draft + 1
        for name, cfg in (("target", t_cfg), ("draft", d_cfg)):
            check_sequence_budget(
                need, cfg.max_seq_len, f"prompt+new+draft for {name}"
            )

        # Prefill both models on the prompt. The first new token comes from
        # the target's last-position logits — exactly as plain greedy.
        t_logits, t_cache = t_apply(t_params, None, prompt)
        _, d_cache = d_apply(d_params, None, prompt)
        t_cur = _greedy(t_logits[:, -1], vocab_limit)

        buf_len = max_new_tokens + num_draft + 1
        buffer = jnp.zeros((b, buf_len), jnp.int32)
        buffer = lax.dynamic_update_slice(buffer, t_cur[:, None], (0, 0))

        def cond(carry):
            n, *_ = carry
            return n < max_new_tokens

        def body(carry):
            n, t_cur, t_cache, d_cache, buffer = carry
            # Invariant: both caches hold prompt + the n-1 accepted tokens
            # BEFORE t_cur (t_cur itself is pending, fed by this round).
            base = prompt_len + n - 1

            # 1. Draft proposes num_draft tokens greedily, one at a time;
            #    one extra feed pushes the last proposal's K/V into the draft
            #    cache so a full acceptance leaves the cache complete.
            def draft_step(carry, _):
                prev, cache = carry
                logits, cache = d_apply(d_params, cache, prev[:, None])
                nxt = _greedy(logits[:, -1], vocab_limit)
                return (nxt, cache), nxt

            (last_d, d_cache), drafts = lax.scan(
                draft_step, (t_cur, d_cache), None, length=num_draft
            )
            drafts = drafts.T  # (num_draft, B) scan stack → (B, num_draft)
            _, d_cache = d_apply(d_params, d_cache, last_d[:, None])

            # 2. Target verifies the whole proposal in one chunked forward:
            #    [t_cur, d_1..d_num_draft] → greedy choice after each.
            chunk = jnp.concatenate([t_cur[:, None], drafts], axis=1)
            t_logits, t_cache = t_apply(t_params, t_cache, chunk)
            choices = _greedy(t_logits, vocab_limit)  # (B, num_draft+1)

            # 3. Accept the longest prefix where draft == target choice;
            #    batch-min keeps a single scalar cache index.
            eq = drafts == choices[:, :-1]  # (B, num_draft)
            m_row = jnp.sum(jnp.cumprod(eq.astype(jnp.int32), axis=1), axis=1)
            m = jnp.min(m_row)  # scalar: accepted draft count this round

            # 4. Emit d_1..d_m then the bonus/correction token choices[:, m].
            #    Positions past m hold the bonus too — junk that later rounds
            #    overwrite (and the final slice drops).
            idx = jnp.arange(num_draft + 1)
            bonus = jnp.take_along_axis(choices, jnp.full((b, 1), m), axis=1)[:, 0]
            padded = jnp.pad(drafts, ((0, 0), (0, 1)))  # (B, num_draft+1)
            emitted = jnp.where(idx[None, :] < m, padded, bonus[:, None])
            # buffer[i] is the (i+1)-th generated token; t_cur sits at n-1,
            # so this round's tokens start at n.
            buffer = lax.dynamic_update_slice(buffer, emitted, (0, n))

            # 5. Roll both caches back to the accepted length. The target
            #    consumed base..base+num_draft; valid prefix is base + 1 + m
            #    (t_cur and the m accepted drafts). Same for the draft.
            accepted = base + 1 + m
            t_cache = _rollback(t_cache, accepted)
            d_cache = _rollback(d_cache, accepted)

            return (n + 1 + m, bonus, t_cache, d_cache, buffer)

        n, _, _, _, buffer = lax.while_loop(
            cond, body, (jnp.asarray(1, jnp.int32), t_cur, t_cache, d_cache, buffer)
        )
        return jnp.concatenate([prompt, buffer[:, :max_new_tokens]], axis=1)

    def to_flogits(logits):
        """The filtered sampling distribution in logit space —
        ``generate.filtered_logits`` is THE definition of the filter order,
        shared with plain sampling so the two distributions cannot drift
        apart. Sampling draws straight from these (as plain ``_sample``
        does); acceptance ratios softmax them into probabilities."""
        from learning_jax_sharding_tpu.models.generate import filtered_logits

        return filtered_logits(
            logits, temperature, top_k, top_p, min_p, vocab_limit
        )

    def to_probs(logits):
        return jax.nn.softmax(to_flogits(logits), axis=-1)

    def generate_sampled(t_params, d_params, prompt, rng):
        b, prompt_len = prompt.shape
        need = prompt_len + max_new_tokens + num_draft + 1
        for name, cfg in (("target", t_cfg), ("draft", d_cfg)):
            check_sequence_budget(
                need, cfg.max_seq_len, f"prompt+new+draft for {name}"
            )

        t_logits, t_cache = t_apply(t_params, None, prompt)
        _, d_cache = d_apply(d_params, None, prompt)
        # Generated position 0 comes straight from the target's prefill
        # distribution (tag 2 = "the final sample of its position").
        t_cur = jax.random.categorical(
            _pos_key(rng, jnp.asarray(0), 2), to_flogits(t_logits[:, -1])
        ).astype(jnp.int32)

        buf_len = max_new_tokens + num_draft + 1
        buffer = jnp.zeros((b, buf_len), jnp.int32)
        buffer = lax.dynamic_update_slice(buffer, t_cur[:, None], (0, 0))

        def cond(carry):
            n, *_ = carry
            return n < max_new_tokens

        def body(carry):
            n, t_cur, t_cache, d_cache, buffer = carry
            base = prompt_len + n - 1  # same cache invariant as greedy

            # 1. Draft SAMPLES num_draft proposals, keeping its full filtered
            #    distribution per position (the residual needs p - q).
            def draft_step(carry, pos):
                prev, cache = carry
                logits, cache = d_apply(d_params, cache, prev[:, None])
                fl = to_flogits(logits[:, -1])
                tok = jax.random.categorical(
                    _pos_key(rng, pos, 0), fl
                ).astype(jnp.int32)
                return (tok, cache), (tok, jax.nn.softmax(fl, axis=-1))

            (last_d, d_cache), (drafts, q_all) = lax.scan(
                draft_step, (t_cur, d_cache), n + jnp.arange(num_draft)
            )
            drafts = drafts.T                      # (B, num_draft)
            q_all = jnp.moveaxis(q_all, 0, 1)      # (B, num_draft, V)
            _, d_cache = d_apply(d_params, d_cache, last_d[:, None])

            # 2. Target distribution at every proposal position + bonus slot.
            chunk = jnp.concatenate([t_cur[:, None], drafts], axis=1)
            t_logits, t_cache = t_apply(t_params, t_cache, chunk)
            p_all = to_probs(t_logits)             # (B, num_draft+1, V)

            # 3. Accept x_j with prob min(1, p(x_j)/q(x_j)); keep the longest
            #    accepted prefix, batch-min for a single scalar cache index.
            p_at = jnp.take_along_axis(
                p_all[:, :num_draft], drafts[..., None], axis=-1
            )[..., 0]
            q_at = jnp.take_along_axis(q_all, drafts[..., None], axis=-1)[..., 0]
            u = jax.vmap(
                lambda pos: jax.random.uniform(_pos_key(rng, pos, 1), (b,)),
                out_axes=1,
            )(n + jnp.arange(num_draft))           # (B, num_draft)
            # Strict <: with u ∈ [0,1), p==q still always accepts (u·q < q),
            # while p==0 (draft token outside the target's filtered support)
            # never does — <= would leak such tokens on exact u==0.0 draws.
            accept = u * q_at < p_at               # u < p/q without the div
            a_row = jnp.sum(
                jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
            )
            m = jnp.min(a_row)                     # scalar accepted count

            # 4. The token at slot m: rows that accepted past m emit their
            #    draft token; rows that rejected AT m sample the residual
            #    norm(max(p - q, 0)). Padding q with zeros makes the
            #    full-acceptance bonus (sample from p, no q to subtract) the
            #    same code path.
            q_pad = jnp.concatenate(
                [q_all, jnp.zeros_like(q_all[:, :1])], axis=1
            )
            def take_m(x):  # x[:, m] with a traced m
                return jnp.take_along_axis(x, jnp.full((b, 1, 1), m), axis=1)[:, 0]

            p_m = take_m(p_all)                    # (B, V)
            q_m = take_m(q_pad)
            residual = jnp.maximum(p_m - q_m, 0.0)
            mass = jnp.sum(residual, axis=-1, keepdims=True)
            residual = jnp.where(mass > 0, residual / mass, p_m)
            res_tok = jax.random.categorical(
                _pos_key(rng, n + m, 2), jnp.log(residual)
            ).astype(jnp.int32)
            drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
            draft_m = jnp.take_along_axis(
                drafts_pad, jnp.full((b, 1), m), axis=1
            )[:, 0]
            token_m = jnp.where(a_row > m, draft_m, res_tok)

            # 5. Emit accepted drafts then token_m; junk past it is
            #    overwritten by later rounds (and the final slice drops it).
            idx = jnp.arange(num_draft + 1)
            emitted = jnp.where(
                idx[None, :] < m, drafts_pad, token_m[:, None]
            )
            buffer = lax.dynamic_update_slice(buffer, emitted, (0, n))

            accepted = base + 1 + m
            t_cache = _rollback(t_cache, accepted)
            d_cache = _rollback(d_cache, accepted)
            return (n + 1 + m, token_m, t_cache, d_cache, buffer)

        n, _, _, _, buffer = lax.while_loop(
            cond, body, (jnp.asarray(1, jnp.int32), t_cur, t_cache, d_cache, buffer)
        )
        return jnp.concatenate([prompt, buffer[:, :max_new_tokens]], axis=1)

    def _check_ragged_budget(prompt_len: int) -> None:
        need = prompt_len + max_new_tokens + num_draft + 1
        for name, cfg in (("target", t_cfg), ("draft", d_cfg)):
            check_sequence_budget(
                need, cfg.max_seq_len, f"prompt+new+draft for {name}"
            )

    def _assemble_ragged(prompt, lengths, buffer):
        # Row b's generated span starts at ITS length (the ragged
        # make_generate_fn convention); everything past it — including the
        # caller's prompt padding — becomes 0-fill.
        b, prompt_len = prompt.shape
        total = prompt_len + max_new_tokens
        col = jnp.arange(total)[None, :]
        out = jnp.where(
            col < lengths[:, None],
            jnp.pad(prompt, ((0, 0), (0, max_new_tokens))),
            0,
        )
        rows = jnp.arange(b)[:, None]
        cols = lengths[:, None] + jnp.arange(max_new_tokens)[None, :]
        return out.at[rows, cols].set(buffer[:, :max_new_tokens])

    def generate_ragged(t_params, d_params, prompt, lengths):
        """Per-row greedy speculative decode over the ragged cache.

        The invariant, per ROW: before a round, the caches hold the row's
        prompt plus its ``n_b - 1`` accepted tokens (``cache_index`` =
        ``lengths_b + n_b - 1``); ``t_cur_b`` is pending. After acceptance
        of ``m_b`` drafts the rewind target is ``lengths_b + n_b + m_b`` =
        ``lengths_b + n_new_b - 1`` — which for a FROZEN row (``n_b`` at
        budget, ``chunk_lengths`` 0 all round) equals its current index, so
        one broadcast rollback serves live and frozen rows alike."""
        from learning_jax_sharding_tpu.models.attention import row_update_masked

        b, prompt_len = prompt.shape
        _check_ragged_budget(prompt_len)

        t_logits_all, t_cache = t_apply(t_params, None, prompt, lengths)
        _, d_cache = d_apply(d_params, None, prompt, lengths)
        t_cur = _greedy(
            jnp.take_along_axis(
                t_logits_all, (lengths - 1)[:, None, None], axis=1
            )[:, 0],
            vocab_limit,
        )

        buf_len = max_new_tokens + num_draft + 1
        buffer = jnp.zeros((b, buf_len), jnp.int32).at[:, 0].set(t_cur)
        n = jnp.ones((b,), jnp.int32)
        acc = jnp.zeros((b,), jnp.int32)
        rounds = jnp.asarray(0, jnp.int32)

        def cond(carry):
            n, *_ = carry
            return jnp.any(n < max_new_tokens)

        def body(carry):
            n, t_cur, t_cache, d_cache, buffer, acc, rounds = carry
            live = n < max_new_tokens
            live32 = live.astype(jnp.int32)

            # 1. Draft proposes per row; frozen rows ride with length 0
            #    (no cache advance, no write disturbance).
            def draft_step(carry, _):
                prev, cache = carry
                logits, cache = d_apply(d_params, cache, prev[:, None], live32)
                nxt = jnp.where(live, _greedy(logits[:, -1], vocab_limit), prev)
                return (nxt, cache), nxt

            (last_d, d_cache), drafts = lax.scan(
                draft_step, (t_cur, d_cache), None, length=num_draft
            )
            drafts = drafts.T
            _, d_cache = d_apply(d_params, d_cache, last_d[:, None], live32)

            # 2. One chunked target verify; per-row valid chunk lengths.
            chunk = jnp.concatenate([t_cur[:, None], drafts], axis=1)
            t_logits, t_cache = t_apply(
                t_params, t_cache, chunk, live32 * (num_draft + 1)
            )
            choices = _greedy(t_logits, vocab_limit)

            # 3+4. PER-ROW acceptance (no batch-min), then emit each row's
            #      accepted drafts + its bonus at its own buffer offset;
            #      frozen rows write nothing.
            m, emitted, bonus = greedy_accept_emit(drafts, choices)
            buffer = row_update_masked(
                buffer, emitted, n, live32 * (num_draft + 1), seq_dim=1
            )

            # 5. Per-row rollback; frozen rows' target equals their index.
            n_new = n + live32 * (1 + m)
            roll = lengths + n_new - 1
            t_cache = _rollback(t_cache, roll)
            d_cache = _rollback(d_cache, roll)
            t_cur = jnp.where(live, bonus, t_cur)
            return (
                n_new, t_cur, t_cache, d_cache, buffer,
                acc + live32 * m, rounds + 1,
            )

        n, _, _, _, buffer, acc, rounds = lax.while_loop(
            cond, body, (n, t_cur, t_cache, d_cache, buffer, acc, rounds)
        )
        stats = {"accepted": acc, "rounds": rounds, "emitted": n}
        return _assemble_ragged(prompt, lengths, buffer), stats

    def _row_keys(rng, pos, tag: int):
        """(B,) keys from per-row (row index, absolute position, tag) — the
        ragged analogue of :func:`_pos_key`. Row-indexed keys make each
        row's stream independent of the rest of the batch; position-keying
        keeps per-row rollback exact (a rewound position re-derives its
        draw)."""
        b = pos.shape[0]

        def one(r, p):
            return jax.random.fold_in(
                jax.random.fold_in(jax.random.fold_in(rng, r), p), tag
            )

        return jax.vmap(one)(jnp.arange(b), pos)

    def generate_ragged_sampled(t_params, d_params, prompt, lengths, rng):
        """Per-row speculative SAMPLING (Leviathan rejection) — acceptance,
        residual draws, and rollback all per row, randomness keyed by
        (row, position) so rewinds re-derive their draws exactly."""
        from learning_jax_sharding_tpu.models.attention import row_update_masked

        b, prompt_len = prompt.shape
        _check_ragged_budget(prompt_len)

        t_logits_all, t_cache = t_apply(t_params, None, prompt, lengths)
        _, d_cache = d_apply(d_params, None, prompt, lengths)
        first_fl = to_flogits(
            jnp.take_along_axis(
                t_logits_all, (lengths - 1)[:, None, None], axis=1
            )[:, 0]
        )
        t_cur = jax.vmap(jax.random.categorical)(
            _row_keys(rng, jnp.zeros((b,), jnp.int32), 2), first_fl
        ).astype(jnp.int32)

        buf_len = max_new_tokens + num_draft + 1
        buffer = jnp.zeros((b, buf_len), jnp.int32).at[:, 0].set(t_cur)
        n = jnp.ones((b,), jnp.int32)
        acc = jnp.zeros((b,), jnp.int32)
        rounds = jnp.asarray(0, jnp.int32)

        def cond(carry):
            n, *_ = carry
            return jnp.any(n < max_new_tokens)

        def body(carry):
            n, t_cur, t_cache, d_cache, buffer, acc, rounds = carry
            live = n < max_new_tokens
            live32 = live.astype(jnp.int32)

            # 1. Draft SAMPLES per row at its own positions n_b + j.
            def draft_step(carry, j):
                prev, cache = carry
                logits, cache = d_apply(d_params, cache, prev[:, None], live32)
                fl = to_flogits(logits[:, -1])
                tok = jax.vmap(jax.random.categorical)(
                    _row_keys(rng, n + j, 0), fl
                ).astype(jnp.int32)
                tok = jnp.where(live, tok, prev)
                return (tok, cache), (tok, jax.nn.softmax(fl, axis=-1))

            (last_d, d_cache), (drafts, q_all) = lax.scan(
                draft_step, (t_cur, d_cache), jnp.arange(num_draft)
            )
            drafts = drafts.T                      # (B, num_draft)
            q_all = jnp.moveaxis(q_all, 0, 1)      # (B, num_draft, V)
            _, d_cache = d_apply(d_params, d_cache, last_d[:, None], live32)

            # 2. Target distribution at every proposal position + bonus.
            chunk = jnp.concatenate([t_cur[:, None], drafts], axis=1)
            t_logits, t_cache = t_apply(
                t_params, t_cache, chunk, live32 * (num_draft + 1)
            )
            p_all = to_probs(t_logits)             # (B, num_draft+1, V)

            # 3. Accept x_j with prob min(1, p/q), per-row prefix length.
            p_at = jnp.take_along_axis(
                p_all[:, :num_draft], drafts[..., None], axis=-1
            )[..., 0]
            q_at = jnp.take_along_axis(q_all, drafts[..., None], axis=-1)[..., 0]
            u = jax.vmap(
                lambda j: jax.vmap(jax.random.uniform)(_row_keys(rng, n + j, 1)),
                out_axes=1,
            )(jnp.arange(num_draft))               # (B, num_draft)
            accept = u * q_at < p_at               # strict <, as rectangular
            m = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

            # 4. Slot-m token per row: draft if the row accepted past m
            #    (never happens per-row — m IS the row's prefix, so slot m
            #    always holds the residual/bonus sample), residual from
            #    norm(max(p - q, 0)); full acceptance makes it the bonus
            #    sample from p (q padded 0).
            q_pad = jnp.concatenate(
                [q_all, jnp.zeros_like(q_all[:, :1])], axis=1
            )

            def take_m(x):
                return jnp.take_along_axis(x, m[:, None, None], axis=1)[:, 0]

            p_m = take_m(p_all)                    # (B, V)
            q_m = take_m(q_pad)
            residual = jnp.maximum(p_m - q_m, 0.0)
            mass = jnp.sum(residual, axis=-1, keepdims=True)
            residual = jnp.where(mass > 0, residual / mass, p_m)
            token_m = jax.vmap(jax.random.categorical)(
                _row_keys(rng, n + m, 2), jnp.log(residual)
            ).astype(jnp.int32)

            # 5. Emit drafts[<m] then token_m at each row's offset.
            emitted = emit_vector(drafts, m, token_m)
            buffer = row_update_masked(
                buffer, emitted, n, live32 * (num_draft + 1), seq_dim=1
            )

            n_new = n + live32 * (1 + m)
            roll = lengths + n_new - 1
            t_cache = _rollback(t_cache, roll)
            d_cache = _rollback(d_cache, roll)
            t_cur = jnp.where(live, token_m, t_cur)
            return (
                n_new, t_cur, t_cache, d_cache, buffer,
                acc + live32 * m, rounds + 1,
            )

        n, _, _, _, buffer, acc, rounds = lax.while_loop(
            cond, body, (n, t_cur, t_cache, d_cache, buffer, acc, rounds)
        )
        stats = {"accepted": acc, "rounds": rounds, "emitted": n}
        return _assemble_ragged(prompt, lengths, buffer), stats

    if ragged:
        jitted = jax.jit(
            generate_ragged if temperature == 0.0 else generate_ragged_sampled
        )
    else:
        jitted = jax.jit(generate if temperature == 0.0 else generate_sampled)

    def run(
        t_params: Any, d_params: Any, prompt: jax.Array,
        rng: Optional[jax.Array] = None,
        lengths: Optional[jax.Array] = None,
        return_stats: bool = False,
    ):
        if ragged and lengths is None:
            raise ValueError(
                "ragged=True: pass lengths (B,) — each row's true prompt "
                "length in the right-padded prompt batch"
            )
        if not ragged and lengths is not None:
            raise ValueError(
                "lengths requires make_speculative_generate_fn(ragged=True)"
            )
        if return_stats and not ragged:
            raise ValueError("return_stats requires ragged=True")
        with activate(mesh, rules):
            args = [maybe_cast(t_params), maybe_cast(d_params), prompt]
            if ragged:
                args.append(jnp.asarray(lengths, jnp.int32))
            if temperature != 0.0:
                args.append(jax.random.key(0) if rng is None else rng)
            else:
                del rng  # greedy: deterministic, kept for signature symmetry
            result = jitted(*args)
            if ragged:
                out, stats = result
                return (out, stats) if return_stats else out
            return result

    run.jitted = jitted
    return run
