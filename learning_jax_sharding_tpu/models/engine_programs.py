"""The step programs a :class:`~.serving.ContinuousEngine` dispatches, as
ONE table built for the engine's mode.

:func:`build_programs` is the entry point: from the cached applies and
the settings the traced bodies close over, it returns ``{family:
Program}`` holding exactly the programs that mode can dispatch. The
engine keeps the table and nothing else about "which programs exist":
``compile_counts``, the relowering reports, the golden contract names,
the ledger's family tags and the compression trip all map over it, so a
change to how programs are built (donation, a second cache kind, a new
family) is made here once.

Nothing here takes an engine or touches host state; every body is what
``jax.jit`` traces.

XLA names a module ``jit_<fn.__name__>``, and the benchmark's trace
metrics match those names (``jit_first_refill``, ``jit_refill_step``,
``jit_decode_block``, ``jit_mixed_step``; the trainer's is ``jit_step``):
every function jitted here keeps the name it has always had, the
speculative and adapter variants of the fused families included
(``tests/test_engine_programs.py`` pins them).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from learning_jax_sharding_tpu.models.attention import row_update_masked
from learning_jax_sharding_tpu.models.generate import filtered_logits
from learning_jax_sharding_tpu.models.speculative import (
    _greedy as greedy_pick,
    _pos_key,
    _rollback,
    emit_vector,
    greedy_accept_emit,
)

#: Cache leaves with a leading PHYSICAL-PAGE dim on paged engines — the
#: leaves ``kv_page_spill``/``kv_page_fill`` move one page of. Per-slot
#: counters (cache_index, position, block_table) stay: a retained prefix
#: page carries K/V only; the mapping is host state.
_PAGE_LEAF_KEYS = ("cached_kv", "key_scale", "value_scale")

#: The per-slot decode counters among the cache leaves: admission sets
#: them (``_reset_rows``), and with a paged engine's ``block_table`` they
#: are what a refill dispatch's chunk rows take from their slots
#: (``_take_rows``).
_SLOT_COUNTER_KEYS = ("cache_index", "position")

#: The second kind of state in the cache tree: a Mamba-2 layer's recurrent
#: state and its convolution's last inputs (``models/ssm.py``), fixed-size
#: and BY SLOT (leading dim = slots, never a page). Admission zeroes them
#: (``_reset_rows``); a refill dispatch's chunk row takes its slot's
#: (``_take_rows``) and the slot keeps what its LAST row leaves
#: (``_put_rows``); an idle or decoding slot's stay as they are.
_SLOT_STATE_KEYS = ("ssm_state", "conv_state")

#: Per chunk row, the row of the SAME dispatch whose final state it starts
#: from (-1: its slot's own): what a recurrent layer needs of ``rows`` and
#: ``offsets``. At rest (decode, a contiguous cache) every entry is -1.
_ROW_CARRY_KEY = "carry_from"


def _is_table(path) -> bool:
    return getattr(path[-1], "key", None) == "block_table"


@functools.cache
def _table_mask(treedef) -> tuple[bool, ...]:
    # Which leaves of a tree of this shape are block tables. By treedef,
    # once: the engine splits its cache in front of every dispatch, and
    # flattening WITH paths is most of what that costs.
    flat, _ = jax.tree_util.tree_flatten_with_path(
        treedef.unflatten(range(treedef.num_leaves))
    )
    return tuple(_is_table(path) for path, _ in flat)


def split_cache(tree: Any) -> tuple[Any, list]:
    """``(tree with None in place of every block_table leaf, those leaves
    in flatten order)``: the two halves a step program's jit boundary
    takes a cache in (:func:`_donating`). ``tree`` is anything that holds
    cache leaves (one cache, a speculative pair, a program's whole output);
    a contiguous cache has no tables and comes back as it is."""
    flat, treedef = jax.tree.flatten(tree)
    mask = _table_mask(treedef)
    if not any(mask):
        return tree, []
    return treedef.unflatten(
        None if table else x for x, table in zip(flat, mask)
    ), [x for x, table in zip(flat, mask) if table]


def merge_cache(tree: Any, tables: list) -> Any:
    """:func:`split_cache`'s inverse: ``tables`` back where the Nones are."""
    if not tables:
        return tree
    it = iter(tables)
    return jax.tree.map(
        lambda x: next(it) if x is None else x, tree,
        is_leaf=lambda x: x is None,
    )


@dataclasses.dataclass
class Program:
    """One row of an engine's program table."""

    #: The name spans (``engine.enqueue.<family>``), ``compile_counts()``
    #: and ``engine.dispatch`` events use.
    family: str
    #: The jitted function; ``fn.__name__`` is the XLA module's name.
    fn: Any
    #: Base name of the golden contract (``analysis/golden/<name>.json``)
    #: before the engine's ``_q8`` / ``spec_`` rules.
    contract: str
    #: Listed by ``compile_counts()`` from construction. The others (the
    #: horizon scan, the handoff and tier programs, a speculative engine's
    #: degraded ``decode_block``) appear once they have dispatched.
    steady: bool = True
    #: Traces the model apply. The ``kv_*`` programs only move cache rows:
    #: a compression trip leaves their executables alone, and their
    #: goldens follow the KV codec, not the collective one.
    applies: bool = True
    #: The most recent dispatch's arguments, as a closure over the
    #: engine's LIVE state (None until the program has dispatched; the
    #: engine clears it when the served params change). Relowering reads
    #: it. Abstract ShapeDtypeStruct capture does not work here: AOT
    #: lowering treats a struct's sharding as a hard constraint, and
    #: host-committed inputs that live dispatch happily transfers then
    #: refuse to lower against the mesh.
    last_args: Callable[[], tuple] | None = None


def _reset_rows(
    cache: Any, mask: jax.Array, values: jax.Array | None = None
) -> Any:
    """Set the per-row decode counters (``cache_index`` and ``position``)
    where ``mask`` is True — request admission. ``values`` (``(B,)``,
    default zeros) is the admission index: 0 for a fresh prompt, or the
    shared-prefix length when prefix caching hands the row pre-filled
    pages. Stale K/V past a reset row's index is masked by causal-at-index
    attention and overwritten as the new request writes (same invariant
    speculative rollback relies on, ``models/speculative.py::_rollback``)."""

    def leaf(path, x):
        key = getattr(path[-1], "key", None)
        if key in _SLOT_COUNTER_KEYS:
            v = (
                jnp.zeros_like(x)
                if values is None
                else jnp.broadcast_to(values.astype(x.dtype), x.shape)
            )
            return jnp.where(mask, v, x)
        if key in _SLOT_STATE_KEYS:
            # A new request starts from a zero recurrent state (an engine
            # with such layers admits at index 0 only: no prefix reuse).
            return jnp.where(mask.reshape((-1,) + (1,) * (x.ndim - 1)), 0, x)
        return x

    return jax.tree_util.tree_map_with_path(leaf, cache)


def _row_chain(rows: jax.Array, offsets: jax.Array):
    """How a dispatch's chunk rows follow one another: ``(carry_from,
    is_last)``. Row ``r``'s predecessor is the row of the same slot with
    the largest smaller offset (-1: none, it starts from the slot's own
    state); ``is_last`` marks the row of each slot no other row follows. A
    row nobody uses is its own slot's only row (``rows[r] = r``, offset 0)."""
    same = rows[:, None] == rows[None, :]
    earlier = same & (offsets[None, :] < offsets[:, None])
    at = jnp.argmax(jnp.where(earlier, offsets[None, :], -1), axis=1)
    carry_from = jnp.where(jnp.any(earlier, axis=1), at, -1).astype(jnp.int32)
    is_last = ~jnp.any(same & (offsets[None, :] > offsets[:, None]), axis=1)
    return carry_from, is_last


def _take_rows(cache: Any, rows: jax.Array, offsets: jax.Array) -> Any:
    """The cache as a refill dispatch's CHUNK ROWS see it: row ``r`` carries
    a chunk of slot ``rows[r]`` that starts ``offsets[r]`` tokens past what
    the slot has consumed, so it takes that slot's ``block_table`` and its
    counters moved on by the offset. Page pools (and ``moe_stats``) are
    shared by all rows and pass through: every layer writes its chunk into
    the pool before it attends through the table, so a row reads in each
    layer what an earlier row of the same slot wrote in that layer. A
    recurrent layer has no pool: each row takes its slot's state
    (``_SLOT_STATE_KEYS``) and the layer is told which row of this dispatch
    it continues (``carry_from``), so that it starts from that row's final
    state in the same layer instead."""
    chain = functools.cache(lambda: _row_chain(rows, offsets))

    def leaf(path, x):
        key = getattr(path[-1], "key", None)
        if key == "block_table" or key in _SLOT_STATE_KEYS:
            return x[rows]
        if key in _SLOT_COUNTER_KEYS:
            return x[rows] + offsets.astype(x.dtype)
        if key == _ROW_CARRY_KEY:
            return chain()[0]
        return x

    return jax.tree_util.tree_map_with_path(leaf, cache)


def _put_rows(
    cache: Any, row_cache: Any, rows: jax.Array, offsets: jax.Array
) -> Any:
    """Fold the chunk rows' cache back into per-SLOT state: a slot's
    counters become the furthest any of its rows reached (a row with no
    tokens reaches where its slot already was), its recurrent state what
    its LAST row left (a slot without a row keeps its own), the tables and
    the resting ``carry_from`` go back as they came, everything else
    (pools, ``moe_stats``) is the call's."""
    # One writer a slot; the other rows aim past the end and are dropped.
    last_rows = functools.cache(
        lambda: jnp.where(_row_chain(rows, offsets)[1], rows, rows.shape[0])
    )

    def leaf(path, old, new):
        key = getattr(path[-1], "key", None)
        if key in ("block_table", _ROW_CARRY_KEY):
            return old
        if key in _SLOT_COUNTER_KEYS:
            return old.at[rows].max(new)
        if key in _SLOT_STATE_KEYS:
            return old.at[last_rows()].set(new, mode="drop")
        return new

    return jax.tree_util.tree_map_with_path(leaf, cache, row_cache)


def _moe_seen(cache):
    """Sum of the expert layers' cumulative ``moe_stats``: ``(3,)`` int32
    (assignments, expert reads, layer-steps)."""
    seen = jnp.zeros((3,), jnp.int32)
    if cache is not None:
        for path, x in jax.tree_util.tree_flatten_with_path(cache)[0]:
            if getattr(path[-1], "key", None) == "moe_stats":
                seen = seen + x
    return seen


def _with_moe(program, cache_arg):
    """A dropless-expert config's split programs return, after their usual
    outputs (the cache last), the growth of ``_moe_seen`` over the call: it
    comes back with the readback the dispatch makes anyway. ``cache_arg``:
    which positional argument is the cache going in (None: the call
    creates it)."""

    @functools.wraps(program)
    def counted(*args):
        before = _moe_seen(None if cache_arg is None else args[cache_arg])
        out = program(*args)
        return (*out, _moe_seen(out[-1]) - before)

    return counted


def _merge_row(p, a):
    """One ROW's adapter folded into the base tree — the EXACT op order of
    ``training.lora.merge_lora`` (scale · A@B, then astype into the kernel
    dtype), with the python-float ``alpha/rank`` scale replaced by the
    pool's per-slot scale array cast to the A@B dtype (same promotion a
    weak-typed scalar takes), so a pooled tenant's merged weights are
    BIT-IDENTICAL to ``merge_lora``'s — the multi-tenant bit-identity
    oracle rests on this mirror."""
    if not isinstance(p, dict):
        return p
    out = {}
    for k, v in p.items():
        sub = a.get(k) if isinstance(a, dict) else None
        if (
            sub is not None and isinstance(sub, dict)
            and set(sub) == {"lora_a", "lora_b", "scale"}
        ):
            ab = sub["lora_a"] @ sub["lora_b"]
            out[k] = v + (sub["scale"].astype(ab.dtype) * ab).astype(v.dtype)
        else:
            out[k] = _merge_row(v, sub if sub is not None else {})
    return out


def _adapter_apply(apply, pool, aidx):
    """Per-row adapter-gathered apply (multi-LoRA serving): ``pool`` is the
    stacked adapter tree (``tenancy.AdapterPool.tree`` — leading slot dim),
    ``aidx`` each row's adapter slot (0 = the base/zero adapter). The
    gather runs ONCE, outside the vmap (and outside a horizon's scan:
    ``aidx`` is fixed for the whole dispatch — admission only lands at its
    boundaries). Each row folds its own adapter into the base and runs the
    model at batch 1; vmap stacks the rows back into one fused program, so
    heterogeneous tenants share a single dispatch, bit-identical to each
    tenant solo against ``merge_lora``-folded weights (test-pinned)."""
    sel = jax.tree.map(lambda s: s[aidx], pool)

    def apply_rows(params, cache, chunk, lens):
        cache_b = jax.tree.map(lambda x: x[:, None], cache)

        def one(sel_row, cache_row, ch, ln):
            merged = _merge_row(params, sel_row)
            lg, c2 = apply(merged, cache_row, ch[None], ln[None])
            return lg[0], jax.tree.map(lambda x: x[0], c2)

        return jax.vmap(one)(sel, cache_b, chunk, lens)

    return apply_rows


def _has_work(live, lens, active):
    # A horizon step runs iff the host planned it and it has anything to do.
    return jnp.logical_and(
        live > 0, jnp.logical_or(jnp.any(lens > 0), jnp.any(active == 1))
    )


@dataclasses.dataclass(frozen=True, eq=False)
class _Bodies:
    """What ``jax.jit`` traces, as methods over what the bodies close
    over (the cached applies and the engine's compile-time settings;
    everything read from ``self`` is a Python value at trace time): the
    split programs themselves, and the cores the fused programs
    (``_fused``) run over the plain or the adapter-gathered apply."""

    apply: Callable
    d_apply: Callable | None     # the draft's; None = not speculative
    head_on_last: bool   # refill runs the head on a row's last position only
    temperature: float
    top_k: int | None
    top_p: float | None
    min_p: float | None
    vocab_limit: int | None
    max_new_tokens: int
    eos_id: int | None
    decode_block_steps: int
    num_draft: int

    # --- sampling and key derivation ---------------------------------------

    def greedy(self, logits):
        return greedy_pick(logits, self.vocab_limit)

    @staticmethod
    def row_keys(rng, rid, pos):
        """(B,) keys from (request id, generated position): the stream a
        request samples from depends only on its own identity and how far
        it has generated — never on scheduling."""

        def one(r, p):
            return jax.random.fold_in(jax.random.fold_in(rng, r), p)

        return jax.vmap(one)(rid, pos)

    @staticmethod
    def spec_keys(rng, rid, pos, tag):
        """Per-REQUEST rejection streams: ``speculative._pos_key``'s
        position+tag derivation (THE definition of the three stream roles)
        under a request-id fold — position-keyed, so a rolled-back
        position re-derives its draws and a round/block boundary lands
        nowhere in the stream (schedule independence, test-pinned)."""

        def one(r, p):
            return _pos_key(jax.random.fold_in(rng, r), p, tag)

        return jax.vmap(one)(rid, pos)

    def to_flogits(self, logits):
        """The filtered sampling distribution in logit space — shared with
        ``sample_rows`` via ``generate.filtered_logits`` (THE definition
        of the filter order) so the speculative acceptance distribution
        cannot drift from what plain sampling draws."""
        return filtered_logits(
            logits, self.temperature, self.top_k, self.top_p, self.min_p,
            self.vocab_limit,
        )

    def sample_rows(self, logits, rng, rid, pos):
        """Per-row sampling with (request, position) keys; greedy ignores
        the keys entirely (deterministic)."""
        if self.temperature == 0.0:
            return self.greedy(logits)
        return jax.vmap(jax.random.categorical)(
            self.row_keys(rng, rid, pos), self.to_flogits(logits)
        ).astype(jnp.int32)

    # --- the split families: jitted as they stand (XLA's module takes a
    # bound method's name) ------------------------------------------------

    def _refill(self, params, d_params, cache, chunk, lengths, rid, rng):
        # Run the chunk through the target (and the draft, whose cache
        # must mirror the target's valid prefix for verification); the
        # pick is each row's first generated token — position 0 of its
        # stream.
        last = jnp.maximum(lengths - 1, 0)
        if self.d_apply is not None:
            t_cache, d_cache = cache
            logits, t_cache = self.apply(params, t_cache, chunk, lengths)
            _, d_cache = self.d_apply(d_params, d_cache, chunk, lengths)
            cache = (t_cache, d_cache)
        elif self.head_on_last:
            # The head on each row's last valid position only: at this
            # family's vocabulary (129,280) the chunk's full (B, S, V)
            # logits are 2.1 GB in float32 and 2.2 TFLOP a dispatch.
            # GPT-2-shaped configs keep the program their goldens pin.
            logits, cache = self.apply(
                params, cache, chunk, lengths, logit_positions=last
            )
            last = jnp.zeros_like(last)      # logits are (B, 1, V)
        else:
            logits, cache = self.apply(params, cache, chunk, lengths)
        pick = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
        tok = self.sample_rows(pick, rng, rid, jnp.zeros_like(rid))
        return tok, cache

    def refill_step(
        self, params, d_params, cache, chunk, lengths, reset_mask, reset_to,
        rid, rng, rows, offsets,
    ):
        # Admission: set the admitted SLOTS' counters (0, or the shared-
        # prefix length under prefix caching), then run the chunk ROWS:
        # row r is a chunk of slot rows[r], offsets[r] tokens past what
        # that slot has consumed (_take_rows), so a long prompt may take
        # several rows of one call; a row's cache advance is its own
        # valid length (0 for a row nobody uses). A slot's counters come
        # back as the furthest its rows reached. A contiguous cache owns
        # its rows: its engine passes rows = arange(B), offsets = 0. The
        # cache-None first call routes to first_refill instead.
        # (The speculative pair's (target, draft) caches are one tree
        # to the three helpers: both take the same rows.)
        cache = _reset_rows(cache, reset_mask, reset_to)
        tok, out = self._refill(
            params, d_params, _take_rows(cache, rows, offsets), chunk,
            lengths, rid[rows], rng,
        )
        return tok, _put_rows(cache, out, rows, offsets)

    def first_refill(self, params, d_params, chunk, lengths, rid, rng):
        # Cache creation needs an apply without a cache; same program shape
        # as refill_step minus the reset (Flax creates the zeroed caches —
        # make_cached_apply treats a None cache as the creating call).
        cache = (None, None) if self.d_apply is not None else None
        return self._refill(params, d_params, cache, chunk, lengths, rid, rng)

    def decode_block(self, params, cache, tok, active, remaining, rid, rng):
        """``decode_block_steps`` tokens per call, scanned ON DEVICE — the
        host loop costs one dispatch/readback per BLOCK, not per token
        (rounds 1-5, on a remotely attached chip: per-token host
        stepping ran 30× slower than the same work scanned; not
        measured on today's machine). Rows that emit ``eos`` OR
        exhaust their per-row ``remaining`` budget flip inactive IN-scan —
        chunk_lengths 0 from then on, so a retired row stops consuming
        cache mid-block and its index can never pass its admission
        budget."""

        def body(carry, _):
            tok, active, remaining, cache = carry
            logits, cache = self.apply(params, cache, tok[:, None], active)
            # This draw's generated position: the row has already emitted
            # max_new_tokens - remaining tokens.
            pos = self.max_new_tokens - remaining
            nxt = self.sample_rows(logits[:, -1], rng, rid, pos)
            nxt = jnp.where(active == 1, nxt, tok)
            remaining = remaining - active
            if self.eos_id is not None:
                active = active * (nxt != self.eos_id).astype(jnp.int32)
            active = active * (remaining > 0).astype(jnp.int32)
            return (nxt, active, remaining, cache), nxt

        (tok, active, remaining, cache), toks = jax.lax.scan(
            body, (tok, active, remaining, cache), None,
            length=self.decode_block_steps,
        )
        return toks.T, active, remaining, cache   # (B, K) tokens

    # --- speculation -----------------------------------------------------------

    def spec_round(self, carry, params, d_params, rid, rng, apply_fn):
        """ONE draft-verify ROUND with PER-ROW acceptance and rollback —
        THE shared speculative core of the engine: ``decode_block_spec``
        scans it ``decode_block_steps`` times, ``spec_mixed_step`` runs
        it once after its fused refill sub-step, so the acceptance /
        emission / rollback rules cannot drift between the two program
        families. Frozen rows (``active == 0`` — idle, refilling, or
        retired) ride every sub-call with length 0 and ``n_emit`` 0, so
        the round's rollback broadcast re-asserts their current ``pos``
        without moving it.

        ``apply_fn`` is the VERIFIER's apply: the target model's, or the
        multi-LoRA engine's per-row adapter-gathered one — the draft
        always proposes with the BASE weights (a proposal distribution
        never defines the output; the verifier does), so one shared draft
        serves every tenant in the batch."""
        num_draft, d_apply = self.num_draft, self.d_apply
        idx = jnp.arange(num_draft + 1)
        (tok, active, pos, remaining, count, buffer, acc, prop,
         t_cache, d_cache) = carry
        # Each row's next GENERATED position (the refill's pick was
        # position 0 of its stream).
        gen = self.max_new_tokens - remaining

        # 1. Draft proposes per row (frozen rows ride with length 0).
        if self.temperature == 0.0:

            def draft_step(c, j):
                prev, dc = c
                lg, dc = d_apply(d_params, dc, prev[:, None], active)
                nxt = jnp.where(active == 1, self.greedy(lg[:, -1]), prev)
                return (nxt, dc), nxt

            (last_d, d_cache), drafts = jax.lax.scan(
                draft_step, (tok, d_cache), jnp.arange(num_draft)
            )
            q_all = None
        else:

            def draft_step(c, j):
                prev, dc = c
                lg, dc = d_apply(d_params, dc, prev[:, None], active)
                fl = self.to_flogits(lg[:, -1])
                nxt = jax.vmap(jax.random.categorical)(
                    self.spec_keys(rng, rid, gen + j, 0), fl
                ).astype(jnp.int32)
                nxt = jnp.where(active == 1, nxt, prev)
                return (nxt, dc), (nxt, jax.nn.softmax(fl, axis=-1))

            (last_d, d_cache), (drafts, q_all) = jax.lax.scan(
                draft_step, (tok, d_cache), jnp.arange(num_draft)
            )
        drafts = drafts.T
        _, d_cache = d_apply(d_params, d_cache, last_d[:, None], active)

        # 2. One chunked target verify.
        chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
        t_logits, t_cache = apply_fn(
            params, t_cache, chunk, active * (num_draft + 1)
        )

        # 3. Per-row acceptance; emitted = accepted drafts + the
        #    bonus/correction (greedy) or residual sample (sampling) —
        #    the shared cores, models/speculative.py.
        if self.temperature == 0.0:
            m, emitted, _ = greedy_accept_emit(drafts, self.greedy(t_logits))
        else:
            q_all = jnp.moveaxis(q_all, 0, 1)    # (B, num_draft, V)
            p_all = jax.nn.softmax(self.to_flogits(t_logits), axis=-1)
            p_at = jnp.take_along_axis(
                p_all[:, :num_draft], drafts[..., None], axis=-1
            )[..., 0]
            q_at = jnp.take_along_axis(
                q_all, drafts[..., None], axis=-1
            )[..., 0]
            u = jax.vmap(
                lambda j: jax.vmap(jax.random.uniform)(
                    self.spec_keys(rng, rid, gen + j, 1)
                ),
                out_axes=1,
            )(jnp.arange(num_draft))             # (B, num_draft)
            accept = u * q_at < p_at
            m = jnp.sum(
                jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
            )
            q_pad = jnp.concatenate(
                [q_all, jnp.zeros_like(q_all[:, :1])], axis=1
            )

            def take_m(x):
                return jnp.take_along_axis(x, m[:, None, None], axis=1)[:, 0]

            p_m = take_m(p_all)
            residual = jnp.maximum(p_m - take_m(q_pad), 0.0)
            mass = jnp.sum(residual, axis=-1, keepdims=True)
            residual = jnp.where(mass > 0, residual / mass, p_m)
            token_m = jax.vmap(jax.random.categorical)(
                self.spec_keys(rng, rid, gen + m, 2), jnp.log(residual)
            ).astype(jnp.int32)
            emitted = emit_vector(drafts, m, token_m)

        # 4. Truncate each row's emission at EOS and at its budget.
        raw = 1 + m
        if self.eos_id is not None:
            hit = (emitted == self.eos_id) & (idx[None, :] < raw[:, None])
            any_hit = jnp.any(hit, axis=1)
            first = jnp.argmax(hit, axis=1)
            n_stop = jnp.where(any_hit, first + 1, raw)
        else:
            any_hit = jnp.zeros_like(active, dtype=bool)
            n_stop = raw
        n_emit = jnp.minimum(n_stop, remaining) * active

        # 5. Append at each row's own offset; advance the pending
        #    token to the last emitted one.
        buffer = row_update_masked(buffer, emitted, count, n_emit, seq_dim=1)
        new_tok = jnp.take_along_axis(
            emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
        )[:, 0]
        tok = jnp.where(active == 1, new_tok, tok)

        # 6. Per-row rollback: the row's new index is pos + n_emit
        #    (frozen rows: +0, i.e. their current index — one
        #    broadcast serves all rows).
        pos = pos + n_emit
        t_cache = _rollback(t_cache, pos)
        d_cache = _rollback(d_cache, pos)

        remaining = remaining - n_emit
        count = count + n_emit
        # Acceptance telemetry: verifier acceptance per live round
        # (before EOS/budget truncation — the DRAFT's quality, which
        # is what the operator tunes num_draft against).
        acc = acc + m * active
        prop = prop + active * num_draft
        stopped_eos = any_hit & (n_stop <= n_emit) & (active == 1)
        active = (
            active
            * (remaining > 0).astype(jnp.int32)
            * (1 - stopped_eos.astype(jnp.int32))
        )
        return (
            tok, active, pos, remaining, count, buffer, acc, prop,
            t_cache, d_cache
        )

    @staticmethod
    def spec_carry_init(tok, active, pos, remaining, width):
        b = tok.shape[0]
        return (
            tok, active, pos, remaining,
            jnp.zeros((b,), jnp.int32),          # count
            jnp.zeros((b, width), jnp.int32),    # buffer
            jnp.zeros((b,), jnp.int32),          # acc
            jnp.zeros((b,), jnp.int32),          # prop
        )

    def decode_block_spec(
        self, params, d_params, t_cache, d_cache, tok, active, pos, remaining,
        rid, rng,
    ):
        """Speculative decode block: ``decode_block_steps`` draft-verify
        ROUNDS (``spec_round`` — the shared core), each emitting
        1..num_draft+1 tokens per row with PER-ROW acceptance and
        rollback (the ragged-cache machinery of
        ``models/speculative.py::generate_ragged``, driven inside the
        engine's scan). ``pos`` is each row's current cache index
        (prompt_len + emitted - 1); EOS and budget truncate a round's
        per-row emission exactly, so the buffer/counts the block returns
        are final — the host appends them verbatim.

        ``temperature > 0``: speculative SAMPLING (Leviathan rejection) —
        the draft proposes from the filtered distribution, acceptance is
        ``u·q < p`` per position, the slot-m token samples the residual
        ``norm(max(p − q, 0))`` — with every draw keyed by (request id,
        generated position, stream tag) via ``spec_keys``, so a request's
        sampled output is independent of batch composition, round
        boundaries, and block boundaries (rollback re-derives draws)."""
        width = self.decode_block_steps * (self.num_draft + 1)

        def body(carry, _):
            return self.spec_round(
                carry, params, d_params, rid, rng, self.apply
            ), None

        (tok, active, pos, remaining, count, buffer, acc, prop,
         t_cache, d_cache), _ = jax.lax.scan(
            body,
            self.spec_carry_init(tok, active, pos, remaining, width)
            + (t_cache, d_cache),
            None,
            length=self.decode_block_steps,
        )
        # tok and pos ride the return so CHAINED dispatches can carry
        # them device-to-device (decode_chain — no host sync between
        # chained blocks).
        return (
            buffer, count, acc, prop, tok, pos, active, remaining,
            t_cache, d_cache,
        )

    # --- the fused families ----------------------------------------------------
    # One iteration body and one horizon scan for plain engines, one of
    # each for speculative ones; ``apply_fn`` is the plain apply or the
    # adapter-gathered one, so the scheduling/sampling rules cannot drift
    # between the single-tenant and multi-tenant programs.

    def mixed_core(
        self, apply_fn, params, cache, chunk, lengths, reset_mask, reset_to,
        tok, active, remaining, rid, rng,
    ):
        """ONE FUSED engine iteration (``mixed=True``): every DECODING
        row advances one token AND every scheduled REFILL row pushes its
        budgeted prompt chunk, in a single compiled dispatch — decode
        never waits for another slot's prefill to stream through.

        Decode rows ride the ragged chunk with length 1 (their pending
        token spliced into column 0); refill rows ride with their
        host-scheduled ``chunk_lengths`` (admission resets applied
        first, exactly as in ``refill_step``); idle rows ride with
        length 0. The per-row computation is identical to what
        ``refill_step`` / ``decode_block``'s scan body would have done
        for that row — ragged rows are independent — so greedy token
        streams stay bit-identical to the split-program engine
        (test-pinned). Carries (tok/active/remaining) ride the return so
        ``decode_chain`` links can flow device-to-device with one host
        sync per chain."""
        cache = _reset_rows(cache, reset_mask, reset_to)
        dec = active == 1   # decoding rows never hold pending tokens
        eff_len = jnp.where(dec, 1, lengths)
        chunk = chunk.at[:, 0].set(jnp.where(dec, tok, chunk[:, 0]))
        logits, cache = apply_fn(params, cache, chunk, eff_len)
        pick = jnp.take_along_axis(
            logits, jnp.maximum(eff_len - 1, 0)[:, None, None], axis=1
        )[:, 0]
        # Refill rows sample their stream's position 0 (the refill
        # pick); decode rows their current generated position — the
        # same keys the split programs use.
        pos = jnp.where(dec, self.max_new_tokens - remaining, 0)
        nxt = self.sample_rows(pick, rng, rid, pos)
        tok = jnp.where(dec, nxt, tok)
        remaining = remaining - dec.astype(jnp.int32)
        if self.eos_id is not None:
            active = active * jnp.where(
                dec, (nxt != self.eos_id).astype(jnp.int32), 1
            )
        active = active * jnp.where(
            dec, (remaining > 0).astype(jnp.int32), 1
        )
        return nxt, tok, active, remaining, cache

    def spec_mixed_core(
        self, apply_fn, params, d_params, t_cache, d_cache, chunk, lengths,
        reset_mask, reset_to, tok, active, pos, remaining, rid, rng,
    ):
        """The speculative fused iteration: the budgeted refill chunk
        streams through TARGET AND DRAFT (decoding rows ride with
        length 0), then ONE draft-verify round (``spec_round`` — the
        same per-row acceptance/rollback core as ``decode_block_spec``)
        advances every decoding row by 1..num_draft+1 tokens. ``pos``
        tracks every row's cache index: refill rows advance by their
        chunk length BEFORE the round, so the round's rollback
        broadcast re-asserts (never clobbers) their refill advance.

        The verifier AND the refill stream run through ``apply_fn``: with
        the adapter-gathered apply, accepted tokens are exactly what the
        tenant's solo merged model would emit (greedy exactness through
        the verifier); the shared draft proposes with the base weights,
        which only moves the acceptance rate, never the output
        distribution."""
        t_cache = _reset_rows(t_cache, reset_mask, reset_to)
        d_cache = _reset_rows(d_cache, reset_mask, reset_to)
        r_logits, t_cache = apply_fn(params, t_cache, chunk, lengths)
        _, d_cache = self.d_apply(d_params, d_cache, chunk, lengths)
        r_pick = jnp.take_along_axis(
            r_logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
        )[:, 0]
        first_tok = self.sample_rows(r_pick, rng, rid, jnp.zeros_like(rid))
        pos = pos + lengths
        (tok, active, pos, remaining, count, buffer, acc, prop,
         t_cache, d_cache) = self.spec_round(
            self.spec_carry_init(
                tok, active, pos, remaining, self.num_draft + 1
            ) + (t_cache, d_cache),
            params, d_params, rid, rng, apply_fn,
        )
        return (
            first_tok, buffer, count, acc, prop, tok, pos, active,
            remaining, t_cache, d_cache,
        )

    def multi_scan(
        self, apply_fn, params, cache, chunks, lengths, reset_mask,
        reset_to, live, tok, active, remaining, rid, rng,
    ):
        """``horizon`` fused engine iterations in ONE dispatch (ROADMAP
        item 1): a ``lax.scan`` over the EXACT ``mixed_core`` body (shared,
        so the two program families cannot drift), with the slot
        bookkeeping the host used to re-derive every iteration
        (tok/active/remaining/cache) carried in the scan state. The host
        plans the whole horizon's refill schedule up front (stacked
        (N, B, ...) plan arrays ride as scan xs) and touches Python ONCE
        per horizon — one executable per horizon, one dispatch, one sync
        per N tokens instead of one per token. Per-row retirement happens
        IN-scan (remaining hits 0 / EOS flips ``active``). Token streams
        are bit-identical to N sequential ``mixed_step`` iterations
        (test-pinned): the per-row computation is the same, and sampling
        draws are keyed by (request id, generated position), never by
        schedule.

        Per-step ``lax.cond`` early-exit: a step the host did not plan
        (``live`` 0 — the fixed-shape horizon's trailing padding) or whose
        plan row has no refill while the carry holds no active row skips
        the model apply entirely, so padded steps cost control flow, not
        FLOPs. The ``live`` gate is load-bearing, not an optimization: the
        host only consumes tokens from PLANNED links, so an unplanned step
        must not advance any row (a speculative row can still be active
        past the optimistic chain cap)."""

        def body(carry, x):
            tok, active, remaining, cache = carry
            chunk, lens, rmask, rto, lv = x

            def step(_):
                nxt, tok2, active2, remaining2, cache2 = self.mixed_core(
                    apply_fn, params, cache, chunk, lens, rmask, rto, tok,
                    active, remaining, rid, rng,
                )
                return (tok2, active2, remaining2, cache2), nxt

            def frozen(_):
                return (tok, active, remaining, cache), tok

            return jax.lax.cond(
                _has_work(lv, lens, active), step, frozen, None
            )

        (tok, active, remaining, cache), toks = jax.lax.scan(
            body, (tok, active, remaining, cache),
            (chunks, lengths, reset_mask, reset_to, live),
        )
        return toks, tok, active, remaining, cache

    def spec_multi_scan(
        self, apply_fn, params, d_params, t_cache, d_cache, chunks, lengths,
        reset_mask, reset_to, live, tok, active, pos, remaining, rid, rng,
    ):
        """The speculative horizon: scans ``spec_mixed_core`` — each step
        a budgeted refill sub-step plus one draft-verify round — with the
        per-row rollback index (``pos``) and BOTH caches in the carry. A
        step's 1..num_draft+1 accepted tokens land in its ys buffer row
        with its count and acceptance telemetry (stacked (N, B, ...)); the
        host appends them per planned link after the single sync —
        bit-identical to N sequential ``spec_mixed_step`` iterations."""
        width = self.num_draft + 1

        def body(carry, x):
            tok, active, pos, remaining, t_cache, d_cache = carry
            chunk, lens, rmask, rto, lv = x

            def step(_):
                (first_tok, buffer, count, acc, prop, tok2, pos2,
                 active2, remaining2, t2, d2) = self.spec_mixed_core(
                    apply_fn, params, d_params, t_cache, d_cache,
                    chunk, lens, rmask, rto, tok, active, pos,
                    remaining, rid, rng,
                )
                return (
                    (tok2, active2, pos2, remaining2, t2, d2),
                    (first_tok, buffer, count, acc, prop),
                )

            def frozen(_):
                zi = jnp.zeros_like(tok)
                zb = jnp.zeros((tok.shape[0], width), jnp.int32)
                return (
                    (tok, active, pos, remaining, t_cache, d_cache),
                    (tok, zb, zi, zi, zi),
                )

            return jax.lax.cond(
                _has_work(lv, lens, active), step, frozen, None
            )

        (tok, active, pos, remaining, t_cache, d_cache), ys = jax.lax.scan(
            body, (tok, active, pos, remaining, t_cache, d_cache),
            (chunks, lengths, reset_mask, reset_to, live),
        )
        first_toks, buffers, counts, accs, props = ys
        return (
            first_toks, buffers, counts, accs, props, tok, pos, active,
            remaining, t_cache, d_cache,
        )


def _fused(name, body, apply, adapter):
    """The fused program ``name`` over ``body(apply_fn, params,
    *operands)``: a multi-LoRA engine's takes ``(pool, aidx)`` after
    ``params`` and runs the body over the adapter-gathered apply; the
    other operands (and the outputs) are the body's own."""
    if adapter:

        def program(params, pool, aidx, *operands):
            return body(
                _adapter_apply(apply, pool, aidx), params, *operands
            )

    else:

        def program(params, *operands):
            return body(apply, params, *operands)

    program.__name__ = program.__qualname__ = name
    return program


def _donating(program, *cache_args):
    """THE donation rule: ``program`` jitted so that every cache it takes
    and returns is updated in place. ``cache_args`` are the (adjacent)
    positions of its cache arguments; the jitted function takes one
    argument more, right after the last of them: the caches' ``block_table``
    leaves (:func:`split_cache`), which stay OUT of the donated trees and
    are not returned. One device array sits under every layer's table leaf
    (``ContinuousEngine._set_tables``) and a buffer cannot be donated
    twice; no program changes a table, so the host puts back the ones it
    holds. Everything else in a cache (pools, counters, scales,
    ``moe_stats``, recurrent state) is donated: XLA aliases each to the
    output that replaces it, and a dispatch neither allocates nor copies a
    second pool. ``program`` itself is traced over the merged trees, as it
    always was. A contiguous cache has no tables and donates whole."""
    lo, at = cache_args[0], cache_args[-1] + 1

    @functools.wraps(program)
    def split_program(*args):
        args = list(args)
        tables = args.pop(at)
        args[lo:at] = merge_cache(args[lo:at], tables)
        return split_cache(program(*args))[0]

    return jax.jit(split_program, donate_argnums=cache_args)


def _kv_programs():
    """The four cache-moving programs, as fresh functions (a jit cache is
    keyed by its function: two engines must not share one)."""

    def kv_export(cache, slot):
        """One slot's cache ROW — every cache leaf indexed at ``slot``
        on its batch dim, per-row counters included (fixed shapes, so
        the export is one executable for the engine's lifetime). The
        prefill half of the DISAGGREGATED handoff (round 11): a pure
        per-device gather whose golden contract
        (``analysis/golden/kv_export.json``) pins that extracting a
        row adds no collectives — the cross-replica byte movement
        rides the explicit host transfer plan
        (``fleet.kv_transfer``), where it is counted, never hidden
        in XLA resharding."""
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, slot, 0, keepdims=False),
            cache,
        )

    def kv_ingest(cache, rows, slot, index):
        """Write an externally produced cache row into ``slot`` and
        set its per-row counters to ``index`` (the row's valid
        length) — the decode half of the disaggregated handoff.
        Bytes past ``index`` are invisible to the causal-at-index
        masks (the ``_reset_rows`` invariant), so the transfer plan
        only has to deliver the valid prefix; its own golden
        (``analysis/golden/kv_ingest.json``) pins that the update
        adds no collectives when the rows arrive in this cache's own
        row layout (``kv_row_shardings``)."""

        def leaf(path, x, row):
            if getattr(path[-1], "key", None) in _SLOT_COUNTER_KEYS:
                row = jnp.asarray(index)
            return jax.lax.dynamic_update_index_in_dim(
                x, row.astype(x.dtype), slot, 0
            )

        return jax.tree_util.tree_map_with_path(leaf, cache, rows)

    def kv_page_spill(cache, pid):
        """One physical PAGE's K/V — every page-pool leaf
        (``_PAGE_LEAF_KEYS``) indexed at ``pid`` on its pool dim,
        returned as a flatten-ordered LIST (the page has no per-slot
        counters; a list avoids inventing a partial tree structure).
        The demotion half of the KV tier ladder (round 15): a pure
        per-device gather whose golden
        (``analysis/golden/kv_page_spill.json``) pins that demoting
        a page adds no collectives — the HBM→host bytes ride the
        counted ``parallel.resharding`` host plan."""
        return [
            jax.lax.dynamic_index_in_dim(x, pid, 0, keepdims=False)
            for path, x in jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", None) in _PAGE_LEAF_KEYS
        ]

    def kv_page_fill(cache, page_rows, pid):
        """Write a spilled page's K/V rows back into physical page
        ``pid`` — the promotion half of the tier ladder, inverse of
        ``kv_page_spill`` (same flatten-ordered leaf list). Its own
        golden (``analysis/golden/kv_page_fill.json``) pins zero
        collectives when the rows arrive in this cache's page-row
        layout (pool dim dropped from each leaf's spec)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        it = iter(page_rows)
        out = []
        for path, x in flat:
            if getattr(path[-1], "key", None) in _PAGE_LEAF_KEYS:
                row = next(it)
                x = jax.lax.dynamic_update_index_in_dim(
                    x, row.astype(x.dtype), pid, 0
                )
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return kv_export, kv_ingest, kv_page_spill, kv_page_fill


def build_programs(
    apply, d_apply=None, *, adapter=False, head_on_last=False, kv_rows=True,
    moe_counted=False,
    mixed=False, paged=False, prefix_cache=False, temperature=0.0,
    top_k=None, top_p=None, min_p=None, vocab_limit=None, max_new_tokens,
    eos_id=None, decode_block_steps, num_draft=4,
) -> dict[str, Program]:
    """The program table of one engine mode, in the order every report
    lists it. ``apply`` / ``d_apply`` are the target's and the draft's
    ``make_cached_apply`` (a draft makes the engine speculative);
    ``adapter``: a multi-LoRA pool rides the fused programs;
    ``head_on_last``: refill runs the head on each row's last valid
    position only (latent-attention and one-mixer-a-layer configs);
    ``kv_rows``: every cache leaf is contiguous ``(B, L, N_kv, H)`` K,V
    rows, which the disaggregated hand-off can move; ``moe_counted``:
    whether the expert layers count; ``mixed`` / ``paged`` /
    ``prefix_cache`` decide which families the scheduler, the handoff and
    the tier ladder can reach; the rest is what the bodies close over.

    The split programs are in every table (``compile_counts`` has always
    listed them, though a multi-LoRA engine's scheduler reaches only
    ``first_refill`` of them). A mixed engine gets ONE fused step and ONE
    horizon scan: speculation picks the body, the pool picks the apply."""
    speculative = d_apply is not None
    b = _Bodies(
        apply=apply, d_apply=d_apply, head_on_last=head_on_last,
        temperature=temperature,
        top_k=top_k, top_p=top_p, min_p=min_p, vocab_limit=vocab_limit,
        max_new_tokens=max_new_tokens, eos_id=eos_id,
        decode_block_steps=decode_block_steps, num_draft=num_draft,
    )

    def counted(program, cache_arg):
        return _with_moe(program, cache_arg) if moe_counted else program

    table: dict[str, Program] = {}

    def add(family, fn, contract=None, **flags):
        table[family] = Program(family, fn, contract or family, **flags)

    add("first_refill", jax.jit(counted(b.first_refill, None)), "first_prefill")
    add("refill_step", _donating(counted(b.refill_step, 2), 2), "prefill")
    if speculative:
        add(
            "decode_block_spec", _donating(b.decode_block_spec, 2, 3),
            "decode_step",
        )
    # On a speculative engine the degradation ladder's target-only decode:
    # the same program a plain engine runs, under the plain golden.
    add(
        "decode_block", _donating(counted(b.decode_block, 1), 1),
        "decode_step", steady=not speculative,
    )
    if mixed:
        tenant = "adapter_" if adapter else ""
        spec = "spec_" if speculative else ""
        # After params (and a pool's two operands): the cache, or the
        # draft's params and the pair's two.
        at = 3 if adapter else 1
        caches = (at + 1, at + 2) if speculative else (at,)
        add(f"{tenant}mixed_step", _donating(_fused(
            f"{tenant}{spec}mixed_step",
            b.spec_mixed_core if speculative else b.mixed_core,
            apply, adapter,
        ), *caches))
        add(f"{tenant}multi_step", _donating(_fused(
            f"{tenant}{spec}multi_step",
            b.spec_multi_scan if speculative else b.multi_scan,
            apply, adapter,
        ), *caches), steady=False)
    kv_export, kv_ingest, kv_page_spill, kv_page_fill = _kv_programs()
    if kv_rows and not (speculative or paged or adapter):
        # The disaggregated handoff moves contiguous (B, L, N_kv, H) rows.
        add("kv_export", jax.jit(kv_export), steady=False, applies=False)
        add("kv_ingest", _donating(kv_ingest, 0), steady=False,
            applies=False)
    if paged and prefix_cache and not speculative:
        # The tier ladder spills and fills retained prefix pages.
        add("kv_page_spill", jax.jit(kv_page_spill), steady=False,
            applies=False)
        add("kv_page_fill", _donating(kv_page_fill, 0), steady=False,
            applies=False)
    return table


def build_drift_probe(apply, oracle_apply):
    """The comm-compression drift probe: one greedy decode step under the
    compressed ``apply`` and the plain-collective ``oracle_apply`` (the
    SAME weights and cache), returning ``(live rows, live rows whose
    argmax diverged)``. The caches it produces are discarded, so probing
    never perturbs the served stream."""

    @jax.jit
    def comp_probe(params, cache, tok, active):
        lc, _ = apply(params, cache, tok[:, None], active)
        lo, _ = oracle_apply(params, cache, tok[:, None], active)
        agree = (
            jnp.argmax(lc[:, -1], axis=-1) == jnp.argmax(lo[:, -1], axis=-1)
        )
        live = active == 1
        return jnp.sum(live), jnp.sum(live & ~agree)

    return comp_probe
