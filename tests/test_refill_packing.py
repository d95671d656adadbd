"""A refill dispatch packs CHUNKS, not slots (models/serving.py).

On a paged engine a dispatch of ``refill_step`` carries up to ``B`` chunk
rows ``(slot, offset, n)``: every refilling slot's next chunk in its own
row, then further consecutive chunks of prompts with tokens left in the rows
nobody refills. The oracle is the one every scheduling change answers to:
streams do not move. A packed engine returns, token for token, what the same
engine returns when held to one row a slot, and what ``generate`` returns.
"""

import dataclasses
import json
import pathlib
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.families import joyai_llm_flash as family  # noqa: E402
from learning_jax_sharding_tpu.models.generate import make_generate_fn  # noqa: E402
from learning_jax_sharding_tpu.models.engine_programs import (  # noqa: E402
    _put_rows,
    _take_rows,
)
from learning_jax_sharding_tpu.models.serving import ContinuousEngine  # noqa: E402
from learning_jax_sharding_tpu.models.transformer import (  # noqa: E402
    CONFIG_TINY,
    Transformer,
)
from learning_jax_sharding_tpu.parallel import build_mesh  # noqa: E402
from learning_jax_sharding_tpu.parallel.logical import (  # noqa: E402
    RULES_DP_TP,
    RULES_TP_SERVING,
)
from learning_jax_sharding_tpu.robustness.chaos import (  # noqa: E402
    ChaosInjector,
    Fault,
)
from learning_jax_sharding_tpu.telemetry.flight_recorder import FlightRecorder  # noqa: E402

NEW, CHUNK, PAGE, B = 5, 4, 16, 4
#: Prompts of 1, 3 and 7 chunks (the last chunk of two of them part full),
#: and two more so that slots are reused.
LENGTHS = (4, 11, 27, 2, 9)

_HF = json.loads((REPO / "benchmark" / "configs" / "joyai-llm-flash.json").read_text())
_HF = {k: v for k, v in {**_HF, **_HF["rehearse"]}.items() if not isinstance(v, dict)}


@pytest.fixture(scope="module")
def mesh11():
    return build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def _params(cfg, seed=3):
    return nn.meta.unbox(
        jax.jit(lambda r, t: Transformer(cfg).init({"params": r}, t))(
            jax.random.key(seed), np.zeros((2, 8), np.int32)
        )["params"]
    )


def _prompts(vocab, lengths=LENGTHS, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=(n,)).astype(np.int32) for n in lengths]


def _one_row_a_slot(eng):
    """Hold a paged engine to the packing a contiguous one has: the same
    program, ``rows = arange(B)`` and ``offsets = 0`` in every dispatch."""
    eng._spare_chunk_rows = lambda firsts: []
    return eng


def _dispatches(eng, phase="refill"):
    return [e for e in eng.recorder.events("engine.dispatch") if e["phase"] == phase]


def _chunk_rows(eng):
    """Rows that carried a chunk, one entry a refill dispatch."""
    return [e["chunk_rows"] for e in _dispatches(eng)]


TINY = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32, decode_attention="blocked")
DRAFT = dataclasses.replace(TINY, num_layers=1)

#: name -> (config, engine keywords, page size, refill chunk)
CASES = {
    "gpt2_learned_positions": (TINY, {}, PAGE, CHUNK),
    "rope_gqa": (dataclasses.replace(TINY, rope=True, num_kv_heads=2), {}, PAGE, CHUNK),
    "int8_kv": (dataclasses.replace(TINY, kv_cache_dtype=jnp.int8), {}, PAGE, CHUNK),
    "prefix_cache": (TINY, dict(prefix_cache=True), PAGE, CHUNK),
    "speculative_pair": (TINY, dict(draft_config=DRAFT, num_draft=2), PAGE, CHUNK),
    "latent_dropless_experts": (
        family.to_config(_HF, dtype=jnp.float32, moe_experts="pallas"), {}, 8, 8,
    ),
}


@pytest.mark.parametrize("name", list(CASES))
def test_packed_streams_equal_one_row_a_slot(name, mesh11):
    cfg, kw, page, chunk = CASES[name]
    params = _params(cfg)
    d_params = _params(DRAFT, seed=7) if "draft_config" in kw else None
    prompts = _prompts(cfg.vocab_size, [n * chunk // CHUNK for n in LENGTHS])
    if name == "prefix_cache":
        # A shared first page: later admissions start at reset_to = 16, so
        # a slot's rows are taken AFTER a reset to a non-zero index.
        prompts = [np.concatenate([prompts[2][:page], p]) for p in prompts]

    def engine():
        return ContinuousEngine(
            cfg, mesh11, RULES_TP_SERVING, batch_size=B, max_new_tokens=NEW,
            refill_chunk=chunk, paged_pages=24, page_size=page,
            recorder=FlightRecorder(), **kw,
        )

    packed, plain = engine(), _one_row_a_slot(engine())
    got = packed.serve(params, prompts, draft_params=d_params)
    want = plain.serve(params, prompts, draft_params=d_params)
    for p, g, w in zip(prompts, got, want):
        np.testing.assert_array_equal(g, w, err_msg=f"{name}: prompt of {p.size}")
        assert len(g) == p.size + NEW
    assert len(_chunk_rows(packed)) < len(_chunk_rows(plain))
    if name == "prefix_cache":
        # What is retained when a request is admitted follows the schedule.
        assert packed.last_stats["prefix_hits"] >= 1
        return
    assert sum(_chunk_rows(packed)) == sum(_chunk_rows(plain))    # the same chunks
    assert packed.registry.counter("engine_prefill_tokens_total").value == (
        plain.registry.counter("engine_prefill_tokens_total").value
    )


@pytest.mark.parametrize("name", ["gpt2_learned_positions", "rope_gqa"])
def test_packed_streams_equal_generate_on_a_mesh(name, mesh22):
    """Against the rectangular single-prompt run, on the (2, 2) mesh the
    paged tests of test_serving.py use (the batch replicated, heads split)."""
    cfg = CASES[name][0]
    params, prompts = _params(cfg), _prompts(cfg.vocab_size)
    eng = ContinuousEngine(
        cfg, mesh22, RULES_TP_SERVING, batch_size=B, max_new_tokens=NEW,
        refill_chunk=CHUNK, paged_pages=24, page_size=PAGE,
    )
    got = eng.serve(params, prompts)
    gen = make_generate_fn(cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW)
    for p, g in zip(prompts, got):
        ref = np.asarray(gen(params, np.repeat(p[None], 2, axis=0), jax.random.key(0)))[0]
        np.testing.assert_array_equal(g, ref, err_msg=f"prompt of {p.size}")


def _four_slots(mesh, **kw):
    """4 slots with 1, 1, 2 and 4 chunks pending."""
    args = dict(
        batch_size=B, max_new_tokens=NEW, refill_chunk=CHUNK, paged_pages=24, page_size=PAGE,
        recorder=FlightRecorder(),
    )
    args.update(kw)
    eng = ContinuousEngine(TINY, mesh, RULES_TP_SERVING, **args)
    return eng, _params(TINY), _prompts(TINY.vocab_size, (4, 3, 8, 15))


def _drain(eng, params):
    while eng.has_work():
        eng.step(params)
    return eng.pop_finished()


def test_eight_chunks_over_four_rows_take_two_dispatches(mesh11):
    eng, params, prompts = _four_slots(mesh11)
    got = eng.serve(params, prompts)
    refills = _dispatches(eng)
    assert _chunk_rows(eng) == [4, 4]
    # The first carries every slot's first chunk (4 + 3 + 4 + 4 tokens); the
    # second slot 2's last chunk, and slot 3's next three in rows 3, 0 and 1.
    assert [e["prefill_tokens"] for e in refills] == [15, 15]
    assert [e["token_slots"] for e in refills] == [B * CHUNK] * 2
    reg = eng.registry
    assert reg.counter("engine_refill_token_slots_total").value == 2 * B * CHUNK
    assert reg.counter("engine_refill_chunk_rows_total").value == 8
    assert reg.counter("engine_prefill_tokens_total").value == 30
    assert all(e["chunk_rows"] == 0 for e in _dispatches(eng, "decode"))
    plain = _one_row_a_slot(_four_slots(mesh11)[0])
    for g, w in zip(got, plain.serve(params, prompts)):
        np.testing.assert_array_equal(g, w)
    assert _chunk_rows(plain) == [4, 2, 1, 1]


def test_spare_rows_go_to_the_prompt_with_fewest_chunks_left(mesh11):
    """One spare row and two prompts with chunks left: the shorter takes it
    (the dispatch completes a prompt); the first chunks stay in their rows."""
    eng, params, _ = _four_slots(mesh11)
    for p in _prompts(TINY.vocab_size, (2, 16, 8, 12)):
        eng.add_request(p)
    eng.step(params)                        # four first chunks, no spare row
    assert [r.size for r in eng._pending] == [0, 12, 4, 8]
    eng.step(params)                        # slot 0 decodes: its row is spare
    assert [r.size for r in eng._pending] == [0, 8, 0, 0]
    args = eng.program("refill_step").last_args()
    np.testing.assert_array_equal(np.asarray(args[-2]), [3, 1, 2, 3])      # rows
    np.testing.assert_array_equal(np.asarray(args[-1]), [4, 0, 0, 0])      # offsets
    assert _dispatches(eng)[-1]["chunk_rows"] == 4
    _drain(eng, params)


def test_chained_refill_dispatches_pack_too(mesh11):
    eng, params, prompts = _four_slots(mesh11, decode_chain=2)
    got = eng.serve(params, prompts)
    # Both dispatches of the wave go in ONE step, one record for the two.
    assert _chunk_rows(eng) == [8]
    assert [e["token_slots"] for e in _dispatches(eng)] == [2 * B * CHUNK]
    plain = _one_row_a_slot(_four_slots(mesh11, decode_chain=2)[0])
    for g, w in zip(got, plain.serve(params, prompts)):
        np.testing.assert_array_equal(g, w)


def test_page_backpressure_takes_rows_before_it_takes_the_slot(mesh11):
    """4 usable pages of 16: three short prompts hold one each, and the long
    one (27 tokens, 2 pages) cannot have the second. Its three spare rows
    fall back to the two its first page covers; the chunk after that finds no
    page and the request is un-admitted, recomputed later, the same tokens."""
    eng, params, _ = _four_slots(mesh11, paged_pages=5)
    prompts = _prompts(TINY.vocab_size, (4, 3, 2, 27))
    got = eng.serve(params, prompts)
    want = _one_row_a_slot(_four_slots(mesh11)[0]).serve(params, prompts)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert _chunk_rows(eng)[:2] == [4, 3]
    assert eng.last_stats["preemptions"] >= 1
    # Conservation: every page came back.
    assert len(eng._free_pages) == 4 and not any(eng._held)


def test_a_fault_in_a_packed_dispatch_strikes_requeues_and_recovers(mesh11):
    eng, params, prompts = _four_slots(mesh11)
    clean = eng.serve(params, prompts)
    for p in prompts:
        eng.add_request(p)                                   # rids 4..7
    eng.step(params)
    assert [r.size for r in eng._pending] == [0, 0, 4, 11]
    slots = eng.registry.counter("engine_refill_token_slots_total").value
    with ChaosInjector(Fault("engine.dispatch", "hang", at=0)) as chaos:
        eng.step(params)        # the packed dispatch: slot 3 in rows 3, 0, 1
    assert chaos.injections[0]["phase"] == "refill"
    # The failed dispatch consumed nothing and claimed nothing...
    assert eng.registry.counter("engine_refill_token_slots_total").value == slots
    assert eng.registry.counter("engine_dispatch_faults_total").value == 1
    # ...and every request it carried earned a strike and went back to the
    # queue, its slot's pages freed, to be recomputed alone (probation).
    assert [r.strikes for r in eng._queue] == [1, 1, 1, 1]
    assert not any(eng._held) and all(r.size == 0 for r in eng._pending)
    out = _drain(eng, params)
    for rid, want in zip((4, 5, 6, 7), clean):
        np.testing.assert_array_equal(out[rid], want)
    for g, w in zip(eng.serve(params, prompts), clean):     # a later clean run
        np.testing.assert_array_equal(g, w)
    assert _chunk_rows(eng)[-2:] == [4, 4]


def test_a_contiguous_engine_keeps_one_row_a_slot(mesh22):
    """No pages to share: the same program with rows = arange(B), offsets =
    0, the dispatches it always made, the streams generate() makes."""
    cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
    params, prompts = _params(cfg), _prompts(cfg.vocab_size, (4, 3, 8, 15))
    eng = ContinuousEngine(
        cfg, mesh22, RULES_DP_TP, batch_size=B, max_new_tokens=NEW, refill_chunk=CHUNK,
        recorder=FlightRecorder(),
    )
    got = eng.serve(params, prompts)
    assert _chunk_rows(eng) == [4, 2, 1, 1]
    assert eng.registry.counter("engine_refill_token_slots_total").value == 4 * B * CHUNK
    rows, offsets = eng.program("refill_step").last_args()[-2:]
    np.testing.assert_array_equal(np.asarray(rows), np.arange(B))
    np.testing.assert_array_equal(np.asarray(offsets), np.zeros(B))
    gen = make_generate_fn(cfg, mesh22, RULES_DP_TP, max_new_tokens=NEW)
    for p, g in zip(prompts, got):
        ref = np.asarray(gen(params, np.repeat(p[None], 2, axis=0), jax.random.key(0)))[0]
        np.testing.assert_array_equal(g, ref)


def test_a_one_token_chunk_keeps_one_row_a_slot(mesh11):
    """At ``refill_chunk=1`` a step folds its K,V write into the attention
    kernel (a row's write lands when the kernel flushes), so a second row of
    the same slot would not see it: such an engine packs nothing."""
    eng, params, _ = _four_slots(mesh11, refill_chunk=1)
    prompts = _prompts(TINY.vocab_size, (3, 1, 2))
    got = eng.serve(params, prompts)
    assert _chunk_rows(eng) == [3, 2, 1]
    want = _four_slots(mesh11)[0].serve(params, prompts)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_rows_take_their_slots_counters_and_give_back_the_furthest():
    cache = {
        "layer": {
            "block_table": jnp.arange(12).reshape(4, 3),
            "cache_index": jnp.asarray([5, 0, 8, 2]),
            "cached_kv": jnp.zeros((6, 1, 2, 4)),
        },
        # A recurrent layer: a state a SLOT, and the row each row continues.
        "ssm": {
            "ssm_state": 10.0 * jnp.arange(4.0)[:, None] + jnp.zeros((4, 2)),
            "carry_from": jnp.full((4,), -1),
        },
        "position": jnp.asarray([5, 0, 8, 2]),
    }
    rows, offsets = jnp.asarray([0, 3, 2, 3]), jnp.asarray([0, 4, 0, 0])
    taken = _take_rows(cache, rows, offsets)
    np.testing.assert_array_equal(taken["layer"]["cache_index"], [5, 6, 8, 2])
    np.testing.assert_array_equal(taken["position"], [5, 6, 8, 2])
    np.testing.assert_array_equal(taken["layer"]["block_table"][1], [9, 10, 11])
    assert taken["layer"]["cached_kv"] is cache["layer"]["cached_kv"]
    # Row 1 continues row 3 (slot 3's first chunk); every row starts from
    # its slot's state.
    np.testing.assert_array_equal(taken["ssm"]["carry_from"], [-1, 3, -1, -1])
    np.testing.assert_array_equal(taken["ssm"]["ssm_state"][:, 0], [0, 30, 20, 30])
    # The model advanced each row by its length: 0, 3 (slot 3's last), 0, 4.
    ran = jax.tree.map(lambda x: x, taken)
    ran["layer"]["cache_index"] = taken["layer"]["cache_index"] + jnp.asarray([0, 3, 0, 4])
    ran["layer"]["cached_kv"] = taken["layer"]["cached_kv"] + 1
    ran["ssm"] = dict(taken["ssm"], ssm_state=jnp.arange(1.0, 5.0)[:, None] + jnp.zeros((4, 2)))
    back = _put_rows(cache, ran, rows, offsets)
    # Slot 3 keeps what its LAST row (row 1) left, slot 1 (no row) its own.
    np.testing.assert_array_equal(back["ssm"]["ssm_state"][:, 0], [1, 10, 3, 2])
    np.testing.assert_array_equal(back["ssm"]["carry_from"], -1)
    np.testing.assert_array_equal(back["layer"]["cache_index"], [5, 0, 8, 9])
    np.testing.assert_array_equal(back["position"], [5, 0, 8, 6])
    np.testing.assert_array_equal(back["layer"]["block_table"], cache["layer"]["block_table"])
    assert float(back["layer"]["cached_kv"].min()) == 1.0


# --- faults under donation: the engine never keeps a consumed cache ----------

def _raising_after(eng, family, nth, error):
    """Make the ``nth`` call of ``family``'s program raise AFTER it ran: the
    cache it was given is consumed and its successor is lost (what a trap
    on a program's outputs does)."""
    prog, calls = eng.program(family), []
    real = prog.fn

    def fn(*args):
        calls.append(None)
        out = real(*args)
        if len(calls) == nth:
            prog.fn = real
            raise error("trapped after the call")
        return out

    prog.fn = fn


@pytest.mark.parametrize("family,nth,chain", [
    ("refill_step", 1, 1),      # the packed dispatch of the wave
    ("refill_step", 2, 2),      # the second link of a chained refill
    ("decode_block", 2, 2),     # the second link of a chained decode
])
def test_a_program_that_raises_after_it_took_the_cache_costs_a_recompute(
    mesh11, family, nth, chain
):
    """The program consumed the engine's cache and its result never came
    back: the fault handler drops the dead tree with the pool's host state,
    the next dispatch creates a cache, and every request is recomputed to
    the tokens of an unfaulted run."""
    eng, params, prompts = _four_slots(
        mesh11, decode_chain=chain, decode_block_steps=2
    )
    clean = eng.serve(params, prompts)
    created = eng.cache_creations
    for p in prompts:
        eng.add_request(p)                                   # rids 4..7
    if family == "refill_step" and chain == 1:
        eng.step(params)        # the first chunks; the packed dispatch next
    _raising_after(eng, family, nth, FloatingPointError)
    while eng.registry.counter("engine_dispatch_faults_total").value == 0:
        eng.step(params)
    assert eng._cache is None and eng.cache_creations == created
    assert len(eng._free_pages) == 23 and not any(eng._held)
    assert [r.strikes for r in eng._queue] == [1, 1, 1, 1]
    out = _drain(eng, params)
    assert eng.cache_creations == created + 1
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng._cache))
    for rid, want in zip((4, 5, 6, 7), clean):
        np.testing.assert_array_equal(out[rid], want)
    for g, w in zip(eng.serve(params, prompts), clean):     # a later clean run
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("phase", ["refill", "decode"])
def test_a_fault_between_two_links_of_a_chain_finds_the_live_cache(mesh11, phase):
    """A recoverable fault raised mid-chain, after a link that consumed the
    cache it was given: the engine holds that link's result (installed as
    the link returned, not after the loop), keeps it, and re-admits."""
    eng, params, prompts = _four_slots(
        mesh11, decode_chain=2, decode_block_steps=2
    )
    clean = eng.serve(params, prompts)
    created = eng.cache_creations
    for p in prompts:
        eng.add_request(p)                                   # rids 4..7
    if phase == "refill":
        # The seam sits in front of every link of a refill chain.
        with ChaosInjector(Fault("engine.dispatch", "hang", at=1)) as chaos:
            eng.step(params)
        assert chaos.injections[0]["phase"] == "refill"
    else:
        # A decode chain has one seam, in front of its first link: the
        # second link's program raises before it runs.
        eng.step(params)        # admission and the whole refill chain
        assert eng._active.all()
        prog, real = eng.program("decode_block"), eng.program("decode_block").fn

        def fn(*args):
            prog.fn = real
            if fn.calls:
                raise FloatingPointError("trapped before the call")
            fn.calls += 1
            prog.fn = fn
            return real(*args)

        fn.calls = 0
        prog.fn = fn
        eng.step(params)
        assert prog.fn is real
    assert eng.registry.counter("engine_dispatch_faults_total").value == 1
    assert eng._cache is not None and eng.cache_creations == created
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng._cache))
    out = _drain(eng, params)
    assert eng.cache_creations == created
    for rid, want in zip((4, 5, 6, 7), clean):
        np.testing.assert_array_equal(out[rid], want)
