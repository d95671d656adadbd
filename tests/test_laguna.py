"""poolside Laguna-XS.2's mechanisms at the benchmark's ``rehearse`` size
(window 16, pages of 8, chunks of 8; a dense global layer, two window
layers, a global expert layer; 8 experts, top 2), on seeded weights,
against the plain reference (``benchmark/families/laguna_reference.py``:
float32, whole-sequence masked attention, every expert visited). The window
layers keep a RING of pages a slot; the tests run contexts over several
turns of it. Logits are compared, never sampled tokens. Each tolerance
carries its reason.
"""

import dataclasses
import json
import math
import pathlib
import sys
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import laguna_costs, reference, spec  # noqa: E402
from benchmark.families import laguna as family  # noqa: E402
from learning_jax_sharding_tpu.models.attention import MultiHeadAttention  # noqa: E402
from learning_jax_sharding_tpu.models.convert import config_from_hf_laguna  # noqa: E402
from learning_jax_sharding_tpu.models.decoding import (  # noqa: E402
    derive_decode_config,
    make_cached_apply,
)
from learning_jax_sharding_tpu.models.engine_programs import (  # noqa: E402
    _put_rows,
    _reset_rows,
    _take_rows,
)
from learning_jax_sharding_tpu.models.moe import DroplessMoE  # noqa: E402
from learning_jax_sharding_tpu.models.serving import ContinuousEngine  # noqa: E402
from learning_jax_sharding_tpu.models.transformer import Transformer  # noqa: E402
from learning_jax_sharding_tpu.ops.decode_attention import (  # noqa: E402
    decode_attention,
    fuse_kv,
    ring_pages,
    window_band,
)
from learning_jax_sharding_tpu.ops.rope import Yarn, apply_rope, rope_frequencies  # noqa: E402
from learning_jax_sharding_tpu.parallel import build_mesh  # noqa: E402
from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING  # noqa: E402

#: float32 program against the float32 reference: both round every matmul
#: once and sum in another order (the program's attention online, in pages,
#: the reference's whole); the largest departure measured over the cases
#: below was 6e-7 on logits of magnitude 0.5.
F32_TOL = 2e-5

_FILE = json.loads((REPO / "benchmark" / "configs" / "laguna-xs.2.json").read_text())
HF = spec._merge(_FILE, _FILE["rehearse"])
DIMS = family.model_dims(HF)
PAGE, CHUNK, PAGES, SLOTS = 8, 8, 80, 4
WINDOW = HF["sliding_window"]
RING = ring_pages(WINDOW, PAGE)


def _config(**over):
    return family.to_config(HF, **over)


def _params(cfg, seed=0):
    return nn.meta.unbox(jax.jit(Transformer(cfg).init)(
        {"params": jax.random.key(seed)}, np.zeros((2, 8), np.int32)
    ))["params"]


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, HF["vocab_size"], (n,)).astype(np.int32)


def _reference(params, tokens):
    return family.reference_fn(DIMS)(params, jnp.asarray(tokens))


# --- the file, the model ---------------------------------------------------------------


#: Widths of the published ``config.json``; none of them may be cut.
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 8192, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "vocab_size": 100352,
    "sliding_window": 512,
}


def test_the_file_holds_the_published_widths_and_names_its_cuts():
    for key, value in PUBLISHED_WIDTHS.items():
        assert _FILE[key] == value, key
    assert sorted(_FILE["reduced"]) == sorted(_FILE["published"]) == sorted(_FILE["changed"])
    for key in _FILE["reduced"][1:]:
        assert _FILE[key] == _FILE["published"][key][:5]
    cfg = family.to_config(_FILE)
    assert cfg.layer_types == ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    assert (cfg.num_heads, cfg.window_num_heads, cfg.window, cfg.rotary_dim) == (48, 64, 512, 64)
    assert cfg.param_count == _FILE["parameters"] == 3_869_857_792


def test_the_blocks_take_their_kinds_heads_and_gate():
    cfg = _config()
    params = _params(cfg)
    assert "pos_embed" not in params and "lm_head" in params
    heads = [params[f"block_{i}"]["attn"]["gate"]["kernel"].shape[1] for i in range(4)]
    assert heads == HF["num_attention_heads_per_layer"]
    assert "ff" in params["block_0"] and "moe" in params["block_1"]
    moe = params["block_1"]["moe"]
    assert "bias" not in moe                           # softmax: no selection bias
    assert moe["shared"]["up"]["kernel"].shape == (64, 32)
    assert cfg.param_count == sum(x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_program_matches_the_reference(seed):
    cfg = _config()
    params = _params(cfg, seed)
    tokens = np.stack([_tokens(seed + 10, 61), _tokens(seed + 20, 61)])
    got = jax.jit(Transformer(cfg).apply)({"params": params}, jnp.asarray(tokens))
    assert np.abs(np.asarray(got) - _reference(params, tokens)).max() < F32_TOL


def test_the_converter_refuses_head_counts_that_disagree_with_the_kinds():
    bad = dict(HF, num_attention_heads_per_layer=[4, 8, 4, 4])
    with pytest.raises(ValueError, match="num_attention_heads_per_layer"):
        config_from_hf_laguna(types.SimpleNamespace(**bad))
    with pytest.raises(ValueError, match="norm_topk_prob"):
        config_from_hf_laguna(types.SimpleNamespace(**dict(HF, norm_topk_prob=True)))


# --- the pieces ------------------------------------------------------------------------


def test_the_router_takes_a_softmax_and_does_not_renormalise():
    layer = DroplessMoE(
        features=16, hidden=8, num_experts=8, top_k=2, routed_scaling=2.5,
        score="softmax", experts="ragged",
    )
    x = jax.random.normal(jax.random.key(1), (1, 5, 16))
    params = nn.meta.unbox(layer.init(jax.random.key(2), x))["params"]
    assert "bias" not in params
    got = np.asarray(layer.apply({"params": params}, x))[0]
    xs = np.asarray(x[0], np.float64)
    p = np.exp(xs @ np.asarray(params["router"]["kernel"], np.float64))
    p /= p.sum(-1, keepdims=True)
    want = np.zeros_like(xs)
    for t in range(5):
        for e in np.argsort(-p[t])[:2]:
            h = xs[t] @ params["gate"][e]
            h = h / (1 + np.exp(-h)) * (xs[t] @ params["up"][e])
            want[t] += 2.5 * p[t, e] * (h @ params["down"][e])       # p itself, not p / sum
    assert np.abs(got - want).max() < 1e-5


def test_a_closed_gate_halves_every_head():
    kw = dict(features=32, num_heads=4, head_dim=8, num_kv_heads=2, causal=True)
    x = jax.random.normal(jax.random.key(3), (1, 6, 32))
    params = nn.meta.unbox(
        MultiHeadAttention(gate=True, **kw).init(jax.random.key(4), x)
    )["params"]
    plain = {k: v for k, v in params.items() if k != "gate"}
    ungated = MultiHeadAttention(**kw).apply({"params": plain}, x)
    # sigmoid(0) = 1/2 for every head; the output projection is linear.
    params["gate"]["kernel"] = jnp.zeros_like(params["gate"]["kernel"])
    half = MultiHeadAttention(gate=True, **kw).apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(half), 0.5 * np.asarray(ungated), atol=1e-6)


def test_partial_yarn_matches_the_transformers_rule():
    torch = pytest.importorskip("torch")
    del torch
    try:
        from transformers.modeling_rope_utils import _compute_yarn_parameters
    except ImportError:
        pytest.skip("transformers has no _compute_yarn_parameters here")
    full = _FILE["rope_parameters"]["full_attention"]
    hf = types.SimpleNamespace(
        rope_theta=full["rope_theta"], head_dim=128, hidden_size=2048,
        num_attention_heads=48, partial_rotary_factor=0.5,
        max_position_embeddings=262144, rope_scaling=dict(full),
    )
    inv_freq, factor = _compute_yarn_parameters(hf, "cpu")
    yarn = _config().rope_yarn
    ours = rope_frequencies(64, full["rope_theta"], family.to_config(_FILE).rope_yarn)
    np.testing.assert_allclose(ours, inv_freq.numpy(), rtol=2e-6)
    assert math.isclose(factor, family.to_config(_FILE).rope_yarn.scale)
    assert yarn.scale == full["attention_factor"]
    # Partial: the first 64 dims rotate (x the factor), the rest pass.
    x = jax.random.normal(jax.random.key(5), (1, 9, 2, 128))
    pos = jnp.arange(9) + 90     # float32 angles: small positions, exact enough
    y = apply_rope(x, pos, full["rope_theta"], rotary_dim=64, yarn=family.to_config(_FILE).rope_yarn)
    np.testing.assert_array_equal(np.asarray(y[..., 64:]), np.asarray(x[..., 64:]))
    ang = np.asarray(pos, np.float64)[:, None] * inv_freq.numpy()
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    c, s = np.cos(ang)[None, :, None] * factor, np.sin(ang)[None, :, None] * factor
    np.testing.assert_allclose(np.asarray(y[..., :32]), x1 * c - x2 * s, atol=2e-4)


def test_yarn_without_a_given_attention_factor_takes_the_log_rule():
    assert math.isclose(Yarn(factor=64, original_max_positions=4096).scale, 0.1 * math.log(64) + 1)


# --- the ring kernel -------------------------------------------------------------------


def _dense_window(q, k, v, idx, window):
    """Masked softmax attention of ``q`` ``(B, S, N, H)`` over logical
    ``k, v`` ``(B, L, N_kv, H)``: query ``t`` of row ``b`` at ``idx_b + t``
    reads keys in ``(p - window, p]``."""
    b, s, n, h = q.shape
    g = n // k.shape[2]
    kk, vv = np.repeat(k, g, 2), np.repeat(v, g, 2)
    out = np.zeros(q.shape, np.float64)
    for r in range(b):
        for t in range(s):
            p = idx[r] + t
            keys = np.arange(max(0, p - window + 1), p + 1)
            sc = np.einsum("nh,knh->nk", q[r, t], kk[r, keys]) / math.sqrt(h)
            w = np.exp(sc - sc.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[r, t] = np.einsum("nk,knh->nh", w, vv[r, keys])
    return out


@pytest.mark.parametrize(
    "h,s,idx",
    [
        (16, 1, [130, 7]),        # the emitter form, one token, a 4th ring turn
        (16, 6, [91, 40]),        # the emitter form, a chunk across a page
        (64, 1, [130, 7]),        # the loop form (rows of whole lane tiles)
        (64, 6, [91, 40]),
    ],
    ids=["emitter-fold", "emitter-chunk", "loop-fold", "loop-chunk"],
)
def test_the_ring_kernel_reads_the_window_through_logical_block_mod_r(h, s, idx):
    b, n, n_kv, window, page = 2, 4, 2, WINDOW, PAGE
    r = ring_pages(window, page)
    length = 160
    rng = np.random.default_rng(7)
    q = rng.standard_normal((b, s, n, h)).astype(np.float32)
    k = rng.standard_normal((b, length, n_kv, h)).astype(np.float32)
    v = rng.standard_normal((b, length, n_kv, h)).astype(np.float32)
    fold = s == 1
    # The pool as the ring leaves it: each position written to its page
    # j mod R in order, so later turns overwrite earlier ones; the fold's
    # own token is not written yet.
    table = 1 + np.arange(b * r, dtype=np.int32).reshape(b, r)
    pool = np.full((b * r + 1, n_kv, page, 2 * h), 99.0, np.float32)
    for row in range(b):
        for pos in range(idx[row] + (0 if fold else s)):
            pool[table[row, (pos // page) % r], :, pos % page] = np.concatenate(
                [k[row, pos], v[row, pos]], -1
            )
    kw = dict(block_table=jnp.asarray(table), window=window, ring=True, interpret=True)
    if fold:
        new = np.stack([np.concatenate([k[i, idx[i]], v[i, idx[i]]], -1) for i in range(b)])
        out, cache = decode_attention(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(idx, jnp.int32),
            kv_new=jnp.asarray(new[:, :, None]), **kw,
        )
        for row in range(b):
            pos = idx[row]
            got = np.asarray(cache)[table[row, (pos // page) % r], :, pos % page]
            np.testing.assert_array_equal(got, new[row])
    else:
        out = decode_attention(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(idx, jnp.int32), **kw
        )
    want = _dense_window(q, k, v, idx, window)
    assert np.abs(np.asarray(out) - want).max() < 1e-4


def test_a_ring_holds_the_window_and_what_a_dispatch_writes_before_it_reads():
    # 2W - 1 consecutive positions touch at most R pages; one more page
    # than that could alias two blocks of one band.
    for window, page in ((16, 8), (512, 128), (24, 8), (100, 16)):
        r = ring_pages(window, page)
        worst = max(
            (p + 2 * window - 2) // page - p // page + 1 for p in range(4 * page)
        )
        assert r == worst
    assert ring_pages(512, 128) == 9 and RING == 5
    first, last = window_band(np.asarray([130, 5]), 1, WINDOW, PAGE)
    np.testing.assert_array_equal(last - first, [
        laguna_costs.window_pages(c + 1, WINDOW, PAGE) for c in (130, 5)
    ])


# --- through the cache -----------------------------------------------------------------


def _served(cfg):
    """The cached apply over ``SLOTS`` slots (global layers on scattered
    pages, window layers on their rings) and one jitted step of it as
    ``refill_step`` runs it."""
    dcfg = dataclasses.replace(
        derive_decode_config(cfg), decode_ragged=True, decode_paged=True,
        decode_page_count=PAGES, decode_block_k=PAGE,
    )
    apply = make_cached_apply(Transformer(dcfg))

    @jax.jit
    def step(params, cache, chunk, lengths, rows, offsets, reset):
        cache = _reset_rows(cache, reset)
        logits, out = apply(params, _take_rows(cache, rows, offsets), chunk, lengths)
        return logits, _put_rows(cache, out, rows, offsets)

    def create(params, tables):
        _, cache = jax.jit(apply)(
            params, None, jnp.zeros((SLOTS, CHUNK), jnp.int32), jnp.zeros((SLOTS,), jnp.int32)
        )
        return jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.asarray(tables) if getattr(p[-1], "key", None) == "block_table" else x,
            cache,
        )

    return step, create


def _run(step, params, cache, dispatches, reset=(), width=CHUNK):
    """``dispatches``: lists of ``{row: (slot, offset, tokens)}``. Returns
    the logits every row produced, by (slot, position), and the cache."""
    seen, pos = {}, {}
    for n, dispatch in enumerate(dispatches):
        rows, offsets = np.arange(SLOTS, dtype=np.int32), np.zeros(SLOTS, np.int32)
        lengths, chunk = np.zeros(SLOTS, np.int32), np.zeros((SLOTS, width), np.int32)
        mask = np.zeros(SLOTS, bool)
        mask[list(reset if n == 0 else ())] = True
        for r, (slot, off, toks) in dispatch.items():
            rows[r], offsets[r], lengths[r] = slot, off, len(toks)
            chunk[r, : len(toks)] = toks
        logits, cache = step(
            params, cache, jnp.asarray(chunk), jnp.asarray(lengths), jnp.asarray(rows),
            jnp.asarray(offsets), jnp.asarray(mask),
        )
        done = {}
        for r, (slot, off, toks) in dispatch.items():
            base = pos.get(slot, 0) + off
            for i in range(len(toks)):
                seen[slot, base + i] = np.asarray(logits[r, i])
            done[slot] = max(done.get(slot, 0), off + len(toks))
        for slot, n_tok in done.items():
            pos[slot] = pos.get(slot, 0) + n_tok
    return seen, cache


def _tables(long_pages):
    tables = np.zeros((SLOTS, HF["max_position_embeddings"] // PAGE), np.int32)
    tables[1, :long_pages] = np.random.default_rng(3).permutation(np.arange(1, long_pages + 1))
    tables[2, :3] = [long_pages + 2, long_pages + 1, long_pages + 3]
    return tables


def _dispatches(long, short):
    """Slot 1's prompt two rows a dispatch (its own row and a spare one, as
    a window engine packs it: window // chunk rows), slot 2's in its row."""
    out, at = [], 0
    while at < len(long):
        d = {1: (1, 0, long[at:at + CHUNK])}
        if at + CHUNK < len(long):
            d[0] = (1, CHUNK, long[at + CHUNK:at + 2 * CHUNK])
        if not out:
            d[2] = (2, 0, short)
        out.append(d)
        at += 2 * CHUNK
    return out


def test_packed_rows_then_decode_through_the_ring_match_the_reference():
    cfg = _config(decode_attention="blocked")
    params = _params(cfg, seed=2)
    step, create = _served(cfg)
    # 130 positions: three and a quarter turns of a ring of 5 pages of 8.
    long, short = _tokens(5, 122), _tokens(6, 7)
    cache = create(params, _tables(17))
    got, cache = _run(step, params, cache, _dispatches(long, short), reset=(1, 2))
    decoded, cache = _run(step, params, cache, [
        {1: (1, 0, long_t), 2: (2, 0, short_t)}
        for long_t, short_t in zip(
            _tokens(7, 8).reshape(8, 1), _tokens(8, 8).reshape(8, 1)
        )
    ], width=1)
    base = {1: len(long), 2: len(short)}
    more = {(slot, base[slot] + at): x for (slot, at), x in decoded.items()}
    full_long = np.concatenate([long, _tokens(7, 8)])
    full_short = np.concatenate([short, _tokens(8, 8)])
    want = {1: _reference(params, full_long[None])[0], 2: _reference(params, full_short[None])[0]}
    assert max(at for slot, at in more if slot == 1) + 1 > 3 * RING * PAGE
    for (slot, at), logits in {**got, **more}.items():
        assert np.abs(logits - want[slot][at]).max() < F32_TOL, (slot, at)


def test_a_ring_read_one_page_off_is_seen(monkeypatch):
    # The kernel reads logical block j from ring page (j + 1) mod R while
    # the chunk writes go where they belong: past the first page of a
    # prompt the window layers read the wrong positions and logits depart.
    from learning_jax_sharding_tpu.ops import decode_attention as kernel

    real = kernel.decode_attention

    def shifted(*args, block_table=None, ring=False, **kw):
        if ring:
            block_table = jnp.roll(block_table, -1, axis=1)
        return real(*args, block_table=block_table, ring=ring, **kw)

    monkeypatch.setattr(kernel, "decode_attention", shifted)
    cfg = _config(decode_attention="blocked")
    params = _params(cfg, seed=2)
    step, create = _served(cfg)
    long, short = _tokens(5, 122), _tokens(6, 7)
    got, _ = _run(step, params, create(params, _tables(17)), _dispatches(long, short), reset=(1, 2))
    want = _reference(params, long[None])[0]
    assert max(np.abs(got[1, at] - want[at]).max() for at in range(PAGE, 122)) > 1e-2


# --- the engine ------------------------------------------------------------------------


def _engine(**kw):
    cfg = _config(decode_attention="blocked")
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    args = dict(batch_size=SLOTS, max_new_tokens=6, refill_chunk=CHUNK, paged_pages=PAGES, page_size=PAGE)
    args.update(kw)
    mesh = args.pop("mesh", mesh)
    return cfg, ContinuousEngine(cfg, mesh, RULES_TP_SERVING, **args)


def test_the_engine_serves_long_prompts_through_the_rings_as_the_reference_decides(
    tmp_path, capsys
):
    cfg, eng = _engine()
    params = _params(cfg, seed=3)
    lens = [122, 11, 45, 5, 90, 33]
    prompts = [_tokens(40 + i, n) for i, n in enumerate(lens)]
    streams = [np.asarray(s) for s in eng.serve(params, prompts)]
    check = reference.teacher_forced(
        family.reference_fn(DIMS), params, prompts, streams, 1e-4, 128
    )
    assert check["ok"] and check["positions"] == 36, check
    reg = eng.registry.snapshot()
    # The counter is the kernel's band, the benchmark's formula its price.
    want = sum(
        laguna_costs.window_pages(n + j, WINDOW, PAGE) for n in lens for j in range(1, 6)
    )
    assert reg["engine_decode_window_pages_total"] == want
    assert reg["engine_decode_steps_total"] == 30
    layer_pool = (SLOTS * RING + 1) * 2 * PAGE * 2 * DIMS["head_dim"] * 4
    assert reg["engine_window_ring_bytes"] == 2 * layer_pool
    assert reg["engine_global_pool_bytes"] == 2 * PAGES * 2 * PAGE * 2 * DIMS["head_dim"] * 4
    # The operator's tool reads the same back from a bundle.
    import importlib.util

    spec_ = importlib.util.spec_from_file_location(
        "engine_breakdown", REPO / "scripts" / "engine_breakdown.py"
    )
    tool = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(tool)
    eng.dump_diagnostics(str(tmp_path))
    out = tool.main([str(tmp_path), "--json"])
    assert out["window"]["pages_per_decode_step"] == want / 30
    assert out["window"]["ring_bytes"] == 2 * layer_pool
    tool.main([str(tmp_path)])
    assert "ring pages read a decode row-step" in capsys.readouterr().out


def test_a_slot_takes_no_more_rows_of_a_dispatch_than_its_window_holds_chunks():
    _, eng = _engine()
    assert eng._rows_a_slot == WINDOW // CHUNK == 2
    for slot in range(SLOTS):
        eng._pending[slot] = np.ones((0,), np.int32)
    eng._plen[1], eng._pending[1] = 60, np.ones((60,), np.int32)
    extra = eng._spare_chunk_rows({1: CHUNK})
    assert extra == [(1, CHUNK, CHUNK)]


@pytest.mark.parametrize(
    "kw,match",
    [
        (dict(prefix_cache=True), "prefix_cache is not supported with window layers.*overwritten"),
        (dict(mixed=True), "mixed / horizon is not supported with window layers"),
        (dict(draft_config="self"), "speculative decoding.*window layers"),
        (dict(kv_cache_dtype="int8"), "kv_cache_dtype=int8 is not supported with window layers"),
        (dict(dequantize=True), "dequantize is not supported with window layers"),
        (dict(mesh="two"), "more than one device is not supported with window layers"),
        (dict(refill_chunk=32), "refill_chunk .32. must not exceed the window"),
    ],
)
def test_the_engine_refuses_by_name_what_a_ring_cannot_do(kw, match):
    if kw.get("draft_config") == "self":
        kw = dict(draft_config=_config())
    if kw.get("mesh") == "two":
        kw = dict(mesh=build_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2]))
    if kw.get("kv_cache_dtype"):
        cfg = _config(decode_attention="blocked", kv_cache_dtype=jnp.int8)
        mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
        with pytest.raises(ValueError, match=match):
            ContinuousEngine(
                cfg, mesh, RULES_TP_SERVING, batch_size=SLOTS, max_new_tokens=6,
                refill_chunk=CHUNK, paged_pages=PAGES, page_size=PAGE,
            )
        return
    with pytest.raises(ValueError, match=match):
        _engine(**kw)


def test_the_engine_refuses_a_handoff_and_a_page_spill():
    _, eng = _engine(paged_pages=None)
    with pytest.raises(ValueError, match="window layers are not supported"):
        eng.export_kv(0)
    _, eng = _engine()
    with pytest.raises(ValueError, match="window layers are not tiered"):
        eng.spill_page(b"key")
