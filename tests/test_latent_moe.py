"""JoyAI-LLM-Flash's mechanisms at the benchmark's ``rehearse`` size, on
seeded weights, against the plain reference
(``benchmark/families/joyai_llm_flash_reference.py``: float32, expanded
attention, every expert visited): latent attention in both forms, the latent
paged cache through refill chunks and decode steps, sigmoid dropless routing
with a selection bias and a shared expert, the dense first layer, and the two
kernels under the interpreter against plain ops. Logits are compared, never
sampled tokens. Each tolerance carries its reason.
"""

import dataclasses
import json
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

from benchmark.families import joyai_llm_flash as family  # noqa: E402
from benchmark.families.joyai_llm_flash_reference import _moe  # noqa: E402
from learning_jax_sharding_tpu.models.convert import (  # noqa: E402
    config_from_hf_joyai_llm_flash,
)
from learning_jax_sharding_tpu.models.decoding import (  # noqa: E402
    derive_decode_config,
    make_cached_apply,
)
from learning_jax_sharding_tpu.models.moe import DroplessMoE  # noqa: E402
from learning_jax_sharding_tpu.models.engine_programs import _reset_rows  # noqa: E402
from learning_jax_sharding_tpu.models.serving import ContinuousEngine  # noqa: E402
from learning_jax_sharding_tpu.models.transformer import Transformer  # noqa: E402
from learning_jax_sharding_tpu.ops.decode_attention import decode_attention  # noqa: E402
from learning_jax_sharding_tpu.ops.moe_experts import (  # noqa: E402
    moe_experts,
    plan,
    routed_experts,
    tile_rows,
)
from learning_jax_sharding_tpu.parallel import build_mesh  # noqa: E402
from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING  # noqa: E402

#: float32 program against the float32 reference: both round every matmul
#: once and sum in another order; the largest departure measured over the
#: seeds below was 6e-7 on logits of magnitude 0.6.
F32_TOL = 2e-5
#: bf16 weights and compute through the latent paged cache against float32
#: math on the SAME bf16 weights: 8 bits of mantissa over two layers read
#: 0.004-0.012 on these seeds; a dropped shared expert reads 0.05 and more.
BF16_TOL = 0.03

_FILE = json.loads(
    (REPO / "benchmark" / "configs" / "joyai-llm-flash.json").read_text()
)
HF = {k: v for k, v in {**_FILE, **_FILE["rehearse"]}.items() if not isinstance(v, dict)}
DIMS = family.model_dims(HF)
PAGE, CHUNK, PAGES = 8, 8, 12


def _config(**over):
    return family.to_config(HF, **over)


def _params(cfg, seed=0, bias=0.3):
    tokens = np.zeros((2, 8), np.int32)
    params = nn.meta.unbox(
        Transformer(cfg).init({"params": jax.random.key(seed)}, tokens)
    )["params"]
    # The selection bias starts at zero; give it what moves picks.
    moe = params["block_1"]["moe"]
    moe["bias"] = (bias * jax.random.normal(jax.random.key(seed + 1), moe["bias"].shape)).astype(
        moe["bias"].dtype
    )
    return params


def _tokens(seed, shape):
    return np.random.default_rng(seed).integers(1, HF["vocab_size"], shape).astype(np.int32)


def _reference(params, tokens):
    return family.reference_fn(DIMS)(params, jnp.asarray(tokens))


# --- the normal path -----------------------------------------------------------


def test_converter_maps_the_published_keys_and_refuses_what_it_cannot_compute():
    full = {k: v for k, v in _FILE.items() if not isinstance(v, dict)}
    cfg = config_from_hf_joyai_llm_flash(types.SimpleNamespace(**full))
    assert (cfg.features, cfg.num_heads, cfg.latent_q_rank, cfg.latent_kv_rank) == (2048, 32, 1536, 512)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.hidden) == (128, 64, 128, 7168)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_hidden, cfg.moe_shared_experts) == (256, 8, 768, 1)
    assert (cfg.moe_routed_scaling, cfg.rope_theta, cfg.vocab_size) == (2.5, 32e6, 129280)
    assert cfg.first_k_dense == 1 and cfg.moe_routing == "sigmoid_dropless" and cfg.ff_gated
    assert cfg.norm == "rmsnorm" and not cfg.use_bias and cfg.max_seq_len == 131072
    for key, value in (("n_group", 8), ("scoring_func", "softmax"), ("rope_scaling", {"type": "yarn"}),
                       ("attention_bias", True), ("rope_interleave", False)):
        with pytest.raises(ValueError, match=key):
            config_from_hf_joyai_llm_flash(types.SimpleNamespace(**{**full, key: value}))


def test_layer_zero_is_dense_and_the_rest_are_expert_layers():
    params = _params(_config())
    assert set(params["block_0"]) == {"attn", "ff", "ln_attn", "ln_ff"}
    assert set(params["block_0"]["ff"]) == {"gate", "up", "down"}
    assert set(params["block_1"]["moe"]) == {"router", "bias", "gate", "up", "down", "shared"}
    assert "pos_embed" not in params and "bias" not in params["lm_head"]
    path = jax.tree_util.keystr(
        [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0] if "bias" in str(p)][0]
    )
    assert path.endswith("['bias']")      # benchmark/serve.py gives such leaves noise


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("form", ["expanded", "absorbed", "absorbed_pallas_experts"])
def test_float32_program_matches_the_reference(form, seed):
    cfg = _config(
        latent_absorbed=form != "expanded",
        moe_experts="pallas" if form.endswith("pallas_experts") else "ragged",
    )
    params, tokens = _params(cfg, seed), _tokens(seed, (2, 24))
    got = Transformer(cfg).apply({"params": params}, tokens)
    assert np.abs(np.asarray(got) - _reference(params, tokens)).max() < F32_TOL


def test_the_selection_bias_moves_picks_and_not_weights():
    cfg = _config()
    params, tokens = _params(cfg), _tokens(3, (2, 24))
    flat = dict(params)
    no_bias = jax.tree.map(lambda x: x, params)
    no_bias["block_1"]["moe"]["bias"] = jnp.zeros_like(params["block_1"]["moe"]["bias"])
    with_b, without = (_reference(p, tokens) for p in (params, no_bias))
    assert np.abs(with_b - without).max() > 100 * F32_TOL     # picks moved
    got = Transformer(cfg).apply({"params": params}, tokens)
    assert np.abs(np.asarray(got) - with_b).max() < F32_TOL
    del flat


def test_a_dropped_shared_expert_or_a_bf16_router_fails_the_tolerance():
    cfg = _config()
    params, tokens = _params(cfg), _tokens(5, (2, 24))
    want = _reference(params, tokens)
    dropped = jax.tree.map(lambda x: x, params)
    dropped["block_1"]["moe"]["shared"] = jax.tree.map(jnp.zeros_like, params["block_1"]["moe"]["shared"])
    got = Transformer(cfg).apply({"params": dropped}, tokens)
    assert np.abs(np.asarray(got) - want).max() > 100 * F32_TOL
    # The expert layer alone, its router in bf16 where the reference's is float32.
    x = jax.random.normal(jax.random.key(9), (4, 32, HF["hidden_size"]))
    layer = dict(features=HF["hidden_size"], hidden=HF["moe_intermediate_size"], num_experts=HF["n_routed_experts"],
                 top_k=HF["num_experts_per_tok"], shared_experts=1, routed_scaling=2.5, experts="ragged")
    p = params["block_1"]["moe"]
    want = np.asarray(_moe(x, p, DIMS))
    exact = DroplessMoE(**layer).apply({"params": p}, x)
    rounded = DroplessMoE(**layer, router_dtype=jnp.bfloat16).apply({"params": p}, x)
    assert np.abs(np.asarray(exact) - want).max() < F32_TOL
    assert np.abs(np.asarray(rounded) - want).max() > 10 * F32_TOL


def test_no_token_lacks_an_expert_however_uneven_the_routing():
    """Every token picks the SAME two experts (a bias of 50 on them): a
    capacity scheme would drop most; here each gets both, and two experts
    are read."""
    e, k, d, f, t = 8, 2, 64, 32, 96
    keys = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(keys[0], (t, d))
    w_g, w_u = (jax.random.normal(kk, (e, d, f)) / 8 for kk in keys[1:3])
    w_d = jax.random.normal(keys[3], (e, f, d)) / 6
    idx = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (t, 1))
    w = jax.random.uniform(keys[4], (t, k))
    g, u = (jnp.einsum("td,edf->tef", x, m) for m in (w_g, w_u))
    every = jnp.einsum("tef,efd->ted", g * jax.nn.sigmoid(g) * u, w_d)
    want = w[:, :1] * every[:, 5] + w[:, 1:] * every[:, 2]
    for backend in ("ragged", "pallas"):
        out, stats = routed_experts(x, idx, w, w_g, w_u, w_d, backend=backend)
        assert np.abs(np.asarray(out - want)).max() < 1e-4, backend
        assert np.asarray(stats).tolist() == [t * k, 2, 1]
        assert np.abs(np.asarray(out)).min(axis=1).max() > 0   # no zero row


def test_invalid_tokens_are_routed_nowhere():
    e, k, d, f, t = 8, 2, 64, 32, 16
    keys = jax.random.split(jax.random.key(4), 6)
    x = jax.random.normal(keys[0], (t, d))
    w_g, w_u = (jax.random.normal(kk, (e, d, f)) / 8 for kk in keys[1:3])
    w_d = jax.random.normal(keys[3], (e, f, d)) / 6
    idx = jax.random.randint(keys[4], (t, k), 0, e)
    w = jax.random.uniform(keys[5], (t, k))
    valid = jnp.arange(t) < 5
    full, _ = routed_experts(x, idx, w, w_g, w_u, w_d, backend="ragged")
    for backend in ("ragged", "pallas"):
        out, stats = routed_experts(x, idx, w, w_g, w_u, w_d, valid=valid, backend=backend)
        assert np.abs(np.asarray(out[:5] - full[:5])).max() < 1e-5
        assert not np.asarray(out[5:]).any()
        assert int(stats[0]) == 5 * k and int(stats[1]) == len(set(np.asarray(idx[:5]).ravel().tolist()))
    _, none = routed_experts(x, idx, w, w_g, w_u, w_d, valid=jnp.zeros((t,), bool), backend="pallas")
    assert np.asarray(none).tolist() == [0, 0, 0]


# --- the kernels under the interpreter, against plain ops ---------------------


def test_plan_gives_every_assignment_a_row_of_its_experts_tiles():
    rng = np.random.default_rng(0)
    e, tm = 8, 16
    expert = rng.integers(0, e + 1, size=(200,)).astype(np.int32)     # e = invalid
    src, pos, tile_expert, num_tiles, counts, _ = map(np.asarray, plan(jnp.asarray(expert), e, tm))
    assert counts.tolist() == np.bincount(expert, minlength=e + 1)[:e].tolist()
    assert num_tiles == sum(-(-c // tm) for c in counts)
    m = len(src)
    for a, ex in enumerate(expert):
        if ex == e:
            assert pos[a] == m
        else:
            assert src[pos[a]] == a and tile_expert[pos[a] // tm] == ex and pos[a] < num_tiles * tm
    assert sorted(src[src < len(expert)].tolist()) == np.flatnonzero(expert < e).tolist()
    assert tile_rows(32 * 8, 256) == 16 and tile_rows(4096 * 8, 256) == 128


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 0.06)])
def test_expert_kernel_matches_plain_ops(dtype, tol):
    e, d, f, tm, tiles = 6, 128, 256, 16, 7
    keys = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(keys[0], (tiles * tm, d), dtype)
    w_g, w_u = (jax.random.normal(kk, (e, d, f), dtype) / 11 for kk in keys[1:3])
    w_d = jax.random.normal(keys[3], (e, f, d), dtype) / 16
    tile_expert = jnp.asarray([0, 0, 2, 3, 3, 5, 1], jnp.int32)
    used = 5
    out = moe_experts(x, tile_expert, jnp.int32(used), w_g, w_u, w_d, tm=tm, interpret=True)
    for i in range(used):
        rows = x[i * tm : (i + 1) * tm].astype(jnp.float32)
        ex = int(tile_expert[i])
        g, u = rows @ w_g[ex].astype(jnp.float32), rows @ w_u[ex].astype(jnp.float32)
        want = (g * jax.nn.sigmoid(g) * u) @ w_d[ex].astype(jnp.float32)
        got = out[i * tm : (i + 1) * tm].astype(jnp.float32)
        assert np.abs(np.asarray(got - want)).max() < tol, i


def _latent_case(seed, b, s, n, r, v, page, pool, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 4)
    t_cap = 6
    rng = np.random.default_rng(seed)
    index = rng.integers(0, page * t_cap - s, size=(b,)).astype(np.int32)
    table = np.zeros((b, t_cap), np.int32)
    free = list(rng.permutation(np.arange(1, pool)))
    for row in range(b):
        for blk in range(-(-(int(index[row]) + s) // page)):
            table[row, blk] = free.pop()
    return dict(
        q=jax.random.normal(keys[0], (b, s, n, r), dtype),
        cache=jax.random.normal(keys[1], (pool, 1, page, r), dtype),
        new=jax.random.normal(keys[2], (b, 1, s, r), dtype),
        index=jnp.asarray(index), table=jnp.asarray(table),
    )


def _latent_plain(q, cache, index, table, v, scale, page):
    """Gather each row's logical cache, attend with plain ops."""
    b, s, n, r = q.shape
    rows = cache[table][:, :, 0].reshape(b, -1, r).astype(jnp.float32)   # (B, T*page, R)
    sc = jnp.einsum("bsnr,bkr->bnsk", q.astype(jnp.float32), rows) * scale
    qpos = index[:, None] + jnp.arange(s)[None]
    seen = jnp.arange(rows.shape[1])[None, None] <= qpos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], sc, -jnp.inf), -1)
    return jnp.einsum("bnsk,bkv->bsnv", p, rows[..., :v])


def test_latent_decode_kernel_folds_the_write_and_matches_plain_ops():
    b, s, n, r, v, page, pool = 4, 1, 4, 24, 16, 8, 20
    c = _latent_case(1, b, s, n, r, v, page, pool)
    enable = jnp.asarray([1, 0, 1, 1], jnp.int32)
    out, cache = decode_attention(
        c["q"], c["cache"], c["index"], kv_new=c["new"], write_enable=enable, row_enable=enable,
        block_table=c["table"], block_k=page, latent_v=v, scale=0.2, interpret=True,
    )
    pages = c["table"][jnp.arange(b), c["index"] // page]
    written = c["cache"].at[pages, 0, c["index"] % page].set(
        jnp.where(enable[:, None] > 0, c["new"][:, 0, 0], c["cache"][pages, 0, c["index"] % page])
    )
    assert np.array_equal(np.asarray(cache), np.asarray(written))          # write-back exact
    want = _latent_plain(c["q"], written, c["index"], c["table"], v, 0.2, page)
    assert out.shape == (b, s, n, v)
    assert np.abs(np.asarray(out - want))[np.asarray(enable) > 0].max() < 1e-5
    assert not np.asarray(out)[1].any()                                     # the row off the list


def test_latent_decode_kernel_attends_a_chunk_through_the_pages():
    b, s, n, r, v, page, pool = 3, 8, 4, 24, 16, 8, 20
    c = _latent_case(2, b, s, n, r, v, page, pool)
    pos = c["index"][:, None] + jnp.arange(s)[None]
    pages = jnp.take_along_axis(c["table"], pos // page, axis=1)
    cache = c["cache"].at[pages, 0, pos % page].set(c["new"][:, 0])
    out = decode_attention(
        c["q"], cache, c["index"], block_table=c["table"], block_k=page, latent_v=v, scale=0.2,
        row_enable=jnp.asarray([1, 1, 0]), interpret=True,
    )
    want = _latent_plain(c["q"], cache, c["index"], c["table"], v, 0.2, page)
    assert np.abs(np.asarray(out - want))[:2].max() < 1e-5 and not np.asarray(out)[2].any()
    nobody = decode_attention(
        c["q"], cache, c["index"], block_table=c["table"], block_k=page, latent_v=v, scale=0.2,
        row_enable=jnp.zeros((b,), jnp.int32), interpret=True,
    )
    assert not np.asarray(nobody).any()


def test_latent_kernel_refuses_what_it_has_no_form_for():
    c = _latent_case(3, 2, 1, 4, 24, 16, 8, 20)
    with pytest.raises(ValueError, match="scale must be given"):
        decode_attention(c["q"], c["cache"], c["index"], block_table=c["table"], latent_v=16, interpret=True)
    with pytest.raises(ValueError, match="multiple of 128"):
        decode_attention(c["q"], c["cache"], c["index"], block_table=c["table"], latent_v=16, scale=1.0, interpret=False)


# --- refill in chunks, then decode, through the latent paged cache -------------


def _served_logits(cfg, params, rows, tables):
    """Each row of ``rows`` (token arrays) through refill chunks of
    ``CHUNK`` and then one-token steps for its last 6 tokens, all rows in
    every call with their own lengths, under block ``tables``: the logits at
    every position, to hold against the reference's full forward."""
    dcfg = dataclasses.replace(
        derive_decode_config(cfg), decode_ragged=True, decode_paged=True,
        decode_page_count=PAGES, decode_block_k=PAGE,
    )
    apply = jax.jit(make_cached_apply(Transformer(dcfg)))
    b = len(rows)
    _, cache = apply(params, None, jnp.zeros((b, CHUNK), jnp.int32), jnp.zeros((b,), jnp.int32))
    return _continue(apply, params, cache, rows, tables)


def _continue(apply, params, cache, rows, tables, reset=None):
    b = len(rows)

    def leaf(path, x):
        return jnp.asarray(tables) if getattr(path[-1], "key", None) == "block_table" else x

    cache = jax.tree_util.tree_map_with_path(leaf, cache)
    if reset is not None:
        cache = _reset_rows(cache, jnp.asarray(reset))
    done = [0] * b
    prefill = [len(r) - 6 if r is not None else 0 for r in rows]
    out = [np.zeros((len(r), HF["vocab_size"]), np.float32) if r is not None else None for r in rows]
    while any(d < len(r) for d, r in zip(done, rows) if r is not None):
        refilling = any(d < p for d, p in zip(done, prefill))
        width = CHUNK if refilling else 1
        chunk, lengths = np.zeros((b, width), np.int32), np.zeros((b,), np.int32)
        for i, r in enumerate(rows):
            if r is None:
                continue
            n = min(width, prefill[i] - done[i]) if refilling else int(done[i] < len(r))
            chunk[i, :n], lengths[i] = r[done[i] : done[i] + n], n
        logits, cache = apply(params, cache, jnp.asarray(chunk), jnp.asarray(lengths))
        for i, n in enumerate(lengths):
            if n:
                out[i][done[i] : done[i] + n] = np.asarray(logits[i, :n])
                done[i] += n
    return out, cache, apply


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)])
def test_refill_and_decode_through_the_latent_paged_cache_match_the_reference(dtype, tol):
    cfg = _config(dtype=dtype, param_dtype=dtype, moe_experts="pallas")
    params = _params(cfg, seed=2)
    # Row 0 crosses three page boundaries and ends mid-page; row 1 is shorter
    # than a chunk; pages are scattered over the pool, page 0 is scratch.
    rows = [_tokens(11, (29,)), _tokens(12, (11,))]
    tables = np.zeros((2, HF["max_position_embeddings"] // PAGE), np.int32)
    tables[0, :4], tables[1, :2] = [3, 1, 5, 2], [4, 7]
    got, cache, apply = _served_logits(cfg, params, rows, tables)
    for row, logits in zip(rows, got):
        assert np.abs(logits - _reference(params, row[None])[0]).max() < tol
    stats = [x for p, x in jax.tree_util.tree_flatten_with_path(cache)[0] if getattr(p[-1], "key", None) == "moe_stats"]
    assert len(stats) == 1 and int(stats[0][0]) == (29 + 11) * HF["num_experts_per_tok"]
    # Row 1 retires; a new request takes ITS slot and row 0's freed pages in
    # another order, while row 0 sits frozen: nothing of the old rows shows.
    new = [None, _tokens(13, (23,))]
    tables[0, :4], tables[1, :3] = 0, [2, 4, 3]
    got, _, _ = _continue(apply, params, cache, new, tables, reset=[False, True])
    assert np.abs(got[1] - _reference(params, new[1][None])[0]).max() < tol


def test_a_dropped_shared_expert_fails_the_bf16_tolerance():
    cfg = _config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, moe_experts="ragged")
    params = _params(cfg, seed=2)
    rows = [_tokens(11, (29,))]
    tables = np.zeros((1, HF["max_position_embeddings"] // PAGE), np.int32)
    tables[0, :4] = [3, 1, 5, 2]
    want = _reference(params, rows[0][None])[0]
    dropped = jax.tree.map(lambda x: x, params)
    dropped["block_1"]["moe"]["shared"] = jax.tree.map(jnp.zeros_like, params["block_1"]["moe"]["shared"])
    got, _, _ = _served_logits(cfg, dropped, rows, tables)
    assert np.abs(got[0] - want).max() > BF16_TOL


# --- the engine ----------------------------------------------------------------


def _engine(**kw):
    cfg = _config(dtype=jnp.float32, moe_experts="pallas")
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    args = dict(batch_size=2, max_new_tokens=4, refill_chunk=CHUNK, paged_pages=PAGES, page_size=PAGE)
    args.update(kw)
    mesh = args.pop("mesh", mesh)
    return cfg, ContinuousEngine(cfg, mesh, RULES_TP_SERVING, **args)


@pytest.mark.parametrize(
    "kw,match",
    [
        (dict(prefix_cache=True), "prefix_cache is not supported with latent attention"),
        (dict(mixed=True), "mixed / horizon is not supported"),
        (dict(dequantize=True), "dequantize is not supported"),
        (dict(draft_config="self"), "speculative decoding"),
        (dict(mesh="two"), "more than one device"),
    ],
)
def test_the_engine_refuses_what_cannot_take_the_latent_row(kw, match):
    if kw.get("draft_config") == "self":
        kw = dict(draft_config=_config())
    if kw.get("mesh") == "two":
        kw = dict(mesh=build_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match=match):
        _engine(**kw)


def test_the_config_refuses_an_int8_latent_cache_and_the_engine_a_handoff():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _config(kv_cache_dtype=jnp.int8)
    _, eng = _engine(paged_pages=None)
    with pytest.raises(ValueError, match="latent-attention engines are not supported"):
        eng.export_kv(0)
