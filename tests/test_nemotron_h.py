"""NVIDIA-Nemotron-3-Super's mechanisms at the benchmark's ``rehearse`` size,
on seeded weights, against the plain reference
(``benchmark/families/nemotron_h_reference.py``: float32, the Mamba
recurrence token by token, every held expert visited): one mixer a layer,
the recurrent state beside the paged cache through packed refill rows and
decode steps, latent relu^2 experts under sigmoid routing, and the chip's
SHARE of the experts. Logits are compared, never sampled tokens. Each
tolerance carries its reason.
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

from benchmark import reference, spec  # noqa: E402
from benchmark.families import nemotron_h as family  # noqa: E402
from benchmark.families.nemotron_h_reference import _moe  # noqa: E402
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
from learning_jax_sharding_tpu.ops.moe_experts import routed_experts  # noqa: E402
from learning_jax_sharding_tpu.parallel import build_mesh  # noqa: E402
from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING  # noqa: E402

#: float32 program against the float32 reference: both round every matmul
#: once and sum in another order; the largest departure measured over the
#: cases below was 5e-7 on logits of magnitude 0.2.
F32_TOL = 2e-5

_FILE = json.loads(
    (REPO / "benchmark" / "configs" / "nemotron-3-super-120b-a12b.json").read_text()
)
#: The file at its rehearsal size (pattern MEM*E, 4 of 8 experts held), the
#: scan's tile cut to the tests' chunk.
HF = {**spec._merge(_FILE, _FILE["rehearse"]), "chunk_size": 8}
DIMS = family.model_dims(HF)
PAGE, CHUNK, PAGES, SLOTS = 8, 8, 24, 4


def _config(**over):
    return family.to_config(HF, **over)


def _params(cfg, seed=0):
    params = nn.meta.unbox(jax.jit(Transformer(cfg).init)(
        {"params": jax.random.key(seed)}, np.zeros((2, 8), np.int32)
    ))["params"]

    # Flax starts a bias at zero; give the selection bias what moves picks
    # and the convolution's bias what its term needs to be checked.
    def noise(path, x):
        name = jax.tree_util.keystr(path)
        if not name.endswith("['bias']"):
            return x
        key = jax.random.fold_in(jax.random.key(seed + 1), len(name))
        return (0.1 * jax.random.normal(key, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(noise, params)


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, HF["vocab_size"], (n,)).astype(np.int32)


def _reference(params, tokens, dims=DIMS):
    return family.reference_fn(dims)(params, jnp.asarray(tokens))


# --- the normal path, and the file -------------------------------------------------


def test_the_file_holds_the_published_widths_and_names_its_cuts():
    published = json.loads(next(
        line for line in open("/opt/skills/guides/model-configs/architectures.jsonl")
        if "NVIDIA-Nemotron-3-Super-120B-A12B-BF16" in line
    ))["config"] if pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl"
    ).exists() else {}
    for key, value in published.items():
        assert _FILE[key] == value or key in _FILE["reduced"], key
    assert sorted(_FILE["reduced"]) == sorted(_FILE["published"]) == sorted(_FILE["changed"])
    assert _FILE["hybrid_override_pattern"] == _FILE["published"]["hybrid_override_pattern"][:11]
    cfg = family.to_config(_FILE)
    assert (cfg.num_experts, cfg.moe_held, cfg.vocab_size) == (512, (0, 128), 32768)
    assert cfg.param_count == _FILE["parameters"]


def test_one_mixer_a_layer_and_no_position_table():
    cfg = _config()
    params = _params(cfg)
    assert cfg.layer_pattern == "MEM*E" and "pos_embed" not in params
    kinds = [sorted(set(params[f"block_{i}"]) - {"ln"}) for i in range(5)]
    assert kinds == [["ssm"], ["moe"], ["ssm"], ["attn"], ["moe"]]
    moe = params["block_1"]["moe"]
    assert "gate" not in moe and "gate" not in moe["shared"]         # relu^2: no gate
    assert moe["router"]["kernel"].shape == (64, 8) and moe["up"].shape == (4, 32, 64)
    assert cfg.param_count == sum(x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_program_matches_the_reference(seed):
    cfg = _config()
    params = _params(cfg, seed)
    tokens = np.stack([_tokens(seed + 10, 37), _tokens(seed + 20, 37)])
    got = jax.jit(Transformer(cfg).apply)({"params": params}, jnp.asarray(tokens))
    assert np.abs(np.asarray(got) - _reference(params, tokens)).max() < F32_TOL


# --- what seeded weights start from ----------------------------------------------------


def _std(x):
    return float(np.std(np.asarray(x, np.float32)))


@pytest.mark.parametrize("scale", [1.0, 0.25])
def test_ungated_experts_start_at_their_own_fan_in_times_the_scale(scale):
    # lecun_normal on (experts, in, out) counts the expert axis into the
    # fan-in; the relu2 form takes each expert's own, and the file's
    # check.expert_init_scale (which the family passes on) on ``down``.
    cfg = dataclasses.replace(_config(), moe_expert_init_scale=scale)
    moe = _params(cfg)["block_1"]["moe"]
    assert moe["up"].shape == (4, 32, 64) and moe["down"].shape == (4, 64, 32)
    assert abs(_std(moe["up"]) / 32 ** -0.5 - 1) < 0.05
    assert abs(_std(moe["down"]) / (scale * 64 ** -0.5) - 1) < 0.05
    assert _config().moe_expert_init_scale == _FILE["check"]["expert_init_scale"]


def test_a_mixer_blocks_down_projections_have_no_gain_for_a_mean():
    # relu^2 >= 0 and the silu-gated norm's output have a mean that a plain
    # initialiser sends to every token as the same vector: MixerBlock
    # centres the projections that read them, and nothing else.
    params = _params(_config())
    moe, ssm = params["block_1"]["moe"], params["block_0"]["ssm"]
    for kernel, axis in (
        (moe["down"], 1), (moe["shared"]["down"]["kernel"], 0),
        (ssm["out_proj"]["kernel"], 0),
    ):
        assert np.abs(np.asarray(kernel).mean(axis)).max() < 1e-6
    for kernel, axis in ((moe["up"], 1), (ssm["in_proj"]["kernel"], 0)):
        assert np.abs(np.asarray(kernel).mean(axis)).max() > 1e-3


def test_other_users_of_the_shared_modules_keep_their_initialisers():
    # The gated expert form (joyai-llm-flash) and a relu2 FeedForward on
    # its own: lecun_normal as it was, nothing centred.
    from learning_jax_sharding_tpu.models.transformer import FeedForward

    x = jnp.ones((1, 4, 64))
    gated = nn.meta.unbox(DroplessMoE(
        features=64, hidden=64, num_experts=8, top_k=2, shared_experts=1,
    ).init(jax.random.key(0), x))["params"]
    assert abs(_std(gated["down"]) / (8 * 64) ** -0.5 - 1) < 0.05
    assert np.abs(np.asarray(gated["down"]).mean(1)).max() > 1e-4
    ff = nn.meta.unbox(FeedForward(
        features=64, hidden=96, activation="relu2",
    ).init(jax.random.key(0), x))["params"]
    assert np.abs(np.asarray(ff["down"]["kernel"]).mean(0)).max() > 1e-3


# --- the chip's share of the experts -------------------------------------------------


def _layer(held, experts="ragged"):
    first, count = held or (0, 8)
    return DroplessMoE(
        features=64, hidden=64, num_experts=8, top_k=2, shared_experts=1,
        shared_hidden=96, routed_scaling=5.0, held=held, gated=False,
        latent=32, experts=experts,
    ), slice(first, first + count)


def test_the_four_shares_add_up_to_the_uncut_layer():
    x = jax.random.normal(jax.random.key(0), (2, 9, 64))
    whole, _ = _layer(None)
    params = nn.meta.unbox(jax.jit(whole.init)(jax.random.key(1), x))["params"]
    params["bias"] = 0.1 * jax.random.normal(jax.random.key(2), (8,))
    uncut = jax.jit(whole.apply)({"params": params}, x)
    dims = {**DIMS, "held_first": 0, "held_count": 8}
    with jax.default_matmul_precision("highest"):
        assert np.abs(np.asarray(uncut) - np.asarray(_moe(x, params, dims))).max() < F32_TOL
    # What every chip computes alike (the shared expert; the latent
    # projections wrap each share's own routed sum) is counted once.
    zero = jax.tree.map(jnp.zeros_like, params)
    shared = jax.jit(whole.apply)({"params": {**zero, "shared": params["shared"]}}, x)
    total = -3 * shared
    for first in (0, 2, 4, 6):
        layer, mine = _layer((first, 2))
        share = {**params, "up": params["up"][mine], "down": params["down"][mine]}
        part = jax.jit(layer.apply)({"params": share}, x)
        with jax.default_matmul_precision("highest"):
            want = _moe(x, share, {**DIMS, "held_first": first, "held_count": 2})
        assert np.abs(np.asarray(part) - np.asarray(want)).max() < F32_TOL
        total = total + part
    assert np.abs(np.asarray(total) - np.asarray(uncut)).max() < F32_TOL


@pytest.mark.parametrize("backend", ["ragged", "pallas"])
def test_a_pick_outside_the_held_range_reads_no_expert_and_counts_nothing(backend):
    x = jax.random.normal(jax.random.key(0), (6, 32))
    up = jax.random.normal(jax.random.key(1), (2, 32, 64)) / 6
    down = jax.random.normal(jax.random.key(2), (2, 64, 32)) / 8
    weights = jnp.full((6, 2), 0.5)
    # Held: experts 4 and 5 of 8. Tokens 0-2 pick outside; 3 picks one held.
    idx = jnp.asarray([[0, 1], [2, 3], [6, 7], [4, 0], [5, 4], [7, 5]])
    out, stats = routed_experts(x, idx, weights, None, up, down, backend=backend, first=4)
    assert np.asarray(stats).tolist() == [4, 2, 1]          # 4 held picks, 2 experts read
    np.testing.assert_array_equal(np.asarray(out[:3]), 0.0)
    want = 0.5 * jnp.square(jax.nn.relu(x[3] @ up[0])) @ down[0]
    assert np.abs(np.asarray(out[3]) - np.asarray(want)).max() < 1e-5
    nowhere = jnp.asarray([[0, 1]] * 6)
    out, stats = routed_experts(x, nowhere, weights, None, up, down, backend=backend, first=4)
    assert np.asarray(stats).tolist() == [0, 0, 0] and not np.asarray(out).any()


# --- served: packed chunk rows, then decode, through both kinds of state -------------


def _served(cfg):
    """The cached apply over ``SLOTS`` slots with scattered pages, and one
    jitted step of it as ``refill_step`` runs it: admission resets, the
    chunk ROWS (``rows``, ``offsets``) taking their slots' state, the
    slots' state put back."""
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
        for r, (slot, off, toks) in sorted(dispatch.items(), key=lambda kv: kv[1][1]):
            base = pos.get(slot, 0) + off
            for i in range(len(toks)):
                seen[slot, base + i] = np.asarray(logits[r, i])
            done[slot] = max(done.get(slot, 0), off + len(toks))
        for slot, n_tok in done.items():
            pos[slot] = pos.get(slot, 0) + n_tok
    return seen, cache


def _tables():
    tables = np.zeros((SLOTS, HF["max_position_embeddings"] // PAGE), np.int32)
    tables[1, :5], tables[2, :2] = [3, 1, 5, 2, 9], [4, 7]
    return tables


@pytest.mark.parametrize("scan", ["xla", "pallas"])
def test_packed_rows_then_decode_match_the_reference_and_the_unpacked_run(scan, monkeypatch):
    # No option chooses the recurrence's form: stand in for the rule that
    # picks the kernels on a TPU (here they run under the interpreter).
    from learning_jax_sharding_tpu.ops import ssm_scan

    monkeypatch.setattr(ssm_scan, "resolve_backend", lambda **_: scan)
    cfg = _config(decode_attention="blocked")
    params = _params(cfg, seed=2)
    step, create = _served(cfg)
    long, short = _tokens(5, 35), _tokens(6, 13)
    # Slot 1's prompt takes rows 1, 0 and 3 of the first dispatch (out of
    # row order) and row 1 of the second; slot 2's short one its own row.
    packed = [
        {1: (1, 0, long[:8]), 0: (1, 8, long[8:16]), 3: (1, 16, long[16:24]), 2: (2, 0, short[:8])},
        {1: (1, 0, long[24:29]), 2: (2, 0, short[8:11])},
    ]
    unpacked = [
        {1: (1, 0, long[:8]), 2: (2, 0, short[:8])},
        {1: (1, 0, long[8:16]), 2: (2, 0, short[8:11])},
        {1: (1, 0, long[16:24])},
        {1: (1, 0, long[24:29])},
    ]
    cache = create(params, _tables())
    got, cache_p = _run(step, params, cache, packed, reset=(1, 2))
    same, cache_u = _run(step, params, cache, unpacked, reset=(1, 2))
    want = {1: _reference(params, long[None])[0], 2: _reference(params, short[None])[0]}
    for (slot, at), logits in got.items():
        assert np.abs(logits - want[slot][at]).max() < F32_TOL
        np.testing.assert_array_equal(logits, same[slot, at])       # packed == unpacked
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(cache_p)[0], jax.tree.leaves(cache_u)
    ):
        if path[-1].key != "moe_stats":     # fewer dispatches read fewer experts
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Decode through the cache: one token a row, slot 0 idle, slot 3 frozen.
    steps = [{1: (1, 0, long[29 + i: 30 + i]), 2: (2, 0, short[11 + i: 12 + i])} for i in range(2)]
    more, cache_d = _run(step, params, cache_p, steps, width=1)
    for (slot, at), logits in more.items():
        assert np.abs(logits - want[slot][(29 if slot == 1 else 11) + at]).max() < F32_TOL
    # Slot 2 retires; a second request takes its slot and other pages while
    # slot 1 sits frozen: it starts from a zero state, nothing old shows.
    again = _tokens(8, 19)
    tables = _tables()
    tables[2, :3] = [7, 10, 4]
    cache_d = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.asarray(tables) if getattr(p[-1], "key", None) == "block_table" else x,
        cache_d,
    )
    new, _ = _run(step, params, cache_d, [
        {2: (2, 0, again[:8]), 0: (2, 8, again[8:16]), 3: (2, 16, again[16:19])}
    ], reset=(2,))
    want_new = _reference(params, again[None])[0]
    for (_, at), logits in new.items():
        assert np.abs(logits - want_new[at]).max() < F32_TOL


# --- the engine ------------------------------------------------------------------------


def _engine(**kw):
    cfg = _config(decode_attention="blocked")
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    args = dict(batch_size=SLOTS, max_new_tokens=6, refill_chunk=CHUNK, paged_pages=40, page_size=PAGE)
    args.update(kw)
    mesh = args.pop("mesh", mesh)
    return cfg, ContinuousEngine(cfg, mesh, RULES_TP_SERVING, **args)


def test_the_engine_serves_packed_prompts_and_reused_slots_as_the_reference_decides():
    cfg, eng = _engine()
    params = _params(cfg, seed=3)
    prompts = [_tokens(40 + i, n) for i, n in enumerate([29, 11, 40, 5, 17, 33])]
    streams = [np.asarray(s) for s in eng.serve(params, prompts)]
    check = reference.teacher_forced(
        family.reference_fn(DIMS), params, prompts, streams, 1e-4, 64
    )
    assert check["ok"] and check["positions"] == 36, check
    reg = eng.registry.snapshot()
    assert reg["engine_ssm_carried_rows_total"] > 0          # PR 30's packing is on
    assert reg["engine_ssm_state_resets_total"] == 6         # six admissions, four slots
    state = 2 * SLOTS * (4 * 16 * 16 * 4 + 3 * (64 + 2 * 2 * 16) * 4)
    assert reg["engine_ssm_state_bytes"] == state
    assert reg['engine_moe_expert_reads_total{phase="decode"}'] <= 4 * reg[
        'engine_moe_layer_steps_total{phase="decode"}'
    ]                                                        # held experts only


@pytest.mark.parametrize(
    "kw,match",
    [
        (dict(prefix_cache=True), "prefix_cache is not supported with state-space layers.*snapshot"),
        (dict(mixed=True), "mixed / horizon is not supported with state-space layers"),
        (dict(dequantize=True), "dequantize is not supported with state-space layers"),
        (dict(draft_config="self"), "speculative decoding.*no rollback"),
        (dict(mesh="two"), "more than one device.*moe_held"),
        (dict(refill_chunk=2), "refill_chunk .2. must cover the convolution"),
    ],
)
def test_the_engine_refuses_by_name_what_a_recurrent_state_cannot_do_yet(kw, match):
    if kw.get("draft_config") == "self":
        kw = dict(draft_config=_config())
    if kw.get("mesh") == "two":
        kw = dict(mesh=build_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match=match):
        _engine(**kw)


def test_the_engine_refuses_a_handoff_and_a_page_spill():
    _, eng = _engine(paged_pages=None)
    with pytest.raises(ValueError, match="state-space layers are not supported"):
        eng.export_kv(0)
    _, eng = _engine()
    with pytest.raises(ValueError, match="state-space layers are not tiered"):
        eng.spill_page(b"key")
