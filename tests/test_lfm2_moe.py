"""LFM2-8B-A1B's mechanisms at the benchmark's ``rehearse`` size, on seeded
weights, against the plain reference
(``benchmark/families/lfm2_moe_reference.py``: float32, the convolution a sum
over its taps, every held expert visited): the operator by layer (gated
short convolution | grouped-query attention with q / k norms), dense layers
then dropless experts, a tied head, the chip's SHARE of the experts, and the
train step's side of it: gradients through the routed experts under both
backends, a selection bias no optimizer step moves, the routing counters.
Logits and gradients are compared, never sampled tokens. Each tolerance
carries its reason.
"""

import dataclasses
import functools
import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import spec  # noqa: E402
from benchmark.families import lfm2_moe as family  # noqa: E402
from benchmark.families import lfm2_moe_reference as ref_mod  # noqa: E402
from learning_jax_sharding_tpu.models.convert import config_from_hf_lfm2_moe  # noqa: E402
from learning_jax_sharding_tpu.models.moe import DroplessMoE  # noqa: E402
from learning_jax_sharding_tpu.models.transformer import (  # noqa: E402
    Transformer,
    next_token_loss,
)
from learning_jax_sharding_tpu.ops.moe_experts import routed_experts  # noqa: E402

#: float32 program against the float32 reference: both round every matmul
#: once and sum in another order. The largest departure measured over the
#: cases below was 4e-7 on logits of magnitude 0.3 and 2e-6 of a gradient
#: leaf's largest entry; a bf16 router reads 4e-4 and a dropped 1e-6 3e-4
#: (``test_a_bf16_router_and_a_dropped_renorm_eps_fail``).
F32_TOL = 2e-5

_FILE = json.loads((REPO / "benchmark" / "configs" / "lfm2-8b-a1b.json").read_text())
HF = spec._merge(_FILE, _FILE["rehearse"])
DIMS = family.model_dims(HF)
B, S = 2, 32


def _config(**over):
    return family.to_config(HF, **over)


def _tokens(seed=0):
    return jax.random.randint(jax.random.key(seed), (B, S + 1), 0, HF["vocab_size"])


def _unboxed(tree):
    import flax.linen as nn

    return nn.meta.unbox(tree)


@functools.lru_cache(maxsize=None)
def _params(seed=1):
    """The rehearsal model's seeded parameters (the tree does not depend on
    the expert backend)."""
    init = jax.jit(lambda key, x: Transformer(_config()).init({"params": key}, x)["params"])
    return _unboxed(init(jax.random.key(seed), _tokens()[:, :-1]))


@functools.lru_cache(maxsize=None)
def _unstepped_paths():
    """The leaves the model ITSELF boxes as moved by no optimizer step
    (``parallel.logical.Unstepped``), by key path."""
    from learning_jax_sharding_tpu.parallel.logical import Unstepped

    boxed = jax.eval_shape(
        lambda: Transformer(_config()).init(
            {"params": jax.random.key(0)}, _tokens()[:, :-1]
        )["params"]
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(
        boxed, is_leaf=lambda box: isinstance(box, Unstepped)
    )
    return {jax.tree_util.keystr(p) for p, box in flat if isinstance(box, Unstepped)}


def is_selection_bias(path):
    return jax.tree_util.keystr(path) in _unstepped_paths()


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))


def test_the_rehearsal_size_keeps_the_published_pattern():
    cfg = _config()
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv", "conv", "conv")
    assert cfg.first_k_dense == 2 and cfg.tie_embeddings and cfg.qk_norm
    assert cfg.moe_held == (0, 4) and cfg.num_experts == 8 and cfg.moe_renorm_eps == 1e-6
    params = _params()
    assert "lm_head" not in params and "ff" in params["block_1"] and "moe" in params["block_2"]
    assert params["block_2"]["moe"]["gate"].shape[0] == 4        # held, not routed over
    assert params["block_2"]["moe"]["router"]["kernel"].shape[1] == 8
    counted = sum(x.size for x in jax.tree.leaves(params))
    assert counted == cfg.param_count


def test_param_count_at_the_published_widths():
    cfg = family.to_config(_FILE)
    assert cfg.param_count == _FILE["parameters"] == 568_647_936


@pytest.mark.parametrize("backend", ["ragged", "pallas"])
def test_program_matches_the_reference_in_logits_and_gradients(backend):
    """The float32 program (either expert backend, the Pallas calls under the
    interpreter) against the plain reference: logits, and the gradient of the
    next-token loss with respect to every parameter but the selection bias
    (whose gradient is zero in both: it only selects)."""
    cfg = _config(moe_experts=backend)
    params = _params()
    tokens = _tokens()
    batch = {"targets": tokens[:, 1:]}
    ref = ref_mod.reference_fn(DIMS)
    model = Transformer(cfg)

    def loss_program(p):
        return next_token_loss(model.apply({"params": p}, tokens[:, :-1]), batch)

    def loss_reference(p):
        return next_token_loss(ref(p, tokens[:, :-1]), batch)

    with jax.default_matmul_precision("highest"):
        logits = jax.jit(model.apply)({"params": params}, tokens[:, :-1])
        assert _rel(logits, ref(params, tokens[:, :-1])) < F32_TOL
        got = jax.jit(jax.grad(loss_program))(params)
        want = jax.jit(jax.grad(loss_reference))(params)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        if is_selection_bias(path):
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert np.asarray(w).any(), path                 # nothing compared is dead
        assert _rel(g, w) < F32_TOL, (jax.tree_util.keystr(path), _rel(g, w))


def _layer(held, **over):
    fields = dict(
        features=HF["hidden_size"], hidden=HF["moe_intermediate_size"],
        num_experts=8, top_k=2, renorm_eps=1e-6, bias_init_std=0.05,
        held=held, experts="ragged",
    )
    fields.update(over)
    return DroplessMoE(**fields)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Chips 0..3 of a group hold experts [0,2) .. [6,8) of 8: their outputs
    add up to the uncut layer's, and their expert gradients are the uncut
    layer's, slice by slice; the router's gradient adds up too."""
    x = jax.random.normal(jax.random.key(3), (B, S, HF["hidden_size"]))
    tgt = jax.random.normal(jax.random.key(4), x.shape)
    whole = _layer(None)
    p_whole = _unboxed(whole.init(jax.random.key(5), x)["params"])

    def share(p, first):
        q = dict(p)
        for name in ("gate", "up", "down"):
            q[name] = p[name][first:first + 2]
        return q

    def loss(layer, p):
        return jnp.sum(layer.apply({"params": p}, x) * tgt)

    @jax.jit
    def everything(p_whole):
        out = whole.apply({"params": p_whole}, x)
        g_whole = jax.grad(lambda p: loss(whole, p))(p_whole)
        parts, grads = 0.0, []
        for first in (0, 2, 4, 6):
            layer, p = _layer((first, 2)), share(p_whole, first)
            parts = parts + layer.apply({"params": p}, x)
            grads.append(jax.grad(lambda p: loss(layer, p))(p))
        return out, g_whole, parts, grads

    with jax.default_matmul_precision("highest"):
        out, g_whole, parts, grads = everything(p_whole)
    for first, g in zip((0, 2, 4, 6), grads):
        for name in ("gate", "up", "down"):
            assert _rel(g[name], g_whole[name][first:first + 2]) < F32_TOL
    g_router = sum(g["router"]["kernel"] for g in grads)
    assert _rel(parts, out) < F32_TOL
    assert _rel(g_router, g_whole["router"]["kernel"]) < F32_TOL


def _dense_loop(x, idx, w, wg, wu, wd, first=0):
    out = jnp.zeros(x.shape, jnp.float32)
    for j in range(wu.shape[0]):
        y = (jax.nn.silu(x @ wg[j]) * (x @ wu[j])) @ wd[j]
        out = out + jnp.sum(jnp.where(idx == j + first, w, 0.0), -1)[:, None] * y
    return out


@pytest.mark.parametrize("backend", ["pallas", "ragged"])
@pytest.mark.parametrize("routing", ["an_expert_nobody_picked", "every_pick_on_one_expert"])
def test_vjp_of_routed_experts_matches_autodiff_of_a_dense_loop(backend, routing):
    """The Pallas VJP (interpreted) and XLA's own derivative of the
    ``ragged`` backend against ``jax.grad`` of a loop over experts: inputs,
    combine weights and all three matrices. Held range [2, 6) of 8: picks
    outside it send no gradient; an expert nobody picked gets zeros; with
    every pick on ONE expert (the most uneven routing) every token still
    gets its expert's output: nothing is dropped."""
    t, d, f, k = 40, 32, 128, 2
    ks = jax.random.split(jax.random.key(11), 7)
    x = jax.random.normal(ks[0], (t, d))
    wg, wu = (jax.random.normal(kk, (4, d, f)) / d**0.5 for kk in ks[1:3])
    wd = jax.random.normal(ks[3], (4, f, d)) / f**0.5
    w = jax.random.uniform(ks[4], (t, k), minval=0.2)
    tgt = jax.random.normal(ks[5], (t, d))
    if routing == "an_expert_nobody_picked":
        a = jax.random.randint(ks[6], (t, 1), 0, 5)          # some picks outside [2, 6)
        idx = jnp.concatenate([a, a + 3], -1)
        idx = jnp.where(idx == 5, 4, idx)                    # expert 5: held, never picked
    else:
        idx = jnp.stack([jnp.full((t,), 3), jnp.full((t,), 7)], -1)   # 7 is held elsewhere

    def loss(fn, *args):
        return jnp.sum(fn(*args) * tgt)

    def program(x, w, wg, wu, wd):
        out, stats = routed_experts(x, idx, w, wg, wu, wd, backend=backend, first=2)
        return out

    def plain(x, w, wg, wu, wd):
        return _dense_loop(x, idx, w, wg, wu, wd, first=2)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda *a: loss(program, *a), argnums=range(5)))(x, w, wg, wu, wd)
        want = jax.jit(jax.grad(lambda *a: loss(plain, *a), argnums=range(5)))(x, w, wg, wu, wd)
        out = program(x, w, wg, wu, wd)
    for g, r in zip(got, want):
        assert _rel(g, r) < F32_TOL
    if routing == "an_expert_nobody_picked":
        assert not np.asarray(got[2][3]).any() and not np.asarray(got[4][3]).any()   # expert 5
    else:
        assert np.asarray(out).any(axis=-1).all()            # no token without its expert
        assert not np.asarray(got[1][:, 1]).any()            # the pick held elsewhere: no gradient


def test_a_bf16_router_and_a_dropped_renorm_eps_fail(monkeypatch):
    """Both departures move an expert layer's output past ``F32_TOL``. The
    scores are made small (a constant feature the router weighs by -7, so
    sigmoid ~ 1e-3 and a pick sum ~ 2e-3) so that the 1e-6 in the sum is a
    5e-4 of it: at scores near a half it would hide under float32 rounding."""
    m = HF["hidden_size"]
    x = jax.random.normal(jax.random.key(3), (B, S, m)).at[..., 0].set(1.0)
    layer = _layer((0, 4), bias_init_std=0.0)
    params = _unboxed(layer.init(jax.random.key(5), x)["params"])
    params["router"]["kernel"] = params["router"]["kernel"].at[0].set(-7.0)
    dims = {**DIMS, "top_k": 2, "num_experts": 8, "held_first": 0, "held_count": 4}
    with jax.default_matmul_precision("highest"):
        out = layer.apply({"params": params}, x)
        assert _rel(out, ref_mod._moe(x, params, dims)) < F32_TOL
        monkeypatch.setattr(ref_mod, "_RENORM_EPS", 1e-20)
        dropped = _rel(out, ref_mod._moe(x, params, dims))
        program_bf16 = _rel(
            _layer((0, 4), bias_init_std=0.0, router_dtype=jnp.bfloat16).apply(
                {"params": params}, x
            ),
            out,
        )
    assert dropped > 5 * F32_TOL and program_bf16 > 5 * F32_TOL


def test_fit_books_the_routing_counters_and_never_moves_the_selection_bias():
    """Three steps of ``fit(registry=, step_kwargs={"routing_stats": True})``
    under ``default_optimizer`` (AdamW with weight decay), every block
    rematerialized. The counters:
    assignments to held experts, held experts touched and the largest load,
    counted on the device inside the step. The selection bias: seeded, it
    moves picks, and it is the ONE leaf the three steps leave as it was (no
    gradient, no decay)."""
    from learning_jax_sharding_tpu.data import SyntheticLMDataset
    from learning_jax_sharding_tpu.parallel import single_device_mesh
    from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
    from learning_jax_sharding_tpu.telemetry import MetricsRegistry
    from learning_jax_sharding_tpu.training.loop import TrainLoopConfig, fit

    cfg, steps = _config(remat=True), 3
    module = Transformer(cfg)
    loop = TrainLoopConfig(
        steps=steps, global_batch_size=B, learning_rate=1e-2, weight_decay=0.1, seed=1
    )
    registry = MetricsRegistry()
    state, _ = fit(
        module, SyntheticLMDataset(vocab_size=HF["vocab_size"], seq_len=S, seed=7),
        single_device_mesh(), RULES_DP_TP, loop,
        registry=registry, step_kwargs={"routing_stats": True},
    )
    snap = registry.snapshot()
    layers, tokens, k = 4, B * S, HF["num_experts_per_tok"]
    assigned = snap["train_moe_held_assignments_total"]
    assert 0 < snap["train_moe_experts_touched_total"] <= steps * layers * 4
    assert 0 < snap["train_moe_expert_max_load"] <= tokens
    # Near the uniform expectation (half the experts are held): seeded weights.
    assert 0.25 < assigned / (steps * layers * tokens * k) < 0.75
    # What ``remat=True`` kept (PR 38): on the emulated CPU mesh the step
    # finds no memory it could count on, so nothing, of a budget of nothing.
    assert snap["train_remat_saved_bytes"] == 0
    assert snap["train_remat_budget_bytes"] == 0

    assert _unstepped_paths() == {
        f"['block_{i}']['moe']['bias']" for i in range(2, 6)
    }
    before = jax.tree.map(np.asarray, _params(loop.seed))       # fit's own initial state
    after = jax.tree.map(np.asarray, state.params)
    assert np.std(before["block_2"]["moe"]["bias"]) > 0     # seeded, not zeros
    moved = jax.tree_util.tree_map_with_path(
        lambda p, a, b: (is_selection_bias(p), bool(np.any(a != b))), before, after
    )
    for is_bias, changed in jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple)):
        assert changed != is_bias                            # decayed or stepped: all but it

    # It selects: the same weights with the bias scaled and negated pick others.
    flipped = jax.tree_util.tree_map_with_path(
        lambda p, v: -20 * v if is_selection_bias(p) else v, before
    )
    apply = jax.jit(module.apply)
    out_a, out_b = (apply({"params": p}, _tokens()[:, :-1]) for p in (before, flipped))
    assert _rel(out_a, out_b) > 1e-3


def test_the_bias_is_unstepped_wherever_the_layer_is_mounted():
    """The mark is the module's own box, not a name: a ``DroplessMoE`` under
    any name, any optimizer handed to ``sharded_train_state``."""
    import flax.linen as nn
    import optax

    from learning_jax_sharding_tpu.parallel import mesh_sharding, put, single_device_mesh
    from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
    from learning_jax_sharding_tpu.training.pipeline import (
        make_train_step,
        sharded_train_state,
    )

    class Mounted(nn.Module):
        @nn.compact
        def __call__(self, x):
            return _layer(None, name="experts_elsewhere")(nn.Dense(x.shape[-1])(x))

    mesh = single_device_mesh()
    x = put(
        np.asarray(jax.random.normal(jax.random.key(3), (B, S, HF["hidden_size"]))),
        mesh_sharding(mesh, "data", None, None),
    )
    module, optimizer = Mounted(), optax.adamw(1e-2, weight_decay=0.1)
    made = [
        sharded_train_state(module, optimizer, x, {"params": jax.random.key(5)}, mesh, RULES_DP_TP)
        for _ in range(2)
    ]
    (state, state_sh), (again, _) = made
    # A state made again is the same tree TYPE: one compiled step serves both.
    assert jax.tree.structure(state) == jax.tree.structure(again)
    before = jax.tree.map(np.asarray, state.params)
    step = make_train_step(state_sh, x.sharding, mesh, RULES_DP_TP)
    for _ in range(2):
        state, _ = step(state, x)
    after = jax.tree.map(np.asarray, state.params)
    moved = {
        jax.tree_util.keystr(p): bool(np.any(a != b))
        for (p, a), b in zip(
            jax.tree_util.tree_flatten_with_path(before)[0], jax.tree.leaves(after)
        )
    }
    assert np.std(before["experts_elsewhere"]["bias"]) > 0
    assert [k for k, m in moved.items() if not m] == ["['experts_elsewhere']['bias']"]


def _published(**over):
    keys = {k: v for k, v in _FILE.items() if isinstance(k, str)}
    keys.update(_FILE["published"])
    keys.update(over)
    return types.SimpleNamespace(**keys)


def test_the_converter_maps_the_published_keys_and_refuses_what_it_does_not_compute():
    cfg = config_from_hf_lfm2_moe(_published())
    assert (cfg.num_layers, cfg.features, cfg.hidden, cfg.moe_hidden) == (24, 2048, 7168, 1792)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.first_k_dense) == (32, 4, 2)
    assert cfg.layer_types.count("full_attention") == 6 and cfg.conv_kernel == 3
    assert cfg.rope and cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5 and cfg.qk_norm
    assert cfg.tie_embeddings and cfg.moe_shared_experts == 0 and cfg.vocab_size == 65536
    assert cfg.moe_routing == "sigmoid_dropless" and cfg.moe_renorm_eps == 1e-6
    for bad in (
        dict(conv_bias=True), dict(norm_topk_prob=False), dict(use_expert_bias=False),
        dict(rope_scaling={"rope_type": "yarn"}), dict(num_hidden_layers=23),
    ):
        with pytest.raises(ValueError, match="unsupported lfm2_moe settings"):
            config_from_hf_lfm2_moe(_published(**bad))
    with pytest.raises(ValueError, match="unknown operators"):
        config_from_hf_lfm2_moe(_published(layer_types=["conv", "sliding_attention"] * 12))
    with pytest.raises(ValueError, match="no cached form"):
        dataclasses.replace(cfg, decode=True)
