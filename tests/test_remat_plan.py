"""What ``remat=True`` keeps (PR 38): the plan from bytes, and the model that
follows it.

* the planner (``utils.memory.remat_plan`` / ``remat_budget``) is pure
  arithmetic: nothing at a budget of 0, a superset at every larger budget,
  never over its budget, the byte figures of the two training cells' shapes,
  divided over a mesh the way ``memory_plan`` divides;
* the model follows it: one forward flash ``pallas_call`` a layer in the
  jaxpr of a gradient where the plan keeps the kernel's output, two where it
  keeps nothing;
* the mathematics is the same: loss and gradients agree under "nothing", a
  fitted plan and ``remat=False`` (a GPT-2 block, a ``layer_types`` stack
  with a short convolution, the scanned stack);
* a name is inert outside ``jax.checkpoint``; an explicit ``remat_policy``
  still overrides; ``fit(registry=)`` books what the step kept.
"""

import dataclasses
import functools
from unittest import mock

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_TINY,
    Transformer,
    TransformerConfig,
    block_remat_policies,
    next_token_loss,
)
from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn
from learning_jax_sharding_tpu.utils.memory import (
    REMAT_GROUPS,
    RematScope,
    block_residual_bytes,
    memory_plan,
    remat_budget,
    remat_fixed_bytes,
    remat_plan,
    remat_scope,
)

BF16 = jnp.bfloat16
MB, GB = 1e6, 1e9


def _flash_marker(*a, **k):     # the planner only asks whether one is set
    raise AssertionError("the planner never calls attn_fn")


#: The two training cells' shapes (benchmark/configs/gpt2-large.json and the
#: lfm2-8b-a1b cut: ``layer_types[:6]``, 2 dense layers, 8 of 32 experts
#: held), at the cells' token counts.
GPT2_LARGE = TransformerConfig(
    vocab_size=50257, num_layers=36, features=1280, num_heads=20, head_dim=64,
    hidden=5120, max_seq_len=1024, use_bias=True, dtype=BF16, remat=True,
    attn_fn=_flash_marker,
)
LARGE_TOKENS = 8 * 1024
LFM2_CUT = TransformerConfig(
    vocab_size=16384, num_layers=6, features=2048, num_heads=32,
    num_kv_heads=8, head_dim=64, hidden=7168, max_seq_len=8192, dtype=BF16,
    norm="rmsnorm", rope=True, qk_norm=True, ff_gated=True,
    layer_types=("conv", "conv", "full_attention", "conv", "conv", "conv"),
    first_k_dense=2, num_experts=32, moe_top_k=4, moe_hidden=1792,
    moe_routing="sigmoid_dropless", moe_held=(0, 8), tie_embeddings=True,
    remat=True, attn_fn=_flash_marker,
)
LFM2_TOKENS = 2 * 8192


def _group_bytes(cfg, tokens, group, **kw):
    return sum(
        size
        for i in range(cfg.num_layers)
        for name, size in block_residual_bytes(cfg, i, tokens, **kw).items()
        if name in group
    )


class TestPlanner:
    @pytest.mark.parametrize("cfg,tokens", [
        (GPT2_LARGE, LARGE_TOKENS), (LFM2_CUT, LFM2_TOKENS),
    ], ids=["gpt2-large", "lfm2-cut"])
    @pytest.mark.parametrize("uniform", [False, True], ids=["by_block", "uniform"])
    def test_nothing_at_zero_then_supersets_never_over_budget(
        self, cfg, tokens, uniform
    ):
        if uniform:
            # A scanned stack's blocks are alike: plan over attention blocks.
            cfg = dataclasses.replace(
                cfg, layer_types=None, first_k_dense=0, num_experts=0,
            )
        empty = remat_plan(cfg, tokens, 0.0, uniform=uniform)
        assert empty.saved_bytes == 0 and not any(empty.names)
        before = empty
        for budget in np.linspace(0.01 * GB, 8 * GB, 41):
            plan = remat_plan(cfg, tokens, float(budget), uniform=uniform)
            assert plan == remat_plan(cfg, tokens, float(budget), uniform=uniform)
            assert plan.saved_bytes <= budget and plan.budget_bytes == budget
            assert len(plan.names) == cfg.num_layers
            for kept, earlier in zip(plan.names, before.names):
                assert set(earlier) <= set(kept)
            sizes = [block_residual_bytes(cfg, i, tokens) for i in range(cfg.num_layers)]
            assert plan.saved_bytes == pytest.approx(sum(
                sizes[i][n] for i, kept in enumerate(plan.names) for n in kept
            ))
            if uniform:
                assert len(set(plan.names)) == 1
            before = plan
        assert before.saved_bytes > 0

    def test_the_flash_output_comes_first_in_every_attention_block(self):
        # 36 x 21.6 MB fit 0.8 GB; nothing else of any block is taken before.
        plan = remat_plan(GPT2_LARGE, LARGE_TOKENS, 0.8 * GB)
        assert set(plan.names) == {("flash_out", "flash_lse")}
        # lfm2's one attention layer, then its q / k / v, before any conv.
        plan = remat_plan(LFM2_CUT, LFM2_TOKENS, 0.2 * GB)
        assert plan.names[2][:2] == ("flash_out", "flash_lse")
        assert not any(names for i, names in enumerate(plan.names) if i != 2)

    def test_gpt2_large_bytes_are_the_issues_table(self):
        by = lambda group: _group_bytes(GPT2_LARGE, LARGE_TOKENS, group)  # noqa: E731
        block = block_residual_bytes(GPT2_LARGE, 0, LARGE_TOKENS)
        assert block["flash_out"] + block["flash_lse"] == pytest.approx(21.6 * MB, rel=0.02)
        assert by(("flash_out", "flash_lse")) == pytest.approx(0.78 * GB, rel=0.02)
        assert by(("attn_q", "attn_k", "attn_v")) == pytest.approx(2.27 * GB, rel=0.02)
        assert by(("operator_out",)) == pytest.approx(0.76 * GB, rel=0.02)
        assert by(("ff_up", "ff_gate")) == pytest.approx(3.02 * GB, rel=0.02)
        # All four groups are 6.8 GB; the first two are 3.05.
        assert sum(by(g) for g in REMAT_GROUPS) == pytest.approx(6.8 * GB, rel=0.02)
        plan = remat_plan(GPT2_LARGE, LARGE_TOKENS, 3.06 * GB)
        assert plan.saved_bytes == pytest.approx(3.05 * GB, rel=0.02)
        assert set(plan.names) == {
            ("flash_out", "flash_lse", "attn_q", "attn_k", "attn_v")
        }

    def test_lfm2_cut_bytes_are_the_issues_figures(self):
        by = lambda group: _group_bytes(LFM2_CUT, LFM2_TOKENS, group)  # noqa: E731
        assert by(("flash_out", "flash_lse")) == pytest.approx(68 * MB, rel=0.02)
        assert by(("conv_in_proj",)) == pytest.approx(1.0 * GB, rel=0.02)
        assert by(("ff_up", "ff_gate")) == pytest.approx(0.94 * GB, rel=0.02)
        # An expert layer's feed-forward has no name: models/moe.py is not
        # touched, its hidden stays recomputed.
        for i in range(2, 6):
            assert "ff_up" not in block_residual_bytes(LFM2_CUT, i, LFM2_TOKENS)
        # Five of six operators have no attention.
        assert [
            "flash_out" in block_residual_bytes(LFM2_CUT, i, LFM2_TOKENS)
            for i in range(6)
        ] == [False, False, True, False, False, False]

    def test_divided_over_a_two_by_two_mesh(self):
        whole = block_residual_bytes(GPT2_LARGE, 0, LARGE_TOKENS)
        scope = RematScope(n_data_shards=2, n_model_shards=2, budget_bytes=100 * GB)
        plan = scope.resolve(GPT2_LARGE, 8, 1024)
        # Tokens halve over data; heads and the hidden halve again over
        # model; the operator's output keeps its full width (EMBED is whole).
        shard = block_residual_bytes(
            GPT2_LARGE, 0, LARGE_TOKENS / 2, n_model_shards=2
        )
        for name, size in whole.items():
            split = 2 if name == "operator_out" else 4
            assert shard[name] == pytest.approx(size / split), name
        assert plan.saved_bytes == pytest.approx(36 * sum(shard.values()))
        assert scope.plan is plan

    def test_the_budget_is_what_the_device_leaves(self):
        state = 10.06 * GB
        fixed = remat_fixed_bytes(GPT2_LARGE, LARGE_TOKENS, 1024, state_bytes=state)
        # State, 36 block inputs of 21 MB, the loss head, one block's set.
        assert fixed > state + 36 * LARGE_TOKENS * 1280 * 2
        budget = remat_budget(
            GPT2_LARGE, LARGE_TOKENS, 1024, device_bytes=16 * GB, state_bytes=state,
        )
        assert budget == pytest.approx(0.9 * 16 * GB - fixed)
        # A device that reports nothing and whose kind is unknown: no budget.
        assert remat_budget(
            GPT2_LARGE, LARGE_TOKENS, 1024, device_bytes=None, state_bytes=state,
        ) == 0.0
        # A state that leaves nothing: 0, never negative.
        assert remat_budget(
            GPT2_LARGE, LARGE_TOKENS, 1024, device_bytes=16 * GB, state_bytes=15 * GB,
        ) == 0.0
        scope = RematScope(device_bytes=16 * GB, state_bytes=state)
        plan = scope.resolve(GPT2_LARGE, 8, 1024)
        assert plan.budget_bytes == pytest.approx(budget)
        assert plan.predicted_peak_bytes == pytest.approx(fixed + plan.saved_bytes)
        assert plan.predicted_peak_bytes <= 0.9 * 16 * GB
        assert "remat keeps" in plan.summary()

    def test_memory_plan_prices_a_rematerialized_stack(self):
        everything = memory_plan(dataclasses.replace(GPT2_LARGE, remat=False), 8, 1024)
        nothing = memory_plan(GPT2_LARGE, 8, 1024)
        fitted = memory_plan(GPT2_LARGE, 8, 1024, device_bytes=16 * GB)
        kept = fitted.detail["remat_plan"]
        assert nothing.detail["remat_plan"].saved_bytes == 0
        assert 0 < kept.saved_bytes <= kept.budget_bytes
        assert fitted.saved_activations == pytest.approx(
            nothing.saved_activations + kept.saved_bytes
        )
        assert nothing.saved_activations < fitted.saved_activations
        assert fitted.saved_activations < everything.saved_activations
        # Under 90 % of the chip without the gradients, which die into the
        # update layer by layer and which the budget does not reserve whole.
        assert fitted.total - fitted.grads <= 0.9 * 16 * GB
        explicit = memory_plan(
            dataclasses.replace(GPT2_LARGE, remat_policy="nothing"), 8, 1024,
            device_bytes=16 * GB,
        )
        assert explicit.saved_activations == nothing.saved_activations


# --- the model follows the plan ------------------------------------------------

S = 64
FLASH = make_flash_attn_fn(interpret=True)
TINY = dataclasses.replace(CONFIG_TINY, max_seq_len=S, use_bias=True)
CONV = dataclasses.replace(
    CONFIG_TINY, max_seq_len=S, num_layers=3, norm="rmsnorm", rope=True,
    ff_gated=True, layer_types=("conv", "full_attention", "conv"),
)
KINDS = {
    "gpt2_block_flash": dataclasses.replace(TINY, attn_fn=FLASH),
    "conv_stack": CONV,
    "scan_layers": dataclasses.replace(TINY, scan_layers=True),
}


def _tokens(cfg, b=2, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, S)), jnp.int32)


@functools.lru_cache(maxsize=None)
def _init(cfg, batch):
    return nn.meta.unbox(
        Transformer(cfg).init({"params": jax.random.key(0)}, _tokens(cfg, batch))["params"]
    )


def _params(cfg, tokens):
    # The tree does not depend on the attention backend or on remat: one
    # init a kind, through the dense path (no interpreted kernel).
    plain = dataclasses.replace(cfg, attn_fn=None, remat=False, remat_policy=None)
    return _init(plain, tokens.shape[0])


def _loss_fn(cfg, tokens):
    model = Transformer(cfg)
    batch = {"inputs": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    return lambda p: next_token_loss(model.apply({"params": p}, tokens), batch)


def _grad_jaxpr(cfg, budget):
    tokens = _tokens(cfg)
    params = _params(cfg, tokens)
    scope = RematScope(budget_bytes=budget)
    with remat_scope(scope):
        jaxpr = jax.make_jaxpr(jax.grad(_loss_fn(cfg, tokens)))(params)
    return jaxpr, scope


def _primitives(jaxpr, skip=("name",)):
    """Every equation's primitive, sub-jaxprs included, in order."""
    out = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name not in skip:
                out.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return out


def test_one_forward_flash_call_a_layer_where_the_plan_keeps_its_output():
    cfg = dataclasses.replace(KINDS["gpt2_block_flash"], remat=True)
    layers = cfg.num_layers
    sizes = block_residual_bytes(cfg, 0, 2 * S)
    out_lse = sizes["flash_out"] + sizes["flash_lse"]

    def flash_calls(budget):
        jaxpr, scope = _grad_jaxpr(cfg, budget)
        return _primitives(jaxpr).count("pallas_call"), scope.plan

    # Forward, recomputed forward, dK/dV, dQ: four calls a layer.
    calls, plan = flash_calls(0.0)
    assert calls == 4 * layers and not any(plan.names)
    # The kernel's output and log-sum-exp kept in every block: three.
    calls, plan = flash_calls(layers * out_lse)
    assert set(plan.names) == {("flash_out", "flash_lse")}
    assert calls == 3 * layers
    # Room for one block only: that block's forward runs once.
    calls, plan = flash_calls(out_lse)
    assert plan.names == (("flash_out", "flash_lse"), ())
    assert calls == 4 * layers - 1
    # (No scope around the trace, a bare apply: no policy at all,
    # test_an_explicit_policy_overrides_the_plan's last line.)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_loss_and_gradients_agree_whatever_is_kept(kind):
    base = KINDS[kind]
    tokens = _tokens(base, seed=3)
    params = _params(base, tokens)

    def value_and_grad(cfg, budget=None):
        scope = RematScope(budget_bytes=budget)
        with remat_scope(scope):
            loss, grads = jax.jit(jax.value_and_grad(_loss_fn(cfg, tokens)))(params)
        return loss, grads, scope.plan

    want_loss, want, _ = value_and_grad(base)
    everything = sum(
        sum(block_residual_bytes(base, i, 2 * S).values())
        for i in range(base.num_layers)
    )
    rematerialized = dataclasses.replace(base, remat=True)
    cases = {
        "nothing": (dataclasses.replace(rematerialized, remat_policy="nothing"), None),
        "fitted_part": (rematerialized, 0.55 * everything),
    }
    if kind == "scan_layers":       # the cheapest kind also keeps everything
        cases["fitted_all"] = (rematerialized, everything)
    kept = {}
    for case, (cfg, budget) in cases.items():
        loss, grads, plan = value_and_grad(cfg, budget)
        kept[case] = plan
        np.testing.assert_allclose(loss, want_loss, rtol=1e-6, err_msg=case)
        for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)
        ):
            np.testing.assert_allclose(
                g, w, atol=1e-6, rtol=1e-5, err_msg=f"{case} {path}"
            )
    assert kept["nothing"] is None          # an explicit policy asks no plan
    assert 0 < kept["fitted_part"].saved_bytes < everything
    if "fitted_all" in kept:
        assert kept["fitted_all"].saved_bytes == pytest.approx(everything)
        assert all(kept["fitted_all"].names)


def _without_names():
    """The model as it was before it named anything."""
    same = lambda x, name: x  # noqa: E731
    return [
        mock.patch(f"learning_jax_sharding_tpu.{mod}.checkpoint_name", same)
        for mod in (
            "models.transformer", "models.attention", "models.ssm",
            "ops.flash_attention",
        )
    ]


@pytest.mark.parametrize("kind", ["gpt2_block_flash", "conv_stack"])
def test_a_name_is_inert_without_remat(kind):
    """``remat=False``: the gradient's jaxpr holds the names and, beside
    them, the very equations of a model that names nothing."""
    cfg = KINDS[kind]
    named, _ = _grad_jaxpr(cfg, 1e12)
    assert "name" in _primitives(named, skip=())
    patches = _without_names()
    for p in patches:
        p.start()
    try:
        bare, _ = _grad_jaxpr(cfg, 1e12)
    finally:
        for p in patches:
            p.stop()
    assert "name" not in _primitives(bare, skip=())
    assert _primitives(named) == _primitives(bare)


def test_a_name_is_inert_in_a_decode_apply():
    """``decode=True`` (what the serving programs trace): the same
    equations with or without the names, and the same lowered program."""
    cfg = dataclasses.replace(CONFIG_TINY, decode=True, remat=True)
    tokens = _tokens(cfg)[:, :8]
    model = Transformer(cfg)
    variables = model.init({"params": jax.random.key(0)}, tokens)

    def apply(v):
        return model.apply(v, tokens, mutable=["cache"])

    def traced():
        return jax.make_jaxpr(apply)(variables), jax.jit(apply).lower(variables).as_text()

    named, named_text = traced()
    patches = _without_names()
    for p in patches:
        p.start()
    try:
        bare, bare_text = traced()
    finally:
        for p in patches:
            p.stop()
    assert _primitives(named) == _primitives(bare)
    assert "remat" not in " ".join(_primitives(named))
    assert named_text == bare_text


def test_an_explicit_policy_overrides_the_plan():
    cfg = dataclasses.replace(KINDS["gpt2_block_flash"], remat=True)
    for name, policy in (
        ("nothing", None),
        ("dots", jax.checkpoint_policies.checkpoint_dots),
        ("dots_no_batch", jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims),
    ):
        explicit = dataclasses.replace(cfg, remat_policy=name)
        scope = RematScope(budget_bytes=1e12)
        with remat_scope(scope):
            assert block_remat_policies(explicit, 2, S) == [policy] * cfg.num_layers
        assert scope.plan is None
    # "nothing" under a scope with room for everything: still four calls.
    jaxpr, scope = _grad_jaxpr(dataclasses.replace(cfg, remat_policy="nothing"), 1e12)
    assert _primitives(jaxpr).count("pallas_call") == 4 * cfg.num_layers
    assert scope.plan is None
    # None: one policy object a distinct set of names, None where nothing.
    with remat_scope(RematScope(budget_bytes=1e12)):
        policies = block_remat_policies(cfg, 2, S)
    assert policies[0] is policies[1] is not None
    assert block_remat_policies(cfg, 2, S) == [None, None]      # no scope


def test_the_train_step_resolves_the_plan_from_what_it_can_see():
    """On the emulated CPU mesh the device reports nothing the plan could
    spend: ``make_train_step``'s scope has no device bytes, the plan is
    empty (``fit(registry=)`` books it as 0 / 0:
    ``tests/test_lfm2_moe.py``). Given a budget (a test's handle, no
    caller's), the same step keeps what fits and the update is the same."""
    import optax

    from learning_jax_sharding_tpu.parallel import build_mesh, mesh_sharding, put
    from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
    from learning_jax_sharding_tpu.training.pipeline import (
        make_train_step,
        sharded_train_state,
    )

    cfg = dataclasses.replace(TINY, remat=True)
    mesh = build_mesh((2, 2), ("data", "model"))
    tokens = _tokens(cfg, b=4)
    sh = mesh_sharding(mesh, "data", None)
    batch = {"inputs": put(tokens, sh), "targets": put(jnp.roll(tokens, -1, 1), sh)}
    updated = {}
    for budget in (None, 1e12):
        state, state_sh = sharded_train_state(
            Transformer(cfg), optax.adamw(1e-2), batch["inputs"],
            {"params": jax.random.key(0)}, mesh, RULES_DP_TP,
        )
        step = make_train_step(
            state_sh, {k: sh for k in batch}, mesh, RULES_DP_TP,
            loss_fn=next_token_loss,
        )
        assert step.remat.plan is None and step.remat.device_bytes is None
        assert (step.remat.n_data_shards, step.remat.n_model_shards) == (2, 2)
        step.remat.budget_bytes = budget
        new_state, loss = step(state, batch)
        updated[budget] = (float(loss), new_state.params)
        plan = step.remat.plan
        # A device's share of params and AdamW's two moments, from shapes.
        assert step.remat.state_bytes == pytest.approx(
            sum(
                x.addressable_shards[0].data.nbytes
                for x in jax.tree.leaves(new_state)
            )
        )
        if budget is None:
            assert plan.saved_bytes == 0 and plan.budget_bytes == 0
        else:
            assert all(plan.names) and plan.saved_bytes > 0
    assert updated[None][0] == pytest.approx(updated[1e12][0], rel=1e-6)
    for a, b in zip(*(jax.tree.leaves(updated[k][1]) for k in (None, 1e12))):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)
