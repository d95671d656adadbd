"""HF GPT-2 interop: converted weights reproduce the torch model's logits.

Built on randomly initialized ``transformers`` models — no downloads, so the
oracle runs in this network-isolated environment; real checkpoints convert
through the identical path. Tolerances reflect torch-CPU vs XLA matmul
accumulation-order noise (~2e-3 over two layers), not model disagreement —
argmax agreement is asserted exactly.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from learning_jax_sharding_tpu.models.convert import (  # noqa: E402
    config_from_hf_gpt2,
    params_from_hf_gpt2,
)
from learning_jax_sharding_tpu.models.transformer import Transformer  # noqa: E402


@pytest.fixture(scope="module")
def hf_pair():
    torch.manual_seed(0)
    hf_cfg = transformers.GPT2Config(
        n_layer=2, n_embd=64, n_head=4, vocab_size=128, n_positions=64,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    cfg = config_from_hf_gpt2(hf_cfg)
    return hf, cfg, params_from_hf_gpt2(hf)


def _tokens(b=2, s=16, seed=0, v=128):
    return np.random.default_rng(seed).integers(0, v, (b, s))


class TestGPT2Conversion:
    def test_logits_match_torch(self, hf_pair):
        hf, cfg, params = hf_pair
        tok = _tokens()
        with torch.no_grad():
            want = hf(torch.tensor(tok)).logits.numpy()
        got = np.asarray(
            Transformer(cfg).apply({"params": params}, jnp.asarray(tok, jnp.int32)),
            np.float32,
        )
        np.testing.assert_allclose(got, want, atol=5e-3)
        np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))

    def test_config_mapping(self, hf_pair):
        hf, cfg, _ = hf_pair
        assert cfg.vocab_size == 128 and cfg.num_layers == 2
        assert cfg.features == 64 and cfg.num_heads == 4 and cfg.head_dim == 16
        assert cfg.hidden == 256 and cfg.max_seq_len == 64
        assert cfg.use_bias and cfg.norm_eps == hf.config.layer_norm_epsilon
        assert not cfg.rope

    def test_unsupported_activation_rejected(self):
        hf_cfg = transformers.GPT2Config(activation_function="relu")
        with pytest.raises(ValueError, match="activation"):
            config_from_hf_gpt2(hf_cfg)

    def test_unsupported_attention_variants_rejected(self):
        for flag in ("scale_attn_by_inverse_layer_idx", "reorder_and_upcast_attn"):
            hf_cfg = transformers.GPT2Config(**{flag: True})
            with pytest.raises(ValueError, match=flag):
                config_from_hf_gpt2(hf_cfg)

    def test_n_inner_and_untied_head_honored(self):
        torch.manual_seed(2)
        hf_cfg = transformers.GPT2Config(
            n_layer=1, n_embd=32, n_head=2, vocab_size=64, n_positions=32,
            n_inner=96, tie_word_embeddings=False,
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
        )
        hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
        cfg = config_from_hf_gpt2(hf_cfg)
        assert cfg.hidden == 96
        params = params_from_hf_gpt2(hf)
        assert params["block_0"]["ff"]["up"]["kernel"].shape == (32, 96)
        tok = _tokens(b=2, s=8, seed=4, v=64)
        with torch.no_grad():
            want = hf(torch.tensor(tok)).logits.numpy()
        got = np.asarray(
            Transformer(cfg).apply({"params": params}, jnp.asarray(tok, jnp.int32)),
            np.float32,
        )
        np.testing.assert_allclose(got, want, atol=5e-3)
        # Exporting an untied head as tied would drop trained weights:
        # the default must refuse, and tie_head=False must round-trip.
        from learning_jax_sharding_tpu.models.convert import (
            state_dict_from_params,
        )

        with pytest.raises(ValueError, match="tie_head=False"):
            state_dict_from_params(params)
        hf2 = transformers.GPT2LMHeadModel(hf_cfg).eval()
        hf2.load_state_dict(
            state_dict_from_params(params, tie_head=False), strict=False
        )
        with torch.no_grad():
            back = hf2(torch.tensor(tok)).logits.numpy()
        np.testing.assert_allclose(back, want, atol=1e-5)

    def test_converted_model_serves_through_the_stack(self, mesh22, hf_pair):
        """The point of interop: a converted checkpoint runs the framework's
        own serving path (sharded KV-cached generation) unchanged."""
        from learning_jax_sharding_tpu.models.generate import make_generate_fn
        from learning_jax_sharding_tpu.parallel import mesh_sharding, put
        from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP

        hf, cfg, params = hf_pair
        prompt_np = _tokens(b=4, s=8, seed=3)
        prompt = put(
            prompt_np.astype(np.int32), mesh_sharding(mesh22, "data", None)
        )
        gen = make_generate_fn(cfg, mesh22, RULES_DP_TP, max_new_tokens=8)
        out = np.asarray(gen(params, prompt))
        assert out.shape == (4, 16)
        np.testing.assert_array_equal(out[:, :8], prompt_np)
        assert ((0 <= out) & (out < cfg.vocab_size)).all()

    def test_export_round_trip(self, hf_pair):
        """params → state dict → fresh HF model: logits identical to the
        original torch model (tied head re-tied by HF on load)."""
        from learning_jax_sharding_tpu.models.convert import (
            state_dict_from_params,
        )

        hf, cfg, params = hf_pair
        sd = state_dict_from_params(params)
        hf2 = transformers.GPT2LMHeadModel(hf.config).eval()
        hf2.load_state_dict(sd, strict=False)
        hf2.tie_weights()
        tok = _tokens(seed=9)
        with torch.no_grad():
            want = hf(torch.tensor(tok)).logits.numpy()
            got = hf2(torch.tensor(tok)).logits.numpy()
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_decode_cache_matches_full_forward(self, hf_pair):
        """Chunked decode through the converted model equals its own full
        forward — biases and norm eps flow through the cache path too."""
        import dataclasses

        hf, cfg, params = hf_pair
        tok = jnp.asarray(_tokens(b=2, s=12, seed=5), jnp.int32)
        full = Transformer(cfg).apply({"params": params}, tok)
        dec_model = Transformer(dataclasses.replace(cfg, decode=True))
        logits, variables = dec_model.apply(
            {"params": params}, tok[:, :6], mutable=("cache",)
        )
        outs = [logits]
        for i in range(6, 12):
            logits, variables = dec_model.apply(
                {"params": params, **variables}, tok[:, i : i + 1],
                mutable=("cache",),
            )
            outs.append(logits)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(full, np.float32), atol=2e-4
        )


class TestNemotronHConfig:
    """``config_from_hf_nemotron_h`` on the benchmark's own file (the keys of
    the model's ``config.json``): what it maps, and what it refuses by name."""

    @staticmethod
    def _hf(**over):
        import json
        import pathlib
        import types

        path = pathlib.Path(__file__).resolve().parents[1] / (
            "benchmark/configs/nemotron-3-super-120b-a12b.json"
        )
        keys = {k: v for k, v in json.loads(path.read_text()).items() if not isinstance(v, dict)}
        return types.SimpleNamespace(**{**keys, **over})

    def test_the_published_keys_map_onto_one_mixer_a_layer(self):
        from learning_jax_sharding_tpu.models.convert import config_from_hf_nemotron_h

        cfg = config_from_hf_nemotron_h(self._hf())
        assert cfg.layer_pattern == "MEMEMEM*EME" and cfg.num_layers == 11
        assert cfg.no_positions and not cfg.rope and cfg.norm == "rmsnorm"
        assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
        assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state_size) == (128, 64, 8, 128)
        assert (cfg.ssm_conv_kernel, cfg.ssm_chunk, cfg.norm_eps) == (4, 128, 1e-5)
        assert (cfg.moe_top_k, cfg.moe_hidden, cfg.moe_latent, cfg.moe_shared_hidden) == (22, 2688, 1024, 5376)
        assert cfg.moe_expert_act == "relu2" and cfg.moe_routed_scaling == 5.0
        # The file's count is the experts HELD; the family module gives the
        # router its published width (benchmark/families/nemotron_h.py).
        assert cfg.num_experts == 128 and cfg.moe_held is None

    @pytest.mark.parametrize(
        "over,match",
        [
            (dict(hybrid_override_pattern="MEMEMEM-EME"), "dense MLP"),
            (dict(hybrid_override_pattern="MEMEMEMXEME"), r"unknown layer kinds \['X'\]"),
            (dict(hybrid_override_pattern="MEM"), "hybrid_override_pattern/num_hidden_layers"),
            (dict(n_group=2), "n_group/topk_group"),
            (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
            (dict(sliding_window=4096), "sliding_window"),
            (dict(use_conv_bias=False), "use_conv_bias"),
            (dict(expand=3), "expand"),
        ],
    )
    def test_what_the_program_does_not_compute_is_refused_by_name(self, over, match):
        from learning_jax_sharding_tpu.models.convert import config_from_hf_nemotron_h

        with pytest.raises(ValueError, match=match):
            config_from_hf_nemotron_h(self._hf(**over))


def test_lfm2_reference_and_program_match_transformers_at_a_tiny_dense_size():
    """``transformers``' ``Lfm2Model`` (the dense sibling of ``lfm2_moe``: the
    same gated short convolution, attention with q / k norms before RoPE,
    SwiGLU, layer and final norm; 4.57.6 has no ``lfm2_moe``) on seeded
    weights against the benchmark's plain reference with every layer dense,
    and against the program reading the same tree. float32 on both sides,
    two libraries' summation orders: 2e-5 of the largest logit (measured
    3e-7)."""
    import dataclasses
    import pathlib
    import sys

    lfm2 = pytest.importorskip("transformers.models.lfm2")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmark.families.lfm2_moe_reference import reference_fn
    from learning_jax_sharding_tpu.models.transformer import TransformerConfig

    d, heads, kv, inter, vocab = 64, 4, 2, 128, 96
    kinds = ("conv", "full_attention", "conv")
    hf_cfg = lfm2.Lfm2Config(
        vocab_size=vocab, hidden_size=d, intermediate_size=inter,
        num_hidden_layers=len(kinds), num_attention_heads=heads,
        num_key_value_heads=kv, max_position_embeddings=128, norm_eps=1e-5,
        rope_theta=1e6, conv_bias=False, conv_L_cache=3,
        block_auto_adjust_ff_dim=False, layer_types=list(kinds),
        tie_word_embeddings=True,
    )
    hf_cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = lfm2.Lfm2Model(hf_cfg).eval().float()
    with torch.no_grad():          # norm weights are ones at init: make them matter
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.1 * torch.randn_like(p))
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}

    def lin(name):
        return {"kernel": sd[name + ".weight"].T}

    params = {
        "tok_embed": {"embedding": sd["embed_tokens.weight"]},
        "ln_out": {"scale": sd["embedding_norm.weight"]},
    }
    for i, kind in enumerate(kinds):
        p = f"layers.{i}."
        blk = {
            "ln_ff": {"scale": sd[p + "ffn_norm.weight"]},
            "ff": {
                "gate": lin(p + "feed_forward.w1"), "up": lin(p + "feed_forward.w3"),
                "down": lin(p + "feed_forward.w2"),
            },
        }
        if kind == "conv":
            blk["ln_conv"] = {"scale": sd[p + "operator_norm.weight"]}
            blk["conv"] = {
                "in_proj": lin(p + "conv.in_proj"), "out_proj": lin(p + "conv.out_proj"),
                "conv": {"kernel": sd[p + "conv.conv.weight"][:, 0, :].T},   # (M,1,L) -> (L,M)
            }
        else:
            blk["ln_attn"] = {"scale": sd[p + "operator_norm.weight"]}
            blk["attn"] = {
                "query": lin(p + "self_attn.q_proj"), "key": lin(p + "self_attn.k_proj"),
                "value": lin(p + "self_attn.v_proj"), "out": lin(p + "self_attn.out_proj"),
                "q_norm": {"scale": sd[p + "self_attn.q_layernorm.weight"]},
                "k_norm": {"scale": sd[p + "self_attn.k_layernorm.weight"]},
            }
        params[f"block_{i}"] = blk
    dims = {
        "layer_types": kinds, "num_dense_layers": len(kinds), "num_heads": heads,
        "num_kv_heads": kv, "head_dim": d // heads, "norm_eps": 1e-5, "rope_theta": 1e6,
    }
    tokens = _tokens(2, 24, v=vocab)
    with torch.no_grad():
        hidden = model(torch.tensor(tokens)).last_hidden_state.numpy()
    want = hidden @ sd["embed_tokens.weight"].T

    def rel(got):
        return np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want))

    assert rel(reference_fn(dims)(params, jnp.asarray(tokens))) < 2e-5
    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=len(kinds), layer_types=kinds, features=d,
        num_heads=heads, num_kv_heads=kv, head_dim=d // heads, hidden=inter,
        max_seq_len=128, norm="rmsnorm", norm_eps=1e-5, rope=True, rope_theta=1e6,
        qk_norm=True, ff_gated=True, tie_embeddings=True, dtype=jnp.float32,
    )
    assert dataclasses.replace(cfg).param_count == sum(
        np.size(x) for x in jax.tree.leaves(params)
    )
    with jax.default_matmul_precision("highest"):
        logits = Transformer(cfg).apply({"params": params}, jnp.asarray(tokens))
    assert rel(logits) < 2e-5
