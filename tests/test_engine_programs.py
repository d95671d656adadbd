"""The engine's program table (models/engine_programs.py).

Every consumer of "which programs exist" maps over one table built for
the engine's mode. These cases pin, per mode and WITHOUT dispatching
anything, what no other test holds together: the table has exactly the
families that mode can dispatch; every jitted function keeps the name
XLA's module names (and so the benchmark's trace metrics) are matched
by; ``contract_name`` maps every entry to the golden it always had; and
``compile_counts()`` lists, before any dispatch, the keys it always did.
The expectations are literals read off the engine as it was before the
table existed.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.analysis import GOLDEN_DIR
from learning_jax_sharding_tpu.models.engine_programs import build_programs
from learning_jax_sharding_tpu.models.serving import ContinuousEngine
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_TINY,
    Transformer,
    TransformerConfig,
)
from learning_jax_sharding_tpu.parallel import build_mesh
from learning_jax_sharding_tpu.parallel.compression import CommCompression
from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING
from learning_jax_sharding_tpu.telemetry.compile_watch import cache_size
from learning_jax_sharding_tpu.tenancy import AdapterPool

TINY = dataclasses.replace(
    CONFIG_TINY, dtype=jnp.float32, decode_attention="blocked"
)
DRAFT = dataclasses.replace(TINY, num_layers=1, hidden=64)
LATENT = TransformerConfig(
    vocab_size=128, num_layers=2, features=32, num_heads=2, hidden=64,
    max_seq_len=64, dtype=jnp.float32, norm="rmsnorm", rope=True,
    latent_kv_rank=16, latent_q_rank=8, qk_nope_dim=8, qk_rope_dim=8,
    v_head_dim=8, ff_gated=True, first_k_dense=1, num_experts=4,
    moe_top_k=2, moe_hidden=32, moe_routing="sigmoid_dropless",
    moe_shared_experts=1,
)
#: One mixer a layer (the nemotron_h family at small widths): a recurrent
#: state a slot beside the attention layer's pages.
RECURRENT = TransformerConfig(
    vocab_size=128, num_layers=4, layer_pattern="ME*M", features=32,
    num_heads=2, head_dim=16, num_kv_heads=1, hidden=64, max_seq_len=64,
    dtype=jnp.float32, norm="rmsnorm", no_positions=True, num_experts=4,
    moe_top_k=2, moe_hidden=32, moe_routing="sigmoid_dropless",
    moe_shared_experts=1, moe_expert_act="relu2", moe_latent=16,
    moe_held=(0, 2), ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
    ssm_state_size=8, ssm_chunk=8, decode_attention="blocked",
)

SPLIT = ("first_refill", "refill_step")
HANDOFF = ("kv_export", "kv_ingest")
TIER = ("kv_page_spill", "kv_page_fill")
BASE = {
    "first_refill": "first_prefill", "refill_step": "prefill",
    "decode_block": "decode_step", "decode_block_spec": "decode_step",
}


def _expect(spec, pool, mixed, *, paged=False, prefix=False, latent=False):
    """``(families in table order, {family: fn.__name__}, families that
    compile_counts lists before any dispatch)`` for one mode."""
    decode = ("decode_block_spec", "decode_block") if spec else ("decode_block",)
    fams = [*SPLIT, *decode]
    names = {f: f for f in fams}
    steady = [*SPLIT, decode[0]]
    if mixed:
        tenant, s = "adapter_" if pool else "", "spec_" if spec else ""
        for kind in ("mixed_step", "multi_step"):
            fams.append(f"{tenant}{kind}")
            names[f"{tenant}{kind}"] = f"{tenant}{s}{kind}"
        steady.append(f"{tenant}mixed_step")
    if not (spec or pool or paged or latent):
        fams += HANDOFF
    if paged and prefix and not spec:
        fams += TIER
    names.update({f: f for f in (*HANDOFF, *TIER) if f in fams})
    return fams, names, steady


def _modes():
    """id -> (constructor keywords, the mode as ``_expect`` reads it)."""
    modes = {}
    for spec in (False, True):
        for pool in (False, True):
            for sched, kw in (
                ("split", {}), ("mixed", {"mixed": True}),
                ("mixed-h4", {"mixed": True, "horizon": 4}),
            ):
                if pool and not kw:
                    continue        # adapter_pool requires mixed=True
                name = "-".join([
                    "spec" if spec else "plain", *(["pool"] if pool else []),
                    sched,
                ])
                modes[name] = (
                    dict(kw, spec=spec, pool=pool),
                    (spec, pool, bool(kw), {}),
                )
    paged = dict(paged_pages=9, page_size=16)
    modes["plain-paged"] = (paged, (False, False, False, dict(paged=True)))
    modes["plain-paged-prefix"] = (
        dict(paged, prefix_cache=True),
        (False, False, False, dict(paged=True, prefix=True)),
    )
    modes["spec-paged-prefix-mixed"] = (
        dict(paged, prefix_cache=True, mixed=True, spec=True),
        (True, False, True, dict(paged=True, prefix=True)),
    )
    modes["latent-dropless"] = (
        dict(paged, cfg=LATENT),
        (False, False, False, dict(paged=True, latent=True)),
    )
    # No contiguous rows to hand off either: the state would have to travel.
    modes["recurrent-paged"] = (
        dict(paged, cfg=RECURRENT),
        (False, False, False, dict(paged=True, latent=True)),
    )
    modes["recurrent-contiguous"] = (
        dict(cfg=RECURRENT), (False, False, False, dict(latent=True)),
    )
    return modes


MODES = _modes()


@pytest.fixture(scope="module")
def mesh():
    return build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def tiny_params():
    return nn.meta.unbox(
        Transformer(TINY).init(
            {"params": jax.random.key(0)}, np.zeros((2, 8), np.int32)
        )["params"]
    )


@pytest.fixture
def make_engine(mesh, tiny_params):
    def build(kw, **more):
        kw = dict(kw, **more)
        cfg = kw.pop("cfg", TINY)
        if kw.pop("spec", False):
            kw["draft_config"] = DRAFT
        if kw.pop("pool", False):
            kw["adapter_pool"] = AdapterPool(tiny_params, slots=2, rank=2)
        return ContinuousEngine(
            cfg, mesh, RULES_TP_SERVING, batch_size=2, max_new_tokens=4,
            refill_chunk=8, decode_block_steps=2, **kw,
        )

    return build


@pytest.mark.parametrize("mode", MODES)
def test_table_holds_the_modes_families_under_their_names(make_engine, mode):
    kw, (spec, pool, mixed, more) = MODES[mode]
    fams, names, steady = _expect(spec, pool, mixed, **more)
    eng = make_engine(kw)
    assert list(eng._programs) == fams
    assert {f: p.fn.__name__ for f, p in eng._programs.items()} == names
    assert all(p.family == f for f, p in eng._programs.items())
    # One fused step and one scanned step at most.
    assert sum(f.endswith("mixed_step") for f in eng._programs) <= 1
    assert sum(f.endswith("multi_step") for f in eng._programs) <= 1
    # Nothing has dispatched: only the steady programs are listed, no
    # slot is filled, nothing is relowerable.
    assert list(eng.compile_counts()) == steady
    assert set(eng.compile_counts().values()) == {0}
    assert all(p.last_args is None for p in eng._programs.values())
    assert eng._dispatched_programs() == []
    with pytest.raises(KeyError):
        eng.program("no_such_program")


@pytest.mark.parametrize("mode", MODES)
def test_contract_names_are_the_goldens(make_engine, mode):
    kw, (spec, *_rest) = MODES[mode]
    eng = make_engine(kw)
    for fam in eng._programs:
        base = BASE.get(fam, fam)
        want = (
            f"spec_{base}" if spec and fam != "decode_block" else base
        )
        assert eng.contract_name(fam) == want
        assert (GOLDEN_DIR / f"{want}.json").is_file(), want


@pytest.mark.parametrize(
    "mode", [m for m in MODES if "latent" not in m and "recurrent" not in m]
)
@pytest.mark.parametrize("collectives", [False, True])
def test_contract_names_under_comm_compression(make_engine, mode, collectives):
    kw, (spec, _pool, mixed, _more) = MODES[mode]
    if collectives and not mixed:
        with pytest.raises(ValueError, match="requires mixed=True"):
            make_engine(kw, comm_compression=True)
        return
    eng = make_engine(
        kw, comm_compression=CommCompression(collectives=collectives)
    )
    for fam, prog in eng._programs.items():
        base = BASE.get(fam, fam)
        if not prog.applies:
            want = f"{base}_q8"         # the KV codec's regime, not spec's
        else:
            q8 = f"{base}_q8" if collectives else base
            want = f"spec_{q8}" if spec and fam != "decode_block" else q8
        assert eng.contract_name(fam) == want
    # A drift trip turns the collective codec off: the apply programs
    # contract under their plain names again, the KV programs stay _q8.
    eng._comp.enabled = False
    for fam, prog in eng._programs.items():
        if prog.applies:
            assert "_q8" not in eng.contract_name(fam)


def test_no_kv_codec_leaves_the_kv_programs_plain(make_engine):
    eng = make_engine(
        dict(paged_pages=9, page_size=16, prefix_cache=True),
        comm_compression=CommCompression(collectives=False, kv_codec=None),
    )
    assert eng.contract_name("kv_page_spill") == "kv_page_spill"
    assert eng.contract_name("kv_page_fill") == "kv_page_fill"


def test_every_jitted_name_is_one_the_trace_metrics_can_match():
    """No built function may be called ``step`` (the trainer's module is
    ``jit_step``) or carry a factory's inner name (``program``, ``run``,
    ``counted``): XLA's module name is ``jit_<__name__>``."""
    seen = set()
    for spec in (None, object()):
        for pool in (False, True):
            for moe in (False, True):
                table = build_programs(
                    object(), spec, adapter=pool, mixed=True, paged=moe,
                    prefix_cache=moe and spec is None, moe_counted=moe,
                    max_new_tokens=4, decode_block_steps=2,
                )
                seen |= {p.fn.__name__ for p in table.values()}
    table = build_programs(object(), max_new_tokens=4, decode_block_steps=2)
    seen |= {p.fn.__name__ for p in table.values()}
    assert seen == {
        "first_refill", "refill_step", "decode_block", "decode_block_spec",
        "mixed_step", "spec_mixed_step", "adapter_mixed_step",
        "adapter_spec_mixed_step", "multi_step", "spec_multi_step",
        "adapter_multi_step", "adapter_spec_multi_step", "kv_export",
        "kv_ingest", "kv_page_spill", "kv_page_fill",
    }


def test_two_engines_share_no_executable_cache(make_engine):
    """A jit cache is keyed by its function: every table is built from
    fresh functions, so one engine's compiles (and a compression trip's
    ``clear_cache``) never show in another's ``compile_counts``."""
    a, b = make_engine({}), make_engine({})
    a.program("kv_export").fn({"x": jnp.zeros((2, 3))}, jnp.int32(0))
    assert cache_size(a.program("kv_export").fn) == 1
    assert cache_size(b.program("kv_export").fn) == 0


# --- donation: a dispatch updates the cache in place -------------------------

#: kind -> (config, engine keywords, the draft's ``max_seq_len`` or None).
_PAGED = dict(paged_pages=9, page_size=16)
DONATING = {
    "split": (TINY, _PAGED, None),
    "contiguous": (TINY, {}, None),
    "int8_kv": (dataclasses.replace(TINY, kv_cache_dtype=jnp.int8), _PAGED, None),
    "speculative_narrower_draft": (TINY, dict(_PAGED, num_draft=2), 32),
    "mixed": (TINY, dict(_PAGED, mixed=True), None),
    "mixed_horizon": (TINY, dict(_PAGED, mixed=True, horizon=4), None),
    "latent_dropless": (LATENT, _PAGED, None),
    "one_mixer_a_layer": (RECURRENT, _PAGED, None),
}


def _init(cfg, seed=0):
    return nn.meta.unbox(
        Transformer(cfg).init(
            {"params": jax.random.key(seed)}, np.zeros((2, 8), np.int32)
        )["params"]
    )


def _is_table(path):
    return getattr(path[-1], "key", None) == "block_table"


def _leaves(tree, tables):
    """``tree``'s block tables, or everything else in it."""
    return [
        x for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
        if _is_table(path) == tables
    ]


def _donating_engine(kind, mesh):
    cfg, kw, draft_len = DONATING[kind]
    d_params = None
    if draft_len:
        draft = dataclasses.replace(DRAFT, max_seq_len=draft_len)
        kw, d_params = dict(kw, draft_config=draft), _init(draft, seed=7)
    eng = ContinuousEngine(
        cfg, mesh, RULES_TP_SERVING, batch_size=2, max_new_tokens=4,
        refill_chunk=8, decode_block_steps=2, **kw,
    )
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in (11, 3, 6)
    ]
    return eng, _init(cfg), d_params, prompts


@pytest.fixture(scope="module")
def mesh22():
    return build_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])


@pytest.mark.parametrize("kind", [*DONATING, "split@2x2", "contiguous@2x2"])
def test_every_step_program_aliases_every_donated_cache_leaf(
    kind, mesh, mesh22
):
    """The compiled executable of every program that takes the cache and
    returns its successor aliases EVERY cache leaf but the block tables to
    an output (``analysis/donation.py``: asked for and applied, none
    dropped); no table is donated or returned; and the gauge
    ``engine_cache_donated_bytes`` is the bytes of those leaves."""
    from learning_jax_sharding_tpu.analysis.donation import report_from_lowered
    from learning_jax_sharding_tpu.parallel.logical import activate

    kind, _, on = kind.partition("@")
    eng, params, d_params, prompts = _donating_engine(
        kind, mesh22 if on else mesh
    )
    assert eng.registry.snapshot()["engine_cache_donated_bytes"] == 0
    eng.serve(params, prompts, draft_params=d_params)
    donated = _leaves(eng._cache, tables=False)
    assert eng.registry.snapshot()["engine_cache_donated_bytes"] == sum(
        x.nbytes for x in donated
    ) > 0
    tables = _leaves(eng._cache, tables=True)
    assert bool(tables) == ("paged_pages" in DONATING[kind][1])
    ran = {name: (fn, args) for name, fn, args in eng._dispatched_programs()}
    assert set(ran) >= (
        {"mixed_step"} if kind == "mixed" else {"multi_step"}
        if kind == "mixed_horizon" else {"refill_step", "decode_block_spec"}
        if "speculative" in kind else {"refill_step", "decode_block"}
    )
    for name, (fn, args) in ran.items():
        if name == "first_refill":
            continue                    # creates the cache, takes none
        with activate(eng._mesh, eng._rules):
            lowered = fn.lower(*args)
            compiled = lowered.compile()
        inputs = report_from_lowered(
            lowered, compiled.as_text(), compiled=compiled
        )["inputs"]
        asked = [i for i in inputs if i["donated"]]
        assert len(asked) == len(donated), name
        assert all(i["verdict"] == "donated" for i in asked), (name, [
            i for i in asked if i["verdict"] != "donated"
        ])
        # The tables ride beside the donated tree, as a list of their own:
        # as many as the cache has, none donated, none among the outputs.
        (beside,) = [a for a in lowered.args_info[0] if isinstance(a, list)]
        assert len(beside) == len(tables), name
        assert not any(i.donated for i in beside), name
        assert not _leaves(lowered.out_info, tables=True), name


@pytest.mark.parametrize("kind", ["split", "speculative_narrower_draft", "mixed"])
def test_a_dispatch_consumes_the_cache_it_was_given(kind, mesh):
    """After a dispatch the tree the engine held before it is gone (every
    leaf but the tables, which were never the program's to take) and
    ``eng._cache`` is the live one."""
    eng, params, d_params, prompts = _donating_engine(kind, mesh)
    for p in prompts:
        eng.add_request(p)
    eng.step(params, d_params)
    while eng.has_work():
        before = eng._cache
        eng.step(params, d_params)
        assert all(x.is_deleted() for x in _leaves(before, tables=False))
        assert not any(x.is_deleted() for x in _leaves(before, tables=True))
        assert not any(x.is_deleted() for x in jax.tree.leaves(eng._cache))


def test_the_cache_moving_programs_donate_too(make_engine, tiny_params):
    """``kv_ingest`` and ``kv_page_fill`` replace the engine's cache and
    take it donated; ``kv_export`` and ``kv_page_spill`` only read it."""
    eng = make_engine({})
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, TINY.vocab_size, size=(9,)).astype(np.int32)
    (out,) = eng.serve(tiny_params, [prompt])
    rows, _ = eng.export_kv(0)
    before = eng._cache
    assert not any(x.is_deleted() for x in jax.tree.leaves(before))
    eng.ingest_kv(tiny_params, prompt, int(out[len(prompt)]), rows, rid=1)
    assert all(x.is_deleted() for x in jax.tree.leaves(before))
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng._cache))

    eng = make_engine(dict(paged_pages=10, page_size=4, prefix_cache=True))
    eng.serve(tiny_params, [prompt])
    (key, *_) = eng.retained_prefixes()
    before = eng._cache
    page, _ = eng.spill_page(key, drop=True)
    assert not any(x.is_deleted() for x in jax.tree.leaves(before))
    eng.fill_page(key, page)
    assert all(x.is_deleted() for x in _leaves(before, tables=False))
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng._cache))


def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr inside it (a jitted call's,
    a scan's body, a Pallas kernel's)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if isinstance(sub, ClosedJaxpr):
                    n += _equations(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    n += _equations(sub)
    return n


#: ``refill_step``'s equations a layer of ``TINY`` (4 rows, chunks of 16,
#: pages of 8) while every paged layer scattered its chunk position by
#: position: read off the program before the whole-page write existed.
SCATTER_EQUATIONS_A_LAYER = 326


def test_refill_step_traces_the_same_handful_of_ops_a_layer(mesh):
    """What set-up pays to trace and lower ``refill_step`` grows with the
    layers by what one layer's ops are, and the whole-page write keeps that
    no larger than the scatter did: no kernel, loop or per-row op a layer
    (a per-row or per-page form multiplies a layer's ops by the rows; each
    of xl's 48 layers pays it again)."""
    from learning_jax_sharding_tpu.models.engine_programs import split_cache
    from learning_jax_sharding_tpu.parallel.logical import activate

    b, chunk = 4, 16
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    rng = jax.eval_shape(lambda: jax.random.key(0))
    counted = {}
    for layers in (2, 8):
        cfg = dataclasses.replace(TINY, num_layers=layers)
        eng = ContinuousEngine(
            cfg, mesh, RULES_TP_SERVING, batch_size=b, max_new_tokens=4,
            refill_chunk=chunk, paged_pages=9, page_size=8,
        )
        assert eng._whole_pages
        params = jax.eval_shape(lambda: nn.meta.unbox(
            Transformer(cfg).init(
                {"params": jax.random.key(0)}, np.zeros((2, 8), np.int32)
            )["params"]
        ))
        with activate(mesh, RULES_TP_SERVING):
            cache = jax.eval_shape(
                eng.program("first_refill").fn,
                params, None, ints(b, chunk), ints(b), ints(b), rng,
            )[1]
            jaxpr = jax.make_jaxpr(eng.program("refill_step").fn)(
                params, None, *split_cache(cache), ints(b, chunk), ints(b),
                jax.ShapeDtypeStruct((b,), jnp.bool_), ints(b), ints(b), rng,
                ints(b), ints(b),
            )
        counted[layers] = _equations(jaxpr.jaxpr)
    a_layer = (counted[8] - counted[2]) / 6
    assert a_layer <= SCATTER_EQUATIONS_A_LAYER + 8, counted
