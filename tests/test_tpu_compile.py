"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached here: the TPU compiler that ships with jaxlib compiles
for a topology that is described, not present (``v5e:2x2``). Interpret-mode
tests cannot see what Mosaic refuses — a misaligned slice, an unsupported
vector cast, too much VMEM — so each kernel the trainer and the engine
dispatch is compiled here with ``interpret=False`` at the widths the chip
runs, and must come out as a ``tpu_custom_call``. Nothing executes: a
compile that passes is not a chip run (``chip_smoke.py`` is). The last
section compiles the engine's step programs and the train step whole, at
small widths, and looks up in them every module and instruction name that
a ``benchmark/metrics/*.json`` file matches in a device trace.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every xdist worker imports
this file.
"""

import dataclasses
import functools
import json
import pathlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from learning_jax_sharding_tpu.ops.decode_attention import (
    auto_block_k,
    decode_attention,
    pages_in_flight,
    ring_pages,
)
from learning_jax_sharding_tpu.ops.flash_attention import flash_attention
from learning_jax_sharding_tpu.ops.fused_norm import fused_residual_norm
from learning_jax_sharding_tpu.ops.int4_ff import int4_ff
from learning_jax_sharding_tpu.ops.int4_matmul import int4_matmul


BF16, F32, I8, I32, U8 = (
    jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32, jnp.uint8
)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device compile can be written to the persistent cache
    # but never read back without a chip; keep it off around these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles_to_kernel(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip and return how many Mosaic
    kernels the optimized program holds (0 = it fell back to plain XLA)."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


@pytest.mark.parametrize(
    "b,s,n,n_kv,h,window",
    [
        (8, 1024, 12, 12, 64, None),     # the 125M train step's shape
        (2, 4096, 16, 16, 128, None),    # long context, head_dim 128
        (2, 4096, 16, 4, 128, None),     # GQA 16/4
        (2, 4096, 16, 16, 128, 1024),    # sliding window
    ],
    ids=["125m-hd64", "s4096-hd128", "gqa16-4", "window1024"],
)
@pytest.mark.parametrize("grads", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention(one_chip, b, s, n, n_kv, h, window, grads):
    def fwd(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, interpret=False
        )

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grads else fwd
    q = ((b, s, n, h), BF16)
    kv = ((b, s, n_kv, h), BF16)
    # fwd is one kernel; the backward adds the dkv and dq kernels.
    assert _compiles_to_kernel(fn, one_chip, q, kv, kv) >= (3 if grads else 1)


# 125M serving shapes: 12 heads x 64, 8 rows, 1024-token rows.
_B, _N, _H, _L = 8, 12, 64, 1024


def _decode_case(
    *, s=1, page=None, fold=False, int8=False, window=None,
    b=_B, n=_N, pool=None, n_kv=None, h=_H, row_enable=False, ring=False,
):
    """(fn, shapes) for one decode_attention variant: at the 125M widths,
    or with ``b``/``n``/``pool`` (and ``n_kv`` KV heads of ``h``) at a
    cell's own. Which form of the kernel a case compiles is the operands'
    to say (``_in_flight``)."""
    cache_dt = I8 if int8 else BF16
    n_q, n = n, n if n_kv is None else n_kv
    if page is None:
        cache = ((b, n, _L, 2 * h), cache_dt)
        scales = ((b, n, _L), F32)
    else:
        pool = b * (_L // page) + 1 if pool is None else pool
        cache = ((pool, n, page, 2 * h), cache_dt)
        scales = ((pool, n, page), F32)
    names = ["q", "kv_cache", "index"]
    shapes = [((b, s, n_q, h), BF16), cache, ((b,), I32)]
    if int8:
        names += ["k_scale", "v_scale"]
        shapes += [scales, scales]
    if fold:
        names += ["kv_new"]
        shapes += [((b, n, 1, 2 * h), cache_dt)]
        if int8:
            names += ["ks_new", "vs_new"]
            shapes += [((b, n, 1), F32)] * 2
        names += ["write_enable"]
        shapes += [((b,), I32)]
    if page is not None:
        names += ["block_table"]
        width = ring_pages(window, page) if ring else _L // page
        shapes += [((b, width), I32)]
    if row_enable:
        names += ["row_enable"]
        shapes += [((b,), I32)]

    def fn(*args):
        kw = dict(zip(names, args))
        return decode_attention(
            kw.pop("q"), kw.pop("kv_cache"), kw.pop("index"),
            window=window, ring=ring, interpret=False, **kw,
        )

    return fn, shapes


def _in_flight(shapes, page=None, int8=False):
    """Cache blocks in flight in a ``_decode_case``: the loop form's ring
    depth, 0 = the emitter form."""
    cache, dtype = shapes[1]
    return pages_in_flight(
        cache, dtype, page or auto_block_k(_L), quantized=int8
    )


# gpt2-xl.chat_backlog's engine: 16 slots, 25 heads x 64, pages of 64, a
# table 16 wide over a 96-page pool.
_XL = dict(b=16, n=25, page=64, pool=96)


@pytest.mark.parametrize(
    "case",
    [
        dict(),
        dict(page=16),
        dict(page=64),
        dict(page=64, fold=True),
        dict(page=64, s=128),
        dict(int8=True),
        dict(page=64, int8=True, fold=True),
        dict(fold=True, int8=True),
        dict(window=256),
        dict(fold=True, **_XL),
        dict(s=128, **_XL),
        dict(fold=True, row_enable=True, **_XL),
        dict(s=128, row_enable=True, **_XL),
    ],
    ids=[
        "per-row", "paged16", "paged64", "paged64-fold", "paged64-chunk128",
        "int8", "paged64-int8-fold", "int8-fold", "window256",
        "xl-cell-fold", "xl-cell-chunk128",
        "xl-cell-fold-row-enable", "xl-cell-chunk128-row-enable",
    ],
)
def test_decode_attention(one_chip, case):
    """bf16 caches of whole tiles compile the LOOP form (its own DMAs from a
    cache left in HBM, eight blocks in flight at xl's 409,600 B a page), int8
    caches the emitter form: their float32 scale arrays are not whole tiles."""
    fn, shapes = _decode_case(**case)
    page, int8 = case.get("page"), case.get("int8", False)
    # Per-row blocks of 256 x 12 heads: 786 KB each, five in the ring.
    assert _in_flight(shapes, page, int8) == (0 if int8 else 8 if page else 5)
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


# joyai-llm-flash.chat_backlog_2k's engine: 32 slots, 32 heads against one
# latent row of 512 + 64 values a token (4.5 lane tiles), pages of 128 in a
# 512-page pool, a table 1,024 wide (131,072 positions).
@pytest.mark.parametrize("s", [1, 128], ids=["fold", "chunk128"])
def test_latent_decode_attention(one_chip, s):
    b, n, r, v, page, pool, t_cap = 32, 32, 576, 512, 128, 512, 1024

    def fn(q, cache, index, table, new, enable):
        fold = dict(kv_new=new, write_enable=enable) if s == 1 else {}
        return decode_attention(
            q, cache, index, block_table=table, row_enable=enable,
            block_k=page, latent_v=v, scale=192**-0.5, interpret=False,
            **fold,
        )

    shapes = [
        ((b, s, n, r), BF16), ((pool, 1, page, r), BF16), ((b,), I32),
        ((b, t_cap), I32), ((b, 1, 1, r), BF16), ((b,), I32),
    ]
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


# The same cell's expert layer: 256 experts of 2048 x 768 (x 3 matrices);
# a decode step's 256 assignments in tiles of 16 rows, a refill chunk's
# 32,768 in tiles of 128.
@pytest.mark.parametrize(
    "tokens,tm", [(32, 16), (4096, 128)], ids=["decode32", "refill4096"]
)
def test_moe_experts(one_chip, tokens, tm):
    from learning_jax_sharding_tpu.ops.moe_experts import (
        routed_experts,
        tile_rows,
    )

    e, d, f, k = 256, 2048, 768, 8
    assert tile_rows(tokens * k, e) == tm

    def fn(x, idx, w, valid, w_gate, w_up, w_down):
        return routed_experts(
            x, idx, w, w_gate, w_up, w_down, valid=valid, backend="pallas",
            interpret=False,
        )

    shapes = [
        ((tokens, d), BF16), ((tokens, k), I32), ((tokens, k), F32),
        ((tokens,), jnp.bool_), ((e, d, f), BF16), ((e, d, f), BF16),
        ((e, f, d), BF16),
    ]
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


# nemotron-3-super-120b-a12b.chat_backlog_2k's engine (PR 33): 128 HELD
# experts of a 512-wide router, ungated (relu^2) in a 1024-wide latent, top
# 22; a decode step's 704 assignments in tiles of 16 rows, a refill
# dispatch's 90,112 in tiles of 128.
@pytest.mark.parametrize(
    "tokens,tm", [(32, 16), (4096, 128)], ids=["decode32", "refill4096"]
)
def test_ungated_latent_experts(one_chip, tokens, tm):
    from learning_jax_sharding_tpu.ops.moe_experts import (
        routed_experts,
        tile_rows,
    )

    held, d, f, k = 128, 1024, 2688, 22
    assert tile_rows(tokens * k, held) == tm

    def fn(x, idx, w, valid, w_up, w_down):
        return routed_experts(
            x, idx, w, None, w_up, w_down, valid=valid, backend="pallas",
            interpret=False, first=0,
        )

    shapes = [
        ((tokens, d), BF16), ((tokens, k), I32), ((tokens, k), F32),
        ((tokens,), jnp.bool_), ((held, d, f), BF16), ((held, f, d), BF16),
    ]
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


# lfm2-8b-a1b.pretrain_8k's expert layer under a train step (PR 35): 8 HELD
# experts of a 32-wide router at 2048 x 1792, float32 parameters read in
# bf16, 2 x 8,192 tokens x top 4 = 65,536 assignments in tiles of 128. The
# forward is the serving call; the backward adds moe.experts_dx and
# moe.experts_dw over the same tile list.
def test_moe_experts_train_step_kernels(one_chip):
    from learning_jax_sharding_tpu.ops.moe_experts import (
        routed_experts,
        tile_rows,
    )

    tokens, held, d, f, k = 16384, 8, 2048, 1792, 4
    assert tile_rows(tokens * k, held) == 128

    def loss(x, w, w_gate, w_up, w_down, idx):
        out, _ = routed_experts(
            x, idx, w, w_gate, w_up, w_down, backend="pallas",
            interpret=False, first=0,
        )
        return out.astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    shapes = [
        ((tokens, d), BF16), ((tokens, k), F32), ((held, d, f), F32),
        ((held, d, f), F32), ((held, f, d), F32), ((tokens, k), I32),
    ]
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 3


# The same cell's Mamba-2 layers: 128 heads x 64 in 8 groups, state 128, the
# state a slot as (64 pairs, 128, 128) float32; 32 rows, one token or one
# 128-token tile a row.
def test_ssm_state_update(one_chip):
    from learning_jax_sharding_tpu.ops.ssm_scan import state_update

    b, h, p, g, n = 32, 128, 64, 8, 128
    shapes = [
        ((b, h // 2, n, 2 * p), F32), ((b, h), F32), ((b, h, p), F32),
        ((b, g, n), F32), ((b, g, n), F32),
    ]
    assert _compiles_to_kernel(
        functools.partial(state_update, interpret=False), one_chip, *shapes
    ) == 1


def test_ssm_chunk_scan(one_chip):
    from learning_jax_sharding_tpu.ops.ssm_scan import chunk_scan

    b, q, h, p, g, n = 32, 128, 128, 64, 8, 128
    d_inner = h * p
    fn = functools.partial(chunk_scan, d_inner=d_inner, grp=g, interpret=False)
    shapes = [
        ((b, q, d_inner + 2 * g * n), BF16), ((b, q, h), F32), ((b, q, h), F32),
        ((b, h // 2, n, 2 * p), F32), ((b,), I32),
    ]
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


# The same cell's one attention layer in eleven: 32 query heads on 2 KV heads
# of 128 (k | v rows of 256), pages of 128 in a 512-page pool.
@pytest.mark.parametrize("s", [1, 128], ids=["fold", "chunk128"])
def test_decode_attention_gqa16_head128(one_chip, s):
    fn, shapes = _decode_case(
        b=32, n=32, n_kv=2, h=128, page=128, pool=512, s=s, fold=s == 1,
    )
    assert _in_flight(shapes, 128) == 8      # the loop form: 131 KB a page
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


# laguna-xs.2.code_mixed_8k's window layers: 64 slots, 64 (or the global
# layers' 48) query heads on 8 KV heads of 128, a window of 512 over pages of
# 128, a ring of 9 pages a slot: groups of 8 and 6 (a chunk tile of 20
# queries, 120 rows: whole sublane tiles).
@pytest.mark.parametrize("n", [64, 48])
@pytest.mark.parametrize("s", [1, 128], ids=["fold", "chunk128"])
def test_window_ring_decode_attention(one_chip, n, s):
    fn, shapes = _decode_case(
        b=64, n=n, n_kv=8, h=128, page=128, pool=64 * 9 + 1, s=s,
        fold=s == 1, row_enable=s > 1, window=512, ring=True,
    )
    assert ring_pages(512, 128) == 9
    assert _in_flight(shapes, 128) == 8      # the loop form: 512 KB a page
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


def test_fused_residual_norm_fwd_bwd(one_chip):
    def loss(x, resid, gamma, beta):
        y, r = fused_residual_norm(x, resid, gamma, beta, interpret=False)
        return (y.astype(jnp.float32) * r.astype(jnp.float32)).sum()

    x = ((8, 1024, 768), BF16)
    vec = ((768,), F32)
    fn = jax.grad(loss, argnums=(0, 1, 2, 3))
    assert _compiles_to_kernel(fn, one_chip, x, x, vec, vec) >= 2


_K, _NOUT, _G = 2048, 8192, 128


@pytest.mark.parametrize("w4a8", [False, True], ids=["bf16", "w4a8"])
def test_int4_matmul(one_chip, w4a8):
    def fn(x, q4, scale):
        return int4_matmul(
            x, q4, scale, group=_G, w4a8=w4a8, interpret=False
        )

    shapes = [
        ((8, _K), BF16), ((_K // 2, _NOUT), U8), ((_K // _G, _NOUT), F32),
    ]
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


def test_int4_ff(one_chip):
    def fn(x, q_up, s_up, q_dn, s_dn):
        return int4_ff(x, q_up, s_up, q_dn, s_dn, group=_G, interpret=False)

    shapes = [
        ((8, _K), BF16),
        ((_K // 2, _NOUT), U8), ((_K // _G, _NOUT), F32),
        ((_NOUT // 2, _K), U8), ((_NOUT // _G, _K), F32),
    ]
    assert _compiles_to_kernel(fn, one_chip, *shapes) == 1


# --- the names the benchmark's trace metrics match ----------------------------
#
# ``benchmark/metrics/*.json`` find their device time by name: a module
# prefix (``jit_decode_block``) and an instruction prefix inside it
# (``attn._blocked_cached_attention``). A rename would not fail anything
# here on the CPU; it would null a metric on the chip. So the whole step
# programs are compiled for the described chip, at widths small enough to
# take seconds, and every name a metric file gives is looked up in them.

_METRICS = pathlib.Path(__file__).resolve().parents[1] / "benchmark" / "metrics"


def _named_by_trace_metrics():
    """``(metric, module prefix, op prefix or None)`` for every metric file
    whose reader matches names in a device trace."""
    out = []
    for path in sorted(_METRICS.glob("*.json")):
        params = json.loads(path.read_text())["params"]
        modules = params.get("modules") or (
            [params["module"]] if "module" in params else []
        )
        for module in modules:
            out.append(pytest.param(
                module, params.get("op"), id=f"{path.stem}-{module}"
            ))
            # A reader that tells a kernel's backward calls from its forward
            # (``trace_roofline_expert_passes``) names them too.
            for op in params.get("backward", []):
                out.append(pytest.param(module, op, id=f"{path.stem}-{module}-{op}"))
    return out


@pytest.fixture(scope="module")
def step_programs(topo):
    """HLO module name -> optimized text of the engine's step programs and
    the train step, compiled for one described chip. Nothing runs."""
    import flax.linen as nn
    import numpy as np
    from flax.training.train_state import TrainState
    from jax.sharding import Mesh

    from learning_jax_sharding_tpu.models.engine_programs import split_cache
    from learning_jax_sharding_tpu.models.serving import ContinuousEngine
    from learning_jax_sharding_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        fused_next_token_loss,
    )
    from learning_jax_sharding_tpu.ops.flash_attention import (
        make_flash_attn_fn,
    )
    from learning_jax_sharding_tpu.parallel import mesh_sharding
    from learning_jax_sharding_tpu.parallel.logical import (
        RULES_DP_TP,
        RULES_TP_SERVING,
        activate,
        tree_shardings,
    )
    from learning_jax_sharding_tpu.training.loop import (
        TrainLoopConfig,
        default_optimizer,
    )
    from learning_jax_sharding_tpu.training.pipeline import make_train_step

    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    mesh = Mesh(np.asarray([dev]).reshape(1, 1), ("data", "model"))
    cfg = TransformerConfig(
        vocab_size=512, num_layers=2, features=128, num_heads=2,
        head_dim=64, hidden=256, max_seq_len=256, dtype=BF16,
        param_dtype=BF16, decode_attention="blocked",
    )
    b, chunk = 4, 128

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree,
        )

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one)

    params = on_chip(jax.eval_shape(lambda: nn.meta.unbox(
        Transformer(cfg).init(
            {"params": jax.random.key(0)}, np.zeros((2, 8), np.int32)
        )["params"]
    )))
    rng = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    flags = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)
    compiled = []
    # The kernels ask the backend whether to interpret: say "tpu" while
    # lowering, as the chip would.
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for mixed in (False, True):
            eng = ContinuousEngine(
                cfg, mesh, RULES_TP_SERVING, batch_size=b, max_new_tokens=8,
                refill_chunk=chunk, paged_pages=9, page_size=64,
                inference_dtype=BF16, mixed=mixed,
            )
            first = (params, None, ints(b, chunk), ints(b), ints(b), rng)
            with activate(mesh, RULES_TP_SERVING):
                cache = on_chip(jax.eval_shape(eng.program("first_refill").fn, *first)[1])
                if mixed:
                    compiled.append(eng.program("mixed_step").fn.lower(
                        params, *split_cache(cache), ints(b, chunk), ints(b),
                        flags, ints(b), ints(b), ints(b), ints(b), ints(b), rng,
                    ))
                    continue
                compiled.append(eng.program("first_refill").fn.lower(*first))
                compiled.append(eng.program("refill_step").fn.lower(
                    params, None, *split_cache(cache), ints(b, chunk), ints(b),
                    flags, ints(b), ints(b), rng, ints(b), ints(b),
                ))
                compiled.append(eng.program("decode_block").fn.lower(
                    params, *split_cache(cache), ints(b), ints(b), ints(b),
                    ints(b), rng,
                ))

        # A latent-attention, dropless-expert engine (the joyai-llm-flash
        # family at small widths; the rank a whole lane tile, as Mosaic asks).
        latent_cfg = TransformerConfig(
            vocab_size=512, num_layers=2, features=128, num_heads=2,
            hidden=256, max_seq_len=256, dtype=BF16, param_dtype=BF16,
            norm="rmsnorm", rope=True, latent_kv_rank=128, latent_q_rank=64,
            qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32, ff_gated=True,
            first_k_dense=1, num_experts=8, moe_top_k=2, moe_hidden=128,
            moe_routing="sigmoid_dropless", moe_shared_experts=1,
        )
        latent_params = on_chip(jax.eval_shape(lambda: nn.meta.unbox(
            Transformer(latent_cfg).init(
                {"params": jax.random.key(0)}, np.zeros((2, 8), np.int32)
            )["params"]
        )))
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            eng = ContinuousEngine(
                latent_cfg, mesh, RULES_TP_SERVING, batch_size=b,
                max_new_tokens=8, refill_chunk=chunk, paged_pages=9,
                page_size=64, inference_dtype=BF16,
            )
            first = (latent_params, None, ints(b, chunk), ints(b), ints(b), rng)
            with activate(mesh, RULES_TP_SERVING):
                cache = on_chip(jax.eval_shape(eng.program("first_refill").fn, *first)[1])
                compiled.append(eng.program("refill_step").fn.lower(
                    latent_params, None, *split_cache(cache), ints(b, chunk),
                    ints(b), flags, ints(b), ints(b), rng, ints(b), ints(b),
                ))
                compiled.append(eng.program("decode_block").fn.lower(
                    latent_params, *split_cache(cache), ints(b), ints(b),
                    ints(b), ints(b), rng,
                ))

        # One mixer a layer (the nemotron_h family at small widths, the
        # Mamba heads and state whole lane tiles so that "auto" picks the
        # kernels): ``ssm.state_update`` and ``ssm.chunk_scan`` by name.
        ssm_cfg = TransformerConfig(
            vocab_size=512, num_layers=3, layer_pattern="ME*", features=128,
            num_heads=2, head_dim=64, num_kv_heads=1, hidden=256,
            max_seq_len=256, dtype=BF16, param_dtype=BF16, norm="rmsnorm",
            no_positions=True, num_experts=8, moe_top_k=2, moe_hidden=128,
            moe_routing="sigmoid_dropless", moe_shared_experts=1,
            moe_expert_act="relu2", moe_latent=128, moe_held=(0, 4),
            ssm_heads=4, ssm_head_dim=64, ssm_groups=2, ssm_state_size=128,
            ssm_chunk=chunk,
        )
        ssm_params = on_chip(jax.eval_shape(lambda: nn.meta.unbox(
            Transformer(ssm_cfg).init(
                {"params": jax.random.key(0)}, np.zeros((2, 8), np.int32)
            )["params"]
        )))
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            eng = ContinuousEngine(
                ssm_cfg, mesh, RULES_TP_SERVING, batch_size=b,
                max_new_tokens=8, refill_chunk=chunk, paged_pages=9,
                page_size=64, inference_dtype=BF16,
            )
            first = (ssm_params, None, ints(b, chunk), ints(b), ints(b), rng)
            with activate(mesh, RULES_TP_SERVING):
                cache = on_chip(jax.eval_shape(eng.program("first_refill").fn, *first)[1])
                compiled.append(eng.program("refill_step").fn.lower(
                    ssm_params, None, *split_cache(cache), ints(b, chunk),
                    ints(b), flags, ints(b), ints(b), rng, ints(b), ints(b),
                ))
                compiled.append(eng.program("decode_block").fn.lower(
                    ssm_params, *split_cache(cache), ints(b), ints(b), ints(b),
                    ints(b), rng,
                ))

        # Window and global layers (the laguna family at small widths, heads
        # of 128 so that the ring's pool takes the loop form): the window
        # layers' call ``attn.window_cached_attention`` beside the global
        # ones' ``attn._blocked_cached_attention``, a per-head gate, partial
        # YaRN rotary, softmax-routed experts.
        from learning_jax_sharding_tpu.ops.rope import Yarn

        window_cfg = TransformerConfig(
            vocab_size=512, num_layers=3, features=128, num_heads=6,
            num_kv_heads=1, head_dim=128, hidden=256, max_seq_len=1024,
            dtype=BF16, param_dtype=BF16, norm="rmsnorm", rope=True,
            rotary_dim=64, rope_yarn=Yarn(factor=4, original_max_positions=256),
            layer_types=("full_attention", "sliding_attention", "full_attention"),
            window=256, window_num_heads=8, window_rope_theta=1e4,
            attn_gate=True, ff_gated=True, first_k_dense=1, num_experts=8,
            moe_top_k=2, moe_hidden=128, moe_routing="softmax_dropless",
            moe_shared_experts=1,
        )
        window_params = on_chip(jax.eval_shape(lambda: nn.meta.unbox(
            Transformer(window_cfg).init(
                {"params": jax.random.key(0)}, np.zeros((2, 8), np.int32)
            )["params"]
        )))
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            eng = ContinuousEngine(
                window_cfg, mesh, RULES_TP_SERVING, batch_size=b,
                max_new_tokens=8, refill_chunk=chunk, paged_pages=17,
                page_size=chunk, inference_dtype=BF16,
            )
            first = (window_params, None, ints(b, chunk), ints(b), ints(b), rng)
            with activate(mesh, RULES_TP_SERVING):
                cache = on_chip(jax.eval_shape(eng.program("first_refill").fn, *first)[1])
                compiled.append(eng.program("refill_step").fn.lower(
                    window_params, None, *split_cache(cache), ints(b, chunk),
                    ints(b), flags, ints(b), ints(b), rng, ints(b), ints(b),
                ))
                compiled.append(eng.program("decode_block").fn.lower(
                    window_params, *split_cache(cache), ints(b), ints(b),
                    ints(b), ints(b), rng,
                ))

        # The train step as benchmark/train.py builds it, its state abstract.
        train_cfg = dataclasses.replace(
            cfg, param_dtype=F32, decode_attention="dense", max_seq_len=1024,
            attn_fn=make_flash_attn_fn(interpret=False),
        )
        module = Transformer(train_cfg)
        optimizer = default_optimizer(
            TrainLoopConfig(steps=10, global_batch_size=2)
        )

        def boxed_init(key, x):
            return TrainState.create(
                apply_fn=module.apply, tx=optimizer,
                params=module.init({"params": key}, x)["params"],
            )

        tokens = jax.ShapeDtypeStruct((2, 1024), I32)
        with activate(mesh, RULES_DP_TP):
            abstract = jax.eval_shape(boxed_init, jax.random.key(0), tokens)
            state_sh = tree_shardings(abstract, mesh, RULES_DP_TP)
            state = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                nn.meta.unbox(abstract), state_sh,
            )
            batch_sh = mesh_sharding(mesh, "data", None)
            batch = {
                k: jax.ShapeDtypeStruct(tokens.shape, I32, sharding=batch_sh)
                for k in ("inputs", "targets")
            }
            step = make_train_step(
                state_sh, {k: batch_sh for k in batch}, mesh, RULES_DP_TP,
                loss_fn=fused_next_token_loss, loss_needs_params=True,
                apply_kwargs={"return_hidden": True},
            )
            compiled.append(step.jitted.lower(state, batch))

        # The lfm2_moe family's train step at small widths (PR 35): a short
        # convolution, GQA with q / k norms, a dense layer then dropless
        # experts held in part, a tied head, remat: ``moe.experts`` forward
        # and its two backward calls by name, inside ``jit_step``.
        lfm2_cfg = TransformerConfig(
            vocab_size=512, num_layers=3, features=128, num_heads=4,
            num_kv_heads=2, head_dim=64, hidden=256, max_seq_len=1024,
            dtype=BF16, param_dtype=F32, norm="rmsnorm", rope=True,
            qk_norm=True, layer_types=("conv", "full_attention", "conv"),
            ff_gated=True, first_k_dense=1, num_experts=8, moe_top_k=2,
            moe_hidden=128, moe_routing="sigmoid_dropless", moe_held=(0, 4),
            moe_renorm_eps=1e-6, tie_embeddings=True, remat=True,
            attn_fn=make_flash_attn_fn(interpret=False),
        )
        lfm2_module = Transformer(lfm2_cfg)

        def lfm2_init(key, x):      # its own function: eval_shape caches by it
            return TrainState.create(
                apply_fn=lfm2_module.apply, tx=optimizer,
                params=lfm2_module.init({"params": key}, x)["params"],
            )

        with activate(mesh, RULES_DP_TP):
            abstract = jax.eval_shape(lfm2_init, jax.random.key(0), tokens)
            state_sh = tree_shardings(abstract, mesh, RULES_DP_TP)
            state = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                nn.meta.unbox(abstract), state_sh,
            )
            step = make_train_step(
                state_sh, {k: batch_sh for k in batch}, mesh, RULES_DP_TP,
                loss_fn=fused_next_token_loss, loss_needs_params=True,
                apply_kwargs={"return_hidden": True},
            )
            compiled.append(step.jitted.lower(state, batch))
    texts = [low.compile().as_text() for low in compiled]
    # Two engines give two programs of one name: a list, not a dict.
    return [
        (re.match(r"HloModule (\S+?),", t).group(1), t, low)
        for t, low in zip(texts, compiled)
    ]


@pytest.mark.parametrize("module,op", _named_by_trace_metrics())
def test_trace_metric_names_exist_in_the_compiled_programs(
    step_programs, module, op
):
    hits = [t for name, t, _ in step_programs if name.startswith(module)]
    assert hits, (
        f"no program named {module}*: {sorted(n for n, *_ in step_programs)}"
    )
    if op is not None:
        # The program of whichever engine runs that op (GPT-2-shaped or
        # latent): one of that name has to hold it, as a Mosaic kernel.
        holding = [
            t for t in hits
            if any(
                n.startswith(op)
                for n in re.findall(r"%([A-Za-z_][\w.\-]*) = ", t)
            )
        ]
        assert holding, f"no instruction named {op}* in {module}"
        assert all("tpu_custom_call" in t for t in holding)


def test_the_chip_compiler_aliases_every_donated_cache_leaf(step_programs):
    """What the CPU tests show of the engine's donation
    (``tests/test_engine_programs.py``) holds for the chip's compiler too:
    each ``refill_step`` / ``decode_block`` / ``mixed_step`` compiled for
    ``v5e:2x2`` aliases every cache leaf it was asked to donate (pools,
    counters, expert counts, recurrent state) to an output, and was asked
    to donate no table."""
    from learning_jax_sharding_tpu.analysis.donation import report_from_lowered

    seen = 0
    for name, text, low in step_programs:
        if name.split(".")[0] not in (
            "jit_refill_step", "jit_decode_block", "jit_mixed_step"
        ):
            continue
        seen += 1
        asked = [
            i for i in report_from_lowered(low, text)["inputs"] if i["donated"]
        ]
        assert asked and all(i["verdict"] == "donated" for i in asked), (
            name, [i for i in asked if i["verdict"] != "donated"]
        )
        # The tables ride as a list of their own, beside the donated tree.
        (tables,) = [a for a in low.args_info[0] if isinstance(a, list)]
        assert tables and not any(i.donated for i in tables), name
    assert seen == 9


def test_refill_step_writes_its_chunk_into_the_pools_in_place(step_programs):
    """No ``refill_step`` compiled for ``v5e:2x2`` relays out a paged pool:
    the GPT-2, state-space and window engines write a chunk as whole pages
    on the pool's leading axis. A position-by-position scatter on pool
    axes 0 and 2 had the compiler copy every attention layer's whole pool
    to another layout and back around it, in every refill dispatch. (The
    compiler also stages a pool through VMEM and writes it back, copies
    that keep the layout: they are not what this pins. The latent engine
    keeps its own write; at these widths it relays out nothing.)"""
    from learning_jax_sharding_tpu.models.engine_programs import (
        _PAGE_LEAF_KEYS,
    )

    def relayouts(line, pools):
        # A copy (an async one, or a fusion the compiler named for one)
        # whose result holds a pool in another layout than its own.
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not m or not (
            m.group(1).startswith("copy")
            or m.group(3) in ("copy", "copy-start")
        ):
            return False
        return any(
            layout != pools[dims]
            for dims, layout in re.findall(r"\w+(\[[\d,]+\])\{([\d,]+)", m.group(2))
            if dims in pools
        )

    seen = 0
    for name, text, low in step_programs:
        if not name.startswith("jit_refill_step"):
            continue
        seen += 1
        # Pool dims -> the row-major layout every argument arrives in.
        pools = {
            "[" + ",".join(map(str, leaf.shape)) + "]":
            ",".join(map(str, reversed(range(len(leaf.shape)))))
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                low.args_info
            )[0]
            if getattr(path[-1], "key", None) in _PAGE_LEAF_KEYS
        }
        assert pools, name
        moved = [
            line.strip()[:200] for line in text.splitlines()
            if relayouts(line, pools)
        ]
        assert not moved, (name, sorted(pools), moved[:4])
    assert seen == 4


def test_the_train_step_keeps_what_its_remat_plan_says(topo):
    """The same train step compiled for one described chip under
    ``remat_policy="nothing"`` and under the plan a budget gives
    (``utils.memory.remat_plan``; the budget set outright, the test's handle:
    a step finds its own from the device). The plan's program holds ONE
    forward flash kernel a layer where "nothing" holds two, and its
    temporaries grow with the plan's bytes and never by more (a residual
    kept in a padded layout would: the kernel's own ``lse`` is 128 x its
    bytes in HBM, its ``out`` twice). By less than all of them at these
    sizes: a block's own residuals are alive in its backward whatever is
    kept, and with 3 blocks that is a third of the plan, the loss head's
    peak beside it (at the cells' 36 blocks the builder's compile read
    1.92 GB of growth for a plan of 2.04 GB: PERF.md, PR 38)."""
    import flax.linen as nn
    import numpy as np
    from flax.training.train_state import TrainState
    from jax.sharding import Mesh

    from learning_jax_sharding_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        fused_next_token_loss,
    )
    from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn
    from learning_jax_sharding_tpu.parallel import mesh_sharding
    from learning_jax_sharding_tpu.parallel.logical import (
        RULES_DP_TP,
        activate,
        tree_shardings,
    )
    from learning_jax_sharding_tpu.training.loop import (
        TrainLoopConfig,
        default_optimizer,
    )
    from learning_jax_sharding_tpu.training.pipeline import make_train_step
    from learning_jax_sharding_tpu.utils.memory import block_residual_bytes

    layers = 3
    mesh = Mesh(np.asarray([topo.devices[0]]).reshape(1, 1), ("data", "model"))
    cfg = TransformerConfig(
        vocab_size=512, num_layers=layers, features=256, num_heads=4,
        head_dim=64, hidden=512, max_seq_len=1024, dtype=BF16,
        param_dtype=F32, remat=True,
        attn_fn=make_flash_attn_fn(interpret=False),
    )
    optimizer = default_optimizer(TrainLoopConfig(steps=10, global_batch_size=8))
    tokens = jax.ShapeDtypeStruct((8, 1024), I32)
    sizes = block_residual_bytes(cfg, 0, 8 * 1024)
    kept_names = ("flash_out", "flash_lse", "attn_q", "attn_k", "attn_v")
    budget = layers * sum(sizes[n] for n in kept_names)

    def compiled(cfg, budget):
        module = Transformer(cfg)

        def init(key, x):
            return TrainState.create(
                apply_fn=module.apply, tx=optimizer,
                params=module.init({"params": key}, x)["params"],
            )

        with activate(mesh, RULES_DP_TP):
            abstract = jax.eval_shape(init, jax.random.key(0), tokens)
            state_sh = tree_shardings(abstract, mesh, RULES_DP_TP)
            state = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                nn.meta.unbox(abstract), state_sh,
            )
            batch_sh = mesh_sharding(mesh, "data", None)
            batch = {
                k: jax.ShapeDtypeStruct(tokens.shape, I32, sharding=batch_sh)
                for k in ("inputs", "targets")
            }
            step = make_train_step(
                state_sh, {k: batch_sh for k in batch}, mesh, RULES_DP_TP,
                loss_fn=fused_next_token_loss, loss_needs_params=True,
                apply_kwargs={"return_hidden": True},
            )
            step.remat.budget_bytes = budget
            program = step.jitted.lower(state, batch).compile()
        text = program.as_text()
        kernels = [
            line for line in text.splitlines()
            if re.match(r"\s*%attn[\w.\-]* = .*custom-call", line)
        ]
        assert kernels and all("tpu_custom_call" in k for k in kernels)
        # The forward kernel is the one that returns the float32 lse.
        forward = [k for k in kernels if "f32[" in k.split("custom-call(")[0]]
        return program.memory_analysis().temp_size_in_bytes, forward, kernels, step.remat

    nothing = compiled(dataclasses.replace(cfg, remat_policy="nothing"), budget)
    fitted = compiled(cfg, budget)
    assert nothing[3].plan is None              # an explicit policy asks no plan
    plan = fitted[3].plan
    assert plan.names == (kept_names,) * layers
    assert plan.saved_bytes == pytest.approx(budget)
    # The described device's kind says what it holds: a step would find a
    # budget of its own here (the emulated CPU mesh finds none).
    assert fitted[3].device_bytes == 16e9
    # Forward, recomputed forward, dK/dV, dQ a layer; then no second forward.
    assert (len(nothing[1]), len(nothing[2])) == (2 * layers, 4 * layers)
    assert (len(fitted[1]), len(fitted[2])) == (layers, 3 * layers)
    grown = fitted[0] - nothing[0]
    assert 0.25 * budget <= grown <= 1.25 * budget, (grown, budget)
