#!/usr/bin/env python
"""Drive the main path once on the TPU and check what comes out.

    python chip_smoke.py            # one chip: trainer, engine, kernels
    python chip_smoke.py --chips 4  # one host, four chips: 2x2 train, TP=4 serve

One process, no children, weights and data from ``--seed``, no network. The
model is ``CONFIG_125M`` at full width and depth (12 x 768, 12 heads x 64,
vocab 50304, S = 1024, bf16 compute). There is no CPU mode: without a TPU
whose kind is in the peak tables the script exits non-zero and prints no
result line. Any phase that raises ends the run non-zero.

Every earlier line is one JSON object per phase. The times in them are smoke
observations of one cold or warm run, NOT benchmark results. The last line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

The plain reference (``reference_fn``) is a GPT-2-style dense float32
forward over the same parameter tree, written with ``jax.numpy`` only; it
imports nothing from ``models/`` or ``ops/``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from learning_jax_sharding_tpu.models.serving import make_continuous_engine
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_125M,
    Transformer,
    next_token_loss,
)
from learning_jax_sharding_tpu.ops.attention import (
    causal_mask,
    dot_product_attention,
)
from learning_jax_sharding_tpu.ops.decode_attention import (
    decode_attention,
    fuse_kv,
)
from learning_jax_sharding_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attn_fn,
)
from learning_jax_sharding_tpu.parallel import (
    build_mesh,
    collective_counts,
    mesh_sharding,
    put,
    shard_shapes,
    single_device_mesh,
    unique_shard_count,
)
from learning_jax_sharding_tpu.parallel.logical import (
    RULES_DP_TP,
    RULES_TP_SERVING,
    activate,
    tree_shardings,
)
from learning_jax_sharding_tpu.telemetry import CompileWatch
from learning_jax_sharding_tpu.training.pipeline import (
    make_train_step,
    sharded_train_state,
)
from learning_jax_sharding_tpu.utils.bench import (
    PEAK_BF16_FLOPS,
    PEAK_HBM_BYTES,
)
from learning_jax_sharding_tpu.utils.compile_cache import place_compile_cache
from learning_jax_sharding_tpu.utils.memory import HBM_BYTES

# ---------------------------------------------------------------- sizes ----
BATCH, SEQ, TRAIN_STEPS = 8, 1024, 5
PROMPT_LENS = (32, 64, 100, 160, 230, 310, 400, 512)
NEW_TOKENS = 32
SLOTS, REFILL_CHUNK, PAGE = 4, 128, 64
ENGINE_FAMILIES = (
    ("split", {}),
    ("mixed", {"mixed": True}),
    ("mixed_h4", {"mixed": True, "horizon": 4}),
)

# ----------------------------------------------------------- tolerances ----
# Set from a CPU rehearsal of the same 125M weights in bf16 against the
# float32 reference (2 x 544 tokens, PR 24): logits have a spread of 0.55;
# bf16 moved a logit by 0.0045 on average and 0.033 at most, flipped the
# argmax at 30 of 1088 positions, and never where the reference preferred
# its own pick by more than 0.017.
#: |bf16 flash-attention step loss - float32 dense reference loss| at step 0
#: (the rehearsal's difference was 8e-5 on a loss of 11.0).
LOSS_TOL = 0.01
#: A generated token may differ from the float32 reference's argmax only
#: where the reference prefers its argmax over that token by less than this
#: many logits: about 2.5 times the largest single-logit bf16 error above.
MARGIN_TOL = 0.08
#: Kernel vs plain op: max |a - b| over max |b|, bf16 operands both sides.
KERNEL_TOL = 2e-2
#: One-device vs four-device loss: same math, another reduction order.
MESH_LOSS_TOL = 0.01

KERNEL_MARKER = "tpu_custom_call"


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


class Phase:
    """Wall and compile seconds of one phase, from the process-wide watch."""

    def __init__(self, watch: CompileWatch):
        self.watch = watch

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.before = self.watch.report()
        return self

    def __exit__(self, *exc):
        after = self.watch.report()
        self.timing = {
            "wall_s": round(time.perf_counter() - self.t0, 3),
            "backend_compiles": (
                after["backend_compiles"] - self.before["backend_compiles"]
            ),
            "backend_compile_s": round(
                after["backend_compile_seconds"]
                - self.before["backend_compile_seconds"], 3
            ),
            "cache_hits": after["cache_hits"] - self.before["cache_hits"],
            "cache_misses": (
                after["cache_misses"] - self.before["cache_misses"]
            ),
        }


def require_kernel(name: str, hlo_text: str) -> int:
    """The compiled program must hold a Mosaic kernel: an ``interpret=None``
    or ``decode_attention="auto"`` rule that resolved to its fallback shows
    up here as a program without one."""
    n = hlo_text.count(KERNEL_MARKER)
    if n == 0:
        raise AssertionError(
            f"{name}: no {KERNEL_MARKER} in the compiled program — a Pallas "
            f"kernel fell back to its plain path"
        )
    return n


# ------------------------------------------------------ plain reference ----
def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) + _f32(
        p["bias"]
    )


def _reference_block(x, blk, cfg):
    """One pre-LN block: dense causal attention, then a tanh-GELU MLP."""
    b, s, _ = x.shape
    n, h = cfg.num_heads, cfg.head_dim
    y = _layer_norm(x, blk["ln_attn"], cfg.norm_eps)
    q, k, v = (
        (y @ _f32(blk["attn"][name]["kernel"])).reshape(b, s, n, h)
        for name in ("query", "key", "value")
    )
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k) / math.sqrt(h)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknh->bqnh", jax.nn.softmax(scores, -1), v)
    x = x + out.reshape(b, s, n * h) @ _f32(blk["attn"]["out"]["kernel"])
    y = _layer_norm(x, blk["ln_ff"], cfg.norm_eps)
    y = y @ _f32(blk["ff"]["up"]["kernel"])
    y = 0.5 * y * (
        1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (y + 0.044715 * y**3))
    )
    return x + y @ _f32(blk["ff"]["down"]["kernel"])


def reference_fn(cfg):
    """The plain reference: ``run(params, tokens) -> float32 logits``. A
    GPT-2-style pre-LN decoder over the same parameter tree as
    ``models.transformer.Transformer`` (learned positions, LayerNorm with
    scale and bias, bias-free projections), float32 throughout, matmuls at
    the highest precision. Embedding, block and head are jitted apart and
    the block is called once per layer: one unrolled float32 program of
    twelve layers is 140 MB of chip code, which no compile cache keeps."""
    embed = jax.jit(
        lambda params, tokens: _f32(params["tok_embed"]["embedding"])[tokens]
        + _f32(params["pos_embed"])[None, : tokens.shape[1]]
    )
    block = jax.jit(lambda x, blk: _reference_block(x, blk, cfg))
    head = jax.jit(
        lambda x, params: _layer_norm(x, params["ln_out"], cfg.norm_eps)
        @ _f32(params["lm_head"]["kernel"])
    )

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = embed(params, tokens)
            for i in range(cfg.num_layers):
                x = block(x, params[f"block_{i}"])
            return head(x, params)

    return run


# --------------------------------------------------------------- device ----
def check_device(expect_count: int):
    """The device this run is about, or exit non-zero with nothing on
    stdout: no TPU, a kind the peak tables do not know, a wrong count."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (platform {dev.platform!r}); no CPU mode")
    for table, name in (
        (PEAK_BF16_FLOPS, "PEAK_BF16_FLOPS"),
        (PEAK_HBM_BYTES, "PEAK_HBM_BYTES"),
        (HBM_BYTES, "HBM_BYTES"),
    ):
        if dev.device_kind not in table:
            sys.exit(f"chip_smoke: {dev.device_kind!r} is not in {name}")
    if len(devices) != expect_count:
        sys.exit(
            f"chip_smoke: this path needs {expect_count} chip(s), "
            f"found {len(devices)}"
        )
    emit(
        "device", devices=[str(d) for d in devices], platform=dev.platform,
        kind=dev.device_kind, count=len(devices),
    )
    return dev


def versions() -> dict:
    import importlib.metadata as md

    import flax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu, "flax": flax.__version__,
    }


def check_sync(dev) -> dict:
    """Does ``jax.block_until_ready`` wait for the device? Time one 8192^3
    bf16 matmul three ways: dispatch only, block_until_ready, and a host
    readback of one element (what ``utils.bench._sync`` did for a device
    whose ``block_until_ready`` returned at once)."""
    n = 8192
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    np.asarray(mm(x)[0, 0])          # compile both programs, settle
    dispatch, block, readback = [], [], []
    for _ in range(5):
        t0 = time.perf_counter()
        y = mm(x)
        dispatch.append(time.perf_counter() - t0)
        jax.block_until_ready(y)
        block.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(mm(x)[0, 0])
        readback.append(time.perf_counter() - t0)
    floor = 2.0 * n**3 / PEAK_BF16_FLOPS[dev.device_kind]
    out = {
        "matmul": f"{n}^3 bf16",
        "least_possible_ms": round(floor * 1e3, 3),
        "dispatch_ms": round(float(np.median(dispatch)) * 1e3, 3),
        "block_until_ready_ms": round(float(np.median(block)) * 1e3, 3),
        "host_readback_ms": round(float(np.median(readback)) * 1e3, 3),
    }
    # It waits if it cannot return before the chip could have finished;
    # ``utils.bench._sync`` and every timing in the repo lean on that.
    if np.median(block) < floor:
        raise AssertionError(f"block_until_ready does not wait: {out}")
    out["block_until_ready_waits"] = True
    return out


# -------------------------------------------------------------- kernels ----
def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def check_kernels(cfg, seed: int) -> dict:
    """The kernels the two paths dispatch, each against its plain op, at the
    model's widths on this device."""
    rng = np.random.default_rng(seed)
    n, h = cfg.num_heads, cfg.head_dim
    errs: dict[str, float] = {}

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    # Flash attention, forward and gradients, at the train step's shape. The
    # cotangent ``w`` (loss = sum(out * w)) is an argument, not a closure: a
    # captured array becomes a constant of the program, and 12 MB of constant
    # made a 77 MB entry in the compile cache.
    q, k, v, w = (normal(BATCH, SEQ, n, h) for _ in range(4))

    def loss_of(attend):
        def loss(q, k, v, w):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    flash = loss_of(lambda q, k, v: flash_attention(q, k, v, causal=True))
    dense = loss_of(
        lambda q, k, v: dot_product_attention(q, k, v, mask=causal_mask(SEQ))
    )
    require_kernel(
        "flash_attention fwd+bwd", flash.lower(q, k, v, w).compile().as_text()
    )
    (_, out_f), grads_f = flash(q, k, v, w)
    (_, out_d), grads_d = dense(q, k, v, w)
    errs["flash_fwd"] = _rel_err(out_f, out_d)
    for name, gf, gd in zip(("dq", "dk", "dv"), grads_f, grads_d):
        errs[f"flash_{name}"] = _rel_err(gf, gd)

    # Paged decode attention. Logical per-row caches are cut into pages and
    # scattered over a shuffled pool; the plain op attends the logical rows.
    b, length = BATCH, cfg.max_seq_len
    nblk = length // PAGE
    kc, vc = (normal(b, n, length, h) for _ in range(2))
    table = rng.permutation(np.arange(1, b * nblk + 1)).reshape(b, nblk)
    pool_shape = (b * nblk + 1, n, PAGE, h)

    def to_pool(cache):
        pages = cache.reshape(b, n, nblk, PAGE, h).transpose(0, 2, 1, 3, 4)
        pool = jnp.zeros(pool_shape, cache.dtype)
        return pool.at[table.reshape(-1)].set(pages.reshape(-1, n, PAGE, h))

    def from_pool(pool):
        pages = pool[table.reshape(-1)].reshape(b, nblk, n, PAGE, h)
        return pages.transpose(0, 2, 1, 3, 4).reshape(b, n, length, h)

    def dense_cached(q, kc, vc, index):
        # (B, N, L, H) caches -> the plain op's (B, L, N, H); query i of a
        # row sits at index_b + i and sees cache slots up to itself.
        s = q.shape[1]
        qpos = index[:, None] + jnp.arange(s)[None, :]
        mask = jnp.arange(length)[None, None, :] <= qpos[:, :, None]
        return dot_product_attention(
            q, kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3),
            mask=mask[:, None],
        )

    table_j = jnp.asarray(table, jnp.int32)
    index = jnp.asarray(
        rng.integers(PAGE, length - REFILL_CHUNK, size=(b,)), jnp.int32
    )

    # (a) one token per row, its k/v merged into the page inside the kernel.
    q1, k_new, v_new = normal(b, 1, n, h), normal(b, n, 1, h), normal(b, n, 1, h)
    fold = jax.jit(
        lambda q, kvp, i, kvn, t: decode_attention(
            q, kvp, i, kv_new=kvn, block_table=t
        )
    )
    args = (
        q1, fuse_kv(to_pool(kc), to_pool(vc)), index, fuse_kv(k_new, v_new),
        table_j,
    )
    require_kernel(
        "decode_attention paged+fold", fold.lower(*args).compile().as_text()
    )
    out, kv_pool = fold(*args)
    k_pool, v_pool = kv_pool[..., :h], kv_pool[..., h:]
    rows = jnp.arange(b)
    kc_new = kc.at[rows, :, index].set(k_new[:, :, 0])
    vc_new = vc.at[rows, :, index].set(v_new[:, :, 0])
    errs["decode_fold"] = _rel_err(out, dense_cached(q1, kc_new, vc_new, index))
    errs["decode_fold_k_writeback"] = _rel_err(from_pool(k_pool), kc_new)
    errs["decode_fold_v_writeback"] = _rel_err(from_pool(v_pool), vc_new)

    # (b) a refill chunk whose k/v are already in the cache.
    qc = normal(b, REFILL_CHUNK, n, h)
    chunk = jax.jit(
        lambda q, kvp, i, t: decode_attention(q, kvp, i, block_table=t)
    )
    args = (qc, fuse_kv(to_pool(kc), to_pool(vc)), index, table_j)
    require_kernel(
        "decode_attention paged chunk", chunk.lower(*args).compile().as_text()
    )
    errs["decode_chunk"] = _rel_err(
        chunk(*args), dense_cached(qc, kc, vc, index)
    )

    bad = {k: e for k, e in errs.items() if not e <= KERNEL_TOL}
    if bad:
        raise AssertionError(f"kernels off their plain ops: {bad}")
    return {"rel_err": errs, "tolerance": KERNEL_TOL}


# -------------------------------------------------------------- trainer ----
def seeded_batch(cfg, mesh, seed: int):
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(BATCH, SEQ + 1)
    ).astype(np.int32)
    sh = mesh_sharding(mesh, "data", None)
    return {"inputs": put(tokens[:, :-1], sh), "targets": put(tokens[:, 1:], sh)}


def run_trainer(cfg, mesh, rules, seed: int, flash=make_flash_attn_fn):
    """``TRAIN_STEPS`` optimizer steps on one fixed seeded batch through
    ``sharded_train_state`` + ``make_train_step``. Returns the losses, the
    initial parameters (copied before the donated first step), the final
    state, its sharding tree and the compiled step's text."""
    single = mesh.size == 1
    cfg = dataclasses.replace(
        cfg, attn_fn=flash() if single else flash(mesh, rules)
    )
    batch = seeded_batch(cfg, mesh, seed)
    state, state_sh = sharded_train_state(
        Transformer(cfg), optax.adamw(3e-4), batch["inputs"],
        {"params": jax.random.key(seed)}, mesh, rules,
    )
    params0 = jax.tree.map(jnp.copy, state.params)
    step = make_train_step(
        state_sh, {k: v.sharding for k, v in batch.items()}, mesh, rules,
        loss_fn=next_token_loss,
    )
    with activate(mesh, rules):
        text = step.jitted.lower(state, batch).compile().as_text()
    require_kernel("train_step", text)
    losses, t_steps = [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        state, loss = step(state, batch)
        losses.append(float(loss))          # host readback: the step is done
        t_steps.append(time.perf_counter() - t0)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return {
        "losses": losses, "step_s": t_steps, "params0": params0,
        "state": state, "state_sh": state_sh, "batch": batch, "hlo": text,
    }


def check_trainer(cfg, seed: int, flash=make_flash_attn_fn) -> dict:
    run = run_trainer(cfg, single_device_mesh(), RULES_DP_TP, seed, flash)
    batch = run["batch"]
    logits = reference_fn(cfg)(run["params0"], batch["inputs"])
    ref_loss = float(next_token_loss(logits, batch))
    diff = abs(run["losses"][0] - ref_loss)
    if not diff <= LOSS_TOL:
        raise AssertionError(
            f"step-0 loss {run['losses'][0]} vs float32 reference "
            f"{ref_loss}: |diff| {diff} > {LOSS_TOL}"
        )
    tokens = BATCH * SEQ
    warm = float(np.median(run["step_s"][1:]))
    return {
        "model": f"{cfg.num_layers}x{cfg.features}, {cfg.num_heads} heads x "
                 f"{cfg.head_dim}, vocab {cfg.vocab_size}",
        "batch": BATCH, "seq": SEQ,
        "steps": TRAIN_STEPS, "losses": run["losses"],
        "reference_loss_f32": ref_loss, "loss_diff": diff,
        "loss_tolerance": LOSS_TOL,
        "kernels_in_step": run["hlo"].count(KERNEL_MARKER),
        "smoke_observed_step_ms": round(warm * 1e3, 2),
        "smoke_observed_tok_per_s": round(tokens / warm),
    }


# --------------------------------------------------------------- engine ----
def init_params(cfg, mesh, rules, seed: int):
    """Serving parameters born sharded under ``rules`` (the sharded-init
    pipeline of ``training.pipeline``, without an optimizer)."""
    model = Transformer(cfg)
    probe = np.zeros((2, 8), np.int32)

    def init(key, tokens):
        return model.init({"params": key}, tokens)

    with activate(mesh, rules):
        abstract = jax.eval_shape(init, jax.random.key(seed), probe)
        shardings = tree_shardings(abstract, mesh, rules)
        return jax.jit(
            lambda k, t: nn.meta.unbox(init(k, t)), out_shardings=shardings
        )(jax.random.key(seed), probe)["params"]


def seeded_prompts(cfg, seed: int):
    rng = np.random.default_rng(seed + 1)
    return [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in PROMPT_LENS
    ]


def serve_family(cfg, mesh, rules, params, prompts, kwargs) -> dict:
    """Serve the request set twice through one engine of a family (the second
    pass is warm: no compile, and the engine reuses its cache and pages), and
    check every program it dispatched for a compiled kernel."""
    pages = SLOTS * math.ceil((max(PROMPT_LENS) + NEW_TOKENS) / PAGE) + 1 + 8
    serve = make_continuous_engine(
        cfg, mesh, rules, batch_size=SLOTS, max_new_tokens=NEW_TOKENS,
        refill_chunk=REFILL_CHUNK, inference_dtype=jnp.bfloat16,
        paged_pages=pages, page_size=PAGE, **kwargs,
    )
    t0 = time.perf_counter()
    streams = serve(params, prompts)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = serve(params, prompts)
    warm_s = time.perf_counter() - t0
    for p, a in zip(prompts * 2, streams + again):
        if len(a) != len(p) + NEW_TOKENS or not np.array_equal(a[: len(p)], p):
            raise AssertionError("a stream is not prompt + NEW_TOKENS tokens")
    hlo = serve.engine.program_hlo()
    programs = {
        name: require_kernel(f"engine program {name}", text)
        for name, text in hlo.items()
    }
    generated = len(prompts) * NEW_TOKENS
    return {
        "streams": streams,
        "again": again,
        "all_reduces": {
            name: collective_counts(text)["all-reduce"]
            for name, text in hlo.items()
        },
        "report": {
            "programs_kernels": programs,
            "tokens_served": 2 * generated,
            "first_serve_s": round(first_s, 3),
            "smoke_observed_warm_serve_s": round(warm_s, 3),
            "smoke_observed_tok_per_s": round(generated / warm_s, 1),
        },
    }


def stream_logits(ref, params, streams) -> np.ndarray:
    """Float32 reference logits over each stream's own tokens, all streams
    padded to one width (one compiled shape; causal attention keeps the
    padding from reaching back)."""
    padded = np.zeros((len(streams), max(PROMPT_LENS) + NEW_TOKENS), np.int32)
    for i, s in enumerate(streams):
        padded[i, : len(s)] = s
    logits = np.asarray(ref(params, jnp.asarray(padded)))
    if not np.all(np.isfinite(logits)):
        raise AssertionError("reference logits are not finite")
    return logits


def teacher_forced(logits, prompts, streams) -> dict:
    """``logits`` are the reference's over each stream's own prompt +
    generated tokens (``stream_logits``). Every generated token must be the
    reference's argmax at its position, or the reference must prefer its
    argmax over that token by less than ``MARGIN_TOL`` logits (bf16 rounding
    can flip such a pick)."""
    excused, worst, positions = 0, 0.0, 0
    for i, (p, s) in enumerate(zip(prompts, streams)):
        for t in range(len(p), len(s)):
            row = logits[i, t - 1]
            positions += 1
            if int(np.argmax(row)) == int(s[t]):
                continue
            gap = float(np.max(row) - row[s[t]])
            worst = max(worst, gap)
            if gap >= MARGIN_TOL:
                top2 = np.sort(row)[-2:]
                raise AssertionError(
                    f"request {i} departs from the float32 reference at a "
                    f"confident position {t}: served {int(s[t])}, reference "
                    f"{int(np.argmax(row))} ahead by {gap:.4f} logits "
                    f"(top-2 margin {float(top2[1] - top2[0]):.4f})"
                )
            excused += 1
    return {
        "positions": positions, "excused_low_margin": excused,
        "largest_excused_gap": round(worst, 5), "margin_tolerance": MARGIN_TOL,
    }


def first_divergences(base_logits, base, other) -> dict:
    """Where a stream of ``other`` first leaves the same request's stream in
    ``base``, the float32 reference (``base_logits``, over ``base``) must be
    nearly indifferent between the two tokens; after that position the two
    condition on different text."""
    equal, splits, worst = 0, 0, 0.0
    for i, (a, b) in enumerate(zip(base, other)):
        differ = np.nonzero(a != b)[0]
        if differ.size == 0:
            equal += 1
            continue
        splits += 1
        t = int(differ[0])
        row = base_logits[i, t - 1]
        gap = abs(float(row[a[t]] - row[b[t]]))
        worst = max(worst, gap)
        if gap >= MARGIN_TOL:
            raise AssertionError(
                f"request {i}: the streams part at position {t} where the "
                f"reference separates the two tokens by {gap:.4f} logits"
            )
    return {
        "streams_identical": equal, "streams_split_at_low_margin": splits,
        "largest_gap_at_split": round(worst, 5),
    }


def check_streams(ref, params, prompts, out) -> dict:
    """Both passes of one engine against the float32 reference. The warm
    pass need not repeat the first bit for bit (it meets another schedule
    and, in bf16, other roundings), but where it first leaves it the
    reference must be nearly indifferent."""
    first = stream_logits(ref, params, out["streams"])
    again = stream_logits(ref, params, out["again"])
    return {
        "reference": teacher_forced(first, prompts, out["streams"]),
        "reference_warm_pass": teacher_forced(again, prompts, out["again"]),
        "warm_pass_vs_first": first_divergences(
            first, out["streams"], out["again"]
        ),
    }


def check_engine(cfg, seed: int, watch) -> None:
    mesh = single_device_mesh()
    params = init_params(cfg, mesh, RULES_TP_SERVING, seed)
    prompts = seeded_prompts(cfg, seed)
    ref = reference_fn(cfg)
    for name, kwargs in ENGINE_FAMILIES:
        with Phase(watch) as ph:
            out = serve_family(
                cfg, mesh, RULES_TP_SERVING, params, prompts, kwargs
            )
            agree = check_streams(ref, params, prompts, out)
        emit(f"engine_{name}", **ph.timing, **out["report"], **agree)


# ------------------------------------------------------------ four chips ----
def check_spread(tree, shardings, devices) -> dict:
    """Every leaf has a shard on every device, of the shape its sharding
    says; the kernels are really split (fewer unique shards than devices
    means replication along an axis, more than one means sharding)."""
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    sh_leaves = jax.tree_util.tree_leaves(shardings)
    split = 0
    for (path, leaf), sh in zip(leaves, sh_leaves):
        on = {s.device for s in leaf.addressable_shards}
        if on != set(devices):
            raise AssertionError(
                f"{jax.tree_util.keystr(path)} lives on {len(on)} devices"
            )
        want = sh.shard_shape(leaf.shape)
        if any(shape != want for shape in shard_shapes(leaf)):
            raise AssertionError(
                f"{jax.tree_util.keystr(path)}: shards {shard_shapes(leaf)} "
                f"!= {want}"
            )
        split += want != leaf.shape
    return {"leaves": len(leaves), "leaves_split": split}


def bytes_in_use(devices) -> list[int]:
    used = [int(d.memory_stats()["bytes_in_use"]) for d in devices]
    if min(used) < 64 << 20:
        raise AssertionError(f"a device holds almost nothing: {used}")
    return used


def four_chips(cfg, seed: int, watch) -> None:
    devices = jax.devices()
    one = build_mesh((1, 1), ("data", "model"), devices=devices[:1])

    # (a) the same train steps on one device and on a 2x2 data x model mesh.
    with Phase(watch) as ph:
        ref_run = run_trainer(cfg, one, RULES_DP_TP, seed)
        ref_losses = ref_run["losses"]
        del ref_run
        mesh = build_mesh((2, 2), ("data", "model"))
        run = run_trainer(cfg, mesh, RULES_DP_TP, seed)
        diffs = [abs(a - b) for a, b in zip(run["losses"], ref_losses)]
        if not max(diffs) <= MESH_LOSS_TOL:
            raise AssertionError(
                f"2x2 losses {run['losses']} vs one device {ref_losses}"
            )
        state, state_sh = run["state"], run["state_sh"]
        spread = {
            "params": check_spread(state.params, state_sh.params, devices),
            "opt_state": check_spread(
                state.opt_state, state_sh.opt_state, devices
            ),
        }
        qk = state.params["block_0"]["attn"]["query"]["kernel"]
        if unique_shard_count(qk) != 2:    # split over model, copied over data
            raise AssertionError("query kernel is not split over 'model' only")
        # At least the two row-parallel projections of every block reduce
        # over 'model' (the gradients over 'data' come on top).
        counts = collective_counts(run["hlo"])
        if counts["all-reduce"] < 2 * cfg.num_layers:
            raise AssertionError(f"too few all-reduces in the 2x2 step: {counts}")
        used = bytes_in_use(devices)
    emit(
        "train_2x2", **ph.timing, mesh="2x2 data x model", rules="RULES_DP_TP",
        losses=run["losses"], one_device_losses=ref_losses,
        max_loss_diff=max(diffs), loss_tolerance=MESH_LOSS_TOL,
        spread=spread, collectives=counts, bytes_in_use=used,
        smoke_observed_step_ms=round(
            float(np.median(run["step_s"][1:])) * 1e3, 2
        ),
    )
    del run, state

    # (b) the engine on one device and tensor-parallel over all four.
    prompts = seeded_prompts(cfg, seed)
    ref = reference_fn(cfg)
    tp = build_mesh((1, 4), ("data", "model"))
    p_one = init_params(cfg, one, RULES_TP_SERVING, seed)
    p_tp = init_params(cfg, tp, RULES_TP_SERVING, seed)
    leaf = p_tp["block_0"]["ff"]["up"]["kernel"]
    if not np.array_equal(
        np.asarray(leaf), np.asarray(p_one["block_0"]["ff"]["up"]["kernel"])
    ):
        raise AssertionError("the two meshes were given different weights")
    if shard_shapes(leaf) != [(leaf.shape[0], leaf.shape[1] // 4)] * 4:
        raise AssertionError(f"ff.up is not split four ways: {shard_shapes(leaf)}")
    for name, kwargs in ENGINE_FAMILIES:
        with Phase(watch) as ph:
            base = serve_family(
                cfg, one, RULES_TP_SERVING, p_one, prompts, kwargs
            )
            out = serve_family(cfg, tp, RULES_TP_SERVING, p_tp, prompts, kwargs)
            agree = check_streams(ref, p_one, prompts, out)
            if min(out["all_reduces"].values()) < 2 * cfg.num_layers:
                raise AssertionError(
                    f"a TP=4 engine program holds too few all-reduces: "
                    f"{out['all_reduces']}"
                )
            first_splits = first_divergences(
                stream_logits(ref, p_one, base["streams"]),
                base["streams"], out["streams"],
            )
        emit(
            f"serve_tp4_{name}", **ph.timing, rules="RULES_TP_SERVING",
            mesh="1x4", **out["report"], **agree,
            all_reduces=out["all_reduces"], vs_one_device=first_splits,
            bytes_in_use=bytes_in_use(devices),
        )


# ----------------------------------------------------------------- main ----
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    t_start = time.perf_counter()
    cache_dir = place_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    dev = check_device(args.chips)
    emit(
        "start", **versions(), compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=entries, seed=args.seed,
        chips=args.chips,
    )
    cfg = dataclasses.replace(
        CONFIG_125M, max_seq_len=SEQ, decode_attention="auto"
    )
    watch = CompileWatch().start()

    if args.chips == 4:
        four_chips(cfg, args.seed, watch)
    else:
        with Phase(watch) as ph:
            sync = check_sync(dev)
        emit("sync", **ph.timing, **sync)
        with Phase(watch) as ph:
            kernels = check_kernels(cfg, args.seed)
        emit("kernels", **ph.timing, **kernels)
        with Phase(watch) as ph:
            trainer = check_trainer(cfg, args.seed)
        emit("trainer", **ph.timing, **trainer)
        check_engine(cfg, args.seed, watch)

    watch.stop()
    report = watch.report()
    if not report["monitoring_available"] or report["backend_compiles"] == 0:
        raise AssertionError(f"CompileWatch saw no compile: {report}")
    emit(
        "summary", compile_watch=report,
        peak_bytes_in_use=[
            int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.devices()
        ],
        # Warm: more programs came out of the persistent cache than went
        # in (a cold run still hits what its own earlier phases wrote).
        cache_was_warm=report["cache_hits"] > report["cache_misses"],
        total_wall_s=round(time.perf_counter() - t_start, 1),
        note="times above are smoke observations, not benchmark results",
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
