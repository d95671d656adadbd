#!/usr/bin/env python
"""Round benchmark: case-6 attention throughput on real TPU hardware.

Prints ONE JSON line:
    {"metric": "case6_attention_tflops_per_chip", "value": N,
     "unit": "TFLOP/s/chip", "vs_baseline": R}

* The workload is the reference's case-6 configuration — multi-head attention
  at B=8, S=256, M=640, 8 heads × 64 (`/root/reference/case6_attention.py:44-45,
  149-151`) — measured with a correct harness (warmup excluded, devices
  synced; the reference's own loop at `case6_attention.py:234-238` has neither).
* ``value`` is this framework's TPU-native path: bf16 compute, fp32-upcast
  softmax, K forward applications chained inside one jitted program so device
  time, not dispatch latency, is measured.
* ``vs_baseline`` compares against a reference-faithful baseline implementation
  (fp32 compute, same math) timed with the same correct harness in the same
  run — the reference publishes no numbers of its own (BASELINE.md).

Extra context (125M composed-transformer train-step MFU, the BASELINE.json
north star) goes to stderr so stdout stays one machine-readable line.
"""

import json
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

from learning_jax_sharding_tpu.models.attention import MultiHeadAttention
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_125M,
    Transformer,
)
from learning_jax_sharding_tpu.parallel import build_mesh, mesh_sharding, put
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from learning_jax_sharding_tpu.training.pipeline import (
    make_train_step,
    sharded_train_state,
)
from learning_jax_sharding_tpu.utils.bench import (
    device_peak_flops,
    measure,
)

# Reference case-6 dims (`/root/reference/case6_attention.py:44-45,149-151`).
B, S, M = 8, 256, 640
NUM_HEADS, HEAD_DIM = 8, 64
CHAIN = 32  # forward applications chained per jitted call


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _emulated_child(script: str, *args: str, timeout: float = 1800) -> str:
    """Run ``scripts/<script>`` on the emulated CPU mesh in a child process
    and return its stdout; a non-zero exit raises with the tail of its output.

    A chip belongs to one process at a time and this parent holds it, so the
    child's environment names the CPU platform outright: it can never reach
    for the chip (an empty ``JAX_PLATFORMS`` would let it try)."""
    import os
    import pathlib
    import subprocess

    path = pathlib.Path(__file__).resolve().parent / "scripts" / script
    proc = subprocess.run(
        [sys.executable, str(path), *args],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        tail = "\n".join((proc.stderr or proc.stdout).splitlines()[-5:])
        raise RuntimeError(
            f"{script} {' '.join(args)} exited {proc.returncode}: {tail}"
        )
    return proc.stdout


def _relay_bench_lines(stdout: str):
    """Relay a child's ``[bench]`` lines to this run's log; return the
    payload of its last ``[bench-json]`` line, if it printed one."""
    block = None
    for line in stdout.splitlines():
        if line.startswith("[bench]"):
            _log(line)
        elif line.startswith("[bench-json] "):
            block = json.loads(line[len("[bench-json] "):])
    return block


def _chained_apply(model, params, x0, n):
    """n chained forwards in one program: x_{i+1} = normalize(f(x_i)).

    Chaining defeats loop-invariant hoisting (each iteration depends on the
    last); the rms normalization (negligible FLOPs next to the matmuls) keeps
    magnitudes stable across repeated un-normalized attention blocks.
    """

    def body(_, x):
        y = model.apply({"params": params}, x)
        return (y * jax.lax.rsqrt(jnp.mean(jnp.square(y)) + 1e-6)).astype(x0.dtype)

    x0 = x0.astype(model.dtype)
    return jax.lax.fori_loop(0, n, body, x0)


def bench_attention(dtype, label):
    from learning_jax_sharding_tpu.telemetry import executable_report

    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    model = MultiHeadAttention(
        features=M, num_heads=NUM_HEADS, head_dim=HEAD_DIM, dtype=dtype
    )
    x = put(
        np.random.default_rng(0).standard_normal((B, S, M)).astype(np.float32),
        mesh_sharding(mesh, "data", None, None),
    )
    params = model.init({"params": jax.random.key(0)}, x)["params"]
    import flax.linen as nn

    params = nn.meta.unbox(params)

    single = jax.jit(lambda p, x: model.apply({"params": p}, x))
    # ONE AOT compile serves both FLOPs and the collective inventory for
    # the JSON telemetry block (all-zero collectives on the 1-chip
    # degenerate mesh — multi-chip counts are pinned in tests/ on the
    # emulated mesh). This diagnostic compile is the one extra
    # backend-compile the headline phase delta includes.
    rep = executable_report(single, params, x)
    flops_single = rep["flops"]
    collectives = rep["collectives"]
    from learning_jax_sharding_tpu.telemetry import axis_collective_volume

    axis_volume = axis_collective_volume(
        rep["collective_instructions"], mesh
    )
    chained = jax.jit(partial(_chained_apply, model, n=CHAIN))
    result = measure(
        chained, params, x,
        flops=(flops_single * CHAIN) if flops_single else None,
        n_devices=1,
    )
    per_iter = result.seconds_per_iter / CHAIN
    tflops = (flops_single / per_iter / 1e12) if flops_single else None
    msg = f"[bench] {label}: {per_iter * 1e6:.1f} us/forward"
    if tflops:
        msg += f", {tflops:.2f} TFLOP/s/chip"
    _log(msg)
    return {
        "tflops": tflops,
        "seconds_per_forward": per_iter,
        "collectives": collectives,
        "axis_volume": axis_volume,
    }


def _timed_train_step(cfg, *, b=8, s=1024, K=8, opt=None):
    """Shared sustained train-step harness for the dense and MoE context
    lines: K full optimizer steps per jitted call (lax.scan, state carried
    in place — the regime ``fit()`` runs; single-call timing cannot donate,
    which charges every step a ~2.7 ms fp32 state copy real training never
    pays). Long chains (≥4 s per run) and the median of 5 pairs date from
    rounds 1-5, whose remotely attached chip drifted ±30% across seconds;
    the drift of today's machine is not measured (PERF.md).
    """
    from learning_jax_sharding_tpu.models.transformer import fused_next_token_loss

    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(b, s + 1)).astype(np.int32)
    sh = mesh_sharding(mesh, "data", None)
    batch = {"inputs": put(tokens[:, :-1], sh), "targets": put(tokens[:, 1:], sh)}
    state, state_sh = sharded_train_state(
        Transformer(cfg), opt if opt is not None else optax.adamw(3e-4),
        batch["inputs"], {"params": jax.random.key(0)}, mesh, RULES_DP_TP,
    )
    stacked = {
        k: put(
            np.stack([np.asarray(v)] * K),
            mesh_sharding(mesh, None, "data", None),
        )
        for k, v in batch.items()
    }
    step = make_train_step(
        state_sh, {k: v.sharding for k, v in batch.items()}, mesh, RULES_DP_TP,
        loss_fn=fused_next_token_loss, loss_needs_params=True,
        apply_kwargs={"return_hidden": True}, donate_state=False,
        steps_per_call=K,
    )
    result = measure(
        step, state, stacked, flops=cfg.train_step_flops(b, s) * K,
        n_devices=1, min_time=4.0, repeats=5,
    )
    return result, result.seconds_per_iter / K, K


def bench_transformer_125m():
    """North-star context: composed 125M transformer train step, MFU.

    Tuned TPU configuration (each measured on the v5e, b=8 s=1024):
    * Pallas flash attention, auto block sizes — the dense path's fp32
      (B, N, S, S) score traffic is the single largest time sink (~26 ms of a
      102 ms step);
    * chunked fused cross-entropy head — the full (B, S, V) logits never
      materialize (~3 ms, and the memory headroom for bigger batches);
    * MFU from analytic model FLOPs (``TransformerConfig.train_step_flops``):
      XLA cost analysis cannot see Pallas/scan FLOPs.
    """
    import dataclasses

    from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn

    cfg = dataclasses.replace(CONFIG_125M, attn_fn=make_flash_attn_fn())
    # fp32 AdamW, unmodified training numerics. Round 3 re-measured every
    # recorded optimizer variant in ONE process (PERF.md "Round-3
    # resolution"): bf16 moments are a no-op (66.4 vs 66.6 ms), flattened
    # params are worse (82.3), sgd is the only thing faster (63.2) — the
    # honest sustained AdamW figure on this chip is ~66.5 ms.
    result, per_step, K = _timed_train_step(cfg)
    msg = f"[bench] 125M transformer train step: {per_step * 1e3:.1f} ms/step"
    if result.tflops_per_chip is not None:
        msg += f", {result.tflops_per_chip:.1f} TFLOP/s/chip"
    if result.mfu is not None:
        msg += f", MFU={result.mfu:.1%} (sustained, {K}-step scan)"
    _log(msg)
    return result


def _decode_ladder(cfg, label, *, b, prompt_len, new, rounds=3):
    """bf16 / int8 / int4-fused greedy decode, measured INTERLEAVED.

    A sequential ladder lets run-to-run drift reorder the variants: each
    variant samples a different window. Here every round times all three
    variants back-to-back and the per-variant MEDIAN across rounds is
    reported, so the ordering is a within-window comparison.
    """
    import flax.linen as nn

    from learning_jax_sharding_tpu.models.generate import make_generate_fn
    from learning_jax_sharding_tpu.models.quantize import (
        map_unquantized,
        quantize_tree,
        quantized_bytes,
    )
    from learning_jax_sharding_tpu.utils.bench import mbu, time_fn

    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    model = Transformer(cfg)
    rng = np.random.default_rng(0)
    prompt = put(
        rng.integers(0, cfg.vocab_size, size=(b, prompt_len)).astype(np.int32),
        mesh_sharding(mesh, "data", None),
    )
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(0), prompt
        )["params"]
    )

    def to_bf16(x):
        return (
            x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x
        )

    def decode_mbu(weight_bytes: float, secs_per_tok: float) -> str:
        # Per-token-step HBM roofline: served weights + the VALID KV cache
        # (mean over the run: prompt + new/2 slots — the blocked decode
        # kernel reads only valid blocks). MBU because decode is
        # bandwidth-bound; its matmuls are too thin for MFU to mean much.
        n_kv = cfg.num_kv_heads or cfg.num_heads
        cache_bytes = (
            cfg.num_layers * b * n_kv * (prompt_len + new / 2)
            * cfg.head_dim * 2 * 2
        )  # K+V, bf16
        frac = mbu(weight_bytes + cache_bytes, secs_per_tok)
        return "" if frac is None else f", MBU={frac:.1%}"

    def make(deq):
        return make_generate_fn(
            cfg, mesh, RULES_DP_TP, max_new_tokens=new,
            inference_dtype=jnp.bfloat16, dequantize=deq,
        )

    variants = [
        ("bf16", jax.tree.map(to_bf16, params), make(False)),
        ("int8", quantize_tree(params), make(True)),
        ("int4-fused", quantize_tree(params, bits=4), make("fused")),
    ]
    del params
    times = {name: [] for name, _, _ in variants}
    # time_fn's own warmup (1 untimed call) covers compile on the first
    # round; keeping it minimal holds the variants' timed samples close
    # together, which is the point of interleaving.
    for _ in range(rounds):
        for name, tree, gen in variants:
            times[name].append(
                time_fn(gen, tree, prompt, jax.random.key(1),
                        min_time=1.0, repeats=1, warmup=1)
            )
    order = sorted(times, key=lambda n: float(np.median(times[n])))
    for name, tree, gen in variants:
        served = quantized_bytes(map_unquantized(to_bf16, tree))
        secs = float(np.median(times[name]))
        _log(
            f"[bench] {label} decode, {name} (b={b}, prompt {prompt_len}, "
            f"+{new} new): {b * new / secs:,.0f} tok/s, "
            f"{secs / new * 1e3:.2f} ms/token-step, served "
            f"{served / 1e6:,.0f} MB" + decode_mbu(served, secs / new)
        )
    _log(
        f"[bench] {label} decode ladder ordering (interleaved medians, "
        f"fastest first): {' > '.join(order)}"
    )


def bench_decode_125m():
    """Serving context: KV-cached greedy decode ladder on the 125M model."""
    _decode_ladder(CONFIG_125M, "125M", b=8, prompt_len=128, new=128)


def bench_decode_1p4b():
    """The weight-BANDWIDTH-bound ladder shape (24×2048, 16 heads×128):
    decode streams 0.9-2.8 GB of weights per token, so the quantization
    ladder separates on served bytes instead of launch overhead — the
    shape where PERF.md claims int4-fused ≥ int8 ("whole-FF kernel"
    section), now in the driver artifact (VERDICT r3 item 2)."""
    from learning_jax_sharding_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        num_layers=24, features=2048, num_heads=16, head_dim=128,
        hidden=8192, max_seq_len=256,
    )
    # rounds=5 (vs the ladder default 3): the ABSOLUTE int4 number is a
    # claim here, not just the ordering — round 4's artifact/shakedown
    # spread (3,036-4,056 tok/s) needs the deeper median (VERDICT item 6).
    _decode_ladder(cfg, "1.4B", b=8, prompt_len=64, new=64, rounds=5)


def bench_longcontext():
    """Long-context train line (SURVEY §5): S=8192, head_dim 128 — the
    configuration of record from PERF.md's round-3 VPU:MXU verification
    (hd=64 is VPU-floored at ~24% of peak on the v5e; doubling the
    contraction dim doubles kernel throughput)."""
    import dataclasses

    from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn

    cfg = dataclasses.replace(
        CONFIG_125M, num_heads=6, head_dim=128, max_seq_len=8192,
        attn_fn=make_flash_attn_fn(), remat=False,
    )
    result, per_step, K = _timed_train_step(cfg, b=2, s=8192, K=2)
    msg = (
        f"[bench] long-context train step (S=8192, b=2, hd=128, flash "
        f"causal): {per_step * 1e3:.1f} ms/step"
    )
    if result.mfu is not None:
        msg += f", MFU={result.mfu:.1%} (sustained, {K}-step scan)"
    _log(msg)


def bench_reference_configs():
    """BASELINE.md's remaining config list, one line each on the real chip.

    The reference shapes are lesson-sized (A(4,16)·B(16,4) — microseconds of
    work), so each pattern is measured at a ×512-scaled shape that keeps the
    MXU busy; the multi-device sharding/collective semantics of these cases
    are pinned on the emulated 8-device mesh in tests/test_matmul_shardings.py
    (HLO collective asserts) — one chip runs each pattern's compute
    degenerate.

    * case1a replicated matmul (`/root/reference/case1a.py:49`)
    * case3 fully-sharded matmul pattern (`/root/reference/case3_fully_sharded.py:23-29`)
    * case4 DP×MP feed-forward einsum (`/root/reference/case4_gspmd_ff.py:30,52`)
    """
    from learning_jax_sharding_tpu.utils.bench import time_fn

    peak = device_peak_flops(jax.devices()[0])
    rng = np.random.default_rng(0)

    def line(label, fn, *args, flops):
        # Warm-weight microbench: the same operands repeat every call, so
        # the chip overlaps weight fetches perfectly — sustained rates can
        # EXCEED the cold-read bf16 peak ratio (PERF.md methodology notes);
        # the ratio is context, not an MFU claim.
        secs = time_fn(jax.jit(fn), *args, min_time=1.0)
        tf = flops / secs / 1e12
        pct = f" ({tf * 1e12 / peak:.0%} of bf16 peak, warm-weight)" if peak else ""
        _log(f"[bench] {label}: {secs * 1e6:.0f} us, {tf:.1f} TFLOP/s/chip{pct}")

    m, k_, n = 2048, 8192, 2048
    a = jnp.asarray(rng.standard_normal((m, k_)), jnp.bfloat16)
    bmat = jnp.asarray(rng.standard_normal((k_, n)), jnp.bfloat16)
    line(
        "case1a replicated matmul (2048x8192x2048 bf16, 1-chip degenerate)",
        jax.lax.dot, a, bmat, flops=2 * m * k_ * n,
    )
    line(
        "case3 fully-sharded matmul pattern (same shapes, fp32-accum)",
        lambda x, y: jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ),
        a, bmat, flops=2 * m * k_ * n,
    )
    bb, s, d, h = 8, 512, 2048, 8192
    x = jnp.asarray(rng.standard_normal((bb, s, d)), jnp.bfloat16)
    w1 = jnp.asarray(rng.standard_normal((d, h)), jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((h, d)), jnp.bfloat16)

    def ff(x, w1, w2):
        return jnp.einsum("bsh,hd->bsd", jax.nn.relu(jnp.einsum("bsd,dh->bsh", x, w1)), w2)

    line(
        "case4 DP*MP feed-forward (8x512x2048, hidden 8192, bf16)",
        ff, x, w1, w2, flops=2 * bb * s * d * h * 2,
    )


def bench_shardflow():
    """Analyzer self-check (round 13): price the tracked program shapes
    with ``analysis.shardflow`` + ``analysis.costmodel`` BEFORE running
    them, then measure the same jitted programs and report the model
    error — the number ``scripts/bench_compare.py`` gates direction-aware
    (``predicted_vs_measured_pct``; a growing error means the propagation
    rules or the platform profile drifted from the real machine).

    On the TPU host the lines price the 125M tracked shapes; on the
    emulated-CPU host a scaled-down same-architecture configuration keeps
    the measured side inside the tier-1 window (PERF.md round 13 records
    the error for both). One-chip degenerate mesh, like every other
    tracked line: the roofline terms (compute/HBM) carry the prediction;
    the multi-chip collective term is reconciled against goldens by
    ``scripts/shardcheck.py`` on the emulated mesh instead, where
    emulated "wire time" would be fiction.
    """
    import dataclasses

    from learning_jax_sharding_tpu.analysis import costmodel
    from learning_jax_sharding_tpu.analysis.shardflow import trace_shardflow
    from learning_jax_sharding_tpu.models.generate import make_generate_fn
    from learning_jax_sharding_tpu.models.transformer import next_token_loss
    from learning_jax_sharding_tpu.parallel.logical import activate
    from learning_jax_sharding_tpu.utils.bench import time_fn

    import flax.linen as nn

    profile = costmodel.current_profile()
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = CONFIG_125M
        b, s = 8, 1024
        db, dprompt, dnew = 8, 128, 128
    else:
        cfg = dataclasses.replace(
            CONFIG_125M, vocab_size=8192, num_layers=2, features=256,
            num_heads=4, head_dim=64, hidden=1024, max_seq_len=512,
        )
        b, s = 4, 256
        db, dprompt, dnew = 4, 64, 32
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    block: dict = {"profile": profile.to_dict()}

    def line(label, rep, measured_s, unit_scale, unit):
        cost = costmodel.price(rep, profile)
        cmp = costmodel.compare(cost.predicted_s, measured_s)
        _log(
            f"[bench] shardflow {label}: predicted "
            f"{cost.predicted_s * unit_scale:.2f} vs measured "
            f"{measured_s * unit_scale:.2f} {unit} "
            f"({cost.bound}-bound), model err {cmp['err_pct']:.1f}%"
        )
        return {**cmp, "bound": cost.bound, "flops": cost.flops,
                "hbm_bytes": cost.hbm_bytes}

    # Train step: same builders as the tracked 125M line (single-call
    # timing here — the prediction is also single-step).
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(b, s + 1)).astype(np.int32)
    sh = mesh_sharding(mesh, "data", None)
    batch = {"inputs": put(tokens[:, :-1], sh), "targets": put(tokens[:, 1:], sh)}
    state, state_sh = sharded_train_state(
        Transformer(cfg), optax.adamw(3e-4), batch["inputs"],
        {"params": jax.random.key(0)}, mesh, RULES_DP_TP,
    )
    step = make_train_step(
        state_sh, {k: v.sharding for k, v in batch.items()}, mesh,
        RULES_DP_TP, loss_fn=next_token_loss, donate_state=False,
    )
    with activate(mesh, RULES_DP_TP):
        rep = trace_shardflow("bench_train_step", step.jitted, state, batch,
                              mesh=mesh)
    measured = time_fn(step, state, batch, min_time=1.0, repeats=2)
    block["train_step"] = line(
        f"train step (b={b}, s={s})", rep, measured, 1e3, "ms/step"
    )

    # Decode: whole greedy generation in one jitted program — the token
    # loop is a scan, so the analyzer's trip multiplier prices the
    # weight re-streaming that makes decode bandwidth-bound.
    model = Transformer(cfg)
    prompt = put(
        np.random.default_rng(1).integers(
            0, cfg.vocab_size, size=(db, dprompt)
        ).astype(np.int32),
        mesh_sharding(mesh, "data", None),
    )
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(0), prompt
        )["params"]
    )
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )
    gen = make_generate_fn(
        cfg, mesh, RULES_DP_TP, max_new_tokens=dnew,
        inference_dtype=jnp.bfloat16,
    )
    with activate(mesh, RULES_DP_TP):
        rep = trace_shardflow("bench_decode", gen, params, prompt,
                              jax.random.key(1), mesh=mesh)
    measured = time_fn(gen, params, prompt, jax.random.key(1),
                       min_time=1.0, repeats=2)
    block["decode"] = line(
        f"decode (b={db}, prompt {dprompt}, +{dnew} new)",
        rep, measured, 1e3 / dnew, "ms/token-step",
    )
    return block


def bench_layout_search():
    """Layout-search closed loop (round 17): run the abstract search
    over the train step's param layout, then compile ONLY the hand
    layout and the argmin layout and measure both — the predicted win
    is confirmed against real execution, and the two tracked numbers
    ride ``scripts/bench_compare.py`` direction-aware: ``layout gap``
    (searched-vs-hand priced gap — growing means the committed layouts
    drifted from the searchable optimum) and ``layout err`` (the
    search's predicted-vs-measured error on the two layouts it
    compiles, the analyzer-loop analogue of the shardflow model err).

    Like ``bench_fleet``, the layout legs need device MULTIPLICITY the
    one-chip bench host lacks, so the search + both measurements run on
    the emulated 8-device mesh in a subprocess
    (``scripts/layout_search.py --bench-lines``) whose ``[bench]``
    lines are relayed verbatim; the subprocess prices the measured legs
    with the live profile scaled to the emulated-device share of the
    socket (its docstring records the convention)."""
    out = _emulated_child(
        "layout_search.py", "--entry", "train_step", "--bench-lines",
        "--budget", "48",
    )
    return _relay_bench_lines(out)


def bench_memflow():
    """Memflow reconciliation (round 18): the static per-device
    peak-HBM analyzer (``analysis/memflow.py``) against what XLA's
    ``compiled.memory_analysis()`` reports for the searchable entry
    points — the accuracy number behind the layout search's HBM budget
    gate and ``shardcheck --memory``'s OOM findings.

    Like ``bench_fleet``, the entry points need the emulated 8-device
    mesh, so the pass runs in a subprocess (``scripts/shardcheck.py
    --pass memory --json``); this relay prints one ``[bench] memflow
    <entry>`` line per searchable entry plus a summary line, and
    ``scripts/bench_compare.py`` gates ``memflow err`` per line
    direction-aware (phrased distinctly from shardflow's ``model err``
    and the search's ``layout err``). The per-entry peak table lands in
    the JSON line's ``memflow`` block. The signed error is structurally
    POSITIVE (memflow over-predicts: it cannot see XLA's rematerialized
    fusions freeing buffers early), which is what makes the budget gate
    safe — drift toward 0 is fine, drift NEGATIVE would mean the gate
    can pass layouts that OOM."""
    out = _emulated_child("shardcheck.py", "--pass", "memory", "--json")
    doc = json.loads(out)
    entries: dict = {}
    worst = 0.0
    for rec in doc.get("memory", []):
        rep, rc = rec["report"], rec["reconciled"]
        err = abs(float(rc["err_pct"]))
        worst = max(worst, err)
        _log(
            f"[bench] memflow {rec['name']}: predicted peak "
            f"{rep['peak_mib']:.1f} MiB/device at {rep['peak_where']}, "
            f"XLA measures {rc['measured_bytes'] / 2**20:.1f} MiB, "
            f"memflow err {err:.1f}%"
        )
        entries[rec["name"]] = {
            "peak_bytes": rep["peak_bytes"],
            "peak_where": rep["peak_where"],
            "measured_bytes": rc["measured_bytes"],
            "signed_err_pct": rc["signed_err_pct"],
            "unexplained": rc["unexplained"],
            "donated": rec["donated"],
        }
    if entries:
        _log(
            f"[bench] memflow summary: worst of {len(entries)} entries, "
            f"memflow err {worst:.1f}%"
        )
    return {"entries": entries, "worst_err_pct": worst} if entries else None


def bench_commscope():
    """Comm observatory (round 19): the measured per-axis α–β link
    profiles from the commscope calibration ladder plus the realized
    comm/compute overlap attribution of one saturated serving window
    (``telemetry/commscope.py`` + the goodput ledger's per-family
    device split).

    Like ``bench_fleet``, the ladder needs device multiplicity, so it
    runs on the emulated 8-device mesh in a subprocess
    (``scripts/perf_commscope.py --bench-lines``) whose ``[bench]``
    lines are relayed verbatim. ``scripts/bench_compare.py`` gates
    them direction-aware: ``axis bandwidth`` (higher), ``comm fit
    err`` / ``exposed comm`` / ``comm prediction err`` (lower). The
    ``overlap ratio`` is printed but NOT gated — overlapping more or
    less comm is a scheduling outcome, not monotonic goodness."""
    out = _emulated_child("perf_commscope.py", "--json")
    res = json.loads(out)
    for axis, ap in sorted(res["profile"].items()):
        _log(
            f"[bench] commscope axis {axis} (8-dev emulated): "
            f"axis bandwidth {ap['beta_gb_s']:.3f} GB/s, "
            f"alpha {ap['alpha_us']:.1f} us, "
            f"comm fit err {ap['fit_err_pct']:.1f}%"
        )
    ratio = res.get("overlap_ratio")
    _log(
        f"[bench] commscope overlap (8-dev emulated): "
        f"exposed comm {res['exposed_share_pct']:.2f}% of device, "
        f"overlap ratio "
        f"{(ratio or 0.0) * 100.0:.1f}%, "
        f"comm prediction err {res['model_err_pct']:.1f}%"
    )
    return {
        "profile": res["profile"],
        "exposed_share_pct": res["exposed_share_pct"],
        "overlap_ratio": ratio,
        "model_err_pct": res["model_err_pct"],
    }


def bench_topology():
    """Topology observatory (round 21): the two-tier interconnect model
    (``analysis/topology.py``) closed against real execution three ways,
    each a tracked bench_compare gate.

    * ``topo err`` per searchable entry — the overlap-aware prediction
      (``max(compute, memory) + exposed comm``) vs the measured step,
      from ``scripts/shardcheck.py --pass topo --json`` on the emulated
      8-device mesh (the same gate CI runs; the serial-sum error on the
      same line is context, not a gate — serial is the honest upper
      bound, not the claim).
    * ``dcn B/token`` + ``overlap gap`` — what the static model says
      the train step pushes across the slow tier per token, and how far
      the profile's pinned overlap ratio sits from the ledger's realized
      one (``decompose_overlap``); both lower-is-better drift signals.
    * ``topo argmin gap`` — the seeded two-tier acceptance scenario
      (``scripts/layout_search.py --topo-gap``, abstract pricing only):
      flat pricing parks the hot all-reduce on the DCN tier, topology
      pricing routes it onto ICI. Deterministic, so the gap collapsing
      toward 0 can only mean hierarchy pricing lost its discrimination
      power — gated HIGHER-is-better, the inverse of every error gate.
    """
    out = _emulated_child("shardcheck.py", "--pass", "topo", "--json")
    doc = json.loads(out)
    topo = doc.get("topo") or {}
    programs = topo.get("programs", [])
    entries: dict = {}
    worst = 0.0
    for pr in programs:
        err = float(pr["err_topo_pct"])
        worst = max(worst, err)
        _log(
            f"[bench] topo {pr['name']}: measured "
            f"{pr['measured_s'] * 1e3:.2f} ms vs overlap-aware "
            f"{pr['topo_predicted_s'] * 1e3:.2f} ms, topo err "
            f"{err:.1f}% (serial-sum {pr['err_serial_pct']:.1f}%), "
            f"dcn {pr['dcn_bytes'] / 1e3:.1f} kB predicted / "
            f"{pr['observed_dcn_bytes'] / 1e3:.1f} kB contract"
        )
        entries[pr["name"]] = {
            k: pr[k] for k in (
                "measured_s", "topo_predicted_s", "serial_predicted_s",
                "err_topo_pct", "err_serial_pct", "ici_bytes",
                "dcn_bytes", "observed_dcn_bytes",
            )
        }
    train = next(
        (p for p in programs if p["name"] == "train_step"), None
    )
    dcn_per_token = None
    if train and train.get("tokens_per_step"):
        dcn_per_token = (
            float(train["dcn_bytes"]) / float(train["tokens_per_step"])
        )
        _log(
            f"[bench] topo dcn: train_step moves {dcn_per_token:,.1f} "
            f"dcn B/token ({train['dcn_bytes']:.0f} B over "
            f"{train['tokens_per_step']} tokens)"
        )
    overlap_gap_pp = None
    if train:
        used = train.get("overlap_ratio_used")
        realized = (train.get("realized") or {}).get(
            "realized_overlap_ratio"
        )
        if used is not None and realized is not None:
            overlap_gap_pp = abs(float(used) - float(realized)) * 100.0
            _log(
                f"[bench] topo overlap: train_step profile predicts "
                f"{float(used):.2f}, ledger realized "
                f"{float(realized):.2f}, overlap gap "
                f"{overlap_gap_pp:.1f} pp"
            )
    # The seeded two-tier canary: abstract pricing, nothing compiles.
    gap_out = _emulated_child(
        "layout_search.py", "--topo-gap", timeout=600
    )
    argmin_block = _relay_bench_lines(gap_out)
    if entries:
        _log(
            f"[bench] topo summary: worst of {len(entries)} entries, "
            f"topo err {worst:.1f}%"
        )
    if not (entries or argmin_block):
        return None
    return {
        "profile": (topo.get("topology") or {}).get("name"),
        "entries": entries,
        "worst_err_pct": worst,
        "dcn_bytes_per_token": dcn_per_token,
        "overlap_predicted_vs_realized_pp": overlap_gap_pp,
        "argmin": argmin_block,
        "findings": [
            f for f in doc.get("findings", [])
            if f.get("check") == "topo"
        ],
    }


def bench_moe_125m():
    """MoE context line: 125M-class with E=8 top-2 routed FFs (GShard
    capacity routing, fp32 router — models/moe.py), same harness as the
    dense 125M step. MFU uses activated-FLOPs (top_k expert FFs + router),
    the honest denominator for routed models."""
    import dataclasses

    from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn

    cfg = dataclasses.replace(
        CONFIG_125M, attn_fn=make_flash_attn_fn(), num_experts=8, moe_top_k=2,
        moe_dispatch="scatter",
    )
    # sgd + b=4: non-donating timing holds INPUT and OUTPUT states at once,
    # and 2× the E=8 fp32 AdamW state (~6.8 GB each) exhausts the 16 GB
    # chip; sgd state is params-only. Round 4: scatter dispatch (routing
    # bit-identical to the einsum path, no (T,E,C) one-hot contractions)
    # replaced remat+einsum — without the stacked dispatch tensors the
    # activations fit un-rematerialized, and the measured ladder
    # (PERF.md round 4) has scatter+noremat at 67.8 ms vs the round-3
    # einsum+remat anchor's 97.8 in the same process.
    result, per_step, _ = _timed_train_step(cfg, b=4, K=2, opt=optax.sgd(3e-4))
    msg = (
        f"[bench] 125M-class MoE (E=8, top-2, scatter dispatch) train step "
        f"(b=4, sgd): {per_step * 1e3:.1f} ms/step"
    )
    if result.mfu is not None:
        msg += f", activated-MFU={result.mfu:.1%}"
    _log(msg)


def bench_moe_headline():
    """The MoE configuration the README headlines (VERDICT r4 item 5):
    E=4 WIDER experts (2x hidden), top-2, capacity 1.0, scatter dispatch,
    remat OFF — scatter has no (T,E,C) dispatch tensors to fit, so the
    activations fit un-rematerialized and routing cost vs the dense
    control collapses (PERF.md round-4 ladder: 46.3% vs 45.9% dense).
    ``bench_moe_125m`` keeps the E=8 cap1.25 workload for cross-round
    comparability; this line is the tuned configuration of record."""
    import dataclasses

    from learning_jax_sharding_tpu.ops.flash_attention import make_flash_attn_fn

    cfg = dataclasses.replace(
        CONFIG_125M, attn_fn=make_flash_attn_fn(), num_experts=4,
        hidden=2 * CONFIG_125M.hidden, moe_top_k=2,
        moe_capacity_factor=1.0, moe_dispatch="scatter", remat=False,
    )
    result, per_step, _ = _timed_train_step(cfg, b=4, K=2, opt=optax.sgd(3e-4))
    msg = (
        f"[bench] 125M-class MoE HEADLINE (E=4 wide, top-2, cap 1.0, "
        f"scatter, noremat) train step (b=4, sgd): {per_step * 1e3:.1f} ms/step"
    )
    if result.mfu is not None:
        msg += f", activated-MFU={result.mfu:.1%}"
    _log(msg)


def bench_serving_125m():
    """The serving-engine story, in the driver artifact (VERDICT r4 item
    2): the shared-system-prompt workload from
    ``scripts/perf_prefix_cache.py`` (512-token system prefix + 32
    request tokens, 24 requests through 8 slots, +32 generated) served by

    * the plain bf16 continuous engine,
    * the COMPOSED stack — int4-fused weights + paged KV (+ prefix), and
    * the prefix cache COLD (registry flushed per call — within-call
      sharing only, the round-4 comparison) and WARM (registry persisted
      from the previous call — the round-5 persistent-engine payoff: the
      system prompt is never re-prefilled).

    Interleaved rounds with per-variant medians, like the decode ladders
    (only within-window comparisons order reliably). Also reports the
    refill-pause share of engine time
    (VERDICT r4 item 9) and the warm prefix hit rate.
    """
    import dataclasses
    import time as _time

    import flax.linen as nn

    from learning_jax_sharding_tpu.models.quantize import quantize_tree
    from learning_jax_sharding_tpu.models.serving import make_continuous_engine

    cfg = dataclasses.replace(
        CONFIG_125M, max_seq_len=1024, decode_attention="blocked"
    )
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    model = Transformer(cfg)
    probe = np.zeros((8, 64), np.int32)
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(0), probe
        )["params"]
    )
    q4 = quantize_tree(params, bits=4)
    system = rng.integers(1, cfg.vocab_size, size=(512,)).astype(np.int32)
    NREQ, NEW = 24, 32
    prompts = [
        np.concatenate(
            [system,
             rng.integers(1, cfg.vocab_size, size=(32,)).astype(np.int32)]
        )
        for _ in range(NREQ)
    ]
    common = dict(
        batch_size=8, max_new_tokens=NEW, refill_chunk=64,
        inference_dtype=jnp.bfloat16,
        # Dispatch-granularity tuning (round 5, perf_block_ladder.py),
        # made on a remotely attached chip that no longer exists, where
        # a jitted call cost ~120 ms in the dispatch itself; the cost on
        # today's machine is not measured. K = max_new (one decode
        # dispatch per generation wave, rows retire exactly at the block
        # boundary) and chained refills (each 544-token prompt's
        # ceil(544/64) = 9 chunks ride one host sync).
        decode_block_steps=NEW, decode_chain=9,
    )
    PAGES = 8 * 10 + 1 + 12   # 8 slots x ceil(608/64) + scratch + slack
    plain = make_continuous_engine(cfg, mesh, RULES_DP_TP, **common)
    # The FUSED scheduler (round 9): every dispatch advances decode AND
    # pushes budgeted refill — the ITL/queue-wait engine. Budget 128 (two
    # chunks + the decode wave) from the perf_mixed.py ladder.
    mixed = make_continuous_engine(
        cfg, mesh, RULES_DP_TP, **common, mixed=True,
        token_budget=128 + 8,
    )
    paged4 = make_continuous_engine(
        cfg, mesh, RULES_DP_TP, **common, dequantize="fused",
        paged_pages=PAGES, page_size=64,
    )
    pfx4 = make_continuous_engine(
        cfg, mesh, RULES_DP_TP, **common, dequantize="fused",
        paged_pages=PAGES, page_size=64, prefix_cache=True,
    )

    def timed(serve, tree):
        t0 = _time.perf_counter()
        outs = serve(tree, prompts)
        dt = _time.perf_counter() - t0
        return dt, sum(len(o) - 544 for o in outs)

    variants = [
        ("bf16 engine", plain, params, None),
        ("bf16 mixed engine", mixed, params, None),
        ("int4-fused + paged", paged4, q4, None),
        ("int4 + paged + prefix (cold)", pfx4, q4, "cold"),
        ("int4 + paged + prefix (warm)", pfx4, q4, "warm"),
    ]
    # Warm every executable once (compiles excluded from the ladder).
    for _, serve, tree, mode in variants[:4]:
        serve(tree, prompts[:8])
    times = {name: [] for name, *_ in variants}
    toks = {}
    stats = {}
    for _ in range(3):
        for name, serve, tree, mode in variants:
            if mode == "cold":
                serve.engine.flush_prefix_cache()
            dt, n = timed(serve, tree)
            times[name].append(dt)
            toks[name] = n
            stats[name] = (serve.last_stats, serve.last_latency)
    base = None
    for name, *_ in variants:
        secs = float(np.median(times[name]))
        rate = toks[name] / secs
        if base is None:
            base = rate
        st, lat = stats[name]
        extra = ""
        if st and "prefix_hits" in st:
            extra += (
                f", hits {st['prefix_hits']}/{NREQ}"
                f" ({st['prefix_pages_reused']} pages reused)"
            )
        if lat and lat.get("refill_frac") is not None:
            extra += f", refill {lat['refill_frac']:.0%} of engine time"
        _log(
            f"[bench] 125M serving, {name}: {rate:,.0f} tok/s "
            f"({secs:.2f} s, {toks[name]} generated, "
            f"{rate / base:.2f}x bf16){extra}"
        )

    # bf16 speculation agreement guard (VERDICT r4 item 10): the verify
    # chunk evaluates num_draft+1 positions in one bf16 forward whose
    # logits differ in the last ulps from the plain path's S=1 forwards,
    # occasionally flipping a greedy argmax (fp32 oracle exact,
    # test-pinned). A SELF-draft isolates exactly that drift; recording
    # the agreement rate every round makes verify-chunk numerics
    # regressions visible. Round-4 observation: 97-99%.
    spec = make_continuous_engine(
        cfg, mesh, RULES_DP_TP, **common, draft_config=cfg, num_draft=4,
    )
    plain_outs = plain(params, prompts)
    spec_outs = spec(params, prompts, draft_params=params)
    agree = float(
        np.mean([
            np.mean(a[544:][: min(len(a), len(b)) - 544]
                    == b[544:][: min(len(a), len(b)) - 544])
            for a, b in zip(plain_outs, spec_outs)
        ])
    )
    _log(
        f"[bench] 125M serving, bf16 self-draft speculative token "
        f"agreement vs plain: {agree:.1%} (per-round drift guard; this "
        f"544-prompt/+32 queue first recorded ~90% — one early argmax "
        f"flip cascades through a short stream; the 64/+128 queue "
        f"recorded 97-99% in round 4)"
    )

    # Staggered-arrival latency (VERDICT r4 item 1): requests arrive over
    # time through the persistent engine's streaming API; TTFT and
    # per-token latency percentiles come from the engine's own telemetry.
    # Round 9: the TRACKED line runs the MIXED engine (decode advances in
    # every dispatch, refill rides the token budget, admission at chunk
    # granularity); the split engine's numbers stay as the stall
    # baseline so bench_compare sees both trajectories.
    def staggered(eng, label):
        eng.decode_chain = 1    # latency-sensitive: no chain coarsening
        eng.reset_stats()
        arrivals = list(prompts[:16])
        gap = 0.05                   # 20 req/s offered load
        t0 = _time.perf_counter()
        nxt = 0
        while eng.has_work() or nxt < len(arrivals):
            while (
                nxt < len(arrivals)
                and _time.perf_counter() - t0 >= nxt * gap
            ):
                eng.add_request(arrivals[nxt])
                nxt += 1
            eng.step(params)
        dt = _time.perf_counter() - t0
        outs = eng.pop_finished()
        toks = sum(len(o) - 544 for o in outs.values())
        generated = toks
        lat = eng.latency_stats()
        extras = f", {toks / dt:,.0f} tok/s"
        if lat.get("refill_frac") is not None:
            extras += f", refill {lat['refill_frac']:.0%} of engine time"
        if lat.get("decode_stall_share") is not None:
            extras += f", decode stalled {lat['decode_stall_share']:.0%}"
        # Recovery-policy telemetry (round 10): with no faults these must
        # hold at 0 — bench_compare gates them direction-aware, so the
        # deadline/admission hooks can't silently start shedding clean
        # traffic.
        extras += (
            f", shed {lat.get('shed_rate', 0.0):.0%}"
            f", deadline miss {lat.get('deadline_miss_rate', 0.0):.0%}"
        )
        _log(
            f"[bench] 125M serving latency{label} (16 staggered arrivals, "
            f"{1 / gap:.0f} req/s): TTFT p50 {lat['ttft_p50'] * 1e3:.0f} ms"
            f" / p99 {lat['ttft_p99'] * 1e3:.0f} ms, TPOT p50 "
            f"{lat['tpot_p50'] * 1e3:.1f} ms, ITL p99 "
            f"{lat['itl_p99'] * 1e3:.0f} ms, queue wait p50 "
            f"{lat['queue_wait_p50'] * 1e3:.0f} ms{extras}"
        )
        return generated

    # The latency engine re-tunes the two mixed knobs (perf_mixed.py
    # ladder): budget 128+B bounds each fused dispatch (the ITL gap a
    # decoding row sees while prompts stream), and decode_block_steps=8
    # bounds the PURE-DECODE fallback's token-visibility gap (in mixed
    # mode the block program only runs when there is no refill to fuse,
    # so a small K costs a few extra tail dispatches, not refill
    # overlap).
    # Recovery hooks ON but never tripping (round 10): a 300 s TTL and a
    # 256-deep queue bound are far beyond this workload, so the tracked
    # line now PRICES the deadline sweep + admission check — the <2%
    # overhead budget PERF.md round 10 measures (scripts/perf_recovery.py).
    mixed_lat = make_continuous_engine(
        cfg, mesh, RULES_DP_TP,
        **{**common, "decode_block_steps": 8},
        mixed=True, token_budget=128 + 8,
        deadline_s=300.0, max_queue=256,
    )
    # Warm before the tracked run: this engine's executables (its
    # decode_block_steps differs from the ladder's warmed engines) must
    # compile outside the measured window — staggered() resets stats, so
    # the warm pass leaves no trace in the gated percentiles.
    mixed_lat(params, prompts[:8])
    # Goodput accounting rides the tracked staggered run (round 14): the
    # engine's ledger windows with reset_stats, a TraceStore collects
    # every request's critical path, and the decode roofline (each
    # generation wave streams the bf16 weights once per batch) prices
    # what an ideally-scheduled device would have needed — host_share /
    # goodput_ratio / telemetry overhead become gated bench facts.
    from learning_jax_sharding_tpu.analysis.costmodel import current_profile
    from learning_jax_sharding_tpu.telemetry import TraceStore

    eng = mixed_lat.engine
    eng.trace_sink = TraceStore(registry=eng.registry)
    generated = staggered(eng, "")
    prof = current_profile()
    wbytes = sum(x.size for x in jax.tree.leaves(params)) * 2  # bf16
    roofline = (
        (generated / common["batch_size"]) * wbytes
        / max(prof.hbm_bw * prof.mbu_eff, 1.0)
    )
    rep = eng.ledger.window_report(roofline_device_s=roofline)
    rec = eng.ledger.reconcile()
    cps = eng.trace_sink.completed()
    ttfts = [cp["ttft_s"] for cp in cps if cp["ttft_s"] is not None]
    cp50 = float(np.percentile(ttfts, 50)) * 1e3 if ttfts else None
    cp99 = float(np.percentile(ttfts, 99)) * 1e3 if ttfts else None
    _log(
        f"[bench] goodput: host_share {(rep['host_share'] or 0) * 100:.1f}%, "
        f"goodput_ratio {rep['goodput_ratio'] * 100:.2f}%, "
        f"top contributor {rep['top_contributor']} "
        f"({rep['top_contributor_s']:.2f} s of {rep['wall_s']:.2f} s), "
        f"telemetry overhead {rep['telemetry_share'] * 100:.2f}%, "
        f"TTFT critical path p50 {cp50:.0f} ms / p99 {cp99:.0f} ms, "
        f"reconcile {'ok' if rec['ok'] else 'FAILED'} "
        f"(residual {rec['residual_s'] * 1e3:.2f} ms)"
    )
    goodput_block = {
        "host_share": rep["host_share"],
        "goodput_ratio": rep["goodput_ratio"],
        "roofline_device_s": roofline,
        "top_contributor": rep["top_contributor"],
        "top_contributor_s": rep["top_contributor_s"],
        "telemetry_share": rep["telemetry_share"],
        "buckets": rep["buckets"],
        "wall_s": rep["wall_s"],
        "reconcile_ok": rec["ok"],
        "reconcile_residual_s": rec["residual_s"],
        "ttft_critical_path_p50_ms": cp50,
        "ttft_critical_path_p99_ms": cp99,
        "traced_requests": len(cps),
    }
    staggered(plain.engine, " split-engine baseline")
    return goodput_block


def bench_fleet():
    """Fleet serving trajectory (round 11): aggregate tok/s and
    router-side e2e tail vs replica count, plus the disaggregated
    2-prefill + 2-decode split with its streamed-KV volume.

    The fleet needs device MULTIPLICITY (replica sub-meshes) that the
    one-chip bench host lacks, so the ladder runs on the emulated
    8-device mesh in a SUBPROCESS (``scripts/perf_fleet.py
    --bench-lines``) and its ``[bench]`` lines are relayed verbatim into
    this run's stderr tail — ``scripts/bench_compare.py`` then gates
    aggregate tok/s and e2e p99 direction-aware per replica count, like
    every other tracked line. Router/handoff overhead is what the
    emulated ladder prices; chip-level scaling claims wait for a
    multi-chip host."""
    _relay_bench_lines(_emulated_child("perf_fleet.py", "--bench-lines"))


def bench_economics():
    """Workload observatory (round 20): the canonical 24h-compressed
    day replayed through a K=4 unified fleet
    (``fleet/loadgen.py``), JOINed into the per-tenant bill
    (``telemetry/economics.py``) — fleet goodput ratio under the paced
    trace, fleet-wide cost per generated token, and the worst tenant's
    SLO burn rate.

    Like ``bench_fleet``, the replay needs device multiplicity, so it
    runs on the emulated 8-device mesh in a subprocess
    (``scripts/replay.py --json``) and its ``[bench]`` line is relayed.
    ``scripts/bench_compare.py`` gates ``goodput_ratio`` (higher),
    ``cost/token`` (lower), and ``worst tenant burn`` (lower — the
    zero-old floor means a clean 0.00 baseline still fails a round
    that starts burning). The returned block also carries the
    conservation verdict: Σ per-tenant device-seconds must equal the
    fleet ledger's device bucket — attribution that invents or drops
    seconds is a bug, not a pricing choice."""
    out = _emulated_child("replay.py", "--json")
    res = json.loads(out)
    _log(res["bench_line"])
    return {
        k: res[k] for k in (
            "k", "speed", "offered", "admitted", "shed",
            "generated_tokens", "goodput_ratio", "cost_per_token_usd",
            "worst_tenant", "worst_tenant_burn_rate", "conservation_ok",
        )
    }


def bench_autoscale():
    """Elastic fleet (round 23): the canonical day replayed twice under
    identical pacing — a static oracle at the planner's best K (the
    SLO-burn threshold) and the elastic fleet (plan floor fed forward,
    SLO-burn loop above it). Like ``bench_economics``, the replay needs
    device multiplicity, so it runs on the emulated mesh in a
    subprocess and its ``[bench]`` line is relayed.
    ``scripts/bench_compare.py`` gates ``elastic uusd/tok`` (lower),
    ``drain p99`` (lower) and ``planner gap`` (lower); peak/final burn
    vs the oracle print for context only (the settled comparison is
    stable, the 50 ms-sample peak jitters with wall-clock pacing on a
    loaded host — a trajectory gate on it would flake)."""
    out = _emulated_child("replay.py", "--autoscale", "--json")
    res = json.loads(out)
    _log(res["bench_line"])
    return {
        k: res[k] for k in (
            "k0", "k_max", "speed", "generated_tokens", "shed",
            "elastic_cost_per_token_usd", "static_cost_per_token_usd",
            "best_static_k", "peak_burn", "static_oracle_peak_burn",
            "worst_tenant_burn_rate", "static_oracle_final_burn",
            "drain_ms_p99", "planner_gap_pct", "decisions",
            "conservation_ok",
        )
    }


def bench_multistep():
    """Multi-step scheduling horizon ladder (round 16): the fused
    ``multi_step`` program (one dispatch per N engine iterations, host
    demoted to an async next-horizon planner) vs today's
    per-iteration loop, N ∈ {1, 2, 4, 8, 16}.

    The ladder is host-loop physics over the emulated 8-device mesh —
    nothing chip-specific — so it runs in a subprocess
    (``scripts/perf_hostloop.py --bench-lines``) whose lines are
    relayed, exactly like ``bench_fleet``. Two regimes per rung: "raw"
    (emulated mesh as-is; owns the structural metrics — host_share,
    steps/dispatch, boundary stall) and "multistep" (a modeled fixed
    per-dispatch cost through the ``engine.dispatch`` seam, the
    regime of BENCH r05's remotely attached chip; owns the headline
    tok/s).
    ``scripts/bench_compare.py`` gates host_share_pct (down) and
    steps_per_dispatch (up) per rung, direction-aware."""
    _relay_bench_lines(_emulated_child("perf_hostloop.py", "--bench-lines"))


def bench_kv_economy():
    """KV economy A/B (round 15): the SAME 80%-prefix-overlap traffic
    mix through K=4 paged replicas, prefix-aware (``KvEconomy`` wired:
    placement scores predicted prefix-hit tokens, cold chains demote
    HBM → host RAM, placed requests promote back on admission) vs
    prefix-blind (round-11 load + burn score only).

    Placement quality and the tier ladder are host/router machinery
    over replica MULTIPLICITY, nothing chip-specific, so the A/B runs
    on the emulated 8-device mesh in a subprocess
    (``scripts/perf_kv_economy.py --bench-lines``) whose lines are
    relayed, exactly like ``bench_fleet``. Tracked per config:
    aggregate tok/s and fleet TTFT p99, plus the aware side's realized
    prefix-hit rate, tier-miss rate, and kv bytes moved per request —
    all gated direction-aware by ``scripts/bench_compare.py``."""
    _relay_bench_lines(_emulated_child("perf_kv_economy.py", "--bench-lines"))


def bench_compression():
    """Comm compression A/B (round 22): the quantized TP all-reduce
    (plain vs int8 block-scaled mixed engine, with the greedy-agreement
    check the drift oracle holds at 100%) and the compressed KV tier
    ladder (K=2 fleet, ``int8_delta`` page codec — wire vs raw kB per
    request and their ratio).

    Codec passes and wire accounting are host machinery, nothing
    chip-specific, so the A/B runs on the emulated 8-device mesh in a
    subprocess (``scripts/perf_compression.py --bench-lines``) whose
    lines are relayed, exactly like ``bench_fleet``. All four numbers
    (compressed tok/s, q8 agreement, kv wire kB/req, compression
    ratio) are gated direction-aware by ``scripts/bench_compare.py``."""
    _relay_bench_lines(_emulated_child("perf_compression.py", "--bench-lines"))


def bench_tenancy():
    """Tenancy (round 12): zero-downtime weight hot-swap under load at
    125M, plus the multi-LoRA mixed-batch ladder.

    The device part serves a saturated queue through the 125M MIXED
    engine while drain-mode ``swap_weights`` rollouts land every few
    dispatches — tracked numbers are the swap stall (the stage → commit
    serve gap, from the engine's ``engine.swap_commit`` events) p50/p99
    and throughput during the rollout vs undisturbed. The warm pass
    commits one swap and serves through the swapped-in weights first:
    the staged tree's layout differs from the born-init layout, and the
    one-time post-commit recompile must not land in the timed rollout.

    The multi-LoRA ladder (mixed-adapter vs solo tok/s at 1/4/16
    adapters) prices host-side pool machinery, nothing chip-specific, so
    it runs on the emulated 8-device mesh in a subprocess
    (``scripts/perf_tenancy.py --bench-lines``) whose lines are relayed,
    exactly like ``bench_fleet``.
    """
    import dataclasses
    import time as _time

    import flax.linen as nn

    from learning_jax_sharding_tpu.models.serving import make_continuous_engine

    cfg = dataclasses.replace(CONFIG_125M, max_seq_len=1024)
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    rng = np.random.default_rng(5)
    model = Transformer(cfg)
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(0), np.zeros((8, 64), np.int32)
        )["params"]
    )
    new_params = jax.jit(
        lambda t: jax.tree.map(lambda x: x * (1.0 + 1e-3), t)
    )(params)
    NREQ, NEW = 16, 32
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(64,)).astype(np.int32)
        for _ in range(NREQ)
    ]
    serve = make_continuous_engine(
        cfg, mesh, RULES_DP_TP, batch_size=8, max_new_tokens=NEW,
        refill_chunk=64, inference_dtype=jnp.bfloat16, mixed=True,
        token_budget=128 + 8, decode_block_steps=NEW,
    )
    eng = serve.engine

    def drive(reqs, swap_every=None, versions=()):
        plen = {}
        for p in reqs:
            plen[eng.add_request(p)] = len(p)
        vq = list(versions)
        steps = 0
        t0 = _time.perf_counter()
        while eng.has_work():
            if (
                vq and swap_every and steps % swap_every == swap_every - 1
                and not eng.swap_pending
            ):
                v = vq.pop(0)
                eng.swap_weights(
                    new_params if v % 2 else params, version=v,
                )
            eng.step(params)
            steps += 1
        dt = _time.perf_counter() - t0
        gen = sum(
            len(t) - plen[rid] for rid, t in eng.pop_finished().items()
            if not hasattr(t, "status")
        )
        return dt, gen

    drive(prompts[:9])                       # warm: first_refill + mixed step
    eng.swap_weights(new_params, version=1)  # warm the stage + commit path
    while eng.has_work():
        eng.step(params)
    drive(prompts[:9])                       # warm the post-commit layout
    dt0, gen0 = drive(prompts)               # undisturbed baseline
    eng.recorder.clear()
    dt, gen = drive(prompts, swap_every=2, versions=[2, 3, 4, 5, 6])
    stalls = np.asarray([
        e["stall_s"] for e in eng.recorder.events("engine.swap_commit")
    ])
    _log(
        f"[bench] 125M hot-swap under load: "
        f"swap stall p50 {np.percentile(stalls, 50) * 1e3:,.0f} ms, "
        f"swap stall p99 {np.percentile(stalls, 99) * 1e3:,.0f} ms "
        f"({len(stalls)} swaps, {gen / dt:,.0f} tok/s during rollout vs "
        f"{gen0 / dt0:,.0f} tok/s undisturbed)"
    )

    _relay_bench_lines(_emulated_child("perf_tenancy.py", "--bench-lines"))


def _device_ready(timeout_s: float = 600.0) -> bool:
    """Probe the device with a tiny op under a watchdog: False when the
    device does not answer within ``timeout_s``, so the run fails loudly
    instead of hanging on its first real program. An error the op raises
    is re-raised with its cause.
    """
    import threading

    ok = threading.Event()
    err: list[BaseException] = []

    def probe():
        try:
            np.asarray(jnp.ones((8, 8)).sum())
        except BaseException as e:
            err.append(e)
            raise
        ok.set()

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    while t.is_alive() and not err:
        t.join(1.0)
        timeout_s -= 1.0
        if timeout_s <= 0:
            break
    if err:  # a real error, not a hang — surface it with its cause
        raise err[0]
    return ok.is_set()


def _diagnosis_block(headline_axis_volume):
    """The round-7 diagnosis summary for the JSON line: predicted HBM for
    the 125M bench configuration vs the chip's LIVE watermark (devview —
    guarded: backends without memory stats report plan-only), plus the
    headline executable's per-mesh-axis collective bytes. Machine-readable
    per round, so drifts in either become bench_compare-visible facts."""
    import dataclasses as _dc

    from learning_jax_sharding_tpu.ops.flash_attention import (
        make_flash_attn_fn,
    )
    from learning_jax_sharding_tpu.telemetry import memory_report
    from learning_jax_sharding_tpu.utils.memory import memory_plan

    cfg = _dc.replace(CONFIG_125M, attn_fn=make_flash_attn_fn())
    plan = memory_plan(cfg, 8, 1024, donate_state=False)
    mem = memory_report(plan)
    block = {
        "memory_predicted_bytes": plan.total,
        "memory_actual_available": mem["actual_available"],
        "memory_actual_peak_bytes": mem.get("actual_peak_bytes"),
        "memory_predicted_over_actual": mem.get("predicted_over_actual"),
        "memory_hbm_bytes": mem.get("hbm_bytes"),
        "headline_collective_bytes_per_axis": headline_axis_volume,
    }
    actual = block["memory_actual_peak_bytes"]
    _log(
        f"[bench] diagnosis: 125M step predicted "
        f"{plan.total / 1e9:.2f} GB"
        + (
            f", device peak {actual / 1e9:.2f} GB "
            f"(predicted/actual {block['memory_predicted_over_actual']:.2f})"
            if actual else ", no live memory stats (plan-only)"
        )
    )
    return block


def _phase_telemetry(watch, before, label):
    """Delta of a CompileWatch report across one phase → a log line plus
    the dict that lands in the JSON telemetry block: compile seconds are
    the one-time cost the steady-state numbers exclude, and the split
    makes 'how much of this run was XLA' a recorded fact per round."""
    after = watch.report()
    delta = {
        k: after[k] - before[k]
        for k in after if isinstance(after[k], (int, float))
    }
    _log(
        f"[bench] telemetry {label}: {delta['backend_compiles']} backend "
        f"compiles, {delta['backend_compile_seconds']:.2f} s compile "
        f"({delta['traces']} traces, {delta['trace_seconds']:.2f} s)"
    )
    return delta


def main():
    from learning_jax_sharding_tpu.telemetry import CompileWatch
    from learning_jax_sharding_tpu.utils.compile_cache import (
        place_compile_cache,
    )

    place_compile_cache()
    if not _device_ready():
        _log("[bench] FATAL: the device did not answer a trivial op")
        sys.exit(1)
    dev = jax.devices()[0]
    _log(f"[bench] device: {dev.device_kind} ({dev.platform}), "
         f"peak bf16 {device_peak_flops(dev)}")

    watch = CompileWatch().start()
    base_report = watch.report()
    ours = bench_attention(jnp.bfloat16, "case6 attention (ours, bf16)")
    headline_compile = _phase_telemetry(
        watch, base_report, "case6 attention headline phase"
    )
    baseline = bench_attention(jnp.float32, "case6 attention (reference-faithful, fp32)")

    failed: list[str] = []

    def block(label, fn, *args):
        """Run one context block. A block that raises is logged and the
        JSON line is still printed, but the run then exits 1: a broken
        block must not read as a clean run with a line missing."""
        try:
            return fn(*args)
        except Exception as e:
            failed.append(label)
            _log(f"[bench] {label} bench FAILED: {type(e).__name__}: {e}")
            return None

    block("125M transformer", bench_transformer_125m)
    block("long-context", bench_longcontext)
    block("125M decode", bench_decode_125m)
    block("1.4B decode", bench_decode_1p4b)
    goodput_block = block("serving", bench_serving_125m)
    block("fleet", bench_fleet)
    block("multistep", bench_multistep)
    block("kv economy", bench_kv_economy)
    block("compression", bench_compression)
    block("tenancy", bench_tenancy)
    block("MoE", bench_moe_125m)
    block("MoE headline", bench_moe_headline)
    block("reference-config", bench_reference_configs)
    shardflow_block = block("shardflow", bench_shardflow)
    layout_search_block = block("layout_search", bench_layout_search)
    memflow_block = block("memflow", bench_memflow)
    commscope_block = block("commscope", bench_commscope)
    economics_block = block("economics", bench_economics)
    topology_block = block("topology", bench_topology)
    autoscale_block = block("autoscale", bench_autoscale)

    watch.stop()
    run_report = watch.report()
    diagnosis = block("diagnosis", _diagnosis_block, ours["axis_volume"])
    ours_tf, base_tf = ours["tflops"], baseline["tflops"]
    vs_baseline = (ours_tf / base_tf) if (ours_tf and base_tf) else None
    print(json.dumps({
        "metric": "case6_attention_tflops_per_chip",
        "value": round(ours_tf, 3) if ours_tf else None,
        "unit": "TFLOP/s/chip",
        "vs_baseline": round(vs_baseline, 3) if vs_baseline else None,
        # Per-phase telemetry (compile_watch): one-time compile cost vs
        # the steady-state per-iteration time the headline measures, and
        # the headline executable's collective inventory.
        "telemetry": {
            "headline_steady_seconds_per_forward": (
                round(ours["seconds_per_forward"], 9)
            ),
            "headline_backend_compiles": (
                headline_compile["backend_compiles"]
            ),
            "headline_backend_compile_seconds": round(
                headline_compile["backend_compile_seconds"], 3
            ),
            "headline_collectives": ours["collectives"],
            "run_backend_compiles": run_report["backend_compiles"],
            "run_backend_compile_seconds": round(
                run_report["backend_compile_seconds"], 3
            ),
            "run_trace_seconds": round(run_report["trace_seconds"], 3),
            "monitoring_available": run_report["monitoring_available"],
        },
        # Round-7 diagnosis: predicted-vs-actual memory + per-axis
        # collective bytes (telemetry.devview).
        "diagnosis": diagnosis,
        # Round-13 analyzer self-check: the cost model's predicted step
        # time vs the measured one for the tracked shapes
        # (analysis.shardflow + costmodel; gated by bench_compare).
        "shardflow": shardflow_block,
        # Round-17 layout-search closed loop: the searched-vs-hand
        # priced gap for the tracked train step and the measured
        # confirmation on the two compiled layouts (analysis/
        # layout_search.py; gated by bench_compare's `layout gap` /
        # `layout err` patterns).
        "layout_search": layout_search_block,
        # Round-18 memflow reconciliation: the static liveness
        # analyzer's per-entry predicted peak vs XLA's memory_analysis
        # on the searchable entries (analysis/memflow.py; gated by
        # bench_compare's `memflow err` pattern) — the accuracy bound
        # on the layout search's HBM budget gate.
        "memflow": memflow_block,
        # Round-19 comm observatory: measured per-axis α–β link
        # profiles (commscope calibration ladder) and the serving
        # window's realized comm/compute overlap decomposition
        # (telemetry/commscope.py; gated by bench_compare's
        # `axis bandwidth` / `comm fit err` / `exposed comm` /
        # `comm prediction err` patterns).
        "commscope": commscope_block,
        # Round-20 workload observatory: the canonical day replayed
        # through a K=4 fleet, priced per tenant (fleet/loadgen.py +
        # telemetry/economics.py; gated by bench_compare's
        # `goodput_ratio` / `cost/token` / `worst tenant burn`
        # patterns), with the tier-1 conservation verdict.
        "economics": economics_block,
        # Round-21 topology observatory: the two-tier interconnect
        # model's reconcile errors per searchable entry, the train
        # step's priced DCN bytes/token and overlap prediction gap, and
        # the seeded flat-vs-topo argmin canary (analysis/topology.py;
        # gated by bench_compare's `topo err` / `dcn B/token` /
        # `overlap gap` / `topo argmin gap` patterns).
        "topology": topology_block,
        # Round-23 elastic fleet: the canonical day on the autoscaled
        # fleet vs the planner's best static K under identical pacing
        # (fleet/autoscaler.py + fleet/capacity.py; gated by
        # bench_compare's `elastic uusd/tok` / `drain p99` /
        # `planner gap` patterns), with burn-vs-oracle context.
        "autoscale": autoscale_block,
        # Round-14 goodput ledger: where the tracked serving window's
        # wall-clock went (exclusive buckets, Σ == wall reconciled),
        # host_share / goodput_ratio vs the decode roofline, and the
        # trace-derived TTFT critical-path tails — the measured
        # anatomy of ROADMAP item 1's host-vs-device gap.
        "goodput": goodput_block,
    }), flush=True)
    if failed:
        _log(f"[bench] FAILED blocks: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
