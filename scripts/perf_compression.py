#!/usr/bin/env python
"""Comm-compression A/B on the emulated 8-device mesh (PERF.md round 22).

Two measurements, both host/wire machinery rather than chip FLOPs, so
they run emulated and feed ``bench.py`` via relayed ``[bench]`` lines:

* **quantized TP collectives** — the same prompt set through the (2,4)
  MIXED engine twice: plain fp32 all-reduce vs the int8 block-scaled
  wire (``ContinuousEngine(comm_compression=CommCompression())``).
  Tracked: plain and compressed tok/s (emulated-CPU numbers pay the
  codec's element work without the wire it buys back — chip numbers
  are not measured; the gate keeps the compressed
  path from silently bloating) and the greedy token agreement between
  the two engines, which the drift oracle holds at 100%.
* **compressed KV movement** — a K=2 tiered fleet (prefix cache on,
  ``KvEconomy`` demoting cold chains each step) serving a
  prefix-overlapping mix with the ``int8_delta`` page codec. Tracked:
  KV wire kB per request (what actually crossed the host/peer buses,
  post-codec), the raw kB the same pages weighed pre-codec, and their
  ratio — the headline wire reduction the layer exists for (≥ 1.8×
  for bf16 pages, ≈ 3.6× for the f32 pages measured here).

Usage:
    python scripts/perf_compression.py [--bench-lines] [--json]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from learning_jax_sharding_tpu.parallel import force_emulated_devices  # noqa: E402

force_emulated_devices(8)

import dataclasses  # noqa: E402

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

NREQ, NEW = 8, 8


def _tp_setup():
    from learning_jax_sharding_tpu.analysis.entrypoints import (
        _sharded_serving_params,
    )
    from learning_jax_sharding_tpu.models.transformer import (
        CONFIG_TINY,
        Transformer,
    )
    from learning_jax_sharding_tpu.parallel import build_mesh
    from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING

    cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
    mesh = build_mesh((2, 4), ("data", "model"))
    params = _sharded_serving_params(Transformer(cfg), mesh, RULES_TP_SERVING)
    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in rng.integers(5, 24, size=NREQ)
    ]
    return cfg, mesh, params, prompts


def _tp_engine(cfg, mesh, comm=None):
    from learning_jax_sharding_tpu.models.serving import ContinuousEngine
    from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING

    return ContinuousEngine(
        cfg, mesh, RULES_TP_SERVING, batch_size=2, max_new_tokens=NEW,
        refill_chunk=16, decode_block_steps=4, mixed=True,
        comm_compression=comm,
    )


def _timed_serve(eng, params, prompts, repeats=3):
    out = eng.serve(params, prompts)          # warm (compiles out)
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = eng.serve(params, prompts)
        dt = time.perf_counter() - t0
        if best is None or dt < best:
            best = dt
    gen = sum(len(t) - len(p) for t, p in zip(out, prompts))
    return out, gen / best


def run_quantized_collectives():
    from learning_jax_sharding_tpu.parallel.compression import (
        CommCompression,
    )

    cfg, mesh, params, prompts = _tp_setup()
    plain_out, plain_rate = _timed_serve(_tp_engine(cfg, mesh), params, prompts)
    comp_out, comp_rate = _timed_serve(
        _tp_engine(cfg, mesh, CommCompression()), params, prompts
    )
    agree = np.mean([
        float((np.asarray(a) == np.asarray(b)).all())
        for a, b in zip(plain_out, comp_out)
    ])
    line = (
        f"[bench] comm compression mixed 2x4: "
        f"plain {plain_rate:,.0f} tok/s, "
        f"compressed {comp_rate:,.0f} tok/s "
        f"(q8 agreement {agree * 100:.0f}%)"
    )
    summary = dict(
        config="quantized_collectives", plain_tok_s=plain_rate,
        compressed_tok_s=comp_rate, q8_agreement=agree,
    )
    return [line], [summary]


def run_compressed_kv():
    from learning_jax_sharding_tpu.fleet import (
        FleetPolicy,
        FleetRouter,
        KvEconomy,
        make_replicas,
    )
    from learning_jax_sharding_tpu.models.transformer import (
        CONFIG_TINY,
        Transformer,
    )
    from learning_jax_sharding_tpu.parallel.compression import (
        CommCompression,
    )
    from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP

    PAGE = 4
    cfg = dataclasses.replace(
        CONFIG_TINY, dtype=jnp.float32, decode_attention="blocked",
    )
    model = Transformer(cfg)
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(0), np.zeros((2, 8), np.int32)
        )["params"]
    )
    rng = np.random.default_rng(7)
    bases = [
        rng.integers(1, cfg.vocab_size, size=(PAGE * 2,)).astype(np.int32)
        for _ in range(4)
    ]
    prompts = [
        np.concatenate([
            bases[i % len(bases)],
            rng.integers(1, cfg.vocab_size, size=(3,)).astype(np.int32),
        ])
        for i in range(12)
    ]
    reps = make_replicas(
        cfg, RULES_DP_TP, params, count=2, mesh_shape=(1, 1),
        batch_size=2, max_new_tokens=4, refill_chunk=8,
        paged_pages=12, page_size=PAGE, prefix_cache=True,
        comm_compression=CommCompression(
            collectives=False, kv_codec="int8_delta"
        ),
    )
    econ = KvEconomy(hbm_retained_target=0, burn_threshold=1e9)
    router = FleetRouter(
        reps, policy=FleetPolicy(prefix_weight=0.5), kv_economy=econ,
    )
    for p in prompts:
        router.add_request(p)
    router.drain(max_steps=4000)
    rep = econ.tier_report()
    lat = router.latency_stats()
    wire = rep["spill_bytes"] + rep["fill_bytes"]
    raw = rep["raw_bytes"]
    nreq = max(1, lat["requests"])
    ratio = raw / max(1, wire)
    line = (
        f"[bench] comm compression kv K=2 (int8_delta): "
        f"kv wire {wire / nreq / 1e3:,.1f} kB/req "
        f"vs {raw / nreq / 1e3:,.1f} kB/req raw, "
        f"compression ratio {ratio:,.2f}x "
        f"({rep['demotions']} demotions, {rep['promotions']} promotions)"
    )
    summary = dict(
        config="compressed_kv", kv_wire_bytes_per_req=wire / nreq,
        kv_raw_bytes_per_req=raw / nreq, compression_ratio=ratio,
        demotions=rep["demotions"], promotions=rep["promotions"],
    )
    return [line], [summary]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench-lines", action="store_true",
                    help="print only the [bench] lines (for bench.py)")
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    lines, summary = run_quantized_collectives()
    kv_lines, kv_summary = run_compressed_kv()
    lines += kv_lines
    summary += kv_summary
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        for ln in lines:
            print(ln)
    if not args.bench_lines and not args.json:
        print("perf_compression: done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
