"""Long-context SERVING through the paged engine (VERDICT r4 item 8).

Training is measured to S=16k; serving stopped at 512-token prompts.
This drives S=4096 prompts through the full serving composition —
chunked refill (512-token chunks stream each prompt through 8 refill
dispatches) × paged page pool × blocked decode kernel — and measures
what long-prompt serving is about: PREFILL throughput, TTFT at depth,
and the page high-water. Bit-identity of chunked refill × paging is
pinned in tests at every scale (the mechanisms are length-blind); this
is the at-depth measurement.

Queue: 8 requests of S=4096 (each its own content), 4 slots, +32
generated, 125M bf16 at max_seq_len=8192. TTFT percentiles come from
the engine's own telemetry (arrival = all at t0, so TTFT includes queue
wait for the second admission wave — the honest serving number).

Run from /root/repo:  python - < scripts/perf_longserve.py
"""
import dataclasses
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from learning_jax_sharding_tpu.models.serving import ContinuousEngine
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_125M,
    Transformer,
)
from learning_jax_sharding_tpu.parallel import build_mesh
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP

S, NEW, NREQ, SLOTS = 4096, 32, 8, 4
cfg = dataclasses.replace(
    CONFIG_125M, max_seq_len=8192, decode_attention="blocked"
)
mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
rng = np.random.default_rng(0)
model = Transformer(cfg)
probe = np.zeros((SLOTS, 64), np.int32)
params = nn.meta.unbox(
    jax.jit(lambda r, t: model.init({"params": r}, t))(
        jax.random.key(0), probe
    )["params"]
)
params = jax.tree.map(
    lambda x: x.astype(jnp.bfloat16)
    if jnp.issubdtype(x.dtype, jnp.floating) else x,
    params,
)
prompts = [
    rng.integers(1, cfg.vocab_size, size=(S,)).astype(np.int32)
    for _ in range(NREQ)
]

# decode_chain=8 (round 5): each prompt's 8 refill chunks ride ONE host
# sync instead of eight — a ~110 ms/dispatch round trip to round 5's
# remotely attached chip dominated the first (unchained) measurement. Page-size ladder: the
# paged kernel's k-grid steps at page granularity, so page 64 walks
# 128 grid steps per q-tile at L=8192 where page 256 walks 32 — the
# long-context page-size tradeoff (vs prefix-sharing granularity).
for PAGE in (64, 256):
    pages_per_req = -(-(S + NEW) // PAGE)
    PAGES = SLOTS * pages_per_req + 1 + 4
    eng = ContinuousEngine(
        cfg, mesh, RULES_DP_TP, batch_size=SLOTS, max_new_tokens=NEW,
        refill_chunk=512, inference_dtype=jnp.bfloat16,
        paged_pages=PAGES, page_size=PAGE, decode_chain=8,
    )
    # Warm the executables on a short queue (compiles excluded).
    eng.serve(params, [p[:600] for p in prompts[:SLOTS]])

    eng.reset_stats()
    t0 = time.perf_counter()
    outs = eng.serve(params, prompts)
    dt = time.perf_counter() - t0
    lat = eng.last_latency
    st = eng.last_stats
    prefill_toks = NREQ * S
    gen_toks = sum(len(o) - S for o in outs)
    assert all(len(o) == S + NEW for o in outs)
    print(
        f"[longserve] page={PAGE}: {NREQ} x S={S} prompts, {SLOTS} slots, "
        f"+{NEW} out: {dt:.2f} s wall, {prefill_toks:,} prompt tokens + "
        f"{gen_toks} generated",
        flush=True,
    )
    print(
        f"[longserve] page={PAGE}: prefill throughput "
        f"{prefill_toks / lat['refill_s']:,.0f} tok/s "
        f"(refill {lat['refill_frac']:.0%} of engine time); TTFT p50 "
        f"{lat['ttft_p50']:.2f} s / p99 {lat['ttft_p99']:.2f} s; TPOT p50 "
        f"{lat['tpot_p50'] * 1e3:.1f} ms; high-water "
        f"{st['page_high_water']}/{st['pages_total']} pages "
        f"({st['page_high_water'] * PAGE:,} token-slots)",
        flush=True,
    )
    eng.close()
