#!/usr/bin/env python
"""Host-loop horizon ladder for multi-step scheduling (PERF.md round 16).

ROADMAP item 1's instrument run: the round-14 goodput ledger put
host_share at ~96% on the saturated engine — the host round-trips Python
between every compiled dispatch, and BENCH r05 pins the consequence as a
16x gap on the remotely attached chip of rounds 1-5, where each
dispatch cost ~120 ms before any math ran (the dispatch cost of today's
machine is not measured). The round-16 ``horizon`` knob fuses N engine iterations
into ONE scanned ``multi_step`` program and demotes the host to an async
next-horizon planner, so this ladder drives the SAME saturated staggered
queue at N ∈ {1, 2, 4, 8, 16} in TWO regimes:

* **raw** — the emulated mesh as-is. Per-dispatch overhead is only the
  Python host loop, so this sweep is where the STRUCTURAL metrics live:
  host_share, steps/dispatch, boundary stall. (Its tok/s is NOT the
  product: on the emulator the "device" is the same CPU, so the fused
  scan's masked refill lanes on decode-only links are paid in real
  compute that ``decode_block`` would have skipped — wall-clock there
  answers a question about the emulator, not the scheduler.)
* **dispatch-cost** — the same ladder with a fixed per-dispatch host
  cost injected through the engine's own ``engine.dispatch`` chaos seam
  (kind="slow", every dispatch). This models the regime BENCH r05
  measured on its remotely attached chip; the modeled cost is scaled down (~10 ms vs the
  real ~120 ms) purely to keep the ladder inside CI time — the REGIME
  (fixed cost x dispatch count dominates wall-clock) is what matters,
  and in it the fused program's N-fold dispatch amortization is the
  whole story. This sweep owns the headline tok/s.

Per rung the ladder records:

* **tok/s** — generated tokens over drain wall-clock;
* **host_share** — 1 − device/busy from ``window_report()``, THE number
  the refactor pushes down;
* **steps/dispatch** — engine iterations fused per host dispatch
  (``latency_stats``; 1.0 at horizon=1 by construction);
* **ITL p99** — inter-token latency must not blow up while the host
  batches its scheduling (tokens release at horizon boundaries, so a
  too-large horizon trades tail latency for throughput — the ladder
  makes that trade visible instead of implicit);
* **boundary stall** — the ``sched`` bucket's share of busy time: host
  planning/bookkeeping at horizon boundaries (the async planner stages
  the next horizon while the program is in flight, holding this down).

Every rung must reconcile (Σ buckets == wall within ε) and EVERY rung —
both regimes, all horizons — must stay BIT-IDENTICAL to the first
rung's outputs: a ladder that bought throughput by changing tokens
measures nothing.

Usage:
    python scripts/perf_hostloop.py [--json]
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
import contextlib  # noqa: E402

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HORIZONS = (1, 2, 4, 8, 16)
NREQ, NEW = 32, 32
SLOTS = 8
# Modeled per-dispatch host cost for the dispatch-cost sweep. BENCH r05
# pins ~120 ms on its remotely attached chip; 10 ms (still 12x smaller)
# keeps five rungs inside CI time while leaving the sweep firmly
# dispatch-cost-dominated at horizon=1 — the property the regime needs
# (at 2 ms the emulator's own compute still drowned the signal).
DISPATCH_COST_S = 1e-2


def _build():
    from learning_jax_sharding_tpu.models.transformer import (
        CONFIG_TINY,
        Transformer,
    )
    from learning_jax_sharding_tpu.parallel import build_mesh

    # CONFIG_TINY on purpose — the OPPOSITE choice from perf_goodput.py,
    # because the products differ. Goodput prices device efficiency, so
    # it needs honest per-dispatch device work (256-wide). This ladder
    # prices the HOST LOOP: the round-14 ~96% host_share came from the
    # tiny-config fleet where per-dispatch device work is small and the
    # Python round-trip between dispatches dominates. A wide model on
    # the emulated mesh buries that signal (measured: host_share ~11%
    # at horizon=1 with a 256-wide config — nothing left to push down).
    cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32, max_seq_len=128)
    mesh = build_mesh((2, 4), ("data", "model"))
    model = Transformer(cfg)
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(0), np.zeros((2, 8), np.int32)
        )["params"]
    )
    rng = np.random.default_rng(16)
    # VARIED prompt lengths are load-bearing, not decoration: with a
    # token budget throttling refill, slots finish prefill (and so
    # retire) at DIFFERENT iterations, which keeps refill perpetually
    # overlapped with decode — the mixed regime whose per-iteration
    # host round-trip is the ~96% host_share pathology. Uniform lengths
    # lock-step the slots and the engine degenerates into alternating
    # pure-refill / pure-decode phases that never exercise the fused
    # path (observed: steps/dispatch pinned at 1.00 on every rung).
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in rng.integers(40, 88, size=NREQ)
    ]
    return cfg, mesh, params, prompts


def _drive(eng, params, prompts, outs=None):
    """Saturated STAGGERED arrivals. Enqueueing the whole queue up front
    lock-steps the cohort — the first dispatch's uncapped refill (no
    decode rows yet, so no budget metering) prefills every slot at
    once, the rows then activate/decode/retire in unison, and the
    engine lives in the pure-decode fallback instead of the fused
    mixed path this ladder exists to measure. So: a staircase seed
    (one admission every other iteration) breaks the cohort, then every
    freed slot is topped up immediately so the engine stays saturated —
    gating steady-state arrivals on iterations would starve the
    deep-horizon rungs (one iteration covers N links there) and measure
    offered load, not the host. Greedy decoding keys tokens by
    (request, position), so outputs stay schedule-independent and the
    cross-rung bit-identity oracle still applies.
    """
    plen, done = {}, {}
    queue = list(enumerate(prompts))
    inflight = it = 0
    while queue or eng.has_work():
        room = SLOTS - inflight
        want = (it % 2 == 0) if it < 2 * SLOTS else room
        for _ in range(min(room, int(want), len(queue))):
            rid, p = queue.pop(0)
            plen[eng.add_request(p, rid=rid)] = len(p)
            inflight += 1
        if eng.has_work():
            eng.step(params)
        fin = eng.pop_finished()
        inflight -= len(fin)
        done.update(fin)
        it += 1
    if outs is not None:
        outs.update(done)
    return sum(len(v) - plen[r] for r, v in done.items())


def run_rung(cfg, mesh, params, prompts, horizon, dispatch_cost_s=0.0):
    from learning_jax_sharding_tpu.models.serving import ContinuousEngine
    from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
    from learning_jax_sharding_tpu.robustness.chaos import (
        ChaosInjector,
        Fault,
    )

    # The tracked staggered-latency line's shape (bench.py mixed_lat):
    # decode_chain=1 so the horizon=1 rung is the genuine one-host-
    # round-trip-per-iteration baseline, and a token budget so refill
    # is metered across iterations instead of swallowed in one link.
    # decode_block_steps stays modest — in mixed mode the pure-decode
    # block only runs when there is NO refill to fuse, and this
    # workload keeps refill live almost every iteration by design.
    eng = ContinuousEngine(
        cfg, mesh, RULES_DP_TP, batch_size=SLOTS, max_new_tokens=NEW,
        refill_chunk=8, decode_block_steps=8, decode_chain=1,
        mixed=True, token_budget=24, horizon=horizon,
    )
    _drive(eng, params, prompts[:5])            # warm: compiles excluded
    eng.reset_stats()
    eng.ledger.begin_window()
    # The dispatch-cost sweep arms the engine's own per-dispatch seam
    # with an always-on "slow" fault: a fixed host cost per dispatch,
    # booked (like every armed seam delay) under "recovery" — so in
    # this regime host_share ≈ the modeled dispatch cost's share, which
    # is what BENCH r05's remotely attached chip looked like.
    inj = (
        ChaosInjector(
            Fault(
                "engine.dispatch", "slow", at=0, count=-1,
                delay_s=dispatch_cost_s,
            )
        )
        if dispatch_cost_s > 0 else contextlib.nullcontext()
    )
    outs: dict = {}
    t0 = time.perf_counter()
    with inj:
        gen = _drive(eng, params, prompts, outs)
    dt = time.perf_counter() - t0
    rep = eng.ledger.window_report()
    rec = eng.ledger.reconcile()
    assert rec["ok"], f"ledger failed to reconcile (h={horizon}): {rec}"
    lat = eng.latency_stats() or {}
    busy = max(rep["busy_s"], 1e-12)
    return dict(
        horizon=horizon,
        tok_s=gen / dt,
        host_share=rep["host_share"],
        steps_per_dispatch=lat.get("steps_per_dispatch", 1.0),
        itl_p99_ms=1e3 * lat.get("itl_p99", 0.0),
        boundary_stall_share=rep["buckets"].get("sched", 0.0) / busy,
        plan_reuse_rate=lat.get("plan_reuse_rate"),
        buckets={k: round(v, 4) for k, v in rep["buckets"].items()},
        wall_s=rep["wall_s"],
    ), outs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench-lines", action="store_true",
                    help="emit only [bench] lines (bench.py subprocess "
                         "relay convention; the default already prints "
                         "them, so this just pins the interface)")
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    cfg, mesh, params, prompts = _build()
    sweeps = {"raw": [], "multistep": []}
    ref = None
    out_stream = sys.stderr if args.json else sys.stdout
    for label, cost in (("raw", 0.0), ("multistep", DISPATCH_COST_S)):
        for h in HORIZONS:
            r, outs = run_rung(cfg, mesh, params, prompts, h, cost)
            if ref is None:
                ref = outs
            else:
                # The value oracle rides the perf run: a rung that
                # changed tokens is a bug, not a data point.
                assert sorted(outs) == sorted(ref)
                for rid in outs:
                    np.testing.assert_array_equal(outs[rid], ref[rid])
            sweeps[label].append(r)
            print(
                f"[bench] {label} h{h}: {r['tok_s']:,.0f} tok/s, "
                f"host_share {100 * r['host_share']:.1f}%, "
                f"steps/dispatch {r['steps_per_dispatch']:.2f}, "
                f"ITL p99 {r['itl_p99_ms']:.1f} ms, "
                f"boundary stall {100 * r['boundary_stall_share']:.1f}%",
                file=out_stream,
            )
    # The headline rides the dispatch-cost sweep (the regime the fused
    # program exists for); best rung by tok/s, ITL is its price tag.
    tuned = sweeps["multistep"]
    best = max(tuned, key=lambda r: r["tok_s"])
    base = tuned[0]
    line = (
        f"[bench] multistep best: {best['tok_s']:,.0f} tok/s at "
        f"horizon={best['horizon']} "
        f"({best['tok_s'] / base['tok_s']:.2f}x the horizon=1 rung), "
        f"host_share {100 * best['host_share']:.1f}% "
        f"(was {100 * base['host_share']:.1f}%), "
        f"steps/dispatch {best['steps_per_dispatch']:.2f}"
    )
    if args.json:
        print(json.dumps({"sweeps": sweeps, "best": best}, indent=2))
        print(line, file=sys.stderr)
    else:
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
