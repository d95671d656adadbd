"""Serving cells: the continuous engine under a closed backlog
(``serve_closed``) or an open arrival schedule (``serve_open``).

One thread drives everything: it adds the requests that are due, calls
``engine.step()``, and reads what retired. Times come from the engine's own
clock: ``engine.retire`` flight-recorder events carry each request's TTFT and
end-to-end time measured from the ``arrival_t`` it was added with, which in
an open loop is the time the request was DUE, not the time the generator got
round to it.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import time

import numpy as np

from benchmark import reference, stats, traffic


class SloTap:
    """The engine's ``slo=`` hook as a plain sample store. The engine calls
    ``observe`` once per retired request for ``queue_wait`` (right after it
    records the ``engine.retire`` event, so the i-th sample belongs to the
    i-th retire event) and then once per token gap for ``itl``: first-token
    time plus the running sum of a request's gaps gives the host time at
    which the engine showed each of its tokens."""

    targets = ()

    def __init__(self):
        self.registry = None
        self.recorder = None
        self.samples = collections.defaultdict(list)
        self.gaps = []            # per retired request, its token gaps

    def observe(self, name, value, tenant=None):
        self.samples[name].append(float(value))
        if name == "queue_wait":
            self.gaps.append([])
        elif name == "itl":
            self.gaps[-1].append(float(value))


def init_params(cfg, mesh, rules, key):
    """Serving weights born on the device in the serving type, one jitted
    call. Flax starts every bias at zero; GPT-2's are not, so each ``bias``
    leaf gets N(0, 0.02) noise, or the reference's bias terms would be
    checked against nothing."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from learning_jax_sharding_tpu.models.transformer import Transformer
    from learning_jax_sharding_tpu.parallel.logical import activate, tree_shardings

    model = Transformer(cfg)
    probe = np.zeros((2, 8), np.int32)

    def init(key, tokens):
        return model.init({"params": key}, tokens)

    def make(key, tokens):
        tree = nn.meta.unbox(init(key, tokens))
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            if jax.tree_util.keystr(path).endswith("['bias']"):
                noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
                leaf = (0.02 * noise).astype(leaf.dtype)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    with activate(mesh, rules):
        abstract = jax.eval_shape(init, key, probe)
        shardings = tree_shardings(abstract, mesh, rules)
        return jax.jit(make, out_shardings=shardings)(key, probe)["params"]


@dataclasses.dataclass
class Load:
    """The load generator and its books."""

    engine: object
    params: object
    prompts: object                       # iterator of prompt arrays
    slots: int
    reqs: dict = dataclasses.field(default_factory=dict)
    live: set = dataclasses.field(default_factory=set)
    lateness: list = dataclasses.field(default_factory=list)
    streams: dict = dataclasses.field(default_factory=dict)
    keep_streams: int = 0
    failures: list = dataclasses.field(default_factory=list)
    retired: int = 0
    due: object = None                    # absolute due times (open loop)
    nxt: int = 0

    def add(self, now: float, due: float | None = None) -> None:
        prompt = next(self.prompts)
        arrival = now if due is None else due
        rid = self.engine.add_request(prompt, arrival_t=arrival)
        self.reqs[rid] = {
            "rid": rid, "due": arrival,
            "prompt_len": int(prompt.size), "prompt": prompt,
        }
        self.live.add(rid)
        if due is not None:
            self.lateness.append(now - due)

    def feed(self, now: float) -> None:
        """Closed loop (no schedule): keep ``slots`` requests in flight.
        Open loop: add every request that is due by ``now``."""
        if self.due is None:
            while len(self.live) < self.slots:
                self.add(now)
            return
        while self.nxt < len(self.due) and self.due[self.nxt] <= now:
            self.add(now, float(self.due[self.nxt]))
            self.nxt += 1

    def turn(self, until: float) -> None:
        """One turn of the loop: feed, then one engine step, or sleep to the
        next due time (``until`` at the latest) when the engine is empty."""
        now = time.perf_counter()
        self.feed(now)
        if self.engine.has_work():
            self.step()
        elif self.due is not None:
            nxt = self.due[self.nxt] if self.nxt < len(self.due) else until
            time.sleep(max(0.0, min(nxt, until) - now))

    def step(self) -> None:
        from learning_jax_sharding_tpu.models.serving import RequestFailure

        done = self.engine.step(self.params)
        if not done:
            return
        for rid, out in self.engine.pop_finished().items():
            self.live.discard(rid)
            self.retired += 1
            req = self.reqs[rid]
            if isinstance(out, RequestFailure):
                req["failed"] = True
                self.failures.append(out.status)
            elif len(self.streams) < self.keep_streams:
                self.streams[rid] = np.asarray(out)
            if rid not in self.streams:
                req.pop("prompt")


def run(cell: dict, ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from learning_jax_sharding_tpu.models.serving import make_continuous_engine
    from learning_jax_sharding_tpu.parallel import single_device_mesh
    from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING
    from learning_jax_sharding_tpu.telemetry.flight_recorder import FlightRecorder

    spec, model = ctx.traffic, ctx.model
    eng = spec["engine"]
    closed = spec["kind"] == "serve_closed"
    dims = ctx.family.model_dims(model)
    cfg = ctx.family.to_config(
        model, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        decode_attention="auto" if ctx.on_tpu else "blocked",
    )
    mesh = single_device_mesh()
    params = init_params(cfg, mesh, RULES_TP_SERVING, ctx.key)
    jax.block_until_ready(params)
    ctx.phase("weights_on_device")

    recorder = FlightRecorder(max_events=2_000_000)
    tap = SloTap()
    serve = make_continuous_engine(
        cfg, mesh, RULES_TP_SERVING, batch_size=eng["slots"],
        max_new_tokens=eng["max_new_tokens"], refill_chunk=eng["refill_chunk"],
        inference_dtype=jnp.bfloat16, paged_pages=eng["pages"],
        page_size=eng["page_size"], recorder=recorder, slo=tap,
    )
    engine = serve.engine
    ctx.phase("engine_built")

    load = Load(
        engine, params,
        traffic.prompt_stream(spec["prompts"], dims["vocab_size"], ctx.seed),
        eng["slots"],
    )

    # Set-up runs every program the window will use. First one request
    # alone: the engine's ``decode_block`` exists in two compiled variants,
    # with block tables just pushed from the host and with tables that came
    # out of the last program, and with every slot busy a decode dispatch in
    # which no row reaches a new page (the second variant) comes about once
    # in a hundred — in the first chip runs it first came, and compiled,
    # inside the window. A lone request has such dispatches for certain.
    # Then the ramp: a closed backlog until ``ramp_requests`` have retired.
    load.add(time.perf_counter())
    while engine.has_work():
        load.step()
    while load.retired < spec["ramp_requests"]:
        load.turn(0.0)
    if not closed:
        while engine.has_work():
            load.step()
    ctx.phase("ramp_served")

    seconds = ctx.seconds
    slice_s = min(float(spec["trace_slice_s"]), seconds / 2) if ctx.trace else 0.0
    load.keep_streams = int(spec["check_streams"])
    if closed:
        t_start = time.perf_counter()
    else:
        ramp_s, grace_s = float(spec["ramp_s"]), float(spec["grace_s"])
        origin = time.perf_counter()
        load.due = origin + traffic.arrival_times(
            spec["arrivals"], ctx.seed, ramp_s + seconds + grace_s
        )
        t_start = origin + ramp_s
        while time.perf_counter() < t_start:      # open-loop ramp, not measured
            load.turn(t_start)
    t_end = t_start + seconds
    t_counters_end = t_end - slice_s

    # ------------------------------------------------------------ window --
    ctx.window_opens(t_start)
    engine.ledger.begin_window()
    reg_start = engine.registry.snapshot()
    wall_start = time.time()
    ledger_report = reg_end = None
    tracing, trace_t0, trace_t1 = False, None, None
    queue_at_middle = None
    while True:
        now = time.perf_counter()
        if queue_at_middle is None and now >= t_start + seconds / 2:
            queue_at_middle = engine.queue_depth()
        if ledger_report is None and now >= t_counters_end:
            ledger_report = engine.ledger.window_report()
            reg_end = engine.registry.snapshot()
            wall_end = time.time()
            t_counters_end = now
            if ctx.trace:
                ctx.start_trace()
                tracing, trace_t0 = True, time.perf_counter()
        if now >= t_end:
            break
        load.turn(t_end)
    t_stop = time.perf_counter()
    queue_at_end = engine.queue_depth()
    if tracing:
        trace_t1 = time.perf_counter()
        ctx.stop_trace()
    window_compiles = ctx.window_closes()

    # Past the window: an open loop keeps arriving until every request due
    # in the window has retired (at most ``grace_s``); a closed run drains
    # what was in flight (nothing new is added), so that every token shown
    # inside the window belongs to a request whose token times are known.
    grace_end = t_stop
    if not closed:
        waiting = {r["rid"] for r in load.reqs.values() if t_start <= r["due"] < t_end}
        deadline = t_end + grace_s
        while waiting & load.live and time.perf_counter() < deadline:
            load.turn(deadline)
        grace_end = time.perf_counter()
    else:
        while engine.has_work():
            load.step()

    # ------------------------------------------------------------- books --
    retire = recorder.events("engine.retire")
    waits = tap.samples["queue_wait"]
    if len(waits) != len(retire):
        raise RuntimeError(
            f"{len(retire)} retire events but {len(waits)} queue_wait samples"
        )
    for ev, wait, gaps in zip(retire, waits, tap.gaps):
        req = load.reqs[ev["rid"]]
        req.update(
            ttft=ev["ttft"], e2e=ev["e2e"], generated=ev["generated"],
            queue_wait=wait,
        )
        if ev["ttft"] is not None:
            req["token_t"] = np.cumsum([req["due"] + ev["ttft"], *gaps])
    reqs = list(load.reqs.values())
    win = stats.serve_window(reqs, t_start, t_stop if closed else t_end, grace_end=grace_end)
    window_s = (t_stop if closed else t_end) - t_start
    w_end = t_stop if closed else t_end
    shown = sum(
        int(np.count_nonzero((r["token_t"] >= t_start) & (r["token_t"] <= w_end)))
        for r in reqs if "token_t" in r and not r.get("failed")
    )
    e2e = {}
    if shown:
        e2e["serve_tok_s"] = shown / window_s
    if win["tpot_ms"]:
        e2e["tpot_p95_ms"] = stats.percentile(win["tpot_ms"], 95)
    if win["ttft_ms"]:
        e2e["ttft_p95_ms"] = stats.percentile(win["ttft_ms"], 95)
    gaps = [g * 1e3 for g in tap.samples["itl"]]
    ctx.info(
        "requests", sent=len(reqs), retired=load.retired,
        finished_in_window=win["finished"], tokens_shown_in_window=shown,
        tokens_of_requests_finished_in_window=win["tokens"],
        due_in_window=len(win["ttft_ms"]), failed_due=win["failed_due"],
        failures=load.failures, window_s=window_s,
        tpot_ms={"n": len(win["tpot_ms"]),
                 "p50": stats.percentile(win["tpot_ms"], 50) if win["tpot_ms"] else None},
        ttft_ms={"n": len(win["ttft_ms"]),
                 "p50": stats.percentile(win["ttft_ms"], 50) if win["ttft_ms"] else None},
        token_gap_ms={"n": len(gaps),
                      "p50": stats.percentile(gaps, 50) if gaps else None,
                      "p99": stats.percentile(gaps, 99) if gaps else None},
        generator_lateness_ms={
            "n": len(load.lateness),
            "p95": stats.percentile(load.lateness, 95) * 1e3 if load.lateness else None,
        },
        queue_at_middle=queue_at_middle, queue_at_end=queue_at_end,
        preemptions_in_window=(reg_end or {}).get("engine_preemptions_total", 0)
        - reg_start.get("engine_preemptions_total", 0),
    )

    peak = ctx.peak_bytes()
    # Decode tokens shown inside the traced slice, each with the tokens its
    # row then had in the cache. Used only to count bytes.
    contexts = []
    if trace_t0 is not None:
        for r in reqs:
            if "token_t" not in r or r.get("failed"):
                continue
            for j, t in enumerate(r["token_t"][1:], start=1):
                if trace_t0 <= t <= trace_t1:
                    contexts.append(r["prompt_len"] + j)

    streams = [load.streams[rid] for rid in sorted(load.streams)]
    prompts = [load.reqs[rid]["prompt"] for rid in sorted(load.streams)]
    observed = {
        "ledger": ledger_report,
        "registry": {"start": reg_start, "end": reg_end},
        "tracer_events": engine.tracer.events,
        "recorder_events": [
            e for e in recorder.events() if wall_start <= e["t"] <= wall_end
        ],
        "requests": reqs,
        "windows": {
            "due_in_window": (t_start, t_counters_end),
            "finished_in_window": (t_start, t_counters_end),
        },
        "work": {
            "model": dims, "page_size": eng["page_size"],
            "decode_contexts_in_slice": contexts,
        },
    }

    # ------------------------------------------------------- correctness --
    del serve, engine, load.engine
    gc.collect()
    width = spec["prompts"]["max"] + eng["max_new_tokens"]
    shapes_ok = len(streams) == spec["check_streams"] and all(
        len(s) == len(p) + eng["max_new_tokens"] and np.array_equal(s[: len(p)], p)
        for p, s in zip(prompts, streams)
    )
    check = {"ok": False}
    if streams:
        check = reference.teacher_forced(
            ctx.family.reference_fn(dims), params, prompts, streams,
            float(model["check"]["margin_tol"]), width,
        )
    ctx.info("correctness", streams_checked=len(streams), shapes_ok=shapes_ok, **check)
    failed = len(load.failures) + (0 if closed else win["failed_due"])
    return {
        "e2e": e2e, "observed": observed, "attempted": len(reqs),
        "failed": failed, "peak_bytes": peak,
        "correct": bool(shapes_ok and check["ok"] and window_compiles == 0),
    }
