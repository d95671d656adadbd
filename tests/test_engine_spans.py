"""The engine's host phases, named where they happen (PR 27).

Every goodput-ledger frame the engine opens is a span: it reaches the
tracer's ring and, through ``jax.profiler.TraceAnnotation``, the
profiler's host timeline. The same frames carry the empty-device clock
(``engine_device_starved_seconds_total``) and feed the per-dispatch
``engine.dispatch`` flight-recorder event. These tests pin the names, the
partition, the identities between counters, events and request records,
that every benchmark metric file reads something a served engine has,
and the request clock (PR 37): every second of a request between its
admission and its retirement in one phase, two identities a request.
"""

import collections
import dataclasses
import glob
import importlib.util
import json
import pathlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.models.serving import ContinuousEngine
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_TINY,
    Transformer,
    TransformerConfig,
)
from learning_jax_sharding_tpu.parallel import build_mesh
from learning_jax_sharding_tpu.parallel.logical import RULES_TP_SERVING
from learning_jax_sharding_tpu.telemetry import GoodputLedger, Tracer
from learning_jax_sharding_tpu.telemetry.flight_recorder import FlightRecorder
from learning_jax_sharding_tpu.telemetry.registry import MetricsRegistry

REPO = pathlib.Path(__file__).resolve().parents[1]
_SPEC = importlib.util.spec_from_file_location(
    "engine_breakdown", REPO / "scripts" / "engine_breakdown.py"
)
engine_breakdown = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(engine_breakdown)
MAX_NEW = 6

#: span -> ledger bucket. ``device`` stands for ``device`` + ``compile``
#: (an enqueue whose executable cache grew is re-bucketed). The three
#: dispatch spans are not frames: their own time is the step's.
SPAN_BUCKET = {
    "engine.step": "sched",
    "engine.h2d": "sched",
    "engine.consume": "sched",
    "engine.plan": "sched",
    "engine.refill": "sched",
    "engine.decode": "sched",
    "engine.mixed": "sched",
    "engine.admission": "admission",
    "engine.page_alloc": "page_alloc",
    "engine.telemetry": "telemetry",
    "engine.recovery": "recovery",
    "engine.kv_handoff": "kv_handoff",
    "engine.swap": "swap",
    "engine.enqueue": "device",
    "engine.wait": "device",
}


def _bucket_of(span: str) -> str:
    return SPAN_BUCKET[".".join(span.split(".")[:2])]


def _setup(**engine_kw):
    cfg = dataclasses.replace(
        CONFIG_TINY, dtype=jnp.float32, decode_attention="blocked"
    )
    mesh = build_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2])
    params = nn.meta.unbox(
        jax.jit(lambda r, t: Transformer(cfg).init({"params": r}, t))(
            jax.random.key(3), np.zeros((2, 8), np.int32)
        )["params"]
    )
    eng = ContinuousEngine(
        cfg, mesh, RULES_TP_SERVING, batch_size=2, max_new_tokens=MAX_NEW,
        refill_chunk=8, paged_pages=12, page_size=8,
        recorder=FlightRecorder(max_events=100_000), **engine_kw,
    )
    return cfg, params, eng


def _prompts(cfg, seed, n):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, cfg.vocab_size, size=(k,)).astype(np.int32)
        for k in rng.integers(5, 20, size=n)
    ]


def _drain(eng, params, prompts):
    rids = [eng.add_request(p) for p in prompts]
    while eng.has_work():
        eng.step(params)
    outs = eng.pop_finished()
    return [outs[r] for r in rids]


@pytest.fixture(scope="module")
def served():
    """A warm engine (everything compiled), then one measured drain."""
    cfg, params, eng = _setup()
    _drain(eng, params, _prompts(cfg, 1, 3))
    eng.tracer.clear()
    eng.recorder.clear()
    eng.ledger.begin_window()
    start = eng.registry.snapshot()
    prompts = _prompts(cfg, 14, 6)
    outs = _drain(eng, params, prompts)
    return {
        "eng": eng, "cfg": cfg, "params": params, "prompts": prompts,
        "outs": outs, "start": start,
        "end": eng.registry.snapshot(),
        "buckets": eng.ledger.window_buckets(),
        "reconcile": eng.ledger.reconcile(),
        "events": [e for e in eng.tracer.events if e["ph"] == "X"],
        "dispatches": eng.recorder.events("engine.dispatch"),
        "retired": eng.recorder.events("engine.retire"),
    }


def _delta(served, name):
    return served["end"].get(name, 0.0) - served["start"].get(name, 0.0)


def _exclusive(events):
    """Per span name its exclusive microseconds, by interval nesting, and
    the events that nest in no other."""
    own = collections.defaultdict(float)
    stack, tops = [], []
    for ev in sorted(events, key=lambda e: (e["ts"], -e["dur"])):
        while stack and ev["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
            stack.pop()
        if stack:
            assert ev["ts"] + ev["dur"] <= (
                stack[-1]["ts"] + stack[-1]["dur"] + 1e-3
            ), f"{ev['name']} straddles the end of {stack[-1]['name']}"
            own[id(stack[-1])] -= ev["dur"]
        else:
            tops.append(ev)
        own[id(ev)] += ev["dur"]
        stack.append(ev)
    by_name = collections.defaultdict(float)
    for ev in events:
        assert own[id(ev)] >= -1e-3, (ev["name"], own[id(ev)])
        by_name[ev["name"]] += own[id(ev)]
    return by_name, tops


PHASES = ("refill_wait", "refill", "stall", "decode")
PHASE_SECONDS = 'engine_request_phase_seconds_total{phase="%s"}'
PHASE_DISPATCHES = 'engine_request_phase_dispatches_total{phase="%s"}'


def _check_identities(retired):
    """The request clock's two identities (and what follows from them) for
    every ``engine.retire`` event, to float rounding. A dispatch path that
    skipped its tick would leave its seconds in no phase and fail the
    second or the first."""
    assert retired
    for e in retired:
        to_first_token = e["ttft"] - e["queue_wait"]     # from the FIRST admission
        assert (
            e["redone_s"] + e["requeue_wait_s"] + e["refill_wait_s"] + e["refill_s"]
        ) == pytest.approx(to_first_token, abs=1e-9), e
        assert e["stall_s"] + e["decode_s"] == pytest.approx(
            e["e2e"] - e["ttft"], abs=1e-9
        ), e
        assert e["first_token_unix"] - e["admit_unix"] == pytest.approx(
            e["refill_wait_s"] + e["refill_s"], abs=1e-4
        ), e                     # from the LAST one, on the recorder's clock
        # ... and the event is written after the request finished.
        assert e["t"] - e["first_token_unix"] >= e["stall_s"] + e["decode_s"] - 1e-4
        gaps = e["generated"] - 1
        if gaps:
            assert e["tpot"] * gaps == pytest.approx(
                e["stall_s"] + e["decode_s"], abs=1e-9
            )
            assert e["stall_per_token_s"] + e["decode_per_token_s"] == (
                pytest.approx(e["tpot"], abs=1e-12)
            )
            assert e["decode_dispatches"] >= 1
        else:
            assert e["tpot"] is e["stall_per_token_s"] is e["decode_per_token_s"] is None
        assert e["refill_dispatches"] >= 1
        assert min(e[f"{p}_s"] for p in PHASES) >= 0.0


# --- (a) the spans partition step() and add up to the ledger's buckets -------


def test_every_instant_of_a_step_lies_in_one_innermost_engine_span(served):
    by_name, tops = _exclusive(served["events"])
    # Nothing but steps at the top: every other span nests inside one, so
    # each instant of a step has exactly one innermost span.
    assert {e["name"] for e in tops} == {"engine.step"}
    assert all(name.startswith("engine.") for name in by_name)
    steps = [e for e in served["events"] if e["name"] == "engine.step"]
    ordinals = [e["args"]["step"] for e in steps]
    assert ordinals == list(range(ordinals[0], ordinals[0] + len(steps)))
    # ... and the exclusive times add up to the wall inside step().
    assert sum(by_name.values()) / 1e6 == pytest.approx(
        _delta(served, "engine_step_seconds_total"), rel=1e-6
    )


def test_span_exclusive_seconds_equal_the_ledger_buckets(served):
    assert served["reconcile"]["ok"], served["reconcile"]
    by_name, _ = _exclusive(served["events"])
    by_bucket = collections.defaultdict(float)
    for name, us in by_name.items():
        by_bucket[_bucket_of(name)] += us / 1e6
    ledger = dict(served["buckets"])
    ledger["device"] += ledger.pop("compile")
    ledger.pop("idle")
    # A page claim writes no ring event: on the ring its time stays in
    # the span that made it, a step's own code or an admission.
    assert ledger["page_alloc"] > 0 and "page_alloc" not in by_bucket
    for merged in (ledger, by_bucket):
        merged["sched"] += merged.pop("admission") + merged.pop("page_alloc", 0)
    for bucket, seconds in ledger.items():
        assert by_bucket[bucket] == pytest.approx(seconds, rel=1e-6, abs=1e-7), (
            bucket, dict(by_bucket), ledger,
        )


@pytest.mark.parametrize("span", [
    "engine.step", "engine.admission", "engine.h2d",
    "engine.enqueue.refill_step", "engine.wait.refill_step",
    "engine.enqueue.decode_block", "engine.wait.decode_block",
    "engine.consume", "engine.telemetry", "engine.recovery",
    "engine.refill", "engine.decode",
])
def test_span_names_are_pinned(served, span):
    assert any(e["name"] == span for e in served["events"])


def test_an_undispatched_step_leaves_no_dispatch_span():
    cfg, params, eng = _setup()
    eng.step(params)                      # nothing queued: nothing to run
    names = [e["name"] for e in eng.tracer.events]
    assert "engine.step" in names
    assert not {"engine.refill", "engine.decode", "engine.mixed"} & set(names)
    assert not eng.recorder.events("engine.dispatch")


@pytest.fixture(scope="module")
def served_mixed():
    """A mixed engine. One drain at a link a dispatch; then, a budget of
    one token a dispatch, a prompt that arrives while another request
    decodes (it waits for a refill turn); then a drain at a two-link
    horizon (``multi_step``: one readback shows two links)."""
    cfg, params, eng = _setup(mixed=True)
    _drain(eng, params, _prompts(cfg, 5, 4))
    got = {
        "eng": eng,
        "names": collections.Counter(
            e["name"] for e in eng.tracer.events if e["ph"] == "X"
        ),
        "kinds": collections.Counter(
            e["phase"] for e in eng.recorder.events("engine.dispatch")
        ),
        "reconcile": eng.ledger.reconcile(),
    }
    budget, eng.token_budget = eng.token_budget, 1
    first, late = _prompts(cfg, 6, 2)
    eng.add_request(first[:5])
    eng.step(params)                      # its first token
    got["late_rid"] = eng.add_request(late)
    eng.step(params)                      # decodes one; nothing left to refill with
    got["budget_dispatch"] = eng.recorder.events("engine.dispatch")[-1]
    while eng.has_work():
        eng.step(params)
    eng.token_budget, eng.horizon = budget, 2
    _drain(eng, params, _prompts(cfg, 7, 3))
    got["horizon_kinds"] = collections.Counter(
        e["family"] for e in eng.recorder.events("engine.dispatch")
    )
    got["retired"] = eng.recorder.events("engine.retire")
    return got


def test_mixed_engine_books_each_dispatch_under_the_program_that_ran(served_mixed):
    names, kinds = served_mixed["names"], served_mixed["kinds"]
    for kind in ("refill", "decode", "mixed"):
        assert names[f"engine.{kind}"] == kinds[kind]
    assert kinds["mixed"] and names["engine.enqueue.mixed_step"]
    assert served_mixed["reconcile"]["ok"]


def test_span_names_leave_the_buckets_alone():
    """The same frames with and without a tracer, on a clock that ticks
    once per reading: every bucket total is identical, so a span is finer
    than its bucket and ``sched_host_share_pct`` reads what it read."""

    def run(tracer):
        ticks = iter(range(10**9))
        led = GoodputLedger(
            registry=MetricsRegistry(), clock=lambda: float(next(ticks)),
            tracer=tracer,
        )
        with led.measure("sched", span="engine.step", step=1):
            with led.measure("admission", span="engine.admission"):
                pass
            with led.measure("sched", span="engine.h2d", leaves=4):
                pass
            with led.measure(
                "device", family="decode_block",
                span="engine.enqueue.decode_block",
            ) as f:
                f.rebucket("compile")
            with led.measure("sched", span="engine.consume"):
                with led.measure("telemetry", span="engine.telemetry"):
                    pass
                with led.measure(
                    "page_alloc", span="engine.page_alloc", ring=False
                ):
                    pass
        assert led.reconcile(eps=1.0)["ok"]
        return led.totals()

    assert run(Tracer()) == run(None) == {
        "admission": 1.0, "compile": 1.0, "telemetry": 1.0,
        "page_alloc": 1.0, "sched": 9.0,
    }


def test_ensure_cache_opens_no_frame():
    """The disaggregated bring-up hook pushes the block tables outside
    ``step()``: the ledger, which covers steps, must not grow."""
    cfg, params, eng = _setup()
    eng.ensure_cache(params)
    assert eng._cache is not None and not eng._tables_dirty
    assert eng.ledger.totals() == {}
    report = eng.ledger.window_report()
    assert report["steps"] == 0 and report["busy_s"] == 0
    assert not eng.tracer.events
    snap = eng.registry.snapshot()
    assert snap["engine_table_push_leaves_total"] == cfg.num_layers
    assert snap["engine_table_push_arrays_total"] == 1


def test_a_disabled_tracer_costs_no_span_and_keeps_the_books():
    cfg, params, eng = _setup(tracer=Tracer(enabled=False))
    assert eng.tracer.begin("engine.step") is eng.tracer.begin("engine.h2d")
    _drain(eng, params, _prompts(cfg, 1, 3))
    assert not eng.tracer.events
    assert eng.ledger.reconcile()["ok"]
    snap = eng.registry.snapshot()
    assert snap[STARVED] > 0 and snap["engine_h2d_seconds_total"] > 0
    assert eng.recorder.events("engine.dispatch")
    retired = eng.recorder.events("engine.retire")
    assert len(retired) == 3
    _check_identities(retired)
    for phase in ("refill", "decode"):
        assert 0 < snap[PHASE_SECONDS % phase] == pytest.approx(
            sum(e[f"{phase}_s"] for e in retired), rel=1e-9
        )


# --- (b) the empty-device clock ------------------------------------------------

STARVED = "engine_device_starved_seconds_total"


def test_the_caller_names_the_starved_series():
    """The ledger parses no span name: a frame's series is its ``label``,
    its bucket by default, ``outside_step`` outside every frame."""
    ticks = iter(range(10**9))
    reg = MetricsRegistry()
    led = GoodputLedger(registry=reg, clock=lambda: float(next(ticks)))
    led.device_empty()                                       # t = 0
    with led.measure("sched", span="nodot"):                 # 1: outside
        with led.measure("sched", span="a.b.c", label="h2d"):   # 2: sched
            pass                                             # 3: h2d
        with led.measure("device", span="a.b.d", label="enqueue"):  # 4: sched
            led.device_busy()                                # 5: enqueue
        with led.measure("admission"):                       # 6 .. 7
            pass
    snap = reg.snapshot()
    assert {
        k[len(STARVED):]: v for k, v in snap.items() if k.startswith(STARVED)
    } == {
        "": 5.0, '{span="outside_step"}': 1.0, '{span="sched"}': 2.0,
        '{span="h2d"}': 1.0, '{span="enqueue"}': 1.0,
    }


def test_labelled_starved_series_sum_to_the_plain_one(served):
    labelled = {
        k: _delta(served, k) for k in served["end"]
        if k.startswith(STARVED + "{")
    }
    assert {'%s{span="%s"}' % (STARVED, s) for s in (
        "h2d", "enqueue", "consume", "admission", "telemetry", "sched",
        "outside_step",
    )} <= set(labelled)
    assert sum(labelled.values()) == pytest.approx(
        _delta(served, STARVED), rel=1e-9
    )
    assert _delta(served, STARVED) > 0


def test_starved_enqueue_and_wait_fit_inside_the_steps(served):
    """Empty-chip seconds outside the enqueue, the enqueues and the waits
    are disjoint pieces of the wall inside and between steps."""
    starved = _delta(served, STARVED)
    in_enqueue = _delta(served, STARVED + '{span="enqueue"}')
    outside = _delta(served, STARVED + '{span="outside_step"}')
    pieces = (
        starved - in_enqueue
        + _delta(served, "engine_enqueue_seconds_total")
        + _delta(served, "engine_wait_seconds_total")
    )
    assert pieces <= _delta(served, "engine_step_seconds_total") + outside + 1e-9


def test_the_clock_stops_while_a_chained_dispatch_is_in_flight():
    """With ``decode_chain=2`` a long prompt's two refill dispatches (its
    four chunks, two rows each) go back to back: after the first readback
    one program is still in flight, so the clock must not start until the
    second."""
    cfg, params, eng = _setup(decode_chain=2)
    prompt = _prompts(cfg, 3, 1)[0][:5]
    long_prompt = np.concatenate([prompt] * 5)       # 25 tokens: 4 chunks
    _drain(eng, params, [long_prompt])               # warm
    marks = []
    real_empty, led = eng.ledger.device_empty, eng.ledger

    def device_empty():
        marks.append(eng._in_flight)
        real_empty()

    led.device_empty = device_empty
    waits = []
    real_tick = led._tick

    def tick(t):
        # Every frame edge: is the clock running, and what is unread?
        waits.append((led._empty_t is not None, eng._in_flight))
        real_tick(t)

    led._tick = tick
    _drain(eng, params, [long_prompt])
    assert marks and set(marks) == {0}
    assert any(n == 2 for _, n in waits), "the chain never had two in flight"
    assert not any(running for running, n in waits if n > 0)
    # The request clock ticks at each segment's readback: the prompt's two
    # dispatches are two refill ticks, and the identities hold.
    retired = eng.recorder.events("engine.retire")
    assert [e["refill_dispatches"] for e in retired] == [2, 2]
    _check_identities(retired)


# --- (c) one engine.dispatch event per dispatch --------------------------------


def test_one_dispatch_event_per_dispatch(served):
    evs = served["dispatches"]
    n = sum(
        _delta(served, f"engine_{k}_dispatches_total")
        for k in ("refill", "decode", "mixed")
    )
    assert len(evs) == n > 0
    spans = [
        e for e in served["events"]
        if e["name"] in ("engine.refill", "engine.decode", "engine.mixed")
    ]
    assert [e["name"] for e in spans] == [f"engine.{e['phase']}" for e in evs]
    steps = [e["step"] for e in evs]
    assert steps == sorted(set(steps))
    for e in evs:
        assert set(e) >= {
            "family", "phase", "step", "rows", "prefill_tokens",
            "decode_steps", "context_tokens", "starved_s", "enqueue_s",
            "wait_s", "h2d_s", "table_leaves", "table_arrays", "compiled",
            "token_slots", "chunk_rows",
        }
        assert e["family"] == {
            "refill": "refill_step", "decode": "decode_block"
        }[e["phase"]]
        assert not e["compiled"]
        assert e["enqueue_s"] > 0 and e["wait_s"] > 0 and e["h2d_s"] > 0


def test_dispatch_events_account_for_every_token(served):
    evs, prompts, outs = served["dispatches"], served["prompts"], served["outs"]
    generated = [len(o) - len(p) for o, p in zip(outs, prompts)]
    assert sum(e["prefill_tokens"] for e in evs) == sum(map(len, prompts))
    # A refill dispatch runs batch x refill_chunk token slots; a row that
    # carries a chunk holds 1 to refill_chunk of the prompt tokens.
    refills = [e for e in evs if e["phase"] == "refill"]
    assert {e["token_slots"] for e in refills} == {2 * 8}
    assert all(
        e["chunk_rows"] <= e["prefill_tokens"] <= 8 * e["chunk_rows"]
        and 1 <= e["chunk_rows"] <= 2 for e in refills
    )
    assert not any(e["token_slots"] or e["chunk_rows"] for e in evs if e not in refills)
    for field, counter in (
        ("token_slots", "engine_refill_token_slots_total"),
        ("chunk_rows", "engine_refill_chunk_rows_total"),
    ):
        assert sum(e[field] for e in evs) == _delta(served, counter)
    first_tokens = len(prompts)
    assert sum(e["decode_steps"] for e in evs) + first_tokens == sum(generated)
    # The j-th generated token (j >= 2) comes out of a step that reads a
    # cache of prompt + j - 1 tokens.
    context = sum(
        len(p) + j - 1 for p, g in zip(prompts, generated)
        for j in range(2, g + 1)
    )
    assert sum(e["context_tokens"] for e in evs) == context
    assert _delta(served, "engine_decode_context_tokens_total") == context
    # (the first event's share began before the window did)
    assert 0 < sum(e["starved_s"] for e in evs[1:]) <= _delta(served, STARVED)


@pytest.mark.parametrize("engine", ["served", "served_moe"])
def test_table_pushes_add_up(engine, request):
    """A push installs its table in every layer's leaf and sends it to
    the device once (one leaf width): the ``engine.dispatch`` events, the
    ``engine.h2d`` spans of the pushes and the two counters agree."""
    got = request.getfixturevalue(engine)
    evs, layers = got["dispatches"], got["eng"]._cfg.num_layers
    leaves = sum(e["table_leaves"] for e in evs)
    arrays = sum(e["table_arrays"] for e in evs)
    assert leaves == _delta(got, "engine_table_push_leaves_total")
    assert arrays == _delta(got, "engine_table_push_arrays_total")
    assert leaves == layers * arrays > 0
    assert all(e["table_leaves"] == layers * e["table_arrays"] for e in evs)
    if "events" in got:             # the fixture that kept its window's ring
        pushes = [
            e["args"] for e in got["events"]
            if e["name"] == "engine.h2d" and "leaves" in e["args"]
        ]
        assert sum(a["arrays"] for a in pushes) == arrays
        assert sum(a["leaves"] for a in pushes) == leaves


# --- (d) the spans reach the profiler's host timeline --------------------------


def test_profiler_capture_holds_the_engine_spans(tmp_path):
    from jax.profiler import ProfileData

    cfg, params, eng = _setup()
    _drain(eng, params, _prompts(cfg, 1, 2))          # warm
    for p in _prompts(cfg, 2, 2):
        eng.add_request(p)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            eng.step(params)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine."):
                        host[ev.name].append(dict(ev.stats))
    assert len(host["engine.step"]) == 3
    for needed in ("engine.h2d", "engine.consume"):
        assert host[needed], sorted(host)
    for phase in ("enqueue", "wait"):
        assert any(n.startswith(f"engine.{phase}.") for n in host), sorted(host)
    ring = {
        e["args"]["step"]: e["ts"] for e in eng.tracer.events
        if e["name"] == "engine.step"
    }
    for stats in host["engine.step"]:
        # One clock: the annotation carries the tracer's own timestamp.
        assert float(stats["ts_us"]) == pytest.approx(ring[int(stats["step"])])
    assert any({"leaves", "arrays"} <= set(stats) for stats in host["engine.h2d"])
    # ... and only a root span is an anchor; a page claim, which writes no
    # ring event, is on the profiler's timeline all the same.
    assert not any("ts_us" in stats for stats in host["engine.h2d"])
    assert host["engine.page_alloc"]
    assert not any(e["name"] == "engine.page_alloc" for e in eng.tracer.events)
    other = eng.tracer.chrome_trace()["otherData"]
    assert abs(other["epoch_unix_ns"] / 1e9 - eng.recorder.events()[0]["t"]) < 600


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """An engine's bundle and a capture of three of its steps."""
    tmp_path = tmp_path_factory.mktemp("captured")
    cfg, params, eng = _setup()
    _drain(eng, params, _prompts(cfg, 1, 2))          # warm, before it
    for p in _prompts(cfg, 2, 2):
        eng.add_request(p)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "xplane"), profiler_options=options)
    try:
        for _ in range(3):
            eng.step(params)
    finally:
        jax.profiler.stop_trace()
    while eng.has_work():                             # ... and after it
        eng.step(params)
    return {
        "cfg": cfg, "eng": eng, "xplane": str(tmp_path / "xplane"),
        "bundle": str(eng.dump_diagnostics(tmp_path / "bundle")),
    }


def test_the_breakdown_tool_places_a_bundle_on_a_capture(captured, capsys):
    """``scripts/engine_breakdown.py`` is the reader of the dispatch
    events, the labelled starved series and the clock anchors: it finds
    in a bundle exactly the dispatches of the steps a capture caught."""
    cfg, eng, bundle = captured["cfg"], captured["eng"], captured["bundle"]
    out = engine_breakdown.main(
        [bundle, "--xplane", captured["xplane"], "--json"]
    )
    assert json.loads(capsys.readouterr().out) == out
    cap = out["capture"]
    assert len(cap["captured_steps"]) == 3
    assert cap["dispatch_steps"] == cap["captured_steps"]
    assert 0 < cap["host_starved_s"] < cap["interval_s"]
    events = eng.recorder.events("engine.dispatch")
    assert len(events) > 3
    rows, snap = out["by_family"], eng.registry.snapshot()
    assert set(rows) == {"refill_step", "decode_block"}
    assert sum(r["dispatches"] + r["compiled"] for r in rows.values()) == (
        len(events)
    )
    steady = [e for e in events if not e["compiled"]]
    assert 0 < len(steady) < len(events)      # the warm drain compiled
    for field in ("wait_s", "h2d_s", "decode_steps", "context_tokens"):
        assert sum(r[field] for r in rows.values()) == pytest.approx(
            sum(e[field] for e in steady), rel=1e-9
        )
    for field, counter in (
        ("prefill_tokens", "engine_prefill_tokens_total"),
        ("token_slots", "engine_refill_token_slots_total"),
        ("chunk_rows", "engine_refill_chunk_rows_total"),
    ):
        assert sum(e[field] for e in events) == snap[counter]
    refill = rows["refill_step"]
    assert refill["token_slots"] == 2 * 8 * refill["dispatches"]
    assert refill["dispatches"] <= refill["chunk_rows"] <= 2 * refill["dispatches"]
    reg = out["registry"]
    assert set(reg["by_span_s"]) >= {"h2d", "enqueue", "consume", "sched"}
    assert abs(reg["labelled_minus_plain_s"]) < 1e-9
    assert reg["wait_share_pct"] + reg["starved_share_pct"] < 100.0
    engine_breakdown.main([str(bundle)])              # the printed form
    printed = capsys.readouterr().out
    assert "starved by span: " in printed
    assert "chunk rows a dispatch, fill " in printed
    assert f"table pushes of {cfg.num_layers} leaves in 1 arrays" in printed


def test_the_breakdown_tool_prints_the_request_table(captured, capsys):
    """The request clock's reader: from a bundle's ``engine.retire``
    events the phases' percentiles, the requests by dispatch count and
    the slowest by TPOT; with a capture, those on its clock."""
    retired = captured["eng"].recorder.events("engine.retire")
    out = engine_breakdown.main(
        [captured["bundle"], "--xplane", captured["xplane"], "--json"]
    )
    capsys.readouterr()
    table = out["requests"]
    # The warm drain's two sat through compiling dispatches: left out, as
    # a dispatch that compiled is in the family table.
    assert len(retired) == 4 and (table["requests"], table["compiled"]) == (2, 2)
    retired = retired[2:]
    assert set(table["phases_ms"]) == set(PHASES)
    for phase in PHASES:
        values = sorted(1e3 * e[f"{phase}_s"] for e in retired)
        assert values[0] <= table["phases_ms"][phase]["p50"] <= (
            table["phases_ms"][phase]["p95"]
        ) <= values[-1]
    for key in ("refill_dispatches", "stall_dispatches"):
        assert sum(table[key].values()) == 2
        assert sum(int(k) * n for k, n in table[key].items()) == sum(
            e[key] for e in retired
        )
    slowest = table["slowest"]
    assert [r["tpot_ms"] for r in slowest] == sorted(
        (1e3 * e["tpot"] for e in retired), reverse=True
    )
    for r in slowest + [table["at_tpot_p95"]]:
        assert r["stall_ms"] + r["decode_ms"] == pytest.approx(
            r["tpot_ms"] * (r["generated"] - 1), rel=1e-9
        )
    # On the capture: admitted in its first step, then a first token
    # inside it; retired after it (three steps do not finish them).
    placed = out["capture"]["slowest_requests_s"]
    assert [r["rid"] for r in placed] == [r["rid"] for r in slowest]
    assert sorted(r["rid"] for r in placed) == [2, 3]
    for r in placed:
        assert 0 <= r["admit"] < r["first_token"] < out["capture"]["interval_s"]
        assert r["first_token"] < r["retire"]
    # The registry's phase counters: every retired request's seconds (no
    # request is live, none was preempted).
    snap = captured["eng"].registry.snapshot()
    assert set(out["request_phases"]) == {*PHASES, "redone"}
    for phase in PHASES:
        row = out["request_phases"][phase]
        assert row["seconds"] == snap[PHASE_SECONDS % phase] == pytest.approx(
            sum(e[f"{phase}_s"] for e in captured["eng"].recorder.events("engine.retire")),
            rel=1e-9, abs=1e-12,
        )
    engine_breakdown.main([captured["bundle"], "--xplane", captured["xplane"]])
    printed = capsys.readouterr().out
    assert "request clock, slot-seconds (readbacks) since the engine was built: " in printed
    assert "ms a token: tpot " in printed and "requests by stall_dispatches: " in printed
    assert "and 2 that sat through a compiling dispatch, left out" in printed
    assert printed.count("  slowest: rid ") == 2
    assert printed.count("slowest on the capture: rid ") == 2
    # A bundle from before the request clock has no table.
    assert engine_breakdown.request_table(
        [{"kind": "engine.retire", "rid": 0, "generated": 6, "ttft": 0.1, "e2e": 0.2}]
    ) is None


@pytest.mark.parametrize("arrays", [None, 2.0], ids=["before_pr32", "with_arrays"])
def test_the_breakdown_tool_reads_bundles_with_and_without_table_arrays(
    arrays, capsys
):
    """``table_arrays`` came with PR 32: an older bundle's events lack it,
    and the tool prints the leaves of a push alone."""
    event = dict.fromkeys(engine_breakdown.SUMMED, 0.0)
    event.update(family="decode_block", compiled=False)
    push, none = dict(event, table_leaves=48.0), dict(event)
    if arrays:
        push["table_arrays"], none["table_arrays"] = arrays, 0.0
    rows = engine_breakdown.by_family([push, none])
    assert rows["decode_block"]["pushes"] == 1
    assert rows["decode_block"].get("table_arrays") == arrays
    engine_breakdown._print_families(rows)
    printed = capsys.readouterr().out
    assert "1 table pushes of 48 leaves" in printed
    assert (" leaves in 2 arrays" in printed) == bool(arrays)


def test_the_breakdown_tool_names_an_idle_gap_by_its_engine_span():
    """The device-plane half of the tool, on a capture written out by
    hand (a CPU capture has no TPU plane): one 250 us hole in the ops,
    its middle inside ``engine.h2d`` and the runtime's allocator."""
    us = 1e3
    capture = {
        "anchors": [(1000 * us, 0.0, 7)],        # tracer 0 us = profiler 1 ms
        "engine": [[
            ("engine.step", 1000 * us, 1000 * us),
            ("engine.h2d", 1100 * us, 200 * us),
            ("engine.enqueue.decode_block", 1300 * us, 100 * us),
        ]],
        "runtime": [[("Allocate", 1150 * us, 100 * us)]],
        "ops": [(1000 * us, 1100 * us), (1350 * us, 2000 * us)],
    }
    event = dict.fromkeys(engine_breakdown.SUMMED, 0.0)
    event.update(
        family="decode_block", compiled=False, step=7, starved_s=0.0003,
        wait_s=0.0006,
    )
    bundle = {"epoch_unix_ns": 10**18, "dispatches": [
        dict(event, t=1e9 + 0.0010),             # enqueued at tracer 0.4 ms
        dict(event, t=1e9 + 0.0050, step=8),     # after the capture
    ]}
    out = engine_breakdown.place_on_capture(bundle, capture)
    assert out["dispatch_steps"] == [7]
    assert out["host_starved_s"] == pytest.approx(0.0003)
    assert out["interval_s"] == pytest.approx(0.001)
    assert out["device_idle_s"] == pytest.approx(250e-6)
    assert out["idle_by_span_s"] == {"engine.h2d": pytest.approx(250e-6)}
    assert list(out["idle_by_span_and_runtime_event_s"]) == [
        "engine.h2d | Allocate"
    ]


# --- (e) the benchmark's metric files name things a served engine has ----------


def _metric_files(readers):
    out = []
    for path in sorted((REPO / "benchmark" / "metrics").glob("*.json")):
        spec = json.loads(path.read_text())
        if spec["reader"] in readers:
            out.append(pytest.param(spec, id=path.stem))
    return out


@pytest.mark.parametrize(
    "spec",
    _metric_files({
        "registry", "ledger_share", "trace_roofline_counted", "trace_roofline_calls",
        "recorder_field",
    }),
)
def test_metric_file_reads_what_a_served_engine_has(served, served_moe, spec):
    params = spec["params"]
    if spec["reader"] == "ledger_share":
        report = served["eng"].ledger.window_report()
        assert set(params["buckets"]) <= set(report["buckets"])
    elif spec["reader"] == "recorder_field":
        # The event kind exists and every event of it carries the field.
        events = served["eng"].recorder.events(params["kind"])
        assert events and all(params["field"] in e for e in events)
        assert any(e[params["field"]] is not None for e in events)
    elif spec["reader"] == "trace_roofline_calls":
        # Rows a call: a dispatch of the family books its token slots.
        mine = [e for e in served["dispatches"] if e["family"] == params["family"]]
        assert mine and all(e["token_slots"] > 0 for e in mine)
    else:
        for key in ("name", "over", "count", "per"):
            if key in params:
                # The expert counters exist on an engine that has experts.
                end = (served_moe if "_moe_" in params[key] else served)["end"]
                assert params[key] in end, params[key]


# --- (f) dropless expert layers: counted on the device, read with the tokens ---


def _setup_moe():
    cfg = TransformerConfig(
        vocab_size=257, num_layers=3, features=64, num_heads=4, hidden=128,
        max_seq_len=128, dtype=jnp.float32, norm="rmsnorm", rope=True,
        latent_kv_rank=16, latent_q_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, ff_gated=True, first_k_dense=1, num_experts=8,
        moe_top_k=2, moe_hidden=32, moe_routing="sigmoid_dropless",
        moe_shared_experts=1, moe_routed_scaling=2.5, moe_experts="pallas",
    )
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = nn.meta.unbox(
        jax.jit(lambda r, t: Transformer(cfg).init({"params": r}, t))(
            jax.random.key(3), np.zeros((2, 8), np.int32)
        )["params"]
    )
    eng = ContinuousEngine(
        cfg, mesh, RULES_TP_SERVING, batch_size=2, max_new_tokens=MAX_NEW,
        refill_chunk=8, paged_pages=12, page_size=8,
        recorder=FlightRecorder(max_events=100_000),
    )
    return cfg, params, eng


@pytest.fixture(scope="module")
def served_moe():
    cfg, params, eng = _setup_moe()
    _drain(eng, params, _prompts(cfg, 1, 3))
    eng.recorder.clear()
    start = eng.registry.snapshot()
    prompts = _prompts(cfg, 14, 5)
    outs = _drain(eng, params, prompts)
    return {
        "eng": eng, "cfg": cfg, "prompts": prompts, "outs": outs,
        "start": start, "end": eng.registry.snapshot(),
        "dispatches": eng.recorder.events("engine.dispatch"),
        "retired": eng.recorder.events("engine.retire"),
    }


def _moe(served_moe, what, phase):
    return _delta(served_moe, f'engine_moe_{what}_total{{phase="{phase}"}}')


def test_expert_counters_count_every_routed_token(served_moe):
    cfg, prompts, outs = (served_moe[k] for k in ("cfg", "prompts", "outs"))
    layers = cfg.num_layers - cfg.first_k_dense
    per_token = cfg.moe_top_k * layers
    assert _moe(served_moe, "assignments", "refill") == per_token * sum(map(len, prompts))
    decoded = sum(len(o) - len(p) - 1 for o, p in zip(outs, prompts))
    assert decoded == _delta(served_moe, "engine_decode_steps_total")
    assert _moe(served_moe, "assignments", "decode") == per_token * decoded
    for phase in ("refill", "decode"):
        reads = _moe(served_moe, "expert_reads", phase)
        steps = _moe(served_moe, "layer_steps", phase)
        # A layer-step reads between 1 expert (every token agrees) and
        # min(experts, its assignments): never none, never an untouched one.
        assert steps > 0 and steps <= reads <= min(
            cfg.num_experts * steps, _moe(served_moe, "assignments", phase)
        )


def test_dispatch_events_carry_the_expert_counts(served_moe):
    evs = served_moe["dispatches"]
    assert all({"moe_assignments", "expert_reads"} <= set(e) for e in evs)
    for phase in ("refill", "decode"):
        mine = [e for e in evs if e["phase"] == phase]
        assert sum(e["moe_assignments"] for e in mine) == _moe(served_moe, "assignments", phase)
        assert sum(e["expert_reads"] for e in mine) == _moe(served_moe, "expert_reads", phase)
        assert all(e["expert_reads"] > 0 for e in mine)


def test_an_engine_without_experts_has_no_expert_series(served):
    assert not [k for k in served["end"] if k.startswith("engine_moe_")]
    assert not any("expert_reads" in e for e in served["dispatches"])


def test_the_breakdown_tool_prints_the_expert_counts(served_moe, tmp_path, capsys):
    served_moe["eng"].dump_diagnostics(tmp_path)
    out = engine_breakdown.main([str(tmp_path)])
    assert set(out["moe"]) == {"decode", "refill"}
    reg = served_moe["end"]
    assert out["moe"]["decode"]["expert_reads"] == reg['engine_moe_expert_reads_total{phase="decode"}']
    assert out["moe"]["decode"]["tokens_per_expert_read"] >= 1.0
    printed = capsys.readouterr().out
    assert "experts, decode:" in printed and "expert assignments over" in printed


# --- (g) state-space layers: a recurrent state a slot, carried across chunk rows ---


@pytest.fixture(scope="module")
def served_ssm():
    """One mixer a layer (``ME*M``) on four paged slots: long prompts take
    several rows of one refill dispatch, and slots are reused."""
    cfg = TransformerConfig(
        vocab_size=257, num_layers=4, layer_pattern="ME*M", features=64,
        num_heads=4, head_dim=16, num_kv_heads=2, hidden=64, max_seq_len=128,
        dtype=jnp.float32, norm="rmsnorm", norm_eps=1e-5, no_positions=True,
        num_experts=8, moe_top_k=2, moe_hidden=32, moe_routing="sigmoid_dropless",
        moe_shared_experts=1, moe_shared_hidden=48, moe_expert_act="relu2",
        moe_latent=32, moe_held=(2, 4), moe_routed_scaling=5.0, ssm_heads=4,
        ssm_head_dim=16, ssm_groups=2, ssm_state_size=16, ssm_chunk=8,
        decode_attention="blocked",
    )
    mesh = build_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = nn.meta.unbox(
        jax.jit(lambda r, t: Transformer(cfg).init({"params": r}, t))(
            jax.random.key(3), np.zeros((2, 8), np.int32)
        )["params"]
    )
    eng = ContinuousEngine(
        cfg, mesh, RULES_TP_SERVING, batch_size=4, max_new_tokens=MAX_NEW,
        refill_chunk=8, paged_pages=40, page_size=8,
        recorder=FlightRecorder(max_events=100_000),
    )
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(k,)).astype(np.int32)
        for k in (29, 11, 40, 5, 17, 33)
    ]
    outs = _drain(eng, params, prompts)
    return {
        "eng": eng, "cfg": cfg, "prompts": prompts, "outs": outs, "start": {},
        "end": eng.registry.snapshot(),
        "dispatches": eng.recorder.events("engine.dispatch"),
        "retired": eng.recorder.events("engine.retire"),
    }


def test_state_space_counters_are_pinned_and_add_up(served_ssm):
    end, evs = served_ssm["end"], served_ssm["dispatches"]
    assert {
        "engine_ssm_carried_rows_total", "engine_ssm_state_resets_total",
        "engine_ssm_state_bytes",
    } <= set(end)
    assert end["engine_ssm_state_resets_total"] == len(served_ssm["prompts"])
    # Two Mamba layers x four slots x (4 heads x 16 x 16 float32 + 3 cached
    # convolution inputs of 64 + 2 x 2 x 16 values, float32 here).
    assert end["engine_ssm_state_bytes"] == 2 * 4 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert all("carried_rows" in e for e in evs)
    assert sum(e["carried_rows"] for e in evs) == end["engine_ssm_carried_rows_total"] > 0
    assert not any(e["carried_rows"] for e in evs if e["phase"] != "refill")
    # A carried row is a chunk row, and a prompt's first chunk is never one.
    chunks = sum(-(-len(p) // 8) for p in served_ssm["prompts"])
    assert end["engine_refill_chunk_rows_total"] == chunks
    assert end["engine_ssm_carried_rows_total"] <= chunks - len(served_ssm["prompts"])


def test_expert_counters_count_held_experts_only(served_ssm):
    cfg, prompts = served_ssm["cfg"], served_ssm["prompts"]
    routed = cfg.moe_top_k * sum(map(len, prompts))        # one expert layer
    held = _moe(served_ssm, "assignments", "refill")
    assert 0 < held < routed                               # 4 of 8 experts held
    for phase in ("refill", "decode"):
        assert _moe(served_ssm, "expert_reads", phase) <= 4 * _moe(
            served_ssm, "layer_steps", phase
        )


def test_an_engine_without_state_space_layers_carries_nothing(served):
    assert served["end"]["engine_ssm_carried_rows_total"] == 0
    assert served["end"]["engine_ssm_state_bytes"] == 0
    assert not any(e["carried_rows"] for e in served["dispatches"])


def test_the_decode_attention_gauge_is_the_kernels_own_rule(served, tmp_path, capsys):
    """``engine_decode_attn_pages_in_flight``: what the kernel's rule gives
    for the cache one device holds (heads of 16 here: rows of 32 lanes are
    not whole tiles, so the emitter form, 0), printed by the tool."""
    from learning_jax_sharding_tpu.ops.decode_attention import pages_in_flight

    eng = served["eng"]
    pools = [
        x for path, x in jax.tree_util.tree_flatten_with_path(eng._cache)[0]
        if getattr(path[-1], "key", None) == "cached_kv"
    ]
    assert len(pools) == CONFIG_TINY.num_layers
    shape = pools[0].sharding.shard_shape(pools[0].shape)
    assert shape == (12, 2, 8, 32)
    assert served["end"]["engine_decode_attn_pages_in_flight"] == (
        pages_in_flight(shape, pools[0].dtype, 8)
    ) == 0
    eng.dump_diagnostics(tmp_path)
    out = engine_breakdown.main([str(tmp_path)])
    assert out["decode_attn_pages_in_flight"] == 0
    assert "0 cache blocks in flight (the emitter form)" in capsys.readouterr().out


def test_the_donated_bytes_gauge_is_the_cache_without_its_tables(served, tmp_path, capsys):
    """``engine_cache_donated_bytes``: every cache leaf but the block tables
    (what the step programs update in place), printed by the tool."""
    eng = served["eng"]
    flat = jax.tree_util.tree_flatten_with_path(eng._cache)[0]
    want = sum(
        x.nbytes for path, x in flat
        if getattr(path[-1], "key", None) != "block_table"
    )
    assert 0 < want < sum(x.nbytes for _, x in flat)
    assert served["end"]["engine_cache_donated_bytes"] == want
    eng.dump_diagnostics(tmp_path)
    out = engine_breakdown.main([str(tmp_path)])
    assert out["cache_donated_bytes"] == want
    assert f"cache: {want / 1e9:.3f} GB updated in place" in capsys.readouterr().out


def test_the_breakdown_tool_prints_the_carried_rows(served_ssm, tmp_path, capsys):
    served_ssm["eng"].dump_diagnostics(tmp_path)
    out = engine_breakdown.main([str(tmp_path)])
    rows = out["by_family"]["refill_step"]
    assert rows["carried_rows"] > 0
    assert "of the rows carried from an earlier row's recurrent state" in capsys.readouterr().out


def test_the_breakdown_tool_times_the_kernels_a_trace_names_by_scope():
    """On lines written by hand (a CPU capture has no TPU plane): the
    kernels' instruction names carry their scope, a fusion's does not."""
    Event = collections.namedtuple("Event", "name start_ns duration_ns")
    Line = collections.namedtuple("Line", "events")
    lines = {
        "XLA Modules": Line([
            Event("jit_decode_block(1)", 0.0, 1000.0),
            Event("jit_refill_step(2)", 2000.0, 1000.0),
        ]),
        "XLA Ops": Line([
            Event("%ssm.state_update.3 = f32[1] custom-call()", 10.0, 100.0),
            Event("%ssm.state_update.4 = f32[1] custom-call()", 200.0, 100.0),
            Event("%fusion.7 = f32[1] fusion()", 400.0, 500.0),
            Event("%ssm.chunk_scan = f32[1] custom-call()", 2100.0, 300.0),
            Event("%moe.experts.1 = f32[1] custom-call()", 2500.0, 50.0),
        ]),
    }
    kernels = collections.defaultdict(lambda: [0.0, 0])
    engine_breakdown._add_kernel_times(kernels, lines)
    assert {k: tuple(v) for k, v in kernels.items()} == {
        "jit_decode_block | ssm.state_update": (pytest.approx(2e-7), 2),
        "jit_refill_step | ssm.chunk_scan": (pytest.approx(3e-7), 1),
        "jit_refill_step | moe.experts": (pytest.approx(5e-8), 1),
    }


# --- (h) the request clock: every second of a request in one phase ---------------

RETIRE_FIELDS = {
    "rid", "slot", "generated", "ttft", "e2e", "version", "queue_wait", "tpot",
    "refill_wait_s", "refill_s", "stall_s", "decode_s", "refill_dispatches",
    "stall_dispatches", "decode_dispatches", "stall_per_token_s",
    "decode_per_token_s", "redone_s", "requeue_wait_s", "admit_unix",
    "first_token_unix",
}


@pytest.mark.parametrize(
    "engine", ["served", "served_moe", "served_ssm", "served_mixed"]
)
def test_a_retired_requests_phases_add_up_to_its_latency(engine, request):
    """``refill_wait_s + refill_s`` is admission to first token and
    ``stall_s + decode_s`` first token to retirement, for every request
    of the split paged engines (dense, latent + experts, state space) and
    of a mixed one (a link a dispatch, a budget that makes a prompt wait,
    a two-link horizon)."""
    retired = request.getfixturevalue(engine)["retired"]
    _check_identities(retired)
    assert all(set(e) >= RETIRE_FIELDS for e in retired)
    assert not any(e["redone_s"] or e["requeue_wait_s"] for e in retired)
    if engine != "served_mixed":
        # A split engine's refill dispatch carries every admitted prompt.
        assert not any(e["refill_wait_s"] for e in retired)


def test_a_mixed_dispatch_books_each_request_by_what_it_got(served_mixed):
    """With one token a dispatch to spend, the link that advances the
    decoding request carries nothing of the prompt that just arrived: it
    waits (``refill_wait``), and nobody stalls."""
    ev = served_mixed["budget_dispatch"]
    assert (ev["phase"], ev["carried"], ev["waiting"], ev["stalled"]) == (
        "mixed", 0, 1, 0
    )
    (late,) = [
        e for e in served_mixed["retired"] if e["rid"] == served_mixed["late_rid"]
    ]
    assert late["refill_wait_s"] > 0 and late["refill_dispatches"] >= 1
    # ... and a two-link horizon reads back once for both links.
    assert served_mixed["horizon_kinds"]["multi_step"]


@pytest.fixture(scope="module")
def pressed(served):
    """The ``served`` engine again, after its measured drain. First a long
    prompt that arrives while a request decodes: ONE refill dispatch (two
    chunk rows) stalls it. Then two prompts whose decode does not fit the
    pool together (11 pages of 8; each needs 6): one is preempted once."""
    eng, params, cfg = served["eng"], served["params"], served["cfg"]
    rng = np.random.default_rng(11)

    def prompt(n):
        return rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)

    def snap():
        return eng.registry.snapshot(), eng.recorder.events()

    got = {"start": snap()}
    got["early"] = eng.add_request(prompt(5))
    eng.step(params)                                  # its first token
    got["before"] = snap()
    got["late"] = eng.add_request(prompt(16))
    eng.step(params)                                  # the late prompt, whole
    got["after"] = snap()
    got["held"] = np.asarray([eng._ph_s[s] for s in range(2) if eng._req[s] >= 0]).sum(axis=0)
    while eng.has_work():
        eng.step(params)
    got["drained"] = snap()
    for _ in range(2):
        eng.add_request(prompt(40))
    while eng.has_work():
        eng.step(params)
    got["end"] = snap()
    eng.pop_finished()
    return got


def _since(got, a, b, kind=None):
    """Registry growth and the recorder's new events between two snaps."""
    (reg_a, ev_a), (reg_b, ev_b) = got[a], got[b]
    events = ev_b[len(ev_a):]
    if kind is not None:
        events = [e for e in events if e["kind"] == kind]
    return (lambda name: reg_b[name] - reg_a.get(name, 0.0)), events


def test_a_late_prompt_stalls_the_rows_already_decoding(pressed):
    grew, (dispatch,) = _since(pressed, "before", "after", "engine.dispatch")
    assert (dispatch["phase"], dispatch["chunk_rows"]) == ("refill", 2)
    # carried + waiting: the requests admitted without a first token.
    assert (dispatch["carried"], dispatch["waiting"], dispatch["stalled"]) == (1, 0, 1)
    assert dispatch["rows"] == dispatch["stalled"]
    _, retired = _since(pressed, "start", "drained", "engine.retire")
    early, late = (
        next(e for e in retired if e["rid"] == pressed[k]) for k in ("early", "late")
    )
    # The early request's stall IS that dispatch's tick: the one interval
    # the clock gave ``stall`` while it was the only holder of a first token.
    assert early["stall_dispatches"] == 1
    assert early["stall_s"] == pytest.approx(grew(PHASE_SECONDS % "stall"), rel=1e-12)
    assert early["stall_s"] > 0
    assert grew(PHASE_DISPATCHES % "stall") == 1
    assert (late["refill_dispatches"], late["stall_dispatches"]) == (1, 0)
    assert late["refill_s"] == pytest.approx(grew(PHASE_SECONDS % "refill"), rel=1e-12)
    assert late["refill_wait_s"] == late["stall_s"] == 0.0
    _check_identities(retired)


def test_the_phase_counters_grow_by_what_requests_were_given(served, pressed):
    # A drain: everything the counters gained is on the retire events ...
    for phase in PHASES:
        assert _delta(served, PHASE_SECONDS % phase) == pytest.approx(
            sum(e[f"{phase}_s"] for e in served["retired"]), rel=1e-9, abs=1e-12
        )
    for phase, field in (
        ("refill", "refill_dispatches"), ("stall", "stall_dispatches"),
        ("decode", "decode_dispatches"),
    ):
        assert 0 < _delta(served, PHASE_DISPATCHES % phase) == sum(
            e[field] for e in served["retired"]
        )
    # ... and so are the dispatch events' causes, request by request.
    for cause, field in (("carried", "refill_dispatches"), ("stalled", "stall_dispatches")):
        assert sum(e[cause] for e in served["dispatches"]) == sum(
            e[field] for e in served["retired"]
        )
    assert not any(e["waiting"] for e in served["dispatches"])
    # Mid-flight: plus what the live requests hold.
    grew, retired = _since(pressed, "start", "after", "engine.retire")
    assert not retired and pressed["held"].sum() > 0
    for i, phase in enumerate(PHASES):
        assert grew(PHASE_SECONDS % phase) == pytest.approx(
            pressed["held"][i], rel=1e-9, abs=1e-12
        )


def test_a_preempted_requests_identities_hold_from_its_last_admission(pressed):
    grew, events = _since(pressed, "drained", "end")
    (preempt,) = [e for e in events if e["kind"] == "engine.preempt"]
    retired = [e for e in events if e["kind"] == "engine.retire"]
    assert len(retired) == 2
    _check_identities(retired)
    (again,) = [e for e in retired if e["rid"] == preempt["rid"]]
    (other,) = [e for e in retired if e["rid"] != preempt["rid"]]
    assert again["redone_s"] > 0 and again["requeue_wait_s"] >= 0
    assert other["redone_s"] == other["requeue_wait_s"] == 0.0
    # 40 tokens are five chunks: five refill dispatches beside the other
    # prompt, three alone (two rows a dispatch); the preempted request's
    # five of its first admission are in ``redone`` only.
    assert (other["refill_dispatches"], again["refill_dispatches"]) == (5, 3)
    assert again["admit_unix"] > other["admit_unix"]
    # The counters keep the redone seconds in the phase they ran in and
    # count them again under ``redone``.
    kept = sum(e[f"{p}_s"] for e in retired for p in PHASES)
    assert grew(PHASE_SECONDS % "redone") == pytest.approx(again["redone_s"], rel=1e-9)
    assert sum(grew(PHASE_SECONDS % p) for p in PHASES) == pytest.approx(
        kept + again["redone_s"], rel=1e-9
    )
    assert grew(PHASE_DISPATCHES % "redone") == 5


def test_a_failed_request_is_accounted_up_to_its_failure(pressed, served):
    """``drain_requests`` fails a request between two readbacks: its event
    carries the clock up to that instant (the tail is a wait)."""
    eng, params, cfg = served["eng"], served["params"], served["cfg"]
    rid = eng.add_request(_prompts(cfg, 21, 1)[0][:5])
    eng.step(params)                                  # its first token
    before = eng.registry.snapshot()
    eng.drain_requests()
    (failed,) = [
        e for e in eng.recorder.events("engine.request_failed") if e["rid"] == rid
    ]
    eng.pop_finished()
    assert failed["status"] == "rerouted" and failed["refill_dispatches"] == 1
    assert failed["refill_wait_s"] == 0.0 and failed["decode_s"] == 0.0
    after = eng.registry.snapshot()
    assert 0 < failed["stall_s"] == pytest.approx(
        after[PHASE_SECONDS % "stall"] - before[PHASE_SECONDS % "stall"], rel=1e-12
    )
    assert failed["stall_dispatches"] == 0
    assert not eng.has_work()
