"""Goodput ledger + request tracing (telemetry/, round 14).

Three layers:

* pure units — the ledger's exclusive-frame accounting identity under a
  fake clock (nesting, retrospective booking, compile re-bucketing,
  windows, the reconcile invariant and its failure modes) and the
  TraceStore's critical-path algebra (stall remainder, wasted legs,
  TTFT, reroute/swap-pin events, Perfetto export, merge rebase);
* engine/loop integration — a real ContinuousEngine drain and a real
  ``fit()`` run must RECONCILE (Σ buckets == wall within ε) with traced
  requests carrying complete critical paths;
* chaos attribution — injected faults (slow dispatch, NaN-trap raise)
  book into ``recovery``, never ``device``: the ledger cannot blame the
  hardware for the failure machinery.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.models.serving import ContinuousEngine
from learning_jax_sharding_tpu.models.transformer import (
    CONFIG_TINY,
    Transformer,
)
from learning_jax_sharding_tpu.parallel import build_mesh
from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP
from learning_jax_sharding_tpu.parallel.multihost import (
    merge_registry_snapshots,
)
from learning_jax_sharding_tpu.robustness import ChaosInjector, Fault
from learning_jax_sharding_tpu.telemetry import (
    BUCKETS,
    GoodputLedger,
    STAGES,
    TraceStore,
    merge_tracers,
)
from learning_jax_sharding_tpu.telemetry.flight_recorder import FlightRecorder
from learning_jax_sharding_tpu.telemetry.registry import (
    MetricsRegistry,
    snapshot_prometheus_text,
)


class _Clock:
    """Deterministic manual clock for the pure units."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, s):
        self.t += s


# --- ledger units ---------------------------------------------------------


class TestLedger:
    def test_nested_frames_book_exclusive_time(self):
        clk = _Clock()
        led = GoodputLedger(clock=clk)
        with led.measure("sched"):
            clk.tick(1.0)
            with led.measure("device"):
                clk.tick(2.0)
            clk.tick(0.5)
        clk.tick(0.5)                       # idle tail
        b = led.window_buckets()
        assert b["device"] == pytest.approx(2.0)
        assert b["sched"] == pytest.approx(1.5)      # 3.5 total − 2.0 child
        assert b["idle"] == pytest.approx(0.5)
        rec = led.reconcile()
        assert rec["ok"], rec
        assert rec["wall_s"] == pytest.approx(4.0)
        assert rec["residual_s"] == pytest.approx(0.0)

    def test_account_steals_from_the_enclosing_frame(self):
        clk = _Clock()
        led = GoodputLedger(clock=clk)
        with led.measure("sched"):
            clk.tick(1.0)
            led.account("telemetry", 0.25)   # part of the elapsed second
        b = led.window_buckets()
        assert b["telemetry"] == pytest.approx(0.25)
        assert b["sched"] == pytest.approx(0.75)
        assert led.reconcile()["ok"]
        with pytest.raises(ValueError):
            led.account("telemetry", -1.0)

    def test_rebucket_moves_a_compile_stolen_dispatch(self):
        clk = _Clock()
        led = GoodputLedger(clock=clk)
        with led.measure("device") as frame:
            clk.tick(3.0)
            frame.rebucket("compile")        # executable cache grew
        b = led.window_buckets()
        assert b["compile"] == pytest.approx(3.0)
        assert b["device"] == pytest.approx(0.0)

    def test_windows_are_deltas(self):
        clk = _Clock()
        led = GoodputLedger(clock=clk)
        with led.measure("device"):
            clk.tick(5.0)
        led.begin_window()
        with led.measure("device"):
            clk.tick(1.0)
        assert led.window_buckets()["device"] == pytest.approx(1.0)
        assert led.totals()["device"] == pytest.approx(6.0)
        assert led.reconcile()["ok"]

    def test_window_report_names_the_top_gap(self):
        clk = _Clock()
        led = GoodputLedger(clock=clk)
        with led.measure("sched"):
            clk.tick(1.5)
            with led.measure("device"):
                clk.tick(2.0)
        clk.tick(0.5)                        # idle
        rep = led.window_report()
        assert rep["wall_s"] == pytest.approx(4.0)
        assert rep["busy_s"] == pytest.approx(3.5)
        assert rep["host_share"] == pytest.approx(1.0 - 2.0 / 3.5)
        assert rep["top_contributor"] == "sched"
        assert rep["top_contributor_s"] == pytest.approx(1.5)
        # Measured ratio without a roofline; the roofline overrides.
        assert rep["goodput_ratio"] == pytest.approx(2.0 / 4.0)
        rep2 = led.window_report(roofline_device_s=1.0)
        assert rep2["goodput_ratio"] == pytest.approx(0.25)

    def test_reconcile_catches_leaks_and_open_frames(self):
        clk = _Clock()
        led = GoodputLedger(clock=clk)
        with led.measure("device"):
            clk.tick(1.0)
        # A booking that never happened on this clock breaks the
        # identity — exactly what reconcile() exists to catch.
        led._totals["sched"] = led._totals.get("sched", 0.0) + 5.0
        assert not led.reconcile()["ok"]
        led2 = GoodputLedger(clock=clk)
        cm = led2.measure("device")
        cm.__enter__()
        assert not led2.reconcile()["ok"]    # open frame → not reconciled
        cm.__exit__(None, None, None)

    def test_meters_into_the_registry(self):
        clk = _Clock()
        reg = MetricsRegistry()
        led = GoodputLedger(registry=reg, clock=clk)
        with led.measure("device"):
            clk.tick(2.0)
        c = reg.get('ledger_seconds_total{bucket="device"}')
        assert c is not None and c.value == pytest.approx(2.0)
        assert 'ledger_seconds_total{bucket="device"} 2' in (
            reg.prometheus_text()
        )

    def test_canonical_buckets_always_report(self):
        led = GoodputLedger(clock=_Clock())
        b = led.window_buckets()
        assert list(b) == list(BUCKETS)


# --- trace-store units ----------------------------------------------------


class TestTraceStore:
    def test_mint_is_idempotent_and_ordered(self):
        ts = TraceStore()
        assert ts.mint(7, arrival_t=1.0) == "trace-00001"
        assert ts.mint(7) == "trace-00001"
        assert ts.mint(9) == "trace-00002"
        assert ts.trace_of(7) == "trace-00001"
        assert ts.trace_of(404) is None

    def test_critical_path_decomposition(self):
        ts = TraceStore(registry=MetricsRegistry())
        ts.mint(1, arrival_t=10.0)
        ts.leg(1, "queue", 10.0, 11.0, replica="p0")
        ts.leg(1, "prefill", 11.0, 12.5, replica="p0", first_token_t=12.5,
               refill_wait_s=0.25)
        ts.leg(1, "handoff", 12.5, 12.7)
        ts.leg(1, "decode", 12.8, 14.0, replica="d0", stall_s=0.4)
        ts.complete(1, finish_t=14.2)
        cp = ts.critical_path(1)
        assert cp["e2e_s"] == pytest.approx(4.2)
        assert cp["ttft_s"] == pytest.approx(2.5)
        assert cp["stages"]["queue"] == pytest.approx(1.0)
        assert cp["stages"]["prefill"] == pytest.approx(1.5)
        assert cp["stages"]["handoff"] == pytest.approx(0.2)
        assert cp["stages"]["decode"] == pytest.approx(1.2)
        # stall = e2e − named stages: the 0.1 gap before decode plus the
        # 0.2 tail after it.
        assert cp["stages"]["stall"] == pytest.approx(0.3)
        # ... beside what the engines' request clocks measured INSIDE the
        # legs (a decode leg's seconds behind another request's refill).
        assert cp["stall_measured_s"] == pytest.approx(0.4)
        assert cp["refill_wait_s"] == pytest.approx(0.25)

    def test_wasted_legs_sum_separately(self):
        ts = TraceStore()
        ts.mint(1, arrival_t=0.0)
        ts.leg(1, "prefill", 0.0, 1.0, wasted=True,    # failover threw it
               refill_wait_s=0.5)
        ts.leg(1, "prefill", 1.0, 1.5, first_token_t=1.5)
        ts.complete(1, finish_t=2.0)
        cp = ts.critical_path(1)
        assert cp["wasted_s"] == pytest.approx(1.0)
        assert cp["stages"]["prefill"] == pytest.approx(0.5)
        assert cp["stages"]["stall"] == pytest.approx(1.5)
        assert cp["refill_wait_s"] == cp["stall_measured_s"] == 0.0
        assert cp["legs"] == 2

    def test_events_count_reroutes_and_pin_versions(self):
        ts = TraceStore()
        ts.mint(1)
        ts.instant(1, "reroute", replica="d1", error="killed")
        ts.instant(1, "reroute", replica="d0")
        ts.instant(1, "swap_pin", version=3)
        ts.complete(1, finish_t=1.0)
        cp = ts.critical_path(1)
        assert cp["reroutes"] == 2
        assert cp["swap_pins"] == [3]

    def test_complete_is_idempotent_and_observes_histograms(self):
        reg = MetricsRegistry()
        ts = TraceStore(registry=reg)
        ts.mint(1, arrival_t=0.0)
        ts.leg(1, "prefill", 0.0, 1.0, first_token_t=1.0)
        ts.complete(1, status="ok", finish_t=2.0)
        ts.complete(1, status="late-duplicate", finish_t=99.0)
        assert ts.record(1)["status"] == "ok"
        h = reg.get('trace_stage_seconds{stage="prefill"}')
        assert h.count == 1 and h.sum == pytest.approx(1.0)
        assert reg.get("trace_ttft_seconds").count == 1
        assert reg.get("trace_e2e_seconds").sum == pytest.approx(2.0)
        assert len(ts.completed()) == 1

    def test_done_traces_age_out_live_ones_never(self):
        ts = TraceStore(max_done=2)
        for rid in (1, 2, 3):
            ts.mint(rid)
            ts.complete(rid, finish_t=1.0)
        ts.mint(77)                          # live
        assert ts.record(1) is None          # oldest done aged out
        assert ts.record(3) is not None
        assert ts.record(77) is not None

    def test_chrome_trace_has_per_replica_process_tracks(self):
        ts = TraceStore()
        ts.mint(1, arrival_t=0.0)
        ts.leg(1, "prefill", 0.0, 1.0, replica="p0")
        ts.leg(1, "decode", 1.0, 2.0, replica="d0")
        ts.instant(1, "reroute")             # replica-less → "fleet"
        doc = ts.chrome_trace()
        meta = {
            e["args"]["name"]: e["pid"]
            for e in doc["traceEvents"] if e["ph"] == "M"
        }
        assert set(meta) == {"replica d0", "replica p0", "fleet"}
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {s["name"] for s in spans} == {"prefill", "decode"}
        assert all(s["tid"] == 1 for s in spans)
        (inst,) = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert inst["pid"] == meta["fleet"]

    def test_merge_tracers_rebases_rings_onto_one_epoch(self):
        class _Ring:
            def __init__(self, t0, events):
                self._t0 = t0
                self.events = events

        merged = merge_tracers(
            {
                "b": _Ring(10.0, [{"name": "x", "ph": "X", "ts": 5.0,
                                   "dur": 1.0, "tid": 0}]),
                "a": _Ring(12.0, [{"name": "y", "ph": "X", "ts": 5.0,
                                   "dur": 1.0, "tid": 0}]),
            },
            extra_events=[{"name": "marker", "ph": "i", "ts": 0.0}],
        )
        ev = merged["traceEvents"]
        names = {
            e["args"]["name"]: e["pid"]
            for e in ev
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert names == {"replica a": 1, "replica b": 2}
        # Deterministic Perfetto ordering: each replica pid also carries
        # a process_sort_index row matching its sorted-name rank.
        sorts = {
            e["pid"]: e["args"]["sort_index"]
            for e in ev
            if e["ph"] == "M" and e["name"] == "process_sort_index"
        }
        assert sorts == {1: 0, 2: 1}
        by_name = {e["name"]: e for e in ev if e["ph"] == "X"}
        # a's epoch is 2 s after b's: same local ts lands 2e6 µs later.
        assert by_name["y"]["ts"] == pytest.approx(
            by_name["x"]["ts"] + 2e6
        )
        assert ev[-1]["name"] == "marker"    # extras appended verbatim
        assert merged["otherData"]["epoch_perf_t0"] == 10.0


# --- engine + fit integration --------------------------------------------


def _params(cfg):
    return nn.meta.unbox(
        jax.jit(lambda r, t: Transformer(cfg).init({"params": r}, t))(
            jax.random.key(3), np.zeros((2, 8), np.int32)
        )["params"]
    )


@pytest.fixture(scope="module")
def served():
    """One traced engine drain, shared by the integration asserts."""
    cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
    mesh = build_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2])
    params = _params(cfg)
    eng = ContinuousEngine(
        cfg, mesh, RULES_DP_TP, batch_size=2, max_new_tokens=4,
        refill_chunk=8,
    )
    eng.trace_sink = TraceStore(registry=eng.registry)
    rng = np.random.default_rng(14)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in rng.integers(5, 12, size=6)
    ]
    for p in prompts:
        eng.add_request(p)
    while eng.has_work():
        eng.step(params)
    outs = eng.pop_finished()
    return eng, outs


class TestEngineLedger:
    def test_engine_wall_reconciles(self, served):
        eng, outs = served
        assert len(outs) == 6
        rec = eng.ledger.reconcile()
        assert rec["ok"], rec
        b = rec["buckets"]
        assert b["device"] > 0.0
        assert b["compile"] > 0.0            # first dispatches compiled
        assert b["sched"] > 0.0

    def test_solo_engine_traces_complete_critical_paths(self, served):
        eng, outs = served
        cps = eng.trace_sink.completed()
        assert len(cps) == 6
        for cp in cps:
            assert cp["status"] == "ok"
            assert cp["stages"]["queue"] >= 0.0
            assert cp["stages"]["prefill"] > 0.0
            assert cp["stages"]["decode"] > 0.0
            assert cp["ttft_s"] is not None and cp["ttft_s"] > 0.0
            assert cp["e2e_s"] >= cp["ttft_s"]
            # The request clock's numbers ride the legs: a split engine's
            # prompts never wait for a refill turn, and a decode leg holds
            # its stall.
            assert cp["refill_wait_s"] == 0.0
            assert 0.0 <= cp["stall_measured_s"] <= cp["stages"]["decode"]
        assert any(cp["stall_measured_s"] > 0.0 for cp in cps)

    def test_ledger_series_reach_prometheus(self, served):
        eng, _ = served
        text = eng.registry.prometheus_text()
        assert 'ledger_seconds_total{bucket="device"}' in text
        assert 'trace_stage_seconds_bucket{stage="queue",le=' in text

    def test_report_names_top_contributor(self, served):
        eng, _ = served
        rep = eng.ledger.window_report()
        assert rep["host_share"] is not None and 0.0 < rep["host_share"] < 1.0
        assert rep["top_contributor"] in set(BUCKETS) - {"device"}
        assert rep["telemetry_share"] < 0.05

    def test_exposed_comm_is_a_view_over_device_never_telemetry(
        self, served
    ):
        """The round-19 overlap decomposition must be a pure VIEW: per
        family it sums back to that family's measured device seconds,
        the family totals cover the device bucket, and arming the view
        moves nothing into ``telemetry`` (so ``reconcile()`` is
        untouched by construction)."""
        eng, _ = served
        before = eng.ledger.window_buckets()
        rep = eng.overlap_report()
        assert rep["families"], "device seconds lost their family tags"
        for fam, row in rep["families"].items():
            total = (row["compute_s"] + row["exposed_comm_s"]
                     + row["overlapped_comm_s"])
            assert total == pytest.approx(row["device_s"]), (fam, row)
        assert rep["attributed_s"] + rep["residual_s"] == pytest.approx(
            rep["device_s"])
        after = eng.ledger.window_buckets()
        assert after["device"] == pytest.approx(before["device"])
        assert after.get("telemetry", 0.0) == pytest.approx(
            before.get("telemetry", 0.0))
        assert eng.ledger.reconcile()["ok"]


class TestChaosAttribution:
    """Injected faults must land in ``recovery``, never ``device`` —
    the attribution contract that keeps the goodput verdict honest under
    failure (an injected hang blamed on the device bucket would read as
    a hardware slowdown)."""

    def _drain(self, eng, params, prompts):
        for p in prompts:
            eng.add_request(p)
        while eng.has_work():
            eng.step(params)
        return eng.pop_finished()

    @pytest.fixture(scope="class")
    def chaos_run(self):
        cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
        mesh = build_mesh(
            (1, 2), ("data", "model"), devices=jax.devices()[:2]
        )
        params = _params(cfg)
        eng = ContinuousEngine(
            cfg, mesh, RULES_DP_TP, batch_size=2, max_new_tokens=4,
            refill_chunk=8,
        )
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in rng.integers(5, 12, size=4)
        ]
        self._drain(eng, params, prompts)          # warm: compiles out
        eng.ledger.begin_window()
        base_device = eng.ledger.totals().get("device", 0.0)
        with ChaosInjector(
            Fault("engine.dispatch", "slow", at=1, count=2, delay_s=0.05),
            Fault("engine.dispatch", "raise", at=3, count=1,
                  error=FloatingPointError),
            recorder=FlightRecorder(),
        ) as inj:
            outs = self._drain(eng, params, prompts)
        return eng, inj, outs, base_device

    def test_injected_delay_books_to_recovery(self, chaos_run):
        eng, inj, outs, _ = chaos_run
        assert len([f for f in inj.injections if f["fault"] == "slow"]) == 2
        assert eng.ledger.window_buckets()["recovery"] >= 0.1

    def test_nan_trap_recovers_and_reconciles(self, chaos_run):
        eng, inj, outs, _ = chaos_run
        assert any(f["fault"] == "raise" for f in inj.injections)
        assert len(outs) == 4                # strikes requeue, none lost
        rec = eng.ledger.reconcile()
        assert rec["ok"], rec

    def test_device_bucket_stays_clean_of_chaos(self, chaos_run):
        eng, inj, outs, base_device = chaos_run
        # The device bucket may only hold real dispatch wall — it must
        # not have absorbed the 2×50 ms injected sleeps.
        device = eng.ledger.totals()["device"] - base_device
        assert device < 0.1 or (
            device < eng.ledger.window_buckets()["recovery"]
        )


class TestFitLedger:
    def test_fit_reconciles_and_books_compile(self, tmp_path):
        from learning_jax_sharding_tpu.data import SyntheticLMDataset
        from learning_jax_sharding_tpu.training.loop import (
            TrainLoopConfig,
            fit,
        )

        mesh = build_mesh(
            (2, 2), ("data", "model"), devices=jax.devices()[:4]
        )
        led = GoodputLedger(registry=MetricsRegistry())
        cfg = TrainLoopConfig(
            steps=3, global_batch_size=8, learning_rate=1e-3,
            metrics_path=str(tmp_path / "m.jsonl"),
        )
        ds = SyntheticLMDataset(
            vocab_size=CONFIG_TINY.vocab_size, seq_len=16, seed=7
        )
        state, hist = fit(
            Transformer(CONFIG_TINY), ds, mesh, RULES_DP_TP, cfg,
            ledger=led,
        )
        assert len(hist) == 3
        rec = led.reconcile()
        assert rec["ok"], rec
        b = rec["buckets"]
        assert b["compile"] > 0.0            # setup + first-step traces
        assert b["device"] > 0.0             # the steady steps
        assert b["sched"] >= 0.0


# --- labeled fleet export -------------------------------------------------


class TestLabeledExport:
    def test_fleet_merge_splices_replica_into_ledger_labels(self):
        regs = {}
        for name in ("p0", "d0"):
            clk = _Clock()
            reg = MetricsRegistry()
            led = GoodputLedger(registry=reg, clock=clk)
            with led.measure("device"):
                clk.tick(1.0 if name == "p0" else 2.0)
            regs[name] = reg
        merged = merge_registry_snapshots(
            [regs["p0"].snapshot(), regs["d0"].snapshot()],
            labels=["p0", "d0"],
        )
        # The fleet sum keeps the bucket-only key; per-replica series
        # carry both labels.
        assert merged['ledger_seconds_total{bucket="device"}'] == (
            pytest.approx(3.0)
        )
        key = 'ledger_seconds_total{bucket="device",replica="d0"}'
        assert merged[key] == pytest.approx(2.0)
        text = snapshot_prometheus_text(merged)
        assert 'ledger_seconds_total{bucket="device",replica="p0"} 1' in text
        # The exposition keeps the family contiguous: fleet sum and
        # per-replica series group together, never interleaved with
        # other families.
        fam = [
            ln for ln in text.splitlines()
            if ln.startswith("ledger_seconds_total")
        ]
        idx = [
            i for i, ln in enumerate(text.splitlines())
            if ln.startswith("ledger_seconds_total")
        ]
        assert len(fam) == 3
        assert idx == list(range(idx[0], idx[0] + 3))
