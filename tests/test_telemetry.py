"""Unified telemetry subsystem (telemetry/): spans, registry, compile
watch — plus the engine/training integrations and the case18 smoke.

The pinned claims: Chrome-trace output is structurally valid (Perfetto
semantics: complete events nest by containment, async pairs match by
id), Prometheus exposition parses, registry-backed engine stats keep the
pre-telemetry contract, and compile accounting observes real compiles
and real recompiles.
"""

import dataclasses
import json
import math
import re
import runpy
import sys
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_jax_sharding_tpu.telemetry import (
    CompileWatch,
    MetricsRegistry,
    Tracer,
    executable_report,
    watched,
)


class TestTracer:
    def test_nested_spans_nest_by_containment(self):
        t = Tracer()
        with t.span("outer", phase="demo"):
            time.sleep(0.002)
            with t.span("inner"):
                time.sleep(0.002)
        evs = {e["name"]: e for e in t.events}
        outer, inner = evs["outer"], evs["inner"]
        assert outer["ph"] == inner["ph"] == "X"
        # Perfetto infers nesting from interval containment per tid.
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["args"]["parent"] == "outer"
        assert outer["args"]["phase"] == "demo"

    def test_async_pairs_and_instants(self):
        t = Tracer()
        t.async_begin("request", 5, prompt_len=7)
        t.instant("request.first_token", rid=5)
        t.async_end("request", 5)
        phases = [e["ph"] for e in t.events]
        assert phases == ["b", "i", "e"]
        b, i, e = t.events
        assert b["id"] == e["id"] == 5 and b["cat"] == "request"
        assert i["s"] == "t" and i["args"]["rid"] == 5

    def test_chrome_trace_and_jsonl_roundtrip(self, tmp_path):
        t = Tracer()
        with t.span("s"):
            pass
        t.dump_chrome_trace(tmp_path / "trace.json")
        t.dump_jsonl(tmp_path / "trace.jsonl")
        ct = json.loads((tmp_path / "trace.json").read_text())
        assert ct["traceEvents"] and ct["displayTimeUnit"] == "ms"
        lines = (tmp_path / "trace.jsonl").read_text().splitlines()
        assert [json.loads(l)["name"] for l in lines] == ["s"]

    def test_sync_is_honest_and_recorded(self):
        t = Tracer()
        out = jax.jit(lambda x: x * 2)(jnp.ones((8,)))
        t.sync(out)
        (ev,) = t.events
        assert ev["name"] == "device_sync" and ev["ph"] == "X"

    def test_bounded_ring_keeps_newest_and_counts_drops(self):
        t = Tracer(max_events=3)
        for i in range(5):
            t.instant(f"e{i}")
        assert [e["name"] for e in t.events] == ["e2", "e3", "e4"]
        assert t.dropped == 2

    def test_disabled_tracer_records_nothing(self):
        t = Tracer(enabled=False)
        with t.span("s"):
            t.instant("i")
        assert t.events == []


class TestRegistry:
    def test_counter_gauge_histogram(self):
        r = MetricsRegistry()
        c = r.counter("reqs_total")
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)
        g = r.gauge("depth")
        g.set(5)
        g.set(2)
        assert g.value == 2 and g.high_water == 5
        g.reset_high_water()
        assert g.high_water == 2
        h = r.histogram("lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(9.0)
        assert h.count == 3 and h.sum == pytest.approx(9.55)
        assert h.cumulative() == [(0.1, 1), (1.0, 2), (math.inf, 3)]

    def test_get_or_create_and_kind_conflict(self):
        r = MetricsRegistry()
        assert r.counter("x") is r.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("x")
        r.histogram("h", buckets=(1.0,))
        with pytest.raises(ValueError, match="different"):
            r.histogram("h", buckets=(2.0,))

    def test_prometheus_text_parses(self):
        r = MetricsRegistry()
        r.counter("a_total", "things").inc(7)
        r.gauge("b").set(1.5)
        h = r.histogram("c_seconds", buckets=(0.5,))
        h.observe(0.2)
        text = r.prometheus_text()
        # Exposition-format shape: every sample line is `name{labels} value`.
        sample = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{le="[^"]+"\})? [0-9.+eEInf-]+$'
        )
        for line in text.strip().splitlines():
            assert line.startswith("#") or sample.match(line), line
        assert "# TYPE a_total counter" in text
        assert "a_total 7" in text
        assert "# HELP a_total things" in text
        assert 'c_seconds_bucket{le="+Inf"} 1' in text
        assert "c_seconds_count 1" in text

    def test_snapshot_is_json_able(self):
        r = MetricsRegistry()
        r.counter("a").inc()
        r.gauge("g").set(2)
        r.histogram("h", buckets=(1.0,)).observe(3.0)
        snap = json.loads(json.dumps(r.snapshot()))
        assert snap["a"] == 1 and snap["g"] == 2
        assert snap["g__high_water"] == 2
        assert snap["h"]["count"] == 1


class TestCompileWatch:
    def test_counts_compiles_inside_watch_only(self):
        w = CompileWatch()
        with w:
            jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,)))
        seen = w.backend_compiles
        assert seen >= 1
        assert w.backend_compile_seconds > 0
        jax.jit(lambda x: x * 5 - 2)(jnp.ones((5,)))   # outside: not counted
        assert w.backend_compiles == seen
        rep = w.report()
        assert rep["monitoring_available"]
        assert rep["traces"] >= 1 and rep["trace_seconds"] > 0

    def test_registry_mirror(self):
        r = MetricsRegistry()
        with CompileWatch(registry=r):
            jax.jit(lambda x: x - 7)(jnp.ones((3,)))
        assert r.counter("compile_backend_compile_total").value >= 1
        assert r.counter("compile_backend_compile_seconds_total").value > 0

    def test_watched_function_flags_recompiling_calls(self):
        f = watched(jax.jit(lambda x: x + 1), "plus1")
        f(jnp.ones((2,)))
        f(jnp.ones((2,)))
        f(jnp.ones((4,)))   # new shape: recompile
        s = f.stats()
        assert s["calls"] == 3 and s["compiles"] == 2
        assert s["compile_calls"] == [1, 3]

    def test_executable_report_flops_memory_collectives(self):
        rep = executable_report(
            lambda a, b: a @ b, jnp.ones((32, 64)), jnp.ones((64, 16))
        )
        assert rep["flops"] == pytest.approx(2 * 32 * 64 * 16, rel=1)
        assert rep["memory"]["output_bytes"] == 32 * 16 * 4
        assert set(rep["collectives"]) == {
            "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute",
        }
        assert sum(rep["collectives"].values()) == 0   # single device

    def test_executable_report_sees_sharded_collectives(self, mesh24, rng):
        from functools import partial

        from learning_jax_sharding_tpu.parallel.collectives import (
            psum_matmul,
        )
        from tests.conftest import matmul_operands

        a, b = matmul_operands(rng)
        rep = executable_report(
            partial(psum_matmul, mesh=mesh24, axis="y"), a, b
        )
        assert rep["collectives"]["all-reduce"] >= 1


class TestEngineTelemetry:
    """The serving engine metered through the registry/tracer: the
    pinned ``last_stats``/``last_latency`` contract is now a window over
    cumulative metrics, and the per-request timeline is exported."""

    @pytest.fixture(scope="class")
    def served(self, mesh22):
        from learning_jax_sharding_tpu.models.serving import (
            ContinuousEngine,
        )
        from learning_jax_sharding_tpu.models.transformer import (
            CONFIG_TINY, Transformer,
        )
        from learning_jax_sharding_tpu.parallel.logical import RULES_DP_TP

        cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
        rng = np.random.default_rng(31)
        model = Transformer(cfg)
        params = nn.meta.unbox(
            jax.jit(lambda r, t: model.init({"params": r}, t))(
                jax.random.key(3), np.zeros((2, 8), np.int32)
            )["params"]
        )
        prompts = [
            rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in (3, 9, 5)
        ]
        eng = ContinuousEngine(
            cfg, mesh22, RULES_DP_TP, batch_size=2, max_new_tokens=4,
            refill_chunk=4,
        )
        outs = eng.serve(params, prompts)
        return eng, prompts, outs

    def test_counters_back_last_stats_window(self, served):
        eng, prompts, outs = served
        snap = eng.registry.snapshot()
        assert snap["engine_requests_total"] == len(prompts)
        assert snap["engine_requests_finished_total"] == len(prompts)
        assert snap["engine_tokens_generated_total"] == sum(
            len(o) - len(p) for o, p in zip(outs, prompts)
        )
        assert snap["engine_cache_creations_total"] == eng.cache_creations
        # The split last_latency reports is the counter-window delta.
        lat = eng.last_latency
        assert lat["refill_s"] == pytest.approx(
            snap["engine_refill_seconds_total"]
        )
        assert lat["decode_s"] == pytest.approx(
            snap["engine_decode_seconds_total"]
        )
        # Same observations landed in the export histograms.
        assert snap["engine_ttft_seconds"]["count"] == len(prompts)
        assert snap["engine_e2e_seconds"]["count"] == len(prompts)
        # README's Prometheus latency series: one sample a retired request
        # (a TPOT needs two tokens); token gaps have no histogram.
        assert snap["engine_queue_wait_seconds"]["count"] == len(prompts)
        assert snap["engine_tpot_seconds"]["count"] == sum(
            len(o) - len(p) > 1 for o, p in zip(outs, prompts)
        )
        assert "engine_itl_seconds" not in snap

    def test_request_timeline_events(self, served):
        eng, prompts, _ = served
        evs = eng.tracer.events
        names = [e["name"] for e in evs]
        for needed in ("request.arrival", "request.admit",
                       "request.first_token", "engine.serve"):
            assert needed in names, needed
        begins = {e["id"] for e in evs
                  if e["ph"] == "b" and e["name"] == "request"}
        ends = {e["id"] for e in evs
                if e["ph"] == "e" and e["name"] == "request"}
        assert begins == ends == set(range(len(prompts)))
        # Dispatch spans carry the host-observed durations.
        assert any(e["name"] == "engine.refill" for e in evs)
        assert any(e["name"] == "engine.decode" for e in evs)

    def test_prometheus_export_has_engine_series(self, served):
        eng, _, _ = served
        text = eng.registry.prometheus_text()
        assert "# TYPE engine_requests_total counter" in text
        assert "# TYPE engine_queue_depth gauge" in text
        assert "# TYPE engine_ttft_seconds histogram" in text

    def test_compile_counts_exposed(self, served):
        eng, _, _ = served
        counts = eng.compile_counts()
        assert set(counts) == {
            "first_refill", "refill_step", "decode_block",
        }
        assert all(v and v <= 2 for v in counts.values()), counts

    def test_window_semantics_across_serves(self, served, mesh22):
        """A second serve() resets the WINDOW, not the counters: the
        cumulative registry keeps growing while last_stats stays
        per-call (the re-derivation contract) — and the warm call
        compiles nothing new."""
        eng, prompts, _ = served
        from learning_jax_sharding_tpu.models.transformer import (
            CONFIG_TINY, Transformer,
        )

        cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
        params = nn.meta.unbox(
            jax.jit(
                lambda r, t: Transformer(cfg).init({"params": r}, t)
            )(jax.random.key(3), np.zeros((2, 8), np.int32))["params"]
        )
        total_before = eng.registry.snapshot()[
            "engine_requests_finished_total"
        ]
        compiles_before = eng.compile_counts()
        eng.serve(params, prompts[:1])
        snap = eng.registry.snapshot()
        assert snap["engine_requests_finished_total"] == total_before + 1
        assert eng.last_latency["requests"] == 1   # window, not lifetime
        assert eng.compile_counts() == compiles_before


class TestTrainingTelemetry:
    def test_metrics_logger_mirrors_into_registry(self):
        from learning_jax_sharding_tpu.utils import MetricsLogger

        r = MetricsRegistry()
        with MetricsLogger(stream=None, tokens_per_step=64,
                           registry=r) as m:
            for s in range(3):
                m.log(s, loss=2.0 - s)
        snap = r.snapshot()
        assert snap["train_steps_total"] == 3
        assert snap["train_loss"] == 0.0           # latest
        assert snap["train_seconds_per_step"] > 0
        assert snap["train_tokens_per_second"] > 0
        assert snap["train_step_seconds"]["count"] == 2


class TestCase18Smoke:
    """CI smoke for the observability driver: run
    cases/case18_observability.py on the emulated 8-device mesh (the
    conftest already forced it — the case's own force is then a no-op)
    and assert the three artifacts parse and carry the expected keys."""

    def test_case18_artifacts(self, tmp_path):
        import pathlib

        repo = pathlib.Path(__file__).resolve().parents[1]
        argv = sys.argv
        path = sys.path[:]
        sys.argv = ["case18_observability.py", str(tmp_path)]
        sys.path.insert(0, str(repo / "cases"))
        try:
            runpy.run_path(
                str(repo / "cases" / "case18_observability.py"),
                run_name="__main__",
            )
        finally:
            sys.argv = argv
            sys.path[:] = path

        trace = json.loads((tmp_path / "trace.json").read_text())
        assert trace["traceEvents"], "empty trace"
        for ev in trace["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)

        prom = (tmp_path / "metrics.prom").read_text()
        assert "# TYPE engine_requests_finished_total counter" in prom
        assert "# TYPE engine_ttft_seconds histogram" in prom
        assert 'engine_ttft_seconds_bucket{le="+Inf"}' in prom

        report = json.loads((tmp_path / "report.json").read_text())
        for key in (
            "ttft_p50", "ttft_p99", "tpot_p50", "page_pool", "compile",
            "collectives_per_step", "requests",
        ):
            assert key in report, key
        assert report["ttft_p50"] > 0
        assert report["page_pool"]["high_water"] >= 1
        assert report["compile"]["per_program_compiles"]["refill_step"]
        decode = report["collectives_per_step"]["decode_block"]
        assert set(decode) == {
            "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute",
        }
        assert sum(decode.values()) > 0    # TP decode puts ops on the wire
