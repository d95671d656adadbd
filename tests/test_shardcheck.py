"""shardcheck static passes: contract diffs, donation verdicts, lints.

Pins the three analysis levels the CI gate stands on:

* the CONTRACT DIFF ENGINE (added/removed/oversized collective,
  while-loop collectives, oversized constants, mesh mismatch) — pure
  logic on synthetic contracts, plus the real-compiler path where a
  deliberately wrong ``in_sharding`` must surface as contract drift;
* the DONATION pass — requested/applied/eligible verdicts read off real
  executables on the emulated-CPU path (this backend APPLIES donation,
  so the exact-alias path is pinned; the parser is additionally pinned
  on synthetic TPU-style multi-entry alias headers, the guarded path);
* the JAXPR lint (f32 promotion in bf16 graphs, dead equations) and the
  AST lint rules with the baseline-suppression budget.
"""

import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from learning_jax_sharding_tpu.analysis.contracts import (
    Contract,
    check_against_golden,
    check_contract,
    contract_of,
)
from learning_jax_sharding_tpu.analysis.donation import (
    aliased_params,
    donation_report,
)
from learning_jax_sharding_tpu.analysis.findings import Finding
from learning_jax_sharding_tpu.analysis.jaxpr_lint import lint_fn
from learning_jax_sharding_tpu.analysis.source_lint import (
    apply_baseline,
    lint_source,
)


def _contract(name="ep", collectives=None, while_c=0, const_b=0):
    return Contract(
        name=name, mesh_shape=[2, 4], mesh_axes=["x", "y"],
        collectives=collectives or {}, while_collectives=while_c,
        max_constant_bytes=const_b,
    )


class TestContractDiffEngine:
    def test_clean_self_diff(self):
        c = _contract(collectives={"all-reduce@y": {"count": 2, "max_bytes": 64}})
        assert check_contract(c, c) == []

    def test_added_collective(self):
        g = _contract(collectives={"all-reduce@y": {"count": 1, "max_bytes": 64}})
        o = _contract(collectives={
            "all-reduce@y": {"count": 1, "max_bytes": 64},
            "all-gather@x": {"count": 2, "max_bytes": 4096},
        })
        rules = [f.rule for f in check_contract(g, o)]
        assert rules == ["added-collective"]

    def test_missing_collective(self):
        g = _contract(collectives={"all-reduce@y": {"count": 2, "max_bytes": 64}})
        o = _contract(collectives={"all-reduce@y": {"count": 1, "max_bytes": 64}})
        [f] = check_contract(g, o)
        assert f.rule == "missing-collective"
        assert "replication" in f.message

    def test_oversized_collective_and_slack(self):
        g = _contract(collectives={"all-gather@y": {"count": 1, "max_bytes": 1000}})
        within = _contract(collectives={"all-gather@y": {"count": 1, "max_bytes": 1200}})
        past = _contract(collectives={"all-gather@y": {"count": 1, "max_bytes": 1300}})
        assert check_contract(g, within) == []          # inside 1.25x slack
        [f] = check_contract(g, past)
        assert f.rule == "oversized-collective"
        assert check_contract(g, past, byte_slack=2.0) == []

    def test_while_loop_collective(self):
        g = _contract(while_c=0)
        o = _contract(while_c=3)
        [f] = check_contract(g, o)
        assert f.rule == "while-loop-collective"
        assert "trip count" in f.message

    def test_oversized_constant(self):
        g = _contract(const_b=0)
        o = _contract(const_b=512 * 1024)
        [f] = check_contract(g, o)
        assert f.rule == "oversized-constant"

    def test_mesh_mismatch_short_circuits(self):
        g = _contract()
        o = Contract(
            name="ep", mesh_shape=[4, 2], mesh_axes=["x", "y"],
            collectives={"all-reduce@y": {"count": 9, "max_bytes": 1}},
            while_collectives=9, max_constant_bytes=1 << 30,
        )
        [f] = check_contract(g, o)
        assert f.rule == "mesh-mismatch"

    def test_json_round_trip(self):
        c = _contract(collectives={"all-to-all@x": {"count": 2, "max_bytes": 128}})
        assert Contract.from_json(c.to_json()) == c

    def test_missing_golden_is_a_finding(self, tmp_path):
        [f] = check_against_golden(tmp_path, _contract(name="unknown_ep"))
        assert f.rule == "no-golden"

    def test_golden_file_round_trip(self, tmp_path):
        c = _contract(collectives={"all-reduce@y": {"count": 1, "max_bytes": 64}})
        (tmp_path / "ep.json").write_text(c.to_json())
        assert check_against_golden(tmp_path, c) == []


class TestContractsOnRealCompiler:
    """contract_of against the real partitioner on the emulated mesh."""

    def test_psum_matmul_contract_records_its_all_reduce(self, mesh24, rng):
        from functools import partial

        from learning_jax_sharding_tpu.parallel.collectives import psum_matmul
        from tests.conftest import matmul_operands

        a, b = matmul_operands(rng)
        fn = partial(psum_matmul, mesh=mesh24, axis="y")
        good = contract_of("psum_matmul", fn, a, b, mesh=mesh24)
        assert good.collectives.get("all-reduce@y", {}).get("count", 0) >= 1

    def test_wrong_in_sharding_is_contract_drift(self, mesh24, tmp_path):
        """The seeded violation class of case20: a column-parallel matmul
        (weight sharded on its OUTPUT dim — zero comms) goldened, then
        recompiled with the weight row-sharded: GSPMD must now insert
        communication, and the check must name it rather than pass."""

        def mm(x, w):
            return x @ w

        x = np.ones((8, 16), np.float32)
        w = np.ones((16, 32), np.float32)
        out_sh = NamedSharding(mesh24, P(None, "y"))
        f = jax.jit(mm, out_shardings=out_sh)
        x_rep = jax.device_put(x, NamedSharding(mesh24, P()))
        w_col = jax.device_put(w, NamedSharding(mesh24, P(None, "y")))
        good = contract_of("mm", f, x_rep, w_col, mesh=mesh24)
        assert good.collectives == {}  # column-parallel: comms-free
        (tmp_path / "mm.json").write_text(good.to_json())
        assert check_against_golden(tmp_path, good) == []

        w_row = jax.device_put(w, NamedSharding(mesh24, P("y", None)))
        bad = contract_of("mm", f, x_rep, w_row, mesh=mesh24)
        findings = check_against_golden(tmp_path, bad)
        assert findings, "wrong in_sharding compiled to the SAME collectives"
        assert all(f.rule == "added-collective" for f in findings)

    def test_enforce_contract_raises_and_reports(self, mesh24, tmp_path):
        """The fail-loudly path fit(contract=) rides: drift raises
        ShardingContractError AND lands in the recorder first."""
        from learning_jax_sharding_tpu.analysis.contracts import (
            ShardingContractError,
            enforce_contract,
        )
        from learning_jax_sharding_tpu.telemetry.flight_recorder import (
            FlightRecorder,
        )

        def mm(x, w):
            return x @ w

        x = np.ones((8, 16), np.float32)
        w = np.ones((16, 32), np.float32)
        out_sh = NamedSharding(mesh24, P(None, "y"))
        f = jax.jit(mm, out_shardings=out_sh)
        x_rep = jax.device_put(x, NamedSharding(mesh24, P()))
        w_col = jax.device_put(w, NamedSharding(mesh24, P(None, "y")))
        golden = contract_of("mm", f, x_rep, w_col, mesh=mesh24)
        (tmp_path / "mm.json").write_text(golden.to_json())

        # Clean compile under the golden: passes, returns the observed.
        obs = enforce_contract(
            tmp_path, f, x_rep, w_col, mesh=mesh24, name="mm"
        )
        assert obs.collectives == golden.collectives

        rec = FlightRecorder()
        w_row = jax.device_put(w, NamedSharding(mesh24, P("y", None)))
        with pytest.raises(ShardingContractError) as ei:
            enforce_contract(
                tmp_path, f, x_rep, w_row, mesh=mesh24, name="mm",
                recorder=rec,
            )
        assert ei.value.findings
        assert rec.events("shardcheck_finding")  # recorded before raising

    def test_scan_collective_lands_in_while(self, mesh24):
        def scanned(x):
            def body(c, _):
                return jax.lax.psum(c, "y"), None

            r, _ = jax.lax.scan(body, x, None, length=4)
            return r

        f = jax.shard_map(
            scanned, mesh=mesh24, in_specs=P(None, "y"),
            out_specs=P(None, "y"), check_vma=False,
        )
        x = jax.device_put(
            np.ones((4, 16), np.float32), NamedSharding(mesh24, P(None, "y"))
        )
        c = contract_of("scanned", f, x, mesh=mesh24)
        assert c.while_collectives >= 1
        assert check_contract(c, c) == []  # a golden ADMITTING it passes


class TestDonationPass:
    def test_applied_donation_verdict(self):
        f = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
        r = donation_report(f, jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert [i["verdict"] for i in r["inputs"]] == ["donated", "ok"]
        assert r["backend_applied_any"]
        assert r["findings"] == []

    def test_requested_but_not_applied(self):
        # No output matches the donated buffer: the request drops and the
        # pass must say so (same verdict a donation-less backend yields —
        # the guarded path shares this code).
        f = jax.jit(lambda s, x: jnp.sum(s + x), donate_argnums=(0,))
        with pytest.warns(UserWarning, match="donated"):
            r = donation_report(f, jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert [i["verdict"] for i in r["inputs"]] == ["not_applied", "ok"]
        assert [f.rule for f in r["findings"]] == ["donation-not-applied"]

    def test_eligible_never_requested(self):
        f = jax.jit(lambda s, x: s + x)
        r = donation_report(f, jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert r["inputs"][0]["verdict"] == "eligible"
        assert [f.rule for f in r["findings"]] == ["donation-missed"]

    def test_alias_header_parser_multi_entry(self):
        # TPU-style header: tuple outputs, several aliased params — the
        # textual path the compiled-HLO parse must survive unchanged.
        hlo = (
            "HloModule jit_step, is_scheduled=true, input_output_alias="
            "{ {0}: (1, {}, may-alias), {2}: (3, {}, must-alias) }, "
            "entry_computation_layout={(f32[8]{0})->f32[8]{0}}"
        )
        assert aliased_params(hlo) == {1, 3}
        assert aliased_params("HloModule jit_f, is_scheduled=true") == set()

    def test_train_step_donation_is_applied(self, mesh24, rng):
        """The framework's own train step donates its state and the
        backend applies it — the clean-repo verdict the jaxpr pass rests
        on (and the cross-check that a donate_state=False step is caught
        lives in cases/case20_shardcheck.py, where the full pipeline is
        already built)."""
        import optax

        from learning_jax_sharding_tpu.analysis.donation import (
            missed_donation_bytes,
        )
        from learning_jax_sharding_tpu.models.transformer import CONFIG_TINY

        # missed_donation_bytes: closed-form planner delta, no compile.
        at_stake = missed_donation_bytes(CONFIG_TINY, 8, 32)
        assert at_stake > 0
        # Donation on a state-shaped pytree: every floating leaf of the
        # (params, opt) input aliases an output when donated.
        params = {"w": jnp.ones((16, 16)), "b": jnp.ones((16,))}
        opt = optax.adam(1e-3)
        opt_state = opt.init(params)

        def step(params, opt_state, g):
            updates, opt_state = opt.update(g, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        jitted = jax.jit(step, donate_argnums=(0, 1))
        r = donation_report(jitted, params, opt_state, params)
        donated = [i for i in r["inputs"] if i["donated"]]
        assert donated and all(i["verdict"] == "donated" for i in donated)


    def test_a_pruned_argument_does_not_shift_the_alias_numbers(self):
        """``jax.jit`` drops an argument its jaxpr never reads, so the
        compiled module's parameter numbers run over the KEPT ones: the
        donated state behind an unread argument is still ``donated``."""
        f = jax.jit(lambda unread, s, x: s + x, donate_argnums=(1,))
        r = donation_report(f, jnp.ones((3,)), jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert [i["verdict"] for i in r["inputs"]] == ["ok", "donated", "ok"]
        assert r["aliased_params"] == [1] and r["findings"] == []

    @pytest.mark.parametrize("names", [
        ("prefill", "decode_step"), ("spec_prefill", "spec_decode_step"),
        ("kv_ingest",), ("kv_page_fill",),
    ])
    def test_the_audit_runs_over_the_engine_programs(self, names):
        """The serving programs are under the donation audit (PR 36: they
        donate their cache): every leaf a program asks to donate is
        aliased on the 2x4 mesh, the pass is clean under the baseline's
        ``donation_budgets``, and without the baseline the only findings
        are the few-byte operands those budgets name."""
        from learning_jax_sharding_tpu.analysis import run_jaxpr_pass
        from learning_jax_sharding_tpu.analysis.entrypoints import (
            build_entry_programs,
        )

        programs = build_entry_programs(list(names))
        assert [p.name for p in programs] == list(names)
        for prog in programs:
            asked = [i for i in prog.donation()["inputs"] if i["donated"]]
            assert asked and all(i["verdict"] == "donated" for i in asked)
        assert run_jaxpr_pass(programs=programs) == []
        loose = run_jaxpr_pass(programs=programs, baseline=None)
        assert {f.rule for f in loose} <= {"donation-missed"}
        assert all(f.data["device_bytes"] <= 32 for f in loose)


class TestJaxprLint:
    def test_f32_promotion_in_bf16_graph(self):
        def f(x):
            h = x * 2
            return jnp.sum(h.astype(jnp.float32))

        rules = [f.rule for f in lint_fn(f, jnp.ones((8, 8), jnp.bfloat16))]
        assert "f32-promotion" in rules

    def test_clean_bf16_graph_no_promotion_finding(self):
        def f(x):
            return x * 2 + x

        fs = lint_fn(f, jnp.ones((8, 8), jnp.bfloat16))
        assert [x for x in fs if x.rule == "f32-promotion"] == []

    def test_f32_graph_promotions_are_fine(self):
        # Majority-f32 graph: converting up is not a drift.
        def f(x):
            return jnp.sum(x.astype(jnp.float32))

        assert lint_fn(f, jnp.ones((8, 8), jnp.float32)) == []

    def test_f32_dot_in_bf16_graph(self):
        def f(x, w):
            return (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(
                jnp.bfloat16
            )

        rules = [
            f.rule
            for f in lint_fn(
                f, jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.bfloat16)
            )
        ]
        assert "f32-dot-in-bf16-graph" in rules

    def test_dead_eqn(self):
        def f(x):
            _wasted = jnp.sum(x * 3)  # noqa: F841 — traced, never returned
            return x + 1

        rules = [x.rule for x in lint_fn(f, jnp.ones(4))]
        assert "dead-eqn" in rules

    def test_live_graph_has_no_dead_eqns(self):
        def f(x):
            return jnp.sum(x * 3) + jnp.prod(x)

        assert [x for x in lint_fn(f, jnp.ones(4)) if x.rule == "dead-eqn"] == []


class TestSourceLint:
    def _rules(self, src):
        return [f.rule for f in lint_source("mod.py", textwrap.dedent(src))]

    def test_jit_in_loop(self):
        src = """
        import jax
        for cfg in configs:
            step = jax.jit(make_step(cfg))
        """
        assert self._rules(src) == ["jit-in-loop"]

    def test_partial_jit_in_loop(self):
        src = """
        import jax
        from functools import partial
        while work:
            f = partial(jax.jit, static_argnames=("n",))(g)
        """
        assert self._rules(src) == ["jit-in-loop"]

    def test_jit_outside_loop_clean(self):
        src = """
        import jax
        step = jax.jit(make_step(cfg))
        for batch in data:
            step(batch)
        """
        assert self._rules(src) == []

    def test_nonhashable_static_default(self):
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("dims",))
        def f(x, dims=[1, 2]):
            return x
        """
        assert "nonhashable-static" in self._rules(src)

    def test_hashable_static_default_clean(self):
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("dims",))
        def f(x, dims=(1, 2)):
            return x
        """
        assert self._rules(src) == []

    def test_captured_device_array(self):
        src = """
        import jax
        import jax.numpy as jnp

        TABLE = jnp.arange(1024)

        @jax.jit
        def lookup(i):
            return TABLE[i]
        """
        assert "captured-device-array" in self._rules(src)

    def test_function_local_array_does_not_poison_globals(self):
        # A function-LOCAL `table = jnp...` must not mark the name, or an
        # unrelated global `table` read by a jitted fn false-positives.
        src = """
        import jax
        import jax.numpy as jnp

        def helper():
            table = jnp.arange(10)
            return table

        table = load_table_from_disk()

        @jax.jit
        def fn(x):
            return x + table
        """
        assert self._rules(src) == []

    def test_shadowing_binding_forms_are_locals_not_captures(self):
        # for-targets, tuple unpacking, and with-as all BIND the name —
        # shadowing the module-level array, not capturing it.
        src = """
        import jax
        import jax.numpy as jnp

        table = jnp.zeros((4,))

        @jax.jit
        def a(y):
            for table in (y, y):
                y = y + table
            return y

        @jax.jit
        def b(y):
            table, other = y, y
            return table + other

        @jax.jit
        def c(y):
            with open("f") as table:
                pass
            return y
        """
        assert self._rules(src) == []

    def test_argument_passing_is_clean(self):
        src = """
        import jax
        import jax.numpy as jnp

        TABLE = jnp.arange(1024)

        @jax.jit
        def lookup(table, i):
            return table[i]

        lookup(TABLE, 3)
        """
        assert self._rules(src) == []

    def test_bare_except_flags(self):
        src = """
        try:
            f()
        except:
            x = 1
        """
        assert self._rules(src) == ["swallowed-exception"]

    def test_bare_except_with_reraise_clean(self):
        src = """
        try:
            f()
        except:
            cleanup()
            raise
        """
        assert self._rules(src) == []

    def test_broad_except_pass_flags(self):
        src = """
        try:
            f()
        except Exception:
            pass
        """
        assert self._rules(src) == ["swallowed-exception"]

    def test_broad_except_in_tuple_pass_flags(self):
        src = """
        try:
            f()
        except (ValueError, Exception):
            ...
        """
        assert self._rules(src) == ["swallowed-exception"]

    def test_broad_except_with_handling_clean(self):
        # Recording the failure IS handling — the rule only hunts
        # failures that leave no trace.
        src = """
        try:
            f()
        except Exception as e:
            recorder.record("fault", error=str(e))
        """
        assert self._rules(src) == []

    def test_narrow_except_pass_clean(self):
        # A narrow `except KeyError: pass` is a deliberate, bounded
        # decision — only the broad catches gate.
        src = """
        try:
            f()
        except KeyError:
            pass
        """
        assert self._rules(src) == []

    def test_raw_clock_without_sync(self):
        src = """
        import time
        t0 = time.perf_counter()
        y = f(x)
        dt = time.perf_counter() - t0
        """
        assert self._rules(src) == ["raw-clock", "raw-clock"]

    def test_raw_clock_with_nearby_sync_clean(self):
        src = """
        import time
        t0 = time.perf_counter()
        y = f(x)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        """
        assert self._rules(src) == []

    def test_host_sync_in_engine_loop_flags(self):
        src = """
        import numpy as np

        class ContinuousEngine:
            def step(self, params):
                for slot in self.slots:
                    tok = np.asarray(self.buf[slot])
                    n = self.counts[slot].item()
                    self.out[slot].block_until_ready()
        """
        # Each sync draws BOTH rules: it sits in a loop (hot-loop rule)
        # and in an untimed engine phase (ledger-coverage rule).
        assert sorted(self._rules(src)) == (
            ["host-sync-in-hot-loop"] * 3 + ["untimed-engine-phase"] * 3
        )

    def test_host_sync_outside_loop_clean(self):
        # The engine's single designed sync point per dispatch — after
        # the loop, inside a ledger frame — is the pattern both rules
        # steer toward.
        src = """
        import numpy as np

        class ContinuousEngine:
            def step(self, params):
                with self._led_device(self._decode_fn):
                    tok = np.asarray(self.dispatch(params))
                for slot in self.slots:
                    self.retire(slot, tok[slot])
        """
        assert self._rules(src) == []

    def test_host_sync_outside_engine_class_clean(self):
        # Loops elsewhere legitimately read results back (bench timing,
        # data loading) — only the serving hot path gates.
        src = """
        import numpy as np

        def drain(streams):
            for s in streams:
                yield np.asarray(s)

        class ShardedBatchLoader:
            def batches(self):
                for b in self.source:
                    yield np.asarray(b)
        """
        assert self._rules(src) == []

    def test_jax_device_get_in_engine_loop_flags(self):
        src = """
        import jax

        class SpecEngine:
            def _drain(self):
                while self.has_work():
                    stats = jax.device_get(self.counters)
        """
        assert self._rules(src) == ["host-sync-in-hot-loop"]

    def test_untimed_engine_phase_flags_the_three_escape_hatches(self):
        # The ledger's 100%-accounting invariant (round 14) dies the
        # moment real work runs outside a frame. The rule names the
        # three ways seconds escape a phase method: a compiled dispatch,
        # a chaos seam, and a host sync.
        src = """
        import numpy as np

        class ContinuousEngine:
            def step(self, params):
                chaos_hook("engine.dispatch", phase="decode")
                out = self._decode_fn(params, self.state)
                return np.asarray(out)
        """
        assert self._rules(src) == ["untimed-engine-phase"] * 3

    def test_untimed_engine_phase_silent_inside_ledger_frames(self):
        # The same three calls, each under a frame (`measure(...)` or
        # the `_led_device` dispatch helper): every second lands in a
        # bucket, nothing to flag.
        src = """
        import numpy as np

        class ContinuousEngine:
            def step(self, params):
                with self.ledger.measure("recovery"):
                    chaos_hook("engine.dispatch", phase="decode")
                with self._led_device(self._decode_fn):
                    out = self._decode_fn(params, self.state)
                with self.ledger.measure("sched"):
                    return np.asarray(out)
        """
        assert self._rules(src) == []

    def test_untimed_engine_phase_only_gates_phase_methods(self):
        # Helpers and non-Engine classes dispatch freely — only the
        # named phases (step/_admit/..._dispatch) carry the ledger
        # contract, and only on classes matching `Engine`.
        src = """
        import numpy as np

        class ContinuousEngine:
            def debug_dump(self):
                return np.asarray(self._decode_fn(self.state))

        class FleetRouter:
            def step(self):
                return self._route_fn(self.pending)
        """
        assert self._rules(src) == []

    def test_unbounded_host_buffer_direct_device_append_flags(self):
        # The host-side KV leak: one device array retained per loop
        # iteration, container never evicted anywhere in the method.
        src = """
        import jax.numpy as jnp

        class ContinuousEngine:
            def _admit(self, req):
                for tok in req.tokens:
                    self._trace.append(jnp.asarray(tok))
        """
        assert self._rules(src) == ["unbounded-host-buffer"]

    def test_unbounded_host_buffer_via_local_name_flags(self):
        # The device value travels through a local binding — the rule
        # tracks names assigned from jnp./jax.random. makers.
        src = """
        import jax.numpy as jnp

        class SpecEngine:
            def step(self):
                while self.has_work():
                    logits = jnp.zeros((8, 1024))
                    self._history.append(logits)
        """
        assert self._rules(src) == ["unbounded-host-buffer"]

    def test_unbounded_host_buffer_evicted_container_clean(self):
        # Any eviction of the SAME container in scope bounds it: a
        # pop on a schedule, a del, or a rebinding trim.
        src = """
        import jax.numpy as jnp

        class ContinuousEngine:
            def step(self):
                for tok in self.stream:
                    self._trace.append(jnp.asarray(tok))
                    if len(self._trace) > 64:
                        self._trace.pop(0)

            def _admit(self, req):
                for tok in req.tokens:
                    self._window.append(jnp.asarray(tok))
                self._window = self._window[-64:]
        """
        assert self._rules(src) == []

    def test_unbounded_host_buffer_host_value_or_cold_path_clean(self):
        # Appending a host value retains no device buffer; appends
        # outside a loop or outside an *Engine class are one-shot /
        # not the serving hot path.
        src = """
        import jax.numpy as jnp

        class ContinuousEngine:
            def step(self):
                for tok in self.stream:
                    self._ids.append(tok)
                self._snapshot.append(jnp.zeros((4,)))

        class TraceRecorder:
            def record(self):
                for tok in self.stream:
                    self._trace.append(jnp.asarray(tok))
        """
        assert self._rules(src) == []

    def test_baseline_budget(self):
        fs = [
            Finding("ast", "raw-clock", "a.py:10", "m"),
            Finding("ast", "raw-clock", "a.py:20", "m"),
            Finding("ast", "jit-in-loop", "a.py:30", "m"),
        ]
        budget = {("a.py", "raw-clock"): 2}
        left = apply_baseline(fs, budget)
        assert [f.rule for f in left] == ["jit-in-loop"]
        # One NEW raw-clock past the budget gates again.
        fs.append(Finding("ast", "raw-clock", "a.py:40", "m"))
        left = apply_baseline(fs, budget)
        assert sorted(f.rule for f in left) == ["jit-in-loop", "raw-clock"]


class TestCheckedInGoldens:
    """The shipped goldens: present for the key entry points, parseable,
    and structurally sane — without paying entry-point compiles here
    (cases/case20_shardcheck.py runs the full loop)."""

    REQUIRED = (
        "train_step", "train_step_gn", "train_step_skip",
        "zero1_update", "zero1_update_q8", "prefill",
        "decode_step", "mixed_step",
        "spec_prefill", "spec_decode_step", "spec_mixed_step",
        "adapter_mixed_step", "spec_adapter_mixed_step",
        "kv_export", "kv_ingest", "kv_page_spill", "kv_page_fill",
        "swap_reshard", "swap_reshard_quant",
        "moe_dispatch", "ring_attention", "ulysses_attention",
    )

    def test_goldens_exist_and_parse(self):
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR

        for name in self.REQUIRED:
            c = Contract.load(GOLDEN_DIR / f"{name}.json")
            assert c.name == name
            assert c.mesh_shape and c.mesh_axes

    def test_goldens_and_entry_points_are_a_bijection(self):
        """Round-13 coverage audit: every entry point has a golden AND
        every golden names a live entry point — an orphaned golden (its
        program renamed or deleted) previously passed silently, pinning
        nothing. ``bench_headline.json`` is exempt: it is bench.py's
        collective contract, not an entry-point golden. Building the
        entry-point list is lazy (no compiles), so this stays cheap."""
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR
        from learning_jax_sharding_tpu.analysis.entrypoints import (
            build_entry_programs,
        )

        entry_names = {p.name for p in build_entry_programs()}
        golden_names = {
            f.stem for f in GOLDEN_DIR.glob("*.json")
        } - {"bench_headline"}
        missing = entry_names - golden_names
        assert not missing, (
            f"entry points without goldens (run scripts/shardcheck.py "
            f"--update-golden): {sorted(missing)}"
        )
        orphaned = golden_names - entry_names
        assert not orphaned, (
            f"goldens naming no live entry point (stale — delete or "
            f"re-wire): {sorted(orphaned)}"
        )

    def test_searchable_entries_are_live_entry_points(self):
        """Round-17 audit extension: every entry the layout search can
        target (``SEARCHABLE_ENTRIES``) must name a live entry-point
        program AND a checked-in golden — a search advisory against a
        renamed entry would otherwise point at nothing, and its emitted
        contract could never be diffed against the golden it claims to
        improve on."""
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR
        from learning_jax_sharding_tpu.analysis.entrypoints import (
            SEARCHABLE_ENTRIES,
            build_entry_programs,
        )

        entry_names = {p.name for p in build_entry_programs()}
        golden_names = {f.stem for f in GOLDEN_DIR.glob("*.json")}
        searchable = set(SEARCHABLE_ENTRIES)
        assert searchable <= entry_names, (
            f"searchable entries naming no live entry point: "
            f"{sorted(searchable - entry_names)}"
        )
        assert searchable <= golden_names, (
            f"searchable entries without a golden to diff against: "
            f"{sorted(searchable - golden_names)}"
        )
        # The search's contract emitter must preserve the entry name so
        # the emitted file is comparable against the golden of the same
        # entry (byte-format parity is pinned in test_layout_search).
        golden = Contract.load(GOLDEN_DIR / "train_step.json")
        assert golden.name == "train_step"

    def test_goldens_record_real_communication(self):
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR

        # The sharded entry points must not have recorded vacuous
        # (replicated, no-comms) contracts: each of these programs
        # provably communicates on its mesh.
        for name in ("train_step", "zero1_update", "zero1_update_q8",
                     "prefill", "decode_step", "mixed_step",
                     "spec_mixed_step", "adapter_mixed_step",
                     "spec_adapter_mixed_step", "moe_dispatch"):
            c = Contract.load(GOLDEN_DIR / f"{name}.json")
            assert c.collectives, f"{name} golden records no collectives"

    def test_kv_handoff_goldens_pin_zero_collectives(self):
        """The round-11 disaggregated-handoff claim, as checked-in
        contract: BOTH device-side programs of the KV handoff (the
        export gather, the ingest update) compile to ZERO collectives —
        every cross-replica byte rides the explicit, counted
        fleet/kv_transfer plan, never a hidden XLA reshard. The round-15
        tier ladder's page programs (the spill gather, the fill update)
        carry the same claim for the HBM↔host rungs: migration bytes
        live in the counted ``HostBuffer`` plans only."""
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR

        for name in (
            "kv_export", "kv_ingest", "kv_page_spill", "kv_page_fill",
        ):
            c = Contract.load(GOLDEN_DIR / f"{name}.json")
            assert c.collectives == {}, (name, c.collectives)
            assert c.while_collectives == 0

    def test_swap_reshard_goldens_pin_pure_data_movement(self):
        """The round-12 hot-swap staging claim, as checked-in contract:
        resharding an FSDP-layout checkpoint into the serving layout
        MOVES weights (the goldens record real collectives — a vacuous
        no-comms contract would mean the source layout silently matched
        serving and the program pins nothing), but never COMBINES them —
        an all-reduce appearing here would mean XLA is summing shards,
        arithmetic that could perturb the swapped weights."""
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR

        for name in ("swap_reshard", "swap_reshard_quant"):
            c = Contract.load(GOLDEN_DIR / f"{name}.json")
            assert c.collectives, f"{name} golden records no collectives"
            assert not any(
                k.startswith("all-reduce") for k in c.collectives
            ), (name, c.collectives)
            assert c.while_collectives == 0

    def test_q8_golden_records_the_ring(self):
        """The quantized grad-sync golden must pin the int8 ring's
        collective-permutes — the whole point of its contract: a silent
        fall-back to the fp32 all-reduce would show up as these ops
        vanishing."""
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR

        c = Contract.load(GOLDEN_DIR / "zero1_update_q8.json")
        assert any(
            k.startswith("collective-permute") for k in c.collectives
        ), c.collectives
        assert c.while_collectives >= 1   # the ring hops ride fori_loops

    def test_ring_golden_admits_while_collectives(self):
        from learning_jax_sharding_tpu.analysis import GOLDEN_DIR

        c = Contract.load(GOLDEN_DIR / "ring_attention.json")
        assert c.while_collectives >= 1  # the ring rotation is a scan


class TestFindingsWiring:
    def test_report_findings_lands_in_recorder_and_registry(self):
        from learning_jax_sharding_tpu.analysis.findings import (
            report_findings,
        )
        from learning_jax_sharding_tpu.telemetry import MetricsRegistry
        from learning_jax_sharding_tpu.telemetry.flight_recorder import (
            FlightRecorder,
        )

        rec = FlightRecorder()
        reg = MetricsRegistry()
        fs = [Finding("ast", "jit-in-loop", "a.py:1", "m")] * 2
        report_findings(fs, recorder=rec, registry=reg)
        assert len(rec.events("shardcheck_finding")) == 2
        snap = reg.snapshot()
        [(name, value)] = [
            (k, v) for k, v in snap.items() if k.startswith("shardcheck_")
        ]
        assert "jit_in_loop" in name
        assert value == 2
