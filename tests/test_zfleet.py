"""Fleet serving (round 11): router, disaggregated handoff, failover.

Named to sort LAST in the suite alongside ``test_zero_downtime`` (same
rationale as that file): the end-to-end oracles build several engine
replicas each, and the tier-1 window should spend its budget on the
faster oracles first.

Four layers, cheapest first:

* the KV TRANSFER PLAN as pure redistribution algebra — cross-mesh
  reshard round-trips, page streaming, valid-length clipping (no
  engines, milliseconds after device bring-up);
* the LABELED registry merge + snapshot Prometheus renderer (pure
  dicts);
* ROUTER POLICY — placement under burn-rate skew, fleet-level shedding
  above the replicas' own bounds;
* the END-TO-END oracles: a disaggregated 2-prefill + 2-decode fleet on
  (1,2) sub-meshes of the emulated 8-device mesh produces token streams
  BIT-IDENTICAL to a single engine of the same mesh shape — greedy AND
  sampled — and a replica kill mid-stream reroutes its work (visible as
  ``rerouted``) to survivors that recompute it bit-identically.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from learning_jax_sharding_tpu.fleet import (
    FleetPolicy,
    FleetRouter,
    execute_transfer,
    make_replicas,
    plan_transfer,
    replicated_params,
    sub_meshes,
    transfer_tree,
)
from learning_jax_sharding_tpu.models.serving import (
    AdmissionError,
    ContinuousEngine,
    RequestFailure,
)
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
from learning_jax_sharding_tpu.telemetry.flight_recorder import FlightRecorder


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(CONFIG_TINY, dtype=jnp.float32)
    model = Transformer(cfg)
    params = nn.meta.unbox(
        jax.jit(lambda r, t: model.init({"params": r}, t))(
            jax.random.key(3), np.zeros((2, 8), np.int32)
        )["params"]
    )
    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in (5, 9, 4, 7)
    ]
    return cfg, params, prompts


def _baseline(cfg, params, prompts, *, temperature=0.0, rng=None):
    """The single-engine oracle on a (1,2) sub-mesh — the SAME mesh
    shape every fleet replica uses, so programs (and ulps) match."""
    mesh = build_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2])
    eng = ContinuousEngine(
        cfg, mesh, RULES_DP_TP, batch_size=2, max_new_tokens=4,
        refill_chunk=8, temperature=temperature,
    )
    return eng.serve(replicated_params(params, mesh), prompts, rng=rng)


class TestTransferPlan:
    def test_cross_mesh_reshard_round_trips(self):
        devs = jax.devices()
        m_a = build_mesh((1, 2), ("data", "model"), devices=devs[:2])
        m_b = build_mesh((1, 4), ("data", "model"), devices=devs[4:])
        x = np.arange(16 * 4 * 8, dtype=np.float32).reshape(16, 4, 8)
        xa = jax.device_put(x, NamedSharding(m_a, P(None, "model", None)))
        dst = NamedSharding(m_b, P(None, None, "model"))
        plan = plan_transfer(
            x.shape, 4, xa.sharding, dst, seq_dim=0, page_tokens=8,
        )
        out, stats = execute_transfer(plan, xa)
        np.testing.assert_array_equal(np.asarray(out), x)
        assert out.sharding == dst
        # Every element crossed exactly once: full-row volume.
        assert stats["bytes"] == x.nbytes == plan.bytes_total
        # ... and came back bit-identically through the reverse plan.
        back, _ = execute_transfer(
            plan_transfer(
                x.shape, 4, out.sharding,
                NamedSharding(m_a, P(None, "model", None)), seq_dim=0,
            ),
            out,
        )
        np.testing.assert_array_equal(np.asarray(back), x)

    def test_stop_clips_pages_and_counts_less(self):
        devs = jax.devices()
        m_a = build_mesh((1, 2), ("data", "model"), devices=devs[:2])
        m_b = build_mesh((1, 2), ("data", "model"), devices=devs[2:4])
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        xa = jax.device_put(x, NamedSharding(m_a, P(None, "model")))
        plan = plan_transfer(
            x.shape, 4, xa.sharding, NamedSharding(m_b, P(None, "model")),
            seq_dim=0, page_tokens=4,
        )
        out, stats = execute_transfer(plan, xa, stop=5)
        got = np.asarray(out)
        np.testing.assert_array_equal(got[:5], x[:5])
        # Pages past the straddling one never crossed; their region is 0.
        assert np.all(got[8:] == 0)
        assert stats["segments_skipped"] > 0
        assert stats["bytes"] < x.nbytes

    def test_replication_is_priced_per_destination_copy(self):
        devs = jax.devices()
        m_a = build_mesh((1, 2), ("data", "model"), devices=devs[:2])
        m_b = build_mesh((1, 2), ("data", "model"), devices=devs[2:4])
        x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        xa = jax.device_put(x, NamedSharding(m_a, P(None, "model")))
        # Sharded → fully REPLICATED: each of the two destination
        # devices needs the whole array — twice the wire bytes.
        plan = plan_transfer(
            x.shape, 4, xa.sharding, NamedSharding(m_b, P()), seq_dim=0,
        )
        out, stats = execute_transfer(plan, xa)
        np.testing.assert_array_equal(np.asarray(out), x)
        assert stats["bytes"] == 2 * x.nbytes

    def test_transfer_tree_handles_scalars_and_caches_plans(self):
        devs = jax.devices()
        m_a = build_mesh((1, 2), ("data", "model"), devices=devs[:2])
        m_b = build_mesh((1, 2), ("data", "model"), devices=devs[2:4])
        x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        tree = {
            "k": jax.device_put(x, NamedSharding(m_a, P(None, "model"))),
            "idx": jax.device_put(
                jnp.int32(7), NamedSharding(m_a, P())
            ),
        }
        dst = {
            "k": NamedSharding(m_b, P(None, "model")),
            "idx": NamedSharding(m_b, P()),
        }
        cache: dict = {}
        out, stats = transfer_tree(tree, dst, stop=8, plan_cache=cache)
        assert int(out["idx"]) == 7
        np.testing.assert_array_equal(np.asarray(out["k"]), x)
        n_plans = len(cache)
        out2, _ = transfer_tree(tree, dst, stop=8, plan_cache=cache)
        assert len(cache) == n_plans   # replayed, not re-planned
        np.testing.assert_array_equal(np.asarray(out2["k"]), x)


class TestLabeledMerge:
    SNAPS = [
        {"c_total": 3.0, "g": 2.0, "g__high_water": 5.0,
         "h": {"buckets": [1.0], "counts": [1, 2], "sum": 0.5, "count": 2}},
        {"c_total": 4.0, "g": 1.0, "g__high_water": 7.0,
         "h": {"buckets": [1.0], "counts": [0, 1], "sum": 2.0, "count": 1}},
    ]

    def test_unlabeled_path_bit_compatible(self):
        merged = merge_registry_snapshots(self.SNAPS)
        labeled = merge_registry_snapshots(
            self.SNAPS, labels=["a", "b"]
        )
        for k, v in merged.items():
            assert labeled[k] == v     # the sums are untouched
        assert merged["c_total"] == 7.0
        assert merged["g__high_water"] == 7.0
        assert merged["h"]["counts"] == [1, 3]

    def test_labels_add_per_source_series(self):
        labeled = merge_registry_snapshots(self.SNAPS, labels=["a", "b"])
        assert labeled['c_total{replica="a"}'] == 3.0
        assert labeled['c_total{replica="b"}'] == 4.0
        assert labeled['h{replica="b"}']["count"] == 1
        # Labeled histograms are COPIES: mutating the merge must not
        # reach back into the source snapshot.
        labeled['h{replica="a"}']["counts"][0] = 99
        assert self.SNAPS[0]["h"]["counts"][0] == 1

    def test_label_count_mismatch_raises(self):
        with pytest.raises(ValueError, match="labels"):
            merge_registry_snapshots(self.SNAPS, labels=["only-one"])

    def test_prometheus_renderer_carries_labels(self):
        from learning_jax_sharding_tpu.telemetry.registry import (
            snapshot_prometheus_text,
        )

        text = snapshot_prometheus_text(
            merge_registry_snapshots(self.SNAPS, labels=["a", "b"])
        )
        assert 'c_total{replica="a"} 3' in text
        assert "c_total 7" in text
        assert 'h_bucket{replica="b",le="1"} 0' in text
        assert 'h_bucket{le="+Inf"} 3' in text
        assert "g_high_water 7" in text


class TestRouterPolicy:
    def _fleet(self, served, n=2, *, slos=False, **kw):
        from learning_jax_sharding_tpu.telemetry.slo import (
            SLOMonitor,
            SLOTarget,
        )

        cfg, params, _ = served
        reps = make_replicas(
            cfg, RULES_DP_TP, params, count=n, mesh_shape=(1, 1),
            batch_size=2, max_new_tokens=4, refill_chunk=8, **kw,
        )
        if slos:
            for r in reps:
                r.engine.slo = SLOMonitor(
                    [SLOTarget("ttft", 0.5, objective=0.5)],
                )
        return reps

    def test_routes_around_burn_rate_skew(self, served):
        rec = FlightRecorder()
        reps = self._fleet(served, slos=True)
        # Replica unified0 is burning error budget hard; unified1 is
        # clean. Every placement must land on unified1 even though both
        # are equally idle.
        for _ in range(32):
            reps[0].engine.slo.observe("ttft", 99.0)
        router = FleetRouter(reps, recorder=rec)
        cfg, params, prompts = served
        for p in prompts[:2]:
            router.add_request(p)
        routed = [e["replica"] for e in rec.events("fleet.route")]
        assert routed == ["unified1", "unified1"], routed
        router.drain(max_steps=200)

    def test_fleet_level_shedding_bounds_inflight(self, served):
        reps = self._fleet(served)
        router = FleetRouter(
            reps, policy=FleetPolicy(max_inflight=2),
        )
        cfg, params, prompts = served
        router.add_request(prompts[0])
        router.add_request(prompts[1])
        with pytest.raises(AdmissionError, match="max_inflight"):
            router.add_request(prompts[2])
        assert router.registry.counter("fleet_shed_total").value == 1
        out = router.drain(max_steps=200)
        assert set(out) == {0, 1}

    def test_all_replicas_refusing_sheds_at_fleet_level(self, served):
        # Replica-level bounds (max_queue=1, batch_size fills): once
        # every replica's own admission refuses, the FLEET sheds — the
        # arrival is never half-enqueued anywhere.
        reps = self._fleet(served, max_queue=1)
        router = FleetRouter(reps)
        cfg, params, prompts = served
        for _ in range(2 * (2 + 1)):   # fill both queues past bound
            try:
                router.add_request(prompts[0])
            except AdmissionError:
                break
        with pytest.raises(AdmissionError, match="every replica refused"):
            router.add_request(prompts[1])
        assert router.registry.counter("fleet_shed_total").value >= 1
        router.drain(max_steps=400)

    def test_validation(self, served):
        cfg, params, prompts = served
        reps = self._fleet(served)
        with pytest.raises(ValueError, match="unique"):
            FleetRouter([reps[0], reps[0]])
        with pytest.raises(ValueError, match="at least one replica"):
            FleetRouter([])
        with pytest.raises(ValueError, match="max_inflight"):
            FleetPolicy(max_inflight=0)
        with pytest.raises(ValueError, match="prefill"):
            make_replicas(
                cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
                role="prefill", batch_size=2, max_new_tokens=4,
            )
        with pytest.raises(ValueError, match="role"):
            make_replicas(
                cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
                role="router", batch_size=2, max_new_tokens=4,
            )
        # A disaggregated fleet needs both halves.
        pre = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
            role="prefill", batch_size=2, max_new_tokens=1,
        )
        with pytest.raises(ValueError, match="decode"):
            FleetRouter(pre)
        # Unified replicas must agree on the generation budget, or a
        # failover requeue could not recompute bit-identically.
        mixed = self._fleet(served) + make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
            prefix="odd", batch_size=2, max_new_tokens=8,
        )
        with pytest.raises(ValueError, match="disagree on max_new"):
            FleetRouter(mixed)


def _disagg_fleet(cfg, params, *, temperature=0.0, rng_key=None):
    pre = make_replicas(
        cfg, RULES_DP_TP, params, count=2, mesh_shape=(1, 2),
        role="prefill", batch_size=2, max_new_tokens=1, refill_chunk=8,
        temperature=temperature,
    )
    dec = make_replicas(
        cfg, RULES_DP_TP, params, count=2, mesh_shape=(1, 2),
        role="decode", offset=4, batch_size=2, max_new_tokens=4,
        refill_chunk=8, temperature=temperature,
    )
    if rng_key is not None:
        for r in pre + dec:
            r.engine.rng = rng_key
    return pre, dec, FleetRouter(pre + dec)


class TestDisaggregatedHandoff:
    def test_greedy_bit_identical_to_single_engine(self, served):
        cfg, params, prompts = served
        ref = _baseline(cfg, params, prompts)
        pre, dec, router = _disagg_fleet(cfg, params)
        for i, p in enumerate(prompts):
            router.add_request(p, rid=i)
        out = router.drain(max_steps=400)
        for i in range(len(prompts)):
            np.testing.assert_array_equal(out[i], ref[i])
        # Telemetry: every handed-off request streamed counted KV bytes.
        handoffs = router.registry.counter("fleet_handoffs_total").value
        assert handoffs == len(prompts)
        assert router.registry.counter(
            "fleet_kv_transfer_bytes_total"
        ).value > 0
        assert router.registry.counter(
            "fleet_kv_transfer_segments_total"
        ).value >= handoffs
        for r in dec:
            n = r.engine.registry.counter("engine_kv_ingests_total").value
            assert n > 0   # the policy spread work over both decoders

    def test_sampled_bit_identical_to_single_engine(self, served):
        cfg, params, prompts = served
        key = jax.random.key(0)
        ref = _baseline(
            cfg, params, prompts, temperature=0.8, rng=key
        )
        pre, dec, router = _disagg_fleet(
            cfg, params, temperature=0.8, rng_key=key
        )
        for i, p in enumerate(prompts):
            router.add_request(p, rid=i)
        out = router.drain(max_steps=400)
        for i in range(len(prompts)):
            np.testing.assert_array_equal(out[i], ref[i])

    def test_blocked_backend_handoff_bit_identical(self, served):
        """The TPU default decode backend ('blocked') caches rows
        HEAD-major (n_kv, S, h): the transfer plan must clip the real
        sequence dim (kv_row_seq_dims derives it from the layout), not
        assume dim 0 — a hard-coded dim-0 clip would truncate KV heads
        and hand the decode replica zeroed heads. Short prompts
        (length < n_kv) are the sharpest probe."""
        cfg, params, prompts = served
        bcfg = dataclasses.replace(cfg, decode_attention="blocked")
        short = [np.asarray([3, 5], np.int32)] + prompts[:2]
        mesh = build_mesh(
            (1, 2), ("data", "model"), devices=jax.devices()[:2]
        )
        eng = ContinuousEngine(
            bcfg, mesh, RULES_DP_TP, batch_size=2, max_new_tokens=4,
            refill_chunk=8,
        )
        ref = eng.serve(replicated_params(params, mesh), short)
        pre = make_replicas(
            bcfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="prefill", batch_size=2, max_new_tokens=1,
            refill_chunk=8,
        )
        dec = make_replicas(
            bcfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="decode", offset=4, batch_size=2, max_new_tokens=4,
            refill_chunk=8,
        )
        router = FleetRouter(pre + dec)
        for i, p in enumerate(short):
            router.add_request(p, rid=i)
        out = router.drain(max_steps=300)
        for i in range(len(short)):
            np.testing.assert_array_equal(out[i], ref[i])
        dims = dec[0].engine.kv_row_seq_dims()
        assert 1 in jax.tree.leaves(dims)   # head-major rows detected

    def test_handoff_rows_match_decode_row_layout(self, served):
        # The transfer plan's destination IS the decode cache's own row
        # layout (kv_row_shardings), which is what makes kv_ingest the
        # purely local update its golden pins.
        cfg, params, prompts = served
        pre, dec, router = _disagg_fleet(cfg, params)
        router.add_request(prompts[0], rid=0)
        router.drain(max_steps=200)
        eng = next(
            r.engine for r in dec
            if r.engine.registry.counter("engine_kv_ingests_total").value
        )
        # (cache, its block tables: none, contiguous rows), then the rows.
        args = eng.program("kv_ingest").last_args()
        rows, shardings = args[2], eng.kv_row_shardings()
        jax.tree.map(
            lambda x, s: None if x.sharding == s else pytest.fail(
                f"ingested row sharding {x.sharding} != cache row {s}"
            ),
            rows, shardings,
        )
        progs = [name for name, *_ in eng._dispatched_programs()]
        assert "kv_ingest" in progs
        assert eng.compile_counts()["kv_ingest"] == 1


class TestFailover:
    def test_kill_mid_stream_reroutes_bit_identically(self, served):
        cfg, params, prompts = served
        ref = _baseline(cfg, params, prompts)
        rec = FlightRecorder()
        reps = make_replicas(
            cfg, RULES_DP_TP, params, count=2, mesh_shape=(1, 2),
            batch_size=2, max_new_tokens=4, refill_chunk=8, recorder=rec,
        )
        router = FleetRouter(reps, recorder=rec)
        with ChaosInjector(
            Fault("fleet.step", "raise", at=2, count=1), recorder=rec,
        ):
            for i, p in enumerate(prompts):
                router.add_request(p, rid=i)
            out = router.drain(max_steps=400)
        dead = [r for r in reps if not r.alive]
        assert len(dead) == 1
        for i in range(len(prompts)):
            assert not isinstance(out[i], RequestFailure), out[i]
            np.testing.assert_array_equal(out[i], ref[i])
        # The failover is VISIBLE: the dead replica retired its work as
        # "rerouted" (never a silent drop, never a fake fresh admission),
        # and the router logged the decision chain.
        assert dead[0].engine.registry.counter(
            "engine_rerouted_total"
        ).value >= 1
        assert rec.events("fleet.failover")
        assert any(
            e["requeue"] for e in rec.events("fleet.route")
        )
        assert router.registry.counter("fleet_reroutes_total").value >= 1
        lat = router.latency_stats()
        assert lat["reroutes"] >= 1 and lat["ok"] == len(prompts)

    def test_losing_every_replica_is_terminal_not_silent(self, served):
        cfg, params, prompts = served
        reps = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
            batch_size=2, max_new_tokens=4, refill_chunk=8,
        )
        router = FleetRouter(reps)
        router.add_request(prompts[0], rid=0)
        router.step()
        router.kill_replica("unified0")
        out = router.pop_finished()
        assert isinstance(out[0], RequestFailure)
        # NOT "rerouted" — that status is the ignorable internal requeue
        # marker; a request the fleet actually lost wears its own.
        assert out[0].status == "failover_failed"
        assert not router.has_work()
        # ... and the loss is NOT an admission shed: a shed-rate
        # dashboard must not misread replica-death losses as overload.
        assert router.registry.counter("fleet_shed_total").value == 0

    def test_killing_last_decode_replica_terminates(self, served):
        """A disaggregated fleet that loses its only decode replica must
        TERMINATE every affected request ("failover_failed"), not park
        re-prefilled handoffs forever while drain() spins."""
        cfg, params, prompts = served
        pre = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="prefill", batch_size=2, max_new_tokens=1,
            refill_chunk=8,
        )
        dec = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="decode", offset=2, batch_size=2, max_new_tokens=4,
            refill_chunk=8,
        )
        router = FleetRouter(pre + dec)
        for i, p in enumerate(prompts):
            router.add_request(p, rid=i)
        while not dec[0].engine.has_work():
            router.step()          # until at least one handoff ingested
        router.kill_replica("decode0")
        out = router.drain(max_steps=300)   # must terminate, not wedge
        assert set(out) == set(range(len(prompts)))
        failed = [
            v for v in out.values() if isinstance(v, RequestFailure)
        ]
        assert failed and all(
            f.status == "failover_failed" for f in failed
        )

    def test_degraded_decode_replica_still_serves_accepted_work(
        self, served
    ):
        """A decode replica degraded to SHEDDING still takes handoffs:
        level 3 sheds NEW fleet admissions (the prefill pool's own
        add_request), never work the fleet already accepted — and an
        idle degraded replica could not de-escalate anyway (no traffic
        freezes its burn window), so gating handoffs on the ladder
        would wedge accepted requests forever."""
        from learning_jax_sharding_tpu.robustness import DegradationLadder

        cfg, params, prompts = served
        pre = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="prefill", batch_size=2, max_new_tokens=1,
            refill_chunk=8,
        )
        dec = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="decode", offset=2, batch_size=2, max_new_tokens=4,
            refill_chunk=8,
        )
        ladder = DegradationLadder()
        ladder.level = 3             # shedding — but the replica LIVES
        dec[0].engine._ladder = ladder
        router = FleetRouter(pre + dec)
        ref = _baseline(cfg, params, prompts[:2])
        for i, p in enumerate(prompts[:2]):
            router.add_request(p, rid=i)
        out = router.drain(max_steps=200)
        for i in range(2):
            np.testing.assert_array_equal(out[i], ref[i])

    def test_handoff_backpressure_and_parked_deadline(self, served):
        """A congested decode side must not grow the handoff queue
        without bound (each entry pins an exported KV-row tree): past
        ``max_pending_handoffs`` the router stops stepping prefill
        replicas. And the round-10 TTL holds in the handoff stage — a
        request that expires while parked fails with ``"deadline"``
        BEFORE paying the transfer or a decode slot."""
        cfg, params, prompts = served
        pre = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="prefill", batch_size=2, max_new_tokens=1,
            refill_chunk=8,
        )
        dec = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 2),
            role="decode", offset=2, batch_size=1, max_new_tokens=4,
            refill_chunk=8,
        )
        router = FleetRouter(pre + dec, max_pending_handoffs=1)
        for i, p in enumerate(prompts):
            router.add_request(p, rid=i, deadline_s=120.0)
        out: dict = {}
        high_water = 0
        steps = 0
        aged = None
        while router.has_work():
            router.step()
            out.update(router.pop_finished())
            high_water = max(high_water, len(router._handoffs))
            if aged is None and router._handoffs:
                # Age one parked request past its TTL (white-box: the
                # wall clock is too coarse to race reliably).
                freq = router._handoffs[0]["freq"]
                freq.arrival_t -= 121.0
                aged = freq.rid
            steps += 1
            assert steps < 400, "fleet wedged"
        out.update(router.pop_finished())
        assert high_water <= 1            # the bound held
        assert aged is not None
        assert isinstance(out[aged], RequestFailure)
        assert out[aged].status == "deadline"
        done = [r for r, v in out.items()
                if not isinstance(v, RequestFailure)]
        assert len(done) == len(prompts) - 1   # the rest completed

    def test_eos_must_agree_across_replicas(self, served):
        cfg, params, prompts = served
        a = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
            batch_size=2, max_new_tokens=4,
        )
        b = make_replicas(
            dataclasses.replace(cfg, dtype=jnp.float32),
            RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
            prefix="b", offset=1, batch_size=2, max_new_tokens=4,
            eos_id=7,
        )
        with pytest.raises(ValueError, match="eos"):
            FleetRouter(a + b)

    def test_finished_requests_do_not_accumulate(self, served):
        """The canonical request records must hold only LIVE work —
        inflight() runs on every admission/step, and retained prompts
        would grow with every request the fleet has ever served."""
        cfg, params, prompts = served
        reps = make_replicas(
            cfg, RULES_DP_TP, params, count=1, mesh_shape=(1, 1),
            batch_size=2, max_new_tokens=4, refill_chunk=8,
        )
        router = FleetRouter(reps)
        for _ in range(3):
            for p in prompts[:2]:
                router.add_request(p)
            router.drain(max_steps=200)
        assert router._requests == {}
        assert router.inflight() == 0


class TestGoodputTracing:
    """Round 14: the fleet's wall-clock ledgers and request traces.

    Every replica ledger must RECONCILE over a served window; every
    retired request must carry a COMPLETE critical path whose trace id
    was minted once at router admission and survived every hop — the KV
    handoff, a mid-stream replica kill's reroute, and a rolling weight
    swap's version pin."""

    def test_disagg_ledgers_reconcile_and_paths_complete(self, served):
        cfg, params, prompts = served
        pre, dec, router = _disagg_fleet(cfg, params)
        minted = {}
        for i, p in enumerate(prompts):
            router.add_request(p, rid=i)
            minted[i] = router.traces.trace_of(i)
        assert len(set(minted.values())) == len(prompts)
        out = router.drain(max_steps=400)
        assert sorted(out) == list(range(len(prompts)))

        rep = router.goodput_report()
        assert rep["reconcile_ok"], {
            n: r["reconcile"] for n, r in rep["replicas"].items()
        }
        assert rep["fleet_buckets"]["device"] > 0.0
        assert rep["fleet_buckets"]["kv_handoff"] > 0.0
        assert rep["host_share"] is not None and 0 < rep["host_share"] <= 1

        cps = {cp["rid"]: cp for cp in router.traces.completed()}
        assert sorted(cps) == list(range(len(prompts)))
        for i, cp in cps.items():
            assert cp["trace_id"] == minted[i]      # the id never changed
            assert cp["status"] == "ok"
            for stage in ("queue", "prefill", "handoff", "decode"):
                assert cp["stages"].get(stage, 0.0) > 0.0, (i, stage, cp)
            assert cp["ttft_s"] is not None and cp["ttft_s"] > 0.0
        # The merged Perfetto timeline carries both engine-dispatch
        # tracks and the request tracks on one clock.
        doc = router.merged_chrome_trace()
        names = {
            e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M" and "name" in e["args"]
        }
        assert {"replica prefill0", "replica decode0"} <= names
        assert any(n.startswith("requests: ") for n in names)
        prom = router.prometheus_text()
        assert 'ledger_seconds_total{bucket="device",replica="' in prom
        assert 'trace_stage_seconds_bucket{stage="handoff"' in prom

    def test_trace_id_survives_a_mid_stream_reroute(self, served):
        cfg, params, prompts = served
        rec = FlightRecorder()
        reps = make_replicas(
            cfg, RULES_DP_TP, params, count=2, mesh_shape=(1, 2),
            batch_size=2, max_new_tokens=4, refill_chunk=8, recorder=rec,
        )
        router = FleetRouter(reps, recorder=rec)
        with ChaosInjector(
            Fault("fleet.step", "raise", at=2, count=1), recorder=rec,
        ):
            minted = {}
            for i, p in enumerate(prompts):
                router.add_request(p, rid=i)
                minted[i] = router.traces.trace_of(i)
            out = router.drain(max_steps=400)
        dead = [r for r in reps if not r.alive]
        assert len(dead) == 1
        assert not any(
            isinstance(v, RequestFailure) for v in out.values()
        )
        cps = {cp["rid"]: cp for cp in router.traces.completed()}
        assert sorted(cps) == list(range(len(prompts)))
        rerouted = [cp for cp in cps.values() if cp["reroutes"] >= 1]
        assert rerouted, "the kill must mark at least one trace rerouted"
        for cp in cps.values():
            # SAME trace id end to end: the reroute appended spans and a
            # marker to the existing trace, it minted nothing new.
            assert cp["trace_id"] == minted[cp["rid"]]
            assert cp["status"] == "ok"
        for cp in rerouted:
            r = router.traces.record(cp["rid"])
            replicas = {s["replica"] for s in r["spans"]}
            assert dead[0].name in replicas          # the wasted legs
            assert len(replicas - {dead[0].name}) >= 1   # the survivor's
            assert any(s["attrs"].get("wasted") for s in r["spans"])
            assert cp["wasted_s"] >= 0.0
            (ev,) = [e for e in r["events"] if e["name"] == "reroute"]
            assert ev["replica"] == dead[0].name
        # The fleet still accounts 100% of its (surviving) wall.
        rep = router.goodput_report()
        assert rep["reconcile_ok"]

    def test_trace_pins_rolling_swap_versions(self, served):
        cfg, params, prompts = served
        reps = make_replicas(
            cfg, RULES_DP_TP, params, count=2, mesh_shape=(1, 1),
            batch_size=2, max_new_tokens=4, refill_chunk=4,
        )
        router = FleetRouter(reps)
        # Oversubscribe on purpose: the version pin lands on requests
        # QUEUED at commit time (in-flight rows finish on the old
        # version in drain mode), so the queues must outlast the slots.
        queue = list(prompts) * 3
        minted = {}
        for i, p in enumerate(queue):
            router.add_request(p, rid=i)
            minted[i] = router.traces.trace_of(i)
        router.step()
        new_params = jax.tree.map(lambda x: x * 1.02, params)
        timeline = router.rolling_swap(new_params, version=5)
        assert [t["committed"] for t in timeline] == [True, True]
        out = router.drain(max_steps=600)
        assert sorted(out) == list(range(len(queue)))
        cps = {cp["rid"]: cp for cp in router.traces.completed()}
        assert sorted(cps) == list(range(len(queue)))
        pinned = [cp for cp in cps.values() if cp["swap_pins"]]
        assert pinned, "a queued request must carry the commit's pin"
        for cp in pinned:
            assert set(cp["swap_pins"]) == {5}
            assert cp["trace_id"] == minted[cp["rid"]]
        versions = {}
        for rep in reps:
            versions.update(rep.engine.finished_versions)
        # Pinned traces really were served on the new weights.
        assert all(versions[cp["rid"]] == 5 for cp in pinned)
