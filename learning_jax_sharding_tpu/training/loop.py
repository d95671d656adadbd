"""The training loop: data, step, metrics, checkpoint/resume — composed.

The reference's "training loop" is ten untimed, unlogged, uncheckpointed
iterations inline at module scope (`/root/reference/case6_attention.py:
222-227`). This module is the framework's actual run entry point, wiring
together the pieces the survey enumerates (SURVEY.md §5): the sharded batch
loader (multi-host correct), the jitted SPMD train step, per-step structured
metrics with honest timing, and Orbax checkpoint/resume.

Resume is exact: the checkpoint step indexes the data loader (deterministic
random-access batches), so a restored run consumes the same batch sequence
the uninterrupted run would have.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional

import jax
import optax

from learning_jax_sharding_tpu.data.loader import ShardedBatchLoader
from learning_jax_sharding_tpu.models.transformer import next_token_loss
from learning_jax_sharding_tpu.parallel.logical import Rules, activate
from learning_jax_sharding_tpu.training.checkpoint import CheckpointManager
from learning_jax_sharding_tpu.training.pipeline import (
    make_eval_step,
    make_train_step,
    sharded_train_state,
)
from learning_jax_sharding_tpu.utils.bench import compiled_flops
from learning_jax_sharding_tpu.utils.metrics import MetricsLogger


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    """Run-level knobs (model knobs live in the model's own config)."""

    steps: int
    global_batch_size: int
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    warmup_steps: int = 0
    lr_schedule: str = "constant"    # "constant" | "cosine" | "linear" decay
    min_learning_rate: float = 0.0   # decay floor (cosine/linear)
    grad_clip_norm: Optional[float] = None  # global-norm gradient clipping
    optimizer: str = "adamw"         # "adamw" | "lion" | "adafactor"
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 100
    max_checkpoints: int = 3
    metrics_path: Optional[str] = None
    log_every: int = 1
    seed: int = 0
    prefetch: int = 2                # batches prepared ahead on a background
                                     # thread (0 = synchronous loading)


def lr_schedule(cfg: TrainLoopConfig) -> optax.Schedule:
    """Warmup → decay schedule from the loop config.

    ``warmup_steps`` of linear warmup from 0, then per ``cfg.lr_schedule``:
    ``"constant"`` holds the peak; ``"cosine"`` / ``"linear"`` decay to
    ``min_learning_rate`` over the remaining steps. A schedule is a pure
    step→rate function traced into the jitted step — no host-side LR state.
    """
    decay_steps = max(cfg.steps - cfg.warmup_steps, 1)
    if cfg.lr_schedule == "constant":
        decay = optax.constant_schedule(cfg.learning_rate)
    elif cfg.lr_schedule == "cosine":
        decay = optax.cosine_decay_schedule(
            cfg.learning_rate, decay_steps,
            alpha=cfg.min_learning_rate / cfg.learning_rate,
        )
    elif cfg.lr_schedule == "linear":
        decay = optax.linear_schedule(
            cfg.learning_rate, cfg.min_learning_rate, decay_steps
        )
    else:
        raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r}")
    if cfg.warmup_steps == 0:
        return decay
    warmup = optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps)
    return optax.join_schedules([warmup, decay], [cfg.warmup_steps])


def default_optimizer(cfg: TrainLoopConfig) -> optax.GradientTransformation:
    """``cfg.optimizer`` under the config's LR schedule, with optional
    global-norm gradient clipping (the reference uses bare Adam(1e-3),
    `/root/reference/case6_attention.py:181`).

    * ``"adamw"`` — the default; two fp32 moments per param.
    * ``"lion"`` — sign-based, ONE bf16-friendly momentum: ~half the
      optimizer-state HBM of AdamW (the big single-chip cost PERF.md
      measures); typical LRs are ~3-10x smaller than AdamW's.
    * ``"adafactor"`` — factored second moment: optimizer state shrinks from
      O(params) to ~O(rows+cols) per matrix, the classic memory-tight
      choice. ``cfg.weight_decay`` is deliberately NOT forwarded: optax's
      ``weight_decay_rate`` is a per-step multiplicative decay applied
      OUTSIDE the learning-rate scaling, so AdamW's 0.01 would shrink
      weights ~1%/step (≈1000x AdamW's effective decay) — pass a custom
      optimizer if adafactor-style decay is wanted.
    """
    sched = lr_schedule(cfg)
    if cfg.optimizer == "adamw":
        opt = optax.adamw(sched, weight_decay=cfg.weight_decay)
    elif cfg.optimizer == "lion":
        opt = optax.lion(sched, weight_decay=cfg.weight_decay)
    elif cfg.optimizer == "adafactor":
        opt = optax.adafactor(sched)
    else:
        raise ValueError(
            f"unknown optimizer {cfg.optimizer!r}: "
            "expected 'adamw', 'lion', or 'adafactor'"
        )
    if cfg.grad_clip_norm is not None:
        opt = optax.chain(optax.clip_by_global_norm(cfg.grad_clip_norm), opt)
    return opt


def fit(
    model: Any,
    dataset: Any,
    mesh: Any,
    rules: Rules,
    cfg: TrainLoopConfig,
    *,
    optimizer: optax.GradientTransformation | None = None,
    loss_fn: Callable[..., jax.Array] = next_token_loss,
    step_kwargs: dict[str, Any] | None = None,
    registry: Any | None = None,
    tracer: Any | None = None,
    watchdog: Any | None = None,
    heartbeat: Any | None = None,
    recorder: Any | None = None,
    contract: Any | None = None,
    resilience: Any | None = None,
    ledger: Any | None = None,
) -> tuple[Any, list[dict]]:
    """Train ``model`` on ``dataset`` for ``cfg.steps`` steps.

    Resumes automatically from ``cfg.checkpoint_dir`` when it holds a
    checkpoint. Returns ``(final_state, metrics_history)``.

    Args:
        model: Flax module with logically partitioned params (applied as
            ``model.apply({"params": p}, inputs)`` by the train step).
        dataset: per-host-sliceable dataset (see :mod:`data.datasets`).
        mesh: device mesh; batches land on its ``"data"`` axis.
        rules: logical→mesh rules for params and activations.
        optimizer: optax transformation; defaults to :func:`default_optimizer`.
        loss_fn: ``loss_fn(y, batch)`` (or with params — forward
            ``loss_needs_params`` via ``step_kwargs``).
        step_kwargs: extra kwargs for :func:`training.pipeline.make_train_step`
            (e.g. ``aux_loss_collection="losses"`` for MoE models,
            ``apply_kwargs={"return_hidden": True}`` for the fused CE loss).
            ``routing_stats=True`` makes the step return what its dropless
            expert layers counted; with a ``registry`` they are booked as
            ``train_moe_held_assignments_total`` and
            ``train_moe_experts_touched_total`` (counters) and
            ``train_moe_expert_max_load`` (a gauge: the largest load of one
            expert in one layer of the last step). They are counted on the
            device inside the step and read with its loss, so they cost no
            sync of their own; there is no ``train_step.moe`` span, because
            a host span cannot see inside a jitted step (the ``train_step``
            span covers the whole of it).
        registry: optional
            :class:`~learning_jax_sharding_tpu.telemetry.MetricsRegistry`
            — per-step metrics are mirrored into it as ``train_*``
            series (same registry the serving engine meters into, one
            export surface for the whole stack).
        tracer: optional
            :class:`~learning_jax_sharding_tpu.telemetry.Tracer` — the
            run's phases (setup, restore, cost analysis, each train
            step) become nested spans, Perfetto-exportable and visible
            in XProf when a profiler capture is active.
        watchdog: optional
            :class:`~learning_jax_sharding_tpu.telemetry.Watchdog` —
            full-speed numeric health: the step additionally returns the
            on-device global grad-norm (``with_grad_norm`` — no extra
            sync), each step is probed asynchronously, and a non-finite
            loss/grad-norm ESCALATES: the offending step's batch is
            re-run under ``utils.profiling.checking()`` to localize the
            first NaN-producing primitive, the flight recorder dumps a
            post-mortem bundle, and
            :class:`~learning_jax_sharding_tpu.telemetry.NonFiniteError`
            is raised naming the step.
        heartbeat: optional
            :class:`~learning_jax_sharding_tpu.telemetry.Heartbeat` —
            each step's dispatch+sync runs under an armed deadline, so a
            wedged device/transport is flagged from the monitor thread
            instead of stalling silently.
        recorder: optional
            :class:`~learning_jax_sharding_tpu.telemetry.FlightRecorder`
            (default: the process-wide ring) — ``fit`` records per-step
            events and the escalation trail into it.
        contract: optional SPMD collective contract
            (:class:`~learning_jax_sharding_tpu.analysis.Contract`, a
            golden ``.json`` path, or a golden directory — then the
            ``"train_step"`` golden is used, or ``"train_step_gn"``
            when a watchdog forces the grad-norm epilogue into the
            step). The compiled step is
            checked BEFORE step 1 and any drift (a new collective, an
            oversized buffer, comms inside a while body) raises
            :class:`~learning_jax_sharding_tpu.analysis.contracts.ShardingContractError`
            — an accidental weight all-gather should cost one failed
            launch, not a week of a slow hot loop. The findings land in
            the flight recorder/registry first.
        resilience: optional
            :class:`~learning_jax_sharding_tpu.robustness.ResilienceConfig`
            — recovery POLICIES on top of the detection stack: the step
            compiles with the on-device non-finite guard
            (``skip_nonfinite`` — a NaN/Inf step cannot write corrupted
            state; bounded consecutive skips, then escalation), a
            finite loss beyond the spike EMA optionally ROLLS BACK to
            the last retained checkpoint and replays, SIGTERM triggers
            an EMERGENCY CHECKPOINT and raises
            :class:`~learning_jax_sharding_tpu.robustness.PreemptionError`
            (re-running with the same ``checkpoint_dir`` resumes
            bit-identically — the preemption drill pinned in
            ``tests/test_zero_downtime.py``), and a watchdog escalation
            saves before it raises. Every action lands in the flight
            recorder.
        ledger: optional
            :class:`~learning_jax_sharding_tpu.telemetry.GoodputLedger`
            — ``fit`` buckets its ENTIRE wall-clock: setup/contract/cost
            analysis as ``compile``, checkpoint restore and every
            resilience action (guarded skips, rollbacks, emergency
            saves, chaos seams) as ``recovery``, the train-step dispatch
            + loss sync as ``device`` (re-bucketed to ``compile`` when
            the executable cache grew under the call), watchdog probes
            and recorder/metrics bookkeeping as ``telemetry``, the
            iteration's own host remainder as ``sched``. One created
            against ``registry`` when omitted;
            ``ledger.reconcile()["ok"]`` holds after fit returns (gated
            in tier-1).
    """
    import math
    import signal
    import threading

    from learning_jax_sharding_tpu.robustness.chaos import chaos_hook
    from learning_jax_sharding_tpu.robustness.recovery import PreemptionError
    from learning_jax_sharding_tpu.telemetry import (
        CompileWatch,
        GoodputLedger,
        Tracer,
        cache_size,
        default_flight_recorder,
    )
    from learning_jax_sharding_tpu.telemetry.watchdog import (
        NonFiniteError,
        localize_nan,
    )

    tr = tracer if tracer is not None else Tracer(enabled=False)
    rec = recorder if recorder is not None else default_flight_recorder()
    led = ledger if ledger is not None else GoodputLedger(registry=registry)
    led.begin_window()
    if tracer is not None:
        # Span closures (setup/restore/train_step, with durations) ride
        # the ring next to the step records — same feed the engine gives.
        rec.attach_tracer(tr)
    if watchdog is not None:
        # Late-bind fit's registry/recorder into an unbound watchdog —
        # same courtesy the engine extends to an unbound SLOMonitor, so
        # fit(watchdog=Watchdog(), registry=reg, recorder=fr) meters and
        # records without constructor plumbing.
        watchdog.bind(registry=registry, recorder=rec)
    if heartbeat is not None:
        heartbeat.bind(registry=registry, recorder=rec)
    # Compile events ride the ring (and the registry, when given) for the
    # training loop's lifetime — a mid-run recompile is exactly the kind
    # of event a post-mortem needs in its timeline. Started (with the
    # owned heartbeat thread) immediately before the try whose finally
    # stops them: a setup-phase raise must not leak the process-wide
    # monitoring listener or a polling daemon thread.
    compile_watch = CompileWatch(registry=registry, recorder=rec)
    hb_owned = heartbeat is not None and not heartbeat.running
    optimizer = default_optimizer(cfg) if optimizer is None else optimizer
    # Setup is compile-dominated wall (sharded init traces + compiles,
    # make_train_step lowers, the contract check AOT-compiles) — one
    # ledger frame buckets the whole launch cost as ``compile``.
    with led.measure("compile"), tr.span("fit.setup"):
        loader = ShardedBatchLoader(
            dataset, mesh, cfg.global_batch_size, spec=("data",)
        )
        sample = loader.batch_at(0)

        state, state_sh = sharded_train_state(
            model, optimizer, sample["inputs"],
            {"params": jax.random.key(cfg.seed)}, mesh, rules,
        )
        extra = dict(step_kwargs or {})
        if watchdog is not None:
            # The watchdog needs the grad-norm on device; the step
            # computes it inside the backward's epilogue (no extra sync).
            extra.setdefault("with_grad_norm", True)
        if resilience is not None and resilience.skip_nonfinite:
            # The on-device update guard (training/pipeline.py): a
            # non-finite loss/grad-norm step keeps the old
            # params/opt_state — forces the grad-norm dict output, so
            # the host sees WHY a step was skipped.
            extra.setdefault("skip_nonfinite", True)
        step_fn = make_train_step(
            state_sh, {k: v.sharding for k, v in sample.items()}, mesh,
            rules, loss_fn=loss_fn, **extra,
        )
        if contract is not None:
            # Fail-fast static gate. Costs ONE extra AOT compile of the
            # step at launch (the .lower().compile() here does not seed
            # the jit dispatch cache on this jax) — the price of failing
            # a bad sharding before step 1 instead of shipping it.
            from learning_jax_sharding_tpu.analysis.contracts import (
                enforce_contract,
            )

            # Under activate(): the goldens are generated with the mesh
            # and logical rules ambient (analysis/entrypoints.py), and a
            # model whose with_logical_constraint calls resolve to no-ops
            # here could compile different collectives than its golden —
            # a spurious launch failure.
            # A watchdog forces the grad-norm epilogue into the step
            # (extra reductions), which has its OWN golden — checking
            # that program against the plain train_step contract would
            # fail every healthy watchdog run at launch.
            # Three train-step program regimes, three goldens: plain,
            # the watchdog's grad-norm epilogue, and the resilience
            # guard (grad-norm + update-gating selects — XLA lays the
            # collectives out slightly differently once the selects are
            # in, so it pins its own golden; analysis/entrypoints.py
            # generates all three).
            if extra.get("skip_nonfinite"):
                golden_name = "train_step_skip"
            elif extra.get("with_grad_norm"):
                golden_name = "train_step_gn"
            else:
                golden_name = "train_step"
            with tr.span("fit.contract_check"), activate(mesh, rules):
                enforce_contract(
                    contract, step_fn.jitted, state, sample, mesh=mesh,
                    name=golden_name, recorder=rec, registry=registry,
                )

    ckpt = None
    start_step = 0
    if cfg.checkpoint_dir is not None:
        # Restore is the recovery path by definition — resuming past a
        # crash/preemption is time spent because something failed.
        with led.measure("recovery"), tr.span("fit.restore"):
            ckpt = CheckpointManager(
                cfg.checkpoint_dir,
                max_to_keep=cfg.max_checkpoints,
                save_interval_steps=cfg.checkpoint_every,
                recorder=rec,
            )
            # restore_latest falls back past a corrupted newest step
            # (preemption mid-write) to an older retained one — the
            # resume path must survive exactly the crash that made the
            # resume necessary.
            restored = ckpt.restore_latest(like=state)
            if restored is not None:
                state = restored
                start_step = int(state.step)
                rec.record("train_restore", step=start_step)

    with led.measure("compile"), tr.span("fit.cost_analysis"), \
            activate(mesh, rules):
        flops = compiled_flops(step_fn.jitted, state, sample)
    tokens_per_step = int(
        sample["inputs"].shape[0] * sample["inputs"].shape[1]
    )

    metrics = MetricsLogger(
        cfg.metrics_path,
        flops_per_step=flops,
        tokens_per_step=tokens_per_step,
        n_devices=mesh.size,
        log_every=cfg.log_every,
        registry=registry,
    )
    def emergency_save(reason: str) -> bool:
        # The incident-path checkpoint: persist the CURRENT state (with
        # the skip guard on it is the last healthy state) before the
        # raise, so the operator resumes instead of rerunning. Forced
        # and awaited — a preemption gives no second chance.
        if (
            ckpt is None or resilience is None
            or not resilience.emergency_checkpoint
        ):
            return False
        step_now = int(state.step)
        ckpt.save(step_now, state, force=True)
        ckpt.wait()
        rec.record("emergency_checkpoint", step=step_now, reason=reason)
        return True

    def escalate():
        # A probe came back non-finite. Localize: re-run the flagged
        # step's batch (still held in the recent-batch window) under
        # scoped NaN trapping, which names the first bad primitive —
        # against the CURRENT state, so data-induced NaNs localize
        # exactly while state-drift ones may come back clean (recorded
        # either way). Then dump the post-mortem bundle and raise.
        emergency_save("watchdog_escalation")
        bad = watchdog.first_bad_step
        batch = recent.get(bad)
        localized = None
        if batch is not None:
            localized = localize_nan(lambda: step_fn(state, batch))
        rec.record(
            "nan_localized", step=bad, what=watchdog.bad_what,
            message=localized,
        )
        err = NonFiniteError(bad, watchdog.bad_what or "loss")
        bundle = rec.dump(registry=registry, tracer=tr, error=err)
        raise NonFiniteError(
            bad, watchdog.bad_what or "loss", localized=localized,
            bundle=bundle,
        )

    batches = None
    if cfg.prefetch > 0:
        batches = loader.prefetched(cfg.prefetch, start=start_step)

    def reseek(step: int):
        # The prefetch pipeline is positional; a rollback rewinds it by
        # rebuilding from the restored step (the loader itself is
        # random-access, so the replayed sequence is exact).
        nonlocal batches
        if batches is not None:
            batches.close()
            batches = loader.prefetched(cfg.prefetch, start=step)

    # SIGTERM → emergency checkpoint → PreemptionError: the cloud
    # preemption path. Handler installed only from the main thread
    # (signal API constraint) and restored in the finally.
    sig = {"tripped": False}
    sig_installed = False
    prev_sig: Any = None
    if (
        resilience is not None and resilience.handle_sigterm
        and threading.current_thread() is threading.main_thread()
    ):
        def _on_sigterm(signum, frame):
            sig["tripped"] = True

        prev_sig = signal.signal(signal.SIGTERM, _on_sigterm)
        sig_installed = True

    c_skips = (
        registry.counter(
            "train_nonfinite_skips_total",
            "train steps skipped by the non-finite guard",
        )
        if registry is not None and resilience is not None else None
    )
    recent: dict[int, Any] = {}
    skips = 0          # CONSECUTIVE guarded skips (budget: max_skips)
    rollbacks = 0
    ema: float | None = None
    ema_seen = 0
    compile_watch.start()
    if hb_owned:
        heartbeat.start()
    try:
        i = start_step
        while i < cfg.steps:
            # The iteration's TOP-LEVEL ledger frame: everything the loop
            # body spends lands in a bucket (nested frames claim their
            # exclusive slices; the unclaimed remainder — batch fetch,
            # checkpoint dispatch, loop bookkeeping — is the host
            # scheduling tax itself). Gaps between iterations (a stalled
            # loader upstream, the caller's own work) derive as idle, so
            # Σ buckets == wall holds for the whole fit() window.
            with led.measure("sched"):
                if sig["tripped"]:
                    with led.measure("recovery"):
                        saved = emergency_save("sigterm")
                        rec.record(
                            "preemption", step=int(state.step),
                            checkpointed=saved,
                        )
                        raise PreemptionError(
                            int(state.step), cfg.checkpoint_dir
                        )
                with led.measure("recovery"):
                    # An armed chaos seam spends its injected delay HERE
                    # — fault time is recovery, never device/sched.
                    chaos_hook("train.step", step=i + 1)
                batch = (
                    next(batches) if batches is not None
                    else loader.batch_at(i)
                )
                with led.measure("recovery"):
                    batch = chaos_hook(
                        "train.batch", value=batch, step=i + 1
                    )
                if watchdog is not None:
                    # Keep the async-probe window's batches for escalation.
                    with led.measure("telemetry"):
                        recent[i + 1] = batch
                        for old in [
                            s for s in recent
                            if s <= i + 1 - (watchdog.lag + 2)
                        ]:
                            del recent[old]
                hb = (
                    heartbeat.expect(f"train_step {i + 1}")
                    if heartbeat is not None else contextlib.nullcontext()
                )
                # Compile-steal: opened as device, re-bucketed to compile
                # when the step's executable cache grew under the call —
                # the first iteration (and any mid-run recompile) paid a
                # trace+compile, not a device step.
                cache_before = cache_size(step_fn.jitted)
                with led.measure("device", family="train_step") as frame, \
                        tr.span("train_step", step=i + 1), hb:
                    state, loss = step_fn(state, batch)
                    out = loss if isinstance(loss, dict) else {"loss": loss}
                    loss, gnorm = out["loss"], out.get("grad_norm")
                    routing = {
                        k: v for k, v in out.items() if k.startswith("moe_")
                    }
                    # metrics.log's float(loss) is the step's honest sync
                    # point — inside the span (and the heartbeat's armed
                    # window), so the span measures the step, not its
                    # dispatch — and a wedged sync is flagged.
                    metrics.log(i + 1, loss=loss)
                    cache_after = cache_size(step_fn.jitted)
                    compiled = cache_after is not None and (
                        cache_before is None or cache_after > cache_before
                    )
                    if compiled:
                        frame.rebucket("compile")
                # The OBSERVED loss: the chaos seam can corrupt the host
                # reading (the spike drill) without touching device state.
                with led.measure("recovery"):
                    loss_f = chaos_hook(
                        "train.loss", value=float(loss), step=i + 1
                    )
                with led.measure("telemetry"):
                    rec.record("train_step", step=i + 1, loss=loss_f)
                    if registry is not None and (compiled or i == start_step):
                        # What remat=True keeps, resolved while the step was
                        # traced (make_train_step): 0 / 0 where it keeps
                        # nothing (no remat, the emulated CPU mesh).
                        plan = step_fn.remat.plan
                        registry.gauge(
                            "train_remat_saved_bytes",
                            "named residuals the rematerialized blocks keep, a device",
                        ).set(plan.saved_bytes if plan else 0)
                        registry.gauge(
                            "train_remat_budget_bytes",
                            "bytes the train step found free for them, a device",
                        ).set(plan.budget_bytes if plan else 0)
                    if routing and registry is not None:
                        # Outputs of the step whose loss was just read.
                        for key in ("held_assignments", "experts_touched"):
                            registry.counter(
                                f"train_moe_{key}_total",
                                f"dropless expert layers: {key} over layers and steps",
                            ).inc(int(routing[f"moe_{key}"]))
                        registry.gauge(
                            "train_moe_expert_max_load",
                            "largest load of one held expert in one layer, last step",
                        ).set(int(routing["moe_expert_max_load"]))
                if resilience is not None:
                    nonfinite = not math.isfinite(loss_f) or (
                        gnorm is not None
                        and not math.isfinite(float(gnorm))
                    )
                    if nonfinite:
                        # The guarded step already refused the update; the
                        # host books the skip and moves to the next batch.
                        with led.measure("recovery"):
                            skips += 1
                            if c_skips is not None:
                                c_skips.inc()
                            rec.record(
                                "step_skipped", step=i + 1, loss=loss_f,
                                consecutive=skips,
                            )
                            if skips > resilience.max_skips:
                                emergency_save("skip_budget_exhausted")
                                err = NonFiniteError(
                                    i + 1, "loss/grad_norm"
                                )
                                bundle = rec.dump(
                                    registry=registry, tracer=tr,
                                    error=err,
                                )
                                raise NonFiniteError(
                                    i + 1, "loss/grad_norm", bundle=bundle
                                )
                            i += 1
                            continue
                    skips = 0
                    spiking = (
                        resilience.rollback_on_spike
                        and ema is not None
                        and ema_seen >= resilience.spike_min_steps
                        and abs(loss_f)
                        > resilience.spike_factor * max(abs(ema), 1e-12)
                    )
                    if spiking:
                        if (
                            ckpt is not None
                            and ckpt.latest_step() is not None
                            and rollbacks < resilience.max_rollbacks
                        ):
                            with led.measure("recovery"):
                                rollbacks += 1
                                # the restore target may be in flight
                                ckpt.wait()
                                state = ckpt.restore_latest(like=state)
                                i = int(state.step)
                                rec.record(
                                    "loss_spike_rollback", step=i,
                                    loss=loss_f, ema=ema,
                                    rollbacks=rollbacks,
                                )
                                reseek(i)
                                ema = None
                                ema_seen = 0
                                continue
                        rec.record(
                            "loss_spike", step=i + 1, loss=loss_f, ema=ema,
                        )
                    a = resilience.spike_ema_alpha
                    ema = (
                        loss_f if ema is None
                        else (1 - a) * ema + a * loss_f
                    )
                    ema_seen += 1
                if watchdog is not None:
                    with led.measure("telemetry"):
                        watchdog.probe(i + 1, loss, gnorm)
                    if watchdog.tripped:
                        with led.measure("recovery"):
                            escalate()
                if ckpt is not None:
                    ckpt.save(i + 1, state)
                i += 1
        if watchdog is not None:
            watchdog.flush()
            if watchdog.tripped:
                escalate()
        if ckpt is not None:
            if ckpt.latest_step() != cfg.steps:
                ckpt.save(cfg.steps, state, force=True)
            ckpt.wait()
    finally:
        compile_watch.stop()
        if hb_owned:
            heartbeat.stop()
        if sig_installed:
            # prev is None when the pre-fit handler was installed from C
            # (signal.getsignal convention) — restore the default then,
            # since None is not a valid handler argument.
            signal.signal(
                signal.SIGTERM,
                prev_sig if prev_sig is not None else signal.SIG_DFL,
            )
        if batches is not None:
            batches.close()
        metrics.close()
        if ckpt is not None:
            ckpt.close()
    return state, metrics.history


def evaluate(
    state: Any,
    dataset: Any,
    mesh: Any,
    rules: Rules,
    *,
    batch_size: int,
    num_batches: int,
    loss_fn: Callable[..., jax.Array] = next_token_loss,
    step_kwargs: dict[str, Any] | None = None,
) -> dict[str, float]:
    """Held-out evaluation: mean loss and perplexity over ``num_batches``.

    Walks batches 0..num_batches-1 in deterministic order through a jitted
    loss-only step on the training mesh (the batch loader is an infinite
    indexed stream, so the caller bounds the pass). ``state`` is used with
    whatever shardings it already carries — pass the state ``fit()`` (or
    ``sharded_train_state``) returned. Returns
    ``{"loss": ..., "perplexity": ..., "batches": ...}``.
    """
    loader = ShardedBatchLoader(dataset, mesh, batch_size, spec=("data",))
    n = num_batches
    if n <= 0:
        raise ValueError("evaluate() needs at least one batch")
    sample = loader.batch_at(0)
    eval_step = make_eval_step(
        mesh, rules, loss_fn=loss_fn, **(step_kwargs or {}),
    )
    total = 0.0
    for i in range(n):
        batch = sample if i == 0 else loader.batch_at(i)  # batch 0 already placed
        total += float(eval_step(state, batch))
    mean = total / n
    import math

    return {"loss": mean, "perplexity": math.exp(min(mean, 700.0)), "batches": n}
