"""Unified telemetry (observability layer): measurement + diagnosis.

Stage 1 (PR 1) — measurement, three pillars:

* :mod:`~.telemetry.spans` — nested structured spans with explicit
  device-sync points, bridged into ``jax.profiler.TraceAnnotation``
  (XProf) and exported as Chrome trace-event JSON (Perfetto) + JSONL;
* :mod:`~.telemetry.registry` — counters / gauges / fixed-bucket
  histograms with JSON snapshot and Prometheus text exposition;
* :mod:`~.telemetry.compile_watch` — recompilation + compile-time
  accounting, per-executable FLOPs/bytes, and the per-step collective
  inventory.

Stage 2 (PR 2) — diagnosis, four more:

* :mod:`~.telemetry.flight_recorder` — bounded ring of structured events
  (admissions, evictions, train steps, compiles, span closures) with a
  post-mortem ``dump()`` bundle on exception or demand;
* :mod:`~.telemetry.watchdog` — full-speed health probes: async on-device
  ``isfinite`` of loss/grad-norm, loss-spike EMA, a hang-flagging
  heartbeat thread, and NaN escalation via ``utils.profiling.checking``;
* :mod:`~.telemetry.devview` — per-device HBM watermarks vs the static
  ``MemoryPlan``, shard-imbalance audit, and per-mesh-axis collective
  byte attribution;
* :mod:`~.telemetry.slo` — streaming TTFT/TPOT/ITL/queue-wait percentile
  estimators and SLO targets with burn-rate counters, exported through
  the registry/Prometheus path;
* :mod:`~.telemetry.commscope` — the comm observatory: a calibration
  ladder of timed micro-collectives fitting per-axis α–β link profiles
  (persisted under ``analysis/profiles/``), per-source-line
  predicted-vs-measured collective attribution, and the compute /
  exposed-comm / overlapped-comm decomposition behind
  ``GoodputLedger.overlap_report``;
* :mod:`~.telemetry.economics` — round 20's workload observatory JOIN:
  per-tenant cost attribution over TraceStore critical paths ×
  GoodputLedger buckets × byte counters, with the tier-1-gated
  conservation invariant (Σ tenant device-seconds == fleet device
  bucket) and per-tenant SLO burn rates.

Round 14's goodput ledger (:mod:`~.telemetry.ledger`) partitions a loop's
wall into exclusive buckets; since PR 27 each frame the engine opens is
also a span on its tracer, and so on the profiler's host timeline:
``engine.step`` > ``engine.admission`` / ``engine.page_alloc`` /
``engine.h2d`` / ``engine.enqueue.<family>`` / ``engine.wait.<family>`` /
``engine.consume`` / ``engine.plan`` / ``engine.telemetry`` /
``engine.recovery`` (and ``engine.kv_handoff``, ``engine.swap`` outside a
step), with ``engine.refill`` / ``engine.decode`` / ``engine.mixed`` around
a whole dispatch. A span is finer than its bucket, never a new bucket (the
table is beside ``ContinuousEngine._led_device``). The same frames carry the
empty-device clock (``engine_device_starved_seconds_total{span=...}``), an
estimate of device idle on the host's clock; ``scripts/engine_breakdown.py``
reads both back from a post-mortem bundle.

Consumers: ``models.serving.ContinuousEngine`` (per-request span
timeline, queue/page-pool gauges, SLO feed, flight-recorder lifecycle
events), ``training.loop.fit`` + ``utils.metrics.MetricsLogger`` (same
registry, watchdog probes), ``bench.py`` (compile-vs-steady-state phase
breakdown + the diagnosis block), and ``cases/case18_observability.py``
/ ``cases/case19_diagnosis.py`` (the end-to-end drivers).
"""

from learning_jax_sharding_tpu.telemetry.commscope import (  # noqa: F401
    AxisProfile,
    CommProfile,
    attribute_measured_seconds,
    calibrate_mesh,
    decompose_overlap,
    fit_alpha_beta,
    fit_axis_profiles,
    run_ladder,
)
from learning_jax_sharding_tpu.telemetry.compile_watch import (  # noqa: F401
    CompileWatch,
    WatchedFunction,
    cache_size,
    executable_report,
    watched,
)
from learning_jax_sharding_tpu.telemetry.devview import (  # noqa: F401
    axis_collective_volume,
    device_memory_stats,
    memory_report,
    shard_imbalance,
)
from learning_jax_sharding_tpu.telemetry.economics import (  # noqa: F401
    ATTRIBUTION_POLICY,
    OVERHEAD_TENANT,
    UNTAGGED_TENANT,
    CostRates,
    deterministic_view,
    fleet_economics,
    write_economics,
)
from learning_jax_sharding_tpu.telemetry.flight_recorder import (  # noqa: F401
    FlightRecorder,
    artifact_dir,
    default_flight_recorder,
)
from learning_jax_sharding_tpu.telemetry.ledger import (  # noqa: F401
    BUCKETS,
    GoodputLedger,
)
from learning_jax_sharding_tpu.telemetry.registry import (  # noqa: F401
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    escape_label_value,
    labeled_name,
)
from learning_jax_sharding_tpu.telemetry.slo import (  # noqa: F401
    SLOMonitor,
    SLOTarget,
    StreamingPercentile,
)
from learning_jax_sharding_tpu.telemetry.spans import (  # noqa: F401
    Tracer,
    default_tracer,
    device_sync,
)
from learning_jax_sharding_tpu.telemetry.tracecontext import (  # noqa: F401
    STAGES,
    TraceStore,
    merge_tracers,
)
from learning_jax_sharding_tpu.telemetry.watchdog import (  # noqa: F401
    Heartbeat,
    NonFiniteError,
    Watchdog,
    localize_nan,
)
