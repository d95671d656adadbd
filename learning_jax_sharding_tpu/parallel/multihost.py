"""Multi-host bootstrap and per-host data feeding (SURVEY.md §2.5 item b).

The reference is strictly single-process: it emulates N devices inside one
host (`/root/reference/case1a.py:2-3`) and never calls
``jax.distributed.initialize`` (SURVEY.md §2.5: "no multi-process runtime").
Scaling the same GSPMD programs across a real multi-host TPU slice (or across
slices over DCN) needs exactly two additions, and this module is them:

1. :func:`initialize` — bring up the JAX distributed runtime so all hosts
   form one system: ``jax.devices()`` then returns the GLOBAL device list and
   every jitted sharded program runs as one SPMD computation, with XLA
   routing intra-slice collectives over ICI and cross-slice traffic over DCN.
   On TPU all coordinates are discovered from the environment, so the
   zero-argument call is the whole bootstrap.

2. :func:`host_local_batch` — the single-controller illusion for input data:
   each host loads only ITS batch rows from its data shard, and the pieces
   are assembled into one global :class:`jax.Array` without any host ever
   materializing the full batch
   (``jax.make_array_from_process_local_data``).

Everything else in the framework — mesh building, logical rules, the
sharded-init/train pipeline — is already multi-host clean because it only
speaks global shapes and ``NamedSharding``.

Single-process environments (tests, the one-chip TPU here) run the same code
with ``process_count() == 1``; nothing in this module requires a cluster.
"""

from __future__ import annotations

from typing import Any, Iterator, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_ids: Sequence[int] | None = None,
) -> None:
    """Bring up the JAX distributed runtime (idempotent).

    On TPU pods every argument is discovered from the TPU environment —
    call with no arguments. On CPU/GPU clusters pass the coordinator's
    ``host:port``, the world size, and this process's rank (mirrors
    ``jax.distributed.initialize``; see that for semantics).

    Safe to call when already initialized (no-op) and in single-process runs
    (``num_processes=1`` explicitly, or TPU metadata saying so).
    """
    # IMPORTANT: nothing here may touch the backend (jax.process_count(),
    # jax.devices(), …) before the distributed client exists —
    # jax.distributed.initialize refuses to run once any JAX computation has
    # initialized the runtime (caught by tests/test_distributed_cluster.py).
    if jax.distributed.is_initialized():
        return  # a cluster is already up
    kwargs: dict[str, Any] = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    try:
        jax.distributed.initialize(**kwargs)
    except (ValueError, RuntimeError):
        # No cluster metadata to discover (plain single-process run): fine —
        # the rest of the module works with process_count() == 1, and a later
        # call with real coordinates simply retries (failures are NOT cached:
        # caching one would turn that later genuine bootstrap into a silent
        # no-op and hang the peer ranks in rendezvous). A real multi-process
        # request must not be swallowed.
        if num_processes not in (None, 1):
            raise


def process_count() -> int:
    """Number of participating hosts (1 in single-controller runs)."""
    return jax.process_count()


def process_index() -> int:
    """This host's rank in the cluster (0 in single-controller runs)."""
    return jax.process_index()


def is_primary() -> bool:
    """True on exactly one host — gate logging/checkpoint-metadata writes."""
    return jax.process_index() == 0


def local_batch_slice(global_batch: int) -> slice:
    """The half-open row range of the global batch this host must load.

    With the batch dim sharded over mesh axes whose devices are distributed
    across hosts, host ``i`` owns an equal contiguous slice (JAX process
    indices order hosts the same way ``mesh_utils`` orders their devices).
    """
    n = jax.process_count()
    if global_batch % n:
        raise ValueError(
            f"global batch {global_batch} not divisible by process count {n}"
        )
    per = global_batch // n
    i = jax.process_index()
    return slice(i * per, (i + 1) * per)


def host_local_batch(
    local_data: Any,
    mesh: Mesh,
    spec: PartitionSpec | Sequence[str | None],
) -> Any:
    """Assemble per-host numpy batches into global sharded ``jax.Array``s.

    Args:
        local_data: pytree of numpy arrays holding THIS host's rows (the
            :func:`local_batch_slice` portion of the global batch).
        mesh: the (global) device mesh.
        spec: partition spec of the GLOBAL array (e.g. ``P("data")`` for a
            batch-sharded input), applied to every tree leaf.

    Returns:
        Pytree of global ``jax.Array``s; each host contributed only its local
        shards — no host ever holds the whole batch
        (``jax.make_array_from_process_local_data``).
    """
    spec = spec if isinstance(spec, PartitionSpec) else PartitionSpec(*spec)
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(
        lambda leaf: jax.make_array_from_process_local_data(
            sharding, np.asarray(leaf)
        ),
        local_data,
    )


def allgather_registry_snapshots(registry: Any) -> dict:
    """Merge every host's metrics-registry snapshot into one report.

    Each host JSON-serializes its ``registry.snapshot()``; the byte
    payloads are allgathered (length-padded — snapshots differ per host)
    and every host returns the same merged view:

    * ``"hosts"`` — the per-host snapshots, indexed by process rank;
    * ``"merged"`` — one fleet dict: plain numbers SUMMED (counters
      become fleet totals; gauges sum too — per-host queue depths add up
      to the fleet's), ``*__high_water`` keys take the MAX, histogram
      dicts merge bucket-wise (buckets must match — they come from the
      same code).

    Every host must call this collectively (the usual SPMD contract);
    single-process runs skip the collective entirely, so the helper is
    free in tests and on the one-chip TPU.
    """
    import json

    snap = registry.snapshot()
    n = jax.process_count()
    if n == 1:
        per_host = [snap]
    else:  # pragma: no cover - exercised only on real multi-host slices
        from jax.experimental import multihost_utils

        payload = np.frombuffer(
            json.dumps(snap).encode("utf-8"), dtype=np.uint8
        )
        lengths = multihost_utils.process_allgather(
            np.array([payload.size], np.int64)
        ).reshape(-1)
        padded = np.zeros((int(lengths.max()),), np.uint8)
        padded[: payload.size] = payload
        gathered = multihost_utils.process_allgather(padded)
        per_host = [
            json.loads(bytes(gathered[i, : int(lengths[i])]).decode("utf-8"))
            for i in range(n)
        ]
    return {
        "process_count": n,
        "hosts": per_host,
        "merged": merge_registry_snapshots(per_host),
    }


def merge_registry_snapshots(
    per_host: Sequence[dict], *, labels: Sequence[str] | None = None
) -> dict:
    """The fleet-merge rule for registry snapshots (see
    :func:`allgather_registry_snapshots` for the semantics).

    ``labels`` (one per snapshot — process ranks, or fleet REPLICA names,
    round 11) adds a per-source label dimension: alongside the unlabeled
    merge (bit-compatible with the labels-free call — counters summed,
    high-waters maxed, histograms bucket-wise), every metric also appears
    under ``'name{replica="<label>"}'`` carrying that source's OWN value,
    so a fleet dashboard can tell replicas apart while scrapes of the
    summed series keep working unchanged.
    ``telemetry.registry.snapshot_prometheus_text`` renders the labeled
    keys as real Prometheus labels.
    """
    if labels is not None and len(labels) != len(per_host):
        raise ValueError(
            f"{len(labels)} labels for {len(per_host)} snapshots"
        )

    def copy_of(v):
        return (
            {
                "buckets": list(v["buckets"]),
                "counts": list(v["counts"]),
                "sum": v["sum"],
                "count": v["count"],
            }
            if isinstance(v, dict) else v
        )

    merged: dict = {}
    for host_snap in per_host:
        for k, v in host_snap.items():
            if k not in merged:
                merged[k] = copy_of(v)
            elif isinstance(v, dict):
                m = merged[k]
                m["counts"] = [a + b for a, b in zip(m["counts"], v["counts"])]
                m["sum"] += v["sum"]
                m["count"] += v["count"]
            elif k.endswith("__high_water"):
                merged[k] = max(merged[k], v)
            else:
                merged[k] += v
    if labels is not None:
        for label, host_snap in zip(labels, per_host):
            # Prometheus label-value escaping (backslash first). Keys
            # that already carry labels (the goodput ledger's
            # 'name{bucket="..."}' series, per-stage trace histograms)
            # get the replica label SPLICED into the existing set —
            # 'name{bucket="x",replica="r0"}', one well-formed label
            # set. A key already carrying replica= is the output of a
            # previous labeled merge: re-labeling it would nest label
            # dimensions, so that still raises.
            esc = str(label).replace("\\", "\\\\").replace('"', '\\"')
            for k, v in host_snap.items():
                if "{" in k:
                    if 'replica="' in k:
                        raise ValueError(
                            f"snapshot key {k!r} already carries a "
                            "replica label — merge raw registry "
                            "snapshots, not a labeled merge"
                        )
                    key = f'{k[:-1]},replica="{esc}"}}'
                else:
                    key = f'{k}{{replica="{esc}"}}'
                merged[key] = copy_of(v)
    return merged


def sharded_batches(
    it: Iterator[Any],
    mesh: Mesh,
    spec: PartitionSpec | Sequence[str | None],
) -> Iterator[Any]:
    """Wrap a host-local batch iterator into a global sharded-array iterator.

    ``it`` must yield this host's rows only (see :func:`local_batch_slice`);
    every host must pull the same number of batches in lockstep (the usual
    SPMD data-loader contract).
    """
    for local in it:
        yield host_local_batch(local, mesh, spec)
