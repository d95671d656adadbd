"""Mesh construction over real TPU topology or emulated CPU devices.

This is layer L1 of the framework (see SURVEY.md §1). The reference builds its
meshes ad hoc at the top of each script (`/root/reference/case1a.py:15`,
`/root/reference/case6_attention.py:155-156`) after forcing emulated host
devices via ``XLA_FLAGS`` (`/root/reference/case1a.py:2-3`). Here both concerns
become real API:

* :func:`build_mesh` — an ICI-topology-aware mesh over whatever devices exist
  (real TPU chips in production, emulated CPU devices in tests).
* :func:`force_emulated_devices` — the reference's device-count hack as a
  checked, documented function usable before the backend initializes.

Axis-name conventions used throughout the framework:

* ``"data"``  — batch (data-parallel) axis.
* ``"model"`` — tensor/model-parallel axis.
* extra axes (``"fsdp"``, ``"seq"``, ``"stage"``, ``"expert"``) are supported by
  :func:`build_mesh`; the logical-axis layer maps onto whatever names the mesh
  declares.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re
import warnings
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"

#: Default 2D mesh axis names, matching the reference's
#: ``Mesh(..., ('data', 'model'))`` (`/root/reference/case6_attention.py:155-156`).
DEFAULT_AXIS_NAMES: tuple[str, ...] = (DATA_AXIS, MODEL_AXIS)


def force_emulated_devices(n: int, *, platform: str = "cpu") -> None:
    """Force ``n`` emulated host devices, before the JAX backend initializes.

    The reference does this with a raw env-var assignment that must precede
    ``import jax`` (`/root/reference/case1a.py:2-3`). JAX only reads the flag
    when the backend client is created, so it is enough to set it before the
    first device access — which lets this live in a function instead of a
    module preamble.

    Raises:
        RuntimeError: if the backend is already initialized with a different
            device count (the flag would be silently ignored).
    """
    flag = f"--xla_force_host_platform_device_count={n}"
    had_flags = "XLA_FLAGS" in os.environ
    existing = os.environ.get("XLA_FLAGS", "")
    prev_platform = jax.config.jax_platforms
    if "--xla_force_host_platform_device_count" in existing:
        updated = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, existing
        )
    else:
        updated = (existing + " " + flag).strip()
    os.environ["XLA_FLAGS"] = updated
    jax.config.update("jax_platforms", platform)
    devices = jax.devices()
    if len(devices) != n:
        # Don't leak the failed configuration into process env / subprocesses.
        if had_flags:
            os.environ["XLA_FLAGS"] = existing
        else:
            del os.environ["XLA_FLAGS"]
        jax.config.update("jax_platforms", prev_platform)
        raise RuntimeError(
            f"requested {n} emulated {platform} devices but backend already "
            f"initialized with {len(devices)}; call force_emulated_devices() "
            "before any other JAX device access in the process"
        )


def _infer_shape(n_devices: int, ndim: int) -> tuple[int, ...]:
    """Pick a balanced ``ndim``-D factorization of ``n_devices``.

    Prefers near-square factorizations (e.g. 8 → (2, 4), 16 → (4, 4)) so that
    both mesh axes get parallelism by default.
    """
    if ndim == 1:
        return (n_devices,)
    if ndim != 2:
        raise ValueError(f"automatic shape inference supports 1D/2D, got ndim={ndim}")
    best = (1, n_devices)
    for a in range(1, int(math.isqrt(n_devices)) + 1):
        if n_devices % a == 0:
            best = (a, n_devices // a)
    return best


def build_mesh(
    shape: Sequence[int] | None = None,
    axis_names: Sequence[str] = DEFAULT_AXIS_NAMES,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a :class:`jax.sharding.Mesh` over the available devices.

    On TPU, ``mesh_utils.create_device_mesh`` orders devices so neighboring
    mesh coordinates are ICI neighbors — collectives along a mesh axis then
    ride the intra-slice interconnect rather than hopping hosts. On CPU
    emulation the ordering is arbitrary but the mesh is shape-identical, which
    is what the tests rely on.

    Args:
        shape: mesh shape, e.g. ``(2, 4)``. ``None`` infers a balanced shape
            over all devices with ``len(axis_names)`` dimensions.
        axis_names: one name per mesh dimension.
        devices: explicit device list (defaults to ``jax.devices()``).

    Returns:
        A ``Mesh`` usable as a context manager and in ``NamedSharding``.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if shape is None:
        shape = _infer_shape(len(devices), len(axis_names))
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axis_names):
        raise ValueError(f"shape {shape} rank != axis_names {tuple(axis_names)} rank")
    n = math.prod(shape)
    if n > len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, have {len(devices)}")
    if n < len(devices):
        warnings.warn(
            f"mesh shape {shape} uses only {n} of {len(devices)} devices; "
            "the rest stay idle",
            stacklevel=2,
        )
        devices = devices[:n]
    # No reshape fallback: on a TPU host a mesh that does not follow the ICI
    # topology is an error, and for CPU devices create_device_mesh is itself
    # a plain reshape.
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, tuple(axis_names))


def build_hybrid_mesh(
    ici_shape: Sequence[int],
    dcn_shape: Sequence[int],
    axis_names: Sequence[str] = DEFAULT_AXIS_NAMES,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a mesh spanning multiple TPU slices: ICI inside, DCN between.

    A multi-slice pod has two interconnect tiers — ICI within each slice
    (fast, the torus) and DCN between slices (slower, the datacenter
    network). Mesh axis ``k`` gets size ``dcn_shape[k] * ici_shape[k]``,
    slice-major, so an axis that is 1 in ``ici_shape`` varies ONLY across
    slices: putting data parallelism there and tensor parallelism on an
    axis that is 1 in ``dcn_shape`` keeps the per-step TP collectives on
    ICI and sends only the once-per-step gradient all-reduce over DCN —
    the standard multi-slice layout.

    Example (2 slices of 4 chips, DP across slices, TP within)::

        mesh = build_hybrid_mesh(ici_shape=(1, 4), dcn_shape=(2, 1))
        # → Mesh('data': 2, 'model': 4)

    On real TPU, ``mesh_utils.create_hybrid_device_mesh`` reads slice ids
    and ICI coordinates from the devices; under CPU emulation (no slice
    metadata) the same slice-major layout is reproduced by index, devices
    ``[0..n/slices)`` forming slice 0, etc.
    """
    ici_shape, dcn_shape = tuple(ici_shape), tuple(dcn_shape)
    axis_names = tuple(axis_names)
    if len(ici_shape) != len(axis_names) or len(dcn_shape) != len(axis_names):
        raise ValueError(
            f"ici_shape {ici_shape} / dcn_shape {dcn_shape} rank must match "
            f"axis_names {axis_names} rank"
        )
    devices = list(jax.devices()) if devices is None else list(devices)
    n = math.prod(ici_shape) * math.prod(dcn_shape)
    if n != len(devices):
        raise ValueError(
            f"hybrid mesh ici{ici_shape}×dcn{dcn_shape} needs exactly {n} "
            f"devices, have {len(devices)}"
        )
    try:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
    except (ValueError, AssertionError, NotImplementedError, KeyError):
        if devices[0].platform != "cpu":
            raise
        # Slice-major by index: reshape to (dcn…, ici…), interleave each
        # (dcn_k, ici_k) pair, merge — mesh[k] then iterates slices outer,
        # in-slice devices inner, matching create_hybrid_device_mesh.
        rank = len(axis_names)
        arr = np.asarray(devices).reshape(dcn_shape + ici_shape)
        perm = [x for k in range(rank) for x in (k, rank + k)]
        dev_array = arr.transpose(perm).reshape(
            tuple(d * i for d, i in zip(dcn_shape, ici_shape))
        )
    return Mesh(dev_array, axis_names)


def single_device_mesh(axis_names: Sequence[str] = DEFAULT_AXIS_NAMES) -> Mesh:
    """Degenerate mesh with every axis of size 1 on the default device.

    Lets every sharded program in the framework run unchanged on one chip —
    the bring-up path for the single-TPU environment (SURVEY.md §7 step 6).
    """
    shape = (1,) * len(axis_names)
    return Mesh(np.asarray([jax.devices()[0]]).reshape(shape), tuple(axis_names))


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh description, for configs and checkpoint metadata.

    The reference hard-codes mesh shapes inline (`/root/reference/case1a.py:15`,
    `/root/reference/case6_attention.py:155`); this is the config-system
    equivalent (SURVEY.md §5 "Config / flag system").
    """

    shape: tuple[int, ...]
    axis_names: tuple[str, ...] = DEFAULT_AXIS_NAMES

    def build(self, devices: Sequence[jax.Device] | None = None) -> Mesh:
        return build_mesh(self.shape, self.axis_names, devices=devices)

    @property
    def size(self) -> int:
        return math.prod(self.shape)
