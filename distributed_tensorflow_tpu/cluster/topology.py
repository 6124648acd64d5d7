"""TPU topology and device-mesh construction.

Behavioral model: ``$TF/python/tpu/topology.py:41`` (``Topology``) and
``device_assignment.py:70`` (``DeviceAssignment``) — device coordinates and
logical→physical mapping (SURVEY.md §3.3).  In JAX the equivalent artifact is
a ``jax.sharding.Mesh``: a named, N-dimensional arrangement of devices that
shardings and collectives refer to by axis name.

Canonical mesh axes (every parallelism form is a named axis; SURVEY.md §8):

- ``data``     pure data parallelism (gradient allreduce; MWMS equivalent)
- ``fsdp``     data parallelism with sharded params/optimizer (ZeRO-3 style)
- ``tensor``   tensor/model parallelism (megatron-style within attention/MLP)
- ``pipe``     pipeline stages (net-new vs reference, SURVEY.md §3.1 "PP")
- ``context``  sequence/context parallelism (ring attention KV rotation)
- ``expert``   expert / embedding-shard parallelism (PS-embedding equivalent)

Axes of size 1 are kept in the mesh so sharding rules can always name them;
XLA elides trivial collectives, so unused axes are free.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import AxisType, Mesh

logger = logging.getLogger(__name__)


def _make_mesh(dev_array: np.ndarray) -> Mesh:
    """Mesh over ``MESH_AXES`` with Auto axis types (GSPMD-driven)."""
    return Mesh(
        dev_array, MESH_AXES, axis_types=(AxisType.Auto,) * len(MESH_AXES)
    )

# Order matters: outer→inner. ``data`` outermost maps replicas across hosts
# (gradient allreduce rides DCN between slices at worst), while ``tensor`` and
# ``context`` innermost keep their heavy collectives on the ICI torus — the
# scaling-book layout recipe.
MESH_AXES: Tuple[str, ...] = ("data", "fsdp", "tensor", "pipe", "context", "expert")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape over the global device set.

    Any axis left at 1 is inert. ``data=-1`` means "absorb all remaining
    devices" (the common case: shard everything else explicitly, data-parallel
    over whatever is left).
    """

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    pipe: int = 1
    context: int = 1
    expert: int = 1

    def axis_sizes(self, num_devices: int) -> Dict[str, int]:
        sizes = {a: getattr(self, a) for a in MESH_AXES}
        bad = {a: s for a, s in sizes.items() if s != -1 and s < 1}
        if bad:
            raise ValueError(
                f"Mesh axis sizes must be -1 (wildcard) or >= 1, got {bad}"
            )
        fixed = math.prod(s for s in sizes.values() if s != -1)
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one axis may be -1, got {wild}")
        if wild:
            if num_devices % fixed != 0:
                fixed_sizes = {a: s for a, s in sizes.items() if s > 1}
                raise ValueError(
                    f"Cannot factor {num_devices} device(s): the fixed mesh "
                    f"axes {fixed_sizes or '{}'} need a multiple of {fixed} "
                    f"devices (axis {wild[0]!r} absorbs the remainder)"
                )
            sizes[wild[0]] = num_devices // fixed
        elif fixed != num_devices:
            raise ValueError(
                f"Mesh {sizes} needs {fixed} devices but {num_devices} present"
            )
        return sizes

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        return build_mesh(self, devices)


def build_mesh(
    config: MeshConfig = MeshConfig(),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh over ``devices`` (default: all global devices).

    Uses ``mesh_utils.create_device_mesh`` so physical ICI topology (the v5e
    2D torus / pod 3D torus) is honored when assigning logical coordinates —
    the role TF's ``device_assignment()`` ($TF/python/tpu/device_assignment.py:343)
    plays for tpu.replicate.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    sizes = config.axis_sizes(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)
    if len(devices) == 1:
        dev_array = np.array(devices).reshape(shape)
    else:
        try:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=devices, allow_split_physical_axes=True
            )
            assignment = "create_device_mesh (physical topology)"
        except (ValueError, NotImplementedError) as e:
            # Shapes the topology-aware assignment cannot place.  On a real
            # multi-chip host this costs ICI locality, so it must be seen.
            level = (logging.WARNING if devices[0].platform == "tpu"
                     else logging.INFO)
            logger.log(level, "create_device_mesh refused mesh %s (%s); "
                       "using row-major device order", shape, e)
            dev_array = np.array(devices).reshape(shape)
            assignment = "row-major"
        logger.info(
            "mesh %s assigned by %s; device ids %s",
            {a: n for a, n in sizes.items() if n > 1}, assignment,
            [d.id for d in dev_array.flat])
    return _make_mesh(dev_array)


def build_hybrid_mesh(
    config: MeshConfig = MeshConfig(),
    *,
    dcn_data_parallelism: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Multi-slice mesh: the ``data`` axis spans slices over DCN, every other
    axis stays inside a slice on ICI (SURVEY.md §8 PR8; the scaling-book
    layout — cross-slice traffic is only the gradient allreduce).

    ``dcn_data_parallelism`` defaults to the number of slices
    (``device.slice_index`` granularity).  Three granule sources, in order:

    1. TPU pods: ``device.slice_index`` (real DCN slices).
    2. Multi-process CPU/test clusters: one granule per PROCESS
       (``process_is_granule`` — the cross-process axis plays DCN, exactly
       the tier-(c) localhost-cluster topology).
    3. Single-process with explicit ``dcn_data_parallelism``: contiguous
       device groups as pseudo-slices (structural: lets the virtual-mesh
       tests and the driver dryrun execute the hybrid layout's collective
       pattern without hardware slices).

    On single-slice platforms without an explicit count this degrades to
    ``build_mesh`` exactly.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    have_slice_ids = any(hasattr(d, "slice_index") for d in devices)
    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    n_processes = len({d.process_index for d in devices})
    if dcn_data_parallelism is not None:
        n_slices = dcn_data_parallelism
    elif have_slice_ids:
        # TPU: the real slice structure (multi-host single-slice pods keep
        # slice_index == 0 everywhere and correctly degrade to one slice).
        n_slices = len(slice_ids)
    else:
        # CPU test clusters: processes are the only DCN-like boundary.
        n_slices = n_processes
    if n_slices <= 1:
        return build_mesh(config, devices)
    sizes = config.axis_sizes(len(devices))
    if sizes["data"] % n_slices:
        if dcn_data_parallelism is None:
            # Inferred granules that the requested layout cannot span (e.g.
            # data=1 with fsdp-only parallelism on a 2-process cluster):
            # keep the documented degrade instead of refusing a layout the
            # caller never asked to slice.
            return build_mesh(config, devices)
        raise ValueError(
            f"data axis ({sizes['data']}) must be divisible by the DCN "
            f"slice count ({n_slices}): cross-slice parallelism rides the "
            "data axis"
        )
    ici_shape = dict(sizes, data=sizes["data"] // n_slices)
    dcn_shape = {a: (n_slices if a == "data" else 1) for a in MESH_AXES}
    shape = tuple(ici_shape[a] for a in MESH_AXES)
    dcn = tuple(dcn_shape[a] for a in MESH_AXES)
    if have_slice_ids and len(slice_ids) == n_slices:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            shape, dcn, devices=devices, allow_split_physical_axes=True,
        )
    elif n_processes == n_slices and n_processes > 1:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            shape, dcn, devices=devices, process_is_granule=True,
            allow_split_physical_axes=True,
        )
    else:
        # Pseudo-slices: contiguous groups, each laid out as one ICI mesh,
        # stacked along the data axis (granule attrs unavailable).
        per = len(devices) // n_slices
        data_ax = MESH_AXES.index("data")
        groups = []
        for s in range(n_slices):
            part = np.array(devices[s * per:(s + 1) * per]).reshape(shape)
            groups.append(part)
        dev_array = np.concatenate(groups, axis=data_ax)
    return _make_mesh(dev_array)


def single_axis_mesh(
    axis: str = "data", devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """All devices on one named axis (pure-DP MultiWorkerMirrored shape)."""
    overrides = {} if axis == "data" else {"data": 1, axis: -1}
    return build_mesh(MeshConfig(**overrides), devices)


def device_summary() -> Dict[str, Any]:
    """The device a result line ran on, as JAX reports it.  Every JSON
    line the entry points print carries this, so a shrunken CPU run can
    never be read as a chip run."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@dataclasses.dataclass(frozen=True)
class Topology:
    """Summary of the physical device topology, TF-Topology-shaped."""

    num_devices: int
    num_hosts: int
    devices_per_host: int
    platform: str
    device_kind: str

    @classmethod
    def detect(cls) -> "Topology":
        devs = jax.devices()
        return cls(
            num_devices=len(devs),
            num_hosts=jax.process_count(),
            devices_per_host=len(jax.local_devices()),
            platform=devs[0].platform,
            device_kind=devs[0].device_kind,
        )
