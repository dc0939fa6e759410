"""build_mesh — topology-aware mesh construction through repro.compat.

The one function the rest of the pipeline calls: given a topology and a
logical mesh shape, pick (or accept) an :class:`~repro.topo.AxisAssignment`
and build the mesh with the device order that realizes it — the
``jax.experimental.mesh_utils`` contiguous-mesh trick, where each logical
axis's neighbours sit on the physical links assigned to it.  All mesh
construction goes through :func:`repro.compat.make_mesh` (Auto axis types).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from repro import compat

from .cost import CollectiveCostModel
from .topology import AxisAssignment, DeviceTopology

__all__ = ["build_mesh"]

# mirror repro.api.executor's axis names without importing the api layer
# (api imports topo lazily; keeping topo api-free avoids a cycle)
_DEFAULT_AXES = {1: ("parts",), 2: ("rows", "cols")}


def build_mesh(
    topology: DeviceTopology,
    mesh_shape: Sequence[int],
    axis_names: Optional[Sequence[str]] = None,
    *,
    assignment=None,
    intensity: Optional[dict] = None,
    devices=None,
) -> Tuple[object, Optional[AxisAssignment]]:
    """Build a mesh whose device order follows the topology.

    Args:
      topology: the physical :class:`~repro.topo.DeviceTopology`.
      mesh_shape: logical mesh shape, e.g. ``(R, C)``.
      axis_names: logical axis names (default ``("parts",)`` /
        ``("rows", "cols")`` by rank, matching ``repro.api.executor``).
      assignment: force a specific :class:`~repro.topo.AxisAssignment` (or
        its ``to_dict`` form) instead of choosing one — how ``repro.tune``
        builds one candidate per assignment and how ``plan_from_ir``
        re-realizes a recorded layout.
      intensity: relative network intensity per logical axis name (higher =
        more traffic), e.g. ``{"rows": load_bytes, "cols": merge_bytes}``.
        When no assignment is forced, the chosen one minimizes
        ``sum(intensity / bottleneck_bandwidth)`` — the mesh_utils /
        lingvo-partitioning idiom of mapping the network-intensive axis onto
        the fastest physical links.  Omitted: every axis weighs 1.0.
      devices: flat device list realizing an *abstract* topology (ignored
        when the topology carries its own device grid).

    Returns:
      ``(mesh, assignment)`` — the assignment actually used, or ``None``
      when the shape cannot be laid out contiguously (the mesh then uses
      plain flat order, exactly the pre-topology behaviour).
    """
    mesh_shape = tuple(int(s) for s in mesh_shape)
    if axis_names is None:
        axis_names = _DEFAULT_AXES.get(len(mesh_shape))
        if axis_names is None:
            raise ValueError(
                f"no default axis names for a rank-{len(mesh_shape)} mesh; "
                "pass axis_names="
            )
    axis_names = tuple(str(a) for a in axis_names)
    if assignment is not None:
        if isinstance(assignment, dict):
            assignment = AxisAssignment.from_dict(assignment)
        order = topology.device_order(assignment, devices=devices)
        return compat.make_mesh(mesh_shape, axis_names, devices=order), assignment

    cands = topology.assignments(mesh_shape, axis_names)
    if not cands:
        flat = topology.flat_devices() or (list(devices) if devices else None)
        if flat is not None:
            flat = flat[: int(np.prod(mesh_shape))]
        return compat.make_mesh(mesh_shape, axis_names, devices=flat), None

    model = CollectiveCostModel(topology)
    weights = {a: 1.0 for a in axis_names}
    if intensity:
        weights.update({str(k): float(v) for k, v in intensity.items()})

    def score(a: AxisAssignment) -> tuple:
        s = sum(
            model.group_cost(a.physical[i], weights[name])
            for i, name in enumerate(axis_names)
        )
        return (s, a.tag)

    assignment = min(cands, key=score)
    order = topology.device_order(assignment, devices=devices)
    return compat.make_mesh(mesh_shape, axis_names, devices=order), assignment
