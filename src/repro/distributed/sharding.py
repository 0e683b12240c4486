"""Logical-axis sharding rules -> concrete PartitionSpecs.

Every operand dimension of the SNN runtime carries a *logical* name
(``executor._param_axes``); a rules table (:func:`snn_rules`) maps
logical names to the axes of a ``("data", "model")`` mesh, and
:func:`spec_for_shape` turns a shape's names into a ``PartitionSpec``
that divides it.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P


def placement_put(arr, device_index: int):
    """Pin an array to one device by index — the placement engine's put.

    The placement -> sharding bridge (:mod:`repro.placement.partition`)
    assigns every tiled projection a device; this is the primitive that
    realizes the assignment.  On a single visible device it is the
    **identity** (the same fallback contract as :func:`snn_mesh`
    returning ``None``), so CPU CI drives the full placement path with no
    actual data movement.
    """
    devices = jax.devices()
    if len(devices) <= 1:
        return arr
    if not 0 <= device_index < len(devices):
        raise ValueError(
            f"device index {device_index} outside 0..{len(devices) - 1}"
        )
    return jax.device_put(arr, devices[device_index])


def snn_rules() -> dict:
    """Logical-axis rules for the SNN runtime's fused executor.

    The SNN runtime names its dimensions after the paper's structures and
    maps them onto the standard 2-axis ``("data", "model")`` mesh:

        batch   -> "data"     # DP over requests (the micro-batch axis)
        neurons -> "model"    # TP of a layer's target population (the
                              # WDM's n_target rows — "subordinate PEs")
        rows    -> "model"    # serial synaptic rows split like the paper
                              # splits dense matrices across adjacent PEs
        steps   -> None       # the scan axis is never sharded
        cols    -> None       # WDM stacked-input columns stay whole so the
                              # ring gather needs no collective

    :func:`spec_for_shape` degrades any rule that does not divide a given
    tensor to replication, and :func:`snn_mesh` returns ``None`` on a
    single device — the identity fallback that keeps CPU CI running the
    exact same code path unsharded.
    """
    return {
        "batch": ("data",),
        "neurons": ("model",),
        "rows": ("model",),
        "steps": (),
        "cols": (),
        None: (),
    }


def snn_mesh(devices=None, *, model_axis: int = 1) -> Optional[Mesh]:
    """A ``("data", "model")`` mesh over the available devices.

    Returns ``None`` when only one device is visible — the caller treats
    that as the identity fallback (no placement, no constraints), so the
    sharded code path is exercised end-to-end on CPU CI without ever
    touching a collective.  ``model_axis`` carves that many devices out
    for tensor parallelism of large layers; the rest do data parallelism
    over the request batch.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) <= 1:
        return None
    if model_axis < 1 or len(devices) % model_axis != 0:
        raise ValueError(
            f"model_axis {model_axis} must divide device count {len(devices)}"
        )
    import numpy as np

    grid = np.array(devices).reshape(len(devices) // model_axis, model_axis)
    return Mesh(grid, ("data", "model"))


def _fit_axes(mesh_axes, dim: int, mesh: Mesh):
    """Longest prefix of mesh axes whose size product divides ``dim``.

    jit argument shardings must divide the dimension exactly; a rule that
    does not divide a given tensor (a 20-neuron population over a model
    axis of 8, a batch of 1) degrades to replication.
    """
    axes = tuple(m for m in mesh_axes if m is not None)
    while axes:
        size = 1
        for m in axes:
            size *= mesh.shape[m]
        if dim % size == 0:
            return axes
        axes = axes[:-1]
    return ()


def spec_for_shape(axes, rules, shape, mesh: Mesh) -> P:
    """The ``PartitionSpec`` of a ``shape`` whose dimensions carry the
    logical ``axes``: each rule that divides its dimension, and whose
    mesh axes no earlier dimension took, shards it; any other dimension
    is replicated."""
    parts = []
    used = set()  # a mesh axis may appear at most once per spec
    for dim, a in zip(shape, axes):
        fit = _fit_axes(rules.get(a, ()), int(dim), mesh)
        fit = tuple(m for m in fit if m not in used)
        used.update(fit)
        parts.append(fit[0] if len(fit) == 1 else (fit or None))
    return P(*parts)
