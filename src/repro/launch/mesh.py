"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.

Mesh semantics (DESIGN.md §6): ``model`` is the intra-node tensor/expert
axis (dense ICI); ``data`` is batch/FSDP; ``pod`` is the cross-pod axis —
in the constellation analogy, node groups along (pod, data) are satellites
and the TDM relation schedules their exchanges.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()[:need]
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            f"run under XLA_FLAGS=--xla_force_host_platform_device_count={need}"
        )
    return _auto_mesh(shape, axes, devices)


def _auto_mesh(shape, axes, devices):
    # every axis Auto: shardings here are hints the compiler propagates
    # (with_sharding_constraint, shard_map specs), not explicit-mode types
    return jax.make_mesh(
        shape,
        axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    need = math.prod(shape)
    devices = jax.devices()[:need]
    if len(devices) < need:
        raise RuntimeError(f"mesh {shape} needs {need} devices")
    return _auto_mesh(shape, axes, devices)


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link direction
