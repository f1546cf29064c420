"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips (v5e pod),
axes (data, model). Multi-pod: 2×16×16 = 512 chips, axes
(pod, data, model); the "pod" axis crosses DCN, so shardings place only
batch parallelism (and compressed gradient reduction) on it.

Every mesh of the repository is built by :func:`make_mesh`, with Auto
axis types: the sharding rules (launch/sharding.py), the shard_map'd
lookup and its cross-shard reduction are written for meshes whose
shardings the compiler propagates. ``jax.make_mesh`` alone now builds
Explicit axes, under which the reduction's ``take_along_axis`` and the
embedding gather refuse to trace.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Sequence | None = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Tiny mesh for CPU integration tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count≥n_data·n_model)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_lookup_mesh(n_devices: int | None = None):
    """1-axis ("data",) mesh over every visible device, for running the
    mesh-sharded cache lookup standalone (benchmarks, tests; 8-way under
    XLA_FLAGS=--xla_force_host_platform_device_count=8). On a production
    pod the lookup instead rides the axes of the production mesh picked
    by launch.sharding.LookupShardPolicy."""
    n = jax.device_count() if n_devices is None else n_devices
    return make_mesh((n,), ("data",))


# v5e hardware constants used by the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
