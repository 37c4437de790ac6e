"""Meshes.

Every mesh in the repo is built by ``make_mesh`` here, so every axis is
``Auto``: since JAX 0.9 ``jax.make_mesh`` makes ``Explicit`` axes by
default, and the repo's sharding (``with_sharding_constraint`` rules,
``shard_map`` over a 2-D layout, sharded GEMMs left to the partitioner) is
written for ``Auto`` axes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else sees the real topology.

Target: TPU v5e pods, 256 chips/pod (16x16), 2 pods for the multi-pod
dry-run.  Axes: ("data", "model") intra-pod; the "pod" axis is the outer
data-parallel axis across the DCN.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """(data, model) mesh over whatever devices exist; ``model_parallel``
    falls back to 1 when it does not divide the device count."""
    n = len(jax.devices())
    if n % model_parallel:
        model_parallel = 1
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


# Hardware constants for the roofline model (TPU v5e per chip).
PEAK_BF16_FLOPS = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link (single-link bottleneck model)
HBM_BYTES = 16 * 1024**3      # 16 GiB
