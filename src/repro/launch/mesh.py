"""Production meshes (deliverable e).

Defined as FUNCTIONS so importing this module never touches jax device
state; ``dryrun.py`` sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import, tests/benches see the single real CPU device.

TPU v5e mapping (DESIGN.md §5): ``model`` is the axis the paper's
§3.2 weight and head partitions live on; ``data`` carries
batch (and the KV sequence for long_500k); ``pod`` is cross-pod data
parallelism (2 pods × 256 chips).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types over the first
    ``prod(shape)`` devices."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:math.prod(shape)])


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1) -> Mesh:
    """Small mesh over however many (possibly forced-host) devices exist
    — used by tests and examples."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(min(data, n // model), 1)
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The batch-carrying axes of a mesh ('pod' included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# Hardware constants (TPU v5e), used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12         # per chip
HBM_BW = 819e9                   # B/s per chip
ICI_BW = 50e9                    # B/s per link
CHIPS_PER_POD = 256
HBM_PER_CHIP = 16 * 2**30        # 16 GiB
