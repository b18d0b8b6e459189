"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls these.
"""
from __future__ import annotations

import jax

# TPU v5e-class hardware constants used across the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip, bf16
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per chip, one direction)
HBM_PER_CHIP = 16e9             # bytes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh on the available device(s) — for CPU tests/examples."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def make_cell_mesh(n_cells: int):
    """("cell",)-axis mesh for the cell-sharded decision scan
    (hierarchical scheduling): one device per cell. Returns None when
    the host lacks the devices — callers fall back to the
    bitwise-identical single-program cell emulation, so a CPU box (one
    device by default; more via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) runs the
    same logical decision without the collectives.

    The axis is Auto: only the scan's `shard_map` is sharded by hand,
    and the hot path's telemetry scatters around it must not carry the
    cell sharding in their types."""
    if n_cells <= 1 or jax.device_count() < n_cells:
        return None
    return jax.make_mesh((n_cells,), ("cell",),
                         axis_types=(jax.sharding.AxisType.Auto,))
