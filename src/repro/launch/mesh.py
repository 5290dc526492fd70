"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state -- smoke tests and benches see the real device
count, only dryrun.py forces 512 host devices.
"""

from __future__ import annotations

import jax

from ..config import ParallelConfig


def _auto_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; (2, 16, 16) = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(parallel: ParallelConfig):
    """Mesh matching a ParallelConfig (used by elastic restart to rebuild a
    smaller mesh after node loss)."""
    if parallel.pods > 1:
        shape = (parallel.pods, parallel.data, parallel.model)
        axes = ("pod", "data", "model")
    else:
        shape = (parallel.data, parallel.model)
        axes = ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(max_devices: int | None = None):
    """Best-effort mesh over whatever devices exist (CPU smoke runs: 1
    device -> 1x1 mesh).  Used by examples and integration tests."""
    n = len(jax.devices()) if max_devices is None else min(
        max_devices, len(jax.devices()))
    model = 1
    for m in (4, 2, 1):
        if n % m == 0 and n >= m:
            model = m
            break
    return _auto_mesh((n // model, model), ("data", "model"))


#: XLA flags a real TPU launch would set for compute/comm overlap (no-ops on
#: CPU; documented in DESIGN.md §5 -- the launch scripts export these).
TPU_PERF_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_megacore_fusion_allow_ags=true "
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
)
