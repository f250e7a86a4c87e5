"""Production mesh construction.

Single pod: (data=16, model=16) — 256 TPU v5e chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the
``pod`` axis carries cross-site aggregation (Caltech/JPL in the paper's ACN
setting — DESIGN.md §3).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""

from __future__ import annotations

import jax

# Canonical production mesh shapes, keyed by the dry-run's mesh name.
# Single source of truth for mesh construction AND the analytic comm
# cross-checks (benchmarks/roofline.py, repro.dist.fed).
PRODUCTION_MESH_SHAPES = {
    "single": {"data": 16, "model": 16},
    "multi": {"pod": 2, "data": 16, "model": 16},
}


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types (sharding left to the partitioner
    outside explicit shard_map regions)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    spec = PRODUCTION_MESH_SHAPES["multi" if multi_pod else "single"]
    return _make_mesh(tuple(spec.values()), tuple(spec))


def make_host_mesh(*, model: int = 1):
    """Whatever this host actually has (CPU smoke / examples)."""
    n = len(jax.devices())
    model = min(model, n)
    return _make_mesh((n // model, model), ("data", "model"))


# v5e hardware constants for the roofline (EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
