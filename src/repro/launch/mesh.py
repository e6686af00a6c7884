"""Production meshes.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis typed Auto. The model code annotates
    activations with sharding constraints and lets the partitioner place
    everything else; JAX's default axis type (Explicit) would instead
    demand an out-sharding on every op whose inputs share a mesh axis."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod: (data=16, model=16); two pods: (pod=2, data=16, model=16).

    "pod" composes with "data" for data parallelism (parallel/shard.py
    LOGICAL_RULES); "model" carries TP / sequence-CP / cache sharding.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for smoke tests/examples on CPU."""
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (roofline terms; EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~4 links usable per chip)
