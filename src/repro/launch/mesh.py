"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod : (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
data parallelism across pods (DCN) — only gradient all-reduces cross it.

Defined as a function so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis of Auto type."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over however many devices exist (tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]
