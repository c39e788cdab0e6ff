"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips. `data` is the ESP
sequence-parallel axis between elastic instances; `model` is intra-instance
tensor parallelism (DESIGN.md §3).
Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips; `pod` is a
pure replica/data axis (ESP rings never cross pods; ICI stays intra-pod).

Every axis is ``Auto``: the SPMD code places arrays with
`with_sharding_constraint` and plain indexing, which explicit-sharding axes
(`jax.make_mesh`'s default in the installed JAX) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 4, model: int = 2, pod: int = 0):
    """Small host-device mesh for CPU tests (XLA_FLAGS device count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
