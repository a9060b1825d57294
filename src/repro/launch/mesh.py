"""Production mesh factory.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so the factory can build the (2, 16, 16) multi-pod mesh on CPU.

Every axis is ``Auto``: the sharding rules in distributed/sharding.py
place arrays with ``NamedSharding``s and let the partitioner do the rest.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, examples, degraded pools)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
