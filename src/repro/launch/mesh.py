"""Production mesh construction (assignment spec, MULTI-POD DRY-RUN §1).

A function, not a module-level constant, so importing this module never
touches jax device state. Axis roles are documented in
distributed/sharding.py; the chip's peaks live with the benchmark, in
bench/harness/device.py.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple):
    """`jax.make_mesh` with Auto axes: the sharding rules place arrays with
    `with_sharding_constraint`, which only Auto axes accept (make_mesh
    defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, *,
                   multi_pod: bool = False):
    """Small mesh for in-CI dry-run smoke tests (8 host devices)."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))
