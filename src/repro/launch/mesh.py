"""Production mesh construction (pure function; importing this module never
touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod: a leading
    'pod' axis (DCI-connected); 'pod' composes with 'data' for batch/FSDP
    sharding — see train/sharding.py."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code shards only its inputs and outputs and lets
    # the partitioner place the rest (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n: int | None = None, axis: str = "data"):
    """1-D mesh over however many (host) devices exist — tests/examples."""
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))
