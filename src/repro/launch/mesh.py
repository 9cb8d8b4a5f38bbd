"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
init.  Single pod: 16x16 = 256 chips (v5e pod).  Multi-pod: 2 pods = 512.
The 'pod' axis is the slow (DCN-ish) axis — hierarchical collectives in
parallel/collectives.py treat it accordingly.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the compiler propagates shardings from the in/out specs
    # (jax.make_mesh defaults to Explicit axes, which make every gather on
    # a sharded operand name its output sharding)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Mesh over whatever devices exist (CPU tests, elastic restore)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return _auto_mesh((data, model), ("data", "model"))
