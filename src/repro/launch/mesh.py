"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state.  Single pod: 256 chips as (16, 16) = ("data", "model").  Multi-pod:
2 pods x 256 = (2, 16, 16) = ("pod", "data", "model") — the "pod" axis is
pure data parallelism across the cross-pod (DCN/optical) links, the inner
two axes live on the ICI torus.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
