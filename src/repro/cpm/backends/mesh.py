"""Pod-scale backend: chips as PEs via ``shard_map`` collectives.

The PE address axis is sharded over one named mesh axis; every op is the
paper's two-phase schedule — phase 1 inside each chip's registers, phase 2
across the ICI ring (`repro.cpm.collectives`).  When a sharding context from
``repro.distributed.sharding`` is active its mesh and innermost data axis
are used; otherwise a 1-axis mesh over all local devices is built.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, PartitionSpec as P

from .. import collectives
from . import _TableBacked


class MeshBackend(_TableBacked):
    name = "mesh"

    def __init__(self, mesh=None, axis: str | None = None,
                 mode: str = "two_phase"):
        if mesh is None:
            from repro.distributed import sharding
            ctx = sharding.current_ctx()
            if ctx.mesh is not None:
                mesh = ctx.mesh
                axis = axis or (ctx.data_axes[-1] if ctx.data_axes
                                else mesh.axis_names[0])
            else:
                devs = jax.devices()
                mesh = jax.make_mesh((len(devs),), ("cpm",),
                                     axis_types=(AxisType.Auto,))
                axis = "cpm"
        self.mesh = mesh
        self.axis = axis or mesh.axis_names[0]
        self.mode = mode

    @property
    def n_devices(self) -> int:
        return int(self.mesh.shape[self.axis])

    def _pad(self, x, fill):
        pad = (-x.shape[-1]) % self.n_devices
        if pad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                        constant_values=fill)
        return x

    def _spec(self, ndim: int):
        """Shard the last (PE address) axis; batch rows replicate."""
        return P(*([None] * (ndim - 1) + [self.axis]))

    def compare(self, x, datum, op="eq"):
        n = x.shape[-1]
        xp = self._pad(x, 0)
        from ..reference import comparable

        f = shard_map(partial(comparable.compare, datum=datum, op=op),
                      mesh=self.mesh, in_specs=self._spec(x.ndim),
                      out_specs=self._spec(x.ndim))
        return f(xp)[..., :n]

    def section_sum(self, x, section=None):
        xp = self._pad(x, 0)
        f = shard_map(
            lambda xl: collectives.distributed_section_sum(
                xl, self.axis, mode=self.mode),
            mesh=self.mesh, in_specs=self._spec(x.ndim), out_specs=P())
        return f(xp)

    def global_limit(self, x, mode="max", section=None):
        from ..semantics import limit_identity
        xp = self._pad(x, limit_identity(x.dtype, mode))
        f = shard_map(
            lambda xl: collectives.distributed_section_limit(
                xl, self.axis, mode=mode),
            mesh=self.mesh, in_specs=self._spec(x.ndim), out_specs=P())
        return f(xp)

    def super_sum(self, x, section=None):
        """§8 on chips: local partial per device, log-depth butterfly
        combine over the mesh axis (``collectives.tree_allreduce``).
        ``check_vma=False``: the ppermute butterfly leaves every device
        holding the full combine, but shard_map's static replication
        checker cannot prove that."""
        xp = self._pad(x, 0)
        f = shard_map(
            lambda xl: collectives.distributed_super_sum(xl, self.axis),
            mesh=self.mesh, in_specs=self._spec(x.ndim), out_specs=P(),
            check_vma=False)
        return f(xp)

    def super_limit(self, x, mode="max", section=None):
        from ..semantics import limit_identity
        xp = self._pad(x, limit_identity(x.dtype, mode))
        f = shard_map(
            lambda xl: collectives.distributed_super_limit(
                xl, self.axis, mode=mode),
            mesh=self.mesh, in_specs=self._spec(x.ndim), out_specs=P(),
            check_vma=False)
        return f(xp)
