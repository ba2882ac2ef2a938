"""Distributed CPM collectives — run in a subprocess with 8 host devices so
the main test process keeps the default single-device view."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # never probe TPU backends
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial
from jax import shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.cpm import collectives

mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)

# ring all-reduce (R7-faithful) == psum
f = shard_map(lambda v: collectives.ring_allreduce(v, "data"),
              mesh=mesh, in_specs=P("data", None), out_specs=P("data", None))
got = f(x)
want = np.tile(np.asarray(x).reshape(2, 4, 4).sum(1, keepdims=True), (1, 4, 1)).reshape(8, 4)
# careful: in_specs shards rows over "data" only -> each data rank holds 2 rows;
# ring_allreduce sums across the 4 data ranks (pod axis unsharded -> replicated rows)
x2 = jnp.arange(16, dtype=jnp.float32).reshape(4, 4)
mesh1 = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
f1 = shard_map(lambda v: collectives.ring_allreduce(v, "data"),
               mesh=mesh1, in_specs=jax.sharding.PartitionSpec("data", None),
               out_specs=jax.sharding.PartitionSpec("data", None))
got1 = np.asarray(f1(x2))
want1 = np.tile(np.asarray(x2).sum(0, keepdims=True), (4, 1))
np.testing.assert_allclose(got1, want1)
print("ring_allreduce OK")

# tree (super-connectivity) all-reduce == psum
f2 = shard_map(lambda v: collectives.tree_allreduce(v, "data"),
               mesh=mesh1, in_specs=jax.sharding.PartitionSpec("data", None),
               out_specs=jax.sharding.PartitionSpec("data", None))
np.testing.assert_allclose(np.asarray(f2(x2)), want1)
print("tree_allreduce OK")

# hierarchical two-phase psum across pod x data == full sum
P_ = jax.sharding.PartitionSpec
f3 = shard_map(lambda v: collectives.hierarchical_psum(v, "data", "pod", mode="two_phase"),
               mesh=mesh, in_specs=P_(("pod", "data"), None), out_specs=P_(("pod", "data"), None))
got3 = np.asarray(f3(x))
want3 = np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1))
np.testing.assert_allclose(got3, want3)
print("hierarchical_psum OK")

# ring mode as well
f4 = shard_map(lambda v: collectives.hierarchical_psum(v, "data", "pod", mode="ring"),
               mesh=mesh, in_specs=P_(("pod", "data"), None), out_specs=P_(("pod", "data"), None))
np.testing.assert_allclose(np.asarray(f4(x)), want3)
print("hierarchical ring OK")

# distributed sectioned sum (the paper's sqrt-N sum with chips as sections)
v = jnp.arange(64, dtype=jnp.float32)
f5 = shard_map(lambda s: collectives.distributed_section_sum(s, "data")[None],
               mesh=mesh1, in_specs=P_("data"), out_specs=P_("data"))
np.testing.assert_allclose(np.asarray(f5(v)), np.full(4, 2016.0))
print("distributed_section_sum OK")

# ring_shift moves the shard to the neighbor
f6 = shard_map(lambda s: collectives.ring_shift(s, "data", 1),
               mesh=mesh1, in_specs=P_("data"), out_specs=P_("data"))
got6 = np.asarray(f6(jnp.arange(8, dtype=jnp.float32)))
np.testing.assert_allclose(got6, np.roll(np.arange(8, dtype=np.float32), 2))
print("ring_shift OK")

# grad_sync over a pytree
tree = {"a": jnp.ones((8, 2)), "b": jnp.full((8,), 2.0)}
f7 = shard_map(lambda t: collectives.grad_sync(t, ("pod", "data")),
               mesh=mesh, in_specs=P_(("pod", "data")), out_specs=P_(("pod", "data")))
out = f7(tree)
np.testing.assert_allclose(np.asarray(out["a"]), np.full((8, 2), 8.0))
print("grad_sync OK")
print("ALL_OK")
"""


@pytest.mark.slow
def test_collectives_8dev():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO_ROOT)
    assert "ALL_OK" in r.stdout, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
