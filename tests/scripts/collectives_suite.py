"""dist.collectives helpers: compressed + hierarchical psum correctness."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import compressed_psum, hierarchical_psum
from repro.launch.mesh import make_mesh
from jax import shard_map

mesh = make_mesh((2, 4), ("pod", "data"))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (8, 16, 128), jnp.float32)


@functools.partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
                   out_specs=P(("pod", "data")), check_vma=False)
def ref_sum(xs):
    return jax.lax.psum(xs, ("pod", "data"))


@functools.partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
                   out_specs=P(("pod", "data")), check_vma=False)
def comp_sum(xs):
    return compressed_psum(xs, ("pod", "data"), group_size=8)


@functools.partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
                   out_specs=P(("pod", "data")), check_vma=False)
def hier_sum(xs):
    return hierarchical_psum(xs[0], pod_axis="pod", inner_axes=("data",))[None]


@functools.partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
                   out_specs=P(("pod", "data")), check_vma=False)
def hier_comp(xs):
    return hierarchical_psum(xs[0], pod_axis="pod", inner_axes=("data",),
                             compress_dcn=True)[None]


ref = ref_sum(x)
got = comp_sum(x)
rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
assert rel < 0.02, rel                      # int8-quantized: ~1% error
h = hier_sum(x)
np.testing.assert_allclose(np.asarray(h), np.asarray(ref), rtol=1e-5,
                           atol=1e-5)       # exact decomposition
hc = hier_comp(x)
rel2 = float(jnp.linalg.norm(hc - ref) / jnp.linalg.norm(ref))
assert rel2 < 0.02, rel2
print("ALL OK")
