"""ICI collectives smoke test (BASELINE.json config 4): psum across all chips
of the slice inside one sandbox. On a v5e-4 sandbox this exercises the ICI
mesh; on a single chip it degenerates gracefully."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

devices = jax.devices()
n = len(devices)
mesh = Mesh(np.array(devices), ("chips",))


@jax.jit
def allreduce(x):
    def inner(block):
        return jax.lax.psum(block, "chips")

    return shard_map(inner, mesh=mesh, in_specs=P("chips"), out_specs=P())(x)


x = jnp.arange(n * 8, dtype=jnp.float32)
total = allreduce(x)
expected = x.reshape(n, -1).sum(axis=0)
print(f"chips={n} psum_ok={bool(jnp.allclose(total, expected))}")
