"""Known-bad: a shard_map-wrapped executor fed raw numpy at one site and
device arrays at another. shard_map builds a traced, cached SPMD callable
— mixed argument flavors double its dispatch cache exactly like plain
jit (the hazard jit-arg-flavor exists for), but the wrapper is
``jax.shard_map`` rather than ``jax.jit``, so the rule
must see through it. Expected finding: jit-arg-flavor."""
import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(np.array(jax.devices()[:1]), ("data",))


def _slab(x):
    return x * 2


run = jax.shard_map(_slab, mesh=mesh, in_specs=(P("data"),),
                    out_specs=P("data"), check_vma=False)

host = np.ones((8, 8), np.float32)
dev = jax.device_put(np.ones((8, 8), np.float32))

run(host)   # numpy flavor populates one dispatch-cache entry...
run(dev)    # ...device flavor populates a second one  <-- finding
