"""Device mesh construction for distributed query execution.

The engine distributes over a 1-D data axis ("shards") — relational query
shuffles are row exchanges, so one axis suffices (the analog of Spark's
``spark.sql.shuffle.partitions`` topology, reference
power_run_cpu.template:30); multi-slice pods extend the same axis across
DCN transparently (XLA picks ICI within a slice, DCN across).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the engine's default: replication check
    off (outputs declared ``P()`` are replicated by construction, via
    the all_gather/psum combines in parallel/exchange.py)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(n_devices: Optional[int] = None,
              axis: str = SHARD_AXIS) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    import numpy as np
    return Mesh(np.array(devs[:n]), (axis,))


def default_mesh() -> Mesh:
    return make_mesh()


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows block-sharded across the mesh axis."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
