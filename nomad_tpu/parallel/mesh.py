"""Device mesh + shardings for the scheduling kernels.

Design: one logical axis 'nodes' over all chips of a region. The node-table
arrays shard along their first (node) axis; per-placement inputs (demands,
tg ids) and scalars replicate.

Two regimes use the mesh differently. The naive scan path
(place_batch_sharded, kept as the oracle/fallback) follows the
scaling-book recipe — annotate shardings, let XLA's SPMD partitioner
insert the ICI collectives for its global argmax/sum reductions — which
is correct but pays collectives per PLACEMENT. The served keyed path
does NOT hand the partitioner that choice: kernels.py's 'shard-local
mesh pipeline' (`_place_batch_keyed_mesh`) runs an explicit `shard_map`
cold stage over these same shardings with ZERO collectives in any
compiled program, exchanges only O(devices x T x k) winner-candidate
rows point-to-point, and keeps warm storm windows resident on the lead
device (`mesh_collective_audit` gates the claim in tier-1).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nomad_tpu.scheduler import kernels

NODE_AXIS = "nodes"


def scheduling_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D mesh over all devices: the node axis shards across ICI."""
    devs = np.array(devices if devices is not None else jax.devices())
    return Mesh(devs.reshape(-1), (NODE_AXIS,))


def pow2_prefix(devices: Sequence[jax.Device]) -> Sequence[jax.Device]:
    """Largest power-of-two prefix of a device list — the mesh-sizing rule
    (node rows pad to powers of two, so the sharded axis must divide
    evenly). THE single definition; server boot and chip_smoke.py both
    use it."""
    n = 1
    while n * 2 <= len(devices):
        n *= 2
    return devices[:n]


def node_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (node) axis."""
    return NamedSharding(mesh, P(NODE_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_node_arrays(mesh: Mesh, arrays: dict) -> dict:
    """Place the node-table arrays with the node axis split over the mesh."""
    ns = node_sharding(mesh)
    return {k: jax.device_put(v, ns) for k, v in arrays.items()}


def place_batch_sharded(mesh: Mesh, capacity, score_cap, usage, tg_masks,
                        job_counts, demands, tg_ids, valid, noise, penalty,
                        distinct_hosts, banned0) -> kernels.PlacementResult:
    """Run the placement scan with the node axis sharded over the mesh.

    tg_masks is [T, N]: sharded on its second axis; demands/tg_ids/valid are
    per-placement and replicate. The same jitted kernel is reused — XLA
    partitions it from the input shardings.
    """
    ns = node_sharding(mesh)
    ns2 = NamedSharding(mesh, P(None, NODE_AXIS))
    rep = replicated(mesh)
    args = (
        jax.device_put(capacity, ns),
        jax.device_put(score_cap, ns),
        jax.device_put(usage, ns),
        jax.device_put(tg_masks, ns2),
        jax.device_put(job_counts, ns),
        jax.device_put(demands, rep),
        jax.device_put(tg_ids, rep),
        jax.device_put(valid, rep),
        jax.device_put(noise, ns),
        jax.device_put(penalty, rep),
        jax.device_put(distinct_hosts, rep),
        jax.device_put(banned0, ns),
    )
    return kernels.place_batch(*args)
