"""Distributed two-stage search: graph parallelism + query parallelism
(paper Fig. 10/11) as explicit shard_map collectives.

Graph parallelism (the paper's winning strategy — 3.67x at 4 devices):
partitions shard over the `model` axis; each device searches only its
resident sub-graphs; per-device top-K results are all-gathered along
`model` and rank-merged (stage 2). The merge is O(P*K) — the paper measured
0.2% of runtime for its host-side equivalent.

Query parallelism: the query batch shards over `data` (and `pod` across
pods). Unlike the paper's variant — where every device had to LOAD THE
WHOLE DATABASE and scaling collapsed to 1.56x — here partitions stay
resident in HBM, so sharding queries across the replicas of the *graph-
sharded* engine is free. The hybrid (graph || within `model`, query ||
across `data`/`pod`) is the scale-out story for 1000+ nodes: pods never
exchange database shards, only (gid, dist) result tuples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.hnsw_graph import DeviceDB
from repro.core.partitioned import PartitionedDB, merge_topk
from repro.core.search import SearchParams, batch_search

__all__ = ["shard_db", "make_distributed_search"]


def shard_db(pdb: PartitionedDB, mesh) -> PartitionedDB:
    """Place partitions round-robin over the `model` axis (P % model == 0)."""
    spec = P("model")
    db = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P(*( ("model",) + (None,) * (a.ndim - 1))))),
        pdb.db)
    return PartitionedDB(db=db, num_partitions=pdb.num_partitions, dim=pdb.dim)


def make_distributed_search(mesh, p: SearchParams, maxM0: int,
                            graph_axes=("model",), query_axes=None,
                            merge: bool = True, pq: bool = False):
    """Builds the jitted two-stage distributed search for a mesh.

    graph_axes : mesh axes the partitions shard over. For the SIFT1B-scale
        deployment this is the WHOLE pod ("data", "model") — one ~3.9M-vector
        partition per chip, the paper's one-sub-graph-per-SmartSSD mapping.
    query_axes : mesh axes the query batch shards over (e.g. ("pod",) across
        pods). None -> queries replicated over the graph axes.
    merge : True -> (ids[B, k], dists[B, k], calcs[B, 1]) after the stage-2
        rank merge. False -> the gathered unmerged candidate pool
        (ids[B, P*k], dists[B, P*k], calcs[B, 1]) for an external rerank.
    pq : dtype="pq" — the returned function takes a third argument, the
        per-query [B, M, 256] ADC LUT, sharded like the queries (codebooks
        are global, so the tables replicate over the graph axes exactly
        like the query rows they belong to).
    calcs is the per-query distance-evaluation count summed over every
    partition on every device (the Fig. 9 "vector reads").
    """
    p = p.resolve(maxM0)
    query_axes = tuple(query_axes or ())
    qspec = P(query_axes if query_axes else None, None)
    in_specs = (
        DeviceDB(*(P(graph_axes) for _ in DeviceDB._fields)),
        qspec,
    )
    if pq:
        in_specs = in_specs + (
            P(query_axes if query_axes else None, None, None),)
    out_specs = (qspec, qspec, qspec)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
    def _search(db_local: DeviceDB, queries, *lut):
        lut = lut[0] if lut else None
        # stage 1: every local partition searches the local query shard.
        ids, ds, stats = jax.vmap(
            lambda db: batch_search(db, queries, p, lut))(db_local)
        # [P_loc, B_loc, k] -> [B_loc, P_loc * k]
        ids = jnp.swapaxes(ids, 0, 1).reshape(queries.shape[0], -1)
        ds = jnp.swapaxes(ds, 0, 1).reshape(queries.shape[0], -1)
        calcs = jnp.sum(stats.dist_calcs, axis=0)      # [B_loc] local reads
        # stage 2: gather candidates across the graph axes, rank-merge.
        all_ids = ids
        all_ds = ds
        for ax in graph_axes:
            all_ids = jax.lax.all_gather(all_ids, ax, axis=1, tiled=True)
            all_ds = jax.lax.all_gather(all_ds, ax, axis=1, tiled=True)
            calcs = jax.lax.psum(calcs, ax)
        if merge:
            order = jnp.argsort(all_ds, axis=1, stable=True)[:, : p.k]
            all_ids = jnp.take_along_axis(all_ids, order, axis=1)
            all_ds = jnp.take_along_axis(all_ds, order, axis=1)
        return all_ids, all_ds, calcs[:, None]

    return jax.jit(_search)
