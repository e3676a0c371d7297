"""Batched on-device stage-2 rerank (paper Fig. 4 stage 2).

The whole [B, C] candidate pool (C = P*K stage-1 intermediates) is
deduplicated, gathered, and exactly re-scored in one jitted call — this is
the single rerank implementation every engine (partitioned, distributed,
csd, and each segment of a mutable index) routes through. Dedup is done by
sorting ids within each row — duplicates become adjacent and are masked to
+inf, which also reproduces an np.unique tie-break (among equal distances
the smallest id wins).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.search import metric_distance

__all__ = ["batched_rerank"]


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def batched_rerank(vectors, sqnorms, queries, cand_ids, k: int,
                   metric: str = "l2"):
    """Exact top-k over per-query candidate pools.

    vectors : [N, D] raw (metric-prepared) database vectors
    sqnorms : [N] ||x||^2 (only read for metric="l2")
    queries : [B, D]
    cand_ids: [B, C] int32 global ids; -1 marks empty slots
    returns : ids [B, k] int32 (-1 padded), dists [B, k] f32 (+inf padded)
    """
    b = cand_ids.shape[0]
    ids_s = jnp.sort(cand_ids, axis=1)            # -1s first, dups adjacent
    dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1)
    valid = (ids_s >= 0) & ~dup
    safe = jnp.maximum(ids_s, 0)

    q = queries.astype(jnp.float32)
    qsq = jnp.einsum("bd,bd->b", q, q)
    vecs = vectors[safe]                          # [B, C, D]
    dot = jnp.einsum("bcd,bd->bc", vecs, q,
                     precision=jax.lax.Precision.HIGHEST)
    d = metric_distance(metric, dot, sqnorms[safe], qsq[:, None])
    d = jnp.where(valid, d, jnp.inf)

    order = jnp.argsort(d, axis=1, stable=True)[:, :k]
    out_d = jnp.take_along_axis(d, order, axis=1)
    out_i = jnp.where(jnp.isfinite(out_d),
                      jnp.take_along_axis(ids_s, order, axis=1), -1)
    return out_i.astype(jnp.int32), out_d
