"""Pallas TPU kernel: fused multi-hop layer-0 traversal (paper §5.2, Fig. 6).

The paper's RTL search engine wins by *pipelining* the hop loop next to the
data: neighbor fetch, distance compute, and candidate-list update run as
stages of one persistent engine, and the host is only consulted when the
beam terminates. The hop-stepped JAX path (core/search.py) instead runs one
`lax.while_loop` iteration of small ops per hop — correct, but every hop
re-reads the beam state from HBM and re-dispatches the whole op graph.

This kernel is the jax_pallas analogue of that engine. One invocation:

  * holds the whole beam state in VMEM — candidate list, final list, and
    the packed uint32 visited bitmap (the paper's single-bit visited list,
    §5.1.1) live in per-lane VMEM blocks for the duration;
  * executes ``fused_hops`` (H) layer-0 hops back to back, so the
    while-loop body costs one kernel dispatch per H hops instead of one
    op-graph dispatch per hop;
  * expresses the neighbor-row gather as async copies (`make_async_copy`
    DMAs from the ANY/HBM-resident tables) issued *before* the visited
    test-and-set, so the fetch overlaps the bookkeeping stage exactly like
    the paper's Fig. 6 pipeline overlaps FetchNeighbors with VisitedCheck;
  * applies every hop under a per-lane `live` guard, which makes the
    result bit-identical to the vmapped-while lockstep path: a lane whose
    termination condition fires mid-superstep keeps its state unchanged
    for the remaining unrolled hops.

Bit-parity is load-bearing, so the in-kernel merge/sort are the *same
mathematics* as core/search.py's `merge_sorted` / stable argsort, expressed
as comparison-matrix rank computations (the paper's parallel insertion sort
computes insert positions as popcounts of comparison bit-vectors — §5.2.4).
Both paths count the merge ranks ``#(b_j < a_i)`` and ``#(a_i <= b_j)`` as
comparison counts under the same order (``<``; -0.0 equals 0.0, +inf is
last); the stable-argsort position is
``pos_i == #(d_j < d_i) + #(j < i, d_j == d_i)``. Identical outputs, but
matmul/reduction-shaped instead of sort-shaped. Mosaic does not lower the
kernel yet (its (1, w) lane blocks, the DMA index read out of a vector, and
the bitmap's dynamic gather/scatter), so it runs in interpret mode only and
a TPU backend refuses `fused_hops > 1` at build. The kernel imports nothing
from repro.core (core imports kernels.ops
lazily for dispatch, so the dependency arrow must point one way only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["fused_traversal_pallas"]


# ---------------------------------------------------------------------------
# In-kernel primitives: rank-based sort/merge + visited bitmap, identical in
# value to core/search.py's argsort/comparison-count/scatter formulations.
# ---------------------------------------------------------------------------


def _metric_dist(metric: str, dot, xsq, qsq):
    """Same formulas as core.search.metric_distance (trace-time branch)."""
    if metric == "l2":
        return jnp.maximum(xsq - 2.0 * dot + qsq, 0.0)
    if metric == "ip":
        return -dot
    if metric == "cosine":
        return 1.0 - dot
    raise ValueError(f"unknown metric {metric!r}")


def _stable_sort_pairs(d, ids):
    """Stable ascending sort of (d, ids) — value-identical to
    ``order = argsort(d, stable=True); d[order], ids[order]``.

    pos_i = #(d_j < d_i) + #(j < i with d_j == d_i) is exactly the slot a
    stable sort assigns; the scatter to sorted order is a one-hot masked
    reduction (pos is a permutation, so each output row selects one lane).
    """
    m = d.shape[0]
    di, dj = d[:, None], d[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    pos = (jnp.sum(dj < di, axis=1, dtype=jnp.int32)
           + jnp.sum((dj == di) & (jj < ii), axis=1, dtype=jnp.int32))
    onehot = pos[None, :] == jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    sd = jnp.sum(jnp.where(onehot, d[None, :], 0.0), axis=1)
    si = jnp.sum(jnp.where(onehot, ids[None, :], 0), axis=1).astype(ids.dtype)
    return sd, si


def _rank_merge(ad, ai, bd, bi):
    """Merge two ascending (dist, id) arrays; ties keep `a` first.

    Value-identical to core.search.merge_sorted: both count the ranks as
    comparison-matrix popcounts under the same order (paper §5.2.4's
    comparison bit-vector); here the position scatter is one-hot masked
    reductions too.
    """
    na, nb = ad.shape[0], bd.shape[0]
    n = na + nb
    pa = (jax.lax.broadcasted_iota(jnp.int32, (na,), 0)
          + jnp.sum(bd[None, :] < ad[:, None], axis=1, dtype=jnp.int32))
    pb = (jax.lax.broadcasted_iota(jnp.int32, (nb,), 0)
          + jnp.sum(ad[None, :] <= bd[:, None], axis=1, dtype=jnp.int32))
    rows_a = pa[None, :] == jax.lax.broadcasted_iota(jnp.int32, (n, na), 0)
    rows_b = pb[None, :] == jax.lax.broadcasted_iota(jnp.int32, (n, nb), 0)
    od = (jnp.sum(jnp.where(rows_a, ad[None, :], 0.0), axis=1)
          + jnp.sum(jnp.where(rows_b, bd[None, :], 0.0), axis=1))
    oi = (jnp.sum(jnp.where(rows_a, ai[None, :], 0), axis=1)
          + jnp.sum(jnp.where(rows_b, bi[None, :], 0), axis=1)).astype(ai.dtype)
    return od, oi


def _visited_tas(vis, ids, valid):
    """core.search.visited_test_and_set on a VMEM-resident value: `ids`
    must be unique where valid (the restructured DB's de-duplicated rows),
    so the scatter-add of distinct bits within a word equals bitwise OR."""
    w = jax.lax.shift_right_logical(ids, 5)
    b = (ids & 31).astype(jnp.uint32)
    bit = jax.lax.shift_left(jnp.uint32(1), b)
    old = vis[w]
    was = (jax.lax.shift_right_logical(old, b) & jnp.uint32(1)) > 0
    was = was | ~valid
    add = jnp.where(~was, bit, jnp.uint32(0))
    return was, vis.at[w].add(add)


# ---------------------------------------------------------------------------
# The kernel: one grid step == one query lane, H hops per invocation
# ---------------------------------------------------------------------------


def _make_kernel(fused_hops: int, max_hops: int, metric: str, maxM0: int):
    H, M0 = fused_hops, maxM0

    def kernel(qsq_ref, q_ref, cand_d_ref, cand_i_ref, fin_d_ref, fin_i_ref,
               vis_ref, hops_ref, calcs_ref, vec_ref, sq_ref, nbr_ref,
               ocand_d_ref, ocand_i_ref, ofin_d_ref, ofin_i_ref, ovis_ref,
               ohops_ref, ocalcs_ref,
               nbr_s, vec_s, sq_s, nbr_sem, vec_sem, sq_sem):
        q = q_ref[0, :]
        qsq = qsq_ref[0, 0]
        cand_d = cand_d_ref[0, :]
        cand_i = cand_i_ref[0, :]
        fin_d = fin_d_ref[0, :]
        fin_i = fin_i_ref[0, :]
        vis = vis_ref[0, :]
        hops = hops_ref[0, 0]
        calcs = calcs_ref[0, 0]
        C, EF = cand_d.shape[0], fin_d.shape[0]

        for _ in range(H):                       # static unroll: H hops
            # Algorithm 1 lines 2&5 — the same per-lane guard the batched
            # while_loop applies; a lane done mid-superstep stays frozen.
            live = (cand_d[0] < fin_d[-1]) & (hops < max_hops)
            c = jnp.maximum(cand_i[0], 0)        # frozen lanes fetch row 0

            # stage 1 (Fig. 6 FetchNeighbors): DMA the popped node's
            # neighbor row; the pop shift proceeds while it is in flight
            ncp = pltpu.make_async_copy(
                nbr_ref.at[pl.ds(c, 1), :], nbr_s, nbr_sem)
            ncp.start()
            pcand_d = jnp.roll(cand_d, -1).at[-1].set(jnp.inf)
            pcand_i = jnp.roll(cand_i, -1).at[-1].set(-1)
            ncp.wait()
            nbrs = nbr_s[0, :]
            valid = nbrs >= 0
            safe = jnp.where(valid, nbrs, 0)

            # stage 2 (FetchVectors): per-neighbor row DMAs from the
            # ANY-resident raw-data/index tables, overlapped with the
            # visited test-and-set below (pad lanes fetch row 0 — their
            # distance is masked to +inf, so the tile content is inert)
            copies = []
            for m in range(M0):
                vcp = pltpu.make_async_copy(
                    vec_ref.at[pl.ds(safe[m], 1), :],
                    vec_s.at[pl.ds(m, 1), :], vec_sem.at[m])
                scp = pltpu.make_async_copy(
                    sq_ref.at[pl.ds(safe[m], 1), :],
                    sq_s.at[pl.ds(m, 1), :], sq_sem.at[m])
                vcp.start()
                scp.start()
                copies.append((vcp, scp))

            # stage 3 (VisitedCheck, §5.1.1): packed-bitmap test-and-set on
            # the VMEM-resident bitmap while the vector rows stream in
            was, vis2 = _visited_tas(vis, safe, valid)
            act = valid & ~was

            for vcp, scp in copies:
                vcp.wait()
                scp.wait()

            # stage 4 (DistCompute): whole neighbor list at once — the
            # 8x16-PE distance array analogue; codes cast to f32. mul+sum
            # (not `vecs @ q`) so the reduction order is bitwise-identical
            # to _batch_distances in core/search.py — a matvec's order is
            # context-dependent, an explicit axis reduction is not.
            vecs = vec_s[...].astype(jnp.float32)
            d = _metric_dist(metric, jnp.sum(vecs * q, axis=-1),
                             sq_s[...][:, 0], qsq)
            d = jnp.where(act, d, jnp.inf)
            ncalcs = calcs + jnp.sum(act)
            # line 11 guard: only candidates that can enter the final list
            d = jnp.where(d < fin_d[-1], d, jnp.inf)
            ids = jnp.where(jnp.isfinite(d), safe, -1)

            # stage 5 (ListUpdate, §5.2.4): rank-based parallel insertion
            bd, bi = _stable_sort_pairs(d, ids)
            fd, fi = _rank_merge(fin_d, fin_i, bd, bi)
            cd, ci = _rank_merge(pcand_d, pcand_i, bd, bi)

            cand_d = jnp.where(live, cd[:C], cand_d)
            cand_i = jnp.where(live, ci[:C], cand_i)
            fin_d = jnp.where(live, fd[:EF], fin_d)
            fin_i = jnp.where(live, fi[:EF], fin_i)
            vis = jnp.where(live, vis2, vis)
            hops = hops + live.astype(jnp.int32)
            calcs = jnp.where(live, ncalcs, calcs)

        ocand_d_ref[0, :] = cand_d
        ocand_i_ref[0, :] = cand_i
        ofin_d_ref[0, :] = fin_d
        ofin_i_ref[0, :] = fin_i
        ovis_ref[0, :] = vis
        ohops_ref[0, 0] = hops
        ocalcs_ref[0, 0] = calcs

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("fused_hops", "max_hops", "metric", "interpret"),
)
def fused_traversal_pallas(
    vectors,              # [N, D_pad] f32 or integer codes (ANY/HBM)
    sqnorms,              # [N] f32 (+inf pad markers)
    l0_nbrs,              # [N, maxM0_pad] int32, -1-padded unique rows
    queries,              # [B, D_pad] f32
    qsq,                  # [B] f32
    cand_d,               # [B, C] f32 ascending, +inf padded
    cand_i,               # [B, C] int32, -1 padded
    fin_d,                # [B, EF] f32
    fin_i,                # [B, EF] int32
    visited,              # [B, W] uint32 packed bitmap, W = ceil(N/32)
    hops,                 # [B] int32
    calcs,                # [B] int32
    *,
    fused_hops: int,
    max_hops: int,
    metric: str = "l2",
    interpret: bool = True,
):
    """Advance every lane of the beam state by up to `fused_hops` hops.

    Returns the updated (cand_d, cand_i, fin_d, fin_i, visited, hops,
    calcs) — bit-identical to `fused_hops` iterations of the hop-stepped
    lockstep body, including the per-lane termination guard.
    """
    B, D = queries.shape
    N, M0 = l0_nbrs.shape
    C, EF, W = cand_d.shape[1], fin_d.shape[1], visited.shape[1]
    lane = lambda w: pl.BlockSpec((1, w), lambda i: (i, 0))  # noqa: E731
    outs = pl.pallas_call(
        _make_kernel(fused_hops, max_hops, metric, M0),
        grid=(B,),
        in_specs=[
            lane(1), lane(D), lane(C), lane(C), lane(EF), lane(EF),
            lane(W), lane(1), lane(1),
            pl.BlockSpec(memory_space=pl.ANY),   # vectors
            pl.BlockSpec(memory_space=pl.ANY),   # sqnorms [N, 1]
            pl.BlockSpec(memory_space=pl.ANY),   # l0_nbrs
        ],
        out_specs=[lane(C), lane(C), lane(EF), lane(EF), lane(W),
                   lane(1), lane(1)],
        out_shape=[
            jax.ShapeDtypeStruct((B, C), jnp.float32),
            jax.ShapeDtypeStruct((B, C), jnp.int32),
            jax.ShapeDtypeStruct((B, EF), jnp.float32),
            jax.ShapeDtypeStruct((B, EF), jnp.int32),
            jax.ShapeDtypeStruct((B, W), jnp.uint32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, M0), jnp.int32),      # neighbor row landing pad
            pltpu.VMEM((M0, D), vectors.dtype),  # gathered vector rows
            pltpu.VMEM((M0, 1), jnp.float32),    # gathered sqnorm rows
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((M0,)),
            pltpu.SemaphoreType.DMA((M0,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qsq[:, None], queries, cand_d, cand_i, fin_d, fin_i, visited,
      hops[:, None], calcs[:, None], vectors, sqnorms.reshape(N, 1),
      l0_nbrs)
    ncand_d, ncand_i, nfin_d, nfin_i, nvis, nhops, ncalcs = outs
    return (ncand_d, ncand_i, nfin_d, nfin_i, nvis,
            nhops[:, 0], ncalcs[:, 0])
