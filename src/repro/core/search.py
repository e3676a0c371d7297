"""Fixed-shape HNSW search kernel in JAX (paper Algorithm 1, HW-modified).

This is the TPU analogue of the paper's RTL search kernel (§5.2). All of the
paper's hardware modifications carry over:

  * single-bit visited list       -> packed uint32 bitmap, N/8 bytes/query
                                     (the paper's 0.62 MB for 5M points)
  * parallel distance calculator  -> MXU-friendly ||q-x||^2 = ||x||^2 - 2 x.q + ||q||^2
                                     over a whole (padded) neighbor list at once
  * parallel insertion sort via   -> rank-based merge of two sorted arrays:
    comparison bit-vector            pos = index + searchsorted(other)
                                     (searchsorted == popcount of "smaller" bits)
  * multi-query processing        -> vmap over the query batch; the masked
                                     lockstep while_loop is the many-module
                                     generalization of the paper's 2 modules
  * fixed-size candidate list     -> the paper sets |C| "larger than ef";
                                     we default to ef + maxM0

Shapes are fully static: candidate/final lists are sorted arrays padded with
+inf, neighbor lists are -1-padded fixed-stride rows (the restructured DB of
hnsw_graph.py), and the data-dependent traversal runs under
``jax.lax.while_loop`` with an explicit hop budget (returned in the stats so
benchmarks can report the paper's "number of vector reads", Fig. 9).

Quantized databases (IndexSpec.dtype uint8/int8 — the paper's SIFT1B
operating point): ``db.vectors`` may hold integer codes and ``queries``
code-valued float32; every distance evaluation casts the gathered rows to
f32 and accumulates in f32 (exact for 8-bit codes up to ~256 dims, since
all partial dot products are integers < 2^24), so the traversal is the
same kernel in code space. ``db.sqnorms`` stays float32 (code norms; +inf
pad markers). The caller rescales distances by ``scale**2`` at the edge.

Product-quantized databases (IndexSpec.dtype "pq"): ``db.vectors`` holds
[n_pad, M] uint8 PQ codes and the caller passes ``lut`` — the per-query
[M, 256] asymmetric-distance table (optim.compression.build_pq_lut).
Every distance evaluation becomes `pq_lut_distances`: a table gather
followed by `jnp.sum(..., axis=-1)` over subspaces — the LUT extension of
the mul+sum reduction-order rule below. Queries are NOT padded to the
code width (the LUT is the per-query operand), and layer 0 always runs
the hop-stepped path (the in-memory fused traversal kernel has no PQ
variant; bit-identity across `fused_hops` then holds trivially — the csd
backend's PQ supersteps replay these exact semantics).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hnsw_graph import DeviceDB

__all__ = [
    "SearchParams",
    "SearchStats",
    "bitmap_words",
    "merge_sorted",
    "metric_distance",
    "pq_lut_distances",
    "visited_test_and_set",
    "search_one",
    "batch_search",
]


def bitmap_words(n: int) -> int:
    """uint32 words needed for an n-bit visited bitmap: ceil(n / 32).

    Floor division here was a real bug: with n % 32 != 0 the last partial
    word was never allocated, so test-and-set on the tail ids indexed past
    the bitmap (JAX clamps the gather/scatter to the last word — tail ids
    silently aliased onto bits 0..31 of the wrong word)."""
    return (n + 31) // 32


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Search-time knobs (paper: ef=40, K=10 for all SIFT1B results).

    `metric` selects the distance the traversal minimizes:
      l2     : squared Euclidean (the paper's metric)
      ip     : negative inner product (MIPS as a minimization)
      cosine : 1 - q.x, assuming the DB vectors and queries are unit-norm
               (repro.api normalizes both at the build/search edge)
    HNSW itself is metric-agnostic — only the distance evaluations change.
    """

    ef: int = 40
    k: int = 10
    cand_size: int = 0        # 0 -> resolved to ef + maxM0
    max_hops: int = 0         # 0 -> resolved to 4*ef + 16
    upper_hops: int = 32      # per-layer greedy budget in upper layers
    metric: str = "l2"
    # layer-0 hops executed per kernel invocation / per host superstep.
    # 1 = the legacy hop-stepped lockstep path; >1 switches the in-memory
    # backends to the fused Pallas traversal kernel (kernels/traversal.py)
    # and the csd backend to speculative H-hop supersteps (one host sync
    # and one batched store read per superstep). Results are bit-identical
    # at every value — this knob trades work per dispatch for round-trips.
    fused_hops: int = 1

    def resolve(self, maxM0: int) -> "SearchParams":
        cand = self.cand_size or (self.ef + maxM0)
        hops = self.max_hops or (4 * self.ef + 16)
        return dataclasses.replace(self, cand_size=cand, max_hops=hops)


class SearchStats(NamedTuple):
    hops: jnp.ndarray         # candidate pops at layer 0 (per query)
    dist_calcs: jnp.ndarray   # distance evaluations == "vector reads" (Fig. 9)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def merge_sorted(ad, ai, bd, bi):
    """Merge two ascending (dist, id) arrays; ties keep `a` first.

    The paper's parallel insertion sort computes an insert position as the
    popcount of a comparison bit-vector; ``searchsorted`` computes exactly
    that rank, vectorized over every element of both lists at once.
    """
    na, nb = ad.shape[0], bd.shape[0]
    pa = jnp.arange(na, dtype=jnp.int32) + jnp.searchsorted(
        bd, ad, side="left"
    ).astype(jnp.int32)
    pb = jnp.arange(nb, dtype=jnp.int32) + jnp.searchsorted(
        ad, bd, side="right"
    ).astype(jnp.int32)
    od = jnp.zeros(na + nb, ad.dtype).at[pa].set(ad).at[pb].set(bd)
    oi = jnp.zeros(na + nb, ai.dtype).at[pa].set(ai).at[pb].set(bi)
    return od, oi


def visited_test_and_set(bitmap, ids, valid):
    """Packed-bitmap visited list (paper §5.1.1 / §5.2.6).

    Returns (was_visited[bool], new_bitmap). `ids` must be unique where
    `valid` (guaranteed by the restructured DB's de-duplicated rows), so the
    scatter-add of distinct power-of-two bits within a word equals bitwise OR.
    """
    w = jax.lax.shift_right_logical(ids, 5)
    b = (ids & 31).astype(jnp.uint32)
    bit = jax.lax.shift_left(jnp.uint32(1), b)
    old = bitmap[w]
    was = (jax.lax.shift_right_logical(old, b) & jnp.uint32(1)) > 0
    was = was | ~valid
    add = jnp.where(~was, bit, jnp.uint32(0))
    return was, bitmap.at[w].add(add)


def metric_distance(metric: str, dot, xsq, qsq):
    """Distance-from-dot-product for every supported metric (ascending ==
    better). `metric` is trace-time static, so the branch costs nothing."""
    if metric == "l2":
        return jnp.maximum(xsq - 2.0 * dot + qsq, 0.0)
    if metric == "ip":
        return -dot
    if metric == "cosine":                       # unit-norm inputs assumed
        return 1.0 - dot
    raise ValueError(f"unknown metric {metric!r}")


def pq_lut_distances(lut, codes):
    """ADC distances for PQ code rows: lut [M, 256] x codes [N, M] -> [N].

    `jnp.take_along_axis(lut.T, codes, axis=0)` then `jnp.sum(..., -1)` is
    the ONE accumulation every engine path uses (in-memory traversal, csd
    hop kernels and supersteps, rerank candidate pools) — the PQ analogue
    of the mul+sum rule in `_batch_distances`. Re-deriving it with a
    different gather shape or reduction order gives last-ulp-different
    sums and breaks the partitioned==csd==cluster bit-identity contract.
    """
    vals = jnp.take_along_axis(lut.T, codes.astype(jnp.int32), axis=0)
    return jnp.sum(vals, axis=-1)


def _batch_distances(db: DeviceDB, q, qsq, ids, valid, metric: str = "l2",
                     lut=None):
    """Distances from q to db.vectors[ids]; invalid lanes -> +inf.

    One fused gather + matvec: the whole (padded) neighbor list is evaluated
    at once — the analogue of the paper's 8x16-PE distance array consuming a
    full 128-dim vector per cycle. With `lut` set (dtype="pq"), the gather
    pulls M-byte code rows and the matvec becomes a LUT gather + sum.
    """
    safe = jnp.where(valid, ids, 0)
    if lut is not None:
        d = pq_lut_distances(lut, db.vectors[safe])
        return jnp.where(valid, d, jnp.inf), safe
    vecs = db.vectors[safe].astype(jnp.float32)  # [M, D_pad] (codes -> f32)
    # mul+sum instead of `vecs @ q`: XLA compiles a matvec with a
    # context-dependent reduction order (gather-fused vs pre-gathered vs
    # Pallas-interpreted give last-ulp-different sums), while an explicit
    # elementwise product + axis reduction is bitwise-stable across every
    # context we run in — the property the fused-kernel parity matrix pins.
    d = metric_distance(metric, jnp.sum(vecs * q, axis=-1),
                        db.sqnorms[safe], qsq)
    return jnp.where(valid, d, jnp.inf), safe


# ---------------------------------------------------------------------------
# Upper layers: greedy descent (ef = 1), paper §5.2.2
# ---------------------------------------------------------------------------


def _greedy_upper(db: DeviceDB, q, qsq, p: SearchParams, lut=None):
    """Descend from db.max_level to layer 1, returning the layer-0 entry."""
    ep = db.entry.astype(jnp.int32)
    if lut is not None:
        ep_d = pq_lut_distances(lut, db.vectors[ep][None])[0]
    else:
        ep_vec = db.vectors[ep].astype(jnp.float32)
        ep_d = metric_distance(p.metric, jnp.sum(ep_vec * q, axis=-1),
                               db.sqnorms[ep], qsq)
    n_layers = db.up_nbrs.shape[0]               # static cap - 1

    def layer_body(i, carry):
        cur, cur_d, calcs = carry
        layer = n_layers - i                      # n_layers .. 1
        active_layer = layer <= db.max_level

        def hop_cond(s):
            _, _, improved, hops, _ = s
            return improved & (hops < p.upper_hops)

        def hop_body(s):
            c, c_d, _, hops, calcs = s
            row = db.up_ptr[c]
            nbrs = db.up_nbrs[layer - 1, jnp.maximum(row, 0)]
            valid = (nbrs >= 0) & (row >= 0)
            d, safe = _batch_distances(db, q, qsq, nbrs, valid, p.metric,
                                       lut)
            j = jnp.argmin(d)
            best_d, best = d[j], safe[j]
            improved = best_d < c_d
            c = jnp.where(improved, best, c)
            c_d = jnp.where(improved, best_d, c_d)
            return c, c_d, improved, hops + 1, calcs + jnp.sum(valid)

        cur2, cur_d2, _, _, calcs2 = jax.lax.while_loop(
            hop_cond,
            hop_body,
            (cur, cur_d, jnp.bool_(True), jnp.int32(0), calcs),
        )
        cur = jnp.where(active_layer, cur2, cur)
        cur_d = jnp.where(active_layer, cur_d2, cur_d)
        calcs = jnp.where(active_layer, calcs2, calcs)
        return cur, cur_d, calcs

    cur, cur_d, calcs = jax.lax.fori_loop(
        0, n_layers, layer_body, (ep, ep_d, jnp.int32(1))
    )
    return cur, cur_d, calcs


# ---------------------------------------------------------------------------
# Layer 0: beam search with candidate/final lists (paper §5.2.3)
# ---------------------------------------------------------------------------


def _search_layer0(db: DeviceDB, q, qsq, ep, ep_d, p: SearchParams,
                   lut=None):
    n_words = bitmap_words(db.vectors.shape[0])
    C, EF = p.cand_size, p.ef

    visited = jnp.zeros((n_words,), jnp.uint32)
    _, visited = visited_test_and_set(
        visited, ep[None], jnp.ones((1,), jnp.bool_)
    )
    cand_d = jnp.full((C,), jnp.inf).at[0].set(ep_d)
    cand_i = jnp.full((C,), -1, jnp.int32).at[0].set(ep)
    fin_d = jnp.full((EF,), jnp.inf).at[0].set(ep_d)
    fin_i = jnp.full((EF,), -1, jnp.int32).at[0].set(ep)

    def cond(s):
        cand_d, _, fin_d, _, _, hops, _ = s
        # Algorithm 1 lines 2&5: candidates remain AND the nearest candidate
        # can still improve the final list. inf < inf is False, so an empty
        # candidate list terminates naturally.
        return (cand_d[0] < fin_d[-1]) & (hops < p.max_hops)

    def body(s):
        cand_d, cand_i, fin_d, fin_i, visited, hops, calcs = s
        # named scopes label the hop's stages in the compiled program's op
        # metadata (profiler traces); they change no operation
        with jax.named_scope("expand"):
            c = cand_i[0]
            # pop: shift the sorted array left (line 3).
            cand_d = jnp.roll(cand_d, -1).at[-1].set(jnp.inf)
            cand_i = jnp.roll(cand_i, -1).at[-1].set(-1)
            nbrs = db.l0_nbrs[c]                   # [maxM0_pad]
            valid = nbrs >= 0
        with jax.named_scope("visited"):
            was, visited = visited_test_and_set(
                visited, jnp.where(valid, nbrs, 0), valid)
            active = valid & ~was
        with jax.named_scope("distances"):
            d, safe = _batch_distances(db, q, qsq, nbrs, active, p.metric,
                                       lut)
            calcs = calcs + jnp.sum(active)
        with jax.named_scope("merge"):
            # line 11 guard: only candidates that can enter the final list.
            d = jnp.where(d < fin_d[-1], d, jnp.inf)
            ids = jnp.where(jnp.isfinite(d), safe, -1)
            order = jnp.argsort(d, stable=True)
            bd, bi = d[order], ids[order]
            fd, fi = merge_sorted(fin_d, fin_i, bd, bi)
            fin_d, fin_i = fd[:EF], fi[:EF]
            cd, ci = merge_sorted(cand_d, cand_i, bd, bi)
            cand_d, cand_i = cd[:C], ci[:C]
        return cand_d, cand_i, fin_d, fin_i, visited, hops + 1, calcs

    cand_d, cand_i, fin_d, fin_i, visited, hops, calcs = jax.lax.while_loop(
        cond,
        body,
        (cand_d, cand_i, fin_d, fin_i, visited, jnp.int32(0), jnp.int32(0)),
    )
    return fin_d, fin_i, hops, calcs


# ---------------------------------------------------------------------------
# Layer 0, fused: H hops per kernel invocation (paper §5.2, Fig. 6)
# ---------------------------------------------------------------------------


def _search_layer0_fused(db: DeviceDB, queries, qsq, ep, ep_d,
                         p: SearchParams):
    """Batched layer-0 beam search driven by the fused multi-hop Pallas
    kernel: the `lax.while_loop` body executes `p.fused_hops` hops per
    invocation with the beam state resident in VMEM (kernels/traversal.py),
    instead of one hop of small ops. Bit-identical to the vmapped
    `_search_layer0` — same merge semantics, same per-lane hop guard, same
    hops/dist_calcs accounting."""
    from repro.kernels.ops import fused_layer0   # lazy: kernels -> core is
                                                 # the only allowed direction
    B = queries.shape[0]
    n_words = bitmap_words(db.vectors.shape[0])
    C, EF = p.cand_size, p.ef

    visited = jnp.zeros((B, n_words), jnp.uint32)
    _, visited = jax.vmap(visited_test_and_set)(
        visited, ep[:, None], jnp.ones((B, 1), jnp.bool_))
    cand_d = jnp.full((B, C), jnp.inf).at[:, 0].set(ep_d)
    cand_i = jnp.full((B, C), -1, jnp.int32).at[:, 0].set(ep)
    fin_d = jnp.full((B, EF), jnp.inf).at[:, 0].set(ep_d)
    fin_i = jnp.full((B, EF), -1, jnp.int32).at[:, 0].set(ep)

    def cond(s):
        cand_d, _, fin_d, _, _, hops, _ = s
        return jnp.any((cand_d[:, 0] < fin_d[:, -1]) & (hops < p.max_hops))

    def body(s):
        cand_d, cand_i, fin_d, fin_i, visited, hops, calcs = s
        return fused_layer0(
            db.vectors, db.sqnorms, db.l0_nbrs, queries, qsq,
            cand_d, cand_i, fin_d, fin_i, visited, hops, calcs,
            fused_hops=p.fused_hops, max_hops=p.max_hops, metric=p.metric)

    s0 = (cand_d, cand_i, fin_d, fin_i, visited,
          jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))
    cand_d, cand_i, fin_d, fin_i, visited, hops, calcs = jax.lax.while_loop(
        cond, body, s0)
    return fin_d, fin_i, hops, calcs


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def search_one(db: DeviceDB, q, p: SearchParams, lut=None):
    """Full multi-layer search for one query. Returns (ids[k], dists[k], stats).

    Returned ids are *global* ids (db.gids applied); -1 marks empty slots.
    `lut` is the per-query [M, 256] ADC table for dtype="pq" databases.
    """
    q = q.astype(jnp.float32)
    qsq = q @ q
    ep, ep_d, up_calcs = _greedy_upper(db, q, qsq, p, lut)
    fin_d, fin_i, hops, calcs = _search_layer0(db, q, qsq, ep, ep_d, p, lut)
    k_d, k_i = fin_d[: p.k], fin_i[: p.k]
    k_g = jnp.where(k_i >= 0, db.gids[jnp.maximum(k_i, 0)], -1)
    return k_g, k_d, SearchStats(hops, calcs + up_calcs)


@functools.partial(jax.jit, static_argnames=("p",))
def batch_search(db: DeviceDB, queries, p: SearchParams, lut=None):
    """Multi-query search (paper §5.1.3): lockstep-masked vmap.

    `p.fused_hops > 1` swaps the layer-0 stage for the fused multi-hop
    Pallas kernel (H hops per invocation, beam state in VMEM); the upper
    layers and the k-extraction are shared, and results stay bit-identical
    to the hop-stepped path.

    `lut` ([B, M, 256]) switches distances to PQ asymmetric lookups. PQ
    always runs the hop-stepped layer 0 (no PQ variant of the fused
    in-memory kernel), so results are trivially identical at every
    `fused_hops` — matching the csd backend, whose PQ supersteps replay
    these semantics. Queries are not padded: db.vectors holds M-byte code
    rows and the LUT is the per-query operand.
    """
    p = p.resolve(db.l0_nbrs.shape[1])
    if lut is not None:
        return jax.vmap(lambda q, t: search_one(db, q, p, t))(
            queries.astype(jnp.float32), lut)
    d_pad = db.vectors.shape[-1]
    if queries.shape[-1] < d_pad:  # zero-pad to the lane-aligned raw-data table
        queries = jnp.pad(queries, ((0, 0), (0, d_pad - queries.shape[-1])))
    if p.fused_hops <= 1:
        return jax.vmap(lambda q: search_one(db, q, p))(queries)
    queries = queries.astype(jnp.float32)
    # same per-query ops as search_one, vmapped — not an einsum, so the
    # reduction order (and thus every distance bit) matches the legacy path
    qsq = jax.vmap(lambda q: q @ q)(queries)
    ep, ep_d, up_calcs = jax.vmap(
        lambda q, qs: _greedy_upper(db, q, qs, p))(queries, qsq)
    fin_d, fin_i, hops, calcs = _search_layer0_fused(
        db, queries, qsq, ep, ep_d, p)
    k_d, k_i = fin_d[:, : p.k], fin_i[:, : p.k]
    k_g = jnp.where(k_i >= 0, db.gids[jnp.maximum(k_i, 0)], -1)
    return k_g, k_d, SearchStats(hops, calcs + up_calcs)
