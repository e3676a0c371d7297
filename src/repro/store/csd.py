"""Out-of-core two-stage search over the block store (the `csd` backend).

This is the repo's model of the paper's computational-storage dataflow: the
restructured DB lives on "flash" (the block store), a small PageCache
stands in for the SmartSSD DRAM, and only block-granular reads flow to the
compute side — host memory stays bounded by `cache_bytes` no matter how
large the dataset is.

The traversal is the *same algorithm* as the accelerator-resident kernel
(core/search.py), re-driven from the host so every data access becomes a
batched block read:

  per hop : pop the best candidates for the whole query batch in lockstep,
            read their neighbor-list rows (layer-0 table), test the visited
            bitmap, read only the unvisited neighbors' vector + sqnorm rows
            (raw-data + index tables), and feed the gathered tile to a
            jitted hop kernel built from the SAME primitives the device
            kernel uses (`metric_distance`, `merge_sorted`) — so the csd
            backend returns bit-identical top-k to the `partitioned`
            backend at equal ef/K/metric.

Stage 2 (`rerank=True`) gathers the candidate vectors back from the store
and re-scores them with `api.rerank.batched_rerank` over a compact,
monotonically-remapped id space — again exactly matching the in-memory
backends. The async Prefetcher overlaps hop t+1's neighbor-block fetches
with hop t's device compute (paper §5.2).

Quantized stores (IndexSpec.dtype uint8/int8 — the paper's SIFT1B regime):
the raw-data table holds 1-byte codes, so every vector row is 4x smaller
and `QueryStats.bytes_read` drops accordingly — this is exactly why the
paper's billion-point database fits the SmartSSD. The traversal runs in
code space (gathered tiles cast to f32, same as the resident kernel),
stage-1 distances are rescaled by `scale**2` at the edge, and stage-2
rerank dequantizes the gathered rows back to float32.

Product-quantized stores (IndexSpec.dtype "pq"): the raw-data table holds
M-byte PQ code rows (16x smaller than uint8 at M=8/d=128) and every hop
kernel takes the per-query [M, 256] ADC LUT instead of (q, qsq) — each
distance is the `core.search.pq_lut_distances` gather + sum, so the csd
traversal stays bit-identical to the in-memory PQ backends. Stage 1 skips
the sqnorm reads entirely (ADC needs no norms), the superstep shadow
predicts pops with a numpy twin of the same LUT (prediction-only:
mispredictions roll back exactly like the f32 path), and stage-2 rerank
reads TRUE float32 rows back from the extra `rerank_vectors` table —
reranking over decoded PQ rows would recover nothing, since ADC already
IS the distance to the reconstruction.
"""

from __future__ import annotations

import functools
import threading
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partitioned import (build_partitioned_db, merge_topk,
                                    quantize_db_vectors)
from repro.core.search import (SearchParams, bitmap_words, merge_sorted,
                               metric_distance, pq_lut_distances)
from repro.optim.compression import build_pq_lut
from repro.obs.metrics import REGISTRY, next_uid
from repro.obs.trace import TRACER
from repro.store.layout import StoreReader, open_store, write_store

if typing.TYPE_CHECKING:  # repro.api imports this module to register the
    from repro.api.types import IndexSpec  # backend — keep runtime acyclic
                                           # by importing api lazily

__all__ = ["CSDBackend", "store_search"]


# ---------------------------------------------------------------------------
# Jitted hop kernels — the device-side compute fed by store gathers.
# The arithmetic mirrors core/search.py line for line; gathers that the
# resident kernel does from HBM arrive here as host-assembled tiles.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric",))
def _query_prep(q, ep_vec, ep_sq, metric):
    """qsq per query + distance to the partition entry point."""
    def one(qq):
        qsq = qq @ qq
        ep_d = metric_distance(metric, jnp.sum(ep_vec * qq, axis=-1),
                               ep_sq, qsq)
        return qsq, ep_d
    return jax.vmap(one)(q)


@jax.jit
def _query_prep_pq(luts, ep_code):
    """ADC distance to the partition entry point, per query (dtype="pq").
    Same expression as `_greedy_upper`'s PQ entry distance, so the bits
    match the in-memory backends."""
    return jax.vmap(lambda lut: pq_lut_distances(lut, ep_code[None])[0])(luts)


@functools.partial(jax.jit, static_argnames=("metric",))
def _upper_step(improved, c, c_d, calcs, nbrs, valid, vecs, sqs, q, qsq,
                metric, lut=None):
    """One lockstep greedy hop in an upper layer (cf. _greedy_upper).

    With `lut` set (dtype="pq") `vecs` holds the gathered [M0, M] uint8
    code tiles and the distance is the LUT gather + sum; sqs/q/qsq ride
    along unused."""
    def one(improved, c, c_d, calcs, nbrs, valid, vecs, sqs, qq, qsq,
            *lut):
        with jax.named_scope("distances"):
            if lut:
                d = pq_lut_distances(lut[0], vecs)
            else:
                d = metric_distance(metric, jnp.sum(vecs * qq, axis=-1),
                                    sqs, qsq)
            d = jnp.where(valid, d, jnp.inf)
        with jax.named_scope("select"):
            safe = jnp.where(valid, nbrs, 0)
            j = jnp.argmin(d)
            best_d, best = d[j], safe[j]
            imp = best_d < c_d
            sel = lambda n, o: jnp.where(improved, n, o)
            return (sel(jnp.where(imp, best, c), c),
                    sel(jnp.where(imp, best_d, c_d), c_d),
                    improved & imp,
                    sel(calcs + jnp.sum(valid), calcs))
    extra = () if lut is None else (lut,)
    return jax.vmap(one)(improved, c, c_d, calcs, nbrs, valid, vecs, sqs,
                         q, qsq, *extra)


@functools.partial(jax.jit, static_argnames=("metric",))
def _layer0_step(active, cand_d, cand_i, fin_d, fin_i, hops, calcs,
                 nbrs, act, vecs, sqs, q, qsq, metric, lut=None):
    """One lockstep beam hop at layer 0 (cf. _search_layer0's body).

    `act` = neighbor lanes that are valid AND unvisited — the visited
    bitmap is tested/updated on the host so only unvisited neighbors'
    vectors were read from the store (the paper's single-bit visited list
    as a flash-read filter). With `lut` set (dtype="pq"), `vecs` holds
    uint8 code tiles and the distance is the LUT gather + sum."""
    EF = fin_d.shape[-1]
    C = cand_d.shape[-1]

    def one(active, cand_d, cand_i, fin_d, fin_i, hops, calcs,
            nbrs, act, vecs, sqs, qq, qsq, *lut):
        # named scopes as in core/search.py's hop body (op metadata only)
        with jax.named_scope("expand"):
            ncand_d = jnp.roll(cand_d, -1).at[-1].set(jnp.inf)
            ncand_i = jnp.roll(cand_i, -1).at[-1].set(-1)
        with jax.named_scope("distances"):
            # mul+sum matches core/search.py's _batch_distances bit for
            # bit — see the note there on matvec reduction-order
            # instability; the PQ branch is the one pq_lut_distances
            # accumulation for the same reason
            if lut:
                d = pq_lut_distances(lut[0], vecs)
            else:
                d = metric_distance(metric, jnp.sum(vecs * qq, axis=-1),
                                    sqs, qsq)
            d = jnp.where(act, d, jnp.inf)
            ncalcs = calcs + jnp.sum(act)
        with jax.named_scope("merge"):
            d = jnp.where(d < fin_d[-1], d, jnp.inf)
            safe = jnp.where(act, nbrs, 0)
            ids = jnp.where(jnp.isfinite(d), safe, -1)
            order = jnp.argsort(d, stable=True)
            bd, bi = d[order], ids[order]
            fd, fi = merge_sorted(fin_d, fin_i, bd, bi)
            cd, ci = merge_sorted(ncand_d, ncand_i, bd, bi)
            sel = lambda n, o: jnp.where(active, n, o)
            return (sel(cd[:C], cand_d), sel(ci[:C], cand_i),
                    sel(fd[:EF], fin_d), sel(fi[:EF], fin_i),
                    hops + active.astype(hops.dtype),
                    sel(ncalcs, calcs))
    extra = () if lut is None else (lut,)
    return jax.vmap(one)(active, cand_d, cand_i, fin_d, fin_i, hops, calcs,
                         nbrs, act, vecs, sqs, q, qsq, *extra)


@functools.partial(jax.jit, static_argnames=("metric", "max_hops"))
def _layer0_superstep(cand_d, cand_i, fin_d, fin_i, hops, calcs,
                      spec, nbrs, act, vecs, sqs, q, qsq, metric,
                      max_hops, lut=None):
    """Replay up to H speculated beam hops in ONE dispatch (cf. the per-hop
    `_layer0_step`) — the csd half of the fused traversal (paper Fig. 6).

    The host plans a whole superstep ahead of time: it simulates the pop
    sequence in numpy, performs the visited test-and-set and the batched
    store reads for all H hops, and hands the kernel per-hop tiles
    (`spec[h]` = predicted pop, -1 where the simulation saw the lane
    terminate; `nbrs/act/vecs/sqs[h]` = that hop's neighbor row, unvisited
    mask, and gathered rows). The kernel *validates* each hop before
    applying it: hop h of a lane counts only while every prior hop
    matched, the lane is live by the device-state termination test, and
    the device candidate head equals the speculated pop. The visited
    evolution (hence `act` and the tiles) depends only on the pop
    sequence, never on distance values, so a validated hop is bit-exact —
    the arithmetic here is the same mul+sum / stable-argsort /
    `merge_sorted` as the hop-stepped kernel. Hop 0 is planned from synced
    device state, so every active lane advances at least one hop per
    superstep; the rare ulp-level mispredictions (numpy's reduction vs
    XLA's flipping a near-tie) stop the replay early and the host rolls
    the speculation back. Returns the per-lane count of applied hops so
    the host can do exactly that."""
    H = spec.shape[-1]
    EF = fin_d.shape[-1]
    C = cand_d.shape[-1]

    def one(cand_d, cand_i, fin_d, fin_i, hops, calcs,
            spec, nbrs, act, vecs, sqs, qq, qsq, *lut):
        ok = jnp.bool_(True)
        applied = jnp.int32(0)
        for h in range(H):                       # static unroll
            live = (cand_d[0] < fin_d[-1]) & (hops < max_hops)
            sim_live = spec[h] >= 0
            match = live & sim_live & (cand_i[0] == spec[h])
            app = ok & match
            # a terminated lane the simulation also saw terminate stays
            # valid (frozen); any live/spec disagreement ends the replay
            ok = ok & (match | (~live & ~sim_live))
            ncand_d = jnp.roll(cand_d, -1).at[-1].set(jnp.inf)
            ncand_i = jnp.roll(cand_i, -1).at[-1].set(-1)
            if lut:
                d = pq_lut_distances(lut[0], vecs[h])
            else:
                d = metric_distance(metric, jnp.sum(vecs[h] * qq, axis=-1),
                                    sqs[h], qsq)
            d = jnp.where(act[h], d, jnp.inf)
            ncalcs = calcs + jnp.sum(act[h])
            d = jnp.where(d < fin_d[-1], d, jnp.inf)
            safe = jnp.where(act[h], nbrs[h], 0)
            ids = jnp.where(jnp.isfinite(d), safe, -1)
            order = jnp.argsort(d, stable=True)
            bd, bi = d[order], ids[order]
            fd, fi = merge_sorted(fin_d, fin_i, bd, bi)
            cd, ci = merge_sorted(ncand_d, ncand_i, bd, bi)
            sel = lambda n, o: jnp.where(app, n, o)
            cand_d, cand_i = sel(cd[:C], cand_d), sel(ci[:C], cand_i)
            fin_d, fin_i = sel(fd[:EF], fin_d), sel(fi[:EF], fin_i)
            hops = hops + app.astype(hops.dtype)
            calcs = sel(ncalcs, calcs)
            applied = applied + app.astype(jnp.int32)
        return cand_d, cand_i, fin_d, fin_i, hops, calcs, applied
    extra = () if lut is None else (lut,)
    return jax.vmap(one)(cand_d, cand_i, fin_d, fin_i, hops, calcs,
                         spec, nbrs, act, vecs, sqs, q, qsq, *extra)


def _metric_dist_np(metric: str, dot, xsq, qsq):
    """numpy twin of metric_distance — only used to *predict* the pop
    sequence for superstep planning; every applied decision is re-made on
    device, so a last-ulp disagreement costs a shorter superstep, never a
    wrong result."""
    if metric == "l2":
        return np.maximum(xsq - 2.0 * dot + qsq, 0.0)
    if metric == "ip":
        return -dot
    if metric == "cosine":
        return 1.0 - dot
    raise ValueError(f"unknown metric {metric!r}")


def _adc_np(lut_h: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """numpy twin of pq_lut_distances over [B, M0, M] code tiles —
    prediction-only (superstep planning), same rollback safety as
    `_metric_dist_np`: a last-ulp disagreement with the device LUT sum
    costs a shorter superstep, never a wrong result."""
    b_ix = np.arange(lut_h.shape[0])[:, None, None]
    m_ix = np.arange(lut_h.shape[1])[None, None, :]
    return lut_h[b_ix, m_ix, codes.astype(np.int64)].sum(-1)


# ---------------------------------------------------------------------------
# Host-driven traversal over store reads
# ---------------------------------------------------------------------------


def _gather_vec_sq(reader: StoreReader, p: int, ids: np.ndarray,
                   mask: np.ndarray):
    """Vector + sqnorm tiles for masked neighbor lanes; zeros elsewhere
    (masked lanes are forced to +inf downstream, so zeros are inert).

    Neighbor ids repeat across lanes whenever two queries expand nodes
    that share a neighbor, so the store read is issued over the *unique*
    ids and the rows scattered back — the reader never sees (or pays row
    bookkeeping for) the duplicates, and the returned tiles are unchanged."""
    vecs = np.zeros(ids.shape + (reader.d_pad,), np.float32)
    sqs = np.zeros(ids.shape, np.float32)
    if mask.any():
        uniq, inv = np.unique(ids[mask], return_inverse=True)
        rows = reader.row("vectors", p, uniq)
        vecs[mask] = reader.read_rows("vectors", rows)[inv]
        sqs[mask] = reader.read_rows("sqnorms", rows)[inv, 0]
    return vecs, sqs


def _gather_codes(reader: StoreReader, p: int, ids: np.ndarray,
                  mask: np.ndarray) -> np.ndarray:
    """PQ variant of `_gather_vec_sq`: M-byte uint8 code tiles only
    (reader.d_pad == M for a PQ store). ADC needs no norms, so the sqnorm
    table is never read in stage 1 — code rows + graph rows are the whole
    per-hop flash traffic. Masked lanes stay zero (inert: forced to +inf
    downstream)."""
    codes = np.zeros(ids.shape + (reader.d_pad,), np.uint8)
    if mask.any():
        uniq, inv = np.unique(ids[mask], return_inverse=True)
        rows = reader.row("vectors", p, uniq)
        codes[mask] = reader.read_rows("vectors", rows)[inv]
    return codes


def _visited_test_and_set(bitmap: np.ndarray, ids: np.ndarray,
                          valid: np.ndarray) -> np.ndarray:
    """Host mirror of core.search.visited_test_and_set over [B, M] lanes.
    Returns `was` (visited-before OR invalid); sets bits for valid lanes."""
    B = bitmap.shape[0]
    safe = np.where(valid, ids, 0).astype(np.int64)
    w = safe >> 5
    b5 = (safe & 31).astype(np.uint32)
    rows = np.arange(B)[:, None]
    was = ((bitmap[rows, w] >> b5) & np.uint32(1)) > 0
    was |= ~valid
    bits = np.where(~was, np.left_shift(np.uint32(1), b5), np.uint32(0))
    np.bitwise_or.at(bitmap, (rows, w), bits)
    return was


def _layer0_supersteps(reader: StoreReader, p: int, q_pad, qsq, bitmap,
                       cand_d, cand_i, fin_d, fin_i, hops, calcs,
                       sp: SearchParams, luts=None, lut_h=None):
    """Speculative, PIPELINED H-hop supersteps over layer 0
    (`fused_hops > 1`).

    The host shadows the beam in numpy to *predict* the next H pops —
    reading neighbor rows and vector/sqnorm tiles as it goes, and applying
    the visited test-and-set for the whole superstep up front — then
    `_layer0_superstep` replays the hops on device, validating each
    against true device state. The two run as a software pipeline: while
    superstep k executes on device, the host plans superstep k+1 from the
    shadow (store reads overlap kernel compute, the paper's §5.3 overlap
    applied to whole supersteps), and only the tiny per-lane `applied`
    count is synced per superstep. Full beam state crosses the host
    boundary only at pipeline bubbles: the start, a misprediction (a
    last-ulp distance tie ordering differently in numpy than in XLA), or
    the shadow terminating while the device disagrees.

    The shadow only ever influences which hops get *planned* — every
    applied hop re-derives its pop, guard, and merge on device, so the
    result is bit-identical to the hop-stepped loop at any H. A lane
    whose speculation was rejected has its visited bits rolled back and
    its shadow resynced from device state, after which its next superstep
    is planned from truth and must apply ≥ 1 hop — no livelock. Returns
    the updated beam plus the number of supersteps (device dispatches ==
    host sync points) taken.

    dtype="pq": `luts` is the device [B, M, 256] ADC table (the kernel's
    distance operand) and `lut_h` its host copy — the shadow predicts
    with `_adc_np` over the same table values, so the only divergence
    source left is the accumulation order, exactly like the f32 path."""
    B = bitmap.shape[0]
    H = sp.fused_hops
    M0, D = reader.m0_pad, reader.d_pad
    C, EF = sp.cand_size, sp.ef
    metric = sp.metric
    steps = 0
    with TRACER.child_span("device-wait"):
        qh = np.asarray(q_pad, np.float32)
        qsqh = np.asarray(qsq, np.float32)
        # shadow of the device beam, advanced in place by plan(); resynced
        # from device arrays only at pipeline bubbles
        scand_d = np.array(cand_d)
        scand_i = np.array(cand_i)
        sfin_d = np.array(fin_d)
        shops = np.array(hops)

    def plan():
        """Plan up to H hops from shadow state (store reads + visited
        test-and-set happen here). Returns None if the shadow sees every
        lane terminated; otherwise the per-hop tiles for the kernel."""
        live0 = (scand_d[:, 0] < sfin_d[:, -1]) & (shops < sp.max_hops)
        if not live0.any():
            return None
        snap = bitmap.copy()
        spec = np.full((B, H), -1, np.int32)
        nbrs_t = np.full((B, H, M0), -1, np.int32)
        act_t = np.zeros((B, H, M0), bool)
        vecs_t = np.zeros((B, H, M0, D),
                          np.uint8 if lut_h is not None else np.float32)
        sqs_t = np.zeros((B, H, M0), np.float32)
        planned = np.zeros(B, np.int32)          # shadow-live hops per lane
        for h in range(H):
            live = (scand_d[:, 0] < sfin_d[:, -1]) & (shops < sp.max_hops)
            if not live.any():
                break
            pops = np.where(live, scand_i[:, 0], -1).astype(np.int32)
            spec[:, h] = pops
            planned += live
            lanes = np.flatnonzero(live)
            nbrs = nbrs_t[:, h]
            nbrs[lanes] = reader.read_rows(
                "l0_nbrs", reader.row("l0_nbrs", p, pops[lanes]))
            valid = (nbrs >= 0) & live[:, None]
            was = _visited_test_and_set(bitmap, nbrs, valid)
            act = valid & ~was
            act_t[:, h] = act
            if lut_h is not None:
                v = _gather_codes(reader, p, nbrs, act)
                vecs_t[:, h] = v
                d = _adc_np(lut_h, v)
            else:
                v, s = _gather_vec_sq(reader, p, nbrs, act)
                vecs_t[:, h], sqs_t[:, h] = v, s
                # shadow hop: the same pop/guard/merge, numpy arithmetic
                d = _metric_dist_np(metric,
                                    np.einsum("bmd,bd->bm", v, qh),
                                    s, qsqh[:, None])
            d = np.where(act, d, np.inf)
            d = np.where(d < sfin_d[:, -1:], d, np.inf)
            ids = np.where(np.isfinite(d), np.where(act, nbrs, 0), -1)
            o = np.argsort(d, axis=1, kind="stable")
            bd = np.take_along_axis(d, o, axis=1)
            bi = np.take_along_axis(ids, o, axis=1)
            pc_d = np.concatenate(
                [scand_d[:, 1:], np.full((B, 1), np.inf, np.float32)], 1)
            pc_i = np.concatenate(
                [scand_i[:, 1:], np.full((B, 1), -1, scand_i.dtype)], 1)
            o2 = np.argsort(np.concatenate([pc_d, bd], axis=1),
                            axis=1, kind="stable")
            sel = live[:, None]
            scand_d[:] = np.where(sel, np.take_along_axis(
                np.concatenate([pc_d, bd], 1), o2, 1)[:, :C], scand_d)
            scand_i[:] = np.where(sel, np.take_along_axis(
                np.concatenate([pc_i, bi], 1), o2, 1)[:, :C], scand_i)
            sfin_d[:] = np.where(sel, np.sort(
                np.concatenate([sfin_d, bd], 1), axis=1)[:, :EF], sfin_d)
            shops[:] = shops + live
        return dict(snap=snap, spec=spec, nbrs=nbrs_t, act=act_t,
                    vecs=vecs_t, sqs=sqs_t, planned=planned)

    def resync(lanes):
        """Pull true device beam state back into the shadow for `lanes`
        (boolean mask) — the only full-state host syncs in this driver."""
        with TRACER.child_span("device-wait"):
            scand_d[lanes] = np.asarray(cand_d)[lanes]
            scand_i[lanes] = np.asarray(cand_i)[lanes]
            sfin_d[lanes] = np.asarray(fin_d)[lanes]
            shops[lanes] = np.asarray(hops)[lanes]

    def settle(prev, applied_h, nxt):
        """Handle rejected speculation of the just-finished superstep
        `prev`: per bad lane, restore its visited bits to the pre-`prev`
        snapshot plus the applied prefix (this also wipes any bits the
        in-flight plan `nxt` set from that lane's diverged shadow),
        resync its shadow from device truth, and void its slots in
        `nxt` so the kernel skips it there."""
        bad = applied_h < prev["planned"]
        if not bad.any():
            return False
        for b in np.flatnonzero(bad):
            bitmap[b] = prev["snap"][b]
            for h in range(int(applied_h[b])):
                ib = prev["nbrs"][b, h][prev["act"][b, h]]
                np.bitwise_or.at(
                    bitmap[b], ib >> 5,
                    np.left_shift(np.uint32(1),
                                  (ib & 31).astype(np.uint32)))
        resync(bad)
        if nxt is not None:
            nxt["spec"][bad] = -1
            nxt["act"][bad] = False
            nxt["planned"][bad] = 0
        return True

    pending = None                   # (plan, applied) in flight on device
    while True:
        ps = plan()                  # overlaps the in-flight kernel
        if pending is not None:
            prev, applied = pending
            with TRACER.child_span("device-wait"):
                applied_h = np.asarray(applied)   # sync: superstep done
            pending = None
            if settle(prev, applied_h, ps) and ps is None:
                ps = plan()          # resynced lanes may still be live
        if ps is None:
            # shadow says done; the device has the final word (a last-ulp
            # tie can terminate the shadow while the device beam is live)
            with TRACER.child_span("device-wait"):
                live = ((np.asarray(cand_d)[:, 0]
                         < np.asarray(fin_d)[:, -1])
                        & (np.asarray(hops) < sp.max_hops))
            if not live.any():
                break
            resync(live)
            ps = plan()
            if ps is None:           # cannot happen: resynced == live
                break
        with TRACER.child_span("hop_superstep", superstep=steps,
                               fused_hops=H,
                               active=int((ps["planned"] > 0).sum())):
            with TRACER.child_span("hop-kernel"):
                (cand_d, cand_i, fin_d, fin_i, hops, calcs,
                 applied) = _layer0_superstep(
                    cand_d, cand_i, fin_d, fin_i, hops, calcs,
                    jnp.asarray(ps["spec"]), jnp.asarray(ps["nbrs"]),
                    jnp.asarray(ps["act"]), jnp.asarray(ps["vecs"]),
                    jnp.asarray(ps["sqs"]), q_pad, qsq, metric, sp.max_hops,
                    lut=luts)
        pending = (ps, applied)
        steps += 1
    return cand_d, cand_i, fin_d, fin_i, hops, calcs, steps


def _search_one_partition(reader: StoreReader, p: int, q_pad: jnp.ndarray,
                          params: SearchParams, luts=None, lut_h=None):
    """Lockstep batched search of one sub-graph, all data via the store.

    Returns (gids [B,k], dists [B,k], hops [B], calcs [B], steps) —
    numerically identical to `batch_search` on the resident partition.
    `steps` counts host-sync'd traversal rounds: one per hop on the legacy
    path, one per `fused_hops`-hop superstep on the fused path.
    `luts`/`lut_h` are the device/host per-query ADC tables for dtype="pq"
    (store_search builds them once per batch; one code space per index,
    shared across partitions)."""
    B = int(q_pad.shape[0])
    pq = luts is not None
    sp = params.resolve(reader.m0_pad)
    C, EF, K = sp.cand_size, sp.ef, sp.k
    metric = sp.metric

    ep = int(reader.entry[p] if reader.entry.ndim else reader.entry)
    max_level = int(reader.max_level[p] if reader.max_level.ndim
                    else reader.max_level)
    ep_row = reader.row("vectors", p, [ep])
    if pq:
        ep_code = jnp.asarray(reader.read_rows("vectors", ep_row)[0])
        qsq = jnp.zeros((B,), jnp.float32)       # unused by ADC
        ep_d = _query_prep_pq(luts, ep_code)
    else:
        ep_vec = jnp.asarray(
            reader.read_rows("vectors", ep_row)[0].astype(np.float32))
        ep_sq = jnp.asarray(reader.read_rows("sqnorms", ep_row)[0, 0])
        qsq, ep_d = _query_prep(q_pad, ep_vec, ep_sq, metric)

    # -- upper layers: lockstep greedy descent (paper §5.2.2) ---------------
    cur = jnp.full((B,), ep, jnp.int32)
    cur_d = ep_d
    calcs = jnp.ones((B,), jnp.int32)
    n_layers = reader.n_layers
    for layer in range(min(n_layers, max_level), 0, -1):
        improved = jnp.ones((B,), bool)
        hop = 0
        while hop < sp.upper_hops:
            with TRACER.child_span("upper-hop", layer=layer, hop=hop):
                with TRACER.child_span("device-wait"):
                    imp_h = np.asarray(improved)
                    cur_h = np.asarray(cur)
                if not imp_h.any():
                    break
                nbrs = np.full((B, reader.m_pad), -1, np.int32)
                ptr = reader.read_rows(
                    "up_ptr", reader.row("up_ptr", p, cur_h[imp_h]))[:, 0]
                has = ptr >= 0
                if has.any():
                    urows = reader.up_row(p, layer - 1, ptr[has])
                    lanes = np.flatnonzero(imp_h)[has]
                    nbrs[lanes] = reader.read_rows("up_nbrs", urows)
                valid = (nbrs >= 0) & imp_h[:, None]
                if pq:
                    vecs = _gather_codes(reader, p, nbrs, valid)
                    sqs = np.zeros(nbrs.shape, np.float32)
                else:
                    vecs, sqs = _gather_vec_sq(reader, p, nbrs, valid)
                cur, cur_d, improved, calcs = _upper_step(
                    improved, cur, cur_d, calcs,
                    jnp.asarray(nbrs), jnp.asarray(valid),
                    jnp.asarray(vecs), jnp.asarray(sqs), q_pad, qsq, metric,
                    lut=luts)
            hop += 1

    # -- layer 0: lockstep beam search (paper §5.2.3) -----------------------
    n_words = bitmap_words(reader.n_pad)
    bitmap = np.zeros((B, n_words), np.uint32)
    with TRACER.child_span("device-wait"):
        ep_ids = np.asarray(cur)[:, None]
    _visited_test_and_set(bitmap, ep_ids, np.ones((B, 1), bool))
    cand_d = jnp.full((B, C), jnp.inf).at[:, 0].set(cur_d)
    cand_i = jnp.full((B, C), -1, jnp.int32).at[:, 0].set(cur)
    fin_d = jnp.full((B, EF), jnp.inf).at[:, 0].set(cur_d)
    fin_i = jnp.full((B, EF), -1, jnp.int32).at[:, 0].set(cur)
    hops = jnp.zeros((B,), jnp.int32)

    if sp.fused_hops > 1:
        # fused path: the superstep driver batches its own store reads per
        # H-hop plan, so the speculative next-hop prefetcher is redundant
        # traffic — it is deliberately not invoked here
        (cand_d, cand_i, fin_d, fin_i, hops, calcs,
         steps) = _layer0_supersteps(reader, p, q_pad, qsq, bitmap,
                                     cand_d, cand_i, fin_d, fin_i,
                                     hops, calcs, sp, luts=luts,
                                     lut_h=lut_h)
    else:
        hop_no = 0
        while True:
            with TRACER.child_span("device-wait"):
                cd_h, fd_h = np.asarray(cand_d), np.asarray(fin_d)
                hops_h = np.asarray(hops)
            active = (cd_h[:, 0] < fd_h[:, -1]) & (hops_h < sp.max_hops)
            if not active.any():
                break
            with TRACER.child_span("hop", hop=hop_no,
                                   active=int(active.sum())):
                with TRACER.child_span("device-wait"):
                    pops = np.asarray(cand_i)[:, 0]
                nbrs = np.full((B, reader.m0_pad), -1, np.int32)
                if active.any():
                    lanes = np.flatnonzero(active)
                    nbrs[lanes] = reader.read_rows(
                        "l0_nbrs", reader.row("l0_nbrs", p, pops[lanes]))
                valid = (nbrs >= 0) & active[:, None]
                was = _visited_test_and_set(bitmap, nbrs, valid)
                act = valid & ~was
                if pq:
                    vecs = _gather_codes(reader, p, nbrs, act)
                    sqs = np.zeros(nbrs.shape, np.float32)
                else:
                    vecs, sqs = _gather_vec_sq(reader, p, nbrs, act)
                # hop-kernel covers only the jitted dispatch (the submit
                # cost); the kernel's device time shows up as the
                # device-wait of the prefetch sync that follows
                with TRACER.child_span("hop-kernel"):
                    cand_d, cand_i, fin_d, fin_i, hops, calcs = _layer0_step(
                        jnp.asarray(active), cand_d, cand_i, fin_d, fin_i,
                        hops, calcs, jnp.asarray(nbrs), jnp.asarray(act),
                        jnp.asarray(vecs), jnp.asarray(sqs), q_pad, qsq,
                        metric, lut=luts)
                # overlap the next hop's fetches with this round-trip; the
                # candidates are this hop's kernel output, so this waits on it
                with TRACER.child_span("device-wait"):
                    nxt = np.asarray(cand_i)[:, :2]
                reader.prefetch_next_hop(p, nxt)
            hop_no += 1
        steps = hop_no

    with TRACER.child_span("device-wait"):
        k_i = np.asarray(fin_i)[:, :K]
        k_d = np.asarray(fin_d)[:, :K]
        hops_h, calcs_h = np.asarray(hops), np.asarray(calcs)
    k_g = np.full_like(k_i, -1)
    vmask = k_i >= 0
    if vmask.any():
        k_g[vmask] = reader.read_rows(
            "gids", reader.row("gids", p, k_i[vmask]))[:, 0]
    return k_g, k_d, hops_h, calcs_h, steps


def store_search(reader: StoreReader, queries, params: SearchParams,
                 merge: bool = True, pq_quant=None):
    """Two-stage search over every partition of the store.

    merge=True  -> (ids [B,k], dists [B,k], hops [B], calcs [B], supersteps)
    merge=False -> the unmerged [B, P*k] stage-1 pool (rerank consumes it).

    `supersteps` is the total host-sync'd traversal rounds across
    partitions — equal to total layer-0 hop rounds at fused_hops=1,
    roughly hops/fused_hops on the fused path.

    `pq_quant` is the index's fitted PQQuantizer for dtype="pq" stores:
    queries stay float32 (NOT padded to the store's d_pad, which is the
    code width M) and the per-query ADC LUT is built once here through
    the one shared jitted builder, then reused by every partition.
    """
    REGISTRY.gauge("traversal_fused_hops").set(float(params.fused_hops))
    q = np.asarray(queries, np.float32)
    luts = lut_h = None
    if pq_quant is not None:
        luts = build_pq_lut(jnp.asarray(q),
                            jnp.asarray(pq_quant.codebooks))
        with TRACER.child_span("device-wait"):
            lut_h = np.asarray(luts)  # shadow planner's prediction twin
    elif q.shape[-1] < reader.d_pad:
        q = np.pad(q, ((0, 0), (0, reader.d_pad - q.shape[-1])))
    q_pad = jnp.asarray(q)
    per_ids, per_ds = [], []
    hops = np.zeros(q.shape[0], np.int64)
    calcs = np.zeros(q.shape[0], np.int64)
    supersteps = 0
    for p in range(reader.num_partitions):
        with TRACER.child_span("traversal", partition=p):
            gi, gd, h, c, s = _search_one_partition(reader, p, q_pad, params,
                                                    luts=luts, lut_h=lut_h)
        per_ids.append(gi)
        per_ds.append(gd)
        hops += h
        calcs += c
        supersteps += s
    ids = np.stack(per_ids, axis=1)          # [B, P, k]
    ds = np.stack(per_ds, axis=1)
    if not merge:
        b = ids.shape[0]
        return ids.reshape(b, -1), ds.reshape(b, -1), hops, calcs, supersteps
    out_i, out_d = merge_topk(jnp.asarray(ids), jnp.asarray(ds), params.k)
    return out_i, out_d, hops, calcs, supersteps


# ---------------------------------------------------------------------------
# Backend
# ---------------------------------------------------------------------------


def _collect_csd(be: "CSDBackend"):
    """Snapshot-time metric samples per live csd backend (repro.obs).

    Publishes the per-query counters `QueryStats` carries (supersteps,
    dist_calcs, bytes_read) as cumulative REGISTRY series — the ADC and
    fused-superstep wins in the Prometheus export, not just per query —
    plus the store geometry gauges `repro.obs.calibrate` needs to price
    the workload (padded graph degree, vector row bytes, block size)."""
    r = be.reader
    labels = {"backend": be.uid}
    with be._tlock:
        q, hops, calcs, steps = (be._queries, be._hops, be._dist_calcs,
                                 be._supersteps)
    t = r.blockfile.tables["vectors"]
    row_bytes = int(t["cols"]) * np.dtype(t["dtype"]).itemsize
    return [
        ("counter", "csd_queries_total", labels, q),
        ("counter", "csd_hops_total", labels, hops),
        ("counter", "csd_supersteps_total", labels, steps),
        ("counter", "search_dist_calcs_total", labels, calcs),
        ("counter", "csd_bytes_read_total", labels,
         r.cache.snapshot()["bytes_read"]),
        ("gauge", "csd_graph_degree", labels, r.m0_pad),
        ("gauge", "csd_vector_row_bytes", labels, row_bytes),
        ("gauge", "csd_block_size", labels, r.block_size),
    ]


class CSDBackend:
    """Storage-resident two-stage engine (registered as `csd`).

    Build restructures the dataset into the block store at
    `spec.storage_path`; serving holds only the PageCache (`cache_bytes`)
    in memory. `rerank` needs no `keep_vectors` — stage 2 reads the raw
    vectors back from the store.
    """

    uses_graph = True

    def __init__(self, spec: IndexSpec, reader: StoreReader):
        self.spec = spec
        self.reader = reader
        self.quant = spec.quantizer()
        self.is_pq = spec.dtype == "pq"
        # cumulative engine counters behind the csd_*/search_* series
        self.uid = next_uid()
        self._tlock = threading.Lock()
        self._queries = 0
        self._hops = 0
        self._dist_calcs = 0
        self._supersteps = 0
        REGISTRY.register_collector(self, _collect_csd)

    @staticmethod
    def _storage_path(spec: IndexSpec) -> str:
        if not spec.storage_path:
            raise ValueError(
                "backend='csd' persists the database to a block store: set "
                "IndexSpec(storage_path=...) to its directory")
        return spec.storage_path

    @classmethod
    def build(cls, vectors: np.ndarray, spec: IndexSpec, mesh=None):
        path = cls._storage_path(spec)
        pdb = build_partitioned_db(vectors, spec.num_partitions, spec.hnsw)
        return cls._write(path, pdb, spec)

    @classmethod
    def from_partitioned(cls, pdb, spec: IndexSpec, raw=None):
        """Convert an already-built resident PartitionedDB into an
        out-of-core service (benchmarks reuse one graph build).

        For a dtype="pq" pdb whose vectors leaf already holds code rows
        (PartitionedBackend.build swaps them in), pass `raw` — the
        ORIGINAL [n, d] float32 rows — so the store still gets its
        `rerank_vectors` table."""
        return cls._write(cls._storage_path(spec), pdb, spec, raw=raw)

    @classmethod
    def _write(cls, path: str, pdb, spec: IndexSpec, raw=None):
        """Quantize the raw-data leaf and commit the block store.

        dtype="pq": the vectors leaf shrinks to M-byte code rows AND the
        TRUE float32 rows are persisted as an extra `rerank_vectors` table
        (same p * n_pad + i row addressing) — stage-2 rerank reads real
        vectors back from flash, because re-scoring decoded PQ rows would
        reproduce the ADC distances exactly and recover no recall."""
        extra = None
        if spec.dtype == "pq":
            quant = spec.quantizer()
            vecs = np.asarray(pdb.db.vectors)
            if vecs.dtype != np.uint8:     # true rows still in hand
                extra = {"rerank_vectors": np.ascontiguousarray(
                    vecs.reshape(-1, vecs.shape[-1]), np.float32)}
            elif raw is not None:          # scatter raw rows to pad layout
                raw = np.asarray(raw, np.float32)
                gids = np.asarray(pdb.db.gids)
                n_valid = np.atleast_1d(np.asarray(pdb.db.n_valid))
                n_pad = gids.shape[-1]
                p_ax = gids.shape[0] if gids.ndim == 2 else 1
                table = np.zeros((p_ax * n_pad, raw.shape[1]), np.float32)
                for pi in range(p_ax):
                    nv = int(n_valid[pi])
                    g = gids[pi, :nv] if gids.ndim == 2 else gids[:nv]
                    table[pi * n_pad: pi * n_pad + nv] = raw[g]
                extra = {"rerank_vectors": table}
            pdb = quantize_db_vectors(pdb, "pq", quant)
        else:
            # quantized spec: on-flash vector rows shrink to 1 byte/dim
            pdb = quantize_db_vectors(pdb, spec.dtype)
        write_store(path, pdb, block_size=spec.block_size,
                    extra_tables=extra)
        del pdb                     # from here on, the store is the database
        return cls(spec, open_store(path, spec.cache_bytes,
                                    prefetch=spec.prefetch))

    def params(self, k: int, ef: int) -> SearchParams:
        return SearchParams(ef=ef, k=k, metric=self.spec.metric,
                            fused_hops=self.spec.fused_hops)

    def search(self, queries, k: int, ef: int, rerank: bool,
               with_stats: bool):
        r = self.reader
        before = None
        if with_stats:
            if r.prefetcher is not None:
                r.prefetcher.drain()     # don't attribute a previous
            before = r.cache.snapshot()  # request's in-flight reads to us
        p = self.params(k, ef)
        pq_quant = self.quant if self.is_pq else None
        if rerank:
            cand, _, hops, calcs, steps = store_search(
                r, queries, p, merge=False, pq_quant=pq_quant)
            with TRACER.child_span("rerank", pool=int(cand.shape[1])):
                ids, dists = self._rerank_from_store(queries, cand, k)
        else:
            ids, dists, hops, calcs, steps = store_search(
                r, queries, p, pq_quant=pq_quant)
            if self.quant is not None and not self.is_pq:
                # code-space -> real-space (ADC is already real-space)
                dists = dists * jnp.float32(self.quant.dist_scale)
        with self._tlock:
            self._queries += int(np.asarray(queries).shape[0])
            self._hops += int(np.asarray(hops).sum())
            self._dist_calcs += int(np.asarray(calcs).sum())
            self._supersteps += int(steps)
        stats = None
        if with_stats:
            from repro.api.types import QueryStats
            if r.prefetcher is not None:
                r.prefetcher.drain()     # settle in-flight reads (counters)
            after = r.cache.snapshot()
            demand = ((after["hits"] - before["hits"])
                      + (after["misses"] - before["misses"]))
            hit_rate = ((after["hits"] - before["hits"]) / demand
                        if demand else 0.0)
            stats = QueryStats(
                hops=jnp.asarray(hops, jnp.int32),
                dist_calcs=jnp.asarray(calcs, jnp.int32),
                block_reads=after["block_reads"] - before["block_reads"],
                cache_hits=after["hits"] - before["hits"],
                cache_misses=after["misses"] - before["misses"],
                cache_hit_rate=hit_rate,
                bytes_read=after["bytes_read"] - before["bytes_read"],
                supersteps=steps,
            )
        return jnp.asarray(ids), jnp.asarray(dists), stats

    def _rerank_from_store(self, queries, cand: np.ndarray, k: int):
        """Stage-2 exact re-score from store reads (paper Fig. 4 stage 2).

        Candidates are remapped onto a compact, monotonically-ordered id
        space so `batched_rerank` behaves exactly as it does over the full
        resident vector table."""
        from repro.api.rerank import batched_rerank
        r = self.reader
        if r.partition_starts is None:
            raise ValueError(
                "rerank over this store is unsupported: partition global "
                "ids are not contiguous ranges")
        valid = cand >= 0
        uniq = np.unique(cand[valid])
        if uniq.size == 0:
            b = cand.shape[0]
            return (np.full((b, k), -1, np.int32),
                    np.full((b, k), np.inf, np.float32))
        part = np.searchsorted(r.partition_starts, uniq, side="right") - 1
        local = uniq - r.partition_starts[part]
        rows = part * r.n_pad + local
        if self.is_pq:
            # stage 2 over TRUE float32 rows from the extra table — the
            # code rows carry no information beyond their ADC distance
            if "rerank_vectors" not in r.blockfile.tables:
                raise ValueError(
                    "this PQ store has no 'rerank_vectors' table, so "
                    "stage-2 rerank cannot read true float32 rows: "
                    "rebuild it with CSDBackend.build/from_partitioned "
                    "over the original vectors")
            rows_f = r.read_rows("rerank_vectors", rows).astype(np.float32)
        else:
            rows_f = r.read_rows("vectors", rows)[:, :r.dim].astype(
                np.float32)
            if self.quant is not None:
                # stage 2 stays float32: dequantize the gathered code rows
                rows_f = self.quant.decode(rows_f)
        vecs = jnp.asarray(rows_f)
        sqs = jnp.einsum("nd,nd->n", vecs, vecs)
        compact = np.where(valid,
                           np.searchsorted(uniq, np.where(valid, cand, 0)),
                           -1).astype(np.int32)
        q = jnp.asarray(np.asarray(queries, np.float32))
        if self.quant is not None and not self.is_pq:
            q = self.quant.decode(q)     # code-valued queries -> f32 values
        ids_c, dists = batched_rerank(vecs, sqs, q, jnp.asarray(compact), k,
                                      self.spec.metric)
        with TRACER.child_span("device-wait"):
            ids_c = np.asarray(ids_c)
        ids = np.where(ids_c >= 0, uniq[np.maximum(ids_c, 0)], -1)
        return ids.astype(np.int32), dists

    # -- persistence ---------------------------------------------------------
    # The block store IS the database: state_tree carries only a format tag,
    # and the index manifest's spec points at the block files (storage_path)
    # instead of pickled arrays.

    def state_tree(self) -> dict:
        return {"meta": {"csd_store": np.int32(1),
                         "block_size": np.int32(self.spec.block_size)}}

    @classmethod
    def from_state(cls, spec: IndexSpec, leaves: dict, mesh=None):
        path = cls._storage_path(spec)
        return cls(spec, open_store(path, spec.cache_bytes,
                                    prefetch=spec.prefetch))
