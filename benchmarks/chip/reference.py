"""Plain reference for served answers, and the comparison behind `correct`.

The reference is an exact scan in numpy over the benchmark's own rows:
squared L2 between integer-valued rows and queries. Every partial sum of
a 128-d dot product of 8-bit values is an integer below 2**24, so the
float32 products are exact and the final sum is taken in int64. It takes
nothing that the system under test made.

An answer is judged by what it says, position by position:
  miss_at_k  the share of the k answer slots that do not hold a true k
             nearest neighbour: an id that is missing, repeated, out of
             range, or whose exact distance lies above the k-th smallest
             exact distance (so ties at the k-th distance count as hits);
  dist_gap   the widest gap between a served distance and the exact
             distance of the id it was served with, relative to that
             exact distance (at least 1).
"""

from __future__ import annotations

import numpy as np


def exact_sq_l2(rows: np.ndarray, queries: np.ndarray,
                block: int = 1 << 15) -> np.ndarray:
    """[q, n] int64 squared L2 from every query to every row."""
    q = queries.astype(np.float32)
    qsq = np.einsum("qd,qd->q", q, q).astype(np.int64)
    out = np.empty((q.shape[0], rows.shape[0]), np.int64)
    for lo in range(0, rows.shape[0], block):
        x = rows[lo:lo + block].astype(np.float32)
        xsq = np.einsum("nd,nd->n", x, x).astype(np.int64)
        dot = (q @ x.T).astype(np.int64)
        out[:, lo:lo + block] = qsq[:, None] - 2 * dot + xsq[None, :]
    return out


def kth_distance(rows: np.ndarray, queries: np.ndarray, k: int,
                 block: int = 256) -> np.ndarray:
    """[q] the k-th smallest exact squared L2 of each query."""
    out = np.empty(queries.shape[0], np.int64)
    for lo in range(0, queries.shape[0], block):
        d = exact_sq_l2(rows, queries[lo:lo + block])
        out[lo:lo + block] = np.partition(d, k - 1, axis=1)[:, k - 1]
    return out


def judge(rows: np.ndarray, queries: np.ndarray, kth: np.ndarray,
          qidx: np.ndarray, ids: np.ndarray, dists: np.ndarray) -> dict:
    """Judge answers: answer j is (ids[j], dists[j]) served for query
    queries[qidx[j]], whose k-th exact distance is kth[qidx[j]]. Returns
    {"answers", "miss_at_k", "dist_gap"}."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    m, k = ids.shape
    if m == 0:
        return {"answers": 0, "miss_at_k": 1.0, "dist_gap": float("inf")}
    n = rows.shape[0]
    inrange = (ids >= 0) & (ids < n)
    safe = np.where(inrange, ids, 0)
    q = queries[qidx].astype(np.int64)                          # [m, d]
    diff = rows[safe].astype(np.int64) - q[:, None, :]          # [m, k, d]
    exact = np.einsum("mkd,mkd->mk", diff, diff)
    srt = np.sort(np.where(inrange, ids, -1 - np.arange(k)), axis=1)
    repeated = np.zeros_like(inrange)
    repeated[:, 1:] = srt[:, 1:] == srt[:, :-1]
    # a repeat anywhere in the row costs one slot per repeat
    n_repeat = repeated.sum(axis=1)
    hit = inrange & (exact <= kth[qidx][:, None])
    hits = np.maximum(hit.sum(axis=1) - n_repeat, 0)
    gap = np.where(inrange, np.abs(dists - exact) / np.maximum(exact, 1),
                   np.inf)
    return {"answers": int(m),
            "miss_at_k": float(1.0 - hits.sum() / (m * k)),
            "dist_gap": float(gap.max())}


def lower_precision_answers(rows: np.ndarray, queries: np.ndarray, k: int,
                            bits: int = 4, block: int = 512):
    """The control: the exact scan run at `bits` bits instead of 8 (int4
    for the configurations' uint8), on whatever device JAX holds. Rows and
    queries are rounded onto 2**bits levels over [0, 255]; the top-k and
    the distances it serves are those of the rounded values, scaled back
    to 8-bit units. Returns (ids [q, k], dists [q, k])."""
    import jax
    import jax.numpy as jnp

    step = 255.0 / (2 ** bits - 1)
    xs = jnp.asarray(np.rint(rows.astype(np.float32) / step))
    xsq = jnp.sum(xs * xs, axis=1)

    @jax.jit
    def top(qb):
        qs = jnp.rint(qb / step)
        dot = jnp.matmul(qs, xs.T, precision=jax.lax.Precision.HIGHEST)
        d = jnp.sum(qs * qs, axis=1)[:, None] - 2.0 * dot + xsq[None, :]
        neg, idx = jax.lax.top_k(-d, k)
        return idx, -neg * step * step

    ids, dists = [], []
    for lo in range(0, queries.shape[0], block):
        qb = np.zeros((block, queries.shape[1]), np.float32)
        part = queries[lo:lo + block]
        qb[:len(part)] = part
        i, d = top(jnp.asarray(qb))
        ids.append(np.asarray(i)[:len(part)])
        dists.append(np.asarray(d)[:len(part)])
    return np.concatenate(ids), np.concatenate(dists)
