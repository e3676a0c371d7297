#!/usr/bin/env python3
"""Smoke run of the served ANN search path on a TPU.

    python3 chip_smoke.py              # one chip: kernel, graph, pq, exact
    python3 chip_smoke.py --chips 4    # four chips: distributed and replicas

It drives the path a user calls (SearchService.build, then
repro.serve.SearchServer, then each future's result()) on BigANN-SIFT-shaped
data (big-ann-benchmarks 2021: 128-d uint8 rows, L2) generated from --seed,
and checks the answers against the repo's own references:

  kernel every ANN Pallas kernel of kernels/ops.py at 128-d, 256 queries,
         k=10, PQ M=16, compiled for the device, against numpy; prints the
         f32 matmul error at default and HIGHEST precision, and checks the
         squared-norm forms the search paths keep at default; and
         IndexSpec(fused_hops=4) must be refused at build, naming the
         kernel that does not compile.
  graph  one `partitioned` index (4 partitions, uint8, M=16,
         ef_construction=100) and a `csd` index written from the same graph
         with a PageCache far smaller than the store, each serving 512
         queries at k=10, ef=40; recall@10 against the `exact` backend over
         the same rows must reach 0.90, and land within 0.01 of the CPU's.
  pq     `exact` with dtype="pq" (M=16) over as many rows, 512 queries
         through the server; each top-10 must equal a float64 numpy ADC
         scan over the same codes and codebooks.
  exact  10M rows (BigANN-10M) served by `exact`; the top-10 of 8 sampled
         queries must equal a chunked numpy scan (equal distances, equal id
         sets up to ties at the 10th distance).
  four   (--chips 4 only) `distributed` over a 4-device `model` mesh against
         a one-chip `partitioned` index over the same graph (ids equal,
         recall as above), and SearchServer(replicas=4) with each
         replica's arrays on its own chip.

Sizes are fixed; cuts of scale are printed on `reduced:` lines. Set-up
(builds, and the first pass through the server, which compiles unless the
compile cache holds the programs) and steady time are printed apart. The
last line of stdout is one JSON object, {"ok": true, "device": {"platform",
"kind", "count"}}; any failed check exits 1, and a host without a TPU exits
2 before any phase runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.api import IndexSpec, SearchRequest, SearchService  # noqa: E402
from repro.core.hnsw_graph import HNSWConfig  # noqa: E402
from repro.data import clustered_vectors  # noqa: E402
from repro.serve import SearchServer  # noqa: E402

DIM = 128
K = 10
EF = 40                      # the paper's operating point (Fig. 1, 6.2)
PQ_M = 16
QUERIES = 512                # graph, pq and four phases, per pass
GRAPH_N = 48_000             # graph and pq phases (see REDUCED)
EXACT_N = 10_000_000         # BigANN-10M
EXACT_QUERIES = 64           # exact phase, per pass
EXACT_CHECKED = 8            # of them, checked against numpy
FOUR_N = 8_000               # --chips 4 (see REDUCED)
KERNEL_N, KERNEL_Q = 8192, 256
RECALL_FLOOR = 0.90          # tests/test_recall_regression.py
GRAPH_CFG = dict(M=16, ef_construction=100)
# recall@10 of the same graph on the CPU (JAX_PLATFORMS=cpu), keyed by
# (n, queries, seed, ef); there partitioned, csd and distributed return
# the same ids. The chip must land within 0.01 of it.
CPU_RECALL = {(48_000, 512, 0, 40): 0.995703125,
              (8_000, 512, 0, 40): 0.9994140625}
REDUCED = {
    "graph": f"graph and pq phases n={GRAPH_N} rows of BigANN's 10^9: the "
             f"numpy HNSW builder inserts one point at a time (~6.4 ms per "
             f"point on the chip host), so a larger graph does not build "
             f"in about 5 minutes; device-side construction is ROADMAP B5",
    "four": f"four-chip phase n={FOUR_N} rows, not {GRAPH_N}: the build "
            f"takes ~310 s at {GRAPH_N} rows and holds all four chips "
            f"meanwhile (~21 chip-minutes for the build alone); "
            f"device-side construction is ROADMAP B5",
}


def log(msg: str) -> None:
    print(msg, flush=True)


def sift_rows(n: int, seed: int, chunk: int = 1 << 20) -> np.ndarray:
    """[n, 128] uint8 rows from repro.data's clustered generator, made in
    chunks so a 10M-row table never exists in float64."""
    out = np.empty((n, DIM), np.uint8)
    for i, lo in enumerate(range(0, n, chunk)):
        hi = min(n, lo + chunk)
        out[lo:hi] = np.rint(clustered_vectors(
            hi - lo, DIM, k=max(8, (hi - lo) // 2000),
            seed=seed * 1000 + i))
    return out


def base_and_queries(n: int, n_queries: int, seed: int):
    """Base rows and in-distribution queries, drawn together and split."""
    rows = sift_rows(n + n_queries, seed)
    return rows[:n], rows[n:].astype(np.float32)


def serve(service, queries, *, k: int, ef: int, replicas: int = 1,
          max_batch: int = 64):
    """Two passes of every query through SearchServer: the first compiles
    each batch shape (set-up), the second is the steady pass. Returns the
    steady (ids, dists), both passes' seconds and per-replica stats."""
    with SearchServer(service, replicas=replicas, max_batch=max_batch,
                      max_wait_ms=50.0) as srv:
        t0 = time.perf_counter()
        first = [f.result() for f in srv.submit_many(queries, k=k, ef=ef)]
        t1 = time.perf_counter()
        steady = [f.result() for f in srv.submit_many(queries, k=k, ef=ef)]
        t2 = time.perf_counter()
        reps = srv.stats().replicas
        for rep, r in zip(reps, srv.pool.replicas):
            rep["devices"] = sorted(str(d)
                                    for d in backend_devices(r.service))
    ids = np.stack([r.ids for r in steady])
    same = bool(np.array_equal(ids, np.stack([r.ids for r in first])))
    return ids, np.stack([r.dists for r in steady]), {
        "first_pass_s": t1 - t0, "steady_s": t2 - t1,
        "steady_qps": len(queries) / (t2 - t1), "passes_equal": same,
        "replicas": reps}


def recall(ids: np.ndarray, gt: np.ndarray) -> float:
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(ids, gt))
    return hits / gt.size


def topk_matches(got_d, got_i, full, rtol: float = 0.0) -> int:
    """Rows of a returned top-k that are the true k smallest of `full`
    ([queries, n] reference distances): the returned distances equal the
    reference's k smallest, and each returned id carries its own reference
    distance, both within rtol (0: exactly). So the id set is the true one
    up to ties (or near-ties within rtol) at the k-th distance."""
    got_d, got_i = np.asarray(got_d), np.asarray(got_i)
    k = got_d.shape[1]
    ok = 0
    for d, i, f in zip(got_d, got_i, full):
        want = np.sort(np.partition(f, k - 1)[:k])
        if (len(set(i.tolist())) == k and i.min() >= 0
                and np.allclose(d, want, rtol=rtol, atol=0.0)
                and np.allclose(f[i], d, rtol=rtol, atol=0.0)):
            ok += 1
    return ok


def store_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(path) for f in fs)


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


def _check(failures: list, ok: bool, what: str) -> None:
    log(f"check {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        failures.append(what)


def _recall_checks(failures: list, name: str, r: float, key: tuple) -> None:
    _check(failures, r >= RECALL_FLOOR,
           f"{name} recall@10 {r:.4f} >= {RECALL_FLOOR}")
    ref = CPU_RECALL.get(key)
    if ref is None:
        log(f"check skip {name}: no CPU recall recorded for "
            f"(n, queries, seed, ef)={key}")
    else:
        _check(failures, abs(r - ref) <= 0.01,
               f"{name} recall@10 {r:.4f} within 0.01 of the CPU run's "
               f"{ref:.4f}")


def kernel_phase(n: int, n_queries: int, *, seed: int = 0
                 ) -> tuple[dict, list]:
    """Each ANN Pallas kernel as the device runs it (compiled through
    Mosaic on a TPU, interpreted elsewhere) against numpy, and the f32
    matmul error at default and HIGHEST precision."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.optim.compression import PQQuantizer, build_pq_lut

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, DIM)).astype(np.float32)
    q = rng.normal(size=(n_queries, DIM)).astype(np.float32)
    x64, q64 = x.astype(np.float64), q.astype(np.float64)
    dot64 = q64 @ x64.T
    full = (q64 ** 2).sum(1)[:, None] + (x64 ** 2).sum(1)[None] - 2 * dot64

    def rel(a, b):
        return float(np.max(np.abs(np.asarray(a, np.float64) - b))
                     / np.max(np.abs(b)))

    qd, xd = jnp.asarray(q), jnp.asarray(x)
    out = {"n": n, "queries": n_queries, "interpret": ops.default_interpret()}
    for name, p in (("default", jax.lax.Precision.DEFAULT),
                    ("highest", jax.lax.Precision.HIGHEST)):
        out[f"matmul_{name}_rel"] = rel(jnp.matmul(qd, xd.T, precision=p),
                                        dot64)
    # the squared-norm forms the search paths keep at default precision
    # (core/search.py, store/csd.py, api/backends.py, cluster/router.py)
    out["sqnorm_einsum_rel"] = rel(jnp.einsum("nd,nd->n", xd, xd),
                                   (x64 ** 2).sum(1))
    out["sqnorm_vmap_dot_rel"] = rel(jax.vmap(lambda v: v @ v)(qd),
                                     (q64 ** 2).sum(1))
    out["l2dist_rel"] = rel(ops.l2dist(qd, xd), full)
    out["l2topk_rows"] = topk_matches(*ops.l2topk(qd, xd, k=K), full,
                                      rtol=1e-5)

    xc = rng.integers(0, 256, size=(n, DIM), dtype=np.uint8)
    qc = rng.integers(0, 256, size=(n_queries, DIM), dtype=np.uint8)
    xi, qi = xc.astype(np.int64), qc.astype(np.int64)
    full_q = (qi ** 2).sum(1)[:, None] + (xi ** 2).sum(1)[None] - 2 * qi @ xi.T
    qcd, xcd = jnp.asarray(qc), jnp.asarray(xc)
    out["l2dist_q_exact"] = bool(np.array_equal(
        np.asarray(ops.l2dist_q(qcd, xcd)), full_q))
    out["l2topk_q_rows"] = topk_matches(*ops.l2topk_q(qcd, xcd, k=K), full_q)

    s = rng.normal(size=(n_queries, n)).astype(np.float32)
    out["topk_rows"] = topk_matches(*ops.topk(jnp.asarray(s), K), s)

    pq = PQQuantizer.fit(x, PQ_M, seed=seed)
    codes = pq.encode(x)
    luts = build_pq_lut(qd, jnp.asarray(pq.codebooks))
    lut = np.asarray(luts)                         # [q, M, 256]
    adc = np.zeros((n_queries, n), np.float32)     # subspace order, as
    for m in range(PQ_M):                          # the kernel sums
        adc += lut[:, m, :][:, codes[:, m]]
    out["pq_adc_bitwise"] = bool(np.array_equal(
        np.asarray(ops.pq_adc(luts, jnp.asarray(codes))), adc))
    out["pq_topk_rows"] = topk_matches(
        *ops.pq_topk(luts, jnp.asarray(codes), k=K), adc)

    failures: list = []
    _check(failures, out["matmul_highest_rel"] <= 1e-5,
           f"f32 matmul at HIGHEST: relative error "
           f"{out['matmul_highest_rel']:.2e} <= 1e-5 (default: "
           f"{out['matmul_default_rel']:.2e}, reading only)")
    for name in ("sqnorm_einsum_rel", "sqnorm_vmap_dot_rel"):
        _check(failures, out[name] <= 1e-5,
               f"{name} at default precision {out[name]:.2e} <= 1e-5")
    _check(failures, out["l2dist_rel"] <= 1e-5,
           f"l2dist relative error {out['l2dist_rel']:.2e} <= 1e-5")
    for name in ("l2dist_q_exact", "pq_adc_bitwise"):
        _check(failures, out[name], name)
    for name in ("l2topk_rows", "l2topk_q_rows", "topk_rows",
                 "pq_topk_rows"):
        _check(failures, out[name] == n_queries,
               f"{name}: {out[name]}/{n_queries} equal numpy's top-{K}")
    return out, failures


def fused_hops_refused(seed: int = 0) -> tuple[bool, str]:
    """Where kernels compile rather than interpret, IndexSpec(fused_hops>1)
    must be refused at build, naming the kernel Mosaic does not compile,
    and never served by another path."""
    rows, _ = base_and_queries(256, 1, seed)
    spec = IndexSpec(backend="partitioned", num_partitions=4, dtype="uint8",
                     fused_hops=4, hnsw=HNSWConfig(**GRAPH_CFG, seed=seed))
    try:
        SearchService.build(rows, spec)
    except NotImplementedError as e:
        return "fused_traversal_pallas" in str(e), str(e)
    return False, "fused_hops=4 built"


def graph_phase(n: int, n_queries: int, *, seed: int = 0, ef: int = EF,
                workdir: str) -> tuple[dict, list]:
    """partitioned and csd over one uint8 graph, recall vs exact."""
    from repro.store.csd import CSDBackend

    rows, queries = base_and_queries(n, n_queries, seed)
    spec = IndexSpec(backend="partitioned", num_partitions=4, dtype="uint8",
                     hnsw=HNSWConfig(**GRAPH_CFG, seed=seed))
    t = time.perf_counter()
    part = SearchService.build(rows, spec)
    build_s = time.perf_counter() - t
    log(f"graph build: n={n} partitions=4 M={GRAPH_CFG['M']} "
        f"ef_construction={GRAPH_CFG['ef_construction']} "
        f"seconds={build_s:.1f} ({1e3 * build_s / n:.2f} ms/point)")

    pdb = part.backend.pdb
    db_bytes = sum(np.asarray(a).nbytes for a in pdb.db)
    path = os.path.join(workdir, "store")
    csd_spec = dataclasses.replace(part.spec, backend="csd",
                                   storage_path=path,
                                   cache_bytes=db_bytes // 16)
    csd = SearchService(csd_spec, CSDBackend.from_partitioned(pdb, csd_spec))
    on_disk = store_bytes(path)

    exact = SearchService.build(rows.astype(np.float32),
                                IndexSpec(backend="exact"))
    gt = np.asarray(exact.search(SearchRequest(queries, k=K)).ids)

    p_ids, _, p_t = serve(part, queries, k=K, ef=ef)
    try:
        c_ids, _, c_t = serve(csd, queries, k=K, ef=ef)
    finally:
        csd.backend.reader.close()
    rep = c_t["replicas"][0]
    out = {
        "n": n, "queries": n_queries, "ef": ef, "build_s": build_s,
        "recall_partitioned": recall(p_ids, gt),
        "recall_csd": recall(c_ids, gt),
        "rows_differing_partitioned_vs_csd": int(
            np.sum(np.any(p_ids != c_ids, axis=1))),
        "partitioned": {k: v for k, v in p_t.items() if k != "replicas"},
        "csd": {k: v for k, v in c_t.items() if k != "replicas"},
        "csd_store_bytes": on_disk, "csd_cache_bytes": csd_spec.cache_bytes,
        "csd_block_reads": rep["block_reads"],
        "csd_cache_hit_rate": rep["cache_hit_rate"],
        "csd_bytes_read": rep["bytes_read"],
    }
    failures: list = []
    for name in ("partitioned", "csd"):
        _recall_checks(failures, name, out[f"recall_{name}"],
                       (n, n_queries, seed, ef))
        _check(failures, out[name]["passes_equal"],
               f"{name} answers equal in both passes")
    _check(failures, out["csd_block_reads"] > 0,
           f"csd block_reads {out['csd_block_reads']} > 0")
    _check(failures, on_disk >= 8 * csd_spec.cache_bytes,
           f"csd store bytes {on_disk} >= 8 x cache_bytes "
           f"{csd_spec.cache_bytes}")
    return out, failures


def pq_phase(n: int, n_queries: int, *, seed: int = 0) -> tuple[dict, list]:
    """`exact` over PQ codes (the fused ADC top-k kernel) through the
    server; every top-10 vs a float64 numpy ADC scan."""
    rows, queries = base_and_queries(n, n_queries, seed + 2)
    t = time.perf_counter()
    svc = SearchService.build(rows.astype(np.float32), IndexSpec(
        backend="exact", dtype="pq", pq_m=PQ_M))
    build_s = time.perf_counter() - t
    log(f"pq build: n={n} M={PQ_M} seconds={build_s:.1f} (numpy k-means "
        f"fit and encode)")
    ids, dists, timing = serve(svc, queries, k=K, ef=EF)
    codes = np.asarray(svc.backend.raw)            # [n, M] uint8
    cbs = svc.backend.quant.codebooks.astype(np.float64)
    m, _, dsub = cbs.shape
    full = np.empty((n_queries, n))
    for j, q in enumerate(queries.astype(np.float64)):
        lut = ((q.reshape(m, 1, dsub) - cbs) ** 2).sum(-1)    # [M, 256]
        full[j] = lut[np.arange(m), codes].sum(1)
    ok = topk_matches(dists, ids, full, rtol=1e-5)
    failures: list = []
    _check(failures, ok == n_queries,
           f"pq top-{K} equals a float64 numpy ADC scan (rtol 1e-5) on "
           f"{ok}/{n_queries} queries")
    _check(failures, timing["passes_equal"], "pq answers equal in both passes")
    out = {"n": n, "queries": n_queries, "pq_m": PQ_M, "build_s": build_s,
           **{k: v for k, v in timing.items() if k != "replicas"}}
    return out, failures


def numpy_dists(rows: np.ndarray, queries: np.ndarray,
                chunk: int = 1 << 20) -> np.ndarray:
    """Exact squared L2 from every row to each query, as int32 [n, q].
    For 8-bit integer rows and queries every partial sum of the f32 dots
    and norms is an integer below 2^24, hence exact; the final sum is
    taken in int64."""
    q = queries.astype(np.float32)
    qsq = np.einsum("qd,qd->q", q, q).astype(np.int64)
    out = np.empty((rows.shape[0], q.shape[0]), np.int32)
    for lo in range(0, rows.shape[0], chunk):
        x = rows[lo:lo + chunk].astype(np.float32)
        xsq = np.einsum("nd,nd->n", x, x).astype(np.int64)
        dot = (x @ q.T).astype(np.int64)
        out[lo:lo + chunk] = xsq[:, None] - 2 * dot + qsq[None, :]
    return out


def exact_phase(n: int, n_queries: int, n_check: int, *, seed: int = 0
                ) -> tuple[dict, list]:
    """`exact` over n uint8 rows; sampled answers vs a numpy scan."""
    t = time.perf_counter()
    rows, queries = base_and_queries(n, n_queries, seed + 1)
    gen_s = time.perf_counter() - t
    t = time.perf_counter()
    svc = SearchService.build(rows, IndexSpec(backend="exact",
                                              dtype="uint8"))
    build_s = time.perf_counter() - t
    log(f"exact build: n={n} rows_bytes={rows.nbytes} generate_s="
        f"{gen_s:.1f} build_s={build_s:.1f} qscale={svc.spec.qscale}")
    ids, dists, timing = serve(svc, queries, k=K, ef=EF)
    failures: list = []
    _check(failures, svc.spec.qscale == 1.0,
           f"qscale {svc.spec.qscale} == 1.0: the stored codes are the rows")
    del svc

    pick = np.random.default_rng(seed).choice(n_queries, n_check,
                                              replace=False)
    ref = numpy_dists(rows, queries[pick])         # [n, n_check]
    ok = topk_matches(dists[pick], ids[pick], ref.T)
    _check(failures, ok == n_check,
           f"exact top-{K} equals numpy (distances, id sets up to ties) on "
           f"{ok}/{n_check} queries")
    _check(failures, timing["passes_equal"],
           "exact answers equal in both passes")
    out = {"n": n, "queries": n_queries, "checked": n_check,
           "build_s": build_s,
           **{k: v for k, v in timing.items() if k != "replicas"}}
    return out, failures


def backend_devices(service) -> set:
    """Every device that holds an array of this service's backend."""
    import jax
    return {d for v in vars(service.backend).values()
            for a in jax.tree.leaves(v) if isinstance(a, jax.Array)
            for d in a.devices()}


def four_chip_phase(n: int, n_queries: int, *, seed: int = 0, ef: int = EF
                    ) -> tuple[dict, list]:
    """distributed over 4 chips vs one-chip partitioned over the same
    graph, then 4 one-chip replicas of partitioned behind SearchServer."""
    import jax
    from repro.api.backends import PartitionedBackend

    devices = jax.devices()
    rows, queries = base_and_queries(n, n_queries, seed)
    spec = IndexSpec(backend="distributed", num_partitions=4, dtype="uint8",
                     hnsw=HNSWConfig(**GRAPH_CFG, seed=seed))
    t = time.perf_counter()
    dist = SearchService.build(rows, spec)
    build_s = time.perf_counter() - t
    log(f"graph build: n={n} partitions=4 seconds={build_s:.1f}")
    # the one-chip partitioned index over the very same graph, through the
    # backends' state-tree round trip (what save/load does)
    tree = dist.backend.state_tree()
    leaves = {f"{top}/{k}": np.asarray(v) for top in ("db", "meta")
              for k, v in tree[top].items()}
    pspec = dataclasses.replace(dist.spec, backend="partitioned")
    part = SearchService(pspec, PartitionedBackend.from_state(pspec, leaves))
    exact = SearchService.build(rows.astype(np.float32),
                                IndexSpec(backend="exact"))
    gt = np.asarray(exact.search(SearchRequest(queries, k=K)).ids)

    d_ids, _, d_t = serve(dist, queries, k=K, ef=ef)
    p_ids, _, p_t = serve(part, queries, k=K, ef=ef)
    r_ids, _, r_t = serve(part, queries, k=K, ef=ef, replicas=4)
    failures: list = []
    _check(failures, len(backend_devices(dist)) == 4,
           f"distributed graph spans {len(backend_devices(dist))} devices")
    r = recall(d_ids, gt)
    _recall_checks(failures, "distributed", r, (n, n_queries, seed, ef))
    same = int(np.sum(np.all(d_ids == p_ids, axis=1)))
    _check(failures, same == n_queries,
           f"distributed ids == partitioned ids on {same}/{n_queries}")
    same_r = int(np.sum(np.all(r_ids == p_ids, axis=1)))
    _check(failures, same_r == n_queries,
           f"4-replica ids == one-replica ids on {same_r}/{n_queries}")
    split = []
    for rep in r_t["replicas"]:
        log(f"replica {rep['replica']}: devices={rep['devices']} "
            f"queries={rep['queries']} batches={rep['batches']}")
        split.append({k: rep[k] for k in ("replica", "devices", "queries")})
    placed = [s["devices"] for s in split]
    _check(failures, placed == [[str(d)] for d in devices[:4]],
           f"replica i holds its arrays on device i only: {placed}")
    out = {"n": n, "queries": n_queries, "ef": ef, "build_s": build_s,
           "recall_distributed": r,
           "distributed": {k: v for k, v in d_t.items() if k != "replicas"},
           "partitioned": {k: v for k, v in p_t.items() if k != "replicas"},
           "replicas4": {k: v for k, v in r_t.items() if k != "replicas"},
           "replica_split": split}
    _check(failures, all(s["queries"] > 0 for s in split),
           "every replica served queries")
    return out, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")

    failures: list = []

    def phase(name, result):
        out, f = result
        failures.extend(f)
        log(f"phase {name}: " + json.dumps(out))
        log(f"peak_bytes_in_use after {name} phase: {peak_bytes()}")

    if args.chips == 4:
        phase("four", four_chip_phase(FOUR_N, QUERIES, seed=args.seed))
        log(f"reduced: {REDUCED['four']}")
    else:
        phase("kernel", kernel_phase(KERNEL_N, KERNEL_Q, seed=args.seed))
        refused, msg = fused_hops_refused(args.seed)
        _check(failures, refused, f"fused_hops=4 refused at build: {msg}")
        with tempfile.TemporaryDirectory(prefix=".chip_smoke_",
                                         dir=ROOT) as work:
            phase("graph", graph_phase(GRAPH_N, QUERIES, seed=args.seed,
                                       workdir=work))
        phase("pq", pq_phase(GRAPH_N, QUERIES, seed=args.seed))
        phase("exact", exact_phase(EXACT_N, EXACT_QUERIES, EXACT_CHECKED,
                                   seed=args.seed))
        log(f"reduced: {REDUCED['graph']}; exact phase n={EXACT_N} rows, "
            f"the BigANN-10M subset")
    if failures:
        log(f"FAILED: {failures}")
        print(json.dumps({"ok": False, "failures": failures}))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
