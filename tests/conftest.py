"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; only launch/dryrun.py forces 512."""

import numpy as np
import pytest

from repro.core import hnsw_graph as hg
from repro.data import clustered_vectors

ZOO_CFG = hg.HNSWConfig(M=12, ef_construction=80, seed=0)


class BackendZoo:
    """Session-cached SearchService per (backend, metric, normalized).

    One graph build is shared wherever bit-identical results are required:
    the csd store is written from the partitioned backend's own DB
    (`CSDBackend.from_partitioned`), and the distributed build is
    deterministic from the same seed — so partitioned / distributed / csd
    answer from the SAME graph. `normalized=True` builds over unit-norm
    vectors (the cosine <-> l2 parity golden); `ids()` then queries with
    unit-norm queries.
    """

    def __init__(self, dataset, tmp_path_factory):
        self.data = dataset
        self._tmp = tmp_path_factory
        self._svcs = {}
        vecs = dataset["vectors"]
        q = dataset["queries"]
        self._vectors = {False: vecs,
                         True: vecs / np.linalg.norm(vecs, axis=1,
                                                     keepdims=True)}
        self._queries = {False: q,
                         True: q / np.linalg.norm(q, axis=1, keepdims=True)}

    def service(self, backend: str, metric: str = "l2", *,
                normalized: bool = False):
        key = (backend, metric, normalized)
        if key not in self._svcs:
            self._svcs[key] = self._build(backend, metric, normalized)
        return self._svcs[key]

    def queries(self, *, normalized: bool = False) -> np.ndarray:
        return self._queries[normalized]

    def ids(self, backend: str, metric: str = "l2", *, k: int = 10,
            ef: int = 40, rerank: bool = False,
            normalized: bool = False) -> np.ndarray:
        from repro.api import SearchRequest
        svc = self.service(backend, metric, normalized=normalized)
        resp = svc.search(SearchRequest(queries=self.queries(
            normalized=normalized), k=k, ef=ef, rerank=rerank))
        return np.asarray(resp.ids)

    def _build(self, backend: str, metric: str, normalized: bool):
        import dataclasses

        from repro.api import IndexSpec, SearchService
        from repro.store.csd import CSDBackend

        vecs = self._vectors[normalized]
        if backend == "uint8":
            # the paper's SIFT1B precision: quantized partitioned engine
            spec = IndexSpec(metric=metric, backend="partitioned",
                             dtype="uint8", num_partitions=2, hnsw=ZOO_CFG,
                             keep_vectors=True)
            return SearchService.build(vecs, spec)
        if backend == "uint8_csd":
            # same quantized graph, served out-of-core (1-byte vector rows)
            part = self.service("uint8", metric, normalized=normalized)
            store = str(self._tmp.mktemp("zoo-csd-u8") / "store")
            spec = dataclasses.replace(part.spec, backend="csd",
                                       keep_vectors=False,
                                       storage_path=store, prefetch=False)
            return SearchService(
                spec, CSDBackend.from_partitioned(part.backend.pdb, spec))
        if backend == "pq":
            # product-quantized engine: M=8 byte codes per row, LUT ADC
            spec = IndexSpec(metric=metric, backend="partitioned",
                             dtype="pq", pq_m=8, num_partitions=2,
                             hnsw=ZOO_CFG, keep_vectors=True)
            return SearchService.build(vecs, spec)
        if backend == "pq_csd":
            # same PQ graph + codebooks, served out-of-core (M-byte rows);
            # `raw` supplies the true float32 rows for the rerank table
            part = self.service("pq", metric, normalized=normalized)
            store = str(self._tmp.mktemp("zoo-csd-pq") / "store")
            spec = dataclasses.replace(part.spec, backend="csd",
                                       keep_vectors=False,
                                       storage_path=store, prefetch=False)
            return SearchService(
                spec, CSDBackend.from_partitioned(part.backend.pdb, spec,
                                                  raw=part.backend.raw))
        if backend == "csd":
            # same graph as the partitioned service, restructured on "flash"
            part = self.service("partitioned", metric, normalized=normalized)
            store = str(self._tmp.mktemp("zoo-csd") / "store")
            spec = IndexSpec(metric=metric, backend="csd", num_partitions=2,
                             hnsw=ZOO_CFG, storage_path=store,
                             prefetch=False)
            return SearchService(
                spec, CSDBackend.from_partitioned(part.backend.pdb, spec))
        partitions = {"exact": 1, "hnsw": 1, "partitioned1": 1}.get(backend, 2)
        spec = IndexSpec(
            metric=metric,
            backend="partitioned" if backend == "partitioned1" else backend,
            num_partitions=partitions, hnsw=ZOO_CFG,
            keep_vectors=backend in ("hnsw", "partitioned", "partitioned1"))
        return SearchService.build(vecs, spec)


@pytest.fixture(scope="session")
def backend_zoo(small_dataset, tmp_path_factory):
    """Shared golden services for the parity matrix, recall-regression, and
    serve tests — built lazily, cached for the whole session."""
    return BackendZoo(small_dataset, tmp_path_factory)


@pytest.fixture(scope="session")
def small_dataset():
    """2k clustered vectors + queries + exact ground truth (session-cached)."""
    n, d, nq, k = 2000, 64, 16, 10
    vecs = clustered_vectors(n, d, k=24, seed=0)
    rng = np.random.default_rng(1)
    queries = vecs[rng.integers(0, n, nq)] + rng.normal(
        scale=2.0, size=(nq, d)).astype(np.float32)
    queries = queries.astype(np.float32)
    d2 = (
        np.einsum("nd,nd->n", vecs, vecs)[None]
        - 2 * queries @ vecs.T
        + np.einsum("qd,qd->q", queries, queries)[:, None]
    )
    gt = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return {"vectors": vecs, "queries": queries, "gt": gt, "k": k}


@pytest.fixture(scope="session")
def built_graph(small_dataset):
    cfg = hg.HNSWConfig(M=12, ef_construction=80, seed=0)
    g = hg.build_hnsw(small_dataset["vectors"], cfg)
    return g, cfg


@pytest.fixture(autouse=True)
def _obs_state_restored():
    """Put the process-wide tracer's and profiler's configuration back
    after every test and clear what they recorded. The chip benchmark's
    span readers turn the tracer on when imported, and an enabled tracer
    would otherwise leak into the other test files of the same worker."""
    from repro.obs import PROFILER, TRACER
    saved = (TRACER.enabled, TRACER.sample_rate, TRACER.max_events,
             PROFILER.enabled)
    yield
    TRACER.configure(enabled=saved[0], sample_rate=saved[1],
                     max_events=saved[2])
    PROFILER.configure(enabled=saved[3])
    TRACER.clear()
    PROFILER.reset()
