"""Fused multi-hop traversal: bit-parity matrix, bitmap-sizing regression,
and store-read accounting.

The contract under test (ISSUE: fused traversal): `fused_hops` is a pure
scheduling knob — H hops per kernel invocation (in-memory backends) or per
host superstep (csd) — and must NEVER change results. Every cell of the
matrix asserts ids, dists, hops, and dist_calcs are bit-identical to the
hop-stepped `fused_hops=1` golden; the oracle property extends that to the
numpy reference. The regression half pins the visited-bitmap ceil-division
fix: a graph whose padded row count is NOT a multiple of 32 must still
visit every row at most once (ids/calcs match the oracle exactly), on both
the in-memory and the store-driven path.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SearchRequest
from repro.core import hnsw_graph as hg
from repro.core.partitioned import PartitionedDB
from repro.core.ref_search import ref_batch_search
from repro.core.search import SearchParams, batch_search, bitmap_words
from repro.store.csd import _gather_vec_sq, _visited_test_and_set, store_search
from repro.store.layout import open_store, write_store

K, EF = 10, 40
CACHE = 32 << 20


@contextlib.contextmanager
def fused(svc, h):
    """Temporarily serve `svc` at fused_hops=h (backend.params reads the
    backend's spec, so swapping it re-tunes an already-built service)."""
    be = svc.backend
    old = be.spec
    be.spec = dataclasses.replace(old, fused_hops=h)
    try:
        yield svc
    finally:
        be.spec = old


def _respond(svc, q, rerank=False):
    r = svc.search(SearchRequest(queries=q, k=K, ef=EF, rerank=rerank,
                                 with_stats=True))
    return (np.asarray(r.ids), np.asarray(r.dists),
            np.asarray(r.stats.hops), np.asarray(r.stats.dist_calcs),
            r.stats.supersteps)


# ---------------------------------------------------------------------------
# the parity matrix: fused == lockstep, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused_hops", [2, 4])
@pytest.mark.parametrize("rerank", [False, True])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("backend", ["hnsw", "partitioned", "csd"])
def test_fused_matches_lockstep_bitwise(backend, metric, rerank, fused_hops,
                                        backend_zoo):
    svc = backend_zoo.service(backend, metric)
    q = backend_zoo.queries()
    with fused(svc, 1):
        golden = _respond(svc, q, rerank)
    with fused(svc, fused_hops):
        got = _respond(svc, q, rerank)
    np.testing.assert_array_equal(got[0], golden[0])   # ids
    np.testing.assert_array_equal(got[1], golden[1])   # dists, bit-exact
    np.testing.assert_array_equal(got[2], golden[2])   # hops
    np.testing.assert_array_equal(got[3], golden[3])   # dist_calcs


def test_csd_supersteps_amortize_host_syncs(backend_zoo):
    """The point of the superstep: host round-trips drop ~1/H while every
    per-query counter stays identical."""
    svc = backend_zoo.service("csd", "l2")
    q = backend_zoo.queries()
    with fused(svc, 1):
        *_, hops1, calcs1, s1 = _respond(svc, q)
    with fused(svc, 4):
        *_, hops4, calcs4, s4 = _respond(svc, q)
    np.testing.assert_array_equal(hops4, hops1)
    np.testing.assert_array_equal(calcs4, calcs1)
    assert s1 > 0 and s4 > 0
    assert s4 < s1, f"superstep count did not drop: {s4} !< {s1}"
    assert s4 <= s1 // 2, (f"H=4 should at least halve host syncs: "
                           f"{s4} vs {s1}")


def test_any_fused_hops_matches_numpy_oracle(built_graph, small_dataset):
    """Property over the knob: every H agrees with core/ref_search.py."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need the hypothesis package")
    from hypothesis import given, settings, strategies as st

    g, _ = built_graph
    db_np = hg.restructure(g)
    db = jax.tree.map(jnp.asarray, db_np)
    q = small_dataset["queries"]
    p0 = SearchParams(ef=EF, k=K)
    rids, rds, rhops, _ = ref_batch_search(db_np, q, p0)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=1, max_value=8))
    def prop(h):
        ids, ds, stats = batch_search(
            db, jnp.asarray(q), dataclasses.replace(p0, fused_hops=h))
        np.testing.assert_array_equal(np.asarray(ids), rids)
        # same tolerance as test_search: the oracle's numpy matvec and the
        # kernel's mul+sum may part in the last ulp; ids/hops stay exact
        np.testing.assert_allclose(np.asarray(ds), rds, rtol=1e-3, atol=2.0)
        np.testing.assert_array_equal(np.asarray(stats.hops), rhops)

    prop()


# ---------------------------------------------------------------------------
# visited-bitmap sizing regression (n_pad % 32 != 0)
# ---------------------------------------------------------------------------


def test_bitmap_words_is_ceil_division():
    assert bitmap_words(32) == 1
    assert bitmap_words(33) == 2          # the old n // 32 said 1
    assert bitmap_words(2040) == 64


def test_visited_mirror_covers_partial_last_word():
    """Rows in the final partial word must be trackable — the floor-division
    bug truncated the bitmap so they could be expanded twice."""
    n_pad = 48                            # 48 % 32 != 0 -> 2 words
    bitmap = np.zeros((1, bitmap_words(n_pad)), np.uint32)
    ids = np.array([[47]], np.int32)      # lives in the partial word
    valid = np.ones((1, 1), bool)
    assert not _visited_test_and_set(bitmap, ids, valid)[0, 0]
    assert _visited_test_and_set(bitmap, ids, valid)[0, 0], \
        "row in the partial bitmap word was not remembered as visited"


@pytest.fixture(scope="module")
def odd_pad_db(built_graph):
    """The 2k graph padded to 2040 rows — 2040 % 32 == 24, the shape the
    floor-division bug silently corrupted (normal builds round to 32)."""
    g, _ = built_graph
    db_np = hg.restructure(g, n_pad=2040)
    assert db_np.vectors.shape[0] % 32 != 0
    return db_np, jax.tree.map(jnp.asarray, db_np)


@pytest.mark.parametrize("fused_hops", [1, 4])
def test_odd_pad_in_memory_matches_oracle(odd_pad_db, small_dataset,
                                          fused_hops):
    """ids AND dist_calcs exact vs the oracle == no row expanded twice
    (a truncated bitmap cannot mark the tail rows, so their re-expansion
    would inflate dist_calcs before anything else)."""
    db_np, db = odd_pad_db
    q = small_dataset["queries"]
    p = SearchParams(ef=EF, k=K, fused_hops=fused_hops)
    ids, _, stats = batch_search(db, jnp.asarray(q), p)
    rids, _, rhops, rcalcs = ref_batch_search(db_np, q, p)
    np.testing.assert_array_equal(np.asarray(ids), rids)
    np.testing.assert_array_equal(np.asarray(stats.hops), rhops)
    np.testing.assert_array_equal(np.asarray(stats.dist_calcs), rcalcs)


def test_odd_pad_csd_matches_partitioned_bitwise(odd_pad_db, small_dataset,
                                                 tmp_path):
    """Same odd-padded table served from the block store: csd must stay
    bit-identical to the in-memory path at every fused_hops."""
    db_np, db = odd_pad_db
    pdb = PartitionedDB(
        db=hg.DeviceDB(*(np.stack([getattr(db_np, f)])
                         for f in hg.DeviceDB._fields)),
        num_partitions=1, dim=small_dataset["vectors"].shape[1])
    write_store(str(tmp_path / "store"), pdb, block_size=4096)
    reader = open_store(str(tmp_path / "store"), CACHE, prefetch=False)
    try:
        assert reader.n_pad % 32 != 0
        q = small_dataset["queries"]
        for h in (1, 4):
            p = SearchParams(ef=EF, k=K, fused_hops=h)
            ids, ds, stats = batch_search(db, jnp.asarray(q), p)
            sids, sds, shops, scalcs, _ = store_search(reader, q, p)
            np.testing.assert_array_equal(np.asarray(sids), np.asarray(ids))
            np.testing.assert_array_equal(np.asarray(sds), np.asarray(ds))
            np.testing.assert_array_equal(shops, np.asarray(stats.hops))
            np.testing.assert_array_equal(scalcs,
                                          np.asarray(stats.dist_calcs))
    finally:
        reader.close()


# ---------------------------------------------------------------------------
# store-read accounting: dedup'd gathers + superstep traffic
# ---------------------------------------------------------------------------


def test_gather_vec_sq_reads_each_row_once(backend_zoo, monkeypatch):
    """Duplicate neighbor ids across lanes must reach the reader as ONE
    row each; the scattered-back tiles stay element-for-element right."""
    reader = backend_zoo.service("csd", "l2").backend.reader
    seen = []
    orig = reader.read_rows

    def spy(table, rows):
        seen.append((table, np.asarray(rows).copy()))
        return orig(table, rows)

    monkeypatch.setattr(reader, "read_rows", spy)
    ids = np.array([[5, 7, 5, -1],
                    [7, 9, 9, 3]], np.int32)
    mask = ids >= 0
    vecs, sqs = _gather_vec_sq(reader, 0, ids, mask)
    for table, rows in seen:
        assert len(rows) == len(np.unique(rows)) == 4, \
            f"{table} read {len(rows)} rows for 4 unique ids"
    monkeypatch.undo()
    for b in range(ids.shape[0]):
        for m in range(ids.shape[1]):
            if not mask[b, m]:
                assert not vecs[b, m].any() and sqs[b, m] == 0
                continue
            row = reader.row("vectors", 0, np.array([ids[b, m]]))
            np.testing.assert_array_equal(
                vecs[b, m], reader.read_rows("vectors", row)[0])
            assert sqs[b, m] == reader.read_rows("sqnorms", row)[0, 0]


def test_superstep_spans_and_gauge(backend_zoo):
    """Fused csd traffic must trace as `hop_superstep` children of
    `traversal` (one per host sync, replacing the per-hop `hop` spans)
    and publish the `traversal_fused_hops` gauge."""
    from repro.obs import TRACER
    from repro.obs.metrics import REGISTRY

    svc = backend_zoo.service("csd", "l2")
    q = backend_zoo.queries()[:4]
    TRACER.configure(enabled=True, sample_rate=1.0)
    TRACER.clear()
    try:
        with fused(svc, 4):
            svc.search(SearchRequest(queries=q, k=K, ef=EF))
        spans = TRACER.spans()
    finally:
        TRACER.configure(enabled=False)
        TRACER.clear()
    ss = [s for s in spans if s["name"] == "hop_superstep"]
    trav = {s["id"] for s in spans if s["name"] == "traversal"}
    assert ss, "fused csd search recorded no hop_superstep spans"
    assert all(s["parent"] in trav for s in ss)
    assert all(s["attrs"]["fused_hops"] == 4 and "superstep" in s["attrs"]
               and "active" in s["attrs"] for s in ss)
    assert not any(s["name"] == "hop" for s in spans), \
        "fused mode must replace per-hop spans, not add to them"
    gauges = [m for m in REGISTRY.snapshot()["gauges"]
              if m["name"] == "traversal_fused_hops"]
    assert gauges and gauges[0]["value"] == 4.0


def test_superstep_mode_strictly_reduces_bytes_read(backend_zoo):
    """With the speculative next-hop prefetcher on (prefetch reads count in
    bytes_read), the superstep driver's exact hop-batched reads must move
    strictly fewer bytes than the hop-stepped loop — same answers.

    A narrow workload (2 queries, ef=10) keeps the demanded block set well
    below the whole store, so the legacy path's speculative blocks — those
    prefetched for runner-up candidates that never get popped — are real
    extra traffic rather than reads the traversal would have made anyway."""
    path = backend_zoo.service("csd", "l2").spec.storage_path
    q = backend_zoo.queries()[:2]

    def run(h):
        reader = open_store(path, CACHE, prefetch=True)
        try:
            out = store_search(reader, q,
                               SearchParams(ef=10, k=K, fused_hops=h))
            if reader.prefetcher is not None:
                reader.prefetcher.drain()
            snap = reader.cache.snapshot()
        finally:
            reader.close()
        return out, snap

    (ids1, ds1, *_), snap1 = run(1)
    (ids4, ds4, *_), snap4 = run(4)
    np.testing.assert_array_equal(np.asarray(ids4), np.asarray(ids1))
    np.testing.assert_array_equal(np.asarray(ds4), np.asarray(ds1))
    assert snap4["bytes_read"] < snap1["bytes_read"], (
        f"superstep mode should read strictly less: "
        f"{snap4['bytes_read']} !< {snap1['bytes_read']}")


def test_fused_hops_refused_where_kernels_compile(monkeypatch):
    """Where kernels compile instead of interpreting (a TPU), the fused
    layer-0 kernel is refused at build, naming it, rather than replaced by
    another path; csd supersteps need no kernel and still build."""
    from repro.api import IndexSpec, SearchService
    from repro.kernels import ops
    vecs = np.asarray(np.random.default_rng(0).normal(size=(64, 8)),
                      np.float32)
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = hg.HNSWConfig(M=4, ef_construction=16, seed=0)
    for backend in ("hnsw", "partitioned"):
        with pytest.raises(NotImplementedError,
                           match="fused_traversal_pallas"):
            SearchService.build(vecs, IndexSpec(backend=backend,
                                                fused_hops=4, hnsw=cfg))
    SearchService.build(vecs, IndexSpec(backend="partitioned", hnsw=cfg))
