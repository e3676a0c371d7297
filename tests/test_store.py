"""repro.store: block layout, crash safety, LRU accounting, out-of-core csd.

The headline acceptance test: a `csd` index over a dataset whose vector
table exceeds `cache_bytes` returns top-k *identical* to the in-memory
`partitioned` backend at the same ef/K/metric, while peak resident store
memory stays bounded by the cache capacity and the stats report real block
traffic.

`REPRO_STORE_TEST_CACHE_BYTES` (CI: 8192 — two blocks) shrinks the cache
so the eviction path is exercised on every hop.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.api import IndexSpec, SearchRequest, SearchService
from repro.core.hnsw_graph import HNSWConfig, db_from_tables, db_to_tables
from repro.store import (
    BlockFile,
    BlockFileWriter,
    CSDBackend,
    PageCache,
    StoreFormatError,
    open_store,
    store_search,
    write_store,
)
from repro.store.blockfile import DATA_NAME

CFG = HNSWConfig(M=12, ef_construction=80, seed=0)
BLOCK = 4096
CACHE_BYTES = max(
    int(os.environ.get("REPRO_STORE_TEST_CACHE_BYTES", 128 * 1024)), BLOCK)


# ---------------------------------------------------------------------------
# fixtures: one partitioned build, served resident and out-of-core
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def svc_partitioned(small_dataset):
    spec = IndexSpec(backend="partitioned", num_partitions=2, hnsw=CFG,
                     keep_vectors=True)
    return SearchService.build(small_dataset["vectors"], spec)


@pytest.fixture(scope="module")
def svc_csd(small_dataset, tmp_path_factory):
    store = str(tmp_path_factory.mktemp("csd") / "store")
    spec = IndexSpec(backend="csd", num_partitions=2, hnsw=CFG,
                     storage_path=store, block_size=BLOCK,
                     cache_bytes=CACHE_BYTES, prefetch=False)
    return SearchService.build(small_dataset["vectors"], spec)


# ---------------------------------------------------------------------------
# block file + manifest
# ---------------------------------------------------------------------------


def _tiny_store(path, blocks=8, block_size=BLOCK):
    """One int32 table, exactly one row per block."""
    rows = np.arange(blocks * block_size // 4,
                     dtype=np.int32).reshape(blocks, -1)
    w = BlockFileWriter(str(path), block_size)
    w.add_table("t", rows)
    w.finalize({"note": "tiny"})
    return rows


def test_blockfile_roundtrip(tmp_path):
    rows = _tiny_store(tmp_path / "s")
    bf = BlockFile(str(tmp_path / "s"))
    assert bf.num_blocks == 8
    got = np.frombuffer(bf.read_block(3), np.int32)
    np.testing.assert_array_equal(got, rows[3])
    assert bf.row_starts("t", [3]).tolist() == [3 * BLOCK]  # one block a row


def test_crash_safety_no_commit_marker(tmp_path):
    _tiny_store(tmp_path / "s")
    os.remove(tmp_path / "s" / "_COMMITTED")
    with pytest.raises(StoreFormatError, match="commit marker"):
        BlockFile(str(tmp_path / "s"))


def test_crash_safety_truncated_data(tmp_path):
    _tiny_store(tmp_path / "s")
    data = tmp_path / "s" / "blocks.bin"
    with open(data, "r+b") as f:
        f.truncate(BLOCK)            # partial write survived a "crash"
    with pytest.raises(StoreFormatError, match="data file"):
        BlockFile(str(tmp_path / "s"))


def test_rewrite_clears_stale_commit(tmp_path):
    _tiny_store(tmp_path / "s")
    # a writer that dies mid-rewrite must not leave the old marker behind
    BlockFileWriter(str(tmp_path / "s"), BLOCK)
    with pytest.raises(StoreFormatError, match="commit marker"):
        BlockFile(str(tmp_path / "s"))


# ---------------------------------------------------------------------------
# page cache: LRU eviction + counters
# ---------------------------------------------------------------------------


def test_page_cache_lru_and_counters(tmp_path):
    rows = _tiny_store(tmp_path / "s")
    cache = PageCache(BlockFile(str(tmp_path / "s")), 2 * BLOCK)
    cache.get(0)
    cache.get(1)
    cache.get(0)                       # hit, refreshes 0's recency
    cache.get(2)                       # evicts 1 (LRU), not 0
    cache.get(1)                       # miss again — 1 was evicted
    assert cache.hits == 1
    assert cache.misses == 4
    assert cache.evictions == 2
    assert cache.block_reads == 4
    assert cache.bytes_read == 4 * BLOCK
    assert cache.current_bytes == 2 * BLOCK
    assert cache.peak_bytes == 2 * BLOCK
    assert cache.hit_rate == pytest.approx(0.2)
    np.testing.assert_array_equal(np.frombuffer(cache.get(2), np.int32),
                                  rows[2])
    assert cache.hits == 2             # 2 is still resident after the last miss


def test_page_cache_rejects_capacity_below_one_block(tmp_path):
    _tiny_store(tmp_path / "s")
    with pytest.raises(ValueError, match="capacity"):
        PageCache(BlockFile(str(tmp_path / "s")), BLOCK - 1)


def _trace_requests(num_blocks, capacity_blocks, seed=0):
    """A recorded demand trace: scattered requests with repeats inside
    them, one request larger than the cache, and one that re-reads a
    resident block after enough new ones to evict it mid-request."""
    rng = np.random.default_rng(seed)
    reqs = [rng.integers(0, num_blocks, rng.integers(1, 2 * capacity_blocks))
            .tolist() for _ in range(40)]
    reqs.append(list(range(num_blocks)) + [0, 1])
    reqs.append(list(range(capacity_blocks)))
    reqs.append(list(range(capacity_blocks,
                           min(2 * capacity_blocks, num_blocks))) + [0])
    return reqs


@pytest.mark.parametrize("capacity_blocks", [1, 3, 5, 16])
def test_get_many_matches_sequential_get(tmp_path, capacity_blocks):
    """Batched `get_many` == `get` on each block in request order: the same
    bytes, counters, high-water mark and LRU order after every request."""
    _tiny_store(tmp_path / "s", blocks=24)
    bf = BlockFile(str(tmp_path / "s"))
    batched = PageCache(bf, capacity_blocks * BLOCK)
    seq = PageCache(bf, capacity_blocks * BLOCK)
    for req in _trace_requests(bf.num_blocks, capacity_blocks):
        got = batched.get_many(req)
        want = {i: seq.get(i) for i in dict.fromkeys(req)}
        assert got == want
        for c in ("hits", "misses", "evictions", "peak_bytes",
                  "current_bytes", "prefetch_reads"):
            assert getattr(batched, c) == getattr(seq, c), c
        assert list(batched._lru) == list(seq._lru)
        assert batched.peak_bytes <= batched.capacity_bytes
        assert not batched._inflight
    # the mid-request eviction took the single-block path
    assert batched.fallbacks > 0


@pytest.mark.parametrize("capacity_blocks", [1, 3, 5, 16])
def test_prefetch_many_reads_the_blocks_missing_at_the_call(tmp_path,
                                                           capacity_blocks):
    """Batched `prefetch_many` == `prefetch` on each block of the request
    that was not resident when it was called, in order, with demand
    requests between: the same counters and LRU order throughout. A
    block its own inserts evict is not read again."""
    _tiny_store(tmp_path / "s", blocks=24)
    bf = BlockFile(str(tmp_path / "s"))
    batched = PageCache(bf, capacity_blocks * BLOCK)
    seq = PageCache(bf, capacity_blocks * BLOCK)
    for n, req in enumerate(_trace_requests(bf.num_blocks, capacity_blocks)):
        if n % 2:
            batched.get_many(req)
            for i in dict.fromkeys(req):
                seq.get(i)
        else:
            missing = [i for i in dict.fromkeys(req) if i not in seq._lru]
            reads = batched.prefetch_reads
            batched.prefetch_many(req)
            assert batched.prefetch_reads - reads == len(missing)
            for i in missing:
                seq.prefetch(i)
        for c in ("hits", "misses", "evictions", "peak_bytes",
                  "current_bytes", "prefetch_reads"):
            assert getattr(batched, c) == getattr(seq, c), c
        assert list(batched._lru) == list(seq._lru)
        assert batched.peak_bytes <= batched.capacity_bytes
        assert not batched._inflight
    assert batched.prefetch_reads > 0


def test_get_many_with_live_prefetcher_reads_each_residency_once(tmp_path):
    """Two demand threads batch-read while the prefetch worker pulls blocks
    in: no block crosses the flash interface while it is resident or
    already being read, every block read is counted, nothing deadlocks."""
    import sys
    import threading

    from repro.store import Prefetcher
    _tiny_store(tmp_path / "s", blocks=32)
    bf = BlockFile(str(tmp_path / "s"))
    cache = PageCache(bf, 6 * BLOCK)
    reading: set[int] = set()
    bad: list[int] = []
    reads = [0]

    def checked(idxs):
        with cache._lock:
            for i in idxs:
                if i in cache._lru or i in reading:
                    bad.append(i)
                reading.add(i)
                reads[0] += 1
        return idxs

    def done(idxs):
        with cache._lock:
            reading.difference_update(idxs)

    real_one, real_many = bf.read_block, bf.read_blocks

    def read_block(i):
        checked([i])
        try:
            return real_one(i)
        finally:
            done([i])

    def read_blocks(idxs):
        checked(idxs)
        try:
            return real_many(idxs)
        finally:
            done(idxs)
    bf.read_block, bf.read_blocks = read_block, read_blocks
    pf = Prefetcher(cache)
    errors: list[BaseException] = []

    def demand(seed):
        try:
            for req in _trace_requests(bf.num_blocks, 6, seed):
                got = cache.get_many(req)
                for i in req:
                    assert np.frombuffer(got[i], np.int32)[0] == i * BLOCK // 4
        except BaseException as e:     # surfaced by the main thread
            errors.append(e)
            raise

    def feed():
        rng = np.random.default_rng(7)
        for _ in range(60):
            pf.prefetch_blocks(rng.integers(0, bf.num_blocks, 8).tolist())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=demand, args=(s,), daemon=True)
                   for s in (1, 2)] + [
            threading.Thread(target=feed, daemon=True)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "deadlock"
        drained = threading.Thread(target=pf.drain, daemon=True)
        drained.start()
        drained.join(timeout=60)
        assert not drained.is_alive(), "prefetch worker stuck"
    finally:
        sys.setswitchinterval(old)
        pf.close()
    assert not errors, errors
    assert not bad, f"blocks read while resident or in flight: {bad}"
    assert reads[0] == cache.misses + cache.prefetch_reads
    assert cache.prefetch_reads > 0
    assert cache.peak_bytes <= cache.capacity_bytes
    assert not cache._inflight


def _demand_against_prefetch_in_flight(tmp_path, monkeypatch, capacity_blocks,
                                       prefetched, request, batched):
    """One demand request that meets the prefetch worker's reads in flight:
    the worker is held inside its flash read until the demand thread waits
    on one of them. Returns the cache's counters and the blocks read."""
    import threading
    import types

    from repro.store import Prefetcher
    from repro.store import cache as cache_mod
    _tiny_store(tmp_path, blocks=16)
    bf = BlockFile(str(tmp_path))
    in_read, gate, waiting = (threading.Event() for _ in range(3))
    demander: list[threading.Thread] = []
    reads: list[int] = []
    real_one, real_many = bf.read_block, bf.read_blocks

    def read_block(i):
        reads.append(i)
        return real_one(i)

    def read_blocks(idxs):
        reads.extend(idxs)
        if threading.current_thread() is pf._thread:
            in_read.set()
            assert gate.wait(30), "the demand never waited on the prefetch"
        return real_many(idxs)

    class Watched(threading.Event):
        def wait(self, timeout=None):
            if threading.current_thread() in demander:
                waiting.set()
            return super().wait(timeout)

    monkeypatch.setattr(cache_mod, "threading", types.SimpleNamespace(
        Event=Watched, Lock=threading.Lock))
    bf.read_block, bf.read_blocks = read_block, read_blocks
    cache = PageCache(bf, capacity_blocks * BLOCK)
    pf = Prefetcher(cache)
    got: dict = {}

    def demand():
        if batched:
            got.update(cache.get_many(request))
        else:
            got.update({i: cache.get(i) for i in dict.fromkeys(request)})
    try:
        pf.prefetch_blocks(prefetched)
        assert in_read.wait(30)
        demander.append(threading.Thread(target=demand, daemon=True))
        demander[0].start()
        assert waiting.wait(30), "the demand did not wait on the prefetch"
        gate.set()
        demander[0].join(30)
        assert not demander[0].is_alive(), "deadlock"
        pf.drain()
    finally:
        gate.set()
        pf.close()
    for i in request:
        assert np.frombuffer(got[i], np.int32)[0] == i * BLOCK // 4
    counters = {c: getattr(cache, c) for c in (
        "hits", "misses", "prefetch_reads", "prefetch_hits", "evictions",
        "peak_bytes")}
    return counters, sorted(reads)


@pytest.mark.parametrize("prefetched,request_", [
    ([0, 1, 2, 3, 4, 5], [3, 4, 5, 6, 7, 8, 9]),
    ([9, 2, 7, 4], list(range(10))),
])
@pytest.mark.parametrize("capacity_blocks", [4, 16])
def test_get_many_against_live_prefetcher_matches_sequential_get(
        tmp_path, monkeypatch, capacity_blocks, prefetched, request_):
    """A demand request that meets blocks the live prefetch worker is still
    reading: batched `get_many` waits for them as `get` on each block does
    — the same demand misses, hits and prefetch hits, every block read
    once."""
    runs = {batched: _demand_against_prefetch_in_flight(
        tmp_path / str(batched), monkeypatch, capacity_blocks, prefetched,
        request_, batched) for batched in (False, True)}
    (seq, seq_reads), (bat, bat_reads) = runs[False], runs[True]
    assert bat == seq
    assert bat_reads == seq_reads
    # the worker's reads land together: the demand waits once
    assert bat["prefetch_hits"] == 1
    assert bat["peak_bytes"] <= capacity_blocks * BLOCK
    if capacity_blocks * BLOCK >= len(set(prefetched) | set(request_)) * BLOCK:
        overlap = set(prefetched) & set(request_)
        assert bat["misses"] == len(set(request_) - overlap)
        assert bat["hits"] == len(overlap)
        assert bat_reads == sorted(set(prefetched) | set(request_))


# ---------------------------------------------------------------------------
# row reads: array addressing and assembly against a per-row oracle
# ---------------------------------------------------------------------------

ROW_BLOCK = 512
# name -> (dtype, cols): rows of 12, 384 and 700 bytes against a 512 B
# block, so rows fit, divide into, or straddle blocks
ROW_TABLES = {"u8_700": (np.uint8, 700), "i32_12": (np.int32, 3),
              "i32_384": (np.int32, 96), "f32_384": (np.float32, 96),
              "f32_700": (np.float32, 175)}
ROW_META = {"num_partitions": 1, "n_pad": 1, "d_pad": 1, "m0_pad": 1,
            "n_layers": 1, "up_pad": 1, "m_pad": 1, "dim": 1, "entry": [0],
            "max_level": [0], "n_valid": [1]}


@pytest.fixture(scope="module")
def row_store(tmp_path_factory):
    """(store path, {table: the rows as written})."""
    path = str(tmp_path_factory.mktemp("rows") / "store")
    rng = np.random.default_rng(5)
    w = BlockFileWriter(path, ROW_BLOCK)
    written = {}
    for name, (dtype, cols) in ROW_TABLES.items():
        n = 90 if cols > 100 else 300
        if dtype is np.float32:
            written[name] = rng.standard_normal((n, cols)).astype(dtype)
        else:
            written[name] = rng.integers(-100, 100, (n, cols)).astype(dtype)
        w.add_table(name, written[name])
    w.finalize(ROW_META)
    return path, written


def _oracle_read_rows(bf, table, rows):
    """The per-row reader `read_rows` replaced: each row's span from the
    manifest's table fields, its blocks read one by one, the slices
    joined."""
    t, bs = bf.tables[table], bf.block_size
    idx = np.asarray(rows, np.int64)
    out = np.empty((idx.size, t["cols"]), t["dtype"])
    for j, r in enumerate(idx.ravel()):
        start = t["offset"] + int(r) * t["row_bytes"]
        end = start + t["row_bytes"]
        parts = []
        for b in range(start // bs, (end - 1) // bs + 1):
            lo = max(start, b * bs) - b * bs
            hi = min(end, (b + 1) * bs) - b * bs
            parts.append(bf.read_block(b)[lo:hi])
        out[j] = np.frombuffer(b"".join(parts), t["dtype"])
    return out.reshape(idx.shape + (t["cols"],))


ROW_CASES = {
    "all": lambda n, rng: np.arange(n),
    "dups": lambda n, rng: rng.integers(0, n, 3 * n),
    "2d": lambda n, rng: rng.integers(0, n, (7, 5)),
    "3d": lambda n, rng: rng.integers(0, n, (2, 3, 4)),
    "empty": lambda n, rng: np.zeros((0,), np.int64),
    "get_hook": lambda n, rng: rng.integers(0, n, (4, 6)),
}


@pytest.mark.parametrize("case", sorted(ROW_CASES))
@pytest.mark.parametrize("table", sorted(ROW_TABLES))
def test_read_rows_matches_per_row_oracle(row_store, table, case):
    path, written = row_store
    reader = open_store(path, 4 * ROW_BLOCK, prefetch=False)
    bf = reader.blockfile
    rows = ROW_CASES[case](bf.tables[table]["rows"],
                           np.random.default_rng(len(case)))
    get = reader.cache.prefetch_get if case == "get_hook" else None
    got = reader.read_rows(table, rows, _get=get)
    want = _oracle_read_rows(bf, table, rows)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    assert got.tobytes() == written[table][rows].tobytes()
    if case == "get_hook":      # the worker's reads count as prefetch only
        assert reader.cache.hits == reader.cache.misses == 0
        assert reader.cache.prefetch_reads > 0
    assert reader.cache.peak_bytes <= reader.cache.capacity_bytes


@pytest.mark.parametrize("table", sorted(ROW_TABLES))
def test_row_addressing_matches_written_rows(row_store, table):
    """`row_starts` puts every row on the bytes the writer stored for it,
    and `blocks_of_rows` gives exactly the blocks those bytes lie in."""
    path, written = row_store
    reader = open_store(path, 4 * ROW_BLOCK, prefetch=False)
    bf, rows = reader.blockfile, written[table]
    rb, bs = rows.dtype.itemsize * rows.shape[1], bf.block_size
    starts = bf.row_starts(table, np.arange(len(rows)))
    data = np.fromfile(os.path.join(path, DATA_NAME), np.uint8)
    every = set()
    for r, start in enumerate(starts.tolist()):
        assert data[start:start + rb].tobytes() == rows[r].tobytes()
        blocks = list(range(start // bs, (start + rb - 1) // bs + 1))
        assert reader.blocks_of_rows(table, [r]) == blocks
        every.update(blocks)
    assert reader.blocks_of_rows(table, np.arange(len(rows))[::-1]) == \
        sorted(every)


# ---------------------------------------------------------------------------
# Fig. 5 table serialization
# ---------------------------------------------------------------------------


def test_db_tables_roundtrip(svc_partitioned):
    db = jax.tree.map(np.asarray, svc_partitioned.backend.pdb.db)
    tables, meta = db_to_tables(db)
    back = db_from_tables(tables, meta)
    for f in db._fields:
        np.testing.assert_array_equal(getattr(db, f), getattr(back, f),
                                      err_msg=f)


# ---------------------------------------------------------------------------
# csd backend: out-of-core parity + bounded memory (acceptance test)
# ---------------------------------------------------------------------------


def test_csd_matches_partitioned_with_bounded_memory(
        svc_partitioned, svc_csd, small_dataset):
    q = small_dataset["queries"]
    reader = svc_csd.backend.reader
    vec_table_bytes = reader.blockfile.tables["vectors"]["nbytes"]
    assert vec_table_bytes > CACHE_BYTES, (
        "scenario precondition: the vector table must not fit the cache")

    req = SearchRequest(queries=q, k=10, ef=40, with_stats=True)
    resp_p = svc_partitioned.search(req)
    resp_c = svc_csd.search(req)

    # identical top-k (ids AND distances), identical traversal counters
    np.testing.assert_array_equal(np.asarray(resp_c.ids),
                                  np.asarray(resp_p.ids))
    np.testing.assert_array_equal(np.asarray(resp_c.dists),
                                  np.asarray(resp_p.dists))
    np.testing.assert_array_equal(np.asarray(resp_c.stats.hops),
                                  np.asarray(resp_p.stats.hops))
    np.testing.assert_array_equal(np.asarray(resp_c.stats.dist_calcs),
                                  np.asarray(resp_p.stats.dist_calcs))

    # storage stats: real block traffic, plausible hit rate
    assert resp_c.stats.block_reads > 0
    assert resp_c.stats.bytes_read == resp_c.stats.block_reads * BLOCK
    assert 0.0 <= resp_c.stats.cache_hit_rate <= 1.0
    if CACHE_BYTES >= 16 * BLOCK:
        # a cache that holds a working set must actually hit; the CI
        # tiny-cache job (2 blocks) legitimately thrashes to ~0
        assert resp_c.stats.cache_hit_rate > 0.0

    # the out-of-core guarantee: resident store memory bounded by the cache
    assert reader.cache.peak_bytes <= CACHE_BYTES
    # and with a cache smaller than the data, eviction actually ran
    assert reader.cache.evictions > 0


def test_csd_rerank_matches_partitioned(svc_partitioned, svc_csd,
                                        small_dataset):
    """Stage-2 rerank from store reads == rerank from kept vectors."""
    req = SearchRequest(queries=small_dataset["queries"], k=10, ef=40,
                        rerank=True)
    resp_p = svc_partitioned.search(req)
    resp_c = svc_csd.search(req)
    np.testing.assert_array_equal(np.asarray(resp_c.ids),
                                  np.asarray(resp_p.ids))
    np.testing.assert_array_equal(np.asarray(resp_c.dists),
                                  np.asarray(resp_p.dists))


def test_csd_requires_storage_path(small_dataset):
    with pytest.raises(ValueError, match="storage_path"):
        SearchService.build(small_dataset["vectors"],
                            IndexSpec(backend="csd", hnsw=CFG))


def test_csd_save_load_points_at_block_store(svc_csd, small_dataset,
                                             tmp_path):
    idx = str(tmp_path / "idx")
    svc_csd.save(idx)
    svc2 = SearchService.load(idx)
    assert svc2.spec == svc_csd.spec
    req = SearchRequest(queries=small_dataset["queries"], k=10, ef=40)
    np.testing.assert_array_equal(np.asarray(svc2.search(req).ids),
                                  np.asarray(svc_csd.search(req).ids))
    # the versioned step holds a tag, not the data: the manifest points at
    # the block files via spec.storage_path
    step = os.path.join(idx, "step_00000000")
    step_bytes = sum(os.path.getsize(os.path.join(step, f))
                     for f in os.listdir(step))
    store_bytes = os.path.getsize(
        os.path.join(svc_csd.spec.storage_path, "blocks.bin"))
    assert step_bytes < store_bytes / 10


def test_prefetcher_overlaps_and_preserves_results(svc_csd, small_dataset):
    q = small_dataset["queries"][:8]
    base = svc_csd.search(SearchRequest(queries=q, k=10, ef=40))
    reader = open_store(svc_csd.spec.storage_path, CACHE_BYTES,
                        prefetch=True)
    try:
        p = svc_csd.backend.params(10, 40)
        ids, _, _, _, _ = store_search(reader, q, p)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(base.ids))
        reader.prefetcher.drain()
        assert reader.cache.prefetch_reads > 0
        assert reader.cache.peak_bytes <= CACHE_BYTES
    finally:
        reader.close()


# csd cosine/l2 parity vs the shared partitioned graph now lives in the
# cross-backend matrix (tests/test_parity_matrix.py); this file keeps the
# storage-specific guarantees (bounded memory, block traffic, crash safety).
