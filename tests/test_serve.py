"""repro.serve: dynamic batcher, replica dispatch, async/direct parity.

The acceptance bar (ISSUE 3): the async serve path must return bit-identical
ids to a direct `SearchService.search` for EVERY backend — batching,
variable-k packing, bucket padding, and replica dispatch are all pure
plumbing and may not change a single result.
"""

import threading
import time

import numpy as np
import pytest

from repro.api import SearchRequest
from repro.serve import (
    DynamicBatcher,
    ReplicaPool,
    RequestQueue,
    SearchServer,
    ServeClosed,
    bucket_size,
)

K, EF = 10, 40


@pytest.fixture(scope="module")
def svc(backend_zoo):
    return backend_zoo.service("partitioned", "l2")


def _direct_ids(service, queries, k=K, ef=EF):
    return np.asarray(service.search(
        SearchRequest(queries=np.atleast_2d(queries), k=k, ef=ef)).ids)


# ---------------------------------------------------------------------------
# batcher mechanics
# ---------------------------------------------------------------------------


def test_flush_on_max_batch(svc, backend_zoo):
    """max_batch queued requests flush immediately — long before the
    (deliberately huge) max_wait deadline."""
    q = backend_zoo.queries()
    with SearchServer(svc, replicas=1, max_batch=4,
                      max_wait_ms=60_000.0) as srv:
        futs = [srv.submit(x, k=K, ef=EF) for x in q[:4]]
        res = [f.result(timeout=60) for f in futs]
        st = srv.stats()
    assert st.batch_sizes == {4: 1}
    ids = np.stack([r.ids for r in res])
    np.testing.assert_array_equal(ids, _direct_ids(svc, q[:4]))


def test_flush_on_max_wait(svc, backend_zoo):
    """A partial batch flushes once the head of line has waited max_wait."""
    q = backend_zoo.queries()
    with SearchServer(svc, replicas=1, max_batch=64, max_wait_ms=30.0) as srv:
        t0 = time.perf_counter()
        futs = [srv.submit(x, k=K, ef=EF) for x in q[:3]]
        res = [f.result(timeout=60) for f in futs]
        st = srv.stats()
    assert st.batch_sizes == {3: 1}           # one flush, nothing waited out
    assert all(r.queue_ms >= 25.0 for r in res)   # they DID wait ~max_wait
    assert time.perf_counter() - t0 < 30          # ...not the full minute
    ids = np.stack([r.ids for r in res])
    np.testing.assert_array_equal(ids, _direct_ids(svc, q[:3]))


def test_result_to_request_ordering_under_interleaved_arrival(
        svc, backend_zoo):
    """Concurrent submitters with jittered arrival: every future must get
    ITS OWN query's results (scatter routes by future, not position)."""
    q = backend_zoo.queries()
    direct = _direct_ids(svc, q)
    out: dict[int, np.ndarray] = {}
    lock = threading.Lock()

    with SearchServer(svc, replicas=2, max_batch=5, max_wait_ms=5.0) as srv:
        def client(worker: int):
            for i in range(worker, len(q), 4):
                time.sleep(0.001 * (i % 3))
                res = srv.submit(q[i], k=K, ef=EF).result(timeout=120)
                with lock:
                    out[i] = res.ids
        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    assert sorted(out) == list(range(len(q)))
    for i, ids in out.items():
        np.testing.assert_array_equal(ids, direct[i])


def test_variable_k_requests_pack_into_one_batch(svc, backend_zoo):
    """k is not part of the batch key: mixed-k requests ride one batch
    (packed at k_max) and each gets its own bit-identical k-prefix."""
    q = backend_zoo.queries()
    ks = [3, 10, 7, 1]
    with SearchServer(svc, replicas=1, max_batch=4,
                      max_wait_ms=60_000.0) as srv:
        futs = [srv.submit(q[i], k=k, ef=EF) for i, k in enumerate(ks)]
        res = [f.result(timeout=60) for f in futs]
        st = srv.stats()
    assert st.batch_sizes == {4: 1}           # one packed batch, despite ks
    for i, (r, k) in enumerate(zip(res, ks)):
        assert r.ids.shape == (k,)
        np.testing.assert_array_equal(r.ids, _direct_ids(svc, q[i], k=k)[0])


def test_drain_returns_all_futures(svc, backend_zoo):
    q = backend_zoo.queries()
    srv = SearchServer(svc, replicas=2, max_batch=4, max_wait_ms=1.0)
    try:
        futs = srv.submit_many(np.repeat(q, 3, axis=0), k=K, ef=EF)
        assert srv.drain(timeout=120)
        assert all(f.done() for f in futs)
        assert srv.stats().completed == len(futs)
    finally:
        srv.shutdown()


def test_submit_after_shutdown_raises(svc, backend_zoo):
    srv = SearchServer(svc, replicas=1)
    srv.shutdown()
    with pytest.raises(ServeClosed):
        srv.submit(backend_zoo.queries()[0])
    # the raw queue refuses too (not just the server wrapper)
    queue = RequestQueue()
    queue.close()
    with pytest.raises(ServeClosed):
        queue.put(backend_zoo.queries()[0])


def test_batch_key_separates_incompatible_requests(svc, backend_zoo):
    """Different ef -> different traversal -> must not share a batch."""
    q = backend_zoo.queries()
    with SearchServer(svc, replicas=1, max_batch=8, max_wait_ms=5.0) as srv:
        futs = ([srv.submit(q[i], k=K, ef=40) for i in range(3)]
                + [srv.submit(q[i], k=K, ef=24) for i in range(3, 6)])
        res = [f.result(timeout=60) for f in futs]
    np.testing.assert_array_equal(
        np.stack([r.ids for r in res[:3]]), _direct_ids(svc, q[:3], ef=40))
    np.testing.assert_array_equal(
        np.stack([r.ids for r in res[3:]]), _direct_ids(svc, q[3:6], ef=24))


def test_dispatch_failure_lands_on_futures(svc, backend_zoo):
    """A failing backend call must reject the batch's futures, not hang."""
    queue = RequestQueue()

    def boom(_req, n_queries=0):
        raise RuntimeError("replica on fire")

    b = DynamicBatcher(queue, boom, max_batch=2, max_wait_ms=5.0)
    b.start()
    p = queue.put(backend_zoo.queries()[0], k=K, ef=EF)
    with pytest.raises(RuntimeError, match="replica on fire"):
        p.future.result(timeout=30)
    queue.close()
    b.join(timeout=10)
    assert not b.alive


def test_bucket_size_shapes():
    assert [bucket_size(n, 64) for n in (1, 2, 3, 5, 9, 64)] == \
        [1, 2, 4, 8, 16, 64]
    assert bucket_size(33, 48) == 48          # capped at max_batch
    assert bucket_size(50, 48) == 50          # n > max_batch never shrinks


# ---------------------------------------------------------------------------
# latency semantics + stats rollup
# ---------------------------------------------------------------------------


def test_latency_split_and_stats_rollup(svc, backend_zoo):
    q = backend_zoo.queries()
    with SearchServer(svc, replicas=2, max_batch=8, max_wait_ms=2.0) as srv:
        res = [f.result(timeout=120)
               for f in srv.submit_many(q, k=K, ef=EF, with_stats=True)]
        st = srv.stats()
    for r in res:
        assert r.queue_ms >= 0 and r.exec_ms > 0
        assert r.e2e_ms == pytest.approx(r.queue_ms + r.exec_ms, rel=1e-6)
        # per-query stats rows were scattered back per request
        assert np.asarray(r.stats.dist_calcs).shape == ()
        assert int(r.stats.dist_calcs) > 0
    assert st.completed == len(q)
    assert st.qps > 0
    assert sum(s * c for s, c in st.batch_sizes.items()) == len(q)
    assert len(st.replicas) == 2
    # per-replica counters count REAL requests, never bucket-padding rows
    assert sum(r["queries"] for r in st.replicas) == len(q)
    assert "QPS" in st.summary()


def test_replica_pool_balances_and_round_robins(svc):
    """Ties round-robin; depth imbalance routes to the idler replica."""
    pool = ReplicaPool.replicate(svc, 2)
    try:
        picked = []

        def slow(rid, orig):
            # keep each replica visibly busy so in-flight depth, not the
            # race to finish, decides the next placement
            def wrapped(req, n_queries):
                picked.append(rid)
                time.sleep(0.05)
                return orig(req, n_queries)
            return wrapped

        for rid in (0, 1):
            pool.replicas[rid]._search = slow(
                rid, pool.replicas[rid]._search)
        q = np.zeros((2, 64), np.float32)
        futs = [pool.submit(SearchRequest(queries=q, k=K, ef=EF))
                for _ in range(4)]
        for f in futs:
            f.result(timeout=60)
        assert sorted(picked) == [0, 0, 1, 1]   # 2 batches each
    finally:
        pool.close()


def test_csd_replicas_have_independent_caches(backend_zoo):
    """csd replication = one block store, N PageCaches (the paper's four
    SmartSSD DRAM tiers): each replica reports its own block traffic."""
    svc_csd = backend_zoo.service("csd", "l2")
    q = backend_zoo.queries()
    with SearchServer(svc_csd, replicas=2, max_batch=4,
                      max_wait_ms=1.0) as srv:
        for f in srv.submit_many(np.repeat(q, 2, axis=0), k=K, ef=EF):
            f.result(timeout=300)
        st = srv.stats()
    readers = {id(r.service.backend.reader) for r in srv.pool.replicas}
    assert len(readers) == 2                  # distinct StoreReaders
    for r in st.replicas:
        assert r["backend"] == "csd"
        assert r["queries"] > 0               # both replicas actually served
        assert r["block_reads"] > 0
        assert 0.0 <= r["cache_hit_rate"] <= 1.0


# ---------------------------------------------------------------------------
# the acceptance bar: async == direct, for every backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["exact", "hnsw", "partitioned",
                                     "distributed", "csd"])
def test_async_serve_is_bit_identical_to_direct(backend, backend_zoo):
    service = backend_zoo.service(backend, "l2")
    q = backend_zoo.queries()
    direct = service.search(SearchRequest(queries=q, k=K, ef=EF))
    with SearchServer(service, replicas=2, max_batch=4,
                      max_wait_ms=1.0) as srv:
        res = [f.result(timeout=300)
               for f in srv.submit_many(q, k=K, ef=EF)]
    np.testing.assert_array_equal(np.stack([r.ids for r in res]),
                                  np.asarray(direct.ids))
    # distances to a few ulps of ||x||^2: XLA CPU matmul rounding depends
    # on the batch shape, and the async path packs different batch sizes
    # than `direct` (same tolerance rationale as test_api's rerank check)
    np.testing.assert_allclose(np.stack([r.dists for r in res]),
                               np.asarray(direct.dists),
                               rtol=1e-3, atol=2.0)


def test_replicas_hold_their_arrays_on_their_own_device():
    """4 virtual devices, in a subprocess so this process keeps its
    one-device view: replica i's graph, scan and rerank tables, PQ
    codebooks and codes all live on device i, and it answers there."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "helpers",
                                      "replica_check.py")],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "REPLICAS OK" in r.stdout
