"""Hypothesis property tests on the system's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import given, settings, strategies as st

from repro.core.search import merge_sorted, visited_test_and_set
from repro.optim.compression import compress_grads, decompress_grads

settings.register_profile("ci", max_examples=50, deadline=None)
settings.load_profile("ci")


# no subnormals: XLA CPU flushes them to zero (FTZ), so tie semantics vs
# numpy differ below the normal range — not an algorithm property.
floats = st.floats(min_value=-1e6, max_value=1e6, width=32,
                   allow_subnormal=False)


@given(st.lists(floats, min_size=1, max_size=24),
       st.lists(floats, min_size=1, max_size=24))
def test_merge_sorted_is_a_sorted_merge(a, b):
    ad = np.sort(np.array(a, np.float32))
    bd = np.sort(np.array(b, np.float32))
    ai = np.arange(len(ad), dtype=np.int32)
    bi = 1000 + np.arange(len(bd), dtype=np.int32)
    od, oi = merge_sorted(jnp.asarray(ad), jnp.asarray(ai),
                          jnp.asarray(bd), jnp.asarray(bi))
    od, oi = np.asarray(od), np.asarray(oi)
    # multiset of values preserved and sorted
    np.testing.assert_allclose(np.sort(np.concatenate([ad, bd])), od)
    assert np.all(np.diff(od) >= 0)
    # ids form a permutation of the inputs
    assert sorted(oi.tolist()) == sorted(ai.tolist() + bi.tolist())


@given(st.lists(floats, min_size=1, max_size=16),
       st.lists(floats, min_size=1, max_size=16))
def test_merge_sorted_tie_break_prefers_existing(a, b):
    """Existing (a) entries must come first among equal distances —
    matches the numpy oracle's stable concat sort."""
    ad = np.sort(np.array(a, np.float32))
    bd = np.sort(np.array(b, np.float32))
    ai = np.zeros(len(ad), np.int32)          # a marked 0
    bi = np.ones(len(bd), np.int32)           # b marked 1
    od, oi = merge_sorted(jnp.asarray(ad), jnp.asarray(ai),
                          jnp.asarray(bd), jnp.asarray(bi))
    d = np.concatenate([ad, bd])
    marks = np.concatenate([np.zeros(len(ad)), np.ones(len(bd))])
    order = np.argsort(d, kind="stable")
    np.testing.assert_array_equal(np.asarray(oi), marks[order].astype(np.int32))


def _merge_by_binary_search(ad, ai, bd, bi):
    """merge_sorted's ranks by searchsorted's binary search (method "scan")."""
    na, nb = ad.shape[0], bd.shape[0]
    pa = jnp.arange(na, dtype=jnp.int32) + jnp.searchsorted(
        bd, ad, side="left", method="scan").astype(jnp.int32)
    pb = jnp.arange(nb, dtype=jnp.int32) + jnp.searchsorted(
        ad, bd, side="right", method="scan").astype(jnp.int32)
    od = jnp.zeros(na + nb, ad.dtype).at[pa].set(ad).at[pb].set(bd)
    oi = jnp.zeros(na + nb, ai.dtype).at[pa].set(ai).at[pb].set(bi)
    return od, oi


def _tie_heavy_sorted(rng, n):
    """[4, 64, n] ascending rows drawn from a few values (-0.0 and 0.0
    among them), each row's tail +inf-padded, as the hop's lists are."""
    vals = np.array([-0.0, 0.0, 0.5, 1.0, 3.0, 1e6], np.float32)
    d = rng.choice(vals, size=(4, 64, n)).astype(np.float32)
    pad = rng.integers(0, n + 1, size=(4, 64, 1))
    d = np.where(np.arange(n) >= n - pad, np.float32(np.inf), d)
    return np.sort(d, axis=-1, kind="stable")


# the layer-0 hop's widths (final list ef=40 and candidate list ef+maxM0=72
# against maxM0=32 new candidates) and the exact scan's k against k
MERGE_WIDTHS = [(72, 32), (40, 32), (10, 10)]


@pytest.mark.parametrize("na,nb", MERGE_WIDTHS)
def test_merge_sorted_batched_matches_binary_search_and_oracle(na, nb):
    rng = np.random.default_rng(na * 100 + nb)
    ad, bd = _tie_heavy_sorted(rng, na), _tie_heavy_sorted(rng, nb)
    ai = np.broadcast_to(np.arange(na, dtype=np.int32), ad.shape)
    bi = np.broadcast_to(1000 + np.arange(nb, dtype=np.int32), bd.shape)
    args = tuple(map(jnp.asarray, (ad, ai, bd, bi)))
    got_d, got_i = map(np.asarray,
                       jax.jit(jax.vmap(jax.vmap(merge_sorted)))(*args))
    ref_d, ref_i = map(np.asarray, jax.jit(jax.vmap(jax.vmap(
        _merge_by_binary_search)))(*args))
    # numpy oracle: a stable sort of the concatenation keeps `a` first
    cat_d = np.concatenate([ad, bd], axis=-1)
    order = np.argsort(cat_d, axis=-1, kind="stable")
    ora_d = np.take_along_axis(cat_d, order, -1)
    ora_i = np.take_along_axis(np.concatenate([ai, bi], axis=-1), order, -1)
    for want_d, want_i in ((ref_d, ref_i), (ora_d, ora_i)):
        # bit for bit: -0.0 and 0.0 must land where the reference puts them
        np.testing.assert_array_equal(got_d.view(np.uint32),
                                      want_d.view(np.uint32))
        np.testing.assert_array_equal(got_i, want_i)


def _compiled_merge_text(merge, na, nb):
    args = [jax.ShapeDtypeStruct((64, n), dt) for n, dt in
            ((na, jnp.float32), (na, jnp.int32),
             (nb, jnp.float32), (nb, jnp.int32))]
    return jax.jit(jax.vmap(merge)).lower(*args).compile().as_text()


def test_merge_sorted_compiles_without_loops_or_gathers():
    """The ranks are one comparison fusion: a binary search's `while` of
    per-element gathers (the slowest op of the TPU's layer-0 hop) would
    show in the compiled program."""
    text = _compiled_merge_text(merge_sorted, 72, 32)
    assert "while(" not in text and "gather(" not in text
    # the guard sees the loop where it is
    assert "while(" in _compiled_merge_text(_merge_by_binary_search, 72, 32)


@given(st.lists(st.integers(min_value=0, max_value=255), min_size=1,
                max_size=64, unique=True))
def test_visited_bitmap_test_and_set(ids):
    ids = np.array(ids, np.int32)
    bitmap = jnp.zeros(8, jnp.uint32)
    valid = jnp.ones(len(ids), bool)
    was, bitmap = visited_test_and_set(bitmap, jnp.asarray(ids), valid)
    assert not np.asarray(was).any()
    # second visit: everything flagged
    was2, bitmap2 = visited_test_and_set(bitmap, jnp.asarray(ids), valid)
    assert np.asarray(was2).all()
    np.testing.assert_array_equal(np.asarray(bitmap), np.asarray(bitmap2))


@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=2, max_value=12))
def test_topk_merge_associative(p, k):
    """Stage-2 invariant: top-k of concat == top-k of per-partition top-ks
    (what makes the distributed tree-merge correct)."""
    rng = np.random.default_rng(p * 100 + k)
    d = rng.uniform(size=(p, 50)).astype(np.float32)
    gids = np.arange(p * 50).reshape(p, 50)
    # per-partition top-k
    part = np.sort(d, axis=1)[:, :k]
    part_ids = np.take_along_axis(gids, np.argsort(d, axis=1, kind="stable"), 1)[:, :k]
    merged = np.sort(part.reshape(-1))[:k]
    direct = np.sort(d.reshape(-1))[:k]
    np.testing.assert_allclose(merged, direct)


@given(st.lists(floats, min_size=1, max_size=128))
def test_compression_error_feedback_converges(gs):
    """Error feedback: quantizing the SAME gradient repeatedly with carried
    residual must average out — cumulative mean error -> 0."""
    g = np.array(gs, np.float32)
    err = None
    total = np.zeros_like(g)
    n = 8
    for _ in range(n):
        q, s, err = compress_grads({"g": jnp.asarray(g)},
                                   {"g": err} if err is not None else None)
        total += np.asarray(decompress_grads(q, s)["g"])
        err = jnp.asarray(np.asarray(err["g"]))
        err = {"g": err}
    scale = max(np.abs(g).max(), 1e-3)
    np.testing.assert_allclose(total / n, g, atol=scale / 100 + 1e-6)


@given(st.integers(min_value=1, max_value=300))
def test_vocab_padding_is_multiple_of_256(v):
    from repro.models.transformer import LayerSpec, ModelConfig
    cfg = ModelConfig(name="t", d_model=8, n_heads=1, n_kv_heads=1, head_dim=8,
                      d_ff=8, vocab_size=v, pattern=(LayerSpec(),), num_periods=1)
    assert cfg.padded_vocab % 256 == 0 and cfg.padded_vocab >= v


# ---------------------------------------------------------------------------
# dtype="pq": the exact backend == a numpy ADC oracle on the same codebooks
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(m=st.sampled_from([1, 2, 4, 8]),
       dsub=st.integers(min_value=1, max_value=6),
       seed=st.integers(min_value=0, max_value=2**16))
def test_pq_exact_backend_matches_numpy_adc_oracle(m, dsub, seed):
    """For ARBITRARY (m, d = m*dsub, seed): the PQ exact backend's answers
    equal a numpy ADC oracle over the same fitted codebooks — the oracle
    gathers from the same device-built LUT and accumulates one subspace at
    a time in f32, the canonical reduction order, so sorted top-k
    distances match BITWISE; ids are compared only when the oracle's
    distances are strictly unique (ties make the winner selection-order
    dependent)."""
    from repro.api import IndexSpec, SearchRequest, SearchService
    from repro.optim.compression import build_pq_lut

    d = m * dsub
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((96, d)).astype(np.float32)
    q = rng.standard_normal((3, d)).astype(np.float32)
    k = 8
    svc = SearchService.build(
        vecs, IndexSpec(backend="exact", dtype="pq", pq_m=m))
    resp = svc.search(SearchRequest(queries=q, k=k))

    quant = svc.quantizer
    codes = quant.encode(vecs).astype(np.int64)
    lut = np.asarray(build_pq_lut(jnp.asarray(q),
                                  jnp.asarray(quant.codebooks)))
    acc = np.zeros((len(q), len(vecs)), np.float32)
    for mi in range(m):
        acc = acc + lut[:, mi, codes[:, mi]]
    np.testing.assert_array_equal(np.asarray(resp.dists),
                                  np.sort(acc, axis=1)[:, :k])
    ids = np.asarray(resp.ids)
    for b in range(len(q)):
        if np.unique(acc[b]).size == acc[b].size:
            want = np.argsort(acc[b], kind="stable")[:k]
            np.testing.assert_array_equal(ids[b], want)


# ---------------------------------------------------------------------------
# repro.serve: the dynamic batcher is lossless and transparent
# ---------------------------------------------------------------------------

_SERVE_CTX: dict = {}


def _serve_ctx():
    """One tiny exact-backend service shared across examples (module-level
    cache, not a fixture: @given and function fixtures don't mix)."""
    if not _SERVE_CTX:
        from repro.api import IndexSpec, SearchService
        rng = np.random.default_rng(7)
        vecs = rng.normal(size=(256, 16)).astype(np.float32)
        _SERVE_CTX["vecs"] = vecs
        _SERVE_CTX["svc"] = SearchService.build(
            vecs, IndexSpec(backend="exact"))
    return _SERVE_CTX["svc"], _SERVE_CTX["vecs"]


# ---------------------------------------------------------------------------
# repro.ingest: any insert/delete/search interleaving matches a numpy oracle
# ---------------------------------------------------------------------------

_INGEST_POOL: dict = {}


def _ingest_pool():
    """Deterministic vector pool (integer-valued: f32 distances are exact,
    so oracle comparisons cannot hinge on rounding)."""
    if not _INGEST_POOL:
        rng = np.random.default_rng(11)
        _INGEST_POOL["vecs"] = rng.integers(
            -8, 8, size=(256, 8)).astype(np.float32)
    return _INGEST_POOL["vecs"]


@settings(max_examples=25, deadline=None)
@given(
    ops=st.lists(
        st.one_of(
            st.tuples(st.just("insert"),
                      st.integers(min_value=1, max_value=24)),
            st.tuples(st.just("delete"),
                      st.integers(min_value=0, max_value=10_000)),
            st.tuples(st.just("search"),
                      st.integers(min_value=1, max_value=8)),
        ),
        min_size=1, max_size=24),
    seal_threshold=st.integers(min_value=4, max_value=64),
)
def test_mutable_index_matches_numpy_oracle(ops, seal_threshold):
    """Exact-backend memtables under ANY interleaving of insert/delete/
    search equal a naive numpy oracle over the surviving rows: same
    distance multiset, only live ids, deleted ids never surface."""
    from repro.api import IndexSpec, MutableSearchService, SearchRequest

    pool = _ingest_pool()
    svc = MutableSearchService(IndexSpec(backend="exact"),
                               seal_threshold=seal_threshold)
    live: dict[int, np.ndarray] = {}      # gid -> vector (the oracle)
    cursor = 0
    next_gid = 0
    for op, arg in ops:
        if op == "insert":
            rows = pool[cursor % 200: cursor % 200 + arg]
            cursor += arg
            gids = svc.insert(rows)
            assert gids.tolist() == list(range(next_gid,
                                               next_gid + len(rows)))
            next_gid += len(rows)
            live.update(zip(gids.tolist(), rows))
        elif op == "delete":
            assigned = sorted(live)
            victims = ([assigned[arg % len(assigned)]] if assigned else []) \
                + [arg]                    # one live id + an arbitrary one
            svc.delete(np.asarray(victims, np.int64))
            for v in victims:
                live.pop(v, None)
        else:
            k = arg
            q = pool[(cursor + 7) % 240: (cursor + 7) % 240 + 2]
            resp = svc.search(SearchRequest(queries=q, k=k))
            ids = np.asarray(resp.ids)
            dists = np.asarray(resp.dists)
            if not live:
                assert (ids == -1).all()
                continue
            oracle_gids = np.asarray(sorted(live), np.int64)
            oracle_vecs = np.stack([live[g] for g in oracle_gids])
            d2 = (np.einsum("nd,nd->n", oracle_vecs, oracle_vecs)[None]
                  - 2 * q @ oracle_vecs.T
                  + np.einsum("qd,qd->q", q, q)[:, None])
            k_eff = min(k, len(oracle_gids))
            for b in range(len(q)):
                got_i, got_d = ids[b], dists[b]
                assert (got_i[:k_eff] >= 0).all()
                assert (got_i[k_eff:] == -1).all()
                # every returned id is live, and its distance is exact
                for j in range(k_eff):
                    assert int(got_i[j]) in live
                    idx = int(np.searchsorted(oracle_gids, got_i[j]))
                    np.testing.assert_allclose(got_d[j], d2[b, idx],
                                               rtol=0, atol=0)
                # the distance multiset equals the oracle's k smallest
                np.testing.assert_allclose(
                    np.sort(got_d[:k_eff]), np.sort(d2[b])[:k_eff],
                    rtol=0, atol=0)
    svc.close()


# ---------------------------------------------------------------------------
# repro.cluster: ANY shard assignment of rows matches the numpy oracle
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    n_rows=st.integers(min_value=8, max_value=120),
    n_shards=st.integers(min_value=1, max_value=5),
    k=st.integers(min_value=1, max_value=12),
    assign_seed=st.integers(min_value=0, max_value=2**16),
)
def test_cluster_random_sharding_matches_numpy_oracle(
        n_rows, n_shards, k, assign_seed):
    """Scatter rows across shards ARBITRARILY (not the contiguous split) —
    the router's merge must still equal a numpy scan over all rows: exact
    distances (integer-valued pool), only real ids, k-smallest multiset.
    """
    from repro.api import IndexSpec, SearchRequest
    from repro.cluster import ClusterRouter, make_shard

    pool = _ingest_pool()
    vecs = pool[:n_rows]
    arng = np.random.default_rng(assign_seed)
    assign = arng.integers(0, n_shards, size=n_rows)
    spec = IndexSpec(backend="exact")
    clients = []
    for s in range(n_shards):
        gids = np.flatnonzero(assign == s).astype(np.int64)
        if gids.size == 0:
            continue                      # hypothesis may empty a shard
        clients.append(make_shard(vecs[gids], spec, name=f"s{s}",
                                  gid_map=gids))
    if not clients:
        return
    router = ClusterRouter(spec, clients)
    try:
        q = pool[200:204, :].astype(np.float32)
        resp = router.search(SearchRequest(queries=q, k=k))
        ids = np.asarray(resp.ids)
        dists = np.asarray(resp.dists)
        d2 = (np.einsum("nd,nd->n", vecs, vecs)[None]
              - 2 * q @ vecs.T + np.einsum("qd,qd->q", q, q)[:, None])
        k_eff = min(k, n_rows)
        for b in range(len(q)):
            assert (ids[b, :k_eff] >= 0).all()
            assert (ids[b, k_eff:] == -1).all()
            # every id is a real row with its exact distance
            for j in range(k_eff):
                np.testing.assert_allclose(
                    dists[b, j], d2[b, int(ids[b, j])], rtol=0, atol=0)
            # the k smallest distances, as a multiset
            np.testing.assert_allclose(np.sort(dists[b, :k_eff]),
                                       np.sort(d2[b])[:k_eff],
                                       rtol=0, atol=0)
    finally:
        router.close()


@settings(max_examples=15, deadline=None)
@given(
    plan=st.lists(
        st.tuples(st.integers(min_value=0, max_value=255),   # query anchor
                  st.integers(min_value=1, max_value=10),    # k
                  st.sampled_from([0.0, 0.0005, 0.002])),    # arrival gap s
        min_size=1, max_size=16),
    max_batch=st.integers(min_value=1, max_value=8),
    max_wait_ms=st.sampled_from([0.5, 2.0, 10.0]),
)
def test_dynamic_batcher_is_lossless_and_matches_direct(
        plan, max_batch, max_wait_ms):
    """Under random arrival schedules, k values, and batch/wait limits, the
    batcher (a) loses no request, (b) duplicates no request, and (c) every
    response is bit-identical to a direct SearchService.search."""
    import time as _time

    from repro.api import SearchRequest
    from repro.serve import SearchServer

    svc, vecs = _serve_ctx()
    with SearchServer(svc, replicas=1, max_batch=max_batch,
                      max_wait_ms=max_wait_ms) as srv:
        submitted = []
        for anchor, k, gap in plan:
            if gap:
                _time.sleep(gap)
            q = vecs[anchor] + np.float32(0.01)
            submitted.append((srv.submit(q, k=k, ef=16), q, k))
        results = [(f.result(timeout=120), q, k) for f, q, k in submitted]
        roll = srv.stats()

    # (a) no request lost: every future resolved
    assert len(results) == len(plan)
    assert roll.completed == len(plan)
    # (b) no request duplicated: the real (pre-padding) batch sizes sum to
    # exactly the number of submissions
    assert sum(s * c for s, c in roll.batch_sizes.items()) == len(plan)
    assert all(s <= max_batch for s in roll.batch_sizes)
    # (c) every response == direct search of that query at its own k:
    # ids bit-identical; distances to a few ulps of ||x||^2 — XLA CPU
    # matmul rounding is batch-shape-dependent, and the cancellation in
    # ||x||^2 - 2 x.q + ||q||^2 scales the absolute error with the squared
    # norms (~16 here), not with the distance itself
    for res, q, k in results:
        direct = svc.search(SearchRequest(queries=q[None], k=k))
        assert res.ids.shape == (k,)
        np.testing.assert_array_equal(res.ids, np.asarray(direct.ids)[0])
        np.testing.assert_allclose(res.dists, np.asarray(direct.dists)[0],
                                   rtol=1e-3, atol=1e-4)
