"""On-disk layout of the restructured DB (paper Fig. 5) + the row reader.

`write_store` persists a PartitionedDB as fixed-stride row tables inside
one block-aligned data file (see store/README.md for the byte-level
diagram); `StoreReader` is the serving-side object: manifest + BlockFile +
PageCache + optional Prefetcher, exposing `read_rows(table, rows)` — the
only way the search engine touches data, so all traffic is block-granular
and accounted.
"""

from __future__ import annotations

import math

import numpy as np

from repro.core import hnsw_graph as hg
from repro.core.partitioned import PartitionedDB
from repro.obs.trace import TRACER
from repro.store.blockfile import BlockFile, BlockFileWriter
from repro.store.cache import PageCache
from repro.store.prefetch import Prefetcher

__all__ = ["write_store", "StoreReader", "open_store"]


def _partition_starts(db: hg.DeviceDB) -> list[int] | None:
    """First global id of each partition, when ids are contiguous ranges
    (build_partitioned_db always produces these). Enables the O(1)
    global-id -> (partition, local-row) mapping stage-2 rerank needs;
    None disables store-side rerank for exotic id layouts."""
    gids = np.asarray(db.gids)
    if gids.ndim == 1:
        gids = gids[None]
    n_valid = np.atleast_1d(np.asarray(db.n_valid))
    starts = []
    for p in range(gids.shape[0]):
        n = int(n_valid[p])
        g = gids[p, :n]
        if n == 0 or not np.array_equal(g, np.arange(g[0], g[0] + n)):
            return None
        starts.append(int(g[0]))
    return starts


def write_store(path: str, pdb: PartitionedDB, block_size: int = 4096,
                extra_tables: dict | None = None) -> None:
    """Persist the stacked DeviceDB as a committed block store.

    `extra_tables` appends additional fixed-stride row tables after the
    canonical set (e.g. the PQ store's `rerank_vectors` float32 table).
    `load_db` ignores them; they are only reachable through
    `StoreReader.read_rows`."""
    db = jax_to_host(pdb.db)
    tables, meta = hg.db_to_tables(db)
    meta.update({
        "dim": int(pdb.dim),
        "partition_starts": _partition_starts(db),
    })
    w = BlockFileWriter(path, block_size=block_size)
    try:
        for name in hg.TABLE_ORDER:
            w.add_table(name, tables[name])
        for name, rows in (extra_tables or {}).items():
            w.add_table(name, np.ascontiguousarray(rows))
    except BaseException:
        w.abort()
        raise
    w.finalize(meta)


def _blocks_spanned(b0: np.ndarray, b1: np.ndarray) -> np.ndarray:
    """Sorted unique blocks covering rows whose first and last blocks are
    `b0` and `b1`, for rows that are sorted and unique: the rows' blocks
    then come in non-decreasing order, so dropping repeats of the one
    before leaves the sorted set."""
    span = int((b1 - b0).max(initial=0)) + 1
    if span == 1:
        blk = b0
    else:
        blk = b0[:, None] + np.arange(span)
        blk = blk[blk <= b1[:, None]]
    keep = np.ones(len(blk), bool)
    np.not_equal(blk[1:], blk[:-1], out=keep[1:])
    return blk[keep]


def jax_to_host(db: hg.DeviceDB) -> hg.DeviceDB:
    return hg.DeviceDB(*(np.asarray(x) for x in db))


class StoreReader:
    """Row-granular reads over the block store, through the page cache.

    n_pad/d_pad/... mirror the DeviceDB geometry; `read_rows` returns host
    arrays assembled from cached blocks. All counters live on `self.cache`.
    """

    def __init__(self, path: str, cache_bytes: int, prefetch: bool = True):
        self.path = path
        self.blockfile = BlockFile(path)
        self.cache = PageCache(self.blockfile, cache_bytes)
        self.prefetcher = Prefetcher(self.cache) if prefetch else None
        self.meta = self.blockfile.meta
        self.block_size = self.blockfile.block_size
        for k in ("num_partitions", "n_pad", "d_pad", "m0_pad", "n_layers",
                  "up_pad", "m_pad", "dim"):
            setattr(self, k, int(self.meta[k]))
        self.entry = np.asarray(self.meta["entry"], np.int32)
        self.max_level = np.asarray(self.meta["max_level"], np.int32)
        self.n_valid = np.asarray(self.meta["n_valid"], np.int32)
        ps = self.meta.get("partition_starts")
        self.partition_starts = None if ps is None else np.asarray(ps, np.int64)

    # -- row addressing ------------------------------------------------------

    def row(self, table: str, p: int, i) -> np.ndarray:
        """Row index of point(s) i of partition p in a per-point table."""
        return np.asarray(i, np.int64) + p * self.n_pad

    def up_row(self, p: int, layer: int, r) -> np.ndarray:
        """Row index into the upper-list table for (partition, layer, slot)."""
        return np.asarray(r, np.int64) + (p * self.n_layers + layer) * self.up_pad

    def blocks_of_rows(self, table: str, rows) -> list[int]:
        """Sorted unique blocks that hold any byte of `rows`."""
        starts = self.blockfile.row_starts(table, np.unique(rows))
        end = starts + (self.blockfile.tables[table]["row_bytes"] - 1)
        return _blocks_spanned(starts // self.block_size,
                               end // self.block_size).tolist()

    # -- reads ---------------------------------------------------------------

    def read_rows(self, table: str, rows, _get=None) -> np.ndarray:
        """Gather rows (any shape of indices) -> array [..., cols].

        Duplicate rows inside one request are fetched once (the engine
        batches a whole hop's gathers into one call — the paper's wide
        block read). Addressing, the cache fetch and the assembly are
        array operations over the whole request: the needed blocks are
        joined in sorted order into one buffer, and every row is one
        gather from it — a row that straddles blocks included, since its
        blocks sit side by side there."""
        # the whole read: block lookup, block copies and row assembly.
        # child_span: only records under an already-sampled span on this
        # thread — prefetcher-worker calls (and untraced callers) stay free.
        with TRACER.child_span("store-read", table=table) as sp:
            t = self.blockfile.tables[table]
            idx = np.asarray(rows, np.int64)
            dtype = np.dtype(t["dtype"])
            cols, rb, bs = t["cols"], t["row_bytes"], self.block_size
            uniq, inv = np.unique(idx.ravel(), return_inverse=True)
            starts = self.blockfile.row_starts(table, uniq)
            b0 = starts // bs
            need = _blocks_spanned(b0, (starts + rb - 1) // bs)
            order = need.tolist()
            misses, fallbacks = self.cache.misses, self.cache.fallbacks
            if _get is None:
                blocks = self.cache.get_many(order)
            else:
                blocks = {b: _get(b) for b in order}
            sp.set(rows=len(uniq), blocks=len(order),
                   misses=self.cache.misses - misses,
                   fallback=self.cache.fallbacks - fallbacks)
            # gather in units of g bytes: every row start and length, and
            # the block size, are multiples of g, so a row is rb // g units
            g = math.gcd(rb, bs, t["offset"])
            buf = np.frombuffer(b"".join([blocks[b] for b in order]),
                                np.dtype((np.void, g)))
            first = (np.searchsorted(need, b0) * bs + starts - b0 * bs) // g
            out = buf[first[:, None] + np.arange(rb // g)].view(dtype)
            return out[inv].reshape(idx.shape + (cols,))

    # -- prefetch hooks ------------------------------------------------------

    def prefetch_rows(self, table: str, rows) -> None:
        if self.prefetcher is not None:
            self.prefetcher.prefetch_blocks(self.blocks_of_rows(table, rows))

    def prefetch_next_hop(self, p: int, cand_ids: np.ndarray) -> None:
        """Chained next-hop prefetch: pull the l0 neighbor-list rows of the
        likely next pops, parse them on the worker, then pull the vector
        blocks those neighbors live in — all overlapped with device compute."""
        if self.prefetcher is None:
            return
        cand = np.asarray(cand_ids).ravel()
        cand = cand[cand >= 0]
        if not cand.size:
            return
        l0_blocks = self.blocks_of_rows("l0_nbrs", self.row("l0_nbrs", p, cand))

        def task():
            self.cache.prefetch_many(l0_blocks)
            nbrs = self._parse_l0_rows(p, cand)
            if len(nbrs):
                self.cache.prefetch_many(self.blocks_of_rows(
                    "vectors", self.row("vectors", p, nbrs)))

        self.prefetcher.submit(task)

    def _parse_l0_rows(self, p: int, ids) -> np.ndarray:
        """Worker-side decode of the just-prefetched l0 rows; traffic counts
        as prefetch, never as demand."""
        rows = self.read_rows("l0_nbrs", self.row("l0_nbrs", p, ids),
                              _get=self.cache.prefetch_get)
        flat = rows.ravel()
        return np.unique(flat[flat >= 0])

    # -- lifecycle / debug ---------------------------------------------------

    def load_db(self) -> hg.DeviceDB:
        """Materialize the full DeviceDB in host memory (tests and small
        stores only — this defeats the out-of-core purpose by design)."""
        tables = {}
        for name, t in self.blockfile.tables.items():
            tables[name] = self.read_rows(name, np.arange(t["rows"]))
        return hg.db_from_tables(tables, self.meta)

    def close(self) -> None:
        if self.prefetcher is not None:
            self.prefetcher.close()
            self.prefetcher = None


def open_store(path: str, cache_bytes: int, prefetch: bool = True) -> StoreReader:
    return StoreReader(path, cache_bytes, prefetch=prefetch)
