"""Backend registry: five engines, one search contract.

Every backend answers the same call — `search(queries, k, ef, rerank,
with_stats)` over metric-prepared queries — and exposes a `state_tree()` /
`from_state()` pair the service uses for versioned save/load. Selection
happens through `IndexSpec.backend`:

  exact       : blocked brute-force scan (paper Fig. 9 baseline); ignores ef
  hnsw        : one monolithic graph (partitioned with P=1)
  partitioned : the paper's two-stage engine — P sub-graphs + device merge
  distributed : partitions sharded over the mesh `model` axis with an
                all-gather stage-2 merge (paper Fig. 10/11)
  csd         : out-of-core over the block store (repro.store) — the
                database stays on "flash", host memory is bounded by the
                PageCache, stats count block reads (the paper's platform)

`register_backend` is open: NDSEARCH-style near-data engines or quantized
variants plug in without touching the service layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.rerank import batched_rerank
from repro.api.types import IndexSpec, QueryStats
from repro.core import hnsw_graph as hg
from repro.core.bruteforce import bruteforce_topk
from repro.core.partitioned import (
    PartitionedDB,
    build_partitioned_db,
    quantize_db_vectors,
    search_partitioned,
    search_partitioned_candidates,
)
from repro.core.search import SearchParams

__all__ = ["register_backend", "get_backend", "available_backends",
           "ExactBackend", "HNSWBackend", "PartitionedBackend",
           "DistributedBackend", "CSDBackend"]

_BACKENDS: dict[str, type] = {}


def register_backend(name: str):
    def deco(cls):
        cls.name = name
        _BACKENDS[name] = cls
        return cls
    return deco


def get_backend(name: str) -> type:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {sorted(_BACKENDS)}"
        ) from None


def available_backends() -> list[str]:
    return sorted(_BACKENDS)


def _device_vectors(vectors: np.ndarray):
    """Raw vectors + sqnorms as device arrays (rerank / exact scoring)."""
    v = jnp.asarray(vectors, jnp.float32)
    return v, jnp.einsum("nd,nd->n", v, v)


# ---------------------------------------------------------------------------
# exact
# ---------------------------------------------------------------------------


@register_backend("exact")
class ExactBackend:
    """Blocked exact scan; the ground-truth engine and the Fig. 9 baseline."""

    uses_graph = False
    CHUNK = 512

    def __init__(self, spec: IndexSpec, raw: np.ndarray):
        self.spec = spec
        self.quant = spec.quantizer()
        self.is_pq = spec.dtype == "pq"
        if self.is_pq:
            # raw is either the original float32 rows (build) or the
            # checkpointed [n, m] uint8 code table (from_state) — the scan
            # runs the fused Pallas ADC top-k over the codes either way
            raw = np.asarray(raw)
            if raw.dtype != np.uint8 or raw.shape[-1] != self.quant.m:
                raw = self.quant.encode(np.asarray(raw, np.float32))
            self.raw = raw
            self.codes = jnp.asarray(raw)
            self._cbs = jnp.asarray(self.quant.codebooks)
            self.n = raw.shape[0]
            self.vectors = self.sqnorms = None
            return
        # quantized: raw IS the code table (uint8/int8); scan it as-is
        self.raw = (np.asarray(raw) if self.quant is not None
                    else np.asarray(raw, np.float32))
        n, d = self.raw.shape
        n_pad = ((n + self.CHUNK - 1) // self.CHUNK) * self.CHUNK
        vp = np.zeros((n_pad, d), self.raw.dtype)
        vp[:n] = self.raw
        rf = self.raw.astype(np.float32)
        sq = np.full(n_pad, np.inf, np.float32)   # +inf == pad marker
        sq[:n] = np.einsum("nd,nd->n", rf, rf)
        self.vectors = jnp.asarray(vp)
        self.sqnorms = jnp.asarray(sq)
        self.n = n

    @classmethod
    def build(cls, vectors: np.ndarray, spec: IndexSpec, mesh=None):
        return cls(spec, vectors)

    def search(self, queries, k: int, ef: int, rerank: bool,
               with_stats: bool):
        if self.is_pq:
            from repro.kernels.ops import pq_topk
            from repro.optim.compression import build_pq_lut
            luts = build_pq_lut(jnp.asarray(queries, jnp.float32),
                                self._cbs)
            dists, ids = pq_topk(luts, self.codes, k=k)
        else:
            ids, dists = bruteforce_topk(
                self.vectors, self.sqnorms, jnp.asarray(queries), k=k,
                chunk=self.CHUNK, metric=self.spec.metric)
            if self.quant is not None:  # code-space -> real-space distances
                dists = dists * jnp.float32(self.quant.dist_scale)
        stats = None
        if with_stats:
            b = ids.shape[0]
            stats = QueryStats(dist_calcs=jnp.full((b,), self.n, jnp.int32))
        return ids, dists, stats

    def state_tree(self) -> dict:
        return {"exact": {"raw": self.raw},
                "meta": {"n": jnp.int32(self.n),
                         "dim": jnp.int32(self.raw.shape[1])}}

    @classmethod
    def from_state(cls, spec: IndexSpec, leaves: dict, mesh=None):
        return cls(spec, leaves["exact/raw"])


# ---------------------------------------------------------------------------
# partitioned (and its P=1 alias, hnsw)
# ---------------------------------------------------------------------------


@register_backend("partitioned")
class PartitionedBackend:
    """The paper's engine: P accelerator-resident sub-graphs, stage-2 merge
    on device, optional exact rerank over the P*K intermediates."""

    uses_graph = True
    forced_partitions: int | None = None

    def __init__(self, spec: IndexSpec, pdb: PartitionedDB,
                 raw: np.ndarray | None = None):
        self.spec = spec
        self.pdb = pdb
        self.quant = spec.quantizer()
        self.is_pq = spec.dtype == "pq"
        self._cbs = (jnp.asarray(self.quant.codebooks)
                     if self.is_pq else None)
        # quantized: `raw` holds the codes; rerank re-scores over the
        # DEQUANTIZED rows (stage 2 stays float32, paper Fig. 4). PQ is
        # different: `raw` holds the TRUE float32 rows — reranking over
        # decoded PQ rows would be a no-op (ADC already IS the distance to
        # the reconstruction), so stage 2 needs the real vectors to
        # recover recall.
        if self.is_pq:
            self.raw = None if raw is None else np.asarray(raw, np.float32)
        else:
            self.raw = (None if raw is None else
                        np.asarray(raw) if self.quant is not None else
                        np.asarray(raw, np.float32))
        if self.raw is not None:
            flt = (self.raw if (self.quant is None or self.is_pq)
                   else self.quant.decode(self.raw))
            self.dev_vectors, self.dev_sqnorms = _device_vectors(flt)
        else:
            self.dev_vectors = self.dev_sqnorms = None

    @staticmethod
    def _check_fused(spec: IndexSpec) -> None:
        """fused_hops > 1 runs layer 0 in the fused_traversal_pallas kernel,
        which Mosaic does not compile yet: refuse on a TPU rather than
        fall back to another path. Off-TPU it runs in interpret mode.
        PQ never takes the kernel (its layer 0 is always hop-stepped)."""
        from repro.kernels.ops import default_interpret
        if (spec.fused_hops > 1 and spec.dtype != "pq"
                and not default_interpret()):
            raise NotImplementedError(
                f"fused_hops={spec.fused_hops} needs the "
                f"fused_traversal_pallas kernel (kernels/traversal.py), "
                f"which does not compile for "
                f"{jax.default_backend()!r} yet; build with fused_hops=1")

    @classmethod
    def build(cls, vectors: np.ndarray, spec: IndexSpec, mesh=None):
        cls._check_fused(spec)
        p = cls.forced_partitions or spec.num_partitions
        # dtype="pq": `vectors` are the ORIGINAL float32 rows — the graphs
        # are built full-precision and quantize_db_vectors re-encodes the
        # raw-data leaf to M-byte code rows afterwards (DiskANN-style:
        # full-precision graph, PQ traversal)
        pdb = build_partitioned_db(vectors, p, spec.hnsw)
        pdb = quantize_db_vectors(
            pdb, spec.dtype,
            spec.quantizer() if spec.dtype == "pq" else None)
        pdb = PartitionedDB(db=jax.tree.map(jnp.asarray, pdb.db),
                            num_partitions=pdb.num_partitions, dim=pdb.dim)
        return cls(spec, pdb, raw=vectors if spec.keep_vectors else None)

    def params(self, k: int, ef: int) -> SearchParams:
        return SearchParams(ef=ef, k=k, metric=self.spec.metric,
                            fused_hops=self.spec.fused_hops)

    def _lut(self, q):
        """Per-query ADC tables for dtype='pq' (None otherwise)."""
        if not self.is_pq:
            return None
        from repro.optim.compression import build_pq_lut
        return build_pq_lut(q.astype(jnp.float32), self._cbs)

    def search(self, queries, k: int, ef: int, rerank: bool,
               with_stats: bool):
        p = self.params(k, ef)
        q = jnp.asarray(queries)
        lut = self._lut(q)
        if rerank:
            if self.dev_vectors is None:
                raise ValueError(
                    "rerank=True needs the raw vectors: build the index "
                    "with IndexSpec(keep_vectors=True)")
            cand, _, st = search_partitioned_candidates(self.pdb, q, p, lut)
            rq = (q if (self.quant is None or self.is_pq)
                  else self.quant.decode(q))
            ids, dists = batched_rerank(
                self.dev_vectors, self.dev_sqnorms, rq, cand, k,
                self.spec.metric)
        else:
            ids, dists, st = search_partitioned(self.pdb, q, p, lut)
            if self.quant is not None and not self.is_pq:
                # code-space -> real-space (PQ is already real-space)
                dists = dists * jnp.float32(self.quant.dist_scale)
        stats = None
        if with_stats:
            stats = QueryStats(hops=st.hops.sum(axis=0),
                               dist_calcs=st.dist_calcs.sum(axis=0))
        return ids, dists, stats

    def state_tree(self) -> dict:
        tree = {"db": self.pdb.db._asdict(),
                "meta": {"num_partitions": jnp.int32(self.pdb.num_partitions),
                         "dim": jnp.int32(self.pdb.dim)}}
        if self.raw is not None:
            tree["vectors"] = {"raw": self.raw}
        return tree

    @classmethod
    def from_state(cls, spec: IndexSpec, leaves: dict, mesh=None):
        cls._check_fused(spec)
        db = hg.DeviceDB(**{k.split("/", 1)[1]: jnp.asarray(v)
                            for k, v in leaves.items()
                            if k.startswith("db/")})
        pdb = PartitionedDB(db=db,
                            num_partitions=int(leaves["meta/num_partitions"]),
                            dim=int(leaves["meta/dim"]))
        return cls(spec, pdb, raw=leaves.get("vectors/raw"))


@register_backend("hnsw")
class HNSWBackend(PartitionedBackend):
    """Single monolithic graph — partitioned with exactly one partition."""

    forced_partitions = 1


# ---------------------------------------------------------------------------
# distributed
# ---------------------------------------------------------------------------


@register_backend("distributed")
class DistributedBackend(PartitionedBackend):
    """Graph parallelism over the mesh `model` axis (paper §6.3): each
    device searches only its resident sub-graphs; stage 2 is an all-gather
    + rank merge. Jitted search fns are cached per (k, ef)."""

    def __init__(self, spec: IndexSpec, pdb: PartitionedDB, mesh,
                 raw: np.ndarray | None = None):
        super().__init__(spec, pdb, raw=raw)
        self.mesh = mesh
        self._fns: dict = {}

    @classmethod
    def build(cls, vectors: np.ndarray, spec: IndexSpec, mesh=None):
        from repro.core.distributed import shard_db
        mesh = mesh or _default_mesh()
        n_model = mesh.shape["model"]
        if spec.num_partitions % n_model != 0:
            raise ValueError(
                f"num_partitions={spec.num_partitions} must divide over "
                f"the mesh model axis ({n_model})")
        pdb = build_partitioned_db(vectors, spec.num_partitions, spec.hnsw)
        pdb = quantize_db_vectors(
            pdb, spec.dtype,
            spec.quantizer() if spec.dtype == "pq" else None)
        pdb = shard_db(pdb, mesh)
        return cls(spec, pdb, mesh,
                   raw=vectors if spec.keep_vectors else None)

    def params(self, k: int, ef: int) -> SearchParams:
        # the fused Pallas traversal is not wired through shard_map — the
        # distributed engine always runs the hop-stepped lockstep path
        return SearchParams(ef=ef, k=k, metric=self.spec.metric)

    def _fn(self, k: int, ef: int, merge: bool = True):
        key = (k, ef, merge)
        if key not in self._fns:
            from repro.core.distributed import make_distributed_search
            from repro.launch.mesh import dp_axes
            maxM0 = int(self.pdb.db.l0_nbrs.shape[-1])
            self._fns[key] = make_distributed_search(
                self.mesh, self.params(k, ef), maxM0,
                graph_axes=("model",), query_axes=dp_axes(self.mesh),
                merge=merge, pq=self.is_pq)
        return self._fns[key]

    def search(self, queries, k: int, ef: int, rerank: bool,
               with_stats: bool):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import dp_axes
        dp = dp_axes(self.mesh)
        q = jax.device_put(
            jnp.asarray(queries),
            NamedSharding(self.mesh, P(dp if dp else None, None)))
        extra = ()
        if self.is_pq:
            # LUTs shard exactly like the query rows they belong to
            extra = (jax.device_put(
                self._lut(jnp.asarray(queries)),
                NamedSharding(self.mesh, P(dp if dp else None, None,
                                           None))),)
        if rerank:
            if self.dev_vectors is None:
                raise ValueError(
                    "rerank=True needs the raw vectors: build the index "
                    "with IndexSpec(keep_vectors=True)")
            # unmerged P*k candidate pool, exactly re-scored (stage 2)
            cand, _, calcs = self._fn(k, ef, merge=False)(
                self.pdb.db, q, *extra)
            rq = jnp.asarray(queries)
            if self.quant is not None and not self.is_pq:
                rq = self.quant.decode(rq)
            ids, dists = batched_rerank(
                self.dev_vectors, self.dev_sqnorms, rq,
                cand, k, self.spec.metric)
        else:
            ids, dists, calcs = self._fn(k, ef)(self.pdb.db, q, *extra)
            if self.quant is not None and not self.is_pq:
                # code-space -> real-space (PQ is already real-space)
                dists = dists * jnp.float32(self.quant.dist_scale)
        stats = None
        if with_stats:
            stats = QueryStats(dist_calcs=calcs[:, 0])
        return ids, dists, stats

    @classmethod
    def from_state(cls, spec: IndexSpec, leaves: dict, mesh=None):
        from repro.core.distributed import shard_db
        mesh = mesh or _default_mesh()
        db = hg.DeviceDB(**{k.split("/", 1)[1]: np.asarray(v)
                            for k, v in leaves.items()
                            if k.startswith("db/")})
        pdb = PartitionedDB(db=db,
                            num_partitions=int(leaves["meta/num_partitions"]),
                            dim=int(leaves["meta/dim"]))
        pdb = shard_db(pdb, mesh)
        return cls(spec, pdb, mesh, raw=leaves.get("vectors/raw"))


def _default_mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh((len(jax.devices()),), ("model",))


# ---------------------------------------------------------------------------
# csd — out-of-core over the block store (defined in repro.store.csd, which
# imports repro.api only lazily inside methods, so this registration import
# is acyclic whichever package loads first)
# ---------------------------------------------------------------------------

from repro.store.csd import CSDBackend  # noqa: E402

register_backend("csd")(CSDBackend)
