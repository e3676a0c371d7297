"""Replica placement worker (run under 4 fake devices).

For each in-memory backend shape (exact float32, exact PQ, partitioned
uint8, partitioned PQ with rerank tables) builds a 4-replica pool and
asserts that replica i holds every device array on device i alone (graph,
scan tables, rerank tables, PQ codebooks and codes) and answers there with
replica 0's ids. Prints `REPLICAS OK` and exits 0 on success. Launched by
tests/test_serve.py in a subprocess so the parent pytest process keeps its
1-device view.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()

import jax
import numpy as np

from repro.api import IndexSpec, SearchRequest, SearchService
from repro.core import hnsw_graph as hg
from repro.data import clustered_vectors
from repro.serve.dispatch import ReplicaPool


def arrays(backend):
    return [a for v in vars(backend).values() for a in jax.tree.leaves(v)
            if isinstance(a, jax.Array)]


def main():
    devices = jax.devices()
    assert len(devices) == 4, devices
    vecs = clustered_vectors(600, 32, k=8, seed=0)
    queries = vecs[:16] + 0.5
    cfg = hg.HNSWConfig(M=8, ef_construction=40, seed=0)
    specs = {
        "exact_f32": IndexSpec(backend="exact"),
        "exact_pq": IndexSpec(backend="exact", dtype="pq", pq_m=4),
        "partitioned_u8": IndexSpec(backend="partitioned", dtype="uint8",
                                    num_partitions=2, hnsw=cfg),
        "partitioned_pq": IndexSpec(backend="partitioned", dtype="pq",
                                    pq_m=4, num_partitions=2, hnsw=cfg,
                                    keep_vectors=True),
    }
    for name, spec in specs.items():
        pool = ReplicaPool.replicate(SearchService.build(vecs, spec), 4)
        want = None
        for i, rep in enumerate(pool.replicas):
            held = arrays(rep.service.backend)
            assert held, name
            for a in held:
                assert a.devices() == {devices[i]}, (name, i, a.devices())
            resp = rep.service.search(SearchRequest(
                queries, k=5, rerank=spec.keep_vectors))
            assert resp.ids.devices() == {devices[i]}, (name, i)
            ids = np.asarray(resp.ids)
            if want is None:
                want = ids
            assert np.array_equal(ids, want), (name, i)
        pool.close()
        print(f"{name}: {len(pool.replicas)} replicas on their own devices")
    print("REPLICAS OK")


if __name__ == "__main__":
    main()
