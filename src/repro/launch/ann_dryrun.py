import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # a modeling tool: never take a chip

"""SIFT1B-scale dry-run of the paper's engine itself on the production mesh.

The paper's deployment: 1B x 128-dim vectors split into DRAM-sized
sub-graphs, graph parallelism across devices. Here: 256 partitions of
~3.9M vectors (cf. the paper's ~5M per SmartSSD), one per chip on the
single-pod mesh; queries shard over `data` (and `pod`). This lowers and
compiles the full two-stage distributed search (stage-1 beam + all-gather +
rank-merge) from ShapeDtypeStructs — no allocation — and reports the memory
and collective footprint.

  PYTHONPATH=src python -m repro.launch.ann_dryrun [--multi-pod]
"""

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.distributed import make_distributed_search
from repro.core.hnsw_graph import DeviceDB
from repro.core.search import SearchParams
from repro.launch.mesh import enter_mesh, make_production_mesh
from repro.launch.roofline import HW, collective_bytes


def sift1b_db_specs(mesh, n_total=1_000_000_000, dim=128, M=16, levels=7):
    """ShapeDtypeStruct stand-ins for the restructured SIFT1B database."""
    P_parts = 256
    n_pad = -(-(n_total // P_parts) // 32) * 32
    d_pad = 128 * -(-dim // 128)
    m0p, mp = 2 * M, M
    up_rows = -(-n_pad // 16) * 2        # ~1/(M-1) of points have level>=1
    sh = lambda spec: NamedSharding(mesh, spec)
    f = jax.ShapeDtypeStruct
    m = P(("data", "model"))   # one partition per chip within a pod
    return DeviceDB(
        vectors=f((P_parts, n_pad, d_pad), jnp.float32, sharding=sh(m)),
        sqnorms=f((P_parts, n_pad), jnp.float32, sharding=sh(m)),
        l0_nbrs=f((P_parts, n_pad, m0p), jnp.int32, sharding=sh(m)),
        up_nbrs=f((P_parts, levels, up_rows, mp), jnp.int32, sharding=sh(m)),
        up_ptr=f((P_parts, n_pad), jnp.int32, sharding=sh(m)),
        levels=f((P_parts, n_pad), jnp.int32, sharding=sh(m)),
        gids=f((P_parts, n_pad), jnp.int32, sharding=sh(m)),
        entry=f((P_parts,), jnp.int32, sharding=sh(m)),
        max_level=f((P_parts,), jnp.int32, sharding=sh(m)),
        n_valid=f((P_parts,), jnp.int32, sharding=sh(m)),
    ), n_pad, d_pad, m0p


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--calibrated", default=None, metavar="METRICS_JSON",
                    help="fit the cost-model HW parameters (effective SSD "
                         "bandwidth, cache hit rate, dispatch overhead) "
                         "from this metrics snapshot (the exporter's .json "
                         "output) and report per-term modeled-vs-measured "
                         "error alongside the prior-based numbers")
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    db, n_pad, d_pad, m0p = sift1b_db_specs(mesh)
    p = SearchParams(ef=40, k=10)                 # the paper's SIFT1B point
    qaxes = ("pod",) if args.multi_pod else ()
    search = make_distributed_search(mesh, p, m0p,
                                     graph_axes=("data", "model"),
                                     query_axes=qaxes)
    q = jax.ShapeDtypeStruct((args.batch, d_pad), jnp.float32,
                             sharding=NamedSharding(
                                 mesh, P(qaxes if qaxes else None, None)))
    with enter_mesh(mesh):
        lowered = search.lower(db, q)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    resident = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    coll = collective_bytes(compiled.as_text())
    hw = HW()
    # memory-bound engine roofline: per-query HBM traffic per hop-budget.
    reads_per_query = 4 * p.ef + 16                # hop budget (worst case)
    bytes_per_query = reads_per_query * m0p * (d_pad * 4 + 4)
    qps_chip = hw.hbm_bw / bytes_per_query

    # storage-bound alternative (repro.store csd mode): the same traversal
    # with the DB on flash — each vector read is one block read over the
    # SSD link; the PageCache absorbs part of it. This reproduces the
    # paper's storage-bound analysis (§6.5 / Fig. 12). SIFT1B itself is
    # uint8 (IndexSpec.dtype): rows shrink 4x, and because the SSD link is
    # byte-limited the effective blocks-per-read shrink with them — the
    # uint8 entry is the paper's actual operating point. The pq entry
    # (M=8 codes, 16x below uint8 at d=128) shows how far LUT-based ADC
    # pushes the same storage-bound roofline.
    from repro.launch.costmodel import storage_cost, vector_row_bytes
    block_size = 4096
    storage = {}
    for dtype in ("float32", "uint8", "pq"):
        row_b = vector_row_bytes(128, dtype)
        # row_bytes/block_size of a block per vector read: the byte-limited
        # SSD-link view (block-packing locality at 8..32 rows per block)
        blocks_per_query = reads_per_query * m0p * row_b / block_size
        per_hit = {}
        for hit in (0.0, 0.5, 0.9):
            sc = storage_cost(blocks_per_query, block_size,
                              cache_hit_rate=hit, ssd_bw=hw.ssd_bw)
            per_hit[f"hit_{hit:.1f}"] = {
                "bytes_from_flash_per_query": sc.bytes_from_flash,
                "modeled_qps_per_device": round(1.0 / sc.storage_s, 2),
            }
        storage[dtype] = {"vector_row_bytes": row_b,
                          "blocks_per_query": round(blocks_per_query, 1),
                          **per_hit}
    blocks_per_query = storage["float32"]["blocks_per_query"]

    # streaming-ingest term (repro.ingest): what growing the same database
    # online would cost in SSD writes. One day of heavy insert traffic at
    # ~1% of the corpus, sealed in SmartSSD-DRAM-sized memtables and
    # compacted every 8 seals (the merge-everything policy the compactor
    # implements), priced as write amplification on the same SSD link the
    # reads contend for.
    from repro.launch.costmodel import compaction_cost
    ingest = {}
    n_daily = 10_000_000
    seal_threshold = 1_000_000
    compact_every = 8
    for dtype in ("float32", "uint8", "pq"):
        row_b = vector_row_bytes(128, dtype)
        cc = compaction_cost(n_daily, row_b, seal_threshold, compact_every,
                             delete_frac=0.05, ssd_bw=hw.ssd_bw)
        ingest[dtype] = {
            "bytes_ingested": cc.bytes_ingested,
            "bytes_rewritten": cc.bytes_rewritten,
            "write_amplification": round(cc.write_amp, 2),
            "seals": cc.seals,
            "compactions": cc.compactions,
            "rewrite_s_on_ssd_link": round(cc.rewrite_s, 1),
        }
    ingest_note = (
        "mutable-index (repro.ingest) write path: {} inserts/day at "
        "seal_threshold={}, compact every {} seals, 5% churn; rewrite "
        "seconds come out of the same SSD link the storage-bound read "
        "roofline above prices".format(n_daily, seal_threshold,
                                       compact_every))

    # cluster fan-out term (repro.cluster): the same storage-bound search
    # sharded across nodes. Each shard replica brings its own SSD link, so
    # aggregate flash bandwidth scales with N*R, but every query pays the
    # router scatter-gather plus full-ef traversal on EVERY shard (the
    # over-fetch that keeps the merge bit-identical) — this row shows where
    # the cluster stops being storage-bound and the router NIC takes over.
    from repro.launch.costmodel import cluster_fanout_cost
    cluster = {}
    for n_shards in (1, 2, 4):
        for reps in (1, 2):
            fc = cluster_fanout_cost(
                n_shards, reps, dim=128, k=10,
                blocks_per_query=blocks_per_query, block_size=block_size,
                cache_hit_rate=0.5, ssd_bw=hw.ssd_bw)
            cluster[f"shards_{n_shards}x{reps}"] = {
                "router_bytes_per_query": fc.router_bytes_q,
                "flash_bytes_per_query": fc.flash_bytes_q,
                "aggregate_ssd_bw": fc.aggregate_ssd_bw,
                "modeled_qps": round(fc.modeled_qps, 1),
                "bound": fc.bound,
            }

    rec = {
        "mesh": "multi" if args.multi_pod else "single",
        "devices": int(mesh.devices.size),
        "partitions": 256,
        "vectors_per_partition": n_pad,
        "db_bytes_per_device": int(resident - ma.temp_size_in_bytes),
        "resident_bytes": int(resident),
        "fits_hbm": bool(resident < hw.hbm_bytes),
        "collectives": {k: float(v) for k, v in coll.items()},
        "modeled_worstcase_qps_per_chip": round(qps_chip, 1),
        "csd_storage_bound": {
            "block_size": block_size,
            "blocks_per_query": blocks_per_query,
            "ssd_bw": hw.ssd_bw,
            **storage,
            "note": ("out-of-core (backend='csd') roofline: storage term "
                     "dominates HBM by ~{:.0f}x at hit 0 — the paper's "
                     "SSD-bound regime (75.59 QPS on 4 SmartSSDs)".format(
                         (blocks_per_query * block_size / hw.ssd_bw)
                         / (bytes_per_query / hw.hbm_bw))),
        },
        "ingest_write_amplification": {**ingest, "note": ingest_note},
        "cluster_fanout": {
            **cluster,
            "note": ("repro.cluster scatter-gather at cache hit 0.5 over a "
                     "10 GbE router link: replicas scale storage QPS "
                     "linearly; shards add SSDs but also duplicate full-ef "
                     "traversal, so gains flatten until the router binds"),
        },
        "note": ("stage-2 merge traffic per query = P*k*(4+4)B across "
                 "`model` — negligible vs stage-1 HBM reads (paper: 0.2%)"),
    }

    if args.calibrated:
        # capacity planning on observed numbers (ROADMAP item 5): fit the
        # HW parameters from the snapshot, report per-term error, and
        # reprice the MEASURED workload with the fitted parameters
        from repro.launch.costmodel import dispatch_cost
        from repro.obs.calibrate import compare_terms, load_calibration
        cal = load_calibration(args.calibrated)
        section = {
            "source": args.calibrated,
            "fitted": cal.asdict(),
            "terms": compare_terms(cal, hw=hw),
        }
        if (cal.queries and cal.blocks_per_query and cal.block_size
                and cal.effective_ssd_bw):
            sc = storage_cost(cal.blocks_per_query, cal.block_size,
                              cache_hit_rate=cal.cache_hit_rate or 0.0,
                              ssd_bw=cal.effective_ssd_bw)
            dc = dispatch_cost(cal.supersteps_per_query or 0.0,
                               cal.dispatch_overhead_s or 0.0)
            total_s = sc.storage_s + dc.dispatch_s
            section["measured_workload"] = {
                "storage_s_per_query": sc.storage_s,
                "dispatch_s_per_query": dc.dispatch_s,
                "calibrated_qps_per_device": (round(1.0 / total_s, 2)
                                              if total_s > 0 else None),
            }
        rec["calibration"] = section

    print(json.dumps(rec, indent=2))


if __name__ == "__main__":
    main()
