"""Calibrate the analytic cost model against XLA's cost_analysis.

XLA counts while-loop bodies once, so calibration uses configs where every
loop has trip count 1: num_periods=1, attention blocks >= T, loss chunks =
T, SSM chunk >= T. On such configs cost_analysis is complete and must agree
with launch/costmodel.py within tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.launch.costmodel import (_count_params, cell_costs,
                                    compaction_cost, storage_cost)
from repro.models.model import prefill_step
from repro.models.transformer import init_cache, init_params

T, B = 64, 4


def _single_trip(cfg):
    kw = dict(num_periods=1, prefix_pattern=(), block_q=T, block_k=T,
              loss_chunk=T, param_dtype=jnp.float32)
    if cfg.mamba is not None:
        kw["mamba"] = dataclasses.replace(cfg.mamba, chunk=T)
    if cfg.xlstm is not None:
        kw["xlstm"] = dataclasses.replace(cfg.xlstm, chunk=T)
    return dataclasses.replace(cfg, **kw)


@pytest.mark.parametrize("arch,tol", [
    ("granite_3_8b", 0.30),
    ("qwen3_14b", 0.30),
    ("deepseek_v2_lite_16b", 0.45),   # scatter/gather flops are fuzzier
])
def test_prefill_flops_match_xla(arch, tol):
    cfg = _single_trip(reduced_config(arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, B, T)
    if cfg.embed_inputs:
        inputs = jnp.zeros((B, T), jnp.int32)
    else:
        inputs = jnp.zeros((B, T, cfg.d_model), jnp.float32)
    lowered = prefill_step.lower(params, {"inputs": inputs}, cache, cfg)
    got = lowered.compile().cost_analysis()["flops"]
    want = cell_costs(cfg, "prefill", T, B, n_devices=1, model_ax=1,
                      dp_ax=1, fsdp=False).flops_per_dev
    # analytic excludes elementwise ops XLA counts (norms, rope, softmax),
    # so allow an asymmetric band.
    ratio = got / want
    assert (1 - tol) < ratio < (1 + 2 * tol), (
        f"{arch}: XLA {got/1e6:.1f}MF vs analytic {want/1e6:.1f}MF "
        f"(ratio {ratio:.2f})")


def test_storage_cost_term():
    """The csd storage-bandwidth term: blocks * block_size / SSD-BW,
    cache-hit-adjusted; hits scale the flash traffic linearly."""
    from repro.launch.roofline import HW
    hw = HW()
    cold = storage_cost(1000, 4096, cache_hit_rate=0.0, ssd_bw=hw.ssd_bw)
    assert cold.blocks_from_flash == 1000
    assert cold.bytes_from_flash == 1000 * 4096
    assert cold.storage_s == pytest.approx(1000 * 4096 / hw.ssd_bw)
    warm = storage_cost(1000, 4096, cache_hit_rate=0.9, ssd_bw=hw.ssd_bw)
    assert warm.bytes_from_flash == pytest.approx(0.1 * cold.bytes_from_flash)
    assert warm.storage_s == pytest.approx(0.1 * cold.storage_s)
    # the paper's regime: the storage term dwarfs the HBM term for the
    # same traffic (SmartSSD ~3 GB/s vs HBM ~819 GB/s)
    assert cold.storage_s > (1000 * 4096 / hw.hbm_bw) * 100
    with pytest.raises(ValueError, match="cache_hit_rate"):
        storage_cost(1, 4096, cache_hit_rate=1.5)


def test_compaction_cost_write_amplification():
    """The ingest write-amp term: no compaction -> amp 1; the
    merge-everything policy compounds; deletes shrink later rewrites;
    uint8 rows cut the absolute bytes 4x at identical amplification."""
    from repro.launch.costmodel import vector_row_bytes
    from repro.launch.roofline import HW

    hw = HW()
    none = compaction_cost(10_000, 400, seal_threshold=100,
                           compact_every=10**9, ssd_bw=hw.ssd_bw)
    assert none.compactions == 0 and none.write_amp == 1.0
    cc = compaction_cost(10_000, 400, seal_threshold=100, compact_every=10,
                         ssd_bw=hw.ssd_bw)
    assert cc.seals == 100 and cc.compactions == 10
    # merge-everything: rewrite_j = j * 10 * 100 rows -> amp = 1 + 5.5
    assert cc.write_amp == pytest.approx(6.5)
    assert cc.rewrite_s == pytest.approx(cc.bytes_rewritten / hw.ssd_bw)
    # more frequent compaction rewrites strictly more
    eager = compaction_cost(10_000, 400, seal_threshold=100,
                            compact_every=2, ssd_bw=hw.ssd_bw)
    assert eager.write_amp > cc.write_amp
    # churn shrinks the live set and therefore later rewrites
    churn = compaction_cost(10_000, 400, seal_threshold=100,
                            compact_every=10, delete_frac=0.3,
                            ssd_bw=hw.ssd_bw)
    assert churn.bytes_rewritten < cc.bytes_rewritten
    # quantized rows: 4x fewer bytes, same amplification factor
    u8 = compaction_cost(10_000, vector_row_bytes(128, "uint8"),
                         seal_threshold=100, compact_every=10,
                         ssd_bw=hw.ssd_bw)
    f32 = compaction_cost(10_000, vector_row_bytes(128, "float32"),
                          seal_threshold=100, compact_every=10,
                          ssd_bw=hw.ssd_bw)
    assert f32.bytes_rewritten == pytest.approx(4 * u8.bytes_rewritten)
    assert f32.write_amp == pytest.approx(u8.write_amp)
    with pytest.raises(ValueError, match="delete_frac"):
        compaction_cost(100, 4, 10, 1, delete_frac=1.0)


def test_cluster_fanout_cost_term():
    """The repro.cluster fan-out term: replicas scale storage QPS linearly;
    shards duplicate full-ef traversal so storage QPS does NOT scale with
    shard count alone; the router link binds once fan-out bytes beat it."""
    from repro.launch.costmodel import cluster_fanout_cost
    from repro.launch.roofline import HW

    hw = HW()
    base = cluster_fanout_cost(1, 1, dim=128, k=10, blocks_per_query=100,
                               block_size=4096, ssd_bw=hw.ssd_bw)
    # router bytes: N * (query scatter + top-k gather)
    assert base.router_bytes_q == 128 * 4 + 10 * 12
    assert base.flash_bytes_q == 100 * 4096
    assert base.storage_qps == pytest.approx(
        hw.ssd_bw / (100 * 4096))
    assert base.modeled_qps == min(base.router_qps, base.storage_qps)

    # replicas: aggregate SSDs grow, per-query flash work does not
    rep2 = cluster_fanout_cost(1, 2, dim=128, k=10, blocks_per_query=100,
                               block_size=4096, ssd_bw=hw.ssd_bw)
    assert rep2.storage_qps == pytest.approx(2 * base.storage_qps)
    assert rep2.router_bytes_q == base.router_bytes_q

    # shards: N SSDs but N full-ef traversals — storage QPS unchanged,
    # router bytes grow with N (the fan-out tax)
    sh4 = cluster_fanout_cost(4, 1, dim=128, k=10, blocks_per_query=100,
                              block_size=4096, ssd_bw=hw.ssd_bw)
    assert sh4.aggregate_ssd_bw == pytest.approx(4 * hw.ssd_bw)
    assert sh4.flash_bytes_q == pytest.approx(4 * base.flash_bytes_q)
    assert sh4.storage_qps == pytest.approx(base.storage_qps)
    assert sh4.router_bytes_q == pytest.approx(4 * base.router_bytes_q)

    # cache hits shrink flash traffic, raising the storage ceiling
    warm = cluster_fanout_cost(4, 1, dim=128, k=10, blocks_per_query=100,
                               block_size=4096, cache_hit_rate=0.9,
                               ssd_bw=hw.ssd_bw)
    assert warm.storage_qps == pytest.approx(10 * sh4.storage_qps)

    # a slow router link eventually binds
    bound = cluster_fanout_cost(64, 8, dim=128, k=10, blocks_per_query=1,
                                block_size=4096, cache_hit_rate=0.99,
                                ssd_bw=hw.ssd_bw, link_bw=1e6)
    assert bound.bound == "router"
    assert bound.modeled_qps == pytest.approx(bound.router_qps)

    with pytest.raises(ValueError, match="n_shards"):
        cluster_fanout_cost(0, 1, dim=128, k=10, blocks_per_query=1,
                            block_size=4096)
    with pytest.raises(ValueError, match="cache_hit_rate"):
        cluster_fanout_cost(1, 1, dim=128, k=10, blocks_per_query=1,
                            block_size=4096, cache_hit_rate=-0.1)


@pytest.mark.parametrize("arch", ["granite_3_8b", "qwen3_14b",
                                  "deepseek_v2_lite_16b", "jamba_v01_52b",
                                  "xlstm_350m", "musicgen_large"])
def test_param_count_matches_init(arch):
    cfg = reduced_config(arch)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    actual = sum(l.size for l in jax.tree.leaves(shapes))
    analytic = _count_params(cfg)
    assert abs(actual - analytic) / actual < 0.02, (actual, analytic)
