"""Compile the served path's kernels and the partitioned search for a TPU
v5e chip that is described, not attached (interpret=False): what Mosaic or
XLA:TPU would refuse on the chip fails here. Nothing runs, so these say
nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under several pytest
workers only the worker given this file should try.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hnsw_graph import DeviceDB, HNSWConfig
from repro.core.partitioned import PartitionedDB, search_partitioned
from repro.core.search import SearchParams, bitmap_words
from repro.kernels import ops
from repro.kernels.traversal import fused_traversal_pallas

Q, D, N, K = 256, 128, 262144, 10      # queries, dims, rows, top-k
PQ_M = 16
EF, M0 = 40, 32                        # paper operating point; 2 * M


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent cache off: its entries for a
    described device could not be read back here anyway."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


KERNELS = {
    "l2dist_f32": (functools.partial(ops.l2dist, interpret=False),
                   [((Q, D), jnp.float32), ((N, D), jnp.float32)]),
    "l2topk_f32": (functools.partial(ops.l2topk, k=K, interpret=False),
                   [((Q, D), jnp.float32), ((N, D), jnp.float32)]),
    "l2dist_q_uint8": (functools.partial(ops.l2dist_q, interpret=False),
                       [((Q, D), jnp.uint8), ((N, D), jnp.uint8)]),
    "l2topk_q_uint8": (functools.partial(ops.l2topk_q, k=K,
                                         interpret=False),
                       [((Q, D), jnp.uint8), ((N, D), jnp.uint8)]),
    "topk": (functools.partial(ops.topk, k=K, interpret=False),
             [((Q, N), jnp.float32)]),
    "pq_adc": (functools.partial(ops.pq_adc, interpret=False),
               [((Q, PQ_M, 256), jnp.float32), ((N, PQ_M), jnp.uint8)]),
    "pq_topk": (functools.partial(ops.pq_topk, k=K, interpret=False),
                [((Q, PQ_M, 256), jnp.float32), ((N, PQ_M), jnp.uint8)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, *(_spec(one_chip, s, d) for s, d in shapes))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, reason=(
    "fused_traversal_pallas does not lower through Mosaic: (1, w) lane "
    "blocks, a vector-to-scalar DMA index and a dynamic gather/scatter on "
    "the visited bitmap. On a TPU, IndexSpec(fused_hops>1) is refused at "
    "build instead."))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint8])
def test_fused_traversal_compiles_for_v5e(one_chip, dtype):
    C, W = EF + M0, bitmap_words(N)
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(fused_traversal_pallas, fused_hops=4,
                           max_hops=4 * EF + 16, interpret=False)
    compiled = _compile(
        fn, s((N, D), dtype), s((N,), jnp.float32), s((N, M0), jnp.int32),
        s((Q, D), jnp.float32), s((Q,), jnp.float32),
        s((Q, C), jnp.float32), s((Q, C), jnp.int32),
        s((Q, EF), jnp.float32), s((Q, EF), jnp.int32),
        s((Q, W), jnp.uint32), s((Q,), jnp.int32), s((Q,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_search_partitioned_compiles_for_v5e(one_chip):
    """The default served program: 4 uint8 partitions of 262,144 rows, 256
    queries, ef=40, hop-stepped layer 0. It must also fit one chip's HBM."""
    P, cfg = 4, HNSWConfig()
    up_rows = N // cfg.M
    s = functools.partial(_spec, one_chip)
    db = DeviceDB(
        vectors=s((P, N, D), jnp.uint8),
        sqnorms=s((P, N), jnp.float32),
        l0_nbrs=s((P, N, cfg.maxM0), jnp.int32),
        up_nbrs=s((P, cfg.max_level_cap - 1, up_rows, cfg.maxM), jnp.int32),
        up_ptr=s((P, N), jnp.int32),
        levels=s((P, N), jnp.int32),
        gids=s((P, N), jnp.int32),
        entry=s((P,), jnp.int32),
        max_level=s((P,), jnp.int32),
        n_valid=s((P,), jnp.int32))
    pdb = PartitionedDB(db=db, num_partitions=P, dim=D)
    compiled = search_partitioned.lower(
        pdb, s((Q, D), jnp.float32), SearchParams(ef=EF, k=K)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, used
