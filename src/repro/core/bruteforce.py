"""Exact brute-force top-K baseline (paper Fig. 9 comparison).

The paper sizes a hypothetical brute-force FPGA design (1968 DSPs, 200 MHz ->
3 GV/s, 3 QPS on SIFT1B) against HNSW. Here the baseline is real: a blocked
scan over the database with a running top-k merge, so benchmarks can report
both QPS and the "number of vector reads" on identical footing.

The chunked scan keeps the distance matrix out of HBM-resident temporaries —
only [B, chunk] tiles exist at once. kernels/l2topk.py is the Pallas-fused
version of exactly this loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.search import merge_sorted, metric_distance

__all__ = ["bruteforce_topk"]


@functools.partial(jax.jit, static_argnames=("k", "chunk", "metric"))
def bruteforce_topk(vectors, sqnorms, queries, k: int = 10, chunk: int = 4096,
                    metric: str = "l2"):
    """Exact k smallest ids/distances for each query under `metric`.

    vectors: [N, D] (N % chunk == 0 after padding; pad rows have sqnorm=+inf —
             the +inf sqnorm is the pad marker for every metric). May hold
             uint8/int8 codes (quantized path): each chunk is cast to f32
             at the matmul, so distances are exact code-space values.
             f32 rows take the matmul at HIGHEST precision (a TPU's default
             rounds f32 operands to bf16); 8-bit codes and the code-valued
             queries they are searched with are exact in bf16 already.
    queries: [B, D]
    returns: ids [B, k] int32, dists [B, k] float32
    """
    n, d = vectors.shape
    b = queries.shape[0]
    assert n % chunk == 0, "pad the database to a multiple of `chunk`"
    queries = queries.astype(jnp.float32)
    qsq = jnp.einsum("bd,bd->b", queries, queries)
    precision = (jax.lax.Precision.DEFAULT
                 if jnp.issubdtype(vectors.dtype, jnp.integer)
                 else jax.lax.Precision.HIGHEST)

    vecs = vectors.reshape(n // chunk, chunk, d)
    sqs = sqnorms.reshape(n // chunk, chunk)

    def step(carry, xs):
        run_d, run_i = carry               # [B, k] sorted ascending
        v, s, off = xs
        dot = jnp.matmul(queries, v.T.astype(jnp.float32),
                         precision=precision)
        d2 = metric_distance(metric, dot, s[None, :], qsq[:, None])
        d2 = jnp.where(jnp.isinf(s)[None, :], jnp.inf, d2)
        cd, ci = jax.lax.top_k(-d2, k)     # [B, k] largest of -d2 == smallest d2
        cd = -cd
        cids = off + ci.astype(jnp.int32)
        md, mi = jax.vmap(merge_sorted)(run_d, run_i, cd, cids)
        return (md[:, :k], mi[:, :k]), None

    init = (jnp.full((b, k), jnp.inf), jnp.full((b, k), -1, jnp.int32))
    offs = (jnp.arange(n // chunk, dtype=jnp.int32) * chunk)
    (fd, fi), _ = jax.lax.scan(step, init, (vecs, sqs, offs))
    return fi, fd
