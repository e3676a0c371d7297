"""Pallas TPU kernel: blocked pairwise squared-L2 distance.

TPU adaptation of the paper's distance calculator (§5.2.5): the FPGA uses
8 units x 16 PEs + adder trees to consume one 128-dim vector pair per cycle;
the MXU-native formulation is

    D2[i, j] = ||q_i||^2 + ||x_j||^2 - 2 * Q @ X^T

i.e. one 128x128 systolic matmul per (block_q x block_x x block_d) tile with
the norm terms added on the first K-step. Blocks are sized so a
(block_q x block_d) query tile, a (block_x x block_d) database tile and the
f32 accumulator tile all fit VMEM, and every matmul dim is a multiple of the
128-lane / 8-sublane hardware tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["l2dist_pallas", "as_f32", "norm_specs"]


def as_f32(x):
    """In-kernel cast to f32. Integer codes go through int32: Mosaic has no
    direct uint8 -> f32 cast, and the detour is exact for 8-bit values."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def norm_specs(block_q, block_x, grid_rank):
    """BlockSpecs for the squared-norm operands, passed 2-D as qsq [Bq, 1]
    and xsq [1, Bx]: a 1-D f32 block does not get the (8, 128) tiling
    Mosaic requires. The kernel adds qsq_ref[...] + xsq_ref[...] and gets
    the [block_q, block_x] tile by broadcasting."""
    if grid_rank == 3:
        return [pl.BlockSpec((block_q, 1), lambda i, j, k: (i, 0)),
                pl.BlockSpec((1, block_x), lambda i, j, k: (0, j))]
    return [pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_x), lambda i, j: (0, j))]


def _kernel(qsq_ref, xsq_ref, q_ref, x_ref, out_ref, *, metric):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        # metric-specific constant term; the dot-product accumulation below
        # is shared. l2: ||q||^2 + ||x||^2 - 2 q.x; ip: -q.x; cosine
        # (unit-norm inputs): 1 - q.x.
        if metric == "l2":
            out_ref[...] = qsq_ref[...] + xsq_ref[...]
        elif metric == "cosine":
            out_ref[...] = jnp.ones_like(out_ref[...])
        else:
            out_ref[...] = jnp.zeros_like(out_ref[...])

    q = as_f32(q_ref[...])
    x = as_f32(x_ref[...])
    scale = -2.0 if metric == "l2" else -1.0
    # HIGHEST: the MXU's default single bf16 pass loses f32 digits on TPU
    out_ref[...] += scale * jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_x", "block_d", "interpret", "metric"),
)
def l2dist_pallas(
    queries,          # [Bq, D]
    xs,               # [Bx, D]
    qsq=None,         # [Bq] optional precomputed ||q||^2
    xsq=None,         # [Bx] optional precomputed ||x||^2 (+inf marks padding)
    *,
    block_q: int = 128,
    block_x: int = 512,
    block_d: int = 128,
    interpret: bool = True,
    metric: str = "l2",
):
    """Returns D[Bq, Bx] float32 under `metric` (l2 / ip / cosine; cosine
    assumes unit-norm inputs). Dims must divide by the block sizes
    (ops.l2dist pads arbitrary shapes before calling this)."""
    bq, d = queries.shape
    bx, _ = xs.shape
    assert bq % block_q == 0 and bx % block_x == 0 and d % block_d == 0
    if qsq is None:
        qsq = jnp.einsum("bd,bd->b", queries.astype(jnp.float32), queries.astype(jnp.float32))
    if xsq is None:
        xsq = jnp.einsum("bd,bd->b", xs.astype(jnp.float32), xs.astype(jnp.float32))
    grid = (bq // block_q, bx // block_x, d // block_d)
    return pl.pallas_call(
        functools.partial(_kernel, metric=metric),
        grid=grid,
        in_specs=norm_specs(block_q, block_x, 3) + [
            pl.BlockSpec((block_q, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_x, block_d), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_q, block_x), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bq, bx), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qsq[:, None], xsq[None, :], queries, xs)
