"""Pallas TPU kernel: FUSED pairwise-L2 + top-k ("never spill the matrix").

This is the paper's central memory lesson (§5.2/§6.2: the RTL design wins by
*minimizing external memory accesses*) applied to the brute-force/stage-2
path: computing D2[B, N] to HBM and re-reading it for top-k costs
2*B*N*4 bytes of traffic that the fusion eliminates entirely. Each grid step
computes one (block_q x block_x) distance tile in VMEM from a single MXU
matmul and immediately folds it into the per-row running top-k scratch.

The arithmetic-intensity argument: for D=128, k=10 the unfused pipeline moves
~8 bytes/FLOP/lane of distance-matrix traffic; fused, the only HBM traffic is
the streamed database (read once) and the [B, k] result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.l2dist import as_f32, norm_specs
from repro.kernels.topk import _fold_block

__all__ = ["l2topk_pallas"]


def _make_kernel(k: int, block_x: int):
    def _kernel(qsq_ref, xsq_ref, q_ref, x_ref, out_v_ref, out_i_ref, run_v, run_i):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            run_v[...] = jnp.full_like(run_v, jnp.inf)
            run_i[...] = jnp.full_like(run_i, -1)

        q = as_f32(q_ref[...])                              # [bq, D]
        x = as_f32(x_ref[...])                              # [bx, D]
        # HIGHEST: the MXU's default single bf16 pass loses f32 digits
        d2 = qsq_ref[...] + xsq_ref[...] - 2.0 * jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        d2 = jnp.maximum(d2, 0.0)                           # +inf padding survives
        cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) + j * block_x
        _fold_block(run_v, run_i, d2, cols, k)

        @pl.when(j == pl.num_programs(1) - 1)
        def _flush():
            out_v_ref[...] = run_v[...]
            out_i_ref[...] = run_i[...]

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_x", "interpret")
)
def l2topk_pallas(
    queries,              # [Bq, D]
    xs,                   # [Bx, D]
    qsq=None,
    xsq=None,             # +inf marks database padding rows
    *,
    k: int = 10,
    block_q: int = 128,
    block_x: int = 1024,
    interpret: bool = True,
):
    """Returns (dists [Bq, k] ascending, ids [Bq, k] int32) — exact top-k."""
    bq, d = queries.shape
    bx, _ = xs.shape
    assert bq % block_q == 0 and bx % block_x == 0
    if qsq is None:
        qsq = jnp.einsum("bd,bd->b", queries.astype(jnp.float32), queries.astype(jnp.float32))
    if xsq is None:
        xsq = jnp.einsum("bd,bd->b", xs.astype(jnp.float32), xs.astype(jnp.float32))
    grid = (bq // block_q, bx // block_x)
    return pl.pallas_call(
        _make_kernel(k, block_x),
        grid=grid,
        in_specs=norm_specs(block_q, block_x, 2) + [
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_x, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bq, k), jnp.float32),
            jax.ShapeDtypeStruct((bq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qsq[:, None], xsq[None, :], queries, xs)
