"""Pallas TPU kernel: per-row k-smallest (value, index) of a matrix.

The paper keeps the final list sorted with a *parallel insertion sort*: all
list entries are compared against the incoming distance at once and the
insert rank is the popcount of the comparison bit-vector (§5.2.6, Fig. 7).
The TPU rendition below streams column blocks through VMEM and maintains a
running sorted top-k per row in scratch; the running list and each block
are reduced together with k vectorized min/mask passes (a k-step selection
network — every comparison of the paper's bit-vector happens lane-parallel
on the VPU).

Also reused by MoE routing (top-k expert choice = 1-hop nearest-centroid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["topk_pallas"]


def _select_k(parts, k):
    """k-step selection over the lane concatenation of `parts`, a list of
    (vals [rows, n_i], ids [rows, n_i]) pairs; returns ([rows, k] ascending
    values, ids). Ties go to the earlier lane, as argmin would pick.

    Each step takes a row min, then the first lane holding it (a min over
    an iota mask) and that lane's id (a one-hot masked sum): reductions and
    selects only, no gather or scatter, which is what Mosaic lowers."""
    rows = parts[0][0].shape[0]
    offs = [0]
    for v, _ in parts:
        offs.append(offs[-1] + v.shape[1])
    lanes = [jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) + o
             for (v, _), o in zip(parts, offs)]
    kcol = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)

    def step(t, carry):
        vals, out_v, out_i = carry
        m = functools.reduce(jnp.minimum, [
            jnp.min(v, axis=1, keepdims=True) for v in vals])
        pos = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(v == m, ln, offs[-1]), axis=1, keepdims=True)
            for v, ln in zip(vals, lanes)])
        hits = [ln == pos for ln in lanes]
        sel = functools.reduce(jnp.add, [
            jnp.sum(jnp.where(h, i, 0), axis=1, keepdims=True)
            for h, (_, i) in zip(hits, parts)])
        out_v = jnp.where(kcol == t, m, out_v)
        out_i = jnp.where(kcol == t, sel, out_i)
        vals = [jnp.where(h, jnp.inf, v) for h, v in zip(hits, vals)]
        return vals, out_v, out_i

    out_v = jnp.zeros((rows, k), jnp.float32)
    out_i = jnp.zeros((rows, k), jnp.int32)
    _, out_v, out_i = jax.lax.fori_loop(
        0, k, step, ([v for v, _ in parts], out_v, out_i))
    return out_v, out_i


def _fold_block(run_v, run_i, vals, ids, k):
    """Fold one block of (vals, ids) into the running top-k scratch refs.
    Selecting over [running list, block] at once equals the block's top-k
    merged into the running list, ties keeping the running entry first."""
    run_v[...], run_i[...] = _select_k(
        [(run_v[...], run_i[...]), (vals, ids)], k)


def _make_kernel(k: int, block_x: int):
    def _kernel(x_ref, out_v_ref, out_i_ref, run_v, run_i):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            run_v[...] = jnp.full_like(run_v, jnp.inf)
            run_i[...] = jnp.full_like(run_i, -1)

        x = x_ref[...].astype(jnp.float32)                 # [block_b, block_x]
        cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * block_x
        _fold_block(run_v, run_i, x, cols, k)

        @pl.when(j == pl.num_programs(1) - 1)
        def _flush():
            out_v_ref[...] = run_v[...]
            out_i_ref[...] = run_i[...]

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("k", "block_b", "block_x", "interpret")
)
def topk_pallas(
    x,                   # [B, N]; +inf marks padding
    k: int,
    *,
    block_b: int = 8,
    block_x: int = 1024,
    interpret: bool = True,
):
    """Returns (values [B, k] ascending, ids [B, k] int32)."""
    b, n = x.shape
    assert b % block_b == 0 and n % block_x == 0
    grid = (b // block_b, n // block_x)
    return pl.pallas_call(
        _make_kernel(k, block_x),
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, block_x), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, k), jnp.float32),
            pltpu.VMEM((block_b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x)
