"""Pallas TPU kernels: integer (uint8/int8) L2 distance, plain and fused.

The paper's distance hardware consumes *integer* vectors — SIFT1B rows are
uint8, and both the SmartSSD RTL (§5.2.5) and the NDSEARCH/Proxima
near-data engines build low-precision distance units because 1 byte/dim is
what matches NAND bandwidth. These kernels are the TPU analogue of that
operating point:

  * blocks stream the *codes* (1 byte/lane — a quarter of the f32 HBM and
    VMEM traffic of `l2dist.py`),
  * each tile is cast to f32 on-core and hits the MXU with f32
    accumulation, which is EXACT for 8-bit codes up to ~256 dims: every
    partial dot product is an integer < 2^24, below the f32 mantissa, and
    8-bit values pass the MXU's single bf16 pass unrounded, so the default
    precision suffices (the f32 kernels need HIGHEST);
  * `out_scale` (the quantizer's `scale**2`) converts code-space squared
    L2 back to real units inside the kernel, so callers never see codes.

`l2dist_q_pallas` is the blocked distance matrix; `l2topk_q_pallas` fuses
the running per-row top-k (same "never spill the matrix" argument as
`l2topk.py` — now with the streamed database 4x smaller again).
References live in `kernels/ref.py` (`l2dist_q_ref` / `l2topk_q_ref`);
`kernels/ops.py` wraps both with padding for arbitrary shapes.

`pq_adc_pallas` / `pq_topk_pallas` are the product-quantization analogue
(dtype="pq"): the database streams M uint8 codes per row (16x less than
uint8 at M=8/d=128), the per-query [M, 256] LUT lives in VMEM, and the
inner loop is a table-gather + accumulate over the codes — one add per
subspace, in subspace order, which the numpy refs reproduce exactly
(bitwise parity). The LUT itself is built once per query on-device by
`optim.compression.build_pq_lut` and passed in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.l2dist import as_f32, norm_specs
from repro.kernels.topk import _fold_block

__all__ = ["l2dist_q_pallas", "l2topk_q_pallas",
           "pq_adc_pallas", "pq_topk_pallas"]


def _code_sqnorms(x):
    xf = x.astype(jnp.float32)
    return jnp.einsum("bd,bd->b", xf, xf)


def _dist_kernel(qsq_ref, xsq_ref, q_ref, x_ref, out_ref, *, out_scale):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = qsq_ref[...] + xsq_ref[...]

    # codes live in VMEM at 1 byte/lane; the cast to f32 happens on-core
    q = as_f32(q_ref[...])
    x = as_f32(x_ref[...])
    out_ref[...] += -2.0 * jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        out_ref[...] = jnp.maximum(out_ref[...], 0.0) * out_scale


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_x", "block_d", "interpret",
                     "out_scale"),
)
def l2dist_q_pallas(
    queries,          # [Bq, D] uint8/int8 codes (or code-valued floats)
    xs,               # [Bx, D] uint8/int8 codes
    qsq=None,         # [Bq] optional precomputed code ||q||^2 (f32)
    xsq=None,         # [Bx] optional code ||x||^2 (+inf marks padding)
    *,
    block_q: int = 128,
    block_x: int = 512,
    block_d: int = 128,
    interpret: bool = True,
    out_scale: float = 1.0,
):
    """Returns D2[Bq, Bx] float32 = out_scale * ||q - x||^2 over the codes.

    Dims must divide by the block sizes (ops.l2dist_q pads arbitrary
    shapes). Pass out_scale = quantizer.dist_scale for real-space output.
    """
    bq, d = queries.shape
    bx, _ = xs.shape
    assert bq % block_q == 0 and bx % block_x == 0 and d % block_d == 0
    if qsq is None:
        qsq = _code_sqnorms(queries)
    if xsq is None:
        xsq = _code_sqnorms(xs)
    grid = (bq // block_q, bx // block_x, d // block_d)
    return pl.pallas_call(
        functools.partial(_dist_kernel, out_scale=out_scale),
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


def _topk_kernel(k: int, block_x: int, out_scale: float):
    def _kernel(qsq_ref, xsq_ref, q_ref, x_ref, out_v_ref, out_i_ref,
                run_v, run_i):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            run_v[...] = jnp.full_like(run_v, jnp.inf)
            run_i[...] = jnp.full_like(run_i, -1)

        q = as_f32(q_ref[...])                              # [bq, D] codes
        x = as_f32(x_ref[...])                              # [bx, D] codes
        d2 = qsq_ref[...] + xsq_ref[...] - 2.0 * jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        d2 = jnp.maximum(d2, 0.0)                           # +inf pad survives
        cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) + j * block_x
        _fold_block(run_v, run_i, d2, cols, k)

        @pl.when(j == pl.num_programs(1) - 1)
        def _flush():
            # monotone rescale AFTER selection: code-space order == real order
            out_v_ref[...] = run_v[...] * out_scale
            out_i_ref[...] = run_i[...]

    return _kernel


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_x", "interpret", "out_scale"),
)
def l2topk_q_pallas(
    queries,              # [Bq, D] codes
    xs,                   # [Bx, D] codes
    qsq=None,
    xsq=None,             # +inf marks database padding rows
    *,
    k: int = 10,
    block_q: int = 128,
    block_x: int = 1024,
    interpret: bool = True,
    out_scale: float = 1.0,
):
    """Fused integer k-NN: (dists [Bq, k] ascending * out_scale, ids)."""
    bq, d = queries.shape
    bx, _ = xs.shape
    assert bq % block_q == 0 and bx % block_x == 0
    if qsq is None:
        qsq = _code_sqnorms(queries)
    if xsq is None:
        xsq = _code_sqnorms(xs)
    grid = (bq // block_q, bx // block_x)
    return pl.pallas_call(
        _topk_kernel(k, block_x, out_scale),
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


# ---------------------------------------------------------------------------
# Product quantization: asymmetric distance (ADC) over uint8 codes
# ---------------------------------------------------------------------------


def _pq_block_dists(lut, codes, xpad):
    """[bq, m, 256] LUT x [bx, m] codes -> [bq, bx] ADC distances.

    One table lookup + one add PER SUBSPACE, in subspace order m=0..M-1 —
    the PQ extension of core.search's mul+sum reduction-order rule. The
    numpy refs accumulate in the same order, so kernel == ref bitwise;
    every engine path gathers from the same `build_pq_lut` tables, so
    changing this order (tree reduction, einsum) breaks cross-backend
    parity. The lookup is a product with the codes' one-hot rows at
    HIGHEST precision (Mosaic has no lane gather): each output sums one
    table entry times 1 and 255 zeros, so it equals the entry exactly.
    `xpad` is [1, bx], +inf on database padding rows (inf + finite stays
    inf, the same marker trick as the xsq pad in the integer kernels).
    """
    m = lut.shape[1]
    codes = codes.astype(jnp.int32)
    entry = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], 256), 1)
    acc = jnp.zeros((lut.shape[0], codes.shape[0]), jnp.float32)
    acc = acc + xpad.astype(jnp.float32)
    for mi in range(m):
        # lut[:, mi, :] is [bq, 256]; codes[:, mi] one-hot is [bx, 256]
        onehot = (codes[:, mi:mi + 1] == entry).astype(jnp.float32)
        acc = acc + jax.lax.dot_general(
            lut[:, mi, :], onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return acc


def _pq_adc_kernel(lut_ref, codes_ref, xpad_ref, out_ref):
    out_ref[...] = _pq_block_dists(lut_ref[...], codes_ref[...],
                                   xpad_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_x", "interpret"))
def pq_adc_pallas(
    luts,             # [Bq, M, 256] f32 per-query LUTs (build_pq_lut)
    codes,            # [Bx, M] uint8 PQ codes
    xpad=None,        # [Bx] f32, +inf marks database padding rows
    *,
    block_q: int = 8,
    block_x: int = 512,
    interpret: bool = True,
):
    """ADC distance matrix D2[Bq, Bx] = sum_m lut[q, m, codes[x, m]].

    The streamed database is M bytes/row; each program holds block_q LUTs
    (block_q * M * 1KB of VMEM) and a block_x x M code tile. Dims must
    divide the block sizes (ops.pq_adc pads arbitrary shapes). The code
    tile's last dim is M, the whole row: the database streams at M bytes
    per row, which is the point of PQ.
    """
    bq, m, k256 = luts.shape
    bx = codes.shape[0]
    assert bq % block_q == 0 and bx % block_x == 0 and k256 == 256
    if xpad is None:
        xpad = jnp.zeros((bx,), jnp.float32)
    grid = (bq // block_q, bx // block_x)
    return pl.pallas_call(
        _pq_adc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, m, 256), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_x, m), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_x), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_x), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bq, bx), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(luts, codes, xpad[None, :])


def _pq_topk_kernel(k: int, block_x: int):
    def _kernel(lut_ref, codes_ref, xpad_ref, out_v_ref, out_i_ref,
                run_v, run_i):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            run_v[...] = jnp.full_like(run_v, jnp.inf)
            run_i[...] = jnp.full_like(run_i, -1)

        d2 = _pq_block_dists(lut_ref[...], codes_ref[...], xpad_ref[...])
        cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) + j * block_x
        _fold_block(run_v, run_i, d2, cols, k)

        @pl.when(j == pl.num_programs(1) - 1)
        def _flush():
            out_v_ref[...] = run_v[...]
            out_i_ref[...] = run_i[...]

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_x", "interpret"))
def pq_topk_pallas(
    luts,                 # [Bq, M, 256] f32 per-query LUTs
    codes,                # [Bx, M] uint8 PQ codes
    xpad=None,            # [Bx] f32, +inf marks padding rows
    *,
    k: int = 10,
    block_q: int = 8,
    block_x: int = 1024,
    interpret: bool = True,
):
    """Fused PQ k-NN: (dists [Bq, k] ascending, ids). The top-k never
    leaves VMEM; the database streams at M bytes/row."""
    bq, m, k256 = luts.shape
    bx = codes.shape[0]
    assert bq % block_q == 0 and bx % block_x == 0 and k256 == 256
    if xpad is None:
        xpad = jnp.zeros((bx,), jnp.float32)
    grid = (bq // block_q, bx // block_x)
    return pl.pallas_call(
        _pq_topk_kernel(k, block_x),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, m, 256), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_x, m), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_x), lambda i, j: (0, j)),
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
    )(luts, codes, xpad[None, :])
