"""Jitted public wrappers around the Pallas kernels.

Handles arbitrary (unaligned) shapes by padding to block multiples — the
software analogue of the paper's database restructuring: callers never pay
for unaligned accesses because alignment is established once at the edge.

`interpret` defaults to True off-TPU (interpret mode executes the kernel
bodies exactly, so CPU correctness tests are real) and to False on TPU,
where the kernels compile through Mosaic. Every ANN kernel here does for
TPU v5e (tests/test_tpu_compile.py) except `fused_layer0`: on a TPU the
backends refuse `fused_hops > 1` at build instead of reaching it.
(`flash_attention`, the LM substrate's, is not compiled there.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.l2dist import l2dist_pallas
from repro.kernels.l2topk import l2topk_pallas
from repro.kernels.attention import flash_attention_pallas
from repro.kernels.qdist import (
    l2dist_q_pallas,
    l2topk_q_pallas,
    pq_adc_pallas,
    pq_topk_pallas,
)
from repro.kernels.topk import topk_pallas
from repro.kernels.traversal import fused_traversal_pallas

__all__ = ["l2dist", "topk", "l2topk", "l2dist_q", "l2topk_q",
           "pq_adc", "pq_topk",
           "flash_attention", "fused_layer0", "default_interpret"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_rows(a, to_rows, fill=0.0):
    pad = to_rows - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=fill)


def _block_d(d_p: int) -> int:
    """Largest K-block <= 512 that divides the 128-padded feature dim
    (d_p = 640 must not pick 512 — the kernels assert divisibility)."""
    b = min(d_p, 512)
    while d_p % b:
        b -= 128
    return b


@functools.partial(jax.jit, static_argnames=("block_q", "block_x", "interpret",
                                             "metric"))
def l2dist(queries, xs, *, block_q=128, block_x=512, interpret=None,
           metric="l2"):
    """Pairwise distance for arbitrary shapes; returns [Bq, Bx] f32.

    metric: "l2" (squared Euclidean), "ip" (-q.x), or "cosine" (1 - q.x over
    unit-norm inputs) — same registry as repro.api.metrics."""
    interpret = default_interpret() if interpret is None else interpret
    bq, d = queries.shape
    bx, _ = xs.shape
    bq_p, bx_p = _round_up(bq, block_q), _round_up(bx, block_x)
    d_p = _round_up(d, 128)
    q = jnp.pad(queries, ((0, bq_p - bq), (0, d_p - d)))
    x = jnp.pad(xs, ((0, bx_p - bx), (0, d_p - d)))
    out = l2dist_pallas(
        q, x, block_q=block_q, block_x=block_x, block_d=_block_d(d_p),
        interpret=interpret, metric=metric,
    )
    return out[:bq, :bx]


@functools.partial(jax.jit, static_argnames=("k", "block_b", "block_x", "interpret"))
def topk(x, k: int, *, block_b=8, block_x=1024, interpret=None):
    """Per-row k smallest of x [B, N] -> (values, ids) ascending."""
    interpret = default_interpret() if interpret is None else interpret
    b, n = x.shape
    b_p, n_p = _round_up(b, block_b), _round_up(n, block_x)
    xp = jnp.pad(x, ((0, b_p - b), (0, n_p - n)), constant_values=jnp.inf)
    v, i = topk_pallas(xp, k, block_b=block_b, block_x=block_x, interpret=interpret)
    return v[:b], i[:b]


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_x", "interpret"))
def l2topk(queries, xs, xsq=None, *, k=10, block_q=128, block_x=1024, interpret=None):
    """Fused exact k-NN: (dists [Bq, k], ids [Bq, k]); xs padding gets +inf."""
    interpret = default_interpret() if interpret is None else interpret
    bq, d = queries.shape
    bx, _ = xs.shape
    bq_p, bx_p = _round_up(bq, block_q), _round_up(bx, block_x)
    d_p = _round_up(d, 128)
    q = jnp.pad(queries, ((0, bq_p - bq), (0, d_p - d)))
    x = jnp.pad(xs, ((0, bx_p - bx), (0, d_p - d)))
    if xsq is None:
        xf = xs.astype(jnp.float32)
        xsq = jnp.einsum("bd,bd->b", xf, xf)
    xsq = jnp.pad(xsq, (0, bx_p - bx), constant_values=jnp.inf)
    v, i = l2topk_pallas(
        q, x, xsq=xsq, k=k, block_q=block_q, block_x=block_x, interpret=interpret
    )
    return v[:bq], i[:bq]


@functools.partial(jax.jit, static_argnames=("block_q", "block_x", "interpret",
                                             "out_scale"))
def l2dist_q(queries, xs, *, block_q=128, block_x=512, interpret=None,
             out_scale=1.0):
    """Integer-code pairwise squared L2 for arbitrary shapes -> [Bq, Bx] f32.

    queries/xs are uint8/int8 codes (IndexSpec.dtype path); out_scale is
    the quantizer's dist_scale (scale**2) for real-space output. Codes are
    zero-padded — pad lanes contribute 0 to every distance."""
    interpret = default_interpret() if interpret is None else interpret
    bq, d = queries.shape
    bx, _ = xs.shape
    bq_p, bx_p = _round_up(bq, block_q), _round_up(bx, block_x)
    d_p = _round_up(d, 128)
    q = jnp.pad(queries, ((0, bq_p - bq), (0, d_p - d)))
    x = jnp.pad(xs, ((0, bx_p - bx), (0, d_p - d)))
    out = l2dist_q_pallas(
        q, x, block_q=block_q, block_x=block_x, block_d=_block_d(d_p),
        interpret=interpret, out_scale=out_scale,
    )
    return out[:bq, :bx]


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_x",
                                             "interpret", "out_scale"))
def l2topk_q(queries, xs, xsq=None, *, k=10, block_q=128, block_x=1024,
             interpret=None, out_scale=1.0):
    """Fused integer k-NN over codes: (dists [Bq, k], ids [Bq, k]).

    The streamed database stays uint8/int8 end to end (4x less traffic
    than the f32 `l2topk`); xs row padding gets +inf via xsq."""
    interpret = default_interpret() if interpret is None else interpret
    bq, d = queries.shape
    bx, _ = xs.shape
    bq_p, bx_p = _round_up(bq, block_q), _round_up(bx, block_x)
    d_p = _round_up(d, 128)
    q = jnp.pad(queries, ((0, bq_p - bq), (0, d_p - d)))
    x = jnp.pad(xs, ((0, bx_p - bx), (0, d_p - d)))
    if xsq is None:
        xf = xs.astype(jnp.float32)
        xsq = jnp.einsum("bd,bd->b", xf, xf)
    xsq = jnp.pad(xsq, (0, bx_p - bx), constant_values=jnp.inf)
    v, i = l2topk_q_pallas(
        q, x, xsq=xsq, k=k, block_q=block_q, block_x=block_x,
        interpret=interpret, out_scale=out_scale,
    )
    return v[:bq], i[:bq]


@functools.partial(jax.jit, static_argnames=("block_q", "block_x",
                                             "interpret"))
def pq_adc(luts, codes, xpad=None, *, block_q=8, block_x=512,
           interpret=None):
    """PQ asymmetric distances for arbitrary shapes -> [Bq, Bx] f32.

    luts are the per-query [M, 256] tables (optim.compression.build_pq_lut);
    codes are [Bx, M] uint8 rows. Optional xpad carries +inf markers for
    database padding rows (padding added here also gets +inf)."""
    interpret = default_interpret() if interpret is None else interpret
    bq = luts.shape[0]
    bx = codes.shape[0]
    bq_p, bx_p = _round_up(bq, block_q), _round_up(bx, block_x)
    lp = _pad_rows(luts, bq_p)
    cp = _pad_rows(codes, bx_p)
    if xpad is None:
        xpad = jnp.zeros((bx,), jnp.float32)
    xp = jnp.pad(xpad, (0, bx_p - bx), constant_values=jnp.inf)
    out = pq_adc_pallas(lp, cp, xp, block_q=block_q, block_x=block_x,
                        interpret=interpret)
    return out[:bq, :bx]


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_x",
                                             "interpret"))
def pq_topk(luts, codes, xpad=None, *, k=10, block_q=8, block_x=1024,
            interpret=None):
    """Fused PQ k-NN over codes: (dists [Bq, k] ascending, ids [Bq, k]).

    The streamed database stays M bytes/row end to end (16x less traffic
    than uint8 at M=8/d=128); padding rows are masked out via +inf."""
    interpret = default_interpret() if interpret is None else interpret
    bq = luts.shape[0]
    bx = codes.shape[0]
    bq_p, bx_p = _round_up(bq, block_q), _round_up(bx, block_x)
    lp = _pad_rows(luts, bq_p)
    cp = _pad_rows(codes, bx_p)
    if xpad is None:
        xpad = jnp.zeros((bx,), jnp.float32)
    xp = jnp.pad(xpad, (0, bx_p - bx), constant_values=jnp.inf)
    v, i = pq_topk_pallas(lp, cp, xp, k=k, block_q=block_q,
                          block_x=block_x, interpret=interpret)
    return v[:bq], i[:bq]


def fused_layer0(vectors, sqnorms, l0_nbrs, queries, qsq,
                 cand_d, cand_i, fin_d, fin_i, visited, hops, calcs, *,
                 fused_hops: int, max_hops: int, metric="l2",
                 interpret=None):
    """One H-hop superstep of the fused layer-0 traversal over the whole
    query batch (kernels/traversal.py — the paper's Fig. 6 engine).

    Unlike the other wrappers, no padding happens here: the restructured
    DB's tables (hnsw_graph.restructure) are already lane-aligned, and the
    beam-state shapes come from SearchParams.resolve. The wrapper exists
    for the interpret dispatch (the CPU runs the kernel body exactly; Mosaic
    does not lower this kernel yet, so TPU backends refuse fused_hops > 1
    at build) and is called from inside batch_search's jit, so it does not
    re-jit."""
    interpret = default_interpret() if interpret is None else interpret
    return fused_traversal_pallas(
        vectors, sqnorms, l0_nbrs, queries, qsq,
        cand_d, cand_i, fin_d, fin_i, visited, hops, calcs,
        fused_hops=fused_hops, max_hops=max_hops, metric=metric,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=256, block_k=256,
                    interpret=None):
    """Causal flash attention for arbitrary [BH, T, hd]; pads T/S to blocks."""
    interpret = default_interpret() if interpret is None else interpret
    bh, t, hd = q.shape
    s = k.shape[1]
    bq, bk = min(block_q, max(t, 8)), min(block_k, max(s, 8))
    t_p, s_p = _round_up(t, bq), _round_up(s, bk)
    qp = jnp.pad(q, ((0, 0), (0, t_p - t), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, s_p - s), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, s_p - s), (0, 0)))
    out = flash_attention_pallas(qp, kp, vp, causal=causal, block_q=bq,
                                 block_k=bk, interpret=interpret, s_valid=s)
    return out[:, :t]
