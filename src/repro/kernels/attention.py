"""Pallas TPU kernel: causal flash attention (the LM substrate's hotspot).

Target layout: one (batch*head, q-block) program per grid cell, streaming KV
blocks through VMEM with the running-softmax carried in scratch — the same
schedule as models/layers.blockwise_attn (its jnp twin / oracle), but with
explicit BlockSpec tiling so on TPU the scores tile lives in VMEM and each
(bq x hd) @ (hd x bk) product maps onto the MXU.

Causal block skipping is structural here: the kernel masks per-element and
relies on the grid executing kj <= qi blocks usefully; fully-future blocks
contribute nothing and are skipped with pl.when (no MXU issue at all) —
the Pallas rendition of the §Perf `skip_masked_blocks` lever.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _make_kernel(bq: int, bk: int, causal: bool, scale: float,
                 s_valid: int):
    def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        qi = pl.program_id(1)
        kj = pl.program_id(2)

        @pl.when(kj == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        live = (not causal) or (kj * bk <= qi * bq + bq - 1)

        @pl.when(live)
        def _compute():
            q = q_ref[0].astype(jnp.float32)            # [bq, hd]
            k = k_ref[0].astype(jnp.float32)            # [bk, hd]
            v = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            col = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < s_valid, s, NEG_INF)   # key padding
            if causal:
                row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                s = jnp.where(col <= row, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        @pl.when(kj == pl.num_programs(2) - 1)
        def _flush():
            o_ref[0] = (acc_scr[...] /
                        jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "s_valid"))
def flash_attention_pallas(
    q,                     # [BH, T, hd]  (batch*heads flattened)
    k,                     # [BH, S, hd]
    v,                     # [BH, S, hd]
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = True,
    s_valid: int | None = None,
):
    """Returns [BH, T, hd]. T % block_q == 0 and S % block_k == 0 (the ops
    wrapper pads; s_valid masks padded key columns)."""
    bh, t, hd = q.shape
    _, s, _ = k.shape
    assert t % block_q == 0 and s % block_k == 0
    scale = 1.0 / math.sqrt(hd)
    grid = (bh, t // block_q, s // block_k)
    return pl.pallas_call(
        _make_kernel(block_q, block_k, causal, scale,
                     s if s_valid is None else s_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
