"""Period-structured decoder stack covering all 10 assigned architectures.

A model is `prefix_pattern` (irregular leading layers, e.g. DeepSeek's dense
layer 0) followed by `num_periods` repetitions of `pattern` (e.g. Jamba's
[mamba, mamba, mamba, mamba, attn, mamba, mamba, mamba] with alternating
MoE). The repeated period is executed under `jax.lax.scan` with stacked
params — compile time and HLO size stay O(period), not O(layers), which is
what keeps 80 dry-run compiles tractable and is also the right shape for
FSDP all-gather prefetch overlap.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import shard_ctx
from repro.models import ssm as S

__all__ = ["LayerSpec", "ModelConfig", "init_params", "forward", "init_cache",
           "compute_logits", "chunked_xent"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = "attn"        # attn | mla | mamba | mlstm | slstm
    ffn: str = "glu"          # glu | relu2 | moe | none
    window: int = 0           # sliding-window size for kind == attn


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    pattern: tuple[LayerSpec, ...]
    num_periods: int
    prefix_pattern: tuple[LayerSpec, ...] = ()
    qk_norm: bool = False
    rope_theta: float = 1e4
    act: str = "silu"
    mla: Any = None           # layers.MLAConfig
    moe: Any = None           # moe.MoEConfig
    mamba: Any = None         # ssm.MambaConfig
    xlstm: Any = None         # ssm.XLSTMConfig
    embed_inputs: bool = True
    num_output_heads: int = 1
    prefix_lm: bool = False   # bidirectional prefix (paligemma)
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    param_dtype: Any = jnp.float32
    remat: bool = True
    loss_chunk: int = 512
    block_q: int = 512
    block_k: int = 1024
    family: str = "dense"     # dense | moe | ssm | vlm | audio | hybrid
    sub_quadratic: bool = False
    grad_accum: int = 1       # microbatches per step (activation memory / N)
    kv_quant: bool = False    # int8 KV cache (decode cells)
    skip_masked_blocks: bool = False  # causal block skipping (attn)

    @property
    def num_layers(self) -> int:
        return len(self.prefix_pattern) + self.num_periods * len(self.pattern)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 256 so the vocab dim always
        shards on the model axis (e.g. granite's 49155 -> 49408). Padded
        logit columns are masked to -inf in the loss / sampling paths."""
        return -(-self.vocab_size // 256) * 256

    def all_specs(self):
        return list(self.prefix_pattern) + list(self.pattern) * self.num_periods


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------


def _layer_init(key, spec: LayerSpec, cfg: ModelConfig):
    kmix, kffn = jax.random.split(key)
    dt = cfg.param_dtype
    p: dict = {"ln1": jnp.ones((cfg.d_model,), dt)}
    if spec.kind == "attn":
        p["attn"] = L.attn_init(kmix, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                cfg.head_dim, qk_norm=cfg.qk_norm, dtype=dt)
    elif spec.kind == "mla":
        p["attn"] = L.mla_init(kmix, cfg.d_model, cfg.n_heads, cfg.mla, dtype=dt)
    elif spec.kind == "mamba":
        p["mixer"] = S.mamba_init(kmix, cfg.d_model, cfg.mamba, dtype=dt)
    elif spec.kind == "mlstm":
        p["mixer"] = S.mlstm_init(kmix, cfg.d_model, cfg.xlstm, dtype=dt)
    elif spec.kind == "slstm":
        p["mixer"] = S.slstm_init(kmix, cfg.d_model, cfg.xlstm, dtype=dt)
    else:
        raise ValueError(spec.kind)
    if spec.ffn in ("glu", "dense"):
        p["ln2"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = L.mlp_init(kffn, cfg.d_model, cfg.d_ff, spec.ffn, dtype=dt)
    elif spec.ffn == "moe":
        p["ln2"] = jnp.ones((cfg.d_model,), dt)
        p["moe"] = M.moe_init(kffn, cfg.d_model, cfg.moe, dtype=dt)
    return p


def _layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int, s_max: int, dtype):
    if spec.kind == "attn":
        return L.attn_cache_init(batch, s_max, cfg.n_kv_heads, cfg.head_dim,
                                 window=spec.window, dtype=dtype,
                                 quant=cfg.kv_quant)
    if spec.kind == "mla":
        return L.mla_cache_init(batch, s_max, cfg.mla, dtype=dtype)
    if spec.kind == "mamba":
        return S.mamba_cache_init(batch, cfg.d_model, cfg.mamba, dtype=dtype)
    if spec.kind == "mlstm":
        return S.mlstm_cache_init(batch, cfg.d_model, cfg.xlstm, dtype=dtype)
    if spec.kind == "slstm":
        return S.slstm_cache_init(batch, cfg.d_model, cfg.xlstm, dtype=dtype)
    raise ValueError(spec.kind)


def _layer_apply(p, spec: LayerSpec, cfg: ModelConfig, x, *, mode, cache, pos,
                 prefix_len=None):
    aux = 0.0
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.kind == "attn":
        h, new_cache = L.attn_apply(
            p["attn"], h, mode=mode, cache=cache, pos=pos, window=spec.window,
            prefix_len=prefix_len if cfg.prefix_lm else None,
            rope_theta=cfg.rope_theta, block_q=cfg.block_q,
            block_k=cfg.block_k, skip_masked_blocks=cfg.skip_masked_blocks)
    elif spec.kind == "mla":
        h, new_cache = L.mla_apply(
            p["attn"], h, mode=mode, cache=cache, pos=pos, mla=cfg.mla,
            rope_theta=cfg.rope_theta, block_q=cfg.block_q, block_k=cfg.block_k)
    elif spec.kind == "mamba":
        h, new_cache = S.mamba_apply(p["mixer"], h, mode=mode, cache=cache,
                                     pos=pos, mc=cfg.mamba)
    elif spec.kind == "mlstm":
        h, new_cache = S.mlstm_apply(p["mixer"], h, mode=mode, cache=cache,
                                     pos=pos, xc=cfg.xlstm)
    else:  # slstm
        h, new_cache = S.slstm_apply(p["mixer"], h, mode=mode, cache=cache,
                                     pos=pos, xc=cfg.xlstm)
    # residual-stream pins: with_sharding_constraint also constrains the
    # cotangent in the transpose, keeping backward gathers batch-sharded.
    x = shard_ctx.constrain(x + h, ("dp", "tp", None))
    if "ffn" in p:
        x = x + L.mlp_apply(p["ffn"], L.rms_norm(x, p["ln2"], cfg.norm_eps),
                            act=cfg.act)
        x = shard_ctx.constrain(x, ("dp", "tp", None))
    elif "moe" in p:
        y, aux = M.moe_apply(p["moe"], L.rms_norm(x, p["ln2"], cfg.norm_eps),
                             cfg.moe, train=(mode == "train"))
        x = shard_ctx.constrain(x + y, ("dp", "tp", None))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Embedding with a partition-friendly backward
# ---------------------------------------------------------------------------


@jax.custom_vjp
def embed_lookup(table, tokens):
    """table[V, d], tokens[B, T] -> [B, T, d].

    Forward is a plain gather (GSPMD slices it fine). Backward REPLACES the
    scatter-add — which the SPMD partitioner replicates at [V, d] f32 per
    device for vocab-sharded tables — with a chunked one-hot einsum:
    elementwise iota-compare + matmul partition as (dp x model) with a psum,
    keeping the gradient sharded like the table. ~12 GB/device saved on
    dbrx-132b train.
    """
    return table[tokens]


def _embed_fwd(table, tokens):
    # zero-size marker array carries the table's (V, dtype) statically.
    marker = jnp.zeros((table.shape[0], 0), table.dtype)
    return table[tokens], (tokens, marker)


def _embed_bwd(res, g):
    tokens, marker = res
    V, dt = marker.shape[0], marker.dtype
    B, T, d = g.shape
    chunk = min(T, 512)
    while T % chunk:
        chunk -= 1
    n = T // chunk
    toks = tokens.reshape(B, n, chunk).swapaxes(0, 1)
    gs = g.reshape(B, n, chunk, d).swapaxes(0, 1)

    def step(acc, xs):
        tok_c, g_c = xs
        oh = (tok_c[..., None] ==
              jax.lax.broadcasted_iota(jnp.int32, (B, chunk, V), 2))
        oh = shard_ctx.constrain(oh.astype(g.dtype), ("dp", None, "tp"))
        acc = acc + jnp.einsum("bcv,bcd->vd", oh, g_c)
        return acc, None

    acc0 = shard_ctx.constrain(jnp.zeros((V, d), g.dtype), ("tp", "dp"))
    dtab, _ = jax.lax.scan(step, acc0, (toks, gs))
    return dtab.astype(dt), None


embed_lookup.defvjp(_embed_fwd, _embed_bwd)


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig):
    keys = jax.random.split(key, 4)
    dt = cfg.param_dtype
    params: dict = {}
    if cfg.embed_inputs:
        params["embed"] = (
            jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model), dt) * 0.02)
    if cfg.prefix_pattern:
        pk = jax.random.split(keys[1], len(cfg.prefix_pattern))
        params["prefix"] = {
            str(i): _layer_init(pk[i], s, cfg)
            for i, s in enumerate(cfg.prefix_pattern)
        }
    pk = jax.random.split(keys[2], cfg.num_periods)

    def one_period(k):
        lk = jax.random.split(k, len(cfg.pattern))
        return {str(i): _layer_init(lk[i], s, cfg)
                for i, s in enumerate(cfg.pattern)}

    params["periods"] = jax.vmap(one_period)(pk)
    params["final_norm"] = jnp.ones((cfg.d_model,), dt)
    if not (cfg.tie_embeddings and cfg.embed_inputs):
        params["head"] = (
            jax.random.normal(
                keys[3], (cfg.d_model, cfg.num_output_heads, cfg.padded_vocab), dt
            ) / (cfg.d_model ** 0.5))
    return params


def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.float32):
    cache: dict = {}
    if cfg.prefix_pattern:
        cache["prefix"] = {
            str(i): _layer_cache(s, cfg, batch, s_max, dtype)
            for i, s in enumerate(cfg.prefix_pattern)
        }

    def one_period(_):
        return {str(i): _layer_cache(s, cfg, batch, s_max, dtype)
                for i, s in enumerate(cfg.pattern)}

    cache["periods"] = jax.vmap(one_period)(jnp.arange(cfg.num_periods))
    return cache


def forward(params, cfg: ModelConfig, inputs, *, mode: str, cache=None, pos=0,
            prefix_len=None):
    """inputs: tokens [B, T] int32 (embed_inputs) or embeds [B, T, d].

    Returns (hidden [B, T, d], new_cache, aux_loss_sum).
    """
    if cfg.embed_inputs:
        x = embed_lookup(params["embed"], inputs)
    else:
        x = inputs
    x = shard_ctx.constrain(x, ("dp", "tp", None))
    aux_total = 0.0
    new_cache: dict = {} if cache is not None else None

    for i, spec in enumerate(cfg.prefix_pattern):
        c = cache["prefix"][str(i)] if cache is not None else None
        x, nc, aux = _layer_apply(params["prefix"][str(i)], spec, cfg, x,
                                  mode=mode, cache=c, pos=pos,
                                  prefix_len=prefix_len)
        aux_total = aux_total + aux
        if cache is not None:
            new_cache.setdefault("prefix", {})[str(i)] = nc

    def period_fn(x, xs):
        pparams, pcache = xs
        # sequence-parallel carry: the scan residual saved per period for
        # backward is stored T-sharded on the model axis (Megatron-SP);
        # GSPMD all-gathers transiently inside the layer. For decode (T=1)
        # the tp factor doesn't divide and the constraint drops to DP-only.
        # The optimization barrier keeps XLA from hoisting the layer-entry
        # bf16->f32 convert out of the scan — without it the carry stack is
        # stored f32 AND full-T (2x + gather blowup on 40-period models).
        x = jax.lax.optimization_barrier(x)
        x = shard_ctx.constrain(x, ("dp", "tp", None))
        new_pc = {}
        aux_p = 0.0
        for i, spec in enumerate(cfg.pattern):
            c = pcache[str(i)] if pcache is not None else None
            x, nc, aux = _layer_apply(pparams[str(i)], spec, cfg, x,
                                      mode=mode, cache=c, pos=pos,
                                      prefix_len=prefix_len)
            aux_p = aux_p + aux
            if nc is not None:
                new_pc[str(i)] = nc
        return x, (new_pc if new_pc else None, aux_p)

    body = period_fn
    if mode == "train" and cfg.remat:
        body = jax.checkpoint(period_fn)
    pcaches = cache["periods"] if cache is not None else None
    if pcaches is None:
        x, (_, auxs) = jax.lax.scan(lambda h, pp: body(h, (pp, None)),
                                    x, params["periods"])
    else:
        x, (ncs, auxs) = jax.lax.scan(body, x, (params["periods"], pcaches))
        new_cache["periods"] = ncs
    aux_total = aux_total + jnp.sum(auxs)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_cache, aux_total


def _head_matrix(params, cfg: ModelConfig):
    if "head" in params:
        return params["head"]
    # tied: [d, 1, V]
    return params["embed"].T[:, None, :]


def compute_logits(params, cfg: ModelConfig, hidden):
    """hidden [B, T, d] -> logits [B, T, (nH,) padded_V] (f32); padded vocab
    columns are -inf so sampling/argmax never selects them."""
    head = _head_matrix(params, cfg)
    logits = jnp.einsum("btd,dhv->bthv", hidden.astype(jnp.float32),
                        head.astype(jnp.float32))
    if cfg.padded_vocab != cfg.vocab_size:
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3)
        logits = jnp.where(cols < cfg.vocab_size, logits, -jnp.inf)
    if cfg.num_output_heads == 1:
        logits = logits[:, :, 0]
    return logits


def chunked_xent(params, cfg: ModelConfig, hidden, labels, mask=None):
    """Cross-entropy without materializing [B, T, V] logits.

    Scans over sequence chunks — each step sees [B, chunk, V], which under
    (data, model)=(batch, vocab) sharding is a few hundred KB per chip even
    at vocab 256k. labels: [B, T] or [B, T, nH] (multi-head: musicgen).
    """
    B, T, d = hidden.shape
    head = _head_matrix(params, cfg)
    chunk = min(cfg.loss_chunk, T)
    if T % chunk:
        chunk = 1 if T < 2 else [c for c in range(chunk, 0, -1) if T % c == 0][0]
    n = T // chunk
    if labels.ndim == 2:
        labels = labels[..., None]
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    elif mask.ndim == 2:
        mask = mask[..., None].astype(jnp.float32)
    hs = hidden.reshape(B, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, n, chunk, -1).swapaxes(0, 1)
    ms = mask.reshape(B, n, chunk, -1).swapaxes(0, 1)

    @jax.checkpoint
    def step(carry, xs):
        h_c, l_c, m_c = xs
        logits = jnp.einsum("bcd,dhv->bchv", h_c.astype(jnp.float32),
                            head.astype(jnp.float32))
        logits = shard_ctx.constrain(logits, ("dp", None, None, "tp"))
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3)
        logits = jnp.where(cols < cfg.vocab_size, logits, -jnp.inf)  # vocab pad
        logz = jax.nn.logsumexp(logits, axis=-1)
        # label logit via masked sum, NOT take_along_axis: a gather across a
        # vocab-sharded axis forces GSPMD to replicate the logits; the masked
        # sum partitions as elementwise + psum.
        ll = jnp.sum(jnp.where(cols == l_c[..., None], logits, 0.0), axis=-1)
        loss = ((logz - ll) * m_c).sum()
        return (carry[0] + loss, carry[1] + m_c.sum()), None

    (loss_sum, count), _ = jax.lax.scan(step, (0.0, 0.0), (hs, ls, ms))
    return loss_sum / jnp.maximum(count, 1.0)
