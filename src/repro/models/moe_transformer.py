"""MoE decoder family: mixtral-8x7b (top-2, SWA), qwen2-moe-a2.7b (4 shared
+ 60 routed, top-4) and DeepSeek-V2 (latent attention, leading dense
layers, 2 shared + 64 routed, top-6).

Identical trunk to the dense transformer, with the FFN replaced by the MoE
block: capacity-dispatched for the training loss (the router aux loss
threads through the layer scan), dropless for prefill and decode.
Attention is GQA, or multi-head latent attention when ``cfg.mla`` is set.
Leading dense layers (``moe.first_dense_layers``) have shapes of their own,
so they are a stack of their own, scanned before the MoE stack; their
cache is then ``{"dense": ..., "moe": ...}``, else one stacked cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers.attention import (
    attention, attn_decode, init_attention, init_attn_cache, init_mla,
    init_mla_cache, mla_attention, mla_decode)
from repro.models.layers.mlp import init_mlp, mlp
from repro.models.layers.moe import init_moe, moe_block, moe_serve
from repro.models.layers.norms import init_rmsnorm, rmsnorm
from repro.models.transformer import (
    BLOCK_KV, BLOCK_Q, BLOCKWISE_THRESHOLD, _finalize_prefill,
    _scatter_ring, _seq_constraint, embed_tokens, logits_fn, ring_scatter)
from repro.models.layers.embeddings import init_embedding
from repro.models.layers.linear import init_dense


def _init_attn(key, cfg: ModelConfig, dtype):
    if cfg.mla is not None:
        return init_mla(key, cfg, dtype)
    return init_attention(key, cfg, dtype=dtype)


def _init_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": init_rmsnorm(cfg.d_model),
        "attn": _init_attn(k1, cfg, dtype),
        "moe_norm": init_rmsnorm(cfg.d_model),
        "moe": init_moe(k2, cfg, dtype),
    }


def _init_dense_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": init_rmsnorm(cfg.d_model),
        "attn": _init_attn(k1, cfg, dtype),
        "mlp_norm": init_rmsnorm(cfg.d_model),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype),
    }


def init(cfg: ModelConfig, key) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kl, kh = jax.random.split(key, 3)
    keys = jax.random.split(kl, cfg.num_layers)
    nd = cfg.moe.first_dense_layers
    p = {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model, dtype),
        "layers": jax.vmap(lambda k: _init_block(k, cfg, dtype))(keys[nd:]),
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if nd:
        p["dense_layers"] = jax.vmap(
            lambda k: _init_dense_block(k, cfg, dtype))(keys[:nd])
    if not cfg.tie_embeddings:
        p["lm_head"] = init_dense(kh, cfg.d_model, cfg.vocab_size, dtype)
    return p


def _stacks(params, cfg: ModelConfig, cache=None):
    """[(layer params stack, its cache)] in depth order."""
    if not cfg.moe.first_dense_layers:
        return [(params["layers"], cache)]
    c = cache or {}
    return [(params["dense_layers"], c.get("dense")),
            (params["layers"], c.get("moe"))]


def _join(cfg: ModelConfig, caches):
    if not cfg.moe.first_dense_layers:
        return caches[0]
    return {"dense": caches[0], "moe": caches[1]}


def _ffn(lp, cfg: ModelConfig, h):
    """The dense layers' SwiGLU, or the MoE layers' dropless experts."""
    if "mlp" in lp:
        return mlp(lp["mlp"], rmsnorm(lp["mlp_norm"], h, cfg.norm_eps),
                   cfg.activation)
    return moe_serve(lp["moe"], cfg, rmsnorm(lp["moe_norm"], h, cfg.norm_eps))


def forward(params, cfg: ModelConfig, tokens, *, remat: bool = True):
    """tokens (B,S) -> (final hidden, total aux loss)."""
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_tokens(params, cfg, tokens)
    blockwise = S >= BLOCKWISE_THRESHOLD
    bq, bkv = (BLOCK_Q, BLOCK_KV) if blockwise else (0, 0)

    def body(carry, lp):
        h, aux = carry
        a_in = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        if cfg.mla is not None:
            a, _ = mla_attention(lp["attn"], cfg, a_in, positions=positions,
                                 window=cfg.sliding_window, block_q=bq,
                                 block_kv=bkv)
        else:
            a = attention(lp["attn"], cfg, a_in, positions=positions,
                          kind="causal", window=cfg.sliding_window,
                          block_q=bq, block_kv=bkv)
        h = h + a
        if "mlp" in lp:
            m, aux_l = _ffn(lp, cfg, h), 0.0
        else:
            m, aux_l = moe_block(lp["moe"], cfg,
                                 rmsnorm(lp["moe_norm"], h, cfg.norm_eps))
        h = _seq_constraint(h + m)
        return (h, aux + aux_l), None

    body_fn = jax.checkpoint(body, prevent_cse=False) if remat else body
    carry = (_seq_constraint(x), jnp.zeros((), jnp.float32))
    for stack, _ in _stacks(params, cfg):
        carry, _ = jax.lax.scan(body_fn, carry, stack)
    x, aux = carry
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               *, force_window: int = 0, dtype=jnp.bfloat16):
    w = force_window or cfg.sliding_window
    cl = min(seq_len, w) if w > 0 else seq_len
    if cfg.mla is not None:
        one = lambda _: init_mla_cache(batch, cl, cfg.mla.cache_dim,  # noqa
                                       dtype)
    else:
        one = lambda _: init_attn_cache(batch, cl, cfg.num_kv_heads,  # noqa
                                        cfg.resolved_head_dim(), dtype)
    nd = cfg.moe.first_dense_layers
    stacks = [nd, cfg.num_layers - nd] if nd else [cfg.num_layers]
    return _join(cfg, [jax.vmap(one)(jnp.arange(n)) for n in stacks])


def decode_step(params, cfg: ModelConfig, cache, token, pos, *,
                force_window: int = 0, block_tbl=None, ring_len=None):
    x = embed_tokens(params, cfg, token)
    w = force_window or cfg.sliding_window
    step = mla_decode if cfg.mla is not None else attn_decode

    def body(h, lp_cache):
        lp, c = lp_cache
        a, c2 = step(lp["attn"], cfg,
                     rmsnorm(lp["attn_norm"], h, cfg.norm_eps),
                     c, pos, window=w, block_tbl=block_tbl,
                     ring_len=ring_len)
        h = h + a
        return h + _ffn(lp, cfg, h), c2

    caches = []
    for stack, c in _stacks(params, cfg, cache):
        x, c = jax.lax.scan(body, x, (stack, c))
        caches.append(c)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_fn(params, cfg, x), _join(cfg, caches)


def prefill(params, cfg: ModelConfig, tokens, *, force_window: int = 0,
            cache_len: int = 0, true_len=None):
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_tokens(params, cfg, tokens)
    blockwise = S >= BLOCKWISE_THRESHOLD
    bq, bkv = (BLOCK_Q, BLOCK_KV) if blockwise else (0, 0)
    w = force_window or cfg.sliding_window
    total = max(S, cache_len)
    cl = min(total, w) if w > 0 else total
    cache_dtype = jnp.dtype(cfg.compute_dtype)

    def body(h, lp):
        a_in = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        if cfg.mla is not None:
            a, entry = mla_attention(lp["attn"], cfg, a_in,
                                     positions=positions, window=w,
                                     block_q=bq, block_kv=bkv)
            c = ring_scatter({"latent": entry.astype(cache_dtype)},
                             positions, cl)
        else:
            a, (k, v) = attention(lp["attn"], cfg, a_in, positions=positions,
                                  kind="causal", window=w, block_q=bq,
                                  block_kv=bkv, return_kv=True)
            c = _scatter_ring(k.astype(cache_dtype), v.astype(cache_dtype),
                              positions, cl)
        h = h + a
        return _seq_constraint(h + _ffn(lp, cfg, h)), c

    x = _seq_constraint(x)
    caches = []
    for stack, _ in _stacks(params, cfg):
        x, c = jax.lax.scan(body, x, stack)
        caches.append(c)
    return _finalize_prefill(params, cfg, x, _join(cfg, caches), true_len)
