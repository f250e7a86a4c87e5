"""Dense decoder-only transformer family.

Covers: qwen3-* (GQA + qk-norm), smollm (llama-arch), gemma2 (local/global
alternating attention + logit softcaps + post-block norms), and the paper's
own LLaMA-2 backbone.  Layers are stacked with ``jax.vmap`` at init and run
with ``jax.lax.scan`` (compile-time economy for the 512-device dry-run);
training wraps each block in ``jax.checkpoint`` and pins the residual stream
to a Megatron-style (batch→data, seq→model) layout so remat checkpoints stay
small (DESIGN.md §5).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers.attention import (
    attention, attn_decode, init_attention, init_attn_cache, map_kv_pos)
from repro.models.layers.embeddings import embed, init_embedding, unembed
from repro.models.layers.linear import dense, init_dense
from repro.models.layers.mlp import init_mlp, mlp
from repro.models.layers.norms import init_rmsnorm, rmsnorm

# Sequence length at/above which attention switches to the blockwise
# online-softmax path (memory-bounded); block sizes chosen 128-aligned.
import os as _os
BLOCKWISE_THRESHOLD = 4096
BLOCK_Q = int(_os.environ.get("REPRO_BLOCK_Q", "512"))
BLOCK_KV = int(_os.environ.get("REPRO_BLOCK_KV", "2048"))


def _seq_constraint(x, *, decode: bool = False):
    """Pin residual stream to (batch->data, seq->model) when a mesh is
    active; no-op outside pjit/mesh contexts or when dims don't divide."""
    from repro.dist.sharding import residual_constraint  # lazy
    return residual_constraint(x, decode=decode)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": init_rmsnorm(cfg.d_model),
        "attn": init_attention(k1, cfg, dtype=dtype),
        "mlp_norm": init_rmsnorm(cfg.d_model),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype),
    }
    if cfg.post_block_norm:
        p["post_attn_norm"] = init_rmsnorm(cfg.d_model)
        p["post_mlp_norm"] = init_rmsnorm(cfg.d_model)
    return p


def init(cfg: ModelConfig, key) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kl, kh = jax.random.split(key, 3)
    if cfg.local_global_alternating:
        assert cfg.num_layers % 2 == 0
        n_pairs = cfg.num_layers // 2
        keys = jax.random.split(kl, 2 * n_pairs).reshape(2, n_pairs, 2)
        layers = {
            "local": jax.vmap(lambda k: _init_block(k, cfg, dtype))(keys[0]),
            "global": jax.vmap(lambda k: _init_block(k, cfg, dtype))(keys[1]),
        }
    else:
        keys = jax.random.split(kl, cfg.num_layers)
        layers = jax.vmap(lambda k: _init_block(k, cfg, dtype))(keys)
    p = {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = init_dense(kh, cfg.d_model, cfg.vocab_size, dtype)
    return p


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _block(p, cfg: ModelConfig, x, *, positions, window, kind="causal",
           prefix_len=None, block_q=0, block_kv=0):
    gemma = cfg.post_block_norm
    h = attention(p["attn"], cfg, rmsnorm(p["attn_norm"], x, cfg.norm_eps,
                                          gemma_style=gemma),
                  positions=positions, kind=kind, window=window,
                  prefix_len=prefix_len, block_q=block_q, block_kv=block_kv)
    if gemma:
        h = rmsnorm(p["post_attn_norm"], h, cfg.norm_eps, gemma_style=True)
    x = x + h
    h = mlp(p["mlp"], rmsnorm(p["mlp_norm"], x, cfg.norm_eps,
                              gemma_style=gemma), cfg.activation)
    if gemma:
        h = rmsnorm(p["post_mlp_norm"], h, cfg.norm_eps, gemma_style=True)
    return x + h


def _block_decode(p, cfg: ModelConfig, x_t, cache, pos, *, window,
                  prefix_len=None, block_tbl=None, ring_len=None,
                  layer=None):
    gemma = cfg.post_block_norm
    h, cache = attn_decode(p["attn"], cfg,
                           rmsnorm(p["attn_norm"], x_t, cfg.norm_eps,
                                   gemma_style=gemma),
                           cache, pos, window=window, prefix_len=prefix_len,
                           block_tbl=block_tbl, ring_len=ring_len,
                           layer=layer)
    if gemma:
        h = rmsnorm(p["post_attn_norm"], h, cfg.norm_eps, gemma_style=True)
    x_t = x_t + h
    h = mlp(p["mlp"], rmsnorm(p["mlp_norm"], x_t, cfg.norm_eps,
                              gemma_style=gemma), cfg.activation)
    if gemma:
        h = rmsnorm(p["post_mlp_norm"], h, cfg.norm_eps, gemma_style=True)
    return x_t + h, cache


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill trunk)
# ---------------------------------------------------------------------------

def forward_hidden(params, cfg: ModelConfig, x, *, positions,
                   prefix_len=None, remat: bool = True,
                   kind: str = "causal"):
    """Embedded input (B,S,d) -> final hidden (B,S,d), scanning layers."""
    S = x.shape[1]
    blockwise = S >= BLOCKWISE_THRESHOLD
    bq, bkv = (BLOCK_Q, BLOCK_KV) if blockwise else (0, 0)
    kind = "prefix" if prefix_len is not None else kind

    def body(h, lp):
        if cfg.local_global_alternating:
            h = _block(lp["local"], cfg, h, positions=positions,
                       window=cfg.sliding_window, kind=kind,
                       prefix_len=prefix_len, block_q=bq, block_kv=bkv)
            h = _seq_constraint(h)
            h = _block(lp["global"], cfg, h, positions=positions,
                       window=0, kind=kind, prefix_len=prefix_len,
                       block_q=bq, block_kv=bkv)
        else:
            h = _block(lp, cfg, h, positions=positions,
                       window=cfg.sliding_window, kind=kind,
                       prefix_len=prefix_len, block_q=bq, block_kv=bkv)
        return _seq_constraint(h), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, _seq_constraint(x), params["layers"])
    return rmsnorm(params["final_norm"], x, cfg.norm_eps,
                   gemma_style=cfg.post_block_norm)


def embed_tokens(params, cfg: ModelConfig, tokens):
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def logits_fn(params, cfg: ModelConfig, hidden):
    if cfg.tie_embeddings:
        lg = unembed(params["embed"], hidden)
    else:
        lg = dense(params["lm_head"], hidden)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        lg = c * jnp.tanh(lg / c)
    return lg


def forward(params, cfg: ModelConfig, tokens, *, prefix_len=None,
            remat: bool = True):
    """tokens (B,S) -> final hidden (B,S,d). Use losses.chunked_ce for LM
    loss (never materializes full logits)."""
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_tokens(params, cfg, tokens)
    return forward_hidden(params, cfg, x, positions=positions,
                          prefix_len=prefix_len, remat=remat)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _cache_lengths(cfg: ModelConfig, seq_len: int, *, force_window: int = 0):
    """(local_len, global_len) ring-buffer sizes for this config."""
    w = force_window or cfg.sliding_window
    local_len = min(seq_len, w) if w > 0 else seq_len
    if cfg.local_global_alternating:
        return min(seq_len, cfg.sliding_window), seq_len
    return local_len, local_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               *, force_window: int = 0, dtype=jnp.bfloat16):
    dh = cfg.resolved_head_dim()
    ll, gl = _cache_lengths(cfg, seq_len, force_window=force_window)
    if cfg.local_global_alternating:
        n_pairs = cfg.num_layers // 2
        mk = lambda n, L: jax.vmap(  # noqa: E731
            lambda _: init_attn_cache(batch, L, cfg.num_kv_heads, dh, dtype)
        )(jnp.arange(n))
        return {"local": mk(n_pairs, ll), "global": mk(n_pairs, gl)}
    mk = jax.vmap(lambda _: init_attn_cache(batch, ll, cfg.num_kv_heads, dh,
                                            dtype))
    return mk(jnp.arange(cfg.num_layers))


def decode_layers(block, x, stack, cache, *, paged: bool):
    """Scan ``block(layer params, h, cache, layer) -> (h, cache)`` over a
    layer stack.

    Rings are the scan's xs/ys: ``block`` sees one layer's ring and
    ``layer`` None.  A paged pool rides the carry whole and ``block`` sees
    every layer-stacked leaf with the layer's index, to write and read at
    it in place: XLA then updates the loop's pool buffer in place (the
    engine donates it), where xs/ys would slice each layer out, write it
    back whole into a second stacked buffer and copy that into the output.
    """
    if not paged:
        def body(h, lp_c):
            lp, c = lp_c
            return block(lp, h, c, None)
        return jax.lax.scan(body, x, (stack, cache))

    def body_paged(carry, lp_layer):
        (h, c), (lp, layer) = carry, lp_layer
        return block(lp, h, c, layer), None
    n = jax.tree.leaves(stack)[0].shape[0]
    carry, _ = jax.lax.scan(body_paged, (x, cache),
                            (stack, jnp.arange(n, dtype=jnp.int32)))
    return carry


def decode_step(params, cfg: ModelConfig, cache, token, pos, *,
                force_window: int = 0, prefix_len=None, block_tbl=None,
                ring_len=None):
    """token (B,1) int32, pos scalar -> (logits (B,1,V), new cache).

    ``block_tbl``/``ring_len`` select the paged-pool cache layout (uniform
    rings only — every layer shares one block geometry and one table; see
    repro.serve.cache_pool.PagedCachePool), which the layer scan reads and
    writes in place (``decode_layers``)."""
    x = embed_tokens(params, cfg, token)
    w = force_window or cfg.sliding_window

    if cfg.local_global_alternating:
        if block_tbl is not None:
            raise ValueError("paged KV pools require uniform ring lengths; "
                             "local/global alternating layers keep "
                             "contiguous lanes")
        def body(h, lp_cache):
            lp, c = lp_cache
            h, c_l = _block_decode(lp["local"], cfg, h, c["local"], pos,
                                   window=cfg.sliding_window,
                                   prefix_len=prefix_len)
            h, c_g = _block_decode(lp["global"], cfg, h, c["global"], pos,
                                   window=0, prefix_len=prefix_len)
            return h, {"local": c_l, "global": c_g}
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    else:
        def block(lp, h, c, layer):
            return _block_decode(lp, cfg, h, c, pos, window=w,
                                 prefix_len=prefix_len, block_tbl=block_tbl,
                                 ring_len=ring_len, layer=layer)
        x, new_cache = decode_layers(block, x, params["layers"], cache,
                                     paged=block_tbl is not None)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps,
                gemma_style=cfg.post_block_norm)
    return logits_fn(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# Prefill: full forward capturing KV into ring caches + last-token logits
# ---------------------------------------------------------------------------

def ring_scatter(leaves: dict, positions, cache_len):
    """Named (B, S, ...) leaves -> ring caches of ``cache_len`` slots
    holding the last ``cache_len`` positions, with their ``kv_pos``."""
    take = min(positions.shape[0], cache_len)
    pos_tail = positions[-take:]
    slots = jnp.mod(pos_tail, cache_len)
    B = next(iter(leaves.values())).shape[0]

    def scatter(val):
        return jnp.zeros((B, cache_len) + val.shape[2:], val.dtype).at[
            :, slots].set(val[:, -take:])

    cp = jnp.full((B, cache_len), -1, jnp.int32).at[:, slots].set(
        jnp.broadcast_to(pos_tail[None], (B, take)))
    return {**{n: scatter(x) for n, x in leaves.items()}, "kv_pos": cp}


def _scatter_ring(k, v, positions, cache_len):
    """k,v: (B,S,Hk,dh) post-RoPE -> ring cache of cache_len slots holding
    the last ``cache_len`` positions (int8-quantized when REPRO_KV_INT8)."""
    from repro.models.layers.attention import _quant_kv, kv_cache_int8
    if kv_cache_int8():
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        return ring_scatter({"k": kq, "v": vq, "k_scale": ks,
                             "v_scale": vs}, positions, cache_len)
    return ring_scatter({"k": k, "v": v}, positions, cache_len)


def _finalize_prefill(params, cfg: ModelConfig, x, cache, true_len):
    """Last-token logits + (when ``true_len`` (B,) is given) bucketed-prompt
    fixup: logits are gathered at row position ``true_len - 1`` (causal
    masking makes that hidden state independent of the right padding) and
    ring slots written by pad positions are invalidated (kv_pos -> -1)."""
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps,
                gemma_style=cfg.post_block_norm)
    B, S = x.shape[:2]
    if true_len is None:
        return cache, logits_fn(params, cfg, x[:, -1:, :])
    tl = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32).reshape(-1), (B,))
    last = x[jnp.arange(B), jnp.clip(tl - 1, 0, S - 1)][:, None, :]

    # kv_pos leaves: (L, B, cache_len) — pad slots carry positions >=
    # true_len
    cache = map_kv_pos(
        lambda kp: jnp.where(kp >= tl[None, :, None], -1, kp), cache)
    return cache, logits_fn(params, cfg, last)


def prefill(params, cfg: ModelConfig, tokens, *, force_window: int = 0,
            prefix_len=None, cache_len: int = 0, true_len=None):
    """tokens (B,S) -> (cache, last-token logits (B,1,V)).

    Runs the full-sequence trunk block-by-block (scan), capturing each
    layer's (k, v) into its ring buffer.  ``true_len`` (B,) marks rows as
    right-padded to a bucket length: logits come from the last *real* token
    and pad-written ring slots are masked invalid (the serving engine's
    prefill-bucketing path — bounds the number of prefill signatures).
    """
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_tokens(params, cfg, tokens)
    blockwise = S >= BLOCKWISE_THRESHOLD
    bq, bkv = (BLOCK_Q, BLOCK_KV) if blockwise else (0, 0)
    kind = "prefix" if prefix_len is not None else "causal"
    ll, gl = _cache_lengths(cfg, max(S, cache_len), force_window=force_window)
    cache_dtype = jnp.dtype(cfg.compute_dtype)

    def attn_with_capture(lp, h, window, cache_len):
        gemma = cfg.post_block_norm
        a_in = rmsnorm(lp["attn_norm"], h, cfg.norm_eps, gemma_style=gemma)
        y, (k, v) = attention(lp["attn"], cfg, a_in, positions=positions,
                              kind=kind, window=window, prefix_len=prefix_len,
                              block_q=bq, block_kv=bkv, return_kv=True)
        if gemma:
            y = rmsnorm(lp["post_attn_norm"], y, cfg.norm_eps,
                        gemma_style=True)
        c = _scatter_ring(k.astype(cache_dtype), v.astype(cache_dtype),
                          positions, cache_len)
        return y, c

    def full_block(lp, h, window, cache_len):
        gemma = cfg.post_block_norm
        y, c = attn_with_capture(lp, h, window, cache_len)
        h = h + y
        m = mlp(lp["mlp"], rmsnorm(lp["mlp_norm"], h, cfg.norm_eps,
                                   gemma_style=gemma), cfg.activation)
        if gemma:
            m = rmsnorm(lp["post_mlp_norm"], m, cfg.norm_eps, gemma_style=True)
        return h + m, c

    if cfg.local_global_alternating:
        def body(h, lp):
            h, c_l = full_block(lp["local"], h, cfg.sliding_window, ll)
            h = _seq_constraint(h)
            h, c_g = full_block(lp["global"], h, 0, gl)
            return _seq_constraint(h), {"local": c_l, "global": c_g}
    else:
        w = force_window or cfg.sliding_window
        def body(h, lp):
            h, c = full_block(lp, h, w, ll)
            return _seq_constraint(h), c

    x, cache = jax.lax.scan(body, _seq_constraint(x), params["layers"])
    return _finalize_prefill(params, cfg, x, cache, true_len)
