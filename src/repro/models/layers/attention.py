"""Attention: GQA + qk-norm + logit softcap + sliding window + prefix-LM,
with a memory-bounded blockwise (online-softmax) path for long sequences and
a ring-buffer KV cache for decode; and DeepSeek-V2's multi-head latent
attention (YaRN RoPE, one latent entry a position in the cache, absorbed
decode through the paged kernel's latent mode).

Position-based masking: every mask is derived from absolute positions of the
query rows (``q_pos``) and of the KV slots (``kv_pos``); a slot with position
``-1`` is invalid (empty ring-buffer slot).  This one rule serves training,
prefill, sliding-window decode and prefix-LM uniformly.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers.embeddings import apply_rope
from repro.models.layers.linear import dense, init_dense
from repro.models.layers.norms import init_rmsnorm, rmsnorm

_NEG_INF = float(jnp.finfo(jnp.float32).min)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_attention(key, cfg, *, q_in: int | None = None, kv_in: int | None = None,
                   out_dim: int | None = None, dtype=jnp.float32):
    """q/k/v/o projections (+ optional per-head qk RMSNorm scales)."""
    dh = cfg.resolved_head_dim()
    q_in = q_in or cfg.d_model
    kv_in = kv_in or q_in
    out_dim = out_dim or cfg.d_model
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_dense(kq, q_in, cfg.num_heads * dh, dtype),
        "wk": init_dense(kk, kv_in, cfg.num_kv_heads * dh, dtype),
        "wv": init_dense(kv, kv_in, cfg.num_kv_heads * dh, dtype),
        "wo": init_dense(ko, cfg.num_heads * dh, out_dim, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(dh)
        p["k_norm"] = init_rmsnorm(dh)
    return p


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def _as_b(pos, batch):
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        pos = jnp.broadcast_to(pos[None, :], (batch, pos.shape[0]))
    return pos


def _mask(q_pos, kv_pos, kind: str, window: int, prefix_len) -> jnp.ndarray:
    """(B, 1, 1, Sq, Skv) boolean mask from absolute positions."""
    qp = q_pos[:, None, None, :, None]
    kp = kv_pos[:, None, None, None, :]
    valid = kp >= 0
    if kind == "causal":
        m = kp <= qp
    elif kind == "prefix":
        pl = jnp.asarray(prefix_len, jnp.int32).reshape(-1, 1, 1, 1, 1)
        m = (kp <= qp) | (kp < pl)
    elif kind == "full":
        m = jnp.ones(qp.shape[:-1] + (kp.shape[-1],), bool)
    else:
        raise ValueError(kind)
    if window > 0 and kind != "full":
        m = m & (qp - kp < window)
    return m & valid


# ---------------------------------------------------------------------------
# Scaled dot-product attention (naive + blockwise online-softmax)
# ---------------------------------------------------------------------------

def _scores(q, k, scale: float, softcap: float) -> jnp.ndarray:
    """q: (B,Sq,Hk,G,D)  k: (B,Skv,Hk,D) -> (B,Hk,G,Sq,Skv) float32."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    return s


def sdpa(q, k, v, *, q_pos, kv_pos, kind: str = "causal", window: int = 0,
         prefix_len=None, softcap: float = 0.0,
         block_q: int = 0, block_kv: int = 0,
         k_scale=None, v_scale=None, scale=None) -> jnp.ndarray:
    """General SDPA.

    q: (B, Sq, H, D); k: (B, Skv, Hk, D); v: (B, Skv, Hk, Dv); returns
    (B, Sq, H, Dv).  ``scale`` defaults to D ** -0.5.
    ``block_q``/``block_kv`` > 0 selects the memory-bounded blockwise path
    (required for 32k+ sequences; see DESIGN.md §3).  Ragged lengths are
    handled by padding the tail block with invalid (position -1) slots.

    ``k_scale``/``v_scale`` ((B, Skv, Hk, 1) absmax scales) mark k/v as an
    int8-quantized cache; the blockwise path dequantizes per KV block inside
    the scan, so the full cache is never materialized at compute precision.
    """
    B, Sq, H, D = q.shape
    Hk, Dv = k.shape[2], v.shape[-1]
    G = H // Hk
    scale = D ** -0.5 if scale is None else scale
    q_pos = _as_b(q_pos, B)
    kv_pos = _as_b(kv_pos, B)
    quantized = k_scale is not None

    if block_kv <= 0 or k.shape[1] <= block_kv:
        # single logical KV block: dequant here is already blockwise
        if quantized:
            k = _dequant_kv(k, k_scale, q.dtype)
            v = _dequant_kv(v, v_scale, q.dtype)
        qg = q.reshape(B, Sq, Hk, G, D)
        s = _scores(qg, k, scale, softcap)
        m = _mask(q_pos, kv_pos, kind, window, prefix_len)
        s = jnp.where(m, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # fully-masked rows produce uniform garbage; zero them via the mask
        p = jnp.where(m.any(-1, keepdims=True), p, 0.0).astype(q.dtype)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
        return o.reshape(B, Sq, H, Dv)

    # ---- blockwise path: outer map over Q blocks, inner scan over KV ----
    # ragged tails are padded: KV slots with position -1 (masked invalid),
    # Q rows with position -1 (fully masked; sliced off the output)
    Skv = k.shape[1]
    pad_kv = -Skv % block_kv
    if pad_kv:
        pad4 = ((0, 0), (0, pad_kv), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad4), jnp.pad(v, pad4)
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_kv)), constant_values=-1)
        if quantized:
            k_scale = jnp.pad(k_scale, pad4)
            v_scale = jnp.pad(v_scale, pad4)
    if block_q <= 0 or Sq < block_q:
        block_q = Sq
    pad_q = -Sq % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    Sq_pad, Skv_pad = q.shape[1], k.shape[1]
    nq, nk = Sq_pad // block_q, Skv_pad // block_kv
    qg = q.reshape(B, Sq_pad, Hk, G, D)

    k_blocks = k.reshape(B, nk, block_kv, Hk, D)
    v_blocks = v.reshape(B, nk, block_kv, Hk, Dv)
    kp_blocks = kv_pos.reshape(B, nk, block_kv)
    if quantized:
        ks_blocks = k_scale.reshape(B, nk, block_kv, Hk, 1)
        vs_blocks = v_scale.reshape(B, nk, block_kv, Hk, 1)

    def one_q_block(args):
        qb, qpb = args                      # (B,block_q,Hk,G,D), (B,block_q)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, blk):
            m_run, l_run, acc = carry
            if quantized:                   # fused in-scan dequant
                kb, vb, kpb, ksb, vsb = blk
                kb = _dequant_kv(kb, ksb, qb.dtype)
                vb = _dequant_kv(vb, vsb, qb.dtype)
            else:
                kb, vb, kpb = blk           # (B,block_kv,Hk,D), (B,block_kv)
            s = _scores(qb, kb, scale, softcap)           # (B,Hk,G,bq,bk) f32
            msk = _mask(qpb, kpb, kind, window, prefix_len)
            s = jnp.where(msk, s, _NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hk, G, block_q), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, Hk, G, block_q, Dv), jnp.float32)
        xs = [k_blocks.transpose(1, 0, 2, 3, 4),
              v_blocks.transpose(1, 0, 2, 3, 4),
              kp_blocks.transpose(1, 0, 2)]
        if quantized:
            xs += [ks_blocks.transpose(1, 0, 2, 3, 4),
                   vs_blocks.transpose(1, 0, 2, 3, 4)]
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), tuple(xs))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return out                           # (B,Hk,G,block_q,D)

    qg_blocks = qg.reshape(B, nq, block_q, Hk, G, D).transpose(1, 0, 2, 3, 4, 5)
    qp_blocks = q_pos.reshape(B, nq, block_q).transpose(1, 0, 2)
    outs = jax.lax.map(one_q_block, (qg_blocks, qp_blocks))
    # outs: (nq, B, Hk, G, block_q, Dv) -> (B, nq·block_q, Hk, G, Dv)
    o = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq_pad, Hk, G, Dv)
    return o.reshape(B, Sq_pad, H, Dv)[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Full-sequence attention (train / prefill / encoder / cross)
# ---------------------------------------------------------------------------

def _project_qkv(params, cfg, x, kv_x, positions, kv_positions, use_rope):
    dh = cfg.resolved_head_dim()
    B, Sq = x.shape[0], x.shape[1]
    kv_x = x if kv_x is None else kv_x
    Skv = kv_x.shape[1]
    q = dense(params["wq"], x).reshape(B, Sq, cfg.num_heads, dh)
    k = dense(params["wk"], kv_x).reshape(B, Skv, cfg.num_kv_heads, dh)
    v = dense(params["wv"], kv_x).reshape(B, Skv, cfg.num_kv_heads, dh)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, _as_b(positions, B), cfg.rope_theta)
        k = apply_rope(k, _as_b(kv_positions, B), cfg.rope_theta)
    return q, k, v


def attention(params, cfg, x, *, positions, kind: str = "causal",
              window: int = 0, prefix_len=None, kv_x=None, kv_positions=None,
              use_rope: bool = True, block_q: int = 0, block_kv: int = 0,
              return_kv: bool = False):
    """Full-sequence attention. x: (B, S, d_in) -> (B, S, out_dim)."""
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(params, cfg, x, kv_x, positions, kv_positions, use_rope)
    o = sdpa(q, k, v, q_pos=positions, kv_pos=kv_positions, kind=kind,
             window=window, prefix_len=prefix_len,
             softcap=cfg.attn_logit_softcap,
             block_q=block_q, block_kv=block_kv)
    B, Sq = x.shape[0], x.shape[1]
    y = dense(params["wo"], o.reshape(B, Sq, -1))
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# Decode with ring-buffer KV cache
# ---------------------------------------------------------------------------

def kv_cache_int8() -> bool:
    """int8 KV-cache quantization (per-slot-per-head absmax scales): halves
    the decode memory term — §Perf iteration 11. Env-gated so baselines
    stay reproducible."""
    import os
    return os.environ.get("REPRO_KV_INT8", "0") == "1"


def init_attn_cache(batch: int, cache_len: int, num_kv_heads: int, head_dim: int,
                    dtype=jnp.bfloat16):
    if kv_cache_int8():
        return {
            "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim),
                           jnp.int8),
            "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim),
                           jnp.int8),
            "k_scale": jnp.zeros((batch, cache_len, num_kv_heads, 1),
                                 jnp.bfloat16),
            "v_scale": jnp.zeros((batch, cache_len, num_kv_heads, 1),
                                 jnp.bfloat16),
            "kv_pos": jnp.full((batch, cache_len), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
        "kv_pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def map_kv_pos(fn, cache):
    """``fn`` applied to every ``kv_pos`` leaf of a cache pytree (rings and
    paged pools of any layout: GQA or latent, stacked or nested)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: fn(x) if getattr(path[-1], "key", None) == "kv_pos"
        else x, cache)


def _quant_kv(x):
    """(B, S, Hk, dh) -> (int8 codes, bf16 scales (B,S,Hk,1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _dequant_kv(q, scale, dtype):
    return (q.astype(jnp.float32) *
            scale.astype(jnp.float32)).astype(dtype)


def _decode_block_kv() -> int:
    """KV block streamed per decode step through the fused path (0 == derive
    from the cache length; the Pallas kernel additionally splits blocks
    across KV splits).  Read per call so REPRO_DECODE_BLOCK_KV behaves like
    every other REPRO_ flag."""
    return int(os.environ.get("REPRO_DECODE_BLOCK_KV", "0"))


def _slot_writers(kv_pos, pos, B: int, *, block_tbl=None, ring_len=None,
                  layer=None):
    """(upd, upd_pos): write one new entry a row into a cache leaf, and the
    row positions into ``kv_pos``.  ``pos`` is a scalar (synchronous batch)
    or (B,) per-row positions, -1 for an inactive row.

    Paged pools (``block_tbl`` (B, T) + ``ring_len``): leaves are (n_blocks,
    block_size, ...), or layer-stacked (L, n_blocks, block_size, ...) and
    written at ``layer``, in place; a row's ring slot ``pos % ring_len``
    resolves through its table row.  Writes scatter with ``mode="drop"``:
    inactive rows and ungranted blocks index out of bounds and write
    nothing.  Rings: leaves are (B, cache_len, ...); an inactive ragged row
    keeps its old slot contents."""
    if block_tbl is not None:
        n_blocks, bs = kv_pos.shape[-2:]
        active = pos >= 0
        rl = jnp.asarray(ring_len, jnp.int32)
        slot = jnp.mod(jnp.maximum(pos, 0), rl)             # (B,) ring slot
        pb = block_tbl[jnp.arange(B), slot // bs]           # physical block
        off = slot % bs
        # out-of-bounds index == dropped write (inactive / ungranted rows)
        widx = jnp.where(active & (pb >= 0), pb, n_blocks)
        at = (widx, off) if layer is None else (layer, widx, off)

        def upd(buf, val):
            return buf.at[at].set(val[:, 0].astype(buf.dtype), mode="drop")

        def upd_pos(buf):
            return buf.at[at].set(pos, mode="drop")
        return upd, upd_pos
    cache_len = kv_pos.shape[1]
    if pos.ndim == 1:
        # per-row ring slot: every row writes its own slot; inactive rows
        # (pos < 0) keep the old slot contents and stay fully masked
        active = pos >= 0
        slots = jnp.mod(jnp.maximum(pos, 0), cache_len)        # (B,)
        bidx = jnp.arange(B)

        def upd(buf, val):
            old = buf[bidx, slots]                             # (B, ...)
            keep = active.reshape((B,) + (1,) * (old.ndim - 1))
            return buf.at[bidx, slots].set(
                jnp.where(keep, val[:, 0].astype(buf.dtype), old))

        def upd_pos(buf):
            old = buf[bidx, slots]
            return buf.at[bidx, slots].set(jnp.where(active, pos, old))
        return upd, upd_pos
    slot = jnp.mod(pos, cache_len)

    def upd(buf, val):
        return jax.lax.dynamic_update_slice_in_dim(
            buf, val.astype(buf.dtype), slot, axis=1)

    def upd_pos(buf):
        return jax.lax.dynamic_update_slice_in_dim(
            buf, jnp.full((B, 1), pos, jnp.int32), slot, axis=1)
    return upd, upd_pos


def attn_decode(params, cfg, x_t, cache, pos, *, window: int = 0,
                kind: str = "causal", prefix_len=None, block_tbl=None,
                ring_len=None, layer=None):
    """One decode step.

    x_t: (B, 1, d_in); ``pos`` scalar int32 (synchronous batch decode) OR
    (B,) int32 per-row positions (ragged continuous-batching decode: every
    row advances independently; ``pos[b] == -1`` marks row ``b`` inactive —
    its ring slot is left untouched and its output is fully masked).
    cache: ring buffer from ``init_attn_cache`` (cache_len == window for SWA
    layers, == max_seq for global layers).  Returns (y_t, new_cache).

    Paged mode (``block_tbl`` (B, T) + ``ring_len``): the cache leaves are a
    shared block pool — k/v (n_blocks, block_size, Hk, dh), kv_pos
    (n_blocks, block_size) — and each row's ring slot ``pos % ring_len``
    resolves through its block-table row to a physical pool slot.  Writes
    scatter with ``mode="drop"``: inactive rows and ungranted blocks index
    out of bounds and write nothing, so no freeze pass over the pool is
    needed (allocator invariant: live requests never share a block).
    ``layer`` given: every pool leaf is layer-stacked (L, n_blocks, ...),
    and the step writes and reads layer ``layer`` in place (the decode
    layer scan carries the whole pool; no layer is sliced out of it).

    Attention over the cache goes through the fused flash-decode path
    (``repro.kernels.ops.flash_decode``): Pallas kernel on TPU /
    REPRO_FORCE_KERNELS=1 (block tables ride a scalar-prefetch operand),
    wide/blockwise XLA fallback elsewhere — the kernel and the scan
    fallback dequantize the int8 cache tile-by-tile inside the streamed
    pass (the fallback's short-cache wide form, <= REPRO_DECODE_WIDE_MAX
    slots, trades one O(S) dequant copy for measured speed).  Under an
    active
    mesh with a seq-sharded cache (REPRO_CACHE_SHARD=seq) the step runs
    per-shard with a psum-style combine over ``model``
    (``repro.dist.decode``; paged pools shard the block axis).
    REPRO_FLASH_DECODE=0 restores the legacy dequantize-then-sdpa step.
    """
    B = x_t.shape[0]
    paged = block_tbl is not None
    int8 = "k_scale" in cache
    pos = jnp.asarray(pos, jnp.int32)
    ragged = pos.ndim == 1
    if paged and not ragged:
        raise ValueError("paged decode requires per-row (B,) positions")
    cache_len = None if paged else cache["k"].shape[1]
    pos_b = pos[:, None] if ragged else jnp.full((B, 1), pos, jnp.int32)
    q, k_t, v_t = _project_qkv(
        params, cfg, x_t, None,
        positions=pos_b, kv_positions=pos_b, use_rope=True)
    upd, upd_pos = _slot_writers(cache["kv_pos"], pos, B,
                                 block_tbl=block_tbl, ring_len=ring_len,
                                 layer=layer)
    if paged:
        n_blocks = cache["kv_pos"].shape[-2]

    new_cache = {}
    if int8:
        kq, ks = _quant_kv(k_t)
        vq, vs = _quant_kv(v_t)
        new_cache["k"] = upd(cache["k"], kq)
        new_cache["v"] = upd(cache["v"], vq)
        new_cache["k_scale"] = upd(cache["k_scale"], ks)
        new_cache["v_scale"] = upd(cache["v_scale"], vs)
    else:
        new_cache["k"] = upd(cache["k"], k_t)
        new_cache["v"] = upd(cache["v"], v_t)
    pos_new = upd_pos(cache["kv_pos"])
    new_cache["kv_pos"] = pos_new

    from repro.kernels import ops
    if ops.flash_decode_enabled():
        from repro.dist.decode import seq_shard_mesh, sharded_flash_decode
        kw = dict(k_scale=new_cache.get("k_scale"),
                  v_scale=new_cache.get("v_scale"),
                  kind=kind, window=window, prefix_len=prefix_len,
                  softcap=cfg.attn_logit_softcap,
                  block_kv=_decode_block_kv())  # kernels clamp to cache_len
        if paged:
            kw.update(block_tables=block_tbl, layer=layer)
        # sharded layout: slot axis for rings, block axis for paged pools
        mesh = seq_shard_mesh(n_blocks if paged else cache_len)
        if mesh is not None:
            o = sharded_flash_decode(q, new_cache["k"], new_cache["v"],
                                     pos_new, pos, mesh, **kw)
        else:
            o = ops.flash_decode(q, new_cache["k"], new_cache["v"],
                                 pos_new, pos, **kw)
    else:
        # legacy path: full-cache dequant + naive sdpa (A/B baseline only;
        # the blockwise scales-aware sdpa is reachable via block_kv > 0)
        k_leg, v_leg, pos_leg = new_cache["k"], new_cache["v"], pos_new
        ks_leg = new_cache.get("k_scale")
        vs_leg = new_cache.get("v_scale")
        if paged:
            from repro.kernels.flash_decode import paged_gather
            k_leg, v_leg, pos_leg, ks_leg, vs_leg = paged_gather(
                k_leg, v_leg, pos_leg, ks_leg, vs_leg, block_tbl, layer)
        if int8:
            k_full = _dequant_kv(k_leg, ks_leg, q.dtype)
            v_full = _dequant_kv(v_leg, vs_leg, q.dtype)
        else:
            k_full, v_full = k_leg, v_leg
        o = sdpa(q, k_full, v_full,
                 q_pos=pos_b, kv_pos=pos_leg,
                 kind=kind, window=window, prefix_len=prefix_len,
                 softcap=cfg.attn_logit_softcap)
    y = dense(params["wo"], o.reshape(B, 1, -1))
    return y, new_cache


def attn_cross_decode(params, cfg, x_t, mem_k, mem_v, mem_pos):
    """Cross-attention decode step against fixed encoder memory (k/v
    precomputed at prefill).  Same fused decode path as self-attention
    (kind="full": every valid memory slot participates)."""
    B = x_t.shape[0]
    dh = cfg.resolved_head_dim()
    q = dense(params["wq"], x_t).reshape(B, 1, cfg.num_heads, dh)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
    from repro.kernels import ops
    if ops.flash_decode_enabled():
        return dense(params["wo"], ops.flash_decode(
            q, mem_k, mem_v, mem_pos, jnp.zeros((), jnp.int32),
            kind="full", softcap=cfg.attn_logit_softcap,
            block_kv=_decode_block_kv()).reshape(B, 1, -1))
    o = sdpa(q, mem_k, mem_v,
             q_pos=jnp.zeros((B, 1), jnp.int32), kv_pos=mem_pos,
             kind="full", softcap=cfg.attn_logit_softcap)
    return dense(params["wo"], o.reshape(B, 1, -1))


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434)
# ---------------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, mla) -> np.ndarray:
    """Rotary frequencies of a ``dim``-wide rotary part: plain RoPE, or
    YaRN's blend (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``) when
    ``mla.rope_factor`` > 1 — frequencies past the correction range of
    ``beta_fast``..``beta_slow`` rotations over the original context are
    divided by the factor, those before it kept, a linear ramp between."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    f = mla.rope_factor
    if f <= 1:
        return extra
    inter = 1.0 / (f * theta ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))

    def corr(rotations):
        return (dim * math.log(mla.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(mla.beta_fast)), 0)
    high = min(math.ceil(corr(mla.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def mla_softmax_scale(cfg) -> float:
    """q_head_dim ** -0.5, times YaRN's mscale(mscale_all_dim) squared."""
    m = cfg.mla
    s = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if m.mscale_all_dim:
        s *= _yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2
    return s


def rope_pairs(x, positions, cfg):
    """RoPE on interleaved pairs, the published DeepSeek-V2 layout: pair
    (x[2i], x[2i+1]) turns by ``position * inv_freq[i]``.  x: (B, S, H, D);
    positions (B, S).  The result keeps the pair layout (the published
    code de-interleaves first; scores, the only use, are the same)."""
    m = cfg.mla
    D = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(D, cfg.rope_theta, m))
    mscale = (_yarn_mscale(m.rope_factor, m.mscale)
              / _yarn_mscale(m.rope_factor, m.mscale_all_dim))
    ang = positions[..., None, None].astype(jnp.float32) * inv  # (B,S,1,D/2)
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    xp = x.astype(jnp.float32).reshape(x.shape[:-1] + (D // 2, 2))
    x0, x1 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def init_mla(key, cfg, dtype=jnp.float32):
    """q, kv_a (latent + rope key), the latent's RMSNorm, kv_b, o."""
    m, H, d = cfg.mla, cfg.num_heads, cfg.d_model
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, d, H * qd, dtype),
        "wkv_a": init_dense(ka, d, m.entry_dim, dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank),
        "wkv_b": init_dense(kb, m.kv_lora_rank,
                            H * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "wo": init_dense(ko, H * m.v_head_dim, d, dtype),
    }


def init_mla_cache(batch: int, cache_len: int, cache_dim: int,
                   dtype=jnp.bfloat16):
    """One latent entry a position (normed latent, roped key, zero pad to
    ``MLAConfig.cache_dim``), no separate V; as a paged pool ``batch`` is
    the block count and ``cache_len`` the block size."""
    return {
        "latent": jnp.zeros((batch, cache_len, cache_dim), dtype),
        "kv_pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def _mla_query(params, cfg, x, positions):
    """(q_nope (B,S,H,nope), roped q_pe (B,S,H,rope))."""
    m = cfg.mla
    B, S = x.shape[:2]
    q = dense(params["wq"], x).reshape(
        B, S, cfg.num_heads, m.qk_nope_head_dim + m.qk_rope_head_dim)
    return (q[..., :m.qk_nope_head_dim],
            rope_pairs(q[..., m.qk_nope_head_dim:], positions, cfg))


def _mla_entry(params, cfg, x, positions):
    """The cache entry (B, S, cache_dim): the RMS-normed latent, the roped
    key every head shares, zeros."""
    m = cfg.mla
    r = m.kv_lora_rank
    ckv = dense(params["wkv_a"], x)
    c = rmsnorm(params["kv_norm"], ckv[..., :r], cfg.norm_eps)
    kr = rope_pairs(ckv[..., None, r:], positions, cfg)[..., 0, :]
    pad = jnp.zeros(c.shape[:-1] + (m.cache_dim - m.entry_dim,), c.dtype)
    return jnp.concatenate([c, kr.astype(c.dtype), pad], -1)


def mla_attention(params, cfg, x, *, positions, window: int = 0,
                  block_q: int = 0, block_kv: int = 0):
    """Causal latent attention over a whole sequence, not absorbed: each
    head's key is [kv_b's k_nope of the latent, the shared roped key], its
    value kv_b's v.  x: (B, S, d) -> (y (B, S, d), cache entries (B, S,
    rank + rope))."""
    with jax.named_scope("obs.mla"):
        m, H = cfg.mla, cfg.num_heads
        B, S = x.shape[:2]
        pos = _as_b(positions, B)
        q_nope, q_pe = _mla_query(params, cfg, x, pos)
        entry = _mla_entry(params, cfg, x, pos)
        r, nope = m.kv_lora_rank, m.qk_nope_head_dim
        kv = dense(params["wkv_b"], entry[..., :r]).reshape(
            B, S, H, nope + m.v_head_dim)
        k_pe = jnp.broadcast_to(entry[..., None, r:m.entry_dim],
                                (B, S, H, m.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], -1)
        k = jnp.concatenate([kv[..., :nope], k_pe.astype(kv.dtype)], -1)
        o = sdpa(q, k, kv[..., nope:], q_pos=pos, kv_pos=pos, kind="causal",
                 window=window, block_q=block_q, block_kv=block_kv,
                 scale=mla_softmax_scale(cfg))
        return dense(params["wo"], o.reshape(B, S, -1)), entry


def mla_decode(params, cfg, x_t, cache, pos, *, window: int = 0,
               block_tbl=None, ring_len=None, layer=None):
    """One decode step of latent attention, absorbed: kv_b's key half folds
    into the query (q_nope W_UK gives a ``kv_lora_rank``-wide latent query
    beside the roped one), so every head scores the cached entries
    directly; the output is p.c, then kv_b's value half W_UV, then o.
    ``cache``: ``init_mla_cache`` leaves, a ring or (``block_tbl`` +
    ``ring_len``) a paged pool; positions, writes and ``layer`` as
    ``attn_decode``.  Returns (y_t, new_cache)."""
    from repro.kernels import ops
    with jax.named_scope("obs.mla"):
        m, H = cfg.mla, cfg.num_heads
        B = x_t.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        if block_tbl is not None and pos.ndim != 1:
            raise ValueError("paged decode requires per-row (B,) positions")
        pos_b = (pos[:, None] if pos.ndim == 1
                 else jnp.full((B, 1), pos, jnp.int32))
        q_nope, q_pe = _mla_query(params, cfg, x_t, pos_b)
        upd, upd_pos = _slot_writers(cache["kv_pos"], pos, B,
                                     block_tbl=block_tbl, ring_len=ring_len,
                                     layer=layer)
        new_cache = {
            "latent": upd(cache["latent"],
                          _mla_entry(params, cfg, x_t, pos_b)),
            "kv_pos": upd_pos(cache["kv_pos"])}
        r, nope = m.kv_lora_rank, m.qk_nope_head_dim
        dtype = q_nope.dtype
        w_b = params["wkv_b"]["w"].reshape(r, H, nope + m.v_head_dim)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope,
                           w_b[..., :nope].astype(dtype),
                           preferred_element_type=jnp.float32)
        # scored against whole entries: the zero pad meets zeros
        q = jnp.concatenate([q_lat.astype(dtype), q_pe.astype(dtype),
                             jnp.zeros(q_pe.shape[:-1]
                                       + (m.cache_dim - m.entry_dim,),
                                       dtype)], -1)
        kw = ({"block_tables": block_tbl, "layer": layer}
              if block_tbl is not None else {})
        o_lat = ops.flash_decode(q, new_cache["latent"], None,
                                 new_cache["kv_pos"], pos, window=window,
                                 scale=mla_softmax_scale(cfg), v_dim=r, **kw)
        o = jnp.einsum("bshr,rhv->bshv", o_lat,
                       w_b[..., nope:].astype(dtype),
                       preferred_element_type=jnp.float32).astype(dtype)
        return dense(params["wo"], o.reshape(B, 1, -1)), new_cache
