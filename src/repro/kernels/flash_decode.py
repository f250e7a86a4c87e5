"""Fused flash-decode Pallas TPU kernels over ring or paged block KV caches.

One decode step: G grouped queries per KV head attend to every valid slot of
the cache, with a running (m, l, acc) online-softmax state in f32.  Fused
into the streamed pass, in both launches:
  - int8 -> f32 dequantization from the per-slot absmax scales
    (``REPRO_KV_INT8`` caches), so the quantized cache is never materialized
    in HBM at full precision; the scales multiply score/probability columns
    after the matmuls instead of the (slots, D) tiles before them;
  - ring-buffer validity / causal / prefix / sliding-window masking from the
    absolute slot positions ``kv_pos`` (slot position -1 == empty);
  - GQA query-group packing: the G queries of one KV head are one MXU
    matmul instead of G vector products.

Two cache layouts, two launches with a kernel body each:

  * contiguous ring (training / fixed-batch ``generate``): k/v are
    (B, S, Hk, dh) per-request rings.  Grid (batch, kv_head, KV blocks): each
    program streams one ``block_kv`` tile through VMEM, and the KV axis is
    carved into ``n_splits`` independent splits whose (m, l, acc) partials a
    final combine (plain jnp) merges.
  * paged block pool (the serving engine's layout): k/v are
    (n_blocks, block_size, Hk, dh) — ONE pool shared by every request — and
    ``block_tables`` (B, T) maps each request's logical block j to a
    physical pool block (-1 == not granted).  Grid (B,): one program a
    request row, which loops over the row's *live* table entries only —
    ``n_live`` = 1 + its last granted entry (0 for a row with none, such as
    an idle lane), a scalar-prefetch operand next to the table.  Entries
    past ``n_live`` are ungranted by definition, so skipping them is exact;
    holes below it stay masked.  Each loop step gathers P = 256 /
    block_size table-named pages (16 at 16-slot blocks; at most T) with
    one DMA a page from the pool, left in HBM (``memory_space=pl.ANY``) in
    its stored shape, into a VMEM slab of all Hk heads, double-buffered so
    step i+1's pages land while step i computes.  The heads run as batched
    matmuls with one (Hk, G, .) softmax state.  QK^T takes bf16 operands
    when the query and the stored KV (or int8 codes) are bf16 — the
    products are exact — and ``P.V`` keeps p in f32 (HIGHEST precision: a
    default-precision f32 dot on the MXU rounds its operands to bf16).
    Each page's slot positions (and, for int8 pools, its per-head scales)
    come by DMA with it, from a view of the layer's per-slot rows laid out
    128 lanes wide (``_lane_rows``: 128 / block_size pages side by side, a
    narrower DMA does not lower); lane rotations then set the step's pages
    side by side in one lane-dense row, -1 where an entry is ungranted or
    past ``n_live``.  Tables are READ-ONLY to the kernel, so one physical
    block may appear in many tables at once (copy-on-write prefix
    sharing): every sharer streams the same page, and slots a sharer
    hasn't logically reached are excluded by the causal/ring masks, not by
    table bookkeeping.  No split-KV here: a
    v5e chip has one TensorCore, so splits would fill nothing, and the
    (m, l, acc) partials contract stays for ``return_partials`` alone.

TPU tiling: every BlockSpec block's last two dims are either tile multiples
or the array's full extent, which is what the TPU lowering accepts.  Per-row
query positions and prefix lengths ride scalar prefetch (SMEM); the ring
launch lays per-slot rows out as ``(rows, 1, slots)`` so a tile is one
``(1, block)`` row.

``paged_block_copy`` is the pool's copy-on-write data move: one physical
block's tile duplicated to another block across all layers of a
layer-stacked pool leaf, with the src/dst pair riding scalar prefetch so
the copy is a pure per-layer DMA (no gather of the pool).

Block policy (``block_kv``/``n_splits`` <= 0 selects it, ring launch): tile
and split counts are derived from the cache length instead of fixed
defaults — short caches get fewer, wider tiles; long caches cap the tile at
1024 and let ``_pick_splits`` choose the splits.  ``flash_decode_xla`` is
the same algorithm without Pallas, with a measured two-regime policy: up to
``REPRO_DECODE_WIDE_MAX`` (4096) slots a single-pass "wide" form (int8
codes transposed *before* dequant — half the transpose traffic of
dequant-then-transpose, the reason the old blockwise scan lost to naive
sdpa at 4k; it does materialize one O(S) f32 copy, the accepted trade at
short S), above it a ``jax.lax.scan`` over 2048-slot tiles with in-scan
dequant (O(block) temporaries).  All paths support ``return_partials`` for
the sequence-sharded path (``repro.dist.decode``): a shard computes local
(m, l, acc) over its slots and the cross-shard combine is a pmax/psum over
the ``model`` axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite mask fill: -inf poisons the online-softmax recurrences (exp(-inf -
# -inf) = nan) on fully-masked blocks; with a finite floor the masked
# probabilities are zeroed explicitly and every carry stays finite.
_NEG = -1e30

# XLA-fallback policy boundary: at/below this cache length the single-pass
# wide form beats the blockwise scan (measured on the kernels bench: the
# scan's per-block overhead + full-cache transpose lost to naive sdpa at 4k,
# 0.5x); above it the scan's O(block) temporaries win (1.4x at 32k).  The
# wide form deliberately trades an O(S) f32 temporary for speed, so the
# boundary stays at the measured 4k crossover and is env-tunable
# (REPRO_DECODE_WIDE_MAX=0 restores scan-always for memory-tight hosts).
_SCAN_BLOCK_KV = 2048


def _wide_max_s() -> int:
    import os
    return int(os.environ.get("REPRO_DECODE_WIDE_MAX", "4096"))


def _slot_mask(kp, qp, plen, *, kind: str, window: int):
    """Boolean keep-mask over KV slots from absolute positions.

    kp: (..., block) int32 slot positions (-1 == empty ring slot);
    qp / plen: scalars (or broadcastable) — the query position and prefix
    length.  Mirrors repro.models.layers.attention._mask for Sq == 1.
    """
    valid = kp >= 0
    if kind == "causal":
        m = kp <= qp
    elif kind == "prefix":
        m = (kp <= qp) | (kp < plen)
    elif kind == "full":
        m = jnp.ones_like(valid)
    else:
        raise ValueError(kind)
    if window > 0 and kind != "full":
        m = m & (qp - kp < window)
    return m & valid


def _pick_splits(n_blocks: int, requested: int) -> int:
    """Largest split count <= requested that divides the block count."""
    n = requested or (8 if n_blocks >= 32 else 4 if n_blocks >= 8 else 1)
    n = max(1, min(n, n_blocks))
    while n_blocks % n:
        n -= 1
    return n


def _auto_block_kv(S: int) -> int:
    """Pallas KV tile from the cache length: target ~16 tiles (split-KV
    parallelism) without dropping below the 128-lane tile or ballooning
    VMEM past a 1024-slot slab."""
    per = -(-S // 16)
    per = -(-per // 128) * 128
    return int(max(128, min(1024, per)))


def _combine(m, l, acc, axis: int):
    """Merge independent online-softmax partials along ``axis``:
    out = sum_i exp(m_i - m*) acc_i / sum_i exp(m_i - m*) l_i."""
    m_g = m.max(axis=axis, keepdims=True)
    w = jnp.exp(m - m_g)
    l_tot = (l * w).sum(axis=axis)
    acc_tot = (acc * w).sum(axis=axis)
    return acc_tot / jnp.maximum(l_tot, 1e-30)


def paged_gather(k, v, kv_pos, k_scale, v_scale, block_tables):
    """Materialize the (B, T*block_size) logical cache view of a paged pool.

    k/v: (n_blocks, bs, Hk, dh) pool; block_tables: (B, T) physical block
    ids (-1 == ungranted — its slots come back with position -1, i.e.
    masked).  The gathered view is bit-identical to the contiguous ring it
    replaces when T*bs equals the ring length, which is what keeps paged
    greedy decode exactly equal to the contiguous pool's.  (Off-TPU
    fallback + oracle only — the Pallas kernel indexes the pool in place.)
    """
    tbl = jnp.asarray(block_tables, jnp.int32)
    B, T = tbl.shape
    nb = k.shape[0]
    safe = jnp.clip(tbl, 0, nb - 1)

    def g(x):
        y = x[safe]                              # (B, T, bs, ...)
        return y.reshape((B, T * x.shape[1]) + x.shape[2:])

    kv_pos_g = jnp.where(tbl[:, :, None] >= 0, kv_pos[safe], -1)
    kv_pos_g = kv_pos_g.reshape(B, T * kv_pos.shape[1])
    ks = g(k_scale) if k_scale is not None else None
    vs = g(v_scale) if v_scale is not None else None
    return g(k), g(v), kv_pos_g, ks, vs


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _kernel(*refs, bps: int, kind: str, window: int, softcap: float,
            scale: float, quantized: bool):
    # scalar-prefetch operands (SMEM) come first: per-row query position and
    # prefix length
    qpos_ref, plen_ref, *refs = refs
    if quantized:
        (q_ref, k_ref, v_ref, kpos_ref, ks_ref, vs_ref,
         o_m, o_l, o_acc, m_s, l_s, acc_s) = refs
    else:
        (q_ref, k_ref, v_ref, kpos_ref,
         o_m, o_l, o_acc, m_s, l_s, acc_s) = refs
    b = pl.program_id(0)
    j = pl.program_id(2)
    local = jax.lax.rem(j, bps)

    @pl.when(local == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, D)
    k = k_ref[0].astype(jnp.float32)                 # (block_kv, D)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if quantized:
        # fused int8 dequant: a per-slot scale multiplies a column of the
        # scores (and a column of p below), so the (1, block_kv) scale rows
        # apply after the matmuls instead of to every (block_kv, D) tile
        s = s * ks_ref[0, 0].astype(jnp.float32)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    kp = kpos_ref[0]                                 # (1, block_kv)
    mask = _slot_mask(kp, qpos_ref[b], plen_ref[b],
                      kind=kind, window=window)      # (1, block_kv)
    s = jnp.where(mask, s, _NEG)

    m_prev = m_s[...]                                # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)     # (G, block_kv)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + p.sum(-1, keepdims=True)
    pv = p * vs_ref[0, 0].astype(jnp.float32) if quantized else p
    acc_s[...] = acc_s[...] * corr + jnp.dot(
        pv, v, preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(local == bps - 1)
    def _flush():
        o_m[0, 0, 0] = m_s[...]
        o_l[0, 0, 0] = l_s[...]
        o_acc[0, 0, 0] = acc_s[...]


def _pack_queries(q, Hk: int):
    """(B, 1, H, D) -> (B, Hk, G_pad, D): GQA groups packed per KV head, G
    padded to the f32 sublane count."""
    B, _, H, D = q.shape
    G = H // Hk
    qg = q.reshape(B, Hk, G, D)
    g_pad = -G % 8
    if g_pad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad), (0, 0)))
    return qg, G, G + g_pad


def _pad_inputs(q, k, v, kv_pos, k_scale, v_scale, block_kv: int):
    """Pad the KV axis to a block multiple (padded slots get position -1 so
    the validity mask drops them) and pack queries per KV head."""
    B, S, Hk, D = k.shape
    qg, G, G_pad = _pack_queries(q, Hk)
    s_pad = -S % block_kv
    if s_pad:
        pad4 = ((0, 0), (0, s_pad), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad4), jnp.pad(v, pad4)
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, s_pad)), constant_values=-1)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, pad4)
            v_scale = jnp.pad(v_scale, pad4)
    return qg, k, v, kv_pos, k_scale, v_scale, G, G_pad


def _broadcast_pos(x, batch: int):
    x = jnp.zeros((), jnp.int32) if x is None else jnp.asarray(x, jnp.int32)
    return jnp.broadcast_to(x.reshape(-1, 1) if x.ndim else x,
                            (batch, 1)).astype(jnp.int32)


def _row_pos(x, batch: int):
    """Scalar or (B,) position -> the (B,) int32 scalar-prefetch row."""
    return _broadcast_pos(x, batch)[:, 0]


def _slot_rows(x):
    """(R, S) per-slot leaf -> (R, 1, S): a (1, 1, tile) block then ends in
    the full unit dim and a lane-aligned (or full) slot extent, the tiling
    the TPU lowering accepts for per-slot rows."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def _scale_rows(x):
    """(R, S, Hk, 1) int8 absmax scales -> (R, Hk, 1, S): one (1, S) row of
    per-slot scales per KV head, tiled like ``_slot_rows``."""
    return x[..., 0].swapaxes(1, 2)[:, :, None, :]


def _partial_outputs(B: int, Hk: int, n_splits: int, G_pad: int, D: int,
                     bps: int):
    """(out_specs, out_shape, scratch_shapes) for the ring launch's
    per-split (m, l, acc) partials (the index_map takes the trailing
    scalar-prefetch args as *_)."""
    def idx(b, h, j, *_, _bps=bps):
        return (b, h, j // _bps, 0, 0)

    out_specs = [
        pl.BlockSpec((1, 1, 1, G_pad, 1), idx),
        pl.BlockSpec((1, 1, 1, G_pad, 1), idx),
        pl.BlockSpec((1, 1, 1, G_pad, D), idx),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, Hk, n_splits, G_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, Hk, n_splits, G_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, Hk, n_splits, G_pad, D), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((G_pad, 1), jnp.float32),
        pltpu.VMEM((G_pad, 1), jnp.float32),
        pltpu.VMEM((G_pad, D), jnp.float32),
    ]
    return out_specs, out_shape, scratch


def _finish(m, l, acc, G: int, q, return_partials: bool):
    """Slice off G padding and either combine splits or hand back partials
    (axis 2 is the split axis)."""
    m, l, acc = m[:, :, :, :G], l[:, :, :, :G], acc[:, :, :, :G]
    if return_partials:
        m_loc = m.max(axis=2)
        w = jnp.exp(m - m.max(axis=2, keepdims=True))
        return m_loc, (l * w).sum(axis=2), (acc * w).sum(axis=2)
    out = _combine(m, l, acc, axis=2)                # (B, Hk, G, D)
    B, Hk, _, D = out.shape
    return out.reshape(B, 1, Hk * G, D).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("kind", "window", "softcap", "block_kv",
                              "n_splits", "interpret", "return_partials"))
def flash_decode(q, k, v, kv_pos, q_pos, *, k_scale=None, v_scale=None,
                 kind: str = "causal", window: int = 0, prefix_len=None,
                 softcap: float = 0.0, block_kv: int = 0, n_splits: int = 0,
                 block_tables=None, interpret: bool = False,
                 return_partials: bool = False):
    """One fused decode step against the ring (or paged) cache.

    q: (B, 1, H, D); k, v: (B, S, Hk, D) ring buffers, or — with
    ``block_tables`` (B, T) — an (n_blocks, block_size, Hk, D) shared pool
    (int8 when ``k_scale``/``v_scale`` absmax scales are given, shaped like
    k/v with a trailing 1); kv_pos: (B, S) / (n_blocks, block_size) absolute
    slot positions (-1 == empty); q_pos: scalar or (B,) query position.
    ``block_kv``/``n_splits`` <= 0 derive the ring launch's tile/split
    counts from the cache length; the paged launch ignores both (its steps
    are whole pool pages, and it does not split).  Returns
    (B, 1, H, D) in q.dtype, or the raw f32 partials (m, l, acc) of shapes
    (B, Hk, G, 1)/(B, Hk, G, 1)/(B, Hk, G, D) when ``return_partials``
    (sequence-sharded combine, repro.dist.decode).
    """
    if block_tables is not None:
        return _flash_decode_paged(
            q, k, v, kv_pos, block_tables, q_pos, k_scale=k_scale,
            v_scale=v_scale, kind=kind, window=window, prefix_len=prefix_len,
            softcap=softcap, interpret=interpret,
            return_partials=return_partials)
    B, S, Hk, D = k.shape
    kv_pos = jnp.asarray(kv_pos, jnp.int32)
    if kv_pos.ndim == 1:
        kv_pos = jnp.broadcast_to(kv_pos[None], (B, S))
    if block_kv <= 0:
        block_kv = _auto_block_kv(S)
    block_kv = min(block_kv, -(-S // 128) * 128)
    quantized = k_scale is not None
    qg, k, v, kv_pos, k_scale, v_scale, G, G_pad = _pad_inputs(
        q, k, v, kv_pos, k_scale, v_scale, block_kv)
    S_pad = k.shape[1]
    n_blocks = S_pad // block_kv
    n_splits = _pick_splits(n_blocks, n_splits)
    bps = n_blocks // n_splits

    # (B, S, Hk, D) -> (B, S, Hk*D): free reshape that turns each per-head
    # KV tile into a contiguous, well-tiled (block_kv, D) slab.
    kr = k.reshape(B, S_pad, Hk * D)
    vr = v.reshape(B, S_pad, Hk * D)

    in_specs = [
        pl.BlockSpec((1, 1, G_pad, D), lambda b, h, j, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, block_kv, D), lambda b, h, j, *_: (b, j, h)),
        pl.BlockSpec((1, block_kv, D), lambda b, h, j, *_: (b, j, h)),
        pl.BlockSpec((1, 1, block_kv), lambda b, h, j, *_: (b, 0, j)),
    ]
    args = [qg, kr, vr, _slot_rows(kv_pos)]
    if quantized:
        scale_spec = pl.BlockSpec((1, 1, 1, block_kv),
                                  lambda b, h, j, *_: (b, h, 0, j))
        in_specs += [scale_spec, scale_spec]
        args += [_scale_rows(k_scale), _scale_rows(v_scale)]

    out_specs, out_shape, scratch = _partial_outputs(B, Hk, n_splits, G_pad,
                                                     D, bps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hk, n_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch)
    m, l, acc = pl.pallas_call(
        functools.partial(_kernel, bps=bps, kind=kind, window=window,
                          softcap=softcap, scale=D ** -0.5,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(_row_pos(q_pos, B), _row_pos(prefix_len, B), *args)
    return _finish(m, l, acc, G, q, return_partials)


# Slots the paged launch gathers a loop step.  At qwen3 widths a step's K
# and V are 1 MiB of bf16 (2 MiB double-buffered), well inside VMEM, and
# enough bytes to hide the step's fixed cost: on a v5e chip the kernel took
# 14-18% longer at 128 slots, and no less time at 512 than at 256.
_PAGED_STEP_SLOTS = 256


def _pages_per_step(block_size: int, table_width: int) -> int:
    """Pool pages one step of the paged launch gathers:
    ``_PAGED_STEP_SLOTS`` slots' worth, at most the table's width."""
    return max(1, min(table_width, _PAGED_STEP_SLOTS // block_size))


def live_pages(tbl, xp=jnp):
    """Table entries the paged launch visits, per row: 1 + the row's last
    granted logical block, 0 for a row with none (such as an idle lane).
    ``xp`` is the array module (``numpy`` for a host-side table)."""
    T = tbl.shape[1]
    return xp.max(xp.where(tbl >= 0, xp.arange(1, T + 1), 0), axis=1)


def _pages_per_row(block_size: int) -> int:
    """Pool pages whose per-slot rows share one 128-lane row of the
    kernel's metadata view (``_lane_rows``)."""
    return 128 // block_size if 128 % block_size == 0 else 1


def _lane_rows(x, fill):
    """Per-slot pool metadata ``(nb, bs, R)`` -> ``(rows, R, W)``: the slots
    of ``_pages_per_row`` consecutive pool pages side by side along ``W``
    (a 128 multiple) lanes, one lane row per metadata channel, so a page's
    DMA moves whole lane tiles (a narrower DMA does not lower).  One layout
    change of the layer's metadata, not a gather through the table."""
    nb, bs, R = x.shape
    k = _pages_per_row(bs)
    W = -(-(k * bs) // 128) * 128
    x = jnp.pad(x, ((0, -nb % k), (0, 0), (0, 0)), constant_values=fill)
    x = x.reshape(-1, k, bs, R).transpose(0, 3, 1, 2).reshape(-1, R, k * bs)
    return jnp.pad(x, ((0, 0), (0, 0), (0, W - k * bs)),
                   constant_values=fill)


def _place(rows, offs, his, *, bs: int, S: int, fill):
    """Step row ``(R, S)`` from the step's page rows ``(pages, R, W)``:
    page p's ``bs`` slots, found at lane ``offs[p]`` of its row, land at
    lanes ``[p*bs, p*bs + his[p])`` — ``his[p]`` is ``bs`` for a live,
    granted page and 0 for one that is not, whose lanes keep ``fill``.
    Built 128 lanes at a time from lane rotations of the page rows."""
    pages, R, W = rows.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
    chunks = []
    for c in range(-(-S // 128)):
        out = jnp.full((R, 128), fill, rows.dtype)
        for p in range(pages):
            lo = p * bs - c * 128           # page p's first lane in chunk c
            if lo >= 128 or lo + bs <= 0:
                continue
            # rolled[j] = row[j - lo + off]: the page's slot 0 moves to lo
            shift = (lo % W - offs[p] + W) % W
            piece = pltpu.roll(rows[p], shift, 1)[:, :128]
            out = jnp.where((lane >= lo) & (lane < lo + his[p]), piece, out)
        chunks.append(out)
    row = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
    return row if row.shape[1] == S else row[:, :S]


def _paged_kernel(qpos_ref, plen_ref, nlive_ref, tbl_ref, q_ref, kp_hbm,
                  k_hbm, v_hbm, *refs, pages: int, bs: int, kind: str,
                  window: int, softcap: float, scale: float, quantized: bool,
                  qk_dtype, qk_precision):
    """One request row of the paged launch: a loop over the row's live
    steps, each gathering ``pages`` table-named pool pages (all KV heads,
    their slot positions and, for int8 pools, their scales) by DMA,
    double-buffered so step i+1's pages land while step i computes."""
    if quantized:
        ks_hbm, vs_hbm, *refs = refs
    o_m, o_l, o_acc, kbuf, vbuf, kpbuf, *refs = refs
    if quantized:
        ksbuf, vsbuf, *refs = refs
    sem, m_s, l_s, acc_s = refs
    b = pl.program_id(0)
    T = tbl_ref.shape[1]
    k_row = _pages_per_row(bs)
    n_live = nlive_ref[b]
    n_steps = (n_live + pages - 1) // pages
    S = pages * bs

    def page(i, p):
        # an entry past the table re-reads its last one and an ungranted
        # one streams pool block 0; _place then gives their slots position
        # -1, so the mask drops them
        e = i * pages + p
        tbl = tbl_ref[b, jnp.minimum(e, T - 1)]
        live = (e < n_live) & (tbl >= 0)
        return jnp.maximum(tbl, 0), live

    def fetch(i, slot):
        pairs = [(k_hbm, kbuf), (v_hbm, vbuf)]
        rows = [(kp_hbm, kpbuf)]
        if quantized:
            rows += [(ks_hbm, ksbuf), (vs_hbm, vsbuf)]
        copies = []
        for p in range(pages):
            blk, _ = page(i, p)
            for hbm, buf in pairs:
                copies.append(pltpu.make_async_copy(
                    hbm.at[blk], buf.at[slot, pl.ds(p * bs, bs)],
                    sem.at[slot]))
            for hbm, buf in rows:
                copies.append(pltpu.make_async_copy(
                    hbm.at[blk // k_row], buf.at[slot, p], sem.at[slot]))
        return copies

    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(n_steps > 0)
    def _first():
        for c in fetch(0, 0):
            c.start()

    def step(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_steps)
        def _next():
            for c in fetch(i + 1, 1 - slot):
                c.start()

        for c in fetch(i, slot):
            c.wait()
        offs, his = [], []
        for p in range(pages):
            blk, live = page(i, p)
            offs.append(blk % k_row * bs if k_row > 1 else 0)
            his.append(jnp.where(live, bs, 0))

        def row(buf, fill):
            # the step's (R, S) row of a metadata buffer, rotated as 32-bit
            x = buf[slot]
            x = x if x.dtype == jnp.int32 else x.astype(jnp.float32)
            return _place(x, offs, his, bs=bs, S=S, fill=fill)

        # heads lead for the batched matmuls: (S, Hk, D) -> (Hk, S, D)
        q = q_ref[0].astype(qk_dtype)                 # (Hk, G, D)
        k = jnp.swapaxes(kbuf[slot], 0, 1).astype(qk_dtype)
        s = jnp.einsum("hgd,hkd->hgk", q, k, precision=qk_precision,
                       preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * row(ksbuf, 0)[:, None, :]
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kp = row(kpbuf, -1)                           # (1, S)
        mask = _slot_mask(kp, qpos_ref[b], plen_ref[b], kind=kind,
                          window=window)
        s = jnp.where(mask, s, _NEG)                  # (Hk, G, S)
        m_prev = m_s[...]                             # (Hk, G, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(-1, keepdims=True)
        if quantized:
            p = p * row(vsbuf, 0)[:, None, :]
        v = jnp.swapaxes(vbuf[slot], 0, 1).astype(jnp.float32)
        # p stays f32: HIGHEST keeps the MXU from rounding it to bf16
        acc_s[...] = acc_s[...] * corr + jnp.einsum(
            "hgk,hkd->hgd", p, v, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_s[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_steps, step, 0)
    o_m[0] = m_s[...]
    o_l[0] = l_s[...]
    o_acc[0] = acc_s[...]


def _flash_decode_paged(q, k, v, kv_pos, block_tables, q_pos, *, k_scale,
                        v_scale, kind: str, window: int, prefix_len,
                        softcap: float, interpret: bool,
                        return_partials: bool):
    """Paged-pool kernel launch: grid (B,), one program a request row,
    looping over the row's live table entries in steps of
    ``_pages_per_step`` pages.  The pool stays in HBM; the kernel DMAs
    exactly the pages the table names, all KV heads at once, with their
    slot positions and scales."""
    nb, bs, Hk, D = k.shape
    tbl = jnp.asarray(block_tables, jnp.int32)
    B, T = tbl.shape
    pages = _pages_per_step(bs, T)
    S = pages * bs
    qg, G, G_pad = _pack_queries(q, Hk)
    quantized = k_scale is not None
    # stored bf16 (or int8 codes, exact in bf16) against a bf16 query: the
    # MXU products are exact, so QK^T runs on bf16 operands in one pass;
    # otherwise f32 operands at HIGHEST, which the MXU does not round
    bf16 = q.dtype == jnp.bfloat16 and k.dtype in (jnp.bfloat16, jnp.int8)
    qk_dtype = jnp.bfloat16 if bf16 else jnp.float32
    qk_precision = None if bf16 else jax.lax.Precision.HIGHEST

    kp = _lane_rows(jnp.asarray(kv_pos, jnp.int32)[..., None], -1)
    hbm = [kp, k, v]
    W = kp.shape[-1]
    rows = [pltpu.VMEM((2, pages, 1, W), jnp.int32)]
    if quantized:
        hbm += [_lane_rows(x[..., 0], 0) for x in (k_scale, v_scale)]
        rows += [pltpu.VMEM((2, pages, Hk, W), k_scale.dtype)] * 2

    def out_spec(last):
        return pl.BlockSpec((1, Hk, G_pad, last), lambda b, *_: (b, 0, 0, 0))

    m, l, acc = pl.pallas_call(
        functools.partial(_paged_kernel, pages=pages, bs=bs, kind=kind,
                          window=window, softcap=softcap, scale=D ** -0.5,
                          quantized=quantized, qk_dtype=qk_dtype,
                          qk_precision=qk_precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, Hk, G_pad, D),
                                   lambda b, *_: (b, 0, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
            out_specs=[out_spec(1), out_spec(1), out_spec(D)],
            scratch_shapes=[
                pltpu.VMEM((2, S, Hk, D), k.dtype),
                pltpu.VMEM((2, S, Hk, D), v.dtype),
                *rows,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((Hk, G_pad, 1), jnp.float32),
                pltpu.VMEM((Hk, G_pad, 1), jnp.float32),
                pltpu.VMEM((Hk, G_pad, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hk, G_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hk, G_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hk, G_pad, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(_row_pos(q_pos, B), _row_pos(prefix_len, B),
      live_pages(tbl).astype(jnp.int32), tbl, qg, *hbm)
    return _finish(m[:, :, None], l[:, :, None], acc[:, :, None], G, q,
                   return_partials)


def paged_block_copy(leaf, src, dst, *, interpret: bool = False):
    """Copy physical block ``src``'s tile to block ``dst`` within one
    layer-stacked pool leaf ``(L, n_blocks, ...)`` — the copy-on-write data
    move when a lane diverges from a shared prefix block.

    Grid is (L,), with the (src, dst) pair as a scalar-prefetch operand:
    each program DMAs exactly one block tile out of the source block (the
    index_map dereferences ``src``), and the result is scattered back at
    ``dst`` — the pool itself is never gathered.  The tile is the block
    flattened to ``(Z // 128, 128)`` rows when it divides the 128-lane
    width, else one ``(1, Z)`` row: either way its last two dims are the
    array's own, the tiling the TPU lowering accepts.  Works for every leaf
    dtype (bf16/f32 KV, int8 codes, scale rows, int32 kv_pos), so the whole
    tile — validity included — moves verbatim.
    """
    L, nb = leaf.shape[0], leaf.shape[1]
    Z = 1
    for d in leaf.shape[2:]:
        Z *= d
    W = 128 if Z % 128 == 0 else Z
    R = Z // W
    flat = leaf.reshape(L, nb, R, W)
    sd = jnp.stack([jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)])

    def body(sd_ref, x_ref, o_ref):
        del sd_ref
        o_ref[...] = x_ref[...]

    tile = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L,),
            in_specs=[pl.BlockSpec((1, 1, R, W),
                                   lambda l, sd: (l, sd[0], 0, 0))],
            out_specs=pl.BlockSpec((1, 1, R, W), lambda l, sd: (l, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((L, 1, R, W), flat.dtype),
        interpret=interpret,
    )(sd, flat)
    return flat.at[:, dst].set(tile[:, 0]).reshape(leaf.shape)


# ---------------------------------------------------------------------------
# XLA fallback: identical semantics without Pallas.  Paged pools are
# gathered through the table first (bit-identical to the contiguous ring
# when T*bs == ring length — the engine's greedy-parity invariant).
# ---------------------------------------------------------------------------

def flash_decode_xla(q, k, v, kv_pos, q_pos, *, k_scale=None, v_scale=None,
                     kind: str = "causal", window: int = 0, prefix_len=None,
                     softcap: float = 0.0, block_kv: int = 0,
                     block_tables=None, return_partials: bool = False,
                     **_unused):
    """Same signature/semantics as ``flash_decode`` without Pallas.

    ``block_kv`` <= 0 picks the measured policy: a single-pass wide form up
    to REPRO_DECODE_WIDE_MAX (4096) slots, else a ``lax.scan`` over
    2048-slot tiles with in-block dequant and online softmax — O(block)
    temporaries instead of O(cache_len).  An explicit ``block_kv`` >= S
    also selects the wide form."""
    if block_tables is not None:
        k, v, kv_pos, k_scale, v_scale = paged_gather(
            k, v, kv_pos, k_scale, v_scale, block_tables)
    B, S, Hk, D = k.shape
    kv_pos = jnp.asarray(kv_pos, jnp.int32)
    if kv_pos.ndim == 1:
        kv_pos = jnp.broadcast_to(kv_pos[None], (B, S))
    if block_kv <= 0:
        block_kv = S if S <= _wide_max_s() else _SCAN_BLOCK_KV
    if block_kv >= S:
        return _decode_wide(q, k, v, kv_pos, q_pos, k_scale=k_scale,
                            v_scale=v_scale, kind=kind, window=window,
                            prefix_len=prefix_len, softcap=softcap,
                            return_partials=return_partials)
    quantized = k_scale is not None
    qg, k, v, kv_pos, k_scale, v_scale, G, _ = _pad_inputs(
        q, k, v, kv_pos, k_scale, v_scale, block_kv)
    qg = qg[:, :, :G].astype(jnp.float32)            # no sublane padding here
    S_pad = k.shape[1]
    nb = S_pad // block_kv
    scale = D ** -0.5
    qp = _broadcast_pos(q_pos, B)[:, :, None, None]  # (B, 1, 1, 1)
    plen = _broadcast_pos(prefix_len, B)[:, :, None, None]

    def to_blocks(x):
        return x.reshape((B, nb, block_kv) + x.shape[2:]).swapaxes(0, 1)

    blocks = [to_blocks(k), to_blocks(v), to_blocks(kv_pos)]
    if quantized:
        blocks += [to_blocks(k_scale), to_blocks(v_scale)]

    def kv_step(carry, blk):
        m_run, l_run, acc = carry
        if quantized:
            kb, vb, kpb, ksb, vsb = blk
            kb = kb.astype(jnp.float32) * ksb.astype(jnp.float32)
            vb = vb.astype(jnp.float32) * vsb.astype(jnp.float32)
        else:
            kb, vb, kpb = blk
            kb, vb = kb.astype(jnp.float32), vb.astype(jnp.float32)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = _slot_mask(kpb[:, None, None, :], qp, plen,
                          kind=kind, window=window)  # (B, 1, 1, block_kv)
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m_run, s.max(-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhgk,bkhd->bhgd", p, vb, preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hk, G, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hk, G, 1), jnp.float32)
    a0 = jnp.zeros((B, Hk, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), tuple(blocks))
    if return_partials:
        return m, l, acc
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, 1, Hk * G, D).astype(q.dtype)


def _decode_wide(q, k, v, kv_pos, q_pos, *, k_scale, v_scale, kind: str,
                 window: int, prefix_len, softcap: float,
                 return_partials: bool):
    """Single-pass short-context form: the int8 codes are transposed to
    (B, Hk, S, D) BEFORE dequant (1-byte traffic instead of the 4-byte
    transpose XLA would insert after), then one masked-softmax pass — the
    profitable shape below ``_WIDE_MAX_S``."""
    B, S, Hk, D = k.shape
    G = q.shape[2] // Hk
    qg = q[:, 0].reshape(B, Hk, G, D).astype(jnp.float32)
    kt = k.swapaxes(1, 2)                            # (B, Hk, S, D)
    vt = v.swapaxes(1, 2)
    if k_scale is not None:
        kst = k_scale[..., 0].swapaxes(1, 2)[..., None]   # (B, Hk, S, 1)
        vst = v_scale[..., 0].swapaxes(1, 2)[..., None]
        kf = kt.astype(jnp.float32) * kst.astype(jnp.float32)
        vf = vt.astype(jnp.float32) * vst.astype(jnp.float32)
    else:
        kf, vf = kt.astype(jnp.float32), vt.astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, kf,
                   preferred_element_type=jnp.float32) * D ** -0.5
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    qp = _broadcast_pos(q_pos, B).reshape(B, 1, 1, 1)
    plen = _broadcast_pos(prefix_len, B).reshape(B, 1, 1, 1)
    mask = _slot_mask(kv_pos[:, None, None, :], qp, plen,
                      kind=kind, window=window)      # (B, 1, 1, S)
    s = jnp.where(mask, s, _NEG)
    m = s.max(-1, keepdims=True)                     # (B, Hk, G, 1)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    acc = jnp.einsum("bhgk,bhkd->bhgd", p, vf,
                     preferred_element_type=jnp.float32)
    if return_partials:
        return m, l, acc
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, 1, Hk * G, D).astype(q.dtype)
