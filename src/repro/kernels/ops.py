"""Jit'd wrappers / dispatch layer for the Pallas kernels.

On TPU the kernels run compiled; everywhere else (this CPU container) they
run in interpret mode or fall back to the jnp oracle.  ``use_kernels()``
reflects the effective mode so model code can branch once.

Decode path: ``flash_decode`` is the serving hot loop — one token against
the ring KV cache.  On TPU it is the fused Pallas split-KV kernel
(int8-aware, GQA-packed, ring/window/prefix masking in-kernel); off-TPU it
dispatches to ``flash_decode_xla``, the same online-softmax algorithm as a
``lax.scan`` over cache blocks with fused blockwise dequant — in neither
mode is the full quantized cache ever dequantized to HBM.  Sequence-sharded
caches (``REPRO_CACHE_SHARD=seq``) go through ``repro.dist.decode``, which
calls this entry point with ``return_partials=True`` per shard and combines
the (m, l, acc) partials with a pmax/psum over the ``model`` axis.

Observability: every dispatch wraps its body in a ``jax.named_scope``
(``obs.flash_decode``, ``obs.qlora_matmul``, ...).  The scopes cost nothing
at runtime (they only name the lowered HLO), but XLA device traces and
``launch/hlo_cost`` dumps then carry the same region names as the host
spans ``repro.obs`` records around the compiled calls, so profiler
timelines line up across the host/device boundary.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.flash_decode import flash_decode_xla as _flash_decode_xla
from repro.kernels.flash_decode import paged_block_copy as _paged_block_copy
from repro.kernels.qlora_matmul import qlora_matmul as _qlora
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_kernels() -> bool:
    """Kernels are the default on TPU; REPRO_FORCE_KERNELS=1 forces
    interpret-mode execution elsewhere (slow — tests only)."""
    return on_tpu() or os.environ.get("REPRO_FORCE_KERNELS") == "1"


def flash_decode_enabled() -> bool:
    """Escape hatch: REPRO_FLASH_DECODE=0 restores the legacy
    dequantize-then-sdpa decode step (baselines / A-B benchmarks)."""
    return os.environ.get("REPRO_FLASH_DECODE", "1") != "0"


def pallas_decode(cache_len: int) -> bool:
    """True when ``flash_decode`` runs the Pallas kernel (compiled or
    interpret) for a cache of ``cache_len`` logical slots."""
    return (flash_decode_enabled() and use_kernels()
            and not _short_cache_xla(cache_len))


def decode_mode(cache_len: int) -> str:
    """Human-readable path ``flash_decode`` takes for a cache of
    ``cache_len`` logical slots (launchers print this)."""
    if not flash_decode_enabled():
        return "naive-sdpa (REPRO_FLASH_DECODE=0)"
    if _short_cache_xla(cache_len):
        return (f"flash_decode (xla, {cache_len} slots < "
                f"REPRO_FLASH_DECODE_MIN_S={_pallas_min_s()})")
    if on_tpu():
        return "flash_decode (pallas, compiled)"
    if use_kernels():
        return "flash_decode (pallas, interpret)"
    return "flash_decode (xla blockwise fallback)"


def qlora_matmul(x, w_nf4, absmax, lora_a, lora_b, lora_scale, **kw):
    with jax.named_scope("obs.qlora_matmul"):
        if use_kernels():
            return _qlora(x, w_nf4, absmax, lora_a, lora_b, lora_scale,
                          interpret=not on_tpu(), **kw)
        return ref.qlora_matmul_ref(x, w_nf4, absmax, lora_a, lora_b,
                                    lora_scale)


def flash_attention(q, k, v, *, causal: bool = True, **kw):
    with jax.named_scope("obs.flash_attention"):
        if use_kernels():
            return _flash(q, k, v, causal=causal, interpret=not on_tpu(),
                          **kw)
        return ref.flash_attention_ref(q, k, v, causal=causal)


def _pallas_min_s() -> int:
    """Profitability floor for the Pallas kernel: below this cache length
    the launch/grid overhead loses to one wide XLA pass, so ops.flash_decode
    dispatches to the fallback instead (read per call like every REPRO_
    flag)."""
    return int(os.environ.get("REPRO_FLASH_DECODE_MIN_S", "1024"))


def _short_cache_xla(cache_len: int) -> bool:
    """True when the compiled kernel is skipped for the XLA path: on TPU,
    caches shorter than REPRO_FLASH_DECODE_MIN_S.  Forced-interpret mode
    keeps the kernel so CI exercises it at test sizes."""
    return on_tpu() and cache_len < _pallas_min_s()


def flash_decode(q, k, v, kv_pos, q_pos, **kw):
    """One decode step over the ring or paged cache (``v`` None: a latent
    cache); see ``repro.kernels.flash_decode`` for signature and semantics,
    and ``decode_mode`` for which path a cache length takes."""
    with jax.named_scope("obs.flash_decode"):
        if use_kernels():
            tbl = kw.get("block_tables")
            s_logical = (tbl.shape[1] * k.shape[1] if tbl is not None
                         else k.shape[1])
            # latent rings (v None, no table): the ring launch has no
            # latent mode
            if _short_cache_xla(s_logical) or (v is None and tbl is None):
                return _flash_decode_xla(q, k, v, kv_pos, q_pos, **kw)
            return _flash_decode(q, k, v, kv_pos, q_pos,
                                 interpret=not on_tpu(), **kw)
        return _flash_decode_xla(q, k, v, kv_pos, q_pos, **kw)


def block_copy(pool_leaf, src, dst, **kw):
    """Copy one physical block's tile to another within a layer-stacked
    pool leaf ``(L, n_blocks, ...)`` — the paged pool's copy-on-write data
    move.  Pallas per-layer DMA under ``use_kernels()``; elsewhere an XLA
    dynamic gather+scatter with identical semantics (the copy is exact for
    every dtype, so CoW preserves bit-identical greedy decode)."""
    with jax.named_scope("obs.block_copy"):
        if use_kernels():
            return _paged_block_copy(pool_leaf, src, dst,
                                     interpret=not on_tpu(), **kw)
        return pool_leaf.at[:, dst].set(pool_leaf[:, src])


def rmsnorm(x, scale, *, eps: float = 1e-6, **kw):
    with jax.named_scope("obs.rmsnorm"):
        if use_kernels():
            return _rmsnorm(x, scale, eps=eps, interpret=not on_tpu(), **kw)
        return ref.rmsnorm_ref(x, scale, eps)
