"""Hand-rolled bidirectional ring all-reduce over the federated LoRA payload.

``repro.dist.fed`` used to lean on XLA's generic psum lowering for the
Algorithm-1 aggregation.  This module owns the collective instead: the
payload is flattened, carved into ``2·n`` chunks (n rotating clockwise, n
counter-clockwise — both ICI directions busy every hop), and pushed around
the ring with ``jax.lax.ppermute``:

  reduce-scatter phase   n-1 hops; each hop a device receives its
                         neighbour's partial chunk and runs the FUSED
                         dequant -> accumulate (f32 master) -> requant step
                         (a Pallas kernel on TPU / forced-interpret CI), so
                         the quantized wire chunk is never materialized at
                         full precision outside the hop.
  all-gather phase       n-1 hops; the fully-reduced owned chunk is
                         quantized ONCE and then forwarded verbatim —
                         every device dequantizes the same codes, so the
                         result is replicated bit-identically.

Wire formats (``REPRO_FED_WIRE``): f32 (bit-exact, the deterministic
baseline), bf16, and int8 codes with per-``qblock`` f32 absmax scales
(``REPRO_FED_QBLOCK``, default 128).  Accumulation is ALWAYS f32 ("master"
copy), whatever the wire carries, and the hop schedule is a fixed ring
order — weighted aggregation stays deterministic run-to-run.

Error feedback: quantization error would bias Algorithm 1 (the same sign
error re-enters every round).  Each device therefore keeps a residual the
shape of its padded chunk layout; every quantization event adds the
residual in before encoding and stores back what the wire dropped
(``r <- t - deq(quant(t))``).  Carried across rounds, the bias telescopes
away (tests/test_ring_collective.py measures the convergence).

Chunk geometry and per-hop transfer sizes come from
``repro.core.comm.ring_wire_plan`` — the SAME plan prices the round in
``repro.core.comm.collective_bytes_per_round`` and ``repro.dist.fed
.expected_collective_bytes``, and the optional ``byte_ledger`` argument
records the actual nbytes of every ppermute'd buffer at trace time, so the
Fig. 5 comm metric is one number measured three ways.  (A fourth way rides
on top: ``repro.dist.fedcomm`` replays the captured ledger into the
``repro.obs`` tracer as per-hop events + wire-byte counters every round,
and each hop's ops are wrapped in a ``jax.named_scope``
(``obs.ring.<axis>.d<dir>.rs_hop<h>``/``ag_hop<h>``) so XLA device traces
name the hop schedule.)

All collective entry points here must be called from inside a
``shard_map`` body where the axis names are bound (``repro.dist.fedcomm``
is the wrapper that owns the shard_map).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.comm import ring_wire_plan, wire_format, wire_qblock

# rows of (qblock,) lanes one fused-hop program handles
_BLOCK_ROWS = 8


def _use_kernels() -> bool:
    """Mirror of ``repro.kernels.ops.use_kernels`` (no import to keep this
    module free of the attention-kernel dependency chain)."""
    return (jax.default_backend() == "tpu" or
            os.environ.get("REPRO_FORCE_KERNELS") == "1")


# ---------------------------------------------------------------------------
# Fused hop: dequant(recv) -> accumulate (f32 master) -> EF requant
# ---------------------------------------------------------------------------

def _quant_rows(t):
    """(R, Q) f32 -> int8 codes + (R, 1) f32 absmax scales.  jnp.round is
    round-half-to-even in BOTH the Pallas and jnp paths, so forced-interpret
    CI and the fallback agree bitwise."""
    s = jnp.max(jnp.abs(t), axis=1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-30)
    q = jnp.clip(jnp.round(t / s), -127.0, 127.0)
    return q, s


def _hop_int8_kernel(acc_ref, codes_ref, scales_ref, res_ref,
                     oacc_ref, ocodes_ref, oscales_ref, ores_ref):
    """One program: dequantize the received tile from its absmax scales,
    fold it into the f32 master accumulator, then requantize (residual
    added in, new residual out) for the next hop's send — the chunk never
    round-trips through HBM at full precision between these steps."""
    acc = acc_ref[...] + codes_ref[...].astype(jnp.float32) * scales_ref[...]
    oacc_ref[...] = acc
    t = acc + res_ref[...]
    q, s = _quant_rows(t)
    ocodes_ref[...] = q.astype(jnp.int8)
    oscales_ref[...] = s
    ores_ref[...] = t - q * s


def _hop_bf16_kernel(acc_ref, codes_ref, res_ref,
                     oacc_ref, ocodes_ref, ores_ref):
    acc = acc_ref[...] + codes_ref[...].astype(jnp.float32)
    oacc_ref[...] = acc
    t = acc + res_ref[...]
    o = t.astype(jnp.bfloat16)
    ocodes_ref[...] = o
    ores_ref[...] = t - o.astype(jnp.float32)


def _rows(x, qblock: int):
    r = x.reshape(-1, qblock)
    pad = -r.shape[0] % _BLOCK_ROWS
    if pad:
        r = jnp.pad(r, ((0, pad), (0, 0)))
    return r, pad


def _hop_pallas(acc, codes, scales, res, *, wire: str, qblock: int,
                interpret: bool = False):
    """Pallas launch of the fused hop over (rows, qblock) tiles."""
    R0 = acc.size // qblock
    acc_r, _ = _rows(acc, qblock)
    res_r, _ = _rows(res, qblock)
    codes_r, _ = _rows(codes, qblock)
    R = acc_r.shape[0]
    grid = (R // _BLOCK_ROWS,)
    row_spec = pl.BlockSpec((_BLOCK_ROWS, qblock), lambda i: (i, 0))
    if wire == "int8":
        scale_spec = pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0))
        scales_r = scales.reshape(-1, 1)
        if scales_r.shape[0] != R:
            scales_r = jnp.pad(scales_r, ((0, R - scales_r.shape[0]), (0, 0)))
        oacc, ocodes, oscales, ores = pl.pallas_call(
            _hop_int8_kernel,
            grid=grid,
            in_specs=[row_spec, row_spec, scale_spec, row_spec],
            out_specs=[row_spec, row_spec, scale_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((R, qblock), jnp.float32),
                jax.ShapeDtypeStruct((R, qblock), jnp.int8),
                jax.ShapeDtypeStruct((R, 1), jnp.float32),
                jax.ShapeDtypeStruct((R, qblock), jnp.float32),
            ],
            interpret=interpret,
        )(acc_r, codes_r, scales_r, res_r)
        return (oacc[:R0].reshape(acc.shape),
                ocodes[:R0].reshape(acc.shape).astype(jnp.int8),
                oscales[:R0, 0],
                ores[:R0].reshape(acc.shape))
    oacc, ocodes, ores = pl.pallas_call(
        _hop_bf16_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, qblock), jnp.float32),
            jax.ShapeDtypeStruct((R, qblock), jnp.bfloat16),
            jax.ShapeDtypeStruct((R, qblock), jnp.float32),
        ],
        interpret=interpret,
    )(acc_r, codes_r, res_r)
    return (oacc[:R0].reshape(acc.shape), ocodes[:R0].reshape(acc.shape),
            None, ores[:R0].reshape(acc.shape))


def _hop_jnp(acc, codes, scales, res, *, wire: str, qblock: int):
    """Oracle of the fused hop — identical arithmetic, no Pallas."""
    if wire == "int8":
        deq = (codes.reshape(-1, qblock).astype(jnp.float32) *
               scales.reshape(-1, 1)).reshape(acc.shape)
    else:
        deq = codes.astype(jnp.float32)
    acc = acc + deq
    t = acc + res
    if wire == "int8":
        q, s = _quant_rows(t.reshape(-1, qblock))
        return (acc, q.astype(jnp.int8).reshape(acc.shape), s[:, 0],
                (t.reshape(-1, qblock) - q * s).reshape(acc.shape))
    o = t.astype(jnp.bfloat16)
    return acc, o, None, t - o.astype(jnp.float32)


def fused_hop(acc, codes, scales, res, *, wire: str, qblock: int):
    """deq(recv) + accumulate + EF requant, one fused step.

    acc/res: (c,) f32 master chunk and its error-feedback residual;
    codes: (c,) wire-dtype received chunk (int8 or bf16);
    scales: (c // qblock,) f32 absmax scales (int8 wire only, else None).
    Returns (new_acc, send_codes, send_scales, new_res).  Pass
    ``codes=None`` for the quantize-only form (the first send of a phase:
    nothing received yet, encode the local value)."""
    if codes is None:
        codes = jnp.zeros(acc.shape, jnp.int8 if wire == "int8"
                          else jnp.bfloat16)
        if wire == "int8":
            scales = jnp.zeros((acc.size // qblock,), jnp.float32)
    if _use_kernels():
        return _hop_pallas(acc, codes, scales, res, wire=wire, qblock=qblock,
                           interpret=jax.default_backend() != "tpu")
    return _hop_jnp(acc, codes, scales, res, wire=wire, qblock=qblock)


def _dequant_chunk(codes, scales, *, wire: str, qblock: int):
    if wire == "int8":
        return (codes.reshape(-1, qblock).astype(jnp.float32) *
                scales.reshape(-1, 1)).reshape(-1)
    return codes.astype(jnp.float32)


# ---------------------------------------------------------------------------
# The ring
# ---------------------------------------------------------------------------

def _ledger_add(ledger, axis, *bufs):
    if ledger is not None:
        ledger.append((axis, sum(b.size * b.dtype.itemsize for b in bufs
                                 if b is not None)))


def _chunk(x, idx, c):
    """x: (n·c,) -> the (c,) chunk at traced index ``idx``."""
    return jax.lax.dynamic_slice_in_dim(x, idx * c, c, 0)


def _set_chunk(x, idx, v, c):
    return jax.lax.dynamic_update_slice_in_dim(x, v, idx * c, 0)


def _ring_one_axis(flat, axis: str, n: int, *, wire: str, qblock: int,
                   residual, byte_ledger):
    """One n-way bidirectional ring all-reduce of a flat f32 payload.

    Called inside a shard_map body with ``axis`` bound.  ``flat`` is this
    device's local contribution; ``residual`` is the (2·n·c,) carried EF
    residual (or None -> zeros).  Returns (reduced (len(flat),) replicated
    across the axis, new residual)."""
    plan = ring_wire_plan(flat.size, n, wire, qblock)
    c = plan.chunk_elems
    total = plan.n_chunks * c
    me = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]

    padded = jnp.zeros((total,), jnp.float32).at[:flat.size].set(
        flat.astype(jnp.float32))
    res = (jnp.zeros((total,), jnp.float32) if residual is None
           else residual.reshape(total).astype(jnp.float32))
    out = jnp.zeros((total,), jnp.float32)

    for d, perm in ((0, fwd), (1, bwd)):
        acc = jax.lax.dynamic_slice_in_dim(padded, d * n * c, n * c, 0)
        rsd = jax.lax.dynamic_slice_in_dim(res, d * n * c, n * c, 0)
        sgn = 1 if d == 0 else -1

        def s_idx(h):
            return (me - sgn * h) % n

        # -- reduce-scatter: n-1 hops, fused dequant/accumulate/requant --
        first = _chunk(acc, s_idx(0), c)
        if wire == "f32":
            codes, scales = first, None          # identity wire, no EF
        else:
            _, codes, scales, r_new = fused_hop(
                first, None, None, _chunk(rsd, s_idx(0), c),
                wire=wire, qblock=qblock)
            rsd = _set_chunk(rsd, s_idx(0), r_new, c)
        for h in range(n - 1):
            with jax.named_scope(f"obs.ring.{axis}.d{d}.rs_hop{h}"):
                _ledger_add(byte_ledger, axis, codes, scales)
                codes = jax.lax.ppermute(codes, axis, perm)
                if scales is not None:
                    scales = jax.lax.ppermute(scales, axis, perm)
                r_idx = s_idx(h + 1)
                if wire == "f32":
                    new_acc = _chunk(acc, r_idx, c) + codes
                    codes = new_acc
                else:
                    new_acc, codes, scales, r_new = fused_hop(
                        _chunk(acc, r_idx, c), codes, scales,
                        _chunk(rsd, r_idx, c), wire=wire, qblock=qblock)
                    rsd = _set_chunk(rsd, r_idx, r_new, c)
                acc = _set_chunk(acc, r_idx, new_acc, c)

        # -- all-gather: quantized owned chunk forwarded verbatim --
        own = s_idx(n - 1)
        owned_val = (codes if wire == "f32"
                     else _dequant_chunk(codes, scales, wire=wire,
                                         qblock=qblock))
        outd = jnp.zeros((n * c,), jnp.float32)
        outd = _set_chunk(outd, own, owned_val, c)
        for h in range(n - 1):
            with jax.named_scope(f"obs.ring.{axis}.d{d}.ag_hop{h}"):
                _ledger_add(byte_ledger, axis, codes, scales)
                codes = jax.lax.ppermute(codes, axis, perm)
                if scales is not None:
                    scales = jax.lax.ppermute(scales, axis, perm)
                idx = s_idx(h)  # chunk owned by my (h+1)-away upstream
                                # neighbour
                outd = _set_chunk(
                    outd, idx,
                    codes if wire == "f32"
                    else _dequant_chunk(codes, scales, wire=wire,
                                        qblock=qblock),
                    c)
        out = jax.lax.dynamic_update_slice_in_dim(out, outd, d * n * c, 0)
        res = jax.lax.dynamic_update_slice_in_dim(res, rsd, d * n * c, 0)

    return out[:flat.size], res


def ring_allreduce(x, axes, axis_sizes: dict, *, wire: str = None,
                   qblock: int = None, residuals: dict = None,
                   byte_ledger: list = None):
    """Bidirectional ring all-reduce of ``x`` over ``axes`` (hierarchical:
    one ring per axis, innermost first — per-axis bytes match the per-axis
    accounting of ``collective_bytes_per_round``).

    Must run inside a shard_map body binding every axis in ``axes``.
    ``residuals`` maps axis -> carried EF residual (see ``residual_len``);
    pass None for fresh zeros (quantization error then discarded — biased;
    fine for one-shot reductions, wrong for training rounds).  Returns
    (reduced x, {axis: new residual}).
    """
    wire = wire or wire_format()
    qblock = qblock or wire_qblock()
    flat = x.reshape(-1).astype(jnp.float32)
    new_res = {}
    for ax in axes:
        n = axis_sizes[ax]
        if n <= 1:
            continue
        r = (residuals or {}).get(ax)
        flat, new_res[ax] = _ring_one_axis(
            flat, ax, n, wire=wire, qblock=qblock, residual=r,
            byte_ledger=byte_ledger)
    return flat.reshape(x.shape).astype(x.dtype), new_res


def residual_len(n_elems: int, n: int, wire: str = None,
                 qblock: int = None) -> int:
    """Length of the per-axis error-feedback residual: the padded chunk
    layout (2·n·chunk_elems) of the ring plan."""
    plan = ring_wire_plan(n_elems, n, wire, qblock)
    return plan.n_chunks * plan.chunk_elems
