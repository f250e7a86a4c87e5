"""Federated communication fast path (paper Fig. 5 / §C5).

``repro.dist.fed`` maps Algorithm 1's aggregation onto mesh collectives;
this module owns HOW those collectives move: the hand-rolled bidirectional
ring all-reduce of ``repro.kernels.ring_allreduce`` with a quantized wire
format (``REPRO_FED_WIRE=int8|bf16|f32``) and an error-feedback residual
carried between rounds.

Two call sites share the wire machinery:

  * ``ring_aggregate`` — the mesh path.  Every data-slice of the mesh is a
    cluster member; its weighted adapter delta is flattened into ONE
    payload vector and pushed around the ring per federation axis
    (``data``, then ``pod`` cross-site).  The EF residual lives sharded
    over the federation axes (each device carries its own), so repeated
    rounds stay unbiased even on the int8 wire.
  * ``quantize_update`` — the host-loop path.  ``train/fed_trainer`` runs
    the paper's client/server simulation outside any mesh; each client's
    uploaded delta passes through the same quantize/dequant + residual
    step, so Algorithm 1 sees exactly what the wire delivers and
    ``comm.fedtime_round(..., wire=...)`` prices what it meters.

``REPRO_FED_RING=0`` restores the XLA psum lowering in
``fed.aggregate_adapters`` (A/B baseline — ``benchmarks/collectives``
compares the two).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.comm import wire_format, wire_qblock
from repro.dist.sharding import _mesh_shape
from repro.kernels.ring_allreduce import (fused_hop, _dequant_chunk,
                                          residual_len, ring_allreduce)


def ring_enabled() -> bool:
    """The ring fast path is the default on a live mesh;
    ``REPRO_FED_RING=0`` falls back to XLA's psum lowering."""
    return os.environ.get("REPRO_FED_RING", "1") != "0"


# one compiled aggregation per (mesh, wire, payload signature): the ring is
# a Python-unrolled hop schedule, so re-tracing it every round would pay
# the full lowering cost 25x in a 25-round federation.  Bounded FIFO so a
# sweep over meshes/configs can't pin executables for the process lifetime.
# Each entry carries (compiled_fn, byte_ledger): the ledger fills at the
# first trace and is bit-identical every subsequent round, so cache hits
# can replay it into the repro.obs tracer without re-compiling.
_AGG_CACHE: dict = {}
_AGG_CACHE_MAX = 32


def _member_elems(member_adapters) -> int:
    """f32 elements of ONE member's adapter payload (leaves carry a
    leading member dim)."""
    return sum(l.size // l.shape[0] for l in jax.tree.leaves(member_adapters))


def init_state(member_adapters, mesh, *, wire: str = None,
               qblock: int = None) -> dict:
    """Zero error-feedback residual state for ``ring_aggregate``:
    ``{axis: (n_devices, residual_len)}`` f32, leading dim sharded over the
    federation axes (every device carries its own residual between
    rounds)."""
    from repro.dist.fed import aggregation_axes
    wire = wire or wire_format()
    shape = _mesh_shape(mesh)
    axes = aggregation_axes(mesh)
    elems = _member_elems(member_adapters)
    prod = 1
    for ax in axes:
        prod *= shape[ax]
    return {ax: jnp.zeros(
        (prod, residual_len(elems, shape[ax], wire, qblock)), jnp.float32)
        for ax in axes}


def ring_aggregate(member_adapters, weights, mesh, *, wire: str = None,
                   qblock: int = None, state: dict = None,
                   byte_ledger: list = None):
    """Algorithm 1, lines 12-14 over the ring fast path: weighted member
    aggregation Σ_k w_k·Δ_k, the member dim sharded over the federation
    axes, the cross-member reduction an explicit bidirectional ring
    all-reduce on the configured wire format.

    ``state`` (from ``init_state``) carries the per-device error-feedback
    residual between rounds.  With ``state=None`` quantization error is
    DISCARDED: fine for a one-shot reduction, but calling this (or
    ``fed.aggregate_adapters``) stateless every round under a quantized
    ``REPRO_FED_WIRE`` re-applies a correlated bias each round — training
    loops must thread the state through.  ``byte_ledger`` (a list)
    receives ``(axis, nbytes)`` per ppermute'd buffer at trace time — the
    measured side of the Fig. 5 three-way byte agreement.

    Returns the aggregated tree, or ``(tree, new_state)`` when ``state``
    is given.

    Partial participation: this kernel reduces whatever rows it is
    handed; drop members BEFORE the call via
    ``repro.dist.fed.mask_members`` (rows zeroed + weights renormalized,
    shapes unchanged) so the compiled executable and its byte ledger are
    reused across cohort changes — see ``fed.aggregate_adapters(alive=)``
    and the ``repro.fault`` round loop.
    """
    from repro.dist.fed import aggregation_axes
    wire = wire or wire_format()
    qblock = qblock or wire_qblock()
    weights = jnp.asarray(weights, jnp.float32)
    n = weights.shape[0]

    def wsum(w, a):
        return (w.reshape((w.shape[0],) + (1,) * (a.ndim - 1)).astype(a.dtype)
                * a).sum(axis=0)

    axes = aggregation_axes(mesh) if mesh is not None else ()
    if not axes or not isinstance(mesh, Mesh):
        out = jax.tree.map(lambda a: wsum(weights, a), member_adapters)
        return out if state is None else (out, state)

    shape = _mesh_shape(mesh)
    prod = 1
    for ax in axes:
        prod *= shape[ax]
    if n % prod:
        raise ValueError(
            f"member dim {n} must divide the federation axes {axes} ({prod})")

    entry = axes if len(axes) > 1 else axes[0]
    member_spec = P(entry)
    carry_state = state is not None
    st_in = state if carry_state else init_state(member_adapters, mesh,
                                                 wire=wire, qblock=qblock)
    st_spec = {ax: P(entry) for ax in st_in}

    leaves, tdef = jax.tree.flatten(member_adapters)
    shapes = [l.shape[1:] for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    splits = np.cumsum(sizes)[:-1]

    key = (mesh, wire, qblock, tdef, n,
           tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
    agg = ledger = None
    if byte_ledger is None:
        cached = _AGG_CACHE.get(key)
        if cached is not None:
            agg, ledger = cached
    if agg is None:
        # the ledger fills at trace time (first call below) and describes
        # every round identically; cache it with the executable so obs
        # telemetry keeps its per-hop numbers on the hot (cached) path
        ledger = [] if byte_ledger is None else byte_ledger

        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(member_spec, member_spec, st_spec),
                           out_specs=(P(), st_spec), check_vma=False)
        def agg(ad, w, st):
            local = jax.tree.map(lambda a: wsum(w, a), ad)
            flat = jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32)
                 for l in jax.tree.leaves(local)])
            red, new_res = ring_allreduce(
                flat, axes, shape, wire=wire, qblock=qblock,
                residuals={ax: r[0] for ax, r in st.items()},
                byte_ledger=ledger)
            parts = jnp.split(red, splits)
            out = jax.tree.unflatten(
                tdef, [p.reshape(s) for p, s in zip(parts, shapes)])
            return out, {ax: new_res[ax][None] for ax in st}

        if byte_ledger is None:
            if len(_AGG_CACHE) >= _AGG_CACHE_MAX:
                _AGG_CACHE.pop(next(iter(_AGG_CACHE)))
            _AGG_CACHE[key] = (agg, ledger)

    with obs.span("fedcomm.ring_aggregate", wire=wire,
                  axes=",".join(axes)):
        out, st_out = agg(member_adapters, weights, st_in)
    _trace_ring_round(ledger, wire)
    return (out, st_out) if carry_state else out


def _trace_ring_round(ledger, wire: str) -> None:
    """Replay one round's measured ppermute ledger into the tracer: a
    ``ring.hop`` instant per chunk transfer and a per-axis
    ``ring.wire_bytes.<axis>`` counter.  The counter's per-round increment
    equals ``repro.dist.fed.expected_collective_bytes`` / ``repro.core.comm
    .collective_bytes_per_round`` for that axis EXACTLY (same plan, fourth
    measurement) — ``tests/test_obs.py`` holds the line."""
    if not ledger or not obs.enabled():
        return
    per_axis: dict = {}
    for i, (ax, nbytes) in enumerate(ledger):
        obs.instant("ring.hop", track=f"ring:{ax}", axis=ax, seq=i,
                    nbytes=nbytes, wire=wire)
        # mergeable sketch, not reservoir: hop-size percentiles stay
        # aggregatable across processes / trace merges
        obs.hist("ring.hop_bytes", float(nbytes), sketch=True)
        per_axis[ax] = per_axis.get(ax, 0) + nbytes
    for ax, nbytes in per_axis.items():
        obs.counter(f"ring.wire_bytes.{ax}", nbytes)
    obs.counter("ring.rounds", 1)


# ---------------------------------------------------------------------------
# Host-loop wire emulation (train/fed_trainer)
# ---------------------------------------------------------------------------

def quantize_update(tree, residual=None, *, wire: str = None,
                    qblock: int = None):
    """One client upload through the wire: quantize the delta tree (EF
    residual added in), return what the server dequantizes plus the new
    residual (flat f32, carried to this client's next round).

    f32 wire is the identity.  Uses the same fused quantize primitives as
    the ring kernel, so the host simulation and the mesh path share one
    wire semantics."""
    wire = wire or wire_format()
    qblock = qblock or wire_qblock()
    if wire == "f32":
        return tree, residual

    leaves, tdef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    splits = np.cumsum([int(np.prod(s)) if s else 1
                        for s in shapes])[:-1]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    pad = -flat.size % qblock
    padded = jnp.pad(flat, (0, pad))
    res = (jnp.zeros_like(padded) if residual is None
           else residual.astype(jnp.float32))
    # encode t = value + residual, keep the wire's loss as the new residual
    t = padded + res
    _, codes, scales, new_res = fused_hop(t, None, None,
                                          jnp.zeros_like(t),
                                          wire=wire, qblock=qblock)
    deq = _dequant_chunk(codes, scales, wire=wire, qblock=qblock)
    parts = jnp.split(deq[:flat.size], splits)
    out = jax.tree.unflatten(
        tdef, [p.reshape(s).astype(l.dtype)
               for p, s, l in zip(parts, shapes, leaves)])
    return out, new_res
