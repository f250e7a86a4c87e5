"""Sequence-sharded flash-decode (DESIGN.md §5 cache layouts).

``REPRO_CACHE_SHARD=seq`` — the default flash-decode layout — puts the ring
cache's slot axis on the ``model`` mesh axis, so no device ever holds the
whole cache.  A decode step then needs a cross-shard softmax: each model
shard runs the flash-decode kernel over its local slots with
``return_partials=True`` (unnormalized online-softmax state), and the
combine

    m* = pmax(m, model)
    out = psum(exp(m - m*) * acc, model) / psum(exp(m - m*) * l, model)

is exactly the kernel's own cross-split (m, l, acc) merge lifted onto mesh
collectives.  Masking needs no adjustment: slots carry absolute positions in
``kv_pos``, which shard with the cache, so ring-validity / causal / window /
prefix masks are position-local facts.

``seq_shard_mesh`` gates the path: it returns the ambient mesh only when a
mesh is active, the ``model`` axis is real, the layout is ``seq``, and the
cache length divides — otherwise ``attn_decode`` stays on the single-shard
kernel and XLA handles whatever layout the arrays actually have.

Ragged continuous-batching steps (``repro.serve.engine``) take this same
path unchanged: ``q_pos`` is per-batch ((B,), sharded over the batch axes
like the queries), so per-slot positions — including the ``-1`` inactive
marker, which fully masks a lane — are shard-local facts exactly like
``kv_pos``; the (m, l, acc) combine is oblivious to which lanes are live.

Paged block pools (``block_tables``) shard the pool's *block* axis over
``model`` instead of a per-request slot axis: each shard owns an
``n_blocks/m`` stripe of physical blocks, the (replicated) table is
localized per shard — entries outside the stripe become -1, i.e. masked —
and the identical (m, l, acc) combine stitches the stripes back together.
A request's blocks land on whichever shards the allocator picked; the
combine is oblivious to that placement exactly as it is to lane liveness.

Copy-on-write prefix sharing composes for free: a shared physical block
appears at the SAME logical index in every sharer's table row, so each
row's entry localizes to the same shard-local index (or -1 off-stripe) —
every sharer attends to the one stored tile, no matter which shard owns
it.  Localization is per-entry and read-only; it never assumes a block
appears in at most one row (tests/test_paged_pool.py drives a duplicated
physical block across rows through the sharded path).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import _batch_axes, _mesh_shape, current_mesh


def seq_shard_mesh(cache_len: int):
    """The ambient mesh when the seq-sharded decode path applies, else
    None."""
    mesh = current_mesh()
    if mesh is None:
        return None
    shape = _mesh_shape(mesh)
    if shape.get("model", 1) <= 1:
        return None
    if os.environ.get("REPRO_CACHE_SHARD", "seq") != "seq":
        return None
    if cache_len % shape["model"]:
        return None
    return mesh


def sharded_flash_decode(q, k, v, kv_pos, q_pos, mesh, *, k_scale=None,
                         v_scale=None, kind: str = "causal", window: int = 0,
                         prefix_len=None, softcap: float = 0.0,
                         block_kv: int = 0, block_tables=None):
    """One decode step against a cache sharded over ``model`` — the slot
    axis of per-request rings, or the block axis of a paged pool
    (``block_tables`` given: k/v are (n_blocks, block_size, Hk, dh), the
    table is replicated and localized inside each shard).  Per-shard kernel
    partials + psum-style combine; same signature/result as
    ``repro.kernels.ops.flash_decode``."""

    from repro.kernels import ops

    paged = block_tables is not None
    B = q.shape[0]
    shape = _mesh_shape(mesh)
    bax = _batch_axes(B, shape)
    q_spec = P(bax, None, None, None)
    if paged:
        kv_spec = P("model", None, None, None)       # pool block axis
        pos_spec = P("model", None)
    else:
        kv_spec = P(bax, "model", None, None)        # per-request slot axis
        pos_spec = P(bax, "model")
    qp = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (B,))
    plen = jnp.broadcast_to(
        jnp.asarray(0 if prefix_len is None else prefix_len,
                    jnp.int32).reshape(-1), (B,))
    args = [q, k, v, kv_pos, qp, plen]
    specs = [q_spec, kv_spec, kv_spec, pos_spec, P(bax), P(bax)]
    if paged:
        args.append(jnp.asarray(block_tables, jnp.int32))
        specs.append(P(bax, None))                   # replicated over model
    if k_scale is not None:
        args += [k_scale, v_scale]
        specs += [kv_spec, kv_spec]

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=tuple(specs),
                       out_specs=q_spec, check_vma=False)
    def body(q, k, v, kv_pos, qp, plen, *rest):
        rest = list(rest)
        tbl = rest.pop(0) if paged else None
        ks, vs = rest if rest else (None, None)
        if paged:
            # localize the table: this shard owns physical blocks
            # [lo, lo + nb_loc); everything else is another shard's problem
            nb_loc = k.shape[0]
            lo = jax.lax.axis_index("model") * nb_loc
            tbl = jnp.where((tbl >= lo) & (tbl < lo + nb_loc), tbl - lo, -1)
        m, l, acc = ops.flash_decode(
            q, k, v, kv_pos, qp, k_scale=ks, v_scale=vs, kind=kind,
            window=window, prefix_len=plen, softcap=softcap,
            block_kv=block_kv, block_tables=tbl, return_partials=True)
        m_g = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, "model")
        acc_g = jax.lax.psum(acc * w, "model")
        out = acc_g / jnp.maximum(l_g, 1e-30)        # (B_loc, Hk, G, D)
        return out.reshape(out.shape[0], 1, -1,
                           out.shape[-1]).astype(q.dtype)

    return body(*args)
