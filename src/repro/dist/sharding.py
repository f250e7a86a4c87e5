"""Mesh-aware partition rules for every pytree the launch stack moves
around: parameters, optimizer state, KV/SSM caches, and input batches
(DESIGN.md §5).

All rules are pure functions of (path, shape, mesh shape), so they work on
``jax.ShapeDtypeStruct`` trees (the dry-run's abstract params) exactly as on
real arrays, and they never touch jax device state.  Every rule enforces
divisibility: a dim that does not divide its mesh axis falls back to
replication rather than erroring, which is what lets one table cover every
architecture family in the repo (dense, MoE, VLM, encoder-decoder, xLSTM,
Zamba2).

Layout summary
  params      — Megatron tensor parallelism over ``model``: column-parallel
                sites shard the output dim, row-parallel sites the input
                dim, embeddings the vocab dim.  LoRA adapters are pinned to
                replication: the federated payload must be a pure psum
                (see repro.dist.fed).
  opt state   — ZeRO-1: the base param spec widened over ``data`` (+``pod``)
                on the first still-replicated dim that divides, so the f32
                AdamW moments never cost more per device than the bf16
                params.
  caches      — ``REPRO_CACHE_SHARD=seq`` (default): batch -> data axes,
                sequence -> ``model`` (flash-decode layout).
                ``REPRO_CACHE_SHARD=heads``: batch -> data axes, KV heads ->
                ``model``, falling through to the head dim when the head
                count does not divide (GQA archs with few KV heads).
  batches     — leading batch dim over the combined (``pod``, ``data``)
                axes, falling back to ``data`` alone, then to replication
                (the long_500k batch=1 shape cannot shard).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _div(n: int, k: int) -> bool:
    """True when an ``n``-sized dim splits evenly ``k`` ways."""
    return k > 0 and n % k == 0


def _mesh_shape(mesh) -> dict:
    """Accept a ``jax.sharding.Mesh`` (``.shape`` is used) or a plain
    ``{axis: size}`` dict — the rule tables only ever need axis sizes."""
    return dict(getattr(mesh, "shape", mesh))


def _axis_candidates(shape: dict):
    """Data-parallel axis combinations to try, widest first: the combined
    (``pod``, ``data``) axes, then ``data`` alone.  Shared by batch
    sharding and ZeRO-1 widening so the two fallback chains never
    diverge."""
    axes = [ax for ax in ("pod", "data") if shape.get(ax, 1) > 1]
    candidates = [axes] if axes else []
    if len(axes) > 1:
        candidates.append(["data"])
    return candidates


def _axis_entry(cand, shape: dict):
    """(spec entry, total ways) for one candidate axis combination."""
    prod = 1
    for ax in cand:
        prod *= shape[ax]
    return (tuple(cand) if len(cand) > 1 else cand[0]), prod


def _batch_axes(n: int, shape: dict):
    """Axis (or axis tuple) an ``n``-sized batch dim shards over: the
    combined (``pod``, ``data``) axes when their product divides, else
    ``data`` alone, else None (replicate)."""
    for cand in _axis_candidates(shape):
        entry, prod = _axis_entry(cand, shape)
        if _div(n, prod):
            return entry
    return None


def _maybe_spec(entries) -> P:
    """Full-length spec, or the canonical empty P() when fully replicated."""
    return P(*entries) if any(e is not None for e in entries) else P()


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Column-parallel sites (shard the output dim): the first matmul of each
# pair in the Megatron decomposition.  Covers attention q/k/v across all
# families, MLP/MoE up+gate projections, fused recurrent in-projections
# (Mamba2 in_proj, mLSTM up, sLSTM w_in), and the vocab-producing lm_head.
_COL_SITES = frozenset((
    "wq", "wk", "wv",
    "gate", "up", "gate_proj", "up_proj",
    "in_proj", "w_in", "ffn_gate", "ffn_up",
    "lm_head", "vis_proj", "frame_proj",
))

# Row-parallel sites (shard the input dim): the second matmul of each pair,
# whose output is the partial-sum that XLA all-reduces back into the
# replicated-hidden residual stream.
_ROW_SITES = frozenset((
    "wo", "down", "down_proj", "out_proj", "ffn_down",
))

# The federated payload: cluster aggregation is a pure psum (DESIGN.md §5 /
# repro.dist.fed), which requires the adapters replicated on every device.
_LORA_LEAVES = frozenset(("lora_a", "lora_b", "lora_scale"))


def _spec_for_param(path: str, leaf, model: int) -> P:
    """Partition spec for one parameter leaf.

    ``path`` is "/"-joined dict keys ("/layers/attn/wq/w"); ``leaf`` needs
    only ``.shape`` (ShapeDtypeStruct or array); ``model`` is the size of
    the ``model`` mesh axis.  Everything unmatched (norm scales, biases,
    routers, conv filters, NF4 codes, recurrent gate weights) replicates.
    """
    parts = [p for p in str(path).split("/") if p]
    tail = parts[-1]
    parent = parts[-2] if len(parts) > 1 else ""
    shape = leaf.shape
    nd = len(shape)

    if tail in _LORA_LEAVES:
        return P()
    if model <= 1 or nd < 2:
        return P()

    # linear sites carry their weight as a "w" leaf; stacked MoE expert
    # weights (gate_proj/up_proj/down_proj) are direct array leaves
    site = parent if tail in ("w",) else tail
    if site in _COL_SITES and _div(shape[-1], model):
        return P(*([None] * (nd - 1)), "model")
    if site in _ROW_SITES and _div(shape[-2], model):
        return P(*([None] * (nd - 2)), "model", None)
    if tail == "table" and nd == 2 and _div(shape[0], model):
        return P("model", None)                      # vocab-sharded embedding
    return P()


def _map_with_path(tree, fn, path: str = ""):
    if isinstance(tree, dict):
        return {k: _map_with_path(v, fn, f"{path}/{k}")
                for k, v in tree.items()}
    return fn(path, tree)


def param_specs(params, mesh):
    """Partition specs for a parameter tree: tensor parallelism over
    ``model``, everything else (incl. the LoRA payload) replicated."""
    model = _mesh_shape(mesh).get("model", 1)
    return _map_with_path(
        params, lambda path, leaf: _spec_for_param(path, leaf, model))


# ---------------------------------------------------------------------------
# Optimizer state (ZeRO-1)
# ---------------------------------------------------------------------------

def opt_state_specs(params, mesh):
    """ZeRO-1 specs for AdamW moments (and grad-accumulation carries): the
    base param spec, additionally widened over the ``data`` (+``pod``) axes
    on the first still-replicated dim that divides.  The moments are pure
    storage between steps, so scattering them over the data-parallel axes
    is free parallelism — XLA all-gathers exactly the slice each update
    needs."""
    shape = _mesh_shape(mesh)
    model = shape.get("model", 1)
    candidates = _axis_candidates(shape)

    def widen(path, leaf):
        base = _spec_for_param(path, leaf, model)
        entries = list(base) + [None] * (len(leaf.shape) - len(base))
        for cand in candidates:
            entry, prod = _axis_entry(cand, shape)
            for d, e in enumerate(entries):
                if e is None and _div(leaf.shape[d], prod):
                    entries[d] = entry
                    return _maybe_spec(entries)
        return _maybe_spec(entries)

    return _map_with_path(params, widen)


# ---------------------------------------------------------------------------
# KV / SSM caches
# ---------------------------------------------------------------------------

# Cache leaf layouts as offsets from the END of the shape — leading dims are
# layer stacks of family-dependent depth (vmap-initialized), so negative
# indexing is what stays stable across families.
_CACHE_DIMS = {
    # attention ring buffers: (..., B, S, Hk, dh)
    "k":       {"batch": -4, "seq": -3, "heads": -2, "dh": -1},
    "v":       {"batch": -4, "seq": -3, "heads": -2, "dh": -1},
    "mem_k":   {"batch": -4, "seq": -3, "heads": -2, "dh": -1},
    "mem_v":   {"batch": -4, "seq": -3, "heads": -2, "dh": -1},
    # int8-KV absmax scales: (..., B, S, Hk, 1) — trailing dim never shards
    "k_scale": {"batch": -4, "seq": -3, "heads": -2},
    "v_scale": {"batch": -4, "seq": -3, "heads": -2},
    # slot-position maps: (..., B, S)
    "kv_pos":  {"batch": -2, "seq": -1},
    "mem_pos": {"batch": -2, "seq": -1},
    # Mamba2: state (..., B, H, P, N), conv tail (..., B, W-1, channels)
    "ssm_state": {"batch": -4, "heads": -3, "dh": -2},
    "conv_buf":  {"batch": -3, "dh": -1},
    # mLSTM: C (..., B, H, dh, dh), n (..., B, H, dh), m (..., B, H)
    "C": {"batch": -4, "heads": -3, "dh": -1},
    "n": {"batch": -3, "heads": -2, "dh": -1},
    "m": {"batch": -2, "heads": -1},
}

# sLSTM scalar-memory state is (..., B, d) — its "n"/"m" leaves collide with
# mLSTM's names, so the table is selected by the enclosing subtree.
_SLSTM_CACHE_DIMS = {
    name: {"batch": -2, "dh": -1} for name in ("c", "n", "m", "h")
}


def cache_specs(cache, mesh, mode: Optional[str] = None):
    """Partition specs for a KV/SSM cache tree.

    ``mode`` (default from ``REPRO_CACHE_SHARD``, then "seq"):
      seq   — flash-decode layout: batch -> data axes, sequence -> ``model``
              (decode attention reduces over the seq-sharded cache).
      heads — batch -> data axes, KV heads -> ``model``, falling through to
              the head dim when the head count does not divide.
    Leaves without the preferred dim (recurrent states have no sequence)
    fall through the same chain; anything that cannot shard replicates.
    """
    shape = _mesh_shape(mesh)
    model = shape.get("model", 1)
    mode = mode or os.environ.get("REPRO_CACHE_SHARD", "seq")
    order = ("seq", "heads", "dh") if mode == "seq" else ("heads", "dh")

    def spec(path, leaf):
        parts = [p for p in path.split("/") if p]
        table = _SLSTM_CACHE_DIMS if "slstm" in parts else _CACHE_DIMS
        dims = table.get(parts[-1])
        nd = len(leaf.shape)
        if dims is None or nd == 0:
            return P()

        def dim_at(key):
            off = dims.get(key)
            return None if off is None or nd + off < 0 else nd + off

        entries = [None] * nd
        b = dim_at("batch")
        if b is not None:
            entries[b] = _batch_axes(leaf.shape[b], shape)
        if model > 1:
            for key in order:
                d = dim_at(key)
                if d is not None and entries[d] is None and \
                        _div(leaf.shape[d], model):
                    entries[d] = "model"
                    break
        return _maybe_spec(entries)

    return _map_with_path(cache, spec)


# ---------------------------------------------------------------------------
# Input batches
# ---------------------------------------------------------------------------

def data_specs(batch, mesh):
    """Shard the leading batch dim of every input leaf over the combined
    (``pod``, ``data``) axes, falling back to ``data`` alone, then to
    replication (scalars like ``pos``, and batch=1 long-context decodes)."""
    shape = _mesh_shape(mesh)

    def spec(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        ax = _batch_axes(leaf.shape[0], shape)
        if ax is None:
            return P()
        return P(ax, *([None] * (nd - 1)))

    return _map_with_path(batch, spec)


# ---------------------------------------------------------------------------
# Mesh plumbing
# ---------------------------------------------------------------------------

def to_shardings(specs, mesh):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def current_mesh():
    """The ambient ``with mesh:`` context's physical mesh, or None."""
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def residual_constraint(x, *, decode: bool = False):
    """Pin the residual stream to the Megatron activation layout
    (batch -> data axes, seq -> ``model``) when a mesh is active.

    No-op outside a mesh context, and per-dim when sizes don't divide —
    decode steps (seq == 1) keep only the batch sharding.  Models call this
    between blocks so remat checkpoints stay small (DESIGN.md §5)."""
    mesh = current_mesh()
    if mesh is None or x.ndim < 3:
        return x
    shape = _mesh_shape(mesh)
    batch_ax = _batch_axes(x.shape[0], shape)
    model = shape.get("model", 1)
    seq_ax = "model" if (not decode and model > 1 and
                         _div(x.shape[1], model)) else None
    if batch_ax is None and seq_ax is None:
        return x
    spec = P(batch_ax, seq_ax, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
