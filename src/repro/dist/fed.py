"""FedTime's federation mapped onto mesh collectives (Algorithm 1, DESIGN.md §3).

Cluster aggregation (Algorithm 1, lines 12-14) is a weighted psum of the
LoRA adapter deltas over the ``data`` axis: each data-slice of the mesh
plays one cluster member, training on its own shard of the batch.  The
cross-site aggregation of the paper's two-site (Caltech/JPL) ACN setting
crosses the ``pod`` axis.  Because ``repro.dist.sharding`` pins the
adapters to replication, the payload each round is exactly the LoRA tree —
FedTime's communication profile (paper Fig. 5): base weights receive no
grads and no traffic.

The aggregation itself runs on the communication fast path by default:
``repro.dist.fedcomm.ring_aggregate`` — the hand-rolled bidirectional ring
all-reduce of ``repro.kernels.ring_allreduce`` on the ``REPRO_FED_WIRE``
wire format (int8 codes + absmax scales, bf16, or f32), with f32 master
accumulation and an error-feedback residual carried between rounds.
``REPRO_FED_RING=0`` restores the generic XLA psum lowering.

``expected_collective_bytes`` recomputes the per-device ring all-reduce
bytes implied by this axis mapping (exact chunk plan, wire encoding
included).  ``repro.core.comm.collective_bytes_per_round`` measures the
same quantity from the comm-accounting side, and the kernel's byte ledger
measures it from the actual ppermute buffers;
``tests/test_dist_fed_mapping.py`` and ``tests/test_ring_collective.py``
keep the three in agreement so the §Roofline collective term and the
paper's Fig. 5 comm metric remain one number measured three ways.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.lora import lora_tree, tree_nbytes
from repro.dist.sharding import _mesh_shape

# Who carries what: every slice along ``data`` is one cluster member; the
# ``pod`` axis separates sites.
CLUSTER_AXIS = "data"
CROSS_SITE_AXIS = "pod"


def aggregation_axes(mesh) -> tuple:
    """Mesh axes the federated psum reduces over, innermost first."""
    shape = _mesh_shape(mesh)
    return tuple(ax for ax in (CLUSTER_AXIS, CROSS_SITE_AXIS)
                 if shape.get(ax, 1) > 1)


def ring_allreduce_bytes(payload_bytes: int, n: int, *,
                         wire: str = "f32") -> int:
    """Per-device bytes moved by an ``n``-way bidirectional ring all-reduce
    of an f32 payload of ``payload_bytes``, in the ``wire`` encoding.

    The count is the kernel's exact chunk plan
    (``repro.core.comm.ring_wire_plan``), not the idealized continuous
    formula: the payload is carved into 2·n chunks of
    ceil(elems / 2n) elements (quantized wires round the chunk up to a
    ``REPRO_FED_QBLOCK`` multiple so absmax scales cover whole blocks), a
    device sends each chunk once per reduce-scatter hop and once per
    all-gather hop, and the int8 wire's per-chunk f32 scales are counted.
    On a divisible f32 payload this reduces exactly to the classic
    2·P·(n-1)/n; non-divisible payloads pay their real padding instead of
    silently truncating to the float formula."""
    from repro.core.comm import ring_wire_bytes
    return ring_wire_bytes(-(-payload_bytes // 4), n, wire)


def adapter_payload_bytes(params) -> int:
    """Bytes of the federated payload — the LoRA tree only (f32)."""
    return tree_nbytes(lora_tree(params))


def expected_collective_bytes(params, mesh, wire: str = None) -> dict:
    """Per-axis ring all-reduce bytes for one aggregation round under this
    module's axis mapping, on the given wire format (default
    ``REPRO_FED_WIRE``).  Must agree with
    ``repro.core.comm.collective_bytes_per_round`` and with the ring
    kernel's measured byte ledger.  Counts payload ELEMENTS directly (like
    the accounting side), so the agreement holds whatever dtype the
    adapters are stored in."""
    from repro.core.comm import ring_wire_bytes, wire_format
    from repro.core.lora import count_params
    shape = _mesh_shape(mesh)
    elems = count_params(lora_tree(params))
    wire = wire or wire_format()
    return {ax: ring_wire_bytes(elems, shape.get(ax, 1), wire)
            for ax in (CLUSTER_AXIS, CROSS_SITE_AXIS)}


def fed_psum(tree, mesh):
    """All-reduce a pytree over the federation axes.  Call from inside a
    ``shard_map``/``pmap`` body where the axis names are bound; outside a
    collective context this is an error by construction."""
    axes = aggregation_axes(mesh)
    if not axes:
        return tree
    return jax.tree.map(lambda x: jax.lax.psum(x, axes), tree)


def mask_members(member_adapters, weights, alive):
    """Partial participation on the mesh path (``repro.fault``): zero out
    dropped members' rows AND weights, renormalizing the surviving
    weights to sum to 1.  Zeroing the rows matters, not just the weights:
    a crashed member's buffer can legitimately hold NaN/Inf, and
    ``0 · NaN = NaN`` — a zero weight alone cannot keep the poison out of
    the reduction.  Returns ``(masked_adapters, renormalized_weights)``
    shaped exactly like the inputs, so the ring fast path's compiled
    cache key is unchanged."""
    alive = jnp.asarray(alive)
    w = jnp.asarray(weights, jnp.float32) * alive.astype(jnp.float32)
    total = w.sum()
    w = jnp.where(total > 0, w / jnp.where(total > 0, total, 1.0), w)

    def zero_dead(a):
        m = alive.reshape((alive.shape[0],) + (1,) * (a.ndim - 1))
        return jnp.where(m.astype(bool), a, jnp.zeros_like(a))

    return jax.tree.map(zero_dead, member_adapters), w


def aggregate_adapters(member_adapters, weights, mesh=None, *,
                       alive=None, wire: str = None, state: dict = None,
                       byte_ledger: list = None):
    """Algorithm 1, lines 12-14: weighted aggregation of member adapter
    trees, Σ_k w_k · Δ_k with Σ w_k = 1 (w_k = n_k / n cluster sizes).

    Every leaf of ``member_adapters`` carries a leading member dim of size
    ``len(weights)``.  Without a real multi-axis mesh this reduces locally.
    On a mesh whose federation axes are live, the member dim is sharded
    over them and the reduction is the hand-rolled bidirectional ring
    all-reduce on the ``wire`` format (default ``REPRO_FED_WIRE``) —
    ``repro.dist.fedcomm.ring_aggregate``, which also accepts the
    error-feedback ``state`` and the measuring ``byte_ledger``; passing
    ``state`` makes this return ``(tree, new_state)``.  ``REPRO_FED_RING=0``
    restores the generic psum lowering below.

    ``alive`` (optional bool/0-1 vector over the member dim) handles
    partial participation: dropped members are excluded via
    :func:`mask_members` — rows zeroed, weights renormalized over the
    survivors — before the reduction, on either lowering."""
    from repro.dist import fedcomm
    if alive is not None:
        member_adapters, weights = mask_members(member_adapters, weights,
                                                alive)
    axes = aggregation_axes(mesh) if mesh is not None else ()
    if axes and isinstance(mesh, Mesh) and fedcomm.ring_enabled():
        return fedcomm.ring_aggregate(member_adapters, weights, mesh,
                                      wire=wire, state=state,
                                      byte_ledger=byte_ledger)

    weights = jnp.asarray(weights, jnp.float32)
    n = weights.shape[0]

    def wsum(w, a):
        return (w.reshape((w.shape[0],) + (1,) * (a.ndim - 1)).astype(a.dtype)
                * a).sum(axis=0)

    if not axes or not isinstance(mesh, Mesh):
        out = jax.tree.map(lambda a: wsum(weights, a), member_adapters)
        return out if state is None else (out, state)

    prod = 1
    for ax in axes:
        prod *= _mesh_shape(mesh)[ax]
    if n % prod:
        raise ValueError(
            f"member dim {n} must divide the federation axes {axes} ({prod})")

    member_spec = P(axes if len(axes) > 1 else axes[0])

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(member_spec, member_spec),
                       out_specs=P(), check_vma=False)
    def agg(ad, w):
        local = jax.tree.map(lambda a: wsum(w, a), ad)
        return jax.tree.map(lambda x: jax.lax.psum(x, axes), local)

    out = agg(member_adapters, weights)
    return out if state is None else (out, state)
