"""Cache pools for the serving engine: contiguous per-slot lanes and the
paged block-KV pool.

``CachePool`` preallocates ``num_slots`` full-length cache lanes in one
donated pytree — a request is "placed" by writing its batch-1 prefill cache
into lane ``slot`` with a traced ``dynamic_update_slice``.  It works for
every servable family (attention rings AND SSM/hybrid state, bf16 and int8,
``REPRO_CACHE_SHARD=seq`` layouts) because it never looks inside the leaves:
``cache_batch_axes`` finds each leaf's batch axis structurally.

``PagedCachePool`` is the HBM-efficient layout for uniform attention-ring
families (dense/moe without local/global alternation): ONE donated block
pool in the model's own cache layout with the block count for its batch —
``(L, n_blocks, block_size, Hk, dh)`` K and V, or for latent attention one
``(L, n_blocks, block_size, rank + rope)`` entry leaf — plus a host-side block
table ``(num_slots, blocks_per_slot)`` mapping each lane's logical ring
blocks to physical pool blocks.  A lane only holds the blocks its tokens
actually occupy — short requests stop reserving a full ``cache_len`` lane,
so at fixed pool bytes strictly more requests fit in flight.  Blocks are
granted on demand (`grant`) as decode crosses block boundaries and freed
wholesale at retirement; freshly granted blocks get their ``kv_pos``
invalidated on device (`reset_blocks`) so a previous owner's stale
positions can never leak through the ring-validity mask.  SSM/hybrid
families keep dense lanes behind the same engine-facing surface
(acquire/release/insert + block accounting).

Copy-on-write prefix sharing (this PR's tentpole): blocks are refcounted
and a prefix-hash index (``match_prefix`` / ``register_prefix``) maps
block-aligned prompt prefixes — and whole prompts, with the last-token
logits row — to live block chains.  A new lane whose prompt matches maps
the chain's blocks read-only into its table (``share_map``: refcount bump,
zero new blocks, and on a full-prompt hit zero prefill recompute); the
first write that would land in a block with refcount > 1 triggers
copy-on-write (``cow``: allocate a fresh block, device block-copy the tile
through ``repro.kernels.ops.block_copy``, remap, decref).  Chain entries
never pin blocks: when a block's refcount hits zero — or its sole owner's
ring wraps back over prefix content — every chain referencing it is
dropped.  Sharing is safe exactly because all prompts start at position 0
(RoPE'd KV at a position depends only on the tokens at/before it), decode
writes always precede reads at the same query position, and stale
future-position slots in a shared tail block are masked by the causal /
ring-validity mask.

The swap tier rides the same geometry: ``gather_lane`` snapshots a lane's
logical ring (one jitted gather, dispatch-async) so the engine can move a
cold lane's blocks to host memory and free them, then ``insert`` the saved
ring back into freshly granted blocks on resume — bit-exact, replacing
evict-and-recompute as the livelock-breaker.

Cache pytrees stack layers OUTSIDE the batch axis (``(L, B, S, Hk, dh)``
for attention rings, ``(nG, nM, B, ...)`` for SSM states), so the batch
axis sits at a different depth per family/leaf.  ``cache_batch_axes``
derives a per-leaf axis map structurally — ``jax.eval_shape`` of
``init_cache`` at two batch sizes, diffed — instead of hard-coding
per-family layouts.
"""

from __future__ import annotations

import os
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_decode import live_pages
from repro.models.layers.attention import map_kv_pos

PAGED_FAMILIES = ("dense", "moe")


def cache_batch_axes(api, cfg, *, probe_len: int = 8):
    """Per-leaf batch-axis pytree for this family's cache layout.

    Abstract-evals ``init_cache`` at batch sizes 1 and 2 and locates the
    one axis that scaled — no arrays are materialized.
    """
    a1 = jax.eval_shape(lambda: api.init_cache(cfg, 1, probe_len))
    a2 = jax.eval_shape(lambda: api.init_cache(cfg, 2, probe_len))

    def axis_of(x, y):
        diff = [i for i, (d1, d2) in enumerate(zip(x.shape, y.shape))
                if d1 != d2]
        if len(diff) != 1:
            raise ValueError(f"cannot locate batch axis: {x.shape} vs "
                             f"{y.shape}")
        return diff[0]

    return jax.tree.map(axis_of, a1, a2)


def _expand(mask, axis: int, ndim: int):
    """(B,) bool -> broadcastable shape with B at ``axis`` of an
    ``ndim``-rank leaf."""
    return mask.reshape((1,) * axis + (-1,) + (1,) * (ndim - axis - 1))


def freeze_inactive(old_cache, new_cache, active, axes):
    """Select ``new_cache`` for active lanes and ``old_cache`` for inactive
    ones, per leaf at its batch axis — retired/empty slots never drift while
    other requests decode (SSM states included; the attention ring guards
    its own writes, recurrent states rely on this select)."""
    return jax.tree.map(
        lambda o, n, ax: jnp.where(_expand(active, ax, n.ndim), n, o),
        old_cache, new_cache, axes)


class _LanePool:
    """Shared lane (slot) free-list: acquire/release bookkeeping common to
    both pool layouts.  Slot lifecycle is owned by the engine; the pools
    only track the free list."""

    def __init__(self, num_slots: int, cache_len: int):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        self.num_slots = num_slots
        self.cache_len = cache_len
        self._free: List[int] = list(range(num_slots - 1, -1, -1))

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def acquire(self) -> int:
        if not self._free:
            raise RuntimeError("cache pool exhausted")
        return self._free.pop()

    def release(self, slot: int) -> None:
        if slot in self._free:
            raise ValueError(f"slot {slot} double-freed")
        self._free.append(slot)


class CachePool(_LanePool):
    """``num_slots`` cache lanes carved out of one preallocated cache.

    ``insert`` is the single compiled entry point — slot index and
    request cache are traced, so admissions at any slot share one
    signature.
    """

    def __init__(self, api, cfg, num_slots: int, cache_len: int, *,
                 force_window: int = 0, dtype=None):
        super().__init__(num_slots, cache_len)
        dtype = jnp.dtype(cfg.compute_dtype) if dtype is None else dtype
        self.cache = api.init_cache(cfg, num_slots, cache_len,
                                    force_window=force_window, dtype=dtype)
        self.axes = cache_batch_axes(api, cfg)

        def _insert(pool, req_cache, slot):
            return jax.tree.map(
                lambda p, r, ax: jax.lax.dynamic_update_slice_in_dim(
                    p, r.astype(p.dtype), slot, axis=ax),
                pool, req_cache, self.axes)

        self._insert = jax.jit(_insert, donate_argnums=(0,))

    # -- block accounting (lane granularity) ---------------------------------

    @property
    def pool_blocks(self) -> int:
        """Block accounting at lane granularity: one lane == one block (the
        paged pool refines this; metrics report both layouts uniformly)."""
        return self.num_slots

    @property
    def blocks_in_use(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def fragmentation(self) -> float:
        """Contiguous lanes can't fragment: always 0 (uniform metrics
        interface with the paged pool)."""
        return 0.0

    @property
    def free_runs(self) -> int:
        return 1 if self._free else 0

    # -- data path ----------------------------------------------------------

    def insert(self, req_cache, slot: int) -> None:
        """Write a batch-1 prefill cache into lane ``slot`` (traced — one
        compiled signature for every slot/admission)."""
        self.cache = self._insert(self.cache, req_cache,
                                  jnp.asarray(slot, jnp.int32))


# ---------------------------------------------------------------------------
# Paged block pool
# ---------------------------------------------------------------------------

class BlockAllocator:
    """LIFO free-list allocator over ``n_blocks`` physical pool blocks,
    with per-block refcounts for copy-on-write prefix sharing.

    Invariant (the hypothesis property in tests/test_paged_pool.py and
    tests/test_prefix_share.py): the free list and the allocated set always
    partition ``range(n_blocks)``, and a block's refcount equals the number
    of lane-table rows referencing it — no block is ever in two hands
    unintentionally, and a shared block can't return to the free list while
    any lane still reads it."""

    def __init__(self, n_blocks: int):
        if n_blocks < 1:
            raise ValueError("n_blocks must be >= 1")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._used: set = set()
        self._ref: dict = {}                   # block -> refcount (>= 1)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._used)

    @property
    def free_runs(self) -> int:
        """Maximal runs of consecutive block ids in the free list (order
        ignored: the LIFO list is a set for adjacency purposes).  One run
        = perfectly coalesced; ``free_blocks`` runs = fully shredded."""
        if not self._free:
            return 0
        ids = sorted(self._free)
        return 1 + sum(1 for a, b in zip(ids, ids[1:]) if b != a + 1)

    @property
    def fragmentation(self) -> float:
        """Free-list shredding in [0, 1]: ``(runs - 1) / (free - 1)``.
        0 when the free space is one contiguous run (or ≤ 1 block free),
        1 when every free block is an island.  Block granularity makes
        this cosmetic for *allocation* (any free block serves any ask) but
        it tracks how interleaved lane lifetimes have scrambled the pool —
        the locality signal for the gather/scatter paths."""
        free = len(self._free)
        if free <= 1:
            return 0.0
        return (self.free_runs - 1) / (free - 1)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, n: int = 1) -> List[int]:
        """Pop ``n`` blocks (each at refcount 1); raises RuntimeError
        (allocating nothing) when fewer than ``n`` are free — the caller
        parks or evicts."""
        if n > len(self._free):
            raise RuntimeError(
                f"block pool exhausted: want {n}, free {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        self._used.update(out)
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, block: int) -> int:
        """Share an allocated block (a new lane maps it read-only)."""
        if block not in self._used:
            raise ValueError(f"cannot share free block {block}")
        self._ref[block] += 1
        return self._ref[block]

    def decref(self, block: int) -> bool:
        """Drop one reference; returns True when the block actually went
        back to the free list (last reference)."""
        if block not in self._used:
            raise ValueError(f"block {block} double-freed (or never "
                             f"allocated)")
        self._ref[block] -= 1
        if self._ref[block] > 0:
            return False
        del self._ref[block]
        self._used.discard(block)
        self._free.append(block)
        return True

    def free(self, blocks: Sequence[int]) -> None:
        """Wholesale free of exclusively-owned blocks.  Shared blocks must
        go through ``decref`` — freeing one here would yank it out from
        under the other owners, so it's rejected before any mutation."""
        blocks = list(blocks)
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate blocks in one free: {blocks}")
        for b in blocks:                       # validate before mutating
            if b not in self._used:
                raise ValueError(f"block {b} double-freed (or never "
                                 f"allocated)")
            if self._ref[b] != 1:
                raise ValueError(f"block {b} still shared "
                                 f"(refcount {self._ref[b]}); decref it")
        for b in blocks:
            del self._ref[b]
            self._used.discard(b)
            self._free.append(b)


def auto_block_size(ring_len: int, target: int = 0, *,
                    min_block: int = 8) -> int:
    """Divisor of ``ring_len`` nearest the target block size (ties -> the
    larger), never below ``min(min_block, ring_len)``.  Divisibility keeps
    the logical gather view exactly the ring — the bit-identical-greedy
    invariant — and makes the free-list/table partition exact (no half-used
    tail blocks).  The minimum-tile clamp closes the degenerate prime case:
    a prime ``ring_len`` (e.g. 97) has only the divisors {1, ring_len}, and
    picking 1 exploded the block table to ``ring_len`` entries per lane and
    shredded the pool into single-token scatters — now the whole ring is
    one block instead.  REPRO_PAGED_BLOCK overrides the target (on real
    TPUs pick a 128-multiple)."""
    target = target or int(os.environ.get("REPRO_PAGED_BLOCK", "16"))
    floor = min(min_block, ring_len)
    divs = [d for d in range(1, ring_len + 1)
            if ring_len % d == 0 and d >= floor]
    return min(divs, key=lambda d: (abs(d - target), -d))


class PagedCachePool(_LanePool):
    """Paged block-KV pool: one shared block pool + per-lane block tables.

    Engine-facing surface mirrors ``CachePool`` (free_slots / acquire /
    release / insert / cache) plus the paged extras: ``table`` (the host
    block table the engine ships into each serve step), ``grant`` /
    ``reset_blocks`` for on-demand block growth during decode, and
    block-level accounting for admission control and metrics.

    Geometry: the logical per-request ring is ``ring_len = min(cache_len,
    window)`` slots, carved into ``blocks_per_slot`` blocks of
    ``block_size`` (which must divide ``ring_len`` — ``auto_block_size``
    picks such a divisor).  The pool holds ``pool_blocks`` physical blocks
    (default: full capacity, ``num_slots * blocks_per_slot``; pass less to
    oversubscribe lanes against actual token footprints — the whole point).
    """

    def __init__(self, cfg, num_slots: int, cache_len: int, *,
                 block_size: int = 0, pool_blocks: int = 0,
                 force_window: int = 0, dtype=None):
        super().__init__(num_slots, cache_len)
        if cfg.family not in PAGED_FAMILIES or cfg.local_global_alternating:
            raise ValueError(
                f"paged KV pools need one uniform ring geometry per layer "
                f"(families {PAGED_FAMILIES}, no local/global alternation); "
                f"got {cfg.family!r}")
        from repro.models.registry import get_model
        w = force_window or cfg.sliding_window
        ring_len = min(cache_len, w) if w > 0 else cache_len
        block_size = block_size or auto_block_size(ring_len)
        if ring_len % block_size:
            raise ValueError(f"block_size {block_size} must divide the ring "
                             f"length {ring_len}")
        self.ring_len = ring_len
        self.block_size = block_size
        self.blocks_per_slot = ring_len // block_size
        n_blocks = pool_blocks or num_slots * self.blocks_per_slot
        dtype = jnp.dtype(cfg.compute_dtype) if dtype is None else dtype
        # the model's own cache layout with the block count as its batch
        # and the block size as its ring: (L, n_blocks, block_size, ...)
        # leaves (GQA k/v, or one latent entry a position), kv_pos
        # (L, n_blocks, block_size)
        self.cache = get_model(cfg).init_cache(cfg, n_blocks, block_size,
                                               dtype=dtype)
        self.allocator = BlockAllocator(n_blocks)
        self.table = np.full((num_slots, self.blocks_per_slot), -1, np.int32)
        # prefix-hash index: key -> {"blocks": tuple, "logits": np | None}.
        # Keys are b"P" + block-aligned token-prefix bytes (share KV, still
        # prefill) or b"F" + whole-prompt bytes (skip prefill entirely: the
        # stored last-token logits row seeds the first sample).  The reverse
        # map lets a block's death (refcount -> 0, or a sole-owner ring
        # wrap overwriting prefix content) drop every chain that cites it.
        self._chains: dict = {}
        self._block_chains: dict = {}          # block -> set of chain keys

        T, bs = self.blocks_per_slot, self.block_size

        def _insert(pool, req_cache, row):
            # req_cache leaves: (L, 1, ring_len, ...) -> (L, T, bs, ...)
            # scattered at the physical ids in ``row`` (-1 == ungranted ->
            # out-of-bounds index, dropped)
            idx = jnp.where(row >= 0, row, n_blocks)

            def scatter(p, r):
                blocks = r[:, 0].reshape((r.shape[0], T, bs) + r.shape[3:])
                return p.at[:, idx].set(blocks.astype(p.dtype), mode="drop")

            return jax.tree.map(scatter, pool, req_cache)

        self._insert = jax.jit(_insert, donate_argnums=(0,))

        def _reset(kv_pos, idx):
            # (L, n_blocks, bs) -> granted blocks' positions invalidated
            return kv_pos.at[:, idx].set(-1, mode="drop")

        self._reset = jax.jit(_reset, donate_argnums=(0,))

        from repro.kernels import ops as _kops

        def _copy(pool, src, dst):
            # CoW data move: one (L, bs, ...) tile per leaf, src -> dst.
            # kv_pos rides along too, so the copy carries validity exactly.
            return jax.tree.map(lambda p: _kops.block_copy(p, src, dst),
                                pool)

        self._copy = jax.jit(_copy, donate_argnums=(0,))

        def _gather(pool, row):
            # Lane snapshot for the swap tier: physical blocks -> the
            # logical (L, 1, ring_len, ...) ring, the SAME leaf shapes a
            # batch-1 prefill cache has — so swap-in rides the one compiled
            # ``_insert`` signature.  Ungranted rows gather block 0 but
            # their kv_pos is forced to -1, so reinsertion drops nothing
            # real and revalidates nothing stale.
            safe = jnp.where(row >= 0, row, 0)

            def pick(p):
                y = p[:, safe]                 # (L, T, bs, ...)
                return y.reshape((p.shape[0], 1, T * bs) + p.shape[3:])

            granted = (row >= 0)[None, :, None]

            def snap(path, p):
                if getattr(path[-1], "key", None) != "kv_pos":
                    return pick(p)
                return jnp.where(granted, p[:, safe], -1).reshape(
                    (p.shape[0], 1, T * bs))

            return jax.tree_util.tree_map_with_path(snap, pool)

        self._gather = jax.jit(_gather)

    # -- slot management ----------------------------------------------------

    @property
    def pool_blocks(self) -> int:
        return self.allocator.n_blocks

    @property
    def blocks_in_use(self) -> int:
        return self.allocator.used_blocks

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    @property
    def free_runs(self) -> int:
        return self.allocator.free_runs

    @property
    def fragmentation(self) -> float:
        return self.allocator.fragmentation

    def blocks_for(self, extent: int) -> int:
        """Blocks covering ring slots [0, extent) — admission cost of a
        prefill whose occupied ring extent is ``extent`` tokens."""
        return -(-min(extent, self.ring_len) // self.block_size)

    def lane_blocks(self, slot: int) -> int:
        """Physical blocks currently mapped by ``slot``'s table row — the
        reclamation size the engine audits when a lane is swapped out or
        cancelled (shared blocks count too: the sharer holds a reference
        even though release may not free them)."""
        return int((self.table[slot] >= 0).sum())

    def live_pages(self) -> int:
        """Table entries the paged flash-decode kernel visits this step,
        summed over lanes (``flash_decode.live_pages`` of the table)."""
        return int(live_pages(self.table, np).sum())

    @property
    def block_bytes(self) -> int:
        """HBM bytes of one physical block across every leaf (all layers) —
        the unit for share/CoW/swap byte accounting."""
        return sum(int(p.nbytes) // p.shape[1]
                   for p in jax.tree.leaves(self.cache))

    def refcount(self, block: int) -> int:
        return self.allocator.refcount(block)

    def release(self, slot: int) -> None:
        """Retire a lane: drop one reference per block in its table row;
        blocks whose last reference this was return to the free list (and
        their prefix chains die with them — stale contents are masked on
        next grant via reset_blocks)."""
        super().release(slot)                  # validates double-free first
        row = self.table[slot]
        for b in row[row >= 0]:
            if self.allocator.decref(int(b)):
                self._drop_chains_of(int(b))
        self.table[slot] = -1

    # -- block lifecycle -----------------------------------------------------

    def grant_prefix(self, slot: int, n: int) -> List[int]:
        """Admission grant: physical blocks for logical blocks [0, n) of
        lane ``slot`` (the prefill extent).  Raises RuntimeError without
        side effects when the pool can't cover it."""
        ids = self.allocator.alloc(n)
        self.table[slot, :n] = ids
        return ids

    def grant(self, slot: int, logical_block: int) -> int:
        """Decode-time grant of one block (the write position crossed into
        an ungranted logical block).  Raises RuntimeError when exhausted —
        the engine parks the request."""
        if self.table[slot, logical_block] >= 0:
            raise ValueError(f"slot {slot} logical block {logical_block} "
                             f"already granted")
        b = self.allocator.alloc(1)[0]
        self.table[slot, logical_block] = b
        return b

    def grant_tail(self, slot: int, start: int, n: int) -> List[int]:
        """Admission grant of logical blocks [start, start+n) — the private
        tail after ``start`` shared prefix blocks.  Raises RuntimeError
        without side effects when the pool can't cover it."""
        if n <= 0:
            return []
        ids = self.allocator.alloc(n)
        self.table[slot, start:start + n] = ids
        return ids

    def reset_blocks(self, blocks: Sequence[int]) -> None:
        """Invalidate kv_pos of freshly granted blocks on device (stale
        positions from a previous owner must not pass the validity mask).
        Padded to num_slots ids per call — at most one grant per lane per
        step — so every reset shares one compiled signature."""
        if not blocks:
            return
        idx = np.full((self.num_slots,), self.allocator.n_blocks, np.int32)
        idx[:len(blocks)] = blocks
        idx = jnp.asarray(idx)
        self.cache = map_kv_pos(lambda kp: self._reset(kp, idx), self.cache)

    # -- prefix sharing / copy-on-write --------------------------------------

    @staticmethod
    def _pkey(tokens: np.ndarray) -> bytes:
        return b"P" + np.ascontiguousarray(tokens, np.int32).tobytes()

    @staticmethod
    def _fkey(tokens: np.ndarray) -> bytes:
        return b"F" + np.ascontiguousarray(tokens, np.int32).tobytes()

    def match_prefix(self, prompt):
        """Longest live block-aligned shared prefix for ``prompt``.

        Returns ``(blocks, full_hit, logits_row)``: the physical chain to
        map read-only (possibly empty), whether the WHOLE prompt matched (a
        full hit shares every prefix block and skips prefill — the stored
        last-token ``logits_row`` seeds the first sample), else
        ``logits_row`` is None.  Prompts longer than the ring never match
        (their early positions already wrapped away)."""
        p = np.ascontiguousarray(prompt, np.int32)
        if len(p) == 0 or len(p) > self.ring_len:
            return [], False, None
        full = self._chains.get(self._fkey(p))
        if full is not None:
            return list(full["blocks"]), True, full["logits"]
        for n in range(len(p) // self.block_size, 0, -1):
            c = self._chains.get(self._pkey(p[:n * self.block_size]))
            if c is not None:
                return list(c["blocks"]), False, None
        return [], False, None

    def share_map(self, slot: int, blocks: Sequence[int]) -> None:
        """Map a matched chain read-only into logical blocks [0, len) of
        lane ``slot``: refcount bump per block, zero new allocations.  The
        lane must copy-on-write before its first write into any of them."""
        for b in blocks:
            self.allocator.incref(int(b))
        self.table[slot, :len(blocks)] = np.asarray(blocks, np.int32)

    def register_prefix(self, slot, prompt, logits_row=None) -> None:
        """Index this lane's freshly prefilled prompt: one chain entry per
        block-aligned prefix plus (when ``logits_row`` — the prompt's
        last-token logits — is given) a whole-prompt entry enabling
        zero-prefill admission of identical prompts.  Entries reference
        live blocks only and die with them; re-registration of an existing
        key keeps the incumbent."""
        p = np.ascontiguousarray(prompt, np.int32)
        if len(p) == 0 or len(p) > self.ring_len:
            return
        row = self.table[slot]
        keys = [(self._pkey(p[:n * self.block_size]), n)
                for n in range(1, len(p) // self.block_size + 1)]
        if logits_row is not None:
            keys.append((self._fkey(p), self.blocks_for(len(p))))
        for key, n in keys:
            if key in self._chains or np.any(row[:n] < 0):
                continue
            blocks = tuple(int(b) for b in row[:n])
            entry = {"blocks": blocks, "logits": None}
            if key[:1] == b"F":
                entry["logits"] = np.asarray(logits_row)
            self._chains[key] = entry
            for b in blocks:
                self._block_chains.setdefault(b, set()).add(key)

    def _drop_chains_of(self, block: int) -> None:
        for key in self._block_chains.pop(block, set()):
            entry = self._chains.pop(key, None)
            if entry is None:
                continue
            for b in entry["blocks"]:
                if b != block:
                    s = self._block_chains.get(b)
                    if s is not None:
                        s.discard(key)
                        if not s:
                            del self._block_chains[b]

    def invalidate_block(self, block: int) -> None:
        """A sole owner is about to overwrite this block's prefix content
        (ring wrap): any chain citing it no longer describes what's stored,
        so drop those entries before the write lands."""
        self._drop_chains_of(block)

    def cow(self, slot: int, logical_block: int):
        """Copy-on-write: lane ``slot`` wants to write into a shared
        physical block.  Allocate a fresh block (RuntimeError when
        exhausted — caller parks, nothing mutated), device-copy the tile,
        remap the table, drop the old reference.  Returns (old, new)."""
        old = int(self.table[slot, logical_block])
        if old < 0:
            raise ValueError(f"slot {slot} logical block {logical_block} "
                             f"not granted")
        new = self.allocator.alloc(1)[0]
        self.cache = self._copy(self.cache, jnp.asarray(old, jnp.int32),
                                jnp.asarray(new, jnp.int32))
        self.table[slot, logical_block] = new
        if self.allocator.decref(old):
            self._drop_chains_of(old)
        return old, new

    # -- swap tier ------------------------------------------------------------

    def gather_lane(self, slot: int):
        """Device snapshot of lane ``slot``'s logical ring as prefill-shaped
        leaves (``(L, 1, ring_len, ...)``) — dispatched async; the engine
        materializes it to host later and reinserts it on swap-in through
        the same compiled ``insert``."""
        return self._gather(self.cache, jnp.asarray(self.table[slot]))

    # -- data path ----------------------------------------------------------

    def insert(self, req_cache, slot: int, *, skip_blocks: int = 0) -> None:
        """Scatter a batch-1 prefill ring into this lane's granted blocks
        (traced — one compiled signature for every slot/admission).
        ``skip_blocks`` masks the first N logical blocks out of the scatter
        (shared prefix blocks are read-only: the donor's data is already
        there and bit-identical, so the write is dropped, not duplicated)."""
        row = self.table[slot]
        if skip_blocks:
            row = row.copy()
            row[:skip_blocks] = -1
        self.cache = self._insert(self.cache, req_cache, jnp.asarray(row))

    # -- invariants (tests) --------------------------------------------------

    def assert_partition(self) -> None:
        """Free list + all table rows partition the physical pool, with a
        block's refcount equal to the number of rows citing it, and every
        chain entry referencing live blocks only."""
        free = set(self.allocator._free)
        held = [int(b) for b in self.table.ravel() if b >= 0]
        counts: dict = {}
        for b in held:
            counts[b] = counts.get(b, 0) + 1
        assert free.isdisjoint(held), "block both free and granted"
        assert free | set(held) == set(range(self.allocator.n_blocks)), \
            "block leaked (neither free nor granted)"
        assert set(held) == self.allocator._used, \
            "allocator used-set out of sync with the table"
        for b, c in counts.items():
            assert self.allocator.refcount(b) == c, \
                f"block {b}: refcount {self.allocator.refcount(b)} != " \
                f"{c} table references"
        for key, entry in self._chains.items():
            for b in entry["blocks"]:
                assert b in self.allocator._used, \
                    f"chain {key[:1]} cites freed block {b}"
                assert key in self._block_chains.get(b, ()), \
                    f"reverse chain map missing {b}"
