"""Paged block-KV pool: allocator invariants (alloc/free/reuse,
fragmentation, partition property), block-table flash-decode vs the oracle,
engine parity on the paged path (incl. int8), pool-exhaustion parking and
livelock-breaking eviction, and the seq-sharded paged combine."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.configs import get_smoke_config
from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode, flash_decode_xla
from repro.models.layers.attention import _quant_kv
from repro.models.registry import get_model
from repro.serve import ForecastEngine, Request
from repro.serve.cache_pool import (BlockAllocator, PagedCachePool,
                                    auto_block_size)

CACHE_LEN = 48


@pytest.fixture(scope="module")
def dense():
    cfg = get_smoke_config("qwen3-0.6b")
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, api, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _solo_greedy(api, cfg, params, prompt, gen, cache_len=CACHE_LEN):
    from repro.launch.steps import make_serve_step
    cache, logits = api.prefill(
        params, cfg, {"tokens": jnp.asarray(prompt[None])},
        cache_len=cache_len)
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
    out = [int(tok[0, 0])]
    P = len(prompt)
    for i in range(gen - 1):
        tok, cache = serve(params, cache,
                           {"token": tok,
                            "pos": jnp.asarray([P + i], jnp.int32)})
        out.append(int(tok[0, 0]))
    return out


# ---------------------------------------------------------------------------
# allocator (host-side, no model)
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_reuse_ordering():
    a = BlockAllocator(4)
    assert a.alloc(2) == [0, 1]               # LIFO free list pops low first
    assert a.alloc(1) == [2]
    a.free([1])
    assert a.alloc(1) == [1]                  # freed block is reused next
    assert a.free_blocks == 1 and a.used_blocks == 3
    with pytest.raises(RuntimeError):         # exhausted: 2 > 1 free
        a.alloc(2)
    assert a.free_blocks == 1                 # failed alloc takes nothing
    a.free([0])
    with pytest.raises(ValueError):
        a.free([0])                           # double-free
    with pytest.raises(ValueError):
        a.free([99])                          # never allocated


def test_allocator_fragmentation_after_staggered_retirement():
    """Interleaved grants from three requests, middle one retires: its
    scattered blocks go back whole and satisfy a new multi-block alloc."""
    a = BlockAllocator(9)
    rows = {r: [] for r in "abc"}
    for _ in range(3):                        # a,b,c round-robin: b's blocks
        for r in "abc":                       # are non-contiguous (1,4,7)
            rows[r] += a.alloc(1)
    assert a.free_blocks == 0
    assert rows["b"] == [1, 4, 7]
    a.free(rows["b"])                         # staggered retirement
    got = a.alloc(3)                          # refill from the holes
    assert sorted(got) == [1, 4, 7]
    assert a.free_blocks == 0


def test_free_runs_and_fragmentation_gauge():
    a = BlockAllocator(8)
    assert a.free_runs == 1 and a.fragmentation == 0.0   # [0..7] contiguous
    held = a.alloc(8)
    assert a.free_runs == 0 and a.fragmentation == 0.0   # nothing free
    a.free([held[1]])
    assert a.free_runs == 1 and a.fragmentation == 0.0   # single block
    a.free([held[3], held[5]])                           # holes: {1,3,5}
    assert a.free_runs == 3
    assert a.fragmentation == pytest.approx(2 / 2)       # fully shredded
    a.free([held[2]])                                    # {1,2,3,5}: 2 runs
    assert a.free_runs == 2
    assert a.fragmentation == pytest.approx(1 / 3)
    a.free([held[0], held[4], held[6], held[7]])         # all free again
    assert a.free_runs == 1 and a.fragmentation == 0.0


def test_fragmentation_bounded_under_churn():
    rng = np.random.default_rng(11)
    a = BlockAllocator(24)
    held = []
    for _ in range(200):
        if rng.integers(2) and a.free_blocks:
            held += a.alloc(int(rng.integers(1, a.free_blocks + 1)))
        elif held:
            k = int(rng.integers(1, len(held) + 1))
            take = [held.pop(int(rng.integers(len(held))))
                    for _ in range(k)]
            a.free(take)
        assert 0.0 <= a.fragmentation <= 1.0
        assert a.free_runs <= max(a.free_blocks, 1)


def test_engine_metrics_fragmentation_summary():
    from repro.serve.metrics import EngineMetrics
    m = EngineMetrics(num_slots=2)
    for frag in (0.0, 0.5, 0.25, 1.0):
        m.record_decode_step(1, 1, 0.01, fragmentation=frag)
    s = m.summary()
    assert s["mean_fragmentation"] == pytest.approx(0.4375)
    assert s["peak_fragmentation"] == 1.0


def _partition_holds(a: BlockAllocator):
    free = set(a._free)
    assert len(free) == len(a._free), "duplicate in free list"
    assert free.isdisjoint(a._used)
    assert free | a._used == set(range(a.n_blocks))


def _drive(a: BlockAllocator, ops):
    held = []
    for want_alloc, amount in ops:
        if want_alloc:
            n = 1 + amount % max(a.free_blocks, 1)
            if n <= a.free_blocks:
                held += a.alloc(n)
        elif held:
            k = 1 + amount % len(held)
            a.free(held[:k])
            held = held[k:]
        _partition_holds(a)


def test_partition_invariant_seeded():
    """Free list + allocations always partition the pool (seeded sweep —
    runs even without hypothesis)."""
    rng = np.random.default_rng(7)
    for _ in range(20):
        a = BlockAllocator(int(rng.integers(1, 24)))
        ops = [(bool(rng.integers(2)), int(rng.integers(100)))
               for _ in range(40)]
        _drive(a, ops)


@settings(max_examples=60, deadline=None)
@given(st.integers(min_value=1, max_value=32),
       st.lists(st.tuples(st.booleans(),
                          st.integers(min_value=0, max_value=999)),
                max_size=60))
def test_partition_invariant_property(n_blocks, ops):
    _drive(BlockAllocator(n_blocks), ops)


def test_auto_block_size_divides():
    for ring in (25, 48, 96, 128, 1, 97, 31, 14):
        bs = auto_block_size(ring)
        assert ring % bs == 0
    assert auto_block_size(96) == 16          # divisor nearest the target
    assert auto_block_size(48) == 16
    assert auto_block_size(25) == 25          # 1/5/25: 25 is closest to 16
    # prime rings must NOT degenerate to block_size=1 (table length ==
    # ring_len, single-token scatters): the min-tile clamp picks the whole
    # ring as one block instead
    assert auto_block_size(97) == 97
    assert auto_block_size(31) == 31
    assert auto_block_size(14) == 14          # 2 and 7 sit below the clamp
    assert auto_block_size(4) == 4            # tiny rings keep working


# ---------------------------------------------------------------------------
# pool lifecycle (device arrays, no model forward)
# ---------------------------------------------------------------------------

def test_paged_pool_lifecycle(dense):
    cfg, _, _ = dense
    pool = PagedCachePool(cfg, num_slots=3, cache_len=32, block_size=8)
    assert pool.blocks_per_slot == 4 and pool.pool_blocks == 12
    s = pool.acquire()
    pool.grant_prefix(s, 2)
    pool.grant(s, 2)
    assert pool.blocks_in_use == 3
    pool.assert_partition()
    with pytest.raises(ValueError):           # logical block 2 already held
        pool.grant(s, 2)
    pool.release(s)                           # frees all three
    assert pool.blocks_in_use == 0
    pool.assert_partition()
    with pytest.raises(ValueError):
        pool.release(s)
    # geometry guards
    with pytest.raises(ValueError, match="divide"):
        PagedCachePool(cfg, num_slots=1, cache_len=32, block_size=5)
    ssm = get_smoke_config("xlstm-350m")
    with pytest.raises(ValueError, match="uniform ring"):
        PagedCachePool(ssm, num_slots=1, cache_len=32)


def test_submit_rejects_unservable_footprint(dense):
    """A request whose ring footprint exceeds the whole pool would park
    forever — reject it at submit, not mid-decode."""
    cfg, _, params = dense
    eng = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN,
                         paged=True, block_size=8, pool_blocks=2)
    with pytest.raises(ValueError, match="blocks"):
        eng.submit(Request(id="big", prompt=np.zeros(20, np.int32),
                           max_new_tokens=20))


# ---------------------------------------------------------------------------
# block-table flash decode vs oracle
# ---------------------------------------------------------------------------

def _paged_case(int8, seed=0, nb=12, bs=16, Hk=2, G=4, D=32, B=3, T=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, Hk * G, D))
    k = jax.random.normal(ks[1], (nb, bs, Hk, D))
    v = jax.random.normal(ks[2], (nb, bs, Hk, D))
    kw = {}
    if int8:
        k, ksc = _quant_kv(k)
        v, vsc = _quant_kv(v)
        kw = dict(k_scale=ksc, v_scale=vsc)
    # non-contiguous physical blocks, ungranted holes, ragged fill levels
    tbl = jnp.asarray([[7, 2, 9, 0], [4, 5, -1, -1], [11, 3, 8, -1]],
                      jnp.int32)[:B]
    q_pos = np.asarray([T * bs - 1, 2 * bs - 1, 2 * bs + 5])[:B]
    kv_pos = np.full((nb, bs), -1, np.int32)
    for b in range(B):
        for j in range(T):
            pb = int(tbl[b, j])
            if pb < 0:
                continue
            for o in range(bs):
                if j * bs + o <= q_pos[b]:
                    kv_pos[pb, o] = j * bs + o
    return (q, k, v, jnp.asarray(kv_pos), tbl,
            jnp.asarray(q_pos, jnp.int32), kw)


@pytest.mark.parametrize("int8", [False, True])
def test_paged_flash_decode_matches_oracle(int8):
    q, k, v, kv_pos, tbl, q_pos, kw = _paged_case(int8, seed=int(int8))
    o_r = ref.flash_decode_ref(q, k, v, kv_pos, q_pos, block_tables=tbl,
                               **kw)
    o_p = flash_decode(q, k, v, kv_pos, q_pos, block_tables=tbl,
                       n_splits=2, interpret=True, **kw)
    o_x = flash_decode_xla(q, k, v, kv_pos, q_pos, block_tables=tbl, **kw)
    tol = 3e-2 if int8 else 1e-5
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_r), rtol=tol,
                               atol=tol)


def _stacked(leaves, L, seed):
    """``L`` layers of pool leaves: layer 0 the given leaves, the others
    fresh draws of the same shapes and dtypes (positions kept)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8 * L))
    out = []
    for x in leaves:
        if x is None or x.dtype == jnp.int32:
            out.append(None if x is None else jnp.stack([x] * L))
            continue
        draw = [x] + [jax.random.normal(next(keys), x.shape).astype(x.dtype)
                      if x.dtype != jnp.int8 else
                      jax.random.randint(next(keys), x.shape, -127, 128,
                                         jnp.int32).astype(jnp.int8)
                      for _ in range(L - 1)]
        out.append(jnp.stack(draw))
    return out


@pytest.mark.parametrize("pool", ["bf16", "int8", "latent"])
@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_paged_decode_reads_a_stacked_pool_at_its_layer(pool, path):
    """Given the layer-stacked pool and a layer's index, the paged launch
    (interpret mode) and the XLA path return exactly what they return on
    that layer sliced out, for the first and the last layer: the page
    DMAs and the gather start at the layer, nothing else changes."""
    q, k, v, kv_pos, tbl, q_pos, kw = _paged_case(pool == "int8", seed=5)
    if pool == "latent":                      # one 32-wide KV head
        k, v, q = k[:, :, 0], None, q[:, :, :4]
    L = 3
    k, v, kv_pos, ks, vs = _stacked([k, v, kv_pos, kw.get("k_scale"),
                                     kw.get("v_scale")], L, seed=6)
    extra = {"v_dim": 16} if pool == "latent" else {}
    if path == "kernel":
        fn = functools.partial(flash_decode, interpret=True)
    else:
        fn = flash_decode_xla
    for layer in (0, L - 1):
        def at(x):
            return None if x is None else x[layer]
        want = fn(q, at(k), at(v), at(kv_pos), q_pos, block_tables=tbl,
                  k_scale=at(ks), v_scale=at(vs), **extra)
        got = fn(q, k, v, kv_pos, q_pos, block_tables=tbl, k_scale=ks,
                 v_scale=vs, layer=jnp.int32(layer), **extra)
        assert np.array_equal(np.asarray(got), np.asarray(want)), layer
    assert not np.array_equal(np.asarray(k[0]), np.asarray(k[L - 1]))


def test_paged_gather_is_bit_identical_to_ring():
    """A fully-granted identity-layout table reproduces the contiguous ring
    EXACTLY — the invariant behind paged == contiguous greedy decode."""
    B, S, Hk, D, bs = 2, 48, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 1, Hk * 2, D))
    k = jax.random.normal(ks[1], (B, S, Hk, D))
    v = jax.random.normal(ks[2], (B, S, Hk, D))
    kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    pos = jnp.asarray(S - 1, jnp.int32)
    # pool = the two rings stacked block-wise; per-row identity tables
    T = S // bs
    kp = k.reshape(B * T, bs, Hk, D)
    vp = v.reshape(B * T, bs, Hk, D)
    pp = kv_pos.reshape(B * T, bs)
    tbl = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    ring = flash_decode_xla(q, k, v, kv_pos, pos)
    paged = flash_decode_xla(q, kp, vp, pp, pos, block_tables=tbl)
    assert np.array_equal(np.asarray(ring), np.asarray(paged))


def test_sharded_paged_decode_on_emulated_mesh():
    """Block axis sharded over ``model``: per-shard localized tables +
    pmax/psum combine must match the unsharded paged path, on one layer's
    leaves and on a layer-stacked pool read at its layer.  Subprocess —
    the device-count flag must precede jax init."""
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.dist.decode import sharded_flash_decode, seq_shard_mesh
from repro.kernels.flash_decode import flash_decode_xla

nb, bs, Hk, G, D, B, T = 16, 16, 2, 4, 32, 4, 4
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, 1, Hk * G, D))
k = jax.random.normal(ks[1], (nb, bs, Hk, D))
v = jax.random.normal(ks[2], (nb, bs, Hk, D))
# blocks deliberately straddle both model shards; row 3 inactive (pos -1);
# rows 0 and 2 SHARE physical block 0 at the same logical index (a CoW
# prefix-share grant) — per-entry localization must resolve both sharers
# to the same stripe-local tile
tbl = jnp.asarray([[0, 8, 1, 9], [15, 2, -1, -1], [0, 12, 5, -1],
                   [3, 11, 6, 14]], jnp.int32)
pos = jnp.asarray([T * bs - 1, 2 * bs - 5, 2 * bs + 7, -1], jnp.int32)
kv_pos = np.full((nb, bs), -1, np.int32)
for b in range(B):
    for j in range(T):
        pb = int(tbl[b, j])
        if pb < 0: continue
        for o in range(bs):
            if j * bs + o <= int(pos[b]):
                kv_pos[pb, o] = j * bs + o
kv_pos = jnp.asarray(kv_pos)
mesh = jax.make_mesh((2, 2), ("data", "model"))
with mesh:
    assert seq_shard_mesh(nb) is not None
    out = sharded_flash_decode(q, k, v, kv_pos, pos, mesh,
                               block_tables=tbl)
want = flash_decode_xla(q, k, v, kv_pos, pos, block_tables=tbl)
np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                           rtol=1e-5, atol=1e-5)
assert np.all(np.asarray(out)[3] == 0.0)
# the decode layer scan's form: layer-stacked leaves, each shard's
# (L, nb/m, ...) stripe read at the layer in place
ks2 = jax.random.split(jax.random.PRNGKey(1), 2)
k2 = jnp.stack([jax.random.normal(ks2[0], k.shape), k])
v2 = jnp.stack([jax.random.normal(ks2[1], v.shape), v])
with mesh:
    out2 = sharded_flash_decode(q, k2, v2, jnp.stack([kv_pos] * 2), pos,
                                mesh, block_tables=tbl, layer=jnp.int32(1))
np.testing.assert_allclose(np.asarray(out2), np.asarray(want),
                           rtol=1e-5, atol=1e-5)
print("SHARDED_PAGED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_CACHE_SHARD", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "SHARDED_PAGED_OK" in r.stdout, \
        r.stdout + "\n" + r.stderr


# ---------------------------------------------------------------------------
# engine on the paged pool
# ---------------------------------------------------------------------------

def test_paged_engine_matches_solo(dense):
    """Staggered trace through a genuinely paged pool (6 blocks/lane) is
    bit-identical to each request alone, in ONE serve_step signature, with
    the partition invariant intact at every retirement."""
    cfg, api, params = dense
    prompts = _prompts(cfg, [6, 9, 6, 11], seed=21)
    gens = [5, 3, 6, 4]
    ref_out = [_solo_greedy(api, cfg, params, p, g)
               for p, g in zip(prompts, gens)]
    eng = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN,
                         paged=True, block_size=8)
    assert eng.paged and eng.pool.blocks_per_slot == 6
    for i, (p, g) in enumerate(zip(prompts, gens)):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=g,
                           arrival_step=i))
    done = eng.run(max_steps=500)
    for i in range(len(prompts)):
        assert done[f"r{i}"].tokens.tolist() == ref_out[i], i
    assert eng.num_step_signatures() == 1
    assert eng.pool.blocks_in_use == 0
    eng.pool.assert_partition()
    assert eng.metrics.summary()["mean_block_utilization"] > 0


@pytest.mark.parametrize("pool", ["bf16", "int8", "latent"])
@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_in_place_serve_step_matches_per_layer(dense, monkeypatch, pool,
                                               path):
    """One paged serve step whose layer scan carries the pool and reads and
    writes it at each layer in place gives the per-layer form's next
    tokens, logits and whole new pool bit for bit — the per-layer form
    being the ring scan's, each layer's pool sliced out as a scan input and
    restacked as its output.  The engine's lanes hold an idle lane
    (position -1) and ungranted (-1) table entries; ``kernel`` is the
    paged launch in interpret mode."""
    from repro.launch.steps import make_serve_step
    from repro.models import moe_transformer, transformer
    from test_mla_moe import params_for, preset
    if pool == "int8":
        monkeypatch.setenv("REPRO_KV_INT8", "1")
    if pool == "latent":
        cfg = preset()
        api, params = get_model(cfg), params_for(cfg)
    else:
        cfg, api, params = dense
    eng = ForecastEngine(cfg, params, num_slots=3, cache_len=CACHE_LEN,
                         paged=True, block_size=8)
    for i, n in enumerate([13, 6]):
        eng.submit(Request(id=f"r{i}", prompt=_prompts(cfg, [n], i)[0],
                           max_new_tokens=8))
    eng.step()
    eng.step()
    batch, cache = eng.decode_batch(), eng.pool.cache
    assert (np.asarray(batch["pos"]) == -1).sum() == 1
    assert (eng.pool.table == -1).any() and (eng.pool.table >= 0).any()
    if path == "kernel":
        monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")

    def run():
        serve = make_serve_step(cfg, sampling=True, guard=True)
        logits, new = jax.jit(lambda p, c, b: api.decode_step(
            p, cfg, c, b))(params, cache, batch)
        tok = jax.jit(serve)(params, cache, batch)[0]
        return [np.asarray(x) for x in (tok, logits, *jax.tree.leaves(new))]

    in_place = run()
    ring_scan = transformer.decode_layers
    for mod in (transformer, moe_transformer):
        monkeypatch.setattr(mod, "decode_layers",
                            lambda *a, paged: ring_scan(*a, paged=False))
    per_layer = run()
    assert len(in_place) == len(per_layer)
    for got, want in zip(in_place, per_layer):
        assert got.dtype == want.dtype and np.array_equal(got, want)
    # the step wrote this tick's entries: the pool moved
    before = [np.asarray(x) for x in jax.tree.leaves(cache)]
    assert any(not np.array_equal(a, b)
               for a, b in zip(in_place[2:], before))


def test_decode_page_visit_share_counts_live_table_entries(dense,
                                                          monkeypatch):
    """``decode_page_visit_share`` is the table entries the paged kernel
    visits (1 + each lane's last granted block, 0 for a lane holding none)
    over lanes x table width, summed over decode steps — counted here by a
    plain loop over the table each serve step is given — and every
    ``engine.decode_step`` span carries its step's ``live_pages``.  Only
    the Pallas path is counted: an engine on the XLA path records none."""
    from repro import obs
    cfg, api, params = dense
    xla = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN,
                         paged=True, block_size=8)
    xla.submit(Request(id="x", prompt=_prompts(cfg, [5], seed=3)[0],
                       max_new_tokens=2))
    xla.step()
    assert xla.metrics.summary()["decode_page_visit_share"] == 0.0
    monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    obs.reset()
    eng = ForecastEngine(cfg, params, num_slots=3, cache_len=CACHE_LEN,
                         paged=True, block_size=8)
    long_p, short_p = _prompts(cfg, [20, 5], seed=31)
    eng.submit(Request(id="long", prompt=long_p, max_new_tokens=6))
    eng.submit(Request(id="short", prompt=short_p, max_new_tokens=6))
    tables = []
    step_fn = eng._step_fn

    def spy(params, cache, batch):
        tables.append(np.asarray(batch["block_tbl"]))
        return step_fn(params, cache, batch)

    eng._step_fn = spy
    for _ in range(3):
        eng.step()

    def live(row):
        granted = [j for j, blk in enumerate(row) if blk >= 0]
        return granted[-1] + 1 if granted else 0

    per_step = [sum(live(row) for row in t) for t in tables]
    first = [live(row) for row in tables[0]]
    T = eng.pool.blocks_per_slot
    assert sorted(first)[0] == 0                  # the idle lane
    assert any(0 < n < T for n in first)          # a partial lane
    assert eng.metrics.summary()["decode_page_visit_share"] == \
        pytest.approx(sum(per_step) / sum(t.size for t in tables))
    spans = obs.get_tracer().events("engine.decode_step")
    assert [e["args"]["live_pages"] for e in spans] == per_step
    # a hole below a lane's last granted entry is visited (and masked)
    eng.pool.table[2, 3] = eng.pool.table[0, 0]
    assert eng.pool.live_pages() == sum(live(r) for r in eng.pool.table)


def test_paged_engine_int8(dense, monkeypatch):
    monkeypatch.setenv("REPRO_KV_INT8", "1")
    cfg, api, params = dense
    prompts = _prompts(cfg, [6, 9], seed=23)
    ref_out = [_solo_greedy(api, cfg, params, p, 4) for p in prompts]
    eng = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN,
                         paged=True, block_size=8)
    assert any(l.dtype == jnp.int8 for l in jax.tree.leaves(eng.pool.cache))
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=4,
                           arrival_step=i))
    done = eng.run(max_steps=200)
    for i in range(len(prompts)):
        assert done[f"r{i}"].tokens.tolist() == ref_out[i], i


def test_pool_exhaustion_parks_without_corruption(dense):
    """An oversubscribed pool (5 blocks for two 3-block requests) must park
    the request that can't grow — and once the neighbour retires and frees
    blocks, the parked request resumes and BOTH outputs stay bit-identical
    to solo decode (a parked lane never corrupts a neighbour)."""
    cfg, api, params = dense
    prompts = _prompts(cfg, [6, 6], seed=25)
    gen = 16                                  # positions reach block 2 of 8
    ref_out = [_solo_greedy(api, cfg, params, p, gen) for p in prompts]
    eng = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN,
                         paged=True, block_size=8, pool_blocks=5)
    eng.submit(Request(id="r0", prompt=prompts[0], max_new_tokens=gen))
    eng.submit(Request(id="r1", prompt=prompts[1], max_new_tokens=gen,
                       arrival_step=2))
    done = eng.run(max_steps=500)
    for i in range(2):
        assert done[f"r{i}"].tokens.tolist() == ref_out[i], i
    assert eng.metrics.parked_events >= 1
    assert eng.metrics.evictions == 0
    eng.pool.assert_partition()


def test_simultaneous_exhaustion_evicts_and_recomputes(dense):
    """Both residents hit the block wall on the same step: the youngest is
    evicted back onto the queue (prompt + generated) and recomputed once
    blocks free — greedy outputs still bit-identical to solo.  This pins
    the recompute FALLBACK, so the swap tier (which would displace without
    evicting) is explicitly off; tests/test_prefix_share.py covers the
    swap-tier version of the same squeeze."""
    cfg, api, params = dense
    prompts = _prompts(cfg, [6, 6], seed=27)
    gen = 16
    ref_out = [_solo_greedy(api, cfg, params, p, gen) for p in prompts]
    # max_tokens_in_flight exactly fits both ORIGINAL footprints: the
    # evicted request's resumed form must not inflate its budget (its
    # prompt absorbs generated tokens the horizon already counts) or it
    # could never re-admit and run() would spin forever
    eng = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN,
                         paged=True, block_size=8, pool_blocks=4,
                         max_tokens_in_flight=2 * (6 + gen),
                         swap_tier=False)
    eng.submit(Request(id="r0", prompt=prompts[0], max_new_tokens=gen))
    eng.submit(Request(id="r1", prompt=prompts[1], max_new_tokens=gen))
    done = eng.run(max_steps=500)
    for i in range(2):
        assert done[f"r{i}"].tokens.tolist() == ref_out[i], i
    assert eng.metrics.evictions >= 1
    assert done["r1"].prompt_len == 6         # reports the ORIGINAL prompt
    eng.pool.assert_partition()


def test_paged_admits_more_than_lane_capacity(dense):
    """The point of paging: at pool bytes worth 2 contiguous lanes, short
    requests run >2-wide because they only pin the blocks they fill."""
    cfg, api, params = dense
    prompts = _prompts(cfg, [5, 5, 5, 5, 5], seed=29)
    gen = 4                                   # footprint 9 tokens = 2 blocks
    ref_out = [_solo_greedy(api, cfg, params, p, gen) for p in prompts]
    # pool bytes == 2 lanes x 48 slots == 12 blocks of 8; 5 lanes share them
    eng = ForecastEngine(cfg, params, num_slots=5, cache_len=CACHE_LEN,
                         paged=True, block_size=8, pool_blocks=12)
    for i, p in enumerate(prompts):
        eng.submit(Request(id=f"r{i}", prompt=p, max_new_tokens=gen))
    done = eng.run(max_steps=300)
    for i in range(len(prompts)):
        assert done[f"r{i}"].tokens.tolist() == ref_out[i], i
    assert eng.metrics.peak_in_flight > 2     # beyond lane-equivalent bytes
    assert eng.num_step_signatures() == 1
