"""flash_decode validation: Pallas kernel (interpret mode) and the XLA
blockwise fallback vs the naive oracle, across GQA ratios, ring wrap-around,
sliding-window + prefix masking, int8 vs bf16 caches; split-partial combine
(the seq-sharded psum math); attn_decode routing (no full-cache dequant on
the fused path); ragged blockwise sdpa."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops, ref
from repro.kernels.flash_decode import (_pages_per_step, flash_decode,
                                        flash_decode_xla)
from repro.models.layers import attention as attn_mod
from repro.models.layers.attention import (_quant_kv as _quant, attn_decode,
                                           init_attention, init_attn_cache,
                                           sdpa)


def _case(B=2, S=200, Hk=2, G=4, D=64, *, int8=False, wrap=False,
          dtype=jnp.float32, seed=0):
    """Build (q, k, v, kv_pos, pos, kwargs-for-scales).  ``wrap`` makes
    pos > cache_len so the ring has been overwritten at least once."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, Hk * G, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hk, D)).astype(dtype)
    if wrap:
        pos = S + S // 2 + 3                 # ring overwritten once
        positions = jnp.arange(pos - S + 1, pos + 1, dtype=jnp.int32)
        kv_pos = jnp.zeros((S,), jnp.int32).at[positions % S].set(positions)
    else:
        pos = S - 1
        kv_pos = jnp.arange(S, dtype=jnp.int32)
    kv_pos = jnp.broadcast_to(kv_pos[None], (B, S))
    kw = {}
    if int8:
        kq, ksc = _quant(k.astype(jnp.float32))
        vq, vsc = _quant(v.astype(jnp.float32))
        k, v = kq, vq
        kw = dict(k_scale=ksc, v_scale=vsc)
    return q, k, v, kv_pos, jnp.asarray(pos, jnp.int32), kw


def _tol(int8, dtype):
    if int8:
        return 3e-2
    return 1e-2 if dtype == jnp.bfloat16 else 1e-5


@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("int8", [False, True])
def test_flash_decode_gqa_sweep(G, int8):
    q, k, v, kv_pos, pos, kw = _case(G=G, int8=int8, seed=G)
    o_r = ref.flash_decode_ref(q, k, v, kv_pos, pos, **kw)
    o_p = flash_decode(q, k, v, kv_pos, pos, block_kv=128, n_splits=2,
                       interpret=True, **kw)
    o_x = flash_decode_xla(q, k, v, kv_pos, pos, block_kv=64, **kw)
    tol = _tol(int8, jnp.float32)
    np.testing.assert_allclose(np.asarray(o_p, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(o_x, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("int8", [False, True])
def test_flash_decode_ring_wraparound(int8):
    """pos > cache_len: slot order no longer equals position order."""
    q, k, v, kv_pos, pos, kw = _case(S=160, int8=int8, wrap=True, seed=7)
    o_r = ref.flash_decode_ref(q, k, v, kv_pos, pos, window=96, **kw)
    o_p = flash_decode(q, k, v, kv_pos, pos, window=96, block_kv=128,
                       interpret=True, **kw)
    tol = _tol(int8, jnp.float32)
    np.testing.assert_allclose(np.asarray(o_p, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)


def test_flash_decode_window_and_prefix():
    q, k, v, kv_pos, pos, _ = _case(B=2, S=130, seed=3)
    plen = jnp.asarray([17, 40], jnp.int32)
    for window in (0, 31):
        o_r = ref.flash_decode_ref(q, k, v, kv_pos, pos, kind="prefix",
                                   prefix_len=plen, window=window)
        o_p = flash_decode(q, k, v, kv_pos, pos, kind="prefix",
                           prefix_len=plen, window=window, block_kv=128,
                           interpret=True)
        o_x = flash_decode_xla(q, k, v, kv_pos, pos, kind="prefix",
                               prefix_len=plen, window=window, block_kv=32)
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_r),
                                   rtol=1e-5, atol=1e-5)


def test_flash_decode_full_kind_bf16():
    """Cross-attention shape: every valid slot attends (kind="full")."""
    q, k, v, kv_pos, _, _ = _case(S=96, dtype=jnp.bfloat16, seed=5)
    o_r = ref.flash_decode_ref(q, k, v, kv_pos, 0, kind="full")
    o_p = flash_decode(q, k, v, kv_pos, 0, kind="full", block_kv=128,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(o_p, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=1e-2, atol=1e-2)


def test_flash_decode_softcap():
    q, k, v, kv_pos, pos, _ = _case(S=64, seed=11)
    o_r = ref.flash_decode_ref(q, k, v * 0 + 1.0, kv_pos, pos, softcap=20.0)
    o_p = flash_decode(q, k, v * 0 + 1.0, kv_pos, pos, softcap=20.0,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                               rtol=1e-5, atol=1e-5)


def test_ops_dispatch_forced_interpret(monkeypatch):
    """REPRO_FORCE_KERNELS=1 routes ops.flash_decode through the Pallas
    kernel in interpret mode off-TPU."""
    monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    assert ops.use_kernels()
    q, k, v, kv_pos, pos, kw = _case(S=140, int8=True, seed=13)
    o = ops.flash_decode(q, k, v, kv_pos, pos, **kw)
    o_r = ref.flash_decode_ref(q, k, v, kv_pos, pos, **kw)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_decode_mode_names_the_path_per_cache_len(monkeypatch):
    """decode_mode says which path a given cache length takes: on TPU a
    cache below REPRO_FLASH_DECODE_MIN_S goes to XLA, a longer one to the
    compiled kernel."""
    monkeypatch.delenv("REPRO_FLASH_DECODE_MIN_S", raising=False)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.decode_mode(512).startswith("flash_decode (xla, 512 slots")
    assert ops.decode_mode(2048) == "flash_decode (pallas, compiled)"
    monkeypatch.setattr(ops, "on_tpu", lambda: False)
    monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    assert ops.decode_mode(512) == "flash_decode (pallas, interpret)"
    monkeypatch.delenv("REPRO_FORCE_KERNELS")
    assert ops.decode_mode(512) == "flash_decode (xla blockwise fallback)"


def test_partials_combine_matches_full():
    """Two half-cache partials merged with the pmax/psum formula must equal
    the unsharded kernel — the math repro.dist.decode runs over ``model``."""
    q, k, v, kv_pos, pos, kw = _case(S=256, int8=True, seed=17)
    half = 128
    parts = []
    for sl in (slice(0, half), slice(half, 256)):
        parts.append(flash_decode_xla(
            q, k[:, sl], v[:, sl], kv_pos[:, sl], pos,
            k_scale=kw["k_scale"][:, sl], v_scale=kw["v_scale"][:, sl],
            block_kv=64, return_partials=True))
    m = jnp.stack([p[0] for p in parts])
    l = jnp.stack([p[1] for p in parts])
    acc = jnp.stack([p[2] for p in parts])
    m_g = m.max(0)
    w = jnp.exp(m - m_g)
    out = ((acc * w).sum(0) / jnp.maximum((l * w).sum(0), 1e-30))
    B, Hk, G, D = out.shape
    out = out.reshape(B, 1, Hk * G, D).astype(q.dtype)
    o_full = flash_decode_xla(q, k, v, kv_pos, pos, block_kv=64, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_full),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# paged launch: live-page skip, all-head tiles, multi-page steps
# ---------------------------------------------------------------------------

# Qwen3 decode shape (Hk 8, G 2, D 128) over 16-slot pages; T = 20 is not a
# multiple of the pages a step gathers, so a row's last step is short.
_PG = dict(T=20, bs=16, Hk=8, G=2, D=128)


def _paged_rows_case(rows, *, T, bs, Hk, G, D, int8=False, seed=0):
    """A paged pool holding ``rows`` of (query position, table row), with
    every granted slot's position written as a ring of ``T * bs`` slots
    holds it (the latest position <= the query's at that ring slot).  A
    block cited by several rows (copy-on-write sharing) must hold the same
    positions for each."""
    ring = T * bs
    nb = 6 * T
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (len(rows), 1, Hk * G, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (nb, bs, Hk, D), jnp.float32)
    v = jax.random.normal(ks[2], (nb, bs, Hk, D), jnp.float32)
    kw = {}
    if int8:
        k, ksc = _quant(k)
        v, vsc = _quant(v)
        kw = dict(k_scale=ksc.astype(jnp.bfloat16),
                  v_scale=vsc.astype(jnp.bfloat16))
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    # block 0 is in no table but holds a freed block's stale positions: an
    # ungranted entry, which streams block 0, must still be masked
    kv_pos = np.full((nb, bs), -1, np.int32)
    kv_pos[0] = np.arange(bs)
    written = {}
    for qp, row in rows:
        assert 0 not in row
        for j, blk in enumerate(row):
            if blk < 0:
                continue
            for o in range(bs):
                r = j * bs + o
                p = r + ring * ((qp - r) // ring) if r <= qp else -1
                assert written.setdefault((blk, o), p) == p
                kv_pos[blk, o] = p
    tbl = jnp.asarray([row for _, row in rows], jnp.int32)
    q_pos = jnp.asarray([qp for qp, _ in rows], jnp.int32)
    return q, k, v, jnp.asarray(kv_pos), tbl, q_pos, kw


def _paged_rows(case):
    T = _PG["T"]
    full = list(range(40, 40 + T))
    if case == "cow_hole":
        # a hole below the last granted entry; block 7 cited by both rows
        return [(5 * 16 + 2, [7, 3, -1, 9, 11, 12] + [-1] * (T - 6)),
                (2 * 16 + 4, [7, 13, 14] + [-1] * (T - 3))]
    if case == "ring_wrap":
        # every entry granted, the ring overwritten once and a half
        return [(T * 16 + T * 8 + 3, full),
                (T * 16 - 1, list(range(60, 60 + T)))]
    # ragged live extents: inactive lane with nothing granted, one slot,
    # a partial last page, the whole table
    return [(-1, [-1] * T), (0, [5] + [-1] * (T - 1)),
            (4 * 16 + 6, [21, 2, 17, 8, 30] + [-1] * (T - 5)),
            (T * 16 - 1, full)]


@pytest.mark.parametrize("case, int8, kw", [
    pytest.param("ragged", False, {}, id="ragged-bf16"),
    pytest.param("ragged", True, {}, id="ragged-int8"),
    pytest.param("cow_hole", False, {}, id="cow_hole-bf16"),
    pytest.param("cow_hole", True, {}, id="cow_hole-int8"),
    pytest.param("ring_wrap", False, {}, id="ring_wrap-bf16"),
    pytest.param("ragged", False, dict(softcap=30.0), id="softcap"),
    pytest.param("ring_wrap", False, dict(window=40), id="window"),
    pytest.param("cow_hole", False, dict(kind="prefix", prefix_len=[70, 20]),
                 id="prefix"),
    pytest.param("ragged", False, dict(kind="full"), id="full"),
    pytest.param("ragged", False, dict(return_partials=True),
                 id="partials-bf16"),
    pytest.param("ring_wrap", True, dict(return_partials=True, window=100),
                 id="partials-int8-window"),
    pytest.param("ragged", True, dict(bs=24), id="ragged-int8-bs24"),
    pytest.param("cow_hole", True, dict(bs=8), id="cow_hole-int8-bs8"),
])
def test_paged_kernel_matches_oracle(case, int8, kw):
    """The paged launch (interpret mode) against the plain oracle over the
    gathered pool, at the Qwen3 shape: rows visit only their live pages
    (an idle row visits none), ungranted holes and shared blocks, the ring
    wrap, every mask kind, int8 dequant, and the sequence-sharded
    partials.  Pages of 16 slots share a 128-lane row of positions and
    scales eight to a row; the block-size cases cover a page that fills a
    padded row alone (24) and a step whose slots are not a whole number of
    128-lane rows (8: 20 pages of 8)."""
    shape = dict(_PG, bs=kw.get("bs", _PG["bs"]))
    kw = {k: x for k, x in kw.items() if k != "bs"}
    if shape["bs"] == _PG["bs"]:
        assert _PG["T"] % _pages_per_step(_PG["bs"], _PG["T"])
    q, k, v, kv_pos, tbl, q_pos, skw = _paged_rows_case(
        _paged_rows(case), int8=int8, seed=len(case), **shape)
    if "prefix_len" in kw:
        kw["prefix_len"] = jnp.asarray(kw["prefix_len"], jnp.int32)
    partials = kw.pop("return_partials", False)
    got = flash_decode(q, k, v, kv_pos, q_pos, block_tables=tbl,
                       interpret=True, return_partials=partials, **skw, **kw)
    if partials:
        want = flash_decode_xla(q, k, v, kv_pos, q_pos, block_tables=tbl,
                                return_partials=True, **skw, **kw)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
        m, l, acc = got
        B, Hk, G, D = acc.shape
        got = (acc / jnp.maximum(l, 1e-30)).reshape(B, 1, Hk * G, D)
    want = ref.flash_decode_ref(q, k, v, kv_pos, q_pos, block_tables=tbl,
                                **skw, **kw)
    tol = _tol(int8, jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_sharded_flash_decode_on_emulated_mesh():
    """The shard_map pmax/psum combine on a real (emulated) multi-device
    mesh must match the oracle.  Runs in a subprocess: the device-count
    flag only takes effect before jax initializes (conftest pins this
    process to one device)."""
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.dist.decode import sharded_flash_decode, seq_shard_mesh
from repro.kernels import ref
from repro.models.layers.attention import _quant_kv

B, S, Hk, G, D = 2, 256, 2, 4, 64
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, 1, Hk * G, D))
kf = jax.random.normal(ks[1], (B, S, Hk, D))
vf = jax.random.normal(ks[2], (B, S, Hk, D))
kq, ksc = _quant_kv(kf)
vq, vsc = _quant_kv(vf)
kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
pos = jnp.asarray(S - 1, jnp.int32)
mesh = jax.make_mesh((2, 2), ("data", "model"))
with mesh:
    assert seq_shard_mesh(S) is not None
    for kw in (dict(), dict(window=70),
               dict(k_scale=ksc, v_scale=vsc, kind="prefix",
                    prefix_len=jnp.asarray([10, 60], jnp.int32))):
        k, v = (kq, vq) if "k_scale" in kw else (kf, vf)
        out = sharded_flash_decode(q, k, v, kv_pos, pos, mesh,
                                   block_kv=64, **kw)
        want = ref.flash_decode_ref(q, k, v, kv_pos, pos, **kw)
        tol = 3e-2 if "k_scale" in kw else 1e-5
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=tol, atol=tol)
print("SHARDED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_CACHE_SHARD", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "SHARDED_OK" in r.stdout, \
        r.stdout + "\n" + r.stderr


def test_attn_decode_fused_path_skips_full_dequant(monkeypatch):
    """On the fused path the int8 cache must never be dequantized whole:
    _dequant_kv (the full-cache helper) must not run during attn_decode."""
    monkeypatch.setenv("REPRO_KV_INT8", "1")
    cfg = get_smoke_config("qwen3-0.6b")
    params = init_attention(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    B, S = 2, 24
    cache = init_attn_cache(B, S, cfg.num_kv_heads, cfg.resolved_head_dim(),
                            dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, cfg.d_model))

    def boom(*a, **k):
        raise AssertionError("full-cache _dequant_kv on the fused path")

    monkeypatch.setattr(attn_mod, "_dequant_kv", boom)
    y, _ = attn_decode(params, cfg, x, cache, jnp.asarray(0, jnp.int32))
    assert np.all(np.isfinite(np.asarray(y, np.float32)))


@pytest.mark.parametrize("int8", [False, True])
def test_attn_decode_fused_matches_legacy(monkeypatch, int8):
    """REPRO_FLASH_DECODE=0 (dequant-then-sdpa) and the fused path must
    agree step by step."""
    monkeypatch.setenv("REPRO_KV_INT8", "1" if int8 else "0")
    cfg = get_smoke_config("qwen3-0.6b")
    params = init_attention(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))

    outs = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("REPRO_FLASH_DECODE", flag)
        cache = init_attn_cache(B, S, cfg.num_kv_heads,
                                cfg.resolved_head_dim(), dtype=jnp.float32)
        ys = []
        for t in range(S):
            y, cache = attn_decode(params, cfg, x[:, t:t + 1], cache,
                                   jnp.asarray(t, jnp.int32))
            ys.append(np.asarray(y[:, 0]))
        outs[flag] = np.stack(ys)
    np.testing.assert_allclose(outs["1"], outs["0"], rtol=2e-4, atol=2e-4)


def test_sdpa_blockwise_ragged_lengths():
    """Skv/Sq not divisible by the block sizes must pad, not crash."""
    B, Sq, Skv, H, Hk, D = 1, 50, 100, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Skv, Hk, D))
    v = jax.random.normal(ks[2], (B, Skv, Hk, D))
    qp = jnp.arange(Skv - Sq, Skv, dtype=jnp.int32)
    kp = jnp.arange(Skv, dtype=jnp.int32)
    naive = sdpa(q, k, v, q_pos=qp, kv_pos=kp, kind="causal")
    block = sdpa(q, k, v, q_pos=qp, kv_pos=kp, kind="causal",
                 block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(block), np.asarray(naive),
                               rtol=2e-5, atol=2e-5)


def test_sdpa_blockwise_int8_scales_in_scan():
    """Scales passed through: blockwise in-scan dequant == naive dequant."""
    B, S, H, Hk, D = 2, 96, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S, Hk, D))
    v = jax.random.normal(ks[2], (B, S, Hk, D))
    kq, ksc = _quant(k)
    vq, vsc = _quant(v)
    qp = jnp.full((B, 1), S - 1, jnp.int32)
    kp = jnp.arange(S, dtype=jnp.int32)
    naive = sdpa(q, (kq.astype(jnp.float32) * ksc.astype(jnp.float32)),
                 (vq.astype(jnp.float32) * vsc.astype(jnp.float32)),
                 q_pos=qp, kv_pos=kp, kind="causal")
    fused = sdpa(q, kq, vq, k_scale=ksc, v_scale=vsc,
                 q_pos=qp, kv_pos=kp, kind="causal", block_kv=32)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(naive),
                               rtol=1e-4, atol=1e-4)
