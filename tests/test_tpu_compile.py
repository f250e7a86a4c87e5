"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode runs the kernels' arithmetic but not the TPU lowering, which
refuses block shapes the tiling cannot hold.  These tests compile each
kernel for one chip of a described ``v5e:2x2`` topology (no chip needed:
the TPU compiler is installed with jaxlib's TPU plug-in) at qwen3-0.6b
decode shapes, and check that the compiled program holds the kernel.

The topology is described inside a module fixture, never at import: only
the process that runs these tests may load the TPU library.  The
persistent compilation cache is off around the compiles — an entry
compiled for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

CFG = get_config("qwen3-0.6b")
B, S = 8, 2048                                  # decode batch, ring slots
HK, H, D = CFG.num_kv_heads, CFG.num_heads, CFG.resolved_head_dim()
L = CFG.num_layers


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _compiled_text(fn, *args, **static):
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile() \
        .as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_flash_decode_ring_compiles(one_chip, kv_dtype):
    from repro.kernels.flash_decode import flash_decode
    s = lambda shp, dt: _sds(one_chip, shp, dt)  # noqa: E731
    args = [s((B, 1, H, D), jnp.bfloat16), s((B, S, HK, D), kv_dtype),
            s((B, S, HK, D), kv_dtype), s((B, S), jnp.int32),
            s((B,), jnp.int32)]
    if kv_dtype == jnp.int8:
        args += [s((B, S, HK, 1), jnp.bfloat16)] * 2

    def fn(q, k, v, kv_pos, q_pos, *scales):
        ks, vs = scales or (None, None)
        return flash_decode(q, k, v, kv_pos, q_pos, k_scale=ks, v_scale=vs)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


def _custom_call_names(text):
    """Instruction names of the compiled program's Pallas kernels."""
    return [line.split("=", 1)[0].strip().lstrip("%")
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("block_size, kv_dtype, batch, slots", [
    pytest.param(bs, dt, b, n, id=f"{bs}-{jnp.dtype(dt).name}{tag}")
    for b, n, tag in ((B, S, ""), (16, 4096, "-b16x4096"))
    for bs in (16, 128) for dt in (jnp.bfloat16, jnp.int8)])
def test_flash_decode_paged_compiles(one_chip, block_size, kv_dtype, batch,
                                     slots):
    """Paged decode compiles, at the serving benchmark's shape too (16
    lanes x 4,096 positions: 256 table entries of 16 slots), and the
    kernel keeps ``flash_decode`` in its name, by which the benchmark's
    roofline reader finds it in a device trace."""
    from repro.kernels.flash_decode import flash_decode
    s = lambda shp, dt: _sds(one_chip, shp, dt)  # noqa: E731
    nb, T = batch * slots // block_size, slots // block_size
    args = [s((batch, 1, H, D), jnp.bfloat16),
            s((nb, block_size, HK, D), kv_dtype),
            s((nb, block_size, HK, D), kv_dtype),
            s((nb, block_size), jnp.int32), s((batch,), jnp.int32),
            s((batch, T), jnp.int32)]
    if kv_dtype == jnp.int8:
        args += [s((nb, block_size, HK, 1), jnp.bfloat16)] * 2

    def fn(q, k, v, kv_pos, q_pos, tbl, *scales):
        ks, vs = scales or (None, None)
        return flash_decode(q, k, v, kv_pos, q_pos, block_tables=tbl,
                            k_scale=ks, v_scale=vs)

    names = _custom_call_names(_compiled_text(fn, *args))
    assert names and all("flash_decode" in n for n in names), names


def test_flash_decode_latent_compiles(one_chip):
    """The paged kernel's latent mode at DeepSeek-V2-Lite's serving shape:
    32 lanes x 4,096 positions in 16-slot pages, 16 query heads over one
    576-wide latent entry stored 640 wide, values its first 512 columns.
    The kernel is named ``mla_decode``, by which the benchmark's roofline
    reader finds it in a device trace."""
    from repro.kernels.flash_decode import flash_decode
    s = lambda shp, dt: _sds(one_chip, shp, dt)  # noqa: E731
    b, slots, bs = 32, 4096, 16
    nb, T = b * slots // bs, slots // bs
    args = [s((b, 1, 16, 640), jnp.bfloat16), s((nb, bs, 640), jnp.bfloat16),
            s((nb, bs), jnp.int32), s((b,), jnp.int32), s((b, T), jnp.int32)]

    def fn(q, latent, kv_pos, q_pos, tbl):
        return flash_decode(q, latent, None, kv_pos, q_pos, block_tables=tbl,
                            scale=0.1147, v_dim=512)

    names = _custom_call_names(_compiled_text(fn, *args))
    assert names and all(n.startswith("mla_decode") for n in names), names


@pytest.mark.parametrize("leaf", ["kv", "kv_int8", "kv_scale", "kv_pos"])
def test_paged_block_copy_compiles(one_chip, leaf):
    """Every leaf of a layer-stacked paged pool: KV tiles, int8 codes,
    absmax scale rows and per-slot positions."""
    from repro.kernels.flash_decode import paged_block_copy
    bs = 16
    nb = B * S // bs
    shape, dtype = {
        "kv": ((L, nb, bs, HK, D), jnp.bfloat16),
        "kv_int8": ((L, nb, bs, HK, D), jnp.int8),
        "kv_scale": ((L, nb, bs, HK, 1), jnp.bfloat16),
        "kv_pos": ((L, nb, bs), jnp.int32),
    }[leaf]
    text = _compiled_text(paged_block_copy, _sds(one_chip, shape, dtype),
                          _sds(one_chip, (), jnp.int32),
                          _sds(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("wire", ["int8", "bf16"])
def test_ring_hop_compiles(one_chip, wire):
    """The fused dequant-accumulate-requant hop at one chunk of
    qwen3-0.6b's LoRA payload on a 4-way ring."""
    from repro.core import fedtime
    from repro.core.comm import ring_wire_plan
    from repro.core.lora import FAMILY_TARGETS, attach_lora, lora_tree
    from repro.kernels.ring_allreduce import _hop_pallas
    ft = CFG.fedtime
    ad = jax.eval_shape(lambda k: lora_tree(attach_lora(
        fedtime.init(CFG, k), k, rank=ft.lora_rank, alpha=ft.lora_alpha,
        targets=FAMILY_TARGETS["dense"])), jax.random.PRNGKey(0))
    elems = sum(x.size for x in jax.tree.leaves(ad))
    c = ring_wire_plan(elems, 4, wire).chunk_elems
    qblock = 128
    s = lambda shp, dt: _sds(one_chip, shp, dt)  # noqa: E731
    codes = s((c,), jnp.int8 if wire == "int8" else jnp.bfloat16)
    scales = s((c // qblock,), jnp.float32) if wire == "int8" else None

    def fn(acc, codes, res, *scales):
        return _hop_pallas(acc, codes, scales[0] if scales else None, res,
                           wire=wire, qblock=qblock, interpret=False)

    args = [s((c,), jnp.float32), codes, s((c,), jnp.float32)]
    args += [scales] if scales is not None else []
    assert "tpu_custom_call" in _compiled_text(fn, *args)
